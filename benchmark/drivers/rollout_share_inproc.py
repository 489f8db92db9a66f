"""Closed-loop rollout against an in-process ``GenerationEngine`` whose
model is blocks of ONE branch (family ``nemotron_h``: Mamba-2 mixers, an
attention, LatentMoE expert layers) of which this chip holds an
expert-parallel rank's SHARE: a per-slot recurrent state beside the page
pool, snapshots of it in the prefix cache, and a router that scores 512
experts of which 128 are here.

``rollout_state_inproc.py`` with these differences; set-up, window, the
exact token count and the p90's population are that driver's line for
line, and ``_warm_admission``, ``_warm_wider_tables``, ``_judge``,
``_pick``, ``_is_long``, ``_drain_long``, ``_probe_state``,
``_state_check``, ``_state_space_init``, ``_spans_under``, ``_peak_bytes``
and ``_VERDICT_KEYS`` are imported from the older drivers, not copied:

- the seeded weights are that driver's (``_state_space_init``: ``A_log``,
  ``dt_bias``, ``D``, the convolution, one slow head in the first Mamba-2
  block); the router's correction bias is normal(0, 0.02) as
  ``benchmark/weights.py`` makes every bias, not zero;
- the engine records each generated token's routing (``record_routing``),
  and the BOUNDARY OF THE 22 CHOSEN is the hazard of the comparison: the
  sigmoid scores of 512 experts lie ~0.002 apart where the 22nd and the
  23rd are, which is what the serving dtype's rounding of the block's
  input moves a score by, so the program (bfloat16) and the float32
  reference keep different experts in a good part of the (token, block)
  pairs, and that token's weights and routed sum then differ by an expert
  in 22, which says nothing about either's arithmetic. So ``correct`` is
  judged against the reference GIVEN the program's choices at the
  generated positions (the weights are still the reference's own scores
  of them; the prompt's positions, whose routing admission does not
  record, run free on both sides), as ``rollout_cca_inproc`` does; beside
  it are reported the free-running comparison and the share of a
  (generated token, block)'s 22 experts that the reference's own router,
  given the choices before, chose too. A router that computed something
  else would agree on 22 in 512: under ``check.router_agreement_min`` the
  run is not correct;
- four controls, each the reference with a defect in the program's place
  (its log-probs AND its own routing, through the same verdict), the
  first three on ``check.control_samples`` of the checked requests, each
  of which has to come out NOT correct in every run, or the run is not:
  the reference in ``check.control_dtype``; the recurrent state DROPPED at
  the prompt's page-aligned boundary (a prefix hit seeded from nothing);
  the combine weights normalised over the chosen experts HELD here in
  place of all 22 (the plausible wrong share: every routed sum ~4 x too
  large); and, on the recurrent STATE itself (``_state_check``: one
  running request's first Mamba-2 block against the reference's), the
  reference with its state rounded to ``check.control_state_dtype``;
- what a COLD run costs outside the window (the driver's check stops a run
  at 360 s): the engine takes the traffic file's ``engine.admit_buckets``,
  and the reference runs every checked sequence at ONE padded length
  (``check.long_max_tokens``), whatever its own, in programs built side
  by side before the first comparison (``build_ahead``);
- under ``--rehearse`` the generic tiny preset leaves the published
  state-space and expert sizes against a hidden size of 64:
  ``_rehearsal_arch`` sets small consistent ones, a pattern with every
  kind of block, and a share (4 of 8 experts).

This is the EIGHTH rollout driver: the next ``benchmark`` issue should
fold them into one (ROADMAP B0(a); PERF.md, section 7).

Tokens are counted exactly: what the requests completed in the window
generated, plus what the requests still running at its end had generated,
minus what the requests running at its start had generated before it.
"""

import gc
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import correct, ssm_flops, sut, traffic_gen, weights
from benchmark.drivers.rollout_inproc import _warm_admission
from benchmark.drivers.rollout_latent_inproc import (
    _judge, _peak_bytes, _spans_under, _warm_wider_tables)
from benchmark.drivers.rollout_looped_inproc import _VERDICT_KEYS
from benchmark.drivers.rollout_state_inproc import (
    _drain_long, _is_long, _pick, _probe_state, _state_check,
    _state_space_init)
from benchmark.resident import ChunkResident
from benchmark.stats import percentile

# the stand-in programs: name -> (what differs in ``arch`` for sample ``s``
# at page size ``page``, the dtype it computes in; None:
# ``check.control_dtype``), and what the comparison cannot tell if it passes
_STAND_INS = {
    "control": (
        lambda s, page: {}, None, "a lower precision"),
    "control_lost_snapshot": (
        lambda s, page: {
            "control_zero_state_at": (s["start"] - 1) // page * page},
        "float32", "a lost snapshot"),
    "control_norm_over_held": (
        lambda s, page: {"control_norm_over_held": True}, "float32",
        "combine weights normalised over the held experts alone"),
}


def _rehearsal_arch(arch: dict) -> dict:
    """Small sizes that agree with the tiny preset's hidden size, every
    kind of block, and a share of the experts."""
    pattern = "MEM*E"
    return dict(
        arch, num_hidden_layers=len(pattern), hybrid_override_pattern=pattern,
        layer_types=[{"M": "mamba", "*": "attention", "E": "moe"}[c]
                     for c in pattern],
        head_dim=16, mamba_num_heads=8, mamba_head_dim=16, ssm_state_size=16,
        n_groups=2, chunk_size=8, moe_intermediate_size=32,
        moe_latent_size=32, moe_shared_expert_intermediate_size=64,
        n_routed_experts=4, expert_parallel_size=2, num_experts_per_tok=3,
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_n_groups=2,
        max_position_embeddings=512)


def _memoised(ref, length: int):
    """``ref._run`` with its results kept (the verdicts, the agreement and
    the controls ask for the same passes of a sample several times over)
    and every sequence padded to ``length``, whatever the caller's own
    padding: one set of the reference's programs, on every seed."""
    plain, kept = ref._run, {}

    def run(params, arch, tokens, dtype, pad_to, **kw):
        assert len(tokens) <= length, (len(tokens), length)
        forced = arch.get("forced_routing")
        key = (tuple(tokens), str(dtype), tuple(sorted(kw.items())),
               tuple(sorted((k, str(v)) for k, v in arch.items()
                            if k.startswith("control_"))),
               None if forced is None else forced.tobytes())
        if key not in kept:
            # (on the host: a pass's five states are 20 MB of the chip)
            kept[key] = jax.device_get(
                plain(params, arch, tokens, dtype, length, **kw))
        return kept[key]

    return plain, run


def _generated(routing, start: int):
    """``routing [blocks, n, k]`` with the prompt's positions (whose routing
    no program records) and the last token's set to -1: what a program
    hands in."""
    out = np.full(routing.shape, -1, np.int32)
    out[:, start - 1 : -1] = routing[:, start - 1 : -1]
    return out


def _verdict(ref, params, arch: dict, served_dtype: str, samples, chk: dict
             ) -> Dict:
    """``_judge`` of each sample against the reference GIVEN that sample's
    routing (one sequence at a time: the routing is the sequence's own),
    pooled as ``correct.check_logprobs`` pools its samples, and the share
    of the handed-in experts that the reference's own router, given the
    choices before, chose too."""
    parts, same, pairs = [], 0, 0
    for s in samples:
        given = dict(arch, forced_routing=s["forced"])
        parts.append(_judge(params, given, served_dtype, [s], chk))
        if "max_abs_diff_nats" not in parts[-1]:
            return parts[-1]
        own = ref.routing(params, given, s["tokens"], "float32", 0)
        took = s["forced"][:, s["start"] - 1 : -1]
        own = own[:, s["start"] - 1 : -1]
        same += int((own[..., :, None] == took[..., None, :]).any(-1).sum())
        pairs += took.size
    n = sum(p["n_positions"] for p in parts)
    diff = max(p["max_abs_diff_nats"] for p in parts)
    yard = max(p["reference_served_dtype_vs_f32_nats"] for p in parts)
    means = [p["seq_mean_abs_diff_nats"][0] for p in parts]
    limit = chk["seq_mean_abs_diff_limit_nats"]
    verdict = {
        "correct": diff <= 2 * yard + correct.FLOOR_NATS,
        "reason": None,
        "max_abs_diff_nats": diff,
        "reference_served_dtype_vs_f32_nats": yard,
        "tolerance_nats": 2 * yard + correct.FLOOR_NATS,
        "mean_abs_diff_nats": sum(
            p["mean_abs_diff_nats"] * p["n_positions"] for p in parts) / n,
        "seq_mean_abs_diff_nats": means,
        "seq_mean_abs_diff_limit_nats": limit,
        "n_sequences": len(parts), "n_positions": n,
        "router_agreement_given_earlier_choices": same / max(pairs, 1),
    }
    if not verdict["correct"]:
        verdict["reason"] = "the largest difference is over the tolerance"
    elif max(means) > limit:
        verdict["correct"] = False
        verdict["reason"] = "a sequence's mean difference is over its limit"
    floor = chk.get("router_agreement_min", 0.0)
    if verdict["router_agreement_given_earlier_choices"] < floor:
        verdict["correct"] = False
        verdict["reason"] = (
            "the reference's router chose "
            f"{verdict['router_agreement_given_earlier_choices']:.3f} of "
            f"the handed-in experts: the floor is {floor}")
    return verdict


def _check(params, arch: dict, served_dtype: str, samples, chk: dict,
           page: int) -> Dict:
    """The verdict on the served log-probs (against the reference given the
    program's routing), the free-running comparison and agreement beside
    it, and the three stand-in programs."""
    ref = correct.reference_module(arch["reference"])
    check = _verdict(ref, params, arch, served_dtype, samples, chk)
    if not samples or "max_abs_diff_nats" not in check:
        return check
    few = samples[: chk.get("control_samples", len(samples))]
    free = _judge(params, arch, served_dtype, few, chk)
    check["free_running"] = {
        k: free.get(k) for k in _VERDICT_KEYS + (
            "reference_served_dtype_vs_f32_nats",)}
    must_fail = chk.get("controls_must_be_refused", True)
    for name, (defect, dtype, cannot_tell) in _STAND_INS.items():
        dtype = dtype or chk["control_dtype"]
        stand_ins = []
        for s in few:
            faulty = dict(arch, **defect(s, page))
            lp, _ = ref.next_token_logprobs(
                params, faulty, s["tokens"], dtype, 0)
            own = ref.routing(params, faulty, s["tokens"], dtype, 0)
            stand_ins.append(dict(
                s, logprobs=lp[s["start"] - 1:],
                forced=_generated(own, s["start"])))
        verdict = _verdict(ref, params, arch, served_dtype, stand_ins, chk)
        check[name] = {k: verdict.get(k) for k in _VERDICT_KEYS + (
            "router_agreement_given_earlier_choices",)}
        if verdict["correct"] and must_fail:
            check["correct"] = False
            check["reason"] = (
                f"the comparison passes the reference with {name}: it "
                f"cannot tell {cannot_tell}")
    return check


def run(bench) -> Dict:
    from areal_tpu.gen.engine import GenerationEngine, GenRequest

    arch, mix = bench.arch, bench.mix
    if bench.rehearse:
        arch = bench.arch = _rehearsal_arch(arch)
    eng_opts = mix["engine"]
    cfg = sut.model_config(arch, mix.get("model_overrides", {}))
    params = _state_space_init(
        weights.make_weights(
            sut.weight_shapes(cfg, cfg.dtype), bench.seed,
            jnp.dtype(cfg.dtype)),
        bench.seed)

    bench.mark("weights")
    stream = traffic_gen.RequestStream(mix, bench.seed, cfg.vocab_size)
    clients = mix["clients"]
    page = eng_opts["page_size"]
    out_hi = mix["output_len"]["hi"]
    max_seqlen = mix["prompt_len"]["hi"] + out_hi
    # what a token takes of the pool: K/V in the attention blocks only
    kv_tok = ssm_flops.kv_bytes_per_token(
        arch, jnp.dtype(cfg.dtype).itemsize)
    n_pages = int(eng_opts["kv_pool_bytes"] // (kv_tok * page))
    engine = GenerationEngine(
        cfg, params, max_slots=clients, max_seqlen=max_seqlen,
        max_new_tokens_cap=out_hi, page_size=page, n_pages=n_pages,
        enable_prefix_cache=eng_opts["enable_prefix_cache"],
        state_snapshots=eng_opts["state_snapshots"],
        admit_buckets=eng_opts["admit_buckets"],
        record_routing=True, seed=bench.seed % (2**31 - 1),
    )
    decode_steps = eng_opts["decode_steps"]
    pool_bytes_stored = engine.kv_pool_bytes()
    bench.facts.update(kv_bytes_per_token=kv_tok, decode_steps=decode_steps)

    bench.mark("engine")
    _warm_admission(engine, mix["temperature"], cfg.vocab_size, decode_steps)
    bench.mark("warm_admission")
    _warm_wider_tables(engine, mix["temperature"], cfg.vocab_size, decode_steps)
    bench.mark("warm_wider_tables")

    # ---- the loop ---------------------------------------------------- #
    live: Dict[str, Dict] = {}      # rid -> request record
    done: List[Dict] = []
    chunk_resident: List[int] = []  # resident tokens at each chunk's start
    chunk_distinct: List[int] = []  # the same, a shared prompt page once
    resident_count = ChunkResident(page, decode_steps)

    def submit(req: traffic_gen.Request):
        engine.submit(GenRequest(
            rid=req.rid, input_ids=req.prompt,
            max_new_tokens=req.max_new_tokens,
            temperature=mix["temperature"]))
        live[req.rid] = {"req": req, "t_submit": time.perf_counter(),
                         "chunks": 0}

    def one_step():
        with bench.span("engine.step"):
            outs = engine.step(decode_steps)
        t = time.perf_counter()
        bench.samples["kv_pool_occupancy"].append(engine.kv_pool_occupancy())
        bench.samples["n_running"].append(engine.n_running())
        # a request that found no pages waits out this whole chunk
        bench.samples["n_pending"].append(engine.n_pending())
        # resident context of this chunk, once a slot and once a distinct
        # page (the newest submissions still pending hold no slot yet)
        per_slot, distinct = resident_count.count(
            list(live.values())[: len(live) - engine.n_pending()])
        chunk_resident.append(per_slot)
        chunk_distinct.append(distinct)
        with bench.span("resolve"):
            for o in outs:
                rec = live.pop(o.rid)
                rec.update(t_done=t, out=o)
                done.append(rec)
            for _ in outs:
                submit(next(stream))
        bench.poll()

    for req in stream.initial():
        submit(req)
    one_step()
    bench.mark("opening_population")
    # (sampled inside the step, before the first finishers' replacements
    # were submitted)
    pending_after_opening = int(bench.samples["n_pending"][-1])
    for _ in range(mix["warm_chunks"]):
        one_step()

    # everything before here is set-up; what it left alive is taken out of
    # the collector's way, as ``rollout_state_inproc`` does and says why
    gc.collect()
    gc.freeze()
    jax.block_until_ready(engine.state.lens)
    before = {rid: len(t) for rid, (t, _) in engine.partial_outputs().items()}
    stats0 = dict(engine.stats)
    n_done0, n_chunks0 = len(done), len(chunk_resident)
    jit0 = engine.n_jit_entries()
    programs0 = engine.program_sizes()
    peak_setup = _peak_bytes()
    bench.window_open()
    while bench.window_due():
        one_step()
    jax.block_until_ready(engine.state.lens)
    bench.window_close()
    jit1 = engine.n_jit_entries()
    programs1 = engine.program_sizes()
    peak_window = _peak_bytes()
    stats1 = dict(engine.stats)
    # what the requests still running had generated when the window closed
    after = {rid: len(t) for rid, (t, _) in engine.partial_outputs().items()}
    chk = mix["check"]
    finished = done[n_done0:]
    n_long_done = sum(
        len(rec["out"].output_ids) >= chk["long_min_generated"]
        and _is_long(rec, chk) for rec in finished)
    drained = _drain_long(
        engine, live, after, max(chk["n_long"] - n_long_done, 0), chk,
        decode_steps)
    probe = _probe_state(engine, live, chk)
    engine.pause()                  # harvests every running slot

    # ---- counts ------------------------------------------------------ #
    failed = [
        rec for rec in finished
        if rec["out"].finish_reason == "interrupted"
        or len(rec["out"].output_ids) != rec["req"].max_new_tokens
        or not np.isfinite(rec["out"].output_logprobs).all()
    ]
    tokens = sum(len(rec["out"].output_ids) for rec in finished)
    tokens += sum(after.values())
    tokens -= sum(before.values())
    in_window = [rec for rec in finished if rec["t_submit"] >= bench.t_open]
    norm_ms = [
        1e3 * (rec["t_done"] - rec["t_submit"]) / len(rec["out"].output_ids)
        for rec in in_window if rec["out"].output_ids
    ]
    window = bench.window_s
    resident = chunk_resident[n_chunks0:]

    def grew(name):
        return stats1.get(name, 0) - stats0.get(name, 0)

    bench.counters.update(
        prefix_hit_tokens=grew("prefix_hit_tokens"),
        prefill_tokens=grew("prefill_tokens"),
        admitted=grew("admitted"),
        state_snapshot_hits=grew("state_snapshot_hits"),
        # the share: (row, expert) pairs the running rows chose in the
        # decode chunks, and those on experts held here
        moe_pairs=grew("moe_pairs"),
        moe_pairs_held=grew("moe_pairs_held"),
    )
    bench.facts["chunk_resident_tokens"] = resident   # one per engine.step span
    bench.facts["chunk_distinct_tokens"] = chunk_distinct[n_chunks0:]
    end_to_end = {
        "rollout_tokens_per_s": tokens / window,
        "rollout_norm_latency_p90_ms": (
            percentile(norm_ms, 90) if len(norm_ms) >= 20 else None),
    }

    # ---- correctness, outside the window ------------------------------ #
    # requests submitted and completed inside the window first; a long
    # generation that was submitted before it, or ended after it
    # (``_drain_long``), counts too: the same path served its every token
    pool = _pick(in_window, chk)
    if len(pool) < chk["n_requests"] + chk["n_long"]:
        pool = _pick(
            in_window + [r for r in finished if r not in in_window]
            + drained, chk)
    samples = []
    for rec in pool:
        toks = rec["req"].prompt + list(rec["out"].output_ids)
        start = len(rec["req"].prompt)
        # [generated, blocks, k] -> [blocks, positions, k]: token i's
        # routing is that of the step that produced it, position start-1+i
        forced = np.full(
            (cfg.n_moe_layers, len(toks), cfg.moe.top_k), -1, np.int32)
        forced[:, start - 1 : -1] = np.asarray(
            rec["out"].output_routing, np.int32).transpose(1, 0, 2)
        samples.append({
            "tokens": toks, "start": start, "forced": forced,
            "logprobs": rec["out"].output_logprobs})
    n_hits = sum(rec["out"].prefix_hit_tokens > 0 for rec in pool)
    n_long = sum(
        len(rec["out"].output_ids) >= chk["long_min_generated"] for rec in pool)
    params = engine.params
    engine.state = None             # the pool's and the state's memory
    del engine
    t_check = time.perf_counter()
    ref = correct.reference_module(arch["reference"])
    # ONE padded length for every sequence the check reads, its programs
    # built side by side before the first comparison
    ref.build_ahead(
        params, arch, ("float32", cfg.dtype, chk["control_dtype"]),
        chk["long_max_tokens"], state_dtype=chk["control_state_dtype"])
    plain, ref._run = _memoised(ref, chk["long_max_tokens"])
    try:
        check = _check(params, arch, cfg.dtype, samples, chk, page)
        state = check["state"] = (
            _state_check(params, arch, probe, chk) if probe else None)
    finally:
        ref._run = plain
    check["check_s"] = time.perf_counter() - t_check
    check["verdict_given_the_programs_routing"] = True
    check["checked_prefix_hits"] = n_hits
    check["checked_long_generations"] = n_long
    check["long_generations_ended_after_the_window"] = len(drained)
    check["checked_lengths"] = [
        [s["start"], len(s["tokens"]) - s["start"]] for s in samples]
    check["jit_entries_added_in_window"] = jit1 - jit0
    check["programs_specialised_in_window"] = sorted(
        k for k, n in programs1.items() if n != programs0.get(k, 0))
    if jit1 != jit0:
        check["correct"] = False
        check["reason"] = "the engine specialised a program inside the window"
    if len(norm_ms) < 20:
        check["correct"] = False
        check["reason"] = f"only {len(norm_ms)} requests ran inside the window"
    if n_hits < 1 or n_long < chk["n_long"]:
        check["correct"] = False
        check["reason"] = (
            f"{n_hits} checked prefix hits and {n_long} checked long "
            f"generations: the check wants 1 and {chk['n_long']}")

    if state is None:
        check["correct"] = False
        check["reason"] = "no running request's recurrent state was compared"
    elif state["worst_head_rel_diff"] > state["rel_diff_limit"]:
        check["correct"] = False
        check["reason"] = (
            f"the recurrent state is {state['worst_head_rel_diff']:.4f} of a "
            f"head's norm from the reference's after {state['after_tokens']} "
            f"tokens: the limit is {state['rel_diff_limit']}")
    elif state["control_state_rounded_rel_diff"] <= state["rel_diff_limit"]:
        check["correct"] = False
        check["reason"] = (
            "the comparison of the state passes the reference with its "
            f"state rounded to {chk['control_state_dtype']}: it cannot tell "
            "a 16-bit state")

    steps = sorted(bench.span_records("engine.step"),
                   key=lambda td: td[1], reverse=True)
    waits_ms = [1e3 * (rec["out"].t_admit - rec["out"].t_submit)
                for rec in in_window]
    return {
        "attempted": len(finished), "failed": len(failed),
        "end_to_end": end_to_end, "check": check,
        "info": {
            "completed_in_window": len(finished),
            "submitted_and_completed_in_window": len(in_window),
            "norm_latency_ms_median": (
                percentile(norm_ms, 50) if norm_ms else None),
            "norm_latency_ms_p90": end_to_end["rollout_norm_latency_p90_ms"],
            "tokens_in_window": tokens, "chunks": len(resident),
            "mean_resident_tokens": float(np.mean(resident)) if resident else 0,
            "mean_running": float(np.mean(bench.samples["n_running"])),
            "n_pages": n_pages, "kv_pool_bytes": n_pages * page * kv_tok,
            "kv_pool_bytes_stored": pool_bytes_stored,
            "cache_bytes_per_token_stored": pool_bytes_stored // (n_pages * page),
            # the per-slot state and its snapshots, and what moved
            "state_bytes_per_slot": ssm_flops.state_bytes_per_slot(
                arch, jnp.dtype(cfg.dtype).itemsize),
            "state_snapshot_entries": eng_opts["state_snapshots"],
            "admitted": grew("admitted"),
            "state_slots": grew("state_slots"),
            "state_snapshots_taken": grew("state_snapshots_taken"),
            "state_snapshot_hits": grew("state_snapshot_hits"),
            "state_snapshot_bytes": grew("state_snapshot_bytes"),
            "state_snapshot_evictions": grew("state_snapshot_evictions"),
            "kv_write_tiles": grew("kv_write_tiles"),
            "layer_passes": grew("layer_passes"),
            # the share of the experts, and where the routed experts ran
            "moe_pairs": grew("moe_pairs"),
            "moe_pairs_held": grew("moe_pairs_held"),
            "moe_held_experts_hit": grew("moe_held_experts_hit"),
            "moe_grouped_rows": grew("moe_grouped_rows"),
            "moe_dense_rows": grew("moe_dense_rows"),
            # a stalled step shows here and nowhere else in the line;
            # and which of the program's spans held its time
            "engine_step_s_longest": [d for _, d in steps[:3]],
            "engine_step_longest_spans_s": (
                _spans_under(*steps[0]) if steps else {}),
            "engine_step_s_median": percentile(bench.spans("engine.step"), 50),
            "queue_wait_ms_max": max(waits_ms, default=None),
            "queue_wait_ms_p90": (
                percentile(waits_ms, 90) if waits_ms else None),
            "pending_after_opening_population": pending_after_opening,
            "pending_after_step_max": int(
                max(bench.samples["n_pending"], default=0)),
            "memory_peak_bytes_setup": peak_setup,
            "memory_peak_bytes_window": peak_window,
            "prefill_tokens": bench.counters["prefill_tokens"],
            "prefix_hit_tokens": bench.counters["prefix_hit_tokens"],
        },
    }
