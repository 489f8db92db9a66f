"""Closed-loop rollout against an in-process ``GenerationEngine`` whose
model is of family ``afmoe`` (Trinity): WINDOW and FULL attention layers in
a period that runs across a dense and an expert stack, a gate on
attention's output, four norms a layer, and sigmoid-routed experts whose
choice a bias moves.

``rollout_hybrid_inproc.py`` with these differences; set-up, window, the
exact token count, the p90's population and the check's two groups (short
in-window requests, and long ones that completed in the window, the most
PREFILLED first) are that driver's line for line, and ``_warm_admission``,
``_warm_wider_tables``, ``_spans_under``, ``_peak_bytes``,
``_sample``'s fields, ``_generated`` and ``_VERDICT_KEYS`` are imported
from the older drivers, not copied:

- the pages of the pool and the bytes a kind holds of a token come from
  ``benchmark/afmoe_flops.py``, which reads the family's OWN keys
  (``layer_types``, ``sliding_window``, ``num_dense_layers``);
- the engine records each generated token's routing (``record_routing``)
  and ``correct`` is judged against the reference GIVEN the program's
  choices at the generated positions (``rollout_share_inproc`` says why of
  a sigmoid router: the scores of 128 experts lie closer at the boundary
  of the 8 chosen than bfloat16 rounds the layer's input, so program and
  float32 reference keep different experts at a share of the (token,
  layer) pairs, which says nothing of either's arithmetic). The weights
  are still the reference's own scores of the handed-in experts; the
  prompt's positions, whose routing admission does not record, run free on
  both sides. Beside it: the share of a (generated token, layer)'s experts
  that the reference's own router, given the choices before, chose too
  (under ``check.router_agreement_min`` the run is not correct), and the
  free-running comparison of two of the short group, for information;
- what a COLD run costs outside the window: the engine takes the traffic
  file's ``engine.admit_buckets`` (two row buckets, not four: half the
  admission programs at each of the three table widths), the reference
  runs EVERY checked sequence at ONE padded length
  (``check.long_max_tokens``), as one program a stack and dtype whatever a
  layer's kind (its window and rotary are arguments), all nine built side
  by side before the first comparison (``build_ahead``), and every pass
  gives its log-probs and its own routing at once
  (``logprobs_and_routing``);
- both controls hand in the faulty reference's log-probs AND its own
  routing, through the same verdict, and both have to come out NOT
  correct or the run is not: the reference in ``check.control_dtype`` on
  the short group, the reference with EVERY LAYER FULL on the long group;
- under ``--rehearse`` the generic tiny preset (two layers) cannot hold
  the family's mechanisms: ``_rehearsal_arch`` sets a depth,
  ``layer_types`` and ``num_dense_layers`` with a dense and an expert layer
  of BOTH kinds and a period that crosses the stacks' boundary, a window
  of 32, and 4 experts of which 2 a token.

This is the NINTH rollout driver: the next ``benchmark`` issue should fold
them into one (ROADMAP B0(a); PERF.md, section 7).

Tokens are counted exactly: what the requests completed in the window
generated, plus what the requests still running at its end had generated,
minus what the requests running at its start had generated before it.
"""

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import afmoe_flops, correct, sut, traffic_gen, weights
from benchmark.drivers.rollout_inproc import _warm_admission
from benchmark.drivers.rollout_latent_inproc import (
    _peak_bytes, _spans_under, _warm_wider_tables)
from benchmark.drivers.rollout_looped_inproc import _VERDICT_KEYS
from benchmark.drivers.rollout_share_inproc import _generated
from benchmark.resident import ChunkResident
from benchmark.stats import percentile


def _rehearsal_arch(arch: dict) -> dict:
    """Small sizes that agree with the tiny preset's hidden size: three
    dense layers (window, full, window) and three expert layers (full,
    window, full), so the period of two crosses the stacks' boundary."""
    kinds = ["sliding_attention", "full_attention"] * 3
    return dict(
        arch, num_hidden_layers=6, layer_types=kinds, num_dense_layers=3,
        global_attn_every_n_layers=2, head_dim=16, sliding_window=32,
        moe_intermediate_size=32, num_experts=4, num_experts_per_tok=2,
        max_position_embeddings=512)


def _pad(chk: dict) -> int:
    """The one length every checked sequence is padded to."""
    return -(-chk["long_max_tokens"] // 256) * 256


def _verdict(ref, params, arch: dict, served_dtype: str, samples, chk: dict
             ) -> Dict:
    """``benchmark/correct.py``'s rule (the largest difference against
    twice what the served dtype costs the reference, plus its floor) and
    the limit on each sequence's mean, each sample against the reference
    GIVEN that sample's routing; and the share
    of the handed-in experts that the reference's own router chose too."""
    if not samples:
        return {"correct": False, "reason": "no sample to compare"}
    pad = _pad(chk)
    yard = diff = total = 0.0
    n = same = pairs = 0
    means = []
    for s in samples:
        given = dict(arch, forced_routing=s["forced"])
        start, toks = s["start"], s["tokens"]
        got = np.asarray(s["logprobs"], np.float64)
        f32, own = ref.logprobs_and_routing(params, given, toks, "float32", pad)
        low, _ = ref.next_token_logprobs(params, given, toks, served_dtype, pad)
        f32, low = f32[start - 1:], low[start - 1:]
        if len(got) != len(f32) or not np.isfinite(got).all():
            return {"correct": False,
                    "reason": f"{len(got)} values for {len(f32)} positions, "
                              f"or a non-finite one"}
        d = np.abs(got - f32)
        yard = max(yard, float(np.abs(low - f32).max()))
        diff = max(diff, float(d.max()))
        means.append(float(d.mean()))
        total += float(d.sum())
        n += len(d)
        took = s["forced"][:, start - 1: -1]
        own = own[:, start - 1: -1]
        same += int((own[..., :, None] == took[..., None, :]).any(-1).sum())
        pairs += took.size
    limit = chk["seq_mean_abs_diff_limit_nats"]
    tol = 2 * yard + correct.FLOOR_NATS
    verdict = {
        "correct": bool(diff <= tol), "reason": None,
        "max_abs_diff_nats": diff,
        "reference_served_dtype_vs_f32_nats": yard, "tolerance_nats": tol,
        "mean_abs_diff_nats": total / max(n, 1),
        "seq_mean_abs_diff_nats": means,
        "seq_mean_abs_diff_limit_nats": limit,
        "n_sequences": len(samples), "n_positions": n,
        "router_agreement_given_earlier_choices": same / max(pairs, 1),
    }
    floor = chk.get("router_agreement_min", 0.0)
    if not verdict["correct"]:
        verdict["reason"] = "the largest difference is over the tolerance"
    elif max(means) > limit:
        verdict["correct"] = False
        verdict["reason"] = "a sequence's mean difference is over its limit"
    elif verdict["router_agreement_given_earlier_choices"] < floor:
        verdict["correct"] = False
        verdict["reason"] = (
            "the reference's router chose "
            f"{verdict['router_agreement_given_earlier_choices']:.3f} of "
            f"the handed-in experts: the floor is {floor}")
    return verdict


def _stand_in(ref, params, arch, served_dtype, samples, chk, dtype, **kw):
    """The reference with a defect (``dtype``, ``kw``) in the program's
    place: its log-probs and its own routing through :func:`_verdict`."""
    pad = _pad(chk)
    stand_ins = []
    for s in samples:
        lp, own = ref.logprobs_and_routing(
            params, arch, s["tokens"], dtype, pad, **kw)
        stand_ins.append(dict(
            s, logprobs=lp[s["start"] - 1:],
            forced=_generated(own, s["start"])))
    verdict = _verdict(ref, params, arch, served_dtype, stand_ins, chk)
    return {k: verdict.get(k) for k in _VERDICT_KEYS + (
        "router_agreement_given_earlier_choices",)}


def _free_running(ref, params, arch, samples, chk) -> Dict:
    """The served log-probs against the reference's OWN routing
    (information: what the routing-given verdict takes out)."""
    pad = _pad(chk)
    diffs = []
    for s in samples:
        f32, _ = ref.next_token_logprobs(
            params, arch, s["tokens"], "float32", pad)
        diffs.append(np.abs(
            np.asarray(s["logprobs"], np.float64) - f32[s["start"] - 1:]))
    return {"max_abs_diff_nats": float(max(d.max() for d in diffs)),
            "seq_mean_abs_diff_nats": [float(d.mean()) for d in diffs]}


def _check(params, arch, served_dtype, short, long_, chk) -> Dict:
    """The verdict on both groups and both controls (module docstring)."""
    ref = correct.reference_module(arch["reference"])
    ref.build_ahead(
        params, arch, ("float32", served_dtype, chk["control_dtype"]),
        _pad(chk))
    check = _verdict(ref, params, arch, served_dtype, short, chk)
    check["verdict_given_the_programs_routing"] = True
    check["n_long_sequences"] = len(long_)
    if "max_abs_diff_nats" not in check:
        return check
    if len(long_) < chk["n_long"]:
        check["correct"] = False
        check["reason"] = (
            f"{len(long_)} sequences of {chk['long_min_tokens']}-"
            f"{chk['long_max_tokens']} positions completed in the window, "
            f"{chk['n_long']} wanted")
        return check
    check["long"] = _verdict(ref, params, arch, served_dtype, long_, chk)
    if check["correct"] and not check["long"]["correct"]:
        check["correct"] = False
        check["reason"] = "long sequences: " + str(check["long"].get("reason"))
    check["free_running"] = _free_running(ref, params, arch, short[:2], chk)
    check["control"] = _stand_in(
        ref, params, arch, served_dtype, short, chk, chk["control_dtype"])
    if check["control"]["correct"]:
        check["correct"] = False
        check["reason"] = (
            f"the comparison passes the reference computed in "
            f"{chk['control_dtype']}: it cannot tell a lower precision")
    check["control_full_attention"] = _stand_in(
        ref, params, arch, served_dtype, long_, chk, "float32", window=None)
    if check["control_full_attention"]["correct"]:
        check["correct"] = False
        check["reason"] = (
            "the comparison passes the reference with every layer full: "
            "it cannot tell a program that forgot the window")
    return check


def run(bench) -> Dict:
    from areal_tpu.gen.engine import GenerationEngine, GenRequest

    arch, mix = bench.arch, bench.mix
    if bench.rehearse:
        arch = bench.arch = _rehearsal_arch(arch)
    eng_opts = mix["engine"]
    cfg = sut.model_config(arch, mix.get("model_overrides", {}))
    params = weights.make_weights(
        sut.weight_shapes(cfg, cfg.dtype), bench.seed, jnp.dtype(cfg.dtype))

    bench.mark("weights")
    stream = traffic_gen.RequestStream(mix, bench.seed, cfg.vocab_size)
    clients = mix["clients"]
    page = eng_opts["page_size"]
    out_hi = mix["output_len"]["hi"]
    max_seqlen = mix["prompt_len"]["hi"] + out_hi
    itemsize = jnp.dtype(cfg.dtype).itemsize
    # what a page of the pool is: the family's own keys
    page_bytes = afmoe_flops.page_bytes(arch, page, itemsize)
    n_pages = int(eng_opts["kv_pool_bytes"] // page_bytes)
    engine = GenerationEngine(
        cfg, params, max_slots=clients, max_seqlen=max_seqlen,
        max_new_tokens_cap=out_hi, page_size=page, n_pages=n_pages,
        enable_prefix_cache=eng_opts["enable_prefix_cache"],
        admit_buckets=eng_opts["admit_buckets"],
        record_routing=True, seed=bench.seed % (2**31 - 1),
    )
    decode_steps = eng_opts["decode_steps"]
    pool_bytes_stored = engine.kv_pool_bytes()
    by_kind = afmoe_flops.kv_bytes_per_token_by_kind(arch, itemsize)
    bench.facts.update(
        kv_bytes_per_token=sum(by_kind.values()), decode_steps=decode_steps)

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

    # everything before here is set-up
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
    leftovers = engine.pause()      # harvests every running slot

    # ---- counts ------------------------------------------------------ #
    finished = done[n_done0:]
    failed = [
        rec for rec in finished
        if rec["out"].finish_reason == "interrupted"
        or len(rec["out"].output_ids) != rec["req"].max_new_tokens
        or not np.isfinite(rec["out"].output_logprobs).all()
    ]
    tokens = sum(len(rec["out"].output_ids) for rec in finished)
    tokens += sum(len(o.output_ids) for o in leftovers)
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
        window_pages_released=grew("window_pages_released"),
    )
    bench.facts["chunk_resident_tokens"] = resident   # one per engine.step span
    bench.facts["chunk_distinct_tokens"] = chunk_distinct[n_chunks0:]
    end_to_end = {
        "rollout_tokens_per_s": tokens / window,
        "rollout_norm_latency_p90_ms": (
            percentile(norm_ms, 90) if len(norm_ms) >= 20 else None),
    }

    # ---- correctness, outside the window ------------------------------ #
    chk = mix["check"]

    def n_positions(rec):
        return len(rec["req"].prompt) + len(rec["out"].output_ids)

    def sample(rec) -> Dict:
        toks = rec["req"].prompt + list(rec["out"].output_ids)
        start = len(rec["req"].prompt)
        # [generated, expert layers, k] -> [expert layers, positions, k]:
        # token i's routing is that of the step that produced it, position
        # start - 1 + i; -1 where no program records it
        forced = np.full(
            (cfg.n_moe_layers, len(toks), cfg.moe.top_k), -1, np.int32)
        forced[:, start - 1: -1] = np.asarray(
            rec["out"].output_routing, np.int32).transpose(1, 0, 2)
        return {"tokens": toks, "start": start, "forced": forced,
                "logprobs": rec["out"].output_logprobs}

    by_rid = lambda rec: rec["req"].rid     # noqa: E731
    short = sorted(
        (rec for rec in in_window if n_positions(rec) <= chk["max_tokens"]),
        key=by_rid)[: chk["n_requests"] - chk["n_long"]]
    # of the long ones, those with the most positions PREFILLED: every
    # log-prob of theirs that is compared then lies past the window's edge
    long_ = sorted(
        (rec for rec in finished
         if chk["long_min_tokens"] <= n_positions(rec) <= chk["long_max_tokens"]
         and len(rec["out"].output_ids) == rec["req"].max_new_tokens),
        key=lambda rec: (-len(rec["req"].prompt), by_rid(rec)))[: chk["n_long"]]
    params = engine.params
    engine.state = None             # the pool's memory, for the reference
    del engine
    t_check = time.perf_counter()
    check = _check(params, arch, cfg.dtype, [sample(r) for r in short],
                   [sample(r) for r in long_], chk)
    check["check_s"] = time.perf_counter() - t_check
    check["long_positions"] = [n_positions(r) for r in long_]
    check["long_prefilled"] = [len(r["req"].prompt) for r in long_]
    check["jit_entries_added_in_window"] = jit1 - jit0
    check["programs_specialised_in_window"] = sorted(
        k for k, n in programs1.items() if n != programs0.get(k, 0))
    if jit1 != jit0:
        check["correct"] = False
        check["reason"] = "the engine specialised a program inside the window"
    if len(norm_ms) < 20:
        check["correct"] = False
        check["reason"] = f"only {len(norm_ms)} requests ran inside the window"

    steps = sorted(bench.span_records("engine.step"),
                   key=lambda td: td[1], reverse=True)
    waits_ms = [1e3 * (rec["out"].t_admit - rec["out"].t_submit)
                for rec in in_window]
    running = sorted(bench.samples["n_running"])
    return {
        "attempted": len(finished), "failed": len(failed),
        "end_to_end": end_to_end, "check": check,
        "info": {
            "completed_in_window": len(finished),
            "submitted_and_completed_in_window": len(in_window),
            "completed_past_the_window_s_edge": sum(
                n_positions(rec) > arch["sliding_window"] + 256
                for rec in finished),
            "norm_latency_ms_median": (
                percentile(norm_ms, 50) if norm_ms else None),
            "norm_latency_ms_p90": end_to_end["rollout_norm_latency_p90_ms"],
            "tokens_in_window": tokens, "chunks": len(resident),
            "mean_resident_tokens": float(np.mean(resident)) if resident else 0,
            "mean_running": float(np.mean(running)) if running else 0,
            # seated at the p90 of the window: 90 % of its chunks ran with
            # at least this many slots
            "running_p10": (
                running[len(running) // 10] if running else None),
            "n_pages": n_pages, "kv_pool_bytes": n_pages * page_bytes,
            "kv_bytes_per_token_by_kind": by_kind,
            "window_pages_released": bench.counters["window_pages_released"],
            # where the routed experts ran
            "moe_grouped_rows": grew("moe_grouped_rows"),
            "moe_dense_rows": grew("moe_dense_rows"),
            # a stalled step shows here and nowhere else in the line;
            # and which of the program's spans held its time
            "engine_step_s_longest": [d for _, d in steps[:3]],
            "engine_step_longest_spans_s": (
                _spans_under(*steps[0]) if steps else {}),
            "queue_wait_ms_max": max(waits_ms, default=None),
            "queue_wait_ms_p90": (
                percentile(waits_ms, 90) if waits_ms else None),
            "pending_after_opening_population": pending_after_opening,
            "pending_after_step_max": int(
                max(bench.samples["n_pending"], default=0)),
            "engine_step_s_median": percentile(bench.spans("engine.step"), 50),
            "memory_peak_bytes_setup": peak_setup,
            "memory_peak_bytes_window": peak_window,
            "kv_pool_bytes_stored": pool_bytes_stored,
            "prefill_tokens": bench.counters["prefill_tokens"],
            "prefix_hit_tokens": bench.counters["prefix_hit_tokens"],
        },
    }
