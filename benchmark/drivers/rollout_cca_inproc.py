"""Closed-loop rollout against an in-process ``GenerationEngine`` whose
model attends inside a convolved latent (family ``zaya``: CCA) and routes
top-1 behind an MLP router with state: a per-slot CARRY beside the page
pool, a snapshot of it with every run the prefix cache files.

``rollout_state_inproc.py`` with these differences; set-up, window, the
exact token count and the p90's population are that driver's line for
line, and ``_warm_admission``, ``_warm_wider_tables``, ``_judge``,
``_pick``, ``_is_long``, ``_drain_long``, ``_pad_of``, ``_spans_under``,
``_peak_bytes`` and ``_VERDICT_KEYS`` are imported from the older drivers,
not copied:

- the bytes a token takes of the pool and a slot's carry come from
  ``benchmark/cca_flops.py``;
- the seeded weights: ``benchmark/weights.py`` fills every matrix with
  normal(0, 0.02), under which the convolved path vanishes, the
  temperature and the residual scales are ~0 and the router's softmax is
  uniform. ``_cca_init`` overwrites them from ``--seed`` (the
  configuration file's ``assumed.seeded_weights`` lists every overwrite) in
  the ONE tree that the program and the reference both read;
- the engine records each generated token's routing (``record_routing``),
  and TOP-1 ROUTING IS THE HAZARD of the comparison: where a row's two
  largest router outputs lie within the serving dtype's rounding, the
  program (bfloat16) and the float32 reference choose different experts
  and that token's whole expert output in that layer differs, which says
  nothing about either's arithmetic; and the chosen output's probability
  is the expert's WEIGHT, so the router's rounding scales the layer's
  whole branch: with these random experts bfloat16 alone moves the
  residual by 1 % a layer (PERF.md section 6, PR 45). So ``correct`` is
  judged against the reference GIVEN the program's choices at the
  generated positions (its own router's probability of the given output
  is still the weight; the prompt's positions, whose routing admission
  does not record, run free on both sides: running the prompt through
  the program's prefill again for them was tried and moved nothing), and
  beside it are reported the free-running comparison and the share of
  (generated token, layer) pairs on which the reference, free-running
  and given the choices before, chose what the program chose. A router
  that computed something else would agree on one pair in 17: under
  ``check.router_agreement_min`` the run is not correct;
- three controls, each the reference with a defect in the program's
  place, each of which has to come out NOT correct in every run, or the
  run is not. Two are whole stand-in PROGRAMS, log-probs and recorded
  routing, through the same verdict: the reference in
  ``check.control_dtype``; the reference in which no layer reads the
  previous layer's router vector. The third, the carry DROPPED at the
  prompt's page-aligned boundary (the convolutions and the value shift
  start again there: what a prefix hit seeded from nothing hands in),
  reaches two positions and no further: the log-probabilities of a
  continuation cannot tell it from rounding. So what the POOL holds is
  compared (``_probe_boundary``, ``_boundary_check``): of one running
  request admitted on a prefix hit, the key and value of the first
  position it computed itself, in the first layer (whose inputs are the
  embeddings: no router's choice is behind it), against the reference's
  under ``check.boundary_rel_diff_limit``; the reference with the carry
  dropped there has to come out over that limit; the LAST layer's is
  reported beside it, behind fifteen layers of free-running choices;
- under ``--rehearse`` the generic tiny preset (``rehearse.json``) leaves
  ``head_dim`` at 128 against a hidden size of 64: ``_rehearsal_arch`` sets
  small consistent sizes.

The next ``benchmark`` issue should fold the SIX rollout drivers into one
(PERF.md, section 7).

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

from benchmark import cca_flops, correct, sut, traffic_gen, weights
from benchmark.drivers.rollout_inproc import _warm_admission
from benchmark.drivers.rollout_latent_inproc import (
    _judge, _pad_of, _peak_bytes, _spans_under, _warm_wider_tables)
from benchmark.drivers.rollout_looped_inproc import _VERDICT_KEYS
from benchmark.drivers.rollout_state_inproc import (
    _drain_long, _is_long, _pick)
from benchmark.resident import ChunkResident
from benchmark.stats import percentile

# the stand-in programs: name -> (what differs in ``arch``, the dtype it
# computes in; None: ``check.control_dtype``)
_STAND_INS = {
    "control": ({}, None),
    "control_no_router_state": ({"control_no_router_state": True}, "float32"),
}
_CONTROLS = (*_STAND_INS, "control_lost_snapshot")


def _rehearsal_arch(arch: dict) -> dict:
    """Small sizes that agree with the tiny preset's hidden size."""
    return dict(
        arch, num_hidden_layers=2, layer_types=["hybrid", "hybrid"],
        head_dim=16, moe_intermediate_size=32, num_experts=4,
        router_hidden_size=16, max_position_embeddings=512)


def _cca_init(params, seed: int):
    """What normal(0, 0.02) would make degenerate (module docstring), from
    ``seed``: the configuration file's ``assumed.seeded_weights``."""
    layers = dict(params["layers"])
    attn, mlp = dict(layers["attn"]), dict(layers["mlp"])
    keys = iter(jax.random.split(
        jax.random.fold_in(weights.fold_seed(seed), 0xCCA), 16))

    def normal(like, std):
        return (jax.random.normal(next(keys), like.shape, jnp.float32)
                * std).astype(like.dtype)

    def uniform(like, lo, hi):
        return jax.random.uniform(
            next(keys), like.shape, jnp.float32, lo, hi).astype(like.dtype)

    taps0 = attn["conv0_w"].shape[1]
    taps1, _, head_dim, _ = attn["conv1_w"].shape[1:]
    attn["conv0_w"] = normal(attn["conv0_w"], taps0 ** -0.5)
    attn["conv1_w"] = normal(attn["conv1_w"], (taps1 * head_dim) ** -0.5)
    attn["k_temp"] = uniform(attn["k_temp"], 0.5, 2.0)
    for name in ("attn_res", "mlp_res"):
        res = dict(layers[name])
        for gain in ("a_r", "a_h"):
            res[gain] = uniform(res[gain], 0.8, 1.2)
        layers[name] = res
    width = mlp["router_w1"].shape[-1]
    mlp["router_mix"] = uniform(mlp["router_mix"], 0.5, 1.0)
    mlp["router_w1"] = normal(mlp["router_w1"], 2.0 * width ** -0.5)
    # the last two layers with every COLUMN summing to zero: a GELU's
    # output has a positive mean, which a random column turns into a
    # constant for or against its output, and the router then sends a
    # quarter of the rows to one expert and none to three (another three
    # with every seed); centred, every expert gets 4-8 % of the rows and
    # the skip 1/17, as a router trained with balancing biases does
    for name, gain in (("router_w2", 2.0), ("router", 6.0)):
        w = normal(mlp[name], gain * width ** -0.5).astype(jnp.float32)
        mlp[name] = (w - w.mean(axis=-2, keepdims=True)).astype(
            mlp[name].dtype)
    return {**params, "layers": {**layers, "attn": attn, "mlp": mlp}}


def _memoised(ref):
    """``ref._run`` with its results kept (the verdicts and the agreement
    ask for the same passes of every sample several times over) and, under
    ``arch["given_routing"]`` (an array ``[L, n]``, -1 where the program
    recorded none), with those choices forced."""
    plain, kept = ref._run, {}

    def run(params, arch, tokens, dtype, pad_to):
        given = arch.get("given_routing")
        key = (tuple(tokens), str(dtype), pad_to,
               arch.get("control_zero_carry_at"),
               bool(arch.get("control_no_router_state")),
               None if given is None else given.tobytes())
        if key not in kept:
            if given is not None:
                arch = dict(arch, forced_routing=given)
            kept[key] = plain(params, arch, tokens, dtype, pad_to)
        return kept[key]

    return plain, run


def _generated(routing, start: int):
    """``routing [L, n]`` with the prompt's positions (whose routing no
    program records) set to -1: what a program hands in."""
    out = np.full(routing.shape, -1, np.int32)
    out[:, start - 1 : -1] = routing[:, start - 1 : -1]
    return out


def _verdict(ref, params, arch: dict, served_dtype: str, samples, chk: dict,
             free_running: bool = False) -> Dict:
    """``_judge`` of each sample against the reference GIVEN that sample's
    routing (one sequence at a time: the routing is the sequence's own),
    and the share of (generated token, layer) pairs on which the
    reference's own router, given the choices before, chose the same;
    under ``check.router_agreement_min`` the verdict is not correct."""
    pad = _pad_of(samples)
    parts, same, pairs = [], 0, 0
    for s in samples:
        given = dict(arch, given_routing=s["forced"])
        parts.append(_judge(params, given, served_dtype, [s], chk))
        if "max_abs_diff_nats" not in parts[-1]:
            return parts[-1]
        own, _ = ref.routing(params, given, s["tokens"], "float32", pad)
        took = s["forced"][:, s["start"] - 1 : -1]
        same += int((own[:, s["start"] - 1 : -1] == took).sum())
        pairs += took.size
    # pooled as ``correct.check_logprobs`` pools its samples: the largest
    # difference of all against twice the largest the served dtype alone
    # costs the reference on any of them, plus the floor
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
    if verdict["correct"] and max(means) > limit:
        verdict["correct"] = False
        verdict["reason"] = "a sequence's mean difference is over its limit"
    floor = chk.get("router_agreement_min", 0.0)
    if verdict["router_agreement_given_earlier_choices"] < floor:
        verdict["correct"] = False
        verdict["reason"] = (
            "the reference's router chose the handed-in expert on "
            f"{verdict['router_agreement_given_earlier_choices']:.3f} of "
            f"the pairs: the floor is {floor}")
    return verdict


def _check(params, arch: dict, served_dtype: str, samples, chk: dict) -> Dict:
    """The verdict on the served log-probs (against the reference given
    the program's routing), the free-running comparison and agreement
    beside it, and the two stand-in programs."""
    ref = correct.reference_module(arch["reference"])
    plain, ref._run = _memoised(ref)
    try:
        check = _verdict(ref, params, arch, served_dtype, samples, chk)
        if not samples or "max_abs_diff_nats" not in check:
            return check
        pad = _pad_of(samples)
        free = _judge(params, arch, served_dtype, samples, chk)
        check["free_running"] = {
            k: free.get(k) for k in _VERDICT_KEYS + (
                "reference_served_dtype_vs_f32_nats",)}
        same, pairs, skipped = 0, 0, 0
        for s in samples:
            own, _ = ref.routing(params, arch, s["tokens"], "float32", pad)
            took = s["forced"][:, s["start"] - 1 : -1]
            same += int((own[:, s["start"] - 1 : -1] == took).sum())
            pairs += took.size
            skipped += int((took == arch["num_experts"]).sum())
        check["router_agreement_free_running"] = same / max(pairs, 1)
        check["program_skip_share"] = skipped / max(pairs, 1)
        for name, (defect, dtype) in _STAND_INS.items():
            dtype = dtype or chk["control_dtype"]
            stand_ins = []
            for s in samples:
                faulty = dict(arch, **defect)
                lp, _ = ref.next_token_logprobs(
                    params, faulty, s["tokens"], dtype, pad)
                own, _ = ref.routing(params, faulty, s["tokens"], dtype, pad)
                stand_ins.append(dict(
                    s, logprobs=lp[s["start"] - 1:],
                    forced=_generated(own, s["start"])))
            verdict = _verdict(
                ref, params, arch, served_dtype, stand_ins, chk)
            check[name] = {k: verdict.get(k) for k in _VERDICT_KEYS + (
                "router_agreement_given_earlier_choices",)}
        return check
    finally:
        ref._run = plain


def _probe_boundary(engine, live: Dict):
    """BEFORE the engine is paused: of the requests still running, one that
    was admitted on a prefix hit, as ``(its tokens up to the first
    position it computed itself, that position's key and value in every
    layer as its pages hold them, [L, 2, Hkv, D])``; read from the
    engine's host tables and its pool (the pull waits for the chunk in
    flight)."""
    with engine._lock:
        for b, slot in enumerate(engine._slots):
            n = slot.prefix_hit_tokens if slot is not None else 0
            if n and slot.rid in live and engine._lens_host[b] > n:
                page = int(engine._tables_host[0, b, n // engine.page])
                kv = jax.device_get(
                    engine.state.cache.pages[:, page, :, :, n % engine.page])
                return (live[slot.rid]["req"].prompt[: n + 1],
                        np.asarray(kv, np.float32))
    return None


def _boundary_check(params, arch: dict, probe, chk: dict) -> Dict:
    """What the pool holds of the first position after a prefix hit, in
    the first layer and in the last, against the float32 reference's (the
    norm of the difference over the reference's norm, key and value
    together); beside it the reference with the carry DROPPED at that
    position. The first layer is held to ``check.boundary_rel_diff_limit``
    and the control has to come out over it. The last layer's inputs are
    behind fifteen layers of top-1 choices that run free on both sides at
    a prompt's positions, so its reading is reported, not judged (PERF.md
    section 7): a snapshot that seeded a deeper layer wrongly shows
    there."""
    ref = correct.reference_module(arch["reference"])
    tokens, got = probe
    n = len(tokens) - 1
    layers = (0, arch["num_hidden_layers"] - 1)
    want = ref.kv_at(params, arch, tokens, n, layers)
    lost = ref.kv_at(
        params, dict(arch, control_zero_carry_at=n), tokens, n, layers)

    def rel(a):
        return [float(np.linalg.norm(x - w) / np.linalg.norm(w))
                for x, w in zip(a, want)]

    (first, last), (lost_first, lost_last) = rel(got[list(layers)]), rel(lost)
    return {"position": n, "rel_diff": first,
            "control_lost_snapshot_rel_diff": lost_first,
            "rel_diff_limit": chk["boundary_rel_diff_limit"],
            "last_layer_rel_diff": last,
            "last_layer_control_lost_snapshot_rel_diff": lost_last}


def run(bench) -> Dict:
    from areal_tpu.gen.engine import GenerationEngine, GenRequest

    arch, mix = bench.arch, bench.mix
    if bench.rehearse:
        arch = bench.arch = _rehearsal_arch(arch)
    eng_opts = mix["engine"]
    cfg = sut.model_config(arch, mix.get("model_overrides", {}))
    params = _cca_init(
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
    itemsize = jnp.dtype(cfg.dtype).itemsize
    kv_tok = cca_flops.kv_bytes_per_token(arch, itemsize)
    n_pages = int(eng_opts["kv_pool_bytes"] // (kv_tok * page))
    engine = GenerationEngine(
        cfg, params, max_slots=clients, max_seqlen=max_seqlen,
        max_new_tokens_cap=out_hi, page_size=page, n_pages=n_pages,
        enable_prefix_cache=eng_opts["enable_prefix_cache"],
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
    probe = _probe_boundary(engine, live)
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
        moe_rows=grew("moe_rows"),
        moe_skip_rows=grew("moe_skip_rows"),
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
        prompt, out = rec["req"].prompt, rec["out"]
        # the program's choice for the INPUT token of each decode step:
        # positions start - 1 .. n - 2; -1 (the reference's own) elsewhere
        samples.append({
            "tokens": prompt + list(out.output_ids), "start": len(prompt),
            "logprobs": out.output_logprobs,
            "forced": _generated(np.pad(
                np.asarray(out.output_routing)[:, :, 0].T,
                ((0, 0), (len(prompt) - 1, 1))), len(prompt))})
    n_hits = sum(rec["out"].prefix_hit_tokens > 0 for rec in pool)
    n_long = sum(
        len(rec["out"].output_ids) >= chk["long_min_generated"] for rec in pool)
    params = engine.params
    engine.state = None             # the pool's and the carry's memory
    del engine
    t_check = time.perf_counter()
    check = _check(params, arch, cfg.dtype, samples, chk)
    boundary = check["boundary"] = (
        _boundary_check(params, arch, probe, chk) if probe else None)
    if boundary is not None:
        lost = boundary["control_lost_snapshot_rel_diff"]
        check["control_lost_snapshot"] = {
            "correct": lost <= boundary["rel_diff_limit"], "rel_diff": lost}
    for name in _CONTROLS:
        if check.get(name, {}).get("correct"):
            check["correct"] = False
            check["reason"] = (
                f"the comparison passes {name}: it cannot tell what that "
                "control breaks")
    if boundary is None:
        check["correct"] = False
        check["reason"] = (
            "no running request admitted on a prefix hit: the carry "
            "behind a snapshot was not compared")
    elif boundary["rel_diff"] > boundary["rel_diff_limit"]:
        check["correct"] = False
        check["reason"] = (
            f"the pool's key and value of the first position after a "
            f"prefix hit are {boundary['rel_diff']:.4f} of their norm from "
            f"the reference's: the limit is {boundary['rel_diff_limit']}")
    check["check_s"] = time.perf_counter() - t_check
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
            "kv_pool_occupancy_mean": float(
                np.mean(bench.samples["kv_pool_occupancy"])),
            "n_pages": n_pages, "kv_pool_bytes": n_pages * page * kv_tok,
            "kv_pool_bytes_stored": pool_bytes_stored,
            "cache_bytes_per_token_stored": pool_bytes_stored // (n_pages * page),
            # the per-slot carry and its snapshots, and what moved
            "state_bytes_per_slot": cca_flops.carry_bytes_per_slot(
                arch, itemsize),
            **{name: grew(name) for name in (
                "admitted", "state_slots", "state_snapshots_taken",
                "state_snapshot_hits", "state_snapshot_bytes",
                "state_snapshot_evictions", "moe_rows", "moe_skip_rows",
                "moe_experts_hit", "moe_expert_slots", "moe_grouped_rows",
                "moe_dense_rows", "kv_write_tiles", "preemptions",
                "slots_held")},
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
