"""Closed-loop rollout against an in-process ``GenerationEngine`` whose
model is two-branch blocks over gated DELTA-RULE linear-attention layers
and gated attention layers without positions (family ``solar_open2``), each
block's second branch an expert-parallel rank's SHARE of the experts: a
per-slot matrix state a head beside the page pool, snapshots of it in the
prefix cache, and a router that scores 320 experts of which 40 are here.

``rollout_share_inproc.py`` with these differences; set-up, window, the
exact token count and the p90's population are that driver's line for
line, and ``_warm_admission``, ``_warm_wider_tables``, ``_judge``,
``_pick``, ``_is_long``, ``_drain_long``, ``_spans_under``,
``_peak_bytes``, ``_VERDICT_KEYS`` and the share driver's ``_memoised``,
``_generated`` and ``_verdict`` (the verdict GIVEN the program's routing at
the generated positions, and why) are imported from the older drivers, not
copied. This one holds only its own:

- the seeded weights (``_delta_init``): ``benchmark/weights.py`` fills every
  matrix with normal(0, 0.02); ``A_log``, ``dt_bias`` and the convolutions
  are overwritten from ``--seed`` with the published initialisation's
  ranges, head 0 of the FIRST linear layer at their slow end, in the ONE
  tree that the program and the reference both read (the configuration
  file's ``assumed.seeded_weights``); the router's correction bias is
  normal(0, 0.02) as ``weights.py`` makes every bias;
- the bytes of a token in the pool and of a slot's state come from
  ``benchmark/kda_flops.py`` (K/V in the ONE attention layer);
- SIX controls, each the reference with a defect in the program's place,
  each of which has to come out NOT correct in every run, or the run is
  not. Five through the verdict on the log-probs (its own log-probs AND
  its own routing), on ``check.control_samples`` of the checked requests:
  the reference in ``check.control_dtype``; the state DROPPED at the
  prompt's page-aligned boundary (a prefix hit seeded from nothing); the
  combine weights normalised over the chosen experts HELD here; and two
  of this mechanism's own plausible errors: ``beta`` without the factor 2
  (``kda_allow_neg_eigval`` forgotten) and ONE decay a head (the mean of
  its channels') in place of a decay a channel. The sixth on the STATE
  itself (``_state_check``: one running request's three linear layers
  against the reference's, GIVEN the program's routing in the expert
  branches before them): the reference with its state rounded
  to ``check.control_state_dtype``, which the share of entries that dtype
  cannot hold refuses (``_state_check`` says why the distance alone cannot);
- under ``--rehearse`` the generic tiny preset leaves the published
  linear-attention and expert sizes against a hidden size of 64:
  ``_rehearsal_arch`` sets small consistent ones and a share (4 of 8).

This is the TENTH rollout driver: the next ``benchmark`` issue should
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

from benchmark import correct, kda_flops, sut, traffic_gen, weights
from benchmark.drivers.rollout_inproc import _warm_admission
from benchmark.drivers.rollout_latent_inproc import (
    _judge, _peak_bytes, _spans_under, _warm_wider_tables)
from benchmark.drivers.rollout_looped_inproc import _VERDICT_KEYS
from benchmark.drivers.rollout_share_inproc import (
    _generated, _memoised, _verdict)
from benchmark.drivers.rollout_state_inproc import (
    _drain_long, _is_long, _pick)
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
    "control_beta_without_two": (
        lambda s, page: {"control_beta_without_two": True}, "float32",
        "beta without the factor 2 of kda_allow_neg_eigval"),
    "control_decay_a_head": (
        lambda s, page: {"control_decay_a_head": True}, "float32",
        "one decay a head in place of a decay a channel"),
}
# the controls whose programs differ from the plain forward's
_CONTROL_PROGRAMS = (
    "control_norm_over_held", "control_beta_without_two",
    "control_decay_a_head")


def _rehearsal_arch(arch: dict) -> dict:
    """Small sizes that agree with the tiny preset's hidden size, one
    period, and a share of the experts."""
    return dict(
        arch, num_hidden_layers=4, gqa_layers=[0], head_dim=16,
        linear_attn_config=dict(
            arch["linear_attn_config"], head_dim=16, num_heads=4),
        moe_intermediate_size=32, n_routed_experts=4, expert_parallel_size=2,
        num_experts_per_tok=3, max_position_embeddings=512)


def _delta_init(params, seed: int):
    """The published initialisation's ranges for what normal(0, 0.02)
    would make degenerate (module docstring), from ``seed``."""
    mixer = dict(params["kda_layers"]["kda"])
    key = jax.random.fold_in(weights.fold_seed(seed), 0xCDA)
    ks = jax.random.split(key, 3)

    def like(name, x):
        return x.astype(mixer[name].dtype)

    n_layers, n_heads = mixer["A_log"].shape
    a = jax.random.uniform(ks[0], (n_layers, n_heads), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(
        ks[1], (n_layers, n_heads, mixer["dt_bias"].shape[1] // n_heads),
        jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    # head 0 of the first layer at the ranges' slow end: the head a state
    # kept in 16 bits loses most of, in the first of the layers that
    # ``_state_check`` compares
    a, dt = a.at[0, 0].set(1.0), dt.at[0, 0].set(1e-3)
    mixer["A_log"] = like("A_log", jnp.log(a))
    mixer["dt_bias"] = like(
        "dt_bias", (dt + jnp.log(-jnp.expm1(-dt))).reshape(n_layers, -1))
    mixer["conv_w"] = like("conv_w", jax.random.uniform(
        ks[2], mixer["conv_w"].shape, jnp.float32, -0.5, 0.5))
    return {
        **params,
        "kda_layers": {**params["kda_layers"], "kda": mixer},
    }


def _check(params, arch: dict, served_dtype: str, samples, chk: dict,
           page: int) -> Dict:
    """The verdict on the served log-probs (against the reference given the
    program's routing), the free-running comparison and agreement beside
    it, and the five stand-in programs."""
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


def _probe_state(engine, live: Dict, chk: dict):
    """BEFORE the engine is paused: of the requests still running, the one
    that has generated most (and that the check can afford), as ``(the
    tokens its state is the state AFTER, that state [linear layers, heads,
    Dk, Dv], the program's routing at those tokens' generated positions
    [layers, tokens, k], -1 elsewhere)``."""
    partial = engine.partial_outputs()
    fits = [rid for rid, (toks, _) in partial.items()
            if toks and len(live[rid]["req"].prompt) + len(toks)
            <= chk["long_max_tokens"]]
    if not fits:
        return None
    rid = max(fits, key=lambda r: len(partial[r][0]))
    n, state = engine.recurrent_state(rid)
    routing = engine.partial_routing(rid)
    prompt = live[rid]["req"].prompt
    tokens = (prompt + partial[rid][0][:n])[:-1]
    forced = None
    if routing is not None and len(routing) >= n:
        r = np.asarray(routing[:n], np.int32).transpose(1, 0, 2)
        forced = np.full((r.shape[0], len(tokens), r.shape[2]), -1, np.int32)
        forced[:, len(prompt) - 1:] = r[:, : len(tokens) - len(prompt) + 1]
    return tokens, state, forced


def _not_representable(state, dtype: str) -> float:
    """Share of a state's entries that ``dtype`` cannot hold: about one in
    a state kept and accumulated in float32, none in one that was rounded
    to ``dtype`` when it was last written."""
    x = np.asarray(state, np.float32)
    back = np.asarray(jnp.asarray(x).astype(jnp.dtype(dtype)).astype(
        jnp.float32))
    return float((back != x).mean())


def _state_check(params, arch: dict, probe, chk: dict) -> Dict:
    """The log-probabilities cannot tell a state kept in 16 bits
    (``rollout_state_inproc._state_check`` says why), so the state itself
    is compared: one running request's, in EVERY linear layer, with the
    float32 reference's after the same tokens GIVEN the program's choice of
    experts at the generated positions, head by head (the norm of the
    difference over the norm of the reference's head; the worst head of the
    worst layer is held to the limit, and each layer's worst is in the
    result). Two limits, because
    the first alone cannot hold the configuration's ``state_dtype`` HERE: a
    delta rule overwrites what it holds within a few hundred tokens, so a
    state rounded to bfloat16 after every token drifts 0.020-0.021 of a
    head's norm from the reference's and no further, while bfloat16
    ACTIVATIONS (the layer's inputs come through an attention layer and
    its experts in the serving dtype) move the program's float32 state by
    0.023-0.024 (PERF.md section 6, PR 59: a state-space layer's rounded
    state piles up over thousands of tokens, 0.1 and more, which is why
    the older cells' one limit serves them). So: (1) ``rel_diff_limit``
    holds the RULE: the reference with ``beta`` without its factor 2 and
    with one decay a head, each through the same comparison, have to come
    out over it; (2) ``not_representable_min`` holds the DTYPE of what is
    STORED: the share of a layer's entries that
    ``check.control_state_dtype`` cannot represent, about one for a float32
    state (the least of the layers is held to it); the reference with its
    state rounded to that dtype after every token reads 0 and has to come
    out under it in every layer. What passes both is in PERF.md's Open
    questions: a float32 array whose WRITE term is computed in 16 bits."""
    ref = correct.reference_module(arch["reference"])
    tokens, got, forced = probe
    if forced is not None:
        arch = dict(arch, forced_routing=forced)

    def layers(**defect):
        return ref.recurrent_state(
            params, dict(arch, **defect), tokens, "float32", 0,
            n_layers=len(got))

    want = layers()

    def worst_heads(a):
        """The worst head of each layer."""
        return (np.sqrt(((a - want) ** 2).sum((-2, -1)))
                / np.sqrt((want ** 2).sum((-2, -1)))).max(-1)

    low = chk["control_state_dtype"]
    rounded = layers(control_state_dtype=low)
    mine = worst_heads(got)
    return {
        "after_tokens": len(tokens),
        "layers": len(got),
        "given_the_programs_routing": forced is not None,
        "worst_head_rel_diff": float(mine.max()),
        "layer_rel_diffs": [float(d) for d in mine],
        "rel_diff_limit": chk["state_rel_diff_limit"],
        "control_rel_diffs": {
            name: float(worst_heads(layers(**{name: True})).max())
            for name in ("control_beta_without_two", "control_decay_a_head")},
        "control_state_rounded_rel_diff": float(worst_heads(rounded).max()),
        "not_representable": min(_not_representable(l, low) for l in got),
        "not_representable_min": chk["state_not_representable_min"],
        "control_state_rounded_not_representable": max(
            _not_representable(l, low) for l in rounded),
    }


def _state_verdict(state, chk: dict):
    """``(correct, reason)`` of :func:`_state_check`'s readings."""
    low = chk["control_state_dtype"]
    if state is None:
        return False, "no running request's delta-rule state was compared"
    limit, floor = state["rel_diff_limit"], state["not_representable_min"]
    if state["worst_head_rel_diff"] > limit:
        return False, (
            f"the delta-rule state is {state['worst_head_rel_diff']:.4f} of "
            f"a head's norm from the reference's after "
            f"{state['after_tokens']} tokens: the limit is {limit}")
    if state["not_representable"] < floor:
        return False, (
            f"{low} holds all but {state['not_representable']:.4f} of the "
            f"state's entries: a float32 state reads over {floor}")
    passed = [n for n, d in state["control_rel_diffs"].items() if d <= limit]
    if passed and chk.get("controls_must_be_refused", True):
        return False, (
            f"the comparison of the state passes the reference with "
            f"{passed[0]}")
    if state["control_state_rounded_not_representable"] >= floor:
        return False, (
            "the comparison of the state passes the reference with its "
            f"state rounded to {low}: it cannot tell a 16-bit state")
    return True, None


def run(bench) -> Dict:
    from areal_tpu.gen.engine import GenerationEngine, GenRequest

    arch, mix = bench.arch, bench.mix
    if bench.rehearse:
        arch = bench.arch = _rehearsal_arch(arch)
    eng_opts = mix["engine"]
    cfg = sut.model_config(arch, mix.get("model_overrides", {}))
    params = _delta_init(
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
    # what a token takes of the pool: K/V in the attention layer only
    kv_tok = kda_flops.kv_bytes_per_token(
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
        chk["long_max_tokens"], state_dtype=chk["control_state_dtype"],
        controls=_CONTROL_PROGRAMS)
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

    ok, why = _state_verdict(state, chk)
    if not ok:
        check["correct"], check["reason"] = False, why

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
            "state_bytes_per_slot": kda_flops.state_bytes_per_slot(
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
