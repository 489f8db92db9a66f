"""Closed-loop rollout against an in-process ``GenerationEngine`` whose
model has STATE-SPACE layers beside attention layers (family
``granitemoehybrid``): a per-slot recurrent state beside the page pool,
and snapshots of it in the prefix cache.

``rollout_looped_inproc.py`` with these differences; set-up, window, the
exact token count and the p90's population are that driver's (and
``rollout_inproc``'s) line for line, and ``_warm_admission``,
``_warm_wider_tables``, ``_judge``, ``_control``, ``_pad_of``,
``_spans_under``, ``_peak_bytes`` and ``_VERDICT_KEYS`` are imported from
them, not copied:

- the bytes a token takes of the pool come from ``benchmark/ssm_flops.py``
  (a key and a value in every ATTENTION layer), not from
  ``flops.kv_bytes_per_token``, which counts all 40 layers;
- the seeded weights: ``benchmark/weights.py`` fills every matrix with
  normal(0, 0.02), which makes every head forget in two tokens and the
  state's share of a layer's output under 1 %. ``_state_space_init``
  overwrites ``A_log``, ``dt_bias``, ``D`` and the convolution from
  ``--seed`` with the published initialisation's ranges (one head of the
  first layer at their slow end, for ``_state_check``), in the ONE tree
  that the program and the reference both read (the configuration file's
  ``assumed.seeded_weights``);
- what is checked: ``check.n_requests`` requests of at most
  ``check.max_tokens``, prefix hits (seeded from a snapshot) first, and
  ``check.n_long`` with at least ``check.long_min_generated`` generated
  tokens (a thousand in-place updates of one state); every prompt is
  admitted in several chunks (256-1024 tokens, 128 a chunk). At this
  cell's 26 tokens a second a slot a generation of 1,024 takes the whole
  window, so few or none END inside it: where fewer than ``n_long`` did,
  the loop runs on AFTER the window has closed and been counted (no new
  submissions, nothing timed) until the running requests nearest to
  their end have made up the number (``_drain_long``);
- three controls beside the served log-probs, each the reference in the
  program's place through the same comparison: the reference in
  ``check.control_dtype``; the reference with the recurrent state DROPPED
  at the prompt's page-aligned boundary (what a prefix hit seeded from
  nothing looks like); the reference with the state rounded to
  ``check.control_state_dtype`` after every token. The first two have to
  come out NOT correct in every run, or the run is not; the third is
  reported (PERF.md section 6 says what it read: the log-probabilities
  cannot tell it);
- so the recurrent STATE is compared too (``_probe_state``,
  ``_state_check``): one running request's, read from the engine before it
  is paused, against the reference's after the same tokens, under
  ``check.state_rel_diff_limit``; the reference with its state rounded to
  ``check.control_state_dtype`` has to come out over that limit, or the
  run is not correct: this is what holds the configuration's
  ``state_dtype``;
- the last act of set-up is ``gc.collect()`` + ``gc.freeze()``: without it
  one full collection of what set-up left alive (383 k objects, 0.13-0.18 s)
  falls inside every window and the tokens a second read 0.4 % lower in
  two modes (PERF.md section 6, PR 41); the other drivers do not do it;
- under ``--rehearse`` the generic tiny preset (``rehearse.json``) leaves
  ``mamba_n_heads x mamba_d_head`` at 4096 against a hidden size of 64
  and two layers of one kind: ``_rehearsal_arch`` sets small consistent
  state-space sizes and a depth with both kinds of layer.

The next ``benchmark`` issue should fold the FIVE rollout drivers into one
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

from benchmark import correct, ssm_flops, sut, traffic_gen, weights
from benchmark.drivers.rollout_inproc import _warm_admission
from benchmark.drivers.rollout_latent_inproc import (
    _control, _judge, _pad_of, _peak_bytes, _spans_under, _warm_wider_tables)
from benchmark.drivers.rollout_looped_inproc import _VERDICT_KEYS
from benchmark.resident import ChunkResident
from benchmark.stats import percentile


def _rehearsal_arch(arch: dict) -> dict:
    """Small state-space sizes that agree with the tiny preset's hidden
    size, and a depth with both kinds of layer."""
    hidden = arch["hidden_size"]
    return dict(
        arch, num_hidden_layers=2, layer_types=["mamba", "attention"],
        mamba_n_heads=8, mamba_d_head=arch["mamba_expand"] * hidden // 8,
        mamba_d_state=16, mamba_chunk_size=8,
        shared_intermediate_size=arch["intermediate_size"],
        max_position_embeddings=512)


def _state_space_init(params, seed: int):
    """The published initialisation's ranges for what normal(0, 0.02)
    would make degenerate (module docstring), from ``seed``."""
    mixer = dict(params["ssm_layers"]["ssm"])
    key = jax.random.fold_in(weights.fold_seed(seed), 0x55D)
    ks = jax.random.split(key, 5)

    def like(name, x):
        return x.astype(mixer[name].dtype)

    shape = mixer["A_log"].shape
    dt = jnp.exp(jax.random.uniform(
        ks[1], shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    a = jax.random.uniform(ks[0], shape, jnp.float32, 1.0, 16.0)
    # head 0 of the first layer at the ranges' slow end: the head a state
    # kept in 16 bits loses most of, in the layer ``_state_check`` compares
    dt, a = dt.at[0, 0].set(1e-3), a.at[0, 0].set(1.0)
    mixer["A_log"] = like("A_log", jnp.log(a))
    mixer["dt_bias"] = like("dt_bias", dt + jnp.log(-jnp.expm1(-dt)))
    mixer["D"] = like(
        "D", 1.0 + 0.1 * jax.random.normal(ks[2], shape, jnp.float32))
    for k, name in ((ks[3], "conv_w"), (ks[4], "conv_b")):
        if name in mixer:
            mixer[name] = like(name, jax.random.uniform(
                k, mixer[name].shape, jnp.float32, -0.5, 0.5))
    return {
        **params,
        "ssm_layers": {**params["ssm_layers"], "ssm": mixer},
    }


def _memoised(ref):
    """The reference's ``next_token_logprobs`` with its results kept: the
    verdict and the three controls each ask for the float32 and the
    served-dtype pass of every sample, 36 scans of a few thousand steps
    apiece."""
    plain, kept = ref.next_token_logprobs, {}

    def cached(params, arch, tokens, dtype, pad_to):
        key = (tuple(tokens), str(dtype), pad_to,
               arch.get("control_zero_state_at"),
               arch.get("control_state_dtype"))
        if key not in kept:
            kept[key] = plain(params, arch, tokens, dtype, pad_to)
        return kept[key]

    return plain, cached


def _stand_in(params, arch: dict, served_dtype: str, samples, chk: dict,
              arch_of) -> Dict:
    """The float32 reference run under ``arch_of(sample)`` in the program's
    place, through ``_judge`` (which compares with the reference under the
    configuration itself)."""
    ref = correct.reference_module(arch["reference"])
    pad = _pad_of(samples)
    stand_ins = []
    for s in samples:
        lp, _ = ref.next_token_logprobs(
            params, arch_of(s), s["tokens"], "float32", pad)
        stand_ins.append(dict(s, logprobs=lp[s["start"] - 1:]))
    verdict = _judge(params, arch, served_dtype, stand_ins, chk)
    return {k: verdict.get(k) for k in _VERDICT_KEYS}


def _check(params, arch: dict, served_dtype: str, samples, chk: dict,
           page: int) -> Dict:
    """The verdict on the served log-probs and on the three controls."""
    ref = correct.reference_module(arch["reference"])
    plain, ref.next_token_logprobs = _memoised(ref)
    try:
        check = _judge(params, arch, served_dtype, samples, chk)
        if not samples or "max_abs_diff_nats" not in check:
            return check
        check["control"] = _control(params, arch, served_dtype, samples, chk)
        if check["control"]["correct"]:
            check["correct"] = False
            check["reason"] = (
                f"the comparison passes the reference computed in "
                f"{chk['control_dtype']}: it cannot tell a lower precision")
        # what a prefix hit seeded from nothing hands in: the state of
        # every state-space layer dropped where the prompt's page sharing
        # ends (its prefilled positions, len - 1, in whole pages)
        check["control_lost_snapshot"] = _stand_in(
            params, arch, served_dtype, samples, chk,
            lambda s: dict(
                arch, control_zero_state_at=(s["start"] - 1) // page * page))
        # (the tiny preset of --rehearse cannot tell: at a hidden size of
        # 64 the state moves a log-probability by a millionth of a nat,
        # and its mix says so)
        if check["control_lost_snapshot"]["correct"] and chk.get(
                "lost_snapshot_must_be_refused", True):
            check["correct"] = False
            check["reason"] = (
                "the comparison passes the reference with the recurrent "
                "state dropped at the prompt's page boundary: it cannot "
                "tell a lost snapshot")
        # reported, not required (module docstring)
        check["control_state_rounded"] = _stand_in(
            params, arch, served_dtype, samples, chk,
            lambda s: dict(arch, control_state_dtype=chk["control_state_dtype"]))
        return check
    finally:
        ref.next_token_logprobs = plain


def _probe_state(engine, live: Dict, chk: dict):
    """BEFORE the engine is paused: of the requests still running, the one
    that has generated most (and that the check can afford), as ``(the
    tokens its recurrent state is the state AFTER, that state [state
    layers, heads, head dim, state])``: the prompt was prefilled in chunks,
    every generated token but the newest was one in-place update."""
    partial = engine.partial_outputs()
    fits = [rid for rid, (toks, _) in partial.items()
            if toks and len(live[rid]["req"].prompt) + len(toks)
            <= chk["long_max_tokens"]]
    if not fits:
        return None
    rid = max(fits, key=lambda r: len(partial[r][0]))
    n, state = engine.recurrent_state(rid)
    tokens = live[rid]["req"].prompt + partial[rid][0][:n]
    return tokens[:-1], state


def _state_check(params, arch: dict, probe, chk: dict) -> Dict:
    """The log-probabilities cannot tell a recurrent state kept in 16 bits
    (module docstring: the third control), so the state itself is
    compared: the program's, after ``probe``'s tokens, with the float32
    reference's, head by head (the norm of the difference over the norm of
    the reference's head), in the FIRST state-space layer: its inputs are
    one matmul from the embedding, where every later layer's carry the
    residual stream's rounding in the serving dtype, which is as large
    there as what a 16-bit state loses (PERF.md section 6 has the
    readings of all 36). Beside it the reference with its state rounded
    to ``check.control_state_dtype`` after every token, which has to come
    out over the limit."""
    ref = correct.reference_module(arch["reference"])
    tokens, got = probe
    pad = -(-len(tokens) // 256) * 256

    def first_layer(arch):
        return ref.recurrent_state(
            params, arch, tokens, "float32", pad, n_layers=1)[0]

    want = first_layer(arch)
    rounded = first_layer(
        dict(arch, control_state_dtype=chk["control_state_dtype"]))

    def worst_head(a):
        return float((np.sqrt(((a - want) ** 2).sum((-2, -1)))
                      / np.sqrt((want ** 2).sum((-2, -1)))).max())

    return {
        "after_tokens": len(tokens),
        "worst_head_rel_diff": worst_head(got[0]),
        "control_state_rounded_rel_diff": worst_head(rounded),
        "rel_diff_limit": chk["state_rel_diff_limit"],
    }


def _is_long(rec, chk: dict) -> bool:
    r = rec["req"]
    return (r.max_new_tokens >= chk["long_min_generated"]
            and len(r.prompt) + r.max_new_tokens <= chk["long_max_tokens"])


def _drain_long(engine, live: Dict, after: Dict, want: int, chk: dict,
                decode_steps: int) -> List[Dict]:
    """AFTER the window: run the engine on, submitting nothing, until
    ``want`` running requests with a long generation have completed
    (those with the fewest tokens to go), and return their records."""
    todo = sorted(
        (rec for rid, rec in live.items() if rid in after and _is_long(rec, chk)),
        key=lambda rec: rec["req"].max_new_tokens - after[rec["req"].rid],
    )[:want]
    waiting = {rec["req"].rid for rec in todo}
    got, limit = [], chk["long_max_tokens"] // decode_steps + 8
    while waiting and limit > 0:
        limit -= 1
        for o in engine.step(decode_steps):
            if o.rid in waiting:
                waiting.discard(o.rid)
                got.append(dict(live[o.rid], out=o))
    return got


def _pick(records, chk: dict) -> List[Dict]:
    """The checked requests: prefix hits before misses among those of at
    most ``max_tokens``, then the shortest of the long generations."""
    def total(rec):
        return len(rec["req"].prompt) + len(rec["out"].output_ids)

    by_rid = sorted(records, key=lambda rec: rec["req"].rid)
    short = [rec for rec in by_rid if total(rec) <= chk["max_tokens"]]
    hits = [rec for rec in short if rec["out"].prefix_hit_tokens > 0]
    cold = [rec for rec in short if rec["out"].prefix_hit_tokens == 0]
    n = chk["n_requests"]
    picked = (hits[: max(n - 1, 1)] + cold)[:n]
    long = sorted(
        (rec for rec in by_rid
         if len(rec["out"].output_ids) >= chk["long_min_generated"]
         and total(rec) <= chk["long_max_tokens"] and rec not in picked),
        key=total)
    return picked + long[: chk["n_long"]]


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
    # what a token takes of the pool: K/V in the attention layers only
    kv_tok = ssm_flops.kv_bytes_per_token(
        arch, jnp.dtype(cfg.dtype).itemsize)
    n_pages = int(eng_opts["kv_pool_bytes"] // (kv_tok * page))
    engine = GenerationEngine(
        cfg, params, max_slots=clients, max_seqlen=max_seqlen,
        max_new_tokens_cap=out_hi, page_size=page, n_pages=n_pages,
        enable_prefix_cache=eng_opts["enable_prefix_cache"],
        state_snapshots=eng_opts["state_snapshots"],
        seed=bench.seed % (2**31 - 1),
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

    # everything before here is set-up. What it left alive (the compiled
    # programs and their traces, the weights' tree) lives as long as the
    # engine: taken out of the collector's way as a server does after its
    # warm-up, or ONE full collection of it falls inside every window of
    # this young process (0.13-0.18 s in one chunk, 0.4 % of the tokens:
    # PERF.md section 6, PR 41)
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
    samples = [{
        "tokens": rec["req"].prompt + list(rec["out"].output_ids),
        "start": len(rec["req"].prompt),
        "logprobs": rec["out"].output_logprobs,
    } for rec in pool]
    n_hits = sum(rec["out"].prefix_hit_tokens > 0 for rec in pool)
    n_long = sum(
        len(rec["out"].output_ids) >= chk["long_min_generated"] for rec in pool)
    params = engine.params
    engine.state = None             # the pool's and the state's memory
    del engine
    t_check = time.perf_counter()
    check = _check(params, arch, cfg.dtype, samples, chk, page)
    state = check["state"] = (
        _state_check(params, arch, probe, chk) if probe else None)
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
