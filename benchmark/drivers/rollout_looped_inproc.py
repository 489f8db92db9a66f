"""Closed-loop rollout against an in-process ``GenerationEngine`` whose
model runs its stack SEVERAL TIMES over one set of weights (family
``ouro``): the page pool holds a token once a pass, ``total_ut_steps x
num_hidden_layers`` cache layers behind ``num_hidden_layers`` of weights.

``rollout_latent_inproc.py`` with two differences; set-up, window, the
exact token count and the p90's population are that driver's (and
``rollout_inproc``'s) line for line, and ``_warm_admission``,
``_warm_wider_tables``, ``_judge``, ``_control``, ``_pad_of``,
``_spans_under`` and ``_peak_bytes`` are imported from them, not copied:

- the bytes a token takes of the pool come from ``benchmark/
  loop_flops.py`` (a key and a value in every CACHE layer), not from
  ``flops.kv_bytes_per_token``, which counts one pass: the pool would be
  given four times the pages that fit;
- a second control beside the reference in ``check.control_dtype``: the
  reference with ``check.control_passes`` passes (one fewer than the
  model's) in the program's place, through the same comparison: what a
  program that left a pass out would hand in. Both have to come out NOT
  correct in every run, or the run is not.

The next ``benchmark`` issue should fold the FOUR rollout drivers into one
(PERF.md, section 7).

Tokens are counted exactly: what the requests completed in the window
generated, plus what the requests still running at its end had generated,
minus what the requests running at its start had generated before it.
"""

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import correct, loop_flops, sut, traffic_gen, weights
from benchmark.drivers.rollout_inproc import _warm_admission
from benchmark.drivers.rollout_latent_inproc import (
    _control, _judge, _pad_of, _peak_bytes, _spans_under, _warm_wider_tables)
from benchmark.resident import ChunkResident
from benchmark.stats import percentile

_VERDICT_KEYS = (
    "correct", "reason", "max_abs_diff_nats", "tolerance_nats",
    "mean_abs_diff_nats", "seq_mean_abs_diff_nats")


def _control_passes(params, arch: dict, served_dtype: str, samples,
                    chk: dict) -> Dict:
    """The reference with ``check.control_passes`` passes in the program's
    place, through ``_judge`` (which compares with the reference at the
    configuration's own pass count)."""
    ref = correct.reference_module(arch["reference"])
    fewer = dict(arch, total_ut_steps=chk["control_passes"])
    pad = _pad_of(samples)
    stand_ins = []
    for s in samples:
        lp, _ = ref.next_token_logprobs(
            params, fewer, s["tokens"], "float32", pad)
        stand_ins.append(dict(s, logprobs=lp[s["start"] - 1:]))
    verdict = _judge(params, arch, served_dtype, stand_ins, chk)
    return {k: verdict.get(k) for k in _VERDICT_KEYS}


def _check(params, arch: dict, served_dtype: str, samples, chk: dict) -> Dict:
    """The verdict on the served log-probs and on both controls."""
    check = _judge(params, arch, served_dtype, samples, chk)
    if not samples:
        return check
    check["control"] = _control(params, arch, served_dtype, samples, chk)
    if check["control"]["correct"]:
        check["correct"] = False
        check["reason"] = (
            f"the comparison passes the reference computed in "
            f"{chk['control_dtype']}: it cannot tell a lower precision")
    check["control_fewer_passes"] = _control_passes(
        params, arch, served_dtype, samples, chk)
    if check["control_fewer_passes"]["correct"]:
        check["correct"] = False
        check["reason"] = (
            f"the comparison passes the reference with "
            f"{chk['control_passes']} passes: it cannot tell a program "
            f"that left a pass out")
    return check


def run(bench) -> Dict:
    from areal_tpu.gen.engine import GenerationEngine, GenRequest

    arch, mix = bench.arch, bench.mix
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
    # the one difference from the other drivers' set-up: what a token
    # takes of the pool (a key and a value a PASS a layer)
    kv_tok = loop_flops.kv_bytes_per_token(
        arch, jnp.dtype(cfg.dtype).itemsize)
    n_pages = int(eng_opts["kv_pool_bytes"] // (kv_tok * page))
    engine = GenerationEngine(
        cfg, params, max_slots=clients, max_seqlen=max_seqlen,
        max_new_tokens_cap=out_hi, page_size=page, n_pages=n_pages,
        enable_prefix_cache=eng_opts["enable_prefix_cache"],
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
    pool = sorted(
        (rec for rec in in_window
         if len(rec["req"].prompt) + len(rec["out"].output_ids) <= chk["max_tokens"]),
        key=lambda rec: rec["req"].rid,
    )[: chk["n_requests"]]
    samples = [{
        "tokens": rec["req"].prompt + list(rec["out"].output_ids),
        "start": len(rec["req"].prompt),
        "logprobs": rec["out"].output_logprobs,
    } for rec in pool]
    params = engine.params
    engine.state = None             # the pool's memory, for the reference
    del engine
    t_check = time.perf_counter()
    check = _check(params, arch, cfg.dtype, samples, chk)
    check["check_s"] = time.perf_counter() - t_check
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
            "cache_layers": loop_flops.cache_layers(arch),
            # the kernels engaged at the cache's layer count: one fused row
            # a (slot, step), one pool tile a (cache layer, slot, step)
            "fused_rows": grew("fused_rows"),
            "kv_write_tiles": grew("kv_write_tiles"),
            "layer_passes": grew("layer_passes"),
            "loop_passes": grew("loop_passes"),
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
