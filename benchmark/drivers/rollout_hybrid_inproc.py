"""Closed-loop rollout against an in-process ``GenerationEngine`` whose
model mixes WINDOW and FULL attention layers in one stack: one page pool,
a page table a position of the layers' period, the window positions' pages
given back behind the window while their request still runs.

``rollout_latent_inproc.py`` with two differences; set-up, window, the
exact token count and the p90's population are that driver's (and
``rollout_inproc``'s) line for line, and ``_warm_admission``,
``_warm_wider_tables``, ``_judge``, ``_control``, ``_spans_under`` and
``_peak_bytes`` are imported from them, not copied:

- the pages of the pool come from ``benchmark/hybrid_flops.py`` (a page
  holds ``page`` tokens of one position of the period in every period:
  ``periods x 2 x Hkv x page x D`` bytes whatever kind it serves), not from
  ``flops.kv_bytes_per_token``, which reads one kind of cache;
- the check's population. Requests submitted and completed inside 40 s
  never pass about 3,000 positions, so the other drivers' rule would judge
  this cell without ever reading a page behind a window. Here the check
  takes ``check.n_requests - check.n_long`` short in-window requests as
  before, plus ``check.n_long`` requests that COMPLETED in the window with
  ``long_min_tokens`` to ``long_max_tokens`` positions, the most
  PREFILLED first (those of the opening population: prefilled in chunks
  past the window, admission's release path, then decoded with the
  window's edge inside the prompt, decode's release path, so that every
  compared position lies past the window). A run with fewer long sequences is not
  ``correct``. The two groups are judged apart (the reference is compiled
  at one padded length a group: the short ones do not pay for 6,144
  positions), each by ``_judge``: ``benchmark/correct.py``'s rule on the
  largest difference and the limit on each sequence's mean.

Two controls, in every run, both of which have to come out NOT correct or
the run is not: the reference computed in ``check.control_dtype`` in the
program's place (``_control``, on the short group: what a path in a lower
precision would hand in; on the long group too it would cost thirty more
reference passes of 6,000 positions), and the reference with EVERY LAYER FULL in
the program's place on the long sequences: what a program that forgot the
window, or read pages it had given back as if they were still its own,
would hand in.

The next ``benchmark`` issue should fold the three rollout drivers into
one (PERF.md, section 7).

Tokens are counted exactly: what the requests completed in the window
generated, plus what the requests still running at its end had generated,
minus what the requests running at its start had generated before it.
"""

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import correct, hybrid_flops, sut, traffic_gen, weights
from benchmark.drivers.rollout_inproc import _warm_admission
from benchmark.drivers.rollout_latent_inproc import (
    _control, _judge, _pad_of, _peak_bytes, _spans_under, _warm_wider_tables)
from benchmark.resident import ChunkResident
from benchmark.stats import percentile


def _control_full(params, arch: dict, served_dtype: str, samples, chk) -> Dict:
    """The reference with every layer FULL (no window) in the program's
    place, through ``_judge``."""
    ref = correct.reference_module(arch["reference"])
    pad = _pad_of(samples)
    stand_ins = []
    for s in samples:
        full, _ = ref.next_token_logprobs(
            params, arch, s["tokens"], "float32", pad, window=None)
        stand_ins.append(dict(s, logprobs=full[s["start"] - 1:]))
    verdict = _judge(params, arch, served_dtype, stand_ins, chk)
    return {k: verdict.get(k) for k in (
        "correct", "reason", "max_abs_diff_nats", "tolerance_nats",
        "mean_abs_diff_nats", "seq_mean_abs_diff_nats")}


def _sample(rec) -> Dict:
    return {
        "tokens": rec["req"].prompt + list(rec["out"].output_ids),
        "start": len(rec["req"].prompt),
        "logprobs": rec["out"].output_logprobs,
    }


def _check(params, arch, served_dtype, short, long_, chk) -> Dict:
    """The verdict on both groups and both controls (module docstring)."""
    check = _judge(params, arch, served_dtype, short, chk)
    check["n_long_sequences"] = len(long_)
    if len(long_) < chk["n_long"]:
        check["correct"] = False
        check["reason"] = (
            f"{len(long_)} sequences of {chk['long_min_tokens']}-"
            f"{chk['long_max_tokens']} positions completed in the window, "
            f"{chk['n_long']} wanted")
        return check
    check["long"] = _judge(params, arch, served_dtype, long_, chk)
    if check["correct"] and not check["long"]["correct"]:
        check["correct"] = False
        check["reason"] = "long sequences: " + str(check["long"].get("reason"))
    check["control"] = _control(params, arch, served_dtype, short, chk)
    if check["control"]["correct"]:
        check["correct"] = False
        check["reason"] = (
            f"the comparison passes the reference computed in "
            f"{chk['control_dtype']}: it cannot tell a lower precision")
    check["control_full_attention"] = _control_full(
        params, arch, served_dtype, long_, chk)
    if check["control_full_attention"]["correct"]:
        check["correct"] = False
        check["reason"] = (
            "the comparison passes the reference with every layer full: "
            "it cannot tell a program that forgot the window")
    return check


def run(bench) -> Dict:
    from areal_tpu.gen.engine import GenerationEngine, GenRequest

    arch, mix = bench.arch, bench.mix
    eng_opts = mix["engine"]
    cfg = sut.model_config(arch, mix.get("model_overrides", {}))
    if mix.get("model_overrides", {}).get("layer_pattern"):
        # a rehearsal's small window: the reference reads it from the
        # configuration's own key
        arch = dict(arch, sliding_window_size=next(
            w for w, _ in cfg.layer_kinds if w is not None))
    params = weights.make_weights(
        sut.weight_shapes(cfg, cfg.dtype), bench.seed, jnp.dtype(cfg.dtype))

    bench.mark("weights")
    stream = traffic_gen.RequestStream(mix, bench.seed, cfg.vocab_size)
    clients = mix["clients"]
    page = eng_opts["page_size"]
    out_hi = mix["output_len"]["hi"]
    max_seqlen = mix["prompt_len"]["hi"] + out_hi
    itemsize = jnp.dtype(cfg.dtype).itemsize
    # one difference from the other drivers: what a page of the pool is
    page_bytes = hybrid_flops.page_bytes(arch, page, itemsize)
    n_pages = int(eng_opts["kv_pool_bytes"] // page_bytes)
    engine = GenerationEngine(
        cfg, params, max_slots=clients, max_seqlen=max_seqlen,
        max_new_tokens_cap=out_hi, page_size=page, n_pages=n_pages,
        enable_prefix_cache=eng_opts["enable_prefix_cache"],
        seed=bench.seed % (2**31 - 1),
    )
    decode_steps = eng_opts["decode_steps"]
    pool_bytes_stored = engine.kv_pool_bytes()
    by_kind = hybrid_flops.kv_bytes_per_token_by_kind(arch, itemsize)
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
    bench.counters.update(
        prefix_hit_tokens=stats1["prefix_hit_tokens"] - stats0["prefix_hit_tokens"],
        prefill_tokens=stats1["prefill_tokens"] - stats0["prefill_tokens"],
        window_pages_released=(
            stats1["window_pages_released"] - stats0["window_pages_released"]),
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

    by_rid = lambda rec: rec["req"].rid     # noqa: E731
    short = sorted(
        (rec for rec in in_window if n_positions(rec) <= chk["max_tokens"]),
        key=by_rid)[: chk["n_requests"] - chk["n_long"]]
    # of the long ones, those with the most positions PREFILLED: every
    # log-prob of theirs that is compared then lies past the window's edge
    # (one that decoded its way from 1,400 to 4,400 positions has nine
    # tenths of its compared positions inside the first window, where a
    # program that forgot the window is right)
    long_ = sorted(
        (rec for rec in finished
         if chk["long_min_tokens"] <= n_positions(rec) <= chk["long_max_tokens"]
         and len(rec["out"].output_ids) == rec["req"].max_new_tokens),
        key=lambda rec: (-len(rec["req"].prompt), by_rid(rec)))[: chk["n_long"]]
    params = engine.params
    engine.state = None             # the pool's memory, for the reference
    del engine
    t_check = time.perf_counter()
    check = _check(params, arch, cfg.dtype, [_sample(r) for r in short],
                   [_sample(r) for r in long_], chk)
    check["check_s"] = time.perf_counter() - t_check
    check["long_positions"] = [n_positions(r) for r in long_]
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
            "completed_past_the_window_s_edge": sum(
                n_positions(rec) > arch["sliding_window_size"] + 256
                for rec in finished),
            "norm_latency_ms_median": (
                percentile(norm_ms, 50) if norm_ms else None),
            "norm_latency_ms_p90": end_to_end["rollout_norm_latency_p90_ms"],
            "tokens_in_window": tokens, "chunks": len(resident),
            "mean_resident_tokens": float(np.mean(resident)) if resident else 0,
            "mean_running": float(np.mean(bench.samples["n_running"])),
            "n_pages": n_pages, "kv_pool_bytes": n_pages * page_bytes,
            "kv_bytes_per_token_by_kind": by_kind,
            "window_pages_released": bench.counters["window_pages_released"],
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
