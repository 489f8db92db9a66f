"""Closed-loop rollout against an in-process ``GenerationEngine`` whose
page pool holds LATENTS (latent attention: one ``kv_lora_rank +
qk_rope_head_dim`` row a token a layer, key and value at once).

``rollout_inproc.py`` with one difference: the bytes a token takes of the
pool come from ``benchmark/mla_flops.py`` (``L x (kv_lora_rank +
qk_rope_head_dim) x itemsize``), not from ``flops.kv_bytes_per_token``,
which reads ``2 x L x Hkv x D``: 7.1 x the truth for JoyAI-LLM-Flash, so
the pool would get a seventh of its pages and a roofline on it would read
over 100 %. Set-up, window, the exact token count, the p90's population
and the check are that driver's, line for line (``_warm_admission`` is
imported from it); ``benchmark.flops`` is not patched. Three additions:

- the engine's page tables come in widths of 32, 64, ... pages up to the
  slot's own (72 here) and ``_warm_admission`` admits its buckets at the
  first of them only; ``_warm_wider_tables`` reaches every bucket at every
  wider one, so that the window specialises nothing when a generation
  passes 8,192 positions or a long opening prompt is admitted late;
- a second limit beside ``benchmark/correct.py``'s rule. That rule bounds
  the LARGEST difference by twice what bf16 costs the reference, and with
  256 sigmoid-routed experts both are set by the few positions where
  rounding flips a member of the top 8 (0.6-0.8 nats, level with what a
  wrong context costs). The MEAN difference of a sequence is not: each
  checked sequence's has to stay under
  ``check.seq_mean_abs_diff_limit_nats``, which lies between the largest
  the program gave over its seeds and the least the reference gives
  computed in ``check.control_dtype``, the nearest precision below the
  configuration's (both readings: PERF.md, section 6);
- the control, in every run: the reference computed in that lower
  precision takes the program's place and goes through the same
  comparison. It has to come out NOT correct, or the run is not. (A
  reference pass is 0.1-0.3 s at these lengths once compiled; one more
  dtype is one more compile, about 9 s.)

The next ``benchmark`` issue should fold the two drivers into one
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

from benchmark import correct, mla_flops, sut, traffic_gen, weights
from benchmark.drivers.rollout_inproc import _warm_admission
from benchmark.resident import ChunkResident
from benchmark.stats import percentile


def _peak_bytes() -> int:
    stats = jax.local_devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _warm_wider_tables(engine, temperature: float, vocab: int,
                       decode_steps: int):
    """Every admission bucket at every page-table width past the first,
    and the decode chunk at each: ``_warm_admission`` admits its buckets
    at the narrowest width only and one row at the second. The opening
    population's prompts carry the part of the output that is already
    done, up to the whole context, and where the pool cannot seat all of
    it during set-up its tail is admitted inside the window, in whatever
    bucket it then falls (my chip run, PR 30, at a pool of 3.6e9:
    ``extend(2, 64)`` and ``extend(4, 64)`` were specialised there).

    A program is keyed by (rows, width, whether the pool is skipped),
    never by the tokens it prefills, so each wider width costs ONE cold
    prompt that fills the width before it (its decode chunk runs at the
    wider one), and then every bucket as that many prefix hits on it: each
    extends a tail of one token through a table of the wider width: 12 k
    tokens of prefill for ten programs, 13 s of set-up (my chip runs,
    PR 30), nearly all of it loading the programs."""
    from areal_tpu.gen.engine import GenRequest

    rng = np.random.default_rng(1)
    widths = engine.table_widths()
    k = 0

    def admit(prompts):
        nonlocal k
        for p in prompts:
            engine.submit(GenRequest(
                rid=f"warm-table-{k}", input_ids=p, max_new_tokens=2,
                temperature=temperature))
            k += 1
        engine.run_until_done(decode_steps=decode_steps)

    for prev in widths[:-1]:
        # ``prev`` whole pages are prefilled and shared (a prompt's last
        # token is decoded, not prefilled), one position lies past them
        shared = rng.integers(1, vocab, prev * engine.page).tolist()
        admit([shared + [1, 1]])
        for bucket in engine.admit_buckets:
            admit([shared + [2 + j, 1] for j in range(bucket)])


def _pad_of(samples) -> int:
    """``correct.check_logprobs``'s padding: the reference runs (and is
    compiled) at one length for all the sequences."""
    return -(-max(len(s["tokens"]) for s in samples) // 256) * 256


def _judge(params, arch: dict, served_dtype: str, samples, chk: dict) -> Dict:
    """``benchmark/correct.py``'s verdict on ``samples``, and then the
    second limit: the mean difference of each sequence alone, so that a
    fault in one slot is not thinned by the seven that are sound."""
    verdict = correct.check_logprobs(params, arch, served_dtype, samples)
    if "max_abs_diff_nats" not in verdict:
        return verdict              # no sample, or a malformed one
    ref = correct.reference_module(arch["reference"])
    pad = _pad_of(samples)
    means = []
    for s in samples:
        f32, _ = ref.next_token_logprobs(
            params, arch, s["tokens"], "float32", pad)
        got = np.asarray(s["logprobs"], np.float64)
        means.append(float(np.abs(got - f32[s["start"] - 1:]).mean()))
    limit = chk["seq_mean_abs_diff_limit_nats"]
    verdict.update(seq_mean_abs_diff_nats=means,
                   seq_mean_abs_diff_limit_nats=limit)
    if verdict["correct"] and max(means) > limit:
        verdict["correct"] = False
        verdict["reason"] = "a sequence's mean difference is over its limit"
    return verdict


def _control(params, arch: dict, served_dtype: str, samples, chk: dict) -> Dict:
    """The reference computed in ``check.control_dtype`` in the program's
    place, through ``_judge``: what a path that computes in a lower
    precision than the configuration states would hand in."""
    ref = correct.reference_module(arch["reference"])
    pad = _pad_of(samples)
    stand_ins = []
    for s in samples:
        low, _ = ref.next_token_logprobs(
            params, arch, s["tokens"], chk["control_dtype"], pad)
        stand_ins.append(dict(s, logprobs=low[s["start"] - 1:]))
    verdict = _judge(params, arch, served_dtype, stand_ins, chk)
    return {k: verdict.get(k) for k in (
        "correct", "reason", "max_abs_diff_nats", "tolerance_nats",
        "mean_abs_diff_nats", "seq_mean_abs_diff_nats")}


def _spans_under(t0: float, dur: float) -> Dict[str, float]:
    """Seconds of the program's spans that began inside ``[t0, t0 + dur]``,
    by name: where a stalled ``engine.step`` spent its time (the device:
    ``gen_engine/flag_wait``; the host: ``admit``, ``dispatch``,
    ``harvest``; neither: outside every span)."""
    from areal_tpu.base import tracing

    out: Dict[str, float] = {}
    for s in tracing.spans_since(t0, t0 + dur):
        out[s["name"]] = out.get(s["name"], 0.0) + s["dur_s"]
    return out


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
    # the one difference from rollout_inproc: what a token takes of the
    # pool. ``flops.kv_bytes_per_token`` reads 2 x L x Hkv x D, which a
    # latent cache does not hold
    kv_tok = mla_flops.latent_bytes_per_token(
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
    check = _judge(params, arch, cfg.dtype, samples, chk)
    if samples:
        check["control"] = _control(params, arch, cfg.dtype, samples, chk)
        if check["control"]["correct"]:
            check["correct"] = False
            check["reason"] = (
                f"the comparison passes the reference computed in "
                f"{chk['control_dtype']}: it cannot tell a lower precision")
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
            # a stalled step shows here and nowhere else in the line;
            # and which of the program's spans held its time
            "engine_step_s_longest": [d for _, d in steps[:3]],
            "engine_step_longest_spans_s": (
                _spans_under(*steps[0]) if steps else {}),
            # from ``submit`` to a slot and its pages: a request that
            # found no pages waits a whole chunk and is still pending
            # after the step
            "queue_wait_ms_max": max(waits_ms, default=None),
            "queue_wait_ms_p90": (
                percentile(waits_ms, 90) if waits_ms else None),
            "pending_after_step_max": int(
                max(bench.samples["n_pending"], default=0)),
            "engine_step_s_median": percentile(bench.spans("engine.step"), 50),
            # the device's peak so far, at the end of set-up and of the
            # window (the run's own figure also covers the check after it)
            "memory_peak_bytes_setup": peak_setup,
            "memory_peak_bytes_window": peak_window,
            # as the program stores it (a latent row is padded to whole
            # lane tiles): what the device really holds
            "kv_pool_bytes_stored": pool_bytes_stored,
            "cache_bytes_per_token_stored": pool_bytes_stored // (n_pages * page),
            "prefill_tokens": bench.counters["prefill_tokens"],
            "prefix_hit_tokens": bench.counters["prefix_hit_tokens"],
        },
    }


