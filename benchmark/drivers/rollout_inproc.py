"""Closed-loop rollout against an in-process ``GenerationEngine``.

The loop is the gen server's (``areal_tpu/gen/server.py`` ``_run``):
``engine.step(decode_steps)``, resolve the finished outputs. The clients
are rollout workers: as many as the engine has slots, each submitting its
next request the moment its last one is done, so the slots stay full and
nothing queues. No server, no checkpoint, no child process.

Set-up: weights from the seed (one jitted call), the engine, a warm-up of
every admission bucket and both table widths, then the population the
window opens on (every slot part-way through an output), then a few
chunks of the loop itself. The window: ``--seconds`` of the loop. After
it: stop the engine, free its pool, compare served log-probs with the
plain reference.

Tokens are counted exactly: what the requests completed in the window
generated, plus what the requests still running at its end had generated,
minus what the requests running at its start had generated before it.
"""

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import correct, flops, sut, traffic_gen, weights
from benchmark.resident import ChunkResident
from benchmark.stats import percentile


def _warm_admission(engine, temperature: float, vocab: int, decode_steps: int):
    """Every program the window can need, through the public API only:
    each admission bucket as cold prompts of three prefill chunks (first
    chunk skips the pool, later ones read it), then the same prompts again
    as prefix hits, then one prompt long enough for the wider page table.
    Shapes depend on bucket, table width and chunk index, never on prompt
    length, so this is the whole set."""
    from areal_tpu.gen.engine import GenRequest

    rng = np.random.default_rng(0)
    n_tok = 2 * engine.admit_chunk + engine.page + 2
    k = 0
    for bucket in engine.admit_buckets:
        prompts = [rng.integers(1, vocab, n_tok).tolist() for _ in range(bucket)]
        for _ in range(2):      # cold, then as prefix-cache hits
            for p in prompts:
                engine.submit(GenRequest(
                    rid=f"warm-{k}", input_ids=p, max_new_tokens=2,
                    temperature=temperature))
                k += 1
            engine.run_until_done(decode_steps=decode_steps)
    wide = min(engine.S - decode_steps - 2, 33 * engine.page)
    if wide > 32 * engine.page:     # tables wider than the 32-page floor
        engine.submit(GenRequest(
            rid="warm-wide", input_ids=rng.integers(1, vocab, wide).tolist(),
            max_new_tokens=2, temperature=temperature))
        engine.run_until_done(decode_steps=decode_steps)


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
    kv_tok = flops.kv_bytes_per_token(arch, jnp.dtype(cfg.dtype).itemsize)
    n_pages = int(eng_opts["kv_pool_bytes"] // (kv_tok * page))
    engine = GenerationEngine(
        cfg, params, max_slots=clients, max_seqlen=max_seqlen,
        max_new_tokens_cap=out_hi, page_size=page, n_pages=n_pages,
        enable_prefix_cache=eng_opts["enable_prefix_cache"],
        seed=bench.seed % (2**31 - 1),
    )
    decode_steps = eng_opts["decode_steps"]
    bench.facts.update(kv_bytes_per_token=kv_tok, decode_steps=decode_steps)

    bench.mark("engine")
    _warm_admission(engine, mix["temperature"], cfg.vocab_size, decode_steps)
    bench.mark("warm_admission")

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
    bench.window_open()
    while bench.window_due():
        one_step()
    jax.block_until_ready(engine.state.lens)
    bench.window_close()
    jit1 = engine.n_jit_entries()
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
    check = correct.check_logprobs(params, arch, cfg.dtype, samples)
    check["jit_entries_added_in_window"] = jit1 - jit0
    if jit1 != jit0:
        check["correct"] = False
        check["reason"] = "the engine specialised a program inside the window"
    if len(norm_ms) < 20:
        check["correct"] = False
        check["reason"] = f"only {len(norm_ms)} requests ran inside the window"

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
            "prefill_tokens": bench.counters["prefill_tokens"],
            "prefix_hit_tokens": bench.counters["prefix_hit_tokens"],
        },
    }


