"""Closed-loop rollout against an in-process ``GenerationEngine`` whose
model is a DECODER-HYBRID-DECODER (family ``phi4flash``): Mamba-1 layers
with a per-slot recurrent state beside window layers and ONE full layer
over one page pool (a page table a cache layer), seven cross-attention
layers that read the full layer's pages with their own queries, gated
memory units fed by the last Mamba layer, differential attention.

``rollout_state_inproc.py`` (the per-slot state, its snapshots, the
state's own comparison) and ``rollout_hybrid_inproc.py`` (window pages
released while a request runs, the long group past the window) in one;
set-up, window and the exact token count are those drivers' line for line
(the p90 of the normalised latency is computed and NOT reported by the
cell: 17 requests of a 40 s window start and end inside it), and
``_warm_admission``, ``_warm_wider_tables``, ``_judge``, ``_control``,
``_pad_of``, ``_spans_under``, ``_peak_bytes``, ``_probe_state``,
``_sample`` and ``_VERDICT_KEYS`` are imported from them, not copied. What
differs:

- a page of the pool is ``page`` positions of ONE cache layer
  (``benchmark/yoco_flops.py``);
- the seeded weights: ``benchmark/weights.py``'s normal(0, 0.02) would
  make every channel forget in two tokens, ``lambda`` equal its constant
  and the pair's norm gain 0.02. ``_seeded_init`` overwrites ``A_log``
  (``log(1..N)`` a channel, the published initialisation), ``dt_bias``,
  ``D``, the convolution, the four ``lambda`` vectors (normal(0, 0.1) x 4)
  and the pair norm's gain (1 + normal(0, 0.1)) from ``--seed``, the first
  ``_STATE_TILE`` channels of the first Mamba layer at the slow end, in
  the ONE tree that the program and the reference both read (the
  configuration file's ``assumed.seeded_weights``);
- what is checked: ``check.n_requests`` requests of at most
  ``check.max_tokens``, prefix hits (seeded from a snapshot) first, and
  ``check.n_long`` COMPLETED requests of ``long_min_tokens`` to
  ``long_max_tokens`` positions, the most PREFILLED first (those of the
  opening population: admitted in chunks past the window, then decoded
  with the window's edge inside the prompt, so every compared position
  lies past the window). The two groups are judged apart, each by
  ``_judge``;
- four controls, each the reference in the program's place through the
  same comparison, each of which has to come out NOT correct in every run
  or the run is not: the reference in ``check.control_dtype``; with
  ``lambda`` = 0 in every layer (plain attention in the difference's
  place); with the recurrent and convolution state DROPPED at the
  prompt's page-aligned boundary (a prefix hit seeded from nothing), all
  three on the short group; with every window layer FULL (a program that
  forgot the window or read pages it had given back) on the long group. A
  control named in ``check.controls_reported`` is reported and not judged
  (PERF.md says why);
- the recurrent STATE of one running request is compared with the
  reference's (``_state_check``), ``_STATE_TILE`` channels at a time, in the
  FIRST Mamba layer, under ``check.state_rel_diff_limit``; the reference with
  its state rounded to ``check.control_state_dtype`` has to come out over
  that limit;
- the last act of set-up is ``gc.collect()`` + ``gc.freeze()``, as in
  ``rollout_state_inproc``;
- what a COLD run costs outside the window (the driver's check stops a
  run at 360 s, and its first run of a cell finds no compiled program):
  the weights are drawn a top-level stack a program, side by side
  (``_make_weights``), the engine takes the traffic file's
  ``engine.admit_buckets`` (two buckets: half the admission programs of
  the default four), and the reference runs every checked sequence at ONE
  padded length (``check.long_max_tokens``) in programs built side by
  side before the first comparison (``build_ahead``);
- under ``--rehearse`` the generic tiny preset leaves two layers:
  ``_rehearsal_arch`` sets a depth that keeps the three segments and small
  state-space sizes.

This is the SEVENTH rollout driver: the next ``benchmark`` issue should
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

from benchmark import correct, sut, traffic_gen, weights, yoco_flops
from benchmark.drivers.rollout_hybrid_inproc import _sample
from benchmark.drivers.rollout_inproc import _warm_admission
from benchmark.drivers.rollout_latent_inproc import (
    _control, _judge, _pad_of, _peak_bytes, _spans_under, _warm_wider_tables)
from benchmark.drivers.rollout_looped_inproc import _VERDICT_KEYS
from benchmark.drivers.rollout_state_inproc import _probe_state
from benchmark.resident import ChunkResident
from benchmark.stats import percentile


# channels a tile of the state's comparison; the first tile of the first
# Mamba layer is seeded WHOLE at the slow end: one slow channel among 127
# others moves its tile's norm by the chance of one random walk (the
# reference rounded to bfloat16 read 0.022-0.154 of a tile's norm over 24
# runs, once under the limit of 0.03), a tile of them reads its mean
_STATE_TILE = 128


def _rehearsal_arch(arch: dict) -> dict:
    """A depth that keeps the three segments, a window a prompt passes, and
    small state-space sizes."""
    return dict(
        arch, num_hidden_layers=8, sliding_window=16, mamba_d_state=4,
        mamba_dt_rank=4, max_position_embeddings=512)


_SEEDED = {"ssm": ("A_log", "dt_bias", "D", "conv_w", "conv_b"),
           "attn": ("lam_q1", "lam_k1", "lam_q2", "lam_k2", "subln")}


@jax.jit
def _seeded_leaves(key, mixer, attns):
    """``_seeded_init``'s leaves (``_SEEDED``) from ``key``, given the ones
    they replace (for their shapes and dtypes). ONE program: leaf by leaf
    these were forty small ones, 14 s of a cold start."""
    ks = iter(jax.random.split(key, 16))

    def like(ref, x):
        return x.astype(ref.dtype)

    Ls, N, C = mixer["A_log"].shape
    dt = jnp.exp(jax.random.uniform(
        next(ks), (Ls, C), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    a = jnp.broadcast_to(
        jnp.arange(1, N + 1, dtype=jnp.float32)[None, :, None], (Ls, N, C))
    # the first tile of the first layer at the slow end: the channels a
    # state kept in 16 bits loses most of, in the layer ``_state_check``
    # compares
    dt = dt.at[0, :_STATE_TILE].set(1e-3)
    a = a.at[0, :, :_STATE_TILE].set(1.0)
    out = {
        "A_log": like(mixer["A_log"], jnp.log(a)),
        "dt_bias": like(mixer["dt_bias"], dt + jnp.log(-jnp.expm1(-dt))),
        "D": like(mixer["D"], 1.0 + 0.1 * jax.random.normal(
            next(ks), (Ls, C), jnp.float32)),
    }
    for name in ("conv_w", "conv_b"):
        if name in mixer:
            out[name] = like(mixer[name], jax.random.uniform(
                next(ks), mixer[name].shape, jnp.float32, -0.5, 0.5))
    pairs = {}
    for tree in ("layers", "cross_layers"):     # (a jit hands a dict sorted)
        attn = attns[tree]
        pairs[tree] = {
            name: like(attn[name], 0.4 * jax.random.normal(
                next(ks), attn[name].shape, jnp.float32))
            for name in _SEEDED["attn"][:-1]}
        pairs[tree]["subln"] = like(
            attn["subln"], 1.0 + 0.1 * jax.random.normal(
                next(ks), attn["subln"].shape, jnp.float32))
    return out, pairs


def _seeded_init(params, seed: int):
    """What normal(0, 0.02) would make degenerate (module docstring), from
    ``seed``."""
    def some(tree, names):
        return {k: tree[k] for k in names if k in tree}

    mixer = params["ssm_layers"]["ssm"]
    attns = {tree: params[tree]["attn"] for tree in ("layers", "cross_layers")}
    new_mixer, pairs = _seeded_leaves(
        jax.random.fold_in(weights.fold_seed(seed), 0x5A3),
        some(mixer, _SEEDED["ssm"]),
        {t: some(a, _SEEDED["attn"]) for t, a in attns.items()})
    out = {**params, "ssm_layers": {
        **params["ssm_layers"], "ssm": {**mixer, **new_mixer}}}
    for tree, new in pairs.items():
        out[tree] = {**params[tree], "attn": {**attns[tree], **new}}
    return out


def _make_weights(shapes, seed: int, dtype):
    """``weights.make_weights`` a top-level stack of the tree, all at once
    on threads: the one program over this model's 65 leaves takes the
    chip's compiler 30-46 s of a cold start, a stack's takes its share of
    that and they are built side by side. Each stack draws from a key of
    its own (``seed`` with the stack's number above the bits ``--seed``
    uses)."""
    import concurrent.futures

    names = sorted(shapes)
    with concurrent.futures.ThreadPoolExecutor(len(names)) as pool:
        # (a stack under its own name: what a leaf is drawn as follows
        # from the names on its path, ``final_ln``'s too)
        made = [pool.submit(weights.make_weights, {name: shapes[name]},
                            seed + ((k + 1) << 40), dtype)
                for k, name in enumerate(names)]
        return {name: m.result()[name] for name, m in zip(names, made)}


def _memoised(ref, length: int):
    """The reference's ``next_token_logprobs`` with its results kept (the
    verdicts and the controls each ask for the float32 and the
    served-dtype pass of every sample) and every sequence padded to
    ``length``, whatever the caller's own padding: the reference's
    programs are then the ones ``build_ahead`` built, on every seed (a
    length of its own a group of samples was fifteen programs more a
    group, 170-290 s of a run whose samples fell otherwise than the last
    run's: PERF.md section 6)."""
    plain, kept = ref.next_token_logprobs, {}

    def cached(params, arch, tokens, dtype, pad_to):
        assert len(tokens) <= length, (len(tokens), length)
        key = (tuple(tokens), str(dtype), tuple(sorted(
            (k, str(v)) for k, v in arch.items() if k.startswith("control_"))))
        if key not in kept:
            kept[key] = plain(params, arch, tokens, dtype, length)
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


def _check(params, arch: dict, served_dtype: str, short, long_, chk: dict,
           page: int) -> Dict:
    """The verdict on both groups and the four controls (module
    docstring)."""
    ref = correct.reference_module(arch["reference"])
    # ONE padded length for every sequence the check reads: the longest a
    # checked request (or the state's probe) may have
    ref.build_ahead(
        params, arch, ("float32", served_dtype, chk["control_dtype"]),
        chk["long_max_tokens"], state_dtype=chk["control_state_dtype"])
    plain, ref.next_token_logprobs = _memoised(ref, chk["long_max_tokens"])
    try:
        check = _judge(params, arch, served_dtype, short, chk)
        check["n_long_sequences"] = len(long_)
        if not short or "max_abs_diff_nats" not in check:
            return check
        if len(long_) < chk["n_long"]:
            check["correct"] = False
            check["reason"] = (
                f"{len(long_)} sequences of {chk['long_min_tokens']}-"
                f"{chk['long_max_tokens']} positions completed, "
                f"{chk['n_long']} wanted")
            return check
        check["long"] = _judge(params, arch, served_dtype, long_, chk)
        if check["correct"] and not check["long"]["correct"]:
            check["correct"] = False
            check["reason"] = "long sequences: " + str(
                check["long"].get("reason"))
        controls = {
            "control": (
                lambda: _control(params, arch, served_dtype, short, chk),
                f"the reference computed in {chk['control_dtype']}: it "
                "cannot tell a lower precision"),
            "control_lambda_zero": (
                lambda: _stand_in(
                    params, arch, served_dtype, short, chk,
                    lambda s: dict(arch, control_lambda_zero=True)),
                "the reference with lambda = 0 in every layer: it cannot "
                "tell plain attention from the difference"),
            # the state of every Mamba layer dropped where the prompt's
            # page sharing ends (its prefilled positions, len - 1, in
            # whole pages)
            "control_lost_snapshot": (
                lambda: _stand_in(
                    params, arch, served_dtype, short, chk,
                    lambda s: dict(
                        arch,
                        control_zero_state_at=(s["start"] - 1) // page * page)),
                "the reference with the recurrent state dropped at the "
                "prompt's page boundary: it cannot tell a lost snapshot"),
            "control_full_attention": (
                lambda: _stand_in(
                    params, arch, served_dtype, long_, chk,
                    lambda s: dict(arch, control_no_window=True)),
                "the reference with every window layer full: it cannot "
                "tell a program that forgot the window"),
        }
        reported = set(chk.get("controls_reported", ()))
        for name, (run, cannot) in controls.items():
            check[name] = run()
            check[name]["judged"] = name not in reported
            if check[name]["correct"] and name not in reported:
                check["correct"] = False
                check["reason"] = "the comparison passes " + cannot
        return check
    finally:
        ref.next_token_logprobs = plain


def _state_check(params, arch: dict, probe, chk: dict) -> Dict:
    """The program's recurrent state after ``probe``'s tokens against the
    float32 reference's, ``_STATE_TILE`` channels at a time (the norm of the
    difference over the norm of the reference's tile), in the FIRST Mamba
    layer: its inputs are one matmul from the embedding, where every later
    layer's carry the residual stream's rounding in the serving dtype.
    Beside it the reference with its state rounded to
    ``check.control_state_dtype`` after every token, which has to come out
    over the limit."""
    ref = correct.reference_module(arch["reference"])
    tokens, got = probe
    pad = chk["long_max_tokens"]      # the length ``_check`` built ahead

    def first_layer(arch):
        return ref.recurrent_state(
            params, arch, tokens, "float32", pad, n_layers=1)[0]

    want = first_layer(arch)                              # [C, N]
    rounded = first_layer(
        dict(arch, control_state_dtype=chk["control_state_dtype"]))
    tile = min(_STATE_TILE, want.shape[0])

    def tiles(a):
        return a.reshape(-1, tile, a.shape[-1])

    def worst_tile(a):
        d, w = tiles(a.reshape(want.shape) - want), tiles(want)
        return float((np.sqrt((d ** 2).sum((-2, -1)))
                      / np.sqrt((w ** 2).sum((-2, -1)))).max())

    return {
        "after_tokens": len(tokens),
        "worst_tile_rel_diff": worst_tile(got[0]),
        "control_state_rounded_rel_diff": worst_tile(rounded),
        "rel_diff_limit": chk["state_rel_diff_limit"],
    }


def run(bench) -> Dict:
    from areal_tpu.gen.engine import GenerationEngine, GenRequest

    arch, mix = bench.arch, bench.mix
    if bench.rehearse:
        arch = bench.arch = _rehearsal_arch(arch)
    eng_opts = mix["engine"]
    cfg = sut.model_config(arch, mix.get("model_overrides", {}))
    params = _seeded_init(
        _make_weights(
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
    # what a page of the pool is: ``page`` positions of one cache layer
    page_bytes = yoco_flops.page_bytes(arch, page, itemsize)
    n_pages = int(eng_opts["kv_pool_bytes"] // page_bytes)
    engine = GenerationEngine(
        cfg, params, max_slots=clients, max_seqlen=max_seqlen,
        max_new_tokens_cap=out_hi, page_size=page, n_pages=n_pages,
        enable_prefix_cache=eng_opts["enable_prefix_cache"],
        state_snapshots=eng_opts["state_snapshots"],
        admit_buckets=eng_opts["admit_buckets"],
        seed=bench.seed % (2**31 - 1),
    )
    decode_steps = eng_opts["decode_steps"]
    pool_bytes_stored = engine.kv_pool_bytes()
    by_kind = yoco_flops.kv_bytes_per_token_by_kind(arch, itemsize)
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

    # everything before here is set-up (``rollout_state_inproc`` says why
    # what it left alive is frozen)
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
    probe = _probe_state(engine, live, chk)
    engine.pause()                  # harvests every running slot

    # ---- counts ------------------------------------------------------ #
    finished = done[n_done0:]
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
    def n_positions(rec):
        return len(rec["req"].prompt) + len(rec["out"].output_ids)

    by_rid = lambda rec: rec["req"].rid     # noqa: E731
    fits = sorted(
        (rec for rec in in_window if n_positions(rec) <= chk["max_tokens"]),
        key=by_rid)
    # prefix hits (seeded from a snapshot) first, one cold prompt at least
    hits = [rec for rec in fits if rec["out"].prefix_hit_tokens > 0]
    cold = [rec for rec in fits if rec["out"].prefix_hit_tokens == 0]
    n = chk["n_requests"]
    short = (hits[: max(n - 1, 1)] + cold)[:n]
    # of the long ones, those with the most positions PREFILLED: every
    # log-prob of theirs that is compared then lies past the window's edge
    long_ = sorted(
        (rec for rec in finished
         if chk["long_min_tokens"] <= n_positions(rec) <= chk["long_max_tokens"]
         and len(rec["req"].prompt) > arch["sliding_window"]
         and len(rec["out"].output_ids) == rec["req"].max_new_tokens),
        key=lambda rec: (-len(rec["req"].prompt), by_rid(rec)))[: chk["n_long"]]
    n_hits = sum(rec["out"].prefix_hit_tokens > 0 for rec in short)
    params = engine.params
    engine.state = None             # the pool's and the state's memory
    del engine
    t_check = time.perf_counter()
    check = _check(params, arch, cfg.dtype, [_sample(r) for r in short],
                   [_sample(r) for r in long_], chk, page)
    state = check["state"] = (
        _state_check(params, arch, probe, chk) if probe else None)
    check["check_s"] = time.perf_counter() - t_check
    check["checked_prefix_hits"] = n_hits
    check["checked_lengths"] = [
        [len(r["req"].prompt), len(r["out"].output_ids)] for r in short + long_]
    check["jit_entries_added_in_window"] = jit1 - jit0
    check["programs_specialised_in_window"] = sorted(
        k for k, n in programs1.items() if n != programs0.get(k, 0))
    if jit1 != jit0:
        check["correct"] = False
        check["reason"] = "the engine specialised a program inside the window"
    # (no floor on the requests that ran inside the window: at this cell's
    # ~23 tokens a second a slot a median output of 1,024 takes longer than
    # the window, so the p90 of the normalised latency has no population
    # here and the cell does not report it)
    if n_hits < 1:
        check["correct"] = False
        check["reason"] = "no checked request was a prefix hit"
    if state is None:
        check["correct"] = False
        check["reason"] = "no running request's recurrent state was compared"
    elif state["worst_tile_rel_diff"] > state["rel_diff_limit"]:
        check["correct"] = False
        check["reason"] = (
            f"the recurrent state is {state['worst_tile_rel_diff']:.4f} of a "
            f"tile's norm from the reference's after {state['after_tokens']} "
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
            "n_pages": n_pages, "kv_pool_bytes": n_pages * page_bytes,
            "kv_pool_bytes_stored": pool_bytes_stored,
            "kv_bytes_per_token_by_kind": by_kind,
            "shared_kv_readers": yoco_flops.shared_kv_readers(arch),
            "state_bytes_per_slot": yoco_flops.state_bytes_per_slot(
                arch, itemsize),
            "state_snapshot_entries": eng_opts["state_snapshots"],
            "admitted": grew("admitted"),
            "state_slots": grew("state_slots"),
            "state_snapshots_taken": grew("state_snapshots_taken"),
            "state_snapshot_hits": grew("state_snapshot_hits"),
            "state_snapshot_bytes": grew("state_snapshot_bytes"),
            "state_snapshot_evictions": grew("state_snapshot_evictions"),
            "window_pages_released": grew("window_pages_released"),
            "slots_held": grew("slots_held"),
            "preemptions": grew("preemptions"),
            "kv_write_tiles": grew("kv_write_tiles"),
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
