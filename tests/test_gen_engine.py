"""Generation engine tests: greedy parity vs the packed forward, continuous
batching with slot turnover, stop tokens, interruption protocol.

Counterpart of the reference's generation tests (in-house engine +
``test_partial_rollout.py`` chunked regeneration semantics).
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import engine_contract
from areal_tpu.base import metrics as metrics_mod
from areal_tpu.gen.engine import GenerationEngine, GenRequest
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig

CFG = ModelConfig(
    n_layers=2, n_q_heads=4, n_kv_heads=2, head_dim=8, hidden_dim=32,
    intermediate_dim=64, vocab_size=128, dtype="float32",
)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.key(5))


def _greedy_reference(params, prompt, n_new):
    """Teacher-forcing argmax chain via the packed forward."""
    ids = list(prompt)
    for _ in range(n_new):
        T = len(ids)
        pad = ((T + 127) // 128) * 128
        seg = np.r_[np.ones(T, np.int32), np.zeros(pad - T, np.int32)]
        inp = np.r_[np.asarray(ids, np.int32), np.zeros(pad - T, np.int32)]
        pos = np.r_[np.arange(T, dtype=np.int32), np.zeros(pad - T, np.int32)]
        logits = tfm.forward_packed(
            params, CFG, jnp.asarray(inp), jnp.asarray(seg), jnp.asarray(pos),
            remat=False,
        )
        ids.append(int(np.argmax(np.asarray(logits)[T - 1])))
    return ids[len(prompt):]


def test_greedy_matches_forward(params, rng):
    eng = GenerationEngine(CFG, params, max_slots=2, max_seqlen=128)
    prompt = [int(x) for x in rng.integers(1, 128, size=5)]
    eng.submit(GenRequest(rid="a", input_ids=prompt, max_new_tokens=8, greedy=True))
    outs = eng.run_until_done(decode_steps=4)
    assert len(outs) == 1
    ref = _greedy_reference(params, prompt, 8)
    assert outs[0].output_ids == ref
    assert outs[0].finish_reason == "length"
    assert len(outs[0].output_logprobs) == 8


def _engine(params, **kw):
    return GenerationEngine(
        CFG, params, max_slots=4, max_seqlen=128, page_size=8, **kw)


@pytest.mark.parametrize("check", engine_contract.CHECKS)
def test_engine_contract(params, check):
    engine_contract.run(check, functools.partial(_engine, params), CFG, params)


class TestWarpContract:
    """The sampling layer's static-``warp`` split: engines that know no
    slot warps (host-side ``_warp_host``) skip the ``[B, V]`` sort — the
    dominant cost of a decode step at a 152k vocab — and the result must
    be EXACT either way, as is a single flattened sort over several
    positions a slot."""

    def test_warp_false_exactness(self, rng):
        from areal_tpu.gen.sampling import SamplingParams, sample_tokens

        B, V = 6, 64
        logits = jnp.asarray(rng.normal(size=(B, V)), jnp.float32)
        # no slot actually warps: top_p=1, top_k >= V, mixed temperatures
        sp = SamplingParams(
            temperature=jnp.asarray([1.0, 0.7, 1.3, 0.0, 1.0, 2.0]),
            top_p=jnp.ones((B,)),
            top_k=jnp.full((B,), 1 << 30, jnp.int32),
        )
        key = jax.random.key(3)
        t1, lp1 = sample_tokens(key, logits, sp, warp=True)
        t2, lp2 = sample_tokens(key, logits, sp, warp=False)
        assert t1.tolist() == t2.tolist()
        np.testing.assert_allclose(np.asarray(lp1), np.asarray(lp2),
                                   atol=1e-6)

    def test_warp_multi_matches_per_position(self, rng):
        """One flattened sort over [B*C, V] must
        equal warping each position independently."""
        from areal_tpu.gen.sampling import (
            SamplingParams, warp_logits, warp_logits_multi,
        )

        B, C, V = 4, 3, 64
        logits = jnp.asarray(rng.normal(size=(B, C, V)), jnp.float32)
        sp = SamplingParams(
            temperature=jnp.asarray([1.0, 0.5, 1.2, 0.9]),
            top_p=jnp.asarray([0.9, 1.0, 0.5, 0.8]),
            top_k=jnp.asarray([5, 1 << 30, 20, 3], jnp.int32),
        )
        got = warp_logits_multi(logits, sp)
        for c in range(C):
            np.testing.assert_allclose(
                np.asarray(got[:, c]),
                np.asarray(warp_logits(logits[:, c], sp)),
                atol=1e-6,
            )

    def test_warp_rows_matches_full_warp(self, rng):
        """Per-slot warp narrowing (``warp_rows``): a mixed batch where
        only some slots warp must sample exactly what the full-batch warp
        samples — greedy/plain slots get the warp=False arm, warping
        slots their warped rows, padding indices drop."""
        from areal_tpu.gen.sampling import SamplingParams, sample_tokens

        B, V = 6, 64
        logits = jnp.asarray(rng.normal(size=(B, V)), jnp.float32)
        sp = SamplingParams(
            temperature=jnp.asarray([0.0, 1.0, 0.7, 0.0, 1.3, 1.0]),
            top_p=jnp.asarray([1.0, 0.9, 1.0, 1.0, 0.8, 1.0]),
            top_k=jnp.asarray(
                [1 << 30, 1 << 30, 5, 1 << 30, 7, 1 << 30], jnp.int32
            ),
        )
        rows = jnp.asarray([1, 2, 4, B], jnp.int32)  # B = padding -> drop
        key = jax.random.key(7)
        t1, lp1 = sample_tokens(key, logits, sp, warp=True)
        t2, lp2 = sample_tokens(key, logits, sp, warp=True, warp_rows=rows)
        assert t1.tolist() == t2.tolist()
        np.testing.assert_allclose(np.asarray(lp1), np.asarray(lp2),
                                   atol=1e-5)

    def test_warp_rows_multi_matches_full(self, rng):
        """The [B, C, V] shape through warp_logits_rows."""
        from areal_tpu.gen.sampling import (
            SamplingParams, warp_logits_multi, warp_logits_rows,
        )

        B, C, V = 4, 3, 64
        logits = jnp.asarray(rng.normal(size=(B, C, V)), jnp.float32)
        sp = SamplingParams(
            temperature=jnp.asarray([1.0, 0.5, 1.2, 0.9]),
            top_p=jnp.asarray([0.9, 1.0, 0.5, 0.8]),
            top_k=jnp.asarray([5, 1 << 30, 20, 3], jnp.int32),
        )
        rows = jnp.asarray([0, 2, 3, B], jnp.int32)
        full = warp_logits_multi(logits, sp)
        sparse = warp_logits_rows(logits, sp, rows)
        for b in (0, 2, 3):
            np.testing.assert_allclose(
                np.asarray(sparse[b]), np.asarray(full[b]), atol=1e-6
            )

    def test_mixed_batch_one_warper_engine_exactness(self, params, rng):
        """Engine-level pin: a batch of greedy requests plus ONE top-p
        request must give the greedy slots exactly the tokens an all-greedy
        engine gives them — the warping request no longer changes (or
        slows) anyone else's path."""
        prompts = [
            [int(x) for x in rng.integers(1, 128, n)] for n in (5, 9, 7)
        ]
        ref = GenerationEngine(CFG, params, max_slots=4, max_seqlen=64,
                               seed=0)
        for i, p in enumerate(prompts):
            ref.submit(GenRequest(
                rid=f"g{i}", input_ids=p, max_new_tokens=8, greedy=True,
            ))
        want = {o.rid: o.output_ids
                for o in ref.run_until_done(decode_steps=3)}
        eng = GenerationEngine(CFG, params, max_slots=4, max_seqlen=64,
                               seed=0)
        for i, p in enumerate(prompts):
            eng.submit(GenRequest(
                rid=f"g{i}", input_ids=p, max_new_tokens=8, greedy=True,
            ))
        eng.submit(GenRequest(
            rid="warp", input_ids=prompts[0], max_new_tokens=8,
            temperature=1.0, top_p=0.9,
        ))
        got = {o.rid: o.output_ids
               for o in eng.run_until_done(decode_steps=3)}
        for rid, ids in want.items():
            assert got[rid] == ids, rid
        # the chunk specialized on the warp bucket, not a batch-wide bool
        assert any(k[2] == 1 for k in eng._jit_chunk)  # bucket-1 program


# --------------------------------------------------------------------------- #
# Tensor-parallel serving (VERDICT r2 #1): engine over a `model` mesh
# --------------------------------------------------------------------------- #


def _tp_mesh(n):
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n]), ("model",))


class TestTensorParallelEngine:
    def test_tp2_greedy_matches_single_device(self, params, rng):
        """A 2-way TP engine must generate the same greedy chains as the
        unsharded engine (counterpart of the reference's per-TP-group SGLang
        servers, realhf/system/generation_server.py:150)."""
        prompts = [
            [int(x) for x in rng.integers(1, 128, size=n)] for n in (5, 9, 3)
        ]
        eng1 = GenerationEngine(CFG, params, max_slots=4, max_seqlen=128)
        eng2 = GenerationEngine(
            CFG, params, max_slots=4, max_seqlen=128, mesh=_tp_mesh(2)
        )
        for eng in (eng1, eng2):
            for i, p in enumerate(prompts):
                eng.submit(GenRequest(
                    rid=f"r{i}", input_ids=p, max_new_tokens=8, greedy=True
                ))
        o1 = {o.rid: o for o in eng1.run_until_done(decode_steps=4)}
        o2 = {o.rid: o for o in eng2.run_until_done(decode_steps=4)}
        assert set(o1) == set(o2)
        for rid in o1:
            assert o1[rid].output_ids == o2[rid].output_ids, rid
            np.testing.assert_allclose(
                o1[rid].output_logprobs, o2[rid].output_logprobs, atol=1e-4
            )

    def test_tp_pool_is_sharded_and_weight_swap_reshards(self, params):
        mesh = _tp_mesh(2)
        eng = GenerationEngine(
            CFG, params, max_slots=2, max_seqlen=128, mesh=mesh
        )
        # KV pool shards over the kv-head axis: each device holds half
        kshard = eng.state.cache.pages.sharding
        assert kshard.spec == jax.sharding.PartitionSpec(
            None, None, None, "model", None, None
        )
        # wq shards on its head-output column axis
        wq = eng.params["layers"]["attn"]["wq"]
        assert wq.sharding.spec[-1] == "model"
        # hot swap from UNSHARDED host params lands back on the mesh
        host = jax.tree.map(np.asarray, tfm.init_params(CFG, jax.random.key(9)))
        eng.update_params(eng.prepare_params(host), version=2)
        assert eng.params["layers"]["attn"]["wq"].sharding.spec[-1] == "model"
        eng.submit(GenRequest(rid="a", input_ids=[1, 2, 3], max_new_tokens=2))
        outs = eng.run_until_done(decode_steps=2)
        assert outs[0].version == 2

    def test_tp_prefix_sharing_and_sampling(self, params):
        """Radix prefix sharing + stochastic sampling still work sharded."""
        mesh = _tp_mesh(2)
        eng = GenerationEngine(
            CFG, params, max_slots=4, max_seqlen=256, page_size=4, seed=0,
            mesh=mesh,
        )
        prompt = [5, 6, 7, 8, 9, 10, 11]  # 1 full page shared
        for i in range(4):
            eng.submit(GenRequest(
                rid=f"s{i}", input_ids=prompt, max_new_tokens=8,
                temperature=1.0, top_p=0.95,
            ))
        outs = {o.rid: o.output_ids for o in eng.run_until_done(decode_steps=4)}
        assert len(outs) == 4
        assert eng.stats["prefix_hits"] >= 3
        assert len(set(map(tuple, outs.values()))) > 1

    def test_tp_decode_stays_on_auto_dispatch(self, params):
        """r5 (VERDICT r4 weak #7): TP serving no longer pins the XLA
        gather path — the Pallas kernel runs under shard_map over the
        kv-head axis, so auto-dispatch stays in charge on every mesh."""
        eng = GenerationEngine(
            CFG, params, max_slots=2, max_seqlen=128, mesh=_tp_mesh(2)
        )
        assert eng._decode_use_pallas is None
        eng1 = GenerationEngine(CFG, params, max_slots=2, max_seqlen=128)
        assert eng1._decode_use_pallas is None  # platform auto-dispatch

    def test_tp_shard_map_pallas_decode_matches_gather(self, params, rng):
        """The shard_map'd Pallas decode (forced on, interpret mode) must
        match the XLA gather path on a kv-head-sharded pool."""
        from areal_tpu.ops.paged_attention import paged_decode_attention

        mesh = _tp_mesh(2)
        L, P_, Hkv, page, D = 2, 8, 2, 8, 16
        B, H = 4, 4
        q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
        k_self = jnp.asarray(rng.normal(size=(B, Hkv, D)), jnp.float32)
        v_self = jnp.asarray(rng.normal(size=(B, Hkv, D)), jnp.float32)
        pages = jnp.asarray(
            rng.normal(size=(L, P_, 2, Hkv, page, D)), jnp.float32
        )
        table = jnp.asarray(
            rng.permutation(P_).reshape(B, 2), jnp.int32
        )
        lens = jnp.asarray([3, 9, 16, 0], jnp.int32)
        ref = paged_decode_attention(
            q, k_self, v_self, pages, jnp.int32(1), table, lens,
            use_pallas=False,
        )
        got = paged_decode_attention(
            q, k_self, v_self, pages, jnp.int32(1), table, lens,
            use_pallas=True, mesh=mesh,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_tp_rejects_indivisible_heads(self, params):
        bad = dataclasses.replace(CFG, n_kv_heads=3, n_q_heads=3)
        p3 = tfm.init_params(bad, jax.random.key(0))
        with pytest.raises(ValueError, match="divisible"):
            GenerationEngine(bad, p3, max_slots=2, mesh=_tp_mesh(2))


# --------------------------------------------------------------------------- #
# Chunk pipelining (r5, VERDICT r4 #5): harvest one chunk late so the
# per-chunk host sync overlaps the next chunk's compute
# --------------------------------------------------------------------------- #


class TestPipelinedChunks:
    def test_pipelined_staggered_admission(self, params, rng):
        """New requests admitted mid-flight (slots freed by late harvests)
        must complete correctly — the fresh slot's lens/harvest state must
        not be clobbered by the stale previous-chunk flags."""
        eng = GenerationEngine(
            CFG, params, max_slots=2, max_seqlen=64, pipeline_chunks=True,
        )
        for i in range(5):  # 5 requests through 2 slots
            eng.submit(GenRequest(
                rid=f"s{i}",
                input_ids=[int(x) for x in rng.integers(1, 128, size=4 + i)],
                max_new_tokens=6, greedy=True,
            ))
        outs = {o.rid: o for o in eng.run_until_done(decode_steps=3)}
        assert set(outs) == {f"s{i}" for i in range(5)}
        assert all(len(o.output_ids) == 6 for o in outs.values())

    def test_steady_state_zero_blocking_device_get(self, params, monkeypatch):
        """The dispatch-ahead flag fetch: the harvest-flag D2H copy starts
        at chunk dispatch and resolves one chunk later (pipelined mode), so
        steady-state decode issues ZERO blocking device_get calls at chunk
        boundaries — proven by trace (a counting device_get shim) plus the
        engine's own blocked-resolve counter, the same event-log proof
        style as the fwd_pipe overlap test."""
        eng = GenerationEngine(
            CFG, params, max_slots=2, max_seqlen=512, pipeline_chunks=True,
        )
        eng.submit(GenRequest(
            rid="a", input_ids=[1, 2, 3, 4, 5], max_new_tokens=400,
            greedy=True,
        ))
        eng.step(decode_steps=4)    # admit + first dispatch
        eng.step(decode_steps=4)    # warm both pipeline stages
        # pace the warm-up's in-flight chunk too: the window's first
        # resolve is of THAT chunk (CPU dispatch is asynchronous)
        jax.block_until_ready((eng.state.lens, eng._prev_flags))
        metrics_mod.counters.clear(metrics_mod.GEN_CHUNK_FLAG_FETCHES)
        metrics_mod.counters.clear(metrics_mod.GEN_CHUNK_FLAG_BLOCKED)
        calls = []
        orig = jax.device_get
        monkeypatch.setattr(
            jax, "device_get",
            lambda *a, **kw: (calls.append(a), orig(*a, **kw))[1],
        )
        n_chunks = 10
        for _ in range(n_chunks):
            eng.step(decode_steps=4)
            # harness pacing only: wait out the in-flight chunk so the
            # next resolve measures the protocol, not CPU scheduling.
            # ALL of its outputs: on jax 0.9's CPU client the outputs of
            # one execution turn ready one by one, so the state being
            # ready does not make the flag tuple ready in the same instant
            jax.block_until_ready((eng.state.lens, eng._prev_flags))
        assert calls == []          # the trace assertion: zero device_get
        assert metrics_mod.counters.get(
            metrics_mod.GEN_CHUNK_FLAG_FETCHES
        ) == n_chunks
        assert metrics_mod.counters.get(
            metrics_mod.GEN_CHUNK_FLAG_BLOCKED
        ) == 0
        # the engine still harvests correctly after the window
        monkeypatch.setattr(jax, "device_get", orig)
        outs = eng.run_until_done(decode_steps=64)
        assert outs and outs[0].finish_reason == "length"


# --------------------------------------------------------------------------- #
# Spans where the chip waits, and request timestamps (docs/observability.md
# "Engine and trainer spans")
# --------------------------------------------------------------------------- #


def _chunks_with_children(spans):
    """[(chunk record, {child name: record})] for chunks that dispatched."""
    by_parent = {}
    for s in spans:
        by_parent.setdefault(s["parent_id"], {})[s["name"]] = s
    return [
        (s, by_parent.get(s["span_id"], {}))
        for s in spans
        if s["name"] == "gen_engine/chunk" and "slots" in s.get("attrs", {})
    ]


class TestEngineSpans:
    @pytest.mark.parametrize("pipelined", [False, True])
    def test_step_records_child_spans(self, params, rng, pipelined):
        from areal_tpu.base import tracing

        eng = GenerationEngine(
            CFG, params, max_slots=4, max_seqlen=128,
            pipeline_chunks=pipelined,
        )
        prompts = [
            [int(x) for x in rng.integers(1, 128, size=n)] for n in (5, 9, 3)
        ]
        tracing.drain()
        for i, p in enumerate(prompts):
            eng.submit(GenRequest(
                rid=f"r{i}", input_ids=p, max_new_tokens=6, greedy=True))
        outs = eng.run_until_done(decode_steps=4)
        assert len(outs) == 3
        spans = tracing.drain()
        chunks = _chunks_with_children(spans)
        assert len(chunks) >= 2
        first, kids = chunks[0]
        assert {"gen_engine/admit", "gen_engine/dispatch"} <= set(kids)
        # the first wave at this shape built its programs under the span's
        # leaf, the stretch that dispatches them (the innermost span pays)
        admit = kids["gen_engine/admit"]
        (prefill,) = [s for s in spans if s["parent_id"] == admit["span_id"]]
        assert prefill["name"] == "gen_engine/admit/prefill"
        assert prefill["attrs"].pop("compiled") >= 1
        assert 0 < prefill["attrs"].pop("compile_s") <= prefill["dur_s"]
        assert prefill["attrs"] == {"programs": 3}  # extend, write, commit
        assert admit["attrs"] == {
            "admitted": 3, "prefill_tokens": sum(len(p) - 1 for p in prompts),
            "prefix_hit_tokens": 0, "pending_left": 0,
            "preempted_tokens_recomputed": 0,
        }
        assert kids["gen_engine/dispatch"]["attrs"]["table_width"] >= 1
        assert first["attrs"]["steps"] == 4 and first["attrs"]["slots"] == 3
        # the page policy's census rides every chunk (a roomy pool: every
        # slot runs, nobody is held or preempted)
        assert (first["attrs"]["slots_running"], first["attrs"]["slots_held"],
                first["attrs"]["preemptions"]) == (3, 0, 0)
        assert first["attrs"]["pages_taken_growing"] >= 0
        # every flag wait says whether it blocked; every harvest how many
        # requests it finished, with their four stamps
        waits = [k["gen_engine/flag_wait"] for _, k in chunks
                 if "gen_engine/flag_wait" in k]
        assert waits and all(
            isinstance(w["attrs"]["blocked"], bool) for w in waits)
        harvests = [k["gen_engine/harvest"] for _, k in chunks
                    if "gen_engine/harvest" in k]
        assert sum(h["attrs"]["finished"] for h in harvests) == 3
        for h in harvests:
            a = h["attrs"]
            assert len(a["stamps"]) == a["finished"]
            for stamps in a["stamps"]:     # submit, admit, first, done
                assert stamps == sorted(stamps) and len(stamps) == 4
        assert sorted(round(o.t_done, 6) for o in outs) == sorted(
            st[3] for h in harvests for st in h["attrs"]["stamps"])
        # (a pipelined chunk with no earlier chunk to resolve counts none)
        assert sum(c["attrs"].get("finished", 0) for c, _ in chunks) == 3
        if not pipelined:
            # unpipelined: the four children tile the chunk, in this order
            order = ["gen_engine/admit", "gen_engine/dispatch",
                     "gen_engine/flag_wait"]
            starts = [kids[n]["t0"] for n in order]
            assert starts == sorted(starts)
            assert sum(k["dur_s"] for k in kids.values()) <= first["dur_s"]

    @pytest.mark.parametrize("engine", ["fused", "fused_pipelined",
                                        "materialised"])
    def test_fused_rows_and_sampler_fallback_rows(self, params, rng, engine):
        """A fused chunk says where its rows were sampled, rows x steps as
        dispatched: ``fused_rows`` by the fused head-and-sample pass,
        ``sampler_fallback_rows`` by the sorted path (a top-p row), on the
        span and summed on ``engine.stats``; a materialised engine carries
        neither attribute and counts 0."""
        from areal_tpu.base import tracing

        fused = engine != "materialised"
        eng = GenerationEngine(
            CFG, params, max_slots=4, max_seqlen=128, fused_sample=fused,
            pipeline_chunks=engine == "fused_pipelined",
        )
        for i, kw in enumerate((
                dict(greedy=True), dict(temperature=1.0),
                dict(temperature=1.0, top_p=0.9))):
            eng.submit(GenRequest(
                rid=f"r{i}",
                input_ids=[int(x) for x in rng.integers(1, 128, size=4 + i)],
                max_new_tokens=20, **kw))
        tracing.drain()
        for _ in range(2):
            eng.step(4)
        attrs = [c["attrs"] for c, _ in _chunks_with_children(tracing.drain())]
        assert len(attrs) == 2 and all(a["slots"] == 3 for a in attrs)
        if fused:
            assert [a["fused_rows"] for a in attrs] == [2 * 4] * 2
            assert [a["sampler_fallback_rows"] for a in attrs] == [1 * 4] * 2
        else:
            assert not any(
                "fused_rows" in a or "sampler_fallback_rows" in a
                for a in attrs)
        assert eng.stats["fused_rows"] == (16 if fused else 0)
        assert eng.stats["sampler_fallback_rows"] == (8 if fused else 0)

    def test_resident_tokens_is_the_running_slots_kv(self, params, rng):
        """``resident_tokens`` at a chunk's dispatch = the KV positions
        its first decode step reads: prompt - 1 + generated, per slot."""
        from areal_tpu.base import tracing

        eng = GenerationEngine(CFG, params, max_slots=4, max_seqlen=128)
        plens = (5, 9, 3)
        for i, n in enumerate(plens):
            eng.submit(GenRequest(
                rid=f"r{i}", input_ids=[int(x) for x in rng.integers(1, 128, size=n)],
                max_new_tokens=20, greedy=True))
        tracing.drain()
        for _ in range(3):
            eng.step(4)
        res = [c["attrs"]["resident_tokens"]
               for c, _ in _chunks_with_children(tracing.drain())]
        assert res == [sum(plens) - 3 + 3 * 4 * k for k in range(3)]

    @pytest.mark.parametrize("path", ["kernel", "xla"])
    def test_kernel_counts_follow_the_kernels_block_plan(
            self, params, rng, path):
        """``kernel_positions`` at a chunk's dispatch = what the paged
        kernel's body runs over at its first step: per block of ``sb``
        rows of the batch SORTED by length (as ``decode_step_paged`` hands
        them over), ``sb`` x the page blocks of its longest row; those page
        blocks are ``kernel_steps_active`` of the ``kernel_steps`` grid
        steps (blocks x page blocks of the table), the steps that still
        walk their table entries, and ``kernel_steps_chained`` of those are
        started by the last reached step of an earlier block (every block
        that reaches a step but the first). Only on chunks that run the kernel
        (forced on here, interpret mode; on the CPU's own XLA gather path
        there is nothing to count), and the tokens are those of the gather path either way."""
        from areal_tpu.base import tracing
        from areal_tpu.ops.pallas import paged_attention as pl_paged

        eng = GenerationEngine(
            CFG, params, max_slots=12, max_seqlen=256, page_size=8)
        eng._decode_use_pallas = None if path == "xla" else True
        plens = (5, 150, 9, 70, 3, 130, 64, 20, 200)    # 3 slots stay free
        prompts = [[int(x) for x in rng.integers(1, 128, size=n)]
                   for n in plens]
        for i, p in enumerate(prompts):
            eng.submit(GenRequest(
                rid=f"r{i}", input_ids=p, max_new_tokens=8, greedy=True))
        tracing.drain()
        outs = {o.rid: o.output_ids for o in eng.run_until_done(4)}
        chunks = _chunks_with_children(tracing.drain())
        attrs = [c["attrs"] for c, _ in chunks]
        names = ("kernel_positions", "kernel_steps_active", "kernel_steps",
                 "kernel_steps_chained")
        if path != "kernel":
            assert attrs
            assert not any(n in a for a in attrs for n in names)
            assert all(eng.stats[n] == 0 for n in names)
            return
        assert len(attrs) == 2
        # 12 slots: blocks of 4; the full-attention program's step is 4
        # pages of 8 (``block_plan``)
        sb, span = 4, 4 * 8
        assert pl_paged.block_plan(
            12, CFG.n_kv_heads, CFG.head_dim, 8, 32, jnp.float32) == (4, 4)
        want = []
        for k in range(2):
            lens = np.sort([0] * 3 + [n - 1 + 4 * k for n in plens])
            want.append(sum(
                -(-int(lens[b:b + sb].max()) // span)
                for b in range(0, 12, sb)))
        assert [a["kernel_steps_active"] for a in attrs] == want
        assert [a["kernel_positions"] for a in attrs] == [
            sb * span * w for w in want]
        # slot order (5, 150, 9, 70 | 3, 130, 64, 20 | 200, -, -, -) would
        # take every block as far as a long row: 5 + 5 + 7 page blocks;
        # and steps of 8 pages would end the last block at 4 x 64
        # positions for its 199, not at 7 x 32
        assert want[0] == 1 + 2 + 7
        # 3 blocks x the page blocks of the chunk's table: the block of
        # short rows and the one with the free slots reach only the first
        widths = [c["gen_engine/dispatch"]["attrs"]["table_width"]
                  for _, c in chunks]
        # (and as many blocks again of the prefix program, a block for
        # every four rows and 8 pages a step, none of which reaches a
        # step: no row shares)
        assert [a["kernel_steps"] for a in attrs] == [
            3 * -(-w // 4) + 3 * -(-w // 8) for w in widths]
        assert all(a["kv_pages_read"] == a["kv_pages_named"] > 0
                   and a["kv_shared_rows"] == 0 for a in attrs)
        assert all(a["kernel_steps_active"] < a["kernel_steps"]
                   for a in attrs)
        # sorted, the three free slots share block 0 with a short row: all
        # three blocks reach a step, two of them behind another block's
        assert [a["kernel_steps_chained"] for a in attrs] == [2, 2]
        for n in names + ("resident_tokens",):
            assert eng.stats[n] == sum(a[n] for a in attrs)
        ref = GenerationEngine(
            CFG, params, max_slots=12, max_seqlen=256, page_size=8)
        for i, p in enumerate(prompts):
            ref.submit(GenRequest(
                rid=f"r{i}", input_ids=p, max_new_tokens=8, greedy=True))
        assert outs == {o.rid: o.output_ids for o in ref.run_until_done(4)}

    @pytest.mark.parametrize("pipelined", [False, True])
    @pytest.mark.parametrize("how", ["finished", "interrupted"])
    def test_genoutput_timestamps(self, params, rng, pipelined, how):
        import time

        eng = GenerationEngine(
            CFG, params, max_slots=2, max_seqlen=128,
            pipeline_chunks=pipelined,
        )
        t_before = time.perf_counter()
        for i in range(3):      # the third waits for a slot
            eng.submit(GenRequest(
                rid=f"r{i}", input_ids=[int(x) for x in rng.integers(1, 128, size=4)],
                max_new_tokens=6 if how == "finished" else 64, greedy=True))
        if how == "finished":
            outs = eng.run_until_done(decode_steps=4)
            assert {o.finish_reason for o in outs} == {"length"}
        else:
            for _ in range(3):
                eng.step(4)
            outs = eng.pause()
            assert {o.finish_reason for o in outs} == {"interrupted"}
            assert len(outs) == 2 and all(o.output_ids for o in outs)
        t_after = time.perf_counter()
        for o in outs:
            assert t_before <= o.t_submit <= o.t_admit <= o.t_first <= o.t_done <= t_after, o
        if how == "finished":
            late = next(o for o in outs if o.rid == "r2")
            # it queued until a slot was harvested: admitted after the
            # first two had their first tokens
            assert late.t_admit >= max(o.t_first for o in outs if o.rid != "r2")

    def test_start_is_a_span_with_its_phases_as_children(self, params):
        """``gen_engine/start`` around the constructor, a child a phase
        that touches the device, and every program built in it a
        ``compile/program`` record under the phase that built it."""
        from areal_tpu.base import tracing

        tracing.drain()
        # sizes no other test of this process builds a state at: its
        # eager programs are new here
        eng = GenerationEngine(
            CFG, params, max_slots=3, max_seqlen=320, max_new_tokens_cap=77)
        spans = tracing.drain()
        (start,) = [s for s in spans if s["name"] == "gen_engine/start"]
        kids = [s for s in spans if s["parent_id"] == start["span_id"]]
        assert [k["name"] for k in kids] == [
            "gen_engine/start/params", "gen_engine/start/state"]
        assert sum(k["dur_s"] for k in kids) <= start["dur_s"]
        a = start["attrs"]
        assert (a["max_slots"], a["n_pages"], a["pool_bytes"]) == (
            3, eng.n_pages, eng.kv_pool_bytes())
        built = [s for s in spans if s["name"] == "compile/program"]
        by_id = {s["span_id"]: s for s in spans}
        assert built and all(
            by_id[b["parent_id"]]["name"].startswith("gen_engine/start/")
            for b in built)
        assert sum(k.get("attrs", {}).get("compiled", 0)
                   for k in kids) == len(built)
        assert "compiled" not in a      # the phases paid, not the start
        assert not [s for s in tracing.live_spans()
                    if s["name"].startswith("gen_engine/start")]

    def test_first_step_at_a_shape_says_what_it_built(self, params, rng):
        """``compiled`` on ``gen_engine/admit/prefill`` /
        ``gen_engine/dispatch/enqueue`` (the leaves of ``gen_engine/admit``
        / ``gen_engine/dispatch`` that dispatch device programs): the first
        step at a shape builds its programs under them, each a
        ``compile/program`` child naming the program; the second builds
        nothing and carries no stamp."""
        from areal_tpu.base import tracing

        eng = GenerationEngine(
            CFG, params, max_slots=2, max_seqlen=128, pipeline_chunks=False)
        eng.submit(GenRequest(
            rid="a", input_ids=[int(x) for x in rng.integers(1, 128, size=5)],
            max_new_tokens=40, greedy=True))
        steps = []
        for _ in range(2):
            tracing.drain()
            eng.step(4)
            steps.append(tracing.drain())
        names = ("gen_engine/admit/prefill", "gen_engine/dispatch/enqueue")
        first = {s["name"]: s for s in steps[0] if s["name"] in names}
        assert first["gen_engine/admit/prefill"]["attrs"]["compiled"] >= 1
        assert first["gen_engine/dispatch/enqueue"]["attrs"]["compiled"] >= 1
        programs = {
            s["attrs"]["fun_name"] for s in steps[0]
            if s["name"] == "compile/program"
            and s["parent_id"] == first["gen_engine/dispatch/enqueue"]["span_id"]}
        assert "jit(chunk)" in programs
        assert not [s for s in steps[1] if s["name"] == "compile/program"]
        assert all("compiled" not in s.get("attrs", {}) for s in steps[1])

    def test_a_table_width_nobody_warmed_is_one_record(self, params, rng):
        """Serving builds a program when a slot grows into the next table
        width: ONE ``compile/program`` record, child of the
        ``gen_engine/dispatch/enqueue`` that paid for it, naming the
        program."""
        from areal_tpu.base import tracing

        eng = GenerationEngine(
            CFG, params, max_slots=2, max_seqlen=512, page_size=8,
            max_new_tokens_cap=64, pipeline_chunks=False)
        eng.submit(GenRequest(       # 240 of the 256 positions 32 pages hold
            rid="a", input_ids=[int(x) for x in rng.integers(1, 128, size=240)],
            max_new_tokens=40, greedy=True))
        eng.step(4)
        tracing.drain()
        while not eng.step(4):
            pass
        spans = tracing.drain()
        dispatches = [
            s for s in spans if s["name"] == "gen_engine/dispatch/enqueue"]
        assert {d["attrs"]["table_width"] for d in dispatches} == {32, 64}
        by_id = {s["span_id"]: s for s in spans}
        assert all(      # the leaf's width is its ``gen_engine/dispatch``'s
            by_id[d["parent_id"]]["attrs"]["table_width"]
            == d["attrs"]["table_width"] for d in dispatches)
        (built,) = [s for s in spans if s["name"] == "compile/program"]
        assert built["attrs"]["fun_name"] == "jit(chunk)"
        (paid,) = [d for d in dispatches if d["span_id"] == built["parent_id"]]
        assert paid["attrs"]["table_width"] == 64
        assert paid["attrs"]["compiled"] == 1
        assert paid["attrs"]["compile_s"] == built["dur_s"]
        assert [d["attrs"].get("compiled", 0) for d in dispatches] == [
            int(d is paid) for d in dispatches]

    def test_weight_swap_span_carries_the_version(self, params):
        from areal_tpu.base import tracing

        eng = GenerationEngine(CFG, params, max_slots=2, max_seqlen=64)
        tracing.drain()
        eng.update_params(params, version=7)
        (rec,) = [s for s in tracing.drain()
                  if s["name"] == "gen_engine/weight_swap"]
        assert rec["attrs"] == {"version": 7}
