"""Int8-quantized paged KV pool (docs/performance.md "KV quantization").

The load-bearing contracts:
- the quantizing post-scan scatter roundtrips values within int8 precision
  and lands scales at the same flat rows as their pages;
- both paged-attention entry points (decode / extend) with an int8 pool
  + scales match the same attention over the explicitly
  dequantized pool — dequant is FUSED, never a materialized pool copy;
- the Pallas decode kernel's in-register dequant matches the XLA path;
- engine-level: greedy decode over an int8 pool is token-identical to the
  raw-dtype pool for (nearly) every sequence of the parity corpus, the
  teacher-forced logit error is bounded, prefix sharing reuses quantized
  pages AND their scales, TP serving and pause/resume compose, and int8
  mode buys itemsize-ratio x pages (2x under bf16 serving) at the same
  configured pool HBM.

Exhaustive dtype x path sweeps ride the ``slow`` marker (run unmarked
locally + compiled on chip); tier-1 keeps one representative per feature,
per the round-6 budget policy.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.base import metrics as metrics_mod
from areal_tpu.gen.engine import GenerationEngine, GenRequest
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig
from areal_tpu.ops import paged_attention as xla_paged

CFG = ModelConfig(
    n_layers=2, n_q_heads=4, n_kv_heads=2, head_dim=8, hidden_dim=32,
    intermediate_dim=64, vocab_size=128, dtype="float32",
)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.key(5))


def _quantize_pool(pool_f: np.ndarray):
    """Reference quantization: symmetric per-(layer, page, K|V, head,
    token-slot) over head_dim — exactly what the scatter writes."""
    amax = np.abs(pool_f).max(axis=-1)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.round(pool_f / scale[..., None]), -127, 127).astype(np.int8)
    return q, scale


def _rand_pool(rng, L=3, P=20, Hkv=2, page=8, D=16):
    return rng.normal(size=(L, P, 2, Hkv, page, D)).astype(np.float32)


class TestQuantScatter:
    def test_scatter_roundtrip_and_scale_rows(self, rng):
        """The int8 scatter must write q = round(x/scale) pages AND their
        scales through the same flat rows; dequant recovers the inputs to
        int8 precision; invalid positions and other slots stay zero."""
        L, P, Hkv, page, D, B, M = 2, 6, 2, 8, 16, 3, 2
        cache = tfm.PagedKVCache.empty(
            dataclasses.replace(CFG, n_layers=L, n_kv_heads=Hkv, head_dim=D),
            P, page, kv_dtype="int8",
        )
        ks = rng.normal(size=(L, B, 1, Hkv, D)).astype(np.float32)
        vs = rng.normal(size=(L, B, 1, Hkv, D)).astype(np.float32)
        table = rng.permutation(P)[: B * M].reshape(B, M).astype(np.int32)
        positions = np.asarray([[0], [7], [9]], np.int32)
        valid = np.asarray([[True], [True], [False]])
        out = tfm._scatter_chunk_kv(
            cache, jnp.asarray(ks), jnp.asarray(vs), jnp.asarray(table),
            jnp.asarray(positions[:, 0]),
            jnp.asarray(valid[:, 0].astype(np.int32)),
        )
        pages = np.asarray(out.pages)
        scales = np.asarray(out.scales)
        for b in range(B):
            p_, o = table[b, positions[b, 0] // page], positions[b, 0] % page
            for l in range(L):
                for kv, src in ((0, ks), (1, vs)):
                    got = (
                        pages[l, p_, kv, :, o, :].astype(np.float32)
                        * scales[l, p_, kv, :, o, None]
                    )
                    if valid[b, 0]:
                        np.testing.assert_allclose(
                            got, src[l, b, 0], atol=2e-2, rtol=1.5 / 127,
                        )
                    else:
                        np.testing.assert_array_equal(got, 0.0)

    def test_unquantized_scatter_untouched(self, rng):
        """scales=None keeps the raw-dtype scatter byte-for-byte (pinned by
        test_paged_engine.test_pool_scatter_matches_reference; this guards
        the branch itself)."""
        cache = tfm.PagedKVCache.empty(CFG, 4, 8)
        assert cache.scales is None and not cache.quantized
        out = tfm._scatter_chunk_kv(
            cache,
            jnp.zeros((CFG.n_layers, 1, 1, CFG.n_kv_heads, CFG.head_dim)),
            jnp.zeros((CFG.n_layers, 1, 1, CFG.n_kv_heads, CFG.head_dim)),
            jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.ones((1,), jnp.int32),
        )
        assert out.scales is None


def _attend_all_paths(pool, scales, q3, k3, v3, table, lens, n_new,
                      soft_cap=None, window=None):
    """(decode, extend) outputs for one pool; q3/k3/v3 are the
    [B, C, H(kv), D] chunk operands, decode uses position 0."""
    kw = dict(soft_cap=soft_cap, sliding_window=window)
    dec = xla_paged.paged_decode_attention(
        q3[:, 0], k3[:, 0], v3[:, 0], pool, jnp.int32(1), table, lens,
        use_pallas=False, scales=scales, **kw,
    )
    ext = xla_paged.paged_extend_attention(
        q3, k3, v3, pool, jnp.int32(1), table, lens, n_new,
        scales=scales, **kw,
    )
    return dec, ext


class TestXLAPathParity:
    """Int8 pool + fused dequant == the same attention over an explicitly
    dequantized pool, for every entry point. Tier-1 runs the plain
    variant; the soft-cap/sliding-window sweep is ``slow``."""

    @pytest.mark.parametrize(
        "soft_cap,window",
        [(None, None),
         pytest.param(5.0, None, marks=pytest.mark.slow),
         pytest.param(None, 6, marks=pytest.mark.slow)],
    )
    def test_all_paths_match_dequantized_pool(self, rng, soft_cap, window):
        B, C, Hq, Hkv, D, page, M, P, L = 3, 3, 4, 2, 16, 8, 4, 20, 3
        pool_f = _rand_pool(rng, L, P, Hkv, page, D)
        pool_q, scale = _quantize_pool(pool_f)
        deq = pool_q.astype(np.float32) * scale[..., None]
        q3 = rng.normal(size=(B, C, Hq, D)).astype(np.float32)
        k3 = rng.normal(size=(B, C, Hkv, D)).astype(np.float32)
        v3 = rng.normal(size=(B, C, Hkv, D)).astype(np.float32)
        table = rng.permutation(P)[: B * M].reshape(B, M).astype(np.int32)
        lens = np.asarray([1, 17, 0], np.int32)
        n_new = np.asarray([C, C, 0], np.int32)
        got = _attend_all_paths(
            jnp.asarray(pool_q), jnp.asarray(scale), q3, k3, v3,
            table, lens, n_new, soft_cap, window,
        )
        want = _attend_all_paths(
            jnp.asarray(deq), None, q3, k3, v3, table, lens, n_new,
            soft_cap, window,
        )
        for name, g, w in zip(("decode", "extend"), got, want):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), atol=2e-5, err_msg=name
            )


class TestPallasInt8Decode:
    """The kernel's in-register dequant (int8 page DMA + scale-stripe DMA,
    scales folded into the score/probability dots) vs the XLA int8 path.
    Tier-1 keeps the multi-step (2, 2) pipeline grid; the full grid x
    mask-feature sweep is ``slow``."""

    @pytest.mark.parametrize(
        "kp_sb,soft_cap,window",
        [((2, 2), None, None),
         pytest.param((8, 8), None, None, marks=pytest.mark.slow),
         pytest.param((1, 2), None, None, marks=pytest.mark.slow),
         pytest.param((2, 2), 5.0, None, marks=pytest.mark.slow),
         pytest.param((2, 2), None, 6, marks=pytest.mark.slow)],
    )
    def test_parity_vs_xla_int8(self, rng, kp_sb, soft_cap, window):
        from areal_tpu.ops.pallas import paged_attention as pl_paged

        B, Hq, Hkv, D, page, M, P, L = 4, 4, 2, 16, 8, 4, 20, 3
        pool_f = _rand_pool(rng, L, P, Hkv, page, D)
        pool_q, scale = _quantize_pool(pool_f)
        q = rng.normal(size=(B, Hq, D)).astype(np.float32)
        k_self = rng.normal(size=(B, Hkv, D)).astype(np.float32)
        v_self = rng.normal(size=(B, Hkv, D)).astype(np.float32)
        table = rng.permutation(P)[: B * M].reshape(B, M).astype(np.int32)
        lens = np.asarray([1, 9, 32, 0], np.int32)
        got = pl_paged.decode(
            q, k_self, v_self, pool_q, jnp.int32(1), table, lens,
            soft_cap=soft_cap, sliding_window=window,
            pages_per_step=kp_sb[0], slots_per_step=kp_sb[1],
            scales=jnp.asarray(scale),
        )
        want = xla_paged.paged_decode_attention(
            q, k_self, v_self, pool_q, jnp.int32(1), table, lens,
            soft_cap=soft_cap, sliding_window=window, use_pallas=False,
            scales=jnp.asarray(scale),
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def _run_greedy(params, prompts, max_new, kv_dtype, **kw):
    kw.setdefault("max_slots", max(4, len(prompts)))
    eng = GenerationEngine(
        CFG, params, max_seqlen=128, page_size=8, seed=0,
        kv_dtype=kv_dtype, **kw,
    )
    for i, p in enumerate(prompts):
        eng.submit(GenRequest(
            rid=f"r{i}", input_ids=p, max_new_tokens=max_new, greedy=True,
        ))
    return {o.rid: o for o in eng.run_until_done(decode_steps=4)}


class TestEngineParity:
    def test_greedy_corpus_token_match(self, params, rng):
        """CPU parity corpus: >= 95% of greedy sequences token-identical
        between raw and int8 pools (the acceptance bar); the engine serves
        both from ONE code path, only the pool dtype differs."""
        prompts = [
            [int(x) for x in rng.integers(1, 128, n)]
            for n in (3, 5, 7, 9, 11, 13, 17, 19, 21, 6, 10, 15)
        ]
        raw = _run_greedy(params, prompts, 12, None)
        q = _run_greedy(params, prompts, 12, "int8")
        assert set(raw) == set(q)
        same = sum(
            raw[r].output_ids == q[r].output_ids
            and raw[r].finish_reason == q[r].finish_reason
            for r in raw
        )
        assert same >= 0.95 * len(prompts), f"{same}/{len(prompts)} matched"

    def test_teacher_forced_logit_error_bounded(
            self, params, rng, decode_tokens_paged):
        """Per-position max-abs logit error of the decode forward over an
        int8 pool vs the raw pool, teacher-forced on the same tokens —
        the quantization-noise bound PPO logprobs see."""
        prompt = [int(x) for x in rng.integers(1, 128, size=9)]
        engines = {}
        for kd in (None, "int8"):
            eng = GenerationEngine(
                CFG, params, max_slots=2, max_seqlen=64, page_size=8,
                seed=0, kv_dtype=kd,
            )
            eng.submit(GenRequest(
                rid="a", input_ids=prompt, max_new_tokens=8, greedy=True,
            ))
            eng.step(decode_steps=3)  # resident context incl. decoded KV
            engines[kd] = eng
        chunk = jnp.asarray(
            [[5, 9, 2, 14]] * 2, jnp.int32
        )
        logits = {}
        for kd, eng in engines.items():
            state = eng.state
            W = eng._table_width(int(np.asarray(state.lens).max()) + 8)
            lg, _ = decode_tokens_paged(
                eng.params, CFG, state.cache, chunk,
                jnp.asarray(eng._table_host[:, :W]), state.lens,
                jnp.where(state.active, 4, 0),
            )
            logits[kd] = np.asarray(lg)
        err = np.abs(logits["int8"] - logits[None]).max()
        assert err < 0.1, f"max logit delta {err}"

    def test_tp2_int8_matches_single_device(self, params, rng):
        """Int8 pool + scales sharded over a 2-way ``model`` mesh (both on
        the kv-head axis) must reproduce the single-device outputs."""
        from jax.sharding import Mesh

        prompts = [[int(x) for x in rng.integers(1, 128, n)] for n in (5, 9)]
        ref = None
        for mesh in (None, Mesh(np.array(jax.devices()[:2]), ("model",))):
            outs = {
                r: o.output_ids
                for r, o in _run_greedy(
                    params, prompts, 8, "int8", max_slots=2, mesh=mesh,
                ).items()
            }
            if ref is None:
                ref = outs
            else:
                assert outs == ref

    def test_pause_resume_roundtrip(self, params, rng):
        """Interrupt mid-generation over an int8 pool: the partial is a
        valid prefix of the uninterrupted run, resumed work completes, and
        every page (and scale slot with it) is accounted for."""
        prompt = [int(x) for x in rng.integers(1, 128, size=7)]
        full = _run_greedy(params, [prompt], 12, "int8")["r0"].output_ids
        eng = GenerationEngine(
            CFG, params, max_slots=2, max_seqlen=64, page_size=8, seed=0,
            kv_dtype="int8",
        )
        eng.submit(GenRequest(
            rid="a", input_ids=prompt, max_new_tokens=12, greedy=True,
        ))
        eng.step(decode_steps=4)
        outs = eng.pause()
        assert outs[0].finish_reason == "interrupted"
        assert outs[0].output_ids == full[: len(outs[0].output_ids)]
        eng.resume()
        eng.submit(GenRequest(
            rid="b", input_ids=prompt, max_new_tokens=12, greedy=True,
        ))
        outs = eng.run_until_done(decode_steps=4)
        assert outs[0].output_ids == full
        eng.prefix.clear()
        assert eng.pool.n_free == eng.n_pages


class TestPrefixSharingQuantized:
    def test_group_shares_quantized_pages_and_scales(self, params, rng):
        """A GRPO group over one prompt on an int8 engine: one prefill
        serves everyone (prefix_hits), and the borrowers' outputs equal
        the owner's AND a no-sharing cold engine's — the shared pages'
        SCALES travel with them (wrong scales would corrupt exactly the
        borrowers)."""
        prompt = [int(x) for x in rng.integers(1, 128, 21)]  # 2 full pages
        eng = GenerationEngine(
            CFG, params, max_slots=4, max_seqlen=64, page_size=8, seed=0,
            kv_dtype="int8",
        )
        for i in range(4):
            eng.submit(GenRequest(
                rid=f"g{i}", input_ids=prompt, max_new_tokens=6, greedy=True,
            ))
        outs = eng.run_until_done(decode_steps=3)
        assert eng.stats["prefix_hits"] == 3
        assert len({tuple(o.output_ids) for o in outs}) == 1
        cold = GenerationEngine(
            CFG, params, max_slots=2, max_seqlen=64, page_size=8, seed=0,
            kv_dtype="int8", enable_prefix_cache=False,
        )
        cold.submit(GenRequest(
            rid="c", input_ids=prompt, max_new_tokens=6, greedy=True,
        ))
        ref = cold.run_until_done(decode_steps=3)[0]
        assert outs[0].output_ids == ref.output_ids


class TestCapacity:
    def test_default_pool_scales_by_itemsize_ratio(self, params):
        """int8 mode resizes the DEFAULT pool to the serving-dtype HBM
        budget: itemsize-ratio x pages (2x under bf16 serving, 4x under
        this float32 test config) — same page-array bytes, more pages."""
        raw = GenerationEngine(CFG, params, max_slots=2, max_seqlen=64,
                               page_size=8)
        q = GenerationEngine(CFG, params, max_slots=2, max_seqlen=64,
                             page_size=8, kv_dtype="int8")
        ratio = jnp.dtype(CFG.dtype).itemsize
        assert q.n_pages == raw.n_pages * ratio
        raw_page_bytes = raw.n_pages * jnp.dtype(CFG.dtype).itemsize
        assert q.n_pages * 1 == raw_page_bytes  # page arrays: equal bytes
        # reported footprint includes the scales (4/head_dim overhead)
        assert q.kv_pool_bytes() > raw.kv_pool_bytes()

    def test_serves_ratio_x_slots_at_equal_pool_hbm(self, params, rng):
        """At the same configured page-array HBM, the int8 engine admits
        itemsize-ratio x the slot count concurrently (the acceptance bar:
        2x under bf16 serving)."""
        B = 2
        raw = GenerationEngine(CFG, params, max_slots=B, max_seqlen=64,
                               page_size=8)
        ratio = jnp.dtype(CFG.dtype).itemsize
        q = GenerationEngine(
            CFG, params, max_slots=B * ratio, max_seqlen=64, page_size=8,
            kv_dtype="int8", n_pages=raw.n_pages * ratio,
            enable_prefix_cache=False,
        )
        # page arrays occupy identical HBM
        assert q.n_pages * 1 == raw.n_pages * jnp.dtype(CFG.dtype).itemsize
        for i in range(B * ratio):
            q.submit(GenRequest(
                rid=f"r{i}",
                input_ids=[int(x) for x in rng.integers(1, 128, 9)],
                max_new_tokens=48, greedy=True,
            ))
        q.step(decode_steps=1)
        assert q.n_running() == B * ratio  # everyone resident at once
        outs = q.run_until_done(decode_steps=8)
        assert len(outs) == B * ratio

    def test_kvq_telemetry_counters(self, params, rng):
        """gen/kvq_pages_quantized counts int8 pages entering service and
        the occupancy histogram records per-chunk pool fractions."""
        before = metrics_mod.counters.get(metrics_mod.GEN_KVQ_PAGES_QUANTIZED)
        h0 = metrics_mod.counters.histogram(metrics_mod.GEN_KV_POOL_OCCUPANCY)
        n0 = h0.count if h0 else 0
        _run_greedy(
            params, [[int(x) for x in rng.integers(1, 128, 9)]], 6, "int8",
        )
        assert metrics_mod.counters.get(
            metrics_mod.GEN_KVQ_PAGES_QUANTIZED
        ) > before
        h1 = metrics_mod.counters.histogram(metrics_mod.GEN_KV_POOL_OCCUPANCY)
        assert h1 is not None and h1.count > n0


class TestKnobResolution:
    def test_env_knob_enables_int8(self, params, monkeypatch):
        from areal_tpu.base import constants

        monkeypatch.setenv(constants.KV_DTYPE_ENV, "int8")
        eng = GenerationEngine(CFG, params, max_slots=1, max_seqlen=32,
                               page_size=8)
        assert eng.kv_quantized and eng.kv_dtype == "int8"

    def test_explicit_arg_overrides_env(self, params, monkeypatch):
        from areal_tpu.base import constants

        monkeypatch.setenv(constants.KV_DTYPE_ENV, "int8")
        eng = GenerationEngine(CFG, params, max_slots=1, max_seqlen=32,
                               page_size=8, kv_dtype="bf16")
        assert not eng.kv_quantized

    def test_unknown_env_value_falls_back(self, params, monkeypatch):
        from areal_tpu.base import constants

        monkeypatch.setenv(constants.KV_DTYPE_ENV, "fp3")
        eng = GenerationEngine(CFG, params, max_slots=1, max_seqlen=32,
                               page_size=8)
        assert not eng.kv_quantized

    def test_unknown_engine_arg_raises(self, params):
        with pytest.raises(ValueError, match="kv_dtype"):
            GenerationEngine(CFG, params, max_slots=1, max_seqlen=32,
                             page_size=8, kv_dtype="fp8")

    def test_metrics_json_gauges(self, params):
        """The serving gauges the fleet watches: kv_dtype / kv_pool_bytes /
        n_pages_free / occupancy, straight off the engine."""
        from areal_tpu.gen.server import GenerationHTTPServer

        eng = GenerationEngine(CFG, params, max_slots=1, max_seqlen=32,
                               page_size=8, kv_dtype="int8")
        srv = GenerationHTTPServer(eng)
        m = srv._metrics_dict()
        assert m["kv_dtype"] == "int8"
        assert m["kv_pool_bytes"] == eng.kv_pool_bytes() > 0
        assert m["n_pages_free"] == eng.pool.n_free
        assert 0.0 <= m["kv_pool_occupancy"] <= 1.0
