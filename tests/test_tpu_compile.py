"""The chip's compiler on the main path's kernels at the 1.5B widths.

Interpret-mode parity tests (test_flash_attention / test_paged_engine /
test_kv_quant / test_fused_sample) prove the kernels' math on the CPU;
they cannot see what Mosaic refuses: scoped-vmem overflow, slices not
aligned to the tiling, casts the TPU lowering does not offer. The TPU
compiler is installed here and compiles for a chip that is *described*,
not attached (``jax.experimental.topologies``), so each case below is an
ahead-of-time compile of one kernel at the shapes chip_smoke.py runs.

A compile that passes is not a run: chip_smoke.py is the proof of that.

Rules this file follows (on-chip-measurement guide §2): the topology is
described inside a module-scoped, non-autouse fixture (only the xdist
worker that owns this file loads libtpu); ``_interpret`` is steered from
the test, not by an option of the program; the persistent compile cache
is off around these compiles (a described-device entry cannot be read
back and only produces warnings).
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# the 1.5B cell's widths (benchmark/configs/r1d-qwen-1p5b.json) and the
# 125M preset
QWEN_1P5B = dict(hq=12, hkv=2, d=128)
PRESET_125M = dict(hq=12, hkv=4, d=64)
# OLMoE-1B-7B: multi-head (n_rep 1); 16 kv heads x 128 at page 128 leave
# room for ONE slot x 8 pages in the paged kernel's 16 MiB of scratch
OLMOE = dict(hq=16, hkv=16, d=128)
# R1-Distill-Qwen-7B: 4 kv heads leave room for 4 slots a grid step
QWEN_7B = dict(hq=28, hkv=4, d=128)
T_TRAIN = 4096          # 8 x 512 packed tokens: the default train step


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch, no_persistent_cache):
    """Steer the three kernel modules off interpret mode (on the CPU their
    `_interpret()` says True) for the duration of one test."""
    from areal_tpu.ops.pallas import flash_attention, fused_sample
    from areal_tpu.ops.pallas import paged_attention as pl_paged

    for mod in (flash_attention, fused_sample, pl_paged):
        monkeypatch.setattr(mod, "_interpret", lambda: False)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _flash_specs(layout, T, one_chip):
    hq, hkv, d = layout["hq"], layout["hkv"], layout["d"]
    return (
        _spec((T, hq, d), jnp.bfloat16, one_chip),
        _spec((T, hkv, d), jnp.bfloat16, one_chip),
        _spec((T, hkv, d), jnp.bfloat16, one_chip),
        _spec((T,), jnp.int32, one_chip),
    )


def _flash(layout, max_seqlen, block=512):
    from areal_tpu.ops.pallas.flash_attention import packed_flash_attention

    return functools.partial(
        packed_flash_attention, softmax_scale=layout["d"] ** -0.5,
        block_size=block, max_seqlen=max_seqlen,
    )


FLASH_CASES = [
    pytest.param(QWEN_1P5B, 512, id="1p5b-band512"),
    pytest.param(QWEN_1P5B, None, id="1p5b-triangle"),
    pytest.param(PRESET_125M, 512, id="125m-band512"),
    pytest.param(PRESET_125M, None, id="125m-triangle"),
]


@pytest.mark.parametrize("layout,max_seqlen", FLASH_CASES)
def test_flash_fwd_compiles(compiled_kernels, one_chip, layout, max_seqlen):
    _compile(_flash(layout, max_seqlen), *_flash_specs(layout, T_TRAIN, one_chip))


@pytest.mark.parametrize("layout,max_seqlen", FLASH_CASES)
def test_flash_bwd_compiles(compiled_kernels, one_chip, layout, max_seqlen):
    attn = _flash(layout, max_seqlen)

    def loss(q, k, v, seg):
        return jnp.sum(attn(q, k, v, seg).astype(jnp.float32))

    _compile(
        jax.grad(loss, argnums=(0, 1, 2)),
        *_flash_specs(layout, T_TRAIN, one_chip),
    )


@pytest.mark.slow  # ~30 s alone; the tier-1 cases keep the file near a minute
def test_flash_long_context_block_compiles(compiled_kernels, one_chip):
    """Block 1024 is the default at T >= 8192 (ops/attention.py)."""
    attn = _flash(QWEN_1P5B, None, block=1024)

    def loss(q, k, v, seg):
        return jnp.sum(attn(q, k, v, seg).astype(jnp.float32))

    _compile(
        jax.grad(loss, argnums=(0, 1, 2)),
        *_flash_specs(QWEN_1P5B, 8192, one_chip),
    )


def _paged_specs(one_chip, *, page, int8, L=28, B=64, P=256, M=16,
                 layout=QWEN_1P5B):
    hq, hkv, d = layout["hq"], layout["hkv"], layout["d"]
    specs = [
        _spec((B, hq, d), jnp.bfloat16, one_chip),
        _spec((B, hkv, d), jnp.bfloat16, one_chip),
        _spec((B, hkv, d), jnp.bfloat16, one_chip),
        _spec(
            (L, P, 2, hkv, page, d), jnp.int8 if int8 else jnp.bfloat16,
            one_chip,
        ),
        _spec((), jnp.int32, one_chip),
        _spec((B, M), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip),
    ]
    if int8:
        specs.append(_spec((L, P, 2, hkv, page), jnp.float32, one_chip))
    return specs


@pytest.mark.parametrize(
    "page,int8,shape",
    [
        pytest.param(128, False, {}, id="bf16-page128"),
        pytest.param(64, False, {}, id="bf16-page64"),
        pytest.param(128, True, {}, id="int8-page128"),
        # the rollout cells' calls, a table of 40 pages in 5 blocks of 8
        # (the OLMoE cell's is the case below)
        pytest.param(128, False, dict(B=128, M=40, P=2588),
                     id="cell1-128x12q2kv-table40"),
        pytest.param(128, False,
                     dict(B=64, M=40, P=1311, L=16, layout=QWEN_7B),
                     id="cell3-64x28q4kv-table40"),
    ],
)
def test_paged_decode_compiles(compiled_kernels, one_chip, page, int8, shape):
    from areal_tpu.ops.pallas import paged_attention as pl_paged

    def f(q, ks, vs, pages, layer, table, lens, *scales):
        return pl_paged.decode(
            q, ks, vs, pages, layer, table, lens,
            scales=scales[0] if scales else None,
        )

    _compile(f, *_paged_specs(one_chip, page=page, int8=int8, **shape))


def test_paged_decode_compiles_at_16_kv_heads(compiled_kernels, one_chip):
    """The OLMoE cell's shape: 64 slots, 16q/16kv x 128 (n_rep 1), page
    128, a table of 32 pages. The block plan leaves one slot a grid step
    (8 pages x 16 heads x 128 x 128 x 2 B x K and V, double-buffered, is
    exactly the 16 MiB the plan allows), and Mosaic takes it."""
    from areal_tpu.ops.pallas import paged_attention as pl_paged

    assert pl_paged.block_plan(64, 16, 128, 128, 32, jnp.bfloat16) == (1, 8)
    _compile(
        pl_paged.decode,
        *_paged_specs(one_chip, page=128, int8=False, L=8, P=715, M=32,
                      layout=OLMOE),
    )


def test_int8_page64_turned_away_by_the_gate(compiled_kernels):
    """An int8 pool's scale stripe has the page as its last dimension and
    Mosaic wants that aligned to the 128-lane tiling: both gates say so in
    their own words instead of leaving it to a compiler error."""
    from areal_tpu.ops.pallas import paged_attention as pl_paged

    assert pl_paged.page_multiple(jnp.int8) == 128
    assert pl_paged.page_multiple(jnp.bfloat16) == 8
    hq, hkv, d = QWEN_1P5B["hq"], QWEN_1P5B["hkv"], QWEN_1P5B["d"]
    B, L, P, M, page = 8, 2, 16, 4, 64
    with pytest.raises(ValueError, match="page%128"):
        jax.eval_shape(
            functools.partial(pl_paged.decode),
            jax.ShapeDtypeStruct((B, hq, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((B, hkv, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((B, hkv, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((L, P, 2, hkv, page, d), jnp.int8),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((B, M), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            scales=jax.ShapeDtypeStruct((L, P, 2, hkv, page), jnp.float32),
        )


@pytest.mark.parametrize(
    "V,E", [pytest.param(151936, 1536, id="1p5b"),
            pytest.param(32768, 768, id="125m")],
)
def test_fused_sample_compiles(compiled_kernels, one_chip, V, E):
    from areal_tpu.ops.pallas.fused_sample import fused_sample_pallas

    R = 64

    def f(x, w, temperature, greedy):
        return fused_sample_pallas(
            jax.random.key(0), x, w, temperature, greedy
        )

    _compile(
        f,
        _spec((R, E), jnp.bfloat16, one_chip),
        _spec((E, V), jnp.bfloat16, one_chip),
        _spec((R,), jnp.float32, one_chip),
        _spec((R,), jnp.bool_, one_chip),
    )


# ------------------------------------------------------------------ #
# JoyAI-LLM-Flash cut to five layers (benchmark/configs/joyai-flash-l5):
# the latent decode kernel alone, then the engine's two programs whole
# ------------------------------------------------------------------ #

# the cell joyai-flash-l5.rollout: 256 slots, a table of 72 pages of 128,
# 5,968 pages of one 640-wide latent row a token a layer
JOYAI_CELL = dict(B=256, M=72, P=5968, L=5, H=32, W=640, DV=512)


def test_mla_decode_compiles(compiled_kernels, one_chip):
    """The paged kernel over ONE stream that is key and value: 32 query
    rows of 640 (576 + padding) on latent pages ``[128, 640]``, values the
    first 512. The plan is 4 slots x 8 pages a step (9.4 MiB of scratch);
    Mosaic takes the 640-wide row whole and refuses a 576-wide one
    ("Slice shape along dimension 5 must be aligned to tiling (128)")."""
    from areal_tpu.ops.pallas import paged_attention as pl_paged

    c = JOYAI_CELL
    assert pl_paged.block_plan(
        c["B"], 1, c["W"], 128, c["M"], jnp.bfloat16, streams=1) == (4, 8)

    def f(q, lat, pages, layer, table, lens):
        return pl_paged.decode(
            q, lat, None, pages, layer, table, lens,
            softmax_scale=192 ** -0.5, value_width=c["DV"])

    compiled = _compile(
        f,
        _spec((c["B"], c["H"], c["W"]), jnp.bfloat16, one_chip),
        _spec((c["B"], 1, c["W"]), jnp.bfloat16, one_chip),
        _spec((c["L"], c["P"], 1, 1, 128, c["W"]), jnp.bfloat16, one_chip),
        _spec((), jnp.int32, one_chip),
        _spec((c["B"], c["M"]), jnp.int32, one_chip),
        _spec((c["B"],), jnp.int32, one_chip),
    )
    assert "mla_decode" in compiled.as_text()


@pytest.fixture(scope="module")
def joyai_engine():
    """The engine of the cell at its real configuration, with placeholder
    weights and a pool of a few pages: its programs are built from the
    configuration alone and are lowered below on SHAPES of the real size
    (11 GB of weights and a 4.9 GB pool are never allocated)."""
    import json

    import numpy as np

    from areal_tpu.gen.engine import GenerationEngine
    from benchmark import sut

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark", "configs", "joyai-flash-l5.json")) as f:
        arch = json.load(f)
    cfg = sut.model_config(arch, {})
    shapes = sut.weight_shapes(cfg, cfg.dtype)
    eng = GenerationEngine(
        cfg, jax.tree.map(lambda s: np.zeros((1,), s.dtype), shapes),
        max_slots=JOYAI_CELL["B"], max_seqlen=9216, max_new_tokens_cap=8192,
        page_size=128, n_pages=80, seed=0)
    eng._decode_use_pallas = True
    return eng, shapes


def _joyai_program_specs(eng, shapes, one_chip):
    import dataclasses

    from areal_tpu.models import transformer as tfm

    def spec(a):
        return _spec(a.shape, a.dtype, one_chip)

    state = jax.tree.map(spec, eng.state)
    pages = eng.state.cache.pages
    state = dataclasses.replace(state, cache=tfm.PagedKVCache(pages=_spec(
        (pages.shape[0], JOYAI_CELL["P"]) + pages.shape[2:], pages.dtype,
        one_chip)))
    return jax.tree.map(spec, shapes), state


@pytest.mark.parametrize("program", ["jit_chunk", "jit_extend"])
def test_joyai_engine_programs_compile(
        compiled_kernels, one_chip, joyai_engine, program):
    """``jit_chunk`` (16 decode steps over the latent pool at 256 slots and
    the full table: two scans, ``mla_decode``, the 256-expert dispatch, the
    129k-vocabulary head) and ``jit_extend`` (an admission wave of 8 x 128
    tokens against the pool) for a described v5e, beside 11.1 GB of weights
    and the cell's pool: arguments + temporaries under the chip's 16.9e9."""
    eng, shapes = joyai_engine
    params, state = _joyai_program_specs(eng, shapes, one_chip)
    B, M = JOYAI_CELL["B"], JOYAI_CELL["M"]
    if program == "jit_chunk":
        fn = eng._chunk_fn(16, M, 0, fused=False, with_topk=False)
        args = (_spec((B, M), jnp.int32, one_chip),
                _spec((0,), jnp.int32, one_chip))
    else:
        n, W, C = 8, 32, eng.admit_chunk
        fn = eng._extend_fn(n, W, skip_pool=False)
        args = (_spec((n, C), jnp.int32, one_chip),
                _spec((n, W), jnp.int32, one_chip),
                _spec((n,), jnp.int32, one_chip),
                _spec((n,), jnp.int32, one_chip))
    compiled = fn.lower(params, state, *args).compile()
    text = compiled.as_text()
    assert ("mla_decode" in text) == (program == "jit_chunk")
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.6e9
