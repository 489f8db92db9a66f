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
