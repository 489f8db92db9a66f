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

import collections
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
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
# granite-4.0-h-micro's attention layers in the pool's geometry: a head of
# 64 is half a lane tile, so two kv heads share a row (kv_heads_per_row)
GRANITE_ROWS = dict(hq=32, hkv=4, d=128)
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
    """The harness's fixture at module scope: an executable for the
    described v5e is never written to, or read from, the run's cache."""
    from conftest import persistent_cache_off

    with persistent_cache_off():
        yield


@pytest.fixture
def compiled_kernels(monkeypatch, no_persistent_cache):
    """Steer the three kernel modules off interpret mode (on the CPU their
    `_interpret()` says True) for the duration of one test."""
    from areal_tpu.ops.pallas import flash_attention, fused_sample
    from areal_tpu.ops.pallas import kda_decode, kv_page_write
    from areal_tpu.ops.pallas import moe_grouped, ssm_decode
    from areal_tpu.ops.pallas import paged_attention as pl_paged

    for mod in (flash_attention, fused_sample, pl_paged, kv_page_write,
                moe_grouped, ssm_decode, kda_decode):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    # ... and the fused epilogue's dispatch (and the engine's rule) off the
    # CPU they would see: a chunk built with ``fused=True`` ends in the
    # kernel, as on the chip, not in the streamed XLA pass
    from areal_tpu.ops import fused_sample as fused_ops
    from areal_tpu.ops import moe as moe_ops

    monkeypatch.setattr(fused_ops, "_platform", lambda: "tpu")
    # ... and the routed experts' (``moe_grouped_applies``): a program that
    # hands them enough rows holds the grouped-matmul kernel
    monkeypatch.setattr(moe_ops, "_platform", lambda: "tpu")


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _custom_call_names(text: str) -> set:
    """The names of a compiled program's Mosaic calls (``%name.3 = ...
    custom-call(...), custom_call_target="tpu_custom_call"``), without the
    number XLA gives the second of a name."""
    return {
        re.sub(r"\.\d+$", "", m.group(1))
        for m in re.finditer(
            r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
            text)
    }


def _flash_specs(layout, T, one_chip):
    hq, hkv, d = layout["hq"], layout["hkv"], layout["d"]
    return (
        _spec((T, hq, d), jnp.bfloat16, one_chip),
        _spec((T, hkv, d), jnp.bfloat16, one_chip),
        _spec((T, hkv, d), jnp.bfloat16, one_chip),
        _spec((T,), jnp.int32, one_chip),
    )


def _flash(layout, max_seqlen, block=None):
    """The kernels at the blocks their own rule takes from the shapes
    (``block=None``: what a model with no override compiles)."""
    from areal_tpu.ops.pallas.flash_attention import packed_flash_attention

    return functools.partial(
        packed_flash_attention, softmax_scale=layout["d"] ** -0.5,
        block_size=block, max_seqlen=max_seqlen,
    )


FLASH_CASES = [
    pytest.param(QWEN_1P5B, 512, id="1p5b-band512"),
    # the train cell's calls: no static bound, the rule's short-row blocks
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
    """Block 1024 is the rule's choice at T >= 8192 (pinned below)."""
    attn = _flash(QWEN_1P5B, None)

    def loss(q, k, v, seg):
        return jnp.sum(attn(q, k, v, seg).astype(jnp.float32))

    _compile(
        jax.grad(loss, argnums=(0, 1, 2)),
        *_flash_specs(QWEN_1P5B, 8192, one_chip),
    )


@pytest.mark.parametrize("T,layout", [
    pytest.param(8192, QWEN_1P5B, id="1p5b-8k"),
    pytest.param(32768, QWEN_1P5B, id="1p5b-32k"),
    pytest.param(8192, PRESET_125M, id="125m-8k"),
    pytest.param(16384, OLMOE, id="olmoe-16k"),
])
@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
def test_flash_rule_keeps_long_rows_blocks(T, layout, backward):
    """A row of 8,192 tokens or more takes 1,024 x 1,024 and the
    specialised (masked / interior) bodies in both directions, as it did
    before the rule read shapes (PR 58)."""
    from areal_tpu.ops.pallas.flash_attention import flash_blocks

    assert flash_blocks(
        T, layout["hq"] // layout["hkv"], backward=backward,
    ) == (1024, 1024, True)


@pytest.mark.parametrize("bound", [
    pytest.param(dict(max_seqlen=512), id="max_seqlen"),
    pytest.param(dict(sliding_window=1024), id="window"),
    pytest.param(dict(max_seqlen=2048, sliding_window=512), id="both"),
])
@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
def test_flash_rule_keeps_bounded_rows_blocks(bound, backward):
    """A short row whose band a static ``max_seqlen`` or a window bounds
    keeps 512 x 512 and the one masked body: no other size was measured
    for it (PERF.md section 6, PR 58)."""
    from areal_tpu.ops.pallas.flash_attention import flash_blocks

    assert flash_blocks(
        T_TRAIN, 6, backward=backward, **bound
    ) == (512, 512, False)


def test_flash_rule_short_rows_and_overrides():
    """The train cell's calls (4,096 tokens, no bound): the forward's and
    the fused backward's own blocks, the one masked body; a model's
    override goes to both directions and is halved until it divides the
    row."""
    from areal_tpu.ops.pallas.flash_attention import flash_blocks

    assert flash_blocks(T_TRAIN, 6) == (256, 1024, False)
    assert flash_blocks(T_TRAIN, 6, backward=True) == (256, 256, False)
    # fewer than three heads a kv head: q blocks of 512, so a tile keeps
    # its rows
    assert flash_blocks(T_TRAIN, 1) == (512, 1024, False)
    assert flash_blocks(T_TRAIN, 1, backward=True) == (512, 512, False)
    for backward in (False, True):
        assert flash_blocks(
            T_TRAIN, 6, block_q=512, backward=backward) == (512, 512, False)
        assert flash_blocks(
            T_TRAIN, 6, block_q=512, block_k=128, backward=backward
        ) == (512, 128, False)
        assert flash_blocks(
            3072, 6, block_q=2048, backward=backward) == (1024, 1024, False)
    assert flash_blocks(768, 6) == (256, 256, False)


@pytest.mark.parametrize("over", [
    pytest.param({}, id="rule"),
    pytest.param(dict(attn_max_seqlen=512), id="max_seqlen"),
    pytest.param(dict(flash_block_size=512, flash_block_size_k=256),
                 id="override"),
    pytest.param(dict(sliding_window=1024), id="window"),
])
def test_flash_counter_and_wrapper_ask_for_the_same_blocks(monkeypatch, over):
    """The trainer's count on the ``train_pipe/pack`` record and the
    kernels' wrapper get their blocks from ONE function, with the same
    arguments for the same model: the count is of the list the forward
    kernel will walk."""
    from areal_tpu.models.config import ModelConfig
    from areal_tpu.ops import attention as attn_ops
    from areal_tpu.ops.pallas import flash_attention as fa
    from areal_tpu.train import batching
    from areal_tpu.train.engine import TrainEngine

    cfg = ModelConfig(
        n_layers=1, n_q_heads=12, n_kv_heads=2, head_dim=128,
        hidden_dim=1536, intermediate_dim=64, vocab_size=64,
        dtype="bfloat16", use_flash_attention=True, **over)
    asked = []
    rule = fa.flash_blocks

    def spy(*args, **kwargs):
        out = rule(*args, **kwargs)  # the forward's call is the one counted
        if not kwargs.pop("backward", False):
            asked.append((args, kwargs, out))
        return out

    monkeypatch.setattr(fa, "flash_blocks", spy)
    monkeypatch.setattr(fa, "_flash_thd", lambda q, *a: q)
    T = T_TRAIN
    jax.eval_shape(
        lambda q, k, v, seg: attn_ops.packed_attention(
            q, k, v, seg, sliding_window=cfg.sliding_window, use_flash=True,
            flash_block_size=cfg.flash_block_size,
            flash_block_size_k=cfg.flash_block_size_k,
            max_seqlen=cfg.attn_max_seqlen),
        jax.ShapeDtypeStruct((T, 12, 128), jnp.bfloat16),
        jax.ShapeDtypeStruct((T, 2, 128), jnp.bfloat16),
        jax.ShapeDtypeStruct((T, 2, 128), jnp.bfloat16),
        jax.ShapeDtypeStruct((T,), jnp.int32),
    )
    from areal_tpu.api.data import SequenceSample

    packed = batching.pack_sequences(
        SequenceSample.from_default(
            ids=[0, 1], seqlens=[3000, 700],
            data={"packed_input_ids": np.zeros(3700, np.int64)}),
        1, capacity=T)
    eng = TrainEngine.__new__(TrainEngine)
    eng.cfg = cfg
    counts = eng._flash_pair_counts([packed])
    wrapper, counter = asked
    assert wrapper == counter
    assert counts["flash_pairs"] > 0


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
        # granite-4.0-h-micro: 32q / 8kv x 64 as the kernel sees them, two
        # kv heads to a 128-lane row (4 x 128), its four attention layers
        pytest.param(128, False,
                     dict(B=80, M=40, P=1621, L=4, layout=GRANITE_ROWS),
                     id="granite-80x32q4rows-table40"),
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


@pytest.mark.parametrize(
    "shape",
    [
        pytest.param(dict(page=128, B=128, M=40, P=2588),
                     id="cell1-128x12q2kv-table40"),
        pytest.param(dict(page=128, B=64, M=32, P=715, L=8, layout=OLMOE),
                     id="olmoe-64x16q16kv-table32"),
        pytest.param(dict(page=64, B=72, M=64, P=600, L=32, layout=OLMOE),
                     id="ouro-72x16q16kv-page64"),
        pytest.param(dict(page=128, B=64, M=40, P=1311, L=16, layout=QWEN_7B),
                     id="cell3-64x28q4kv-table40"),
    ],
)
def test_shared_prefix_programs_compile(compiled_kernels, one_chip, shape):
    """What a full-attention layer runs where rows of the call name the
    same pages (``ops/paged_attention.py:shared_prefix_step``): the plan
    from the table, the ``paged_decode_prefix`` program over the blocks'
    folded queries, and ``paged_decode`` over the rows' own pages from the
    state it leaves, at the rollout cells' geometries. Every Mosaic call of
    it carries a name the benchmark's rooflines find (``^paged_decode``)."""
    from areal_tpu.ops import paged_attention as paged_ops

    def f(q, ks, vs, pages, layer, table, lens):
        page = pages.shape[4]
        plan, own_table, own_lens = paged_ops.shared_prefix_step(
            table, lens, lens > 0, page)
        order = jnp.argsort(own_lens)
        inverse = jnp.argsort(order)
        prefix = paged_ops.prefix_pass(plan, table, page, order, inverse)
        return paged_ops.paged_decode_attention(
            q[order], ks[order], vs[order], pages, layer, own_table[order],
            own_lens[order], shared=prefix, use_pallas=True)[inverse]

    text = _compile(
        f, *_paged_specs(one_chip, int8=False, **shape)).as_text()
    assert _custom_call_names(text) == {"paged_decode", "paged_decode_prefix"}


def test_paged_decode_compiles_at_16_kv_heads(compiled_kernels, one_chip):
    """The OLMoE cell's shape: 64 slots, 16q/16kv x 128 (n_rep 1), page
    128, a table of 32 pages. The block plan leaves one slot a grid step
    (8 pages x 16 heads x 128 x 128 x 2 B x K and V, double-buffered, is
    exactly the 16 MiB the plan allows), and Mosaic takes it."""
    from areal_tpu.ops.pallas import paged_attention as pl_paged

    # (one slot is what 8 pages a step leave room for; the full-attention
    # program's step is 4)
    assert pl_paged.block_plan(64, 16, 128, 128, 32, jnp.bfloat16) == (1, 4)
    assert pl_paged.block_plan(
        64, 16, 128, 128, 32, jnp.bfloat16, windowed=True) == (1, 8)
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


@pytest.mark.parametrize("B,G,R,P,N", [
    pytest.param(80, 1, 64, 64, 128, id="granite-80-slots"),
    # two groups, the pairs not whole phases of 8, a state of two tiles
    pytest.param(5, 2, 8, 32, 256, id="two-groups-ragged-phase"),
])
def test_ssm_decode_compiles(compiled_kernels, one_chip, B, G, R, P, N):
    """The state update's kernel at granite-4.0-h-micro's sizes and the
    cell's 80 slots (phases of 8 rows of ``[32, 128, 128]``: a ring of 32
    MiB), the state of all 36 layers donated: the result IS the argument
    (aliased) and the program holds no second state."""
    from areal_tpu.ops.pallas import ssm_decode

    Ls, K = 36, R * P // 128
    f32 = lambda *shape: _spec(shape, jnp.float32, one_chip)
    compiled = jax.jit(ssm_decode.ssm_decode, donate_argnums=(0,)).lower(
        f32(Ls, B, G, K, N, 128), _spec((), jnp.int32, one_chip),
        f32(B, G, R, P), f32(B, G, R), f32(G, R), f32(B, G, N), f32(B, G, N),
        f32(G, R), _spec((B,), jnp.bool_, one_chip),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ssm_decode" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * Ls * B * G * K * N * 128
    assert mem.temp_size_in_bytes < 0.05e9


@pytest.mark.parametrize("n_rows,width", [(1, 40), (8, 16)])
def test_granite_admission_re_lays_no_state(
        compiled_kernels, one_chip, monkeypatch, n_rows, width):
    """The granite cell's admission program (the chunked scan continuing
    ``n_rows`` slots' state, all 36 + 4 layers) at 16 slots: it READS the
    per-slot state, so nothing in it may result in an array of the
    state's size. With all 4,096 channels as the minor axis the chip's
    compiler cut the whole state in two to gather a few rows of it, and a
    one-row gather, which it makes a slice, had it re-lay the whole array
    out for the scan's matmuls (PERF.md §6 PR 42)."""
    import json

    from areal_tpu.gen.engine import GenerationEngine
    from areal_tpu.ops.pallas import ssm_decode
    from benchmark import sut

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark", "configs", "granite-4.0-h-micro.json")) as f:
        cfg = sut.model_config(json.load(f), {})
    assert ssm_decode.ssm_decode_applies(cfg, None, "tpu")
    shapes = sut.weight_shapes(cfg, cfg.dtype)
    B = 16
    eng = GenerationEngine(
        cfg, jax.tree.map(lambda s: np.zeros((1,), s.dtype), shapes),
        max_slots=B, max_seqlen=5120, max_new_tokens_cap=4096,
        page_size=128, n_pages=80, state_snapshots=2, seed=0)
    whole = eng.state.ssm.ssm.shape
    assert whole == (36, B, 1, 32, 128, 128)

    def spec(a):
        return _spec(a.shape, a.dtype, one_chip)

    i32 = lambda *shape: _spec(shape, jnp.int32, one_chip)
    compiled = eng._extend_fn(n_rows, width, skip_pool=False).lower(
        jax.tree.map(spec, shapes), jax.tree.map(spec, eng.state),
        i32(n_rows, eng.admit_chunk), i32(n_rows, width), i32(n_rows),
        i32(n_rows), i32(n_rows),
    ).compile()
    sized = "f32[" + ",".join(map(str, whole)) + "]"
    made = [ln.strip()[:120] for ln in compiled.as_text().split("\n")
            if f"= {sized}" in ln and " parameter(" not in ln
            and " get-tuple-element(" not in ln]
    assert not made, made
    # (8 rows' chunk of 128 tokens: 0.55 GB of float32 activations)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.7e9


def _assert_one_token_convolution(text, layers, B, C):
    """A compiled decode chunk runs the state-space mixer's convolution
    over the stacked FLAT state ``bf16[layers, B, 3 x C]``
    (``ops/ssm.py:conv_step``): no line results in a layer's state with
    its 3 taps as an axis of their own (``[B, 3, C]``, which the chip's
    compiler re-lays out with the 3 on the sublanes and pads to ``[B, 4,
    C]``; in any layout), and no ``gather`` reads the convolution's state
    (the next state as a slice at a start a row). Before PR 54 both stood
    in the step of every state-space layer."""
    assert f"bf16[{layers},{B},{3 * C}]" in text
    lines = text.split("\n")
    axis = re.compile(rf"= bf16\[{B},[34],{C}\]")
    made = [ln.strip()[:120] for ln in lines if axis.search(ln)]
    assert not made, made
    state = re.compile(rf"bf16\[(\d+,)?{B},({3 * C}|[34],{C})\]")
    gathers = [ln.strip()[:160] for ln in lines
               if " gather(" in ln and state.search(ln)]
    assert not gathers, gathers


def test_granite_decode_chunk_ends_in_the_fused_kernel(
        compiled_kernels, one_chip, monkeypatch):
    """The granite cell's decode chunk in small (one state-space and one
    attention layer at the model's widths, the cell's 80 slots and 16
    steps): ``ssm_decode``, ``paged_decode`` and ``kv_page_write`` are in
    it, and the step ends in ``fused_sample`` over the tied embedding as
    stored with its logits divided by 8 inside the kernel: no ``[80,
    100352]`` array in any dtype, no transposed copy of the embedding."""
    import dataclasses
    import json

    from areal_tpu.gen.engine import GenerationEngine
    from areal_tpu.models import transformer as tfm
    from areal_tpu.ops.pallas import ssm_decode
    from benchmark import sut

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark", "configs", "granite-4.0-h-micro.json")) as f:
        arch = json.load(f)
    arch.update(num_hidden_layers=2, layer_types=["mamba", "attention"])
    cfg = sut.model_config(arch, {})
    assert cfg.tied_embedding and cfg.logits_scaling == 8.0
    shapes = sut.weight_shapes(cfg, cfg.dtype)
    B = 80
    eng = GenerationEngine(
        cfg, jax.tree.map(lambda s: np.zeros((1,), s.dtype), shapes),
        max_slots=B, max_seqlen=5120, max_new_tokens_cap=4096,
        page_size=128, n_pages=80, state_snapshots=2, seed=0)
    eng._decode_use_pallas = True
    assert eng.fused       # the rule, on what the fixture describes
    # the state update's rule asks the first device itself: here the CPU
    assert ssm_decode.ssm_decode_applies(cfg, None, "tpu")
    monkeypatch.setattr(eng, "_ssm_update", lambda: ssm_decode.ssm_decode)

    def spec(a):
        return _spec(a.shape, a.dtype, one_chip)

    pages = eng.state.cache.pages
    state = dataclasses.replace(
        jax.tree.map(spec, eng.state),
        cache=tfm.PagedKVCache(pages=_spec(
            (pages.shape[0], 1600) + pages.shape[2:], pages.dtype, one_chip)))
    text = eng._chunk_fn(16, eng.M, 0, fused=True, with_topk=False).lower(
        jax.tree.map(spec, shapes), state,
        _spec((B, eng.M), jnp.int32, one_chip),
        _spec((0,), jnp.int32, one_chip),
    ).compile().as_text()
    for kernel in ("ssm_decode", "paged_decode", "kv_page_write"):
        assert re.search(rf"%{kernel}(\.\d+)? = ", text), kernel
    _assert_fused_epilogue(text, B, cfg.vocab_size)
    assert f"bf16[{cfg.hidden_dim},{cfg.vocab_size}]" not in text
    _assert_one_token_convolution(text, 1, B, cfg.ssm.conv_dim)


# the rollout cells' decode epilogue: slots x hidden x vocabulary
FUSED_SAMPLE_CELLS = {
    "cell1": (128, 1536, 151936),
    "cell3": (64, 3584, 152064),
    "olmoe": (64, 2048, 50304),
    "joyai": (256, 2048, 129280),
    "smallthinker": (112, 2560, 151936),
}


# the two cells whose head is the embedding: the kernel's operand is the
# tree's [V, E] array itself; granite divides its logits by 8
FUSED_SAMPLE_TIED_CELLS = {
    "granite": (80, 2048, 100352, 8.0),
    "zaya": (256, 2048, 262272, 1.0),
}


@pytest.mark.parametrize(
    "R,E,V,vocab_rows,logits_scale",
    [pytest.param(*shape, False, 1.0, id=cell)
     for cell, shape in FUSED_SAMPLE_CELLS.items()]
    + [pytest.param(64, 768, 32768, False, 1.0, id="125m")]
    + [pytest.param(*shape[:3], True, shape[3], id=cell)
       for cell, shape in FUSED_SAMPLE_TIED_CELLS.items()],
)
def test_fused_sample_compiles(
        compiled_kernels, one_chip, R, E, V, vocab_rows, logits_scale):
    """The head-and-sample kernel as the decode chunk calls it at every
    rollout cell's ``(R, E, V)``:
    a block of 2048 columns everywhere (3584 rows of bf16 are 14.7 MB a
    buffer), the chip's PRNG for the uniforms. A tied head is the
    embedding as stored, ``[V, E]``, streamed in row blocks (granite's
    logits divided by 8 on the way): the program holds no ``[E, V]`` copy
    of it."""
    from areal_tpu.ops.pallas import fused_sample as fsk

    assert fsk.block_columns(R, E, V, 2) == 2048

    def f(x, w, temperature, greedy):
        return fsk.fused_sample_pallas(
            jax.random.key(0), x, w, temperature, greedy,
            vocab_rows=vocab_rows, logits_scale=logits_scale,
        )

    text = _compile(
        f,
        _spec((R, E), jnp.bfloat16, one_chip),
        _spec((V, E) if vocab_rows else (E, V), jnp.bfloat16, one_chip),
        _spec((R,), jnp.float32, one_chip),
        _spec((R,), jnp.bool_, one_chip),
    ).as_text()
    assert "fused_sample" in text
    assert f"[{R},{V}]" not in text
    if vocab_rows:
        assert f"bf16[{E},{V}]" not in text


# sha256[:16] of the kernel's Mosaic module (its MLIR without locations)
# with an [E, V] head, taken on PR 45's tree: cell 1's shape and JoyAI's
EV_KERNEL_MODULES = {
    (128, 1536, 151936): "91d7126640d10999",
    (256, 2048, 129280): "cad458fe11a9c090",
}


def ev_kernel_module_hash(R, E, V):
    """The hash above, of this tree: the module ``pallas_call`` hands to
    the chip's compiler for ``(R, E, V)``, read back out of the lowered
    program and printed without source locations (so it moves only with
    what the kernel computes, not with the lines it is written on)."""
    import base64
    import hashlib

    from jaxlib.mlir import ir

    from areal_tpu.ops.pallas import fused_sample as fsk

    def f(x, w, temperature, greedy):
        return fsk.fused_sample_pallas(
            jax.random.key(0), x, w, temperature, greedy)

    lowered = jax.jit(f).trace(
        jax.ShapeDtypeStruct((R, E), jnp.bfloat16),
        jax.ShapeDtypeStruct((E, V), jnp.bfloat16),
        jax.ShapeDtypeStruct((R,), jnp.float32),
        jax.ShapeDtypeStruct((R,), jnp.bool_),
    ).lower(lowering_platforms=("tpu",))
    # backend_config = "{\22custom_call_config\22: {\22body\22: \22<base64>\22
    (body,) = re.findall(
        r'\\22body\\22: \\22([^\\]+)\\22', lowered.as_text())
    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True   # the serialised dialect
    with ctx:
        module = ir.Module.parse(base64.b64decode(body))
        text = module.operation.get_asm(enable_debug_info=False)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize(
    "R,E,V", [pytest.param(*shape, id="x".join(map(str, shape)))
              for shape in EV_KERNEL_MODULES])
def test_ev_head_kernel_is_the_module_it_was(compiled_kernels, R, E, V):
    """The six cells with an untied head run the kernel they ran before it
    learnt the ``[V, E]`` layout and ``logits_scale``: with neither, the
    module handed to the chip's compiler is PR 45's, operation for
    operation. (A deliberate change to the kernel's body changes these:
    take the new hashes then, and measure the six cells.)"""
    assert ev_kernel_module_hash(R, E, V) == EV_KERNEL_MODULES[(R, E, V)]


def _assert_fused_epilogue(text, B, V):
    """A chunk program whose steps end in the fused kernel: the kernel is
    in it, and nothing of the logits' shape is (no ``[B, V]`` buffer in
    any dtype, so no head matmul for XLA to compute twice)."""
    assert re.search(r"%fused_sample(\.\d+)? = ", text)
    assert f"[{B},{V}]" not in text


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


# ------------------------------------------------------------------ #
# kv_page_write: fresh K/V into the pool by tile copies, in place
# ------------------------------------------------------------------ #

# the rollout cells' pools [L, P, S, H, 128, W], slots and table widths
KV_WRITE_CELLS = {
    "cell1": dict(L=28, P=2588, S=2, H=2, W=128, B=128, M=40,
                  config="r1d-qwen-1p5b", seqlen=5120, out=4096),
    "cell3": dict(L=16, P=1311, S=2, H=4, W=128, B=64, M=40,
                  config="r1d-qwen-7b-l16", seqlen=5120, out=4096),
    "olmoe": dict(L=8, P=1072, S=2, H=16, W=128, B=64, M=32,
                  config="olmoe-1b-7b-l8", seqlen=4096, out=3072),
    "joyai": dict(L=5, P=5967, S=1, H=1, W=640, B=256, M=72),
    # Ouro-2.6B-l8: 4 passes x 8 layers = 32 CACHE layers behind 8 layers of
    # weights, pages of 64 (33.5 MB a page of 128 wastes too much a slot)
    "ouro": dict(L=32, P=858, S=2, H=16, W=128, B=72, M=40, page=64,
                 config="ouro-2p6b-l8", seqlen=2560, out=2048),
    # granite-4.0-h-micro: 4 attention layers, 8 kv heads of 64 as 4 rows
    "granite": dict(L=4, P=1621, S=2, H=4, W=128, B=80, M=40),
}


@pytest.mark.parametrize(
    "cell,rows,chunk",
    [
        # a decode step: one token a slot (one 16-row slab a layer and slot)
        ("cell1", None, 1), ("cell3", None, 1), ("olmoe", None, 1),
        ("joyai", None, 1), ("ouro", None, 1), ("granite", None, 1),
        # an admission wave of 8 x 128 tokens at the two ends: 128 KB
        # slabs, and a latent row of five lane tiles (its window of fresh
        # rows starts between tiles: Mosaic takes that one lane tile wide)
        ("olmoe", 8, 128), ("joyai", 8, 128),
    ],
)
def test_kv_page_write_compiles(compiled_kernels, one_chip, cell, rows, chunk):
    """The write kernel alone at the cells' pools, the pool donated: the
    result IS the argument (aliased), and the program holds no second
    pool (temporaries: the admission wave's rows in f32, nothing else)."""
    from areal_tpu.ops.pallas import kv_page_write

    c = KV_WRITE_CELLS[cell]
    B = rows or c["B"]
    pool = (c["L"], c["P"], c["S"], c["H"], c.get("page", 128), c["W"])
    compiled = jax.jit(kv_page_write.write, donate_argnums=(0,)).lower(
        _spec(pool, jnp.bfloat16, one_chip),
        _spec((c["L"], B, chunk, c["S"], c["H"], c["W"]), jnp.bfloat16,
              one_chip),
        _spec((B, c["M"]), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "kv_page_write" in text
    mem = compiled.memory_analysis()
    pool_bytes = 2 * int(np.prod(pool))
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 0.4e9 < pool_bytes


@pytest.mark.parametrize(
    "cell,fused",
    [("cell1", True), ("cell3", True), ("olmoe", True),
     # a looped stack: both scans, the pool's 32 cache layers behind 8
     # layers of weights, the stack not copied for the inner scan
     ("ouro", True),
     # the materialised epilogue, which a mesh, a tied head and every
     # platform but a TPU keep (``fused_sample_applies``)
     ("cell3", False)],
)
def test_kv_cells_chunk_writes_the_pool_in_place(
        compiled_kernels, one_chip, cell, fused):
    """``jit_chunk`` of the three K/V rollout cells (16 decode steps at
    the cell's slots and table, the engine's own program with its state
    donated): ``paged_decode`` and ``kv_page_write`` are both in it, no
    scatter over the pool is, and arguments + temporaries leave no room
    for a second pool (9.5 / 5.5 / 9.0 GB beside the weights). As the cell
    runs it the step ends in ``fused_sample`` and holds no ``[B, V]``
    buffer."""
    import dataclasses
    import json

    from areal_tpu.gen.engine import GenerationEngine
    from areal_tpu.models import transformer as tfm
    from benchmark import sut

    c = KV_WRITE_CELLS[cell]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark", "configs", c["config"] + ".json")) as f:
        cfg = sut.model_config(json.load(f), {})
    shapes = sut.weight_shapes(cfg, cfg.dtype)
    eng = GenerationEngine(
        cfg, jax.tree.map(lambda s: np.zeros((1,), s.dtype), shapes),
        max_slots=c["B"], max_seqlen=c["seqlen"],
        max_new_tokens_cap=c["out"], page_size=c.get("page", 128),
        n_pages=80, seed=0)
    eng._decode_use_pallas = True
    assert eng.M == c["M"] and eng._kv_write_rows() == 16

    def spec(a):
        return _spec(a.shape, a.dtype, one_chip)

    pages = eng.state.cache.pages
    pool = (pages.shape[0], c["P"]) + pages.shape[2:]
    page = c.get("page", 128)
    assert pool == (c["L"], c["P"], c["S"], c["H"], page, c["W"])
    assert pages.shape[0] == cfg.cache_layers
    state = dataclasses.replace(
        jax.tree.map(spec, eng.state),
        cache=tfm.PagedKVCache(pages=_spec(pool, pages.dtype, one_chip)))
    compiled = eng._chunk_fn(16, c["M"], 0, fused=fused, with_topk=False).lower(
        jax.tree.map(spec, shapes), state,
        _spec((c["B"], c["M"]), jnp.int32, one_chip),
        _spec((0,), jnp.int32, one_chip),
    ).compile()
    text = compiled.as_text()
    assert "paged_decode" in text and "kv_page_write" in text
    # every attention call carries a name the benchmark's rooflines find
    # (``^paged_decode|^mla_decode``, benchmark/resident.py): the prefix
    # program over the pages rows share and the program over their own
    names = _custom_call_names(text)
    assert {"paged_decode", "paged_decode_prefix"} <= names
    assert all(
        re.match(r"paged_decode|mla_decode", n)
        for n in names - {"kv_page_write", "fused_sample"}), names
    assert eng.fused       # the rule, on what the fixture describes
    # OLMoE's 64 rows a step are under the ridge; the others have no router
    assert not re.search(r"%moe_grouped(\.\d+)? = ", text)
    if fused:
        _assert_fused_epilogue(text, c["B"], cfg.vocab_size)
    else:
        assert not re.search(r"%fused_sample(\.\d+)? = ", text)
        assert f"[{c['B']},{cfg.vocab_size}]" in text
    n_rows = int(np.prod(pool[:5]))
    assert f"bf16[{n_rows},128]" not in text     # the scatter's flat view
    mem = compiled.memory_analysis()
    pool_bytes = 2 * n_rows * 128
    weight_bytes = sum(
        2 * int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert mem.alias_size_in_bytes >= pool_bytes
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < weight_bytes + pool_bytes + 1.0e9)


def _assert_no_slice_of_the_routed_stack(text, cfg):
    """No instruction of the compiled program RESULTS in one layer's
    ``[X, E, F]`` or ``[X, F, E]`` of the routed stacks (what XLA makes of
    a dynamic slice it cannot fuse into its reader: a custom call's
    operand)."""
    X, E, F = cfg.moe.num_experts, cfg.hidden_dim, cfg.expert_dim
    for shape in (f"bf16[{X},{E},{F}]", f"bf16[{X},{F},{E}]"):
        assert not re.search(
            r"= (\()?" + re.escape(shape) + r"\{", text), shape


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


@pytest.mark.parametrize("program", ["jit_chunk", "jit_extend", "jit_write"])
def test_joyai_engine_programs_compile(
        compiled_kernels, one_chip, joyai_engine, program):
    """``jit_chunk`` (16 decode steps over the latent pool at 256 slots and
    the full table: two scans, ``mla_decode``, the 256-expert dispatch, the
    129k-vocabulary head inside ``fused_sample``), ``jit_extend`` (an admission wave of 8 x 128
    tokens against the pool, which it reads and does not write) and
    ``jit_write`` (the wave's fresh latents into the pool, the one program
    every admission bucket and table width shares) for a described v5e,
    beside 11.1 GB of weights and the cell's pool: arguments + temporaries
    under the chip's 16.9e9."""
    eng, shapes = joyai_engine
    params, state = _joyai_program_specs(eng, shapes, one_chip)
    B, M = JOYAI_CELL["B"], JOYAI_CELL["M"]
    n, W, C = 8, 32, eng.admit_chunk
    extend = eng._extend_fn(n, W, skip_pool=False)
    extend_args = (params, state,
                   _spec((n, C), jnp.int32, one_chip),
                   _spec((n, W), jnp.int32, one_chip),
                   _spec((n,), jnp.int32, one_chip),
                   _spec((n,), jnp.int32, one_chip))
    if program == "jit_chunk":
        fn = eng._chunk_fn(16, M, 0, fused=True, with_topk=False)
        args = (params, state, _spec((B, M), jnp.int32, one_chip),
                _spec((0,), jnp.int32, one_chip))
    elif program == "jit_extend":
        fn, args = extend, extend_args
    else:
        assert eng._kv_write_batch(1) == eng._kv_write_batch(n) == n
        fresh = jax.tree.map(
            lambda a: _spec(a.shape, a.dtype, one_chip),
            jax.eval_shape(extend, *extend_args))
        fn = eng._kv_write_fn(n)
        args = (state, fresh, _spec((n, M), jnp.int32, one_chip),
                _spec((n,), jnp.int32, one_chip),
                _spec((n,), jnp.int32, one_chip))
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert ("mla_decode" in text) == (program == "jit_chunk")
    # fresh latents reach the pool by the tile-copy kernel, in the chunk
    # and in admission's write program; admission's layers hold no write
    # (the instruction, not the word: a jnp helper first traced inside
    # ``kv_page_write.py`` keeps that file in its ops' metadata wherever
    # it is used next)
    assert bool(re.search(r"%kv_page_write(\.\d+)? = ", text)) == (
        program != "jit_extend")
    if program == "jit_chunk":
        # the step ends in the fused kernel: no [256, 129280] logits, so
        # no head for XLA to rematerialise at the memory limit
        _assert_fused_epilogue(text, B, eng.cfg.vocab_size)
    # 256 rows a decode step and 1024 a wave: both over the ridge, so the
    # routed experts are the grouped-matmul kernel, which is handed the
    # STACK: no op's result is a layer's slice of it
    assert bool(re.search(r"%moe_grouped(\.\d+)? = ", text)) == (
        program != "jit_write")
    _assert_no_slice_of_the_routed_stack(text, eng.cfg)
    # ... and the ROWS as they are: no array of the padded rows (6,144 of
    # 2,048 a decode step, 24,576 a wave) anywhere in the program
    E = eng.cfg.hidden_dim
    assert not re.search(rf"\[(6144|12288|24576),{E}\]", text)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.6e9


# ------------------------------------------------------------------ #
# The smallthinker-l8.rollout_out15k cell (layer kinds: one page pool, a
# page table a position of the period, the window layers' own program of
# the paged kernel).
# ------------------------------------------------------------------ #

HYBRID_CELL = dict(B=112, M=128, P=14495, periods=2)


def test_paged_decode_window_compiles_alone(compiled_kernels, one_chip):
    """``paged_decode_window`` at the cell's shape: 112 slots, 28q/4kv x
    128, page 128, a table of 128 pages, the pool's leading axis the two
    periods; a fourth scalar-prefetch operand (each row's first visible
    position) and the kernel's own name."""
    from areal_tpu.ops.pallas import paged_attention as pl_paged

    def f(q, ks, vs, pages, layer, table, lens):
        return pl_paged.decode(
            q, ks, vs, pages, layer, table, lens, sliding_window=4096)

    text = _compile(f, *_paged_specs(
        one_chip, page=128, int8=False, L=HYBRID_CELL["periods"],
        B=HYBRID_CELL["B"], P=HYBRID_CELL["P"], M=HYBRID_CELL["M"],
        layout=QWEN_7B)).as_text()
    assert "paged_decode_window" in text


def _count_primitives(jaxpr, counts=None):
    """Primitive names of ``jaxpr`` and every jaxpr nested in it."""
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] += 1
        for value in eqn.params.values():
            for x in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(x, "jaxpr", x)
                if hasattr(inner, "eqns"):
                    _count_primitives(inner, counts)
    return counts


def _call_windows(jaxpr):
    """The one ``pallas_call`` of ``jaxpr``: the block mappings of its
    operands and results that Mosaic's pipeline copies (every one not left
    in ``ANY`` memory for the kernel's own DMAs), and the VMEM limit it asks
    the compiler for."""
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    piped = [
        bm for bm in call.params["grid_mapping"].block_mappings
        if bm.block_aval.memory_space is not pltpu.MemorySpace.ANY
    ]
    limit = call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
    return piped, limit


@pytest.mark.parametrize(
    "program",
    ["cell1-table32", "cell3-table32", "olmoe-table32", "window-table64",
     "mla_decode-table64", "mla_decode-table72"],
)
def test_chained_decode_lowers_with_two_copies_of_issue(
        compiled_kernels, one_chip, program):
    """The kernel whose prefetch chain runs over REACHED steps (a walk over
    blocks on the scalar core, the buffer's parity in SMEM) lowers for a
    v5e in every program of it, at the NARROWER table width the cells'
    chunk programs also come in; and it is traced with TWO copies of the
    page copies' start (the prologue's and the chain's) and ONE of the
    wait, each ONE entry of the table that the lowering unrolls over the
    step's ``SB * KP``: what is traced is traced by every chunk program at
    every start (PERF.md §6, PRs 31, 35 and 36).

    Since PR 47 q, the current token's K/V and the output are WHOLE in VMEM
    for the call: the pipeline has no window that moves with the grid, so it
    copies each of them once a call and none at a change of block (every
    piped operand is the whole array at index 0), and the VMEM limit the call
    asks for covers them beside the page scratch. The largest there is, the
    JoyAI cell's ``mla_decode`` at 256 rows x 32 heads of a 576-wide latent
    (stored 640 wide: Mosaic takes the row in whole lane tiles) over its
    table of 72 pages, lowers inside that limit."""
    from areal_tpu.ops.pallas import paged_attention as pl_paged

    kw, streams = {}, 2
    if program.startswith("mla_decode"):
        c = JOYAI_CELL
        B, hkv, width, streams = c["B"], 1, c["W"], 1
        specs = [
            _spec((B, c["H"], width), jnp.bfloat16, one_chip),
            _spec((B, 1, width), jnp.bfloat16, one_chip),
            None,
            _spec((c["L"], c["P"], 1, 1, 128, width), jnp.bfloat16, one_chip),
            _spec((), jnp.int32, one_chip),
            _spec((B, int(program.split("table")[1])), jnp.int32, one_chip),
            _spec((B,), jnp.int32, one_chip),
        ]
        kw = dict(softmax_scale=192 ** -0.5, value_width=c["DV"])
    else:
        shape = {
            "cell1-table32": dict(B=128, M=32, P=2588),
            "cell3-table32": dict(B=64, M=32, P=1311, L=16, layout=QWEN_7B),
            "olmoe-table32": dict(B=64, M=32, P=715, L=8, layout=OLMOE),
            "window-table64": dict(
                B=HYBRID_CELL["B"], M=64, P=HYBRID_CELL["P"],
                L=HYBRID_CELL["periods"], layout=QWEN_7B),
        }[program]
        if program.startswith("window"):
            kw = dict(sliding_window=4096)
        specs = _paged_specs(one_chip, page=128, int8=False, **shape)
        B, hkv, width = specs[0].shape[0], specs[1].shape[1], 128
    sb, kp = pl_paged.block_plan(
        B, hkv, width, 128, specs[5].shape[1], jnp.bfloat16, streams=streams,
        windowed=program.startswith("window"))

    def f(q, ks, vs, pages, layer, table, lens):
        return pl_paged.decode(q, ks, vs, pages, layer, table, lens, **kw)

    jaxpr = jax.make_jaxpr(f)(*specs).jaxpr
    counts = _count_primitives(jaxpr)
    piped, limit = _call_windows(jaxpr)
    # q, k_self, (v_self,) and the output; the pool is the kernel's own
    assert len(piped) == (3 if streams == 1 else 4)
    for bm in piped:
        assert bm.block_aval.memory_space is pltpu.MemorySpace.VMEM
        assert bm.has_trivial_window(), bm.origin
    assert limit <= 100 * 2**20           # of the v5e's 128 MiB
    assert sb * kp > 1
    assert counts["dma_start"] == 2
    assert counts["dma_wait"] == 1
    assert counts["while"] == 2
    name = {"window": "paged_decode_window", "mla_decode": "mla_decode"}.get(
        program.split("-")[0], "paged_decode")
    assert re.search(rf"%{name}(\.\d+)? = ", _compile(f, *specs).as_text())


@pytest.fixture(scope="module")
def hybrid_engine():
    """The engine of the cell at its real configuration, with placeholder
    weights and a pool of a few pages (7.9 GB of weights and a 7.6 GB pool
    are never allocated)."""
    import json

    import numpy as np

    from areal_tpu.gen.engine import GenerationEngine
    from benchmark import sut

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark", "configs", "smallthinker-21b-l8.json")) as f:
        arch = json.load(f)
    cfg = sut.model_config(arch, {})
    shapes = sut.weight_shapes(cfg, cfg.dtype)
    eng = GenerationEngine(
        cfg, jax.tree.map(lambda s: np.zeros((1,), s.dtype), shapes),
        max_slots=HYBRID_CELL["B"], max_seqlen=16384,
        max_new_tokens_cap=15360, page_size=128, n_pages=80, seed=0)
    eng._decode_use_pallas = True
    return eng, shapes


@pytest.mark.parametrize("program", ["jit_chunk", "jit_extend", "jit_write"])
def test_hybrid_engine_programs_compile(
        compiled_kernels, one_chip, hybrid_engine, program):
    """``jit_chunk`` (16 decode steps at 112 slots and the full table of
    128 pages in each of the four kinds: one scan over the two periods,
    both programs of the paged kernel, the 64-expert dispatch, the
    152k-vocabulary head inside ``fused_sample``), ``jit_extend`` (an admission wave of 8 x 128
    tokens against the pool) and ``jit_write`` (the wave's fresh K/V into
    every kind's pages) for a described v5e, beside 7.93 GB of weights and
    the cell's pool of 14,495 pages (7.6 GB): arguments + temporaries
    under 16.4e9."""
    import dataclasses

    from areal_tpu.models import transformer as tfm

    eng, shapes = hybrid_engine

    def spec(a):
        return _spec(a.shape, a.dtype, one_chip)

    params = jax.tree.map(spec, shapes)
    pages = eng.state.cache.pages
    assert pages.shape[0] == HYBRID_CELL["periods"]
    state = dataclasses.replace(
        jax.tree.map(spec, eng.state),
        cache=tfm.PagedKVCache(pages=_spec(
            (pages.shape[0], HYBRID_CELL["P"]) + pages.shape[2:],
            pages.dtype, one_chip)))
    B, M = HYBRID_CELL["B"], HYBRID_CELL["M"]
    n, W, C = 8, 64, eng.admit_chunk
    extend = eng._extend_fn(n, W, skip_pool=False)
    extend_args = (params, state,
                   _spec((n, C), jnp.int32, one_chip),
                   _spec((4, n, W), jnp.int32, one_chip),
                   _spec((n,), jnp.int32, one_chip),
                   _spec((n,), jnp.int32, one_chip))
    if program == "jit_chunk":
        fn = eng._chunk_fn(16, M, 0, fused=True, with_topk=False)
        args = (params, state, _spec((4, B, M), jnp.int32, one_chip),
                _spec((0,), jnp.int32, one_chip))
    elif program == "jit_extend":
        fn, args = extend, extend_args
    else:
        fresh = jax.tree.map(
            lambda a: _spec(a.shape, a.dtype, one_chip),
            jax.eval_shape(extend, *extend_args))
        fn = eng._kv_write_fn(n)
        args = (state, fresh, _spec((4, n, M), jnp.int32, one_chip),
                _spec((n,), jnp.int32, one_chip),
                _spec((n,), jnp.int32, one_chip))
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    # the full layers' program and the window layers', by the names of
    # their instructions (a file name of an earlier compile can turn up in
    # a module's table of source files)
    assert bool(re.search(r"%paged_decode_window(\.\d+)? = ", text)) == (
        program == "jit_chunk")
    assert bool(re.search(r"%paged_decode(\.\d+)? = ", text)) == (
        program == "jit_chunk")
    assert bool(re.search(r"%kv_page_write(\.\d+)? = ", text)) == (
        program != "jit_extend")
    if program == "jit_chunk":
        _assert_fused_epilogue(text, B, eng.cfg.vocab_size)
    # 112 rows a decode step: under the ridge, the einsums (the program the
    # engine ran before the kernel existed); the wave's 1024: the kernel
    assert bool(re.search(r"%moe_grouped(\.\d+)? = ", text)) == (
        program == "jit_extend")
    if program == "jit_extend":
        _assert_no_slice_of_the_routed_stack(text, eng.cfg)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.4e9


def _zaya_program(one_chip, program: str, n_layers: int, n_pages: int):
    """``(cfg, the jitted program, its arguments as shapes on the chip)``
    of a ``zaya`` model at ZAYA1-8B's widths and ``n_layers`` layers under
    the engine at the cell's 256 slots of 72 pages and a pool of
    ``n_pages``, placeholder weights."""
    import dataclasses
    import json

    from areal_tpu.gen.engine import GenerationEngine
    from areal_tpu.models import transformer as tfm
    from benchmark import sut

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark", "configs", "zaya1-8b-l16.json")) as f:
        arch = json.load(f)
    arch.update(num_hidden_layers=n_layers, layer_types=["hybrid"] * n_layers)
    cfg = sut.model_config(arch, {})
    shapes = sut.weight_shapes(cfg, cfg.dtype)
    B, M = 256, 72
    eng = GenerationEngine(
        cfg, jax.tree.map(lambda s: np.zeros((1,), s.dtype), shapes),
        max_slots=B, max_seqlen=9216, max_new_tokens_cap=8192,
        page_size=128, n_pages=80, record_routing=True, seed=0)
    eng._decode_use_pallas = True
    # a tied head in the serving dtype on one TPU device: the rule's yes
    assert eng.fused and eng._moe_grouped(B) and eng._stateful
    carry = eng.state.ssm.carry
    assert carry.shape == (n_layers, B, 2688) and carry.dtype == jnp.bfloat16

    def spec(a):
        return _spec(a.shape, a.dtype, one_chip)

    i32 = lambda *shape: _spec(shape, jnp.int32, one_chip)
    pages = eng.state.cache.pages
    assert pages.shape[2:] == (2, 2, 128, 128)
    # the pool at ``n_pages`` and the snapshot table as the engine sizes it
    # there: two entries a slot
    state = dataclasses.replace(
        jax.tree.map(spec, eng.state),
        cache=tfm.PagedKVCache(pages=_spec(
            (n_layers, n_pages) + pages.shape[2:], pages.dtype, one_chip)),
        snaps=tfm.CCAState(_spec(
            (n_layers, min(2 * B, n_pages), 2688), jnp.bfloat16, one_chip)))
    params = jax.tree.map(spec, shapes)
    if program == "jit_chunk":
        fn = eng._chunk_fn(16, M, 0, fused=eng.fused, with_topk=False)
        args = (params, state, i32(B, M), i32(0))
    else:
        fn = eng._extend_fn(8, 64, skip_pool=False)
        args = (params, state, i32(8, eng.admit_chunk), i32(8, 64), i32(8),
                i32(8), i32(8))
    return cfg, fn, args


@pytest.mark.parametrize("program", ["jit_chunk", "jit_extend"])
def test_zaya_engine_programs_compile(compiled_kernels, one_chip, program):
    """A two-layer ``zaya`` model at ZAYA1-8B's widths (attention inside
    the convolved latent, top-1 of 16 experts and a skip behind the MLP
    router, the 262k tied head) through the engine at the cell's 256
    slots, placeholder weights: ``jit_chunk`` (16 decode steps over the
    full table of 72 pages, the per-slot carry through the layer scan)
    holds ``paged_decode`` at the cell-1 geometry (8 q / 2 kv x 128),
    ``kv_page_write`` and, at 256 rows of 16 experts, ``moe_grouped`` (the
    rule's choice: over 0.6 of the ridge), and ends in ``fused_sample``
    over the embedding as stored, with nothing of the logits' shape
    ``[256, 262272]`` in it; ``jit_extend`` (a wave of 8 x 128 tokens
    continuing 8 slots' carry, its first token's sampler where it was)
    holds ``moe_grouped`` and makes no array of the carry's size."""
    B = 256
    cfg, fn, args = _zaya_program(one_chip, program, 2, 3576)
    text = fn.lower(*args).compile().as_text()
    chunk = program == "jit_chunk"
    assert bool(re.search(r"%paged_decode(\.\d+)? = ", text)) == chunk
    assert bool(re.search(r"%kv_page_write(\.\d+)? = ", text)) == chunk
    assert re.search(r"%moe_grouped(\.\d+)? = ", text)
    _assert_no_slice_of_the_routed_stack(text, cfg)
    if chunk:
        _assert_fused_epilogue(text, B, cfg.vocab_size)
        # ... nor a transposed copy of the embedding for the kernel
        assert f"bf16[{cfg.hidden_dim},{cfg.vocab_size}]" not in text
    else:
        assert not re.search(r"%fused_sample(\.\d+)? = ", text)
        made = [ln.strip()[:120] for ln in text.split("\n")
                if "= bf16[2,256,2688]" in ln and " parameter(" not in ln
                and " get-tuple-element(" not in ln]
        assert not made, made


@pytest.mark.parametrize("more_pages", [0, 48])
def test_zaya_cell_decode_chunk_computes_its_head_once(
        compiled_kernels, one_chip, more_pages):
    """At the cell's sixteen layers and its pool (the traffic file's bytes)
    the decode chunk fits beside what it is handed without recomputing
    anything: where the compiler is short of memory it REMATERIALISES, and
    at a pool of 7.65e9 with a snapshot an entry a page it computed the
    262k-row tied head three times a step (PERF.md section 6, PR 45). The
    same holds 48 pages (100 MB) further on: the cell does not sit on the
    edge, where a few MB freed would read as a gain. Since PR 46 the step
    ends in ``fused_sample`` and the ``[256, 262272]`` logits, the array
    that was computed three times, are in neither program."""
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark", "traffic",
            "grpo16_closed256_out8k_cca.json")) as f:
        pool_bytes = json.load(f)["engine"]["kv_pool_bytes"]
    n_pages = pool_bytes // (16_384 * 128) + more_pages
    _, fn, args = _zaya_program(one_chip, "jit_chunk", 16, n_pages)
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert f"bf16[16,{n_pages},2,2,128,128]" in text
    again = sorted(set(re.findall(r"%([\w.\-]*remat[\w.\-]*) = ", text)))
    assert not again, again
    _assert_fused_epilogue(text, 256, 262272)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.4e9


# ------------------------------------------------------------------ #
# moe_grouped: the routed experts as one grouped matmul over the stack
# ------------------------------------------------------------------ #

# experts (held here), experts a token, hidden, expert width, expert layers
# of the cell; ``n_routed``: the experts the router chooses among where the
# stack holds a share; ``gated`` False: experts of two matrices
MOE_CELLS = {
    "joyai": dict(X=256, k=8, E=2048, F=768, L=4, act="silu"),
    "olmoe": dict(X=64, k=8, E=2048, F=1024, L=8, act="silu"),
    "smallthinker": dict(X=64, k=6, E=2560, F=768, L=8, act="relu"),
    # top-1 with the skip index (a row's choice may be ``X``: no expert)
    "zaya": dict(X=16, k=1, E=2048, F=2048, L=16, act="silu"),
    # one rank's 128 of 512 two-matrix experts in the latent of 1024
    "nemotron": dict(X=128, k=22, E=1024, F=2688, L=8, act="relu2",
                     gated=False, n_routed=512),
}
V5E_VMEM_BYTES = 128 * 2 ** 20


@pytest.mark.parametrize(
    "cell,T",
    [("joyai", 256), ("joyai", 1024), ("olmoe", 1024),
     ("smallthinker", 1024), ("joyai", 1), ("zaya", 256),
     ("nemotron", 1024)],
)
def test_moe_grouped_compiles(compiled_kernels, one_chip, cell, T):
    """The kernel at the expert cells' widths, for JoyAI's and ZAYA's
    decode steps, a full admission wave of four, and one row: it lowers for
    the described v5e with the stacks as they are (no ``[X, E, F]`` copy:
    the layer is a scalar-prefetch index) and the rows as they are: no
    array of the padded rows exists outside the call (the gather of a
    tile's rows and the weighted sum are inside it), nothing is sorted,
    and what the call asks of VMEM the chip has."""
    from areal_tpu.ops.pallas import moe_grouped as mg

    c = MOE_CELLS[cell]
    X, k, E, F, L = c["X"], c["k"], c["E"], c["F"], c["L"]
    gated, n_routed = c.get("gated", True), c.get("n_routed")

    def f(x, top_idx, top_vals, w_up, w_down, layer, w_gate=None):
        return mg.moe_grouped(
            x, top_idx, top_vals, w_gate, w_up, w_down, layer,
            activation=c["act"], n_routed=n_routed)

    compiled = _compile(
        f,
        _spec((T, E), jnp.bfloat16, one_chip),
        _spec((T, k), jnp.int32, one_chip),
        _spec((T, k), jnp.float32, one_chip),
        _spec((L, X, E, F), jnp.bfloat16, one_chip),
        _spec((L, X, F, E), jnp.bfloat16, one_chip),
        _spec((), jnp.int32, one_chip),
        *([_spec((L, X, E, F), jnp.bfloat16, one_chip)] if gated else []),
    )
    text = compiled.as_text()
    assert re.search(r"%moe_grouped(\.\d+)? = ", text)
    assert f"bf16[{X},{E},{F}]" not in text
    assert f"bf16[{X},{F},{E}]" not in text
    tm = mg.row_tile(T * k, n_routed or X)
    padded = (T * k // tm + min(X, T * k)) * tm
    assert padded > T
    assert not re.search(rf"\[{padded},{E}\]", text)
    assert not re.search(r"\bsort\(", text)
    # the plan's small arrays (a one-hot of the pairs, the running counts):
    # under a hundredth of the padded rows in and out that the parent made
    assert compiled.memory_analysis().temp_size_in_bytes < max(
        padded * E * 6 // 100, 2 ** 20)
    assert mg.vmem_bytes(
        T, tm, E, F, 3 if gated else 2, jnp.bfloat16, jnp.bfloat16
    ) < V5E_VMEM_BYTES


def _benchmark_config(config: str):
    """``(cfg, weight shapes)`` of ``benchmark/configs/<config>.json`` as
    the benchmark runs it."""
    import json

    from benchmark import sut

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark", "configs", config + ".json")) as f:
        cfg = sut.model_config(json.load(f), {})
    return cfg, sut.weight_shapes(cfg, cfg.dtype)


def test_olmoe_wave_holds_the_kernel_and_its_decode_step_does_not(
        compiled_kernels, one_chip):
    """The OLMoE cell's engine: a wave of 8 x 128 tokens (1024 rows, 4.3 x
    the ridge) runs the routed experts through ``moe_grouped`` on the
    whole stack; the rule keeps its 64-row decode step and its one-row
    admission program on the einsums."""
    from areal_tpu.gen.engine import GenerationEngine

    c = KV_WRITE_CELLS["olmoe"]
    cfg, shapes = _benchmark_config(c["config"])
    eng = GenerationEngine(
        cfg, jax.tree.map(lambda s: np.zeros((1,), s.dtype), shapes),
        max_slots=c["B"], max_seqlen=c["seqlen"],
        max_new_tokens_cap=c["out"], page_size=128, n_pages=80, seed=0)
    C = eng.admit_chunk
    assert [eng._moe_grouped(rows) for rows in (c["B"], C, 8 * C)] == [
        False, False, True]

    def spec(a):
        return _spec(a.shape, a.dtype, one_chip)

    n, W = 8, 32
    text = eng._extend_fn(n, W, skip_pool=False).lower(
        jax.tree.map(spec, shapes), jax.tree.map(spec, eng.state),
        _spec((n, C), jnp.int32, one_chip),
        _spec((n, W), jnp.int32, one_chip),
        _spec((n,), jnp.int32, one_chip),
        _spec((n,), jnp.int32, one_chip),
    ).compile().as_text()
    assert re.search(r"%moe_grouped(\.\d+)? = ", text)
    _assert_no_slice_of_the_routed_stack(text, cfg)


# ------------------------------------------------------------------ #
# a program the rule leaves to the einsums is the program it was
# ------------------------------------------------------------------ #

# sha256 (16 hex digits) of the StableHLO of decode_step_paged,
# extend_paged_kv, forward_packed and its gradient at the benchmark's six
# configurations (real widths, a tiny batch), taken at the commit before
# the grouped kernel's (PR 37's). A forward that changes on purpose
# changes its line here.
FORWARD_HASHES = {
    "r1d-qwen-1p5b": (
        "7cd5a9d6e3b9b38e", "c486ef017c9542d2", "0028c23830abfc7a",
        "3cf1838c96fd400a"),
    "r1d-qwen-7b-l16": (
        "fef06ad21a9a4993", "e165b92075f76cfb", "6c9ed9487d925f10",
        "2b863e5eef51fbd1"),
    "ouro-2p6b-l8": (
        "5d68e08adaf3e168", "2c177b99a1758a49", "0b368df30a8875a6",
        "40ce0428625e79f5"),
    "olmoe-1b-7b-l8": (
        "ae37e3d107b1d6f6", "b605946f099dbfa5", "0fbc94975171839f",
        "e30805641f8f4350"),
    "joyai-flash-l5": (
        "c4379504a8fa255a", "ff2da943fb04abdb", "8cf30c2a352c52fa",
        "72aa22489a7ab0a3"),
    "smallthinker-21b-l8": (
        "a0e19bf38715ae01", "a91439fc8081a034", "c881c069dfe706c9",
        "a7f3ebf91e10838f"),
    # new in PR 55 (taken at its commit): a period across two stacks
    "trinity-mini-l8": (
        "d86f8ec23d9f8fa8", "cf687d0d46bc2fd9", "70100e2a817c0dd7",
        "c5bcb4d983e0a773"),
}


def forward_hashes(config: str):
    """``(decode, extend, packed, packed's gradient)``: the hashes above,
    of this tree."""
    import hashlib

    from areal_tpu.models import transformer as tfm

    cfg, shapes = _benchmark_config(config)
    B, M, T = 8, 4, 64
    cache = jax.eval_shape(lambda: tfm.PagedKVCache.empty(cfg, 12, 16))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    kinds = len(cfg.layer_kinds)
    table = i32(B, M) if kinds == 1 else i32(kinds, B, M)

    def decode(p, c, t, tb, ln, a):
        return tfm.decode_step_paged(
            p, cfg, c, t, tb, ln, a, use_pallas=False)[:3]

    def extend(p, c, t, tb, s, n):
        return tfm.extend_paged_kv(p, cfg, c, t, tb, s, n)

    def packed(p, i, s, q):
        return tfm.forward_packed(p, cfg, i, s, q)

    def loss(p, i, s, q):
        return tfm.forward_packed(p, cfg, i, s, q, with_aux=True)[0].sum()

    return tuple(
        hashlib.sha256(
            jax.jit(f).lower(*a).as_text().encode()).hexdigest()[:16]
        for f, a in (
            (decode, (shapes, cache, i32(B), table, i32(B),
                      jax.ShapeDtypeStruct((B,), jnp.bool_))),
            (extend, (shapes, cache, i32(B, 16), table, i32(B), i32(B))),
            (packed, (shapes, i32(T), i32(T), i32(T))),
            (jax.grad(loss), (shapes, i32(T), i32(T), i32(T))),
        ))


@pytest.mark.parametrize("config", list(FORWARD_HASHES))
def test_forwards_without_the_grouped_kernel_are_the_programs_they_were(
        config):
    """The three forwards as every caller but an engine over the ridge
    builds them (``moe_grouped`` left False: the trainer, ``ppo/inference``,
    a mesh, the CPU, every model without a router, OLMoE's and
    SmallThinker's decode steps) lower to the StableHLO they lowered to
    before the kernel existed: the routed stacks leave the scanned tree
    only in a program that runs the kernel."""
    assert forward_hashes(config) == FORWARD_HASHES[config]


# sha256 (16 hex digits) of the StableHLO of decode_step_paged WITH the
# paged kernel (``use_pallas=True``; interpret mode here) for the two cells
# whose per-slot state holds the step to slot order, taken at PR 49's
# commit: what a step observes of rows that name the same pages, and the
# prefix program, are no part of a program that keeps slot order.
# (traced at a table of 4 pages, which is one grid step at 8 pages a step
# and at the full-attention program's 4 since PR 52: the hashes stayed. At
# the cells' tables of 40-128 pages these models' full layers run the new
# plan like every other model's; their window layers run the old one.)
# RE-PINNED by PR 54, on purpose and here only: the step's convolution is
# ``ops/ssm.py:conv_step`` (static slices of the flat state, no ``[B, K,
# C]`` array, no gather) where it was the many-token form at ``T = 1``
# (PR 49's: 5f8d9a2459ff4965 and 252a10f65ca8b879); nothing else of the
# two programs changed.
KERNEL_DECODE_HASHES = {
    "granite-4.0-h-micro": "e7e09b7d01ced581",
    "phi4-mini-flash": "604ef403ddac6f68",
    # new in PR 55 (taken at its commit): NOT a slot-order step (the rows go
    # by length and the one full kind's shared pages through the prefix
    # program); pinned so that a later change to the period across two
    # stacks with the kernel shows
    "trinity-mini-l8": "5cefdff0dd4b5893",
}


def kernel_decode_hash(config: str) -> str:
    import hashlib

    from areal_tpu.models import transformer as tfm

    cfg, shapes = _benchmark_config(config)
    B, M = 8, 4
    cache = jax.eval_shape(lambda: tfm.PagedKVCache.empty(cfg, 12, 16))
    state = jax.eval_shape(lambda: tfm.row_state_empty(cfg, B))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    kinds = len(cfg.layer_kinds)
    table = i32(B, M) if kinds == 1 else i32(kinds, B, M)

    def decode(p, c, t, tb, ln, a, st):
        return tfm.decode_step_paged(
            p, cfg, c, t, tb, ln, a, use_pallas=True, ssm=st)

    text = jax.jit(decode).lower(
        shapes, cache, i32(B), table, i32(B),
        jax.ShapeDtypeStruct((B,), jnp.bool_), state).as_text()
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("config", list(KERNEL_DECODE_HASHES))
def test_slot_order_steps_with_the_kernel_are_the_programs_they_were(config):
    """granite-4.0-h-micro's and Phi-4-mini-flash's decode steps keep slot
    order (``shared_prefix_applies``: ``slot_order``), so with the kernel
    they lower to what they lowered to before a step looked at its table
    for rows that name the same pages. (``trinity-mini-l8``, PR 55: the
    kernel step of a period across two stacks, pinned at its first
    commit.)"""
    assert kernel_decode_hash(config) == KERNEL_DECODE_HASHES[config]


# ------------------------------------------------------------------ #
# Phi-4-mini-flash (benchmark/configs/phi4-mini-flash): the decode chunk
# of the WHOLE model at the cell's slots and pool
# ------------------------------------------------------------------ #


def _phi4flash_program(one_chip, program: str, n_pages: int):
    """``(cfg, the jitted program, its arguments as shapes on the chip)``
    of the published model under the engine at the cell's 128 slots of 128
    pages in each of nine tables and a pool of ``n_pages``, placeholder
    weights."""
    import dataclasses
    import json

    from areal_tpu.gen.engine import GenerationEngine
    from areal_tpu.models import transformer as tfm
    from benchmark import sut

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark", "configs", "phi4-mini-flash.json")) as f:
        arch = json.load(f)
    cfg = sut.model_config(arch, {})
    shapes = sut.weight_shapes(cfg, cfg.dtype)
    B, M = 128, 128
    eng = GenerationEngine(
        cfg, jax.tree.map(lambda s: np.zeros((1,), s.dtype), shapes),
        max_slots=4, max_seqlen=16384, max_new_tokens_cap=15360,
        page_size=128, n_pages=80, state_snapshots=8, seed=0)
    eng._decode_use_pallas = True
    assert eng.fused and eng._stateful and eng._windowed
    assert eng._ssm_update() is None            # the selective scan is XLA's
    eng.B = B

    def spec(a):
        return _spec(a.shape, a.dtype, one_chip)

    def rows(a):
        # the engine above holds 4 slots (a CPU's worth): the cell's 128
        return _spec(
            tuple(B if d == 4 else d for d in a.shape), a.dtype, one_chip)

    i32 = lambda *shape: _spec(shape, jnp.int32, one_chip)
    pages = eng.state.cache.pages
    assert pages.shape[2:] == (2, 10, 128, 128) and pages.shape[0] == 1
    st = eng.state
    state = dataclasses.replace(
        jax.tree.map(rows, dataclasses.replace(st, snaps=None, cache=None)),
        cache=tfm.PagedKVCache(pages=_spec(
            (1, n_pages) + pages.shape[2:], pages.dtype, one_chip)),
        snaps=jax.tree.map(spec, st.snaps),
        rng=spec(st.rng))
    params = jax.tree.map(spec, shapes)
    if program == "jit_chunk":
        fn = eng._chunk_fn(16, M, 0, fused=eng.fused, with_topk=False)
        args = (params, state, i32(9, B, M), i32(0))
    else:
        fn = eng._extend_fn(8, 64, skip_pool=False)
        args = (params, state, i32(8, eng.admit_chunk), i32(9, 8, 64), i32(8),
                i32(8), i32(8))
    return cfg, fn, args


@pytest.mark.parametrize("more_pages", [0, 160])
def test_phi4flash_cell_decode_chunk_computes_its_head_once(
        compiled_kernels, one_chip, more_pages):
    """The WHOLE published model's decode chunk at the cell's 128 slots
    and its pool (the traffic file's bytes), and 160 pages (105 MB)
    further on: both programs of the paged kernel and the write kernel
    over NINE cache layers are in it, the step ends in ``fused_sample``
    over the 200k-row embedding as stored (no ``[128, 200064]`` array, no
    transposed copy), nothing is rematerialised, and arguments and
    temporaries fit the chip."""
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark", "traffic",
            "grpo16_closed128_out15k_yoco.json")) as f:
        pool_bytes = json.load(f)["engine"]["kv_pool_bytes"]
    n_pages = pool_bytes // (5_120 * 128) + more_pages
    cfg, fn, args = _phi4flash_program(one_chip, "jit_chunk", n_pages)
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert f"bf16[1,{n_pages},2,10,128,128]" in text
    for kernel in ("paged_decode", "paged_decode_window", "kv_page_write"):
        assert re.search(rf"%{kernel}(\.\d+)? = ", text), kernel
    assert not re.search(r"%ssm_decode(\.\d+)? = ", text)
    again = sorted(set(re.findall(r"%([\w.\-]*remat[\w.\-]*) = ", text)))
    assert not again, again
    _assert_fused_epilogue(text, 128, cfg.vocab_size)
    assert f"bf16[{cfg.hidden_dim},{cfg.vocab_size}]" not in text
    # nine selective-scan mixers' convolution, 128 x 5,120 channels
    _assert_one_token_convolution(text, 9, 128, cfg.ssm.conv_dim)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.4e9


def test_phi4flash_admission_runs_no_cross_decoder(compiled_kernels, one_chip):
    """A wave of 8 x 128 tokens continuing 8 slots' state: the program
    holds the matmuls of layers 0-17 and none over the gated memory units'
    or the cross layers' stacks (admission keeps nothing of them), and
    makes no array of the whole recurrent state's size."""
    cfg, fn, args = _phi4flash_program(one_chip, "jit_extend", 9600)
    text = fn.lower(*args).compile().as_text()
    assert "bf16[7,2560,5120]" not in text      # gmu_layers' w_in
    assert "bf16[7,2560,2560]" not in text      # cross_layers' wq / wo
    assert "bf16[9,2560,5120]" in text          # ssm_layers' w_x / w_z
    made = [ln.strip()[:120] for ln in text.split("\n")
            if "= f32[9,128,1,40,16,128]" in ln and " parameter(" not in ln
            and " get-tuple-element(" not in ln]
    assert not made, made


# ------------------------------------------------------------------ #
# nemotron_h cut to one period, an expert-parallel rank's share
# (benchmark/configs/nemotron3-super-l11-ep4): the engine's decode chunk
# ------------------------------------------------------------------ #


def _nemotron_program(one_chip, n_pages: int, program: str = "jit_chunk"):
    """``(cfg, the jitted program, its arguments as shapes on the chip)``
    of the configuration under the engine at the cell's 192 slots of 40
    pages and a pool of ``n_pages``, placeholder weights: the decode chunk,
    or (``jit_extend``) a wave of 8 x 128 tokens continuing 8 slots."""
    import dataclasses
    import json

    from areal_tpu.gen.engine import GenerationEngine
    from areal_tpu.models import transformer as tfm
    from areal_tpu.ops.pallas import ssm_decode
    from benchmark import sut

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark", "configs",
            "nemotron3-super-l11-ep4.json")) as f:
        cfg = sut.model_config(json.load(f), {})
    shapes = sut.weight_shapes(cfg, cfg.dtype)
    B, M, few = 192, 40, 6
    eng = GenerationEngine(
        cfg, jax.tree.map(lambda s: np.zeros((1,), s.dtype), shapes),
        max_slots=few, max_seqlen=5120, max_new_tokens_cap=4096,
        page_size=128, n_pages=80, state_snapshots=8, admit_buckets=(2, 8),
        record_routing=True, seed=0)
    eng._decode_use_pallas = True
    assert eng.fused and eng._stateful and eng.M == M
    # the state update's rule asks the first device itself: here the CPU
    assert ssm_decode.ssm_decode_applies(cfg, None, "tpu")
    eng._ssm_update = lambda: ssm_decode.ssm_decode
    eng.B = B
    # two-matrix experts: the einsums up to 1.15 of the ridge, the kernel
    # from there (a full admission wave)
    assert not eng._moe_grouped(B) and eng._moe_grouped(8 * eng.admit_chunk)

    def spec(a):
        return _spec(a.shape, a.dtype, one_chip)

    def rows(a):
        # the engine above holds 6 slots (a CPU's worth): the cell's 192
        return _spec(
            tuple(B if d == few else d for d in a.shape), a.dtype, one_chip)

    pages = eng.state.cache.pages
    assert pages.shape[2:] == (2, 2, 128, 128) and pages.shape[0] == 1
    st = eng.state
    state = dataclasses.replace(
        jax.tree.map(rows, dataclasses.replace(st, snaps=None, cache=None)),
        cache=tfm.PagedKVCache(pages=_spec(
            (1, n_pages) + pages.shape[2:], pages.dtype, one_chip)),
        snaps=jax.tree.map(spec, st.snaps),
        rng=spec(st.rng))
    i32 = lambda *shape: _spec(shape, jnp.int32, one_chip)
    params = jax.tree.map(spec, shapes)
    if program == "jit_chunk":
        fn = eng._chunk_fn(16, M, 0, fused=eng.fused, with_topk=False)
        return cfg, fn, (params, state, i32(B, M), i32(0))
    fn = eng._extend_fn(8, M, skip_pool=False)
    return cfg, fn, (params, state, i32(8, eng.admit_chunk), i32(8, M),
                     i32(8), i32(8), i32(8))


def test_nemotron_cell_decode_chunk_copies_no_stack_and_no_state(
        compiled_kernels, one_chip):
    """The cell's decode chunk (11 one-branch blocks at the published
    widths, 128 of 512 experts, 192 slots, 16 steps) at the traffic file's
    pool: ``ssm_decode`` over the state
    of eight groups, ``paged_decode``, ``kv_page_write`` and
    ``fused_sample`` are in it; the held experts' two stacks are read in
    place by the einsums (192 rows of two-matrix experts are under the
    kernel's crossing; the admission wave of 1,024 rows holds
    ``moe_grouped``), so the temporaries are under ONE routed layer's 0.7
    GB; nothing results in an array of the state's size (the kernel
    updates the donated state in place: the state is aliased to the
    result); arguments and temporaries fit the chip with 1.5e9 B to spare (800
    pages further on, by hand, as well: the cell sits on no memory edge;
    three small ops carry ``remat`` in their names there, here and 4,000
    pages under: the compiler's placement of the kernel's gathers in fast
    memory, not a shortage)."""
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark", "traffic", "grpo16_closed192_ep4.json")) as f:
        pool_bytes = json.load(f)["engine"]["kv_pool_bytes"]
    n_pages = pool_bytes // (1_024 * 128)
    cfg, fn, args = _nemotron_program(one_chip, n_pages)
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert f"bf16[1,{n_pages},2,2,128,128]" in text
    for kernel in ("ssm_decode", "paged_decode", "kv_page_write"):
        assert re.search(rf"%{kernel}(\.\d+)? = ", text), kernel
    # 192 rows of two-matrix experts run on the einsums (the rule's table,
    # ``ops/moe.py``), which read their layer's slice of the stacks in place
    assert not re.search(r"%moe_grouped(\.\d+)? = ", text)
    _assert_fused_epilogue(text, 192, cfg.vocab_size)
    made = [ln.strip()[:120] for ln in text.split("\n")
            if "= f32[5,192,8,8,128,128]" in ln and " parameter(" not in ln
            and " get-tuple-element(" not in ln and "custom-call" not in ln
            and " while(" not in ln and " bitcast(" not in ln]
    assert not made, made
    _assert_one_token_convolution(text, 5, 192, cfg.ssm.conv_dim)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * 5 * 192 * 8 * 8 * 128 * 128
    assert mem.temp_size_in_bytes < 0.6e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.4e9


def test_nemotron_admission_wave_holds_the_grouped_kernel(
        compiled_kernels, one_chip):
    """A wave of 8 x 128 tokens continuing 8 slots' state at the cell's
    sizes: its 1,024 rows are over the two-matrix experts' crossing, so the
    held experts run in ``moe_grouped``, which is handed the two stacks
    whole (nothing results in one layer's ``[128, 1024, 2688]``), and the
    program makes no array of the whole recurrent state's size."""
    cfg, fn, args = _nemotron_program(one_chip, 7781, "jit_extend")
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert _custom_call_names(text) == {"moe_grouped"}
    n_held, lat, width = cfg.moe.held[0], cfg.moe.latent_dim, cfg.expert_dim
    for shape in (f"bf16[{n_held},{lat},{width}]",
                  f"bf16[{n_held},{width},{lat}]"):
        assert not re.search(r"= (\()?" + re.escape(shape) + r"\{", text), shape
    made = [ln.strip()[:120] for ln in text.split("\n")
            if "= f32[5,192,8,8,128,128]" in ln and " parameter(" not in ln
            and " get-tuple-element(" not in ln]
    assert not made, made
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


# ------------------------------------------------------------------ #
# Trinity-Mini cut to eight layers (benchmark/configs/trinity-mini-l8):
# the decode chunk of the cell at its slots and pool, a period of layer
# kinds that runs ACROSS the dense and the expert stack
# ------------------------------------------------------------------ #

# the cell trinity-mini-l8.rollout_out15k: 64 slots, a table of 128 pages of
# 128 in each of the four kinds
AFMOE_CELL = dict(B=64, M=128)


def _afmoe_program(one_chip, n_pages: int, program: str = "jit_chunk"):
    """``(cfg, the jitted program, its arguments as shapes on the chip)``
    of the configuration under the engine at the cell's 64 slots of 128
    pages in each of the four tables and a pool of ``n_pages``, placeholder
    weights: the decode chunk, or (``jit_extend``) a wave of 8 x 128 tokens
    against the pool."""
    import dataclasses
    import json

    from areal_tpu.gen.engine import GenerationEngine
    from areal_tpu.models import transformer as tfm
    from benchmark import sut

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark", "configs", "trinity-mini-l8.json")) as f:
        cfg = sut.model_config(json.load(f), {})
    shapes = sut.weight_shapes(cfg, cfg.dtype)
    B, M = AFMOE_CELL["B"], AFMOE_CELL["M"]
    eng = GenerationEngine(
        cfg, jax.tree.map(lambda s: np.zeros((1,), s.dtype), shapes),
        max_slots=B, max_seqlen=16384, max_new_tokens_cap=15360,
        page_size=128, n_pages=80, admit_buckets=(2, 8),
        record_routing=True, seed=0)
    eng._decode_use_pallas = True
    assert eng.fused and eng.M == M
    # a decode step's rows are under the ridge (the einsums); a full
    # admission wave is over it (the kernel)
    assert not eng._moe_grouped(B) and eng._moe_grouped(8 * eng.admit_chunk)

    def spec(a):
        return _spec(a.shape, a.dtype, one_chip)

    pages = eng.state.cache.pages
    assert pages.shape[0] == 2 and pages.shape[2:] == (2, 4, 128, 128)
    state = dataclasses.replace(
        jax.tree.map(spec, eng.state),
        cache=tfm.PagedKVCache(pages=_spec(
            (2, n_pages) + pages.shape[2:], pages.dtype, one_chip)))
    i32 = lambda *shape: _spec(shape, jnp.int32, one_chip)
    params = jax.tree.map(spec, shapes)
    if program == "jit_chunk":
        fn = eng._chunk_fn(16, M, 0, fused=eng.fused, with_topk=False)
        return cfg, fn, (params, state, i32(4, B, M), i32(0))
    fn = eng._extend_fn(8, 64, skip_pool=False)
    return cfg, fn, (params, state, i32(8, eng.admit_chunk), i32(4, 8, 64),
                     i32(8), i32(8))


def _afmoe_pool_pages() -> int:
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark", "traffic",
            "grpo16_closed80_out15k_w2k.json")) as f:
        pool_bytes = json.load(f)["engine"]["kv_pool_bytes"]
    return pool_bytes // (2 * 128 * 2048)


@pytest.mark.parametrize("more_pages", [0, 572])
def test_trinity_cell_decode_chunk_computes_its_head_once(
        compiled_kernels, one_chip, more_pages):
    """The cell's decode chunk (2 dense + 6 expert layers at the published
    widths, 128 experts, 64 slots, 16 steps, a table of 128 pages in each
    of the four kinds) at the traffic file's pool and 0.3e9 B (572 pages)
    past it: the dense layers and the two expert layers that complete the
    first period run one by one and the second period in the scan, each
    layer through the program of its kind (``paged_decode_window`` and
    ``paged_decode``), ``kv_page_write`` writes the pool in place, the step
    ends in ``fused_sample`` over the 200,192-row untied head with no
    ``[B, 200192]`` array in the program, a step's rows keep the einsums
    (no ``moe_grouped``). At the cell's pool NOTHING is rematerialised; 0.3e9
    past it the compiler is short enough to recompute 16 MB slices of the
    attention projections (from 3.6e9 on: PERF.md section 7), but never
    anything of the vocabulary's size: the head is computed once at both
    (PR 45's lesson)."""
    n_pages = _afmoe_pool_pages() + more_pages
    cfg, fn, args = _afmoe_program(one_chip, n_pages)
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert f"bf16[2,{n_pages},2,4,128,128]" in text
    for kernel in ("paged_decode_window", "paged_decode", "kv_page_write"):
        assert re.search(rf"%{kernel}(\.\d+)? = ", text), kernel
    assert not re.search(r"%moe_grouped(\.\d+)? = ", text)
    _assert_fused_epilogue(text, AFMOE_CELL["B"], cfg.vocab_size)
    again = [ln.strip()[:160] for ln in text.split("\n")
             if re.search(r"%[\w.\-]*remat[\w.\-]* = ", ln)]
    if not more_pages:
        assert not again, again
    assert not [ln for ln in again if str(cfg.vocab_size) in ln], again
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.4e9


def test_trinity_admission_wave_holds_the_grouped_kernel(
        compiled_kernels, one_chip):
    """A wave of 8 x 128 tokens against the pool at the cell's sizes: its
    1,024 rows are over the kernel's crossing, so the routed experts of
    ALL six expert layers (the two that complete the first period among
    them) run in ``moe_grouped``, handed the stack whole with the layer's
    index in it: nothing results in one layer's ``[128, 2048, 1024]``."""
    cfg, fn, args = _afmoe_program(one_chip, _afmoe_pool_pages(), "jit_extend")
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert _custom_call_names(text) == {"moe_grouped"}
    _assert_no_slice_of_the_routed_stack(text, cfg)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.0e9


# ------------------------------------------------------------------ #
# Solar-Open2 cut to one period (benchmark/configs/solar-open2-l4-ep8):
# the decode chunk and an admission wave of the cell at its slots and pool,
# delta-rule layers beside one attention layer, experts in every block
# ------------------------------------------------------------------ #

# the cell solar-open2-l4.rollout_out8k: 256 slots, a table of 72 pages
SOLAR2_CELL = dict(B=256, M=72)


def _solar2_traffic():
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark", "traffic",
            "grpo16_closed256_out8k_kda.json")) as f:
        return json.load(f)


def _solar2_program(one_chip, program: str = "jit_chunk"):
    """``(cfg, the jitted program, its arguments as shapes on the chip)``
    of the configuration under the engine at the cell's 256 slots of 72
    pages and the traffic file's pool, placeholder weights: the decode
    chunk, or (``jit_extend``) a wave of 8 x 128 tokens continuing 8
    slots."""
    import dataclasses
    import json

    from areal_tpu.gen.engine import GenerationEngine
    from areal_tpu.models import transformer as tfm
    from areal_tpu.ops.pallas import kda_decode
    from benchmark import kda_flops, sut

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark", "configs", "solar-open2-l4-ep8.json")) as f:
        arch = json.load(f)
    cfg = sut.model_config(arch, {})
    mix = _solar2_traffic()
    n_pages = mix["engine"]["kv_pool_bytes"] // (
        kda_flops.kv_bytes_per_token(arch) * 128)
    shapes = sut.weight_shapes(cfg, cfg.dtype)
    B, M, few = SOLAR2_CELL["B"], SOLAR2_CELL["M"], 6
    eng = GenerationEngine(
        cfg, jax.tree.map(lambda s: np.zeros((1,), s.dtype), shapes),
        max_slots=few, max_seqlen=9216, max_new_tokens_cap=8192,
        page_size=128, n_pages=80,
        state_snapshots=mix["engine"]["state_snapshots"],
        admit_buckets=(2, 8), record_routing=True, seed=0)
    eng._decode_use_pallas = True
    assert eng.fused and eng._stateful and eng.M == M
    # the state update's rule asks the first device itself: here the CPU
    assert kda_decode.kda_decode_applies(cfg, None, "tpu")
    eng._ssm_update = lambda: kda_decode.kda_decode
    eng.B = B
    # gated experts: a step's 256 rows and a wave's 1,024 are both over
    # the kernel's crossing
    assert eng._moe_grouped(B) and eng._moe_grouped(8 * eng.admit_chunk)

    def spec(a):
        return _spec(a.shape, a.dtype, one_chip)

    def rows(a):
        # the engine above holds 6 slots (a CPU's worth): the cell's 256
        return _spec(
            tuple(B if d == few else d for d in a.shape), a.dtype, one_chip)

    pages = eng.state.cache.pages
    assert pages.shape[2:] == (2, 8, 128, 128) and pages.shape[0] == 1
    st = eng.state
    state = dataclasses.replace(
        jax.tree.map(rows, dataclasses.replace(st, snaps=None, cache=None)),
        cache=tfm.PagedKVCache(pages=_spec(
            (1, n_pages) + pages.shape[2:], pages.dtype, one_chip)),
        snaps=jax.tree.map(spec, st.snaps),
        rng=spec(st.rng))
    i32 = lambda *shape: _spec(shape, jnp.int32, one_chip)
    params = jax.tree.map(spec, shapes)
    if program == "jit_chunk":
        fn = eng._chunk_fn(16, M, 0, fused=eng.fused, with_topk=False)
        return cfg, fn, (params, state, i32(B, M), i32(0))
    fn = eng._extend_fn(8, M, skip_pool=False)
    return cfg, fn, (params, state, i32(8, eng.admit_chunk), i32(8, M),
                     i32(8), i32(8), i32(8))


def test_solar2_cell_decode_chunk_updates_the_state_in_place(
        compiled_kernels, one_chip):
    """The cell's decode chunk (one attention and three delta-rule layers
    at the published widths, 40 of 320 experts in every block, 256 slots,
    16 steps) at the traffic file's pool: ``kda_decode`` over the stacked
    state, ``paged_decode``, ``kv_page_write`` and ``moe_grouped`` (handed
    both expert stacks whole) are in it and the step ends in
    ``fused_sample`` over the 24,576-row head, computed once; NOTHING
    results in an array of the stacked state's size or of one layer's
    ``[256, 64, 128, 128]`` (the kernel updates the donated state in
    place: the state is aliased to the result); arguments and temporaries
    fit the chip."""
    cfg, fn, args = _solar2_program(one_chip)
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    for kernel in ("kda_decode", "paged_decode", "kv_page_write",
                   "moe_grouped"):
        assert re.search(rf"%{kernel}(\.\d+)? = ", text), kernel
    # (the vocabulary slice is as wide as [q ; k ; v], 3 x 8192, so ``[256,
    # 24576]`` is also the mixers' projection and its convolution, and the
    # other cells' "no array of the logits' shape" cannot be asked here:
    # the head is the ONE ``fused_sample`` call, and no op takes a softmax
    # over such an array)
    assert cfg.vocab_size == cfg.kda.conv_dim
    assert len(re.findall(r"%fused_sample(?:\.\d+)? = ", text)) == 1
    assert not [ln[:160] for ln in text.split("\n")
                if "[256,24576]" in ln and "exponential(" in ln
                and "kda_conv" not in ln]
    for shape in ("f32[3,256,64,128,128]", "f32[256,64,128,128]"):
        made = [ln.strip()[:120] for ln in text.split("\n")
                if f"= {shape}" in ln and " parameter(" not in ln
                and " get-tuple-element(" not in ln
                and "custom-call" not in ln and " while(" not in ln
                and " bitcast(" not in ln]
        assert not made, made
    held, E, F = cfg.moe.held[0], cfg.hidden_dim, cfg.expert_dim
    for shape in (f"bf16[{held},{E},{F}]", f"bf16[{held},{F},{E}]"):
        assert not re.search(r"= (\()?" + re.escape(shape) + r"\{", text), shape
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * 3 * 256 * 64 * 128 * 128
    assert mem.temp_size_in_bytes < 0.8e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.4e9


def test_solar2_admission_wave_runs_the_chunked_form(
        compiled_kernels, one_chip):
    """A wave of 8 x 128 tokens continuing 8 slots' state at the cell's
    sizes: the held experts of both stacks run in ``moe_grouped`` (the only
    Mosaic call: the delta rule's chunked form is XLA's), the pair terms of
    a chunk stay inside their fusions (no ``[.., 64, 64, 128]`` array a
    head), and the program makes no array of the whole state's size."""
    cfg, fn, args = _solar2_program(one_chip, "jit_extend")
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert _custom_call_names(text) == {"moe_grouped"}
    made = [ln.strip()[:120] for ln in text.split("\n")
            if "= f32[3,256,64,128,128]" in ln and " parameter(" not in ln
            and " get-tuple-element(" not in ln]
    assert not made, made
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
