"""Pallas flash attention vs the XLA reference path (interpret mode on CPU).

Counterpart of the reference's kernel tests (``tests/cpp_extensions``): the
custom kernel must match the straightforward masked implementation.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.ops.attention import _attention_xla
from areal_tpu.ops.pallas.flash_attention import packed_flash_attention

# These kernels run in interpret mode on CPU, which costs minutes for the
# full parity sweep. Tier-1 keeps one representative per kernel feature
# (fwd parity, window, fused bwd, multiblock bwd, band narrowing,
# pipelined grads); the exhaustive sweep stays under -m slow and runs
# whenever the kernels change (`pytest tests/test_flash_attention.py`
# with no marker filter) and compiled on chip.
slow = pytest.mark.slow


def _mk(rng, T, H, Hkv, D, lens):
    q = rng.normal(size=(T, H, D)).astype(np.float32)
    k = rng.normal(size=(T, Hkv, D)).astype(np.float32)
    v = rng.normal(size=(T, Hkv, D)).astype(np.float32)
    seg = np.zeros(T, np.int32)
    off = 0
    for i, n in enumerate(lens):
        seg[off : off + n] = i + 1
        off += n
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(seg)


@pytest.mark.parametrize(
    "lens",
    [
        [256],
        pytest.param([100, 156], marks=slow),
        pytest.param([7, 64, 100, 85], marks=slow),
    ],
)
def test_flash_matches_xla(rng, lens):
    T, H, Hkv, D = 256, 4, 2, 16
    q, k, v, seg = _mk(rng, T, H, Hkv, D, lens)
    scale = D**-0.5
    ref = _attention_xla(q, k, v, seg, scale)
    got = packed_flash_attention(
        q, k, v, seg, softmax_scale=scale, block_size=128
    )
    valid = np.asarray(seg) > 0
    np.testing.assert_allclose(
        np.asarray(got)[valid], np.asarray(ref)[valid], atol=2e-5, rtol=2e-5
    )


def test_flash_with_padding_and_window(rng):
    T, H, Hkv, D = 256, 2, 2, 8
    q, k, v, seg = _mk(rng, T, H, Hkv, D, [120, 60])  # 76 pad tokens
    scale = D**-0.5
    ref = _attention_xla(q, k, v, seg, scale, sliding_window=32)
    got = packed_flash_attention(
        q, k, v, seg, softmax_scale=scale, sliding_window=32, block_size=128
    )
    valid = np.asarray(seg) > 0
    np.testing.assert_allclose(
        np.asarray(got)[valid], np.asarray(ref)[valid], atol=2e-5, rtol=2e-5
    )


def test_flash_gradients_match(rng):
    T, H, Hkv, D = 128, 2, 1, 8
    q, k, v, seg = _mk(rng, T, H, Hkv, D, [50, 40])
    scale = D**-0.5

    def loss_flash(q, k, v):
        o = packed_flash_attention(q, k, v, seg, softmax_scale=scale, block_size=128)
        return jnp.sum(jnp.where((seg > 0)[:, None, None], o, 0.0) ** 2)

    def loss_xla(q, k, v):
        o = _attention_xla(q, k, v, seg, scale)
        return jnp.sum(jnp.where((seg > 0)[:, None, None], o, 0.0) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


@slow
def test_flash_pad_rows_are_zero(rng):
    """Fully-padded query rows must output exactly 0, like the XLA path
    (ADVICE round 1: finite NEG_INF made exp(s - m) == 1 on masked rows)."""
    T, H, Hkv, D = 256, 2, 2, 8
    q, k, v, seg = _mk(rng, T, H, Hkv, D, [100])  # 156 pad tokens
    got = np.asarray(
        packed_flash_attention(q, k, v, seg, softmax_scale=D**-0.5, block_size=128)
    )
    pad = np.asarray(seg) == 0
    np.testing.assert_array_equal(got[pad], 0.0)


@pytest.mark.parametrize(
    "kwargs",
    [
        dict(),                                            # plain causal
        pytest.param(dict(sliding_window=64), marks=slow),  # windowed
        pytest.param(dict(soft_cap=20.0), marks=slow),      # soft-cap
    ],
)
def test_flash_bwd_matches_xla_multiblock(rng, kwargs):
    """Pallas backward kernels vs XLA autodiff: GQA (n_rep=3), multiple
    q/k blocks, padding, uneven segments."""
    T, H, Hkv, D = 384, 6, 2, 16
    q, k, v, seg = _mk(rng, T, H, Hkv, D, [100, 156, 60])  # 68 pad tokens
    scale = D**-0.5

    def loss(attn):
        def f(q, k, v):
            o = attn(q, k, v)
            w = jnp.asarray(
                np.linspace(0.5, 1.5, o.size).reshape(o.shape), jnp.float32
            )
            return jnp.sum(jnp.where((seg > 0)[:, None, None], o * w, 0.0))
        return f

    g1 = jax.grad(
        loss(lambda q, k, v: packed_flash_attention(
            q, k, v, seg, softmax_scale=scale, block_size=128, **kwargs
        )),
        argnums=(0, 1, 2),
    )(q, k, v)
    g2 = jax.grad(
        loss(lambda q, k, v: _attention_xla(q, k, v, seg, scale, **kwargs)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4
        )


@slow
def test_flash_specialized_path_matches_xla(rng, monkeypatch):
    """Force the interior/boundary dual-body kernels (which the block rule
    turns on from the call's shapes) at a test-sized T: fwd and bwd must match XLA,
    including blocks that are fully interior (one long segment spanning
    many blocks) and boundary blocks (segment edges, padding)."""
    from areal_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "flash_blocks", lambda *a, **kw: (64, 64, True))
    T, H, Hkv, D = 512, 4, 2, 16
    # one long segment (interior blocks at block_size=64) + short ones + pad
    q, k, v, seg = _mk(rng, T, H, Hkv, D, [320, 64, 100])
    scale = D**-0.5

    ref = _attention_xla(q, k, v, seg, scale)
    got = fa.packed_flash_attention(
        q, k, v, seg, softmax_scale=scale, block_size=64
    )
    valid = (np.asarray(seg) > 0)[:, None, None]
    np.testing.assert_allclose(
        np.asarray(got) * valid, np.asarray(ref) * valid, atol=2e-5, rtol=2e-5
    )

    def loss(attn):
        def f(q, k, v):
            o = attn(q, k, v)
            return jnp.sum(jnp.where((seg > 0)[:, None, None], o * o, 0.0))
        return f

    g1 = jax.grad(
        loss(lambda q, k, v: fa.packed_flash_attention(
            q, k, v, seg, softmax_scale=scale, block_size=64
        )),
        argnums=(0, 1, 2),
    )(q, k, v)
    g2 = jax.grad(
        loss(lambda q, k, v: _attention_xla(q, k, v, seg, scale)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4
        )


@slow
def test_flash_bwd_fallback_sweeps_match_fused(rng, monkeypatch):
    """The separate dq/dkv fallback sweeps (taken when the fused kernel's
    whole-group dq scratch exceeds FUSED_BWD_MAX_DQ_BYTES) must produce the
    same gradients as the fused path — forced here by zeroing the budget."""
    from areal_tpu.ops.pallas import flash_attention as fa

    T, H, Hkv, D = 384, 6, 2, 16
    q, k, v, seg = _mk(rng, T, H, Hkv, D, [100, 156, 60])
    scale = D**-0.5

    def g():
        return jax.grad(
            lambda q, k, v: jnp.sum(
                fa.packed_flash_attention(
                    q, k, v, seg, softmax_scale=scale, block_size=128
                )
                ** 2
            ),
            argnums=(0, 1, 2),
        )(q, k, v)

    fused = g()
    monkeypatch.setattr(fa, "FUSED_BWD_MAX_DQ_BYTES", 0)
    fallback = g()
    for a, b in zip(fused, fallback):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5
        )


@pytest.mark.parametrize(
    "max_seqlen",
    [64, pytest.param(100, marks=slow), pytest.param(200, marks=slow)],
)
def test_flash_band_narrowing_matches_xla(rng, max_seqlen):
    """The static max_seqlen band hint must not change results as long as
    every segment respects the bound — fwd and bwd, multi-segment + pad."""
    T, H, Hkv, D = 512, 4, 2, 16
    lens = [100, 64, 100, 90, 37]  # all <= 100 <= max_seqlen... for 64: no
    if max_seqlen == 64:
        lens = [64, 33, 64, 50, 21]
    q, k, v, seg = _mk(rng, T, H, Hkv, D, lens)
    scale = D**-0.5
    ref = _attention_xla(q, k, v, seg, scale)
    got = packed_flash_attention(
        q, k, v, seg, softmax_scale=scale, block_size=64, max_seqlen=max_seqlen
    )
    valid = (np.asarray(seg) > 0)[:, None, None]
    np.testing.assert_allclose(
        np.asarray(got) * valid, np.asarray(ref) * valid, atol=2e-5, rtol=2e-5
    )

    g1 = jax.grad(
        lambda q, k, v: jnp.sum(
            packed_flash_attention(
                q, k, v, seg, softmax_scale=scale, block_size=64,
                max_seqlen=max_seqlen,
            )
            ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    g2 = jax.grad(
        lambda q, k, v: jnp.sum(_attention_xla(q, k, v, seg, scale) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4
        )


def test_band_violation_caught_under_debug_checks(rng, monkeypatch):
    """AREAL_DEBUG_CHECKS=1 turns the silent over-band truncation into an
    error: a segment longer than the static max_seqlen hint must raise
    instead of returning truncated attention (advisor round-2 finding)."""
    monkeypatch.setenv("AREAL_DEBUG_CHECKS", "1")
    T, H, Hkv, D = 256, 2, 2, 16
    q, k, v, seg = _mk(rng, T, H, Hkv, D, [200, 40])  # 200 > 128 bound
    with pytest.raises(Exception, match="max_seqlen"):
        out = packed_flash_attention(
            q, k, v, seg, softmax_scale=D**-0.5, block_size=64, max_seqlen=128
        )
        jax.block_until_ready(out)
    # respecting the bound stays silent
    q, k, v, seg = _mk(rng, T, H, Hkv, D, [100, 40])
    out = packed_flash_attention(
        q, k, v, seg, softmax_scale=D**-0.5, block_size=64, max_seqlen=128
    )
    jax.block_until_ready(out)


def test_engine_rejects_overlong_sequence():
    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.models.config import ModelConfig
    from areal_tpu.parallel.mesh import ParallelConfig
    from areal_tpu.train.engine import OptimizerConfig, TrainEngine

    cfg = ModelConfig(
        n_layers=1, n_q_heads=2, n_kv_heads=1, head_dim=8, hidden_dim=16,
        intermediate_dim=32, vocab_size=64, dtype="float32",
        attn_max_seqlen=16,
    )
    eng = TrainEngine(cfg, ParallelConfig(), OptimizerConfig(lr=1e-3))
    eng.init_random(0)
    eng.setup_optimizer(10)
    sample = SequenceSample.from_default(
        ids=[0], seqlens=[24],
        data={"packed_input_ids": np.zeros(24, np.int64)},
    )
    with pytest.raises(ValueError, match="attn_max_seqlen"):
        eng.train_batch(
            sample, MicroBatchSpec(n_mbs=1, max_tokens_per_mb=64),
            lambda p, c, a: (jnp.float32(0), {}),
        )


@pytest.mark.parametrize("gqa", [False, pytest.param(True, marks=slow)])
@pytest.mark.parametrize("banded", [False, pytest.param(True, marks=slow)])
def test_flash_gradients_match_pipelined(rng, monkeypatch, gqa, banded):
    """Cross-block software-pipelined fused backward (round 5): parking
    (p, ds) one grid step must be numerically IDENTICAL to the in-step
    dots, across the triangle (banded=False) and band (max_seqlen) kernels
    and with GQA rep folding."""
    monkeypatch.setenv("AREAL_FLASH_BWD_PIPELINE", "1")
    T, H, Hkv, D = 256, 4, 2 if gqa else 4, 16
    q, k, v, seg = _mk(rng, T, H, Hkv, D, [100, 120])
    scale = D**-0.5
    kwargs = dict(softmax_scale=scale, block_size=128)
    if banded:
        kwargs["max_seqlen"] = 128

    def loss_flash(q, k, v):
        o = packed_flash_attention(q, k, v, seg, **kwargs)
        return jnp.sum(jnp.where((seg > 0)[:, None, None], o, 0.0) ** 2)

    def loss_xla(q, k, v):
        o = _attention_xla(q, k, v, seg, scale)
        return jnp.sum(jnp.where((seg > 0)[:, None, None], o, 0.0) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4
        )


def _rule_geometries():
    """Every (block_q, block_k, specialised) the block rule returns over
    the shapes a trainer can hand it."""
    import itertools

    from areal_tpu.ops.pallas.flash_attention import flash_blocks

    return sorted({
        flash_blocks(T, n_rep, sliding_window=w, max_seqlen=m, backward=back)
        for T, n_rep, w, m, back in itertools.product(
            (1024, 2048, 4096, 8192, 32768), (1, 2, 3, 6, 8), (None, 1024),
            (None, 512, 4096), (False, True))
    })


# rows of 1,024 shaped like the train cell's: sequences and a pad tail, one
# sequence that fills the row, a row that is padding past its first block
RULE_ROWS = {
    "packed": [400, 300, 200], "full": [1024], "head": [100],
}


def _rule_cases():
    for bq, bk, spec in _rule_geometries():
        for row, n_rep, window in (
            ("packed", 6, None), ("full", 1, None), ("head", 6, None),
            ("packed", 1, 200), ("full", 6, None), ("packed", 1, None),
        ):
            yield pytest.param(
                bq, bk, spec, row, n_rep, window,
                id=f"{bq}x{bk}{'s' if spec else 'm'}-{row}-rep{n_rep}"
                   f"{'-w' if window else ''}")


@pytest.mark.parametrize("bq,bk,spec,row,n_rep,window", list(_rule_cases()))
def test_flash_rule_geometries_match_xla(rng, bq, bk, spec, row, n_rep,
                                         window):
    """Forward and dq / dk / dv at every geometry the block rule can return
    against the dense path: the pair list at its blocks, the masked and the
    interior body, folded heads and not, a window."""
    from areal_tpu.ops.pallas import flash_attention as fa

    T, Hkv, D = 1024, 1, 16
    q, k, v, seg = _mk(rng, T, Hkv * n_rep, Hkv, D, RULE_ROWS[row])
    scale = D**-0.5
    live = (seg > 0)[:, None, None]

    def loss(attn):
        def f(q, k, v):
            o = jnp.where(live, attn(q, k, v), 0.0)
            return jnp.sum(o * o), o
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    blocks = (bq, bk, spec)
    (_, got), g1 = loss(lambda q, k, v: fa._flash_thd(
        q, k, v, seg, scale, None, window, blocks, blocks, None))(q, k, v)
    (_, ref), g2 = loss(lambda q, k, v: _attention_xla(
        q, k, v, seg, scale, sliding_window=window))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)


def _segments(T, lens):
    seg = np.zeros(T, np.int32)
    off = 0
    for i, n in enumerate(lens):
        seg[off:off + n] = i + 1
        off += n
    return seg


# (lens in a row of 256, block_q, block_k, window, max_seqlen)
PAIR_LIST_CASES = [
    pytest.param([100, 60, 50], 32, 32, None, None, id="three-and-a-pad-tail"),
    pytest.param([256], 32, 32, None, None, id="one-fills-the-row"),
    pytest.param([20], 32, 32, None, None, id="all-pad-past-block-0"),
    pytest.param([], 64, 32, None, None, id="an-empty-row"),
    pytest.param([100, 60, 50], 16, 64, None, None, id="wide-k-blocks"),
    pytest.param([100, 60, 50], 64, 16, None, None, id="tall-q-blocks"),
    pytest.param([120, 100], 32, 32, 40, None, id="window"),
    pytest.param([90, 70, 80], 32, 32, None, 96, id="max-seqlen"),
    pytest.param([120, 100], 32, 16, 70, 128, id="window-and-max-seqlen"),
]


@pytest.mark.parametrize("xp", [jnp, np], ids=["program", "host-count"])
@pytest.mark.parametrize("lens,bq,bk,window,max_seqlen", PAIR_LIST_CASES)
def test_pair_list_is_the_masks_block_pairs(lens, bq, bk, window, max_seqlen,
                                            xp):
    """The list the kernels walk against a brute-force enumeration of the
    token mask: every (q block, k block) pair with an unmasked element is
    in it exactly once and no other pair runs a body, in q-block order;
    a pair that skips the mask is unmasked throughout; each q block's
    sweep has one FIRST and one LAST step (an all-pad block's one step
    runs no body); the tail repeats the last indices without a flag. The
    host's count (`pair_counts`) runs the same code over numpy."""
    from areal_tpu.ops.pallas import flash_attention as fa

    T = 256
    seg = _segments(T, lens)
    idx = np.arange(T)
    mask = (seg[:, None] == seg[None, :]) & (seg[:, None] > 0) & (
        idx[:, None] >= idx[None, :])
    if window is not None:
        mask &= idx[:, None] - idx[None, :] < window
    blocks = mask.reshape(T // bq, bq, T // bk, bk)
    some, every = blocks.any(axis=(1, 3)), blocks.all(axis=(1, 3))

    iq, ik, flags = (np.asarray(t) for t in fa._pair_list(
        xp.asarray(seg), bq, bk, window, max_seqlen, T, xp=xp))
    assert len(iq) == fa._pair_steps(bq, bk, window, max_seqlen, T)
    active = flags & fa.ACTIVE != 0
    ran = np.zeros_like(some, dtype=np.int64)
    np.add.at(ran, (iq[active], ik[active]), 1)
    np.testing.assert_array_equal(ran, some.astype(np.int64))
    interior = active & (flags & fa.MASKED == 0)
    assert every[iq[interior], ik[interior]].all()

    n_live = int((flags != 0).sum())
    assert (flags[:n_live] != 0).all() and n_live >= T // bq
    assert (np.diff(iq[:n_live]) >= 0).all()
    for b in range(T // bq):
        sweep = flags[:n_live][iq[:n_live] == b]
        assert sweep[0] & fa.FIRST and sweep[-1] & fa.LAST
        assert (sweep & fa.FIRST != 0).sum() == 1
        assert (sweep & fa.LAST != 0).sum() == 1
        assert (np.diff(ik[:n_live][iq[:n_live] == b]) == 1).all()
    assert (iq[n_live:] == iq[n_live - 1]).all()
    assert (ik[n_live:] == ik[n_live - 1]).all()
    assert ((0 <= ik) & (ik < T // bk)).all()

    counts = fa.pair_counts(seg, bq, bk, True, window, max_seqlen)
    assert counts["flash_pairs"] == some.sum()
    assert counts["flash_interior_pairs"] == interior.sum()
    if lens:
        np.testing.assert_allclose(
            counts["flash_fill"],
            sum(n * n for n in lens) / 2 / (some.sum() * bq * bk))


def test_pack_record_carries_the_pair_counts():
    """The host half of a train step says how tightly the flash kernels
    will cover its packed rows: ``flash_pairs``, ``flash_interior_pairs``
    and ``flash_fill`` on the ``train_pipe/pack`` record, at the blocks the
    kernels' wrapper gets from the same rule; nothing for a model that
    runs no flash kernel."""
    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.base import tracing
    from areal_tpu.models.config import ModelConfig
    from areal_tpu.ops.pallas import flash_attention as fa
    from areal_tpu.parallel.mesh import ParallelConfig
    from areal_tpu.train.engine import OptimizerConfig, TrainEngine

    lens = [700, 200, 60]
    sample = SequenceSample.from_default(
        ids=list(range(len(lens))), seqlens=lens,
        data={"packed_input_ids": np.zeros(sum(lens), np.int64)},
    )

    def pack_attrs(use_flash):
        cfg = ModelConfig(
            n_layers=1, n_q_heads=4, n_kv_heads=2, head_dim=8, hidden_dim=16,
            intermediate_dim=32, vocab_size=64, dtype="float32",
            use_flash_attention=use_flash,
        )
        eng = TrainEngine(cfg, ParallelConfig(), OptimizerConfig(lr=1e-3))
        eng.init_random(0)
        eng.prepare_train_batch(
            sample, MicroBatchSpec(n_mbs=1, max_tokens_per_mb=1024))
        rec = [r for r in tracing.recent_spans()
               if r["name"] == "train_pipe/pack"][-1]
        return rec.get("attrs", {})

    assert "flash_pairs" not in pack_attrs(False)
    got = pack_attrs(True)
    bq, bk, spec = fa.flash_blocks(1024, 2)
    seg = _segments(1024, lens)
    want = fa.pair_counts(seg, bq, bk, spec)
    assert want["flash_pairs"] > 0
    assert {k: got[k] for k in want} == want


def test_flash_under_a_mesh_runs_as_shard_map():
    """GSPMD cannot partition a Mosaic kernel, so under a multi-device mesh
    the flash dispatch wraps the kernel in shard_map over the head axis
    (``ops/attention.flash_mesh``; first seen as "Mosaic kernels cannot be
    automatically partitioned" from the d1f2m2 trainer on four chips).
    Forward and gradients must match the XLA path, with batch rows vmapped
    over the data axes the way the train engine does it."""
    import functools

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from areal_tpu.ops import attention as attn_ops

    mesh = Mesh(
        np.array(jax.devices()[:4]).reshape(1, 2, 1, 2),
        ("data", "fsdp", "ctx", "model"),
    )
    rows, T, H, Hkv, D = 2, 128, 4, 2, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(rows, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(rows, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(rows, T, Hkv, D)), jnp.float32)
    seg = jnp.asarray(
        np.tile(np.r_[np.ones(70), 2 * np.ones(40), np.zeros(18)], (rows, 1)),
        jnp.int32,
    )
    row_sh = NamedSharding(mesh, P(("data", "fsdp"), None, "model", None))
    q, k, v = (jax.device_put(x, row_sh) for x in (q, k, v))
    seg = jax.device_put(seg, NamedSharding(mesh, P(("data", "fsdp"), None)))

    def loss(use_flash, q, k, v):
        attn = functools.partial(
            attn_ops.packed_attention, use_flash=use_flash,
            flash_block_size=64, max_seqlen=128,
        )
        out = jax.vmap(attn, spmd_axis_name=("data", "fsdp"))(q, k, v, seg)
        live = (seg > 0)[..., None, None]
        return jnp.sum(jnp.where(live, out, 0.0) ** 2), out

    def run(use_flash):
        def f(q, k, v):
            with attn_ops.flash_mesh(mesh):
                return jax.value_and_grad(
                    functools.partial(loss, use_flash), argnums=(0, 1, 2),
                    has_aux=True,
                )(q, k, v)
        return jax.jit(f)(q, k, v)

    (l_f, out_f), g_f = run(True)
    (l_x, out_x), g_x = run(False)
    live = np.asarray(seg > 0)[..., None, None]
    np.testing.assert_allclose(
        np.where(live, out_f, 0), np.where(live, out_x, 0), atol=2e-5
    )
    np.testing.assert_allclose(l_f, l_x, rtol=1e-5)
    for a, b in zip(g_f, g_x):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
