"""Ring attention (context parallelism): parity vs the single-device flash
path — forward AND gradients — on an 8-virtual-device mesh.

The long-context bar (SURVEY §2.2 "SP" / brief: "ring attention or
all-to-all sequence parallelism"): per-device attention memory scales with
T/cp while results match the unsharded computation.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from areal_tpu.ops import attention as attn_ops
from areal_tpu.ops.ring_attention import ring_attention


def _ctx_mesh(cp):
    devs = np.asarray(jax.devices()[:cp])
    return Mesh(devs.reshape(cp), ("ctx",))


def _packed_inputs(rng, T, H, Hkv, D, seqlens):
    assert sum(seqlens) <= T
    seg = np.zeros(T, np.int32)
    pos = 0
    for i, n in enumerate(seqlens):
        seg[pos : pos + n] = i + 1
        pos += n
    q = rng.normal(size=(T, H, D)).astype(np.float32)
    k = rng.normal(size=(T, Hkv, D)).astype(np.float32)
    v = rng.normal(size=(T, Hkv, D)).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(seg)


def _reference(q, k, v, seg, **kw):
    # the packed dense/XLA path is the numerics oracle
    kw.setdefault("softmax_scale", q.shape[-1] ** -0.5)
    return attn_ops._attention_xla(q, k, v, seg, **kw)


@pytest.mark.parametrize("cp", [2, 4, 8])
def test_forward_parity(cp, rng):
    T, H, Hkv, D = 256, 4, 2, 16
    q, k, v, seg = _packed_inputs(rng, T, H, Hkv, D, [100, 60, 40])
    mesh = _ctx_mesh(cp)
    out = ring_attention(q, k, v, seg, mesh, block_k=32)
    ref = _reference(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_forward_parity_softcap_window(rng):
    T, H, Hkv, D = 256, 4, 2, 16
    q, k, v, seg = _packed_inputs(rng, T, H, Hkv, D, [120, 90])
    mesh = _ctx_mesh(4)
    out = ring_attention(
        q, k, v, seg, mesh, soft_cap=8.0, sliding_window=48, block_k=64
    )
    ref = _reference(q, k, v, seg, soft_cap=8.0, sliding_window=48)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_pad_rows_zero(rng):
    T, H, Hkv, D = 128, 4, 2, 16
    q, k, v, seg = _packed_inputs(rng, T, H, Hkv, D, [50])  # 78 pad tokens
    mesh = _ctx_mesh(4)
    out = np.asarray(ring_attention(q, k, v, seg, mesh, block_k=32))
    assert np.all(out[50:] == 0)


@pytest.mark.parametrize("cp", [2, 8])
def test_gradient_parity(cp, rng):
    """The backward ring (autodiff through ppermute) matches unsharded
    gradients for q, k, and v."""
    T, H, Hkv, D = 128, 4, 2, 8
    q, k, v, seg = _packed_inputs(rng, T, H, Hkv, D, [70, 33])
    mesh = _ctx_mesh(cp)
    tgt = jnp.asarray(rng.normal(size=(T, H, D)).astype(np.float32))

    def loss_ring(q, k, v):
        o = ring_attention(q, k, v, seg, mesh, block_k=32)
        return jnp.sum((o - tgt) ** 2)

    def loss_ref(q, k, v):
        o = _reference(q, k, v, seg)
        return jnp.sum((o - tgt) ** 2)

    # each side ONE program: eagerly the ring's backward is some 750
    # one-op programs at cp=8, and proves nothing more
    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-4, err_msg=f"d{name}"
        )


def test_under_jit_with_sharded_inputs(rng):
    """ring_attention composes with jit + GSPMD-sharded operands (the way
    the train engine calls it)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    T, H, Hkv, D = 256, 4, 2, 16
    q, k, v, seg = _packed_inputs(rng, T, H, Hkv, D, [200])
    mesh = _ctx_mesh(4)
    sh = NamedSharding(mesh, P("ctx"))
    q = jax.device_put(q, NamedSharding(mesh, P("ctx", None, None)))

    @jax.jit
    def f(q, k, v, seg):
        return ring_attention(q, k, v, seg, mesh, block_k=64)

    out = f(q, k, v, jax.device_put(seg, sh))
    ref = _reference(jax.device_put(q), k, v, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


class TestContextParallelTraining:
    """Full train step with the token axis ring-sharded: a d1f1c4m2 mesh
    reaches the same losses as d2f2m2 on the same global batch."""

    def _train(self, parallel, rng_seed=0, steps=4):
        from areal_tpu.api.data import MicroBatchSpec, SequenceSample
        from areal_tpu.api.model import make_interface
        from areal_tpu.models.config import ModelConfig
        from areal_tpu.ops import attention as attn_ops
        from areal_tpu.parallel.mesh import ParallelConfig
        from areal_tpu.train.engine import OptimizerConfig, TrainEngine

        cfg = ModelConfig(
            n_layers=2, n_q_heads=4, n_kv_heads=2, head_dim=8, hidden_dim=32,
            intermediate_dim=64, vocab_size=128, dtype="float32",
        )
        rng = np.random.default_rng(rng_seed)
        lens = [int(x) for x in rng.integers(10, 30, size=6)]
        sample = SequenceSample.from_default(
            ids=list(range(6)), seqlens=lens,
            data={
                "packed_input_ids": rng.integers(0, 128, sum(lens)).astype(np.int64),
                "prompt_mask": np.concatenate(
                    [np.r_[np.ones(2, bool), np.zeros(n - 2, bool)] for n in lens]
                ),
            },
        )
        try:
            eng = TrainEngine(
                cfg, ParallelConfig.from_str(parallel),
                OptimizerConfig(lr=1e-3),
            )
            eng.init_random(0)
            eng.setup_optimizer(total_train_steps=20)
            sft = make_interface("sft")
            return [
                sft.train_step(eng, sample, MicroBatchSpec())["loss"]
                for _ in range(steps)
            ]
        finally:
            attn_ops.clear_context_parallel()

    @pytest.mark.slow
    def test_ctx_parallel_matches_data_parallel(self):
        ring = self._train("d1f1c4m2")
        base = self._train("d2f2m2")
        for a, b in zip(ring, base):
            assert a == pytest.approx(b, rel=2e-4)

    def test_from_str_parses_ctx(self):
        from areal_tpu.parallel.mesh import ParallelConfig

        p = ParallelConfig.from_str("d2f2c2m1")
        assert (p.data, p.fsdp, p.ctx, p.model) == (2, 2, 2, 1)
        assert p.world_size == 8
        assert ParallelConfig.from_str("d2m2").ctx == 1


def test_ring_preserves_data_and_model_sharding(rng):
    """Review regression: under vmap with spmd_axis_name, the ring must not
    all-gather rows/heads — the output keeps the data-axis sharding and the
    compiled program contains zero all-gathers."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import Mesh

    devs = np.asarray(jax.devices()[:8]).reshape(2, 2, 2)
    mesh = Mesh(devs, ("data", "ctx", "model"))
    R, T, H, Hkv, D = 2, 64, 4, 2, 8
    q = jax.device_put(
        jnp.asarray(rng.normal(size=(R, T, H, D)).astype(np.float32)),
        NamedSharding(mesh, P("data", "ctx", None, None)),
    )
    k = jax.device_put(
        jnp.asarray(rng.normal(size=(R, T, Hkv, D)).astype(np.float32)),
        NamedSharding(mesh, P("data", "ctx", None, None)),
    )
    seg = jax.device_put(
        jnp.asarray(np.ones((R, T), np.int32)),
        NamedSharding(mesh, P("data", "ctx")),
    )

    f = jax.jit(jax.vmap(
        lambda q, k, v, s: ring_attention(q, k, v, s, mesh, block_k=32),
        spmd_axis_name="data",
    ))
    out = f(q, k, k, seg)
    assert out.sharding.spec[0] == "data", out.sharding.spec
    hlo = f.lower(q, k, k, seg).compile().as_text()
    assert "all-gather" not in hlo
