"""The MoE layer (``ops/moe.py``) against a plain reference.

The reference is ``benchmark/reference/olmoe.py``'s ``_sparse_mlp``
(softmax over all router logits, top-k, every expert applied to every
token in a plain loop, times its combine weight), which shares no code
with the program. Whole-model parity with HF Mixtral is in
``test_model_hf_parity.py``, with the OLMoE reference in
``test_olmoe.py``. Counterpart of the reference's token dispatcher tests
(``realhf/impl/model/modules/moe/token_dispatcher.py``).

Tolerances: float32 against float32 on the CPU, so 2e-5 on outputs (the
order in which 4 experts x 32 columns are summed); gradients 5e-4
relative.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.models.config import ModelConfig, MoEConfig
from areal_tpu.ops import moe as moe_ops
from benchmark.reference import olmoe as ref


def _cfg(top_k=2, aux=0.01, z=0.001, norm_topk=True):
    return ModelConfig(
        n_layers=1,
        n_q_heads=4,
        n_kv_heads=2,
        head_dim=8,
        hidden_dim=16,
        intermediate_dim=32,
        vocab_size=64,
        mlp_type="moe",
        activation_function="silu",
        moe=MoEConfig(
            num_experts=4,
            top_k=top_k,
            aux_loss_coeff=aux,
            z_loss_coeff=z,
            norm_topk_prob=norm_topk,
        ),
    )


def _params(rng, E=16, F=32, X=4):
    k = iter(jax.random.split(rng, 4))
    w = lambda shape: jax.random.normal(next(k), shape, jnp.float32) * 0.1
    return {
        "router": w((E, X)),
        "w_gate": w((X, E, F)),
        "w_up": w((X, E, F)),
        "w_down": w((X, F, E)),
    }


def _reference(p, x, top_k=2, norm_topk=True):
    out, idx = ref._sparse_mlp(
        x.reshape(-1, x.shape[-1]), p, top_k=top_k, norm_topk=norm_topk,
        dtype=jnp.float32)
    return out.reshape(x.shape), idx.reshape(*x.shape[:-1], top_k)


@pytest.mark.parametrize("top_k", [1, 2, 3])
def test_matches_reference_forward(top_k):
    p = _params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 17, 16), jnp.float32)
    out, aux, idx = moe_ops.moe_mlp(_cfg(top_k=top_k), p, x)
    want, want_idx = _reference(p, x, top_k=top_k)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    assert aux.shape == () and np.isfinite(aux)


def test_matches_reference_without_renormalised_topk():
    """OLMoE's router: the chosen experts keep the weights the softmax
    over ALL experts gave them; renormalising would scale every output."""
    p = _params(jax.random.PRNGKey(10))
    x = jax.random.normal(jax.random.PRNGKey(11), (23, 16), jnp.float32)
    out, _, _ = moe_ops.moe_mlp(_cfg(norm_topk=False), p, x)
    want, _ = _reference(p, x, norm_topk=False)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    renorm, _ = _reference(p, x, norm_topk=True)
    assert np.abs(np.asarray(renorm) - np.asarray(want)).max() > 1e-3


def test_matches_reference_grads():
    """Differentiated directly, no ``vmap`` around it (the ragged path
    could not be)."""
    p = _params(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (29, 16), jnp.float32)
    cfg = _cfg(aux=0.0, z=0.0)

    g = jax.grad(lambda pp: jnp.sum(moe_ops.moe_mlp(cfg, pp, x)[0] ** 2))(p)
    g_ref = jax.grad(lambda pp: jnp.sum(_reference(pp, x)[0] ** 2))(p)
    for key in p:
        np.testing.assert_allclose(
            g[key], g_ref[key], rtol=5e-4, atol=5e-5, err_msg=key
        )


def test_matches_reference_grads_under_vmap():
    """The train engine differentiates through vmap-over-rows."""
    p = _params(jax.random.PRNGKey(6))
    x = jax.random.normal(jax.random.PRNGKey(7), (4, 13, 16), jnp.float32)
    cfg = _cfg(aux=0.0, z=0.0)

    def loss(params):
        out, aux, _ = jax.vmap(lambda row: moe_ops.moe_mlp(cfg, params, row))(x)
        return jnp.sum(out**2) + jnp.mean(aux)

    g = jax.jit(jax.grad(loss))(p)
    g_ref = jax.grad(lambda pp: jnp.sum(_reference(pp, x)[0] ** 2))(p)
    for key in p:
        np.testing.assert_allclose(
            g[key], g_ref[key], rtol=5e-4, atol=5e-4, err_msg=key
        )


def test_runs_on_mesh_with_sharded_experts():
    """Jits under the 8-device test mesh with data-sharded inputs AND the
    expert axis sharded over ``model``: the contraction over (expert,
    width) crosses the shards (expert parallelism by one psum)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("data", "model"))
    p = _params(jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (8, 16, 16), jnp.float32)
    cfg = _cfg()
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
    ps = {
        "router": jax.device_put(p["router"], NamedSharding(mesh, P())),
        **{k: jax.device_put(p[k], NamedSharding(mesh, P("model", None, None)))
           for k in ("w_gate", "w_up", "w_down")},
    }
    out, _, _ = jax.jit(lambda pp, xx: moe_ops.moe_mlp(cfg, pp, xx))(ps, xs)
    want, _ = _reference(p, x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_aux_loss_is_the_switch_loss():
    """Load-balance term X * sum_x(share of tokens choosing x * mean router
    probability of x) and z term mean(logsumexp(logits)^2), by hand."""
    p = _params(jax.random.PRNGKey(8))
    x = jax.random.normal(jax.random.PRNGKey(9), (31, 16), jnp.float32)
    _, aux, idx = moe_ops.moe_mlp(_cfg(aux=0.5, z=0.25), p, x)
    logits = np.asarray(x, np.float64) @ np.asarray(p["router"], np.float64)
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    chosen = np.zeros_like(probs)
    np.put_along_axis(chosen, np.asarray(idx), 1.0, axis=-1)
    balance = 4 * (chosen.mean(0) * probs.mean(0)).sum()
    z = (np.log(np.exp(logits).sum(-1)) ** 2).mean()
    np.testing.assert_allclose(aux, 0.5 * balance + 0.25 * z, rtol=1e-5)


def test_dispatch_is_no_longer_a_config_switch():
    """ROADMAP D5: one dispatch, chosen by the chip; the string is gone."""
    assert "dispatch" not in {
        f.name for f in dataclasses.fields(MoEConfig)}
    with pytest.raises(TypeError):
        MoEConfig(dispatch="ragged")


def test_experts_run_under_a_named_scope():
    """The three expert einsums, and nothing of the router, lie under
    ``moe_experts`` in the lowered program's ``op_name`` (HLO dumps and
    profilers that keep metadata find them there; this chip's xplane does
    not keep it, ``benchmark/moe_flops.py``)."""
    p = _params(jax.random.PRNGKey(8))
    x = jnp.zeros((5, 16), jnp.float32)
    hlo = jax.jit(lambda pp, xx: moe_ops.moe_mlp(_cfg(), pp, xx)).lower(
        p, x).as_text(debug_info=True)
    scoped = [ln for ln in hlo.splitlines() if moe_ops.EXPERTS_SCOPE in ln]
    assert sum("dot_general" in ln for ln in scoped) == 3
    assert not any("top_k" in ln or "softmax" in ln for ln in scoped)
