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


# ------------------------------------------------------------------ #
# the grouped-matmul kernel (``ops/pallas/moe_grouped.py``, interpret
# mode here) against the einsums
# ------------------------------------------------------------------ #

def _grouped_case(T=24, X=8, k=2, scoring="softmax", act="silu",
                  early=False, E=16, F=32):
    cfg = dataclasses.replace(
        _cfg(top_k=k),
        activation_function=act,
        moe=MoEConfig(
            num_experts=X, top_k=k, aux_loss_coeff=0.01, z_loss_coeff=0.001,
            scoring=scoring, router_on_layer_input=early,
            routed_scaling_factor=2.5 if scoring == "sigmoid" else 1.0,
        ),
    )
    return cfg, T, E, F


GROUPED_CASES = {
    # name: (case keywords, how the router's logits are bent, layer)
    "random_routing": (dict(), None, 1),
    "an_expert_with_no_row": (dict(), "starve", 1),
    "every_row_on_one_expert": (dict(k=1), "one", 1),
    "pairs_not_a_multiple_of_the_tile": (dict(T=37, k=3), None, 1),
    "one_row": (dict(T=1), None, 1),
    "more_rows_than_a_tile_an_expert": (dict(T=300, X=4), "one_heavy", 1),
    "sigmoid_scoring": (dict(scoring="sigmoid"), None, 1),
    "reglu": (dict(act="relu"), None, 1),
    "router_on_the_layer_input": (dict(early=True), None, 1),
    "first_layer_of_the_stack": (dict(), None, 0),
    "last_layer_of_the_stack": (dict(), None, 2),
}


@pytest.mark.parametrize("name", list(GROUPED_CASES))
def test_grouped_kernel_matches_the_einsums(name):
    """``moe_mlp`` with the routed matrices as (stack, index) — the
    kernel — against ``moe_mlp`` with the layer's slice — the einsums:
    outputs within rounding (float32 here: the order of the sums),
    ``top_idx`` identical, the aux loss to the last bit or the one before
    it."""
    kw, bend, layer = GROUPED_CASES[name]
    cfg, T, E, F = _grouped_case(**kw)
    X, L = cfg.moe.num_experts, 3
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    w = lambda key, shape: jax.random.normal(key, shape, jnp.float32) * 0.1
    stacks = {
        "w_gate": w(ks[0], (L, X, E, F)),
        "w_up": w(ks[1], (L, X, E, F)),
        "w_down": w(ks[2], (L, X, F, E)),
    }
    router = w(ks[3], (E, X)) * 10
    x = jax.random.normal(ks[4], (T, E), jnp.float32)
    rest = {"router": router}
    if cfg.moe.scoring == "sigmoid":
        rest["b_router"] = w(ks[5], (X,))
    if bend == "starve":        # expert 3 never wins
        rest["router"] = router.at[:, 3].set(0.0)
        rest["b_router"] = jnp.zeros((X,)).at[3].set(-1e9)
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, scoring="sigmoid"))
    elif bend in ("one", "one_heavy"):   # expert 2 always wins
        rest["router"] = jnp.zeros((E, X))
        rest["b_router"] = jnp.zeros((X,)).at[2].set(
            5.0 if bend == "one" else 0.5)
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, scoring="sigmoid"))
    early = (
        jax.random.normal(ks[6], (T, E), jnp.float32)
        if cfg.moe.router_on_layer_input else None)
    dense_p = dict(rest, **{k: v[layer] for k, v in stacks.items()})
    want, aux_w, idx_w = jax.jit(
        lambda p: moe_ops.moe_mlp(cfg, p, x, router_input=early))(dense_p)
    got, aux_g, idx_g = jax.jit(
        lambda s, i: moe_ops.moe_mlp(
            cfg, rest, x, router_input=early, routed=(s, i))
    )(stacks, jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(idx_g), np.asarray(idx_w))
    # one formula over the same choice, compiled into two programs
    np.testing.assert_allclose(
        np.asarray(aux_g), np.asarray(aux_w), rtol=1e-6, atol=0)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    if bend == "starve":
        assert not (np.asarray(idx_g) == 3).any()
    if bend == "one":
        assert (np.asarray(idx_g) == 2).all()


def test_grouped_kernel_in_bfloat16_is_within_rounding_of_the_einsums():
    cfg, T, E, F = _grouped_case(T=64, E=128, F=128)
    X = cfg.moe.num_experts
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    w = lambda key, shape: (
        jax.random.normal(key, shape, jnp.float32) * 0.1
    ).astype(jnp.bfloat16)
    stacks = {
        "w_gate": w(ks[0], (2, X, E, F)), "w_up": w(ks[1], (2, X, E, F)),
        "w_down": w(ks[2], (2, X, F, E)),
    }
    rest = {"router": w(ks[3], (E, X))}
    x = jax.random.normal(ks[4], (T, E), jnp.bfloat16)
    want, _, idx_w = moe_ops.moe_mlp(
        cfg, dict(rest, **{k: v[1] for k, v in stacks.items()}), x)
    got, _, idx_g = moe_ops.moe_mlp(
        cfg, rest, x, routed=(stacks, jnp.int32(1)))
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(idx_g), np.asarray(idx_w))
    scale = float(jnp.abs(want.astype(jnp.float32)).max())
    # two or three roundings to 8 bits of mantissa apart
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=scale * 2 ** -6)


def test_tile_plan_pads_every_run_to_whole_tiles():
    """The kernel's walk over runs of 0, 5, 0, 40, 3 pairs in tiles of 16:
    expert 1 takes tile 0, expert 3 tiles 1-3, expert 4 tile 4; an expert
    with no pair takes none; the tiles past the fifth repeat it."""
    from areal_tpu.ops.pallas import moe_grouped as mg

    sizes = jnp.asarray([0, 5, 0, 40, 3])
    expert, first_row, n_active = mg.tile_plan(sizes, 8, 16)
    assert int(n_active[0]) == 5
    assert expert.tolist() == [1, 3, 3, 3, 4, 4, 4, 4]
    assert first_row.tolist() == [0, 0, 16, 16, 64]
    assert [mg.row_tile(n, x) for n, x in (
        (8, 256), (2048, 256), (8192, 256), (512, 64), (8192, 64),
    )] == [16, 16, 64, 16, 128]


class _Mesh:
    def __init__(self, size):
        self.size = size


# JoyAI's, OLMoE's and SmallThinker's experts a token; the rows of their
# decode steps and admission programs (ISSUE 40's expected verdicts)
@pytest.mark.parametrize(
    "platform,mesh,dtype,T,X,k,want",
    [
        ("tpu", None, "bfloat16", 256, 256, 8, True),    # JoyAI decode
        ("tpu", None, "bfloat16", 1024, 256, 8, True),   # a full wave
        ("tpu", None, "bfloat16", 1024, 64, 8, True),
        ("tpu", None, "bfloat16", 1024, 64, 6, True),
        ("tpu", None, "bfloat16", 128, 256, 8, False),   # one-row admission
        ("tpu", None, "bfloat16", 128, 64, 8, False),
        ("tpu", None, "bfloat16", 64, 64, 8, False),     # OLMoE decode
        ("tpu", None, "bfloat16", 112, 64, 6, False),    # SmallThinker
        ("tpu", None, "bfloat16", 8, 256, 8, True),      # 23 % of them hit
        ("tpu", None, "bfloat16", 8, 64, 8, False),      # 66 % hit
        ("tpu", _Mesh(1), "bfloat16", 256, 256, 8, True),
        ("tpu", _Mesh(4), "bfloat16", 256, 256, 8, False),
        ("tpu", None, "float32", 256, 256, 8, False),    # a lazy cast
        ("cpu", None, "bfloat16", 256, 256, 8, False),
        ("tpu", None, "bfloat16", 0, 256, 8, False),
    ],
)
def test_moe_grouped_applies(platform, mesh, dtype, T, X, k, want):
    cfg = dataclasses.replace(
        _cfg(top_k=k), dtype="bfloat16",
        moe=MoEConfig(num_experts=X, top_k=k))
    params = {"layers": {"mlp": {
        "w_gate": jax.ShapeDtypeStruct((2, X, 16, 32), jnp.dtype(dtype))}}}
    assert moe_ops.moe_grouped_applies(
        cfg, params, mesh, rows=T, platform=platform) is want


def test_moe_grouped_applies_to_no_model_without_a_router():
    cfg = dataclasses.replace(_cfg(), mlp_type="gated", moe=None)
    assert not moe_ops.moe_grouped_applies(
        cfg, {}, None, rows=1024, platform="tpu")
