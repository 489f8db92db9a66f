"""nemotron_h as Nemotron-3-Super lays it out: blocks of ONE branch (a
Mamba-2 mixer with grouped B/C and a grouped gated norm, an attention
without positions, a LatentMoE expert layer), of which a weight tree holds
an expert-parallel rank's SHARE of the experts.

The program (``models/config.py``'s plan of one-branch blocks, ``ops/moe.py``
and ``ops/pallas/moe_grouped.py`` over two-matrix experts in a latent and
the held share, ``ops/ssm.py``'s grouped norm, the HF family) against the
plain reference (``benchmark/reference/nemotron_h.py``: a block-by-block
float32 forward that shares only the tree's names with the program), at a
small size on the CPU in float32 under ``Precision.HIGHEST``.

Tolerance ``TOL`` = 2e-5 on LOGITS of magnitude ~1: program and reference
run the same float32 arithmetic in another order (chunked matmuls against
a sequential scan, one contraction over the experts against an expert at a
time), which reads under 2e-6 here; what a fault costs is in the tests
that say so (a whole-width norm 3e-2, weights normalised over the held
experts 1e-1).
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import transformer as tfm
from areal_tpu.models.hf import family_for_model_type
from areal_tpu.ops import moe as moe_ops
from areal_tpu.ops import ssm as ssm_ops
from benchmark.reference import nemotron_h as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = family_for_model_type("nemotron_h")
TOL = 2e-5
RANKS, HELD = 4, 4

ARCH = {
    "model_type": "nemotron_h", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 128,
    "num_hidden_layers": 5, "hybrid_override_pattern": "MEM*E",
    "intermediate_size": 48, "layer_norm_epsilon": 1e-5,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 16,
    "n_groups": 4, "conv_kernel": 4, "chunk_size": 8, "expand": 2,
    "use_conv_bias": True, "mamba_hidden_act": "silu",
    "mlp_hidden_act": "relu2", "moe_intermediate_size": 48,
    "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 96,
    "n_routed_experts": HELD, "expert_parallel_size": RANKS,
    "expert_parallel_rank": 1, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_experts_per_tok": 6, "routed_scaling_factor": 5.0, "n_group": 1,
    "topk_group": 1, "tie_word_embeddings": False,
    "num_nextn_predict_layers": 0, "max_position_embeddings": 512,
}


def _cfg(arch=ARCH, **over):
    over = {"dtype": "float32", "use_flash_attention": False, **over}
    return dataclasses.replace(FAMILY.config_from_hf(arch), **over)


CFG = _cfg()


def seeded_params(cfg, seed=53):
    """``init_params`` with the matrices five times their init (at a hidden
    size of 64 normal(0, 0.02) leaves the logits flat), the convolution and
    ``D`` off their init, and the router's correction bias not zero."""
    p = tfm.init_params(cfg, jax.random.key(seed))
    p = jax.tree.map(lambda a: a * 5 if a.ndim >= 3 else a, p)
    ks = jax.random.split(jax.random.key(seed + 1), 4)
    mixer = dict(p["ssm_layers"]["ssm"])
    mixer["conv_w"] = jax.random.uniform(
        ks[0], mixer["conv_w"].shape, minval=-0.5, maxval=0.5)
    mixer["conv_b"] = jax.random.uniform(
        ks[1], mixer["conv_b"].shape, minval=-0.5, maxval=0.5)
    mixer["D"] = 1.0 + 0.1 * jax.random.normal(ks[2], mixer["D"].shape)
    mlp = dict(p["moe_layers"]["mlp"])
    mlp["b_router"] = 0.1 * jax.random.normal(ks[3], mlp["b_router"].shape)
    return {**p, "ssm_layers": {**p["ssm_layers"], "ssm": mixer},
            "moe_layers": {**p["moe_layers"], "mlp": mlp}}


@pytest.fixture(scope="module")
def params():
    return seeded_params(CFG)


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.key(3), (16,), 1, ARCH["vocab_size"])


@pytest.fixture(scope="module")
def want(params, ids):
    return np.asarray(ref.sequence_logits(params, ARCH, ids))


def _packed(params, cfg, ids, **kw):
    T = len(ids)
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p: tfm.forward_packed(
            p, cfg, ids, jnp.ones(T, jnp.int32), jnp.arange(T),
            remat=False, **kw))(params)


# --- (a) the three forwards against the reference's full forward ---------- #


def test_forward_packed_matches_the_reference(params, ids, want):
    out, routing = _packed(params, CFG, ids, with_routing=True)
    np.testing.assert_allclose(out, want, atol=TOL)
    # every expert block's choice, of all the router scores
    assert routing.shape == (2, 16, 6) and int(routing.max()) >= HELD
    own = ref.routing(params, ARCH, list(map(int, ids)), "float32", 16)
    assert (np.sort(routing, -1) == np.sort(own, -1)).all()


def test_dense_prefill_then_decode_matches_the_reference(params, ids, want):
    n0, T = 8, len(ids)
    step = jax.jit(lambda cache, tok: tfm.decode_step(params, CFG, cache, tok))
    with jax.default_matmul_precision("highest"):
        cache = tfm.KVCache.empty(CFG, 1, 32)
        logits, cache = tfm.prefill(
            params, CFG, cache, ids[None, : n0 + 4].at[:, n0:].set(0),
            jnp.array([n0]))
        np.testing.assert_allclose(logits[0], want[n0 - 1], atol=TOL)
        for t in range(n0, T):
            logits, cache = step(cache, ids[t : t + 1])
            np.testing.assert_allclose(logits[0], want[t], atol=TOL)


@pytest.mark.parametrize("grouped", [False, True])
def test_paged_prefill_then_decode_matches_the_reference(
        params, ids, want, grouped):
    """Chunked prefill over the page pool, then decode steps through it,
    two rows of one batch; once with the routed experts on the einsums and
    once on the ``moe_grouped`` kernel (interpret mode) over the held
    stacks."""
    page, n0, T = 8, 10, len(ids)
    cache = tfm.PagedKVCache.empty(CFG, 8, page)
    ssm = tfm.row_state_empty(CFG, 2)
    table = jnp.array([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    rows = jnp.stack([ids, ids])
    slots = jnp.arange(2)
    step = jax.jit(lambda cache, tok, lens, ssm: tfm.decode_step_paged(
        params, CFG, cache, tok, table, lens, jnp.ones(2, bool),
        with_routing=True, moe_grouped=grouped, ssm=ssm))
    with jax.default_matmul_precision("highest"):
        cache, ssm = tfm.extend_paged(
            params, CFG, cache, rows[:, :n0], table, jnp.zeros(2, jnp.int32),
            jnp.full(2, n0, jnp.int32), skip_pool=True, ssm=ssm, slots=slots,
            moe_grouped=grouped)
        lens = jnp.full(2, n0, jnp.int32)
        for t in range(n0, T):
            logits, cache, lens, routing, ssm = step(
                cache, rows[:, t], lens, ssm)
            np.testing.assert_allclose(logits[0], want[t], atol=TOL)
            np.testing.assert_allclose(logits[1], want[t], atol=TOL)
    assert routing.shape == (2, 2, 6)


# --- (b) the four shares add up to the uncut layer ------------------------ #


def _share(params, rank):
    """Rank ``rank``'s configuration and its expert block's weights, cut
    from an UNCUT tree."""
    cfg = _cfg(dict(ARCH, expert_parallel_rank=rank))
    mlp = dict(jax.tree.map(lambda a: a[0], params["moe_layers"]["mlp"]))
    for k in ("w_up", "w_down"):
        mlp[k] = mlp[k][rank * HELD : (rank + 1) * HELD]
    return cfg, mlp


def test_the_four_shares_and_the_shared_expert_once_make_the_whole_layer():
    """One test ties the share to the model: the program's expert layer run
    as each of the four ranks (its routed part, and the shared expert that
    every rank computes alike) against the reference's UNCUT layer of all
    16 experts: routed parts summed, shared expert counted once."""
    whole_arch = dict(
        ARCH, n_routed_experts=RANKS * HELD, expert_parallel_size=1,
        expert_parallel_rank=0)
    whole = seeded_params(_cfg(whole_arch))
    x = jax.random.normal(jax.random.key(9), (12, ARCH["hidden_size"]))
    routed, shared = ref.expert_block_parts(whole, whole_arch, x)
    h = np.asarray(ref._rms(
        x, whole["moe_layers"]["ln1"]["weight"][0], 1e-5))
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for rank in range(RANKS):
            cfg, mlp = _share(whole, rank)
            out, _, top = moe_ops.moe_mlp(cfg, mlp, jnp.asarray(h))
            total = total + np.asarray(out) - shared
            part, same = ref.expert_block_parts(
                {"moe_layers": {"ln1": whole["moe_layers"]["ln1"], "mlp": {
                    k: v[None] for k, v in mlp.items()}}},
                dict(ARCH, expert_parallel_rank=rank), x)
            np.testing.assert_allclose(out, part + same, atol=TOL)
    assert int(top.max()) >= HELD        # the router keeps all 16 outputs
    np.testing.assert_allclose(total, routed, atol=TOL)
    # a share is a PART: no rank's routed sum is the whole
    assert np.abs(np.asarray(out) - shared - routed).max() > 1e-2


def test_weights_normalised_over_the_held_experts_are_another_function(
        params, ids, want):
    """The plausible wrong share, the reference's control: 1e-1 on the
    logits, 5,000 x the tolerance."""
    wrong = ref.sequence_logits(
        params, dict(ARCH, control_norm_over_held=True), ids)
    assert np.abs(np.asarray(wrong) - want).max() > 1e-1


# --- (c) dense and grouped dispatch agree on a share ---------------------- #


@pytest.mark.parametrize("T", [5, 40])
def test_dense_and_grouped_dispatch_agree_on_a_share(params, T):
    mlp = params["moe_layers"]["mlp"]
    x = jax.random.normal(jax.random.key(T), (T, ARCH["hidden_size"]))
    rest = {k: v[1] for k, v in mlp.items() if k not in tfm._ROUTED}
    with jax.default_matmul_precision("highest"):
        dense, _, top = moe_ops.moe_mlp(
            CFG, jax.tree.map(lambda a: a[1], mlp), x)
        grouped, _, top2 = moe_ops.moe_mlp(
            CFG, rest, x,
            routed=({k: mlp[k] for k in ("w_up", "w_down")}, jnp.int32(1)))
    assert (top == top2).all()
    held = (top >= HELD) & (top < 2 * HELD)       # rank 1's
    assert held.any() and not held.all()
    np.testing.assert_allclose(dense, grouped, atol=TOL)


# --- (d) the grouped gated norm ------------------------------------------- #


def test_grouped_gated_norm(params, ids, want):
    """Over each group's channels, as the reference has it; over all of
    ``d_inner`` it is another function (3e-2 on the logits); and with one
    group the switch changes NOTHING, bit for bit."""
    whole = dataclasses.replace(
        CFG, ssm=dataclasses.replace(CFG.ssm, norm_per_group=False))
    assert np.abs(np.asarray(_packed(params, whole, ids)) - want).max() > 3e-2
    p = jax.tree.map(lambda a: a[0], params["ssm_layers"]["ssm"])
    y = jax.random.normal(jax.random.key(1), (3, CFG.ssm.d_inner))
    z = jax.random.normal(jax.random.key(2), (3, CFG.ssm.d_inner))
    one = dataclasses.replace(CFG.ssm, n_groups=1, norm_per_group=True)
    plain = dataclasses.replace(one, norm_per_group=False)
    a, b = (ssm_ops._gated_out(dataclasses.replace(CFG, ssm=s), p, y, z)
            for s in (one, plain))
    assert np.array_equal(a, b)


# --- (e) the HF round trip ------------------------------------------------ #


def test_hf_round_trip_and_a_whole_checkpoint_loads_the_ranks_share(params):
    hf_cfg = FAMILY.config_to_hf(CFG)
    assert hf_cfg["n_routed_experts"] == HELD
    assert (hf_cfg["expert_parallel_size"], hf_cfg["expert_parallel_rank"]) == (
        RANKS, 1)
    assert _cfg(hf_cfg) == CFG
    sd = FAMILY.params_to_hf(params, CFG)
    # the experts under their place among all 16
    assert "backbone.layers.1.mixer.experts.7.up_proj.weight" in sd
    assert "backbone.layers.1.mixer.experts.0.up_proj.weight" not in sd
    assert sd["backbone.layers.0.mixer.in_proj.weight"].shape == (
        CFG.ssm.in_dim, 64)
    back = FAMILY.params_from_hf(sd, CFG)
    jax.tree.map(np.testing.assert_array_equal, back,
                 jax.tree.map(np.asarray, params))
    # a checkpoint of ALL the experts: the rank's four are taken
    whole_cfg = _cfg(dict(
        ARCH, n_routed_experts=RANKS * HELD, expert_parallel_size=1,
        expert_parallel_rank=0))
    whole = seeded_params(whole_cfg)
    got = FAMILY.params_from_hf(FAMILY.params_to_hf(whole, whole_cfg), CFG)
    np.testing.assert_array_equal(
        got["moe_layers"]["mlp"]["w_up"],
        np.asarray(whole["moe_layers"]["mlp"]["w_up"])[:, HELD : 2 * HELD])
    assert got["moe_layers"]["mlp"]["router"].shape == (2, 64, RANKS * HELD)


def test_the_published_configuration_builds():
    """The benchmark's configuration file and the catalog row's whole
    config (88 blocks, 512 experts), shapes only."""
    with open(os.path.join(
            ROOT, "benchmark", "configs",
            "nemotron3-super-l11-ep4.json")) as f:
        arch = json.load(f)
    cfg = FAMILY.config_from_hf(arch)
    assert cfg.moe.held == (128, 0) and cfg.moe.num_experts == 512
    assert cfg.mixers.count("moe") == 5 and cfg.ssm.n_groups == 8
    shapes = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.key(0), jnp.bfloat16))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n == arch["parameters"] == 4_648_163_712
    full = {**arch, **arch["reduced_from"], "num_nextn_predict_layers": 0,
            "expert_parallel_size": 1}
    cfg = FAMILY.config_from_hf(full)
    assert (cfg.n_layers, cfg.n_moe_layers, cfg.n_attn_layers) == (88, 40, 8)
    assert "".join({"ssm": "M", "attn": "*", "moe": "E"}[m]
                   for m in cfg.mixers) == full["hybrid_override_pattern"]
    shapes = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.key(0), jnp.bfloat16))
    assert shapes["moe_layers"]["mlp"]["w_up"].shape == (40, 512, 1024, 2688)


# --- (f) what is refused, by name ----------------------------------------- #


@pytest.mark.parametrize("change,match", [
    ({"n_group": 2}, "n_group"),
    ({"topk_group": 2}, "topk_group"),
    ({"hybrid_override_pattern": "MEM-EM"}, "dense feed-forward block"),
    ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"expert_parallel_rank": 4}, "expert_parallel_rank"),
])
def test_refused_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        FAMILY.config_from_hf(dict(ARCH, **change))


def test_a_router_outside_a_plan_of_one_branch_blocks_is_refused():
    with pytest.raises(ValueError, match="one_branch"):
        dataclasses.replace(CFG, one_branch=False)
    with pytest.raises(ValueError, match="experts held"):
        dataclasses.replace(
            CFG, moe=dataclasses.replace(CFG.moe, held_offset=14))


# --- (g) the dense dispatch differentiates through the plan --------------- #


def test_gradients_through_a_plan_with_expert_blocks(params, ids):
    def loss(p):
        out, aux = _packed(p, CFG, ids[:8], with_aux=True)
        return jnp.mean(jax.nn.logsumexp(out, -1)) + aux

    grads = jax.jit(jax.grad(loss))(params)
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
    mlp = grads["moe_layers"]["mlp"]
    for name in ("w_up", "w_down", "latent_down", "latent_up", "shared_up"):
        assert float(jnp.abs(mlp[name]).max()) > 0, name
