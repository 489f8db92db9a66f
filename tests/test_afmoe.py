"""Trinity-Mini (family ``afmoe``) against its plain reference: the family,
the three forwards without a page pool, the router, the trainer and the
benchmark's check. The engine's paged path is ``test_afmoe_engine.py``.

A tiny model of the family's shape: 2 dense + 6 expert layers (and 2 + 10)
in periods of three WINDOW layers (rotary, window 8) and one FULL layer (no
positional encoding) counted over the MODEL's layers, so the expert stack
starts at position 2 of the period; hidden 64, 4 query / 2 key-value heads
of 16 with a norm a head, a GATE on attention's output, four norms a layer,
8 SwiGLU experts of width 32 with 2 a token chosen by sigmoid score plus a
bias and weighted by the score alone, one shared expert, the embedding
times sqrt(64); seeded random weights with gains away from 1, float32
everywhere. The reference is the benchmark's
(``benchmark/reference/afmoe.py``): plain ``jax.numpy``, it walks
``layer_types`` layer by layer and knows nothing of stacks or periods.

Tolerance: 1e-4 nats on log-probabilities. Both sides compute in float32
on the CPU, so no rounding difference flips a top-2 choice; what is left is
summation order, about 1e-6. Each mechanism left out or put in the wrong
place (the SENSITIVITY cases) moves a log-probability by 1e-2 to 1 nat; the
same path in bfloat16 is off by more than 1e-3.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.api.data import MicroBatchSpec, SequenceSample
from areal_tpu.api.model import PPOHyperparameters
from areal_tpu.base import flops as flops_mod
from areal_tpu.interfaces.ppo import PPOActorInterface
from areal_tpu.models import hf as hf_conv
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import MLAConfig, ModelConfig
from areal_tpu.ops import moe as moe_ops
from areal_tpu.ops import ppo as ppo_ops
from areal_tpu.parallel.mesh import ParallelConfig
from areal_tpu.train.engine import OptimizerConfig, TrainEngine
from benchmark import weights as bench_weights
from benchmark.reference import afmoe as ref

TOL_NATS = 1e-4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S, F = "sliding_attention", "full_attention"

# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# Trinity-Mini), key for key
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": [S, S, S, F] * 8, "load_balance_coeff": 0.001,
    "max_position_embeddings": 131072, "model_type": "afmoe",
    "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_expert_groups": 1,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192,
}

WINDOW = 8


def _arch(depth=8, **over):
    return {**dict(
        PUBLISHED, hidden_size=64, head_dim=16, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=96, moe_intermediate_size=32,
        num_experts=8, num_experts_per_tok=2, vocab_size=128,
        num_hidden_layers=depth, layer_types=([S, S, S, F] * 8)[:depth],
        sliding_window=WINDOW, max_position_embeddings=256), **over}


ARCH = _arch()
FAMILY = hf_conv.family_for_model_type("afmoe")


def _cfg(arch=ARCH, **over) -> ModelConfig:
    return dataclasses.replace(
        FAMILY.config_from_hf(arch), dtype="float32", **over)


CFG = _cfg()


def _weights(cfg, seed=20261004):
    """Seeded weights with gains away from 1 (the benchmark's fill), and
    the router's bias TEN times the fill's normal(0, 0.02): it then changes
    the choice for a good share of the tokens."""
    shapes = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.key(0)))
    p = bench_weights.make_weights(shapes, seed, jnp.float32)
    p["layers"]["mlp"]["b_router"] = 10.0 * p["layers"]["mlp"]["b_router"]
    return p


@pytest.fixture(scope="module")
def params():
    return _weights(CFG)


@pytest.fixture()
def rng():
    return np.random.default_rng(7)


def _ref_logprobs(params, tokens, arch=ARCH, window="config"):
    pad = -(-len(tokens) // 64) * 64
    lp, _ = ref.next_token_logprobs(
        params, arch, list(tokens), "float32", pad, window=window)
    return lp


def _forward_logprobs(cfg, params, ids):
    n = len(ids)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, i: tfm.forward_packed(
            p, cfg, i, jnp.ones((n,), jnp.int32), jnp.arange(n)))(
                params, jnp.asarray(ids, jnp.int32))
    lp = jax.nn.log_softmax(logits, axis=-1)
    return np.asarray(lp[np.arange(n - 1), np.asarray(ids[1:])])


def _toks(rng, n):
    return [int(x) for x in rng.integers(1, 128, n)]


# ------------------------------------------------------------------ #
# (i) the family and its tree
# ------------------------------------------------------------------ #

def test_family_reads_the_published_config_key_for_key():
    """Every key of the catalog row builds the model (all 32 layers: 2
    dense + 30 expert, 8 periods): writing the config back gives the row."""
    cfg = FAMILY.config_from_hf(PUBLISHED)
    back = FAMILY.config_to_hf(cfg)
    assert {k: back[k] for k in PUBLISHED} == PUBLISHED
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.n_moe_layers) == (32, 2, 30)
    assert (cfg.period, cfg.n_periods, cfg.cache_layers) == (4, 8, 32)
    assert cfg.layer_kinds == (
        (2048, True), (2048, True), (2048, True), (None, False))
    assert (cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 4, 128)
    moe = cfg.moe
    assert (moe.num_experts, moe.top_k, cfg.expert_dim) == (128, 8, 1024)
    assert moe.scoring == "sigmoid" and moe.selection_bias
    assert moe.norm_topk_prob and moe.routed_scaling_factor == 2.826
    assert moe.n_shared_experts == 1 and moe.aux_loss_coeff == 0.001
    assert not moe.router_on_layer_input
    assert cfg.attn_gate and cfg.norm_branch_out and cfg.normalize_embed
    assert cfg.qk_layernorm and cfg.qk_norm_over == "head"
    assert cfg.mlp_type == "moe" and cfg.intermediate_dim == 6144
    assert not cfg.tied_embedding and not cfg.use_attention_bias
    assert (cfg.rotary_base, cfg.n_positions) == (10000, 131072)
    assert cfg.layer_norm_epsilon == 1e-5
    # the published "26B"
    assert round(flops_mod.param_count(cfg) / 1e9, 1) == 26.1


def test_benchmark_config_is_the_published_one_cut_in_depth():
    with open(os.path.join(
            ROOT, "benchmark", "configs", "trinity-mini-l8.json")) as f:
        arch = json.load(f)
    cut = dict(PUBLISHED, num_hidden_layers=8, layer_types=[S, S, S, F] * 2)
    assert {k: arch[k] for k in PUBLISHED} == cut
    assert arch["reduced"] == ["num_hidden_layers", "layer_types"]
    assert arch["reduced_from"] == {
        "num_hidden_layers": 32, "layer_types": PUBLISHED["layer_types"]}
    assert "reader_aliases" not in arch
    cfg = FAMILY.config_from_hf(arch)
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.period, cfg.n_periods) == (
        8, 2, 4, 2)
    shapes = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.key(0), dtype=jnp.bfloat16))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n == arch["parameters"] == 5_984_817_920
    # the matrices alone (no norm gain, no router bias), as the trainer's
    # FLOPs count them
    assert flops_mod.param_count(cfg) == (
        2 * 65_011_712 + 6 * 839_122_944 + 2 * 200_192 * 2048)


@pytest.mark.parametrize("key,value", [
    ("n_group", 2), ("topk_group", 2), ("num_expert_groups", 4),
    ("num_limited_groups", 2), ("score_func", "softmax"),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}),
    ("layer_types", [S, S, S, "chunked_attention"] * 2),
    ("layer_types", [S, S, S]),
    ("layer_types", [S, S, F, S] * 2),
    ("global_attn_every_n_layers", 2),
    ("sliding_window", None),
    ("attention_bias", True), ("hidden_act", "gelu"),
    ("num_dense_layers", 8),
])
def test_family_refuses_what_it_does_not_implement(key, value):
    with pytest.raises(ValueError, match="afmoe"):
        FAMILY.config_from_hf(dict(ARCH, **{key: value}))


@pytest.mark.parametrize("over", [
    dict(mla=MLAConfig(16, 16, 8, 8, 8), n_kv_heads=4),
    dict(diff_attn=True),
    dict(n_mtp_layers=1),
    dict(n_passes=2),
    dict(moe=dataclasses.replace(CFG.moe, router_on_layer_input=True)),
    dict(residual_scaling=True),
    dict(layer_pattern=((8, True), (8, True), (None, False))),
], ids=["latent_attention", "differential", "mtp", "looped",
        "router_on_layer_input", "residual_scaling", "period_of_3_in_8"])
def test_combinations_no_published_model_uses_stay_refused(over):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **over)


@pytest.mark.parametrize("n_dense,every,depth", [
    (1, 4, 8), (3, 2, 6), (5, 4, 8), (4, 4, 8)])
def test_a_period_runs_across_the_two_stacks_wherever_the_boundary_falls(
        rng, n_dense, every, depth):
    """The period is counted over the model's layers: the expert stack
    starts at position ``n_dense % period`` of it, whole periods of dense
    layers included (5 and 4 dense of 8), and the forward is the
    reference's, which walks ``layer_types`` and knows no stack."""
    kinds = [F if (l + 1) % every == 0 else S for l in range(depth)]
    arch = _arch(depth, num_dense_layers=n_dense, layer_types=kinds,
                 global_attn_every_n_layers=every)
    cfg = _cfg(arch)
    assert (cfg.period, cfg.n_dense_layers, cfg.n_moe_layers) == (
        every, n_dense, depth - n_dense)
    p = _weights(cfg, 3)
    assert jax.tree.leaves(p["dense_layers"])[0].shape[0] == n_dense
    ids = _toks(rng, 24)
    np.testing.assert_allclose(
        _forward_logprobs(cfg, p, ids), _ref_logprobs(p, ids, arch),
        atol=TOL_NATS)


def test_tree_holds_the_gate_in_both_stacks_with_qs_axes():
    shapes = jax.eval_shape(lambda: tfm.init_params(CFG, jax.random.key(0)))
    axes = tfm.param_logical_axes(CFG)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    for stack, n in (("dense_layers", 2), ("layers", 6)):
        a = shapes[stack]["attn"]
        assert a["wg"].shape == a["wq"].shape == (n, 64, 64)
        assert axes[stack]["attn"]["wg"] == axes[stack]["attn"]["wq"]
        assert a["q_norm"].shape == (n, 16)
        for norm in ("ln1", "attn_out_ln", "ln2", "mlp_out_ln"):
            assert shapes[stack][norm]["weight"].shape == (n, 64)
    assert shapes["dense_layers"]["mlp"]["w_gate"].shape == (2, 64, 96)
    assert "router" not in shapes["dense_layers"]["mlp"]
    mlp = shapes["layers"]["mlp"]
    assert mlp["w_gate"].shape == (6, 8, 64, 32)
    assert mlp["b_router"].shape == (6, 8)
    assert mlp["shared_up"].shape == (6, 64, 32)


def test_hf_names_round_trip(tmp_path):
    """Through disk, under the published names."""
    p = jax.tree.map(np.asarray, _weights(CFG, 5))
    hf_conv.save_hf_checkpoint(p, CFG, "afmoe", str(tmp_path))
    from safetensors.numpy import load_file

    sd = load_file(str(tmp_path / "model.safetensors"))
    for name in (
        "model.embed_tokens.weight",
        "model.layers.0.self_attn.q_proj.weight",
        "model.layers.0.self_attn.gate_proj.weight",
        "model.layers.7.self_attn.gate_proj.weight",
        "model.layers.3.self_attn.q_norm.weight",
        "model.layers.3.self_attn.k_norm.weight",
        "model.layers.1.mlp.gate_proj.weight",
        "model.layers.1.mlp.down_proj.weight",
        "model.layers.2.mlp.router.gate.weight",
        "model.layers.2.mlp.expert_bias",
        "model.layers.7.mlp.experts.7.gate_proj.weight",
        "model.layers.2.mlp.experts.0.up_proj.weight",
        "model.layers.2.mlp.experts.0.down_proj.weight",
        "model.layers.2.mlp.shared_experts.gate_proj.weight",
        "model.layers.4.input_layernorm.weight",
        "model.layers.4.post_attention_layernorm.weight",
        "model.layers.4.pre_mlp_layernorm.weight",
        "model.layers.4.post_mlp_layernorm.weight",
        "model.norm.weight", "lm_head.weight",
    ):
        assert name in sd, name
    assert "model.layers.1.mlp.router.gate.weight" not in sd     # dense
    assert "model.layers.2.mlp.gate_proj.weight" not in sd       # expert
    assert sd["model.layers.2.mlp.router.gate.weight"].shape == (8, 64)
    assert sd["model.layers.2.mlp.expert_bias"].shape == (8,)
    assert sd["model.layers.0.self_attn.gate_proj.weight"].shape == (64, 64)
    assert sd["model.layers.2.mlp.experts.0.down_proj.weight"].shape == (64, 32)
    cfg2, p2 = hf_conv.load_hf_checkpoint(str(tmp_path))
    assert dataclasses.replace(cfg2, dtype="float32") == CFG
    jax.tree.map(np.testing.assert_array_equal, p, p2)


def test_the_gates_matmul_is_counted_with_the_projections():
    """``base/flops.py``: the gate is a fifth projection as wide as q's, in
    every layer of both stacks, in the parameter count and so in the
    forward's and the train step's FLOPs."""
    plain = dataclasses.replace(CFG, attn_gate=False)
    gate = CFG.n_layers * CFG.hidden_dim * CFG.n_q_heads * CFG.head_dim
    assert flops_mod.param_count(CFG) - flops_mod.param_count(plain) == gate
    assert flops_mod.forward_flops(CFG, 100) - flops_mod.forward_flops(
        plain, 100) == 2 * gate * 100
    assert flops_mod.train_flops(CFG, 100) - flops_mod.train_flops(
        plain, 100) == 6 * gate * 100


# ------------------------------------------------------------------ #
# (ii) the forwards without a page pool, and the router
# ------------------------------------------------------------------ #

@pytest.fixture(scope="module")
def deep():
    arch = _arch(12)
    cfg = _cfg(arch)
    return arch, cfg, _weights(cfg, 9)


@pytest.mark.parametrize("depth", [8, 12])
@pytest.mark.parametrize("n", [5, 40])
def test_forward_matches_reference(params, deep, rng, n, depth):
    """Two lengths, of which one passes the window of 8; 2 dense + 6 expert
    layers (the scan runs ONE whole period of the expert stack) and 2 + 10
    (two)."""
    arch, cfg, p = (ARCH, CFG, params) if depth == 8 else deep
    ids = _toks(rng, n)
    np.testing.assert_allclose(
        _forward_logprobs(cfg, p, ids), _ref_logprobs(p, ids, arch),
        atol=TOL_NATS)


def test_forgetting_the_window_is_seen(params, rng):
    """The benchmark's second control at test size."""
    ids = _toks(rng, 40)
    own, full = (_ref_logprobs(params, ids, window=w) for w in ("config", None))
    np.testing.assert_allclose(own[: WINDOW - 1], full[: WINDOW - 1], atol=1e-6)
    assert np.abs(own[WINDOW:] - full[WINDOW:]).mean() > 1e-2


@pytest.mark.parametrize("depth", [8, 12])
def test_routing_matches_reference(params, deep, rng, depth):
    arch, cfg, p = (ARCH, CFG, params) if depth == 8 else deep
    ids = _toks(rng, 24)
    with jax.default_matmul_precision("highest"):
        _, chosen = tfm.forward_packed(
            p, cfg, jnp.asarray(ids, jnp.int32),
            jnp.ones((24,), jnp.int32), jnp.arange(24), with_routing=True)
    want = ref.routing(p, arch, ids)
    assert chosen.shape == (depth - 2, 24, 2)
    np.testing.assert_array_equal(np.asarray(chosen), want)


def test_the_bias_changes_the_choice_and_never_a_weight(params, rng):
    """``moe_ops._route`` against the reference's: with the bias, some
    token keeps another pair of experts than without, and every combine
    weight is ``route_scale`` times the sigmoid score of a chosen expert
    over the chosen scores' sum: the bias is in none of them."""
    h = jnp.asarray(rng.normal(0, 1, (200, 64)), jnp.float32)
    router = params["layers"]["mlp"]["router"][0]
    bias = params["layers"]["mlp"]["b_router"][0]
    free = jnp.full((200, 2), -1, jnp.int32)
    vals, idx, _, _ = moe_ops._route(CFG, router, h, bias)
    idx_ref, w_ref, _ = ref._route(
        h, router, bias, free, top_k=2, norm=True, scale=2.826)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(w_ref), atol=1e-6)
    np.testing.assert_allclose(np.asarray(vals).sum(-1), 2.826, atol=1e-5)
    _, idx0, _, _ = moe_ops._route(CFG, router, h, jnp.zeros_like(bias))
    moved = (np.sort(np.asarray(idx), -1) != np.sort(np.asarray(idx0), -1)
             ).any(-1)
    assert 0 < moved.sum() < 200
    s = np.asarray(jax.nn.sigmoid(h @ router))
    took = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(vals), 2.826 * took / took.sum(-1, keepdims=True),
        atol=1e-6)


def test_combine_weights_of_the_model_match_reference(params, rng):
    ids = _toks(rng, 16)
    taken, weights = ref.combine_weights(params, ARCH, ids)
    assert taken.shape == weights.shape == (6, 16, 2)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.826, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(taken), ref.routing(params, ARCH, ids))


def test_the_reference_given_a_routing_takes_it(params, rng):
    """``forced_routing``: the experts handed in are the experts taken
    (their weights the router's own scores of them), -1 leaves the router
    free, and the router's OWN choice is still reported."""
    ids = _toks(rng, 12)
    own = ref.routing(params, ARCH, ids)
    forced = np.full_like(own, -1)
    forced[:, 4:9] = (own[:, 4:9] + 1) % 8
    given = dict(ARCH, forced_routing=forced)
    lp_free = _ref_logprobs(params, ids)
    lp_given = _ref_logprobs(params, ids, given)
    np.testing.assert_allclose(lp_free[:4], lp_given[:4], atol=1e-6)
    assert np.abs(lp_free[4:] - lp_given[4:]).max() > 1e-3
    # the first expert layer's own choice does not depend on what it took
    np.testing.assert_array_equal(ref.routing(params, given, ids)[0], own[0])
    same = dict(ARCH, forced_routing=own)
    np.testing.assert_allclose(
        _ref_logprobs(params, ids, same), lp_free, atol=1e-6)


@pytest.mark.parametrize("depth", [8, 12])
def test_dense_cache_prefill_and_decode_match_reference(
        params, deep, rng, depth):
    arch, cfg, p = (ARCH, CFG, params) if depth == 8 else deep
    seq = _toks(rng, 24)
    cache = tfm.KVCache.empty(cfg, 1, 24)
    assert cache.k.shape[0] == depth
    prefill = jax.jit(lambda c, ids, n: tfm.prefill(p, cfg, c, ids, n))
    step = jax.jit(lambda c, t: tfm.decode_step(p, cfg, c, t))
    with jax.default_matmul_precision("highest"):
        logits, cache = prefill(
            cache, jnp.asarray([seq[:12]], jnp.int32), jnp.asarray([12]))
        got = [jax.nn.log_softmax(logits[0])[seq[12]]]
        for t in range(12, 23):     # the window's edge (8) lies behind
            logits, cache = step(cache, jnp.asarray([seq[t]], jnp.int32))
            got.append(jax.nn.log_softmax(logits[0])[seq[t + 1]])
    np.testing.assert_allclose(
        np.asarray(got), _ref_logprobs(p, seq, arch)[11:], atol=TOL_NATS)


def _without_gate(cfg, p):
    drop = lambda st: {**st, "attn": {
        k: v for k, v in st["attn"].items() if k != "wg"}}
    return cfg, {**p, "dense_layers": drop(p["dense_layers"]),
                 "layers": drop(p["layers"])}


def _kinds(pattern):
    return lambda cfg, p: (dataclasses.replace(cfg, layer_pattern=pattern), p)


# what each case does to the PROGRAM (its configuration or its tree); the
# last one moves the reference instead, to the layout a program would run
# that counted the period from the expert stack's first layer
SENSITIVITY = {
    "the_gate_left_out": _without_gate,
    "rotary_on_the_full_layers": _kinds(
        ((8, True), (8, True), (8, True), (None, True))),
    "a_branch_norm_left_out": lambda cfg, p: (
        dataclasses.replace(cfg, norm_branch_out=False), p),
    "the_embedding_not_scaled": lambda cfg, p: (
        dataclasses.replace(cfg, normalize_embed=False), p),
    "route_scale_1": lambda cfg, p: (dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, routed_scaling_factor=1.0)), p),
    "the_window_forgotten": _kinds(
        ((None, True), (None, True), (None, True), (None, False))),
    "the_bias_in_the_weights": None,
    "the_period_started_at_the_expert_stack": None,
}


@pytest.mark.parametrize("case", list(SENSITIVITY))
def test_each_mechanism_moves_the_logits_past_the_tolerance(
        params, rng, case):
    """SENSITIVITY: one mechanism wrong at a time, and the comparison sees
    each (past the window where the window is at stake)."""
    ids = _toks(rng, 40)
    want = _ref_logprobs(params, ids)
    if case == "the_period_started_at_the_expert_stack":
        shifted = dict(ARCH, layer_types=[S, S] + [S, S, S, F, S, S])
        got = _forward_logprobs(CFG, params, ids)
        want = _ref_logprobs(params, ids, shifted)
    elif case == "the_bias_in_the_weights":
        # the reference with no bias at all chooses other experts
        p0 = jax.tree.map(lambda a: a, params)
        p0["layers"]["mlp"]["b_router"] = jnp.zeros_like(
            params["layers"]["mlp"]["b_router"])
        got = _forward_logprobs(CFG, params, ids)
        want = _ref_logprobs(p0, ids)
    else:
        got = _forward_logprobs(*SENSITIVITY[case](CFG, params), ids)
    assert np.abs(got - want).max() > 100 * TOL_NATS, case
    np.testing.assert_allclose(
        _forward_logprobs(CFG, params, ids), _ref_logprobs(params, ids),
        atol=TOL_NATS)


def test_full_layers_carry_no_positions(rng):
    """One full layer (dense): without rotary it has no notion of order."""
    arch = _arch(1, layer_types=[F], num_dense_layers=0,
                 global_attn_every_n_layers=1)
    cfg = _cfg(arch)
    assert cfg.layer_kinds == ((None, False),)
    p = _weights(cfg, 3)
    ids = _toks(rng, 12)
    perm = ids[:11][::-1] + ids[11:]

    def last(seq):
        with jax.default_matmul_precision("highest"):
            return np.asarray(tfm.forward_packed(
                p, cfg, jnp.asarray(seq, jnp.int32),
                jnp.ones((12,), jnp.int32), jnp.arange(12))[-1])

    assert np.abs(last(ids) - last(perm)).max() < 1e-5


def test_bfloat16_fails_the_float32_tolerance(params, rng):
    ids = _toks(rng, 40)
    low = _forward_logprobs(
        dataclasses.replace(CFG, dtype="bfloat16"), params, ids)
    assert np.abs(low - _ref_logprobs(params, ids)).max() > 10 * TOL_NATS


# ------------------------------------------------------------------ #
# (iii) the trainer
# ------------------------------------------------------------------ #

def _train_engine(params, cfg=CFG):
    eng = TrainEngine(cfg, ParallelConfig(), OptimizerConfig())
    eng.load_params(jax.tree.map(np.asarray, params))
    return eng


@pytest.fixture(scope="module")
def ppo_case(params):
    rng = np.random.default_rng(11)
    seqs = [rng.integers(1, 128, n) for n in (23, 31, 17)]
    prompt_lens = [6, 9, 5]
    behav = [np.r_[_ref_logprobs(params, s), 0.0]
             + rng.normal(0, 0.05, len(s)) for s in seqs]
    lens = [len(s) for s in seqs]
    prompt_mask = np.concatenate([
        np.r_[np.ones(pl, bool), np.zeros(n - pl, bool)]
        for n, pl in zip(lens, prompt_lens)])
    sample = SequenceSample.from_default(
        seqlens=lens, ids=list(range(len(seqs))),
        data={
            "packed_input_ids": np.concatenate(seqs).astype(np.int32),
            "packed_logprobs": np.concatenate(behav).astype(np.float32),
            "prompt_mask": prompt_mask,
            "rewards": rng.normal(0, 1, len(seqs)).astype(np.float32),
            "seq_no_eos_mask": np.zeros(len(seqs), bool),
        },
    )
    return seqs, prompt_lens, sample


def test_trainer_inference_matches_reference(params, ppo_case):
    """Three packed sequences, all past the window."""
    seqs, _, sample = ppo_case
    actor = PPOActorInterface(hp=PPOHyperparameters(disable_value=True))
    out = actor.inference(_train_engine(params), sample, MicroBatchSpec())
    got = np.asarray(out.data["prox_logp"])
    want = np.concatenate([np.r_[_ref_logprobs(params, s), 0.0] for s in seqs])
    np.testing.assert_allclose(got, want, atol=TOL_NATS)


HP = PPOHyperparameters(
    disable_value=True, ppo_n_minibatches=1, use_decoupled_loss=False,
    recompute_logprob=False)


@pytest.fixture(scope="module")
def reference_gradient(params, ppo_case):
    """``of(sample)``: ``jax.grad`` of the PPO actor loss built on the
    REFERENCE's log-probs, with the advantages ``train_step`` left in
    ``sample`` (it computes them from the rewards, whatever the policy);
    computed for the first policy that asks and kept for the others."""
    seqs, prompt_lens, _ = ppo_case
    kept = []

    def of(sample):
        if kept:
            return kept[0]
        adv = np.asarray(sample.data["advantages"], np.float32)
        old = np.asarray(sample.data["packed_logprobs"], np.float32)
        mask = np.concatenate([
            np.r_[np.arange(1, n) >= pl, False]
            for n, pl in zip(map(len, seqs), prompt_lens)])

        def reference_loss(p):
            lp = jnp.concatenate([
                jnp.concatenate(
                    [ref.sequence_logprobs(p, ARCH, s), jnp.zeros(1)])
                for s in seqs])
            return ppo_ops.actor_loss_fn(
                lp, jnp.asarray(old), jnp.asarray(adv), HP.eps_clip,
                jnp.asarray(mask))[0]

        # ONE program: eagerly its backward is ~140 one-op programs
        kept.append(jax.jit(jax.grad(reference_loss))(params))
        return kept[0]

    return of


def _policy_cfg(policy):
    return dataclasses.replace(
        CFG, remat_policy=policy,
        moe=dataclasses.replace(CFG.moe, aux_loss_coeff=0.0))


@pytest.mark.parametrize("policy", ["full", "dots", "dots_attn"])
def test_trainer_gradients_match_reference(
        params, ppo_case, reference_gradient, policy):
    """``train_step`` under plain SGD of rate 1 moves every weight by minus
    its gradient, so (before - after) IS the trainer's gradient, through
    its real jitted step (the dense layers and two expert layers one by
    one, a scan over the second period, remat under each policy; the gate
    recomputed from the input norm inside ``dots_attn``'s second region).
    The expected gradient is ``jax.grad`` of the same PPO actor loss built
    on the REFERENCE's log-probs; the router's bias, which moves a choice
    and no weight, has none on either side. (``load_balance_coeff`` is 0
    here: the published model builds no loss term from it, and this
    trainer's balance loss is its own.)"""
    import optax

    seqs, _, sample = ppo_case
    eng = _train_engine(params, _policy_cfg(policy))
    eng.setup_optimizer(10)
    eng.tx = optax.sgd(1.0)
    eng.opt_state = eng.tx.init(eng.params)
    before = jax.tree.map(np.asarray, eng.params)
    sample = SequenceSample.from_default(
        ids=list(sample.ids), seqlens=[len(s) for s in seqs],
        data=dict(sample.data))
    PPOActorInterface(hp=HP).train_step(eng, sample, MicroBatchSpec())
    g_prog = jax.tree.map(lambda a, b: a - np.asarray(b), before, eng.params)
    g_ref = reference_gradient(sample)
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(g_prog), jax.tree.leaves(g_ref)):
        b = np.asarray(b)
        name = jax.tree_util.keystr(path)
        scale = float(np.abs(b).max())
        if "b_router" in name:
            assert scale == 0 and np.abs(a).max() == 0, name
            continue
        assert scale > 0, name
        # relative to the leaf's largest entry; the trainer's gradient is a
        # DIFFERENCE of float32 weights, so it carries their rounding
        np.testing.assert_allclose(
            a, b, atol=3e-3 * scale + 3e-7, err_msg=name)


# ------------------------------------------------------------------ #
# (iv) the benchmark's check of the cell, and its bytes
# ------------------------------------------------------------------ #

@pytest.mark.parametrize(
    "case", ["sound", "too_few_long", "forgot_the_window", "low_precision",
             "another_router"])
def test_benchmark_check_takes_long_sequences_and_two_controls(
        params, rng, case):
    """The afmoe driver's check (``rollout_afmoe_inproc._check``): short
    and long sequences judged apart GIVEN the routing handed in, a run with
    fewer long ones than asked is not correct, and two stand-ins in the
    program's place have to be refused: the reference in 8 bits, and the
    reference with every layer full on the long sequences. Handing the
    full-attention log-probs in as the PROGRAM's fails the run too, and so
    does a routing the reference's router would not have chosen."""
    from benchmark.drivers import rollout_afmoe_inproc as drv
    from benchmark.drivers.rollout_share_inproc import _generated

    chk = {"seq_mean_abs_diff_limit_nats": 0.01, "n_long": 1,
           "long_min_tokens": 30, "long_max_tokens": 64,
           "router_agreement_min": 0.9, "control_dtype": "float8_e5m2"}
    arch = dict(ARCH, reference="afmoe")

    def sample(n, start, window="config"):
        toks = _toks(rng, n)
        lp = _ref_logprobs(params, toks, window=window)
        own = ref.routing(params, arch, toks, window=window)
        if case == "another_router":
            own = (own + 3) % 8
        return {"tokens": toks, "start": start, "logprobs": lp[start - 1:],
                "forced": _generated(own, start)}

    short = [sample(7, 3), sample(8, 4)]        # inside the window of 8
    long_ = [sample(48, 20, None if case == "forgot_the_window" else "config")]
    if case == "too_few_long":
        long_ = []
    if case == "low_precision":
        got = drv._stand_in(
            ref, params, arch, "float32", short + long_, chk, "float8_e5m2")
        assert got["correct"] is False
        return
    got = drv._check(params, arch, "float32", short, long_, chk)
    assert got["correct"] is (case == "sound"), got
    assert got["n_long_sequences"] == len(long_)
    assert got["verdict_given_the_programs_routing"] is True
    if case == "sound":
        assert got["router_agreement_given_earlier_choices"] == 1.0
        assert got["control"]["correct"] is False
        assert got["control_full_attention"]["correct"] is False
        assert min(got["control_full_attention"]["seq_mean_abs_diff_nats"]) > (
            10 * max(got["long"]["seq_mean_abs_diff_nats"] + [1e-6]))
    elif case == "too_few_long":
        assert "0 sequences" in got["reason"]
    elif case == "forgot_the_window":
        assert "long sequences" in got["reason"]


def test_benchmark_bytes_from_shapes():
    """``benchmark/afmoe_flops.py`` against the program at the cell's
    configuration: the bytes of a page, the bytes a token by kind, what a
    decode step must read, an expert's bytes, and which ops stream the
    routed experts; all from the family's OWN keys."""
    from benchmark import afmoe_flops, sut

    with open(os.path.join(
            ROOT, "benchmark", "configs", "trinity-mini-l8.json")) as f:
        arch = json.load(f)
    assert afmoe_flops.is_afmoe(arch) and not afmoe_flops.is_afmoe(
        {"sliding_window_layout": [0, 1], "num_hidden_layers": 2})
    assert afmoe_flops.period(arch) == 4
    assert afmoe_flops.n_layers_by_kind(arch) == {"full": 2, "window": 6}
    assert afmoe_flops.kv_bytes_per_token_by_kind(arch) == {
        "full": 4096, "window": 12288}
    assert afmoe_flops.page_bytes(arch, 128) == 524288
    assert afmoe_flops.decode_step_bytes(arch, [100, 5000]) == (
        4096 * 5100 + 12288 * (100 + 2048))
    assert afmoe_flops.resident_bytes(arch, 1000, 600, 2, 0.5) == (
        4096 * 500 + 12288 * 600)
    assert afmoe_flops.expert_bytes(arch) == 3 * 2048 * 1024 * 2
    assert afmoe_flops.n_expert_layers(arch) == 6
    cfg = sut.model_config(arch, {})
    streams, heads, width = tfm.kv_page_geometry(cfg)
    assert cfg.n_periods * 128 * streams * heads * width * 2 == 524288
    rx = afmoe_flops.expert_op_pattern(arch, "jit_chunk")
    assert rx.search("jit_chunk/%fusion.605 fusion f32[80],bf16[80,2048] "
                     "<- bf16[6,128,1024,2048]")
    assert rx.search("jit_chunk/%fusion.7 fusion bf16[80,128,1024] "
                     "<- bf16[128,2048,1024]")
    assert rx.search("jit_chunk/%moe_grouped.3 custom-call:tpu_custom_call "
                     "bf16[1024,2048] <- bf16[6,128,2048,1024]")
    assert not rx.search("jit_extend/%moe_grouped.3 custom-call bf16[8,8]")
    assert not rx.search(
        "jit_chunk/%while.3 while (s32[]) <- bf16[6,128,1024,2048]")
    # the shared expert, the dense layers' MLP and the gate are not routed
    for other in ("bf16[6,2048,1024]", "bf16[2,2048,6144]",
                  "bf16[6,2048,4096]"):
        assert not rx.search(
            f"jit_chunk/%fusion.1 fusion bf16[80,2048] <- {other}")
