"""ZAYA1 (family ``zaya``: attention inside a convolved latent, CCA, and a
top-1 expert layer behind an MLP router with state) against its plain
reference: the model's forwards.

A tiny model of the family's shape: 3 layers, hidden 32, 4 query / 2
key-value heads of 16 (a latent of 6 heads, two convolutions of 2 taps),
4 experts of 24 and a skip behind a router of width 8, half of each head
rotary, learned residual scaling, a tied head; seeded as the benchmark
seeds it (``benchmark/weights.py`` and the driver's ``_cca_init``: the
convolutions at fan-in scale, a router with margins), float32 everywhere.
The reference is the benchmark's (``benchmark/reference/zaya.py``): plain
``jax.numpy``, a Python loop over layers and experts, none of the
program's model code.

Tolerance: 1e-4 nats on log-probabilities. Both sides compute in float32
on the CPU; what is left is summation order, about 1e-6. Every mechanism
the family adds moves a log-probability by 1e-3 to 1 nat when left out
(``test_what_the_tolerance_has_to_see``).
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.models import hf as hf_conv
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig
from areal_tpu.ops import moe as moe_ops
from benchmark import weights as bench_weights
from benchmark.drivers.rollout_cca_inproc import _cca_init
from benchmark.reference import zaya as ref

TOL_NATS = 1e-4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rope(theta):
    return {"partial_rotary_factor": 0.5, "rope_theta": theta,
            "rope_type": "default"}


# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# ZAYA1-8B), key for key
PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "layer_types": ["hybrid"] * 40,
    "lm_head_bias": False, "max_position_embeddings": 131072,
    "model_type": "zaya", "moe_intermediate_size": 2048,
    "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 1,
    "num_hidden_layers": 40, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
    "rope_parameters": {"hybrid": _rope(5000000),
                        "hybrid_sliding": _rope(10000),
                        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 262272,
}

L = 3
ARCH = dict(
    PUBLISHED, hidden_size=32, head_dim=16, num_attention_heads=4,
    num_key_value_heads=2, moe_intermediate_size=24, num_experts=4,
    router_hidden_size=8, vocab_size=96, num_hidden_layers=L,
    layer_types=["hybrid"] * L, max_position_embeddings=512,
)
FAMILY = hf_conv.family_for_model_type("zaya")


def _cfg(arch=ARCH, **over) -> ModelConfig:
    return dataclasses.replace(
        FAMILY.config_from_hf(arch), dtype="float32",
        use_flash_attention=False, **over)


CFG = _cfg()


def _weights(cfg, seed=20261001):
    shapes = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.key(0)))
    return _cca_init(
        bench_weights.make_weights(shapes, seed, jnp.float32), seed)


@pytest.fixture(scope="module")
def params():
    return _weights(CFG)


@pytest.fixture()
def rng():
    return np.random.default_rng(7)


def _toks(rng, n):
    return [int(x) for x in rng.integers(1, 96, n)]


def _ref_logprobs(params, tokens, arch=ARCH):
    pad = -(-len(tokens) // 32) * 32
    lp, _ = ref.next_token_logprobs(params, arch, list(tokens), "float32", pad)
    return lp


def _packed(cfg, params, seqs, **kw):
    """``forward_packed`` over ``seqs`` on one row (then padding); returns
    what it returns and each sequence's offset."""
    ids = np.concatenate([*seqs, np.zeros(5, int)])
    seg = np.concatenate(
        [np.full(len(s), i + 1) for i, s in enumerate(seqs)] + [np.zeros(5, int)])
    pos = np.concatenate([*(np.arange(len(s)) for s in seqs), np.zeros(5, int)])
    with jax.default_matmul_precision("highest"):
        out = tfm.forward_packed(
            params, cfg, jnp.asarray(ids, jnp.int32),
            jnp.asarray(seg, jnp.int32), jnp.asarray(pos, jnp.int32), **kw)
    return out, np.cumsum([0] + [len(s) for s in seqs])[:-1]


def _forward_logprobs(cfg, params, seqs):
    logits, offs = _packed(cfg, params, seqs)
    lp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    return [lp[o + np.arange(len(s) - 1), np.asarray(s[1:])]
            for o, s in zip(offs, seqs)]


# ------------------------------------------------------------------ #
# (i) the family, its tree, its refusals
# ------------------------------------------------------------------ #


def test_family_reads_the_published_config_key_for_key():
    cfg = FAMILY.config_from_hf(PUBLISHED)
    assert FAMILY.config_to_hf(cfg) == PUBLISHED
    assert (cfg.n_layers, cfg.hidden_dim, cfg.vocab_size) == (40, 2048, 262272)
    assert (cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim) == (8, 2, 128)
    assert (cfg.rot_dim, cfg.rotary_base) == (64, 5e6) and cfg.tied_embedding
    assert (cfg.cca.time0, cfg.cca.time1) == (2, 2) and cfg.residual_scaling
    assert (cfg.cca_latent_dim, cfg.cca_carry_dim) == (1280, 2688)
    m = cfg.moe
    assert (m.num_experts, m.top_k, m.router_dim, cfg.expert_dim) == (
        16, 1, 256, 2048)
    assert m.skip_expert and m.selection_bias and not m.norm_topk_prob
    assert cfg.kv_heads_per_row == 1 and cfg.cache_layers == 40


def test_benchmark_config_is_the_published_one_cut_in_depth():
    with open(os.path.join(
            ROOT, "benchmark", "configs", "zaya1-8b-l16.json")) as f:
        arch = json.load(f)
    assert arch["reduced"] == ["num_hidden_layers", "layer_types"]
    for key, value in PUBLISHED.items():
        if key not in arch["reduced"]:
            assert arch[key] == value, key
    assert arch["num_hidden_layers"] == 16
    assert arch["layer_types"] == ["hybrid"] * 16
    cfg = FAMILY.config_from_hf(arch)
    shapes = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.key(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == arch["parameters"] == 3858475312
    # every from-memory constant of the reference is in the file
    for name in arch["assumed"]["from_memory"]:
        assert name in ("CCA_CONVS", "weight_names") or hasattr(ref, name), name
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["configs"] if c["name"] == "zaya1-8b-l16"]
    assert entry["reduced"] == arch["reduced"]
    assert entry["source"] == arch["source"]


def test_parameter_count_at_the_published_sizes():
    """Shapes only: 8.3 B without the embedding, 0.75 B of them active a
    token (one expert of 16 a layer), as the family's name says."""
    cfg = FAMILY.config_from_hf(PUBLISHED)
    shapes = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.key(0)))

    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    layers = count(shapes["layers"])
    experts = count({k: shapes["layers"]["mlp"][k]
                     for k in ("w_gate", "w_up", "w_down")})
    assert round(layers / 1e9, 1) == 8.3
    active = layers - experts * 15 // 16
    assert 0.745e9 < active < 0.765e9
    assert count(shapes["embed"]) == 262272 * 2048 and "head" not in shapes


@pytest.mark.parametrize("key,value", [
    ("layer_types", ["hybrid_sliding"] + ["hybrid"] * 39),
    ("layer_types", ["hybrid"] * 3),
    ("sliding_window", 4096),
    ("attention_bias", True),
    ("lm_head_bias", True),
])
def test_family_refuses_what_it_does_not_implement(key, value):
    with pytest.raises(ValueError):
        FAMILY.config_from_hf({**PUBLISHED, key: value})


@pytest.mark.parametrize("over", [
    dict(sliding_window=64), dict(n_passes=2), dict(qk_layernorm=True),
    dict(n_kv_heads=1, n_q_heads=4), dict(use_attention_bias=True),
])
def test_config_refuses_cca_beside_what_no_model_has_with_it(over):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **over)


def test_hf_names_round_trip(params):
    sd = FAMILY.params_to_hf(params, CFG)
    assert sd["model.layers.0.self_attn.conv_qk.0.weight"].shape == (96, 1, 2)
    assert sd["model.layers.0.self_attn.conv_qk.1.weight"].shape == (96, 16, 2)
    assert sd["model.layers.1.self_attn.val_proj2.weight"].shape == (16, 32)
    assert sd["model.layers.2.mlp.router.mlp.4.weight"].shape == (5, 8)
    assert "lm_head.weight" not in sd
    back = FAMILY.params_from_hf(sd, CFG)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the grouped convolution as torch lays it out: [out channel, in, tap]
    w1 = np.asarray(params["layers"]["attn"]["conv1_w"])     # [L,tap,H,in,out]
    t = sd["model.layers.0.self_attn.conv_qk.1.weight"]
    assert t[3 * 16 + 5, 7, 1] == w1[0, 1, 3, 7, 5]


def test_param_axes_follow_the_tree(params):
    axes = tfm.param_logical_axes(CFG)
    is_axes = lambda x: isinstance(x, tuple)
    assert jax.tree.structure(axes, is_leaf=is_axes) == jax.tree.structure(
        params)
    for ax, leaf in zip(jax.tree.leaves(axes, is_leaf=is_axes),
                        jax.tree.leaves(params)):
        assert len(ax) == leaf.ndim


# ------------------------------------------------------------------ #
# (ii) the forwards
# ------------------------------------------------------------------ #


def test_forward_over_two_packed_sequences_matches_reference(params, rng):
    """Two documents on one row: the convolutions and the value shift
    reset at the boundary, the router's state is a token's own."""
    seqs = [_toks(rng, 11), _toks(rng, 17)]
    got = _forward_logprobs(CFG, params, seqs)
    for s, g in zip(seqs, got):
        np.testing.assert_allclose(g, _ref_logprobs(params, s), atol=TOL_NATS)
    # ... which a second document read as the first's continuation is not
    (joined,) = _forward_logprobs(CFG, params, [seqs[0] + seqs[1]])
    assert np.abs(joined[11:] - got[1]).max() > 1e-2


def test_routing_matches_reference_and_takes_the_skip(params, rng):
    seq = _toks(rng, 64)
    (_, routing), _ = _packed(CFG, params, [seq], with_routing=True)
    own, margin = ref.routing(params, ARCH, seq, "float32", 64)
    assert routing.shape == (L, 69, 1)
    np.testing.assert_array_equal(np.asarray(routing)[:, :64, 0], own)
    assert (own == 4).any() and (own < 4).any() and np.median(margin) > 0.05


@pytest.mark.parametrize("fault", [
    "RESIDUAL_SCALING", "VALUE_SHIFT_BY_HEAD", "QK_MEAN",
    "QK_L2NORM_TEMP_ON_K", "ROTARY_AFTER_NORM", "CONV_ZERO_LEFT_PAD",
    "ROUTER_EDA",
    "SKIP_IS_IDENTITY", "control_zero_carry_at", "control_no_router_state",
])
def test_what_the_tolerance_has_to_see(params, rng, fault, monkeypatch):
    """Each mechanism left out of the REFERENCE (or each of the benchmark's
    controls) moves some log-probability by far more than the tolerance."""
    seq = _toks(rng, 40)
    (got,) = _forward_logprobs(CFG, params, [seq])
    arch = ARCH
    if fault == "control_zero_carry_at":
        arch = dict(ARCH, control_zero_carry_at=16)
    elif fault == "control_no_router_state":
        arch = dict(ARCH, control_no_router_state=True)
    else:
        monkeypatch.setattr(ref, fault, False)
        ref._layer.clear_cache()
    try:
        off = np.abs(_ref_logprobs(params, seq, arch) - got)
    finally:
        monkeypatch.undo()
        ref._layer.clear_cache()
    assert off.max() > 20 * TOL_NATS, off.max()
    if fault == "control_zero_carry_at":
        # nothing before the dropped carry can tell
        assert off[:15].max() < TOL_NATS < off[16:].max()


def test_dense_cache_prefill_and_decode_match_reference(params, rng):
    a, b = _toks(rng, 12), _toks(rng, 9)
    cache = tfm.KVCache.empty(CFG, 2, 32)
    assert cache.ssm.carry.shape == (L, 2, CFG.cca_carry_dim)
    pad = np.zeros((2, 8), int)
    pad[0, :5], pad[1, :3] = a[:5], b[:3]
    with jax.default_matmul_precision("highest"):
        logits, cache = tfm.prefill(
            params, CFG, cache, jnp.asarray(pad), jnp.asarray([5, 3]))
        steps = [logits]
        for t in range(6):
            logits, cache = tfm.decode_step(
                params, CFG, cache, jnp.asarray([a[5 + t], b[3 + t]]),
                active=jnp.asarray([True, t < 4]))
            steps.append(logits)
    for i, (toks, n0, n) in enumerate(((a, 5, 7), (b, 3, 5))):
        got = [float(jax.nn.log_softmax(steps[j][i])[toks[n0 + j]])
               for j in range(n)]
        want = _ref_logprobs(params, toks)[n0 - 1 : n0 - 1 + n]
        np.testing.assert_allclose(got, want, atol=TOL_NATS)
    assert list(np.asarray(cache.lens)) == [11, 7]


def test_chunked_logprobs_match_reference(params, rng):
    seqs = [_toks(rng, 13), _toks(rng, 14)]
    hidden, offs = _packed(CFG, params, seqs, with_head=False)
    ids = np.concatenate([*seqs, np.zeros(5, int)])
    seg = np.concatenate([np.full(13, 1), np.full(14, 2), np.zeros(5, int)])
    with jax.default_matmul_precision("highest"):
        lp = np.asarray(tfm.chunked_next_token_logprobs(
            params, CFG, hidden, jnp.asarray(ids, jnp.int32),
            jnp.asarray(seg, jnp.int32), chunk=8))
    for o, s in zip(offs, seqs):
        np.testing.assert_allclose(
            lp[o : o + len(s) - 1], _ref_logprobs(params, s), atol=TOL_NATS)
        assert lp[o + len(s) - 1] == 0.0


@pytest.mark.parametrize("policy", ["full", "dots", "dots_attn", "none"])
def test_loss_and_gradients_match_reference(params, rng, policy):
    """The trainer's forward (packed, through ``forward_packed`` under each
    checkpointing policy) and ``jax.grad`` of the plain reference."""
    cfg = dataclasses.replace(CFG, remat_policy=policy)
    seq = _toks(rng, 24)
    ids = jnp.asarray(seq, jnp.int32)

    def loss(p):
        with jax.default_matmul_precision("highest"):
            logits = tfm.forward_packed(
                p, cfg, ids, jnp.ones((24,), jnp.int32), jnp.arange(24))
        lp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(lp[jnp.arange(23), ids[1:]])

    # each side ONE program (eagerly ~180 one-op programs a side)
    got_l, got_g = jax.jit(jax.value_and_grad(loss))(params)
    want_l, want_g = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, ARCH, seq)))(params)
    assert abs(float(got_l) - float(want_l)) < 1e-5
    flat_w = jax.tree_util.tree_leaves_with_path(want_g)
    for (path, w), g in zip(flat_w, jax.tree.leaves(got_g)):
        scale = max(float(jnp.abs(w).max()), 1e-3)
        assert float(jnp.abs(g - w).max()) <= 2e-3 * scale, (
            jax.tree_util.keystr(path))
    # the mechanisms' own parameters are trained
    for name in ("conv0_w", "conv1_w", "k_temp"):
        assert float(jnp.abs(got_g["layers"]["attn"][name]).max()) > 0
    assert float(jnp.abs(got_g["layers"]["mlp"]["router_mix"][1:]).max()) > 0


# ------------------------------------------------------------------ #
# (iii) the router and the experts
# ------------------------------------------------------------------ #


def _layer_mlp(params, l=1):
    return jax.tree.map(lambda a: a[l], params["layers"]["mlp"])


def test_router_state_reaches_the_next_layer(params, rng):
    """Zeroing the gain on the previous layer's router vector changes the
    logits, and is what the reference's control computes."""
    seq = _toks(rng, 20)
    mlp = dict(params["layers"]["mlp"])
    mlp["router_mix"] = jnp.zeros_like(mlp["router_mix"])
    cut = {**params, "layers": {**params["layers"], "mlp": mlp}}
    (whole,), (without,) = (
        _forward_logprobs(CFG, p, [seq]) for p in (params, cut))
    assert np.abs(whole - without).max() > 1e-2
    np.testing.assert_allclose(
        without,
        _ref_logprobs(params, seq, dict(ARCH, control_no_router_state=True)),
        atol=TOL_NATS)
    # ... and the state itself is handed on: layer l's vector in, layer
    # l + 1's vector out differs with it
    p = _layer_mlp(params)
    x = jnp.asarray(rng.normal(size=(6, 32)), jnp.float32)
    zero = jnp.zeros((6, 8), jnp.float32)
    *_, r0 = moe_ops.moe_mlp(CFG, p, x, router_state=zero)
    *_, r1 = moe_ops.moe_mlp(CFG, p, x, router_state=r0)
    np.testing.assert_allclose(r1 - r0, p["router_mix"] * r0, atol=1e-6)


def test_bias_moves_the_choice_and_not_the_weight(params, rng):
    p = _layer_mlp(params)
    x = jnp.asarray(rng.normal(size=(32, 32)), jnp.float32)
    zero = jnp.zeros((32, 8), jnp.float32)
    base_w, base_idx, probs, _, _ = moe_ops._route_mlp(CFG, p, x, zero)
    pushed = dict(p, b_router=p["b_router"].at[2].add(10.0))
    w, idx, probs2, _, _ = moe_ops._route_mlp(CFG, pushed, x, zero)
    np.testing.assert_array_equal(probs, probs2)
    assert (idx == 2).all() and not (base_idx == 2).all()
    np.testing.assert_allclose(w[:, 0], probs[:, 2], rtol=1e-6)
    assert float(w.sum()) < 32.0            # not renormalised to one
    assert float(jnp.abs(base_w[:, 0] - probs.max(axis=-1)).max()) < 0.05


def test_a_row_routed_to_the_skip_reads_no_expert(params, rng):
    p = _layer_mlp(params)
    x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    zero = jnp.zeros((16, 8), jnp.float32)
    skip = dict(p, b_router=p["b_router"].at[4].add(10.0))
    out, _, idx, _ = moe_ops.moe_mlp(CFG, skip, x, router_state=zero)
    assert (idx == 4).all()
    _, _, probs, _, _ = moe_ops._route_mlp(CFG, skip, x, zero)
    np.testing.assert_allclose(out, probs[:, 4:5] * x, atol=1e-6)
    # no expert's weights reach a skipped row's output
    other = {k: jnp.full_like(skip[k], 7.0)
             for k in ("w_gate", "w_up", "w_down")}
    mixed = dict(p, **other)
    out2, _, idx2, _ = moe_ops.moe_mlp(CFG, mixed, x, router_state=zero)
    rows = np.asarray(idx2[:, 0] == 4)
    assert rows.any() and not rows.all()
    ref_out, *_ = moe_ops.moe_mlp(CFG, p, x, router_state=zero)
    np.testing.assert_allclose(out2[rows], ref_out[rows], atol=1e-6)


def test_grouped_and_dense_dispatch_agree_at_top1(params, rng):
    """The ``moe_grouped`` kernel (interpreted) over the stacks and the
    einsums over the layer's slice, rows that take the skip among them."""
    from areal_tpu.ops.pallas import moe_grouped as kernel

    p = _layer_mlp(params)
    x = jnp.asarray(rng.normal(size=(40, 32)), jnp.float32)
    zero = jnp.zeros((40, 8), jnp.float32)
    want, _, idx_w, r_w = moe_ops.moe_mlp(CFG, p, x, router_state=zero)
    stacks = {k: params["layers"]["mlp"][k]
              for k in ("w_gate", "w_up", "w_down")}
    rest = {k: v for k, v in p.items() if k not in stacks}
    got, _, idx_g, r_g = moe_ops.moe_mlp(
        CFG, rest, x, routed=(stacks, jnp.int32(1)), router_state=zero)
    np.testing.assert_array_equal(idx_w, idx_g)
    np.testing.assert_array_equal(r_w, r_g)
    assert (np.asarray(idx_w) == 4).any()
    np.testing.assert_allclose(got, want, atol=2e-5)
    # every row to the skip: no tile runs, nothing uninitialised comes back
    skip = dict(rest, b_router=rest["b_router"].at[4].add(10.0))
    got, _, idx, _ = moe_ops.moe_mlp(
        CFG, skip, x, routed=(stacks, jnp.int32(1)), router_state=zero)
    want, *_ = moe_ops.moe_mlp(
        CFG, dict(p, b_router=skip["b_router"]), x, router_state=zero)
    assert (idx == 4).all() and np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert kernel.row_tile(40, 4) == 32
