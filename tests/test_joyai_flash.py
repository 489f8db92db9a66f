"""JoyAI-LLM-Flash (family ``joyai_llm_flash``) against its plain reference,
end to end.

A tiny model of the family's shape: one leading dense layer and two expert
layers, hidden 64, 4 heads of 16 nope + 8 rope (values 16), query latent
48, key/value latent 32 + 8 rotary, 16 sigmoid-routed experts of width 32
with 4 a token, one shared expert, with seeded random weights, float32
everywhere. The reference is the benchmark's
(``benchmark/reference/joyai_llm_flash.py``): plain ``jax.numpy``, the
EXPANDED attention (per-head keys and values from the latent, pairs
rotated in place), every expert for every token in a loop, none of the
program's model code.

Tolerance: 1e-4 nats on log-probabilities. Both sides compute in float32
on the CPU, so no rounding difference can flip a top-k choice (the gap
between the 4th and 5th biased score is ~1e-2 here); what is left is
summation order (the absorbed form multiplies ``q W_uk^T`` first, the
paged cache comes in pages of 8, the head in one block against blocks),
about 1e-6. A wrong norm, the bias in the combine weights, a missing
shared expert, the wrong rotary pairing or a wrong cache position moves a
log-probability by 1e-2 to 1 nat; the same path in bfloat16 is off by
more than 1e-3 (last test of section ii).
"""

import dataclasses
import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import engine_contract
from areal_tpu.api.data import MicroBatchSpec, SequenceSample
from areal_tpu.api.model import PPOHyperparameters
from areal_tpu.base import tracing
from areal_tpu.gen.engine import GenerationEngine, GenRequest
from areal_tpu.interfaces.ppo import PPOActorInterface
from areal_tpu.models import hf as hf_conv
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig
from areal_tpu.ops import attention as attn_ops
from areal_tpu.ops import moe as moe_ops
from areal_tpu.ops import ppo as ppo_ops
from areal_tpu.parallel.mesh import ParallelConfig
from areal_tpu.train.engine import OptimizerConfig, TrainEngine
from benchmark import weights as bench_weights
from benchmark.reference import joyai_llm_flash as ref

TOL_NATS = 1e-4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# JoyAI-LLM-Flash), key for key
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280,
}

ARCH = dict(
    PUBLISHED, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    n_routed_experts=16, num_experts_per_tok=4, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24, head_dim=8,
    v_head_dim=16, vocab_size=128, num_hidden_layers=3,
    num_nextn_predict_layers=0, max_position_embeddings=512,
)
ARCH_MTP = dict(ARCH, num_nextn_predict_layers=1)
FAMILY = hf_conv.family_for_model_type("joyai_llm_flash")


def _cfg(arch=ARCH, **over) -> ModelConfig:
    return dataclasses.replace(
        FAMILY.config_from_hf(arch), dtype="float32", **over)


CFG = _cfg()


def _weights(cfg, seed=20260928):
    """Seeded weights with gains away from 1 and a router correction bias
    away from 0 (the benchmark's fill), so a norm over the wrong span, a
    missing gain or a bias in the wrong place shows."""
    shapes = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.key(0)))
    return bench_weights.make_weights(shapes, seed, jnp.float32)


@pytest.fixture(scope="module")
def params():
    return _weights(CFG)


@pytest.fixture()
def rng():
    return np.random.default_rng(7)


def _ref_logprobs(params, tokens, arch=ARCH):
    lp, _ = ref.next_token_logprobs(params, arch, list(tokens), "float32", 64)
    return lp


def _forward_logprobs(cfg, params, ids):
    n = len(ids)
    with jax.default_matmul_precision("highest"):
        logits = tfm.forward_packed(
            params, cfg, jnp.asarray(ids, jnp.int32),
            jnp.ones((n,), jnp.int32), jnp.arange(n))
    lp = jax.nn.log_softmax(logits, axis=-1)
    return np.asarray(lp[np.arange(n - 1), np.asarray(ids[1:])])


# ------------------------------------------------------------------ #
# (i) the family and its tree
# ------------------------------------------------------------------ #

def test_family_reads_the_published_config_key_for_key():
    """Every key of the catalog row either builds the model or repeats a
    key that does: writing the model's config back gives the row."""
    cfg = FAMILY.config_from_hf(PUBLISHED)
    back = FAMILY.config_to_hf(cfg)
    assert {k: back[k] for k in PUBLISHED} == PUBLISHED
    m, moe = cfg.mla, cfg.moe
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.n_moe_layers,
            cfg.n_mtp_layers) == (40, 1, 39, 1)
    assert (cfg.n_q_heads, cfg.head_dim, m.v_head_dim) == (32, 192, 128)
    assert (m.q_lora_rank, m.kv_lora_rank, m.latent_dim) == (1536, 512, 576)
    assert cfg.rot_dim == 64
    assert cfg.rotary_base == 32e6 and cfg.rotary_scaling_type is None
    assert (cfg.intermediate_dim, cfg.expert_dim) == (7168, 768)
    assert (moe.num_experts, moe.top_k, moe.n_shared_experts) == (256, 8, 1)
    assert (moe.scoring, moe.selection_bias, moe.norm_topk_prob,
            moe.routed_scaling_factor) == ("sigmoid", True, True, 2.5)
    assert cfg.layer_norm_epsilon == 1e-6 and not cfg.tied_embedding
    assert tfm.latent_pool_width(cfg) == 640


def test_benchmark_config_is_the_published_one_cut_in_depth():
    with open(os.path.join(
            ROOT, "benchmark", "configs", "joyai-flash-l5.json")) as f:
        arch = json.load(f)
    assert arch["reduced"] == ["num_hidden_layers", "num_nextn_predict_layers"]
    want = dict(PUBLISHED, num_hidden_layers=5, num_nextn_predict_layers=0)
    assert {k: arch[k] for k in PUBLISHED} == want
    assert arch["reduced_from"] == {
        "num_hidden_layers": 40, "num_nextn_predict_layers": 1}


@pytest.mark.parametrize("key,value", [
    ("n_group", 2), ("topk_group", 2),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}),
    ("rope_interleave", False),
    ("scoring_func", "softmax"), ("topk_method", "greedy"),
    ("moe_layer_freq", 2), ("attention_bias", True), ("q_lora_rank", None),
    ("qk_head_dim", 128), ("first_k_dense_replace", 40),
])
def test_family_refuses_what_it_does_not_implement(key, value):
    with pytest.raises(ValueError, match="joyai_llm_flash"):
        FAMILY.config_from_hf(dict(PUBLISHED, **{key: value}))


def test_leading_layer_is_dense_and_the_rest_are_experts(params):
    """Two stacks: ``dense_layers`` has no router, no expert axis and the
    dense width; ``layers`` has the router, its bias, the experts and the
    shared expert. The logical axes describe the same tree."""
    dense, moe = params["dense_layers"]["mlp"], params["layers"]["mlp"]
    assert sorted(dense) == ["w_down", "w_gate", "w_up"]
    assert dense["w_gate"].shape == (1, 64, 96)
    assert sorted(moe) == ["b_router", "router", "shared_down", "shared_gate",
                           "shared_up", "w_down", "w_gate", "w_up"]
    assert moe["w_gate"].shape == (2, 16, 64, 32)
    assert moe["b_router"].shape == (2, 16)
    assert sorted(params["layers"]["attn"]) == [
        "kv_a_norm", "q_a_norm", "wkv_a", "wkv_b", "wo", "wq_a", "wq_b"]
    axes = tfm.param_logical_axes(CFG)
    assert jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple)
    ) == jax.tree.structure(params)
    for ax, leaf in zip(
            jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple)),
            jax.tree.leaves(params)):
        assert len(ax) == leaf.ndim
    # seeded as gains and as a bias, not as matrices
    assert abs(float(params["layers"]["attn"]["q_a_norm"].mean()) - 1) < 0.1
    assert abs(float(params["layers"]["attn"]["kv_a_norm"].mean()) - 1) < 0.1
    assert float(jnp.abs(moe["b_router"]).max()) < 0.2
    # zeroing the dense layer's MLP changes the output; it is really run
    cut = jax.tree.map(lambda a: a, params)
    cut["dense_layers"]["mlp"]["w_down"] = jnp.zeros_like(dense["w_down"])
    ids = list(range(1, 20))
    assert np.abs(_forward_logprobs(CFG, cut, ids)
                  - _forward_logprobs(CFG, params, ids)).max() > 1e-3


def test_hf_names_round_trip(tmp_path):
    """Through disk, with the multi-token-prediction module, under the
    ``deepseek_v3`` names."""
    cfg = _cfg(ARCH_MTP)
    p = jax.tree.map(np.asarray, _weights(cfg, 5))
    hf_conv.save_hf_checkpoint(p, cfg, "joyai_llm_flash", str(tmp_path))
    from safetensors.numpy import load_file

    sd = load_file(str(tmp_path / "model.safetensors"))
    for name in (
        "model.layers.0.mlp.gate_proj.weight",
        "model.layers.0.self_attn.kv_a_proj_with_mqa.weight",
        "model.layers.1.self_attn.q_a_layernorm.weight",
        "model.layers.1.self_attn.kv_b_proj.weight",
        "model.layers.1.mlp.gate.weight",
        "model.layers.1.mlp.gate.e_score_correction_bias",
        "model.layers.2.mlp.experts.15.down_proj.weight",
        "model.layers.2.mlp.shared_experts.up_proj.weight",
        "model.layers.3.enorm.weight", "model.layers.3.hnorm.weight",
        "model.layers.3.eh_proj.weight",
        "model.layers.3.shared_head.head.weight",
        "model.layers.3.mlp.experts.0.gate_proj.weight",
    ):
        assert name in sd, name
    assert "model.layers.0.mlp.gate.weight" not in sd
    assert sd["model.layers.1.self_attn.kv_a_proj_with_mqa.weight"].shape == (40, 64)
    assert sd["model.layers.3.eh_proj.weight"].shape == (64, 128)
    cfg2, p2 = hf_conv.load_hf_checkpoint(str(tmp_path))
    assert dataclasses.replace(cfg2, dtype="float32") == cfg
    jax.tree.map(np.testing.assert_array_equal, p, p2)


# ------------------------------------------------------------------ #
# (ii) forward, routing, the two forms of the attention
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("n", [5, 40, 64])
def test_forward_matches_reference(params, rng, n):
    ids = [int(x) for x in rng.integers(1, 128, n)]
    np.testing.assert_allclose(
        _forward_logprobs(CFG, params, ids), _ref_logprobs(params, ids),
        atol=TOL_NATS)


def test_routing_matches_reference(params, rng):
    """Chosen sets and combine weights of every token in every expert
    layer (the dense layer has none)."""
    ids = jnp.asarray(rng.integers(1, 128, 48), jnp.int32)
    with jax.default_matmul_precision("highest"):
        _, got = tfm.forward_packed(
            params, CFG, ids, jnp.ones((48,), jnp.int32), jnp.arange(48),
            with_routing=True)
    want_idx, want_w = ref.routing(params, ARCH, ids)
    assert got.shape == (2, 48, 4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want_idx))
    # the weights, through the op itself on the reference's inputs
    x = jnp.asarray(rng.normal(0, 1, (48, 64)), jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"]["mlp"])
    vals, idx, _, _ = moe_ops._route(CFG, lp["router"], x, lp["b_router"])
    r_idx, r_w, _ = ref._route(
        x, lp["router"], lp["b_router"], top_k=4, norm_topk=True, scale=2.5)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(r_idx))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(r_w), atol=1e-6)
    np.testing.assert_allclose(np.asarray(vals).sum(-1), 2.5, atol=1e-5)


def test_correction_bias_moves_the_choice_and_not_the_weights(params, rng):
    """A large bias on expert 0 puts it among every token's chosen, and
    its weight is still its sigmoid over the chosen sigmoids' sum: the
    weights of a token's chosen set are a function of the set alone."""
    x = jnp.asarray(rng.normal(0, 1, (32, 64)), jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"]["mlp"])
    base_w, base_idx, _, logits = moe_ops._route(
        CFG, lp["router"], x, lp["b_router"])
    assert not np.all(np.any(np.asarray(base_idx) == 0, axis=-1))
    pushed = lp["b_router"].at[0].add(10.0)
    w, idx, _, _ = moe_ops._route(CFG, lp["router"], x, pushed)
    idx, w = np.asarray(idx), np.asarray(w)
    assert np.all(idx[:, 0] == 0)       # the largest biased score
    s = np.asarray(jax.nn.sigmoid(logits))
    chosen_s = np.take_along_axis(s, idx, axis=-1)
    np.testing.assert_allclose(
        w, 2.5 * chosen_s / chosen_s.sum(-1, keepdims=True), atol=1e-6)
    assert w[:, 0].max() < 2.5          # a sigmoid's share, not 10's
    # and the reference agrees on both
    r_idx, r_w, _ = ref._route(
        x, lp["router"], pushed, top_k=4, norm_topk=True, scale=2.5)
    np.testing.assert_array_equal(idx, np.asarray(r_idx))
    np.testing.assert_allclose(w, np.asarray(r_w), atol=1e-6)


def test_absorbed_attention_equals_expanded(params, rng):
    """One layer's attention on the same input, both forms: per-head keys
    and values up-projected from the latent (what the trainer runs), and
    multi-query attention of ``q W_uk^T`` over the padded latents with
    ``W_uv`` applied after (what the page pool is read by)."""
    T = 37
    x = jnp.asarray(rng.normal(0, 1, (T, 64)), jnp.float32)
    p = jax.tree.map(lambda a: a[1], params["layers"]["attn"])
    pos = jnp.arange(T)
    cos, sin = tfm.rotary_cos_sin(tfm._rotary_cfg(CFG), pos, jnp.float32)
    with jax.default_matmul_precision("highest"):
        q, k, v = tfm._mla_expanded(CFG, p, x, cos, sin)
        assert q.shape == k.shape == v.shape == (T, 4, 24)
        assert float(jnp.abs(v[..., 16:]).max()) == 0.0    # the padding
        ctx = attn_ops.packed_attention(
            q, k, v, jnp.ones((T,), jnp.int32), use_flash=False)
        want = tfm._attn_out(p, ctx)
        qa, lat = tfm._mla_absorbed(CFG, p, x, cos, sin)
        assert qa.shape == (T, 4, 128) and lat.shape == (T, 128)
        assert float(jnp.abs(lat[:, 40:]).max()) == 0.0    # 32 + 8, then 0
        s = jnp.einsum("thw,sw->hts", qa, lat) * 24 ** -0.5
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        ctx_lat = jnp.einsum("hts,sr->thr", pr, lat[:, :32])
        got = tfm._attn_out(p, tfm._mla_absorbed_out(CFG, p, ctx_lat))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_dense_cache_prefill_and_decode_match_reference(params, rng):
    """``prefill`` + ``decode_step`` (dense cache, expanded form)."""
    prompt = [int(x) for x in rng.integers(1, 128, 21)]
    more = [int(x) for x in rng.integers(1, 128, 6)]
    cache = tfm.KVCache.empty(CFG, 2, 32)
    ids = jnp.zeros((2, 24), jnp.int32).at[0, :21].set(jnp.asarray(prompt))
    with jax.default_matmul_precision("highest"):
        logits, cache = tfm.prefill(
            params, CFG, cache, ids, jnp.asarray([21, 0]))
        got = [float(jax.nn.log_softmax(logits[0])[more[0]])]
        for t, nxt in zip(more[:-1], more[1:]):
            logits, cache = tfm.decode_step(
                params, CFG, cache, jnp.asarray([t, 0]),
                jnp.asarray([True, False]))
            got.append(float(jax.nn.log_softmax(logits[0])[nxt]))
    want = _ref_logprobs(params, prompt + more)[20:]
    np.testing.assert_allclose(got, want, atol=TOL_NATS)


def test_bfloat16_fails_the_float32_tolerance(params, rng):
    """The tolerance separates precisions: the same forward computed in
    bfloat16 is off by far more than it allows."""
    ids = [int(x) for x in rng.integers(1, 128, 64)]
    low = _forward_logprobs(dataclasses.replace(CFG, dtype="bfloat16"),
                            params, ids)
    assert np.abs(low - _ref_logprobs(params, ids)).max() > 10 * TOL_NATS


def test_mtp_matches_reference(rng):
    """The multi-token-prediction module (``num_nextn_predict_layers`` 1)
    as a function of the model: position ``i`` predicts token ``i + 2``."""
    cfg = _cfg(ARCH_MTP)
    p = _weights(cfg, 11)
    assert sorted(p["mtp"]) == ["block", "e_norm", "eh_proj", "h_norm"]
    assert p["mtp"]["eh_proj"].shape == (1, 128, 64)
    n = 33
    ids = jnp.asarray(rng.integers(1, 128, n), jnp.int32)
    with jax.default_matmul_precision("highest"):
        logits, mtp = tfm.forward_packed(
            p, cfg, ids, jnp.ones((n,), jnp.int32), jnp.arange(n),
            with_mtp=True)
    assert mtp.shape == (1, n, 128)
    lp = jax.nn.log_softmax(mtp[0], axis=-1)
    got = np.asarray(lp[np.arange(n - 2), np.asarray(ids[2:])])
    want = np.asarray(ref.mtp_logprobs(p, ARCH_MTP, ids))[0, : n - 2]
    np.testing.assert_allclose(got, want, atol=TOL_NATS)
    # the main head is untouched by the module's presence
    main = jax.nn.log_softmax(logits, axis=-1)
    np.testing.assert_allclose(
        np.asarray(main[np.arange(n - 1), np.asarray(ids[1:])]),
        np.asarray(ref.sequence_logprobs(p, ARCH_MTP, ids)), atol=TOL_NATS)
    with pytest.raises(ValueError, match="no such module"):
        tfm.forward_packed(
            _weights(CFG), CFG, ids, jnp.ones((n,), jnp.int32),
            jnp.arange(n), with_mtp=True)


# ------------------------------------------------------------------ #
# (iii) the generation engine over the latent page pool
# ------------------------------------------------------------------ #

def _engine(params, **kw):
    return GenerationEngine(
        CFG, params, max_slots=4, max_seqlen=128, max_new_tokens_cap=32,
        page_size=8, enable_prefix_cache=True, seed=3, **kw)


@pytest.mark.parametrize("check", engine_contract.CHECKS)
def test_engine_contract(params, check):
    engine_contract.run(check, functools.partial(_engine, params), CFG, params)


def test_pool_holds_one_latent_stream(params):
    """No V half, no per-head copy: a token takes ``layers x width`` values
    of the pool, the width being the latent's padded to whole lane tiles
    (40 -> 128 here; 576 -> 640 as published, 6,400 B a token at five
    layers in bf16 against 5,760 B of latents)."""
    eng = _engine(params)
    assert eng.state.cache.pages.shape == (3, eng.n_pages, 1, 1, 8, 128)
    assert eng.state.cache.scales is None
    assert eng.cache_bytes_per_token() == 3 * 128 * 4
    assert eng.kv_pool_bytes() == eng.n_pages * 8 * 3 * 128 * 4
    with open(os.path.join(
            ROOT, "benchmark", "configs", "joyai-flash-l5.json")) as f:
        big = dataclasses.replace(
            FAMILY.config_from_hf(json.load(f)), dtype="bfloat16")
    assert tfm.kv_page_geometry(big) == (1, 1, 640)
    assert big.n_layers * 640 * 2 == 6400           # as stored, bf16
    assert big.n_layers * big.mla.latent_dim * 2 == 5760


@pytest.mark.parametrize("case", ["int8_argument", "int8_config", "tp"])
def test_engine_refuses_int8_pool_and_tensor_parallel(params, case):
    if case == "int8_argument":
        with pytest.raises(NotImplementedError, match="int8"):
            _engine(params, kv_dtype="int8")
    elif case == "int8_config":
        with pytest.raises(NotImplementedError, match="int8"):
            GenerationEngine(_cfg(kv_dtype="int8"), params, page_size=8)
    else:
        from areal_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(ParallelConfig.from_str("d1f1m2"),
                         devices=jax.devices()[:2])
        with pytest.raises(NotImplementedError, match="tensor-parallel"):
            _engine(params, mesh=mesh)


@pytest.mark.parametrize("use_pallas", [True, None],
                         ids=["interpret_kernel", "xla_gather"])
def test_engine_logprobs_match_reference(params, rng, use_pallas):
    """Prefill in chunks of a page, a shared prefix served from the cache,
    then decode through the latent pool (the ``mla_decode`` kernel in
    interpret mode, and the XLA gather path): the served log-probs of
    sampled tokens are the reference's full forward on prompt + output."""
    eng = _engine(params)
    eng._decode_use_pallas = use_pallas
    shared = [int(x) for x in rng.integers(1, 128, 24)]
    prompts = [shared + [int(x) for x in rng.integers(1, 128, k)]
               for k in (3, 9)] + [[int(x) for x in rng.integers(1, 128, 13)]]
    tracing.drain()
    outs = {}
    for wave in (prompts[:1], prompts[1:]):     # second wave hits the prefix
        for p in wave:
            eng.submit(GenRequest(
                rid=f"r{prompts.index(p)}", input_ids=p, max_new_tokens=12,
                temperature=1.0))
        outs.update({o.rid: o for o in eng.run_until_done(4)})
    assert eng.stats["prefix_hit_tokens"] >= 24
    for i, p in enumerate(prompts):
        o = outs[f"r{i}"]
        assert len(o.output_ids) == 12
        want = _ref_logprobs(params, p + o.output_ids)[len(p) - 1:]
        np.testing.assert_allclose(
            np.asarray(o.output_logprobs), want, atol=TOL_NATS)
    chunks = [s["attrs"] for s in tracing.drain()
              if s["name"] == "gen_engine/chunk" and "slots" in s["attrs"]]
    # what the pool really holds a token, and the census over the TWO
    # expert layers' 16 experts (the dense layer routes nothing)
    assert chunks and all(
        c["cache_bytes_per_token"] == 3 * 128 * 4 for c in chunks)
    assert all(c["moe_expert_slots"] == c["steps"] * 2 * 16 for c in chunks)
    assert all(0 < c["moe_experts_hit"] <= c["moe_expert_slots"]
               for c in chunks)
    assert all(1 <= c["moe_load_max"] <= 4 for c in chunks)
    assert eng.stats["moe_experts_hit"] == sum(
        c["moe_experts_hit"] for c in chunks)
    # the kernel's census is there exactly when the kernel runs
    assert all(("kernel_positions" in c) == bool(use_pallas) for c in chunks)
    if use_pallas:
        assert all(c["kernel_steps_active"] <= c["kernel_steps"]
                   for c in chunks)
        assert all(c["kernel_positions"] >= c["resident_tokens"]
                   for c in chunks)


def test_prefix_hit_gives_the_cold_prompt_s_tokens(params, rng):
    """The same prompt, greedy, first cold and then from the prefix cache
    (its latent pages shared, only the tail prefilled): the same ten
    tokens, each the reference's most likely one (a greedy request
    reports no log-prob; the sampled case, against the reference, is the
    test above, whose second wave is a prefix hit)."""
    eng = _engine(params)
    prompt = [int(x) for x in rng.integers(1, 128, 29)]
    runs = []
    for k in range(2):
        eng.submit(GenRequest(rid=f"g{k}", input_ids=prompt,
                              max_new_tokens=10, greedy=True))
        (o,) = eng.run_until_done(4)
        runs.append(o)
    assert eng.stats["prefix_hit_tokens"] >= 24
    assert runs[0].output_ids == runs[1].output_ids
    lp_tok, lp_max = ref.next_token_logprobs(
        params, ARCH, prompt + runs[1].output_ids, "float32", 64)
    np.testing.assert_allclose(lp_tok[28:], lp_max[28:], atol=1e-6)


def test_benchmark_warm_up_reaches_every_bucket_at_every_table_width(params):
    """The latent driver's set-up (``_warm_admission`` of the older driver,
    then its own ``_warm_wider_tables``: one cold prompt a wider width and
    every bucket as prefix hits on it) leaves nothing to specialise: every
    admission bucket at every page-table width, the chunk at each width;
    a bucket of distinct prompts that fill the widest table then adds no
    program and no jax-level entry."""
    from benchmark.drivers import rollout_latent_inproc as drv

    eng = GenerationEngine(
        CFG, params, max_slots=4, max_seqlen=280, max_new_tokens_cap=8,
        page_size=4, admit_buckets=(1, 2), enable_prefix_cache=True, seed=3)
    widths = eng.table_widths()
    assert widths == [32, 64, 70]
    drv._warm_admission(eng, 1.0, 128, 4)
    drv._warm_wider_tables(eng, 1.0, 128, 4)
    have = eng.program_sizes()
    for w in widths:
        assert any(k.startswith(f"chunk(4, {w},") for k in have), w
        for b in eng.admit_buckets:
            assert have.get(f"extend({b}, {w}, False)") == 1, (b, w)
    entries = eng.n_jit_entries()
    rng = np.random.default_rng(5)
    for j in range(2):
        eng.submit(GenRequest(
            rid=f"late-{j}", input_ids=rng.integers(1, 128, 270).tolist(),
            max_new_tokens=6, temperature=1.0))
    outs = eng.run_until_done(4)
    assert sorted(len(o.output_ids) for o in outs) == [6, 6]
    assert eng.program_sizes() == have and eng.n_jit_entries() == entries


@pytest.mark.parametrize("case", ["sound", "mean_over_limit", "control"])
def test_benchmark_check_has_a_limit_on_the_mean_and_a_control(
        params, rng, case):
    """The latent driver's comparison: ``benchmark/correct.py``'s rule on
    the largest difference AND a limit on each sequence's mean. Log-probs
    level with the reference pass; with ONE of two sequences shifted by
    0.03 nats everywhere they stay inside the rule on the largest (twice
    what bf16 costs the reference, + 0.02) and fall to the limit on a
    sequence's mean; the reference computed in 8 bits in the program's
    place is refused."""
    from benchmark.drivers import rollout_latent_inproc as drv

    chk = {"seq_mean_abs_diff_limit_nats": 0.02,
           "control_dtype": "float8_e5m2"}
    arch = dict(ARCH, reference="joyai_llm_flash")
    samples = []
    for _ in range(2):
        toks = [int(x) for x in rng.integers(1, 128, 48)]
        lp, _ = ref.next_token_logprobs(params, ARCH, toks, "float32", 256)
        samples.append({"tokens": toks, "start": 16, "logprobs": lp[15:]})
    if case == "control":
        got = drv._control(params, arch, "bfloat16", samples, chk)
        assert got["correct"] is False
        return
    if case == "mean_over_limit":
        samples[1] = dict(samples[1], logprobs=samples[1]["logprobs"] - 0.03)
    got = drv._judge(params, arch, "bfloat16", samples, chk)
    assert got["max_abs_diff_nats"] <= got["tolerance_nats"]
    assert got["correct"] is (case == "sound")
    if case != "sound":
        assert "mean" in got["reason"]
        assert got["mean_abs_diff_nats"] < 0.02 < max(
            got["seq_mean_abs_diff_nats"])


def test_engine_routing_record(params, rng):
    """``record_routing``: each output token's chosen experts in the two
    expert layers are those the reference routes that position to."""
    eng = _engine(params, record_routing=True)
    prompt = [int(x) for x in rng.integers(1, 128, 19)]
    eng.submit(GenRequest(rid="a", input_ids=prompt, max_new_tokens=10,
                          temperature=1.0))
    (out,) = eng.run_until_done(4)
    assert out.output_routing.shape == (10, 2, 4)
    seq = prompt + out.output_ids
    want, _ = ref.routing(params, ARCH, seq[:-1])            # [Lx, T, K]
    np.testing.assert_array_equal(
        out.output_routing,
        np.asarray(want)[:, len(prompt) - 1:].transpose(1, 0, 2))


def test_extend_then_decode_matches_reference(
        params, rng, decode_tokens_paged):
    """``extend_paged`` (two chunks: the first skips the empty pool, the
    second reads it) then four decode steps straight on a latent pool with
    a page table of their own: the steps' logits are the reference's at
    those positions."""
    seq = [int(x) for x in rng.integers(1, 128, 24)]
    cache = tfm.PagedKVCache.empty(CFG, 12, 8)
    assert cache.pages.shape == (3, 12, 1, 1, 8, 128)
    # two rows; row 1 holds the sequence in pages 5, 2, 9, 7
    table = jnp.zeros((2, 4), jnp.int32).at[1].set(jnp.asarray([5, 2, 9, 7]))
    with jax.default_matmul_precision("highest"):
        for c, skip in ((0, True), (1, False)):
            toks = jnp.zeros((2, 10), jnp.int32).at[1].set(
                jnp.asarray(seq[c * 10:(c + 1) * 10]))
            cache = tfm.extend_paged(
                params, CFG, cache, toks, table,
                jnp.asarray([0, c * 10]), jnp.asarray([0, 10]),
                skip_pool=skip)
        chunk = jnp.zeros((2, 4), jnp.int32).at[1].set(jnp.asarray(seq[20:]))
        logits, cache2 = decode_tokens_paged(
            params, CFG, cache, chunk, table, [0, 20], [0, 4])
    lp = jax.nn.log_softmax(logits[1], axis=-1)
    got = np.asarray(lp[np.arange(3), np.asarray(seq[21:])])
    np.testing.assert_allclose(
        got, _ref_logprobs(params, seq)[20:], atol=TOL_NATS)
    # the steps' latents landed at positions 20..23 (page 9, offsets 4..7)
    # and nowhere else; the rotary part follows the 32 latent values
    assert float(jnp.abs(cache2.pages[:, 9, 0, 0, 4:, :40]).min()) > 0
    assert float(jnp.abs(cache2.pages[:, :, 0, 0, :, 40:]).max()) == 0
    assert float(jnp.abs(cache2.pages[:, 7]).max()) == 0
    np.testing.assert_array_equal(
        np.asarray(cache2.pages[:, 5]), np.asarray(cache.pages[:, 5]))


# ------------------------------------------------------------------ #
# (iv) the trainer
# ------------------------------------------------------------------ #

def _train_engine(params):
    eng = TrainEngine(CFG, ParallelConfig(), OptimizerConfig())
    eng.load_params(jax.tree.map(np.asarray, params))
    return eng


def _ppo_sample(rng, seqs, prompt_lens, behav):
    lens = [len(s) for s in seqs]
    prompt_mask = np.concatenate([
        np.r_[np.ones(pl, bool), np.zeros(n - pl, bool)]
        for n, pl in zip(lens, prompt_lens)])
    return SequenceSample.from_default(
        seqlens=lens, ids=list(range(len(seqs))),
        data={
            "packed_input_ids": np.concatenate(seqs).astype(np.int32),
            "packed_logprobs": np.concatenate(behav).astype(np.float32),
            "prompt_mask": prompt_mask,
            "rewards": rng.normal(0, 1, len(seqs)).astype(np.float32),
            "seq_no_eos_mask": np.zeros(len(seqs), bool),
        },
    )


@pytest.fixture(scope="module")
def ppo_case(params):
    rng = np.random.default_rng(11)
    seqs = [rng.integers(1, 128, n) for n in (23, 31, 17)]
    prompt_lens = [6, 9, 5]
    behav = [np.r_[_ref_logprobs(params, s), 0.0]
             + rng.normal(0, 0.05, len(s)) for s in seqs]
    return seqs, prompt_lens, behav, _ppo_sample(rng, seqs, prompt_lens, behav)


def test_trainer_inference_matches_reference(params, ppo_case):
    seqs, _, _, sample = ppo_case
    actor = PPOActorInterface(hp=PPOHyperparameters(disable_value=True))
    out = actor.inference(_train_engine(params), sample, MicroBatchSpec())
    got = np.asarray(out.data["prox_logp"])
    want = np.concatenate([np.r_[_ref_logprobs(params, s), 0.0] for s in seqs])
    np.testing.assert_allclose(got, want, atol=TOL_NATS)


def test_trainer_gradients_match_reference(params, ppo_case):
    """``train_step`` under plain SGD of rate 1 moves every weight by minus
    its gradient, so (before - after) IS the trainer's gradient, through
    its real jitted step (two scans, vmap over packed rows, remat). The
    expected gradient is ``jax.grad`` of the same PPO actor loss built on
    the REFERENCE's log-probs: both latent norms, the up-projections, the
    router through the combine weights, the shared expert and the dense
    layer included. The router's correction bias only moves a choice: its
    gradient is zero on both sides."""
    import optax

    seqs, prompt_lens, _, sample = ppo_case
    hp = PPOHyperparameters(
        disable_value=True, ppo_n_minibatches=1, use_decoupled_loss=False,
        recompute_logprob=False)
    actor = PPOActorInterface(hp=hp)
    eng = _train_engine(params)
    eng.setup_optimizer(10)
    eng.tx = optax.sgd(1.0)
    eng.opt_state = eng.tx.init(eng.params)
    before = jax.tree.map(np.asarray, eng.params)
    sample = SequenceSample.from_default(
        ids=list(sample.ids), seqlens=[len(s) for s in seqs],
        data=dict(sample.data))
    actor.train_step(eng, sample, MicroBatchSpec())
    g_prog = jax.tree.map(lambda a, b: a - np.asarray(b), before, eng.params)

    adv = np.asarray(sample.data["advantages"], np.float32)
    old = np.asarray(sample.data["packed_logprobs"], np.float32)
    mask = np.concatenate([
        np.r_[np.arange(1, n) >= pl, False]
        for n, pl in zip(map(len, seqs), prompt_lens)])

    def reference_loss(p):
        lp = jnp.concatenate([
            jnp.concatenate([ref.sequence_logprobs(p, ARCH, s), jnp.zeros(1)])
            for s in seqs])
        return ppo_ops.actor_loss_fn(
            lp, jnp.asarray(old), jnp.asarray(adv), hp.eps_clip,
            jnp.asarray(mask))[0]

    g_ref = jax.jit(jax.grad(reference_loss))(params)    # ONE program
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(g_prog), jax.tree.leaves(g_ref)):
        b = np.asarray(b)
        name = jax.tree_util.keystr(path)
        scale = float(np.abs(b).max())
        if "b_router" in name:
            assert scale == 0.0 and float(np.abs(a).max()) == 0.0, name
            continue
        assert scale > 0, name
        # relative to the leaf's largest entry: 1e-3 covers float32
        # summation order through three layers and a 128-wide softmax; a
        # missing term is of order 1. The trainer's gradient is a
        # DIFFERENCE of float32 weights, so it carries their rounding: a
        # gain near 1 whose gradient is 1e-4 (the query latent's norm) is
        # known to 1.2e-7 / 1e-4 of it (measured 1.5e-3 there; the
        # program's own ``jax.grad`` agrees with the reference to 1e-6)
        w = np.asarray(jax.tree_util.tree_reduce(
            lambda x, k: x[k.key], path, before))
        ulp = 4 * np.finfo(np.float32).eps * float(np.abs(w).max()) / scale
        np.testing.assert_allclose(
            a / scale, b / scale, atol=max(1e-3, ulp), err_msg=name)


def test_engine_serves_the_same_tokens_through_the_grouped_kernel(
        params, rng, check_moe_grouped_serves_the_same):
    """The routed experts on the einsums and on ``moe_grouped``
    (sigmoid scores with a correction bias, a leading dense
    layer, the latent pool): the same
    greedy tokens, and the two counters add up (``conftest.py``)."""
    prompts = [[int(x) for x in rng.integers(1, 128, n)] for n in (5, 19, 33)]
    check_moe_grouped_serves_the_same(lambda: _engine(params), prompts)
