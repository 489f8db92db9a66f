"""Ouro (family ``ouro``: a LOOPED stack) against its plain reference, end
to end.

A tiny model of the family's shape: 2 layers of weights run 3 times (6
layers of CACHE), hidden 64, 4 query / 4 key-value heads of 16, SwiGLU of
128, four norms a layer, the model's final norm after every pass, an exit
gate that nothing reads; seeded random weights with the norm gains off
their init values (the benchmark's fill: 1 + normal(0.1)), float32
everywhere. The reference is the benchmark's (``benchmark/reference/
ouro.py``): plain ``jax.numpy``, a Python loop over passes and layers,
dense attention recomputed in every pass, none of the program's model
code.

What the family forces: the number of layers of WEIGHTS and of CACHE are
two numbers. Every cache's leading axis is ``cfg.cache_layers = n_passes x
n_layers``; pass ``t``, layer ``l`` reads and writes cache layer ``t *
n_layers + l`` (``models/transformer._scan_passes``).

Tolerance: 1e-4 nats on log-probabilities. Both sides compute in float32
on the CPU; what is left is summation order, about 1e-6. A pass left out,
a missing between-pass norm, a branch norm in the wrong place or a pass
reading another pass's cache layer moves a log-probability by 1e-2 to 1
nat (``test_what_the_tolerance_has_to_see`` holds the first three to
that); the same path in bfloat16 is off by more than 1e-3.
"""

import dataclasses
import functools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import engine_contract
from areal_tpu.api.data import MicroBatchSpec, SequenceSample
from areal_tpu.api.model import PPOHyperparameters
from areal_tpu.base import flops as flops_mod
from areal_tpu.base import tracing
from areal_tpu.gen.engine import GenerationEngine, GenRequest
from areal_tpu.interfaces.ppo import PPOActorInterface
from areal_tpu.models import hf as hf_conv
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import MLAConfig, ModelConfig
from areal_tpu.parallel.mesh import ParallelConfig
from areal_tpu.train.engine import OptimizerConfig, TrainEngine
from benchmark import weights as bench_weights
from benchmark.reference import ouro as ref

TOL_NATS = 1e-4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# Ouro-2.6B), key for key
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152,
}

T, L, PAGE = 3, 2, 8
ARCH = dict(
    PUBLISHED, hidden_size=64, head_dim=16, num_attention_heads=4,
    num_key_value_heads=4, intermediate_size=128, vocab_size=128,
    num_hidden_layers=L, layer_types=["full_attention"] * L,
    max_window_layers=L, total_ut_steps=T, max_position_embeddings=256,
)
FAMILY = hf_conv.family_for_model_type("ouro")


def _cfg(arch=ARCH, **over) -> ModelConfig:
    return dataclasses.replace(
        FAMILY.config_from_hf(arch), dtype="float32", **over)


CFG = _cfg()


def _weights(cfg, seed=20260930):
    """Seeded weights with gains away from 1 (the benchmark's fill), so a
    norm in the wrong place or a missing gain shows."""
    shapes = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.key(0)))
    return bench_weights.make_weights(shapes, seed, jnp.float32)


@pytest.fixture(scope="module")
def params():
    return _weights(CFG)


@pytest.fixture()
def rng():
    return np.random.default_rng(7)


def _ref_logprobs(params, tokens, arch=ARCH):
    pad = -(-len(tokens) // 64) * 64
    lp, _ = ref.next_token_logprobs(params, arch, list(tokens), "float32", pad)
    return lp


def _forward_logprobs(cfg, params, ids):
    n = len(ids)
    with jax.default_matmul_precision("highest"):
        logits = tfm.forward_packed(
            params, cfg, jnp.asarray(ids, jnp.int32),
            jnp.ones((n,), jnp.int32), jnp.arange(n))
    lp = jax.nn.log_softmax(logits, axis=-1)
    return np.asarray(lp[np.arange(n - 1), np.asarray(ids[1:])])


def _toks(rng, n):
    return [int(x) for x in rng.integers(1, 128, n)]


# ------------------------------------------------------------------ #
# (i) the family, its tree, its refusals
# ------------------------------------------------------------------ #

def test_family_reads_the_published_config_key_for_key():
    cfg = FAMILY.config_from_hf(PUBLISHED)
    assert (cfg.n_layers, cfg.n_passes, cfg.cache_layers) == (48, 4, 192)
    assert (cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim) == (16, 16, 128)
    assert (cfg.hidden_dim, cfg.intermediate_dim) == (2048, 5632)
    assert cfg.vocab_size == 49152 and cfg.n_positions == 65536
    assert cfg.rotary_base == 1000000 and cfg.layer_norm_epsilon == 1e-6
    assert cfg.norm_branch_out and cfg.exit_gate and not cfg.tied_embedding
    assert not cfg.use_attention_bias and cfg.sliding_window is None
    assert cfg.n_periods == 192 and cfg.period == 1
    back = FAMILY.config_to_hf(cfg)
    assert back.pop("architectures") == ["OuroForCausalLM"]
    assert back == PUBLISHED
    # --rehearse keeps the file's 8 layer_types beside 2 layers: the first
    # num_hidden_layers entries are the ones read
    assert FAMILY.config_from_hf(dict(PUBLISHED, num_hidden_layers=2)).n_layers == 2


def test_benchmark_config_is_the_published_one_cut_in_depth():
    with open(os.path.join(
            ROOT, "benchmark", "configs", "ouro-2p6b-l8.json")) as f:
        arch = json.load(f)
    changed = {k for k, v in PUBLISHED.items() if arch.get(k) != v}
    assert changed == set(arch["reduced"]) == {
        "num_hidden_layers", "layer_types", "max_window_layers"}
    assert arch["num_hidden_layers"] == arch["max_window_layers"] == 8
    assert arch["layer_types"] == ["full_attention"] * 8
    assert arch["reduced_from"]["num_hidden_layers"] == 48
    assert arch["source"].endswith("ByteDance/Ouro-2.6B/blob/main/config.json")
    assert arch["reference"] == "ouro" and "from_memory" in arch["assumed"]
    from benchmark import loop_flops, sut

    cfg = sut.model_config(arch, {})
    assert cfg.cache_layers == loop_flops.cache_layers(arch) == 32
    assert loop_flops.kv_bytes_per_token(arch) == 262144
    assert loop_flops.layer_weight_bytes(arch) == 102760448
    streams, heads, width = tfm.kv_page_geometry(cfg)
    assert cfg.n_periods * streams * heads * width * 2 == 262144
    shapes = sut.weight_shapes(cfg, cfg.dtype)
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) == (
        612438017)
    rx = loop_flops.weight_op_pattern(arch, "jit_chunk")
    assert rx.search("jit_chunk/%fusion.12 fusion bf16[72,5632] <- bf16[8,2048,5632]")
    assert rx.search("jit_chunk/%fusion.3 fusion bf16[72,2048] <- bf16[2048,2048]")
    assert rx.search("jit_chunk/%fusion.208 fusion bf16[72,16,128] <- bf16[16,128,2048]")
    assert not rx.search("jit_chunk/%fusion.215 fusion f32[72],bf16[72,2048] <- bf16[72,2048]")
    assert not rx.search("jit_chunk/%while.3 while (s32[]) <- bf16[8,2048,5632]")
    assert not rx.search("jit_extend/%fusion.1 fusion bf16[8,2048] <- bf16[8,5632,2048]")
    assert not rx.search("jit_chunk/%fused_sample.1 custom-call s32[72] <- bf16[2048,49152]")


@pytest.mark.parametrize("key,value", [
    ("early_exit_threshold", 0.9),
    ("use_sliding_window", True),
    ("sliding_window", 4096),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}),
    ("layer_types", ["full_attention", "sliding_attention"] * 24),
    ("layer_types", ["full_attention"] * 47),
])
def test_family_refuses_what_it_does_not_implement(key, value):
    with pytest.raises(ValueError, match="ouro"):
        FAMILY.config_from_hf(dict(PUBLISHED, **{key: value}))


@pytest.mark.parametrize("over", [
    dict(mla=MLAConfig(q_lora_rank=8, kv_lora_rank=8, qk_nope_head_dim=8,
                       qk_rope_head_dim=8, v_head_dim=8)),
    dict(n_dense_layers=1, mlp_type="moe"),
    dict(n_mtp_layers=1),
    dict(layer_pattern=((None, True), (8, True))),
    dict(n_passes=0),
], ids=["mla", "dense_layers", "mtp", "layer_pattern", "no_pass"])
def test_config_refuses_a_loop_beside_what_no_model_has_with_it(over):
    with pytest.raises(ValueError, match="n_passes"):
        dataclasses.replace(CFG, **over)


def test_reference_refuses_an_early_exit():
    with pytest.raises(ValueError, match="early_exit_threshold"):
        ref.next_token_logprobs(
            {}, dict(ARCH, early_exit_threshold=0.5), [1, 2], "float32", 64)


def test_hf_names_round_trip(params, tmp_path):
    """Through DISK: every tensor under the family's names, the two branch
    norms and the gate's two tensors among them, and back bit for bit."""
    from safetensors.numpy import load_file

    host = jax.tree.map(np.asarray, params)
    hf_conv.save_hf_checkpoint(host, CFG, "ouro", str(tmp_path))
    sd = {}
    for f in os.listdir(tmp_path):
        if f.endswith(".safetensors"):
            sd.update(load_file(os.path.join(tmp_path, f)))
    per_layer = [
        "self_attn.q_proj", "self_attn.k_proj", "self_attn.v_proj",
        "self_attn.o_proj", "mlp.gate_proj", "mlp.up_proj", "mlp.down_proj",
        "input_layernorm", "input_layernorm_2", "post_attention_layernorm",
        "post_attention_layernorm_2"]
    assert set(sd) == {
        f"model.layers.{i}.{n}.weight" for i in range(L) for n in per_layer
    } | {"model.embed_tokens.weight", "model.norm.weight", "lm_head.weight",
         "model.early_exit_gate.weight", "model.early_exit_gate.bias"}
    assert sd["model.early_exit_gate.weight"].shape == (1, 64)
    assert sd["model.layers.1.mlp.down_proj.weight"].shape == (64, 128)
    with open(os.path.join(tmp_path, "config.json")) as f:
        written = json.load(f)
    assert {k: written[k] for k in ARCH} == ARCH
    cfg2, p2 = hf_conv.load_hf_checkpoint(str(tmp_path))
    assert dataclasses.replace(cfg2, dtype="float32") == CFG
    assert jax.tree.structure(p2) == jax.tree.structure(host)
    for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(a, b)


def test_param_axes_and_flops_follow_the_tree(params):
    axes = tfm.param_logical_axes(CFG)
    assert jax.tree.structure(
        jax.tree.map(lambda _: 0, axes, is_leaf=lambda x: isinstance(x, tuple))
    ) == jax.tree.structure(jax.tree.map(lambda _: 0, params))
    assert axes["layers"]["mlp_out_ln"]["weight"] == ("layer", "embed")
    # the layers' matmuls T times, the head once; attention once a cache layer
    E, F, V = 64, 128, 128
    layer = 4 * E * E + 3 * E * F
    once = dataclasses.replace(CFG, n_passes=1)
    assert flops_mod.forward_flops(CFG, 10) == 2 * 10 * (T * L * layer + E * V)
    assert flops_mod.forward_flops(once, 10) == 2 * 10 * (L * layer + E * V)
    attn = flops_mod.forward_flops(CFG, 10, [10]) - flops_mod.forward_flops(CFG, 10)
    assert attn == T * (
        flops_mod.forward_flops(once, 10, [10]) - flops_mod.forward_flops(once, 10))
    assert flops_mod.train_flops(CFG, 10) == 3 * flops_mod.forward_flops(CFG, 10)


# ------------------------------------------------------------------ #
# (ii) the trainer's forward, loss and gradients
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("n", [5, 40])
def test_forward_matches_reference(params, rng, n):
    ids = _toks(rng, n)
    np.testing.assert_allclose(
        _forward_logprobs(CFG, params, ids), _ref_logprobs(params, ids),
        atol=TOL_NATS)


def test_one_pass_emits_no_outer_scan(params):
    """``_scan_passes`` at a pass count of 1 is ``_scan_layers`` and nothing
    around it (the programs of every other family are what they were); a
    looped stack is ONE scan over the passes around ONE over the layers."""
    ids = jnp.arange(8, dtype=jnp.int32) + 1

    def n_scans(cfg):
        jaxpr = jax.make_jaxpr(lambda p: tfm.forward_packed(
            p, cfg, ids, jnp.ones((8,), jnp.int32), jnp.arange(8),
            remat=False))(params)
        return str(jaxpr).count("scan[")

    assert n_scans(dataclasses.replace(CFG, n_passes=1)) == 1
    assert n_scans(CFG) == 2


@pytest.mark.parametrize(
    "fault", ["pass_left_out", "no_norm_between_passes", "kv_of_first_pass"])
def test_what_the_tolerance_has_to_see(params, rng, fault, monkeypatch):
    """The reference with one of the mechanism's parts taken away is off
    by a hundred tolerances: the comparisons of this file would see a
    program that made the same mistake."""
    ids = _toks(rng, 40)
    sound = _ref_logprobs(params, ids)
    arch = ARCH
    if fault == "pass_left_out":
        arch = dict(ARCH, total_ut_steps=T - 1)
    elif fault == "no_norm_between_passes":
        monkeypatch.setattr(ref, "FINAL_NORM_EVERY_PASS", False)
    else:
        monkeypatch.setattr(ref, "KV_OF_ITS_OWN_PASS", False)
    assert np.abs(_ref_logprobs(params, ids, arch) - sound).max() > 100 * TOL_NATS


def _program_loss(cfg, p, ids):
    n = len(ids)
    with jax.default_matmul_precision("highest"):
        logits = tfm.forward_packed(
            p, cfg, jnp.asarray(ids, jnp.int32), jnp.ones((n,), jnp.int32),
            jnp.arange(n), remat=True)
    lp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(lp[jnp.arange(n - 1), jnp.asarray(ids[1:])])


def _assert_grads_close(got, want):
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        b = np.asarray(b)
        scale = float(np.abs(b).max())
        if "exit_gate" in name:
            # carried, read by no forward: its gradient is exactly zero
            assert scale == 0 and float(np.abs(a).max()) == 0, name
            continue
        assert scale > 0, name
        # relative to the leaf's largest entry: summation order only
        np.testing.assert_allclose(
            np.asarray(a), b, atol=2e-4 * scale, err_msg=name)


@pytest.mark.parametrize("policy", ["full", "dots", "dots_attn", "none"])
def test_loss_and_gradients_match_reference(params, rng, policy):
    """``jax.grad`` through ``forward_packed`` (both scans, the layer
    checkpointed as ``remat_policy`` says) against ``jax.grad`` of the
    plain reference's loss."""
    ids = _toks(rng, 24)
    cfg = dataclasses.replace(CFG, remat_policy=policy)
    # each side ONE program, not an op at a time
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: _program_loss(cfg, p, ids)))(params)
    want, g_ref = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, ARCH, ids)))(params)
    np.testing.assert_allclose(float(loss), float(want), atol=1e-5)
    _assert_grads_close(grads, g_ref)


def test_shared_weight_gradient_is_the_sum_of_its_uses(params, rng):
    """An UNTIED copy of the stack (one set of weights a pass) through the
    reference: its T per-pass gradients sum to the program's gradient of
    the shared stack, and no single pass's does."""
    ids = _toks(rng, 24)
    grads = jax.jit(jax.grad(lambda p: _program_loss(CFG, p, ids)))(params)
    untied = dict(params, layers=jax.tree.map(
        lambda a: jnp.broadcast_to(a, (T, *a.shape)), params["layers"]))
    g_untied = jax.jit(jax.grad(
        lambda p: ref.loss(p, ARCH, ids, untied=True)))(untied)
    summed = dict(g_untied, layers=jax.tree.map(
        lambda a: a.sum(axis=0), g_untied["layers"]))
    _assert_grads_close(grads, summed)
    wq, per_pass = grads["layers"]["attn"]["wq"], g_untied["layers"]["attn"]["wq"]
    scale = float(np.abs(wq).max())
    for t in range(T):
        assert float(np.abs(per_pass[t] - wq).max()) > 0.05 * scale


def test_saved_activations_are_what_the_passes_cost(params):
    """What the backward keeps of the forward (the residuals of
    ``jax.vjp``), by shape. ``full``: ONE layer input ``[n, E]`` a (pass,
    layer), stacked ``[T, L, n, E]`` where one pass keeps ``[L, n, E]``,
    and three ``[T, n, E]`` a pass for the norm between passes. ``dots``:
    every matmul output of every (pass, layer), six of width E and two of
    width F. So four passes cost four times one pass's activations under
    either policy: the loop saves weights, not activations."""
    import collections

    n, E, F = 32, 64, 128

    def saved(policy, passes):
        cfg = dataclasses.replace(CFG, remat_policy=policy, n_passes=passes)
        ids = jnp.arange(n, dtype=jnp.int32) % 100 + 1

        def loss(p):
            logits = tfm.forward_packed(
                p, cfg, ids, jnp.ones((n,), jnp.int32), jnp.arange(n))
            return jnp.sum(logits)

        _, f_vjp = jax.vjp(loss, params)
        return collections.Counter(
            x.shape for x in jax.tree.leaves(f_vjp) if hasattr(x, "shape"))

    assert saved("full", 1)[(L, n, E)] == 1
    full = saved("full", T)
    assert full[(T, L, n, E)] == 1 and full[(T, n, E)] == 3
    assert (T, L, n, F) not in full
    one = saved("dots", 1)
    assert (one[(L, n, E)], one[(L, n, F)]) == (6, 2)
    dots = saved("dots", T)
    assert (dots[(T, L, n, E)], dots[(T, L, n, F)]) == (6, 2)
    assert dots[(T, n, E)] == 3


# ------------------------------------------------------------------ #
# (iii) the caches: dense, paged (kernel interpreted and XLA path)
# ------------------------------------------------------------------ #

def test_dense_cache_prefill_and_decode_match_reference(params, rng):
    seq = _toks(rng, 30)
    cache = tfm.KVCache.empty(CFG, 2, 32)
    assert cache.k.shape == (T * L, 2, 32, 4, 16)
    prompts = np.zeros((2, 16), np.int32)
    prompts[0, :16], prompts[1, :9] = seq[:16], seq[:9]
    with jax.default_matmul_precision("highest"):
        logits, cache = tfm.prefill(
            params, CFG, cache, jnp.asarray(prompts), jnp.asarray([16, 9]))
        got = [[], []]
        lens = [16, 9]
        for step in range(10):
            for b in range(2):
                got[b].append(float(
                    jax.nn.log_softmax(logits[b])[seq[lens[b] + step]]))
            logits, cache = tfm.decode_step(
                params, CFG, cache,
                jnp.asarray([seq[16 + step], seq[9 + step]]))
    want = _ref_logprobs(params, seq)
    np.testing.assert_allclose(got[0], want[15:25], atol=TOL_NATS)
    np.testing.assert_allclose(got[1], want[8:18], atol=TOL_NATS)
    # a cache layer a PASS: the same layer's keys differ from pass to pass
    k = np.asarray(cache.k)
    assert np.abs(k[0, 0, :16] - k[L, 0, :16]).max() > 1e-2


@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["interpret_kernel", "xla_gather"])
def test_paged_extend_and_decode_match_reference(
        params, rng, use_pallas):
    """Two rows of unequal length through ``extend_paged`` (two chunks, the
    second reading the pool), then nine steps of ``decode_step_paged``:
    every log-prob is the reference's full forward, and each (pass, layer)
    wrote its own slice of the pool."""
    seq = _toks(rng, 34)
    cache = tfm.PagedKVCache.empty(CFG, 24, PAGE)
    assert cache.pages.shape == (T * L, 24, 2, 4, PAGE, 16)
    table = jnp.asarray(np.arange(1, 21, dtype=np.int32).reshape(2, 10))
    n0 = [20, 11]
    toks = np.zeros((2, 12), np.int32)
    # one program a chunk and ONE for the nine steps, as the engine builds
    # them: eagerly every op of every (pass, layer) is a program of its own
    extend = jax.jit(
        lambda skip_pool, *a: tfm.extend_paged(
            params, CFG, *a, skip_pool=skip_pool, use_pallas=use_pallas),
        static_argnums=0)
    decode = jax.jit(lambda *a: tfm.decode_step_paged(
        params, CFG, *a, use_pallas=use_pallas))
    with jax.default_matmul_precision("highest"):
        for c in range(2):
            start = np.asarray([12 * c, 12 * c])
            n_new = np.clip(np.asarray(n0) - start, 0, 12)
            for b in range(2):
                toks[b, : n_new[b]] = seq[start[b]: start[b] + n_new[b]]
            cache = extend(
                c == 0, cache, jnp.asarray(toks), table,
                jnp.asarray(start), jnp.asarray(n_new))
        lens = jnp.asarray(n0)
        got = [[], []]
        for step in range(9):
            cur = [seq[n0[0] + step], seq[n0[1] + step]]
            logits, cache, lens = decode(
                cache, jnp.asarray(cur), table, lens,
                jnp.asarray([True, True]))
            for b in range(2):
                got[b].append(float(jax.nn.log_softmax(logits[b])[
                    seq[n0[b] + step + 1]]))
    want = _ref_logprobs(params, seq)
    np.testing.assert_allclose(got[0], want[20:29], atol=TOL_NATS)
    np.testing.assert_allclose(got[1], want[11:20], atol=TOL_NATS)
    pages = np.asarray(cache.pages)
    first = pages[:, 1]         # row 0's first page, in every cache layer
    assert np.abs(first).min(axis=(1, 2, 3, 4)).min() > 0
    for li in range(1, T * L):
        assert np.abs(first[li] - first[0]).max() > 1e-2, li
    assert float(np.abs(pages[:, 21:]).max()) == 0


# ------------------------------------------------------------------ #
# (iv) the engine
# ------------------------------------------------------------------ #

def _engine(params, cfg=CFG, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seqlen", 128)
    kw.setdefault("max_new_tokens_cap", 64)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("admit_buckets", (1, 2, 4))
    return GenerationEngine(cfg, params, **kw)


@pytest.mark.parametrize("check", engine_contract.CHECKS)
def test_engine_contract(params, check):
    engine_contract.run(check, functools.partial(_engine, params), CFG, params)


def _check_outputs(params, prompts, outs, n_new):
    for rid, p in prompts.items():
        o = outs[rid]
        assert len(o.output_ids) == n_new, rid
        want = _ref_logprobs(params, p + o.output_ids)[len(p) - 1:]
        np.testing.assert_allclose(
            np.asarray(o.output_logprobs), want, atol=TOL_NATS, err_msg=rid)


def _chunk_attrs():
    return [s["attrs"] for s in tracing.drain()
            if s["name"] == "gen_engine/chunk" and "slots" in s["attrs"]]


@pytest.mark.parametrize("use_pallas", [True, None],
                         ids=["interpret_kernel", "xla_gather"])
def test_engine_logprobs_match_reference(params, rng, use_pallas):
    """Chunked prefill, then paged decode through a pool whose leading
    axis is passes x layers: served log-probs are the reference's full
    forward on prompt + output; the spans and counters count cache layers."""
    eng = _engine(params, n_pages=96)
    eng._decode_use_pallas = use_pallas
    assert eng.state.cache.pages.shape == (T * L, 96, 2, 4, PAGE, 16)
    assert eng._tables_host.shape == (1, 4, 128 // PAGE)
    one = 2 * 4 * 16 * 4            # a key and a value in one cache layer
    assert eng.cache_bytes_per_token() == T * L * one
    assert eng.kv_pool_bytes() == T * L * 96 * PAGE * one
    prompts = {f"r{i}": _toks(rng, n) for i, n in enumerate((3, 9, 14))}
    tracing.drain()
    for rid, p in prompts.items():
        eng.submit(GenRequest(rid=rid, input_ids=p, max_new_tokens=12,
                              temperature=1.0))
    outs = {o.rid: o for o in eng.run_until_done(4)}
    _check_outputs(params, prompts, outs, 12)
    chunks = _chunk_attrs()
    assert len(chunks) == 3
    for c in chunks:
        assert (c["loop_passes"], c["cache_layers"]) == (T, T * L)
        assert c["layer_passes"] == c["steps"] * T * L
        assert c["cache_bytes_per_token"] == T * L * one
        assert ("kv_write_tiles" in c) == bool(use_pallas)
        if use_pallas:
            # one pool tile a (CACHE layer, running slot, step)
            assert c["kv_write_tiles"] == T * L * c["slots"] * c["steps"]
    assert eng.stats["layer_passes"] == 3 * 4 * T * L
    assert eng.stats["loop_passes"] == 3 * 4 * T
    if use_pallas:
        assert eng.stats["kv_write_tiles"] > 3 * 4 * T * L * 3


def test_prefix_hit_and_resumed_request_give_the_cold_one_s_logits(
        params, rng):
    """The same 27-token prompt cold, then as a prefix hit (three whole pages
    shared in every cache layer): greedy gives the same tokens and sampled
    siblings the reference's log-probs. Then a request interrupted after
    its first chunk and RESUMED (prompt + what it had generated, submitted
    again): the rest is the cold run's, token for token."""
    eng = _engine(params, n_pages=96)
    prompt = _toks(rng, 27)
    runs = []
    for k in range(2):
        eng.submit(GenRequest(rid=f"g{k}", input_ids=prompt,
                              max_new_tokens=12, greedy=True))
        (o,) = eng.run_until_done(4)
        runs.append(o)
    assert eng.stats["prefix_hit_tokens"] == 24
    assert runs[0].output_ids == runs[1].output_ids
    np.testing.assert_allclose(
        runs[0].output_logprobs, runs[1].output_logprobs, atol=TOL_NATS)
    for k in range(2):
        eng.submit(GenRequest(rid=f"s{k}", input_ids=prompt,
                              max_new_tokens=12, temperature=1.0))
    outs = {o.rid: o for o in eng.run_until_done(4)}
    assert eng.stats["prefix_hit_tokens"] == 3 * 24
    _check_outputs(params, {"s0": prompt, "s1": prompt}, outs, 12)
    # interrupted after one chunk of 4, then resumed
    cold = _toks(rng, 10)
    eng.submit(GenRequest(rid="c", input_ids=cold, max_new_tokens=12,
                          greedy=True))
    (whole,) = eng.run_until_done(4)
    eng2 = _engine(params, n_pages=96)
    eng2.submit(GenRequest(rid="i", input_ids=cold, max_new_tokens=12,
                           greedy=True))
    assert eng2.step(4) == []
    (part,) = eng2.pause()
    assert part.finish_reason == "interrupted" and len(part.output_ids) == 4
    eng2.resume()
    eng2.submit(GenRequest(
        rid="i2", input_ids=cold + list(part.output_ids),
        max_new_tokens=12 - 4, greedy=True))
    (rest,) = eng2.run_until_done(4)
    assert list(part.output_ids) + list(rest.output_ids) == list(whole.output_ids)
    np.testing.assert_allclose(
        list(part.output_logprobs) + list(rest.output_logprobs),
        whole.output_logprobs, atol=TOL_NATS)
    assert eng2.stats["prefix_hit_tokens"] == PAGE    # 13 prefilled: 1 page


def test_tensor_parallel_engine_shards_the_pool_s_heads(params, rng):
    """Two devices of the CPU mesh: the pool ``[T*L, P, 2, Hkv, page, D]``
    shards its head axis as ever, and greedy chains are one device's."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    prompts = [_toks(rng, n) for n in (5, 9)]
    got = []
    for m in (None, mesh):
        eng = _engine(params, n_pages=48, mesh=m)
        for i, p in enumerate(prompts):
            eng.submit(GenRequest(rid=f"r{i}", input_ids=p, max_new_tokens=8,
                                  greedy=True))
        got.append({o.rid: o for o in eng.run_until_done(4)})
    assert eng.state.cache.pages.shape[0] == T * L
    assert eng.state.cache.pages.sharding.spec == P(
        None, None, None, "model", None, None)
    for rid in got[0]:
        assert got[0][rid].output_ids == got[1][rid].output_ids, rid
    # (a greedy token's log-prob is reported as 0: sampled ones are held
    # to the reference, on the mesh)
    sampled = {f"s{i}": p for i, p in enumerate(prompts)}
    for rid, p in sampled.items():
        eng.submit(GenRequest(rid=rid, input_ids=p, max_new_tokens=8,
                              temperature=1.0))
    _check_outputs(
        params, sampled, {o.rid: o for o in eng.run_until_done(4)}, 8)


# ------------------------------------------------------------------ #
# (v) the trainer and the PPO interface
# ------------------------------------------------------------------ #

def test_ppo_recomputed_logprobs_equal_the_engine_s(params, rng):
    """What the engine served (prefill + paged decode) and what the PPO
    actor's inference pass recomputes on the packed batch (the trainer's
    jitted forward over both scans) are the same numbers, and the
    reference's."""
    eng = _engine(params, n_pages=96)
    prompts = {f"r{i}": _toks(rng, n) for i, n in enumerate((6, 11, 4))}
    for rid, p in prompts.items():
        eng.submit(GenRequest(rid=rid, input_ids=p, max_new_tokens=9,
                              temperature=1.0))
    outs = {o.rid: o for o in eng.run_until_done(4)}
    seqs = [np.asarray(p + list(outs[rid].output_ids))
            for rid, p in prompts.items()]
    lens = [len(s) for s in seqs]
    sample = SequenceSample.from_default(
        seqlens=lens, ids=list(range(len(seqs))),
        data={
            "packed_input_ids": np.concatenate(seqs).astype(np.int32),
            "packed_logprobs": np.zeros(sum(lens), np.float32),
            "prompt_mask": np.concatenate([
                np.r_[np.ones(len(p), bool), np.zeros(n - len(p), bool)]
                for n, p in zip(lens, prompts.values())]),
            "rewards": np.zeros(len(seqs), np.float32),
            "seq_no_eos_mask": np.zeros(len(seqs), bool),
        },
    )
    train = TrainEngine(CFG, ParallelConfig(), OptimizerConfig())
    train.load_params(jax.tree.map(np.asarray, params))
    actor = PPOActorInterface(hp=PPOHyperparameters(disable_value=True))
    got = np.asarray(
        actor.inference(train, sample, MicroBatchSpec()).data["prox_logp"])
    at = 0
    for (rid, p), s in zip(prompts.items(), seqs):
        mine = got[at + len(p) - 1: at + len(s) - 1]
        np.testing.assert_allclose(
            mine, outs[rid].output_logprobs, atol=TOL_NATS, err_msg=rid)
        np.testing.assert_allclose(
            got[at: at + len(s) - 1], _ref_logprobs(params, s),
            atol=TOL_NATS, err_msg=rid)
        at += len(s)


# ------------------------------------------------------------------ #
# (vi) the benchmark's cell
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("case", ["sound", "pass_left_out", "low_precision"])
def test_benchmark_check_and_its_two_controls(params, rng, case):
    """The looped driver's check (``rollout_looped_inproc._check``): the
    reference's own log-probs pass, and both stand-ins are refused inside
    it; log-probs of a forward with one pass fewer handed in as the
    PROGRAM's fail the run."""
    from benchmark.drivers import rollout_looped_inproc as drv

    chk = {"seq_mean_abs_diff_limit_nats": 0.01, "control_passes": T - 1,
           "control_dtype": "float8_e5m2"}
    arch = dict(ARCH, reference="ouro")
    samples = []
    for n, start in ((20, 8), (33, 12)):
        toks = _toks(rng, n)
        lp = _ref_logprobs(
            params, toks,
            dict(ARCH, total_ut_steps=T - 1) if case == "pass_left_out"
            else ARCH)
        samples.append(
            {"tokens": toks, "start": start, "logprobs": lp[start - 1:]})
    if case == "low_precision":
        got = drv._control(params, arch, "float32", samples, chk)
        assert got["correct"] is False
        return
    got = drv._check(params, arch, "float32", samples, chk)
    assert got["correct"] is (case == "sound"), got
    if case == "sound":
        assert got["control"]["correct"] is False
        assert got["control_fewer_passes"]["correct"] is False
        assert min(got["control_fewer_passes"]["seq_mean_abs_diff_nats"]) > (
            100 * max(got["seq_mean_abs_diff_nats"] + [1e-6]))


def test_rehearsal_of_the_cell_and_selfcheck(capsys):
    """``--rehearse`` of the new cell end to end on the CPU (exits 3, its
    last line counts-only, the check and both controls inside), and the
    yardstick's own checks with the new entries."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}

    def rehearse(seconds):
        p = subprocess.run(
            [sys.executable, "-m", "benchmark.run", "--workload",
             "ouro-l8.rollout_out2k", "--seed", str(2**31 + 37), "--seconds",
             str(seconds), "--trace", "1", "--rehearse"],
            cwd=ROOT, capture_output=True, text=True, timeout=900, env=env)
        assert p.returncode == 3, p.stdout[-2000:] + p.stderr[-3000:]
        lines = p.stdout.strip().splitlines()
        return json.loads(lines[-1]), json.loads(lines[-2])["info"]

    last, info = rehearse(3)
    got = info["submitted_and_completed_in_window"]
    if got < 20:
        # the driver's verdict counts REQUESTS, and refuses a window in
        # which fewer than 20 were submitted and completed: a window is
        # seconds, and on a CPU that five other workers share 3 of them
        # can be too few (alone: 120 requests in 72 chunks). Sized by what
        # the first window got, with room, the second holds them
        assert not last["correct"] and info["check"]["reason"].startswith(
            f"only {got} requests ran inside the window"), info["check"]
        last, info = rehearse(min(120, 3 * 40 // max(got, 1) + 3))
    assert last["rehearsal"] and last["correct"] and last["failed"] == 0
    check = info["check"]
    assert check["control"]["correct"] is False
    assert check["control_fewer_passes"]["correct"] is False
    assert info["cache_layers"] == 4 * 2        # the file's passes, 2 layers
    assert info["layer_passes"] == info["loop_passes"] * 2 > 0
    # the part of the yardstick's own checks that this cell's entries can
    # move (check 1: BENCHMARK.json against the files it names), in
    # process; the whole of ``python -m benchmark.selfcheck --no-cells``,
    # 20 s of it the traffic generator's, is ``test_start_metrics.py``'s
    from benchmark import selfcheck
    from benchmark.run import load_json

    capsys.readouterr()
    failed_before = list(selfcheck.FAILED)
    selfcheck.check_files(load_json(ROOT, "BENCHMARK.json"))
    said = capsys.readouterr().out
    assert selfcheck.FAILED == failed_before, said
    for name in ("kernel.looped_decode_roofline", "loop.weight_stream_roofline",
                 "loop.weight_stream_share"):
        assert f"ok   reader {name}: unit, layer, moves, source agree" in said
