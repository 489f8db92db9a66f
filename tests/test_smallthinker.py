"""SmallThinker (family ``smallthinker``) against its plain reference, end
to end.

A tiny model of the family's shape: 8 layers in two periods of one FULL
layer (no positional encoding) and three WINDOW layers (rotary, window 8),
hidden 64, 4 query / 2 key-value heads of 16, 8 ReGLU experts of width 32
with 2 a token, the router reading the layer's normed input, seeded random
weights, float32 everywhere. The reference is the benchmark's
(``benchmark/reference/smallthinker.py``): plain ``jax.numpy``, dense
attention with the mask built from positions, the top logits first and
then their softmax, every expert for every token in a loop, none of the
program's model code.

The page pool is what the family forces (``gen/engine.py``): ONE pool and
one free list, a page table a position of the period, a window position's
pages released behind the window WHILE the request runs. Pages are 4
tokens here, so a generation of 30 tokens crosses the window's edge five
times.

Tolerance: 1e-4 nats on log-probabilities. Both sides compute in float32
on the CPU, so no rounding difference flips a top-2 choice; what is left
is summation order, about 1e-6. A wrong window edge, a released page read
as if it were still the slot's, rotary on a full layer, the router fed the
wrong tensor or a wrong cache position moves a log-probability by 1e-2 to
1 nat; the same path in bfloat16 is off by more than 1e-3.
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
from areal_tpu.base import flops as flops_mod
from areal_tpu.base import tracing
from areal_tpu.gen.engine import GenerationEngine, GenRequest
from areal_tpu.gen.pages import PagePool, PrefixRegistry
from areal_tpu.interfaces.ppo import PPOActorInterface
from areal_tpu.models import hf as hf_conv
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig
from areal_tpu.ops import moe as moe_ops
from areal_tpu.ops import paged_attention as paged_ops
from areal_tpu.ops import ppo as ppo_ops
from areal_tpu.ops.pallas import paged_attention as pl_paged
from areal_tpu.parallel.mesh import ParallelConfig
from areal_tpu.train.engine import OptimizerConfig, TrainEngine
from benchmark import weights as bench_weights
from benchmark.reference import smallthinker as ref

TOL_NATS = 1e-4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# SmallThinker-21BA3B-Instruct), key for key
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936,
}

WINDOW, PAGE = 8, 4
ARCH = dict(
    PUBLISHED, hidden_size=64, head_dim=16, num_attention_heads=4,
    num_key_value_heads=2, moe_ffn_hidden_size=32, moe_num_primary_experts=8,
    moe_num_active_primary_experts=2, vocab_size=128, num_hidden_layers=8,
    rope_layout=[0, 1, 1, 1] * 2, sliding_window_layout=[0, 1, 1, 1] * 2,
    sliding_window_size=WINDOW, max_position_embeddings=256,
)
FAMILY = hf_conv.family_for_model_type("smallthinker")


def _cfg(arch=ARCH, **over) -> ModelConfig:
    return dataclasses.replace(
        FAMILY.config_from_hf(arch), dtype="float32", **over)


CFG = _cfg()


def _weights(cfg, seed=20260929):
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


def _ref_logprobs(params, tokens, arch=ARCH, window="config"):
    pad = -(-len(tokens) // 64) * 64
    lp, _ = ref.next_token_logprobs(
        params, arch, list(tokens), "float32", pad, window=window)
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
# (i) the family and its tree
# ------------------------------------------------------------------ #

def test_family_reads_the_published_config_key_for_key():
    """Every key of the catalog row builds the model: writing the model's
    config back gives the row."""
    cfg = FAMILY.config_from_hf(PUBLISHED)
    back = FAMILY.config_to_hf(cfg)
    assert {k: back[k] for k in PUBLISHED} == PUBLISHED
    assert (cfg.n_layers, cfg.period, cfg.n_periods) == (52, 4, 13)
    assert cfg.layer_kinds == (
        (None, False), (4096, True), (4096, True), (4096, True))
    assert (cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim) == (28, 4, 128)
    moe = cfg.moe
    assert (moe.num_experts, moe.top_k, cfg.expert_dim) == (64, 6, 768)
    assert moe.router_on_layer_input and moe.norm_topk_prob
    assert moe.scoring == "softmax" and moe.n_shared_experts == 0
    assert cfg.activation_function == "relu" and cfg.mlp_type == "moe"
    assert cfg.n_dense_layers == 0 and not cfg.tied_embedding
    assert (cfg.rotary_base, cfg.n_positions) == (1500000, 16384)
    assert not cfg.use_attention_bias and not cfg.qk_layernorm


def test_benchmark_config_is_the_published_one_cut_in_depth():
    with open(os.path.join(
            ROOT, "benchmark", "configs", "smallthinker-21b-l8.json")) as f:
        arch = json.load(f)
    cut = dict(PUBLISHED, num_hidden_layers=8,
               rope_layout=[0, 1, 1, 1] * 2,
               sliding_window_layout=[0, 1, 1, 1] * 2)
    assert {k: arch[k] for k in PUBLISHED} == cut
    assert arch["reduced"] == [
        "num_hidden_layers", "rope_layout", "sliding_window_layout"]
    assert arch["reduced_from"]["num_hidden_layers"] == 52
    assert arch["reduced_from"]["rope_layout"] == PUBLISHED["rope_layout"]
    cfg = FAMILY.config_from_hf(arch)
    assert (cfg.n_layers, cfg.period, cfg.n_periods) == (8, 4, 2)
    # the issue's arithmetic: 3,966,937,600 parameters with the norms
    norms = 8 * 2 * 2560 + 2560
    assert flops_mod.param_count(cfg) + norms == 3_966_937_600


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"type": "yarn", "factor": 4.0}),
    ("sliding_window_layout", [0, 1, 1]),
    ("rope_layout", [0, 1, 1, 1]),
    ("sliding_window_layout", [0, 1, 2, 1] * 2),
    ("moe_primary_router_apply_softmax", False),
    ("norm_topk_prob", False),
])
def test_family_refuses_what_it_does_not_implement(key, value):
    with pytest.raises(ValueError, match=key.split("_layout")[0][:12]):
        FAMILY.config_from_hf(dict(ARCH, **{key: value}))


@pytest.mark.parametrize("layout,period", [
    ([0, 1, 1, 1] * 2, 4), ([0, 1] * 4, 2), ([1] * 8, 1),
    ([0, 0, 1, 1, 1, 1, 1, 1], 8),
])
def test_period_is_found_from_the_layout(layout, period):
    cfg = _cfg(dict(ARCH, sliding_window_layout=layout, rope_layout=layout))
    assert cfg.period == period
    assert [w is not None for w, _ in cfg.layer_kinds] == [
        bool(v) for v in layout[:period]]
    back = FAMILY.config_to_hf(cfg)
    assert back["sliding_window_layout"] == layout
    assert back["rope_layout"] == layout


def test_hf_names_round_trip(tmp_path):
    """Through disk, under the published names."""
    p = jax.tree.map(np.asarray, _weights(CFG, 5))
    hf_conv.save_hf_checkpoint(p, CFG, "smallthinker", str(tmp_path))
    from safetensors.numpy import load_file

    sd = load_file(str(tmp_path / "model.safetensors"))
    for name in (
        "model.layers.0.self_attn.q_proj.weight",
        "model.layers.3.self_attn.o_proj.weight",
        "model.layers.1.block_sparse_moe.primary_router.weight",
        "model.layers.7.block_sparse_moe.experts.7.gate.weight",
        "model.layers.2.block_sparse_moe.experts.0.up.weight",
        "model.layers.2.block_sparse_moe.experts.0.down.weight",
        "model.layers.4.input_layernorm.weight",
        "model.layers.4.post_attention_layernorm.weight",
        "model.norm.weight", "lm_head.weight",
    ):
        assert name in sd, name
    assert sd["model.layers.1.block_sparse_moe.primary_router.weight"].shape == (8, 64)
    assert sd["model.layers.2.block_sparse_moe.experts.0.down.weight"].shape == (64, 32)
    cfg2, p2 = hf_conv.load_hf_checkpoint(str(tmp_path))
    assert dataclasses.replace(cfg2, dtype="float32") == CFG
    jax.tree.map(np.testing.assert_array_equal, p, p2)


# ------------------------------------------------------------------ #
# (ii) forward, routing, what is static about a layer kind
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("n", [5, 20, 40])
def test_forward_matches_reference(params, rng, n):
    """Three lengths, of which two pass the window of 8."""
    ids = _toks(rng, n)
    np.testing.assert_allclose(
        _forward_logprobs(CFG, params, ids), _ref_logprobs(params, ids),
        atol=TOL_NATS)


def test_forgetting_the_window_is_seen(params, rng):
    """The benchmark's second control at test size: the reference with
    every layer FULL is far from the model's own past the window, and the
    same before it."""
    ids = _toks(rng, 40)
    own, full = (_ref_logprobs(params, ids, window=w) for w in ("config", None))
    np.testing.assert_allclose(own[: WINDOW - 1], full[: WINDOW - 1], atol=1e-6)
    assert np.abs(own[WINDOW:] - full[WINDOW:]).mean() > 1e-2


def test_routing_matches_reference(params, rng):
    ids = _toks(rng, 24)
    with jax.default_matmul_precision("highest"):
        _, chosen = tfm.forward_packed(
            params, CFG, jnp.asarray(ids, jnp.int32),
            jnp.ones((24,), jnp.int32), jnp.arange(24), with_routing=True)
    want, _ = ref.routing(params, ARCH, ids)
    assert chosen.shape == (8, 24, 2)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(want))


def test_top_k_then_softmax_is_softmax_top_k_renormalised(rng):
    """The published order (top logits, then their softmax) and the
    program's (softmax over all, top-k, renormalised): the same sets and
    the same weights to 1e-6."""
    h = jnp.asarray(rng.normal(0, 1, (50, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(0, 0.3, (64, 8)), jnp.float32)
    w_ref, idx_ref = ref._route(h, router, 2)
    vals, idx, _, _ = moe_ops._route(CFG, router, h)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(w_ref), atol=1e-6)
    np.testing.assert_allclose(np.asarray(vals).sum(-1), 1.0, atol=1e-6)


def test_router_reads_the_tensor_it_is_handed(params, rng):
    """``moe_mlp`` routes on ``router_input`` and feeds the experts ``x``;
    the family's model may not call it without, nor another's with."""
    lp = jax.tree.map(lambda a: a[0], params["layers"]["mlp"])
    x = jnp.asarray(rng.normal(0, 1, (12, 64)), jnp.float32)
    r = jnp.asarray(rng.normal(0, 1, (12, 64)), jnp.float32)
    out, _, idx = moe_ops.moe_mlp(CFG, lp, x, router_input=r)
    _, idx_r = ref._route(r, lp["router"], 2)
    _, idx_x = ref._route(x, lp["router"], 2)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_r))
    assert (np.asarray(idx_r) != np.asarray(idx_x)).any()
    w, _ = ref._route(r, lp["router"], 2)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref._experts(x, lp, w, idx_r, jnp.float32)),
        atol=1e-5)
    with pytest.raises(ValueError, match="router_input"):
        moe_ops.moe_mlp(CFG, lp, x)
    plain = dataclasses.replace(CFG, moe=dataclasses.replace(
        CFG.moe, router_on_layer_input=False))
    with pytest.raises(ValueError, match="router_input"):
        moe_ops.moe_mlp(plain, lp, x, router_input=r)


def test_router_is_fed_the_normed_layer_input_not_the_mlp_input(params, rng):
    """With the input norm's gain of layer 2 at zero, the layer's normed
    INPUT is zero: the router's logits are all equal and every token takes
    experts 0 and 1, while the attention adds nothing and the experts'
    own input (the normed residual) is as alive as ever. A router fed
    that tensor would choose by it."""
    dead = jax.tree.map(lambda a: a, params)
    dead["layers"]["ln1"]["weight"] = (
        params["layers"]["ln1"]["weight"].at[2].set(0.0))
    ids = _toks(rng, 16)
    with jax.default_matmul_precision("highest"):
        _, chosen = tfm.forward_packed(
            dead, CFG, jnp.asarray(ids, jnp.int32),
            jnp.ones((16,), jnp.int32), jnp.arange(16), with_routing=True)
        _, alive = tfm.forward_packed(
            params, CFG, jnp.asarray(ids, jnp.int32),
            jnp.ones((16,), jnp.int32), jnp.arange(16), with_routing=True)
    assert (np.asarray(chosen[2]) == np.asarray([0, 1])).all()
    assert not (np.asarray(alive[2]) == np.asarray([0, 1])).all()
    np.testing.assert_allclose(
        _forward_logprobs(CFG, dead, ids), _ref_logprobs(dead, ids),
        atol=TOL_NATS)


@pytest.mark.parametrize("rope", [0, 1])
def test_full_layers_carry_no_positions(rng, rope):
    """A full layer without rotary has no notion of order (one layer: in a
    deeper stack the causal mask itself tells positions apart): the last
    token's distribution does not change when the tokens before it are
    permuted. With rotary it does."""
    arch = dict(ARCH, num_hidden_layers=1, sliding_window_layout=[0],
                rope_layout=[rope])
    cfg = _cfg(arch)
    p = _weights(cfg, 3)
    ids = _toks(rng, 12)
    perm = ids[:11][::-1] + ids[11:]

    def last(seq):
        with jax.default_matmul_precision("highest"):
            return np.asarray(tfm.forward_packed(
                p, cfg, jnp.asarray(seq, jnp.int32),
                jnp.ones((12,), jnp.int32), jnp.arange(12))[-1])

    gap = np.abs(last(ids) - last(perm)).max()
    assert gap < 1e-5 if rope == 0 else gap > 1e-3


def test_dense_cache_prefill_and_decode_match_reference(params, rng):
    seq = _toks(rng, 30)
    cache = tfm.KVCache.empty(CFG, 1, 32)
    with jax.default_matmul_precision("highest"):
        logits, cache = tfm.prefill(
            params, CFG, cache, jnp.asarray([seq[:12]], jnp.int32),
            jnp.asarray([12]))
        got = [jax.nn.log_softmax(logits[0])[seq[12]]]
        for t in range(12, 29):
            logits, cache = tfm.decode_step(
                params, CFG, cache, jnp.asarray([seq[t]], jnp.int32))
            got.append(jax.nn.log_softmax(logits[0])[seq[t + 1]])
    np.testing.assert_allclose(
        np.asarray(got), _ref_logprobs(params, seq)[11:], atol=TOL_NATS)


def test_bfloat16_fails_the_float32_tolerance(params, rng):
    ids = _toks(rng, 40)
    low = _forward_logprobs(
        dataclasses.replace(CFG, dtype="bfloat16"), params, ids)
    assert np.abs(low - _ref_logprobs(params, ids)).max() > 10 * TOL_NATS


# ------------------------------------------------------------------ #
# (iii) the engine: one pool, a table a kind, pages released behind the
# window while the request runs
# ------------------------------------------------------------------ #

def _engine(params, cfg=CFG, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seqlen", 128)
    kw.setdefault("max_new_tokens_cap", 64)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("admit_buckets", (1, 2, 4))
    return GenerationEngine(cfg, params, **kw)


def _window_within_full(chunk: dict, eng) -> bool:
    """A window layer's call computes over no more positions than a full
    layer's, each counted in ITS program's grid steps (``block_plan``: 8
    pages a window step, 4 a full-attention step), so up to one step of 4
    pages a row lies between the two roundings."""
    return chunk["kernel_positions_window"] <= (
        chunk["kernel_positions_full"] + eng.B * 4 * eng.page)


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


def test_pool_has_a_period_axis_and_a_table_a_kind(params):
    eng = _engine(params, n_pages=96)
    # [periods, P, K|V, Hkv, page, D]: a page holds one position of the
    # period in both periods
    assert eng.state.cache.pages.shape == (2, 96, 2, 2, PAGE, 16)
    assert eng._tables_host.shape == (4, 4, 32)
    assert eng.kv_pool_bytes() == 2 * 96 * 2 * 2 * PAGE * 16 * 4
    one = 2 * 2 * 2 * 16 * 4        # a token in one position of the period
    assert eng.cache_bytes_per_token_by_kind() == {
        "full": one, "window": 3 * one}
    assert eng.cache_bytes_per_token() == 4 * one
    # a model of one kind: the same code with one position a period
    plain = _cfg(dict(ARCH, sliding_window_layout=[0] * 8, rope_layout=[1] * 8))
    eng1 = _engine(_weights(plain, 1), plain, n_pages=24)
    assert eng1.state.cache.pages.shape[0] == 8
    assert eng1._tables_host.shape == (1, 4, 32)
    assert eng1.cache_bytes_per_token_by_kind() == {"full": 8 * 2 * 2 * 16 * 4}


@pytest.mark.parametrize("use_pallas", [True, None],
                         ids=["interpret_kernel", "xla_gather"])
def test_engine_logprobs_match_reference_past_the_window(
        params, rng, use_pallas):
    """Prefill, then decode through the paged cache FAR past the window of
    8 (``paged_decode_window`` in interpret mode, and the XLA path): the
    served log-probs are the reference's full forward on prompt + output,
    while the window kinds' pages go back to the free list as the rows
    move on."""
    eng = _engine(params, n_pages=96)
    eng._decode_use_pallas = use_pallas
    prompts = {f"r{i}": _toks(rng, n) for i, n in enumerate((3, 9, 14))}
    tracing.drain()
    for rid, p in prompts.items():
        eng.submit(GenRequest(rid=rid, input_ids=p, max_new_tokens=30,
                              temperature=1.0))
    outs = {o.rid: o for o in eng.run_until_done(4)}
    _check_outputs(params, prompts, outs, 30)
    chunks = _chunk_attrs()
    assert sum(c["window_pages_released"] for c in chunks) > 0
    assert eng.stats["window_pages_released"] >= 3 * 3 * 5
    assert all(c["window_resident_tokens"] <= c["resident_tokens"]
               for c in chunks)
    assert any(c["window_resident_tokens"] < c["resident_tokens"]
               for c in chunks)
    assert all(c["cache_bytes_per_token_window"]
               == 3 * c["cache_bytes_per_token_full"] for c in chunks)
    assert all(("kernel_positions" in c) == bool(use_pallas) for c in chunks)
    if use_pallas:
        # a window layer's call computes over fewer positions than a full
        # layer's once rows pass the window
        assert all(_window_within_full(c, eng) for c in chunks)
    # nothing is held or promised once every request is done, but what the
    # prefix registry keeps of the prompts' whole pages
    kept = {p for b in (eng.prefix._children,) for n in _nodes(b)
            for p in n.page if p >= 0}
    assert eng.pool.reserved == 0
    assert eng.pool.n_free == 96 - len(kept)
    assert eng.pool.n_cached_only == len(kept)


def _nodes(children):
    out, stack = [], list(children.values())
    while stack:
        n = stack.pop()
        out.append(n)
        stack.extend(n.children.values())
    return out


@pytest.mark.parametrize("prefix_cache", [True, False])
def test_a_prompt_longer_than_the_window_is_admitted_in_chunks(
        params, rng, prefix_cache):
    """61 tokens of prompt through chunks of one page (4): a window kind
    takes 11 pages at admission (its claim: window 8 + look-ahead 32),
    gives them back as the chunks pass (counted on the admit span) and
    takes the later ones from its reservation; prefill + decode still
    equal the reference."""
    eng = _engine(params, n_pages=96, enable_prefix_cache=prefix_cache)
    assert eng._window_claim == [None, 11, 11, 11]
    prompts = {"long": _toks(rng, 61), "short": _toks(rng, 6)}
    tracing.drain()
    for rid, p in prompts.items():
        eng.submit(GenRequest(rid=rid, input_ids=p, max_new_tokens=10,
                              temperature=1.0))
    outs = {o.rid: o for o in eng.run_until_done(4)}
    _check_outputs(params, prompts, outs, 10)
    admits = [s["attrs"] for s in tracing.drain()
              if s["name"] == "gen_engine/admit"]
    assert sum(a.get("window_pages_released", 0) for a in admits) >= 3 * 12
    assert eng.pool.reserved == 0
    if not prefix_cache:
        assert eng.pool.n_free == 96


def test_prefix_hit_equals_cold_prefill_in_every_kind(params, rng):
    """The same 27-token prompt first cold, then as a prefix hit (six whole
    pages of every kind shared, the tail prefilled): greedy gives the same
    tokens, and sampled siblings' log-probs are the reference's."""
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
    for k in range(2):
        eng.submit(GenRequest(rid=f"s{k}", input_ids=prompt,
                              max_new_tokens=12, temperature=1.0))
    outs = {o.rid: o for o in eng.run_until_done(4)}
    assert eng.stats["prefix_hit_tokens"] == 3 * 24
    _check_outputs(params, {"s0": prompt, "s1": prompt}, outs, 12)
    # the registry files the page of every kind for a page of prompt
    (node,) = eng.prefix._children.values()
    assert len(node.page) == 4 and len(_nodes(eng.prefix._children)) == 6


def test_released_pages_are_reused_by_another_slot_and_nothing_changes(
        params, rng):
    """A pool too small for two requests of 44 positions in a one-kind
    pool (2 x 11 pages x 4 kinds = 88 > 72): the second is admitted only
    because a window kind reserves its window and not its whole output,
    and while both run, pages that one gave up behind its window turn up
    in the other's tables. Every log-prob is still the reference's."""
    eng = _engine(params, n_pages=72, max_slots=2, enable_prefix_cache=False)
    prompts = {"a": _toks(rng, 5), "b": _toks(rng, 7)}
    for rid, p in prompts.items():
        eng.submit(GenRequest(rid=rid, input_ids=p, max_new_tokens=38,
                              temperature=1.0))
    given_up, reused, outs = set(), set(), {}
    before = eng._tables_host[1:, :, :].copy()
    held = eng._held[1:].copy()
    while eng.n_running() or eng.n_pending():
        outs.update({o.rid: o for o in eng.step(4)})
        assert eng.pool.n_unpromised >= 0
        now, now_held = eng._tables_host[1:], eng._held[1:]
        given_up |= set(before[held & ~now_held].tolist())
        reused |= given_up & set(now[now_held & ~held].tolist())
        before, held = now.copy(), now_held.copy()
    assert eng.stats["admitted"] == 2 and len(reused) >= 3
    _check_outputs(params, prompts, outs, 38)
    assert eng.pool.n_free == 72 and eng.pool.reserved == 0


@pytest.mark.parametrize("how", ["pause", "update_params", "cancel"])
def test_free_list_returns_to_its_size(params, rng, how):
    """Mid-generation, past the window: ``pause`` / ``cancel`` release
    every kind's pages and what was reserved; ``update_params`` clears the
    registry of every kind's pages."""
    eng = _engine(params, n_pages=160)
    for i in range(3):
        eng.submit(GenRequest(rid=f"r{i}", input_ids=_toks(rng, 10 + i),
                              max_new_tokens=40, temperature=1.0))
    for _ in range(5):
        eng.step(4)
    assert eng.n_running() == 3 and eng.pool.reserved > 0
    assert eng.stats["window_pages_released"] > 0
    if how == "cancel":
        for i in range(3):
            assert eng.cancel(f"r{i}")
    else:
        outs = eng.pause()
        assert len(outs) == 3
        assert all(o.finish_reason == "interrupted" for o in outs)
    assert eng.pool.reserved == 0 and not eng._held.any()
    if how == "update_params":
        eng.update_params(eng.params)
        assert len(eng.prefix) == 0
    else:
        eng.prefix.clear()
    assert eng.pool.n_free == 160 and eng.pool.n_cached_only == 0
    assert (eng.pool._ref == 0).all()


def test_a_registry_held_page_outlives_the_slot_that_released_it(params, rng):
    """A 13-token prompt's three whole pages are the registry's too. The
    slot decodes past them and releases its references to the window
    kinds' pages; they stay resident (held by the registry alone), a
    sibling admitted later borrows them and matches the reference."""
    eng = _engine(params, n_pages=96)
    prompt = _toks(rng, 13)
    eng.submit(GenRequest(rid="first", input_ids=prompt, max_new_tokens=40,
                          temperature=1.0))
    for _ in range(6):
        eng.step(4)                      # 24 tokens on: well past 12 + 8
    (node,) = eng.prefix._children.values()
    full, *window = node.page
    assert eng.pool.refcount(full) == 2              # registry and slot
    assert [eng.pool.refcount(p) for p in window] == [1, 1, 1]
    assert not eng._held[1:, 0, :3].any() and eng._held[0, 0, :3].all()
    assert eng.pool.n_cached_only == 3 * 3
    eng.submit(GenRequest(rid="sibling", input_ids=prompt, max_new_tokens=9,
                          temperature=1.0))
    outs = {o.rid: o for o in eng.run_until_done(4)}
    assert eng.stats["prefix_hit_tokens"] == 12
    _check_outputs(params, {"sibling": prompt}, {"sibling": outs["sibling"]}, 9)
    _check_outputs(params, {"first": prompt}, {"first": outs["first"]}, 40)


def test_registry_gives_back_window_pages_a_slot_moved_past(params, rng):
    """Under pressure the registry first drops the window kinds' pages of
    nodes whose full page is still borrowed (a running slot has moved past
    them); a later hit on that prompt is cut to what a borrower can use."""
    eng = _engine(params, n_pages=96)
    prompt = _toks(rng, 13)
    eng.submit(GenRequest(rid="first", input_ids=prompt, max_new_tokens=40,
                          temperature=1.0))
    for _ in range(6):
        eng.step(4)
    free0 = eng.pool.n_free
    assert eng.prefix.evict_lru(free0 + 9) == 9
    assert eng.pool.n_free == free0 + 9 and eng.pool.n_cached_only == 0
    assert all(n.page[1:] == [-1, -1, -1] and n.page[0] >= 0
               for n in _nodes(eng.prefix._children))
    # the sibling can use none of the three pages now: a cold prefill,
    # which also brings the window kinds' pages home
    eng.submit(GenRequest(rid="sibling", input_ids=prompt, max_new_tokens=9,
                          temperature=1.0))
    outs = {o.rid: o for o in eng.run_until_done(4)}
    assert eng.stats["prefix_hit_tokens"] == 0
    assert all(min(n.page) >= 0 for n in _nodes(eng.prefix._children))
    _check_outputs(params, {"sibling": prompt}, {"sibling": outs["sibling"]}, 9)


def test_registry_cuts_a_hit_to_what_a_borrower_can_use():
    """``PrefixRegistry`` alone, kinds (full, window 8) at pages of 4: a
    borrower that starts at page ``m`` reads the window kind back to
    position ``4 m - 7``, i.e. pages ``m - 2`` and ``m - 1``."""
    pool = PagePool(40, 4)
    reg = PrefixRegistry(pool, (None, 8))
    ids = list(range(100, 120))
    pages = [pool.alloc(2) for _ in range(5)]
    reg.insert(ids, pages)
    pool.release([p for page in pages for p in page])
    assert pool.n_cached_only == 10
    hit = reg.lookup(ids, 5)
    assert hit == pages and pool.n_cached_only == 0
    pool.release([p for page in hit for p in page])
    node = reg._children[tuple(ids[:4])]
    third = node.children[tuple(ids[4:8])].children[tuple(ids[8:12])]
    reg._drop([third.page[1]])
    third.page[1] = -1
    # hits of 5 pages need window pages 3, 4: fine; of 4 or 3 pages would
    # need page 2: cut to 2
    assert len(reg.lookup(ids, 5)) == 5
    assert len(reg.lookup(ids, 4)) == 2
    assert len(reg.lookup(ids, 3)) == 2
    # the full kind's page of the hollow node is still handed out
    hit = reg.lookup(ids, 5)
    assert hit[2][0] == pages[2][0] and hit[2][1] == -1


def test_reservation_is_backed_while_a_small_pool_is_busy(params, rng):
    """Twelve requests through three slots and a pool that cannot hold
    them at once, prompts shared in groups of four: some wait, none fails,
    ``n_unpromised`` never goes negative, every log-prob is right."""
    eng = _engine(params, n_pages=112, max_slots=3)
    shared = [_toks(rng, 9) for _ in range(3)]
    prompts = {f"r{i}": shared[i // 4] for i in range(12)}
    for rid, p in prompts.items():
        eng.submit(GenRequest(rid=rid, input_ids=p, max_new_tokens=25,
                              temperature=1.0))
    outs, waited = {}, 0
    while eng.n_running() or eng.n_pending():
        outs.update({o.rid: o for o in eng.step(4)})
        waited = max(waited, eng.n_pending())
        assert eng.pool.n_unpromised >= 0
        assert 0.0 <= eng.kv_pool_occupancy() <= 1.0
    assert waited > 0 and eng.stats["prefix_hits"] >= 6
    _check_outputs(params, prompts, outs, 25)


def test_pipelined_chunks_release_nothing_that_is_live(params, rng):
    """Pipelined mode: the host's lengths lag one chunk, so the release
    rule works from a lower bound."""
    eng = _engine(params, n_pages=96, pipeline_chunks=True)
    prompts = {f"r{i}": _toks(rng, n) for i, n in enumerate((4, 11))}
    for rid, p in prompts.items():
        eng.submit(GenRequest(rid=rid, input_ids=p, max_new_tokens=30,
                              temperature=1.0))
    outs = {o.rid: o for o in eng.run_until_done(4)}
    _check_outputs(params, prompts, outs, 30)
    assert eng.stats["window_pages_released"] > 0


def test_one_kind_with_a_window_is_the_same_code(rng):
    """A model whose layers all have one window (a period of one): one
    table, and the same release behind the window while the request runs."""
    arch = dict(ARCH, num_hidden_layers=2, sliding_window_layout=[1, 1],
                rope_layout=[1, 1])
    cfg = _cfg(arch)
    p = _weights(cfg, 4)
    eng = _engine(p, cfg, n_pages=64, enable_prefix_cache=False)
    assert eng._tables_host.shape[0] == 1
    prompts = {"a": _toks(rng, 21), "b": _toks(rng, 5)}
    for rid, ids in prompts.items():
        eng.submit(GenRequest(rid=rid, input_ids=ids, max_new_tokens=22,
                              temperature=1.0))
    outs = {o.rid: o for o in eng.run_until_done(4)}
    for rid, ids in prompts.items():
        want = _ref_logprobs(p, ids + outs[rid].output_ids, arch)[len(ids) - 1:]
        np.testing.assert_allclose(
            np.asarray(outs[rid].output_logprobs), want, atol=TOL_NATS)
    assert eng.stats["window_pages_released"] > 0
    assert eng.pool.n_free == 64 and eng.pool.reserved == 0


@pytest.mark.parametrize("layout,shares", [
    ([0, 1, 1, 1] * 2, True), ([0, 0, 1, 1] * 2, False),
    ([0, 1, 0, 1] * 2, True),
], ids=["one_full_kind", "two_full_kinds", "one_full_kind_period_2"])
def test_a_group_s_shared_pages_with_layer_kinds(rng, layout, shares):
    """A group on one prompt through the kernels (interpret mode), prefix
    cache on: the members' full-kind tables name the prompt's whole pages.
    With ONE full kind in the period those go through the prefix program
    (``shared_prefix_applies``); with two, each with a table and pages of
    its own, the step observes neither and every layer keeps the call it
    had. Either way the served log-probs are the reference's."""
    arch = dict(ARCH, sliding_window_layout=layout, rope_layout=layout)
    cfg = _cfg(arch)
    assert sum(w is None for w, _ in cfg.layer_kinds) == 1 + (not shares)
    p = _weights(cfg, 5)
    eng = _engine(p, cfg, n_pages=96)
    eng._decode_use_pallas = True
    prompt = _toks(rng, 14)
    prompts = {f"g{i}": prompt for i in range(3)}
    prompts["alone"] = _toks(rng, 6)
    tracing.drain()
    # the first member fills the registry, its siblings hit it
    eng.submit(GenRequest(rid="g0", input_ids=prompt, max_new_tokens=12,
                          temperature=1.0))
    outs = {o.rid: o for o in eng.run_until_done(4)}
    for rid, ids in list(prompts.items())[1:]:
        eng.submit(GenRequest(rid=rid, input_ids=ids, max_new_tokens=12,
                              temperature=1.0))
    outs.update((o.rid, o) for o in eng.run_until_done(4))
    for rid, ids in prompts.items():
        want = _ref_logprobs(p, ids + outs[rid].output_ids, arch)[len(ids) - 1:]
        np.testing.assert_allclose(
            np.asarray(outs[rid].output_logprobs), want, atol=TOL_NATS,
            err_msg=rid)
    chunks = _chunk_attrs()
    assert any(c["kv_shared_rows"] for c in chunks) == shares
    assert all((c["kv_pages_read"] < c["kv_pages_named"])
               == bool(c["kv_shared_rows"]) for c in chunks)
    assert all(_window_within_full(c, eng) for c in chunks)


# ------------------------------------------------------------------ #
# (iv) the kernels and the model's paged entry points, straight
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("lens", [
    [0, 3, 7, 8, 9, 12, 17, 40], [33, 35, 36, 38, 40, 44, 45, 47],
    [0, 0, 0, 0, 64, 64, 64, 64],
], ids=["mixed", "all_past_a_grid_step", "free_and_full"])
def test_window_kernel_reads_from_the_first_visible_position(rng, lens):
    """``paged_decode_window`` (interpret mode) against the XLA path and
    against dense attention over the last ``window`` positions, with the
    table entries BEFORE each row's first visible page pointing at a page
    of NaNs: a kernel that copied or walked them would say so."""
    B, M, page, W = 8, 16, 4, 8
    H, Hkv, D = 4, 2, 16
    lens = np.asarray(lens, np.int32)
    P = 1 + B * M
    pool = rng.normal(0, 1, (2, P, 2, Hkv, page, D)).astype(np.float32)
    pool[:, 0] = np.nan
    table = (1 + np.arange(B * M, dtype=np.int32)).reshape(B, M)
    first = np.maximum(lens + 1 - W, 0)
    for b in range(B):
        table[b, : first[b] // page] = 0        # given back: stale entries
    q = jnp.asarray(rng.normal(0, 1, (B, H, D)), jnp.float32)
    ks = jnp.asarray(rng.normal(0, 1, (B, Hkv, D)), jnp.float32)
    vs = jnp.asarray(rng.normal(0, 1, (B, Hkv, D)), jnp.float32)
    args = (q, ks, vs, jnp.asarray(pool), jnp.int32(1), jnp.asarray(table),
            jnp.asarray(lens))
    with jax.default_matmul_precision("highest"):
        got = {
            use: np.asarray(paged_ops.paged_decode_attention(
                *args, sliding_window=W, use_pallas=use))
            for use in (True, False)
        }
    want = np.zeros((B, H, D), np.float32)
    for b in range(B):
        pos = np.arange(first[b], lens[b])
        k = np.concatenate([
            pool[1, table[b, pos // page], 0, :, pos % page], ks[b][None]])
        v = np.concatenate([
            pool[1, table[b, pos // page], 1, :, pos % page], vs[b][None]])
        for h in range(H):
            s = k[:, h // 2] @ np.asarray(q[b, h]) * D ** -0.5
            pr = np.exp(s - s.max())
            want[b, h] = (pr / pr.sum()) @ v[:, h // 2]
    for use in (True, False):
        assert np.isfinite(got[use]).all(), use
        np.testing.assert_allclose(got[use], want, atol=2e-5, err_msg=str(use))
    # the census the engine keeps, from the kernel's own plan: steps that
    # end before a block's least first position are not reached
    sb, span = 4, 8 * page
    full = pl_paged.kernel_positions(np.sort(lens), sb, span)
    win = pl_paged.kernel_positions(
        np.sort(lens), sb, span, pl_paged.first_visible(np.sort(lens), W))
    assert win <= full
    if lens.min() >= 33:
        assert win == full - sb * span      # the longer block skips one


def test_extend_and_decode_across_the_window_s_edge(
        params, rng, decode_tokens_paged):
    """``extend_paged`` in chunks of 6 (so that chunks straddle pages and
    the window's edge), then four decode steps, straight on a pool with a
    table a kind, the window kinds' entries behind each chunk's first
    visible position pointing at a page of NaNs as the chunks pass: the
    steps' logits are the reference's."""
    seq = _toks(rng, 34)
    cache = tfm.PagedKVCache.empty(CFG, 60, PAGE)
    assert cache.pages.shape == (2, 60, 2, 2, PAGE, 16)
    cache = tfm.PagedKVCache(pages=cache.pages.at[:, 0].set(jnp.nan))
    # row 1 holds the sequence; kind j's pages are 1 + 12 j + i
    own = 1 + 12 * np.arange(4)[:, None] + np.arange(12)[None, :]
    n_done = 0
    with jax.default_matmul_precision("highest"):
        while n_done < 30:
            table = np.zeros((4, 2, 12), np.int32)
            table[:, 1] = own
            table[1:, 1, : max(n_done + 1 - WINDOW, 0) // PAGE] = 0
            toks = jnp.zeros((2, 6), jnp.int32).at[1].set(
                jnp.asarray(seq[n_done:n_done + 6]))
            cache = tfm.extend_paged(
                params, CFG, cache, toks, jnp.asarray(table),
                jnp.asarray([0, n_done]), jnp.asarray([0, 6]),
                skip_pool=n_done == 0)
            n_done += 6
        table = np.zeros((4, 2, 12), np.int32)
        table[:, 1] = own
        table[1:, 1, : (30 + 1 - WINDOW) // PAGE] = 0
        chunk = jnp.zeros((2, 4), jnp.int32).at[1].set(jnp.asarray(seq[30:]))
        logits, cache2 = decode_tokens_paged(
            params, CFG, cache, chunk, jnp.asarray(table), [0, 30], [0, 4])
    lp = jax.nn.log_softmax(logits[1], axis=-1)
    got = np.asarray(lp[np.arange(3), np.asarray(seq[31:])])
    np.testing.assert_allclose(
        got, _ref_logprobs(params, seq)[30:], atol=TOL_NATS)
    # the steps' K/V landed in every kind's own page of positions 30..33
    # (pages 7 and 8 of the row) and in no other kind's
    for j in range(4):
        assert float(jnp.abs(cache2.pages[:, own[j, 8], :, :, :2]).min()) > 0
        assert float(jnp.abs(cache2.pages[:, own[j, 8], :, :, 2:]).max()) == 0
        assert float(jnp.abs(cache2.pages[:, own[j, 9]]).max()) == 0


# ------------------------------------------------------------------ #
# (v) the trainer
# ------------------------------------------------------------------ #

def _train_engine(params):
    eng = TrainEngine(CFG, ParallelConfig(), OptimizerConfig())
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


def test_trainer_gradients_match_reference(params, ppo_case):
    """``train_step`` under plain SGD of rate 1 moves every weight by minus
    its gradient, so (before - after) IS the trainer's gradient, through
    its real jitted step (one scan over the periods, remat). The expected
    gradient is ``jax.grad`` of the same PPO actor loss built on the
    REFERENCE's log-probs: the router through the combine weights (fed by
    the input norm, whose gain so gets a second path), both kinds of
    layer."""
    import optax

    seqs, prompt_lens, sample = ppo_case
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

    g_ref = jax.jit(jax.grad(reference_loss))(params)
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(g_prog), jax.tree.leaves(g_ref)):
        b = np.asarray(b)
        name = jax.tree_util.keystr(path)
        scale = float(np.abs(b).max())
        assert scale > 0, name
        # relative to the leaf's largest entry; the trainer's gradient is a
        # DIFFERENCE of float32 weights, so it carries their rounding
        # (1.2e-7 of a gain near 1, against gradients of 1e-4)
        np.testing.assert_allclose(
            a, b, atol=3e-3 * scale + 3e-7, err_msg=name)


# ------------------------------------------------------------------ #
# (vi) the benchmark's check of the cell
# ------------------------------------------------------------------ #

@pytest.mark.parametrize(
    "case", ["sound", "too_few_long", "forgot_the_window", "low_precision"])
def test_benchmark_check_takes_long_sequences_and_two_controls(
        params, rng, case):
    """The hybrid driver's check (``rollout_hybrid_inproc._check``): short
    and long sequences judged apart, a run with fewer long ones than asked
    is not correct, and two stand-ins in the program's place have to be
    refused: the reference in 8 bits, and the reference with every layer
    full on the long sequences. Handing the full-attention log-probs in as
    the PROGRAM's fails the run too (by the limit on a sequence's mean)."""
    from benchmark.drivers import rollout_hybrid_inproc as drv

    chk = {"seq_mean_abs_diff_limit_nats": 0.01, "n_long": 1,
           "long_min_tokens": 30, "long_max_tokens": 64,
           "control_dtype": "float8_e5m2"}
    arch = dict(ARCH, reference="smallthinker")

    def sample(n, start, window="config"):
        toks = _toks(rng, n)
        lp = _ref_logprobs(params, toks, window=window)
        return {"tokens": toks, "start": start, "logprobs": lp[start - 1:]}

    short = [sample(7, 3), sample(8, 4)]        # inside the window of 8
    long_ = [sample(48, 20, None if case == "forgot_the_window" else "config")]
    if case == "too_few_long":
        long_ = []
    if case == "low_precision":
        got = drv._control(params, arch, "float32", short + long_, chk)
        assert got["correct"] is False
        return
    got = drv._check(params, arch, "float32", short, long_, chk)
    assert got["correct"] is (case == "sound"), got
    assert got["n_long_sequences"] == len(long_)
    if case == "sound":
        assert got["control"]["correct"] is False
        assert got["control_full_attention"]["correct"] is False
        assert min(got["control_full_attention"]["seq_mean_abs_diff_nats"]) > (
            10 * max(got["long"]["seq_mean_abs_diff_nats"] + [1e-6]))
    elif case == "too_few_long":
        assert "0 sequences" in got["reason"]
    else:
        assert "long sequences" in got["reason"]


def test_benchmark_bytes_of_a_cache_with_layer_kinds():
    """``benchmark/hybrid_flops.py`` against the program at the cell's
    configuration: the bytes of a page, the bytes a token by kind, what a
    decode step must read."""
    from benchmark import hybrid_flops, sut

    with open(os.path.join(
            ROOT, "benchmark", "configs", "smallthinker-21b-l8.json")) as f:
        arch = json.load(f)
    assert hybrid_flops.period(arch) == 4
    assert hybrid_flops.kv_bytes_per_token_by_kind(arch) == {
        "full": 4096, "window": 12288}
    assert hybrid_flops.page_bytes(arch, 128) == 524288
    assert hybrid_flops.decode_step_bytes(arch, [100, 5000]) == (
        4096 * 5100 + 12288 * (100 + 4096))
    assert hybrid_flops.primary_expert_bytes(arch) == 3 * 2560 * 768 * 2
    cfg = sut.model_config(arch, {})
    streams, heads, width = tfm.kv_page_geometry(cfg)
    assert cfg.n_periods * 128 * streams * heads * width * 2 == 524288
    rx = hybrid_flops.primary_op_pattern(arch, "jit_chunk")
    assert rx.search("jit_chunk/%fusion.605 fusion f32[112],bf16[112,2560] "
                     "<- bf16[8,64,768,2560]")
    assert not rx.search("jit_chunk/%while.3 while (s32[]) <- bf16[8,64,768,2560]")
    assert not rx.search("jit_chunk/%fusion.1 fusion bf16[112,2560] "
                         "<- bf16[8,2560,3584]")


def test_engine_serves_the_same_tokens_through_the_grouped_kernel(
        params, rng, check_moe_grouped_serves_the_same):
    """The routed experts on the einsums and on ``moe_grouped``
    (a router on the layer's input, ReGLU experts, layer kinds:
    the index in the stack comes from the period): the same
    greedy tokens, and the two counters add up (``conftest.py``)."""
    prompts = [[int(x) for x in rng.integers(1, 128, n)] for n in (5, 19, 33)]
    check_moe_grouped_serves_the_same(lambda: _engine(params), prompts)
