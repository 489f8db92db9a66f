"""OLMoE (family ``olmoe``) against its plain reference, end to end.

A tiny OLMoE (2 layers, hidden 64, 4 heads x 16, 8 experts of width 32,
2 a token, full-width q/k norm, no renormalised top-k) with seeded random
weights, float32 everywhere. The reference is the benchmark's
(``benchmark/reference/olmoe.py``): plain ``jax.numpy``, every expert for
every token in a loop, none of the program's model code.

Tolerance: 1e-4 nats on log-probabilities. Both sides compute in float32
on the CPU, so no rounding difference can flip a top-k choice (the gap
between the 2nd and 3rd router probability is ~1e-2 here); what is left
is summation order (a paged cache in pages of 8, a head in one block
against blocks), about 1e-6. A wrong norm, a renormalised top-k, a
dropped expert or a wrong cache position moves a log-probability by
1e-2 to 1 nat.
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import engine_contract
from areal_tpu.api.data import MicroBatchSpec, SequenceSample
from areal_tpu.base import tracing
from areal_tpu.gen.engine import GenerationEngine, GenRequest
from areal_tpu.api.model import PPOHyperparameters
from areal_tpu.interfaces.ppo import PPOActorInterface
from areal_tpu.ops import ppo as ppo_ops
from areal_tpu.models import hf as hf_conv
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig
from areal_tpu.parallel.mesh import ParallelConfig
from areal_tpu.train.engine import OptimizerConfig, TrainEngine
from benchmark import weights as bench_weights
from benchmark.reference import olmoe as ref

TOL_NATS = 1e-4

ARCH = dict(
    model_type="olmoe", hidden_size=64, intermediate_size=32,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
    vocab_size=128, num_experts=8, num_experts_per_tok=2,
    norm_topk_prob=False, rms_norm_eps=1e-5, rope_theta=10000,
    max_position_embeddings=512, tie_word_embeddings=False,
    attention_bias=False, clip_qkv=None, hidden_act="silu",
    router_aux_loss_coef=0.0,
)


def _cfg(**over) -> ModelConfig:
    cfg = hf_conv.family_for_model_type("olmoe").config_from_hf(ARCH)
    return dataclasses.replace(cfg, dtype="float32", **over)


CFG = _cfg()


@pytest.fixture(scope="module")
def params():
    """Seeded weights with norm gains away from 1 (the benchmark's fill),
    so a norm over the wrong span or a missing gain shows."""
    shapes = jax.eval_shape(lambda: tfm.init_params(CFG, jax.random.key(0)))
    return bench_weights.make_weights(shapes, 20260927, jnp.float32)


@pytest.fixture()
def rng():
    return np.random.default_rng(7)


def _ref_logprobs(params, tokens):
    lp, _ = ref.next_token_logprobs(params, ARCH, list(tokens), "float32", 64)
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
# (i) forward, (iv) the two q/k norms, (v) HF names
# ------------------------------------------------------------------ #

def test_family_reads_the_published_config():
    cfg = hf_conv.family_for_model_type("olmoe").config_from_hf(dict(
        ARCH, hidden_size=2048, intermediate_size=1024, num_hidden_layers=16,
        num_attention_heads=16, num_key_value_heads=16, vocab_size=50304,
        num_experts=64, num_experts_per_tok=8, max_position_embeddings=4096))
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim) == (
        2048, 16, 16, 128)
    assert cfg.intermediate_dim == 1024 and cfg.mlp_type == "moe"
    assert (cfg.moe.num_experts, cfg.moe.top_k) == (64, 8)
    assert cfg.moe.norm_topk_prob is False
    assert cfg.qk_layernorm and cfg.qk_norm_over == "full"
    assert not cfg.tied_embedding and not cfg.use_attention_bias
    back = hf_conv.HF_FAMILIES["olmoe"].config_to_hf(cfg)
    for key in ("num_experts", "num_experts_per_tok", "norm_topk_prob",
                "intermediate_size", "hidden_size", "num_attention_heads",
                "num_key_value_heads", "vocab_size", "model_type"):
        assert back[key] == (ARCH | dict(
            hidden_size=2048, intermediate_size=1024, num_attention_heads=16,
            num_key_value_heads=16, vocab_size=50304, num_experts=64,
            num_experts_per_tok=8))[key], key
    with pytest.raises(ValueError, match="clip_qkv"):
        hf_conv.family_for_model_type("olmoe").config_from_hf(
            dict(ARCH, clip_qkv=8.0))


@pytest.mark.parametrize("n", [5, 40, 64])
def test_forward_matches_reference(params, rng, n):
    ids = rng.integers(1, 128, n)
    got = _forward_logprobs(CFG, params, ids)
    np.testing.assert_allclose(got, _ref_logprobs(params, ids), atol=TOL_NATS)


def test_routing_matches_reference(params, rng):
    ids = rng.integers(1, 128, 48)
    _, routing = tfm.forward_packed(
        params, CFG, jnp.asarray(ids, jnp.int32), jnp.ones((48,), jnp.int32),
        jnp.arange(48), with_routing=True)
    want = np.asarray(ref.routing(params, ARCH, ids))
    assert routing.shape == (2, 48, 2)
    np.testing.assert_array_equal(np.asarray(routing), want)


def test_full_width_norm_is_not_the_per_head_norm(params, rng):
    """The same weights under qwen3's per-head norm (gains cut to one
    head's width) give other log-probs: a wrong choice of norm fails the
    reference by far more than the tolerance."""
    ids = rng.integers(1, 128, 40)
    per_head = _cfg(qk_norm_over="head")
    p2 = jax.tree.map(lambda a: a, params)
    attn = dict(p2["layers"]["attn"])
    attn["q_norm"] = attn["q_norm"][:, : CFG.head_dim]
    attn["k_norm"] = attn["k_norm"][:, : CFG.head_dim]
    p2 = {**p2, "layers": {**p2["layers"], "attn": attn}}
    other = _forward_logprobs(per_head, p2, ids)
    want = _ref_logprobs(params, ids)
    assert np.abs(other - want).max() > 100 * TOL_NATS
    with pytest.raises(ValueError, match="qk_norm_over"):
        _cfg(qk_norm_over="rows")


def test_per_head_norm_is_what_it_was(rng):
    """qwen3's path: ``_qkv`` = projection, split into heads, RMSNorm over
    each head's D with ``[L, D]`` gains; bit-equal to that written out."""
    from areal_tpu.ops import norms

    cfg = hf_conv.family_for_model_type("qwen3").config_from_hf(dict(
        ARCH, model_type="qwen3", head_dim=16, rms_norm_eps=1e-6))
    assert cfg.qk_layernorm and cfg.qk_norm_over == "head"
    cfg = dataclasses.replace(cfg, dtype="float32")
    p = tfm.init_params(cfg, jax.random.key(3))
    assert p["layers"]["attn"]["q_norm"].shape == (2, 16)
    lp = jax.tree.map(lambda a: a[0], p["layers"]["attn"])
    lp["q_norm"] = jnp.asarray(rng.normal(1, 0.1, 16), jnp.float32)
    lp["k_norm"] = jnp.asarray(rng.normal(1, 0.1, 16), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (7, 64)), jnp.float32)
    q, k, v = tfm._qkv(cfg, lp, x)
    want_q = norms.rms_norm(
        (x @ lp["wq"]).reshape(7, 4, 16), lp["q_norm"], 1e-6)
    want_k = norms.rms_norm(
        (x @ lp["wk"]).reshape(7, 4, 16), lp["k_norm"], 1e-6)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(want_q))
    np.testing.assert_array_equal(np.asarray(k), np.asarray(want_k))
    np.testing.assert_array_equal(
        np.asarray(v), np.asarray((x @ lp["wv"]).reshape(7, 4, 16)))


def test_hf_names_round_trip(params, tmp_path):
    fam = hf_conv.HF_FAMILIES["olmoe"]
    host = hf_conv.jax_to_numpy(params)
    sd = fam.params_to_hf(host, CFG)
    for name, shape in {
        "model.layers.1.mlp.gate.weight": (8, 64),
        "model.layers.0.mlp.experts.7.gate_proj.weight": (32, 64),
        "model.layers.0.mlp.experts.7.up_proj.weight": (32, 64),
        "model.layers.0.mlp.experts.7.down_proj.weight": (64, 32),
        "model.layers.1.self_attn.q_norm.weight": (64,),
        "model.layers.1.self_attn.k_norm.weight": (64,),
        "lm_head.weight": (128, 64),
    }.items():
        assert sd[name].shape == shape, name
    assert not any("block_sparse_moe" in k for k in sd)
    back = fam.params_from_hf(sd, CFG)
    for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(back)):
        np.testing.assert_array_equal(a, b)
    # and through disk, the way a checkpoint travels
    hf_conv.save_hf_checkpoint(params, CFG, "olmoe", str(tmp_path))
    cfg2, p2 = hf_conv.load_hf_checkpoint(str(tmp_path))
    assert cfg2.qk_norm_over == "full" and cfg2.moe == CFG.moe
    for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(a, b)


def test_full_width_norm_under_tensor_parallelism(params, rng):
    """Heads sharded over ``model``: the full-width norm's mean of squares
    crosses the shards, and GSPMD completes it; same log-probs as on one
    device (a norm taken over each shard's half would be off by ~0.1)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    ids = rng.integers(1, 128, 40)
    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    axes = tfm.param_logical_axes(CFG)
    assert axes["layers"]["attn"]["q_norm"] == ("layer", "heads")

    def spec(ax):
        return NamedSharding(mesh, P(*[
            "model" if a in ("heads", "expert", "vocab") else None
            for a in ax]))

    sharded = jax.tree.map(
        lambda a, ax: jax.device_put(a, spec(ax)), params, axes,
        is_leaf=lambda x: isinstance(x, tuple))

    @jax.jit
    def fwd(p, ids):
        n = ids.shape[0]
        with jax.default_matmul_precision("highest"):
            logits = tfm.forward_packed(
                p, CFG, ids, jnp.ones((n,), jnp.int32), jnp.arange(n))
        return jax.nn.log_softmax(logits, axis=-1)

    lp = fwd(sharded, jnp.asarray(ids, jnp.int32))
    got = np.asarray(lp)[np.arange(39), ids[1:]]
    np.testing.assert_allclose(got, _ref_logprobs(params, ids), atol=TOL_NATS)


# ------------------------------------------------------------------ #
# (ii) the generation engine: prefill, prefix cache, paged decode
# ------------------------------------------------------------------ #

def _engine(params, **kw):
    return GenerationEngine(
        CFG, params, max_slots=4, max_seqlen=128, max_new_tokens_cap=32,
        page_size=8, enable_prefix_cache=True, seed=3, **kw)


@pytest.mark.parametrize("check", engine_contract.CHECKS)
def test_engine_contract(params, check):
    engine_contract.run(check, functools.partial(_engine, params), CFG, params)


@pytest.mark.parametrize("use_pallas", [True, None],
                         ids=["interpret_kernel", "xla_gather"])
def test_engine_logprobs_match_reference(params, rng, use_pallas):
    """Chunked prefill, a shared prefix served from the cache, then paged
    decode (the Pallas kernel in interpret mode at 4 kv heads = n_rep 1,
    and the XLA gather path): the served log-probs of sampled tokens are
    the reference's full forward on prompt + output."""
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
    # the census every chunk of an MoE model carries
    chunks = [s["attrs"] for s in tracing.drain()
              if s["name"] == "gen_engine/chunk" and "slots" in s["attrs"]]
    assert chunks and all(
        c["moe_expert_slots"] == c["steps"] * 2 * 8 for c in chunks)
    assert all(0 < c["moe_experts_hit"] <= c["moe_expert_slots"]
               for c in chunks)
    # 4 rows x 2 experts a token: one expert gets at most 4 tokens a layer
    assert all(1 <= c["moe_load_max"] <= 4 for c in chunks)
    assert eng.stats["moe_experts_hit"] == sum(
        c["moe_experts_hit"] for c in chunks)
    assert eng.stats["moe_expert_slots"] == sum(
        c["moe_expert_slots"] for c in chunks)
    assert eng.stats["moe_load_max"] == max(c["moe_load_max"] for c in chunks)


def test_engine_routing_record(params, rng):
    """``record_routing``: each output token's chosen experts are those
    the reference routes the token at ``prompt_len - 1 + i`` to."""
    eng = _engine(params, record_routing=True)
    prompt = [int(x) for x in rng.integers(1, 128, 19)]
    eng.submit(GenRequest(rid="a", input_ids=prompt, max_new_tokens=10,
                          temperature=1.0))
    (out,) = eng.run_until_done(4)
    assert out.output_routing.shape == (10, 2, 2)
    seq = prompt + out.output_ids
    want = np.asarray(ref.routing(params, ARCH, seq[:-1]))   # [L, T, K]
    np.testing.assert_array_equal(
        out.output_routing, want[:, len(prompt) - 1:].transpose(1, 0, 2))
    dense = dataclasses.replace(CFG, mlp_type="gated", moe=None)
    with pytest.raises(ValueError, match="no router"):
        GenerationEngine(dense, params, record_routing=True)


# ------------------------------------------------------------------ #
# (iii) the trainer, (vi) router agreement
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
    # behaviour log-probs a little off the policy's own, token-aligned
    # (position t holds log p(token t+1 | ..t), 0 at the last position)
    behav = [np.r_[_ref_logprobs(params, s), 0.0]
             + rng.normal(0, 0.05, len(s)) for s in seqs]
    return seqs, prompt_lens, behav, _ppo_sample(rng, seqs, prompt_lens, behav)


def test_trainer_inference_matches_reference(params, ppo_case):
    seqs, _, _, sample = ppo_case
    actor = PPOActorInterface(hp=PPOHyperparameters(disable_value=True))
    eng = _train_engine(params)
    out = actor.inference(eng, sample, MicroBatchSpec())
    got = np.asarray(out.data["prox_logp"])
    want = np.concatenate([np.r_[_ref_logprobs(params, s), 0.0] for s in seqs])
    np.testing.assert_allclose(got, want, atol=TOL_NATS)


def test_trainer_gradients_match_reference(params, ppo_case):
    """``train_step`` under plain SGD of rate 1 moves every weight by
    minus its gradient, so (before - after) IS the trainer's gradient,
    through its real jitted step (vmap over packed rows, remat, the
    chunk of the loss). The expected gradient is ``jax.grad`` of the same
    PPO actor loss (``ops.ppo.actor_loss_fn`` on the advantages the
    interface's model-free pre-pass made) built on the REFERENCE's
    log-probs: router, experts and full-width gains included."""
    import optax

    seqs, prompt_lens, _, sample = ppo_case
    hp = PPOHyperparameters(
        disable_value=True, ppo_n_minibatches=1, use_decoupled_loss=False,
        recompute_logprob=False)
    actor = PPOActorInterface(hp=hp)
    eng = _train_engine(params)
    eng.setup_optimizer(10)         # the schedule's host mirror; then SGD
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
    # position t is an action iff its label, token t+1, was generated
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
        scale = float(np.abs(b).max())
        assert scale > 0, jax.tree_util.keystr(path)
        # relative to the leaf's largest entry: 1e-3 covers float32
        # summation order through two layers and a 128-wide softmax; a
        # missing term (say the router's gradient through the combine
        # weights) is of order 1
        np.testing.assert_allclose(
            a / scale, b / scale, atol=1e-3,
            err_msg=jax.tree_util.keystr(path))


def test_router_agreement_counts(params, rng):
    """``ppo/inference`` counts the (token, layer) pairs whose chosen
    experts in the trainer's recompute are the set the engine chose, where
    the caller passes the engine's record. Same weights, same dtype: every
    pair agrees; a record with one expert swapped disagrees exactly there."""
    gen = _engine(params, record_routing=True)
    prompts = [[int(x) for x in rng.integers(1, 128, n)] for n in (11, 20)]
    for i, p in enumerate(prompts):
        gen.submit(GenRequest(rid=str(i), input_ids=p, max_new_tokens=9,
                              temperature=1.0))
    outs = {int(o.rid): o for o in gen.run_until_done(4)}
    seqs, routed, behav = [], [], []
    for i, p in enumerate(prompts):
        o = outs[i]
        seq = np.asarray(p + o.output_ids)
        r = np.full((len(seq), 2, 2), -1, np.int32)
        r[len(p) - 1: len(p) - 1 + len(o.output_ids)] = o.output_routing
        seqs.append(seq), routed.append(r)
        b = np.zeros(len(seq))
        b[len(p) - 1: len(seq) - 1] = o.output_logprobs
        behav.append(b)
    sample = _ppo_sample(rng, seqs, [len(p) for p in prompts], behav)
    sample.update_(SequenceSample.from_default(
        seqlens=[len(s) for s in seqs], ids=list(sample.ids),
        data={"routed_experts": np.concatenate(routed)}))
    actor = PPOActorInterface(hp=PPOHyperparameters(disable_value=True))
    eng = _train_engine(params)

    def counts(smp):
        tracing.drain()
        out = actor.inference(eng, smp, MicroBatchSpec())
        (span,) = [s for s in tracing.drain() if s["name"] == "ppo/inference"]
        return out, span["attrs"]

    out, attrs = counts(sample)
    assert attrs["router_total"] == 2 * 9 * 2      # 2 seqs x 9 tokens x 2 layers
    assert attrs["router_agree"] == attrs["router_total"]
    # the recompute's log-probs are the engine's behaviour log-probs
    served = np.concatenate(behav)
    np.testing.assert_allclose(
        np.asarray(out.data["prox_logp"])[served != 0], served[served != 0],
        atol=TOL_NATS)
    # swap one expert of one (token, layer) pair for one it did not choose
    r0 = routed[0].copy()
    t = len(prompts[0]) + 2
    r0[t, 1, 0] = next(x for x in range(8) if x not in r0[t, 1])
    sample.data["routed_experts"] = np.concatenate([r0, routed[1]])
    _, attrs = counts(sample)
    assert attrs["router_agree"] == attrs["router_total"] - 1
    # no record passed: nothing counted
    plain = _ppo_sample(rng, seqs, [len(p) for p in prompts], behav)
    _, attrs = counts(plain)
    assert "router_total" not in attrs


def test_engine_serves_the_same_tokens_through_the_grouped_kernel(
        params, rng, check_moe_grouped_serves_the_same):
    """The routed experts on the einsums and on ``moe_grouped``
    (softmax scores over 8 of the experts, a K/V pool): the same
    greedy tokens, and the two counters add up (``conftest.py``)."""
    prompts = [[int(x) for x in rng.integers(1, 128, n)] for n in (5, 19, 33)]
    check_moe_grouped_serves_the_same(lambda: _engine(params), prompts)
