"""Solar Open 2 (family ``solar_open2``) against its plain reference
(``benchmark/reference/solar_open2.py``) at a small size on the CPU:
gated delta-rule linear-attention layers 3:1 with gated attention without
positions, two-branch blocks whose second branch is an expert-parallel
rank's share of the experts in EVERY block.

What is held to the reference: the three forwards (packed, dense cache,
page pool: prefill in pieces, then decode) on seeded weights, in float32
and in bfloat16 with its tolerance; the two forms of the recurrence
(``ops/kda.py``: the step and the chunked form) to the reference's
token-by-token scan for lengths that are no multiple of the chunk, with
packed documents and ``A_log`` at both ends; that ``beta`` beyond 1 occurs
and matters; the EIGHT ranks' partial results, the shared expert counted
once, to the uncut reference's layer; the HF round trip, the weight names
and every refused key; the ``kda_decode`` kernel in interpret mode to the
plain step. The engine's side is ``tests/test_solar_open2_engine.py``.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import KDAConfig, ModelConfig
from areal_tpu.models.hf import family_for_model_type
from areal_tpu.ops import kda as kda_ops
from areal_tpu.ops import moe as moe_ops
from benchmark.reference import solar_open2 as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = family_for_model_type("solar_open2")
TOL = 2e-5
RANKS, HELD = 8, 2

ARCH = {
    "model_type": "solar_open2", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 128,
    "num_hidden_layers": 4, "gqa_interval": 3, "gqa_layers": [0],
    "linear_attn_config": {
        "short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4,
        "num_kv_heads": None},
    "intermediate_size": 48, "moe_intermediate_size": 32,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "partial_rotary_factor": 1,
    "tie_word_embeddings": False, "max_position_embeddings": 512,
    "first_k_dense_replace": 0, "use_rope": False, "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "n_routed_experts": HELD, "expert_parallel_size": RANKS,
    "expert_parallel_rank": 1, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 4,
}


def _cfg(arch=ARCH, **over):
    over = {"dtype": "float32", "use_flash_attention": False, **over}
    cfg = FAMILY.config_from_hf(arch)
    return dataclasses.replace(
        cfg, kda=dataclasses.replace(cfg.kda, chunk_size=8), **over)


CFG = _cfg()


def seeded_params(cfg, seed=59, dtype=jnp.float32):
    """``init_params`` with the matrices five times their init (at a hidden
    size of 64 normal(0, 0.02) leaves the logits flat), the convolutions off
    their init, and the router's correction bias not zero."""
    p = tfm.init_params(cfg, jax.random.key(seed))
    p = jax.tree.map(lambda a: a * 5 if a.ndim >= 3 else a, p)
    ks = jax.random.split(jax.random.key(seed + 1), 3)
    mixer = dict(p["kda_layers"]["kda"])
    mixer["conv_w"] = jax.random.uniform(
        ks[0], mixer["conv_w"].shape, minval=-0.5, maxval=0.5)
    out = {**p, "kda_layers": {**p["kda_layers"], "kda": mixer}}
    for n, tree in enumerate(("layers", "kda_layers")):
        mlp = dict(out[tree]["mlp"])
        mlp["b_router"] = 0.1 * jax.random.normal(
            ks[1 + n], mlp["b_router"].shape)
        out[tree] = {**out[tree], "mlp": mlp}
    return jax.tree.map(lambda a: a.astype(dtype), out)


@pytest.fixture(scope="module")
def params():
    return seeded_params(CFG)


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.key(3), (21,), 1, ARCH["vocab_size"])


@pytest.fixture(scope="module")
def want(params, ids):
    return np.asarray(ref.sequence_logits(params, ARCH, ids))


def _packed(params, cfg, ids, seg=None, pos=None, **kw):
    T = len(ids)
    seg = jnp.ones(T, jnp.int32) if seg is None else seg
    pos = jnp.arange(T) if pos is None else pos
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p: tfm.forward_packed(
            p, cfg, ids, seg, pos, remat=False, **kw))(params)


# --- (a) the three forwards against the reference's full forward ---------- #


def test_forward_packed_matches_the_reference(params, ids, want):
    got, routing = _packed(params, CFG, ids, with_routing=True)
    np.testing.assert_allclose(got, want, atol=TOL)
    # every layer holds a router, in the order the layers run
    assert routing.shape == (4, len(ids), ARCH["num_experts_per_tok"])
    np.testing.assert_array_equal(
        np.sort(routing, -1),
        np.sort(ref.routing(params, ARCH, list(map(int, ids)), "float32", 0),
                -1))


def test_bfloat16_forward_within_what_the_dtype_costs_the_reference(ids):
    """The program in bfloat16 is held to the float32 reference by what the
    serving dtype alone costs the REFERENCE on the same weights (twice its
    own bfloat16-to-float32 distance plus 0.02, ``benchmark/correct.py``'s
    rule): the state stays float32 in both, so what differs is rounding of
    activations, not of the recurrence."""
    cfg = _cfg(dtype="bfloat16")
    params = seeded_params(cfg, dtype=jnp.bfloat16)
    toks = list(map(int, ids))
    f32, _ = ref.next_token_logprobs(params, ARCH, toks, "float32", 0)
    low, _ = ref.next_token_logprobs(params, ARCH, toks, "bfloat16", 0)
    logits = jax.jit(lambda p: tfm.forward_packed(
        p, cfg, ids, jnp.ones(len(ids), jnp.int32), jnp.arange(len(ids)),
        remat=False))(params)
    got = jnp.take_along_axis(
        jax.nn.log_softmax(logits[:-1], -1), ids[1:, None], axis=-1)[:, 0]
    yard = float(np.abs(low - f32).max())
    assert float(np.abs(np.asarray(got) - f32).max()) <= 2 * yard + 0.02


def test_dense_prefill_then_decode_matches_the_reference(params, ids, want):
    n = 13
    cache = tfm.KVCache.empty(CFG, 1, 32)
    with jax.default_matmul_precision("highest"):
        lg, cache = tfm.prefill(
            params, CFG, cache, jnp.pad(ids[:n], (0, 3))[None],
            jnp.array([n]))
        np.testing.assert_allclose(lg[0], want[n - 1], atol=TOL)
        for t in range(n, len(ids)):
            lg, cache = tfm.decode_step(params, CFG, cache, ids[t][None])
            np.testing.assert_allclose(lg[0], want[t], atol=TOL)


@pytest.mark.parametrize("pieces", [(8, 5), (3, 8, 2)])
def test_paged_prefill_in_pieces_then_decode_matches_the_reference(
        params, ids, want, pieces):
    """Admission's chunked form continues a slot's state a piece at a time
    (pieces that are no multiple of the chunk of 8), then every decode step
    updates it through the page pool's forward."""
    page = 8
    cache = tfm.PagedKVCache.empty(CFG, 8, page)
    state = tfm.row_state_empty(CFG, 2)
    table = jnp.array([[0, 0, 0, 0], [1, 2, 3, 4]])
    start = 0
    with jax.default_matmul_precision("highest"):
        for n in pieces:
            toks = jnp.pad(ids[start : start + n], (0, page - n))[None]
            cache, state = tfm.extend_paged(
                params, CFG, cache, toks, table[1:], jnp.array([start]),
                jnp.array([n]), ssm=state, slots=jnp.array([1]))
            start += n
        lens, active = jnp.array([0, start]), jnp.array([False, True])
        for t in range(start, len(ids)):
            lg, cache, lens, routing, state = tfm.decode_step_paged(
                params, CFG, cache, jnp.array([0, ids[t]]), table, lens,
                active, use_pallas=False, with_routing=True, ssm=state)
            np.testing.assert_allclose(lg[1], want[t], atol=TOL)
            assert routing.shape == (4, 2, ARCH["num_experts_per_tok"])
    # the free slot's state stayed empty
    assert float(jnp.abs(state.s[:, 0]).max()) == 0.0


# --- (b) the recurrence's two forms against the token-by-token scan ------- #


def _recurrence_inputs(T, H=3, D=16, a_log=None, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    shape = (2, T, H, D)
    q = kda_ops._l2norm(jax.random.normal(ks[0], shape)) * D ** -0.5
    k = kda_ops._l2norm(jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    a = jnp.exp(jnp.full((H,), a_log)) if a_log is not None else (
        jax.random.uniform(ks[5], (H,), minval=1.0, maxval=16.0))
    g = -a[:, None] * jax.nn.softplus(jax.random.normal(ks[3], shape))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (2, T, H)))
    return q, k, v, g, beta


def _token_by_token(q, k, v, g, beta, reset, init):
    s, outs = init, []
    for t in range(q.shape[1]):
        s = jnp.where(reset[:, t][:, None, None, None], 0.0, s)
        o, s = kda_ops.step_update(
            s, q[:, t], k[:, t], v[:, t], jnp.exp(g[:, t]), beta[:, t])
        outs.append(o)
    return jnp.stack(outs, 1), s


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("T", [37, 80])
def test_chunked_form_is_the_step_form(chunk, T):
    """Lengths that are no multiple of the chunk, two packed documents a
    row (a reset at a document's position 0, one inside a chunk), a state
    handed in."""
    q, k, v, g, beta = _recurrence_inputs(T)
    reset = jnp.zeros((2, T), bool).at[0, 0].set(True).at[0, 19].set(
        True).at[1, 33].set(True)
    init = jax.random.normal(jax.random.key(7), (2, 3, 16, 16))
    want_o, want_s = _token_by_token(q, k, v, g, beta, reset, init)
    got_o, got_s = jax.jit(
        kda_ops.scan_chunked, static_argnums=7)(
            q, k, v, g, beta, reset, init, chunk)
    np.testing.assert_allclose(got_o, want_o, atol=TOL)
    np.testing.assert_allclose(got_s, want_s, atol=TOL)


@pytest.mark.parametrize("a_log", [-9.0, 0.0, 6.0])
def test_both_ends_of_a_log_give_no_inf_or_nan(a_log):
    """``A = exp(A_log)`` from 1e-4 (a state that never forgets) to 400 (a
    log-decay of hundreds a token: ``exp(-G)`` would leave float32 within
    a few tokens, which is why the chunked form only ever takes
    DIFFERENCES of running sums): finite, equal to the step form, and
    finite gradients."""
    q, k, v, g, beta = _recurrence_inputs(70, a_log=a_log)
    reset = jnp.zeros((2, 70), bool).at[:, 0].set(True)
    init = jnp.zeros((2, 3, 16, 16))
    want_o, want_s = _token_by_token(q, k, v, g, beta, reset, init)
    got_o, got_s = kda_ops.scan_chunked(q, k, v, g, beta, reset, init, 64)
    assert bool(jnp.isfinite(got_o).all() and jnp.isfinite(got_s).all())
    np.testing.assert_allclose(got_o, want_o, atol=TOL)
    np.testing.assert_allclose(got_s, want_s, atol=TOL)
    grad = jax.grad(lambda g_: kda_ops.scan_chunked(
        q, k, v, g_, beta, reset, init, 16)[0].sum())(g)
    assert bool(jnp.isfinite(grad).all())


def test_packed_documents_do_not_see_each_other(params, ids, want):
    """Two documents on one packed row: the second starts from an empty
    state and an empty convolution (the trainer's forward)."""
    both = jnp.concatenate([ids[:9], ids])
    seg = jnp.concatenate([jnp.ones(9), 2 * jnp.ones(len(ids))]).astype(int)
    pos = jnp.concatenate([jnp.arange(9), jnp.arange(len(ids))])
    got = _packed(params, CFG, both, seg, pos)
    np.testing.assert_allclose(got[9:], want, atol=TOL)
    np.testing.assert_allclose(got[:9], want[:9], atol=TOL)


def test_beta_beyond_one_occurs_and_matters(params, ids, want):
    """``kda_allow_neg_eigval``: ``beta = 2 sigmoid`` lies in (1, 2) for
    about half the (token, head) pairs, and a program that forgot the 2
    (the reference's control) is another function."""
    h = jax.random.normal(jax.random.key(5), (64, 64))
    p = jax.tree.map(lambda a: a[0], params["kda_layers"]["kda"])
    qkv = jax.nn.silu(h @ p["w_qkv"])
    beta = kda_ops._inputs(CFG, p, h, qkv)[4]
    assert 0.2 < float((beta > 1.0).mean()) < 0.8 and float(beta.max()) < 2.0
    plain = dict(ARCH, kda_allow_neg_eigval=False)
    assert not _cfg(plain).kda.neg_eigval
    np.testing.assert_allclose(
        _packed(params, _cfg(plain), ids),
        ref.sequence_logits(
            params, dict(ARCH, control_beta_without_two=True), ids), atol=TOL)
    assert np.abs(_packed(params, _cfg(plain), ids) - want).max() > 1e-2
    # ... and one decay a head, the other plausible error, likewise
    one = ref.sequence_logits(params, dict(ARCH, control_decay_a_head=True), ids)
    assert np.abs(np.asarray(one) - want).max() > 1e-3


# --- (c) the share --------------------------------------------------------- #


def _share(params, tree, rank):
    """Rank ``rank``'s configuration and ``tree``'s first expert layer's
    weights, cut from an UNCUT tree."""
    cfg = _cfg(dict(ARCH, expert_parallel_rank=rank))
    mlp = dict(jax.tree.map(lambda a: a[0], params[tree]["mlp"]))
    for k in ("w_gate", "w_up", "w_down"):
        mlp[k] = mlp[k][rank * HELD : (rank + 1) * HELD]
    return cfg, mlp


@pytest.mark.parametrize("tree", ["layers", "kda_layers"])
def test_the_eight_shares_and_the_shared_expert_once_make_the_whole_layer(
        tree):
    """One test ties the share to the model: the program's expert layer run
    as each of the eight ranks (its routed part, and the shared expert that
    every rank computes alike) against the reference's UNCUT layer of all
    16 experts: routed parts summed, shared expert counted once."""
    whole_arch = dict(
        ARCH, n_routed_experts=RANKS * HELD, expert_parallel_size=1,
        expert_parallel_rank=0)
    whole = seeded_params(_cfg(whole_arch))
    x = jax.random.normal(jax.random.key(9), (12, ARCH["hidden_size"]))
    routed, shared = ref.expert_layer_parts(whole, whole_arch, x, tree)
    h = np.asarray(ref._rms(x, whole[tree]["ln2"]["weight"][0], 1e-5))
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for rank in range(RANKS):
            cfg, mlp = _share(whole, tree, rank)
            out, _, top = moe_ops.moe_mlp(cfg, mlp, jnp.asarray(h))
            total = total + np.asarray(out) - shared
            part, same = ref.expert_layer_parts(
                {tree: {"ln2": whole[tree]["ln2"], "mlp": {
                    k: v[None] for k, v in mlp.items()}}},
                dict(ARCH, expert_parallel_rank=rank), x, tree)
            np.testing.assert_allclose(out, part + same, atol=TOL)
    assert int(top.max()) >= HELD        # the router keeps all 16 outputs
    np.testing.assert_allclose(total, routed, atol=TOL)
    # a share is a PART: no rank's routed sum is the whole
    assert np.abs(np.asarray(out) - shared - routed).max() > 1e-3


def test_weights_normalised_over_the_held_experts_are_another_function(
        params, ids, want):
    wrong = ref.sequence_logits(
        params, dict(ARCH, control_norm_over_held=True), ids)
    assert np.abs(np.asarray(wrong) - want).max() > 1e-2


@pytest.mark.parametrize("T", [5, 40])
def test_dense_and_grouped_dispatch_agree_on_a_share(params, T):
    """Both expert stacks handed whole to the grouped kernel (interpret
    mode), the delta-rule layers' by their own index."""
    held, stacks = tfm._hold_routed(params)
    assert "w_up" not in held["layers"]["mlp"]
    assert "w_up" not in held["kda_layers"]["mlp"]
    assert stacks["kda"]["w_up"].shape[0] == 3
    x = jax.random.normal(jax.random.key(T), (T, ARCH["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        for tree, routed in (("layers", stacks), ("kda_layers", stacks["kda"])):
            j = 0 if tree == "layers" else 2
            dense, _, _ = moe_ops.moe_mlp(
                CFG, jax.tree.map(lambda a: a[j], params[tree]["mlp"]), x)
            grouped, _, _ = moe_ops.moe_mlp(
                CFG, jax.tree.map(lambda a: a[j], held[tree]["mlp"]), x,
                routed=(routed, jnp.int32(j)))
            np.testing.assert_allclose(grouped, dense, atol=1e-4)


# --- (d) the kernel --------------------------------------------------------- #


@pytest.mark.parametrize("B", [1, 3])
def test_kda_decode_in_interpret_mode_is_the_plain_step(B):
    """One layer of a stacked state updated in place: the other layers and
    an inactive row (decay 1, beta 0) keep theirs bit for bit."""
    from areal_tpu.ops.pallas import kda_decode as kd

    L, H, D = 2, 8, 128
    cfg = dataclasses.replace(CFG, kda=KDAConfig(H, D))
    assert kd.kda_decode_applies(cfg, None, "tpu")
    assert not kd.kda_decode_applies(CFG, None, "tpu")      # heads of 16
    assert not kd.kda_decode_applies(cfg, None, "cpu")
    ks = jax.random.split(jax.random.key(B), 6)
    s_all = jax.random.normal(ks[0], (L, B, H, D, D))
    q = kda_ops._l2norm(jax.random.normal(ks[1], (B, H, D))) * D ** -0.5
    k = kda_ops._l2norm(jax.random.normal(ks[2], (B, H, D)))
    v = jax.random.normal(ks[3], (B, H, D))
    a = jnp.exp(-jax.nn.softplus(jax.random.normal(ks[4], (B, H, D))))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[5], (B, H)))
    a, beta = a.at[B - 1].set(1.0), beta.at[B - 1].set(0.0)
    want_o, want_s = kda_ops.step_update(s_all[1], q, k, v, a, beta)
    got_o, got_s = jax.jit(kd.kda_decode)(s_all, 1, q, k, v, a, beta)
    np.testing.assert_allclose(got_o, want_o, atol=1e-5)
    np.testing.assert_allclose(got_s[1], want_s, atol=1e-5)
    np.testing.assert_array_equal(got_s[0], s_all[0])
    np.testing.assert_array_equal(got_s[1, B - 1], s_all[1, B - 1])


# --- (e) the HF round trip ------------------------------------------------ #


def test_hf_round_trip_weight_names_and_a_whole_checkpoint(params):
    hf_cfg = FAMILY.config_to_hf(CFG)
    assert hf_cfg["n_routed_experts"] == HELD
    assert (hf_cfg["expert_parallel_size"], hf_cfg["expert_parallel_rank"]) == (
        RANKS, 1)
    for key, value in ARCH.items():
        assert hf_cfg[key] == value, key
    assert _cfg(hf_cfg) == CFG
    sd = FAMILY.params_to_hf(params, CFG)
    # the experts under their place among all 16; Kimi Linear's names
    for name, shape in (
            ("model.layers.0.self_attn.g_proj.weight", (64, 64)),
            ("model.layers.1.self_attn.q_conv1d.weight", (64, 1, 4)),
            ("model.layers.1.self_attn.f_a_proj.weight", (16, 64)),
            ("model.layers.1.self_attn.f_b_proj.weight", (64, 16)),
            ("model.layers.2.self_attn.g_b_proj.weight", (64, 16)),
            ("model.layers.3.self_attn.b_proj.weight", (4, 64)),
            ("model.layers.3.self_attn.A_log", (4,)),
            ("model.layers.3.self_attn.dt_bias", (64,)),
            ("model.layers.3.self_attn.o_norm.weight", (16,)),
            ("model.layers.2.block_sparse_moe.gate.weight", (16, 64)),
            ("model.layers.2.block_sparse_moe.gate.e_score_correction_bias",
             (16,)),
            ("model.layers.0.block_sparse_moe.experts.3.up_proj.weight",
             (32, 64)),
            ("model.layers.1.block_sparse_moe.shared_experts.down_proj.weight",
             (64, 32))):
        assert sd[name].shape == shape, name
    assert "model.layers.0.block_sparse_moe.experts.0.up_proj.weight" not in sd
    assert "model.layers.0.self_attn.q_conv1d.weight" not in sd
    back = FAMILY.params_from_hf(sd, CFG)
    jax.tree.map(np.testing.assert_array_equal, back,
                 jax.tree.map(np.asarray, params))
    # a checkpoint of ALL the experts: the rank's two are taken
    whole_cfg = _cfg(dict(
        ARCH, n_routed_experts=RANKS * HELD, expert_parallel_size=1,
        expert_parallel_rank=0))
    whole = seeded_params(whole_cfg)
    got = FAMILY.params_from_hf(FAMILY.params_to_hf(whole, whole_cfg), CFG)
    np.testing.assert_array_equal(
        got["kda_layers"]["mlp"]["w_up"],
        np.asarray(whole["kda_layers"]["mlp"]["w_up"])[:, HELD : 2 * HELD])
    assert got["layers"]["mlp"]["router"].shape == (1, 64, RANKS * HELD)


def test_the_published_configuration_builds():
    """The benchmark's configuration file (every key of the catalog row's
    config, the four under ``reduced`` changed) and the row's whole config
    (48 layers, 320 experts), shapes only."""
    with open(os.path.join(
            ROOT, "benchmark", "configs", "solar-open2-l4-ep8.json")) as f:
        arch = json.load(f)
    assert arch["reduced"] == [
        "num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"]
    cfg = FAMILY.config_from_hf(arch)
    assert cfg.moe.held == (40, 0) and cfg.moe.num_experts == 320
    assert cfg.mixers == ("attn", "kda", "kda", "kda") and cfg.attn_gate
    assert (cfg.kda.n_heads, cfg.kda.head_dim, cfg.kda.d_conv) == (64, 128, 4)
    assert cfg.kda.neg_eigval and not cfg.apply_rotary
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.expert_dim, cfg.moe.top_k) == (4096, 64, 8, 128, 1280, 8)
    shapes = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.key(0), jnp.bfloat16))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n == arch["parameters"] == 3_308_353_344
    assert tfm.row_state_bytes(
        dataclasses.replace(cfg, dtype="bfloat16")) == 13_025_280
    full = {**arch, **arch["reduced_from"], "expert_parallel_size": 1}
    cfg = FAMILY.config_from_hf(full)
    assert (cfg.n_layers, cfg.n_moe_layers, cfg.n_attn_layers,
            cfg.n_kda_layers) == (48, 48, 12, 36)
    assert cfg.layer_ids["attn"] == full["gqa_layers"]
    shapes = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.key(0), jnp.bfloat16))
    assert shapes["kda_layers"]["mlp"]["w_up"].shape == (36, 320, 4096, 1280)


# --- (f) what is refused, by name ----------------------------------------- #


@pytest.mark.parametrize("change,match", [
    ({"kda_use_full_proj": True}, "kda_use_full_proj"),
    ({"linear_attn_config": dict(ARCH["linear_attn_config"], num_kv_heads=2)},
     "linear_attn_config.num_kv_heads"),
    ({"use_rope": True}, "use_rope"),
    ({"first_k_dense_replace": 1}, "first_k_dense_replace"),
    ({"gqa_layers": [1]}, "gqa_layers"),
    ({"gqa_interval": 2}, "gqa_layers"),
    ({"expert_parallel_rank": 8}, "expert_parallel_rank"),
])
def test_refused_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        FAMILY.config_from_hf(dict(ARCH, **change))
    if "expert_parallel_rank" not in change and "gqa" not in match:
        with pytest.raises(ValueError, match=match):
            ref.sequence_logits({}, dict(ARCH, **change), jnp.zeros(4, int))


@pytest.mark.parametrize("change,match", [
    (dict(one_branch=True), "TWO branches"),
    (dict(stack_plan=((1, (("attn", None), "kda", "ssm", "kda")),)),
     "'ssm' or 'kda'"),
    (dict(stack_plan=((2, (("attn", None), "moe")),)), "'ssm' or 'kda'"),
    (dict(kda=None), "ONE recurrent mixer"),
    (dict(kda=KDAConfig(4, 16, d_conv=1)), "at least two taps"),
    (dict(residual_scaling=True), "residual scaling"),
])
def test_plans_the_config_refuses_say_what_is_allowed(change, match):
    """A router in every block's second branch is for a plan over 'attn'
    and 'kda' layers; a plan of one-branch blocks still holds it in 'moe'
    positions alone (``tests/test_nemotron_h.py`` holds that side)."""
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **change)


# --- (g) autodiff through the chunked form --------------------------------- #


def test_gradients_through_delta_rule_layers_with_experts(params, ids):
    def loss(p):
        out, aux = _packed(p, CFG, ids[:12], with_aux=True)
        return jnp.mean(jax.nn.logsumexp(out, -1)) + aux

    grads = jax.jit(jax.grad(loss))(params)
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
    for name in ("w_qkv", "conv_w", "w_fb", "A_log", "dt_bias", "w_beta",
                 "w_gb", "o_norm", "wo"):
        assert float(jnp.abs(grads["kda_layers"]["kda"][name]).max()) > 0, name
    assert float(jnp.abs(grads["kda_layers"]["mlp"]["w_up"]).max()) > 0


def test_chip_smoke_child_runs_the_kernel_beside_the_plain_step(tmp_path):
    """``chip_smoke.py``'s child ``kdadecode`` at its rehearsal size (the
    kernel interpreted): what the smoke requires of it on the chip holds
    here too, but for the kernel's name in a lowered program."""
    import subprocess
    import sys

    arg = tmp_path / "arg.json"
    arg.write_text(json.dumps({"seed": 11, "rehearse": True}))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), "--child",
         "kdadecode", str(arg)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().split("\n")[-1])
    assert max(got["max_abs_diff_out"], got["max_abs_diff_state"]) <= got[
        "tolerance"]
    assert got["other_layers_untouched"] and got["inactive_row_untouched"]
    assert (got["heads"], got["head_dim"]) == (8, 128) and not got["compiled"]
