"""granitemoehybrid: state-space layers beside attention layers.

The program (``ops/ssm.py``'s chunked scan and one-token update, the weight
stack a kind under ``_scan_mixers``, the per-slot state, the paged kernels
at a head of 64) against the plain reference
(``benchmark/reference/granitemoehybrid.py``: a token-by-token float32
recurrence that shares nothing with ``ops/ssm.py``), at a small size on
the CPU in float32. Weights are seeded with the published initialisation's
ranges for ``A_log``, ``dt_bias``, ``D`` and the convolution (normal(0,
0.02) there would make every head forget in two tokens and a wrong state
pass).

Tolerance ``TOL`` = 2e-5 nats: program and reference run the same float32
arithmetic in another order (chunked matmuls against a sequential scan),
which reads under 2e-6 here; a state rounded to bfloat16 once a token
reads over 1e-4 (``test_what_the_tolerance_has_to_see``).
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig, SSMConfig
from areal_tpu.models.hf import family_for_model_type
from areal_tpu.ops import paged_attention as paged_ops
from areal_tpu.ops import ssm as ssm_ops
from benchmark.reference import granitemoehybrid as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = family_for_model_type("granitemoehybrid")
TOL = 2e-5

ARCH = {
    "attention_bias": False, "attention_multiplier": 0.125,
    "embedding_multiplier": 3, "hidden_act": "silu", "hidden_size": 32,
    "intermediate_size": 64,
    "layer_types": ["mamba", "attention", "mamba"] * 2,
    "logits_scaling": 2, "mamba_chunk_size": 8, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 8, "mamba_d_state": 16,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 8,
    "mamba_proj_bias": False, "max_position_embeddings": 512,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 4, "num_experts_per_tok": 0,
    "num_hidden_layers": 6, "num_key_value_heads": 2, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.5,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 64, "tie_word_embeddings": True,
    "vocab_size": 128,
}


def _cfg(arch=ARCH, **over) -> ModelConfig:
    # (two kv heads to a cache row, as the published head of 64 gets:
    # ``ModelConfig.kv_heads_per_row``)
    over = {"dtype": "float32", **over}
    return dataclasses.replace(FAMILY.config_from_hf(arch), **over)


CFG = _cfg()


def seeded_params(cfg, seed=41):
    """``init_params`` (which draws ``A_log`` / ``dt_bias`` in the published
    ranges) with the convolution's and the norms' values moved off their
    init too, and the matrices ten times their init (normal(0, 0.02) at a
    hidden size of 32 leaves every branch a hundredth of the residual and
    the logits flat: no fault would show)."""
    p = tfm.init_params(cfg, jax.random.key(seed))
    p = jax.tree.map(lambda a: a * 10 if a.ndim >= 3 else a, p)
    p["embed"] = {"weight": p["embed"]["weight"] * 15}
    ks = jax.random.split(jax.random.key(seed + 1), 4)
    mixer = dict(p["ssm_layers"]["ssm"])
    mixer["conv_w"] = jax.random.uniform(
        ks[0], mixer["conv_w"].shape, minval=-0.5, maxval=0.5)
    mixer["conv_b"] = jax.random.uniform(
        ks[1], mixer["conv_b"].shape, minval=-0.5, maxval=0.5)
    mixer["D"] = 1 + 0.1 * jax.random.normal(ks[2], mixer["D"].shape)
    mixer["gate_norm"] = 1 + 0.1 * jax.random.normal(
        ks[3], mixer["gate_norm"].shape)
    return {**p, "ssm_layers": {**p["ssm_layers"], "ssm": mixer}}


@pytest.fixture(scope="module")
def params():
    return seeded_params(CFG)


def _toks(seed, n):
    return np.random.RandomState(seed).randint(1, ARCH["vocab_size"], n)


def _ref_logprobs(params, ids, arch=ARCH):
    return np.asarray(ref.sequence_logprobs(params, arch, ids))


def _packed_logits(cfg, params, ids, seg=None, pos=None, **kw):
    n = len(ids)
    seg = np.ones(n, np.int32) if seg is None else seg
    pos = np.arange(n) if pos is None else pos
    return tfm.forward_packed(
        params, cfg, jnp.asarray(ids), jnp.asarray(seg), jnp.asarray(pos),
        **kw)


def _label_logprobs(logits, ids):
    lp = jax.nn.log_softmax(logits, -1)
    return lp[jnp.arange(len(ids) - 1), jnp.asarray(ids[1:])]


# ---- the family ------------------------------------------------------ #

def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    for line in open(path):
        row = json.loads(line)
        if row["name"] == "granite-4.0-h-micro":
            return row
    pytest.skip("the catalog has no such row")


def test_family_reads_and_writes_the_published_config_key_for_key():
    row = _catalog_row()
    cfg = FAMILY.config_from_hf(row["config"])
    back = FAMILY.config_to_hf(cfg)
    for key, value in row["config"].items():
        assert back[key] == value, key
    assert cfg.stack_plan == ((4, tuple(
        (m, None) for m in ("ssm",) * 5 + ("attn",) + ("ssm",) * 4)),)
    assert (cfg.n_ssm_layers, cfg.n_attn_layers, cfg.cache_layers) == (36, 4, 4)
    assert cfg.kv_heads_per_row == 2 and cfg.softmax_scale == 0.015625
    shapes = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.key(0), dtype=jnp.bfloat16))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 3_191_396_096
    # what a slot keeps, and what the pool keeps of a token
    assert ssm_ops.state_bytes_per_slot(cfg) == 36 * (
        64 * 64 * 128 * 4 + 3 * 4352 * 2)
    assert tfm.kv_page_geometry(cfg) == (2, 4, 128)


def test_benchmark_config_is_the_published_one_uncut():
    row = _catalog_row()
    with open(os.path.join(
            ROOT, "benchmark/configs/granite-4.0-h-micro.json")) as f:
        arch = json.load(f)
    for key, value in row["config"].items():
        assert arch[key] == value, key
    assert arch["reduced"] == [] and arch["state_dtype"] == "float32"
    assert arch["parameters"] == 3_191_396_096
    assert arch["source"] == row["source_url"]


@pytest.mark.parametrize("key,value", [
    ("num_local_experts", 8),
    ("position_embedding_type", "rope"),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}),
    ("normalization_function", "layernorm"),
    ("layer_types", ["mamba", "attention", "linear"] * 2),
    ("layer_types", ["mamba"] * 6),
    ("mamba_n_groups", 3),
    ("mamba_expand", 3),
])
def test_family_refuses_what_it_does_not_implement(key, value):
    with pytest.raises(ValueError):
        FAMILY.config_from_hf({**ARCH, key: value})


@pytest.mark.parametrize("over", [
    {"n_passes": 2}, {"mlp_type": "moe"}, {"norm_branch_out": True},
    {"layer_pattern": ((None, False), (8, False))},
    {"ssm": dataclasses.replace(CFG.ssm, state_dtype="bfloat16")},
    {"stack_plan": None},
])
def test_config_refuses_state_space_beside_what_no_test_covers(over):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **over)


def test_hf_names_round_trip(params):
    sd = FAMILY.params_to_hf(jax.tree.map(np.asarray, params), CFG)
    E, F = ARCH["hidden_size"], ARCH["shared_intermediate_size"]
    s = CFG.ssm
    want = {
        "model.embed_tokens.weight": (ARCH["vocab_size"], E),
        "model.norm.weight": (E,),
        "model.layers.0.mamba.in_proj.weight": (s.in_dim, E),
        "model.layers.0.mamba.conv1d.weight": (s.conv_dim, 1, s.d_conv),
        "model.layers.0.mamba.conv1d.bias": (s.conv_dim,),
        "model.layers.0.mamba.dt_bias": (s.n_heads,),
        "model.layers.0.mamba.A_log": (s.n_heads,),
        "model.layers.0.mamba.D": (s.n_heads,),
        "model.layers.0.mamba.norm.weight": (s.d_inner,),
        "model.layers.0.mamba.out_proj.weight": (E, s.d_inner),
        "model.layers.1.self_attn.q_proj.weight": (E, E),
        "model.layers.1.self_attn.k_proj.weight": (E // 2, E),
        "model.layers.1.self_attn.o_proj.weight": (E, E),
        "model.layers.1.shared_mlp.input_linear.weight": (2 * F, E),
        "model.layers.5.shared_mlp.output_linear.weight": (E, F),
        "model.layers.4.input_layernorm.weight": (E,),
        "model.layers.4.post_attention_layernorm.weight": (E,),
    }
    for name, shape in want.items():
        assert sd[name].shape == shape, name
    assert "lm_head.weight" not in sd                   # tied
    assert "model.layers.1.mamba.in_proj.weight" not in sd
    assert "model.layers.0.self_attn.q_proj.weight" not in sd
    back = FAMILY.params_from_hf(sd, CFG)
    flat = jax.tree_util.tree_leaves_with_path(jax.tree.map(np.asarray, params))
    got = dict(jax.tree_util.tree_leaves_with_path(back))
    assert set(got) == {k for k, _ in flat}
    for k, v in flat:
        np.testing.assert_array_equal(got[k], v, err_msg=str(k))


def test_param_axes_follow_the_tree(params):
    axes = tfm.param_logical_axes(CFG)
    is_axes = lambda x: isinstance(x, tuple)
    got = jax.tree.map(lambda a, p: len(a) == p.ndim, axes, params,
                       is_leaf=is_axes)
    assert all(jax.tree.leaves(got))


# ---- forwards against the reference ---------------------------------- #

@pytest.mark.parametrize("n", [5, 41])
def test_forward_packed_matches_reference(params, n):
    ids = _toks(n, n)
    got = _label_logprobs(_packed_logits(CFG, params, ids, remat=False), ids)
    np.testing.assert_allclose(got, _ref_logprobs(params, ids), atol=TOL)


@pytest.mark.parametrize("fault,seen", [
    ("bfloat16", True), ("float32", False)])
def test_what_the_tolerance_has_to_see(params, fault, seen):
    """A recurrent state rounded to bfloat16 after every token lands
    outside ``TOL`` over this test's 41 tokens; float32 does not."""
    ids = _toks(3, 41)
    got = _label_logprobs(_packed_logits(CFG, params, ids, remat=False), ids)
    faulty = _ref_logprobs(
        params, ids, {**ARCH, "control_state_dtype": fault})
    assert (float(np.abs(np.asarray(got) - faulty).max()) > TOL) == seen


def test_lost_state_is_seen(params):
    """The reference with the state dropped at token 16 differs from the
    program: what the benchmark's lost-snapshot control rests on."""
    ids = _toks(4, 41)
    got = _label_logprobs(_packed_logits(CFG, params, ids, remat=False), ids)
    lost = _ref_logprobs(params, ids, {**ARCH, "control_zero_state_at": 16})
    d = np.abs(np.asarray(got) - lost)
    assert d[:15].max() < TOL and d[16:].max() > 10 * TOL


def test_two_documents_packed_equal_each_alone(params):
    """State AND convolution reset at a document's first token."""
    a, b = _toks(5, 19), _toks(6, 23)
    both = _packed_logits(
        CFG, params, np.concatenate([a, b]),
        seg=np.r_[np.ones(19), 2 * np.ones(23)].astype(np.int32),
        pos=np.r_[np.arange(19), np.arange(23)], remat=False)
    np.testing.assert_allclose(
        both[:19], _packed_logits(CFG, params, a, remat=False), atol=1e-5)
    np.testing.assert_allclose(
        both[19:], _packed_logits(CFG, params, b, remat=False), atol=1e-5)


@pytest.mark.parametrize("chunk", [1, 8, 64])
def test_any_chunk_length_gives_the_same_function(params, chunk):
    cfg = dataclasses.replace(
        CFG, ssm=dataclasses.replace(CFG.ssm, chunk_size=chunk))
    ids = _toks(7, 29)
    got = _label_logprobs(_packed_logits(cfg, params, ids, remat=False), ids)
    np.testing.assert_allclose(got, _ref_logprobs(params, ids), atol=TOL)


def _program_loss(cfg, p, ids):
    return -jnp.mean(_label_logprobs(_packed_logits(cfg, p, ids), ids))


@pytest.mark.parametrize("policy", ["full", "none"])
def test_gradients_match_reference(params, policy):
    """``jax.grad`` through the chunked scan (the trainer's backward pass)
    against the gradient of the token-by-token reference."""
    cfg = dataclasses.replace(CFG, remat_policy=policy)
    ids = _toks(8, 33)
    # each side ONE program (eagerly ~250 one-op programs a side)
    got = jax.jit(jax.grad(lambda p: _program_loss(cfg, p, ids)))(params)
    want = jax.jit(jax.grad(
        lambda p: -jnp.mean(ref.sequence_logprobs(p, ARCH, ids))))(params)
    for (path, g), w in zip(
            jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        scale = float(jnp.abs(w).max()) + 1e-6
        assert float(jnp.abs(g - w).max()) <= 2e-4 * scale + 1e-7, path


def test_one_token_update_equals_the_scan_s_last_step(params):
    p = jax.tree.map(lambda a: a[0], params["ssm_layers"]["ssm"])
    h = jax.random.normal(jax.random.key(0), (3, 12, ARCH["hidden_size"]))
    pos = jnp.broadcast_to(jnp.arange(12), (3, 12))
    out, st = ssm_ops.mixer_chunk(CFG, p, h, pos)
    _, before = ssm_ops.mixer_chunk(CFG, p, h[:, :11], pos[:, :11])
    active = jnp.asarray([True, False, True])
    step, st1 = ssm_ops.mixer_step(CFG, p, h[:, 11], before, active)
    keep = np.asarray(active)
    np.testing.assert_allclose(step[keep], out[keep, 11], atol=1e-5)
    for got, want, was in zip(st1, st, before):
        np.testing.assert_allclose(got[keep], want[keep], atol=1e-5)
        np.testing.assert_array_equal(got[~keep], was[~keep])


def test_dense_cache_prefill_and_decode_match_reference(params):
    ids = _toks(9, 40)
    want = _ref_logprobs(params, ids)
    cache = tfm.KVCache.empty(CFG, 2, 48)
    lens = np.array([13, 20])
    inp = np.zeros((2, 24), np.int32)
    for b, n in enumerate(lens):
        inp[b, :n] = ids[:n]
    logits, cache = tfm.prefill(
        params, CFG, cache, jnp.asarray(inp), jnp.asarray(lens))
    step = jax.jit(tfm.decode_step, static_argnums=(1,))
    for t in range(6):
        lp = jax.nn.log_softmax(logits, -1)
        for b, n in enumerate(lens):
            assert abs(float(lp[b, ids[n + t]]) - want[n + t - 1]) < TOL
        logits, cache = step(
            params, CFG, cache, jnp.asarray([ids[n + t] for n in lens]))


def _paged_setup(cfg, n_slots=4, page=8, pages_a_slot=8):
    pool = tfm.PagedKVCache.empty(cfg, n_slots * pages_a_slot, page)
    # what the last tenant left: a slot starts from zero all the same
    ssm = jax.tree.map(lambda a: a + 3.0, tfm.SSMState.empty(cfg, n_slots))
    table = jnp.arange(n_slots * pages_a_slot).reshape(n_slots, pages_a_slot)
    return pool, ssm, table


def _extend(cfg, params, pool, ssm, table, slots, pieces, starts, **kw):
    C = 16
    toks = np.zeros((len(slots), C), np.int32)
    for i, t in enumerate(pieces):
        toks[i, : len(t)] = t
    slots = jnp.asarray(slots)
    return tfm.extend_paged(
        params, cfg, pool, jnp.asarray(toks), table[slots],
        jnp.asarray(starts), jnp.asarray([len(t) for t in pieces]),
        ssm=ssm, slots=slots, **kw)


@pytest.mark.parametrize("use_pallas,kernel", [
    (True, True), (False, False)], ids=["kernels", "xla"])
def test_paged_admission_in_unequal_chunks_and_decode_match_reference(
        params, use_pallas, kernel):
    """``extend_paged`` in chunks of 5 + 8 and 16 + 4 tokens (the state is
    carried between them), then ``decode_step_paged``; once with the paged
    kernel, ``kv_page_write`` and ``ssm_decode`` in interpret mode, once
    with their XLA references."""
    from areal_tpu.ops.pallas import ssm_decode

    ids = _toks(10, 40)
    want = _ref_logprobs(params, ids)
    pool, ssm, table = _paged_setup(CFG)
    slots = [2, 0]
    kw = dict(use_pallas=use_pallas)
    pool, ssm = _extend(CFG, params, pool, ssm, table, slots,
                        [ids[:5], ids[:16]], [0, 0], **kw)
    pool, ssm = _extend(CFG, params, pool, ssm, table, slots,
                        [ids[5:13], ids[16:20]], [5, 16], **kw)
    lens = jnp.asarray([20, 0, 13, 0])
    active = jnp.asarray([True, False, True, False])
    untouched = jax.tree.map(lambda a: a[:, 1], ssm)
    step = jax.jit(
        tfm.decode_step_paged, static_argnums=(1,),
        static_argnames=("use_pallas", "ssm_update"))
    for t in range(5):
        last = jnp.asarray([ids[20 + t], 0, ids[13 + t], 0])
        logits, pool, lens, ssm = step(
            params, CFG, pool, last, table, lens, active, ssm=ssm,
            ssm_update=ssm_decode.ssm_decode if kernel else None, **kw)
        lp = jax.nn.log_softmax(logits, -1)
        assert abs(float(lp[0, ids[21 + t]]) - want[20 + t]) < TOL
        assert abs(float(lp[2, ids[14 + t]]) - want[13 + t]) < TOL
    for a, b in zip(jax.tree.leaves(untouched),
                    jax.tree.leaves(jax.tree.map(lambda a: a[:, 1], ssm))):
        np.testing.assert_array_equal(a, b)


def test_admission_in_chunks_equals_one_prefill(params):
    ids = _toks(11, 31)
    pool, ssm, table = _paged_setup(CFG, page=16, pages_a_slot=4)
    _, one = _extend(CFG, params, pool, ssm, table, [1], [ids[:16]], [0])
    whole = jax.tree.map(lambda a: a[:, 1], one)
    pool2, ssm2, _ = _paged_setup(CFG, page=16, pages_a_slot=4)
    for lo, hi in ((0, 3), (3, 4), (4, 11), (11, 16)):
        pool2, ssm2 = _extend(
            CFG, params, pool2, ssm2, table, [1], [ids[lo:hi]], [lo])
    for a, b in zip(jax.tree.leaves(whole),
                    jax.tree.leaves(jax.tree.map(lambda a: a[:, 1], ssm2))):
        np.testing.assert_allclose(a, b, atol=1e-5)


SSM_DECODE_SHAPES = [              # G, R, P, N
    pytest.param(1, 4, 64, 128, id="published-head-64x128"),
    pytest.param(1, 8, 32, 128, id="head-of-32"),
    pytest.param(2, 2, 64, 128, id="two-groups"),
    pytest.param(1, 8, 8, 16, id="test-model"),
]
SSM_DECODE_ROWS = {
    # before, between and after active rows; less than a phase of copies
    "edges": [False, True, False, False, True, True],
    # three phases, the last one ragged
    "a-tenth-off": ([True] * 3 + [False] + [True] * 6) * 2,
    # a phase with nothing to copy between two that have
    "a-phase-off": [True] * 8 + [False] * 8 + [True] * 8,
    "none": [False] * 3,
}


@pytest.mark.parametrize("rows", list(SSM_DECODE_ROWS))
@pytest.mark.parametrize("G,R,P,N", SSM_DECODE_SHAPES)
def test_ssm_decode_kernel_equals_the_plain_update(G, R, P, N, rows):
    """The kernel in interpret mode against ``ops/ssm.py:step_update`` on
    the stored layout ``[Ls, B, G, K, N, lanes]``: the state AND ``y`` to
    float32's rounding (both sum in float32, the kernel's ``y`` in another
    order), the rows that are not active and the other layers bit for
    bit; whole phases and a ragged last one."""
    from areal_tpu.ops.pallas import ssm_decode

    active = jnp.asarray(SSM_DECODE_ROWS[rows])
    Ls, B = 3, active.shape[0]
    ks = jax.random.split(jax.random.key(2), 8)
    whole = ssm_ops._tiles(jax.random.normal(ks[0], (Ls, B, G, N, R * P)))
    whole = jnp.moveaxis(whole, -2, -3)       # [Ls, B, G, K, N, lanes]
    x = jax.random.normal(ks[1], (B, G, R, P))
    dt = jnp.where(active[:, None, None],
                   jax.nn.softplus(jax.random.normal(ks[2], (B, G, R))), 0.0)
    a = -jnp.exp(jax.random.normal(ks[3], (G, R)))
    b = jax.random.normal(ks[4], (B, G, N))
    c = jax.random.normal(ks[5], (B, G, N))
    d = jax.random.normal(ks[6], (G, R))
    want_y, want_s = ssm_ops.step_update(whole[1], x, dt, a, b, c, d)
    y, got = ssm_decode.ssm_decode(whole, 1, x, dt, a, b, c, d, active)
    keep = np.asarray(active)
    assert y.shape == want_y.shape
    np.testing.assert_allclose(y[keep], want_y[keep], rtol=1e-5, atol=3e-5)
    np.testing.assert_allclose(
        got[1][keep], want_s[keep], rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(got[1][~keep], whole[1][~keep])
    np.testing.assert_array_equal(got[0], whole[0])
    np.testing.assert_array_equal(got[2], whole[2])


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("rows", ["active", "inactive", "mixed"])
@pytest.mark.parametrize("C", [256, 96], ids=["lane-tiles", "ragged"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no-bias"])
@pytest.mark.parametrize("K", [2, 4])
def test_conv_step_is_conv_chunk_at_one_token_bit_for_bit(
        K, bias, C, rows, dtype):
    """The decode step's convolution (``conv_step``: static slices of the
    flat state, one elementwise pass for the next state) against the
    many-token form at ``T = 1`` behind ``d_conv`` tokens of the document,
    which is what the decode step ran before PR 54: the output and the
    state bit for bit, and a row that is not active keeps its state."""
    B = 6
    active = jnp.asarray({
        "active": [True] * B, "inactive": [False] * B,
        "mixed": [False, True, True, False, True, False]}[rows])
    ks = jax.random.split(jax.random.key(54), 4)
    p = {"conv_w": jax.random.normal(ks[0], (K, C)).astype(dtype)}
    if bias:
        p["conv_b"] = jax.random.normal(ks[1], (C,)).astype(dtype)
    x = jax.random.normal(ks[2], (B, C)).astype(dtype)
    state = jax.random.normal(ks[3], (B, (K - 1) * C)).astype(dtype)
    want, want_state = ssm_ops.conv_chunk(
        p, x[:, None], jnp.full((B, 1), K, jnp.int32), state,
        active.astype(jnp.int32))
    out, got_state = ssm_ops.conv_step(p, x, state, active)
    assert out.dtype == want.dtype and out.shape == (B, C)
    assert got_state.dtype == state.dtype and got_state.shape == state.shape
    np.testing.assert_array_equal(_bits(out), _bits(want[:, 0]))
    np.testing.assert_array_equal(_bits(got_state), _bits(want_state))
    keep = ~np.asarray(active)
    np.testing.assert_array_equal(
        _bits(got_state)[keep], _bits(state)[keep])


@pytest.mark.parametrize("over,platform,mesh_size,want", [
    ({}, "tpu", 1, True),
    ({"n_groups": 2}, "tpu", 1, True),        # 32 heads x 64 a group
    ({"n_groups": 64}, "tpu", 1, False),      # a group of 64 lanes
    ({"head_dim": 8, "n_heads": 8}, "tpu", 1, False),
    ({"d_state": 16}, "tpu", 1, False),       # N does not turn in whole tiles
    ({"state_dtype": "bfloat16"}, "tpu", 1, False),
    ({"n_heads": 256}, "tpu", 1, False),      # a ring of 128 MiB
    ({}, "tpu", 2, False),
    ({}, "cpu", 1, False),
], ids=["published", "two-groups", "group-under-a-lane-tile", "test-model",
        "small-state", "16-bit-state", "ring-over-vmem", "mesh", "cpu"])
def test_ssm_decode_applies_from_what_the_kernel_needs(
        over, platform, mesh_size, want):
    """The rule asks the shapes for whole lane tiles a GROUP (not for one
    group) of which two phases fit VMEM, a float32 state, one TPU
    device."""
    import types

    from areal_tpu.ops.pallas import ssm_decode

    ssm = dataclasses.replace(
        SSMConfig(n_heads=64, head_dim=64, d_state=128, n_groups=1), **over)
    cfg = types.SimpleNamespace(ssm=ssm)
    mesh = types.SimpleNamespace(size=mesh_size)
    assert ssm_decode.ssm_decode_applies(cfg, mesh, platform) is want
    assert not ssm_decode.ssm_decode_applies(
        types.SimpleNamespace(ssm=None), None, "tpu")


def test_stored_state_is_the_recurrences_channels_minor(params):
    """What ``mixer_chunk`` leaves (a dense-cache prefill) and ``mixer_step``
    then updates token by token IS the token-by-token recurrence's state
    ``[H, P, N]`` with its last two axes exchanged and the heads' channels
    run together in lane tiles: ``[G, K, N, lanes]``, one layout for both
    forms."""
    ids = _toks(12, 29)
    cache = tfm.KVCache.empty(CFG, 1, 32)
    s = CFG.ssm
    assert cache.ssm.ssm.shape == ssm_ops.state_shapes(CFG, 1)[0] == (
        CFG.n_ssm_layers, 1, s.n_groups, 1, s.d_state, s.n_heads * s.head_dim)
    published = dataclasses.replace(
        CFG, ssm=SSMConfig(n_heads=64, head_dim=64, d_state=128))
    assert ssm_ops.state_shapes(published, 80)[0] == (
        CFG.n_ssm_layers, 80, 1, 32, 128, 128)
    _, cache = tfm.prefill(
        params, CFG, cache, jnp.asarray(ids[None, :21]), jnp.asarray([21]))
    step = jax.jit(tfm.decode_step, static_argnums=(1,))
    for t in range(21, 29):
        _, cache = step(params, CFG, cache, jnp.asarray(ids[t:t + 1]))
        if t not in (21, 28):
            continue
        want = ref.recurrent_state(
            params, ARCH, ids[:t + 1], "float32", t + 1)      # [Ls, H, P, N]
        got = np.asarray(cache.ssm.ssm[:, 0]).transpose(0, 1, 2, 4, 3)
        np.testing.assert_allclose(
            got.reshape(want.shape), want, rtol=1e-4, atol=1e-5)


# ---- the paged kernels at a head of 64 -------------------------------- #

WIDE = ModelConfig(
    n_layers=4, n_q_heads=8, n_kv_heads=4, head_dim=64, hidden_dim=64,
    intermediate_dim=64, vocab_size=64, dtype="float32",
    apply_rotary=False, softmax_scale=0.015625,
    ssm=SSMConfig(n_heads=2, head_dim=8, d_state=16),
    stack_plan=((2, ("attn", "ssm")),))


def _dense_attention(q, k, v, scale):
    """q [H, D] over k, v [S, Hkv, D]."""
    rep = q.shape[0] // k.shape[1]
    kk, vv = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    p = jax.nn.softmax(jnp.einsum("hd,shd->hs", q, kk) * scale, -1)
    return jnp.einsum("hs,shd->hd", p, vv)


@pytest.mark.parametrize("use_pallas", [True, False], ids=["kernels", "xla"])
def test_paged_kernels_at_head_width_64(use_pallas):
    """Two kv heads of 64 to one 128-lane row of the pool: the write
    (``kv_page_write`` / the XLA scatter) and the paged decode kernel
    against plain attention over the unpacked keys and values."""
    cfg, page, B, S = WIDE, 16, 3, 40
    assert tfm.kv_page_geometry(cfg) == (2, 2, 128)
    ks = jax.random.split(jax.random.key(3), 4)
    k = jax.random.normal(ks[0], (B, S, 4, 64))
    v = jax.random.normal(ks[1], (B, S, 4, 64))
    q = jax.random.normal(ks[2], (B, 8, 64))
    lens = jnp.asarray([40, 17, 33])
    pool = tfm.PagedKVCache.empty(cfg, 12, page)
    table = jnp.arange(12).reshape(B, 4)
    _, pk, pv = tfm._pack_qkv(cfg, q, k, v)
    # layer 1's pages; S positions a row, ``lens`` of them valid
    zeros = jnp.zeros_like(pk)
    pool = tfm._write_chunk_kv(
        pool, jnp.stack([zeros, pk]), jnp.stack([zeros, pv]), table,
        jnp.zeros((B,), jnp.int32), lens, use_pallas)
    k_new = jax.random.normal(ks[3], (B, 4, 64))
    pq, pk1, pv1 = tfm._pack_qkv(cfg, q, k_new, k_new)
    ctx = tfm._unpack_ctx(cfg, paged_ops.paged_decode_attention(
        pq, pk1, pv1, pool.pages, 1, table, lens,
        softmax_scale=cfg.softmax_scale, use_pallas=use_pallas))
    for b in range(B):
        n = int(lens[b])
        want = _dense_attention(
            q[b], jnp.concatenate([k[b, :n], k_new[b][None]]),
            jnp.concatenate([v[b, :n], k_new[b][None]]), cfg.softmax_scale)
        np.testing.assert_allclose(ctx[b], want, atol=2e-5)
