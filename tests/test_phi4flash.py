"""phi4flash (Phi-4-mini-flash-reasoning, a decoder-hybrid-decoder) at a tiny
preset that keeps its three segments: 8 layers = 2 x (Mamba-1, window 8) +
(Mamba-1, FULL) + (gated memory unit, cross attention), ``d_state`` 4,
differential attention over 4 query / 2 kv heads of 16.

Every forward that runs through ``_run_stack`` is held to
``benchmark/reference/phi4flash.py`` (plain ``jax.numpy``, the recurrence
token by token, a pair's score at the published width, no cache, no
packing) on seeded random weights in float32 at the highest matmul
precision. TOL = 2e-5 nats: float32 rounding through 8 layers; the program
sums a softmax over a 2 D-wide packed row where the reference sums over D
(zeros added in another order) and scans the same recurrence in the same
order, so nothing larger is expected, and a wrong head map, window, lambda
or memory is 1e-2 and up."""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.extend
import jax.numpy as jnp

from areal_tpu.models import hf, transformer as tfm
from areal_tpu.ops import ssm as ssm_ops
from benchmark.reference import phi4flash as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5
FAMILY = hf.family_for_model_type("phi4flash")
ARCH = {
    "model_type": "phi4flash", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 8, "mb_per_layer": 2,
    "sliding_window": 8, "intermediate_size": 96, "vocab_size": 128,
    "layer_norm_eps": 1e-5, "max_position_embeddings": 512,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
    "hidden_act": "silu", "mamba_d_state": 4, "mamba_dt_rank": 4,
}
CFG = dataclasses.replace(FAMILY.config_from_hf(ARCH), dtype="float32")


def seeded_params(cfg, seed=0):
    """Random weights with every bias, gain and lambda off its initial
    value (a forward that dropped one would pass on zeros and ones)."""
    params = tfm.init_params(cfg, jax.random.key(seed))
    a_log = params["ssm_layers"]["ssm"]["A_log"]
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    params = jax.tree.unflatten(treedef, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])
    params["ssm_layers"]["ssm"]["A_log"] = a_log
    for tree in ("layers", "cross_layers"):
        for name in tfm._LAMBDAS:
            params[tree]["attn"][name] = 4 * params[tree]["attn"][name]
    return params


@pytest.fixture(scope="module")
def params():
    return seeded_params(CFG)


def _ids(seed, n):
    return np.random.RandomState(seed).randint(1, ARCH["vocab_size"], n)


def _want(params, ids, arch=ARCH):
    return ref.next_token_logprobs(
        params, arch, list(ids), "float32", len(ids))[0]


def _picked(logits, ids):
    lp = jax.nn.log_softmax(logits)
    return np.asarray(lp[np.arange(len(ids) - 1), ids[1:]])


# ---- the configuration ------------------------------------------------ #


def test_plan_is_the_published_layout():
    kinds = [k for k, _ in ref.layer_kinds(ARCH)]
    back = {"ssm": "mamba", "attn": "attention", "gmu": "gmu", "cross": "cross"}
    assert [back[m] for m in CFG.mixers] == kinds
    assert CFG.layer_kinds == ((8, False), (8, False), (None, False))
    assert (CFG.cache_layers, CFG.n_periods, CFG.kv_heads_per_row) == (3, 1, 2)
    (_, seg0), (_, seg1), (_, seg2) = CFG.plan
    assert seg1[1].exports and not seg0[1].exports
    assert seg2[1].source == 2 and seg2[0].mixer == "gmu"
    assert CFG.softmax_scale == 0.25 and CFG.ssm.selective


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    for line in open(path):
        row = json.loads(line)
        if row["name"] == "Phi-4-mini-flash-reasoning":
            return row
    pytest.skip("the catalog has no such row")


def test_family_reads_and_writes_the_published_config_key_for_key():
    row = _catalog_row()
    cfg = FAMILY.config_from_hf(row["config"])
    back = FAMILY.config_to_hf(cfg)
    for key, value in row["config"].items():
        assert back[key] == value, key
    assert [cfg.n_mixers(k) for k in ("ssm", "attn", "gmu", "cross")] == [
        9, 9, 7, 7]
    assert cfg.layer_kinds == ((512, False),) * 8 + ((None, False),)
    assert (cfg.cache_layers, cfg.n_periods) == (9, 1)
    assert cfg.positions[17].exports and cfg.positions[19].source == 8
    assert (cfg.ssm.d_inner, cfg.ssm.d_state, cfg.ssm.dt_rank) == (5120, 16, 160)
    shapes = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.key(0), dtype=jnp.bfloat16))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 3_852_562_944
    # what a slot keeps, and what the pool keeps of a token in a cache layer
    assert ssm_ops.state_bytes_per_slot(cfg) == 9 * (
        5120 * 16 * 4 + 3 * 5120 * 2)
    assert tfm.kv_page_geometry(cfg) == (2, 10, 128)


def test_benchmark_config_is_the_published_one_uncut():
    row = _catalog_row()
    with open(os.path.join(
            ROOT, "benchmark/configs/phi4-mini-flash.json")) as f:
        arch = json.load(f)
    for key, value in row["config"].items():
        assert arch[key] == value, key
    assert arch["reduced"] == [] and arch["state_dtype"] == "float32"
    assert arch["parameters"] == 3_852_562_944
    assert arch["source"] == row["source_url"]
    assert arch["reference"] == "phi4flash"


@pytest.mark.parametrize("key,value", [
    ("mlp_bias", True), ("lm_head_bias", True), ("hidden_act", "gelu"),
    ("mb_per_layer", 4), ("num_hidden_layers", 6), ("sliding_window", None),
    ("mamba_proj_bias", True),
])
def test_family_refuses_what_it_does_not_implement(key, value):
    with pytest.raises(ValueError):
        FAMILY.config_from_hf({**ARCH, key: value})


@pytest.mark.parametrize("plan", [
    ((3, ("ssm", ("attn", 8))),),                       # not n_layers
    ((8, ("ssm",)),),                                   # no attention
    ((4, ("ssm", "attn")), (2, ("gmu", "ssm"))),        # reader beside writer
    ((3, ("attn", "gmu")), (1, ("ssm", "cross"))),      # reader before writer
    ((3, ("ssm", ("attn", 8))), (1, ("gmu", "cross"))),  # shared K/V: window
    ((2, ("ssm", "attn")), (2, ("gmu", "cross"))),      # ... of two layers
    ((4, (("ssm", 8), "attn")),),                       # a window on "ssm"
    ((4, ("ssm", "conv")),),                            # no such mixer
], ids=str)
def test_config_refuses_a_plan_it_cannot_run(plan):
    with pytest.raises(ValueError, match="stack_plan"):
        dataclasses.replace(CFG, stack_plan=plan)


@pytest.mark.parametrize("over", [
    {"n_passes": 2}, {"mlp_type": "moe"}, {"norm_branch_out": True},
    {"layer_pattern": ((None, False), (8, False))}, {"sliding_window": 8},
    {"stack_plan": None}, {"qk_layernorm": True},
    {"ssm": dataclasses.replace(CFG.ssm, state_dtype="bfloat16")},
    {"ssm": dataclasses.replace(CFG.ssm, n_heads=2, head_dim=64)},
], ids=str)
def test_config_refuses_what_no_test_covers_beside_a_plan(over):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **over)


def test_window_and_full_kinds_beside_state_space_layers_are_accepted():
    """What ``__post_init__`` refused before the plan (ROADMAP R3-R5, D22):
    state-space layers WITH layer kinds."""
    cfg = dataclasses.replace(
        CFG, diff_attn=False,
        stack_plan=((2, ("ssm", ("attn", 8), "ssm", ("attn", None))),))
    assert cfg.layer_kinds == ((8, False), (None, False))
    assert (cfg.cache_layers, cfg.n_periods) == (4, 2)


# ---- the forwards ----------------------------------------------------- #


def test_packed_forward_is_the_reference(params):
    """Two documents in one row: the second starts from an empty state,
    convolution, window and shared K/V."""
    a, b = _ids(1, 29), _ids(2, 23)
    ids = np.concatenate([a, b, [0, 0]])
    seg = np.concatenate([np.full(29, 1), np.full(23, 2), [0, 0]])
    pos = np.concatenate([np.arange(29), np.arange(23), [0, 0]])
    with jax.default_matmul_precision("highest"):
        logits = tfm.forward_packed(
            params, CFG, jnp.asarray(ids), jnp.asarray(seg), jnp.asarray(pos),
            remat=False)
    np.testing.assert_allclose(
        _picked(logits[:29], a), _want(params, a), atol=TOL)
    np.testing.assert_allclose(
        _picked(logits[29:52], b), _want(params, b), atol=TOL)


@pytest.mark.parametrize("policy", ["full", "dots", "dots_attn"])
def test_packed_forward_under_remat_has_the_reference_s_gradient(
        params, policy):
    cfg = dataclasses.replace(CFG, remat_policy=policy)
    ids = _ids(3, 21)

    def loss(p):
        logits = tfm.forward_packed(
            p, cfg, jnp.asarray(ids), jnp.ones((21,), jnp.int32),
            jnp.arange(21))
        lp = jax.nn.log_softmax(logits)
        return -lp[jnp.arange(20), ids[1:]].mean()

    with jax.default_matmul_precision("highest"):
        # each side ONE program (eagerly ~130 one-op programs a side)
        got = jax.jit(jax.grad(loss))(params)
        want = jax.jit(jax.grad(lambda p: -ref.sequence_logprobs(
            p, ARCH, ids).mean()))(params)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=2e-4)


def test_dense_cache_prefill_and_decode_are_the_reference(params):
    ids = _ids(4, 44)
    want = _want(params, ids)
    n0 = 19
    prefill = jax.jit(lambda p, c, t, n: tfm.prefill(p, CFG, c, t, n))
    step = jax.jit(lambda p, c, t, a: tfm.decode_step(p, CFG, c, t, a))
    with jax.default_matmul_precision("highest"):
        cache = tfm.KVCache.empty(CFG, 2, 64)
        assert cache.k.shape == (3, 2, 64, 1, 32)    # a pair of kv heads a row
        prompts = np.zeros((2, 24), np.int32)
        prompts[0, :n0], prompts[1, :5] = ids[:n0], ids[:5]
        logits, cache = prefill(
            params, cache, jnp.asarray(prompts), jnp.asarray([n0, 5]))
        got = [jax.nn.log_softmax(logits)[0, ids[n0]]]
        for t in range(n0, 43):
            logits, cache = step(
                params, cache, jnp.asarray([ids[t], 0]),
                jnp.asarray([True, False]))
            got.append(jax.nn.log_softmax(logits)[0, ids[t + 1]])
    np.testing.assert_allclose(np.asarray(got), want[n0 - 1:], atol=TOL)


def test_paged_admission_in_chunks_then_decode_is_the_reference(params):
    """Admission in chunks of a page over a table a cache layer, then one
    token a step, held to the reference."""
    ids = _ids(5, 46)
    want = _want(params, ids)
    page, n0, M = 8, 21, 8
    extend = jax.jit(lambda p, c, st, toks, table, start, n: tfm.extend_paged(
        p, CFG, c, toks, table, start, n, ssm=st, slots=jnp.asarray([0]),
        use_pallas=False))
    step = jax.jit(lambda p, c, st, tok, table, lens: tfm.decode_step_paged(
        p, CFG, c, tok, table, lens, jnp.asarray([True]), use_pallas=False,
        ssm=st))
    with jax.default_matmul_precision("highest"):
        cache = tfm.PagedKVCache.empty(CFG, 64, page)
        assert cache.pages.shape == (1, 64, 2, 1, page, 32)
        table = jnp.asarray(
            1 + np.arange(CFG.period * M).reshape(CFG.period, 1, M), jnp.int32)
        st = tfm.SSMState.empty(CFG, 1)
        for c0 in range(0, n0, page):
            n = min(page, n0 - c0)
            toks = np.zeros((1, page), np.int32)
            toks[0, :n] = ids[c0 : c0 + n]
            cache, st = extend(
                params, cache, st, jnp.asarray(toks), table,
                jnp.asarray([c0]), jnp.asarray([n]))
        got, lens = [], jnp.asarray([n0])
        for t in range(n0, 45):
            logits, cache, lens, st = step(
                params, cache, st, jnp.asarray(ids[t : t + 1]), table, lens)
            got.append(jax.nn.log_softmax(logits)[0, ids[t + 1]])
    np.testing.assert_allclose(np.asarray(got), want[n0:], atol=TOL)


def _weights_read(fn, params):
    """The leaves of ``params`` (their paths as strings) that any equation
    of ``fn(params)``'s jaxpr takes: what the traced program reads."""
    closed = jax.make_jaxpr(fn)(params)
    read = {v for eqn in closed.jaxpr.eqns for v in eqn.invars
            if isinstance(v, jax.extend.core.Var)}
    paths = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    assert len(paths) == len(closed.jaxpr.invars)
    return {p for p, v in zip(paths, closed.jaxpr.invars) if v in read}


@pytest.mark.parametrize("stack", ["gmu_layers", "cross_layers"])
def test_admission_reads_no_weight_of_the_cross_decoder(params, stack):
    """Admission stops behind the last layer that writes a cache or a
    state (the test above holds what it wrote to the reference): its
    program takes no weight of a gated memory unit or a cross-attention
    layer, and every weight of the layers before them; the decode step
    takes them all."""
    toks = jnp.zeros((1, 8), jnp.int32)
    table = jnp.zeros((CFG.period, 1, 8), jnp.int32)
    zero = jnp.zeros((1,), jnp.int32)

    def admit(p):
        return tfm.extend_paged_kv(
            p, CFG, tfm.PagedKVCache.empty(CFG, 16, 8), toks, table, zero,
            jnp.full((1,), 8, jnp.int32), ssm=tfm.SSMState.empty(CFG, 1),
            slots=zero)

    def decode(p):
        return tfm.decode_step_paged(
            p, CFG, tfm.PagedKVCache.empty(CFG, 16, 8), zero, table,
            jnp.full((1,), 8, jnp.int32), jnp.asarray([True]),
            use_pallas=False, ssm=tfm.SSMState.empty(CFG, 1))

    def of(paths, tree):
        return {p for p in paths if p.startswith(f"['{tree}']")}

    every = {jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]}
    admitted, decoded = _weights_read(admit, params), _weights_read(decode, params)
    assert of(every, stack) and not of(admitted, stack)
    assert of(decoded, stack) == of(every, stack)
    for writers in ("ssm_layers", "layers"):
        assert of(admitted, writers) == of(every, writers)


# ---- the Mamba-1 recurrence ------------------------------------------- #


def _mixer_params(params, layer=0):
    return jax.tree.map(lambda a: a[layer], params["ssm_layers"]["ssm"])


@pytest.mark.parametrize("pieces", [(40,), (1,) * 40, (7, 16, 3, 14), (33, 7)],
                         ids=str)
def test_chunk_form_is_the_one_token_form_at_any_chunking(params, pieces):
    """The carried recurrent and convolution state: a prompt handed over a
    piece at a time, and one token a step, compute one function."""
    p = _mixer_params(params)
    h = jax.random.normal(jax.random.key(7), (2, 40, 64))
    with jax.default_matmul_precision("highest"):
        want, st, mem = ssm_ops.mixer_chunk(
            CFG, p, h, jnp.broadcast_to(jnp.arange(40), (2, 40)), memory=True)
        shapes = ssm_ops.state_shapes(CFG, 2)
        state = (jnp.zeros(shapes[0][1:]), jnp.zeros(shapes[1][1:]))
        outs, mems, at = [], [], 0
        for n in pieces:
            pos = jnp.broadcast_to(jnp.arange(at, at + n), (2, n))
            if n == 1:
                out, state, y = ssm_ops.mixer_step(
                    CFG, p, h[:, at], state, memory=True)
                out, y = out[:, None], y[:, None]
            else:
                out, state, y = ssm_ops.mixer_chunk(
                    CFG, p, h[:, at : at + n], pos, state, memory=True)
            outs.append(out), mems.append(y)
            at += n
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, atol=1e-5)
    np.testing.assert_allclose(jnp.concatenate(mems, 1), mem, atol=1e-5)
    for a, b in zip(state, st):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_chunk_form_resets_at_a_packed_document_and_skips_padding(params):
    p = _mixer_params(params, 1)
    h = jax.random.normal(jax.random.key(8), (1, 30, 64))
    pos = jnp.concatenate([jnp.arange(18), jnp.arange(12)])[None]
    with jax.default_matmul_precision("highest"):
        packed, _ = ssm_ops.mixer_chunk(CFG, p, h, pos)
        first, st = ssm_ops.mixer_chunk(
            CFG, p, h[:, :18], pos[:, :18])
        second, _ = ssm_ops.mixer_chunk(CFG, p, h[:, 18:], pos[:, 18:])
        # 7 tokens of padding behind the 18 leave the state as it is
        padded = jnp.pad(h[:, :18], ((0, 0), (0, 7), (0, 0)))
        _, st_pad = ssm_ops.mixer_chunk(
            CFG, p, padded, jnp.arange(25)[None], n_valid=jnp.asarray([18]))
    np.testing.assert_allclose(packed[:, :18], first, atol=1e-6)
    np.testing.assert_allclose(packed[:, 18:], second, atol=1e-6)
    for a, b in zip(st, st_pad):
        np.testing.assert_allclose(a, b, atol=1e-7)


def test_one_token_form_leaves_an_inactive_row_s_state(params):
    p = _mixer_params(params)
    shapes = ssm_ops.state_shapes(CFG, 2)
    state = (jnp.ones(shapes[0][1:]), jnp.ones(shapes[1][1:]))
    _, new = ssm_ops.mixer_step(
        CFG, p, jnp.ones((2, 64)), state, jnp.asarray([True, False]))
    for a, b in zip(state, new):
        assert not np.allclose(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])


def test_state_layout_is_pr_42_s(params):
    """Channels on the lanes, ``N`` on the sublanes, ``A`` laid out as the
    state: ``[B, 1, K, N, lanes]``."""
    assert ssm_ops.state_shapes(CFG, 3) == ((3, 3, 1, 1, 4, 128), (3, 3, 384))
    full = FAMILY.config_from_hf({
        **ARCH, "hidden_size": 2560, "num_attention_heads": 40,
        "num_key_value_heads": 20, "mamba_d_state": 16, "mamba_dt_rank": "auto"})
    assert ssm_ops.state_shapes(full, 2)[0] == (3, 2, 1, 40, 16, 128)
    assert ssm_ops._s6_a(_mixer_params(params)).shape == (1, 4, 128)


# ---- differential attention over the packed rows ---------------------- #


def test_pair_combine_over_packed_rows_is_the_width_64_computation(params):
    """``_pack_qkv`` + one softmax a packed query + ``_diff_combine``
    against the reference's unpacked form (a pair's scores at width D, the
    value ``[v1 ; v2]``), on 8 query / 4 kv heads so that the map from a
    query head to its kv head is not the identity: original query heads
    ``4 m, 4 m + 2`` read kv head ``2 m`` and ``4 m + 1, 4 m + 3`` read ``2 m
    + 1``, which plain grouped-query attention (``h // 2``) does not."""
    cfg = dataclasses.replace(CFG, n_q_heads=8, n_kv_heads=4, head_dim=8)
    T, D = 12, 8
    ks = jax.random.split(jax.random.key(9), 8)
    q = jax.random.normal(ks[0], (T, 8, D))
    k = jax.random.normal(ks[1], (T, 4, D))
    v = jax.random.normal(ks[2], (T, 4, D))
    a = {name: 0.4 * jax.random.normal(kk, (D,))
         for name, kk in zip(tfm._LAMBDAS, ks[3:7])}
    a["subln"] = 1 + 0.1 * jax.random.normal(ks[7], (2 * D,))
    want = ref._diff_attention(
        q, k, v, a, jnp.ones((T,), bool), None, ref.lambda_init(5), eps=1e-5,
        dtype=jnp.float32, lam_zero=False)
    qp, kp, vp = tfm._pack_qkv(cfg, q, k, v)
    assert qp.shape == (T, 8, 2 * D) and kp.shape == vp.shape == (T, 2, 2 * D)
    rows = jnp.repeat(kp, 4, axis=1), jnp.repeat(vp, 4, axis=1)
    s = jnp.einsum("thd,shd->hts", qp, rows[0]) * D ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    ctx = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), rows[1])
    got = tfm._unpack_ctx(cfg, ctx, {**a, "index": jnp.int32(5)})
    np.testing.assert_allclose(got.reshape(T, -1), want, atol=1e-5)
    # the map itself, from which half of which row a packed query reads
    part = np.asarray(tfm._row_part(cfg, jnp.float32)).argmax(-1)
    kv_head = [2 * (h // 4) + part[h] for h in range(8)]
    assert kv_head == [0, 1, 0, 1, 2, 3, 2, 3]
    # plain attention in the difference's place is another function
    plain = ref._diff_attention(
        q, k, v, a, jnp.ones((T,), bool), None, ref.lambda_init(5), eps=1e-5,
        dtype=jnp.float32, lam_zero=True)
    assert np.abs(np.asarray(plain) - np.asarray(want)).max() > 0.05


def test_lambda_s_constant_follows_the_layer_s_place_in_the_model():
    np.testing.assert_allclose(
        tfm.diff_lambda_init(jnp.arange(32)),
        [0.8 - 0.6 * np.exp(-0.3 * l) for l in range(32)], rtol=1e-6)
    # the place :func:`_scan_plan` hands a layer is its index in the MODEL
    assert CFG.layer_ids == {
        "ssm": [0, 2, 4], "attn": [1, 3, 5], "gmu": [6], "cross": [7]}


# ---- the reference's controls ----------------------------------------- #


@pytest.mark.parametrize("control", [
    {"control_no_window": True}, {"control_lambda_zero": True},
    {"control_zero_state_at": 16}, {"control_state_dtype": "bfloat16"},
], ids=lambda c: next(iter(c)))
def test_reference_controls_compute_another_function(params, control):
    ids = _ids(11, 40)
    if "control_state_dtype" in control:
        # (the log-probabilities cannot tell a 16-bit state at this size:
        # the state itself can)
        a, b = (ref.recurrent_state(params, arch, list(ids), "float32", 40)
                for arch in (ARCH, dict(ARCH, **control)))
        assert np.abs(a - b).max() / np.abs(a).max() > 1e-3
        return
    moved = np.abs(_want(params, ids, dict(ARCH, **control))
                   - _want(params, ids))
    assert moved.max() > 1e-4
    if "control_zero_state_at" in control:
        assert moved[:15].max() == 0 and moved[16:].max() > 1e-4


def test_the_cell_s_weights_a_stack_at_once_are_drawn_as_the_tree_s():
    """``rollout_yoco_inproc._make_weights`` (a program a top-level stack,
    built side by side) draws every leaf as ``weights.make_weights`` draws
    it in the whole tree: a norm's gain about 1 (the FINAL norm's too,
    whose path is its stack's name and nothing else), the rest about 0;
    two stacks draw from keys of their own."""
    from benchmark import sut, weights
    from benchmark.drivers import rollout_yoco_inproc as drv

    shapes = sut.weight_shapes(CFG, CFG.dtype)
    made = drv._make_weights(shapes, 4_800_000_123, jnp.float32)
    whole = weights.make_weights(shapes, 4_800_000_123, jnp.float32)
    assert jax.tree.structure(made) == jax.tree.structure(whole)
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(made)[0],
            jax.tree.leaves(whole)):
        assert a.shape == b.shape and a.dtype == b.dtype
        gain = weights._kind(path) == "gain"
        assert abs(float(a.mean()) - gain) < 0.1, jax.tree_util.keystr(path)
        assert abs(float(b.mean()) - gain) < 0.1
    ln1 = [made[t]["ln1"]["weight"] for t in ("layers", "cross_layers")]
    assert np.abs(np.asarray(ln1[0][0] - ln1[1][0])).max() > 1e-3


def test_reference_builds_its_programs_ahead_and_meets_them_again(params):
    """``build_ahead`` at a length and in the dtypes of a check builds
    every program the forwards there are made of (a kind of layer each,
    the head, the state-space layer that rounds its state): the forwards
    after it, the controls too (window, full and ``lambda`` = 0 are
    arguments of ONE attention program), build none, and read what they
    read without it."""
    from areal_tpu.base import jitcache

    ids, pad = list(_ids(21, 30)), 48
    before = _want(params, ids)
    layers = (ref._ssm_layer, ref._attn_layer, ref._gmu_layer,
              ref._cross_layer, ref._head_logprobs)
    ref.build_ahead(params, ARCH, ("float32", "bfloat16"), pad,
                    state_dtype="bfloat16")
    built = jitcache.total_cache_size(layers)
    for arch in (ARCH, dict(ARCH, control_no_window=True),
                 dict(ARCH, control_lambda_zero=True),
                 dict(ARCH, control_zero_state_at=16)):
        for dtype in ("float32", "bfloat16"):
            got = ref.next_token_logprobs(params, arch, ids, dtype, pad)[0]
            if arch is ARCH and dtype == "float32":
                np.testing.assert_allclose(got, before, atol=1e-6)
    ref.recurrent_state(params, dict(ARCH, control_state_dtype="bfloat16"),
                        ids, "float32", pad, n_layers=1)
    assert jitcache.total_cache_size(layers) == built


def test_reference_state_is_the_dense_cache_s(params):
    ids = _ids(12, 24)
    with jax.default_matmul_precision("highest"):
        cache = tfm.KVCache.empty(CFG, 1, 32)
        _, cache = tfm.prefill(
            params, CFG, cache, jnp.asarray(ids[None]), jnp.asarray([24]))
    want = ref.recurrent_state(params, ARCH, list(ids), "float32", 24)
    got = np.asarray(cache.ssm.ssm[:, 0, 0])          # [Ls, K, N, lanes]
    got = got.transpose(0, 1, 3, 2).reshape(want.shape)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    first = ref.recurrent_state(
        params, ARCH, list(ids), "float32", 24, n_layers=1)
    np.testing.assert_array_equal(first[0], want[0])


# ---- HF names --------------------------------------------------------- #


def test_hf_names_round_trip(params):
    sd = FAMILY.params_to_hf(jax.tree.map(np.asarray, params), CFG)
    E, F, C = 64, 96, 128
    want = {
        "model.embed_tokens.weight": (128, E),
        "model.final_layernorm.weight": (E,),
        "model.final_layernorm.bias": (E,),
        "model.layers.0.attn.in_proj.weight": (2 * C, E),
        "model.layers.0.attn.conv1d.weight": (C, 1, 4),
        "model.layers.0.attn.conv1d.bias": (C,),
        "model.layers.0.attn.x_proj.weight": (4 + 2 * 4, C),
        "model.layers.0.attn.dt_proj.weight": (C, 4),
        "model.layers.0.attn.dt_proj.bias": (C,),
        "model.layers.0.attn.A_log": (C, 4),
        "model.layers.0.attn.D": (C,),
        "model.layers.0.attn.out_proj.weight": (E, C),
        "model.layers.1.attn.Wqkv.weight": (E + 2 * 32, E),
        "model.layers.1.attn.Wqkv.bias": (E + 2 * 32,),
        "model.layers.5.attn.out_proj.weight": (E, E),
        "model.layers.5.attn.out_proj.bias": (E,),
        "model.layers.5.attn.subln.weight": (32,),
        "model.layers.5.attn.lambda_q1": (16,),
        "model.layers.5.attn.lambda_k2": (16,),
        "model.layers.6.attn.in_proj.weight": (C, E),
        "model.layers.6.attn.out_proj.weight": (E, C),
        "model.layers.7.attn.Wqkv.weight": (E, E),
        "model.layers.7.attn.Wqkv.bias": (E,),
        "model.layers.7.attn.lambda_q2": (16,),
        "model.layers.7.mlp.fc1.weight": (2 * F, E),
        "model.layers.7.mlp.fc2.weight": (E, F),
        "model.layers.7.input_layernorm.bias": (E,),
        "model.layers.7.post_attention_layernorm.weight": (E,),
    }
    for name, shape in want.items():
        assert sd[name].shape == shape, name
    assert "lm_head.weight" not in sd
    assert not any(".7.attn.conv1d" in k or ".6.attn.Wqkv" in k for k in sd)
    back = FAMILY.params_from_hf(sd, CFG)
    flat_a = jax.tree_util.tree_leaves_with_path(jax.tree.map(np.asarray, params))
    flat_b = jax.tree_util.tree_leaves_with_path(back)
    assert [p for p, _ in flat_a] == [p for p, _ in flat_b]
    for (path, a), (_, b) in zip(flat_a, flat_b):
        np.testing.assert_array_equal(a, b, err_msg=str(path))
    # gate first in fc1, x before z in in_proj, q before k before v
    np.testing.assert_array_equal(
        sd["model.layers.7.mlp.fc1.weight"][:F].T,
        np.asarray(params["cross_layers"]["mlp"]["w_gate"][0]))
    np.testing.assert_array_equal(
        sd["model.layers.0.attn.in_proj.weight"][:C].T,
        np.asarray(params["ssm_layers"]["ssm"]["w_x"][0]))
    np.testing.assert_array_equal(
        sd["model.layers.1.attn.Wqkv.weight"][E : E + 32].T,
        np.asarray(params["layers"]["attn"]["wk"][0]))


def test_param_axes_have_the_tree_s_structure():
    shapes = jax.eval_shape(lambda: tfm.init_params(CFG, jax.random.key(0)))
    axes = tfm.param_logical_axes(CFG)
    is_axes = lambda x: isinstance(x, tuple)        # noqa: E731
    assert jax.tree.structure(jax.tree.map(lambda x: 0, shapes)) == (
        jax.tree.structure(jax.tree.map(lambda x: 0, axes, is_leaf=is_axes)))
    for s, a in zip(jax.tree.leaves(shapes),
                    jax.tree.leaves(axes, is_leaf=is_axes)):
        assert len(s.shape) == len(a)
