"""Plain reference for the solar_open2 family (Upstage's Solar Open 2):
two-branch blocks whose mixer is softmax attention WITHOUT positions in
every fourth layer and gated delta-rule linear attention (Kimi Delta
Attention's layout) in the others, and whose second branch is an expert
layer in EVERY block.

The forward pass in straightforward ``jax.numpy``: a Python loop over the
layers; an attention layer is gated grouped-query attention, dense and
causal over the whole sequence; a linear layer is the recurrence run as a
plain ``lax.scan`` over the TOKENS, one state update a token, float32
state; an expert layer scores all the experts, keeps ``num_experts_per_tok``
and runs the HELD ones a row chose one expert at a time: no chunks, no
cache, no batching, no kernel. Independent of the program's model code: it
shares only the NAMES of the weight tree (``embed.weight`` [V,E];
``layers`` the attention layers in the order they run: ``ln1.weight``,
``attn.{wq,wk,wv,wo,wg}``, ``ln2.weight``, ``mlp``; ``kda_layers`` the
linear layers: ``ln1.weight``, ``kda.{w_qkv [E, q+k+v], conv_w [taps, q+k+v],
w_fa [E,R], w_fb [R,HD], dt_bias [HD], A_log [H], w_beta [E,H], w_ga, w_gb,
o_norm [D], wo [HD,E]}``, ``ln2.weight``, ``mlp``; ``mlp.{router [E,X],
b_router [X], w_gate, w_up [Xh,E,F], w_down [Xh,F,E], shared_gate,
shared_up [E,Fs], shared_down [Fs,E]}``; ``final_ln.weight``;
``head.weight`` [E,V], untied; matrices stored input-major, ``y = x @ w``).

    x = E[token]
    x = x + mixer_l(rms(x) w1);  x = x + moe(rms(x) w2)     every block
    logits = rms_f(x) W_head

    linear layer (H heads of D, a state S of D x D a head, float32):
        q, k, v = silu(conv4(Wq a)), silu(conv4(Wk a)), silu(conv4(Wv a))
        q = l2norm_head(q) * D ** -0.5;  k = l2norm_head(k)
        g = -exp(A_log[h]) * softplus(Wfb (Wfa a) + dt_bias)        [H, D]
        beta = 2 * sigmoid(Wb a)                                    [H]
        S <- diag(exp(g)) S;  S <- S + beta k (v - S^T k)^T;  o = S^T q
        out = Wo (rms_head(o) w_o * sigmoid(Wgb (Wga a)))
    attention layer: q, k, v, o without bias, grouped queries, causal,
        scale head_dim ** -0.5, NO positions; out = Wo (ctx * sigmoid(Wg a))
    expert layer: s = sigmoid(Wr u) in float32;  chosen = top-k of s + b
        w_j = scale * s_j / (sum over the k chosen of s + 1e-20)
        out = sum_{j chosen AND held} w_j W2_j (silu(W1_j u) * W3_j u)
              + S2 (silu(S1 u) * S3 u)                      shared expert

THE SHARE (``n_routed_experts`` the experts HELD, ``expert_parallel_size``
ranks, ``expert_parallel_rank``): as ``benchmark/reference/nemotron_h.py``
states it. The routed sum runs over the chosen experts held here; nothing
stands in for the other ranks.

What the published config does not say is ASSUMED, one constant each,
below; a correction is one line here and one in ``areal_tpu/ops/kda.py``,
``ops/moe.py`` or ``models/hf.py``.

Departures from the published description, all stated: the share above
(the configuration file's ``deployment``). And, to fit beside a model that
fills the chip, none of which changes the mathematics: one layer at a time
is cast from the stored dtype to the compute dtype, the experts one at a
time; attention runs in blocks of queries; the head is applied in
vocabulary blocks with a running log-sum-exp. In float32 it runs under
``jax.default_matmul_precision("highest")``.

Stand-ins for a faulty program, for the benchmark's controls (keys of
``arch`` that no published config has): ``control_zero_state_at`` (a
position: the state of every linear layer is dropped before that token is
read), ``control_state_dtype`` (the state is rounded to that dtype after
every token), ``control_norm_over_held`` (the combine weights normalised
over the chosen experts HELD here), ``control_beta_without_two`` (``beta =
sigmoid``: the sign extension forgotten) and ``control_decay_a_head`` (ONE
decay a head, the mean of its channels' log-decays, in place of a decay a
channel). ``forced_routing`` ``[layers, T, k]`` int: the experts a row
chose in each layer, -1 (in slot 0) where the router runs free; the
weights are still this router's scores of them.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

# (a) the router scores by sigmoid and chooses by score + a correction
# bias that never enters the weights (the solar_open / GLM-MoE family's)
ROUTER_SIGMOID_WITH_SELECTION_BIAS = True
# (a) the weights' normalisation adds 1e-20 to the sum
NORM_EPS = 1e-20
# (a) the router reads the block's normed residual in float32
ROUTER_DTYPE = jnp.float32
# (a) the attention gate is a channel-wise sigmoid of a SEPARATE projection
# of the layer's normed input, on the heads' context before o_proj
ATTN_GATE_IS_SIGMOID_OF_OWN_PROJECTION = True
# (a) attention has no q/k norm and no positional encoding (``use_rope``
# false; ``rope_theta`` and ``partial_rotary_factor`` shape nothing)
ATTN_QK_NORM = False
# (a) the convolutions carry no bias and act BEFORE the silu
CONV_BIAS = False
# (a) ``conv_w[k]`` weighs the input ``K - 1 - k`` tokens back
CONV_LAST_TAP_IS_CURRENT = True
# (a) the decay is applied to the state BEFORE the delta step reads it
DECAY_BEFORE_DELTA = True
# (a) x / sqrt(sum x^2 + eps) over a head's channels
L2_EPS = 1e-6
# (a) the output norm's epsilon is the model's ``rms_norm_eps``
O_NORM_EPS_IS_MODEL_EPS = True
# (a) the rank-128 pairs (f_a/f_b, g_a/g_b) carry no bias; dt_bias is the
# decay's only one
GATE_PROJECTIONS_WITHOUT_BIAS = True
# the recurrent state is float32 whatever the compute dtype
STATE_DTYPE = jnp.float32

_VOCAB_BLOCK = 16384
_QUERY_BLOCK = 512
_BUILD_THREADS = 8


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


@functools.partial(jax.jit, static_argnames=("n_q", "n_kv", "eps", "dtype"))
def _attn_mixer(x, lp, valid, *, n_q, n_kv, eps, dtype):
    lp = jax.tree.map(lambda a: a.astype(dtype), lp)
    T = x.shape[0]
    a = lp["attn"]
    h = _rms(x, lp["ln1"]["weight"], eps)
    q = (h @ a["wq"]).reshape(T, n_q, -1)             # no positions at all
    k = (h @ a["wk"]).reshape(T, n_kv, -1)
    v = (h @ a["wv"]).reshape(T, n_kv, -1)
    scale = q.shape[-1] ** -0.5
    rep = n_q // n_kv
    kk, vv = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    pos = jnp.arange(T)
    out = []
    for lo in range(0, T, _QUERY_BLOCK):
        qb = q[lo : lo + _QUERY_BLOCK]
        s = jnp.einsum("thd,shd->hts", qb, kk).astype(jnp.float32) * scale
        ok = (pos[None, :] <= pos[lo : lo + _QUERY_BLOCK, None]) & valid[None]
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hts,shd->thd", p.astype(dtype), vv))
    ctx = jnp.concatenate(out).reshape(T, -1)
    if "wg" in a:
        gate = jax.nn.sigmoid((h @ a["wg"]).astype(jnp.float32))
        ctx = ctx * gate.astype(dtype)
    return x + ctx @ a["wo"]


@functools.partial(
    jax.jit,
    static_argnames=("n_heads", "d_head", "eps", "dtype", "round_to",
                     "beta_scale", "decay_a_head"))
def _kda_mixer(x, lp, zero_at, keep_at, *, n_heads, d_head, eps, dtype,
               round_to, beta_scale, decay_a_head):
    """The layer's mixer branch added to ``x``, and its recurrent state
    after token ``keep_at`` (zeros where no token is)."""
    lp = jax.tree.map(lambda a: a.astype(dtype), lp)
    m = lp["kda"]
    T = x.shape[0]
    H, D = n_heads, d_head
    h = _rms(x, lp["ln1"]["weight"], eps)
    qkv = h @ m["w_qkv"]
    K = m["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[1]), qkv.dtype), qkv])
    conv = sum(
        padded[k : k + T].astype(jnp.float32)
        * m["conv_w"][k if CONV_LAST_TAP_IS_CURRENT else K - 1 - k].astype(
            jnp.float32)
        for k in range(K))
    qkv = jax.nn.silu(conv).astype(dtype).astype(jnp.float32)
    q, k, v = (a.reshape(T, H, D) for a in jnp.split(qkv, 3, axis=-1))
    q, k = _l2norm(q) * D ** -0.5, _l2norm(k)
    f = ((h @ m["w_fa"]) @ m["w_fb"]).astype(jnp.float32)
    g = -jnp.exp(m["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
        f + m["dt_bias"].astype(jnp.float32)).reshape(T, H, D)
    if decay_a_head:
        g = jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape)
    beta = beta_scale * jax.nn.sigmoid((h @ m["w_beta"]).astype(jnp.float32))
    hi = jax.lax.Precision.HIGHEST

    def token(carry, inp):
        S, kept = carry
        t, q_t, k_t, v_t, g_t, b_t = inp
        S = jnp.where(t == zero_at, 0.0, S)
        if DECAY_BEFORE_DELTA:
            S = jnp.exp(g_t)[:, :, None] * S
        u = jnp.einsum("hkv,hk->hv", S, k_t, precision=hi)
        S = S + b_t[:, None, None] * k_t[:, :, None] * (v_t - u)[:, None, :]
        if not DECAY_BEFORE_DELTA:
            S = jnp.exp(g_t)[:, :, None] * S
        if round_to is not None:
            # (not a cast there and back: the compiler drops such a pair)
            info = jnp.finfo(round_to)
            S = jax.lax.reduce_precision(S, info.nexp, info.nmant)
        o = jnp.einsum("hkv,hk->hv", S, q_t, precision=hi)
        return (S, jnp.where(t == keep_at, S, kept)), o

    zeros = jnp.zeros((H, D, D), STATE_DTYPE)
    (_, kept), o = jax.lax.scan(
        token, (zeros, zeros), (jnp.arange(T), q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = o * m["o_norm"].astype(jnp.float32)
    gate = jax.nn.sigmoid(((h @ m["w_ga"]) @ m["w_gb"]).astype(jnp.float32))
    y = (o.reshape(T, H * D) * gate).astype(dtype)
    return x + y @ m["wo"], kept


def _route(h, router, bias, forced, *, top_k):
    """h [T, E] -> (the router's own choice [T, K], the experts used [T, K],
    their scores [T, K]); ``forced`` [T, K] is used in the choice's place
    where its first entry is not -1."""
    logits = h.astype(ROUTER_DTYPE) @ router.astype(ROUTER_DTYPE)
    s = jax.nn.sigmoid(logits)
    _, own = jax.lax.top_k(s + bias.astype(ROUTER_DTYPE), top_k)
    idx = jnp.where(forced[:, :1] >= 0, forced, own)
    return own, idx, jnp.take_along_axis(s, idx, axis=-1)   # WITHOUT the bias


@functools.partial(jax.jit, static_argnames=(
    "top_k", "norm_topk", "scale", "first", "eps", "dtype", "norm_over_held"))
def _moe_branch(x, stack, j, forced, *, top_k, norm_topk, scale, first, eps,
                dtype, norm_over_held):
    """The expert layer of entry ``j`` of ``stack`` (a WHOLE stack of one
    kind of layer in the stored dtype: each expert's three matrices are
    cut out and cast inside the loop). Returns ``(x, the router's own
    choice [T, K], routed part [T, E], shared part [T, E])``."""
    m = stack["mlp"]
    at = lambda name: m[name][j]                    # small leaves only
    h = _rms(x, stack["ln2"]["weight"][j].astype(dtype), eps)
    own, idx, w = _route(h, at("router"), at("b_router"), forced, top_k=top_k)
    n_held = m["w_up"].shape[1]
    held = (idx >= first) & (idx < first + n_held)
    if norm_topk:
        total = jnp.where(held, w, 0.0) if norm_over_held else w
        w = w / (total.sum(axis=-1, keepdims=True) + NORM_EPS)
    w = jnp.where(held, w * scale, 0.0)
    rows = jnp.arange(h.shape[0])[:, None]
    # [T, Xh]: a chosen expert of another rank lands past the end, dropped
    combine = jnp.zeros((h.shape[0], n_held), jnp.float32).at[
        rows, jnp.where(held, idx - first, n_held)].add(
            w, mode="drop").astype(dtype)

    def one_expert(acc, e):
        gate, up, down = (
            m[k][j, e].astype(dtype) for k in ("w_gate", "w_up", "w_down"))
        y = (jax.nn.silu(h @ gate) * (h @ up)) @ down
        return acc + y * combine[:, e][:, None], None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(n_held))
    shared = jnp.zeros_like(x)
    if "shared_up" in m:
        shared = (
            jax.nn.silu(h @ at("shared_gate").astype(dtype))
            * (h @ at("shared_up").astype(dtype))
        ) @ at("shared_down").astype(dtype)
    return x + routed + shared, own, routed, shared


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head_logprobs(x, final_w, head_w, labels, *, eps, dtype):
    """log p(labels[t] | ..t) from hidden x [T, E]; the untied head [E, V]
    applied in vocabulary blocks. Returns (logprob of label, max logprob)."""
    h = _rms(x, final_w.astype(dtype), eps)
    V = head_w.shape[1]
    lse = jnp.full((x.shape[0],), -jnp.inf, jnp.float32)
    top = jnp.full((x.shape[0],), -jnp.inf, jnp.float32)
    picked = jnp.zeros((x.shape[0],), jnp.float32)
    for lo in range(0, V, _VOCAB_BLOCK):
        hi = min(lo + _VOCAB_BLOCK, V)
        logits = (h @ head_w[:, lo:hi].astype(dtype)).astype(jnp.float32)
        lse = jnp.logaddexp(lse, jax.nn.logsumexp(logits, axis=-1))
        top = jnp.maximum(top, logits.max(axis=-1))
        inside = (labels >= lo) & (labels < hi)
        idx = jnp.clip(labels - lo, 0, hi - lo - 1)
        here = jnp.take_along_axis(logits, idx[:, None], axis=-1)[:, 0]
        picked = jnp.where(inside, here, picked)
    return picked - lse, top - lse


def _refuse(arch: dict):
    lin = arch["linear_attn_config"]
    for key, bad in (
            ("kda_use_full_proj", bool(arch.get("kda_use_full_proj"))),
            ("linear_attn_config.num_kv_heads",
             lin.get("num_kv_heads") is not None),
            ("use_rope", bool(arch.get("use_rope"))),
            ("first_k_dense_replace",
             int(arch.get("first_k_dense_replace", 0) or 0) > 0)):
        if bad:
            raise ValueError(f"solar_open2: {key} is not supported")


def _kinds(arch: dict):
    """'attn' or 'kda' a layer (``gqa_layers``)."""
    gqa = set(arch["gqa_layers"])
    return ["attn" if l in gqa else "kda"
            for l in range(arch["num_hidden_layers"])]


def _statics(arch: dict, dt):
    _refuse(arch)
    eps = float(arch["rms_norm_eps"])
    lin = arch["linear_attn_config"]
    round_to = arch.get("control_state_dtype")
    two = 2.0 if arch.get("kda_allow_neg_eigval") else 1.0
    return dict(
        attn=dict(n_q=arch["num_attention_heads"],
                  n_kv=arch["num_key_value_heads"], eps=eps, dtype=dt),
        kda=dict(n_heads=lin["num_heads"], d_head=lin["head_dim"], eps=eps,
                 dtype=dt,
                 round_to=None if round_to is None else jnp.dtype(round_to),
                 beta_scale=1.0 if arch.get("control_beta_without_two") else two,
                 decay_a_head=bool(arch.get("control_decay_a_head"))),
        moe=dict(top_k=arch["num_experts_per_tok"],
                 norm_topk=bool(arch.get("norm_topk_prob", True)),
                 scale=float(arch.get("routed_scaling_factor", 1.0)),
                 first=int(arch.get("expert_parallel_rank", 0))
                 * arch["n_routed_experts"],
                 eps=eps, dtype=dt,
                 norm_over_held=bool(arch.get("control_norm_over_held"))),
    )


_TREES = {"attn": "layers", "kda": "kda_layers"}


def _stack(params, arch: dict, ids, valid, dt, keep_at=-1, n_states=None):
    """The layers in order: ``(hidden [T, E], every linear layer's state
    after token ``keep_at``, every layer's router's own choice [layers][T,
    k])``; with ``n_states`` the stack ends behind that many linear layers'
    MIXERS (hidden None)."""
    kw = _statics(arch, dt)
    zero_at = jnp.int32(arch.get("control_zero_state_at", -1))
    keep_at, states, chosen = jnp.int32(keep_at), [], []
    forced = arch.get("forced_routing")
    T, k = ids.shape[0], arch["num_experts_per_tok"]
    x = params["embed"]["weight"][ids].astype(dt)
    at = dict.fromkeys(_TREES.values(), 0)
    for l, kind in enumerate(_kinds(arch)):
        tree = _TREES[kind]
        j = at[tree]
        at[tree] += 1
        lp = {name: jax.tree.map(lambda a: a[j], params[tree][name])
              for name in ("ln1", kind)}
        if kind == "attn":
            x = _attn_mixer(x, lp, valid, **kw["attn"])
        else:
            x, kept = _kda_mixer(x, lp, zero_at, keep_at, **kw["kda"])
            states.append(kept)
            if len(states) == n_states:
                return None, states, chosen
        given = jnp.full((T, k), -1, jnp.int32)
        if forced is not None:
            f = np.asarray(forced[l], np.int32)
            given = given.at[: f.shape[0]].set(f)
        x, idx, _, _ = _moe_branch(
            x, {n: params[tree][n] for n in ("ln2", "mlp")}, jnp.int32(j),
            given, **kw["moe"])
        chosen.append(idx)
    return x, states, chosen


def _forward(params, arch: dict, ids, valid, dt, **kw):
    """``(log-prob of the next token, largest log-prob)`` a position (None
    where the stack ended early), and :func:`_stack`'s states and choices."""
    x, states, chosen = _stack(params, arch, ids, valid, dt, **kw)
    if x is None:
        return None, states, chosen
    labels = jnp.concatenate([ids[1:], ids[:1]])
    return _head_logprobs(
        x, params["final_ln"]["weight"], params["head"]["weight"], labels,
        eps=float(arch["rms_norm_eps"]), dtype=dt), states, chosen


def _run(params, arch, tokens, dtype, pad_to, **kw):
    dt = jnp.dtype(dtype)
    n = len(tokens)
    pad_to = max(pad_to, n)
    ids = np.zeros((pad_to,), np.int32)
    ids[:n] = tokens
    valid = jnp.asarray(np.arange(pad_to) < n)
    precision = "highest" if dt == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        return _forward(params, arch, jnp.asarray(ids), valid, dt, **kw)


def next_token_logprobs(params, arch: dict, tokens, dtype: str, pad_to: int):
    """``tokens``: one sequence of ids. Returns float32 numpy arrays of
    length len(tokens)-1: log p(tokens[t+1] | tokens[..t]) and the largest
    log-probability at that position."""
    n = len(tokens)
    (lp_tok, lp_max), _, _ = _run(params, arch, tokens, dtype, pad_to)
    lp_tok, lp_max = jax.device_get((lp_tok, lp_max))
    return np.asarray(lp_tok[: n - 1]), np.asarray(lp_max[: n - 1])


def routing(params, arch: dict, tokens, dtype: str, pad_to: int):
    """The experts each layer's router chose for each token, int32 numpy
    ``[layers, len(tokens), k]``: its OWN choice also where
    ``forced_routing`` put another in its place (the layers before it then
    ran on the forced ones)."""
    _, _, chosen = _run(params, arch, tokens, dtype, pad_to)
    return np.stack(jax.device_get(chosen))[:, : len(tokens)]


def recurrent_state(params, arch: dict, tokens, dtype: str, pad_to: int,
                    n_layers=None):
    """The state of every linear layer (or of the first ``n_layers`` of
    them: the forward then ends there) after ALL of ``tokens``, in the order
    the layers run: float32 numpy ``[layers, heads, Dk, Dv]``."""
    _, states, _ = _run(
        params, arch, tokens, dtype, pad_to, keep_at=len(tokens) - 1,
        n_states=n_layers)
    return np.stack(jax.device_get(states))


def build_ahead(params, arch: dict, dtypes, pad_to: int, state_dtype=None,
                controls=()):
    """Build every program that the forwards in ``dtypes`` at ``pad_to``
    are made of (each kind of mixer, the expert layer and the head; in
    float32 also those of ``controls``, keys of ``arch`` set true, and,
    with ``state_dtype``, the linear mixer that rounds its state to it),
    all at once on ``_BUILD_THREADS`` threads, each by one run on a
    sequence of token 0, as ``benchmark/reference/nemotron_h.py`` does and
    says why. Nothing here computes a result."""
    import concurrent.futures

    ids = jnp.zeros((pad_to,), jnp.int32)
    valid = jnp.ones((pad_to,), bool)
    free = jnp.full((pad_to, arch["num_experts_per_tok"]), -1, jnp.int32)
    none, j0 = jnp.int32(-1), jnp.int32(0)

    def first(tree, kind):
        return {name: jax.tree.map(lambda a: a[0], params[tree][name])
                for name in ("ln1", kind)}

    def calls(dt, **defect):
        kw = _statics(dict(arch, **defect), dt)
        x = params["embed"]["weight"][ids].astype(dt)
        kda = lambda: _kda_mixer(
            x, first("kda_layers", "kda"), none, none, **kw["kda"])
        moe = lambda: _moe_branch(
            x, {n: params["layers"][n] for n in ("ln2", "mlp")}, j0, free,
            **kw["moe"])
        moe_kda = lambda: _moe_branch(
            x, {n: params["kda_layers"][n] for n in ("ln2", "mlp")}, j0, free,
            **kw["moe"])
        if "control_norm_over_held" in defect:
            return [moe, moe_kda]
        if defect:
            return [kda]
        return [
            kda, moe, moe_kda,
            lambda: _attn_mixer(
                x, first("layers", "attn"), valid, **kw["attn"]),
            lambda: _head_logprobs(
                x, params["final_ln"]["weight"], params["head"]["weight"], ids,
                eps=kw["attn"]["eps"], dtype=dt)]

    def build(dt, call):
        # (the precision is a thread's own setting, and part of what a
        # compiled program is kept under)
        precision = "highest" if dt == jnp.float32 else "default"
        with jax.default_matmul_precision(precision):
            jax.block_until_ready(call())

    f32 = jnp.dtype("float32")
    todo = [(jnp.dtype(d), c) for d in dtypes for c in calls(jnp.dtype(d))]
    for key in controls:
        todo += [(f32, c) for c in calls(f32, **{key: True})]
    if state_dtype is not None:
        todo += [(f32, c) for c in calls(f32, control_state_dtype=state_dtype)]
    with concurrent.futures.ThreadPoolExecutor(_BUILD_THREADS) as pool:
        for done in [pool.submit(build, *t) for t in todo]:
            done.result()


def expert_layer_parts(params, arch: dict, x, tree: str = "layers",
                       entry: int = 0):
    """The expert layer of entry ``entry`` of ``tree`` alone on hidden
    states ``x [T, E]``, float32: ``(routed part, shared part)`` of its
    branch (the layer's output is ``x`` + both). For the test that ties a
    share to the model: the routed parts of all the ranks and the shared
    part ONCE add up to the uncut layer's branch."""
    kw = _statics(arch, jnp.dtype("float32"))
    T, k = x.shape[0], arch["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):
        _, _, routed, shared = _moe_branch(
            jnp.asarray(x, jnp.float32),
            {n: params[tree][n] for n in ("ln2", "mlp")}, jnp.int32(entry),
            jnp.full((T, k), -1, jnp.int32), **kw["moe"])
    return np.asarray(routed), np.asarray(shared)


def sequence_logits(params, arch: dict, ids):
    """Float32 logits ``[T, V]`` of one sequence (small sizes: the tests
    compare LOGITS)."""
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x, _, _ = _stack(
            params, arch, ids, jnp.ones(ids.shape, bool), jnp.dtype("float32"))
        h = _rms(x, params["final_ln"]["weight"].astype(jnp.float32),
                 float(arch["rms_norm_eps"]))
        return h @ params["head"]["weight"].astype(jnp.float32)
