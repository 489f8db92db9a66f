"""Plain reference for the granitemoehybrid family (Granite 4.0-H: state-
space layers beside attention layers without positions).

The forward pass in straightforward ``jax.numpy``: a Python loop over the
layers in ``layer_types`` order; an attention layer is grouped-query
attention with NO positional encoding and the softmax scale
``attention_multiplier``, dense and causal over the whole sequence; a
state-space layer is the published Mamba-2 recurrence run as a plain
``lax.scan`` over the TOKENS, one state update a token, float32 state:
no chunks, no cache, no batching. Independent of the program's model code
(``ops/ssm.py`` computes the same function in chunks): it shares only the
NAMES of the weight tree (``embed.weight`` [V,E]; ``layers`` the attention
layers in the order they run: ``{ln1,ln2}.weight``, ``attn.{wq,wk,wv,wo}``,
``mlp.{w_gate,w_up,w_down}``; ``ssm_layers`` the state-space layers:
``{ln1,ln2}.weight``, ``mlp.*``, ``ssm.{w_z, w_xbc, w_dt (the input
projection [E, z+xBC+dt] in its three parts), conv_w [taps,
channels], conv_b, dt_bias, A_log, D, gate_norm, w_out}``;
``final_ln.weight``; the head is the embedding, tied; matrices stored
input-major, ``y = x @ w``).

    h = embedding_multiplier * E[token]
    h = h + residual_multiplier * mixer(rms(h))
    h = h + residual_multiplier * W_out (silu(g) * u),  [g ; u] = W_in rms(h)
    logits = rms(h) E^T / logits_scaling

    [z ; xBC ; dt] = W_in x;  xBC = silu(conv(xBC));  [x ; B ; C] = xBC
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
    out = W_out (rms(y * silu(z)) * w)

What the published config does not say was written from MEMORY of the
family's public modelling file (no copy of it, and no network, where this
was written): one constant each, below; a correction is one line.

``recurrent_state`` returns what the state-space layers hold after a
sequence, for a comparison of the state itself.

Two stand-ins for a faulty program, for the benchmark's controls (keys of
``arch`` that no published config has): ``control_zero_state_at`` (a
position: the recurrent state of every state-space layer is dropped before
that token is read: what a prefix hit seeded from nothing looks like) and
``control_state_dtype`` (the state is rounded to that dtype after every
token).

Departures from a textbook forward, all to fit beside a model that fills
the chip: one layer at a time is cast from the stored dtype to the compute
dtype, attention runs in blocks of queries, and the head is applied in
vocabulary blocks with a running log-sum-exp. None changes the
mathematics. In float32 it runs under
``jax.default_matmul_precision("highest")``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

# (m) the gated norm's epsilon is the model's ``rms_norm_eps``
GATED_NORM_EPS_IS_MODEL_EPS = True
# (m) the gate is applied BEFORE the norm: rms(y * silu(z)) * w
GATE_BEFORE_NORM = True
# (m) that norm spans all of d_inner (not one group of heads)
GATED_NORM_OVER_D_INNER = True
# (m) ``time_step_limit``: dt is clipped to [0, inf) after the softplus,
# which changes nothing
DT_LIMIT = (0.0, float("inf"))
# (m) the convolution's taps: ``conv_w[k]`` weighs the input ``K - 1 - k``
# tokens back (torch's Conv1d with left padding K - 1)
CONV_LAST_TAP_IS_CURRENT = True
# (m) the recurrent state is float32 whatever the compute dtype
STATE_DTYPE = jnp.float32

_VOCAB_BLOCK = 16384
_QUERY_BLOCK = 512


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _mlp(x, lp, eps, res):
    m = lp["mlp"]
    h = _rms(x, lp["ln2"]["weight"], eps)
    return x + res * (
        (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"])


@functools.partial(
    jax.jit, static_argnames=("n_q", "n_kv", "eps", "scale", "res", "dtype"))
def _attn_layer(x, lp, valid, *, n_q, n_kv, eps, scale, res, dtype):
    lp = jax.tree.map(lambda a: a.astype(dtype), lp)
    T = x.shape[0]
    a = lp["attn"]
    h = _rms(x, lp["ln1"]["weight"], eps)
    q = (h @ a["wq"]).reshape(T, n_q, -1)             # no positions at all
    k = (h @ a["wk"]).reshape(T, n_kv, -1)
    v = (h @ a["wv"]).reshape(T, n_kv, -1)
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
    return _mlp(x + res * (ctx @ a["wo"]), lp, eps, res)


@functools.partial(
    jax.jit,
    static_argnames=("n_heads", "d_head", "d_state", "n_groups", "eps", "res",
                     "dtype", "round_to"))
def _ssm_layer(x, lp, zero_at, keep_at, *, n_heads, d_head, d_state, n_groups,
               eps, res, dtype, round_to):
    """The layer's output, and its recurrent state after token ``keep_at``
    (zeros where no token is)."""
    lp = jax.tree.map(lambda a: a.astype(dtype), lp)
    m = lp["ssm"]
    T = x.shape[0]
    d_inner, gn = n_heads * d_head, n_groups * d_state
    h = _rms(x, lp["ln1"]["weight"], eps)
    zxd = h @ jnp.concatenate([m["w_z"], m["w_xbc"], m["w_dt"]], axis=-1)
    if "b_in" in m:
        zxd = zxd + m["b_in"]
    z, xbc, dt = jnp.split(zxd, [d_inner, 2 * d_inner + 2 * gn], axis=-1)
    K = m["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), xbc.dtype), xbc])
    conv = sum(
        padded[k : k + T].astype(jnp.float32)
        * m["conv_w"][k if CONV_LAST_TAP_IS_CURRENT else K - 1 - k].astype(
            jnp.float32)
        for k in range(K))
    if "conv_b" in m:
        conv = conv + m["conv_b"].astype(jnp.float32)
    xbc = jax.nn.silu(conv).astype(dtype).astype(jnp.float32)
    xs = xbc[:, :d_inner].reshape(T, n_heads, d_head)
    per = n_heads // n_groups
    bs = jnp.repeat(
        xbc[:, d_inner : d_inner + gn].reshape(T, n_groups, d_state), per, 1)
    cs = jnp.repeat(
        xbc[:, d_inner + gn :].reshape(T, n_groups, d_state), per, 1)
    dts = jnp.clip(
        jax.nn.softplus(
            dt.astype(jnp.float32) + m["dt_bias"].astype(jnp.float32)),
        *DT_LIMIT)
    A = -jnp.exp(m["A_log"].astype(jnp.float32))
    D = m["D"].astype(jnp.float32)

    def token(carry, inp):
        S, kept = carry
        t, x_t, b_t, c_t, dt_t = inp
        S = jnp.where(t == zero_at, 0.0, S)
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if round_to is not None:
            # (not a cast there and back: the compiler drops such a pair)
            info = jnp.finfo(round_to)
            S = jax.lax.reduce_precision(S, info.nexp, info.nmant)
        y = jnp.einsum("hpn,hn->hp", S, c_t,
                       precision=jax.lax.Precision.HIGHEST)
        return (S, jnp.where(t == keep_at, S, kept)), y + D[:, None] * x_t

    zeros = jnp.zeros((n_heads, d_head, d_state), STATE_DTYPE)
    (_, kept), ys = jax.lax.scan(
        token, (zeros, zeros), (jnp.arange(T), xs, bs, cs, dts))
    y = ys.reshape(T, d_inner)
    gate = jax.nn.silu(z.astype(jnp.float32))
    if GATE_BEFORE_NORM:
        y = _rms(y * gate, m["gate_norm"], eps)
    else:
        y = _rms(y, m["gate_norm"], eps) * gate
    out = y.astype(dtype) @ m["w_out"]
    if "b_out" in m:
        out = out + m["b_out"]
    return _mlp(x + res * out, lp, eps, res), kept


@functools.partial(jax.jit, static_argnames=("eps", "dtype", "scaling"))
def _head_logprobs(x, final_w, embed_w, labels, *, eps, dtype, scaling):
    """log p(labels[t] | ..t) from hidden x [T, E]; the tied head applied
    in vocabulary blocks. Returns (logprob of label, max logprob)."""
    h = _rms(x, final_w.astype(dtype), eps)
    V = embed_w.shape[0]
    lse = jnp.full((x.shape[0],), -jnp.inf, jnp.float32)
    top = jnp.full((x.shape[0],), -jnp.inf, jnp.float32)
    picked = jnp.zeros((x.shape[0],), jnp.float32)
    for lo in range(0, V, _VOCAB_BLOCK):
        hi = min(lo + _VOCAB_BLOCK, V)
        logits = (
            h @ embed_w[lo:hi].astype(dtype).T).astype(jnp.float32) / scaling
        lse = jnp.logaddexp(lse, jax.nn.logsumexp(logits, axis=-1))
        top = jnp.maximum(top, logits.max(axis=-1))
        inside = (labels >= lo) & (labels < hi)
        idx = jnp.clip(labels - lo, 0, hi - lo - 1)
        here = jnp.take_along_axis(logits, idx[:, None], axis=-1)[:, 0]
        picked = jnp.where(inside, here, picked)
    return picked - lse, top - lse


def _forward(params, arch: dict, ids, valid, dt, keep_at=-1, n_states=None):
    """``(log-prob of the next token, largest log-prob)`` a position, and
    every state-space layer's recurrent state after token ``keep_at``;
    with ``n_states`` the forward ends behind that many state-space layers
    and returns their states alone."""
    for key, want in (("num_local_experts", 0), ("rope_scaling", None),
                      ("position_embedding_type", "nope"),
                      ("normalization_function", "rmsnorm")):
        if arch.get(key, want) != want:
            raise ValueError(f"granitemoehybrid: {key}={arch[key]!r}")
    eps = float(arch["rms_norm_eps"])
    res = float(arch["residual_multiplier"])
    n_q = arch["num_attention_heads"]
    attn_kw = dict(
        n_q=n_q, n_kv=arch.get("num_key_value_heads") or n_q, eps=eps,
        scale=float(arch["attention_multiplier"]), res=res, dtype=dt)
    round_to = arch.get("control_state_dtype")
    ssm_kw = dict(
        n_heads=arch["mamba_n_heads"], d_head=arch["mamba_d_head"],
        d_state=arch["mamba_d_state"], n_groups=arch["mamba_n_groups"],
        eps=eps, res=res, dtype=dt,
        round_to=None if round_to is None else jnp.dtype(round_to))
    zero_at = jnp.int32(arch.get("control_zero_state_at", -1))
    keep_at, states = jnp.int32(keep_at), []
    labels = jnp.concatenate([ids[1:], ids[:1]])
    x = (params["embed"]["weight"][ids].astype(dt)
         * jnp.asarray(arch["embedding_multiplier"], dt))
    at = {"attention": 0, "mamba": 0}
    for kind in arch["layer_types"][: arch["num_hidden_layers"]]:
        stack = params["layers" if kind == "attention" else "ssm_layers"]
        lp = jax.tree.map(lambda a: a[at[kind]], stack)
        at[kind] += 1
        if kind == "attention":
            x = _attn_layer(x, lp, valid, **attn_kw)
        elif kind == "mamba":
            x, kept = _ssm_layer(x, lp, zero_at, keep_at, **ssm_kw)
            states.append(kept)
            if len(states) == n_states:
                return None, states
        else:
            raise ValueError(f"granitemoehybrid: layer type {kind!r}")
    return _head_logprobs(
        x, params["final_ln"]["weight"], params["embed"]["weight"], labels,
        eps=eps, dtype=dt, scaling=float(arch["logits_scaling"])), states


def next_token_logprobs(params, arch: dict, tokens, dtype: str, pad_to: int):
    """``tokens``: one sequence of ids. Returns float32 numpy arrays of
    length len(tokens)-1: log p(tokens[t+1] | tokens[..t]) and the largest
    log-probability at that position."""
    dt = jnp.dtype(dtype)
    n = len(tokens)
    ids = np.zeros((pad_to,), np.int32)
    ids[:n] = tokens
    valid = jnp.asarray(np.arange(pad_to) < n)
    precision = "highest" if dt == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        (lp_tok, lp_max), _ = _forward(
            params, arch, jnp.asarray(ids), valid, dt)
    lp_tok, lp_max = jax.device_get((lp_tok, lp_max))
    return np.asarray(lp_tok[: n - 1]), np.asarray(lp_max[: n - 1])


def recurrent_state(params, arch: dict, tokens, dtype: str, pad_to: int,
                    n_layers=None):
    """The recurrent state of every state-space layer (or of the first
    ``n_layers`` of them: the forward then ends there) after ALL of
    ``tokens`` (one sequence of ids), in the order the layers run: float32
    numpy ``[state layers, heads, head dim, state]``."""
    dt = jnp.dtype(dtype)
    n = len(tokens)
    ids = np.zeros((pad_to,), np.int32)
    ids[:n] = tokens
    valid = jnp.asarray(np.arange(pad_to) < n)
    precision = "highest" if dt == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        _, states = _forward(
            params, arch, jnp.asarray(ids), valid, dt, keep_at=n - 1,
            n_states=n_layers)
    return np.stack(jax.device_get(states))


def sequence_logprobs(params, arch: dict, ids, dtype: str = "float32"):
    """The same forward as one traceable function: float32
    ``log p(ids[t+1] | ids[..t])`` for t < len(ids)-1, differentiable in
    ``params``. For small sizes."""
    dt = jnp.dtype(dtype)
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        (lp_tok, _), _ = _forward(
            params, arch, ids, jnp.ones(ids.shape, bool), dt)
    return lp_tok[:-1]
