"""Plain reference for the phi4flash family (Phi-4-mini-flash-reasoning:
the SambaY decoder-hybrid-decoder of arXiv:2507.06607 with differential
attention).

The forward pass in straightforward ``jax.numpy``: a Python loop over the
layers; no cache, no kernels, no packing of heads (a pair's score is
computed at the published width of 64), the recurrence as a plain
``lax.scan`` over the TOKENS with a float32 state. Independent of the
program's model code: it shares only the NAMES of the weight tree (one
stack a kind of layer, each in the order its layers run; matrices stored
input-major, ``y = x @ w``):

- ``embed.weight`` [V, E] (the head is its transpose, tied);
  ``final_ln.{weight,bias}``; every stack has ``{ln1,ln2}.{weight,bias}``
  and ``mlp.{w_gate,w_up,w_down}``;
- ``ssm_layers`` (Mamba-1): ``ssm.{w_x, w_z [E, d_inner], conv_w [taps,
  d_inner], conv_b, w_xproj [d_inner, dt_rank + 2 N], w_dt [dt_rank,
  d_inner], dt_bias, A_log [N, d_inner], D, w_out [d_inner, E]}``;
- ``layers`` (self attention): ``attn.{wq, wk, wv, bq, bk, bv, wo, bo,
  subln [2 D], lam_q1, lam_k1, lam_q2, lam_k2 [D]}``;
- ``gmu_layers``: ``gmu.{w_in [E, d_inner], w_out [d_inner, E]}``;
- ``cross_layers``: ``attn.{wq, bq, wo, bo, subln, lam_*}``.

    x = x + mixer(ln1(x));  x = x + W_down (silu(g) * u), [g ; u] = W ln2(x)

    layer l even, l <= L/2 (Mamba-1):
      [x ; z] = W_in h;  x = silu(conv(x) + b);  [dt_low ; B ; C] = W_x x
      dt = softplus(W_dt dt_low + b_dt);  A = -exp(A_log)
      S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
      y_t[c] = sum_n S_t[c, n] C_t[n] + D[c] x_t[c];  out = W_out (y silu(z))
      layer L/2 also exports M = y
    layer l odd, l <= L/2 + 1 (self attention, differential):
      q1, q2 = even, odd query heads; k1, k2, v1, v2 likewise of the kv heads
      a1 = softmax(q1 k1^T / sqrt(D)) [v1 ; v2];  a2 likewise of q2, k2
      lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l)
      out = W_o ((1 - lam0) rms(a1 - lam a2) w) + b
      l < L/2: itself and the ``sliding_window - 1`` positions before;
      l = L/2 + 1: everything, and its K/V are kept
    layer l even, l >= L/2 + 2 (gated memory unit):
      out = W_out (silu(W_in h) * M)
    layer l odd, l >= L/2 + 3 (cross attention): q = W_q h + b; keys and
      values are layer L/2 + 1's; the same differential form

What the published config does not say was written from MEMORY of the
family's public modelling file and the paper (no copy of either, and no
network, where this was written): one constant each, below; a correction
is one line.

``recurrent_state`` returns what the state-space layers hold after a
sequence, for a comparison of the state itself.

Stand-ins for a faulty program, for the benchmark's controls (keys of
``arch`` that no published config has): ``control_zero_state_at`` (a
position: the recurrent AND the convolution state of every state-space
layer are dropped before that token is read: a prefix hit seeded from
nothing), ``control_state_dtype`` (the state is rounded to that dtype after
every token), ``control_no_window`` (every window layer full: a program
that forgot the window, or read pages it had given back),
``control_lambda_zero`` (``lam`` = 0 in every layer: plain attention in
place of the difference).

Departures from a textbook forward, all to fit beside a model that fills
the chip: one layer at a time is cut from its stack and cast from the
stored dtype to the compute dtype (inside the compiled layer), attention
runs in blocks of queries, and the head is applied in vocabulary blocks
with a running log-sum-exp. And two to fit a check's time: a kind of layer
is ONE compiled program a dtype and a length (the layer's index, its
window, its ``lambda`` constant and the controls' switches are arguments),
and ``build_ahead`` builds those programs side by side. None changes the
mathematics. In float32 it runs under
``jax.default_matmul_precision("highest")``.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# (m) head_dim = hidden_size / num_attention_heads
HEAD_DIM_IS_HIDDEN_OVER_HEADS = True
# (m) the Mamba sizes are the config class's defaults
MAMBA_D_STATE = 16
MAMBA_D_CONV = 4
MAMBA_EXPAND = 2
MAMBA_DT_RANK_DIVISOR = 16          # dt_rank = ceil(hidden_size / 16)
MAMBA_CONV_BIAS = True
MAMBA_PROJ_BIAS = False
# (m) no positional encoding anywhere
POSITIONS = None
# (m) every norm is a LayerNorm WITH bias, the final one too
NORM_IS_LAYERNORM_WITH_BIAS = True
# (m) biases on Wqkv and on the attention output projection; none in the
# MLP or the head (``mlp_bias``, ``lm_head_bias``)
ATTN_QKV_BIAS = True
ATTN_OUT_BIAS = True
# (m) the MLP's one input matrix is [gate ; up], gate first
MLP_GATE_FIRST = True
# (m) a state-space layer every ``mb_per_layer`` layers, from layer 0, up
# to layer L/2; the full layer is L/2 + 1; gated memory units and cross
# attention from L/2 + 2
FULL_LAYER_AFTER_HALF = 1
CROSS_DECODER_AFTER_HALF = 2
# (m) the memory is the scan's output BEFORE the gate, WITH the D x term
MEMORY_BEFORE_GATE = True
# (m) heads are split in stripes: q1 the even query heads, q2 the odd,
# likewise k1, k2, v1, v2 of the kv heads
STRIPED_HEADS = True
# (m) lam0(l) = 0.8 - 0.6 exp(-0.3 l), l the 0-based layer index
LAMBDA_INIT = (0.8, 0.6, 0.3)
# (m) the pair's norm is an RMSNorm over 2 D with the model's epsilon
SUBLN_EPS_IS_MODEL_EPS = True
# (m) a window layer sees itself and sliding_window - 1 positions before
WINDOW_INCLUDES_SELF = True
# (m) the convolution's taps: ``conv_w[k]`` weighs the input ``K - 1 - k``
# tokens back (torch's Conv1d with left padding K - 1)
CONV_LAST_TAP_IS_CURRENT = True
# (m) the recurrent state is float32 whatever the compute dtype
STATE_DTYPE = jnp.float32

_VOCAB_BLOCK = 16384
_QUERY_BLOCK = 512
_BUILD_THREADS = 8      # ``build_ahead``: programs built side by side


def sizes(arch: dict) -> dict:
    """The Mamba sizes of ``arch`` (its own keys where it has them, else
    the defaults above) and the head size."""
    hidden = arch["hidden_size"]
    expand = arch.get("mamba_expand", MAMBA_EXPAND)
    return {
        "head_dim": hidden // arch["num_attention_heads"],
        "d_inner": expand * hidden,
        "d_state": arch.get("mamba_d_state", MAMBA_D_STATE),
        "d_conv": arch.get("mamba_d_conv", MAMBA_D_CONV),
        "dt_rank": arch.get("mamba_dt_rank") or math.ceil(
            hidden / MAMBA_DT_RANK_DIVISOR),
    }


def layer_kinds(arch: dict):
    """``(kind, window)`` of every layer: "mamba", "attention" (a window or
    None), "gmu", "cross"."""
    L, every = arch["num_hidden_layers"], arch["mb_per_layer"]
    half = L // 2
    out = []
    for l in range(L):
        if l % every == 0:
            out.append(("mamba" if l <= half else "gmu", None))
        elif l < half:
            out.append(("attention", arch["sliding_window"]))
        elif l < half + CROSS_DECODER_AFTER_HALF:
            out.append(("attention", None))
        else:
            out.append(("cross", None))
    return out


def _ln(x, p, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps) * p["weight"].astype(jnp.float32)
    return (y + p["bias"].astype(jnp.float32)).astype(x.dtype)


def _mlp(x, lp, eps):
    m = lp["mlp"]
    h = _ln(x, lp["ln2"], eps)
    return x + (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def _layer_of(stack, i, dtype):
    """Layer ``i`` of a stack of layers, in the compute dtype (cut inside
    the compiled layer: one layer at a time is cast, and no copy of a
    layer is made outside it)."""
    return jax.tree.map(lambda a: a[i].astype(dtype), stack)


def lambda_init(l: int) -> float:
    """``lam0`` of layer ``l`` (0-based)."""
    c0, c1, c2 = LAMBDA_INIT
    return c0 - c1 * math.exp(-c2 * l)


def _diff_attention(q, k, v, a, valid, window, lam0, *, eps, dtype, lam_zero):
    """q ``[T, Hq, D]`` over k, v ``[T, Hkv, D]``: the differential form,
    causal (and inside ``window``), in blocks of queries. ``lam0`` (the
    layer's constant), ``window`` (None: none) and ``lam_zero`` are
    arguments, not statics: one compiled layer serves every depth, window
    and full layers, and the control without the difference."""
    T, _, D = q.shape
    q1, q2 = q[:, 0::2], q[:, 1::2]
    k1, k2 = k[:, 0::2], k[:, 1::2]
    vv = jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1)   # [T, Hkv/2, 2D]
    rep = q1.shape[1] // k1.shape[1]
    k1, k2, vv = (jnp.repeat(x, rep, axis=1) for x in (k1, k2, vv))
    pos = jnp.arange(T)

    def half(qh, kh):
        out = []
        for lo in range(0, T, _QUERY_BLOCK):
            qb, pq = qh[lo : lo + _QUERY_BLOCK], pos[lo : lo + _QUERY_BLOCK]
            s = jnp.einsum("thd,shd->hts", qb, kh).astype(
                jnp.float32) * D ** -0.5
            ok = (pos[None, :] <= pq[:, None]) & valid[None]
            if window is not None:
                ok = ok & (pq[:, None] - pos[None, :] < window)
            # (finite: a padding query past the window sees no key at
            # all, and a NaN there would reach every row through 0 x NaN)
            p = jax.nn.softmax(jnp.where(ok[None], s, -1e30), axis=-1)
            out.append(jnp.einsum("hts,shd->thd", p.astype(dtype), vv))
        return jnp.concatenate(out).astype(jnp.float32)

    a1, a2 = half(q1, k1), half(q2, k2)
    f32 = jnp.float32
    lam = (jnp.exp(jnp.sum(a["lam_q1"].astype(f32) * a["lam_k1"].astype(f32)))
           - jnp.exp(jnp.sum(a["lam_q2"].astype(f32) * a["lam_k2"].astype(f32)))
           + lam0)
    lam = jnp.where(lam_zero, 0.0, lam)
    d = a1 - lam * a2
    d = d * jax.lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True) + eps)
    ctx = (1.0 - lam0) * d * a["subln"].astype(f32)
    return ctx.astype(dtype).reshape(T, -1)


@functools.partial(jax.jit, static_argnames=("n_q", "n_kv", "eps", "dtype"))
def _attn_layer(x, stack, i, valid, lam0, window, lam_zero, *, n_q, n_kv,
                eps, dtype):
    """Self attention, layer ``i`` of its stack: the layer's output and its
    K/V. ``window``: a number of positions (a full layer's is the
    sequence's length)."""
    lp = _layer_of(stack, i, dtype)
    T = x.shape[0]
    a = lp["attn"]
    h = _ln(x, lp["ln1"], eps)
    q = (h @ a["wq"] + a["bq"]).reshape(T, n_q, -1)   # no positions at all
    k = (h @ a["wk"] + a["bk"]).reshape(T, n_kv, -1)
    v = (h @ a["wv"] + a["bv"]).reshape(T, n_kv, -1)
    ctx = _diff_attention(
        q, k, v, a, valid, window, lam0, eps=eps, dtype=dtype,
        lam_zero=lam_zero)
    return _mlp(x + ctx @ a["wo"] + a["bo"], lp, eps), (k, v)


@functools.partial(jax.jit, static_argnames=("n_q", "eps", "dtype"))
def _cross_layer(x, stack, i, kv, valid, lam0, lam_zero, *, n_q, eps, dtype):
    lp = _layer_of(stack, i, dtype)
    T = x.shape[0]
    a = lp["attn"]
    h = _ln(x, lp["ln1"], eps)
    q = (h @ a["wq"] + a["bq"]).reshape(T, n_q, -1)
    ctx = _diff_attention(
        q, *kv, a, valid, None, lam0, eps=eps, dtype=dtype, lam_zero=lam_zero)
    return _mlp(x + ctx @ a["wo"] + a["bo"], lp, eps)


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _gmu_layer(x, stack, i, memory, *, eps, dtype):
    lp = _layer_of(stack, i, dtype)
    g = lp["gmu"]
    h = _ln(x, lp["ln1"], eps)
    gate = jax.nn.silu((h @ g["w_in"]).astype(jnp.float32))
    return _mlp(x + (gate * memory).astype(dtype) @ g["w_out"], lp, eps)


@functools.partial(
    jax.jit, static_argnames=("dt_rank", "d_state", "eps", "dtype", "round_to"))
def _ssm_layer(x, stack, i, zero_at, keep_at, *, dt_rank, d_state, eps,
               dtype, round_to):
    """The layer's output, its scan output before the gate (the memory),
    and its recurrent state ``[d_inner, d_state]`` after token ``keep_at``
    (zeros where no token is)."""
    lp = _layer_of(stack, i, dtype)
    m = lp["ssm"]
    T = x.shape[0]
    h = _ln(x, lp["ln1"], eps)
    xs, z = h @ m["w_x"], h @ m["w_z"]
    K = m["conv_w"].shape[0]
    # (the dropped state of ``control_zero_state_at`` is the convolution's
    # too: from that token on, what lies before it reads as nothing)
    pos = jnp.arange(T)
    conv = 0.0
    for d in range(K):                                # the tap d tokens back
        src = pos - d
        gone = (src < 0) | ((pos >= zero_at) & (src < zero_at))
        tap = jnp.where(gone[:, None], 0, xs[jnp.maximum(src, 0)])
        conv = conv + tap.astype(jnp.float32) * m["conv_w"][
            K - 1 - d if CONV_LAST_TAP_IS_CURRENT else d].astype(jnp.float32)
    if "conv_b" in m:
        conv = conv + m["conv_b"].astype(jnp.float32)
    xc = jax.nn.silu(conv).astype(dtype)
    dbc = xc @ m["w_xproj"]
    dts = jax.nn.softplus(
        (dbc[:, :dt_rank] @ m["w_dt"]).astype(jnp.float32)
        + m["dt_bias"].astype(jnp.float32))
    dbc = dbc.astype(jnp.float32)
    bs, cs = dbc[:, dt_rank : dt_rank + d_state], dbc[:, dt_rank + d_state :]
    xc = xc.astype(jnp.float32)
    A = -jnp.exp(m["A_log"].astype(jnp.float32)).T           # [C, N]
    D = m["D"].astype(jnp.float32)

    def token(carry, inp):
        S, kept = carry
        t, x_t, b_t, c_t, dt_t = inp
        S = jnp.where(t == zero_at, 0.0, S)
        S = jnp.exp(dt_t[:, None] * A) * S + (dt_t * x_t)[:, None] * b_t[None]
        if round_to is not None:
            # (not a cast there and back: the compiler drops such a pair)
            info = jnp.finfo(round_to)
            S = jax.lax.reduce_precision(S, info.nexp, info.nmant)
        y = jnp.sum(S * c_t[None], axis=-1) + D * x_t
        return (S, jnp.where(t == keep_at, S, kept)), y

    zeros = jnp.zeros(A.shape, STATE_DTYPE)
    (_, kept), y = jax.lax.scan(
        token, (zeros, zeros), (jnp.arange(T), xc, bs, cs, dts))
    out = (y * jax.nn.silu(z.astype(jnp.float32))).astype(dtype) @ m["w_out"]
    return _mlp(x + out, lp, eps), y, kept


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head_logprobs(x, final_ln, embed_w, labels, *, eps, dtype):
    """log p(labels[t] | ..t) from hidden x [T, E]; the tied head applied
    in vocabulary blocks. Returns (logprob of label, max logprob)."""
    h = _ln(x, jax.tree.map(lambda a: a.astype(dtype), final_ln), eps)
    V = embed_w.shape[0]
    lse = jnp.full((x.shape[0],), -jnp.inf, jnp.float32)
    top = jnp.full((x.shape[0],), -jnp.inf, jnp.float32)
    picked = jnp.zeros((x.shape[0],), jnp.float32)
    for lo in range(0, V, _VOCAB_BLOCK):
        hi = min(lo + _VOCAB_BLOCK, V)
        logits = (h @ embed_w[lo:hi].astype(dtype).T).astype(jnp.float32)
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
    for key, want in (("mlp_bias", False), ("lm_head_bias", False),
                      ("tie_word_embeddings", True), ("hidden_act", "silu")):
        if arch.get(key, want) != want:
            raise ValueError(f"phi4flash: {key}={arch[key]!r}")
    eps = float(arch["layer_norm_eps"])
    n_q = arch["num_attention_heads"]
    sz = sizes(arch)
    lam_zero = bool(arch.get("control_lambda_zero", False))
    round_to = arch.get("control_state_dtype")
    zero_at = jnp.int32(arch.get("control_zero_state_at", -1))
    keep_at, states = jnp.int32(keep_at), []
    labels = jnp.concatenate([ids[1:], ids[:1]])
    x = params["embed"]["weight"][ids].astype(dt)
    stacks = {"mamba": "ssm_layers", "attention": "layers",
              "gmu": "gmu_layers", "cross": "cross_layers"}
    at = dict.fromkeys(stacks, 0)
    memory = shared_kv = None
    for l, (kind, window) in enumerate(layer_kinds(arch)):
        lp = params[stacks[kind]], jnp.int32(at[kind])
        at[kind] += 1
        if kind == "mamba":
            x, memory, kept = _ssm_layer(
                x, *lp, zero_at, keep_at, dt_rank=sz["dt_rank"],
                d_state=sz["d_state"], eps=eps, dtype=dt,
                round_to=None if round_to is None else jnp.dtype(round_to))
            states.append(kept)
            if len(states) == n_states:
                return None, states
        elif kind == "attention":
            if window is None or arch.get("control_no_window"):
                window = ids.shape[0]           # every position
            x, shared_kv = _attn_layer(
                x, *lp, valid, jnp.float32(lambda_init(l)), jnp.int32(window),
                jnp.bool_(lam_zero), n_q=n_q,
                n_kv=arch["num_key_value_heads"], eps=eps, dtype=dt)
        elif kind == "gmu":
            x = _gmu_layer(x, *lp, memory, eps=eps, dtype=dt)
        else:
            x = _cross_layer(
                x, *lp, shared_kv, valid, jnp.float32(lambda_init(l)),
                jnp.bool_(lam_zero), n_q=n_q, eps=eps, dtype=dt)
    return _head_logprobs(
        x, params["final_ln"], params["embed"]["weight"], labels,
        eps=eps, dtype=dt), states


def _padded(tokens, pad_to):
    n = len(tokens)
    ids = np.zeros((pad_to,), np.int32)
    ids[:n] = tokens
    return n, jnp.asarray(ids), jnp.asarray(np.arange(pad_to) < n)


def next_token_logprobs(params, arch: dict, tokens, dtype: str, pad_to: int):
    """``tokens``: one sequence of ids. Returns float32 numpy arrays of
    length len(tokens)-1: log p(tokens[t+1] | tokens[..t]) and the largest
    log-probability at that position."""
    dt = jnp.dtype(dtype)
    n, ids, valid = _padded(tokens, pad_to)
    precision = "highest" if dt == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        (lp_tok, lp_max), _ = _forward(params, arch, ids, valid, dt)
    lp_tok, lp_max = jax.device_get((lp_tok, lp_max))
    return np.asarray(lp_tok[: n - 1]), np.asarray(lp_max[: n - 1])


def recurrent_state(params, arch: dict, tokens, dtype: str, pad_to: int,
                    n_layers=None):
    """The recurrent state of every state-space layer (or of the first
    ``n_layers`` of them: the forward then ends there) after ALL of
    ``tokens`` (one sequence of ids), in the order the layers run: float32
    numpy ``[state layers, d_inner, d_state]``."""
    dt = jnp.dtype(dtype)
    n, ids, valid = _padded(tokens, pad_to)
    precision = "highest" if dt == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        _, states = _forward(
            params, arch, ids, valid, dt, keep_at=n - 1, n_states=n_layers)
    return np.stack(jax.device_get(states))


def build_ahead(params, arch: dict, dtypes, pad_to: int, state_dtype=None):
    """Build every program that the forwards in ``dtypes`` at ``pad_to``
    are made of (each kind of layer and the head; with ``state_dtype``
    also the float32 state-space layer that rounds its state to it), all
    at once on ``_BUILD_THREADS`` threads, each by one run on a sequence of
    token 0. A forward meets its programs one after another, and the chip's
    compiler takes 10-20 s over a float32 layer at the highest precision
    (4-6 s in 16 bits): fifteen programs are minutes in a row and one
    program's time side by side. Nothing here computes a result."""
    import concurrent.futures

    _, ids, valid = _padded([0] * pad_to, pad_to)
    eps, sz = float(arch["layer_norm_eps"]), sizes(arch)
    n_q, n_kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    i0, none = jnp.int32(0), jnp.int32(-1)
    lam0, lam_zero = jnp.float32(0), jnp.bool_(False)
    memory = jnp.zeros((pad_to, sz["d_inner"]), jnp.float32)

    def calls(dt, round_to=None):
        x = params["embed"]["weight"][ids].astype(dt)
        kv = jnp.zeros((pad_to, n_kv, sz["head_dim"]), dt)
        kw = {"eps": eps, "dtype": dt}

        def ssm():
            return _ssm_layer(
                x, params["ssm_layers"], i0, none, none, round_to=round_to,
                dt_rank=sz["dt_rank"], d_state=sz["d_state"], **kw)

        def attn():
            return _attn_layer(
                x, params["layers"], i0, valid, lam0, jnp.int32(pad_to),
                lam_zero, n_q=n_q, n_kv=n_kv, **kw)

        def gmu():
            return _gmu_layer(x, params["gmu_layers"], i0, memory, **kw)

        def cross():
            return _cross_layer(
                x, params["cross_layers"], i0, (kv, kv), valid, lam0,
                lam_zero, n_q=n_q, **kw)

        def head():
            return _head_logprobs(
                x, params["final_ln"], params["embed"]["weight"], ids, **kw)

        return [ssm] if round_to is not None else [
            ssm, attn, gmu, cross, head]

    def build(dt, call):
        # (the precision is a thread's own setting, and part of what a
        # compiled program is kept under)
        precision = "highest" if dt == jnp.float32 else "default"
        with jax.default_matmul_precision(precision):
            jax.block_until_ready(call())

    todo = [(jnp.dtype(d), c) for d in dtypes for c in calls(jnp.dtype(d))]
    if state_dtype is not None:
        f32 = jnp.dtype("float32")
        todo += [(f32, c) for c in calls(f32, jnp.dtype(state_dtype))]
    with concurrent.futures.ThreadPoolExecutor(_BUILD_THREADS) as pool:
        for done in [pool.submit(build, *t) for t in todo]:
            done.result()


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
