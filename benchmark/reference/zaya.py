"""Plain reference for the zaya family (ZAYA1-8B: attention inside a
compressed, convolved latent, and a top-1 expert layer behind an MLP
router with state).

The forward pass in straightforward ``jax.numpy``, one sequence at a time:
a Python loop over the layers; in each, the attention sublayer (CCA: the
query and key projections side by side through two causal convolutions
over the sequence, a mean of the two projections added, every head scaled
to a fixed norm, THEN half-split rotary on the first ``partial_rotary_
factor`` of a head; half the kv heads hold the PREVIOUS token's values;
dense causal attention over the whole sequence, GQA) and the expert
sublayer (a router that is a down-projection plus the previous layer's
router vector, an RMSNorm and three GELU layers to ``num_experts + 1``
logits; top-1 by probability plus a balancing bias, weighted by the
probability alone; the last output is a skip). Both sublayers join the
residual through four learned vectors. No kernels, no cache, no batching.
Independent of the program's model code: it shares only the NAMES of the
weight tree (``embed.weight`` [V,E], tied head; ``layers.{ln1,ln2}.weight``
[L,E]; ``layers.attn.{wq,wk,wv,wo}`` input-major, ``y = x @ w``;
``layers.attn.conv0_w`` [L,taps,C] and ``conv1_w`` [L,taps,heads,D,D],
the newest tap LAST, a head's block input-major; ``conv0_b, conv1_b``
[L,C]; ``k_temp`` [L,Hkv]; ``layers.{attn_res,mlp_res}.{a_r,b_r,a_h,b_h}``
[L,E]; ``layers.mlp.{router_in,b_router_in,router_mix,router_norm,
router_w1,b_router1,router_w2,b_router2,router,b_router}`` and
``{w_gate,w_up,w_down}`` [L,X,...]; ``final_ln.weight``).

The config gives every SIZE. What it does not say was written from MEMORY
of the two public papers (arXiv 2510.04476, 2511.17127) and the family's
public modelling file (no copy of it, and no network, where this was
written): one named constant each, below, and one entry each of the
configuration file's ``assumed.from_memory``. A correction is one line
here and one in ``areal_tpu/ops/cca.py`` / ``ops/moe.py``.

Controls (keys of ``arch`` that no configuration file has; each makes a
WRONG answer that the benchmark's comparison must refuse):
``control_zero_carry_at`` n: the convolutions and the value shift start
again at position n as at a sequence's start (what a prefix hit seeded
from nothing hands in); ``control_no_router_state``: no layer reads the
previous layer's router vector. ``forced_routing`` [L, T] int: the experts
to take in place of the router's own choice (the weight is still the
router's probability of THAT output), for a comparison that a flipped
top-1 choice cannot blur.

Departures from a textbook forward, all to fit beside a model that fills
the chip: one layer at a time is cast from the stored dtype to the compute
dtype, the experts are applied one at a time, the embedding rows are
gathered before the cast, and the tied head is applied in vocabulary
blocks with a running log-sum-exp. None changes the mathematics. In
float32 it runs under ``jax.default_matmul_precision("highest")``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

# (m) a sublayer's output f joins the residual x as
# a_r * (x + b_r) + a_h * (f + b_h), four learned [hidden] vectors a
# sublayer. The published code defers the sum to the NEXT sublayer's entry
# (none before the first sublayer, one more set before the final norm):
# the same function, written here at the sublayer's exit
RESIDUAL_SCALING = True
# (m) the second half of the kv heads hold the PREVIOUS token's value
# projection (zeros at a sequence's first token); the first half the
# current token's
VALUE_SHIFT_BY_HEAD = True
# (m) after the convolutions: q += (q~_h + k~_g(h)) / 2 a query head, k +=
# the mean of that over the group's query heads
QK_MEAN = True
# (m) every q and k head is scaled to norm sqrt(head_dim), in float32; the
# keys then times a learned temperature a kv head
QK_L2NORM_TEMP_ON_K = True
# (m) the rotary embedding comes after the norm, on the first
# partial_rotary_factor of a head, half-split pairs (i, i + rot/2)
ROTARY_AFTER_NORM = True
# (m) both convolutions pad with zeros on the left: conv1 reads c_{-1} = 0
# (not conv0's bias)
CONV_ZERO_LEFT_PAD = True
# (m) the router's vector r_l = W_d h + b_d + s_l * r_{l-1}: the previous
# layer's AFTER its own addition, times a learned [router_hidden] gain
ROUTER_EDA = True
# (m) the router MLP's activation is the exact (erf) GELU; its input is
# RMSNorm(r) with rms_norm_eps
ROUTER_GELU_EXACT = True
# (m) the router has num_experts + 1 outputs, the last a skip; the
# balancing bias is added to the PROBABILITIES for the choice only; the
# weight is the chosen output's probability, not renormalised
ROUTER_SKIP_OUTPUT = True
# (m) a row that takes the skip passes through: y = g * h with h the
# sublayer's normed input (False: y = 0)
SKIP_IS_IDENTITY = True

_VOCAB_BLOCK = 16384
_F32 = jnp.float32


def _rms(x, w, eps):
    x32 = x.astype(_F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(_F32)).astype(x.dtype)


def _rope(x, positions, theta, rot):
    """x [T, H, D]: rotate-half on the first ``rot`` dimensions."""
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=_F32) / rot))
    ang = positions.astype(_F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x32 = x.astype(_F32)
    head, tail = x32[..., :rot], x32[..., rot:]
    turned = jnp.concatenate([-head[..., rot // 2:], head[..., : rot // 2]], -1)
    return jnp.concatenate([head * cos + turned * sin, tail], -1).astype(x.dtype)


def _back(z, d, starts, fill=0):
    """``z [T, ...]`` read ``d`` tokens back: ``fill`` (zeros) where that
    reaches behind the start of the token's own run (``starts [T]``: the
    position at which the run a token belongs to began)."""
    if d == 0:
        return z
    T = z.shape[0]
    shifted = jnp.concatenate([jnp.zeros_like(z[:d]), z[: T - d]], axis=0)
    ok = jnp.arange(T) - d >= starts
    return jnp.where(ok.reshape((T,) + (1,) * (z.ndim - 1)), shifted, fill)


def _unit(x):
    """Heads ``[T, H, D]`` (float32) at norm sqrt(D)."""
    n = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    return x * (x.shape[-1] ** 0.5 / jnp.maximum(n, 1e-12))


def _join(x, f, s, dtype):
    if not RESIDUAL_SCALING:
        return x + f
    s = jax.tree.map(lambda a: a.astype(dtype), s)
    return s["a_r"] * (x + s["b_r"]) + s["a_h"] * (f + s["b_h"])


def _qkv(x, lp, starts, *, n_q, n_kv, eps, theta, rot, dtype):
    """The attention sublayer up to what the cache would hold: ``q [T,
    Hq, D]``, ``k, v [T, Hkv, D]`` of ``x [T, E]`` (``lp`` in ``dtype``)."""
    T = x.shape[0]
    a = lp["attn"]
    G = n_q // n_kv
    h = _rms(x, lp["ln1"]["weight"], eps)
    q_lat, k_lat, val = h @ a["wq"], h @ a["wk"], h @ a["wv"]
    D = q_lat.shape[-1] // n_q
    p = jnp.concatenate([q_lat, k_lat], axis=-1)              # [T, C]
    w0, w1 = a["conv0_w"].astype(_F32), a["conv1_w"].astype(_F32)
    K0, K1 = w0.shape[0], w1.shape[0]
    c = a["conv0_b"].astype(_F32)
    for d in range(K0):             # depthwise: the tap d back, weight K-1-d
        c = c + _back(p, d, starts).astype(_F32) * w0[K0 - 1 - d]
    c = c.astype(dtype)
    e = a["conv1_b"].astype(_F32).reshape(n_q + n_kv, D)
    behind = 0 if CONV_ZERO_LEFT_PAD else a["conv0_b"]
    for d in range(K1):             # one D x D block a head
        e = e + jnp.einsum(
            "thi,hio->tho",
            _back(c, d, starts, behind).reshape(T, n_q + n_kv, D),
            a["conv1_w"][K1 - 1 - d]).astype(_F32)
    lat = p.astype(_F32).reshape(T, n_q + n_kv, D)
    q, k = e[:, :n_q], e[:, n_q:]
    if QK_MEAN:
        mean_q = 0.5 * (lat[:, :n_q].reshape(T, n_kv, G, D)
                        + lat[:, n_q:, None])
        q = q + mean_q.reshape(T, n_q, D)
        k = k + mean_q.mean(axis=2)
    if QK_L2NORM_TEMP_ON_K:
        q = _unit(q)
        k = _unit(k) * a["k_temp"].astype(_F32)[:, None]
    q, k = q.astype(dtype), k.astype(dtype)
    pos = jnp.arange(T)
    if ROTARY_AFTER_NORM:
        q, k = _rope(q, pos, theta, rot), _rope(k, pos, theta, rot)
    v = val.reshape(T, n_kv, D)
    if VALUE_SHIFT_BY_HEAD:
        half = n_kv // 2
        v = jnp.concatenate(
            [v[:, :half], _back(v[:, half:], 1, starts)], axis=1)
    return q, k, v


@functools.partial(jax.jit, static_argnames=(
    "n_q", "n_kv", "eps", "theta", "rot", "dtype", "carry_router"))
def _layer(x, r_prev, lp, valid, starts, forced, *, n_q, n_kv, eps, theta,
           rot, dtype, carry_router):
    """One layer on x [T, E]. ``r_prev`` [T, R] the previous layer's router
    vector (float32); ``starts`` [T] where each token's run began (0
    everywhere but under ``control_zero_carry_at``); ``forced`` [T] the
    expert to take, or -1 for the router's own. Returns (x, r, chosen)."""
    lp = jax.tree.map(lambda a: a.astype(dtype), lp)
    T = x.shape[0]
    a = lp["attn"]
    G = n_q // n_kv
    # ---- attention inside the convolved latent ------------------------ #
    q, k, v = _qkv(x, lp, starts, n_q=n_q, n_kv=n_kv, eps=eps, theta=theta,
                   rot=rot, dtype=dtype)
    D = q.shape[-1]
    pos = jnp.arange(T)
    kk, vv = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    s = jnp.einsum("thd,shd->hts", q, kk).astype(_F32) * (D ** -0.5)
    causal = (pos[None, :] <= pos[:, None]) & valid[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    prob = jax.nn.softmax(s, axis=-1).astype(dtype)
    attn = jnp.einsum("hts,shd->thd", prob, vv).reshape(T, -1) @ a["wo"]
    x = _join(x, attn, lp["attn_res"], dtype)
    # ---- the expert sublayer ------------------------------------------ #
    m = lp["mlp"]
    h = _rms(x, lp["ln2"]["weight"], eps)
    m32 = {k_: m[k_].astype(_F32) for k_ in m if k_ not in (
        "w_gate", "w_up", "w_down")}
    r = h.astype(_F32) @ m32["router_in"] + m32["b_router_in"]
    if ROUTER_EDA and carry_router:
        r = r + m32["router_mix"] * r_prev
    z = _rms(r, m32["router_norm"], eps)
    for w_, b_ in (("router_w1", "b_router1"), ("router_w2", "b_router2")):
        z = jax.nn.gelu(z @ m32[w_] + m32[b_], approximate=not ROUTER_GELU_EXACT)
    probs = jax.nn.softmax(z @ m32["router"], axis=-1)        # [T, X + 1]
    X = m["w_gate"].shape[0]
    own = jnp.argmax(probs + m32["b_router"], axis=-1)
    chosen = jnp.where(forced >= 0, forced, own)
    g = jnp.take_along_axis(probs, chosen[:, None], axis=-1)[:, 0]
    y = jnp.zeros(x.shape, _F32)
    for ex in range(X):
        out = (jax.nn.silu(h @ m["w_gate"][ex]) * (h @ m["w_up"][ex])
               ) @ m["w_down"][ex]
        y = y + jnp.where(chosen == ex, g, 0.0)[:, None] * out.astype(_F32)
    if ROUTER_SKIP_OUTPUT and SKIP_IS_IDENTITY:
        y = y + jnp.where(chosen == X, g, 0.0)[:, None] * h.astype(_F32)
    x = _join(x, y.astype(dtype), lp["mlp_res"], dtype)
    return x, r, (own, chosen, probs)


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head_logprobs(x, final_w, embed_w, labels, *, eps, dtype):
    """log p(labels[t] | ..t) from the stack's output x [T, E]; the TIED
    head ``embed_w [V, E]`` applied in vocabulary blocks. Returns (logprob
    of label, max logprob), float32."""
    h = _rms(x, final_w.astype(dtype), eps)
    V = embed_w.shape[0]
    lse = jnp.full((x.shape[0],), -jnp.inf, _F32)
    top = jnp.full((x.shape[0],), -jnp.inf, _F32)
    picked = jnp.zeros((x.shape[0],), _F32)
    for lo in range(0, V, _VOCAB_BLOCK):
        hi = min(lo + _VOCAB_BLOCK, V)
        logits = (h @ embed_w[lo:hi].astype(dtype).T).astype(_F32)
        lse = jnp.logaddexp(lse, jax.nn.logsumexp(logits, axis=-1))
        top = jnp.maximum(top, logits.max(axis=-1))
        inside = (labels >= lo) & (labels < hi)
        idx = jnp.clip(labels - lo, 0, hi - lo - 1)
        here = jnp.take_along_axis(logits, idx[:, None], axis=-1)[:, 0]
        picked = jnp.where(inside, here, picked)
    return picked - lse, top - lse


def _statics(arch: dict, dt) -> dict:
    rope = arch["rope_parameters"]["hybrid"]
    return dict(
        n_q=arch["num_attention_heads"], n_kv=arch["num_key_value_heads"],
        eps=float(arch["rms_norm_eps"]), theta=float(rope["rope_theta"]),
        rot=int(arch["head_dim"] * float(rope["partial_rotary_factor"])),
        dtype=dt)


def _starts(arch: dict, T: int):
    """Where each position's run began: 0, or under
    ``control_zero_carry_at`` that position from there on."""
    cut = arch.get("control_zero_carry_at")
    if cut is None:
        return jnp.zeros((T,), jnp.int32)
    return jnp.where(jnp.arange(T) >= cut, cut, 0).astype(jnp.int32)


def _forward(params, arch: dict, ids, valid, dt):
    """(log p of the next token, largest log p, routing [L, T] the
    router's own choices, margins [L, T] between its first and second
    output's biased probability)."""
    if arch.get("sliding_window") is not None or any(
            t != "hybrid" for t in arch["layer_types"]):
        raise ValueError("zaya: only 'hybrid' layers without a window")
    kw = dict(
        _statics(arch, dt),
        carry_router=not arch.get("control_no_router_state", False))
    T = ids.shape[0]
    labels = jnp.concatenate([ids[1:], ids[:1]])
    starts = _starts(arch, T)
    forced = arch.get("forced_routing")
    x = params["embed"]["weight"][ids].astype(dt)
    r = jnp.zeros((T, arch["router_hidden_size"]), _F32)
    routing, margins = [], []
    for l in range(arch["num_hidden_layers"]):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        f = jnp.full((T,), -1, jnp.int32)
        if forced is not None:
            n = forced.shape[1]
            f = f.at[:n].set(jnp.asarray(forced[l], jnp.int32))
        x, r, (own, _, probs) = _layer(x, r, lp, valid, starts, f, **kw)
        routing.append(own)
        best = jax.lax.top_k(
            probs + lp["mlp"]["b_router"].astype(_F32), 2)[0]
        margins.append(best[:, 0] - best[:, 1])
    lp_tok, lp_max = _head_logprobs(
        x, params["final_ln"]["weight"], params["embed"]["weight"], labels,
        eps=kw["eps"], dtype=dt)
    return lp_tok, lp_max, jnp.stack(routing), jnp.stack(margins)


def _run(params, arch, tokens, dtype, pad_to):
    dt = jnp.dtype(dtype)
    n = len(tokens)
    ids = np.zeros((pad_to,), np.int32)
    ids[:n] = tokens
    valid = jnp.asarray(np.arange(pad_to) < n)
    precision = "highest" if dt == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        out = _forward(params, arch, jnp.asarray(ids), valid, dt)
    return n, jax.device_get(out)


def next_token_logprobs(params, arch: dict, tokens, dtype: str, pad_to: int):
    """``tokens``: one sequence of ids. Returns float32 numpy arrays of
    length len(tokens)-1: log p(tokens[t+1] | tokens[..t]) and the largest
    log-probability at that position."""
    n, (lp_tok, lp_max, _, _) = _run(params, arch, tokens, dtype, pad_to)
    return np.asarray(lp_tok[: n - 1]), np.asarray(lp_max[: n - 1])


def routing(params, arch: dict, tokens, dtype: str, pad_to: int):
    """The router's own choice in every layer at every position, int
    ``[L, len(tokens)]`` (``num_experts``: the skip), and the margin
    between its first and second output there."""
    n, (_, _, chosen, margins) = _run(params, arch, tokens, dtype, pad_to)
    return np.asarray(chosen[:, :n]), np.asarray(margins[:, :n])


def kv_at(params, arch: dict, tokens, position: int, layers=(0,),
          dtype: str = "float32"):
    """What a cache holds of ``tokens[position]`` in each of ``layers``:
    the key (after the convolutions, the mean, the norm and the rotary
    embedding) and the value (after the shift), float32 ``[len(layers), 2,
    Hkv, D]``. The first layer's inputs are the embeddings, so no router's
    choice is behind it: what the program's pool holds there can be held
    to it closely; behind a deeper layer's are the routers' own choices in
    the layers before it."""
    dt = jnp.dtype(dtype)
    ids = jnp.asarray(np.asarray(tokens[: position + 1], np.int32))
    T = len(ids)
    kw = dict(
        _statics(arch, dt),
        carry_router=not arch.get("control_no_router_state", False))
    qkv_kw = {k: v for k, v in kw.items() if k != "carry_router"}
    starts, valid = _starts(arch, T), jnp.ones((T,), bool)
    free = jnp.full((T,), -1, jnp.int32)
    precision = "highest" if dt == jnp.float32 else "default"
    out = {}
    with jax.default_matmul_precision(precision):
        x = params["embed"]["weight"][ids].astype(dt)
        r = jnp.zeros((T, arch["router_hidden_size"]), _F32)
        for l in range(max(layers) + 1):
            lp = jax.tree.map(lambda a: a[l], params["layers"])
            if l in layers:
                _, k, v = _qkv(x, jax.tree.map(lambda a: a.astype(dt), {
                    name: lp[name] for name in ("ln1", "attn")}),
                    starts, **qkv_kw)
                out[l] = np.stack([np.asarray(k[-1], np.float32),
                                   np.asarray(v[-1], np.float32)])
            if l < max(layers):
                x, r, _ = _layer(x, r, lp, valid, starts, free, **kw)
    return np.stack([out[l] for l in layers])


def sequence_logprobs(params, arch: dict, ids, dtype: str = "float32"):
    """The same forward as one traceable function: float32
    ``log p(ids[t+1] | ids[..t])`` for t < len(ids)-1, differentiable in
    ``params``. For small sizes (every layer's residuals are kept)."""
    dt = jnp.dtype(dtype)
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        lp_tok = _forward(params, arch, ids, jnp.ones(ids.shape, bool), dt)[0]
    return lp_tok[:-1]


def loss(params, arch: dict, ids, dtype: str = "float32"):
    """Mean next-token cross entropy of one sequence; ``jax.grad`` of this
    plain function is the reference's gradient."""
    return -jnp.mean(sequence_logprobs(params, arch, ids, dtype))
