"""Plain reference for the ouro family (Ouro-1.4B / 2.6B: a LOOPED stack).

The forward pass in straightforward ``jax.numpy``: a Python loop over the
``total_ut_steps`` passes and, inside it, over the ``num_hidden_layers``
layers, the SAME weights at every pass; multi-head attention with
half-split rotary embedding at the token's position (the same at every
pass), SwiGLU, four RMSNorms a layer (one before each branch, one on each
branch's output before it is added), the model's one final norm after
EVERY pass, an untied LM head after the last. Dense causal attention over
the whole sequence, recomputed in every pass from that pass's own keys and
values; no kernels, no cache, no batching. Independent of the program's
model code: it shares only the NAMES of the weight tree (``embed.weight``
[V,E]; ``layers.{ln1,attn_out_ln,ln2,mlp_out_ln}.weight`` [L,E];
``layers.attn.{wq,wk,wv,wo}``; ``layers.mlp.{w_gate,w_up,w_down}``;
``final_ln.weight``; ``head.weight`` [E,V]; ``exit_gate.{weight,bias}``,
which nothing here reads; matrices stored input-major, ``y = x @ w``).

What was written from MEMORY of the family's public ``modeling_ouro.py``
(no copy of it, and no network, where this was written) is one constant
each, below: a correction is one line. The exit gate (a ``Linear(hidden,
1)`` on each pass's normed output, whose cumulative probability lets a
token leave the loop once it reaches ``early_exit_threshold``) never fires
at the published threshold of 1.0: every token takes every pass and the
logits are the head of the last. A threshold below 1 is refused.

Departures from a textbook forward, all to fit beside a model that fills
the chip: one layer at a time is cast from the stored dtype to the compute
dtype, the embedding rows are gathered before the cast, and the LM head is
applied in vocabulary blocks with a running log-sum-exp. None changes the
mathematics. In float32 it runs under
``jax.default_matmul_precision("highest")`` (a TPU otherwise multiplies
float32 in bf16 passes).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

# (m) the q/k/v/o projections carry no bias (the published config has no
# ``attention_bias`` key)
QKV_BIAS = False
# (m) ``input_layernorm_2`` / ``post_attention_layernorm_2`` norm the
# BRANCH's output, which is then added: x + norm(branch(norm(x)))
NORM_BRANCH_OUT = True
# (m) ``model.norm`` is applied after EVERY pass; pass t + 1 reads it
FINAL_NORM_EVERY_PASS = True
# (m) a pass attends over the keys and values IT computed (a cache layer a
# pass); False: over those of the first pass, the paper's sharing variant
KV_OF_ITS_OWN_PASS = True

_VOCAB_BLOCK = 16384


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta):
    """x [T, H, D], rotate-half convention: pairs ``(i, i + D/2)``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x32 = x.astype(jnp.float32)
    rot = jnp.concatenate([-x32[..., d // 2:], x32[..., : d // 2]], -1)
    return (x32 * cos + rot * sin).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("n_q", "n_kv", "eps", "theta", "dtype"))
def _layer(x, lp, valid, kv, *, n_q, n_kv, eps, theta, dtype):
    """One decoder layer on x [T, E]; ``valid`` [T] masks padding keys.
    ``kv``: keys and values to attend over in place of the layer's own
    (``None``: its own). Returns ``(x, (k, v))``."""
    lp = jax.tree.map(lambda a: a.astype(dtype), lp)
    T = x.shape[0]
    a = lp["attn"]
    h = _rms(x, lp["ln1"]["weight"], eps)
    q, k, v = h @ a["wq"], h @ a["wk"], h @ a["wv"]
    if QKV_BIAS:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    pos = jnp.arange(T)
    q = _rope(q.reshape(T, n_q, -1), pos, theta)
    k = _rope(k.reshape(T, n_kv, -1), pos, theta)
    v = v.reshape(T, n_kv, -1)
    own = (k, v)
    if kv is not None:
        k, v = kv
    rep = n_q // n_kv
    kk, vv = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("thd,shd->hts", q, kk).astype(jnp.float32)
    s = s * (q.shape[-1] ** -0.5)
    causal = (pos[None, :] <= pos[:, None]) & valid[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(dtype)
    branch = jnp.einsum("hts,shd->thd", p, vv).reshape(T, -1) @ a["wo"]
    if NORM_BRANCH_OUT:
        branch = _rms(branch, lp["attn_out_ln"]["weight"], eps)
    x = x + branch
    m = lp["mlp"]
    h = _rms(x, lp["ln2"]["weight"], eps)
    branch = (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]
    if NORM_BRANCH_OUT:
        branch = _rms(branch, lp["mlp_out_ln"]["weight"], eps)
    return x + branch, own


@functools.partial(jax.jit, static_argnames=("eps", "dtype", "normed"))
def _head_logprobs(x, final_w, head_w, labels, *, eps, dtype, normed):
    """log p(labels[t] | ..t) from hidden x [T, E] (``normed``: the final
    norm was applied already); head applied in vocabulary blocks. Returns
    (logprob of label, max logprob), float32."""
    h = x if normed else _rms(x, final_w.astype(dtype), eps)
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


def _forward(params, arch: dict, ids, valid, dt, untied: bool = False):
    """(log p of the next token, largest log p). ``untied``: the stack of
    ``params["layers"]`` has a leading axis over the PASSES, ``[T, L,
    ...]``, one copy of the weights a pass (the tests' gradient check)."""
    if float(arch.get("early_exit_threshold", 1.0)) < 1.0:
        raise ValueError("ouro: early_exit_threshold below 1 is not supported")
    n_q = arch["num_attention_heads"]
    kw = dict(
        n_q=n_q, n_kv=arch.get("num_key_value_heads") or n_q,
        eps=float(arch["rms_norm_eps"]), theta=float(arch["rope_theta"]),
        dtype=dt,
    )
    labels = jnp.concatenate([ids[1:], ids[:1]])
    final_w = params["final_ln"]["weight"]
    x = params["embed"]["weight"][ids].astype(dt)           # no scaling
    first_kv = {}
    for t in range(arch["total_ut_steps"]):
        for l in range(arch["num_hidden_layers"]):
            lp = jax.tree.map(
                lambda a: a[t, l] if untied else a[l], params["layers"])
            kv = None if KV_OF_ITS_OWN_PASS or t == 0 else first_kv[l]
            x, own = _layer(x, lp, valid, kv, **kw)
            if t == 0 and not KV_OF_ITS_OWN_PASS:
                first_kv[l] = own
        if FINAL_NORM_EVERY_PASS:
            x = _rms(x, final_w.astype(dt), kw["eps"])
    return _head_logprobs(
        x, final_w, params["head"]["weight"], labels, eps=kw["eps"],
        dtype=dt, normed=FINAL_NORM_EVERY_PASS,
    )


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
        lp_tok, lp_max = _forward(params, arch, jnp.asarray(ids), valid, dt)
    lp_tok, lp_max = jax.device_get((lp_tok, lp_max))
    return np.asarray(lp_tok[: n - 1]), np.asarray(lp_max[: n - 1])


def sequence_logprobs(params, arch: dict, ids, dtype: str = "float32",
                      untied: bool = False):
    """The same forward as one traceable function: float32
    ``log p(ids[t+1] | ids[..t])`` for t < len(ids)-1, differentiable in
    ``params``. For small sizes (every layer's residuals are kept)."""
    dt = jnp.dtype(dtype)
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        lp_tok, _ = _forward(
            params, arch, ids, jnp.ones(ids.shape, bool), dt, untied)
    return lp_tok[:-1]


def loss(params, arch: dict, ids, dtype: str = "float32",
         untied: bool = False):
    """Mean next-token cross entropy of one sequence; ``jax.grad`` of this
    plain function is the reference's gradient."""
    return -jnp.mean(sequence_logprobs(params, arch, ids, dtype, untied))
