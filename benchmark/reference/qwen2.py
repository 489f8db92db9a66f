"""Plain reference for the qwen2 family (Qwen2 / R1-Distill-Qwen).

The published forward pass in straightforward ``jax.numpy``: RMSNorm,
grouped-query attention with qkv bias and half-split rotary embedding,
SwiGLU MLP, untied LM head; dense causal attention over the whole
sequence, no kernels, no cache, no batching. Independent of the program's
model code: it shares only the NAMES of the weight tree (``embed.weight``
[V,E]; ``layers.{ln1,ln2}.weight`` [L,E]; ``layers.attn.{wq,wk,wv,wo,
bq,bk,bv}``; ``layers.mlp.{w_gate,w_up,w_down}``; ``final_ln.weight``;
``head.weight`` [E,V]; matrices stored input-major, ``y = x @ w``).

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

_VOCAB_BLOCK = 16384


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta):
    """x [T, H, D], rotate-half convention of the HF implementation."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x32 = x.astype(jnp.float32)
    rot = jnp.concatenate([-x32[..., d // 2:], x32[..., : d // 2]], -1)
    return (x32 * cos + rot * sin).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("n_q", "n_kv", "eps", "theta", "dtype"))
def _layer(x, lp, valid, *, n_q, n_kv, eps, theta, dtype):
    """One decoder layer on x [T, E]; ``valid`` [T] masks padding keys."""
    lp = jax.tree.map(lambda a: a.astype(dtype), lp)
    T = x.shape[0]
    a = lp["attn"]
    h = _rms(x, lp["ln1"]["weight"], eps)
    q = (h @ a["wq"] + a["bq"]).reshape(T, n_q, -1)
    k = (h @ a["wk"] + a["bk"]).reshape(T, n_kv, -1)
    v = (h @ a["wv"] + a["bv"]).reshape(T, n_kv, -1)
    pos = jnp.arange(T)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    rep = n_q // n_kv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k).astype(jnp.float32)
    s = s * (q.shape[-1] ** -0.5)
    causal = (pos[None, :] <= pos[:, None]) & valid[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(dtype)
    ctx = jnp.einsum("hts,shd->thd", p, v).reshape(T, -1)
    x = x + ctx @ a["wo"]
    m = lp["mlp"]
    h = _rms(x, lp["ln2"]["weight"], eps)
    x = x + (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]
    return x


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head_logprobs(x, final_w, head_w, labels, *, eps, dtype):
    """log p(labels[t] | ..t) from hidden x [T, E]; head applied in
    vocabulary blocks. Returns (logprob of label, max logprob), float32."""
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


def next_token_logprobs(params, arch: dict, tokens, dtype: str, pad_to: int):
    """``tokens``: one sequence of ids. Returns float32 numpy arrays of
    length len(tokens)-1: log p(tokens[t+1] | tokens[..t]) and the largest
    log-probability at that position."""
    dt = jnp.dtype(dtype)
    n = len(tokens)
    ids = np.zeros((pad_to,), np.int32)
    ids[:n] = tokens
    valid = jnp.asarray(np.arange(pad_to) < n)
    ids = jnp.asarray(ids)
    labels = jnp.concatenate([ids[1:], ids[:1]])
    n_q = arch["num_attention_heads"]
    kw = dict(
        n_q=n_q, n_kv=arch.get("num_key_value_heads") or n_q,
        eps=float(arch["rms_norm_eps"]), theta=float(arch["rope_theta"]),
        dtype=dt,
    )
    precision = "highest" if dt == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        x = params["embed"]["weight"][ids].astype(dt)
        layers = params["layers"]
        for i in range(arch["num_hidden_layers"]):
            lp = jax.tree.map(lambda a: a[i], layers)
            x = _layer(x, lp, valid, **kw)
        lp_tok, lp_max = _head_logprobs(
            x, params["final_ln"]["weight"], params["head"]["weight"],
            labels, eps=kw["eps"], dtype=dt,
        )
    lp_tok, lp_max = jax.device_get((lp_tok, lp_max))
    return np.asarray(lp_tok[: n - 1]), np.asarray(lp_max[: n - 1])
