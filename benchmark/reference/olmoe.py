"""Plain reference for the olmoe family (OLMoE-1B-7B).

The published forward pass (the public HF implementation,
``modeling_olmoe.py``) in straightforward ``jax.numpy``: RMSNorm;
multi-head attention without bias whose ``q_norm`` / ``k_norm`` are
RMSNorms over the WHOLE projected vector (``[Hq*D]`` / ``[Hkv*D]``),
applied before the split into heads and before the half-split rotary
embedding; a sparse MLP: softmax over ALL router logits in float32, the
``num_experts_per_tok`` largest kept with the weights the softmax gave
them (renormalised only if ``norm_topk_prob``), SwiGLU experts summed with
those weights; untied LM head. Dense causal attention over the whole
sequence, no kernels, no cache, no batching, no sort, no grouped matmul:
every expert is applied to every token in a plain loop over the experts
and multiplied by its combine weight, which is zero where the router did
not choose it. Independent of the program's model code: it shares only
the NAMES of the weight tree (``embed.weight`` [V,E];
``layers.{ln1,ln2}.weight`` [L,E]; ``layers.attn.{wq,wk,wv,wo}``,
``layers.attn.{q_norm,k_norm}`` [L,H*D]; ``layers.mlp.router`` [L,E,X],
``layers.mlp.{w_gate,w_up}`` [L,X,E,F], ``layers.mlp.w_down`` [L,X,F,E];
``final_ln.weight``; ``head.weight`` [E,V]; matrices stored input-major,
``y = x @ w``).

Departures from a textbook forward, all to fit beside a model that fills
the chip: one layer at a time is cast from the stored dtype to the compute
dtype, the experts are a ``lax.scan`` (one expert's three matrices live
at a time, not a ``[T, X, F]`` intermediate), the embedding rows are
gathered before the cast, and the LM head is applied in vocabulary blocks
with a running log-sum-exp. None changes the mathematics. In float32 it
runs under ``jax.default_matmul_precision("highest")`` (a TPU otherwise
multiplies float32 in bf16 passes).

For the CPU tests of the trainer, ``sequence_logprobs`` is the same
forward as ONE traceable function of the weights (no padding, no host
round trip), so ``jax.grad`` of a loss built on it is the reference for
the trainer's gradients; ``routing`` gives the experts each token chose
in each layer.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

# the same RMSNorm, rotate-half rotary and blockwise head as the qwen2
# reference: one plain implementation of each, not the program's
from benchmark.reference.qwen2 import _head_logprobs, _rms, _rope


def _sparse_mlp(h, m, *, top_k, norm_topk, dtype):
    """h [T, E] -> ([T, E], chosen experts [T, top_k])."""
    logits = h @ m["router"]                                   # [T, X]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    vals, idx = jax.lax.top_k(probs, top_k)
    if norm_topk:
        vals = vals / vals.sum(axis=-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    combine = jnp.zeros_like(probs).at[rows, idx].set(vals).astype(dtype)

    def one_expert(acc, w):
        gate, up, down, c = w
        y = (jax.nn.silu(h @ gate) * (h @ up)) @ down
        return acc + y * c[:, None], None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (m["w_gate"], m["w_up"], m["w_down"], combine.T),
    )
    return out, idx


@functools.partial(jax.jit, static_argnames=(
    "n_q", "n_kv", "eps", "theta", "top_k", "norm_topk", "dtype"))
def _layer(x, lp, valid, *, n_q, n_kv, eps, theta, top_k, norm_topk, dtype):
    """One decoder layer on x [T, E]; ``valid`` [T] masks padding keys.
    Returns (x, chosen experts [T, top_k])."""
    lp = jax.tree.map(lambda a: a.astype(dtype), lp)
    T = x.shape[0]
    a = lp["attn"]
    h = _rms(x, lp["ln1"]["weight"], eps)
    q = _rms(h @ a["wq"], a["q_norm"], eps).reshape(T, n_q, -1)
    k = _rms(h @ a["wk"], a["k_norm"], eps).reshape(T, n_kv, -1)
    v = (h @ a["wv"]).reshape(T, n_kv, -1)
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
    h = _rms(x, lp["ln2"]["weight"], eps)
    m, idx = _sparse_mlp(
        h, lp["mlp"], top_k=top_k, norm_topk=norm_topk, dtype=dtype)
    return x + m, idx


def _layer_kw(arch: dict, dt):
    n_q = arch["num_attention_heads"]
    return dict(
        n_q=n_q, n_kv=arch.get("num_key_value_heads") or n_q,
        eps=float(arch["rms_norm_eps"]), theta=float(arch["rope_theta"]),
        top_k=arch["num_experts_per_tok"],
        norm_topk=bool(arch.get("norm_topk_prob", False)), dtype=dt,
    )


def _forward(params, arch, ids, valid, dt):
    """(log p of the next token, largest log p, chosen experts [L, T, K])."""
    kw = _layer_kw(arch, dt)
    labels = jnp.concatenate([ids[1:], ids[:1]])
    x = params["embed"]["weight"][ids].astype(dt)
    chosen = []
    for i in range(arch["num_hidden_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        x, idx = _layer(x, lp, valid, **kw)
        chosen.append(idx)
    lp_tok, lp_max = _head_logprobs(
        x, params["final_ln"]["weight"], params["head"]["weight"],
        labels, eps=kw["eps"], dtype=dt,
    )
    return lp_tok, lp_max, jnp.stack(chosen)


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
        lp_tok, lp_max, _ = _forward(params, arch, jnp.asarray(ids), valid, dt)
    lp_tok, lp_max = jax.device_get((lp_tok, lp_max))
    return np.asarray(lp_tok[: n - 1]), np.asarray(lp_max[: n - 1])


def sequence_logprobs(params, arch: dict, ids, dtype: str = "float32"):
    """The same forward as one traceable function: float32
    ``log p(ids[t+1] | ids[..t])`` for t < len(ids)-1, differentiable in
    ``params``. For small sizes (every layer's residuals are kept)."""
    dt = jnp.dtype(dtype)
    ids = jnp.asarray(ids, jnp.int32)
    valid = jnp.ones(ids.shape, bool)
    with jax.default_matmul_precision("highest"):
        lp_tok, _, _ = _forward(params, arch, ids, valid, dt)
    return lp_tok[:-1]


def routing(params, arch: dict, ids, dtype: str = "float32"):
    """The experts each token chose: int32 ``[L, T, num_experts_per_tok]``
    (in the order of their weights, largest first)."""
    dt = jnp.dtype(dtype)
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        _, _, chosen = _forward(
            params, arch, ids, jnp.ones(ids.shape, bool), dt)
    return chosen
