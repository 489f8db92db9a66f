"""Plain reference for the smallthinker family (SmallThinker-21BA3B).

The published forward pass in straightforward ``jax.numpy``, layer ``l`` of
the stack on the residual stream ``x``:

    h = RMSNorm_in(x)
    r = h @ W_router                    float32: the router reads the
                                        layer's normed INPUT, before attention
    q, k, v = h @ Wq, h @ Wk, h @ Wv    no bias, no q/k norm
    rotary on q, k where rope_layout[l] is 1 (half-split pairs, as the HF
        implementations rotate); NO positional encoding where it is 0
    a_i = softmax_j(q_i . k_j / sqrt(D)) v_j over j <= i, and where
        sliding_window_layout[l] is 1 also i - j < sliding_window_size
        (a query sees itself and window - 1 positions before it); the mask
        is built from the positions
    x = x + a @ Wo
    u = RMSNorm_post(x)
    S = top-k of r; w = softmax(r[S])   the published ORDER: the top
                                        logits first, then their softmax
    x = x + sum_{e in S} w_e (relu(u @ Wgate_e) * (u @ Wup_e)) @ Wdown_e

then the final RMSNorm and the untied head. Dense attention over the whole
sequence computed in blocks of QUERIES (so that 6,144 tokens fit beside
7.93 GB of weights: the scores of one block are ``[H, block, T]``), no
kernel, no cache, no batching, no sort, no grouped matmul: every expert is
applied to every token in a loop over the experts and multiplied by its
combine weight, which is zero where the router did not choose it.
Independent of the program's model code: it shares only the NAMES of the
weight tree (``embed.weight`` [V,E]; ``layers.{ln1,ln2}.weight`` [L,E];
``layers.attn.{wq,wk,wv,wo}``; ``layers.mlp.router`` [L,E,X],
``layers.mlp.{w_gate,w_up}`` [L,X,E,F], ``layers.mlp.w_down`` [L,X,F,E];
``final_ln.weight``; ``head.weight`` [E,V]; matrices stored input-major,
``y = x @ w``).

``window`` (every entry point): ``"config"`` is the configuration's own
layout; ``None`` makes EVERY layer full, which is what a program that
forgot the window (or read pages it had given back as if they were still
its own) would compute: the benchmark's second control
(``drivers/rollout_hybrid_inproc.py``).

Departures from a textbook forward, all to fit beside a model that fills
the chip and none changing the mathematics: the attention weights of one
layer at a time are cast from the stored dtype to the compute dtype and
each expert's three matrices inside the loop over experts; the embedding
rows are gathered before the cast; the LM head is applied in vocabulary
blocks with a running log-sum-exp. In float32 it runs under
``jax.default_matmul_precision("highest")`` (a TPU otherwise multiplies
float32 in bf16 passes).

For the CPU tests: ``sequence_logprobs`` is the same forward as ONE
traceable function of the weights (``jax.grad`` of a loss built on it is
the reference for the trainer's gradients); ``routing`` gives the experts
each token chose in each layer and their combine weights.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

# the same RMSNorm, rotate-half rotary and blockwise head as the qwen2
# reference: one plain implementation of each, not the program's
from benchmark.reference.qwen2 import _head_logprobs, _rms, _rope

_QUERY_BLOCK = 512


def _route(h, router, top_k):
    """The published order: the ``top_k`` largest LOGITS (float32), then
    the softmax of those alone. ``(weights [T, k], experts [T, k])``."""
    logits = h.astype(jnp.float32) @ router.astype(jnp.float32)
    vals, idx = jax.lax.top_k(logits, top_k)
    return jax.nn.softmax(vals, axis=-1), idx


def _experts(u, m, w, idx, dtype):
    """u [T, E] -> sum over the chosen experts of w_e * ReGLU_e(u)."""
    rows = jnp.arange(u.shape[0])[:, None]
    X = m["router"].shape[-1]
    combine = jnp.zeros((u.shape[0], X), jnp.float32).at[rows, idx].set(w)

    def one_expert(acc, e):
        gate, up, down, c = e
        gate, up, down = (a.astype(dtype) for a in (gate, up, down))
        y = (jax.nn.relu(u @ gate) * (u @ up)) @ down
        return acc + y * c[:, None].astype(dtype), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (m["w_gate"], m["w_up"], m["w_down"], combine.T),
    )
    return out


def _attention(q, k, v, valid, window):
    """q [T, Hq, D], k/v [T, Hkv, D] -> [T, Hq * D]: causal softmax
    attention, in blocks of queries, the mask from the positions."""
    T, n_q, D = q.shape
    rep = n_q // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    pos = jnp.arange(T)
    out = []
    for lo in range(0, T, _QUERY_BLOCK):
        qpos = pos[lo: lo + _QUERY_BLOCK]
        s = jnp.einsum("thd,shd->hts", q[lo: lo + _QUERY_BLOCK], k)
        s = s.astype(jnp.float32) * (D ** -0.5)
        # (a padding query sees itself, so that no row is all masked)
        mask = (pos[None, :] <= qpos[:, None]) & (
            valid[None, :] | (pos[None, :] == qpos[:, None]))
        if window is not None:
            mask &= qpos[:, None] - pos[None, :] < window
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        out.append(jnp.einsum("hts,shd->thd", p, v).reshape(len(qpos), -1))
    return jnp.concatenate(out)


@functools.partial(jax.jit, static_argnames=(
    "n_q", "n_kv", "eps", "theta", "top_k", "window", "rotary", "dtype"))
def _layer(x, lp, valid, *, n_q, n_kv, eps, theta, top_k, window, rotary,
           dtype):
    """One decoder layer on x [T, E]; ``valid`` [T] masks padding keys.
    Returns (x, chosen experts [T, top_k], their weights [T, top_k])."""
    T = x.shape[0]
    a = jax.tree.map(lambda t: t.astype(dtype), lp["attn"])
    h = _rms(x, lp["ln1"]["weight"].astype(dtype), eps)
    w, idx = _route(h, lp["mlp"]["router"], top_k)    # BEFORE attention
    q = (h @ a["wq"]).reshape(T, n_q, -1)
    k = (h @ a["wk"]).reshape(T, n_kv, -1)
    v = (h @ a["wv"]).reshape(T, n_kv, -1)
    if rotary:
        pos = jnp.arange(T)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    x = x + _attention(q, k, v, valid, window) @ a["wo"]
    u = _rms(x, lp["ln2"]["weight"].astype(dtype), eps)
    return x + _experts(u, lp["mlp"], w, idx, dtype), idx, w


def _layer_kinds(arch: dict, window):
    """``(window, rotary)`` of every layer. ``window``: ``"config"`` or
    ``None`` (every layer full)."""
    L = arch["num_hidden_layers"]
    size = arch["sliding_window_size"]
    return [
        (size if window == "config" and arch["sliding_window_layout"][l]
         else None, bool(arch["rope_layout"][l]))
        for l in range(L)
    ]


def _forward(params, arch, ids, valid, dt, window="config"):
    """(log p of the next token, largest log p, chosen experts [L, T, K],
    their weights [L, T, K])."""
    n_q = arch["num_attention_heads"]
    kw = dict(
        n_q=n_q, n_kv=arch.get("num_key_value_heads") or n_q,
        eps=float(arch["rms_norm_eps"]), theta=float(arch["rope_theta"]),
        top_k=arch["moe_num_active_primary_experts"], dtype=dt,
    )
    labels = jnp.concatenate([ids[1:], ids[:1]])
    x = params["embed"]["weight"][ids].astype(dt)
    chosen, weights = [], []
    for l, (win, rotary) in enumerate(_layer_kinds(arch, window)):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        x, idx, w = _layer(x, lp, valid, window=win, rotary=rotary, **kw)
        chosen.append(idx)
        weights.append(w)
    lp_tok, lp_max = _head_logprobs(
        x, params["final_ln"]["weight"], params["head"]["weight"],
        labels, eps=kw["eps"], dtype=dt,
    )
    return lp_tok, lp_max, jnp.stack(chosen), jnp.stack(weights)


def next_token_logprobs(params, arch: dict, tokens, dtype: str, pad_to: int,
                        window="config"):
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
        lp_tok, lp_max, _, _ = _forward(
            params, arch, jnp.asarray(ids), valid, dt, window)
    lp_tok, lp_max = jax.device_get((lp_tok, lp_max))
    return np.asarray(lp_tok[: n - 1]), np.asarray(lp_max[: n - 1])


def sequence_logprobs(params, arch: dict, ids, dtype: str = "float32",
                      window="config"):
    """The same forward as one traceable function: float32
    ``log p(ids[t+1] | ids[..t])`` for t < len(ids)-1, differentiable in
    ``params``. For small sizes (every layer's residuals are kept)."""
    dt = jnp.dtype(dtype)
    ids = jnp.asarray(ids, jnp.int32)
    valid = jnp.ones(ids.shape, bool)
    with jax.default_matmul_precision("highest"):
        lp_tok, _, _, _ = _forward(params, arch, ids, valid, dt, window)
    return lp_tok[:-1]


def routing(params, arch: dict, ids, dtype: str = "float32"):
    """The experts each token chose, int32 ``[L, T, k]`` (largest weight
    first), and their combine weights, float32 ``[L, T, k]``."""
    dt = jnp.dtype(dtype)
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        _, _, chosen, weights = _forward(
            params, arch, ids, jnp.ones(ids.shape, bool), dt)
    return chosen, weights
