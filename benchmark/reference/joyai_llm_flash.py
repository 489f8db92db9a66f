"""Plain reference for the joyai_llm_flash family (JoyAI-LLM-Flash).

The published forward pass (key for key a ``deepseek_v3`` config; the
public HF implementation ``modeling_deepseek_v3.py`` and the DeepSeek-V3
report) in straightforward ``jax.numpy``: pre-norm residual blocks with
RMSNorm; multi-head LATENT attention in its EXPANDED form: the query
through a normed latent (``q_a`` / ``q_b``), ONE normed key/value latent a
token (``kv_a``) up-projected to per-head ``k_nope`` and ``v`` (``kv_b``),
beside one rotary key shared by all heads; rotary on the rope parts only,
pairs ``(2i, 2i+1)`` rotated IN PLACE (``rope_interleave``); scores over
``[nope ; rope]`` scaled by ``(nope + rope) ** -0.5``; no bias. The first
``first_k_dense_replace`` layers have a dense SwiGLU; the others a sparse
MLP: ``s = sigmoid(logits)`` in float32, the ``num_experts_per_tok``
experts with the largest ``s + e_score_correction_bias``, weights ``s`` of
the chosen (without the bias) over their sum (+1e-20) if
``norm_topk_prob``, times ``routed_scaling_factor``; plus the shared
expert on every token. Untied LM head.

No absorption of the up-projections, no cache, no kernels, no batching, no
einsum over experts: every expert is applied to every token in a plain
loop over the experts (``lax.scan``) and multiplied by its combine weight,
which is zero where the router did not choose it. Independent of the
program's model code: it shares only the NAMES of the weight tree
(``embed.weight`` [V,E]; two stacks of layers, ``dense_layers`` then
``layers``, each ``{ln1,ln2}.weight`` [L,E], ``attn.{wq_a [L,E,rq],
q_a_norm [L,rq], wq_b [L,rq,H*(nope+rope)], wkv_a [L,E,rkv+rope],
kv_a_norm [L,rkv], wkv_b [L,rkv,H*(nope+v)], wo [L,H*v,E]}``;
``mlp.{w_gate,w_up,w_down}`` dense, or ``mlp.{router [L,E,X], b_router
[L,X], w_gate,w_up [L,X,E,F], w_down [L,X,F,E], shared_gate, shared_up
[L,E,Fs], shared_down [L,Fs,E]}``; ``final_ln.weight``; ``head.weight``
[E,V]; ``mtp.{e_norm,h_norm}.weight`` [N,E], ``mtp.eh_proj`` [N,2E,E],
``mtp.block`` one more stack; matrices stored input-major, ``y = x @ w``).

Departures from the published description, all to fit beside a model that
fills the chip and none changing the mathematics: the attention weights
and the shared expert of one layer at a time are cast from the stored
dtype to the compute dtype, and each routed expert's three matrices are
cut out of the stack and cast INSIDE the loop over experts (a layer's
experts are 2.4 GB as a copy and 4.8 GB in float32, beside 11.1 GB of
weights); the embedding rows are
gathered before the cast; the LM head is applied in vocabulary blocks with
a running log-sum-exp. In float32 it runs under
``jax.default_matmul_precision("highest")`` (a TPU otherwise multiplies
float32 in bf16 passes). The multi-token-prediction module concatenates
``[norm(embedding of the next token) ; norm(hidden)]`` in that order and
takes as hidden the stack's output before the final norm (the
configuration file's ``assumed``).

For the CPU tests: ``sequence_logprobs`` is the same forward as ONE
traceable function of the weights (``jax.grad`` of a loss built on it is
the reference for the trainer's gradients); ``routing`` gives the experts
each token chose in each expert layer and their combine weights;
``mtp_logprobs`` the multi-token-prediction module's log-probabilities.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

# the same RMSNorm and blockwise head as the qwen2 reference: one plain
# implementation of each, not the program's
from benchmark.reference.qwen2 import _head_logprobs, _rms


def _rope_pairs(x, positions, theta):
    """x [T, H, d]: pair ``(x[2i], x[2i+1])`` rotated by ``pos * theta **
    (-2i/d)``, left where it was."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]     # [T, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _attention(h, a, valid, *, n_heads, nope, rope, v_dim, eps, theta):
    """Expanded latent attention on h [T, E] -> [T, E]."""
    T = h.shape[0]
    pos = jnp.arange(T)
    c_q = _rms(h @ a["wq_a"], a["q_a_norm"], eps)
    q = (c_q @ a["wq_b"]).reshape(T, n_heads, nope + rope)
    kv_a = h @ a["wkv_a"]
    rank = a["kv_a_norm"].shape[-1]
    c_kv = _rms(kv_a[:, :rank], a["kv_a_norm"], eps)
    k_r = _rope_pairs(kv_a[:, None, rank:], pos, theta)                 # [T, 1, rope]
    kv = (c_kv @ a["wkv_b"]).reshape(T, n_heads, nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_nope, q_r = q[..., :nope], _rope_pairs(q[..., nope:], pos, theta)
    s = (
        jnp.einsum("thd,shd->hts", q_nope, k_nope)
        + jnp.einsum("thd,sd->hts", q_r, k_r[:, 0])
    ).astype(jnp.float32) * ((nope + rope) ** -0.5)
    causal = (pos[None, :] <= pos[:, None]) & valid[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(h.dtype)
    ctx = jnp.einsum("hts,shd->thd", p, v).reshape(T, -1)
    return ctx @ a["wo"]


def _route(h, router, bias, *, top_k, norm_topk, scale):
    """h [T, E] -> (chosen [T, K], weights [T, K], scores [T, X])."""
    logits = h.astype(jnp.float32) @ router.astype(jnp.float32)
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)        # WITHOUT the bias
    if norm_topk:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return idx, w * scale, s


def _sparse_mlp(h, m, j, *, top_k, norm_topk, scale, dtype):
    """h [T, E] -> ([T, E], chosen [T, K], weights [T, K]). ``m`` is the
    WHOLE stack's expert weights in the stored dtype and ``j`` the layer:
    each expert's three matrices are cut out and cast inside the loop, so
    neither a layer's copy (2.4 GB) nor its float32 form (4.8 GB) exists."""
    at = lambda name: m[name][j]                    # small leaves only
    idx, w, s = _route(
        h, at("router"), at("b_router"),
        top_k=top_k, norm_topk=norm_topk, scale=scale)
    rows = jnp.arange(h.shape[0])[:, None]
    combine = jnp.zeros_like(s).at[rows, idx].set(w).astype(dtype)

    def one_expert(acc, x):
        gate, up, down = (
            m[k][j, x].astype(dtype) for k in ("w_gate", "w_up", "w_down"))
        y = (jax.nn.silu(h @ gate) * (h @ up)) @ down
        return acc + y * combine[:, x][:, None], None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h), jnp.arange(s.shape[-1]))
    if "shared_gate" in m:
        sg, su, sd = (at(k).astype(dtype) for k in
                      ("shared_gate", "shared_up", "shared_down"))
        out = out + (jax.nn.silu(h @ sg) * (h @ su)) @ sd
    return out, idx, w


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "nope", "rope", "v_dim", "eps", "theta",
    "top_k", "norm_topk", "scale", "dtype"))
def _layer(x, stack, j, valid, *, n_heads, nope, rope, v_dim, eps, theta,
           top_k, norm_topk, scale, dtype):
    """Decoder layer ``j`` of ``stack`` (a stack of identical layers, in
    the stored dtype) on x [T, E]; ``valid`` [T] masks padding keys. Dense
    or sparse by its tree. Returns (x, chosen or None, weights or None)."""
    cast = lambda t: jax.tree.map(lambda a: a[j].astype(dtype), t)
    h = _rms(x, stack["ln1"]["weight"][j].astype(dtype), eps)
    x = x + _attention(
        h, cast(stack["attn"]), valid, n_heads=n_heads, nope=nope, rope=rope,
        v_dim=v_dim, eps=eps, theta=theta)
    h = _rms(x, stack["ln2"]["weight"][j].astype(dtype), eps)
    m = stack["mlp"]
    if "router" not in m:
        m = cast(m)
        y = (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]
        return x + y, None, None
    y, idx, w = _sparse_mlp(
        h, m, j, top_k=top_k, norm_topk=norm_topk, scale=scale, dtype=dtype)
    return x + y, idx, w


def _layer_kw(arch: dict, dt):
    return dict(
        n_heads=arch["num_attention_heads"],
        nope=arch["qk_nope_head_dim"], rope=arch["qk_rope_head_dim"],
        v_dim=arch["v_head_dim"], eps=float(arch["rms_norm_eps"]),
        theta=float(arch["rope_theta"]),
        top_k=arch["num_experts_per_tok"],
        norm_topk=bool(arch.get("norm_topk_prob", True)),
        scale=float(arch.get("routed_scaling_factor", 1.0)), dtype=dt,
    )


def _stack(params, arch, ids, valid, dt):
    """(stack output [T, E] before the final norm, chosen [Lx, T, K],
    weights [Lx, T, K]) over the dense layers, then the expert layers."""
    kw = _layer_kw(arch, dt)
    x = params["embed"]["weight"][ids].astype(dt)
    n_dense = arch.get("first_k_dense_replace", 0)
    chosen, weights = [], []
    for i in range(arch["num_hidden_layers"]):
        stack, j = ((params["dense_layers"], i) if i < n_dense
                    else (params["layers"], i - n_dense))
        x, idx, w = _layer(x, stack, jnp.int32(j), valid, **kw)
        if idx is not None:
            chosen.append(idx)
            weights.append(w)
    return x, jnp.stack(chosen), jnp.stack(weights)


def _forward(params, arch, ids, valid, dt):
    labels = jnp.concatenate([ids[1:], ids[:1]])
    x, chosen, weights = _stack(params, arch, ids, valid, dt)
    lp_tok, lp_max = _head_logprobs(
        x, params["final_ln"]["weight"], params["head"]["weight"],
        labels, eps=float(arch["rms_norm_eps"]), dtype=dt,
    )
    return lp_tok, lp_max, chosen, weights


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
        lp_tok, lp_max, _, _ = _forward(
            params, arch, jnp.asarray(ids), valid, dt)
    lp_tok, lp_max = jax.device_get((lp_tok, lp_max))
    return np.asarray(lp_tok[: n - 1]), np.asarray(lp_max[: n - 1])


def sequence_logprobs(params, arch: dict, ids, dtype: str = "float32"):
    """The same forward as one traceable function: float32
    ``log p(ids[t+1] | ids[..t])`` for t < len(ids)-1, differentiable in
    ``params``. For small sizes (every layer's residuals are kept)."""
    dt = jnp.dtype(dtype)
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        lp_tok, _, _, _ = _forward(
            params, arch, ids, jnp.ones(ids.shape, bool), dt)
    return lp_tok[:-1]


def routing(params, arch: dict, ids, dtype: str = "float32"):
    """``(chosen, weights)``: the experts each token chose in each EXPERT
    layer, int32 ``[Lx, T, K]`` in the order of their biased scores
    (largest first), and their combine weights, float32 ``[Lx, T, K]``."""
    dt = jnp.dtype(dtype)
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        _, _, chosen, weights = _forward(
            params, arch, ids, jnp.ones(ids.shape, bool), dt)
    return chosen, weights


def mtp_logprobs(params, arch: dict, ids, dtype: str = "float32"):
    """The multi-token-prediction modules: float32 ``[N, T-1-k..]`` is
    ragged, so row ``k`` is returned padded to ``T``: entry ``i`` is
    ``log p(ids[i+k+2] | ids[..i+k+1])`` by module ``k`` for ``i < T-k-2``
    (later entries are not predictions of anything). Module ``k``:
    ``h' = [RMSNorm_e(Emb(ids[i+k+1])) ; RMSNorm_h(h_i)] @ eh_proj``, one
    block as the expert layers, the model's final norm and head."""
    dt = jnp.dtype(dtype)
    ids = jnp.asarray(ids, jnp.int32)
    T = ids.shape[0]
    valid = jnp.ones((T,), bool)
    kw = _layer_kw(arch, dt)
    mtp = params["mtp"]
    out = []
    with jax.default_matmul_precision("highest"):
        h, _, _ = _stack(params, arch, ids, valid, dt)
        for k in range(arch["num_nextn_predict_layers"]):
            nxt = jnp.roll(ids, -(k + 1))
            e = params["embed"]["weight"][nxt].astype(dt)
            both = jnp.concatenate([
                _rms(e, mtp["e_norm"]["weight"][k].astype(dt), kw["eps"]),
                _rms(h, mtp["h_norm"]["weight"][k].astype(dt), kw["eps"]),
            ], axis=-1)
            h = both @ mtp["eh_proj"][k].astype(dt)
            h, _, _ = _layer(h, mtp["block"], jnp.int32(k), valid, **kw)
            lp_tok, _ = _head_logprobs(
                h, params["final_ln"]["weight"], params["head"]["weight"],
                jnp.roll(ids, -(k + 2)), eps=kw["eps"], dtype=dt)
            out.append(lp_tok)
    return jnp.stack(out)
