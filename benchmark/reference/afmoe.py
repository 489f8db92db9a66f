"""Plain reference for the afmoe family (Arcee's Trinity-Mini, 26B-A3B).

The published forward pass in straightforward ``jax.numpy``. The model's
layers are walked ONE BY ONE along ``layer_types`` (no stacks' logic, no
period, no scan over layers), layer ``l`` on the residual stream ``x``:

    x0 = Embed[token] * sqrt(hidden_size)            where mup_enabled
    a = RMSNorm_in(x)
    q, k, v, g = a @ Wq, a @ Wk, a @ Wv, a @ Wg      no bias
    q, k = RMSNorm over each HEAD's head_dim (one gain for q, one for k)
    layer_types[l] == "sliding_attention": rotary on q, k (half-split pairs
        (i, i + D/2), theta rope_theta), and a query sees itself and the
        sliding_window - 1 positions before it
    layer_types[l] == "full_attention": NO positional encoding, full causal
    ctx_i = softmax_j(q_i . k_j / sqrt(D)) v_j
    x = x + RMSNorm_post_attn((ctx * sigmoid(g)) @ Wo)      the GATE
    m = RMSNorm_pre_mlp(x)
    l <  num_dense_layers: f = (silu(m @ Wgate) * (m @ Wup)) @ Wdown
    l >= num_dense_layers: s = sigmoid(m @ Wr) in float32;
        S = top-k of (s + expert_bias);   the bias moves the CHOICE only
        w_e = route_scale * s_e / (sum_{e in S} s_e + 1e-20)   (route_norm)
        f = Shared(m) + sum_{e in S} w_e Expert_e(m), each a SwiGLU
    x = x + RMSNorm_post_mlp(f)

then the final RMSNorm and the untied head. What no key of the public
``config.json`` says and the family's published modelling code does (each
is also listed under ``assumed`` in the configuration file):

(a) the gate multiplies the heads' context before the
    output projection, its sigmoid taken of ``Wg a`` with ``a`` the SAME
    normed input the q/k/v projections read;
(b) both branches' outputs are normed before they join the
    residual (``post_attention_layernorm``, ``post_mlp_layernorm``), beside
    the two input norms;
(c) full layers carry no positions;
(d) the weights are the
    sigmoid scores without ``expert_bias``, renormalised over the chosen
    with ``ROUTE_EPS`` = ``1e-20`` in the denominator;
(e) ``mup_enabled`` multiplies the embedding by
    ``sqrt(hidden_size)`` and nothing else.

Departures from the published description: ``load_balance_coeff`` builds no
term here (the published training moves ``expert_bias`` by it; this is a
forward pass). Departures from a textbook forward, all to fit beside a
model that fills the chip and none changing the mathematics: attention in
blocks of queries; a layer's attention weights cast from the stored dtype
one layer at a time and each expert's three matrices inside a loop over
the experts, every expert applied to every token and multiplied by its
combine weight (zero where the router did not choose it); the embedding
rows gathered before the cast; the head in vocabulary blocks with a running
log-sum-exp. In float32 it runs under
``jax.default_matmul_precision("highest")``.

``window`` (every entry point): ``"config"`` is the configuration's own
``layer_types``; ``None`` makes EVERY layer attend over everything (the
rotary stays where it was), which is what a program that forgot the window
or read pages it had given back would compute: the benchmark's second
control. ``arch["forced_routing"]`` ``[expert layers, T, k]`` int: the
experts a token takes in each expert layer, -1 (in slot 0) where the
router runs free; the weights are still this router's scores of them.

Independent of the program's model code: it shares only the NAMES of the
weight tree (``embed.weight``; two stacks ``dense_layers`` and ``layers``
of ``{ln1, attn_out_ln, ln2, mlp_out_ln}.weight``, ``attn.{wq, wk, wv, wo,
wg, q_norm, k_norm}``, ``mlp.{w_gate, w_up, w_down}`` and in ``layers``
also ``mlp.{router, b_router, shared_gate, shared_up, shared_down}``;
``final_ln.weight``; ``head.weight``; matrices input-major, ``y = x @ w``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

# the same RMSNorm, rotate-half rotary and blockwise head as the qwen2
# reference and the same attention in blocks of queries as smallthinker's
# (causal, the mask from the positions, a query sees itself and the
# ``window - 1`` positions before it): one plain implementation of each,
# not the program's. A full layer's ``window`` is the sequence's length.
from benchmark.reference.qwen2 import _head_logprobs, _rms, _rope
from benchmark.reference.smallthinker import _attention

ROUTE_EPS = 1e-20       # (d): in the denominator of the renormalised weights


def _route(m, router, bias, given, *, top_k, norm, scale):
    """m [T, E] -> (taken [T, k], weights [T, k], the router's own choice
    [T, k]). ``given [T, k]``: experts put in the choice's place where its
    slot 0 is not -1."""
    s = jax.nn.sigmoid(m.astype(jnp.float32) @ router.astype(jnp.float32))
    _, own = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    idx = jnp.where(given[:, :1] >= 0, given, own)
    w = jnp.take_along_axis(s, idx, axis=-1)        # WITHOUT the bias
    if norm:
        w = w / (w.sum(axis=-1, keepdims=True) + ROUTE_EPS)
    return idx, w * scale, own


def _experts(m, mlp, j, idx, w, dtype):
    """sum over the taken experts of ``w_e SwiGLU_e(m)``, and the shared
    expert. ``mlp`` is the WHOLE expert stack's tree in the stored dtype
    and ``j`` the layer in it: each expert's matrices are cut out and cast
    inside the loop."""
    rows = jnp.arange(m.shape[0])[:, None]
    X = mlp["router"].shape[-1]
    combine = jnp.zeros((m.shape[0], X), jnp.float32).at[rows, idx].set(w)
    combine = combine.astype(dtype)

    def one_expert(acc, x):
        gate, up, down = (
            mlp[name][j, x].astype(dtype) for name in ("w_gate", "w_up", "w_down"))
        y = (jax.nn.silu(m @ gate) * (m @ up)) @ down
        return acc + y * combine[:, x][:, None], None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(m), jnp.arange(X))
    if "shared_up" in mlp:
        sg, su, sd = (mlp[name][j].astype(dtype) for name in
                      ("shared_gate", "shared_up", "shared_down"))
        out = out + (jax.nn.silu(m @ sg) * (m @ su)) @ sd
    return out


@functools.partial(jax.jit, static_argnames=(
    "n_q", "n_kv", "eps", "theta", "top_k", "norm", "scale", "dtype"))
def _layer(x, stack, j, valid, given, window, rotary, *, n_q, n_kv, eps,
           theta, top_k, norm, scale, dtype):
    """Layer ``j`` of ``stack`` (dense or expert by its tree, in the stored
    dtype) on x [T, E]; ``window`` (an integer) and ``rotary`` (a boolean)
    are the layer's kind, ARGUMENTS so that one program serves both kinds.
    Returns (x, taken or None, the router's own choice or None, the combine
    weights or None)."""
    T = x.shape[0]
    gain = lambda name: stack[name]["weight"][j].astype(dtype)
    a = jax.tree.map(lambda t: t[j].astype(dtype), stack["attn"])
    h = _rms(x, gain("ln1"), eps)
    q = _rms((h @ a["wq"]).reshape(T, n_q, -1), a["q_norm"], eps)
    k = _rms((h @ a["wk"]).reshape(T, n_kv, -1), a["k_norm"], eps)
    v = (h @ a["wv"]).reshape(T, n_kv, -1)
    pos = jnp.arange(T)
    q = jnp.where(rotary, _rope(q, pos, theta), q)
    k = jnp.where(rotary, _rope(k, pos, theta), k)
    ctx = _attention(q, k, v, valid, window)
    gate = jax.nn.sigmoid((h @ a["wg"]).astype(jnp.float32)).astype(dtype)
    x = x + _rms((ctx * gate) @ a["wo"], gain("attn_out_ln"), eps)
    m = _rms(x, gain("ln2"), eps)
    mlp = stack["mlp"]
    if "router" not in mlp:
        wg, wu, wd = (mlp[name][j].astype(dtype)
                      for name in ("w_gate", "w_up", "w_down"))
        f, idx, own, w = (jax.nn.silu(m @ wg) * (m @ wu)) @ wd, None, None, None
    else:
        idx, w, own = _route(
            m, mlp["router"][j], mlp["b_router"][j], given, top_k=top_k,
            norm=norm, scale=scale)
        f = _experts(m, mlp, j, idx, w, dtype)
    return x + _rms(f, gain("mlp_out_ln"), eps), idx, own, w


def _forward(params, arch, ids, valid, dt, window="config",
             with_weights=False):
    """(log p of the next token, largest log p, the experts taken [Lx, T,
    k], the router's own choice [Lx, T, k]), and with ``with_weights`` the
    combine weights [Lx, T, k]."""
    n_q = arch["num_attention_heads"]
    kw = dict(
        n_q=n_q, n_kv=arch.get("num_key_value_heads") or n_q,
        eps=float(arch["rms_norm_eps"]), theta=float(arch["rope_theta"]),
        top_k=arch["num_experts_per_tok"],
        norm=bool(arch.get("route_norm", True)),
        scale=float(arch.get("route_scale", 1.0)), dtype=dt,
    )
    T, n_dense = ids.shape[0], arch.get("num_dense_layers", 0)
    forced = arch.get("forced_routing")
    labels = jnp.concatenate([ids[1:], ids[:1]])
    x = params["embed"]["weight"][ids].astype(dt)
    if arch.get("mup_enabled", False):
        x = x * jnp.asarray(arch["hidden_size"] ** 0.5, dt)
    taken, chosen, weights = [], [], []
    for l in range(arch["num_hidden_layers"]):
        kind = arch["layer_types"][l]
        if kind not in ("sliding_attention", "full_attention"):
            raise ValueError(f"afmoe: layer_types entry {kind!r}")
        local = kind == "sliding_attention"
        stack, j = ((params["dense_layers"], l) if l < n_dense
                    else (params["layers"], l - n_dense))
        given = jnp.full((T, kw["top_k"]), -1, jnp.int32)
        if forced is not None and l >= n_dense:
            f = np.asarray(forced[l - n_dense], np.int32)
            given = given.at[: f.shape[0]].set(f)
        x, idx, own, w = _layer(
            x, stack, jnp.int32(j), valid, given,
            jnp.int32(arch["sliding_window"]
                      if local and window == "config" else T),
            jnp.bool_(local), **kw)
        if idx is not None:
            taken.append(idx)
            chosen.append(own)
            weights.append(w)
    lp_tok, lp_max = _head_logprobs(
        x, params["final_ln"]["weight"], params["head"]["weight"],
        labels, eps=kw["eps"], dtype=dt,
    )
    out = (lp_tok, lp_max, jnp.stack(taken), jnp.stack(chosen))
    return out + (jnp.stack(weights),) if with_weights else out


def _run(params, arch, tokens, dtype, pad_to, window="config"):
    dt = jnp.dtype(dtype)
    n = len(tokens)
    pad_to = max(pad_to, n)
    ids = np.zeros((pad_to,), np.int32)
    ids[:n] = tokens
    valid = jnp.asarray(np.arange(pad_to) < n)
    precision = "highest" if dt == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        return _forward(params, arch, jnp.asarray(ids), valid, dt, window)


def next_token_logprobs(params, arch: dict, tokens, dtype: str, pad_to: int,
                        window="config"):
    """``tokens``: one sequence of ids. Returns float32 numpy arrays of
    length len(tokens)-1: log p(tokens[t+1] | tokens[..t]) and the largest
    log-probability at that position."""
    n = len(tokens)
    lp_tok, lp_max, _, _ = _run(params, arch, tokens, dtype, pad_to, window)
    lp_tok, lp_max = jax.device_get((lp_tok, lp_max))
    return np.asarray(lp_tok[: n - 1]), np.asarray(lp_max[: n - 1])


def logprobs_and_routing(params, arch: dict, tokens, dtype: str,
                         pad_to: int, window="config"):
    """:func:`next_token_logprobs`' first result and :func:`routing`'s, from
    ONE forward (the benchmark's check wants both of every pass)."""
    n = len(tokens)
    lp_tok, _, _, chosen = jax.device_get(
        _run(params, arch, tokens, dtype, pad_to, window))
    return np.asarray(lp_tok[: n - 1]), np.asarray(chosen)[:, :n]


def build_ahead(params, arch: dict, dtypes, pad_to: int):
    """Build every program that the forwards in ``dtypes`` at ``pad_to``
    are made of (a dense layer, an expert layer and the head, each once a
    dtype: a layer's kind is an argument), all at once on threads, each by
    one run on a sequence of token 0: a forward meets its programs one
    after another, nine compiles in a row (~100 s of a cold check on the
    chip's host; my chip runs, PR 55) where side by side they are one
    program's time. Nothing here computes a result."""
    import concurrent.futures

    ids = jnp.zeros((pad_to,), jnp.int32)
    valid = jnp.ones((pad_to,), bool)
    free = jnp.full((pad_to, arch["num_experts_per_tok"]), -1, jnp.int32)
    n_q = arch["num_attention_heads"]

    def calls(dt):
        kw = dict(
            n_q=n_q, n_kv=arch.get("num_key_value_heads") or n_q,
            eps=float(arch["rms_norm_eps"]), theta=float(arch["rope_theta"]),
            top_k=arch["num_experts_per_tok"],
            norm=bool(arch.get("route_norm", True)),
            scale=float(arch.get("route_scale", 1.0)), dtype=dt)
        x = params["embed"]["weight"][ids].astype(dt)
        layer = lambda stack: lambda: _layer(       # noqa: E731
            x, params[stack], jnp.int32(0), valid, free, jnp.int32(pad_to),
            jnp.bool_(True), **kw)
        return [layer(stack) for stack in ("dense_layers", "layers")
                if stack in params] + [lambda: _head_logprobs(
                    x, params["final_ln"]["weight"], params["head"]["weight"],
                    ids, eps=kw["eps"], dtype=dt)]

    def build(dt, call):
        # (the precision is a thread's own setting, and part of what a
        # compiled program is kept under)
        precision = "highest" if dt == jnp.float32 else "default"
        with jax.default_matmul_precision(precision):
            jax.block_until_ready(call())

    todo = [(jnp.dtype(d), c) for d in dict.fromkeys(dtypes)
            for c in calls(jnp.dtype(d))]
    with concurrent.futures.ThreadPoolExecutor(len(todo)) as pool:
        for f in [pool.submit(build, dt, c) for dt, c in todo]:
            f.result()


def routing(params, arch: dict, tokens, dtype: str = "float32",
            pad_to: int = 0, window="config"):
    """The experts each expert layer's router chose for each token, int32
    numpy ``[expert layers, len(tokens), k]`` in the order of their biased
    scores (largest first): its OWN choice also where ``forced_routing``
    put another in its place (the layers before it then ran on the forced
    ones)."""
    _, _, _, chosen = _run(params, arch, tokens, dtype, pad_to, window)
    return np.asarray(jax.device_get(chosen))[:, : len(tokens)]


def sequence_logprobs(params, arch: dict, ids, dtype: str = "float32",
                      window="config"):
    """The same forward as one traceable function: float32
    ``log p(ids[t+1] | ids[..t])`` for t < len(ids)-1, differentiable in
    ``params``. For small sizes (every layer's residuals are kept)."""
    dt = jnp.dtype(dtype)
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        lp_tok, _, _, _ = _forward(
            params, arch, ids, jnp.ones(ids.shape, bool), dt, window)
    return lp_tok[:-1]


def combine_weights(params, arch: dict, ids, dtype: str = "float32"):
    """``(taken, weights)`` of every expert layer, ``[Lx, T, k]`` each: the
    experts in the order of their biased scores and their combine weights
    (for the CPU tests of the router)."""
    dt = jnp.dtype(dtype)
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        _, _, taken, _, weights = _forward(
            params, arch, ids, jnp.ones(ids.shape, bool), dt, with_weights=True)
    return taken, weights
