"""Plain reference for the nemotron_h family as Nemotron-3-Super lays it
out: blocks of ONE branch (a Mamba-2 mixer, an attention without positions,
or a LatentMoE expert layer), one norm a block.

The forward pass in straightforward ``jax.numpy``: a Python loop over the
blocks in ``hybrid_override_pattern`` order; an attention block is
grouped-query attention with NO positional encoding, dense and causal over
the whole sequence; a Mamba-2 block is the published recurrence run as a
plain ``lax.scan`` over the TOKENS, one state update a token, float32
state; an expert block scores all the experts, keeps ``num_experts_per_tok``
and runs the HELD ones a row chose one expert at a time: no chunks, no
cache, no batching, no kernel. Independent of the program's model code: it
shares only the NAMES of the weight tree (``embed.weight`` [V,E];
``layers`` the attention blocks in the order they run: ``ln1.weight``,
``attn.{wq,wk,wv,wo}``; ``ssm_layers`` the Mamba-2 blocks: ``ln1.weight``,
``ssm.{w_z, w_xbc, w_dt (the input projection [E, z+xBC+dt] in its three
parts), conv_w [taps, channels], conv_b, dt_bias, A_log, D, gate_norm,
w_out}``; ``moe_layers`` the expert blocks: ``ln1.weight``, ``mlp.{router
[E,X], b_router [X], latent_down [E,latent], latent_up [latent,E], w_up
[Xh,latent,F], w_down [Xh,F,latent], shared_up [E,Fs], shared_down
[Fs,E]}``; ``final_ln.weight``; ``head.weight`` [E,V], untied; matrices
stored input-major, ``y = x @ w``).

    h = E[token]                            no multipliers
    h = h + f_i(rms_i(h))                   ONE f and one norm a block
    logits = rms_f(h) W_head

    M:  [z ; x ; B ; C ; dt] = W_in u;  [x;B;C] = silu(conv([x;B;C]) + b)
        dt = softplus(dt + dt_bias);  A = -exp(A_log) a head
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
        heads 16g .. 16g+15 read B_g, C_g (``n_groups`` 8)
        out = W_out (rms_g(y * silu(z)) * w)    the RMS over each GROUP's
                                                d_inner / n_groups channels
    *:  q, k, v, o without bias, grouped queries, causal, scale
        head_dim ** -0.5, no positions
    E:  s = sigmoid(W_r u) in float32;  chosen = top-k of s + b
        w_j = scale * s_j / (sum over the k chosen of s + 1e-20)
        l = W_dn u;  r = sum_{j chosen AND held} w_j W2_j relu(W1_j l)^2
        out = W_up r + S2 relu(S1 u)^2          shared expert at hidden width

THE SHARE (``n_routed_experts`` the experts HELD, ``expert_parallel_size``
ranks, ``expert_parallel_rank``): the router scores ``held x ranks`` experts
and keeps ``num_experts_per_tok`` of them, the weights are normalised over
ALL the chosen, and ``r`` sums over the chosen experts held here, ``rank x
held .. + held - 1``: the rank's partial result before any exchange, which
is what goes on to the next block. Nothing stands in for the other ranks.

What the published config does not say was written from the family's
public modelling code and report (no copy of either, and no network, where
this was written): one constant each, below; a correction is one line.

Departures from the published description, all stated: the share above
(the configuration file's ``deployment``); the multi-token-prediction
module is absent (``num_nextn_predict_layers`` 0). And, to fit beside a
model that fills the chip, none of which changes the mathematics: one
block at a time is cast from the stored dtype to the compute dtype, the
experts one at a time; attention runs in blocks of queries; the head is
applied in vocabulary blocks with a running log-sum-exp. In float32 it
runs under ``jax.default_matmul_precision("highest")``.

Stand-ins for a faulty program, for the benchmark's controls (keys of
``arch`` that no published config has): ``control_zero_state_at`` (a
position: the recurrent state of every Mamba-2 block is dropped before
that token is read), ``control_state_dtype`` (the state is rounded to that
dtype after every token), ``control_norm_over_held`` (the combine weights
normalised over the chosen experts HELD here instead of all the chosen:
the plausible wrong share). ``forced_routing`` ``[expert blocks, T, k]``
int: the experts a row chose in each expert block, -1 (in slot 0) where
the router runs free; the weights are still this router's scores of them.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

# (m) the gated norm's epsilon is the model's ``layer_norm_epsilon``
GATED_NORM_EPS_IS_MODEL_EPS = True
# (m) the gate is applied BEFORE the norm: rms(y * silu(z)) * w
GATE_BEFORE_NORM = True
# (m) that norm spans ONE GROUP's d_inner / n_groups channels
GATED_NORM_PER_GROUP = True
# (m) dt is clipped to (0, inf) after the softplus, which changes nothing
DT_LIMIT = (0.0, float("inf"))
# (m) ``conv_w[k]`` weighs the input ``K - 1 - k`` tokens back
CONV_LAST_TAP_IS_CURRENT = True
# (m) the recurrent state is float32 whatever the compute dtype
STATE_DTYPE = jnp.float32
# (m) no positional encoding in the attention blocks (``rope_theta`` and
# ``partial_rotary_factor`` shape nothing)
NO_POSITIONS = True
# (m) the correction bias moves the CHOICE of experts, never their weight
BIAS_IN_CHOICE_ONLY = True
# (m) the weights' normalisation adds 1e-20 to the sum
NORM_EPS = 1e-20
# (m) no norm, bias or activation on the latent projections
PLAIN_LATENT_PROJECTIONS = True
# (m) the router reads the block's hidden-width input in float32
ROUTER_DTYPE = jnp.float32

_VOCAB_BLOCK = 16384
_QUERY_BLOCK = 512
_BUILD_THREADS = 8
_BLOCKS = {"M": "ssm_layers", "*": "layers", "E": "moe_layers"}


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


@functools.partial(jax.jit, static_argnames=("n_q", "n_kv", "eps", "dtype"))
def _attn_block(x, lp, valid, *, n_q, n_kv, eps, dtype):
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
    return x + jnp.concatenate(out).reshape(T, -1) @ a["wo"]


@functools.partial(
    jax.jit,
    static_argnames=("n_heads", "d_head", "d_state", "n_groups", "eps",
                     "dtype", "round_to"))
def _ssm_block(x, lp, zero_at, keep_at, *, n_heads, d_head, d_state, n_groups,
               eps, dtype, round_to):
    """The block's output, and its recurrent state after token ``keep_at``
    (zeros where no token is)."""
    lp = jax.tree.map(lambda a: a.astype(dtype), lp)
    m = lp["ssm"]
    T = x.shape[0]
    d_inner, gn = n_heads * d_head, n_groups * d_state
    h = _rms(x, lp["ln1"]["weight"], eps)
    zxd = h @ jnp.concatenate([m["w_z"], m["w_xbc"], m["w_dt"]], axis=-1)
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
    per = n_heads // n_groups       # heads 16g .. 16g+15 read group g
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
    gate = jax.nn.silu(z.astype(jnp.float32))
    # the norm over each group's channels, or over all of d_inner
    span = (T, n_groups, d_inner // n_groups) if GATED_NORM_PER_GROUP else (
        T, 1, d_inner)
    w = m["gate_norm"].reshape(span[1:])
    y = ys.reshape(span)
    gate = gate.reshape(span)
    if GATE_BEFORE_NORM:
        y = _rms(y * gate, w, eps)
    else:
        y = _rms(y, w, eps) * gate
    return x + y.reshape(T, d_inner).astype(dtype) @ m["w_out"], kept


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
def _moe_block(x, stack, j, forced, *, top_k, norm_topk, scale, first, eps,
               dtype, norm_over_held):
    """Expert block ``j`` of ``stack`` (the WHOLE stack in the stored dtype:
    each expert's two matrices are cut out and cast inside the loop).
    Returns ``(x, the router's own choice [T, K], routed part [T, E], shared
    part [T, E])``."""
    m = stack["mlp"]
    at = lambda name: m[name][j]                    # small leaves only
    h = _rms(x, stack["ln1"]["weight"][j].astype(dtype), eps)
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
    lat = h @ at("latent_down").astype(dtype) if "latent_down" in m else h

    def one_expert(acc, e):
        up, down = (m[k][j, e].astype(dtype) for k in ("w_up", "w_down"))
        return acc + (_relu2(lat @ up) @ down) * combine[:, e][:, None], None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(lat), jnp.arange(n_held))
    if "latent_up" in m:
        routed = routed @ at("latent_up").astype(dtype)
    shared = jnp.zeros_like(x)
    if "shared_up" in m:
        shared = _relu2(h @ at("shared_up").astype(dtype)) @ at(
            "shared_down").astype(dtype)
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


def _statics(arch: dict, dt):
    for key, want in (("n_group", 1), ("topk_group", 1),
                      ("num_nextn_predict_layers", 0)):
        if (arch.get(key, want) or 0) != want:
            raise ValueError(f"nemotron_h: {key}={arch[key]!r}")
    if arch.get("mlp_hidden_act", "relu2") != "relu2":
        raise ValueError(f"nemotron_h: mlp_hidden_act={arch['mlp_hidden_act']!r}")
    eps = float(arch["layer_norm_epsilon"])
    round_to = arch.get("control_state_dtype")
    return dict(
        attn=dict(n_q=arch["num_attention_heads"],
                  n_kv=arch["num_key_value_heads"], eps=eps, dtype=dt),
        ssm=dict(n_heads=arch["mamba_num_heads"], d_head=arch["mamba_head_dim"],
                 d_state=arch["ssm_state_size"], n_groups=arch["n_groups"],
                 eps=eps, dtype=dt,
                 round_to=None if round_to is None else jnp.dtype(round_to)),
        moe=dict(top_k=arch["num_experts_per_tok"],
                 norm_topk=bool(arch.get("norm_topk_prob", True)),
                 scale=float(arch.get("routed_scaling_factor", 1.0)),
                 first=int(arch.get("expert_parallel_rank", 0))
                 * arch["n_routed_experts"],
                 eps=eps, dtype=dt,
                 norm_over_held=bool(arch.get("control_norm_over_held"))),
    )


def _stack(params, arch: dict, ids, valid, dt, keep_at=-1, n_states=None):
    """The blocks in order: ``(hidden [T, E], every Mamba-2 block's recurrent
    state after token ``keep_at``, every expert block's router's own choice
    [blocks][T, k])``; with ``n_states`` the stack ends behind that many
    Mamba-2 blocks (hidden None)."""
    kw = _statics(arch, dt)
    zero_at = jnp.int32(arch.get("control_zero_state_at", -1))
    keep_at, states, chosen = jnp.int32(keep_at), [], []
    forced = arch.get("forced_routing")
    T, k = ids.shape[0], arch["num_experts_per_tok"]
    x = params["embed"]["weight"][ids].astype(dt)
    at = dict.fromkeys(_BLOCKS.values(), 0)
    pattern = arch["hybrid_override_pattern"][: arch["num_hidden_layers"]]
    for kind in pattern:
        if kind not in _BLOCKS:
            raise ValueError(f"nemotron_h: block {kind!r} in the pattern")
        tree = _BLOCKS[kind]
        j = at[tree]
        at[tree] += 1
        if kind == "E":
            given = jnp.full((T, k), -1, jnp.int32)
            if forced is not None:
                f = np.asarray(forced[j], np.int32)
                given = given.at[: f.shape[0]].set(f)
            x, idx, _, _ = _moe_block(
                x, params[tree], jnp.int32(j), given, **kw["moe"])
            chosen.append(idx)
            continue
        lp = jax.tree.map(lambda a: a[j], params[tree])
        if kind == "*":
            x = _attn_block(x, lp, valid, **kw["attn"])
        else:
            x, kept = _ssm_block(x, lp, zero_at, keep_at, **kw["ssm"])
            states.append(kept)
            if len(states) == n_states:
                return None, states, chosen
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
        eps=float(arch["layer_norm_epsilon"]), dtype=dt), states, chosen


def _run(params, arch, tokens, dtype, pad_to, **kw):
    dt = jnp.dtype(dtype)
    n = len(tokens)
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
    """The experts each block's router chose for each token, int32 numpy
    ``[blocks, len(tokens), k]``: its OWN choice also where
    ``forced_routing`` put another in its place (the blocks before it then
    ran on the forced ones)."""
    _, _, chosen = _run(params, arch, tokens, dtype, pad_to)
    return np.stack(jax.device_get(chosen))[:, : len(tokens)]


def recurrent_state(params, arch: dict, tokens, dtype: str, pad_to: int,
                    n_layers=None):
    """The recurrent state of every Mamba-2 block (or of the first
    ``n_layers`` of them: the forward then ends there) after ALL of
    ``tokens``, in the order the blocks run: float32 numpy ``[blocks,
    heads, head dim, state]``."""
    _, states, _ = _run(
        params, arch, tokens, dtype, pad_to, keep_at=len(tokens) - 1,
        n_states=n_layers)
    return np.stack(jax.device_get(states))


def build_ahead(params, arch: dict, dtypes, pad_to: int, state_dtype=None):
    """Build every program that the forwards in ``dtypes`` at ``pad_to``
    are made of (each kind of block and the head; in float32 also the
    expert block of ``control_norm_over_held`` and, with ``state_dtype``,
    the Mamba-2 block that rounds its state to it), all at once on
    ``_BUILD_THREADS`` threads, each by one run on a sequence of token 0:
    a forward meets its programs one after another, fourteen compiles in a
    row (75 s of a cold check on the chip's host) where side by side they
    are one program's time. Nothing here computes a result."""
    import concurrent.futures

    ids = jnp.zeros((pad_to,), jnp.int32)
    valid = jnp.ones((pad_to,), bool)
    free = jnp.full((pad_to, arch["num_experts_per_tok"]), -1, jnp.int32)
    none, j0 = jnp.int32(-1), jnp.int32(0)

    def first(tree):
        return jax.tree.map(lambda a: a[0], params[tree])

    def calls(dt, **defect):
        kw = _statics(dict(arch, **defect), dt)
        x = params["embed"]["weight"][ids].astype(dt)
        ssm = lambda: _ssm_block(x, first("ssm_layers"), none, none, **kw["ssm"])
        moe = lambda: _moe_block(x, params["moe_layers"], j0, free, **kw["moe"])
        if "control_state_dtype" in defect:
            return [ssm]
        if "control_norm_over_held" in defect:
            return [moe]
        return [
            ssm, moe,
            lambda: _attn_block(x, first("layers"), valid, **kw["attn"]),
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
    todo += [(f32, c) for c in calls(f32, control_norm_over_held=True)]
    if state_dtype is not None:
        todo += [(f32, c) for c in calls(f32, control_state_dtype=state_dtype)]
    with concurrent.futures.ThreadPoolExecutor(_BUILD_THREADS) as pool:
        for done in [pool.submit(build, *t) for t in todo]:
            done.result()


def expert_block_parts(params, arch: dict, x, block: int = 0):
    """Expert block ``block`` alone on hidden states ``x [T, E]``, float32:
    ``(routed part, shared part)`` of its branch (the block's output is
    ``x`` + both). For the test that ties a share to the model: the routed
    parts of all the ranks and the shared part ONCE add up to the uncut
    block's branch."""
    kw = _statics(arch, jnp.dtype("float32"))
    T, k = x.shape[0], arch["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):
        _, _, routed, shared = _moe_block(
            jnp.asarray(x, jnp.float32), params["moe_layers"],
            jnp.int32(block), jnp.full((T, k), -1, jnp.int32), **kw["moe"])
    return np.asarray(routed), np.asarray(shared)


def sequence_logits(params, arch: dict, ids):
    """Float32 logits ``[T, V]`` of one sequence (small sizes: the tests
    compare LOGITS)."""
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x, _, _ = _stack(
            params, arch, ids, jnp.ones(ids.shape, bool), jnp.dtype("float32"))
        h = _rms(x, params["final_ln"]["weight"].astype(jnp.float32),
                 float(arch["layer_norm_epsilon"]))
        return h @ params["head"]["weight"].astype(jnp.float32)
