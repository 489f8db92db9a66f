"""Gated delta-rule linear attention in plain ``jax.numpy`` (Kimi Delta
Attention as its authors' modelling code lays it out; family
``solar_open2``, ``cfg.kda``).

One layer over its normed input ``a``, ``H`` heads of ``D`` (a key and a
value head are one width), a state ``S`` of ``D x D`` a head, float32::

    q, k, v = silu(conv(Wq a)), silu(conv(Wk a)), silu(conv(Wv a))
                                        three causal depthwise convolutions,
                                        kept as ONE over [q ; k ; v]
    q = l2norm_head(q) * D ** -0.5      k = l2norm_head(k)
    g = -exp(A_log[h]) * softplus(Wfb (Wfa a) + dt_bias)      [H, D]: a
                                        log-decay a CHANNEL of the key
    beta = sigmoid(Wb a)                [H]; x 2 under ``neg_eigval``
    S <- diag(exp(g)) S                 the rows decay
    S <- S + beta k (v - S^T k)^T       the delta rule, AFTER the decay
    o = S^T q
    out = Wo (rms_head(o) w_o * sigmoid(Wgb (Wga a)))

i.e. ``S_t = (I - beta_t k_t k_t^T) diag(alpha_t) S_{t-1} + beta_t k_t
v_t^T`` with ``alpha = exp(g)``. Under ``neg_eigval`` the eigenvalues of
``I - beta k k^T`` lie in [-1, 1] (``|k| = 1``).

Two forms of one function, as ``ops/ssm.py`` has them:

- :func:`mixer_chunk`: many tokens a row, the CHUNKED form
  (:func:`scan_chunked`). It takes and returns the recurrent and the
  convolution state (admission hands it a prompt a piece at a time), and a
  token at position 0 of its document RESETS both before it is read.
  Autodiff through it is the trainer's backward pass at test size. Any
  chunk length gives the same function.
- :func:`mixer_step`: one token a row, the decode step's update
  (:func:`step_update`, the plain reference of the ``kda_decode`` kernel).

The state is ``[H, Dk, Dv]`` a row a layer: the value channels on the minor
axis (the chip's lanes), the key channels on the sublanes. Both sums of a
token's update (``S^T k``, ``S^T q``) then run DOWN the sublanes, and what
varies a key channel (the decay, ``k``, ``q``) is a column spread over the
lanes (``ops/pallas/kda_decode.py``). The convolution's last inputs are
kept flat, ``[(d_conv - 1) x 3 H D]``, in the serving dtype
(``ops/conv.py``).

The state and everything that accumulates into it are float32 whatever the
serving dtype, and the einsums over it run at ``Precision.HIGHEST``
(``ops/ssm.py`` says why of both).
"""

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from areal_tpu.models.config import ModelConfig
from areal_tpu.ops.conv import conv_chunk, conv_step

_HI = jax.lax.Precision.HIGHEST
# x / sqrt(sum x^2 + eps) over a head's channels
L2_EPS = 1e-6
# the recurrent state and what accumulates into it, whatever the serving
# dtype (a 16-bit state is another configuration, not supported)
STATE_DTYPE = jnp.float32


def state_shapes(cfg: ModelConfig, batch: int):
    """``(s, conv)`` shapes of ``batch`` rows' state in ALL delta-rule
    layers: ``[Lk, B, H, Dk, Dv]`` and ``[Lk, B, (d_conv - 1) x 3 H D]``."""
    d = cfg.kda
    return (
        (cfg.n_kda_layers, batch, d.n_heads, d.head_dim, d.head_dim),
        (cfg.n_kda_layers, batch, (d.d_conv - 1) * d.conv_dim),
    )


def state_bytes_per_slot(cfg: ModelConfig) -> int:
    """What one slot's recurrent and convolution state take, all layers."""
    s, conv = state_shapes(cfg, 1)
    return (
        math.prod(s) * jnp.dtype(STATE_DTYPE).itemsize
        + math.prod(conv) * jnp.dtype(cfg.dtype).itemsize
    )


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _in_proj(p, h):
    with jax.named_scope("kda_in_proj"):
        return h @ p["w_qkv"]


def _inputs(cfg: ModelConfig, p, h, qkv):
    """What the recurrence reads of ``h [..., E]`` and the convolved,
    activated ``qkv [..., 3 H D]``: ``q, k, v [..., H, D]`` (q and k
    normed, q scaled), the log-decay ``g [..., H, D]`` (<= 0) and ``beta
    [..., H]``; float32."""
    d = cfg.kda
    H, D = d.n_heads, d.head_dim
    lead = h.shape[:-1]
    q, k, v = (
        x.astype(jnp.float32).reshape(*lead, H, D)
        for x in jnp.split(qkv, 3, axis=-1))
    q, k = _l2norm(q) * D ** -0.5, _l2norm(k)
    with jax.named_scope("kda_gates"):
        f = ((h @ p["w_fa"]) @ p["w_fb"]).astype(jnp.float32)
        g = -jnp.exp(p["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
            f + p["dt_bias"].astype(jnp.float32)).reshape(*lead, H, D)
        beta = jax.nn.sigmoid((h @ p["w_beta"]).astype(jnp.float32))
        if d.neg_eigval:
            beta = 2.0 * beta
    return q, k, v, g, beta


def _out(cfg: ModelConfig, p, o, h):
    """``Wo (rms_head(o) w_o * sigmoid(Wgb (Wga h)))``: ``o [..., H, D]``
    float32; the norm over each head's channels, the gate a channel."""
    with jax.named_scope("kda_out_gate"):
        var = jnp.mean(o * o, axis=-1, keepdims=True)
        o = o * jax.lax.rsqrt(var + cfg.layer_norm_epsilon) * p[
            "o_norm"].astype(jnp.float32)
        gate = jax.nn.sigmoid(((h @ p["w_ga"]) @ p["w_gb"]).astype(jnp.float32))
        o = o.reshape(gate.shape) * gate
    return o.astype(h.dtype) @ p["wo"]


def step_update(s, q, k, v, a, beta):
    """One token of the recurrence, every row: ``s [B, H, Dk, Dv]``, ``q, k,
    v [B, H, D]``, ``a [B, H, Dk]`` the decay FACTORS ``exp(g)`` (1: the
    rows stay), ``beta [B, H]`` (0: nothing is written); float32. Returns
    ``(o [B, H, Dv], s)``. The plain reference of the ``kda_decode``
    kernel; elementwise, so float32 whatever the matmul precision."""
    with jax.named_scope("kda_step"):
        s = s * a[..., None]
        u = jnp.sum(s * k[..., None], axis=-2)
        d = beta[..., None] * (v - u)
        s = s + k[..., None] * d[..., None, :]
        return jnp.sum(s * q[..., None], axis=-2), s


def scan_chunked(q, k, v, g, beta, reset, init, chunk: int):
    """The recurrence over ``T`` tokens a row in chunks of ``chunk``.

    ``q, k, v, g [B, T, H, D]`` (``g`` the log-decay, <= 0), ``beta [B, T,
    H]`` (``g`` 0 and ``beta`` 0 where a token must leave the state alone:
    padding), ``reset [B, T]`` (the state is dropped before this token),
    ``init [B, H, Dk, Dv]``; float32. Returns ``o [B, T, H, Dv]`` and the
    state after the last token.

    Inside a chunk, with ``G_t`` the running sum of ``g`` and ``w_t =
    beta_t (v_t - (diag(alpha_t) S_{t-1})^T k_t)`` what token ``t`` writes
    (``S_t = diag(alpha_t) S_{t-1} + k_t w_t^T``)::

        A_ts = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])     s <  t
        B_ts = sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])     s <= t
        (I + diag(beta) A) W = beta (V - (K exp(G)) S_0)    unit lower
                                                            triangular
        O = (Q exp(G)) S_0 + B W
        S_C = diag(exp(G_C)) S_0 + (K exp(G_C - G))^T W

    The decay a CHANNEL stands between two tokens as the DIFFERENCE of
    their running sums, token pair by token pair: every exponent is <= 0
    (``g <= 0``), and nothing is divided by a running decay (``exp(-G)``
    leaves float32 within 64 tokens at the fast end of ``A_log``). The
    solve is linear in its right side, so its two parts (``beta V``,
    ``beta K exp(G)``) are solved for every chunk at once and only ``W =
    U - W_k S_0`` and the three products with the state run in the short
    ``lax.scan`` over the chunks. A reset inside a chunk cuts every pair
    across it and what the tokens behind it see of ``S_0``."""
    Bt, T, H, D = q.shape
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            for x in (q, k, v, g, beta))
        reset = jnp.pad(reset, [(0, 0), (0, pad)])
    nc = (T + pad) // Q

    def chunks(x):      # [B, T, H, ...] -> [B, nc, H, Q, ...]
        return jnp.moveaxis(x.reshape(Bt, nc, Q, *x.shape[2:]), 3, 2)

    with jax.named_scope("kda_chunk"):
        q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
        cum = jnp.cumsum(g, axis=3)                       # [B, nc, H, Q, D]
        seg = jnp.cumsum(
            reset.reshape(Bt, nc, Q).astype(jnp.int32), axis=2)
        t_idx = jnp.arange(Q)
        # token s reaches token t: not later, and no reset in (s, t]
        reach = (t_idx[:, None] >= t_idx[None, :]) & (
            seg[..., :, None] == seg[..., None, :])       # [B, nc, t, s]
        m = reach[:, :, None, :, :, None]
        diff = cum[:, :, :, :, None] - cum[:, :, :, None, :]
        decay = jnp.where(m, jnp.exp(jnp.where(m, diff, 0.0)), 0.0)
        kk = k[:, :, :, None] * decay                     # [B, nc, H, t, s, D]
        a_ts = jnp.sum(k[:, :, :, :, None] * kk, axis=-1)
        b_ts = jnp.sum(q[:, :, :, :, None] * kk, axis=-1)
        strict = (t_idx[:, None] > t_idx[None, :])
        lower = jnp.eye(Q, dtype=jnp.float32) + jnp.where(
            strict, beta[..., None] * a_ts, 0.0)
        # what a token sees of the state it was handed, and what the chunk
        # keeps and adds at its end
        first = (seg == 0)[:, :, None, :, None]           # [B, nc, 1, Q, 1]
        from_init = jnp.where(first, jnp.exp(cum), 0.0)
        last = seg[:, :, -1:]
        to_end = jnp.where(
            (seg == last)[:, :, None, :, None],
            jnp.exp(cum[:, :, :, -1:] - cum), 0.0)
        keep = jnp.where(
            (last == 0)[:, :, :, None], jnp.exp(cum[:, :, :, -1]), 0.0)
        rhs = beta[..., None] * jnp.concatenate(
            [v, k * from_init], axis=-1)                  # [B, nc, H, Q, 2 D]
        sol = jax.scipy.linalg.solve_triangular(
            lower, rhs, lower=True, unit_diagonal=True)
        u_v, w_k = sol[..., :D], sol[..., D:]

        def carry(s, inp):
            u_c, wk_c, qi_c, b_c, ke_c, keep_c = inp
            w = u_c - jnp.einsum("bhqk,bhkv->bhqv", wk_c, s, precision=_HI)
            o = jnp.einsum("bhqk,bhkv->bhqv", qi_c, s, precision=_HI) + (
                jnp.einsum("bhts,bhsv->bhtv", b_c, w, precision=_HI))
            s = keep_c[..., None] * s + jnp.einsum(
                "bhqk,bhqv->bhkv", ke_c, w, precision=_HI)
            return s, o

        final, o = jax.lax.scan(
            carry, init,
            tuple(jnp.moveaxis(x, 1, 0) for x in (
                u_v, w_k, q * from_init, b_ts, k * to_end, keep)))
    o = jnp.moveaxis(o, 0, 1)                             # [B, nc, H, Q, D]
    o = jnp.moveaxis(o, 2, 3).reshape(Bt, nc * Q, H, D)
    return (o[:, :T] if pad else o), final


def mixer_chunk(
    cfg: ModelConfig, p, h, positions, state: Optional[Tuple] = None,
    n_valid=None,
):
    """The delta-rule mixer over ``h [B, T, E]`` (normed layer input);
    ``positions``, ``state`` (``(s [B, H, Dk, Dv], conv [B, (d_conv - 1) x
    3 H D])``; None: empty), ``n_valid`` as ``ops/ssm.py:mixer_chunk``
    takes them. Returns ``(out [B, T, E], (s, conv))``."""
    d = cfg.kda
    Bt, T = h.shape[:2]
    if state is None:
        s_shape, conv_shape = state_shapes(cfg, Bt)
        state = (
            jnp.zeros(s_shape[1:], STATE_DTYPE),
            jnp.zeros(conv_shape[1:], h.dtype),
        )
    if n_valid is None:
        n_valid = jnp.full((Bt,), T, jnp.int32)
    s0, conv0 = state
    qkv, conv1 = conv_chunk(
        p, _in_proj(p, h), positions, conv0, n_valid, scope="kda_conv")
    q, k, v, g, beta = _inputs(cfg, p, h, qkv)
    valid = jnp.arange(T)[None, :] < n_valid[:, None]
    g = jnp.where(valid[..., None, None], g, 0.0)
    beta = jnp.where(valid[..., None], beta, 0.0)
    o, s1 = scan_chunked(
        q, k, v, g, beta, (positions == 0) & valid, s0.astype(jnp.float32),
        d.chunk_size)
    return _out(cfg, p, o, h), (s1.astype(s0.dtype), conv1)


def mixer_step(cfg: ModelConfig, p, h, state, active=None, update=None):
    """The mixer over ONE token a row: ``h [B, E]``, ``state`` as
    :func:`mixer_chunk` takes it. Rows where ``active [B]`` is false leave
    their state as it was (their output is garbage nobody reads).
    ``update``: what stands in for :func:`step_update` (the engine's
    kernel, which works on the state of all layers in place): it is handed
    the state of ALL layers in place of this layer's; what it returns as
    the state is returned as is. Returns ``(out [B, E], (s, conv))``."""
    s0, conv0 = state
    if active is None:
        active = jnp.ones((h.shape[0],), bool)
    qkv, conv1 = conv_step(p, _in_proj(p, h), conv0, active, scope="kda_conv")
    q, k, v, g, beta = _inputs(cfg, p, h, qkv)
    a = jnp.where(active[:, None, None], jnp.exp(g), 1.0)
    beta = jnp.where(active[:, None], beta, 0.0)
    o, s1 = (step_update if update is None else update)(s0, q, k, v, a, beta)
    return _out(cfg, p, o, h), (s1, conv1)
