"""The state-space mixers in plain ``jax.numpy``: the published Mamba-2
recurrence as the ``granitemoehybrid`` family lays it out, and Mamba-1's
selective scan as ``phi4flash`` does (``cfg.ssm.selective``; its equations
and what differs stand above :func:`s6_scan`). First Mamba-2.

One layer, for a head with a state ``S`` of ``head_dim x d_state``::

    [z ; xBC ; dt] = W_in x
    xBC  = silu(conv(xBC))          causal depthwise, width d_conv, with bias
    [x ; B ; C] = xBC               B and C shared by the heads of a group
    dt   = softplus(dt + dt_bias)   A = -exp(A_log)
    S_t  = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t  = S_t C_t + D x_t
    out  = W_out (rms(y * silu(z)) * w)

The gated norm spans all of ``d_inner`` (``granitemoehybrid``, one group),
or each group's ``d_inner / n_groups`` channels by itself
(``cfg.ssm.norm_per_group``: ``nemotron_h``, eight groups of 1,024).

Two forms of one function:

- :func:`mixer_chunk`: many tokens a row, the CHUNKED scan. Inside a chunk
  of ``chunk`` tokens the recurrence is two masked matmuls (the decay
  between two tokens of a chunk is ``exp(cum_t - cum_s)``); between chunks
  a short ``lax.scan`` carries the state. It takes and returns the
  recurrent and the convolution state (admission hands it a prompt a piece
  at a time), and a token at position 0 of its document RESETS both before
  it is read, so packed documents do not see each other. Autodiff through
  it is the trainer's backward pass. Any chunk length gives the same
  function.
- :func:`mixer_step`: one token a row, the decode step's update; its
  convolution is ``conv_step``, the many-token form's ``conv_chunk``
  (``ops/conv.py``).

The recurrent state is kept TRANSPOSED and in lane tiles, ``[G, K, N,
128]`` a row a layer (``G`` groups of ``R`` heads of ``P`` channels, a state
of ``N`` a channel; channel ``r P + p`` of its group is lane ``l`` of tile
``k``, ``k 128 + l``): the channels lie on the minor axis, the chip's
lanes, and ``N`` on the sublanes. What varies a HEAD or a channel (``dt
x``, the decay ``exp(dt A)``, ``y``) is then a plain ``[R x P]`` row as the
model already holds it, and what is the SAME for every head of a group
(``B``, ``C``) is the one operand that has to be spread over the lanes,
once a row; the sum for ``y`` runs down the sublanes. With ``N`` on the
lanes (``[H, P, N]``, what this module kept before PR 42) it is the other
way round: ``dt x`` becomes a column pushed across the lanes for every
head, and ``y`` a lane reduction a head (``ops/pallas/ssm_decode.py`` has
the table). The tiles are an axis of their own because a minor axis of
all ``R x P`` = 4,096 channels is more than the chip's compiler gathers
rows of: admission's gather of a few slots' state first cut the WHOLE
array in two along its lanes, and a slice a row in its place made it
re-lay the whole array out for the chunked scan's matmuls (2.8 and 5.6
GB of temporaries beside a state of 6; PERF.md §6 PR 42). A row is the
same 2 MiB in one piece either way. ONE layout wherever the state lives:
:func:`state_shapes`, both forms below, the engine's slots and its
snapshots.

The recurrent state and everything that accumulates into it are float32
whatever the serving dtype (``cfg.ssm.state_dtype``): a rollout's state is
updated in place once a token for thousands of tokens, and the trainer
recomputes the same log-probabilities with this module's chunked form.
The state-space einsums run at ``Precision.HIGHEST``: at the default a TPU
rounds float32 operands to bfloat16, which the one-token form (elementwise)
never does, and the two forms must agree.
"""

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from areal_tpu.models.config import ModelConfig
from areal_tpu.ops import norms
# the mixers' convolution, shared with ``ops/kda.py`` and ``ops/cca.py``
from areal_tpu.ops.conv import conv_chunk, conv_step  # noqa: F401

_HI = jax.lax.Precision.HIGHEST


LANES = 128     # the chip's: the minor axis of a register and of a tile


def _lane_tiles(w: int):
    """``(K, lanes)`` of ``w`` channels: whole lane tiles (one tile of ``w``
    where it is not whole tiles: test sizes)."""
    return (w // LANES, LANES) if w % LANES == 0 else (1, w)


def _tiles(v):
    """``[..., W]`` channels -> ``[..., K, lanes]``."""
    return v.reshape(*v.shape[:-1], *_lane_tiles(v.shape[-1]))


def state_shapes(cfg: ModelConfig, batch: int):
    """``(ssm, conv)`` shapes of ``batch`` rows' state in ALL state-space
    layers: ``[Ls, B, G, K, N, lanes]`` (the module docstring says why the
    channels are minor, and in tiles) and ``[Ls, B, (d_conv - 1) x
    channels]``.
    The convolution's last inputs are kept FLAT: with the 3 taps as an
    axis of their own the chip's compiler, gathering a few rows, re-laid
    the whole array out with that axis on the lanes (3 padded to 128:
    2.99 GB at the published sizes; PERF.md §6 PR 41). The decode step's
    reader, ``ops/conv.py:conv_step``, depends on it: a tap is a static slice
    of whole lane tiles along the minor axis (``channels`` is 34, 40 or 80
    tiles at the published sizes)."""
    s = cfg.ssm
    k, lanes = _lane_tiles(s.n_heads // s.n_groups * s.head_dim)
    return (
        (cfg.n_ssm_layers, batch, s.n_groups, k, s.d_state, lanes),
        (cfg.n_ssm_layers, batch, (s.d_conv - 1) * s.conv_dim),
    )


def state_bytes_per_slot(cfg: ModelConfig) -> int:
    """What one slot's recurrent and convolution state take, all layers."""
    ssm, conv = state_shapes(cfg, 1)
    return (
        math.prod(ssm) * jnp.dtype(cfg.ssm.state_dtype).itemsize
        + math.prod(conv) * jnp.dtype(cfg.dtype).itemsize
    )


def _split_in(cfg: ModelConfig, p, h):
    """``h [..., E]`` -> ``z [..., d_inner]``, ``xBC [..., conv_dim]``,
    ``dt [..., H]`` (raw). The published input projection is ONE matrix of
    ``z + xBC + dt`` columns; the tree keeps its three parts apart
    (``w_z``, ``w_xbc``, ``w_dt``): at the published sizes the whole is
    8,512 wide, not whole lane tiles, and the chip's compiler then kept a
    transposed COPY of all 36 layers of it beside the decode chunk (1.17
    GB; PERF.md §6 PR 41)."""
    s = cfg.ssm
    with jax.named_scope("ssm_in_proj"):
        z, xbc, dt = h @ p["w_z"], h @ p["w_xbc"], h @ p["w_dt"]
        if "b_in" in p:
            b = p["b_in"]
            z = z + b[..., : s.d_inner]
            xbc = xbc + b[..., s.d_inner : s.d_inner + s.conv_dim]
            dt = dt + b[..., s.d_inner + s.conv_dim :]
    return z, xbc, dt


def _dt_a(p, dt):
    """``(dt, A)`` in float32: ``softplus(dt + dt_bias)`` and ``-exp(A_log)``
    a head."""
    dt = jax.nn.softplus(
        dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    return dt, -jnp.exp(p["A_log"].astype(jnp.float32))


def _gated_out(cfg: ModelConfig, p, y, z):
    """``W_out (rms(y * silu(z)) * w)``; the norm spans ``d_inner``, or
    each group's channels alone (``cfg.ssm.norm_per_group``)."""
    s = cfg.ssm
    grouped = s.norm_per_group and s.n_groups > 1
    with jax.named_scope("ssm_grouped_norm" if grouped else "ssm_gated_norm"):
        g = y * jax.nn.silu(z.astype(jnp.float32))
        w = p["gate_norm"]
        if grouped:     # a group's channels on an axis of their own
            g = g.reshape(*g.shape[:-1], s.n_groups, -1)
            w = w.reshape(s.n_groups, -1)
        g = norms.rms_norm(g, w, cfg.layer_norm_epsilon).reshape(z.shape)
    out = g.astype(z.dtype) @ p["w_out"]
    if "b_out" in p:
        out = out + p["b_out"]
    return out


def _split_xbc(cfg: ModelConfig, xbc):
    """``xBC [..., conv_dim]`` -> ``x [..., G, R, P]``, ``B, C [..., G, N]``
    (``G`` groups of ``R`` heads each), float32."""
    s = cfg.ssm
    G, R = s.n_groups, s.n_heads // s.n_groups
    xbc = xbc.astype(jnp.float32)
    lead = xbc.shape[:-1]
    x = xbc[..., : s.d_inner].reshape(*lead, G, R, s.head_dim)
    b = xbc[..., s.d_inner : s.d_inner + G * s.d_state].reshape(
        *lead, G, s.d_state)
    c = xbc[..., s.d_inner + G * s.d_state :].reshape(*lead, G, s.d_state)
    return x, b, c


def scan_chunked(x, dt, a_head, b, c, reset, init, chunk: int):
    """The recurrence over ``T`` tokens a row in chunks of ``chunk``.

    ``x [B, T, G, R, P]``, ``dt [B, T, G, R]`` (0 where a token must leave
    the state alone: padding), ``a_head [G, R]``, ``b, c [B, T, G, N]``,
    ``reset [B, T]`` (the state is dropped before this token), ``init [B,
    G, K, N, lanes]`` (the stored layout); all float32. Returns ``y [B, T,
    G, R, P]`` (without the ``D x`` term) and the state after the last
    token, laid out as ``init``: every product with the state is a matmul
    whose result has the lanes minor, as it is stored."""
    Bt, T = x.shape[:2]
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        # trailing tokens with dt 0 and no reset: the state passes through
        x, dt, b, c = (
            jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
            for v in (x, dt, b, c))
        reset = jnp.pad(reset, [(0, 0), (0, pad)])
    nc = (T + pad) // Q

    def chunks(v):
        return v.reshape(Bt, nc, Q, *v.shape[2:])

    x, dt, b, c, reset = map(chunks, (x, dt, b, c, reset))
    with jax.named_scope("ssm_scan"):
        cum = jnp.cumsum(dt * a_head, axis=2)             # [B, nc, Q, G, R]
        seg = jnp.cumsum(reset.astype(jnp.int32), axis=2)  # [B, nc, Q]
        t_idx = jnp.arange(Q)
        # token s reaches token t: not later, and no reset in (s, t]
        reach = (t_idx[:, None] >= t_idx[None, :]) & (
            seg[..., :, None] == seg[..., None, :])       # [B, nc, Q, Q]
        diff = cum[:, :, :, None] - cum[:, :, None, :]    # [B, nc, t, s, G, R]
        decay = jnp.where(reach[..., None, None], jnp.exp(
            jnp.where(reach[..., None, None], diff, 0.0)), 0.0)
        cb = jnp.einsum("bctgn,bcsgn->bctsg", c, b, precision=_HI)
        m = cb[..., None] * decay * dt[:, :, None]        # [B, nc, t, s, G, R]
        y = jnp.einsum("bctsgr,bcsgrp->bctgrp", m, x, precision=_HI)
        # what each chunk adds to the state at its end, and what it keeps
        # of the state it was handed
        last = seg[:, :, -1:]
        to_end = jnp.where(
            (seg == last)[..., None, None],
            jnp.exp(cum[:, :, -1:] - cum), 0.0) * dt      # [B, nc, Q, G, R]
        G, R, P = x.shape[-3:]
        add = jnp.einsum(
            "bcsgkl,bcsgn->bcgknl",
            _tiles((to_end[..., None] * x).reshape(Bt, nc, Q, G, R * P)), b,
            precision=_HI)
        keep = jnp.where(
            (last == 0)[..., None], jnp.exp(cum[:, :, -1]), 0.0)  # [B, nc, G, R]

        def carry(s, inp):
            k, a = inp
            return s * k + a, s

        final, s_in = jax.lax.scan(
            carry, init,
            (jnp.moveaxis(_tiles(jnp.repeat(keep, P, axis=-1)), 1, 0)[
                ..., None, :], jnp.moveaxis(add, 1, 0)))
        s_in = jnp.moveaxis(s_in, 0, 1)                # [B, nc, G, K, N, lanes]
        from_init = jnp.where(
            (seg == 0)[..., None, None], jnp.exp(cum), 0.0)  # [B, nc, Q, G, R]
        y = y + jnp.einsum(
            "bctgn,bcgknl->bctgkl", c, s_in, precision=_HI
        ).reshape(y.shape) * from_init[..., None]
    y = y.reshape(Bt, nc * Q, *y.shape[3:])
    return (y[:, :T] if pad else y), final


# --------------------------------------------------------------------------- #
# Mamba-1's selective scan (``cfg.ssm.selective``)
# --------------------------------------------------------------------------- #
#
#     [x ; z] = W_in h                      two matrices, ``w_x`` and ``w_z``
#     x    = silu(conv(x) + b)              causal depthwise over x ALONE
#     [dt_low ; B ; C] = W_xproj x          read from the CONVOLVED x
#     dt   = softplus(W_dt dt_low + dt_bias)    a channel
#     S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
#     y_t[c]    = sum_n S_t[c, n] C_t[n] + D[c] x_t[c]
#     out  = W_out (y * silu(z))            no norm
#
# What differs from Mamba-2 is the DECAY: ``A = -exp(A_log)`` has the
# state's own shape, one number a (channel, state) pair, so the decay
# between two tokens of a chunk is no scalar a head that a masked matmul
# could factor out (``scan_chunked``): the recurrence is computed as it is
# written, token by token, and is elementwise in the stored layout (the
# channels on the lanes, ``N`` on the sublanes: ``A`` is laid out the same
# way once a call). ``A_log`` is kept ``[N, C]``, channels minor as in the
# state. Both forms below take and return the state as Mamba-2's do; ``y``
# before the gate is the MEMORY a later gated memory unit reads.
#
# What a chunk costs: ``T`` sequential steps over ``[B, C, N]`` (admission's
# 128 tokens x 8 rows at 5,120 channels x 16: 655 k multiply-adds and
# exponentials a step, the state read and written once a step, 5.2 MB: 128
# steps a layer), where Mamba-2's form is two matmuls a chunk. An
# associative scan over the chunk would hold ``T x C x N`` floats a row
# (42 MB a row a layer) for a ``log T`` depth; not taken.


def _s6_a(p):
    """``A = -exp(A_log)`` in the state's layout ``[K, N, lanes]``."""
    a = -jnp.exp(p["A_log"].astype(jnp.float32))            # [N, C]
    return jnp.moveaxis(_tiles(a), 0, 1)


def s6_step(ssm, x, dt, a, b, c, d_skip):
    """One token of the selective scan, every row: ``ssm [B, 1, K, N,
    lanes]``, ``x, dt [B, C]`` (``dt`` 0: the row's state stays), ``a [K, N,
    lanes]``, ``b, c [B, N]``, ``d_skip [C]``; float32. Returns ``(y [B, C],
    ssm)``."""
    dt_t = _tiles(dt)[:, :, None, :]                        # [B, K, 1, lanes]
    dtx = _tiles(dt * x)[:, :, None, :]
    s = ssm[:, 0] * jnp.exp(dt_t * a) + b[:, None, :, None] * dtx
    y = jnp.sum(s * c[:, None, :, None], axis=2)            # [B, K, lanes]
    return y.reshape(x.shape) + d_skip * x, s[:, None]


def s6_scan(x, dt, a, b, c, d_skip, reset, init):
    """The selective scan over ``T`` tokens a row, token by token: ``x, dt
    [B, T, C]`` (``dt`` 0 where a token must leave the state alone:
    padding), ``b, c [B, T, N]``, ``reset [B, T]`` (the state is dropped
    before this token), ``init [B, 1, K, N, lanes]``; float32. Returns ``(y
    [B, T, C], state after the last token)``."""

    def token(s, inp):
        x_t, dt_t, b_t, c_t, r_t = inp
        s = jnp.where(r_t[:, None, None, None, None], 0.0, s)
        y, s = s6_step(s, x_t, dt_t, a, b_t, c_t, d_skip)
        return s, y

    with jax.named_scope("ssm_s6"):
        final, y = jax.lax.scan(
            token, init,
            tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c, reset)))
    return jnp.moveaxis(y, 0, 1), final


def _s6_in(p, h):
    """``h [..., E]`` -> ``(x, z)``, each ``[..., d_inner]``."""
    with jax.named_scope("ssm_in_proj"):
        return h @ p["w_x"], h @ p["w_z"]


def _s6_inputs(cfg: ModelConfig, p, x):
    """``x [..., d_inner]``, convolved and activated, to what the scan
    reads: ``(x, dt, b, c)``, ``dt`` after its projection and softplus, all
    float32."""
    s = cfg.ssm
    with jax.named_scope("ssm_x_proj"):
        dbc = x @ p["w_xproj"]
        dt = dbc[..., : s.dt_rank] @ p["w_dt"]
    dt = jax.nn.softplus(
        dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    dbc = dbc.astype(jnp.float32)
    b = dbc[..., s.dt_rank : s.dt_rank + s.d_state]
    c = dbc[..., s.dt_rank + s.d_state :]
    return x.astype(jnp.float32), dt, b, c


def _s6_out(p, y, z):
    with jax.named_scope("ssm_gate"):
        g = y * jax.nn.silu(z.astype(jnp.float32))
    return g.astype(z.dtype) @ p["w_out"]


def _s6_chunk(cfg: ModelConfig, p, h, positions, state, n_valid):
    """:func:`mixer_chunk` under the selective scan: ``(out, (ssm, conv),
    y)`` with ``y [B, T, d_inner]`` the scan's output before the gate."""
    T = h.shape[1]
    ssm0, conv0 = state
    x, z = _s6_in(p, h)
    x, conv1 = conv_chunk(p, x, positions, conv0, n_valid)
    x, dt, b, c = _s6_inputs(cfg, p, x)
    valid = jnp.arange(T)[None, :] < n_valid[:, None]
    dt = jnp.where(valid[..., None], dt, 0.0)
    y, ssm1 = s6_scan(
        x, dt, _s6_a(p), b, c, p["D"].astype(jnp.float32),
        (positions == 0) & valid, ssm0.astype(jnp.float32))
    return _s6_out(p, y, z), (ssm1.astype(ssm0.dtype), conv1), y


def _s6_token(cfg: ModelConfig, p, h, state, active):
    """:func:`mixer_step` under the selective scan (``y [B, d_inner]``)."""
    ssm0, conv0 = state
    x, z = _s6_in(p, h)
    x, conv1 = conv_step(p, x, conv0, active)
    x, dt, b, c = _s6_inputs(cfg, p, x)
    dt = jnp.where(active[:, None], dt, 0.0)
    with jax.named_scope("ssm_s6"):
        y, ssm1 = s6_step(
            ssm0, x, dt, _s6_a(p), b, c, p["D"].astype(jnp.float32))
    return _s6_out(p, y, z), (ssm1, conv1), y


def mixer_chunk(
    cfg: ModelConfig, p, h, positions, state: Optional[Tuple] = None,
    n_valid=None, chunk: Optional[int] = None, memory: bool = False,
):
    """The state-space mixer over ``h [B, T, E]`` (normed layer input).
    ``positions [B, T]``: each token's place in its own document: a token
    at 0 starts from an empty state and convolution, whatever came before
    it on the row. ``state``: ``(ssm [B, G, K, N, lanes], conv [B, (d_conv
    - 1) x C])`` the rows continue from (None: empty;
    :func:`state_shapes`).
    ``n_valid [B]``: tokens of each row
    that count (the rest is padding BEHIND them, which leaves the state
    as it is). Returns ``(out [B, T, E], (ssm, conv))``, and with
    ``memory`` a third, the scan's output ``y [B, T, d_inner]`` before the
    gate (what a gated memory unit reads)."""
    s = cfg.ssm
    Bt, T = h.shape[:2]
    G, R = s.n_groups, s.n_heads // s.n_groups
    if state is None:
        ssm_shape, conv_shape = state_shapes(cfg, Bt)
        state = (
            jnp.zeros(ssm_shape[1:], jnp.dtype(s.state_dtype)),
            jnp.zeros(conv_shape[1:], h.dtype),
        )
    if n_valid is None:
        n_valid = jnp.full((Bt,), T, jnp.int32)
    if s.selective:
        out = _s6_chunk(cfg, p, h, positions, state, n_valid)
        return out if memory else out[:2]
    ssm0, conv0 = state
    z, xbc, dt = _split_in(cfg, p, h)
    xbc, conv1 = conv_chunk(p, xbc, positions, conv0, n_valid)
    x, b, c = _split_xbc(cfg, xbc)
    dt, a = _dt_a(p, dt)
    valid = jnp.arange(T)[None, :] < n_valid[:, None]
    dt = jnp.where(valid[..., None], dt, 0.0).reshape(Bt, T, G, R)
    y, ssm1 = scan_chunked(
        x, dt, a.reshape(G, R), b, c, (positions == 0) & valid,
        ssm0.astype(jnp.float32), chunk or s.chunk_size,
    )
    y = y + p["D"].astype(jnp.float32).reshape(G, R)[..., None] * x
    y = y.reshape(Bt, T, s.d_inner)
    out = _gated_out(cfg, p, y, z), (ssm1.astype(ssm0.dtype), conv1)
    return (*out, y) if memory else out


def step_rows(x, dt, a):
    """What varies a channel in one token's update, as rows over the
    state's minor axis: ``(exp(dt A), dt x)``, each ``[B, G, R x P]``, from
    ``x [B, G, R, P]``, ``dt [B, G, R]`` and ``a [G, R]``. No transpose:
    the channels are minor in ``x`` as in the state."""
    Bt, G, R, P = x.shape
    dt = jnp.repeat(dt, P, axis=-1)
    return jnp.exp(dt * jnp.repeat(a, P, axis=-1)), dt * x.reshape(
        Bt, G, R * P)


def step_out(y, x, d_skip):
    """``y [B, G, R x P]`` (the state's sum against ``C``) ``+ D x``, as ``x
    [B, G, R, P]``."""
    Bt, G, R, P = x.shape
    y = y + jnp.repeat(d_skip, P, axis=-1) * x.reshape(Bt, G, R * P)
    return y.reshape(x.shape)


def step_update(ssm, x, dt, a, b, c, d_skip):
    """One token of the recurrence, every row: ``ssm [B, G, K, N, lanes]``,
    ``x [B, G, R, P]``, ``dt [B, G, R]`` (0: the row's state stays), ``a,
    d_skip [G, R]``, ``b, c [B, G, N]``; float32. Returns ``(y [B, G, R,
    P], ssm)``. The plain reference of the ``ssm_decode`` kernel."""
    with jax.named_scope("ssm_step"):
        decay, dtx = (_tiles(v)[..., None, :] for v in step_rows(x, dt, a))
        ssm = ssm * decay + b[:, :, None, :, None] * dtx
        y = jnp.sum(ssm * c[:, :, None, :, None], axis=3)
    return step_out(y.reshape(*y.shape[:2], -1), x, d_skip), ssm


def mixer_step(cfg: ModelConfig, p, h, state, active=None, update=None,
               memory: bool = False):
    """The mixer over ONE token a row: ``h [B, E]``, ``state`` as
    :func:`mixer_chunk` takes it. Rows where ``active [B]`` is false leave
    their state as it was (their output is garbage nobody reads).
    ``update``: what stands in for :func:`step_update` (the engine's
    kernel, which works on the state of all layers in place): it is handed
    the state of ALL layers in place of this layer's, and ``active`` by
    name; what it returns as the state is returned as is. Returns ``(out
    [B, E], (ssm, conv))``, and with ``memory`` the scan's output ``y [B,
    d_inner]`` before the gate."""
    s = cfg.ssm
    Bt = h.shape[0]
    G, R = s.n_groups, s.n_heads // s.n_groups
    ssm0, conv0 = state
    if active is None:
        active = jnp.ones((Bt,), bool)
    if s.selective:
        out = _s6_token(cfg, p, h, state, active)
        return out if memory else out[:2]
    z, xbc, dt = _split_in(cfg, p, h)
    xbc, conv1 = conv_step(p, xbc, conv0, active)
    x, b, c = _split_xbc(cfg, xbc)
    dt, a = _dt_a(p, dt)
    dt = jnp.where(active[:, None], dt, 0.0).reshape(Bt, G, R)
    args = (x, dt, a.reshape(G, R), b, c,
            p["D"].astype(jnp.float32).reshape(G, R))
    y, ssm1 = (
        step_update(ssm0, *args) if update is None
        else update(ssm0, *args, active=active))
    y = y.reshape(Bt, s.d_inner)
    out = _gated_out(cfg, p, y, z), (ssm1, conv1)
    return (*out, y) if memory else out
