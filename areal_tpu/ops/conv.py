"""Causal depthwise convolutions of a few taps over a token axis, with the
last inputs carried as per-row state: what the state-space mixers
(``ops/ssm.py``), the delta-rule mixer (``ops/kda.py``) and the convolved
attention latent (``ops/cca.py``) share.

A row's state is its last ``K - 1`` inputs, kept FLAT, ``[B, (K - 1) x C]``
(``ops/ssm.py:state_shapes`` says why). Two forms of one function:
:func:`conv_chunk`, many tokens a row (a token at position 0 of its own
document reads nothing from before it), and :func:`conv_step`, one token a
row, the decode step's.
"""

import jax
import jax.numpy as jnp


def conv_history(x, state):
    """``x [B, T, C]`` behind what its rows continue: ``state [B, (K - 1)
    x C]``, a row's last ``K - 1`` inputs of a causal convolution of ``K``
    taps, flat (``ops/ssm.py:state_shapes``). ``[B, K - 1 + T, C]``. With
    :func:`conv_reads` and :func:`conv_next_state`, what the state-space
    mixer's convolution over many tokens a row (:func:`conv_chunk`; its
    decode step is :func:`conv_step`, which builds no such array) and the
    attention latent's (``ops/cca.py``) share."""
    B, _, C = x.shape
    return jnp.concatenate(
        [state.astype(x.dtype).reshape(B, state.shape[-1] // C, C), x], axis=1)


def conv_reads(full, positions):
    """What each tap reads: for ``d = 0 .. K - 1`` the input ``d`` tokens
    back ``[B, T, C]``, zero where that would reach behind position 0 of
    the token's own document (``positions [B, T]``, restarting a
    document). ``full``: :func:`conv_history`."""
    T = positions.shape[1]
    K = full.shape[1] - T + 1
    for d in range(K):
        tap = full[:, K - 1 - d : K - 1 - d + T]
        ok = (positions >= d)[..., None]
        yield jnp.where(ok, tap, 0)


def conv_next_state(full, n_valid, state):
    """The state after each row's first ``n_valid [B]`` of many tokens (0:
    as it was), in ``state``'s shape and dtype: a slice of ``full`` at a
    start a row."""
    K = state.shape[-1] // full.shape[-1] + 1
    new_state = jax.vmap(
        lambda f, n: jax.lax.dynamic_slice_in_dim(f, n, K - 1, axis=0)
    )(full, n_valid)
    return new_state.astype(state.dtype).reshape(state.shape)


def conv_out(p, taps, dtype, scope: str = "ssm_conv"):
    """``silu(sum_d tap_d * w[K - 1 - d] + b)`` in float32, as ``dtype``:
    ``taps`` from the token itself (``d = 0``) back, added in that order.
    ``p``: ``conv_w [K, C]`` and, where the family has one, ``conv_b [C]``;
    ``scope``: the ``jax.named_scope`` the sum runs under."""
    w = p["conv_w"]                                       # [K, C]
    K = w.shape[0]
    with jax.named_scope(scope):
        out = 0.0
        for d, tap in enumerate(taps):
            # the tap ``d`` tokens back: weight K - 1 - d
            out = out + tap.astype(jnp.float32) * w[
                K - 1 - d].astype(jnp.float32)
        if "conv_b" in p:
            out = out + p["conv_b"].astype(jnp.float32)
        return jax.nn.silu(out).astype(dtype)


def conv_chunk(p, xbc, positions, conv_state, n_valid, scope: str = "ssm_conv"):
    """The causal depthwise convolution over ``xbc [B, T, C]``, many tokens
    a row, whose rows continue ``conv_state [B, (K - 1) x C]``
    (:func:`conv_history`). Returns the activated output and the state
    after each row's first ``n_valid [B]`` tokens."""
    full = conv_history(xbc, conv_state)
    out = conv_out(p, conv_reads(full, positions), xbc.dtype, scope)
    return out, conv_next_state(full, n_valid, conv_state)


def conv_step(p, x, state, active, scope: str = "ssm_conv"):
    """The convolution over ONE token a row, the decode step's: ``x [B,
    C]``, ``state [B, (K - 1) x C]``, ``active [B]`` (false: the row's
    state stays). Bit for bit :func:`conv_chunk` at ``T = 1`` behind ``K -
    1`` or more tokens of the document (no tap is masked), in another
    form: the taps are static slices of the FLAT state along its minor
    axis and the next state is one elementwise pass, which the chip's
    compiler fuses into the in-place update of the stacked state. Built
    as ``[B, K, C]`` with a start a row, the same values cost two re-laid
    copies, a padded ``[B, 4, C]`` and a gather a layer a token (PERF.md
    §6 PR 54)."""
    C = x.shape[-1]
    K = state.shape[-1] // C + 1
    taps = [x] + [
        state[:, j * C : (j + 1) * C].astype(x.dtype)
        for j in reversed(range(K - 1))]
    # each piece chosen a row BEFORE the two are laid end to end: one select
    # over the whole shifted row measured a fifth slower on the chip
    moves = active[:, None]
    state = jnp.concatenate([
        jnp.where(moves, state[:, C:], state[:, :-C]),
        jnp.where(moves, x.astype(state.dtype), state[:, -C:])], axis=-1)
    return conv_out(p, taps, x.dtype, scope), state
