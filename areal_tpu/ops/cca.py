"""Compressed convolutional attention (family ``zaya``): what stands in
for the q/k/v projections, in plain ``jax.numpy``.

One layer, token ``t`` of a row, ``h`` the normed residual, ``D`` the head
width, ``G`` query heads a kv head::

    q~ = W_q h_t          k~ = W_k h_t          p_t = [q~ ; k~]
    c_t = sum_d w0[d] * p_{t-d} + b0            depthwise, ``time0`` taps
    e_t = sum_d W1[d] c_{t-d} + b1              a D x D block a head, ``time1`` taps
    m^q_h = (q~_h + k~_g(h)) / 2                m^k_g = mean of m^q_h over g's heads
    q_h = unit(e^q_h + m^q_h) sqrt(D)           k_g = unit(e^k_g + m^k_g) sqrt(D) tau_g
    v_t = [W_v h_t](first half of the kv heads) ; [W_v h_{t-1}](second half)

then the rotary embedding and attention as for any model: the cache holds
``k`` after all of this and ``v`` after the shift, in the ONE page pool's
usual geometry. Everything that looks back reads zeros behind position 0
of the token's own document, so packed documents do not see each other.

ONE function, :func:`qkv`, serves many tokens a row (the trainer, prefill,
admission's chunks) and one (a decode step, ``T = 1``): a row continues a
CARRY ``[B, W]`` (``ModelConfig.cca_carry_dim``: the first convolution's
last ``time0 - 1`` inputs, the second's last ``time1 - 1``, the shifted
half of the last token's value projection; flat, in the serving dtype)
and hands back the carry after its first ``n_valid`` tokens. The look-back
itself is ``ops/conv.py:conv_history`` and its two companions, the
state-space mixer's.
"""

import jax
import jax.numpy as jnp

from areal_tpu.models.config import ModelConfig
from areal_tpu.ops.conv import conv_history, conv_next_state, conv_reads


def _unit(x):
    """Each head of ``x [..., H, D]`` (float32) at norm ``sqrt(D)``."""
    norm = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    return x * (x.shape[-1] ** 0.5 / jnp.maximum(norm, 1e-12))


def qkv(cfg: ModelConfig, p, h, positions, carry=None, n_valid=None):
    """``h [B, T, E]`` -> ``q [B, T, Hq, D]``, ``k, v [B, T, Hkv, D]``
    (rotary NOT yet applied) and the rows' carry ``[B, W]``. ``positions
    [B, T]``: each token's place in its own document. ``carry``: what the
    rows continue from (None: nothing). ``n_valid [B]``: tokens of each
    row that count (the rest is padding behind them, which leaves the
    carry as it is; 0: a row that is not running)."""
    cca, D = cfg.cca, cfg.head_dim
    Hq, Hkv, G = cfg.n_q_heads, cfg.n_kv_heads, cfg.n_rep
    B, T = h.shape[:2]
    C = cfg.cca_latent_dim
    if carry is None:
        carry = jnp.zeros((B, cfg.cca_carry_dim), h.dtype)
    if n_valid is None:
        n_valid = jnp.full((B,), T, jnp.int32)
    s0 = (cca.time0 - 1) * C
    s1 = s0 + (cca.time1 - 1) * C
    f32 = jnp.float32
    with jax.named_scope("cca_proj"):
        lat = jnp.concatenate([h @ p["wq"], h @ p["wk"]], axis=-1)
        val = h @ p["wv"]
    with jax.named_scope("cca_conv"):
        full0 = conv_history(lat, carry[:, :s0])
        c = p["conv0_b"].astype(f32)
        for d, tap in enumerate(conv_reads(full0, positions)):
            c = c + tap.astype(f32) * p["conv0_w"][cca.time0 - 1 - d].astype(f32)
        c = c.astype(h.dtype)
        full1 = conv_history(c, carry[:, s0:s1])
        e = p["conv1_b"].astype(f32).reshape(Hq + Hkv, D)
        for d, tap in enumerate(conv_reads(full1, positions)):
            e = e + jnp.einsum(
                "bthi,hio->btho", tap.reshape(B, T, Hq + Hkv, D),
                p["conv1_w"][cca.time1 - 1 - d], preferred_element_type=f32)
    with jax.named_scope("cca_qk_mean_norm"):
        lat = lat.astype(f32).reshape(B, T, Hq + Hkv, D)
        q_lat = lat[:, :, :Hq].reshape(B, T, Hkv, G, D)
        mean_q = 0.5 * (q_lat + lat[:, :, Hq:, None])
        q = _unit(e[:, :, :Hq] + mean_q.reshape(B, T, Hq, D))
        k = _unit(e[:, :, Hq:] + mean_q.mean(axis=3))
        k = k * p["k_temp"].astype(f32)[:, None]
    # the second half of the kv heads hold the previous token's values
    half = Hkv // 2 * D
    full_v = conv_history(val[..., half:], carry[:, s1:])
    _, prev = conv_reads(full_v, positions)
    v = jnp.concatenate([val[..., :half], prev], axis=-1)
    carry = jnp.concatenate([
        conv_next_state(full, n_valid, carry[:, lo:hi])
        for full, lo, hi in (
            (full0, 0, s0), (full1, s0, s1), (full_v, s1, carry.shape[1]))
    ], axis=-1)
    return (
        q.astype(h.dtype), k.astype(h.dtype), v.reshape(B, T, Hkv, D), carry)
