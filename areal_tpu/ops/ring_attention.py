"""Ring attention: context parallelism for long packed sequences.

The first-class long-context story (SURVEY §2.2 "SP"): the packed token axis
shards over a ``ctx`` mesh axis; each device holds a T/cp chunk of q/k/v and
K/V chunks ROTATE around the ring (``lax.ppermute``) while every device
accumulates online-softmax partials against its resident queries — attention
memory and FLOPs per device scale with T/cp, and the K/V traffic rides ICI
(the reference reaches long context through Megatron's sequence parallelism
+ flash-attn varlen kernels; the ring is the TPU-native equivalent of its
context-parallel decomposition).

Implementation notes:
- Pure JAX inside ``shard_map``: ``ppermute`` is differentiable (its
  transpose is the reverse rotation), so the BACKWARD ring — dq locally,
  dk/dv accumulated while rotating back — falls out of autodiff instead of
  a second hand-written protocol.
- Each (q-chunk, kv-chunk) pair runs blockwise online-softmax over k
  sub-chunks (``lax.scan``) under ``jax.checkpoint``: nothing quadratic in
  T is ever materialized, forward or backward.
- Masks use GLOBAL positions (chunk offset = ring index * chunk length):
  causal + packed segment ids + optional sliding window, matching
  ``ops/pallas/flash_attention.py`` semantics (pad rows output 0).
- Per-pair skip: a kv chunk strictly after the q chunk (causal) contributes
  nothing and is skipped with ``lax.cond``, so the causal ring costs ~half.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -2.3819763e38


def _pair_partial(
    q, k, v, seg_q, seg_k, q_off, k_off, m, l, acc,
    *, scale, soft_cap, sliding_window, block_k,
):
    """Fold one kv chunk into the (m, l, acc) online-softmax state.

    q [T, H, D]; k/v [T, Hkv, D]; seg_* [T]; offsets are global token
    positions of each chunk's first token. State: m, l [H, T, 1] f32;
    acc [H, T, D] f32.
    """
    T, H, Dh = q.shape
    Hkv = k.shape[1]
    n_rep = H // Hkv
    nb = T // block_k
    qg = q_off + jnp.arange(T)
    qT = q.swapaxes(0, 1)                        # [H, T, D]

    def body(state, inputs):
        m, l, acc = state
        kb, vb, seg_kb, kg = inputs              # [bk, Hkv, D], ..., [bk]
        kb = jnp.repeat(kb, n_rep, axis=1)       # [bk, H, D]
        vb = jnp.repeat(vb, n_rep, axis=1)
        s = jnp.einsum(
            "htd,bhd->htb", qT, kb, preferred_element_type=jnp.float32
        ) * scale                                # [H, T, bk]
        if soft_cap is not None:
            s = soft_cap * jnp.tanh(s / soft_cap)
        mask = (
            (qg[:, None] >= kg[None, :])
            & (seg_q[:, None] == seg_kb[None, :])
            & (seg_q[:, None] > 0)
        )
        if sliding_window is not None:
            mask &= qg[:, None] - kg[None, :] < sliding_window
        s = jnp.where(mask[None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask[None], jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = corr * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum(
            "htb,bhd->htd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l, acc), None

    kb = k.reshape(nb, block_k, Hkv, Dh)
    vb = v.reshape(nb, block_k, Hkv, Dh)
    segb = seg_k.reshape(nb, block_k)
    kg = (k_off + jnp.arange(T)).reshape(nb, block_k)
    (m, l, acc), _ = jax.lax.scan(body, (m, l, acc), (kb, vb, segb, kg))
    return m, l, acc


def _ring_shard(
    q, k, v, seg,
    *, axis_name, scale, soft_cap, sliding_window, block_k, cp,
):
    """Per-shard body (inside shard_map): q/k/v [T, H(kv), D], seg [T]."""
    T, H, Dh = q.shape
    idx = jax.lax.axis_index(axis_name)
    m = jnp.full((H, T, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((H, T, 1), jnp.float32)
    acc = jnp.zeros((H, T, Dh), jnp.float32)
    q_off = idx * T

    kv = (k, v, seg)
    perm = [(i, (i + 1) % cp) for i in range(cp)]
    pair = functools.partial(
        _pair_partial, scale=scale, soft_cap=soft_cap,
        sliding_window=sliding_window, block_k=block_k,
    )
    for s in range(cp):
        src = (idx - s) % cp
        k_s, v_s, seg_s = kv
        k_off = src * T

        def with_chunk(state):
            return jax.checkpoint(
                lambda st: pair(
                    q, k_s, v_s, seg, seg_s, q_off, k_off, *st
                )
            )(state)

        # causal skip: a kv chunk strictly after the q chunk is all-masked
        m, l, acc = jax.lax.cond(
            k_off <= q_off, with_chunk, lambda st: st, (m, l, acc)
        )
        if s != cp - 1:
            kv = jax.lax.ppermute(kv, axis_name, perm)
    safe_l = jnp.where(l > 0.0, l, 1.0)
    out = (acc / safe_l).swapaxes(0, 1).astype(q.dtype)  # [T, H, D]
    return out


def ring_attention(
    q: jnp.ndarray,          # [T, H, D] global (token axis sharded over ctx)
    k: jnp.ndarray,          # [T, Hkv, D]
    v: jnp.ndarray,
    segment_ids: jnp.ndarray,  # [T] int32, 0 = padding
    mesh,
    axis_name: str = "ctx",
    *,
    softmax_scale: Optional[float] = None,
    soft_cap: Optional[float] = None,
    sliding_window: Optional[float] = None,
    block_k: int = 512,
) -> jnp.ndarray:
    """Causal packed-varlen attention with the token axis ring-sharded.

    Call from inside (or outside) jit with GLOBAL arrays; the internal
    shard_map re-partitions over ``axis_name``. Differentiable end-to-end
    (the backward ring is autodiff through ppermute).
    """

    T, H, Dh = q.shape
    cp = mesh.shape[axis_name]
    if softmax_scale is None:
        softmax_scale = Dh ** -0.5
    if T % cp != 0:
        raise ValueError(f"token axis {T} not divisible by ctx={cp}")
    chunk = T // cp
    bk = min(block_k, chunk)
    if chunk % bk != 0:
        import math

        bk = math.gcd(chunk, bk)  # largest workable sub-chunk
    # NOT named `body`: _ring_shard's internal scan body def shares that
    # name, and the shadowing made the wrapped callable ambiguous to
    # read (and to arealint's shard_map arity resolution)
    ring_body = functools.partial(
        _ring_shard,
        axis_name=axis_name,
        scale=softmax_scale,
        soft_cap=soft_cap,
        sliding_window=sliding_window,
        block_k=bk,
        cp=cp,
    )
    # keep TP: heads stay split over `model` inside the ring when both q and
    # kv head counts divide it (they must split together or the GQA group
    # ratio breaks); otherwise heads replicate across model for this op
    m = mesh.shape.get("model", 1)
    head_ax = "model" if (m > 1 and H % m == 0 and k.shape[1] % m == 0) else None
    spec_t = P(axis_name)
    spec_qkv = P(axis_name, head_ax, None)
    return jax.shard_map(
        ring_body,
        mesh=mesh,
        in_specs=(spec_qkv, spec_qkv, spec_qkv, spec_t),
        out_specs=spec_qkv,
        check_vma=False,
    )(q, k, v, segment_ids)
