"""Packed varlen causal attention.

TPU-native counterpart of the reference's flash-attn varlen path
(``realhf/impl/model/modules/attn.py:272-289``). Where the reference carries
``cu_seqlens`` into ``flash_attn_varlen_func`` (CUDA), we pack sequences into
one token axis and carry integer ``segment_ids`` (0 = padding, real segments
start at 1). A token attends to a key iff they share a segment id and the key
does not come later in the packed order. Positions restart per segment, so
causality within a segment coincides with packed-order causality.

Two implementations behind one entry point:
- ``_attention_xla``: plain einsum + mask. Reference semantics; used on CPU
  (tests) and as the autodiff-friendly fallback.
- Pallas flash attention (``areal_tpu.ops.pallas.flash_attention``) on TPU for
  long contexts — selected by ``use_flash`` when available.

All shapes static: ``q,k,v`` are ``[T, H, D]`` / ``[T, Hkv, D]`` where T is
the padded packed-token budget, so one compiled program serves every batch.
"""

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

_NEG_INF = -2.3819763e38  # ~ -float32 max; matches common flash-attn masks


def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """[T, Hkv, D] -> [T, Hkv*n_rep, D] (GQA key/value head expansion)."""
    if n_rep == 1:
        return k
    t, hkv, d = k.shape
    return jnp.broadcast_to(k[:, :, None, :], (t, hkv, n_rep, d)).reshape(
        t, hkv * n_rep, d
    )


def _attention_xla(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    segment_ids: jnp.ndarray,
    softmax_scale: float,
    soft_cap: Optional[float] = None,
    sliding_window: Optional[int] = None,
) -> jnp.ndarray:
    t, h, d = q.shape
    n_rep = h // k.shape[1]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scores = jnp.einsum(
        "qhd,khd->hqk", q, k, preferred_element_type=jnp.float32
    ) * softmax_scale
    if soft_cap is not None:
        scores = soft_cap * jnp.tanh(scores / soft_cap)
    idx = jnp.arange(t)
    same_seg = (segment_ids[:, None] == segment_ids[None, :]) & (
        segment_ids[:, None] > 0
    )
    causal = idx[:, None] >= idx[None, :]
    mask = same_seg & causal
    if sliding_window is not None:
        mask &= idx[:, None] - idx[None, :] < sliding_window
    scores = jnp.where(mask[None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # Fully-masked (padding) rows: softmax over all -inf gives garbage; zero them.
    probs = jnp.where(mask.any(axis=-1)[None, :, None], probs, 0.0)
    return jnp.einsum("hqk,khd->qhd", probs.astype(v.dtype), v)


def packed_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    segment_ids: jnp.ndarray,
    *,
    softmax_scale: Optional[float] = None,
    soft_cap: Optional[float] = None,
    sliding_window: Optional[int] = None,
    use_flash: bool = False,
    flash_block_size: Optional[int] = None,
    flash_block_size_k: Optional[int] = None,
    max_seqlen: Optional[int] = None,
) -> jnp.ndarray:
    """Causal self-attention over a packed token axis.

    Args:
      q: ``[T, H, D]``; k, v: ``[T, Hkv, D]`` (``H % Hkv == 0``).
      segment_ids: ``[T]`` int32, 0 marks padding tokens.
      flash_block_size, flash_block_size_k: None = the kernels' own rule
        (``ops/pallas/flash_attention.flash_blocks``), which reads the
        call's shapes: 1024 x 1024 and the interior body at T >= 8192;
        under it 256 x 1024 for the forward and 256 x 256 for the fused
        backward (512 x 512 with a window or a ``max_seqlen``). A value
        overrides both directions' block.
      max_seqlen: STATIC upper bound on any segment length; shortens the
        flash kernels' pair list (see ``packed_flash_attention``).
    Returns ``[T, H, D]``.
    """
    if softmax_scale is None:
        softmax_scale = q.shape[-1] ** -0.5
    if _RING_CTX is not None:
        from areal_tpu.ops.ring_attention import ring_attention

        mesh, axis = _RING_CTX
        return ring_attention(
            q, k, v, segment_ids, mesh, axis,
            softmax_scale=softmax_scale,
            soft_cap=soft_cap,
            sliding_window=sliding_window,
        )
    if use_flash:
        from areal_tpu.ops.pallas import flash_attention as _fa

        flash = functools.partial(
            _fa.packed_flash_attention,
            softmax_scale=softmax_scale,
            soft_cap=soft_cap,
            sliding_window=sliding_window,
            block_size=flash_block_size,
            block_size_k=flash_block_size_k,
            max_seqlen=max_seqlen,
        )
        if _FLASH_MESH is not None:
            # GSPMD cannot partition a Mosaic kernel ("Mosaic kernels
            # cannot be automatically partitioned"): under a multi-device
            # mesh the call is a shard_map. Attention is per-head
            # independent, and contiguous q-head chunks of H/tp cover whole
            # GQA groups, so each model shard runs the kernel on its own
            # heads; a caller's vmap over batch rows (spmd_axis_name =
            # the data axes) shards the rows the same way.
            tp = _FLASH_MESH.shape.get("model", 1)
            if k.shape[1] % tp:
                raise ValueError(
                    f"flash attention under a {tp}-way model axis needs "
                    f"n_kv_heads ({k.shape[1]}) divisible by it"
                )
            heads = P(None, "model", None)
            flash = jax.shard_map(
                flash, mesh=_FLASH_MESH,
                in_specs=(heads, heads, heads, P(None)), out_specs=heads,
                check_vma=False,
            )
        return flash(q, k, v, segment_ids)
    return _attention_xla(
        q, k, v, segment_ids, softmax_scale, soft_cap, sliding_window
    )


# The mesh a multi-device engine is tracing its programs under, or None.
# Trace-time state, set while an engine's jitted functions are traced
# (`trace_on_mesh`): what the flash dispatch above needs to know to wrap
# the kernel.
_FLASH_MESH = None


@contextlib.contextmanager
def flash_mesh(mesh):
    global _FLASH_MESH
    prev = _FLASH_MESH
    _FLASH_MESH = mesh if mesh is not None and mesh.size > 1 else None
    try:
        yield
    finally:
        _FLASH_MESH = prev


def trace_on_mesh(mesh, f):
    """``f``, to be jitted by an engine that owns ``mesh``: whenever it is
    traced, the flash dispatch knows the mesh."""
    @functools.wraps(f)
    def traced(*args):
        with flash_mesh(mesh):
            return f(*args)
    return traced


# Context-parallel override: when set, packed training attention rings the
# token axis over the given mesh axis (engines with ParallelConfig.ctx > 1
# set this at init; the trace picks it up wherever the forward runs).
_RING_CTX = None


def set_context_parallel(mesh, axis_name: str = "ctx"):
    global _RING_CTX
    if _RING_CTX is not None:
        old_mesh, old_axis = _RING_CTX
        if old_axis != axis_name or dict(old_mesh.shape) != dict(mesh.shape):
            raise ValueError(
                "conflicting context-parallel topologies in one process: "
                f"{dict(old_mesh.shape)} vs {dict(mesh.shape)} — every train "
                "engine in a CP experiment must share the same mesh shape"
            )
    _RING_CTX = (mesh, axis_name)


def get_context_parallel():
    return _RING_CTX


def clear_context_parallel():
    global _RING_CTX
    _RING_CTX = None


def decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    cache_lens: jnp.ndarray,
    *,
    softmax_scale: Optional[float] = None,
    soft_cap: Optional[float] = None,
    sliding_window: Optional[int] = None,
) -> jnp.ndarray:
    """Single-token decode attention against a per-sequence KV cache.

    Args:
      q: ``[B, H, D]`` — one new token per sequence.
      k_cache, v_cache: ``[B, S, Hkv, D]`` — S is the static cache capacity;
        the new token's K/V must already be written at ``cache_lens - 1``.
      cache_lens: ``[B]`` int32 — number of valid cache entries per sequence
        (including the current token).
    Returns ``[B, H, D]``.
    """
    if softmax_scale is None:
        softmax_scale = q.shape[-1] ** -0.5
    b, s = k_cache.shape[0], k_cache.shape[1]
    n_rep = q.shape[1] // k_cache.shape[2]
    k = k_cache
    v = v_cache
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=2)
        v = jnp.repeat(v, n_rep, axis=2)
    scores = jnp.einsum(
        "bhd,bshd->bhs", q, k, preferred_element_type=jnp.float32
    ) * softmax_scale
    if soft_cap is not None:
        scores = soft_cap * jnp.tanh(scores / soft_cap)
    pos = jnp.arange(s)[None, :]
    mask = pos < cache_lens[:, None]
    if sliding_window is not None:
        mask &= pos >= cache_lens[:, None] - sliding_window
    scores = jnp.where(mask[:, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(cache_lens[:, None, None] > 0, probs, 0.0)
    return jnp.einsum("bhs,bshd->bhd", probs.astype(v.dtype), v)
