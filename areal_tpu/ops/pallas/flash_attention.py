"""Packed varlen flash attention (Pallas TPU kernels, forward AND backward).

Block-wise online-softmax attention over one packed token axis with
segment-id masking — the TPU counterpart of the reference's
``flash_attn_varlen_func(cu_seqlens)`` path
(``realhf/impl/model/modules/attn.py:272-289``), which trains through fused
varlen flash in both directions.

Layout: ``q [H, T, D]``-major inside the kernels (the public wrapper
transposes from the model's ``[T, H, D]``). TPU grids run sequentially
minor-to-major, so VMEM scratch accumulators carry state across the
innermost grid axis:

- **forward**: grid ``(Hkv, L)`` over the pair list below; online-softmax
  state (m, l, acc) per (kv head's q group, q block); also emits the
  logsumexp ``lse [H, T]`` for the backward.
- **bwd (fused)**: the same grid, q-stationary: one (p, ds) recompute a
  pair feeds ``dv += pᵀ dо``, ``dk += dsᵀ q`` (whole-T ``[T, D]`` f32 VMEM
  scratches, flushed once per kv head) AND ``dq += ds k`` (one q block's
  scratch, flushed at the end of its sweep) — 5 dots + 1 exp per block pair
  instead of the 7 + 2 of separate dq/dkv sweeps. Falls back to the separate
  ``_dq_kernel``/``_dkv_kernel`` sweeps when the whole-T scratch exceeds
  ``FUSED_BWD_MAX_DQ_BYTES``. GQA never materializes a K/V repeat: the
  group's q heads are folded into the q block's rows.

**The pair list.** Packed rows carry non-decreasing segment ids (padding 0
at the tail), so the only (q block, k block) pairs with any unmasked work
form a band: the causal diagonal and the pad tail on one side, the first k
block containing the q block's minimum segment (`kstart`, narrowed further
by a sliding window) on the other. `_pair_list` builds the band's pairs at
run time in XLA from ``segment_ids``, compacted in q-block order, as three
scalar-prefetched tables (q block, k block, flags) that the grid's second
axis walks and the BlockSpec index maps read. The axis's length is static:
the causal triangle's count, or less under a static ``max_seqlen`` or a
window (`_pair_steps`); steps past the list's end repeat the last pair's
indices, so Pallas copies nothing for them, and run no body: what a band
narrower than its static bound costs is those empty steps, not K/V copies.
How large the blocks are is `flash_blocks`' choice from the call's shapes
(a tight cover of a short row's band by small blocks does NOT pay: a
step's fixed work outweighs the masked-away part of a wider tile). The
split fallback sweeps keep band-relative grids with clamped index maps.

**Interior-block specialization.** The kernels are VPU-bound, not MXU-bound:
at D=64 each score element costs ~128 MXU FLOPs but several VPU passes when
the token-level mask is applied (the mask itself is built once a pair for
one head and shared by the group's folded heads: `_where`). A pair is
*interior* when every token pair in it is unmasked — all of a long causal
row but its diagonal — so where the rule says a row is long
(``specialize``) the list's MASKED flag (`_block_needs_mask`) routes each
step to either the masked body or a mask-free fast body that runs just the
online-softmax update; a short packed row runs the one masked body (the
second body measured a loss there). Softmax runs in the log2 domain
(``exp2(s·scale·log2e)``) — one fewer VPU multiply per element than ``exp``,
matching how Mosaic lowers transcendentals; the emitted ``lse`` stays in
natural log, so the contract with the backward and with ring attention is
unchanged.

The backward follows the flash-attention-2 recipe: residuals are
``(q, k, v, out, lse)``; ``delta = rowsum(dо * out)`` is computed by the
fused kernel at the first step of a q block's sweep (as an XLA pass it is
written lane-padded to 128 x its size and read back a pair; only the split
sweeps still take it from XLA), and ``ds = p * (dp - delta)``.
All matmuls take bf16 operands with f32 accumulation (operand-side f32
casts would quarter MXU throughput).
"""

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.3819763e38
LANES = 128
LOG2E = 1.4426950408889634
LN2 = 0.6931471805599453
# What a step of the pair list is, one int32 a step (`_pair_list`)
FIRST, LAST, ACTIVE, MASKED = 1, 2, 4, 8
# From this many packed tokens a row's pairs are mostly interior ones: the
# rule (`flash_blocks`) gives it 1,024-blocks and the mask-free body.
LONG_ROW = 8192
# Fused-backward dq scratch + output block budget (v5e has 128 MB VMEM; the
# rest of the kernel needs ~30 MB at block 1024). Above this the backward
# falls back to separate dq/dkv sweeps.
FUSED_BWD_MAX_DQ_BYTES = 48 * 2**20


# Mosaic's scoped-vmem budget: the default a kernel gets when it asks for
# nothing (v5e), and the most it may ask for (v5e VMEM is 128 MiB).
DEFAULT_SCOPED_VMEM = 16 * 2**20
MAX_SCOPED_VMEM = 114 * 2**20


def _vmem_bytes(shape, dtype) -> int:
    """VMEM bytes of one buffer as Mosaic lays it out: the minor dim pads
    to 128 lanes and the second-minor to the dtype's sublane tile (8 rows
    of 32 bits) — a ``[..., block_q, 1]`` f32 column costs 128x its
    logical size."""
    item = jnp.dtype(dtype).itemsize
    *lead, rows, cols = (1,) + tuple(shape)
    sub = 8 * max(1, 4 // item)
    n = -(-rows // sub) * sub * -(-cols // LANES) * LANES * item
    for d in lead:
        n *= d
    return n


def _vmem_limit(blocks, scratch, temps) -> Optional[int]:
    """Scoped-vmem budget from the kernel's real footprint: pipelined
    in/out ``blocks`` are double-buffered, ``scratch`` is resident, and
    ``temps`` are the in-kernel score/probability tiles. None (the
    compiler's default) while the estimate sits clearly inside the default
    budget; otherwise the estimate plus half again for what the estimate
    cannot see (spills, relayout copies)."""
    est = (
        2 * sum(_vmem_bytes(*b) for b in blocks)
        + sum(_vmem_bytes(*b) for b in scratch)
        + temps
    )
    if est <= DEFAULT_SCOPED_VMEM * 3 // 4:
        return None
    return min(est * 3 // 2, MAX_SCOPED_VMEM)


def _params(limit, **kwargs):
    if limit is not None:
        kwargs["vmem_limit_bytes"] = limit
    return pltpu.CompilerParams(**kwargs)


def _bwd_pipeline() -> bool:
    # cross-block software pipelining in the fused backward (VERDICT r4
    # #4): park (p, ds) one step and issue their gradient dots alongside
    # the next block's VPU work. Numerics identical (parking dtype = the
    # dots' operand dtype). Default off and unjudged: neither side has
    # been timed in the train cell (ROADMAP D1, S4).
    from areal_tpu.base import constants

    return constants.flash_bwd_pipeline_enabled()


def _interpret() -> bool:
    # off-TPU (CPU tests) the kernels run in the pallas interpreter
    return jax.devices()[0].platform != "tpu"


def _search(xp, a, v, side):
    """``searchsorted`` over a few dozen blocks: inside a program as ONE
    compare-and-sum (jnp's default is a binary search, a ``while`` of tiny
    steps that costs a TPU more than the kernel's empty steps it saves)."""
    if xp is np:
        return np.searchsorted(a, v, side=side).astype(np.int32)
    return jnp.searchsorted(a, v, side=side, method="compare_all").astype(
        jnp.int32)


def _band_bounds(segment_ids, block_q, block_k, sliding_window, T, xp=jnp):
    """Per-block band bounds for the packed row (all int32; ``xp`` is jnp
    inside a program, numpy for the host's count `pair_counts`):

    - ``kstart [nq]``: first k block with any key the q block may attend to
      (segment- and window-derived; can exceed the causal diagonal for
      all-pad q blocks — callers clamp to it).
    - ``qlast [nk]``: last q block with any query attending into the k block
      (-1 when the k block is all padding).
    """
    nq, nk = T // block_q, T // block_k
    BIG = 2**30
    sq = segment_ids.reshape(nq, block_q)
    sk = segment_ids.reshape(nk, block_k)
    qmin = xp.where(sq > 0, sq, BIG).min(axis=1).astype(xp.int32)
    kmax = sk.max(axis=1).astype(xp.int32)
    # monotone prefix: pad-tail kmax drops to 0, so search on the running max
    kmax_mono = (
        np.maximum.accumulate(kmax) if xp is np else jax.lax.cummax(kmax)
    )
    kstart = _search(xp, kmax_mono, qmin, "left")
    # qmin is globally non-decreasing (BIG on the pad tail)
    qlast = _search(xp, qmin, kmax, "right") - 1
    qlast = xp.where(kmax > 0, qlast, -1)
    if sliding_window is not None:
        iq = xp.arange(nq, dtype=xp.int32)
        ik = xp.arange(nk, dtype=xp.int32)
        kstart = xp.maximum(
            kstart,
            xp.maximum(iq * block_q - (sliding_window - 1), 0) // block_k,
        )
        qlast = xp.minimum(
            qlast, (ik * block_k + block_k - 1 + sliding_window - 1) // block_q
        )
    return kstart, qlast


def _last_k(iq, block_q, block_k):
    """Causal diagonal: last k block with keys not after this q block."""
    return (iq * block_q + block_q - 1) // block_k


def _first_q(ik, block_q, block_k):
    """Causal diagonal: first q block with queries not before this k block."""
    return (ik * block_k) // block_q


def _k_band_blocks(block_q, block_k, max_seqlen, T):
    """Static bound on the k-block band width per q block: a q block's
    earliest needed key starts at most ``max_seqlen - 1`` tokens before the
    block (the segment containing its first token), and its last is the
    causal diagonal — so the span is <= block_q + max_seqlen - 1 tokens."""
    nk = T // block_k
    if max_seqlen is None:
        return nk
    return min(nk, -(-(block_q + max_seqlen - 1) // block_k) + 1)


def _q_band_blocks(block_q, block_k, max_seqlen, T):
    """Static bound on the q-block band width per k block (symmetric)."""
    nq = T // block_q
    if max_seqlen is None:
        return nq
    return min(nq, -(-(block_k + max_seqlen - 1) // block_q) + 1)


def _block_needs_mask(segment_ids, block_q, block_k, sliding_window, T,
                      xp=jnp):
    """``[nq*nk] int32``: 0 where the (q block, k block) pair is *interior* —
    every token pair unmasked (block fully below the causal diagonal, one
    shared nonzero segment, fully inside any sliding window) — so the
    kernels skip mask construction entirely; 1 where token-level masking is
    required. Out-of-band pairs never execute a body, so their value is
    irrelevant."""
    nq, nk = T // block_q, T // block_k
    sq = segment_ids.reshape(nq, block_q)
    sk = segment_ids.reshape(nk, block_k)
    q_seg = sq.min(axis=1)
    q_uni = (q_seg == sq.max(axis=1)) & (q_seg > 0)
    k_seg = sk.min(axis=1)
    k_uni = k_seg == sk.max(axis=1)
    same = q_uni[:, None] & k_uni[None, :] & (q_seg[:, None] == k_seg[None, :])
    iq = xp.arange(nq, dtype=xp.int32)
    ik = xp.arange(nk, dtype=xp.int32)
    causal = (iq * block_q)[:, None] >= (ik * block_k + block_k - 1)[None, :]
    interior = same & causal
    if sliding_window is not None:
        maxdiff = (iq * block_q + block_q - 1)[:, None] - (ik * block_k)[None, :]
        interior &= maxdiff < sliding_window
    return xp.where(interior, 0, 1).astype(xp.int32).reshape(-1)


def _scores_log2(q2d, k_ref, scale, soft_cap):
    """Block scores in the log2 domain: ``(q·kᵀ)·scale·log2e`` (soft-capped
    in the natural domain first when requested). ``q2d`` is the (possibly
    rep-folded) ``[rows, D]`` q block; result f32 [rows, bk].

    Without a cap, the scale folds into the q BLOCK before the dot — a
    [rows, D] multiply instead of a full [rows, bk] VPU pass over the
    scores (D=64 models are VPU-bound at long context; one pass of ~5 is
    free). The extra bf16 rounding on q is below the dot's own bf16
    noise."""
    if soft_cap is None:
        qs = q2d * jnp.asarray(scale * LOG2E, q2d.dtype)
        return jax.lax.dot_general(
            qs, k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    s = jax.lax.dot_general(
        q2d, k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    s = soft_cap * jnp.tanh(s * (scale / soft_cap))
    return s * LOG2E


def _token_mask(seg_q_ref, seg_k_ref, iq, ik, block_q, block_k,
                sliding_window):
    """``[block_q, block_k]`` token-level mask for a boundary block (causal
    ∧ same segment ∧ not pad ∧ window): one head's, which `_where` shares
    among the ``n_rep`` folded heads' rows."""
    shape = (block_q, block_k)
    q_idx = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_idx = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    # a pad query (segment 0) matches no key: -1 in the COLUMN, not a third
    # compare over the tile
    seg_q = seg_q_ref[0][:, None]
    seg_q = jnp.where(seg_q > 0, seg_q, -1)
    seg_k = seg_k_ref[0][None, :]
    mask = (q_idx >= k_idx) & (seg_q == seg_k)
    if sliding_window is not None:
        mask &= q_idx - k_idx < sliding_window
    return mask


def _where(mask, x, fill, n_rep):
    """``where(mask, x, fill)`` over a rep-folded ``[n_rep*block_q,
    block_k]`` tile: row r*block_q + t is token t of rep r, so the
    ``[block_q, block_k]`` mask is built once and broadcast over the
    leading axis of the tile's ``[n_rep, block_q, block_k]`` view."""
    if n_rep == 1:
        return jnp.where(mask, x, fill)
    return jnp.where(
        mask[None], x.reshape(n_rep, *mask.shape), fill
    ).reshape(x.shape)


def _dispatch_masked(active, specialize, needs_mask, body):
    """Register the masked/interior pl.when branches shared by every kernel:
    ``body(masked)`` runs under ``active``; with ``specialize`` a false
    ``needs_mask`` routes to the mask-free interior body."""
    if specialize:

        @pl.when(active & needs_mask)
        def _boundary():
            body(masked=True)

        @pl.when(active & jnp.logical_not(needs_mask))
        def _interior():
            body(masked=False)

    else:

        @pl.when(active)
        def _body():
            body(masked=True)


def flash_blocks(T, n_rep, *, sliding_window=None, max_seqlen=None,
                 block_q=None, block_k=None, backward=False):
    """``(block_q, block_k, specialize)`` of one call's forward (or fused
    ``backward``) kernel, from what the call can observe: the packed row's
    length ``T``, the q heads folded a kv head, and whether a window or a
    static ``max_seqlen`` bounds its band. ``block_q`` / ``block_k`` are a
    model's overrides (``ModelConfig.flash_block_size`` / ``_k``: both
    directions take them, halved until they divide ``T``). The kernels'
    wrapper and the trainer's count of what the pair list holds
    (`pair_counts`) both ask here.

    What decides (the kernels alone on a v5e, bf16, over the train cell's
    sixteen packed rows of 4,096, at H / Hkv / D 12 / 2 / 128 and, for the
    rule's choices, 12 / 12 / 128, 12 / 4 / 64, 16 / 2 / 128; PERF.md
    section 6, PR 58):

    - ``T >= LONG_ROW``: 1,024 x 1,024 and the interior body: all but a
      long causal row's diagonal pairs skip the mask.
    - a shorter row: its pairs are few and mostly boundary ones (the
      interior body read -1 to +15 % at every block size from 128 to 512:
      never a gain), and what a step costs beside its tile (the
      accumulator's rescale, the running max and sum, the MXU's fill
      between the score and the value dot) weighs more than the
      masked-away part of a wider tile. The FORWARD takes k blocks of
      1,024: fewer, fuller steps beat a tighter cover (at six folded
      heads 0.434 ms a call at fill 0.45 against 0.49 at 512 x 512 and
      fill 0.54, 0.79 at 256 x 256, 1.2 at 128 x 128 and fill 0.82;
      2,048-wide k blocks read 0.61). The fused BACKWARD, five dots a pair
      into accumulators it revisits, takes square blocks (256 x 256: 0.45
      against 0.49 at 512 x 512 and 0.59 at 256 x 1,024). Both take q
      blocks of 256 where the folded group makes that 768 rows or more,
      and of 512 under it (one head a kv head: the backward at 256 x 256
      read 0.93 against 0.57).
    - a row with a window or a static ``max_seqlen``: 512 x 512, masked
      body, as before PR 58 (not measured at any other size).
    """
    long_row = T >= LONG_ROW and T % 1024 == 0
    if block_q is None:
        if long_row:
            block_q = 1024
        elif sliding_window is None and max_seqlen is None:
            block_q = 256 if n_rep >= 3 else 512
            block_k = block_k or (block_q if backward else 1024)
        else:
            block_q = 512
    block_k = block_k or block_q
    # an override that does not divide T would silently truncate the
    # kernel grid: fall back to the largest dividing block
    while T % block_q:
        block_q //= 2
    while T % block_k:
        block_k //= 2
    return block_q, block_k, long_row


def _pair_steps(block_q, block_k, sliding_window, max_seqlen, T):
    """Static length of the pair list: a q block's sweep ends at the causal
    diagonal, and is no wider than the band a static ``max_seqlen`` or a
    sliding window leaves. With neither it is the causal triangle's count,
    which one sequence of ``T`` tokens fills."""
    spans = [s for s in (max_seqlen, sliding_window) if s is not None]
    band = _k_band_blocks(block_q, block_k, min(spans) if spans else None, T)
    return sum(
        min(_last_k(iq, block_q, block_k) + 1, band)
        for iq in range(T // block_q)
    )


def _pair_list(segment_ids, block_q, block_k, sliding_window, max_seqlen, T,
               xp=jnp):
    """The (q block, k block) pairs that hold work, compacted: three int32
    ``[L]`` tables (``L = _pair_steps``) that the forward and the fused
    backward walk as their grid's second axis and their index maps read.

    Pairs come in q-block order, each q block's from ``kstart`` up to the
    causal diagonal or the pad tail. ``flags`` says what a step is: FIRST /
    LAST step of its q block's sweep, ACTIVE (it runs a body) and MASKED
    (the body builds the token mask: `_block_needs_mask`). An all-pad q
    block keeps ONE step with no body, FIRST and LAST, so its zero output
    and NEG_INF lse are still written. Steps past the list's end repeat the
    last pair's indices (no block is copied) and carry no flag. A segment
    longer than ``max_seqlen`` can overflow the list: the caller's
    contract, as it was the band's."""
    nq, nk = T // block_q, T // block_k
    n_steps = _pair_steps(block_q, block_k, sliding_window, max_seqlen, T)
    kstart, _ = _band_bounds(
        segment_ids, block_q, block_k, sliding_window, T, xp
    )
    # ... or at the last k block with a token in it, ahead of the pad tail
    klast = xp.minimum(
        _last_k(xp.arange(nq, dtype=xp.int32), block_q, block_k),
        (xp.sum(segment_ids > 0, dtype=xp.int32) - 1) // block_k,
    )
    count = xp.maximum(klast - kstart + 1, 0)
    steps = xp.maximum(count, 1)
    end = xp.cumsum(steps)
    step = xp.arange(n_steps, dtype=xp.int32)
    live = step < end[-1]
    at = xp.minimum(step, end[-1] - 1)
    iq = _search(xp, end, at, "right")
    j = at - (end - steps)[iq]
    # (an all-pad q block's one step names a block that exists)
    ik = xp.maximum(xp.minimum(kstart[iq] + j, klast[iq]), 0)
    needs = _block_needs_mask(
        segment_ids, block_q, block_k, sliding_window, T, xp
    )
    flags = xp.where(
        live,
        FIRST * (j == 0) + LAST * (j == steps[iq] - 1)
        + ACTIVE * (count[iq] > 0) + MASKED * needs[iq * nk + ik],
        0,
    )
    return iq, ik, flags.astype(xp.int32)


def pair_counts(segment_ids, block_q, block_k, specialize,
                sliding_window=None, max_seqlen=None) -> Dict[str, float]:
    """What the kernels' pair lists hold for packed rows ``[..., T]``,
    counted on the host by the list's own code over numpy (a few dozen
    blocks a row: microseconds): ``flash_pairs`` the pairs that run a body
    (a kv head, summed over the rows), ``flash_interior_pairs`` those of
    them that run the mask-free body, ``flash_fill`` the score elements
    the sequences need (sum len^2 / 2) over the elements the pairs hold."""
    seg = np.asarray(segment_ids)
    T = seg.shape[-1]
    block_q, block_k = min(block_q, T), min(block_k, T)
    pairs = interior = need = 0
    for row in seg.reshape(-1, T):
        _, _, flags = _pair_list(
            row, block_q, block_k, sliding_window, max_seqlen, T, xp=np
        )
        active = flags & ACTIVE != 0
        pairs += int(active.sum())
        if specialize:
            interior += int((active & (flags & MASKED == 0)).sum())
        need += int((np.bincount(row)[1:].astype(np.int64) ** 2).sum())
    return {
        "flash_pairs": pairs,
        "flash_interior_pairs": interior,
        "flash_fill": need / 2 / max(pairs * block_q * block_k, 1),
    }


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #


def _fwd_kernel(
    iq_tab,      # [L] int32 scalar-prefetch: q block of step l (`_pair_list`)
    ik_tab,      # [L] int32: k block of step l
    flag_tab,    # [L] int32: FIRST | LAST | ACTIVE | MASKED
    seg_q_ref,   # [1, block_q] int32
    seg_k_ref,   # [1, block_k] int32
    q_ref,       # [n_rep, block_q, D] — the kv head's whole q group
    k_ref,       # [1, block_k, D]
    v_ref,       # [1, block_k, D]
    o_ref,       # [n_rep, block_q, D]
    lse_ref,     # [n_rep, 1, block_q, 1] f32 (column layout; see _flash_forward)
    m_scr,       # [n_rep*block_q, LANES] f32 (running max, log2 domain)
    l_scr,       # [n_rep*block_q, LANES] f32
    acc_scr,     # [n_rep*block_q, D] f32
    *,
    scale: float,
    block_q: int,
    block_k: int,
    soft_cap: Optional[float],
    sliding_window: Optional[int],
    specialize: bool,
    n_rep: int,
):
    """One forward grid step: grid ``(Hkv, L)``, the second axis walking
    the pair list.

    GQA head folding: the grid's head dim walks KV heads; the q/o blocks
    carry ALL ``n_rep`` grouped q heads stacked ``[n_rep, block_q, D]``
    and fold to ``[n_rep*block_q, D]`` rows for ONE score/PV dot pair per
    step — n_rep x fewer grid steps, n_rep x fewer k/v block fetches, and
    n_rep x taller dots (better MXU occupancy at D=64)."""
    step = pl.program_id(1)
    iq, ik, flags = iq_tab[step], ik_tab[step], flag_tab[step]
    rows = n_rep * block_q

    @pl.when(flags & FIRST != 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _update(masked: bool):
        q2d = q_ref[...].reshape(rows, q_ref.shape[-1])
        s2 = _scores_log2(q2d, k_ref, scale, soft_cap)  # [rows, bk] f32
        if masked:
            mask = _token_mask(
                seg_q_ref, seg_k_ref, iq, ik, block_q, block_k,
                sliding_window,
            )
            s2 = _where(mask, s2, NEG_INF, n_rep)
        m_prev = m_scr[:, 0:1]                     # [rows, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s2, axis=1, keepdims=True))
        p = jnp.exp2(s2 - m_new)                   # [rows, bk]
        if masked:
            # NEG_INF is finite, so exp2(s2 - m_new) is 1 (not 0) on
            # fully-masked rows — zero masked entries explicitly so pad rows
            # keep l == 0 and output 0, matching the XLA path.
            p = _where(mask, p, 0.0, n_rep)
        corr = jnp.exp2(m_prev - m_new)            # [rows, 1]
        l_new = corr * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    _dispatch_masked(
        flags & ACTIVE != 0, specialize, flags & MASKED != 0, _update
    )

    @pl.when(flags & LAST != 0)
    def _done():
        l = l_scr[:, 0:1]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        D = o_ref.shape[-1]
        o_ref[...] = (
            (acc_scr[...] / safe_l).reshape(n_rep, block_q, D)
        ).astype(o_ref.dtype)
        # natural-log logsumexp residual; NEG_INF on fully-masked (pad) rows
        lse = jnp.where(
            l > 0.0, m_scr[:, 0:1] * LN2 + jnp.log(safe_l), NEG_INF
        )                                          # [rows, 1]
        lse_ref[...] = lse.reshape(n_rep, 1, block_q, 1)


def _flash_forward(
    q, k, v, segment_ids, scale, soft_cap, sliding_window, block_q, block_k,
    specialize, max_seqlen,
):
    """q: [H, T, D]; k, v: [Hkv, T, D]; segment_ids: [T]
    -> (out [H, T, D], lse [H, T] f32).

    The kernel-side lse layout is ``[H, nq, block_q, 1]`` — Mosaic requires
    the last two block dims be (÷8, ÷128) or full, and a trailing size-1 lane
    dim keeps per-q-block logsumexp columns addressable per (head, q block)
    without a 128-lane broadcast buffer. It is compacted to ``[H, T]`` in XLA
    right after the call, so the padded layout never persists as a residual."""
    H, T, D = q.shape
    Hkv = k.shape[0]
    n_rep = H // Hkv
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    # rep folding multiplies the q-side tile rows by n_rep: halve block_q
    # only when the folded [n_rep*block_q, block_k] f32 score tiles would
    # overflow the maximum scoped-vmem budget (~114 MB) — big-tile configs
    # like n_rep=8 x flash_block_size=2048 previously compiled unfolded
    while 2 * n_rep * block_q * block_k * 4 > 90 * 2**20 and block_q > 512:
        block_q //= 2
    assert T % block_q == 0 and T % block_k == 0, (T, block_q, block_k)
    seg2d = segment_ids.reshape(1, T)
    iq_tab, ik_tab, flag_tab = _pair_list(
        segment_ids, block_q, block_k, sliding_window, max_seqlen, T
    )
    # GQA head folding: the grid walks KV heads; each step carries the
    # whole q-head group [n_rep, block_q, D]
    scratch_shapes = [
        pltpu.VMEM((n_rep * block_q, LANES), jnp.float32),
        pltpu.VMEM((n_rep * block_q, LANES), jnp.float32),
        pltpu.VMEM((n_rep * block_q, D), jnp.float32),
    ]

    def qmap(h, l, iqt, ikt, ft):
        return (h, iqt[l], 0)

    def kvmap(h, l, iqt, ikt, ft):
        return (h, ikt[l], 0)

    out, lse4 = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
            soft_cap=soft_cap, sliding_window=sliding_window,
            specialize=specialize, n_rep=n_rep,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(Hkv, iq_tab.shape[-1]),
            in_specs=[
                pl.BlockSpec(
                    (1, block_q), lambda h, l, iqt, ikt, ft: (0, iqt[l])
                ),
                pl.BlockSpec(
                    (1, block_k), lambda h, l, iqt, ikt, ft: (0, ikt[l])
                ),
                pl.BlockSpec((n_rep, block_q, D), qmap),
                pl.BlockSpec((1, block_k, D), kvmap),
                pl.BlockSpec((1, block_k, D), kvmap),
            ],
            out_specs=[
                pl.BlockSpec((n_rep, block_q, D), qmap),
                pl.BlockSpec(
                    (n_rep, 1, block_q, 1),
                    lambda h, l, iqt, ikt, ft: (h, iqt[l], 0, 0),
                ),
            ],
            scratch_shapes=scratch_shapes,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((H, T, D), q.dtype),
            jax.ShapeDtypeStruct((H, T // block_q, block_q, 1), jnp.float32),
        ],
        compiler_params=_params(_vmem_limit(
            blocks=[
                ((1, block_q), jnp.int32), ((1, block_k), jnp.int32),
                ((n_rep, block_q, D), q.dtype),            # q
                ((block_k, D), k.dtype), ((block_k, D), v.dtype),
                ((n_rep, block_q, D), q.dtype),            # out
                ((n_rep, block_q, 1), jnp.float32),        # lse column
            ],
            scratch=[(s.shape, s.dtype) for s in scratch_shapes],
            # s2 and p: [n_rep*block_q, block_k] f32 each
            temps=2 * n_rep * block_q * block_k * 4,
        )),
        interpret=_interpret(),
        name="flash_fwd",
    )(iq_tab, ik_tab, flag_tab, seg2d, seg2d, q, k, v)
    return out, lse4.reshape(H, T)


# --------------------------------------------------------------------------- #
# backward
# --------------------------------------------------------------------------- #


def _recompute_p_ds(
    q_ref, k_ref, seg_q_ref, seg_k_ref, lse_ref, delta, do_ref, v_ref,
    iq, ik, *, scale, block_q, block_k, soft_cap, sliding_window,
    masked: bool, n_rep: int = 1,
):
    """Shared block math for both backward kernels: returns (p, ds_raw) with
    ds_raw = dL/d(q·kᵀ) BEFORE the `scale` factor (folded in by callers).
    ``masked=False`` is the interior fast path: no mask construction.
    With ``n_rep > 1`` the q-side refs carry the whole grouped head stack
    ``[n_rep, block_q, ...]`` and everything runs rep-folded ``[rows, bk]``
    (see ``_fwd_step``)."""
    rows = n_rep * block_q
    D = q_ref.shape[-1]
    q2d = q_ref[...].reshape(rows, D)
    if soft_cap is not None:
        s_dot = jax.lax.dot_general(
            q2d, k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        t = jnp.tanh(s_dot * (scale / soft_cap))
        s2 = (soft_cap * LOG2E) * t                # log2 domain
    else:
        s2 = _scores_log2(q2d, k_ref, scale, None)
    # residual lse is natural-log; clamp the log2 conversion so pad rows
    # (lse == NEG_INF) don't overflow to -inf and feed exp2 an inf argument
    lse2 = jnp.maximum(
        lse_ref[...].reshape(rows, 1) * LOG2E, NEG_INF
    )                                              # [rows, 1]
    p = jnp.exp2(s2 - lse2)                        # [rows, bk]
    if masked:
        mask = _token_mask(
            seg_q_ref, seg_k_ref, iq, ik, block_q, block_k, sliding_window
        )
        p = _where(mask, p, 0.0, n_rep)
    dp = jax.lax.dot_general(
        do_ref[...].reshape(rows, D), v_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                              # [rows, bk] f32
    ds = p * (dp - delta)                          # delta [rows, 1]: dL/ds
    if soft_cap is not None:
        ds = ds * (1.0 - t * t)                    # through the tanh cap
    return p, ds


def _bwd_kernel(
    iq_tab,      # [L] int32 scalar-prefetch: q block of step l (`_pair_list`)
    ik_tab,      # [L] int32: k block of step l
    flag_tab,    # [L] int32: FIRST | LAST | ACTIVE | MASKED
    seg_q_ref, seg_k_ref, lse_ref,
    o_ref,      # [n_rep, block_q, D] — the forward's output block
    q_ref, k_ref, v_ref, do_ref,
    dk_ref, dv_ref,
    dq_ref,     # [n_rep, block_q, D] — one q-head group's block
    dk_scr,     # [T, D] f32 — whole-T accumulator, flushed per kv head
    dv_scr,     # [T, D] f32
    dq_scr,     # [n_rep*block_q, D] f32 — one q sweep's accumulator
    delta_scr,  # [n_rep*block_q, LANES] f32 — one q sweep's rowsum(do * out)
    *pipe_scr,  # optional (p, ds, kprev, meta) parking scratch (pipelined)
    scale, block_q, block_k, soft_cap, sliding_window, specialize, n_rep,
):
    """Fused flash backward, Q-STATIONARY + rep-folded: grid ``(Hkv, L)``,
    the second axis walking the forward's pair list (q-block order, each q
    block's k sweep innermost); every step carries the WHOLE q-head group
    ``[n_rep, block_q, ...]`` folded to ``[n_rep*block_q, bk]`` (one dot
    set per group — see `_fwd_kernel`). dq accumulates across a q block's
    sweep (FIRST to LAST) in a ``[rows, D]`` scratch and flushes into its
    (consecutively-revisited) output window at the sweep's end; dk/dv
    accumulate into WHOLE-T ``[T, D]`` f32 scratches (16.8 MB at 32k/D=64
    — independent of n_rep, unlike the previous kv-stationary whole-group
    dq scratch whose rep-folded tiles blew the 128 MB VMEM budget) and
    flush once per kv head, at the grid axis's last step. One (p, ds)
    recompute feeds all three gradients: 5 dots + 1 exp per group-block
    pair.

    With ``pipe_scr`` (cross-block software pipelining, VERDICT r4 #4):
    the three gradient dots consuming (p, ds) are DEFERRED one grid step —
    step j issues step j-1's ``dv += pᵀdo``, ``dk += dsᵀq``, ``dq += ds·k``
    from VMEM scratch between j's score/dp dots and j's exp/mask VPU work,
    so the MXU chews the previous block's gradients while the VPU builds
    the current block's probabilities instead of serializing p→dv, ds→dk/dq
    every step (~7.7 µs/step vs ~4.4 ideal, the round-4 limiter). do/q/
    delta/lse are q-stationary across the inner k sweep, so only the k
    block (for dq) and the dv/dk column offset need carrying in scratch;
    the deferred dots flush at the sweep's LAST step before q/do move on."""
    step = pl.program_id(1)
    iq, ik, flags = iq_tab[step], ik_tab[step], flag_tab[step]
    rows = n_rep * block_q
    D = q_ref.shape[-1]
    pipeline = bool(pipe_scr)
    if pipeline:
        p_scr, ds_scr, kprev_scr, meta_scr = pipe_scr

    @pl.when(flags & FIRST != 0)
    def _init_dq():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        # delta_i = rowsum(do * out), once a q block's sweep and in the
        # column layout the tiles want: as an XLA pass it is written to HBM
        # lane-padded (128 x its size) and read back a pair
        delta = jnp.sum(
            do_ref[...].reshape(rows, D).astype(jnp.float32)
            * o_ref[...].reshape(rows, D).astype(jnp.float32),
            axis=1, keepdims=True,
        )
        delta_scr[...] = jnp.broadcast_to(delta, delta_scr.shape)

    @pl.when(step == 0)
    def _init_kv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)
        if pipeline:
            meta_scr[1] = 0  # no pending block

    def _grad_dots(p, ds, col, kblk):
        # dv += pᵀ @ do ; dk += dsᵀ @ q over the FOLDED rows — summing the
        # group's per-head contributions inside the dot itself
        dv_scr[pl.ds(col, block_k), :] += jax.lax.dot_general(
            p, do_ref[...].reshape(rows, D),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_scr[pl.ds(col, block_k), :] += jax.lax.dot_general(
            ds, q_ref[...].reshape(rows, D),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dq_scr[...] += jax.lax.dot_general(
            ds, kblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def _issue_pending():
        @pl.when(meta_scr[1] == 1)
        def _():
            _grad_dots(
                p_scr[...], ds_scr[...],
                meta_scr[0], kprev_scr[...],
            )
        meta_scr[1] = 0

    def _accum(masked: bool):
        p, ds = _recompute_p_ds(
            q_ref, k_ref, seg_q_ref, seg_k_ref, lse_ref, delta_scr[:, 0:1],
            do_ref, v_ref, iq, ik, scale=scale, block_q=block_q,
            block_k=block_k, soft_cap=soft_cap,
            sliding_window=sliding_window, masked=masked, n_rep=n_rep,
        )
        col = ik * block_k
        if pipeline:
            # park this block's (p, ds, k, col); consumed next step (or in
            # the sweep's LAST step below). bf16 parking matches the dots'
            # operand dtype, so numerics are unchanged.
            p_scr[...] = p.astype(do_ref.dtype)
            ds_scr[...] = ds.astype(q_ref.dtype)
            kprev_scr[...] = k_ref[0]
            meta_scr[0] = col
            meta_scr[1] = 1
        else:
            _grad_dots(
                p.astype(do_ref.dtype), ds.astype(q_ref.dtype), col,
                k_ref[0],
            )

    if pipeline:
        # previous block's gradient dots FIRST: no data dependency on this
        # step's VPU work, so Mosaic can overlap them with _accum's
        # exp/mask while this step's own dots queue behind
        _issue_pending()
    _dispatch_masked(
        flags & ACTIVE != 0, specialize, flags & MASKED != 0, _accum
    )

    @pl.when(flags & LAST != 0)
    def _done_dq():
        if pipeline:
            _issue_pending()  # the sweep's last block, parked just above
        dq_ref[...] = (
            (dq_scr[...] * scale).reshape(n_rep, block_q, D)
        ).astype(dq_ref.dtype)

    @pl.when(step == pl.num_programs(1) - 1)
    def _done_kv():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(
    kstart_ref,
    needs_ref,
    seg_q_ref, seg_k_ref, lse_ref, delta_ref, q_ref, k_ref, v_ref, do_ref,
    dq_ref,
    dq_scr,     # [n_rep*block_q, D] f32
    *,
    scale, block_q, block_k, nk_blocks, soft_cap, sliding_window, specialize,
    n_rep,
):
    # grid (Hkv, nq, k_band): reps folded into the q block (see _fwd_kernel)
    rows = n_rep * block_q
    D = q_ref.shape[-1]
    iq = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    ik = kstart_ref[iq] + j

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _accum(masked: bool):
        _, ds = _recompute_p_ds(
            q_ref, k_ref, seg_q_ref, seg_k_ref, lse_ref,
            delta_ref[...].reshape(rows, 1), do_ref, v_ref, iq, ik,
            scale=scale, block_q=block_q, block_k=block_k, soft_cap=soft_cap,
            sliding_window=sliding_window, masked=masked, n_rep=n_rep,
        )
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    active = ik <= _last_k(iq, block_q, block_k)
    needs = needs_ref[iq * nk_blocks + jnp.minimum(ik, nk_blocks - 1)]
    _dispatch_masked(active, specialize, needs == 1, _accum)

    @pl.when(j == nk - 1)
    def _done():
        dq_ref[...] = (
            (dq_scr[...] * scale).reshape(n_rep, block_q, D)
        ).astype(dq_ref.dtype)


def _dkv_kernel(
    qlast_ref,
    needs_ref,
    seg_q_ref, seg_k_ref, lse_ref, delta_ref, q_ref, k_ref, v_ref, do_ref,
    dk_ref, dv_ref,
    dk_scr,     # [block_k, D] f32
    dv_scr,     # [block_k, D] f32
    *,
    scale, block_q, block_k, nk_blocks, nq_blocks, soft_cap, sliding_window,
    specialize, n_rep,
):
    # grid: (Hkv, nk, nq) — nq innermost, reps folded into the q block;
    # the (hkv, nk) output block stays resident while every q block of the
    # whole head group accumulates.
    rows = n_rep * block_q
    D = q_ref.shape[-1]
    ik = pl.program_id(1)
    jq = pl.program_id(2)
    nq = pl.num_programs(2)
    iq = _first_q(ik, block_q, block_k) + jq

    @pl.when(jq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _accum(masked: bool):
        p, ds = _recompute_p_ds(
            q_ref, k_ref, seg_q_ref, seg_k_ref, lse_ref,
            delta_ref[...].reshape(rows, 1), do_ref, v_ref, iq, ik,
            scale=scale, block_q=block_q, block_k=block_k, soft_cap=soft_cap,
            sliding_window=sliding_window, masked=masked, n_rep=n_rep,
        )
        # dv += pᵀ @ do ; dk += dsᵀ @ q over the folded rows (bf16
        # operands, f32 accumulate) — the group's heads sum inside the dot
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[...].reshape(rows, D),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[...].reshape(rows, D),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    active = iq <= qlast_ref[ik]
    needs = needs_ref[jnp.minimum(iq, nq_blocks - 1) * nk_blocks + ik]
    _dispatch_masked(active, specialize, needs == 1, _accum)

    @pl.when(jq == nq - 1)
    def _done():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_backward(
    q, k, v, segment_ids, out, lse, do,
    scale, soft_cap, sliding_window, block_q, block_k, specialize, max_seqlen,
):
    """All [H|Hkv, T, D]-layout. Returns (dq, dk, dv)."""
    H, T, D = q.shape
    Hkv = k.shape[0]
    n_rep = H // Hkv
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    # rep folding multiplies the q-side tile rows by n_rep: cap the folded
    # [n_rep*block_q, block_k] f32 score/ds tiles so the fused kernel's
    # VMEM (tiles + whole-T dk/dv scratch) stays inside the 128 MB budget
    # (block_k-aware like the forward's cap: n_rep=8 x 2048 blocks would
    # otherwise request ~190 MB)
    while n_rep * block_q > 2048 and block_q > 512:
        block_q //= 2
    while 2 * n_rep * block_q * block_k * 4 > 64 * 2**20 and block_k > 512:
        block_k //= 2
    seg2d = segment_ids.reshape(1, T)
    # kernel-side column layout (see _flash_forward docstring)
    nq = T // block_q
    lse4 = lse.reshape(H, nq, block_q, 1)

    common = dict(
        scale=scale, block_q=block_q, block_k=block_k, soft_cap=soft_cap,
        sliding_window=sliding_window, specialize=specialize, n_rep=n_rep,
    )

    # Fused q-stationary backward: dq flushes per q sweep into its
    # (consecutively-revisited) output window; dk/dv accumulate in WHOLE-T
    # [T, D] f32 scratches (n_rep-independent) flushed once per kv head.
    # Fall back to separate dq/dkv sweeps only when the whole-T scratch
    # itself won't fit VMEM (extreme context lengths).
    dkv_scr_bytes = 2 * T * D * 4
    if dkv_scr_bytes <= FUSED_BWD_MAX_DQ_BYTES:
        rows = n_rep * block_q
        iq_tab, ik_tab, flag_tab = _pair_list(
            segment_ids, block_q, block_k, sliding_window, max_seqlen, T
        )
        scratch_shapes = [
            pltpu.VMEM((T, D), jnp.float32),
            pltpu.VMEM((T, D), jnp.float32),
            pltpu.VMEM((rows, D), jnp.float32),
            pltpu.VMEM((rows, LANES), jnp.float32),    # delta column
        ]
        if _bwd_pipeline():
            scratch_shapes += [
                pltpu.VMEM((rows, block_k), do.dtype),   # parked p
                pltpu.VMEM((rows, block_k), q.dtype),    # parked ds
                pltpu.VMEM((block_k, D), k.dtype),       # parked k block
                pltpu.SMEM((2,), jnp.int32),             # [col, valid]
            ]
        limit = _vmem_limit(
            blocks=[
                ((1, block_q), jnp.int32), ((1, block_k), jnp.int32),
                ((n_rep, block_q, 1), jnp.float32),    # lse column
                ((n_rep, block_q, D), out.dtype),      # out
                ((n_rep, block_q, D), q.dtype),        # q
                ((block_k, D), k.dtype), ((block_k, D), v.dtype),
                ((n_rep, block_q, D), do.dtype),       # do
                ((T, D), k.dtype), ((T, D), v.dtype),  # whole-T dk, dv
                ((n_rep, block_q, D), q.dtype),        # dq
            ],
            scratch=[
                (s.shape, s.dtype) for s in scratch_shapes
                if s.memory_space == pltpu.MemorySpace.VMEM
            ],
            # s2, p, ds + slack: [n_rep*block_q, block_k] f32 each
            temps=4 * rows * block_k * 4,
        )
        kv_whole = pl.BlockSpec((1, T, D), lambda h, *_: (h, 0, 0))
        kv_blk = pl.BlockSpec(
            (1, block_k, D), lambda h, l, iqt, ikt, ft: (h, ikt[l], 0)
        )
        q_blk = pl.BlockSpec(
            (n_rep, block_q, D), lambda h, l, iqt, ikt, ft: (h, iqt[l], 0)
        )
        q_col = pl.BlockSpec(
            (n_rep, 1, block_q, 1),
            lambda h, l, iqt, ikt, ft: (h, iqt[l], 0, 0),
        )
        dk, dv, dq = pl.pallas_call(
            functools.partial(_bwd_kernel, **common),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(Hkv, iq_tab.shape[-1]),
                in_specs=[
                    pl.BlockSpec(
                        (1, block_q), lambda h, l, iqt, ikt, ft: (0, iqt[l])
                    ),
                    pl.BlockSpec(
                        (1, block_k), lambda h, l, iqt, ikt, ft: (0, ikt[l])
                    ),
                    q_col, q_blk, q_blk, kv_blk, kv_blk, q_blk,
                ],
                out_specs=[kv_whole, kv_whole, q_blk],
                scratch_shapes=scratch_shapes,
            ),
            out_shape=[
                jax.ShapeDtypeStruct((Hkv, T, D), k.dtype),
                jax.ShapeDtypeStruct((Hkv, T, D), v.dtype),
                jax.ShapeDtypeStruct((H, T, D), q.dtype),
            ],
            compiler_params=_params(
                limit, dimension_semantics=("parallel", "arbitrary"),
            ),
            interpret=_interpret(),
            name="flash_bwd_fused",
        )(iq_tab, ik_tab, flag_tab, seg2d, seg2d, lse4, out, q, k, v, do)
        return dq, dk, dv

    # split sweeps (whole-T dk/dv scratch does not fit): band-relative
    # grids over ``kstart`` / ``qlast``, clamped index maps, both kernels
    # streaming the same q-side blocks and holding the same p/ds tiles;
    # delta_i = rowsum(do * out) comes from XLA (the k-stationary sweep
    # meets a q block once a k block)
    delta4 = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ).reshape(H, nq, block_q, 1)
    kstart, qlast = _band_bounds(
        segment_ids, block_q, block_k, sliding_window, T
    )
    needs = _block_needs_mask(segment_ids, block_q, block_k, sliding_window, T)
    common["nk_blocks"] = T // block_k

    def dq_kj(h, i, j, ks, nm):
        return (
            h,
            jnp.minimum(ks[i] + j, _last_k(i, block_q, block_k)),
            0,
        )

    q_side_blocks = [
        ((1, block_q), jnp.int32), ((1, block_k), jnp.int32),
        ((n_rep, block_q, 1), jnp.float32),    # lse column
        ((n_rep, block_q, 1), jnp.float32),    # delta column
        ((n_rep, block_q, D), q.dtype),        # q
        ((n_rep, block_q, D), do.dtype),       # do
    ]
    split_temps = 4 * n_rep * block_q * block_k * 4

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(
                Hkv, T // block_q,
                _k_band_blocks(block_q, block_k, max_seqlen, T),
            ),
            in_specs=[
                pl.BlockSpec((1, block_q), lambda h, i, j, ks, nm: (0, i)),
                pl.BlockSpec(
                    (1, block_k),
                    lambda h, i, j, ks, nm: (
                        0,
                        jnp.minimum(ks[i] + j, _last_k(i, block_q, block_k)),
                    ),
                ),
                pl.BlockSpec(
                    (n_rep, 1, block_q, 1), lambda h, i, j, ks, nm: (h, i, 0, 0)
                ),
                pl.BlockSpec(
                    (n_rep, 1, block_q, 1), lambda h, i, j, ks, nm: (h, i, 0, 0)
                ),
                pl.BlockSpec(
                    (n_rep, block_q, D), lambda h, i, j, ks, nm: (h, i, 0)
                ),
                pl.BlockSpec((1, block_k, D), dq_kj),
                pl.BlockSpec((1, block_k, D), dq_kj),
                pl.BlockSpec(
                    (n_rep, block_q, D), lambda h, i, j, ks, nm: (h, i, 0)
                ),
            ],
            out_specs=pl.BlockSpec(
                (n_rep, block_q, D), lambda h, i, j, ks, nm: (h, i, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((n_rep * block_q, D), jnp.float32)
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((H, T, D), q.dtype),
        compiler_params=_params(_vmem_limit(
            blocks=q_side_blocks + [
                ((block_k, D), k.dtype), ((block_k, D), v.dtype),
                ((n_rep, block_q, D), q.dtype),        # dq
            ],
            scratch=[((n_rep * block_q, D), jnp.float32)],
            temps=split_temps,
        )),
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(kstart, needs, seg2d, seg2d, lse4, delta4, q, k, v, do)

    def dkv_qi(ql, j, i):
        # clip: qlast can be -1 (all-pad k block); the step is inactive then
        return jnp.clip(
            _first_q(j, block_q, block_k) + i, 0, (T // block_q) - 1
        )

    def qi3(h, j, i, ql, nm):
        return (h, dkv_qi(ql, j, i), 0)

    def qi4(h, j, i, ql, nm):
        return (h, dkv_qi(ql, j, i), 0, 0)

    kv_spec = pl.BlockSpec((1, block_k, D), lambda h, j, i, ql, nm: (h, j, 0))
    group_in_specs = [
        pl.BlockSpec(
            (1, block_q),
            lambda h, j, i, ql, nm: (0, dkv_qi(ql, j, i)),
        ),
        pl.BlockSpec((1, block_k), lambda h, j, i, ql, nm: (0, j)),
        pl.BlockSpec((n_rep, 1, block_q, 1), qi4),
        pl.BlockSpec((n_rep, 1, block_q, 1), qi4),
        pl.BlockSpec((n_rep, block_q, D), qi3),
        kv_spec,
        kv_spec,
        pl.BlockSpec((n_rep, block_q, D), qi3),
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common, nq_blocks=T // block_q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(
                Hkv, T // block_k,
                _q_band_blocks(block_q, block_k, max_seqlen, T),
            ),
            in_specs=group_in_specs,
            out_specs=[kv_spec, kv_spec],
            scratch_shapes=[
                pltpu.VMEM((block_k, D), jnp.float32),
                pltpu.VMEM((block_k, D), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Hkv, T, D), k.dtype),
            jax.ShapeDtypeStruct((Hkv, T, D), v.dtype),
        ],
        compiler_params=_params(_vmem_limit(
            blocks=q_side_blocks + [
                ((block_k, D), k.dtype), ((block_k, D), v.dtype),
                ((block_k, D), k.dtype), ((block_k, D), v.dtype),  # dk, dv
            ],
            scratch=[((block_k, D), jnp.float32)] * 2,
            temps=split_temps,
        )),
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(qlast, needs, seg2d, seg2d, lse4, delta4, q, k, v, do)
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# custom-vjp entry ([T, H, D] public layout)
# --------------------------------------------------------------------------- #


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_thd(q, k, v, segment_ids, scale, soft_cap, sliding_window,
               fwd_blocks, bwd_blocks, max_seqlen):
    """[T, H, D]-layout entry with custom vjp; ``fwd_blocks`` /
    ``bwd_blocks`` are `flash_blocks`' ``(block_q, block_k, specialize)``
    for the two directions."""
    return _flash_fwd_rule(
        q, k, v, segment_ids, scale, soft_cap, sliding_window, fwd_blocks,
        bwd_blocks, max_seqlen,
    )[0]


def _flash_fwd_rule(q, k, v, segment_ids, scale, soft_cap, sliding_window,
                    fwd_blocks, bwd_blocks, max_seqlen):
    out, lse = _flash_forward(
        q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1), segment_ids,
        scale, soft_cap, sliding_window, *fwd_blocks, max_seqlen,
    )
    return out.swapaxes(0, 1), (q, k, v, segment_ids, out, lse)


def _flash_bwd_rule(scale, soft_cap, sliding_window, fwd_blocks, bwd_blocks,
                    max_seqlen, res, g):
    q, k, v, segment_ids, out_htd, lse = res
    dq, dk, dv = _flash_backward(
        q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1), segment_ids,
        out_htd, lse, g.swapaxes(0, 1),
        scale, soft_cap, sliding_window, *bwd_blocks, max_seqlen,
    )
    return dq.swapaxes(0, 1), dk.swapaxes(0, 1), dv.swapaxes(0, 1), None


_flash_thd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def packed_flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    segment_ids: jnp.ndarray,
    *,
    softmax_scale: float,
    soft_cap: Optional[float] = None,
    sliding_window: Optional[int] = None,
    block_size: Optional[int] = None,
    block_size_k: Optional[int] = None,
    max_seqlen: Optional[int] = None,
) -> jnp.ndarray:
    """Causal packed-varlen flash attention. q ``[T, H, D]``, k/v
    ``[T, Hkv, D]``, segment_ids ``[T]`` (0 = pad) -> ``[T, H, D]``.

    ``block_size`` / ``block_size_k``: None = `flash_blocks`' choice from
    the call's shapes, forward and backward each its own; a value
    overrides both.

    ``max_seqlen``: STATIC upper bound on any single segment's length. When
    set, the pair list's static length is the band's (`_pair_steps`) and
    not the causal triangle's — at short-segment packing most of the
    triangle's steps would be empty ones that still cost a fraction of a
    µs each. Segments longer than the bound overflow the list and get
    silently truncated attention: callers must validate (the train engine
    does; any other caller gets a device-side check under
    ``AREAL_DEBUG_CHECKS=1``). The flag is read at TRACE time — set it
    before the first jit of a calling step; flipping it later does not
    retrace cached programs.
    """
    from areal_tpu.base import constants

    if max_seqlen is not None and constants.debug_checks_enabled():
        T = segment_ids.shape[0]
        seg_max = jnp.max(
            jnp.bincount(
                jnp.where(segment_ids > 0, segment_ids, 0), length=T + 1
            )[1:]
        )

        def _check(observed, bound=max_seqlen):
            if int(observed) > bound:
                raise ValueError(
                    f"packed_flash_attention: a segment has {int(observed)} "
                    f"tokens but max_seqlen={bound}; attention beyond the "
                    "band would be silently truncated"
                )

        jax.debug.callback(_check, seg_max)
    fwd_blocks, bwd_blocks = (
        flash_blocks(
            q.shape[0], q.shape[1] // k.shape[1],
            sliding_window=sliding_window, max_seqlen=max_seqlen,
            block_q=block_size, block_k=block_size_k, backward=backward,
        )
        for backward in (False, True)
    )
    return _flash_thd(
        q, k, v, segment_ids.astype(jnp.int32), softmax_scale, soft_cap,
        sliding_window, fwd_blocks, bwd_blocks, max_seqlen,
    )
