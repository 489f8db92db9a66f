"""Paged decode attention (Pallas TPU kernel).

One new token per slot attends to its KV pages IN PLACE — the page table
rides in as a scalar-prefetch operand and the kernel issues its own async
DMAs from the pool (which stays in HBM/ANY memory, full
``[L, P, 2, Hkv, page, D]`` — K and V interleaved head-major, so each
page is ONE DMA landing directly in the ``[Hkv, S, D]`` compute layout;
no flat reshape, no per-layer slice, no in-VMEM transpose). TPU
counterpart of vLLM/SGLang's paged-attention CUDA kernels, which the
reference inherits (SURVEY §2.1).

Grid ``(B/SB, ceil(M/KP))``: SB slots x KP pages (``S = KP * page``
positions) per step, ``(SB, KP)`` from :func:`block_plan`. What a call
costs has four parts (measured alone on a TPU v5e at page 128, bf16, at
128 slots x 12q/2kv x 128 with a table of 40 pages unless said; PERF.md,
PRs 25 and 27):

- a grid step costs 0.3-0.45 us whatever it holds (the pipeline's block
  copies and ONE test of the block's longest row against the step's first
  position): 80 steps 0.026 ms, 256 steps (64 slots, 16 kv heads, SB 1)
  0.11 ms, with NO token resident. A step that row does not reach has no
  page to fetch and nothing the body would read, and does nothing else:
  no copy started, no page zeroed, no wait, no body.
  :func:`kernel_steps` counts the steps that are reached (a third of them
  on heavy-tailed rollout traffic, once rows are sorted);
- a step that IS reached walks its ``SB * KP`` table entries on the scalar
  core (a start and a zero branch for the next step, a wait branch for
  this one), page or no page: about 36 ns an entry (all ``B * M`` of them
  would be 0.19 ms a call). Bounding the walk by each row's own page
  count (dynamic loops in place of the unrolled branches) measured
  0.1-0.2 % of a call and is not taken;
- page DMAs are issued per slot, only for pages the slot holds, so the
  bytes read from HBM are the resident KV and no more; they stream at
  about 950 GB/s and overlap the dots;
- the body (QK dot, softmax, PV dot, batched over ``[SB*Hkv, S, D]``)
  runs for the WHOLE block of SB rows at every page block up to
  ``ceil(max_len / S)`` of its LONGEST row, and ``_zero`` stores a page of
  zeros (``2*Hkv*page*D`` elements) for every page a shorter row does not
  hold up to that same maximum (masked probabilities are 0, but ``0 * NaN
  = NaN`` in the PV dot). :func:`kernel_positions` counts that:
  ``SB * S * ceil(max_len / S)`` summed over blocks. Rows of mixed length
  in one block are work over positions that hold no KV (2.4 x the
  resident KV with rows in random order, 1.5 x sorted).

THE CALLER ORDERS ROWS BY LENGTH (``decode_step_paged`` sorts the batch
once per step, before its layer scan), so a block's rows are of
neighbouring length and its maximum is close to every member's; empty
and short rows gather in the first blocks, whose later steps are skipped
outright. Rows of a block are independent in the batched dots and a page
block that is all masked for a row leaves its ``m``/``l``/``acc`` as they
were, so a row's result does not depend on which rows share its block.
Every slot's page DMAs for a step start together. GQA runs without
materializing the K/V head repeat: scores are batched ``dot_general``
over the kv-head axis.

The CURRENT token's K/V ride as separate operands and fold into the
online softmax at the last grid step (the pool is read-only during the
caller's layer scan; the model scatters all layers' new KV afterwards).

A WINDOW layer (``sliding_window``) is another program of the same kernel,
``paged_decode_window``: each row also has a FIRST visible position
(``max(len + 1 - window, 0)``, a fourth scalar-prefetch operand). Pages
wholly before it are neither copied nor walked (their table entries may
be stale: the engine gives such pages back to the free list while the
request runs, ``gen/engine.py``), the page the edge falls in is masked
inside, and a grid step that lies wholly before the first position of
every row of its block costs the one test, as a step past the longest row
does (``_steps_reached`` counts both ends). The full-attention program
has none of this and is what it was.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38
LANES = 128


def _interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


def page_multiple(pool_dtype) -> int:
    """What the page size must be a multiple of for the compiled kernel —
    the ONE rule both this module's check and the auto-dispatch gate
    (``ops/paged_attention.py``) apply. A bf16/f32 page is a sublane dim
    (tile of 8). An int8 pool also DMAs a ``[..., Hkv, page]`` f32 scale
    stripe whose LAST dim is the page, and Mosaic wants a slice along the
    lane dim aligned to 128 ("Slice shape along dimension 4 must be
    aligned to tiling (128)") — so int8 pools with smaller pages take the
    XLA gather path."""
    return LANES if jnp.dtype(pool_dtype) == jnp.int8 else 8


def _scratch_bytes(sb, kp, page, n_kv, head_dim, pool_dtype, streams=2) -> int:
    """Double-buffered KV page scratch of one grid step, plus an int8
    pool's f32 scale stripes. ``streams``: 2 for a K and a V half, 1 for
    a latent pool whose one stream is both."""
    dt = jnp.dtype(pool_dtype)
    b = 2 * streams * sb * kp * page * n_kv * head_dim * dt.itemsize
    if dt == jnp.int8:
        b += 2 * streams * sb * kp * page * n_kv * 4
    return b


def block_plan(
    batch: int,
    n_kv_heads: int,
    head_dim: int,
    page: int,
    table_width: int,
    pool_dtype,
    pages_per_step: int = 8,
    slots_per_step: int = 8,
    streams: int = 2,
) -> Tuple[int, int]:
    """``(sb, kp)``: the slots and pages of one grid step that
    :func:`decode` runs a batch with. ``kp`` is the table width capped at
    ``pages_per_step``; ``sb`` is ``slots_per_step`` halved until it
    divides the batch and the KV scratch is NOT OVER 16 MiB (a scratch of
    exactly 16 MiB stays: 12q/2kv x 128 at page 128 runs 8 slots a step,
    28q/4kv x 128 runs 4; one latent stream of 576 runs 4). Pure, so the engine counts
    :func:`kernel_positions` with the plan the kernel uses."""
    kp = min(pages_per_step, table_width)
    sb = slots_per_step
    while batch % sb:
        sb //= 2
    while sb > 1 and _scratch_bytes(
        sb, kp, page, n_kv_heads, head_dim, pool_dtype, streams
    ) > 16 * 1024 * 1024:
        sb //= 2
    return sb, kp


def first_visible(lens, sliding_window: Optional[int]):
    """First pool position the query at position ``lens`` sees (numpy or
    jax integers): it sees itself and ``sliding_window - 1`` before it."""
    if sliding_window is None:
        return lens * 0
    return (lens + 1 - sliding_window).clip(0)


def _steps_reached(lens, sb: int, span: int, first=None) -> int:
    """Grid steps whose block of ``sb`` consecutive rows of ``lens`` reaches
    the step's first position: ``ceil(longest / span)`` a block; with the
    rows' ``first`` visible positions (a window layer), less the steps that
    end before the block's least one."""
    longest = np.asarray(lens, np.int64).reshape(-1, sb).max(axis=1)
    steps = -(-longest // span)
    if first is not None:
        least = np.asarray(first, np.int64).reshape(-1, sb).min(axis=1)
        steps = np.maximum(steps - least // span, 0)
    return int(steps.sum())


def kernel_positions(lens, sb: int, span: int, first=None) -> int:
    """KV positions the kernel's body runs over for rows of resident
    lengths ``lens`` IN THE ORDER THE KERNEL GETS THEM (host integers;
    ``decode_step_paged`` hands them sorted, so its callers pass
    ``np.sort(lens)``): every block of ``sb`` consecutive rows computes
    ``sb * span`` positions for each of the ``ceil(max_len / span)`` page
    blocks its longest row reaches (``span = kp * page``). Over the sum of
    ``lens`` it is how many times the resident KV the kernel computes."""
    return sb * span * _steps_reached(lens, sb, span, first)


def kernel_steps(
    lens, sb: int, span: int, nblk: int, first=None
) -> Tuple[int, int]:
    """``(active, total)`` grid steps of a call over rows ``lens`` (in the
    kernel's order, as for :func:`kernel_positions`) with ``nblk`` page
    blocks a row: a block of ``sb`` rows is active in the steps its longest
    row reaches. Only those walk their table entries, wait and run the
    body; the others cost one test each."""
    return _steps_reached(lens, sb, span, first), len(lens) // sb * nblk


def _decode_kernel(
    *refs,
    scale: float,
    page: int,
    kp: int,
    sb: int,
    n_kv: int,
    n_rep: int,
    soft_cap: Optional[float],
    windowed: bool,
    quantized: bool,
    dv: Optional[int] = None,
):
    # ``dv`` set: a LATENT pool ``[L, P, 1, 1, page, D]`` (absorbed MLA,
    # ``models/transformer.py``). Its one stream is key and value at once
    # for every query head: ``n_kv`` is 1, the value of a position is the
    # first ``dv`` of its ``D`` key values, there is no ``vs_ref`` (the
    # current token's value is the head of its key) and the output is
    # ``[SB, Hq, dv]``. Grid, page walk, copies and softmax are the same.
    # Ref order (inputs, outputs, scratch); the int8 pool adds a scales
    # input + a scales scratch/semaphore pair right after their KV twins:
    #   layer_ref  [1] int32 scalar-prefetch: which layer of the pool
    #   table_ref  [B, M] int32 scalar-prefetch
    #   lens_ref   [B] int32 scalar-prefetch (pool-resident, EXCL. self)
    #   first_ref  [B] int32 scalar-prefetch: first visible position
    #              (``windowed`` only: a window layer's program)
    #   q_ref      [SB, Hq, D]
    #   ks_ref     [SB, Hkv, D] the current tokens' K (not in the pool)
    #   vs_ref     [SB, Hkv, D]
    #   kv_hbm     [L, P, 2, Hkv, page, D] whole pool, ANY/HBM
    #   sc_hbm     [L, P, 2, Hkv, page] f32 scales, ANY/HBM   (quantized)
    #   o_ref      [SB, Hq, D]
    #   kv_scr     [2, SB, 2, Hkv, KP*page, D] DOUBLE-buffered page scratch
    #              — pages DMA straight into the compute layout while the
    #              previous grid step's buffer is being consumed
    #   sc_scr     [2, SB, 2, Hkv, KP*page] f32 scale scratch (quantized)
    #   m_scr      [SB, HqP, LANES] f32
    #   l_scr      [SB, HqP, LANES] f32
    #   acc_scr    [SB, HqP, Dp] f32
    #   sems       DMA semaphores [2, SB, KP]
    #   sc_sems    DMA semaphores [2, SB, KP]                 (quantized)
    first_ref = None
    if windowed:
        first_ref, refs = refs[3], refs[:3] + refs[4:]
    if quantized:
        (layer_ref, table_ref, lens_ref, q_ref, ks_ref, vs_ref, kv_hbm,
         sc_hbm, o_ref, kv_scr, sc_scr, m_scr, l_scr, acc_scr, sems,
         sc_sems) = refs
    elif dv is not None:
        (layer_ref, table_ref, lens_ref, q_ref, ks_ref, kv_hbm,
         o_ref, kv_scr, m_scr, l_scr, acc_scr, sems) = refs
        vs_ref = sc_hbm = sc_scr = sc_sems = None
    else:
        (layer_ref, table_ref, lens_ref, q_ref, ks_ref, vs_ref, kv_hbm,
         o_ref, kv_scr, m_scr, l_scr, acc_scr, sems) = refs
        sc_hbm = sc_scr = sc_sems = None
    latent = dv is not None
    n_str = 1 if latent else 2
    bb = pl.program_id(0)
    j = pl.program_id(1)
    nblk = pl.num_programs(1)
    total = pl.num_programs(0) * nblk
    g = bb * nblk + j         # linearized grid step
    Hq = q_ref.shape[1]
    D = q_ref.shape[2]
    Dv = dv if latent else D  # width of a value
    S = kp * page             # positions of one grid step
    layer = layer_ref[0]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _reaches(bb_t, j_t):
        """Whether step ``j_t`` holds a visible position of any row of block
        ``bb_t``: the predicate copies are started AND waited for under."""
        rows = [bb_t * sb + s for s in range(sb)]
        longest = functools.reduce(
            jnp.maximum, [lens_ref[r] for r in rows])
        reached = j_t * S < longest
        if windowed:
            least = functools.reduce(
                jnp.minimum, [first_ref[r] for r in rows])
            reached &= (j_t + 1) * S > least
        return reached

    def _page_tests(slot):
        """``tests(pg) -> (held, not held)`` for the pages of the slot's
        table: held where the page has a visible position (a page wholly
        behind a window is not the slot's any more). The scalars a slot's
        ``kp`` tests share are read once."""
        n_used = pl.cdiv(lens_ref[slot], page)
        if not windowed:
            return lambda pg: (pg < n_used, pg >= n_used)
        p0 = first_ref[slot] // page

        def tests(pg):
            held = (pg < n_used) & (pg >= p0)
            return held, jnp.logical_not(held)

        return tests

    def _issue(g_t, buf):
        """Start every resident-page DMA (and zero un-DMA'd tail blocks the
        body will read) for linear grid step ``g_t`` into buffer ``buf``."""
        bb_t = g_t // nblk
        j_t = g_t % nblk
        # the batched body reads EVERY slot's stripe whenever any slot of
        # the block is active, so un-DMA'd pages of shorter slots must be
        # zeroed up to the block the longest slot reaches (masked
        # probabilities are 0, but 0 * NaN = NaN in the PV dot)
        # a step its block's longest row does not reach (or, in a window
        # layer, that ends before every row's first visible position) has
        # no page to fetch and nothing the body will read: ONE test skips
        # its SB * KP entries (most steps, once the caller has sorted rows
        # by length)
        @pl.when(_reaches(bb_t, j_t))
        def _reached():
            for s in range(sb):
                slot = bb_t * sb + s
                tests = _page_tests(slot)
                for i in range(kp):
                    held, free = tests(j_t * kp + i)

                    @pl.when(held)
                    def _start(s=s, i=i, slot=slot):
                        pidx = table_ref[slot, j_t * kp + i]
                        # K and V are interleaved per page: ONE DMA per
                        # page, landing in the [2, Hkv, i*page:(i+1)*page,
                        # D] stripe of the compute-layout scratch
                        pltpu.make_async_copy(
                            kv_hbm.at[layer, pidx],
                            kv_scr.at[buf, s, :, :, pl.ds(i * page, page), :],
                            sems.at[buf, s, i],
                        ).start()
                        if quantized:
                            # the page's scale stripe rides a second (tiny
                            # — 1/D of the page bytes) DMA into the
                            # parallel scale scratch; dequant happens
                            # in-register at the dots, never as a widened
                            # pool copy
                            pltpu.make_async_copy(
                                sc_hbm.at[layer, pidx],
                                sc_scr.at[buf, s, :, :, pl.ds(i * page, page)],
                                sc_sems.at[buf, s, i],
                            ).start()

                    @pl.when(free)
                    def _zero(s=s, i=i):
                        kv_scr[buf, s, :, :, pl.ds(i * page, page), :] = (
                            jnp.zeros((n_str, n_kv, page, D), kv_scr.dtype)
                        )
                        if quantized:
                            sc_scr[buf, s, :, :, pl.ds(i * page, page)] = (
                                jnp.zeros((2, n_kv, page), sc_scr.dtype)
                            )

    # Software pipeline over the (sequential) linearized grid: step g's
    # pages were prefetched at step g-1; here we kick off g+1's DMAs BEFORE
    # consuming g's, so the HBM reads for the next block overlap this
    # block's dots. Un-overlapped DMA cost drops from every grid step to
    # one per kernel call (measured r4: the serial issue->wait->compute
    # loop held the kernel at ~0.42 of HBM bandwidth).
    buf = jax.lax.rem(g, 2)

    @pl.when(g == 0)
    def _prologue():
        _issue(0, 0)

    @pl.when(g + 1 < total)
    def _prefetch():
        _issue(g + 1, jax.lax.rem(g + 1, 2))

    # the predicate _issue started this step's copies under, over the same
    # scalars: every started copy is waited for, and a step that started
    # none tests nothing
    reached = _reaches(bb, j)

    @pl.when(reached)
    def _arrived():
        for s in range(sb):
            slot = bb * sb + s
            tests = _page_tests(slot)
            for i in range(kp):
                @pl.when(tests(j * kp + i)[0])
                def _wait(s=s, i=i, slot=slot):
                    pidx = table_ref[slot, j * kp + i]
                    pltpu.make_async_copy(
                        kv_hbm.at[layer, pidx],
                        kv_scr.at[buf, s, :, :, pl.ds(i * page, page), :],
                        sems.at[buf, s, i],
                    ).wait()
                    if quantized:
                        pltpu.make_async_copy(
                            sc_hbm.at[layer, pidx],
                            sc_scr.at[buf, s, :, :, pl.ds(i * page, page)],
                            sc_sems.at[buf, s, i],
                        ).wait()

    # per-slot resident lengths as an [SB, 1, S] operand built from stacked
    # scalar SPLATS (Mosaic rejects 1D->3D vector reshapes); the whole
    # block body is BATCHED over slots — one slot-folded-batch dot pair
    # instead of SB sequential small-dot bodies, which left the MXU idle
    # between per-slot dots and made the (now DMA-overlapped) kernel
    # compute-bound
    def _splat(ref):
        return jnp.stack(
            [jnp.full((1, S), ref[bb * sb + s], jnp.int32)
             for s in range(sb)]
        )                                                      # [SB, 1, S]

    lens_v = _splat(lens_ref)
    first_v = _splat(first_ref) if windowed else None

    @pl.when(reached)
    def _body():
        # (SB, Hkv) folds into ONE batch dim (Mosaic's tpu.matmul supports
        # a single batch dim); the reshape is layout-free
        q = q_ref[...].reshape(sb * n_kv, n_rep, D)
        k = kv_scr[buf, :, 0].reshape(sb * n_kv, S, D)
        if latent:
            v = kv_scr[buf, :, 0, :, :, :Dv].reshape(sb * n_kv, S, Dv)
        else:
            v = kv_scr[buf, :, 1].reshape(sb * n_kv, S, D)
        if quantized:
            # in-register widening: int8 in [-127, 127] is exact in bf16
            # (8 mantissa bits cover 256), so casting to q's dtype loses
            # nothing, and the per-(head, position) K scale folds into the
            # SCORES after the dot — it is constant over D, so
            # q·(k_int*s) == (q·k_int)*s with one [*, S] multiply instead
            # of rescaling the whole [*, S, D] block
            k = k.astype(q.dtype)
        sc = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                             # [SB*Hkv,r,S]
        if quantized:
            k_sc = sc_scr[buf, :, 0].reshape(sb * n_kv, S)
            sc = sc * k_sc[:, None, :]
        if soft_cap is not None:
            sc = soft_cap * jnp.tanh(sc / soft_cap)
        sc = sc.reshape(sb, Hq, S)
        kpos = j * S + jax.lax.broadcasted_iota(jnp.int32, (sb, Hq, S), 2)
        mask = kpos < lens_v
        if windowed:
            # the edge page of a window: positions before the first
            # visible one are resident in it and not the query's to see
            mask &= kpos >= first_v
        sc = jnp.where(mask, sc, NEG_INF)

        m_prev = m_scr[:, :Hq, 0:1]                           # [SB,Hq,1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=2, keepdims=True))
        p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)         # [SB,Hq,S]
        corr = jnp.exp(
            jnp.where(m_prev > NEG_INF / 2, m_prev - m_new, 0.0)
        )
        l_new = corr * l_scr[:, :Hq, 0:1] + jnp.sum(
            p, axis=2, keepdims=True
        )
        pq = p.reshape(sb * n_kv, n_rep, S)
        if quantized:
            # the V scale folds into the probabilities (constant over D):
            # Σ_s p[s]·(v_int[s]·vs[s]) == Σ_s (p[s]·vs[s])·v_int[s]
            v_sc = sc_scr[buf, :, 1].reshape(sb * n_kv, S)
            pq = (pq * v_sc[:, None, :]).astype(jnp.float32)
            v = v.astype(jnp.float32)
        else:
            pq = pq.astype(v.dtype)
        pv = jax.lax.dot_general(
            pq, v,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ).reshape(sb, Hq, Dv)
        acc_scr[:, :Hq, :Dv] = acc_scr[:, :Hq, :Dv] * corr + pv
        m_scr[:, :Hq] = jnp.broadcast_to(m_new, (sb, Hq, LANES))
        l_scr[:, :Hq] = jnp.broadcast_to(l_new, (sb, Hq, LANES))

    @pl.when(j == nblk - 1)
    def _done():
        # fold the current tokens' self-attention (always attended; their
        # KV is scattered into the pool by the caller AFTER the layer scan)
        q = q_ref[...].reshape(sb, n_kv, n_rep, D)
        ks = ks_ref[...]                                      # [SB,Hkv,D]
        vs = ks[:, :, :Dv] if latent else vs_ref[...]
        s_self = jnp.sum(
            q.astype(jnp.float32) * ks[:, :, None].astype(jnp.float32),
            axis=3,
        ) * scale                                             # [SB,Hkv,r]
        if soft_cap is not None:
            s_self = soft_cap * jnp.tanh(s_self / soft_cap)
        s_self = s_self.reshape(sb, Hq, 1)
        m_prev = m_scr[:, :Hq, 0:1]
        m_new = jnp.maximum(m_prev, s_self)
        corr = jnp.exp(
            jnp.where(m_prev > NEG_INF / 2, m_prev - m_new, 0.0)
        )
        p_self = jnp.exp(s_self - m_new)                      # [SB,Hq,1]
        l = corr * l_scr[:, :Hq, 0:1] + p_self
        v_rep = jnp.broadcast_to(
            vs[:, :, None].astype(jnp.float32), (sb, n_kv, n_rep, Dv)
        ).reshape(sb, Hq, Dv)
        acc = acc_scr[:, :Hq, :Dv] * corr + p_self * v_rep
        o_ref[...] = (acc / l).astype(o_ref.dtype)


def decode(
    q: jnp.ndarray,          # [B, Hq, D]
    k_self: jnp.ndarray,     # [B, Hkv, D] current token's K (not in pool)
    v_self: jnp.ndarray,     # [B, Hkv, D]
    pages: jnp.ndarray,      # [L, P, 2, Hkv, page, D] the WHOLE pool
    layer: jnp.ndarray,      # scalar i32 layer index
    table: jnp.ndarray,      # [B, M] i32
    lens: jnp.ndarray,       # [B] tokens resident in the pool (excl. self)
    *,
    softmax_scale: Optional[float] = None,
    soft_cap: Optional[float] = None,
    sliding_window: Optional[int] = None,
    pages_per_step: int = 8,
    slots_per_step: int = 8,
    scales: Optional[jnp.ndarray] = None,  # [L, P, 2, Hkv, page] f32
    value_width: Optional[int] = None,
) -> jnp.ndarray:
    """The pool rides in whole (ANY memory space); the kernel issues its own
    per-page DMAs keyed by the scalar-prefetched layer index and page table
    — the caller's layer scan never slices or reshapes the pool.

    ``scales`` marks an int8 pool (docs/performance.md "KV quantization"):
    each page's scale stripe DMAs alongside the page into a parallel
    scratch and dequant fuses into the dots — the HBM read stays int8
    (half the KV bytes of bf16 + a 1/D scale overhead), values widen only
    in-register.

    ``value_width`` marks a LATENT pool ``[L, P, 1, 1, page, D]`` (absorbed
    MLA): ``q`` is ``[B, Hq, D]`` with ``D`` the latent's whole width,
    ``k_self`` ``[B, 1, D]`` the current token's latent, ``v_self`` is not
    read (pass ``None``), and the result is ``[B, Hq, value_width]``: the
    probabilities over the first ``value_width`` values of every resident
    latent. The kernel is then named ``mla_decode``.

    ``sliding_window``: the query (at position ``lens``) sees itself and
    ``sliding_window - 1`` positions before it; the kernel is then the
    ``_window`` program of its name and reads each row's pages from its
    first visible position on (module docstring)."""
    B, Hq, D = q.shape
    L, P, streams, Hkv, page, _ = pages.shape
    M = table.shape[1]
    n_rep = Hq // Hkv
    quantized = scales is not None
    latent = value_width is not None
    if latent and (streams != 1 or Hkv != 1 or quantized):
        raise ValueError(
            f"a latent pool is [L, P, 1, 1, page, D] in the serving dtype; "
            f"got {pages.shape}, scales={quantized}"
        )
    Dv = value_width if latent else D
    page_mult = page_multiple(pages.dtype)
    # a latent row is DMA'd and multiplied whole and sliced at ``Dv``: the
    # slice, not the row, has to end on a lane tile
    if not _interpret() and (
        (Dv if latent else D) % 128 != 0 or page % page_mult != 0
    ):
        raise ValueError(
            f"paged kernel needs head_dim%128==0 and page%{page_mult}==0 "
            f"on TPU; got D={D}, page={page} — use the XLA gather path"
        )
    if softmax_scale is None:
        softmax_scale = D ** -0.5
    hq_pad = max(8, Hq)
    windowed = sliding_window is not None
    sb, kp = block_plan(
        B, Hkv, D, page, M, pages.dtype, pages_per_step, slots_per_step,
        streams,
    )
    nblk = -(-M // kp)

    kernel = functools.partial(
        _decode_kernel,
        scale=softmax_scale,
        page=page,
        kp=kp,
        sb=sb,
        n_kv=Hkv,
        n_rep=n_rep,
        soft_cap=soft_cap,
        windowed=windowed,
        quantized=quantized,
        dv=value_width,
    )
    row = lambda b, j, *_: (b, 0, 0)
    in_specs = [
        pl.BlockSpec((sb, Hq, D), row),
        pl.BlockSpec((sb, Hkv, D), row),
        *([] if latent else [pl.BlockSpec((sb, Hkv, D), row)]),
        pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
    ]
    scratch_shapes = [
        pltpu.VMEM((2, sb, streams, Hkv, kp * page, D), pages.dtype),
        pltpu.VMEM((sb, hq_pad, LANES), jnp.float32),
        pltpu.VMEM((sb, hq_pad, LANES), jnp.float32),
        # lanes padded to a full tile; the kernel uses [:, :Dv]
        pltpu.VMEM((sb, hq_pad, max(Dv, LANES)), jnp.float32),
        pltpu.SemaphoreType.DMA((2, sb, kp)),
    ]
    operands = [
        jnp.asarray(layer, jnp.int32).reshape(1), table, lens,
        *([first_visible(lens, sliding_window).astype(jnp.int32)]
          if windowed else []),
        q, k_self, *([] if latent else [v_self]), pages,
    ]
    if quantized:
        # scales ride whole in ANY/HBM like the pool; their scratch and
        # semaphores slot in right after their KV twins (kernel ref order)
        in_specs.append(pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY))
        scratch_shapes.insert(
            1, pltpu.VMEM((2, sb, 2, Hkv, kp * page), jnp.float32)
        )
        scratch_shapes.append(pltpu.SemaphoreType.DMA((2, sb, kp)))
        operands.append(scales)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4 if windowed else 3,
            grid=(B // sb, nblk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((sb, Hq, Dv), row),
            scratch_shapes=scratch_shapes,
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Dv), q.dtype),
        # the double-buffered page scratch alone can exceed the 16 MB
        # default scoped-vmem budget; size the limit from the actual
        # scratch + generous op margin (v5e VMEM is 128 MB)
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_scratch_bytes(
                sb, kp, page, Hkv, D, pages.dtype, streams
            ) + 32 * 2**20,
        ),
        interpret=_interpret(),
        # the kernel's name in the compiled program and the device trace
        name=(
            "mla_decode" if latent
            else "paged_decode_int8" if quantized else "paged_decode"
        ) + ("_window" if windowed else ""),
    )(*operands)

