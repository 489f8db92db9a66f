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
costs, measured alone on a TPU v5e at page 128, bf16, at 128 slots x
12q/2kv x 128 unless said (PERF.md §6, PRs 25, 27, 36 and 47):

- the launch: a call of this grid and these operands whose kernel does
  NOTHING takes 0.0042 ms at 80 grid steps and 0.0047 at 256 by the device's
  trace (PR 47; the table and the lengths reach SMEM before step 0). A
  kernel timed by the host's clock over back-to-back calls reads 0.009-0.012
  ms MORE a call than the trace does: the records' "0.025 / 0.029 / 0.11 ms"
  for the empty call were such readings;
- a change of block. With NO token resident a call is 0.018-0.019 ms at 16
  blocks x 5 steps (128 slots, SB 8), 0.021 at 16 x 5 of 28q/4kv (SB 4), 0.097
  at 64 x 4 (64 slots, 16 kv heads, SB 1), 0.101 at 36 x 5 (72 slots, 16 kv
  heads, page 64, SB 2): 0.65 / 0.80 / 1.25 / 2.4 us a BLOCK, and nearly all
  of it is the ``j == nblk - 1`` fold of the current token (``_done``: 0.3 us
  a block at SB 8 and 6 query heads a kv head, 1.1-2.4 us where every head is
  its own kv head and so its own tile) and the ``j == 0`` fills (0.08 us).
  On a LOADED call they run under the page copies in flight: without both,
  the call on the cells' own rows is 0.2-0.8 % shorter. Until PR 47 ``q``,
  ``k_self``, ``v_self`` and the output were ``(SB, ...)`` blocks that the
  pipeline copied at every change of block. Where those operands live in HBM
  (this kernel timed alone) the copies IN made the call on the cells' rows
  7-10 % longer (0.2838 -> 0.2545 ms at the 1.5B cell's geometry, 1.024 ->
  0.954 at OLMoE's, 0.611 -> 0.560 at Ouro's, 0.727 -> 0.676 at 256 slots x
  8q/2kv; resident inputs alone give all of it, a resident output nothing:
  a block's small copies wait among the page copies in flight); they are now
  WHOLE in VMEM for the call, one copy in and one out. Inside the engine's
  ``jit_chunk`` XLA's memory-space assignment already kept all four in VMEM
  (``S(1)`` on the custom call's operands and result), so there a block's
  copies never left the chip and the cells' calls are the same to the
  microsecond before and after (``%paged_decode.9`` 7.8107 | 7.8090 ms a
  step): a kernel-alone table says what a cell will do only if its operands
  sit where the cell's do;
- an unreached step: the one test of the block's rows against the step's
  positions (``_block_span``) and nothing else: no copy started, no page
  zeroed, no wait, no body, no look at the next step; 0.06-0.085 us by the
  trace (80 against 32 steps of 16 blocks: 0.0183 against 0.0147 ms).
  :func:`kernel_steps` counts the steps that are reached (a third of them on
  heavy-tailed rollout traffic, once rows are sorted);
- page DMAs are issued per slot, only for pages the slot holds, so the
  bytes read from HBM are the resident KV and no more. Where EVERY step is
  reached the copies of step ``n + 1`` run under the dots of step ``n`` and
  the call reads its bytes at 713 GB/s (all rows 2,048: 0.376 ms) to 734
  GB/s (all rows 4,096: 0.731 ms), 87-90 % of the chip's 819: the steady
  state. The scalar walk over a reached step's ``SB * KP`` table entries
  (a start or a zero branch for the next step, a wait branch for this one)
  hides under it: bounding the walk by each row's own page count measured
  0.1-0.2 % of a call, folding start and zero into one branch 0.0-0.3 %
  (PR 27). An earlier fit of "36 ns an entry, bytes at 950 GB/s" was the
  next item read as a walk: no byte here moves faster than 819 GB/s. What
  the entries DID cost was the host's time: written as Python loops each
  was traced on its own by every chunk program at every start, so they
  are ONE traced visit that the lowering unrolls (``_each_entry``);
- what the steady state loses at a block's edge. The prefetch chain runs
  over REACHED steps in grid order (``_next_reached``; its host twin is
  :func:`reached_chain`): the last reached step of a block starts the first
  reached step of the next block that reaches any, over whatever unreached
  steps and empty blocks lie between. Until PR 36 the chain ran over grid
  steps ``g -> g + 1``: at every block's last reached step it started
  nothing, the step ended with the DMA queue empty and the next block's
  first step waited for its copies in full, ~2.5 us a crossing alone (all
  rows 2,048 in a table of 40 pages, 16 crossings: 0.427 ms, now 0.387;
  with one reached step a block, all rows 1,024: 0.248 -> 0.207) and ~3 in
  the 1.5B rollout cell (a call 0.330 -> 0.280 ms).
  :func:`kernel_steps_chained` counts the crossings: ~15 a call in the
  1.5B rollout cell, 63 at SB 1;
- the body (QK dot, softmax, PV dot, batched over ``[SB*Hkv, S, D]``)
  runs for the WHOLE block of SB rows at every page block up to
  ``ceil(max_len / S)`` of its LONGEST row, over every row's stripe of the
  scratch whether a page was copied into it or not (masked probabilities
  are 0, but ``0 * NaN = NaN`` in the PV dot, so what such a stripe holds as
  VALUES must be finite). :func:`kernel_positions` counts that: ``SB * S *
  ceil(max_len / S)`` summed over blocks. Rows of mixed length in one block
  are work over positions that hold no KV (2.4 x the resident KV with rows
  in random order, 1.5 x sorted). Until PR 47 ``_zero`` stored a page of
  zeros, keys and values, for every page a shorter row does not hold, at
  every reached step (635 stores of 128 KB a call on the 1.5B cell's rows:
  1.2 % of the call, 0.6 % at SB 1, nothing at SB 2-4); now only the call's
  first two issues store zeros, values only (``_issue``): after them every
  stripe of both buffers holds a page of the pool or zeros. At SB 8 mixed
  blocks also lose what the chain wins: on rows in SLOT order it measures
  6-9 % SLOWER alone than the chain over grid steps did (at SB 4 1 % and at
  SB 1 12 % faster; sorted 9-27 % faster at the five cells' geometries).
  The zero stores were much of that: with them stored once the 1.5B cell's
  rows SHUFFLED take 0.2897 ms for 0.3239 (SB 4: 0.2669 for 0.2863), against
  0.2520 sorted; it is still one more reason for the caller's sort.

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
does (``_reached_spans`` gives both ends; the chain enters a block at its
FIRST reached step, not at step 0). The full-attention program has none
of this and is what it was.
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


def _resident_bytes(batch, n_q, n_kv, head_dim, dv, dtype, latent) -> int:
    """VMEM that q, the current token's K (and V, but in a latent program)
    and the output hold for the whole call, as the chip lays them out: the
    head axis in whole sublane tiles (8 rows of 32 bits: 16 of bf16), the
    width in whole 128-lane tiles. 2.1 MB at 128 slots x 12q/2kv x 128,
    24 MB at the largest there is, 256 x 32 latent rows of 576."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * 4 // item

    def tiled(heads, width):
        return batch * -(-heads // sub) * sub * -(-width // LANES) * LANES * item

    return (tiled(n_q, head_dim) + tiled(n_q, dv)
            + (1 if latent else 2) * tiled(n_kv, head_dim))


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


def _reached_spans(lens, sb: int, span: int, first=None, nblk=None):
    """``(lo, hi)`` a block of ``sb`` consecutive rows of ``lens``: the grid
    steps ``lo <= j < hi`` hold a visible position of some row of the block.
    ``hi`` is ``ceil(longest / span)``, held to the table's ``nblk`` page
    blocks where given; ``lo`` is 0, or with the rows' ``first`` visible
    positions (a window layer) the step the block's least one falls in. A
    block that reaches nothing has ``lo == hi``."""
    longest = np.asarray(lens, np.int64).reshape(-1, sb).max(axis=1)
    hi = -(-longest // span)
    if nblk is not None:
        hi = np.minimum(hi, nblk)
    if first is None:
        return np.zeros_like(hi), hi
    least = np.asarray(first, np.int64).reshape(-1, sb).min(axis=1)
    return np.minimum(least // span, hi), hi


def _steps_reached(lens, sb: int, span: int, first=None) -> int:
    """Grid steps whose block of ``sb`` consecutive rows of ``lens`` reaches
    the step's first position: ``ceil(longest / span)`` a block; with the
    rows' ``first`` visible positions (a window layer), less the steps that
    end before the block's least one."""
    lo, hi = _reached_spans(lens, sb, span, first)
    return int((hi - lo).sum())


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
    body; the others cost one test."""
    return _steps_reached(lens, sb, span, first), len(lens) // sb * nblk


def reached_chain(lens, sb: int, span: int, nblk: int, first=None):
    """The kernel's prefetch chain over rows ``lens`` (any order), as four
    vectors a block of ``sb`` rows: ``(lo, hi, nxt, before)``. The block's
    reached steps are ``lo <= j < hi`` (:func:`_reached_spans`, ``hi`` held
    to ``nblk``); ``nxt`` is the next block that reaches any step (the
    number of blocks where none does); ``before`` counts the reached steps
    of the blocks before it. So reached step ``(b, j)`` is the call's
    ``before[b] + j - lo[b]``-th (its scratch buffer is that ordinal's
    parity) and starts the copies of ``(b, j + 1)`` where ``j + 1 <
    hi[b]``, else of ``(nxt[b], lo[nxt[b]])``, else of nothing."""
    lo, hi = _reached_spans(lens, sb, span, first, nblk)
    nb = len(lo)
    nxt = np.full(nb, nb, np.int64)
    after = nb
    for b in range(nb - 1, -1, -1):
        nxt[b] = after
        if lo[b] < hi[b]:
            after = b
    before = np.concatenate([[0], np.cumsum(hi - lo)[:-1]])
    return lo, hi, nxt, before


def kernel_steps_chained(
    lens, sb: int, span: int, nblk: int, first=None
) -> int:
    """Reached steps of a call (:func:`kernel_steps`) whose copies a reached
    step of ANOTHER block starts: every block that reaches a step but the
    call's first such. Before the chain ran over reached steps each of these
    waited for its copies in full behind an empty DMA queue."""
    lo, hi = _reached_spans(lens, sb, span, first, nblk)
    return max(int((lo < hi).sum()) - 1, 0)


def _block_span(lens_ref, first_ref, b, *, sb: int, S: int, nblk: int):
    """``(lo, hi)`` of block ``b`` on the scalar core (:func:`_reached_spans`
    held to ``nblk``; ``first_ref`` is ``None`` but in a window program):
    step ``j`` is reached where ``lo <= j < hi``. The ONE predicate copies
    are started, waited for and chained under. Refs or arrays."""
    rows = [b * sb + s for s in range(sb)]
    longest = functools.reduce(jnp.maximum, [lens_ref[r] for r in rows])
    hi = jnp.minimum(pl.cdiv(longest, S), nblk)
    if first_ref is None:
        return jnp.zeros_like(hi), hi
    least = functools.reduce(jnp.minimum, [first_ref[r] for r in rows])
    return least // S, hi


def _first_reached(lens_ref, first_ref, b0, *, nb: int, **plan):
    """``(b, lo, found)``: the first block from ``b0`` on that reaches a
    step, and that step; a bounded walk over blocks on the scalar core
    (over a whole call every block is looked at once)."""
    def span(b):
        return _block_span(
            lens_ref, first_ref, jnp.minimum(b, nb - 1), **plan)

    b0 = jnp.asarray(b0, jnp.int32)
    b, lo, _ = jax.lax.while_loop(
        lambda c: (c[0] < nb) & (c[1] >= c[2]),
        lambda c: (c[0] + 1, *span(c[0] + 1)),
        (b0, *span(b0)),
    )
    return b, lo, b < nb


def _next_reached(lens_ref, first_ref, bb, j, *, nb: int, **plan):
    """``(b, j, found)``: the reached step after reached step ``(bb, j)`` in
    grid order (:func:`reached_chain` is its host twin): the block's next
    where it reaches one, else the first of the next block that reaches
    any."""
    _, hi = _block_span(lens_ref, first_ref, bb, **plan)
    stay = j + 1 < hi
    # staying, the walk starts past the last block and looks at none
    b, lo, found = _first_reached(
        lens_ref, first_ref, jnp.where(stay, nb, bb + 1), nb=nb, **plan)
    return jnp.where(stay, bb, b), jnp.where(stay, j + 1, lo), stay | found


def _decode_kernel(
    *refs,
    scale: float,
    page: int,
    kp: int,
    sb: int,
    nb: int,
    nblk: int,
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
    #   q_ref      [B, Hq, D]   WHOLE in VMEM for the call, as ``ks_ref``,
    #              ``vs_ref`` and ``o_ref`` are: copied in once and out once;
    #              block ``bb`` reads and writes its ``rows`` of them
    #   ks_ref     [B, Hkv, D] the current tokens' K (not in the pool)
    #   vs_ref     [B, Hkv, D]
    #   kv_hbm     [L, P, 2, Hkv, page, D] whole pool, ANY/HBM
    #   sc_hbm     [L, P, 2, Hkv, page] f32 scales, ANY/HBM   (quantized)
    #   o_ref      [B, Hq, D]
    #   kv_scr     [2, SB, 2, Hkv, KP*page, D] DOUBLE-buffered page scratch
    #              — pages DMA straight into the compute layout while the
    #              previous grid step's buffer is being consumed
    #   sc_scr     [2, SB, 2, Hkv, KP*page] f32 scale scratch (quantized)
    #   m_scr      [SB, HqP, LANES] f32
    #   l_scr      [SB, HqP, LANES] f32
    #   acc_scr    [SB, HqP, Dp] f32
    #   sems       DMA semaphores [2, SB, KP]
    #   sc_sems    DMA semaphores [2, SB, KP]                 (quantized)
    #   ord_scr    [2] int32 SMEM: which of the two buffers the NEXT reached
    #              step's pages are in (the parity of its ordinal), and how
    #              many reached steps' copies the call has started so far
    *refs, ord_scr = refs
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
    rows = pl.ds(bb * sb, sb)  # the block's, on the untiled leading dimension
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

    def _each_entry(bb_t, visit):
        """``visit(s, slot, i, at, tests)`` over the ``SB * KP`` table
        entries of a step of block ``bb_t``: row ``s`` of the block is
        ``slot`` with its page ``tests``, entry ``i`` of the step lands in
        the stripe ``at`` of the row's scratch. The visit is traced ONCE and
        unrolled where the kernel is lowered; as Python loops every entry
        was a trace of its own, in every chunk program at every start
        (PERF.md §6, PR 36)."""
        def row(s, _):
            slot = bb_t * sb + s
            tests = _page_tests(slot)

            def entry(i, _):
                at = pl.ds(pl.multiple_of(i * page, page), page)
                visit(s, slot, i, at, tests)

            jax.lax.fori_loop(0, kp, entry, None, unroll=True)

        jax.lax.fori_loop(0, sb, row, None, unroll=True)

    def _issue(bb_t, j_t, buf):
        """Start every resident-page DMA for REACHED step ``j_t`` of block
        ``bb_t`` into buffer ``buf`` (and, the first time the call uses the
        buffer, zero the VALUES of the stripes it starts no copy into)."""
        # the batched body reads EVERY slot's stripe whenever any slot of
        # the block is active, and masked probabilities are 0, but 0 * NaN =
        # NaN in the PV dot: a stripe no page is copied into must hold
        # finite VALUES. It does once the call has written it, with a page
        # (the pool's are finite: the tail of every row's last page is read
        # the same way) or with zeros; what VMEM held before the call is
        # anything. So only the call's first two issues, one a buffer,
        # store zeros (until PR 47 every issue did, keys and values: 635
        # stores of 128 KB a call on the 1.5B cell's rows, -1.2 % of the
        # call alone). Keys need none: a masked score is replaced, not
        # multiplied.
        issued = ord_scr[1]
        fresh = issued < 2

        def visit(s, slot, i, at, tests):
            held, free = tests(j_t * kp + i)

            @pl.when(held)
            def _start():
                pidx = table_ref[slot, j_t * kp + i]
                # K and V are interleaved per page: ONE DMA per page,
                # landing in the [2, Hkv, i*page:(i+1)*page, D] stripe of
                # the compute-layout scratch
                pltpu.make_async_copy(
                    kv_hbm.at[layer, pidx],
                    kv_scr.at[buf, s, :, :, at, :],
                    sems.at[buf, s, i],
                ).start()
                if quantized:
                    # the page's scale stripe rides a second (tiny — 1/D
                    # of the page bytes) DMA into the parallel scale
                    # scratch; dequant happens in-register at the dots,
                    # never as a widened pool copy
                    pltpu.make_async_copy(
                        sc_hbm.at[layer, pidx],
                        sc_scr.at[buf, s, :, :, at],
                        sc_sems.at[buf, s, i],
                    ).start()

            @pl.when(free & fresh)
            def _zero():
                # the value stream: the second of a K/V pool, the one of a
                # latent pool
                kv_scr[buf, s, n_str - 1, :, at, :] = (
                    jnp.zeros((n_kv, page, D), kv_scr.dtype)
                )
                if quantized:
                    sc_scr[buf, s, 1, :, at] = (
                        jnp.zeros((n_kv, page), sc_scr.dtype)
                    )

        _each_entry(bb_t, visit)
        ord_scr[1] = issued + 1

    # Software pipeline over the REACHED steps of the (sequential) grid, in
    # grid order: a reached step's pages were started by the reached step
    # before it, in whatever block that lies, and it starts the next reached
    # step's BEFORE it waits for its own, so the HBM reads of the next page
    # block run under this one's dots across block boundaries too (a chain
    # over grid steps ``g -> g + 1`` started nothing at the last reached step
    # of every block: that step ended with the DMA queue empty and the next
    # block's first step waited for its copies in full). The call's first
    # reached step is started here, at grid step 0. A step that is not
    # reached starts nothing, waits for nothing and looks for nothing. The
    # two buffers alternate with the ORDINAL of the reached step, kept as a
    # parity in SMEM across the grid: a block that reaches an odd number of
    # steps hands the next block the other buffer.
    plan = dict(sb=sb, S=S, nblk=nblk, nb=nb)

    @pl.when((bb == 0) & (j == 0))
    def _prologue():
        ord_scr[0] = 0
        ord_scr[1] = 0
        b_t, j_t, found = _first_reached(lens_ref, first_ref, 0, **plan)

        @pl.when(found)
        def _():
            _issue(b_t, j_t, 0)

    # the predicate this step's copies were started under, over the same
    # scalars (``_block_span``): every started copy is waited for, and a
    # step that started none tests nothing
    lo, hi = _block_span(lens_ref, first_ref, bb, sb=sb, S=S, nblk=nblk)
    reached = (lo <= j) & (j < hi)
    buf = ord_scr[0]

    @pl.when(reached)
    def _arrived():
        b_t, j_t, found = _next_reached(lens_ref, first_ref, bb, j, **plan)

        @pl.when(found)
        def _prefetch():
            _issue(b_t, j_t, 1 - buf)

        def visit(s, slot, i, at, tests):
            @pl.when(tests(j * kp + i)[0])
            def _wait():
                pidx = table_ref[slot, j * kp + i]
                pltpu.make_async_copy(
                    kv_hbm.at[layer, pidx],
                    kv_scr.at[buf, s, :, :, at, :],
                    sems.at[buf, s, i],
                ).wait()
                if quantized:
                    pltpu.make_async_copy(
                        sc_hbm.at[layer, pidx],
                        sc_scr.at[buf, s, :, :, at],
                        sc_sems.at[buf, s, i],
                    ).wait()

        _each_entry(bb, visit)

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

    @pl.when(reached)
    def _body():
        lens_v = _splat(lens_ref)
        first_v = _splat(first_ref) if windowed else None
        ord_scr[0] = 1 - buf
        # (SB, Hkv) folds into ONE batch dim (Mosaic's tpu.matmul supports
        # a single batch dim); the reshape is layout-free
        q = q_ref[rows].reshape(sb * n_kv, n_rep, D)
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
        q = q_ref[rows].reshape(sb, n_kv, n_rep, D)
        ks = ks_ref[rows]                                      # [SB,Hkv,D]
        vs = ks[:, :, :Dv] if latent else vs_ref[rows]
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
        o_ref[rows] = (acc / l).astype(o_ref.dtype)


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
        nb=B // sb,
        nblk=nblk,
        n_kv=Hkv,
        n_rep=n_rep,
        soft_cap=soft_cap,
        windowed=windowed,
        quantized=quantized,
        dv=value_width,
    )
    # q, the current token's K/V and the output are WHOLE in VMEM for the
    # call: one copy in before the grid and one out after it. As blocks of
    # ``sb`` rows the pipeline copied them at every change of block, which
    # from HBM costs a loaded call 7-10 %; a caller whose program already
    # keeps them in VMEM (the engine's ``jit_chunk``) pays neither (module
    # docstring)
    resident = pl.BlockSpec(memory_space=pltpu.MemorySpace.VMEM)
    in_specs = [
        resident,
        resident,
        *([] if latent else [resident]),
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
    # the parity of the next reached step's ordinal and the count of issues,
    # last of the scratch
    scratch_shapes.append(pltpu.SMEM((2,), jnp.int32))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4 if windowed else 3,
            grid=(B // sb, nblk),
            in_specs=in_specs,
            out_specs=resident,
            scratch_shapes=scratch_shapes,
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Dv), q.dtype),
        # the double-buffered page scratch alone can exceed the 16 MB
        # default scoped-vmem budget; size the limit from the actual
        # scratch and the resident operands + generous op margin (v5e VMEM
        # is 128 MB)
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_scratch_bytes(
                sb, kp, page, Hkv, D, pages.dtype, streams
            ) + _resident_bytes(B, Hq, Hkv, D, Dv, q.dtype, latent)
            + 32 * 2**20,
        ),
        interpret=_interpret(),
        # the kernel's name in the compiled program and the device trace
        name=(
            "mla_decode" if latent
            else "paged_decode_int8" if quantized else "paged_decode"
        ) + ("_window" if windowed else ""),
    )(*operands)

