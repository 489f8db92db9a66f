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
positions) per step, ``(SB, KP)`` from :func:`block_plan`: 4 pages a step
in the full-attention program since PR 52, 8 in the window, latent and int8
programs, ``SB`` as 8 pages give it. What a call costs, measured alone on a
TPU v5e at page 128, bf16, at 128 slots x 12q/2kv x 128 and 8 pages a step
unless said (PERF.md §6, PRs 25, 27, 36 and 47):

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
  Since PR 50 took a group's prompt pages out of this program a row's OWN
  pages are 1-8 in the rollout cells, most blocks reach ONE step of 8 pages
  and padded it: 1.9 x the own positions at the 1.5B cell's rows, 1.5 x at 4
  pages a step (196,608 | 155,648 row-positions over 104,634 own). The
  full-attention program therefore steps 4 pages (:func:`block_plan`, PR
  52): the call over the cells' own pages, ALONE (operands in HBM, 200 calls
  a scan, host clock; 8 -> 4 pages a step, ``SB`` held): 0.1892 -> 0.1760 ms
  at 128 x 12q/2kv (-7.0 %), 0.1684 -> 0.1576 at 64 x 28q/4kv (-6.4 %),
  0.3735 -> 0.3596 at 72 x 16q/16kv, page 64 (-3.7 %), 0.6053 -> 0.5911 at
  256 x 8q/2kv (-2.4 %), 0.6460 -> 0.6331 at 64 x 16q/16kv (-2.0 %); rows of
  1,500-4,500 positions in slot order, 128 x 40q/10kv: 2.6216 -> 2.6079;
  rows of 2,000-6,000, 112 x 28q/4kv: 1.2445 -> 1.2467. The OTHER form, the
  copies left at 8 pages and the body as passes over tiles of 4 as far as the
  block's longest row reaches (one ``fori_loop`` over one softmax state),
  measured half of that where ``SB`` is 4-8 (0.1824 and 0.1626: a step of 8
  still waits for all its copies before its first dot) and the same at ``SB``
  1-2, and +0.5 % on the long rows; tiles of 2 pages less again. The window
  program: 4 pages a step +9.8 % alone at window 512 (0.6437 -> 0.7066, twice
  the steps for the same 4-5 pages a row), tiles of 4 in steps of 8 -2.9 %
  (0.6248): not taken, one geometry and 12 % of the one cell that has it.

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

SHARED PAGES (PR 50). ACTIVE rows of one call whose tables agree on their
leading whole pages (a GRPO group on one prompt, through the prefix cache;
a re-admitted request; a later turn: only the table is read) form a GROUP
(:func:`shared_prefix`, which ``decode_step_paged`` applies once a step,
outside its layer scan, and :func:`shared_counts` applies on the host for
the engine's census: one function, numpy or ``jax.numpy``). A
full-attention layer is then TWO programs over ONE float32 softmax state,
in the order the one program had (prefix positions, own positions, the
current token last):

- ``paged_decode_prefix`` (:func:`decode_prefix`): grid over group BLOCKS of
  16 seats (:func:`prefix_plan`; a group of more takes several). A block
  copies each page its seats share ONCE, and multiplies it once against all
  its seats' queries, gathered from the whole-in-VMEM ``q`` by row and folded
  with the ``n_rep`` query heads of a kv head into the row dimension of one
  dot (``[Hkv, 16 * n_rep, D] x [Hkv, S, D]``: 96 rows on one copy of a K
  tile at 12q/2kv where the program over rows has 6 and loads the tile 16
  times; 16 rows where it has 1 at 16 kv heads). No mask but the shared
  length. It leaves ``m``, ``l``, ``acc`` BY SEAT.
- ``paged_decode`` with ``carry`` (:func:`decode`): each row's OWN pages,
  through a table shifted past the shared ones and the own length (both
  made once a step by the caller, which also orders the rows by OWN length,
  so the body shrinks with the bytes); ``_init`` takes the row's seat's
  state where it filled constants. Nothing else of the program changes.

What the two cost in the rollout cells' ``jit_chunk`` (operands in VMEM;
traced pairs on one seed a cell, the last ~4 s of a 40 s window, shared
pages 33-37 % of the per-slot bytes there; PERF.md §6, PR 50), ms a layer's
call, the one program -> own + prefix: 128 slots x 12q/2kv, page 128:
0.2761 -> 0.1750 + 0.0352 (-24 %); 64 slots x 28q/4kv (SB 4): 0.2532 ->
0.1620 + 0.0282 (-25 %); 64 slots x 16q/16kv, page 128 (SB 1): 0.9226 ->
0.5823 + 0.0646 (-30 %); 72 slots x 16q/16kv, page 64 (SB 2): 0.5242 ->
0.3857 + 0.0365 (-19 %). ALONE (operands in HBM, 200 calls a scan, the
sandbox's scheduler run of each cell's traffic for a table; the form with
one reshaped store a seat, whose prefix program is 0.006 ms longer at 2 kv
heads than the committed one) the first, third and fourth read 0.2801 ->
0.2306 (own 0.1851, prefix 0.0507, each alone), 0.8872 -> 0.6876 (0.6467,
0.0525) and 0.5389 -> 0.3978 (0.3729, 0.0386): the table understates a
cell, whose small operands sit in VMEM (PR 47's
lesson). Where nothing is shared (the same lengths, every page a row's
own) the prefix program's blocks reach no step, 0.0055-0.0076 ms alone for
its launch and its 16-32 tests, and the two programs are +0.2 to +0.5 % of
the one (0.2811 -> 0.2826, 0.8875 -> 0.8893, 0.5389 -> 0.5414). The prefix
program's seat loops (gather the queries, leave the state) are traced ONCE
with a store a kv head: as Python loops they run 0.006 ms a call faster at
2 kv heads (0.0392 for 0.0454 alone) and are 512 stores to trace and lower
in every chunk program at 16; one reshaped store a seat is slower (0.0512).
The own-pages program keeps its ROWS a loop where the other programs unroll
them (``_each_entry``): alone the call is the same (0.1913 | 0.1919 ms) and
a chunk program lowers 2-3 s sooner on the chip's host, which more than
pays for tracing the prefix program beside it.

The prefix program KEEPS 8 pages a step (PR 52, alone, 8 pages | 4 pages a
step | 8 with the body in tiles of 4): 0.0425 | 0.0508 | 0.0524 ms at 128 x
12q/2kv, 0.0199 | 0.0229 | 0.0231 at 64 x 28q/4kv, 0.0387 | 0.0387 | 0.0415 at
72 x 16q/16kv page 64, 0.0312 | 0.0310 | 0.0319 at 256 x 8q/2kv, 0.0525 |
0.0500 | 0.0514 at 64 x 16q/16kv. A pass of its body updates the state of
``16 * n_rep`` folded rows a kv head (``m``, ``l`` and ``acc``: 0.7 MB at
28q/4kv), which costs more than the dots over the 512 positions a shorter
pass leaves out; a prompt of 2-7 whole pages padded to one step of 8 is the
cheaper form wherever a kv head has more than one query head.
"""

import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38
LANES = 128
# a softmax state between two programs: ``m`` and ``l`` of a query row share
# one ``[..., LANES]`` vector, ``m`` in the lanes under this one
ML_SPLIT = LANES // 2
# pages of one grid step (the table's width where that is less): the
# prefix program's, and the window, latent and int8 programs' default
# (:func:`block_plan`)
PAGES_PER_STEP = 8
# the full-attention program's over a K/V pool in the serving dtype, with or
# without ``carry`` (:func:`block_plan` has the reason)
FULL_PAGES_PER_STEP = 4


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


def _state_bytes(batch, n_q, head_dim) -> int:
    """VMEM a float32 softmax state of ``batch`` rows holds for a call
    (``acc`` and the ``m`` / ``l`` vector of every query row), tiled as
    :func:`_resident_bytes` tiles."""
    heads = -(-n_q // 8) * 8
    return batch * heads * (-(-head_dim // LANES) * LANES + LANES) * 4


def block_plan(
    batch: int,
    n_kv_heads: int,
    head_dim: int,
    page: int,
    table_width: int,
    pool_dtype,
    pages_per_step: Optional[int] = None,
    slots_per_step: int = 8,
    streams: int = 2,
    windowed: bool = False,
) -> Tuple[int, int]:
    """``(sb, kp)``: the slots and pages of one grid step that
    :func:`decode` runs a batch with. ``kp`` is the table width capped at
    ``pages_per_step``; ``sb`` is ``slots_per_step`` halved until it
    divides the batch and the KV scratch is NOT OVER 16 MiB (a scratch of
    exactly 16 MiB stays: 12q/2kv x 128 at page 128 runs 8 slots a step,
    28q/4kv x 128 runs 4; one latent stream of 576 runs 4). Pure, so the engine counts
    :func:`kernel_positions` with the plan the kernel uses.

    ``pages_per_step`` left to the plan: ``FULL_PAGES_PER_STEP`` (4) in the
    full-attention program over a K/V pool in the serving dtype (two
    ``streams``, not int8, not ``windowed``), ``PAGES_PER_STEP`` (8) in the
    window, latent and int8 programs, and ``sb`` as 8 pages a step give it
    either way (the scratch halves; 8 slots of 4 pages at 28q/4kv measured
    +3.3 % alone, PR 47). A step's body runs over ALL ``sb x kp`` pages'
    positions, so a block whose longest row ends in the first four pages of
    an 8-page step multiplied its rows' queries against 512 positions that
    hold nothing; at 4 it does not, and the copies of the step's second
    half run under the body of its first: 2-7 % of the call on the rollout
    cells' own pages, nothing on long rows, and a LOSS in the window program
    (the module docstring has the table; the latent and int8 programs were
    not measured)."""
    if pages_per_step is None:
        full = streams == 2 and jnp.dtype(pool_dtype) != jnp.int8 and (
            not windowed)
        kp = min(FULL_PAGES_PER_STEP if full else PAGES_PER_STEP, table_width)
        fit = min(PAGES_PER_STEP, table_width)
    else:
        kp = fit = min(pages_per_step, table_width)
    sb = slots_per_step
    while batch % sb:
        sb //= 2
    while sb > 1 and _scratch_bytes(
        sb, fit, page, n_kv_heads, head_dim, pool_dtype, streams
    ) > 16 * 1024 * 1024:
        sb //= 2
    return sb, kp


class SharedPrefix(NamedTuple):
    """What a call's page table shows of rows that name the same pages
    (:func:`shared_prefix`). A GROUP is the rows whose tables agree on their
    leading ``n`` entries, all wholly under every member's length, with
    ``n`` the longest such run each member has with any row; it sits in
    BLOCKS of ``seats`` rows (a group of more takes several)."""

    pages: Any   # [B] leading pages a row reads through its block (0: none)
    # [B] the row's seat, block * seats + place (blocks * seats: none)
    seat: Any
    rows: Any    # [blocks, seats] the row in each seat (B: empty)
    n: Any       # [blocks] the block's shared pages (0: a block not in use)


def prefix_plan(batch: int) -> Tuple[int, int]:
    """``(seats, blocks)`` of the prefix pass over a call of ``batch`` rows:
    16 rows a block, the GRPO group every rollout cell sends (with ``n_rep``
    query heads a kv head that is ``16 * n_rep`` rows of the MXU on one copy
    of a K tile), and a block for every four rows (a call of groups thinned
    to 6-8 running members fills two thirds of them; rows of a group past
    the last block read their pages themselves)."""
    return min(16, batch), max(batch // 4, 1)


def shared_prefix(
    table, lens, active, page: int, seats: int, blocks: int, xp=np,
):
    """:class:`SharedPrefix` of a call's ``table [B, M]``, ``lens [B]`` and
    ``active [B]`` (``xp``: numpy on the host, ``jax.numpy`` in the step;
    ONE arithmetic, so the engine's census counts what the kernel does).
    Only the table is read: two rows share a page where their tables name it
    at the same place behind the same leading entries, whoever put it there
    (a prefix hit, a re-admitted request, a later turn). Entries past a
    row's whole pages are never compared as shared (the page a row is still
    writing is its own; what lies past its length is stale), and a row that
    is not ``active`` shares nothing: its result is thrown away, and a freed
    slot's zeroed table under the length the device still has would
    otherwise sit in a block beside every other freed slot. ``B x B``
    compares and no sort, once a step, outside the layer scan."""
    B, M = table.shape
    ids = xp.arange(B)
    whole = xp.where(active, lens // page, 0)
    # lcp[b, c]: the leading entries rows b and c agree on, whole pages of
    # both (behind its entries a row carries its index: two rows differ
    # there at the latest, and a row and itself agree on nothing)
    keyed = xp.concatenate([table, ids[:, None].astype(table.dtype)], axis=1)
    differ = (keyed[:, None, :] != keyed[None, :, :]).argmax(axis=2)
    lcp = xp.minimum(differ, xp.minimum(whole[:, None], whole[None, :]))
    n = lcp.max(axis=1)
    # rows of one n that agree on those n entries (an equivalence: agreement
    # on a leading run is transitive); a row whose partners all share more
    # with others is alone and reads its pages itself
    mate = (lcp >= n[:, None]) & (n[None, :] == n[:, None]) & (n[:, None] > 0)
    shared = mate.any(axis=1)
    mate = mate | ((ids[:, None] == ids[None, :]) & shared[:, None])
    head = xp.argmax(mate, axis=1)
    place = (mate & (ids[None, :] < ids[:, None])).sum(axis=1)
    size = mate.sum(axis=1)
    # groups take their blocks in the order of their heads
    is_head = shared & (head == ids)
    blocks_of = xp.where(is_head, -(-size // seats), 0)
    before = (xp.where(ids[None, :] < head[:, None], blocks_of[None, :], 0)
              ).sum(axis=1)
    block = before + place // seats
    seated = shared & (block < blocks)
    none = blocks * seats
    seat = xp.where(seated, block * seats + place % seats, none)
    at = seat[None, :] == xp.arange(none)[:, None]            # [seats, B]
    rows = xp.where(at.any(axis=1), xp.argmax(at, axis=1), B)
    rows = rows.reshape(blocks, seats)
    first = rows[:, 0]
    return SharedPrefix(
        pages=xp.where(seated, n, 0),
        seat=seat,
        rows=rows,
        n=xp.where(first < B, n[xp.minimum(first, B - 1)], 0),
    )


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


def _columns_compared(table, whole) -> int:
    """Leading columns of ``table`` that :func:`shared_prefix` has to be
    handed for rows of ``whole`` pages (0: not active) to get the plan of
    the whole table: as far as the longest row's whole pages, or ``k`` of
    them where no two rows that reach past ``k`` pages agree on all ``k``
    (then no run is longer than ``k``; a GRPO group's prompt is a few
    pages of a table of up to 128). The dense compare is most of what the
    engine's census costs the host a chunk."""
    width = max(int(whole.max()), 1)
    k = 8
    while k < width:
        reach = table[whole > k, :k]
        if len({row.tobytes() for row in reach}) == len(reach):
            return k
        k *= 2
    return width


def shared_counts(
    table, lens, active, page: int, sb: int, kp: int, nblk: int,
    by_own: bool = True,
) -> dict:
    """What the two programs of a full-attention call do over ``table [B,
    M]``, ``lens [B]`` and ``active [B]`` (host arrays, slot order; ``sb``,
    ``kp`` the own program's :func:`block_plan`, ``nblk`` its page blocks a
    row; ``by_own`` false where the step keeps its rows by length), as the
    engine's census names it. ``kernel_positions``: row-positions the
    bodies run over, the prefix program's seated rows times the padded
    positions of their block (in ITS steps, ``PAGES_PER_STEP`` pages) and
    the own program's :func:`kernel_positions` over the own lengths (in
    steps of ``kp``); ``kernel_steps*`` as
    :func:`kernel_steps` and :func:`kernel_steps_chained`, both programs;
    ``kv_pages_named``: pages the rows' tables hold under their lengths;
    ``kv_pages_read``: page copies the two programs start;
    ``kv_shared_groups`` blocks in use and ``kv_shared_rows`` rows seated in
    them."""
    B = len(lens)
    seats, blocks = prefix_plan(B)
    lens, active = np.asarray(lens, np.int64), np.asarray(active)
    table = np.asarray(table)
    plan = shared_prefix(
        table[:, :_columns_compared(table, np.where(active, lens // page, 0))],
        lens, active, page, seats, blocks)
    own, span = lens - plan.pages * page, kp * page
    own = own[np.argsort(own if by_own else lens, kind="stable")]
    shared_lens = plan.n * page
    seated = (plan.rows < B).sum(axis=1)
    # (the prefix program's steps are its own: ``decode_prefix``)
    prefix_kp = min(PAGES_PER_STEP, table.shape[1])
    prefix_span, prefix_nblk = prefix_kp * page, -(-table.shape[1] // prefix_kp)
    prefix_steps = -(-shared_lens // prefix_span)
    active, total = kernel_steps(own, sb, span, nblk)
    return {
        "kernel_positions": kernel_positions(own, sb, span)
        + int((seated * prefix_span * prefix_steps).sum()),
        "kernel_steps_active": active + int(prefix_steps.sum()),
        "kernel_steps": total + blocks * prefix_nblk,
        "kernel_steps_chained": kernel_steps_chained(own, sb, span, nblk)
        + kernel_steps_chained(shared_lens, 1, prefix_span, prefix_nblk),
        "kv_pages_named": int((-(-lens // page)).sum()),
        "kv_pages_read": int((-(-own // page)).sum() + plan.n.sum()),
        "kv_shared_groups": int((plan.n > 0).sum()),
        "kv_shared_rows": int(seated.sum()),
    }


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
    carried: bool = False,
):
    # ``carried``: the program over the rows' OWN pages, behind the pages
    # the prefix program (``_prefix_kernel``) read for their group. It
    # starts from the state that program left BY SEAT: ``seat_ref [B]`` (a
    # fourth scalar operand) each row's seat, and ``acc0_ref [seats, Hq,
    # D]``, ``ml0_ref [seats, Hq, LANES]`` (``m`` in the lanes under
    # ``ML_SPLIT``, ``l`` in the others) after ``vs_ref``; a row without a
    # seat starts from the constants ``_init`` fills otherwise.
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
    elif carried:
        (layer_ref, table_ref, lens_ref, seat_ref, q_ref, ks_ref, vs_ref,
         acc0_ref, ml0_ref, kv_hbm, o_ref, kv_scr, m_scr, l_scr, acc_scr,
         sems) = refs
        sc_hbm = sc_scr = sc_sems = None
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

    def _fresh():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    if carried:
        n_seats = acc0_ref.shape[0]

        @pl.when(j == 0)
        def _init():
            # what the prefix pass left for each row's seat: the row's own
            # pages go on from it (a row without a seat from the constants)
            def row(s, _):
                seat = seat_ref[bb * sb + s]
                taken = seat < n_seats
                at = jnp.minimum(seat, n_seats - 1)
                ml = ml0_ref[at]                              # [Hq,LANES]
                m_scr[s, :Hq] = jnp.where(
                    taken, jnp.broadcast_to(ml[:, :1], (Hq, LANES)), NEG_INF)
                l_scr[s, :Hq] = jnp.where(
                    taken,
                    jnp.broadcast_to(
                        ml[:, ML_SPLIT:ML_SPLIT + 1], (Hq, LANES)),
                    0.0)
                acc_scr[s, :Hq, :Dv] = jnp.where(taken, acc0_ref[at], 0.0)

            jax.lax.fori_loop(0, sb, row, None)
    else:
        pl.when(j == 0)(_fresh)

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

        # (the program over own pages keeps its rows a loop: a third of what
        # the unrolled rows cost every chunk program to lower, which pays
        # for the prefix program beside it; PERF.md §6, PR 50)
        jax.lax.fori_loop(0, sb, row, None, unroll=not carried)

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


def _prefix_kernel(
    layer_ref,   # [1] int32 scalar-prefetch: which layer of the pool
    table_ref,   # [G, M] int32: the pages the seats of each block share
    lens_ref,    # [G] int32: their positions, whole pages (0: not in use)
    rows_ref,    # [G, R] int32: the row of ``q_ref`` in each seat
    q_ref,       # [B, Hq, D] WHOLE in VMEM
    kv_hbm,      # [L, P, 2, Hkv, page, D] whole pool, ANY/HBM
    acc_out,     # [G * R, Hq, D] f32: the state BY SEAT
    ml_out,      # [G * R, Hq, LANES] f32: m under ML_SPLIT, l in the rest
    kv_scr,      # [2, 2, Hkv, KP*page, D] ONE stripe, double-buffered
    m_scr,       # [Hkv, R * heads, LANES] f32
    l_scr,
    acc_scr,     # [Hkv, R * heads, D] f32
    sems,        # DMA semaphores [2, KP]
    qf_scr,      # [Hkv, R * heads, D] f32: the block's folded queries
    ord_scr,     # [3] int32 SMEM: the buffer of the next step, the issues
                 # so far, the block whose first step is already started
    *,
    scale: float,
    page: int,
    kp: int,
    n_blocks: int,
    n_kv: int,
    heads: int,
    seats: int,
    soft_cap: Optional[float],
):
    """The PREFIX program, ``paged_decode_prefix``: grid ``(G,)``, a step a
    GROUP BLOCK (:class:`SharedPrefix`). A block copies each page its seats
    share ONCE, ``kp`` pages a step of an inner loop that runs as far as the
    block's pages go (a block not in use costs the one test), and multiplies
    it once against all its seats' queries: they are gathered from ``q_ref``
    by row and folded with the ``heads`` query heads of a kv head into the
    row dimension of ONE dot on that head's K tile (``seats * heads`` rows
    where the program over rows has ``heads``). No mask but the shared
    length (every seat sees all of it), no current token. It leaves each
    seat's float32 ``m``, ``l`` and ``acc`` for :func:`decode`'s ``carry``.
    The copies of a step run under the dots of the step before, across
    blocks too: a block's last step starts the first of the next block in
    use (blocks in use come first, :func:`shared_prefix`; a block that
    finds its first step not started starts it itself)."""
    g = pl.program_id(0)
    D = q_ref.shape[2]
    S = kp * page
    n_rep = seats * heads     # rows of the fold a kv head
    layer = layer_ref[0]
    steps = pl.cdiv(lens_ref[g], S)

    def _issue(b, j, buf):
        """Start the copies of step ``j`` of block ``b`` into ``buf`` (the
        call's first two issues also zero the VALUES of the stripes they
        start no copy into: see ``_decode_kernel._issue``)."""
        n_pages = lens_ref[b] // page
        issued = ord_scr[1]

        def entry(i, _):
            at = pl.ds(pl.multiple_of(i * page, page), page)
            held = j * kp + i < n_pages

            @pl.when(held)
            def _start():
                pltpu.make_async_copy(
                    kv_hbm.at[layer, table_ref[b, j * kp + i]],
                    kv_scr.at[buf, :, :, at, :],
                    sems.at[buf, i],
                ).start()

            @pl.when(jnp.logical_not(held) & (issued < 2))
            def _zero():
                kv_scr[buf, 1, :, at, :] = jnp.zeros(
                    (n_kv, page, D), kv_scr.dtype)

        jax.lax.fori_loop(0, kp, entry, None, unroll=True)
        ord_scr[1] = issued + 1

    @pl.when(g == 0)
    def _prologue():
        ord_scr[0] = 0
        ord_scr[1] = 0
        ord_scr[2] = -1

    @pl.when(steps > 0)
    def _block():
        @pl.when(ord_scr[2] != g)
        def _first():
            _issue(g, 0, ord_scr[0])

        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        # the seats' queries, folded: seat ``r``'s ``heads`` rows of every
        # kv head. Through float32 (the rows start anywhere in a tile), and
        # ONE traced visit for all seats, as ``_each_entry``'s: as Python
        # loops over seats x kv heads this and ``seat_out`` were 512 stores
        # to trace and lower in every chunk program at 16 kv heads (the
        # loops unrolled run 0.006 ms a call faster at 2 kv heads and cost
        # seconds of every start; one reshaped store a seat, ``[Hq, D]`` to
        # ``[Hkv, heads, D]``, is slower than a store a kv head: PERF.md
        # §6, PR 50)
        def seat_in(r, _):
            row = jnp.minimum(rows_ref[g, r], q_ref.shape[0] - 1)
            q_row = q_ref[row].astype(jnp.float32)            # [Hq_row, D]
            for h in range(n_kv):
                qf_scr[h, pl.ds(r * heads, heads), :] = (
                    q_row[h * heads:(h + 1) * heads])

        jax.lax.fori_loop(0, seats, seat_in, None)
        q = qf_scr[...].astype(q_ref.dtype)                   # [Hkv,n_rep,D]
        nxt = jnp.minimum(g + 1, n_blocks - 1)
        next_in_use = (g + 1 < n_blocks) & (lens_ref[nxt] > 0)
        n_len = lens_ref[g]

        def step(j, _):
            buf = ord_scr[0]
            last = j + 1 >= steps

            @pl.when(jnp.logical_not(last) | next_in_use)
            def _prefetch():
                _issue(jnp.where(last, nxt, g), jnp.where(last, 0, j + 1),
                       1 - buf)

            @pl.when(last & next_in_use)
            def _handed():
                ord_scr[2] = nxt

            def wait(i, _):
                @pl.when(j * kp + i < n_len // page)
                def _wait():
                    pltpu.make_async_copy(
                        kv_hbm.at[layer, table_ref[g, j * kp + i]],
                        kv_scr.at[
                            buf, :, :,
                            pl.ds(pl.multiple_of(i * page, page), page), :],
                        sems.at[buf, i],
                    ).wait()

            jax.lax.fori_loop(0, kp, wait, None, unroll=True)
            ord_scr[0] = 1 - buf
            k = kv_scr[buf, 0]                                # [Hkv,S,D]
            v = kv_scr[buf, 1]
            sc = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ) * scale                                         # [Hkv,n_rep,S]
            if soft_cap is not None:
                sc = soft_cap * jnp.tanh(sc / soft_cap)
            kpos = j * S + jax.lax.broadcasted_iota(
                jnp.int32, (n_kv, n_rep, S), 2)
            mask = kpos < n_len
            sc = jnp.where(mask, sc, NEG_INF)
            m_prev = m_scr[:, :, 0:1]                         # [Hkv,n_rep,1]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=2, keepdims=True))
            p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)     # [Hkv,n_rep,S]
            corr = jnp.exp(
                jnp.where(m_prev > NEG_INF / 2, m_prev - m_new, 0.0))
            l_new = corr * l_scr[:, :, 0:1] + jnp.sum(
                p, axis=2, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )                                                 # [Hkv,n_rep,D]
            acc_scr[...] = acc_scr[...] * corr + pv
            m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

        jax.lax.fori_loop(0, steps, step, None)
        # the state by SEAT: seat ``r``'s ``heads`` rows of every kv head
        # are its query heads in order
        lane = jax.lax.broadcasted_iota(jnp.int32, m_scr.shape, 2)
        m_scr[...] = jnp.where(lane < ML_SPLIT, m_scr[...], l_scr[...])

        def seat_out(r, _):
            piece = pl.ds(r * heads, heads)
            for h in range(n_kv):
                to = pl.ds(h * heads, heads)
                acc_out[g * seats + r, to, :] = acc_scr[h, piece, :]
                ml_out[g * seats + r, to, :] = m_scr[h, piece, :]

        jax.lax.fori_loop(0, seats, seat_out, None)


def decode_prefix(
    q: jnp.ndarray,          # [B, Hq, D] the step's queries
    pages: jnp.ndarray,      # [L, P, 2, Hkv, page, D] the WHOLE pool
    layer: jnp.ndarray,      # scalar i32 layer index
    table: jnp.ndarray,      # [G, M] i32: each block's shared pages
    lens: jnp.ndarray,       # [G] i32: their positions (whole pages)
    rows: jnp.ndarray,       # [G, R] i32: the row of ``q`` in each seat
    *,
    softmax_scale: Optional[float] = None,
    soft_cap: Optional[float] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The prefix pass of a full-attention layer (module docstring, "SHARED
    PAGES"; :func:`_prefix_kernel`): the pages each block's seats share are
    copied once a block and multiplied once against all its seats' queries.
    Returns the float32 softmax state by seat, ``(acc [G * R, Hq, D], ml [G
    * R, Hq, LANES])``, for :func:`decode`'s ``carry``; the seats of a
    block not in use are not written (no row's ``seat`` names them). A K/V
    pool in the serving dtype."""
    B, Hq, D = q.shape
    L, P, streams, Hkv, page, _ = pages.shape
    G, M = table.shape
    seats = rows.shape[1]
    heads = Hq // Hkv
    if streams != 2 or jnp.dtype(pages.dtype) == jnp.int8:
        raise ValueError(
            f"the prefix program reads a K/V pool in the serving dtype; "
            f"got {pages.shape} {pages.dtype}")
    if softmax_scale is None:
        softmax_scale = D ** -0.5
    kp = min(PAGES_PER_STEP, M)
    folded = (Hkv, seats * heads)
    resident = pl.BlockSpec(memory_space=pltpu.MemorySpace.VMEM)
    return pl.pallas_call(
        functools.partial(
            _prefix_kernel, scale=softmax_scale, page=page, kp=kp,
            n_blocks=G, n_kv=Hkv, heads=heads, seats=seats,
            soft_cap=soft_cap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(G,),
            in_specs=[
                resident, pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
            out_specs=[resident, resident],
            scratch_shapes=[
                pltpu.VMEM((2, 2, Hkv, kp * page, D), pages.dtype),
                pltpu.VMEM((*folded, LANES), jnp.float32),
                pltpu.VMEM((*folded, LANES), jnp.float32),
                pltpu.VMEM((*folded, D), jnp.float32),
                pltpu.SemaphoreType.DMA((2, kp)),
                pltpu.VMEM((*folded, D), jnp.float32),
                pltpu.SMEM((3,), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((G * seats, Hq, D), jnp.float32),
            jax.ShapeDtypeStruct((G * seats, Hq, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_scratch_bytes(
                1, kp, page, Hkv, D, pages.dtype)
            + _resident_bytes(B, Hq, Hkv, D, D, q.dtype, True)
            + _state_bytes(G * seats, Hq, D) + 32 * 2**20,
        ),
        interpret=_interpret(),
        # a name the benchmark's rooflines find (``^paged_decode``)
        name="paged_decode_prefix",
    )(jnp.asarray(layer, jnp.int32).reshape(1), table, lens, rows, q, pages)


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
    pages_per_step: Optional[int] = None,
    slots_per_step: int = 8,
    scales: Optional[jnp.ndarray] = None,  # [L, P, 2, Hkv, page] f32
    value_width: Optional[int] = None,
    carry: Optional[Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]] = None,
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
    first visible position on (module docstring).

    ``carry``: the state :func:`decode_prefix` left by seat and each
    row's seat, ``(acc, ml, seat [B])`` (module docstring, "SHARED PAGES"),
    which the rows' own pages go on from: ``table`` and ``lens`` are then
    each row's pages and positions BEHIND the ones its group shares. A K/V
    pool in the serving dtype under full attention."""
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
    carried = carry is not None
    if carried and (quantized or latent or sliding_window is not None):
        raise ValueError(
            "a carried state goes on over a K/V pool in the serving dtype "
            "under full attention"
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
        streams, windowed,
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
        carried=carried,
    )
    # q, the current token's K/V and the output are WHOLE in VMEM for the
    # call: one copy in before the grid and one out after it. As blocks of
    # ``sb`` rows the pipeline copied them at every change of block, which
    # from HBM costs a loaded call 7-10 %; a caller whose program already
    # keeps them in VMEM (the engine's ``jit_chunk``) pays neither (module
    # docstring)
    resident = pl.BlockSpec(memory_space=pltpu.MemorySpace.VMEM)
    # (a carried state is two more)
    small = (
        [q, k_self] if latent
        else [q, k_self, v_self, *(carry[:2] if carried else ())]
    )
    in_specs = [
        *([resident] * len(small)),
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
          if windowed else [carry[2]] if carried else []),
        *small, pages,
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
            num_scalar_prefetch=4 if windowed or carried else 3,
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
            + (_state_bytes(carry[0].shape[0], Hq, D) if carried else 0)
            + 32 * 2**20,
        ),
        interpret=_interpret(),
        # the kernel's name in the compiled program and the device trace
        name=(
            "mla_decode" if latent
            else "paged_decode_int8" if quantized else "paged_decode"
        ) + ("_window" if windowed else ""),
    )(*operands)

