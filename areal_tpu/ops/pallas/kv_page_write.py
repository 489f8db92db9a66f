"""Fresh K/V into the page pool, tile by tile (Pallas TPU kernel).

What ``models/transformer.py:_scatter_chunk_kv`` does with one XLA row
scatter, done by copies of whole TILES: after the layer scan every batch
row has a RUN of ``count`` fresh tokens for positions ``[start, start +
count)`` of its sequence, in every layer, and their rows go into the
slot's own pages of the pool ``[L, P, S, H, page, W]``.

Why not the scatter: on a TPU v5e XLA's scatter pays per ROW, serially,
70 ns for every 256-byte row whatever the row count (the three K/V
rollout cells of the ledger's PR 30 lines: 14,336 / 8,192 / 16,384 rows a
decode step in 1.00 / 0.58 / 1.14 ms), while the bytes are nothing (3.7
MB a step, 4.5 us at 819 GB/s).

Why tiles: a bf16 array keeps two rows in each 32-bit sublane word, so
the smallest piece of the pool a DMA can address along the token axis is
the ``(16, 128)`` tile (:func:`tile_rows`), and one row cannot be copied
into it. The unit here is the SLAB: that tile for ALL streams and heads
of a token at once, ``pages[l, p, :, :, t*R:(t+1)*R, :]`` (``S*H``
contiguous pieces of ``R x W``: 16 / 32 / 128 KB at 2 / 4 / 16 kv heads x
128, 20 KB for a 640-wide latent row). For every (layer, batch row) with
a valid token the kernel reads the slab(s) its run touches into VMEM,
puts the fresh rows in under an ``lo <= iota < hi`` mask, and writes the
slab back; a slab that lies whole inside the run is written without the
read. One token a row (decode) is one slab a (layer, row); a chunk of
``C`` tokens (admission's 128) touches at most ``(C + R - 2) // R + 1``.

The pool is in ``ANY`` (HBM) and aliased to the result: it is updated IN
PLACE, nothing else in it is touched, and it is BIT-equal to what the
scatter leaves (the scatter is this kernel's plain reference and its
fallback: ``ops/paged_attention.py:kv_write_kernel_applies``).

Read-modify-write of a tile without a lock is safe because the prefix
registry shares PAGE-ALIGNED prefixes only (``gen/pages.py``): the page a
slot writes is its own, so no two batch rows of a call touch one tile,
and two layers never share one. Rows with no valid token (free and
finished slots, padding rows of an admission wave, whose table entries
may point at page 0) are skipped, never read-modified-written.

A step of work owns the slabs of ``SB`` batch rows in one layer (``L x
B / SB`` of them, in order), and the steps form a software pipeline over
a RING of ``ahead + lag`` VMEM buffers, one grid step each: grid step
``v`` first waits for the writes of step ``v - ahead - lag`` (whose
buffer comes free), starts the reads of step ``v`` into it, then waits
for the reads of step ``v - ahead``, merges it, and starts its writes
(the grid is ``ahead + lag`` steps longer than the work). So ``SB x
(ahead + lag)`` slabs are in flight: one at a time would pay a DMA's
latency 3,584 times a step in the 1.5B cell, which is the disease being
cured. :func:`write_plan` picks ``SB`` from the slab's size so the ring
stays inside ``RING_BYTES``.

Measured alone on a TPU v5e (PERF.md §6, PR 31; ms a call, scatter ->
kernel, one token a row at the rollout cells' pools and populations, a
thirty-second of the rows free; in brackets with every row valid): 1.062
-> 0.399 [0.313] (28 layers x 128 rows, 16 KB slabs; 0.31 in the cell),
0.624 -> 0.189 [0.150] (16 x 64, 32 KB), 1.188 -> 0.246 [0.248] (8 x 64,
128 KB: 130 MB moved, the only one near its bytes), 0.183 -> 0.182
[0.142] (5 x 256 latent rows, 20 KB); an admission wave of 8 x 128 tokens
8.10 -> 0.65, 8.97 -> 0.51, 18.16 -> 0.75, 0.72 -> 0.24. At small slabs a
call is what the SCALAR core does a slab, not bytes or latency: the depth
of the ring moves nothing (``AHEAD``/``LAG`` 1/1, 2/2, 3/3: 0.330 / 0.326
/ 0.391 ms in an earlier, fully unrolled form), how the passes are
written moves everything (the kernel's comments say which form is where,
and why). What a START of the server pays for the kernel is tracing and
lowering it, once for every program that holds it: each pass of copies
is written once (four, not eight), and admission's chunks share one
write program (``gen/engine.py:_kv_write_fn``).
"""

import functools
import itertools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# the ring of slab buffers may take this much VMEM (128 KB slabs, the
# OLMoE cell's, then run 16 rows a step at 4 buffers)
RING_BYTES = 8 * 1024 * 1024
# batch rows of one grid step, at most: a step with ONE free slot in it
# takes the loop for all its rows (2.6 x the unrolled form's time), so
# fewer rows a step lose less to it (alone, 1.5B geometry, 4 of 128 rows
# free: 0.43 ms a call at 32 rows a step, 0.35 at 16; all valid 0.24 /
# 0.26), and the unrolled passes are traced at every start
MAX_SLOTS_PER_STEP = 16
AHEAD = 2   # steps whose reads are started before the step that merges
LAG = 2     # steps a write may stay in flight before its buffer is reused


def _interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


def _div(x, c: int):
    """``x // c`` of a scalar that is not negative, by a static ``c``: a
    shift or a truncating division (``//`` on a tracer floors, which is
    three more scalar operations a slab: the kernel's comments)."""
    if isinstance(x, int):
        return x // c
    if c & (c - 1) == 0:
        return x >> (c.bit_length() - 1)
    return jax.lax.div(x, jnp.int32(c))


def _mod(x, c: int):
    """``x % c`` of a scalar that is not negative, by a static ``c``."""
    if isinstance(x, int):
        return x % c
    if c & (c - 1) == 0:
        return x & (c - 1)
    return jax.lax.rem(x, jnp.int32(c))


def _lane_width(width: int) -> int:
    """Lanes of one piece of a row: a lane tile, or (interpret mode only) a
    row narrower than one."""
    return LANES if width % LANES == 0 else width


def tile_rows(pool_dtype) -> int:
    """Rows of the pool's tile along the token axis of a page: what one
    32-bit sublane word holds of this dtype, times 8 sublanes (8 for f32,
    16 for bf16)."""
    return 8 * (4 // jnp.dtype(pool_dtype).itemsize)


def tiles_of_run(start: int, count: int, rows: int) -> int:
    """Tiles of ``rows`` positions that the run ``[start, start + count)``
    touches (host integers: the engine's ``kv_write_tiles``)."""
    if count <= 0:
        return 0
    return (start + count - 1) // rows - start // rows + 1


def write_plan(
    batch: int, chunk: int, slab_bytes: int, rows: int
) -> Tuple[int, int]:
    """``(sb, tiles)``: batch rows of one grid step and the slabs a row's
    run of ``chunk`` tokens can touch. ``sb`` is the largest power of two
    that divides the batch, is at most ``MAX_SLOTS_PER_STEP`` and keeps
    the ring of ``AHEAD + LAG`` buffers of ``sb * tiles`` slabs inside
    ``RING_BYTES``."""
    tiles = (chunk + rows - 2) // rows + 1
    sb = 1
    while (
        sb * 2 <= MAX_SLOTS_PER_STEP
        and batch % (sb * 2) == 0
        and (AHEAD + LAG) * sb * 2 * tiles * slab_bytes <= RING_BYTES
    ):
        sb *= 2
    return sb, tiles


def _write_kernel(
    pg_ref,      # [B * NP] i32 scalar-prefetch: the pages of each row's run
    start_ref,   # [B] i32 scalar-prefetch: first position of the run
    count_ref,   # [B] i32 scalar-prefetch: valid tokens of the run (0: skip)
    fresh_ref,   # C == 1: [SB, S*H, W]; else [SB, S, H, W/128, CP, 128]
    _pool_in,    # the pool, aliased to pool_ref: never touched by name
    pool_ref,    # [L, P, S, H, page, W] ANY/HBM, updated in place
    ring,        # [NBUF, SB * T, S, H, R, W] VMEM slab buffers
    rsem,        # DMA semaphores [NBUF]: reads into a buffer
    wsem,        # DMA semaphores [NBUF]: writes out of a buffer
    full,        # SMEM [NBUF] i32: every row of the buffer's step has a token
    *,
    page: int,
    chunk: int,
    sb: int,
    tiles: int,
    n_pages_run: int,
    n_layers: int,
    nb: int,
):
    total = n_layers * nb
    nbuf = AHEAD + LAG
    S, H, R, W = ring.shape[2:]
    assert (tiles == 1) == (chunk == 1)
    # what a wait needs of its copy is the semaphore and the byte count
    any_slab = pool_ref.at[0, 0, :, :, pl.ds(0, R), :]

    # The scalar core does all of this for every slab, four times a step
    # (start and wait of the read, start and wait of the write) and once
    # more for the merge, and at 16 KB slabs IT is what a call costs
    # (module docstring). So: shifts for the powers of two, truncating
    # division elsewhere (positions are not negative), nothing a pass does
    # not use, and a wait that builds no address.
    # Two forms of every pass of copies. Where each row writes ONE token
    # and every row of the step has one (``full``: nearly every step
    # of a decode chunk), the pass is unrolled with no test a slab: a
    # slab's scalar chain is a dependent one (load, address, descriptor),
    # and only unrolled do the chains of neighbouring slabs overlap
    # (alone, 1.5B geometry, every row valid: 0.63 ms a call as loops,
    # 0.24-0.26 unrolled). Everything else (a step with a free or
    # finished slot in it, every chunk of several tokens) is a LOOP over
    # the step's slabs with the tests inside: its body is traced and
    # lowered once, and every program that holds the kernel is traced
    # again at every start of the server (every pass unrolled WITH its
    # tests, 32 ``pl.when`` a pass, was 0.33 ms a call, but 2.5-5 s of
    # tracing a program and 25 s of the 1.5B cell's set-up).
    def _each_slab(s_t, slab):
        """``slab(layer, b, u, i, j)`` for every slab ``u`` of step ``s_t``
        (the ``j``-th tile of its ``i``-th row, ``b``), in a loop."""
        layer, b0 = _div(s_t, nb), _mod(s_t, nb) * sb

        def each(u, carry):
            i, j = (u, 0) if tiles == 1 else (_div(u, tiles), _mod(u, tiles))
            slab(layer, b0 + i, u, i, j)
            return carry

        jax.lax.fori_loop(0, sb * tiles, each, 0)

    def _span(b, j):
        """``(lo0, lo, hi)``: rows ``[lo, hi)`` of the ``j``-th tile of row
        ``b``'s run that the run fills (it touches the tile where ``hi >
        lo``), from the offset of its first token in ITS tile, ``lo0``."""
        lo0 = _mod(start_ref[b], R)
        lo = lo0 if tiles == 1 else jnp.where(j == 0, lo0, 0)
        end = lo0 + count_ref[b] - j * R
        return lo0, lo, jnp.minimum(jnp.maximum(end, lo), R)

    def _tile(layer, b, j, lo0):
        """The ``j``-th tile of row ``b``'s run in the pool, all streams and
        heads."""
        # rows past the start of the run's first page: a multiple of R
        q = _mod(start_ref[b], page) - lo0 + j * R
        if tiles == 1:
            k, t0 = 0, q
        else:
            k = jnp.minimum(_div(q, page), n_pages_run - 1)
            t0 = _mod(q, page)
        p = pg_ref[b * n_pages_run + k]
        return pool_ref.at[layer, p, :, :, pl.ds(pl.multiple_of(t0, R), R), :]

    def _own_tile(layer, b):
        """:func:`_tile` of a row that writes one token, in as few scalar
        operations as it takes (a power-of-two page: one mask)."""
        s0 = start_ref[b]
        t0 = _mod(s0, page) - _mod(s0, R) if page & (page - 1) else (
            s0 & ((page - 1) & ~(R - 1)))
        return pool_ref.at[
            layer, pg_ref[b], :, :, pl.ds(pl.multiple_of(t0, R), R), :
        ]

    def _copies(s_t, out: bool, wait: bool):
        """One pass over step ``s_t``: the starts, or the waits, of its
        reads into the ring, or (``out``) of its writes back. Unrolled
        over its rows with no test where that form applies, else
        :func:`_each_slab`; the start of a step's reads, its first pass,
        finds out which, for the later ones."""
        buf = _mod(s_t, nbuf)
        sem = (wsem if out else rsem).at[buf]

        def copy(tile, u):
            mine = ring.at[buf, u]
            dma = pltpu.make_async_copy(
                *((mine, tile) if out else (tile, mine)), sem)
            dma.wait() if wait else dma.start()

        def slab(layer, b, u, i, j):
            lo0, lo, hi = _span(b, j)
            # a slab the run covers whole is written without the read
            needed = hi > lo if out else (hi > lo) & ((lo > 0) | (hi < R))

            @pl.when(needed)
            def _():
                copy(any_slab if wait else _tile(layer, b, j, lo0), u)

        if chunk > 1:
            _each_slab(s_t, slab)
            return
        layer, b0 = _div(s_t, nb), _mod(s_t, nb) * sb
        if not (out or wait):
            # the least count of the step's rows: 1 where all have a token
            # (kept in the kernel: as one more small XLA op a decode step,
            # it made XLA rematerialise the 129k-vocabulary head twice
            # more in the JoyAI cell's chunk program, 3 % of its step)
            least = count_ref[b0]
            for i in range(1, sb):
                least = jnp.minimum(least, count_ref[b0 + i])
            full[buf] = least
        unrolled = full[buf] > 0

        @pl.when(unrolled)
        def _():
            for i in range(sb):
                copy(any_slab if wait else _own_tile(layer, b0 + i), i)

        @pl.when(jnp.logical_not(unrolled))
        def _():
            _each_slab(s_t, slab)

    def _merge(s_t):
        buf = _mod(s_t, nbuf)
        lw = _lane_width(W)
        row_id = jax.lax.broadcasted_iota(jnp.int32, (R, lw), 0)

        def slab(layer, b, u, i, j):
            lo0, lo, hi = _span(b, j)

            @pl.when(hi > lo)
            def _():
                mask = (row_id >= lo) & (row_id < hi)
                # chunk token c sits at fresh index R + c (the caller pads
                # R in front) and tile row r of tile j holds token j * R +
                # r - lo0: the window of this tile
                w0 = (j + 1) * R - lo0
                for s, h, k in itertools.product(
                    range(S), range(H), range(W // lw)
                ):
                    # one lane tile at a time: Mosaic takes the window's
                    # unaligned dynamic start only where the block's rows
                    # are one lane tile wide
                    lanes = pl.ds(k * lw, lw)
                    if chunk == 1:
                        new = jnp.broadcast_to(
                            fresh_ref[i, pl.ds(s * H + h, 1), lanes], (R, lw)
                        )
                    else:
                        new = fresh_ref[i, s, h, k, pl.ds(w0, R), :]
                    old = ring[buf, u, s, h, :, lanes]
                    ring[buf, u, s, h, :, lanes] = jnp.where(
                        mask, new.astype(old.dtype), old
                    )

        # vector work, not a scalar chain: always the loop (alone, unrolled
        # or not: 0.236 / 0.243 ms a call at the 1.5B geometry)
        _each_slab(s_t, slab)

    # The software pipeline, each pass written ONCE (a pass is what tracing
    # and lowering the kernel cost, at every start of every program that
    # holds it): grid step v starts the reads of step v, merges and writes
    # back step v - AHEAD, and first of all waits for the writes of step
    # v - AHEAD - LAG, whose buffer the reads of step v take. The grid is
    # AHEAD + LAG steps longer than the work; the ends run short of passes.
    v = pl.program_id(0)

    @pl.when(v >= nbuf)
    def _buffer_free():
        _copies(v - nbuf, out=True, wait=True)

    @pl.when(v < total)
    def _prefetch():
        _copies(v, out=False, wait=False)

    @pl.when((v >= AHEAD) & (v < total + AHEAD))
    def _own():
        _copies(v - AHEAD, out=False, wait=True)
        _merge(v - AHEAD)
        _copies(v - AHEAD, out=True, wait=False)


def write(
    pages: jnp.ndarray,    # [L, P, S, H, page, W] the WHOLE pool
    fresh: jnp.ndarray,    # [L, B, C, S, H, W] the chunk's rows, every layer
    table: jnp.ndarray,    # [B, M] i32
    start: jnp.ndarray,    # [B] first position each row writes
    count: jnp.ndarray,    # [B] valid tokens of the row's chunk (<= C)
) -> jnp.ndarray:
    """The pool with ``fresh[l, b, c]`` at position ``start[b] + c`` of row
    ``b``'s pages in layer ``l`` for every ``c < count[b]``, everything
    else as it was; the same array, updated in place where the caller
    donates it."""
    L, P, S, H, page, W = pages.shape
    B, C = fresh.shape[1:3]
    M = table.shape[1]
    R = tile_rows(pages.dtype)
    if page % R or (not _interpret() and W % LANES):
        raise ValueError(
            f"kv_page_write needs page%{R}==0 and width%128==0; got "
            f"page={page}, width={W}: use the XLA scatter"
        )
    n_pages_run = (C + page - 2) // page + 1
    # each row's pages for the run it writes (clipped like the scatter's)
    run_pages = jnp.take_along_axis(
        table,
        jnp.clip(
            (start // page)[:, None] + jnp.arange(n_pages_run)[None, :],
            0, M - 1,
        ),
        axis=1,
    ).reshape(-1).astype(jnp.int32)
    fresh = fresh.astype(pages.dtype)
    start = start.astype(jnp.int32)
    count = jnp.clip(count, 0, C).astype(jnp.int32)
    slab_bytes = S * H * R * W * pages.dtype.itemsize
    sb, tiles = write_plan(B, C, slab_bytes, R)
    nb = B // sb
    last = L * nb - 1

    def block(v, *_):
        """(layer, block of rows) that grid step ``v`` merges."""
        s_t = jnp.clip(v - AHEAD, 0, last)
        return _div(s_t, nb), _mod(s_t, nb)

    if C == 1:
        fresh = fresh.reshape(L, B, S * H, W)
        fresh_spec = pl.BlockSpec(
            (None, sb, S * H, W), lambda *a: block(*a) + (0, 0)
        )
    else:
        # [L, B, S, H, W / 128, CP, 128]: tokens on the sublane axis like
        # the pool's, one lane tile a row (a 640-wide latent row is five),
        # R of padding in front and enough behind that every tile's window
        # of R rows is inside the block; f32, whose rows a window can
        # start between (two bf16 rows share a sublane word), and exact
        lw = _lane_width(W)
        cp = R * (tiles + 1)
        fresh = jnp.pad(
            fresh.reshape(L, B, C, S, H, W // lw, lw)
            .transpose(0, 1, 3, 4, 5, 2, 6).astype(jnp.float32),
            ((0, 0),) * 5 + ((R, cp - R - C), (0, 0)),
        )
        fresh_spec = pl.BlockSpec(
            (None, sb, S, H, W // lw, cp, lw),
            lambda *a: block(*a) + (0, 0, 0, 0, 0),
        )
    nbuf = AHEAD + LAG
    kernel = functools.partial(
        _write_kernel, page=page, chunk=C, sb=sb, tiles=tiles,
        n_pages_run=n_pages_run, n_layers=L, nb=nb,
    )
    ring_bytes = nbuf * sb * tiles * slab_bytes
    any_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # the steps of work, and the pipeline's run-in and run-out
            grid=(L * nb + nbuf,),
            in_specs=[fresh_spec, any_spec],
            out_specs=any_spec,
            scratch_shapes=[
                pltpu.VMEM((nbuf, sb * tiles, S, H, R, W), pages.dtype),
                pltpu.SemaphoreType.DMA((nbuf,)),
                pltpu.SemaphoreType.DMA((nbuf,)),
                pltpu.SMEM((nbuf,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(pages.shape, pages.dtype),
        # operands: 3 scalar-prefetch, fresh, pool -> the pool is no. 4
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=ring_bytes + 32 * 2**20,
        ),
        interpret=_interpret(),
        name="kv_page_write",
    )(
        run_pages, start, count, fresh, pages,
    )
