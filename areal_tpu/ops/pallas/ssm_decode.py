"""The decode step's state-space update over the per-slot state of ALL
layers, in place (Pallas TPU kernel ``ssm_decode``).

What ``ops/ssm.py:step_update`` computes for one layer, ``S <- exp(dt A)
S + (dt x) B^T`` and ``y = S C + D x`` for every head of every row, with
the recurrent state read ONCE and written ONCE. XLA's form of the same
update is two fusions a layer, one that reduces the new state to ``y``
and one that writes it back: both read the state, 1.5 x the bytes of a
step that at the published sizes IS the state's bytes (PERF.md §6).

The state operand is the whole ``[Ls, B, G, K, N, 128]`` array of the
engine (``ops/ssm.py`` says why the channels are minor, in ``K`` lane
tiles), left in HBM and aliased to the result, and a layer index from
scalar prefetch: the kernel copies one (row, group)'s ``[K, N, 128]``, one
piece of HBM, at a time into a ring in VMEM, updates it there and copies
it back, so no layer's slice is ever materialised and what it does not
touch stays where it is. The state of a row that is not ``active`` is
neither read nor written.

Layout of the work: ``N`` on the sublanes, the channels ``(r, p)`` on the
lanes. What varies a head or a channel (the decay ``exp(dt A)`` repeated
over ``P``, ``dt x``, ``y``) is a ``[1, R x P]`` row as the model holds
it: a row spreads DOWN the sublanes for nothing, and XLA makes the two
beside the call without a transpose (``ops/ssm.py:step_rows``). What is
the same for every head of the group (``B``, ``C``: ``[1, N]`` rows) is
turned to a column and spread over the lanes ONCE a row, 2 x ``N / 8``
registers that every lane tile of the row uses again. ``y`` is the sum
down the sublanes: vector adds and one 8 -> 1 reduce a lane tile, in
float32. Nothing a head does crosses lanes, and the arithmetic is a
quarter of the call (0.126 ms of it with the copies taken out).

With ``N`` on the lanes (``[H, P, N]`` a row: PR 41's kernel) it was the
other way round, ``dt x`` a ``[P, 1]`` column pushed across the lanes for
every head and ``y`` a lane sum a head, and the call was bound by that.
In this layout it is bound by its copies, so their ORDER is the kernel's
other half: this chip reads HBM at 741 GB/s and writes it at 645 (rows of
2 MiB, one after another), and does the two AT ONCE at 652 together, which
is what a grid's pipeline does (the next block's fetch beside the last
block's write-back). So the copies run in PHASES, ``PHASE_ROWS`` rows of
reads, then as many of writes, never both, and the update of the rows
just read hides under the write-back of the rows before them. One call at
the published sizes is 80 rows of ``[32, 128, 128]`` float32: 0.410 ms of
bytes at the 819 GB/s the chip is sold with, 0.486 at what it reads and
writes. Ms a call, 36 calls a program, alone on one v5e (PERF.md §6 PRs
41 and 42; the call itself by the trace in brackets):

    N on the lanes, decay and dt x as columns, y a lane sum   0.662
    ... decay an SMEM scalar, the lane sum on the MXU (PR 41) 0.555 (0.520)
    channels on the lanes, a grid step a row, pipelined       0.543 (0.511)
    ... the copies alone, no arithmetic                       0.542
    ... the copies alone in phases of 2 / 4 / 8 rows          0.502 / 0.492 / 0.487
    channels on the lanes, phases of 8 rows (this file)       0.499 (0.487)

No gradient, no partitioning rule: ``ssm_decode_applies`` says where the
engine runs it.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops import ssm as ssm_ops

# (row, group) pairs a phase of copies moves: a sublane tile of them, so
# that their small operands, and their ``y``, are ONE aligned copy of [8,
# .] float32 between arrays laid out as XLA keeps them
PHASE_ROWS = 8
# what the ring of two phases may take of VMEM (128 MiB on a v5e)
RING_BYTES = 64 * 2**20


def _interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


def ssm_decode_applies(cfg, mesh=None, platform=None) -> bool:
    """Whether a generation engine's decode steps update the recurrent
    state with this kernel or with ``ops/ssm.py:step_update``, from what it
    can observe: ONE TPU device, a float32 state whose groups are whole
    lane tiles, whose ``N`` turns to a column in whole tiles, and of which
    two phases of (row, group) pairs fit the ring."""
    if platform is None:
        platform = jax.devices()[0].platform
    s = cfg.ssm
    if s is None:
        return False
    lanes = s.n_heads // s.n_groups * s.head_dim
    return (
        platform == "tpu"
        and (mesh is None or mesh.size == 1)
        and s.state_dtype == "float32"
        and not s.selective     # a decay a (channel, state) pair: XLA's
        and lanes % ssm_ops.LANES == 0
        and s.d_state % ssm_ops.LANES == 0
        and 2 * PHASE_ROWS * 4 * s.d_state * lanes <= RING_BYTES
    )


def _kernel(li_ref, act_ref, s_hbm, x_hbm, y_hbm, o_hbm,
            ring, xring, yring, rsem, xsem, wsem, ysem):
    """Batch ``k`` is the (row, group) pairs ``[k R, (k + 1) R)`` (``R`` =
    ``PHASE_ROWS``; pair ``u`` is row ``u // G``, group ``u % G``), in half
    ``k % 2`` of the ring; those of rows that are not active are passed
    over."""
    li = li_ref[0]
    G = s_hbm.shape[2]
    _, K, N, tile = ring.shape
    W = K * tile
    R = PHASE_ROWS
    n_batches = x_hbm.shape[0] // R

    def pair(k, u):       # (row, group) of batch k's u-th
        return divmod(k * R + u, G)

    def copies(k, out: bool, wait: bool):
        """Start, or wait for, batch ``k``'s copies in (state and the small
        operands) or out (state and ``y``)."""
        half = k % 2
        src, dst, sem = (
            (yring.at[half], y_hbm.at[pl.ds(k * R, R)], ysem) if out
            else (x_hbm.at[pl.ds(k * R, R)], xring.at[half], xsem))
        dma = pltpu.make_async_copy(src, dst, sem.at[half])
        dma.wait() if wait else dma.start()

        def one(u, carry):
            row, g = pair(k, u)

            @pl.when(act_ref[row] != 0)
            def _():
                slot = half * R + u
                state = (o_hbm if out else s_hbm).at[li, row, g]
                src, dst, sem = (
                    (ring.at[slot], state, wsem) if out
                    else (state, ring.at[slot], rsem))
                dma = pltpu.make_async_copy(src, dst, sem.at[slot])
                dma.wait() if wait else dma.start()
            return carry

        jax.lax.fori_loop(0, R, one, 0)

    def update(k):
        half = k % 2

        def one(u, carry):
            @pl.when(act_ref[pair(k, u)[0]] != 0)
            def _():
                s_ref = ring.at[half * R + u]
                mine = jax.lax.broadcasted_iota(jnp.int32, (R, tile), 0) == u

                def small(at, width):
                    # [1, width] of the pair's row of the phase's [R, .]:
                    # rolled up to sublane 0 (a load cannot start at a
                    # sublane that is not known when the kernel is built)
                    rows = xring[half, :, at : at + width]
                    return pltpu.roll(rows, R - u, 0)[:1]

                def column(at):   # [1, N] -> [N, tile], entry n on every lane
                    return jnp.broadcast_to(small(at, N), (tile, N)).T

                b, c = column(2 * W), column(2 * W + N)
                for k_ in range(K):
                    t = k_ * tile
                    at = slice(t, t + tile)
                    s = s_ref[k_] * small(t, tile) + b * small(W + t, tile)
                    s_ref[k_] = s
                    y = jnp.sum(s * c, axis=0, keepdims=True)
                    yring[half, :, at] = jnp.where(mine, y, yring[half, :, at])
            return carry

        jax.lax.fori_loop(0, R, one, 0)

    copies(0, out=False, wait=False)

    def batch(k, carry):
        # reads and writes take turns; the update hides under a phase
        copies(k, out=False, wait=True)

        if n_batches > 1:
            @pl.when(k == 0)
            def _():
                copies(1, out=False, wait=False)

        @pl.when(k > 0)
        def _():
            copies(k - 1, out=True, wait=False)

        update(k)

        @pl.when(k > 0)
        def _():
            copies(k - 1, out=True, wait=True)

            @pl.when(k + 1 < n_batches)
            def _():
                copies(k + 1, out=False, wait=False)
        return carry

    jax.lax.fori_loop(0, n_batches, batch, 0)
    copies(n_batches - 1, out=True, wait=False)
    copies(n_batches - 1, out=True, wait=True)


def ssm_decode(ssm_all, layer, x, dt, a, b, c, d_skip, active):
    """``ssm_all [Ls, B, G, K, N, 128]`` f32 (donated: updated in place),
    ``layer`` int32 scalar, then :func:`ops.ssm.step_update`'s arguments
    (``x [B, G, R, P]``, ``dt [B, G, R]``, ``a, d_skip [G, R]``, ``b, c [B,
    G, N]``); ``active [B]``: rows that are not keep their state and get
    ``y`` 0. Returns ``(y [B, G, R, P], ssm_all)``."""
    Ls, B, G, K, N, lanes = ssm_all.shape
    W = K * lanes
    # a (row, group) pair's small operands as ONE row: [decay ; dt x ; B ;
    # C], the pairs padded to whole phases
    decay, dtx = ssm_ops.step_rows(x, dt, a)
    small = jnp.concatenate([decay, dtx, b, c], axis=-1).reshape(B * G, -1)
    pad = -(B * G) % PHASE_ROWS
    small = jnp.pad(small, ((0, pad), (0, 0)))
    act = jnp.pad(active.astype(jnp.int32), (0, -(-pad // G)))
    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    y, ssm_all = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[hbm, hbm],
            out_specs=[hbm, hbm],
            scratch_shapes=[
                pltpu.VMEM((2 * PHASE_ROWS, K, N, lanes), jnp.float32),
                pltpu.VMEM((2, PHASE_ROWS, 2 * W + 2 * N), jnp.float32),
                pltpu.VMEM((2, PHASE_ROWS, W), jnp.float32),
                pltpu.SemaphoreType.DMA((2 * PHASE_ROWS,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2 * PHASE_ROWS,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B * G + pad, W), jnp.float32),
            jax.ShapeDtypeStruct(ssm_all.shape, ssm_all.dtype),
        ],
        # operands: 2 scalar-prefetch, the state, the small ones
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * PHASE_ROWS * 4 * N * W + 16 * 2**20),
        interpret=_interpret(),
        name="ssm_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1), act, ssm_all, small)
    # (a row that is not active: its y is whatever the ring held)
    y = jnp.where(active[:, None, None], y[: B * G].reshape(B, G, W), 0.0)
    return ssm_ops.step_out(y, x, d_skip), ssm_all
