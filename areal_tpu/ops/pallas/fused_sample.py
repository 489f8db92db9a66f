"""Fused LM-head + sampling epilogue (Pallas TPU kernel).

One grid step per vocab block: the head block ``W[:, j*BV:(j+1)*BV]``
streams HBM -> VMEM through the pallas pipeline while the hidden states
``x [R, E]`` stay resident, the block's logits come off the MXU in f32,
and the sampling state folds in online. The full ``[R, V]`` logits tensor
never exists in HBM: HBM traffic is exactly one read of the head weight —
the decode-epilogue roofline.

The weight comes in the layout the parameter tree stores it, stated
statically (``vocab_rows``): an untied head ``[E, V]`` in column blocks
``[E, BV]``, or a tied embedding ``[V, E]`` in ROW blocks ``[BV, E]``
(one contiguous run of HBM a grid step), contracted over its E by the
same ``dot_general`` with the other dimension number: the MXU takes the
block either way (on a v5e the two read the same time to 0.2 % at 80-256
rows; logits kept TRANSPOSED, ``[BV, R]`` against a stationary ``x^T``,
read 5.6 % less at 256 rows and the same at 80-128: PERF.md section 6,
PR 46), and everything after the block's logits is one body.
``logits_scale`` (static, ``cfg.logits_scaling``) divides the block's
float32 logits before the soft cap, where
``models/transformer.py:_head`` divides.

The fold is written for the vector unit, which at 256 rows x 129k columns
bounds the call together with the MXU once the weight streams at the
memory's rate. A block's logits are taken in pieces of ``[ROW_GROUP,
128]`` (a few vector registers; a loop over row groups, and within one
over lane tiles, ``TILE_UNROLL`` an iteration, so the program stays small
to trace), and all running state is kept PER LANE, ``[R, 128]``: a
piece folds into it with element-wise compares and selects only, and the
cross-lane reductions (one max a block for the softmax reference, the
arg-max's tie-break at the very end) touch ``[R, 128]``, never
``[R, BV]``. Per piece:

- running raw arg-max per lane (value, tile index): strict ``>`` keeps the
  earliest tile, and the final min over lanes of ``tile * 128 + lane``
  among the lanes at the row max IS ``jnp.argmax``'s tie order (greedy
  slots, token-exact). Its row max is also the block's softmax reference
  (``max(logits) / T == max(logits / T)``), so no second max is taken;
- running sum of ``exp(warped - m)`` per lane (``m``: the row's running
  max, rescaled once a block): the exact log-normaliser ``m + log l``;
- running Gumbel-top-1 arg-max per lane over ``warped - log(-log u)``
  (value, tile index, the warped logit there): the categorical sample and
  its log-prob.

The uniforms ``u`` come from the chip's PRNG (``pltpu.prng_seed`` /
``prng_random_bits``: one instruction a register, reseeded per block from
the scalar-prefetched seed and the block index, so the stream does not
depend on grid order) where the kernel is compiled, and from a
counter-based hash of (seed, row, column) (the murmur3 finaliser, about
twelve integer ops an element) in interpret mode, which has no PRNG
lowering. Either way the draw is an exact Gumbel-top-1 over every valid
column, reproducible for one seed on one path.

Columns past the vocabulary exist in the last block only, and how many of
its columns are real is static: that block's program folds its whole
tiles, masks the one partial tile and skips the rest.

Top-k slots are NOT handled here (the online top-k buffer lives in the
streamed XLA path of ``ops/fused_sample.py``; the engine routes top-k
rows there or to the sorted fallback). The dispatch in
``ops/fused_sample.py`` enforces this.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.3819763e38
LANES = 128
# rows of one piece, at most: [32, 128] f32 is four vector registers, so a
# piece's chain of element-wise ops and the seven per-lane state arrays of
# its row group fit the register file together
ROW_GROUP = 32
# columns of one grid step, at most: a block of 1536+ rows x 2048 columns
# is 6+ MB, far past where a DMA is efficient (1024 and 4096 measured the
# same within 3 % at the five rollout cells' shapes, 512 up to 15 % slower)
MAX_BLOCK_V = 2048
# pieces of a block the compiler sees at once: a loop over a block's lane
# tiles takes this many an iteration, so the scheduler can overlap one
# piece's chain with the next. It matters only where the vector unit
# bounds the call (256 rows x 129,280 columns, ms a call on a v5e at 1 / 2
# / 4 / 8 / 16 an iteration: 1.13 / 1.02 / 0.96 / 0.94 / 0.91; nothing at 64-128
# rows), and every piece is traced and lowered again by each of the dozen
# programs of a rollout cell that hold this kernel, at every start (all
# 16 unrolled: 0.4 s a program in the sandbox, 8: 0.25)
TILE_UNROLL = 8
# what the double-buffered weight block and the block's f32 logits may
# take of a v5e's 128 MiB of VMEM
_VMEM_BUDGET = 40 * 2 ** 20
_BIG_I32 = 2 ** 30  # python literal: a jnp scalar would be a captured const


def _interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


def block_columns(R: int, E: int, V: int, itemsize: int) -> int:
    """Vocabulary columns of one grid step, from the shapes: the most (in
    whole lane tiles, up to ``MAX_BLOCK_V``) whose double-buffered weight
    block (``[E, BV]`` or ``[BV, E]``: the same bytes) and f32 logits
    ``[R, BV]`` (with two temporaries of that size) fit the VMEM budget.
    3584 rows of bf16 leave 2048 columns (14.7 MB a buffer)."""
    per_col = 2 * E * itemsize + 3 * R * 4
    fit = max(LANES, _VMEM_BUDGET // per_col // LANES * LANES)
    return min(MAX_BLOCK_V, fit, -(-V // LANES) * LANES)


def _hash_uniform_bits(row_term, lane_term, col0):
    """uint32 bits of the counter hash at columns ``col0 + lane``:
    ``row_term`` is ``row * c2 ^ seed`` and ``lane_term`` ``lane * c1``,
    both ``[n, 128]`` and made once a grid step."""
    h = (lane_term + col0 * -1640531527) ^ row_term
    h = jax.lax.bitcast_convert_type(h, jnp.uint32)
    h = h ^ (h >> 16)
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _kernel(
    seed_ref, x_ref, w_ref, temp_ref, greedy_ref,
    tok_ref, lp_ref, argmax_ref, norm_ref,
    lg_scr, m_scr, l_scr, amv_scr, ami_scr, gp_scr, gw_scr, gi_scr,
    *, nb: int, block_v: int, vocab: int, soft_cap: Optional[float],
    logits_scale: float, vocab_rows: bool, hw_prng: bool,
):

    j = pl.program_id(0)
    R = x_ref.shape[0]
    n_tiles = block_v // LANES
    # rows of one piece (R is whole sublane tiles)
    rg = next(n for n in (ROW_GROUP, 16, 8) if R % n == 0)
    seed = seed_ref[0]

    @pl.when(j == 0)
    def _init():
        for ref, fill in (
            (m_scr, NEG_INF), (l_scr, 0.0), (amv_scr, NEG_INF),
            (ami_scr, 0), (gp_scr, NEG_INF), (gw_scr, 0.0), (gi_scr, 0),
        ):
            ref[...] = jnp.full(ref.shape, fill, ref.dtype)

    def fold(tiles: int, partial: int):
        """One block's ``tiles`` whole lane tiles and, after them, the
        first ``partial`` columns of one more."""
        # the weight block is [E, BV], or [BV, E] contracted over its E
        logits = jax.lax.dot_general(
            x_ref[...], w_ref[...],
            (((1,), (1 if vocab_rows else 0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if logits_scale != 1.0:
            logits = logits / logits_scale
        if soft_cap is not None and soft_cap > 0:
            logits = jnp.tanh(logits / soft_cap) * soft_cap
        lg_scr[...] = logits
        if hw_prng:
            pltpu.prng_seed(seed, j)

        def group(g, carry):
            rs = pl.ds(pl.multiple_of(g * rg, rg), rg)
            lane = jax.lax.broadcasted_iota(jnp.int32, (rg, LANES), 1)
            inv_t = 1.0 / jnp.maximum(temp_ref[rs, :], 1e-6)

            def piece(c, columns):
                lg = lg_scr[rs, pl.ds(pl.multiple_of(c * LANES, LANES), LANES)]
                if columns < LANES:
                    lg = jnp.where(lane < columns, lg, -jnp.inf)
                return lg

            def over_pieces(body, state):
                # the whole tiles in a loop, TILE_UNROLL of them an
                # iteration, then what is left of them and the partial one
                def some(o, st):
                    for i in range(TILE_UNROLL):
                        st = body(o * TILE_UNROLL + i, LANES, st)
                    return st

                state = jax.lax.fori_loop(
                    0, tiles // TILE_UNROLL, some, state)
                for c in range(tiles // TILE_UNROLL * TILE_UNROLL, tiles):
                    state = body(c, LANES, state)
                if partial:
                    state = body(tiles, partial, state)
                return state

            # pass A: the running raw arg-max per lane; its row max is the
            # block's softmax reference
            def arg_max(c, columns, state):
                am_v, am_i = state
                lg = piece(c, columns)
                upd = lg > am_v
                return (jnp.where(upd, lg, am_v),
                        jnp.where(upd, j * n_tiles + c, am_i))

            am_v, am_i = over_pieces(
                arg_max, (amv_scr[rs, :], ami_scr[rs, :]))
            amv_scr[rs, :] = am_v
            ami_scr[rs, :] = am_i
            m_prev = m_scr[rs, :]
            m_new = jnp.maximum(
                m_prev, jnp.max(am_v, axis=-1, keepdims=True) * inv_t
            )
            m_scr[rs, :] = m_new

            # pass B: the normaliser's sum and the Gumbel arg-max
            if not hw_prng:
                row_term = (
                    (jax.lax.broadcasted_iota(jnp.int32, (rg, LANES), 0)
                     + g * rg) * -2048144789
                ) ^ seed
                lane_term = lane * -1640531527

            def sample(c, columns, state):
                l, gp, gw, gi = state
                col0 = j * block_v + c * LANES
                warped = piece(c, columns) * inv_t
                l = l + jnp.exp(warped - m_new)
                if hw_prng:
                    bits = pltpu.bitcast(
                        pltpu.prng_random_bits((rg, LANES)), jnp.uint32
                    )
                else:
                    bits = _hash_uniform_bits(row_term, lane_term, col0)
                # the TPU lowering has no uint32 -> float32 cast; the top
                # 24 bits fit an int32 exactly
                u = ((bits >> 8).astype(jnp.int32).astype(jnp.float32)
                     + 0.5) * (1.0 / (1 << 24))
                pert = warped - jnp.log(-jnp.log(u))
                upd = pert > gp
                return (l, jnp.where(upd, pert, gp),
                        jnp.where(upd, warped, gw),
                        jnp.where(upd, j * n_tiles + c, gi))

            l, gp, gw, gi = over_pieces(sample, (
                l_scr[rs, :] * jnp.exp(m_prev - m_new),
                gp_scr[rs, :], gw_scr[rs, :], gi_scr[rs, :],
            ))
            l_scr[rs, :] = l
            gp_scr[rs, :] = gp
            gw_scr[rs, :] = gw
            gi_scr[rs, :] = gi
            return carry

        jax.lax.fori_loop(0, R // rg, group, 0)

    # columns past the vocabulary exist in the last block only, and how
    # many of its columns are real is known here
    tail = vocab - (nb - 1) * block_v
    if tail == block_v:
        fold(n_tiles, 0)
    else:
        pl.when(j < nb - 1)(functools.partial(fold, n_tiles, 0))
        pl.when(j == nb - 1)(
            functools.partial(fold, tail // LANES, tail % LANES))

    @pl.when(j == nb - 1)
    def _emit():
        lane = jax.lax.broadcasted_iota(jnp.int32, (R, LANES), 1)

        def first_at_max(vals, tiles):
            # the row max and the FIRST column that attains it: per lane
            # the earliest tile was kept, so the min column among the lanes
            # at the max is jnp.argmax's tie order
            v = jnp.max(vals, axis=-1, keepdims=True)
            cols = jnp.where(vals == v, tiles * LANES + lane, _BIG_I32)
            return v, cols, jnp.min(cols, axis=-1, keepdims=True)

        t = jnp.maximum(temp_ref[...], 1e-6)
        am_v, _, am_i = first_at_max(amv_scr[...], ami_scr[...])
        _, g_cols, g_i = first_at_max(gp_scr[...], gi_scr[...])
        g_w = jnp.max(
            jnp.where(g_cols == g_i, gw_scr[...], NEG_INF),
            axis=-1, keepdims=True,
        )
        norm = m_scr[...] + jnp.log(
            jnp.sum(l_scr[...], axis=-1, keepdims=True)
        )
        is_greedy = greedy_ref[...] > 0
        tok_ref[...] = jnp.where(is_greedy, am_i, g_i)
        # a greedy row's m IS am_v * (1 / t), the same product
        lp_ref[...] = jnp.where(is_greedy, am_v * (1.0 / t), g_w) - norm
        argmax_ref[...] = jnp.broadcast_to(am_i, argmax_ref.shape)
        norm_ref[...] = norm


def fused_sample_pallas(
    rng: jax.Array,
    x: jnp.ndarray,               # [R, E]
    w: jnp.ndarray,               # [E, V], or [V, E] with ``vocab_rows``
    temperature: jnp.ndarray,     # [R] f32
    greedy: jnp.ndarray,          # [R] bool
    soft_cap: Optional[float] = None,
    block_v: Optional[int] = None,
    interpret: Optional[bool] = None,
    logits_scale: float = 1.0,
    vocab_rows: bool = False,
):
    """Kernel wrapper; same result dict as the XLA path of
    ``ops/fused_sample.py`` (minus top-k, which the dispatch never routes
    here). The PRNG seed derives from ``rng`` on device — no host
    round-trip rides the dispatch. ``block_v`` (columns of one grid step)
    follows from the shapes (:func:`block_columns`); a caller's value is
    held to what fits. ``vocab_rows`` (STATIC) says ``w`` is ``[V, E]``, a
    tied embedding as the parameter tree stores it; ``logits_scale``
    (STATIC) divides the block's float32 logits before the soft cap, as
    ``models/transformer.py:_head`` does."""
    R0, E = x.shape
    V = w.shape[0 if vocab_rows else 1]
    R = -(-R0 // 8) * 8  # whole sublane tiles; the padding rows are cut off
    fit = block_columns(R, E, V, w.dtype.itemsize)
    if block_v is None:
        block_v = fit
    else:
        block_v = max(LANES, min(block_v // LANES * LANES, fit))
    nb = -(-V // block_v)
    interpret = _interpret() if interpret is None else interpret
    seed = jax.random.randint(
        rng, (1,), jnp.iinfo(jnp.int32).min, jnp.iinfo(jnp.int32).max,
        dtype=jnp.int32,
    )

    def _rows(v, dtype, fill):
        arr = jnp.pad(v.astype(dtype), (0, R - R0), constant_values=fill)
        return jnp.broadcast_to(arr[:, None], (R, LANES))

    x = jnp.pad(x, ((0, R - R0), (0, 0)))
    operands = [
        seed, x, w,
        _rows(temperature, jnp.float32, 1.0),
        _rows(greedy, jnp.int32, 0),
    ]
    out_dtypes = [jnp.int32, jnp.float32, jnp.int32, jnp.float32]
    # per-lane running state: softmax max and sum, raw arg-max (value,
    # tile), Gumbel arg-max (perturbed value, warped logit there, tile)
    scratch = [
        jnp.float32, jnp.float32, jnp.float32, jnp.int32,
        jnp.float32, jnp.float32, jnp.int32,
    ]
    row_spec = pl.BlockSpec((R, LANES), lambda j, s: (0, 0))
    kernel = functools.partial(
        _kernel, nb=nb, block_v=block_v, vocab=V, soft_cap=soft_cap,
        logits_scale=float(logits_scale), vocab_rows=vocab_rows,
        hw_prng=not interpret,
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((R, E), lambda j, s: (0, 0)),
                pl.BlockSpec((block_v, E), lambda j, s: (j, 0))
                if vocab_rows
                else pl.BlockSpec((E, block_v), lambda j, s: (0, j)),
            ] + [row_spec] * (len(operands) - 3),
            out_specs=[row_spec] * len(out_dtypes),
            scratch_shapes=[pltpu.VMEM((R, block_v), jnp.float32)] + [
                pltpu.VMEM((R, LANES), dt) for dt in scratch
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((R, LANES), dt) for dt in out_dtypes
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(
                # double-buffered head block and resident x, the block's
                # logits with temporaries, the row state
                2 * E * block_v * w.dtype.itemsize
                + 2 * R * E * x.dtype.itemsize
                + 6 * R * block_v * 4 + 32 * R * LANES * 4
                + 16 * 2 ** 20
            ),
        ),
        interpret=interpret,
        name="fused_sample",
    )(*operands)
    tok, lp, am, norm = (o[:R0, 0] for o in outs)
    return {
        "tokens": tok,
        "logprobs": lp,
        "argmax": am,
        "norm": norm,
    }
