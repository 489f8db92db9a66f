"""Fused LM-head + sampling epilogue (Pallas TPU kernel).

One grid step per vocab block: the head block ``W[:, j*BV:(j+1)*BV]``
streams HBM -> VMEM through the pallas pipeline while the hidden states
``x [R, E]`` stay resident, the block's logits come off the MXU in f32,
and the sampling state folds in online — running softmax normalizer
(max + rescaled sum-of-exponentials, the same recurrence as the paged
extend kernel), running raw argmax (greedy slots, token-exact), and a
running Gumbel-top-1 argmax over the temperature-warped logits (the
categorical sample; in-kernel PRNG via ``pltpu.prng_seed`` /
``prng_random_bits``, reseeded per block from the scalar-prefetched seed
so the stream is grid-order independent). The full ``[R, V]`` logits
tensor never exists in HBM: HBM traffic is exactly one read of the head
weight — the decode-epilogue roofline.

Per-row extras for the speculative verify path: an *excluded* token
(masked out of the Gumbel argmax only — the rejection-sampling residual
"p with the rejected token removed") and a *gathered* token whose warped
logit is returned (the draft-token acceptance score).

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
_BIG_I32 = 2 ** 30  # python literal: a jnp scalar would be a captured const


def _interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


def _first_max_idx(vals, cols, valid):
    """(max value [R,1], first column index attaining it [R,1]) — the 2D
    formulation of argmax (min column id among the maxima) so the kernel
    never needs a 1-D iota, and tie order matches ``jnp.argmax``."""
    mv = jnp.max(jnp.where(valid, vals, NEG_INF), axis=-1, keepdims=True)
    at_max = valid & (vals == mv)
    mi = jnp.min(jnp.where(at_max, cols, _BIG_I32), axis=-1, keepdims=True)
    return mv, mi


def _kernel(
    seed_ref, x_ref, w_ref, temp_ref, greedy_ref, excl_ref, gid_ref,
    tok_ref, lp_ref, argmax_ref, gat_ref, norm_ref,
    m_scr, l_scr, amv_scr, ami_scr, gp_scr, gw_scr, gi_scr, gat_scr,
    *, nb: int, block_v: int, vocab: int, soft_cap: Optional[float],
):
    j = pl.program_id(0)
    R = x_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        amv_scr[...] = jnp.full_like(amv_scr, NEG_INF)
        ami_scr[...] = jnp.zeros_like(ami_scr)
        gp_scr[...] = jnp.full_like(gp_scr, NEG_INF)
        gw_scr[...] = jnp.zeros_like(gw_scr)
        gi_scr[...] = jnp.zeros_like(gi_scr)
        gat_scr[...] = jnp.full_like(gat_scr, NEG_INF)

    logits = jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )
    if soft_cap is not None and soft_cap > 0:
        logits = jnp.tanh(logits / soft_cap) * soft_cap
    cols = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (R, block_v), 1
    )
    valid = cols < vocab
    t = jnp.maximum(temp_ref[:, :1], 1e-6)
    warped = jnp.where(valid, logits, 0.0) / t

    # online logsumexp of the warped logits
    m_prev = m_scr[:, :1]
    bm = jnp.max(jnp.where(valid, warped, NEG_INF), axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, bm)
    l_new = l_scr[:, :1] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.where(valid, jnp.exp(warped - m_new), 0.0),
        axis=-1, keepdims=True,
    )
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    # running raw argmax: strict > keeps the earliest maximum across
    # blocks, matching jnp.argmax tie order over the full vocab
    bv, bi = _first_max_idx(logits, cols, valid)
    upd = bv > amv_scr[:, :1]
    amv_new = jnp.where(upd, bv, amv_scr[:, :1])
    ami_new = jnp.where(upd, bi, ami_scr[:, :1])
    amv_scr[...] = jnp.broadcast_to(amv_new, amv_scr.shape)
    ami_scr[...] = jnp.broadcast_to(ami_new, ami_scr.shape)

    # Gumbel-top-1 over warped (+ per-row exclusion): running argmax of
    # warped + G across every block IS a categorical draw. Uniforms come
    # from a counter-based hash of (seed, row, global column) — the
    # murmur3 finalizer over a per-element counter — rather than the
    # stateful pltpu PRNG: identical bits in compiled and interpret mode
    # (the interpret path has no prng_seed lowering), and independent of
    # grid-iteration order by construction.
    rows_i = jax.lax.broadcasted_iota(jnp.int32, (R, block_v), 0)
    h = (cols * -1640531527) ^ (rows_i * -2048144789) ^ seed_ref[0]
    h = jax.lax.bitcast_convert_type(h, jnp.uint32)
    h = h ^ (h >> 16)
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * np.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    # the TPU lowering has no uint32 -> float32 cast; the top 24 bits fit
    # an int32 exactly
    u = ((h >> 8).astype(jnp.int32).astype(jnp.float32) + 0.5) * (
        1.0 / (1 << 24)
    )
    pert = warped - jnp.log(-jnp.log(u))
    pert = jnp.where(cols == excl_ref[:, :1], NEG_INF, pert)
    pbv, pbi = _first_max_idx(pert, cols, valid)
    pw = jnp.sum(
        jnp.where(cols == pbi, warped, 0.0), axis=-1, keepdims=True
    )
    upd2 = pbv > gp_scr[:, :1]
    gp_new = jnp.where(upd2, pbv, gp_scr[:, :1])
    gw_new = jnp.where(upd2, pw, gw_scr[:, :1])
    gi_new = jnp.where(upd2, pbi, gi_scr[:, :1])
    gp_scr[...] = jnp.broadcast_to(gp_new, gp_scr.shape)
    gw_scr[...] = jnp.broadcast_to(gw_new, gw_scr.shape)
    gi_scr[...] = jnp.broadcast_to(gi_new, gi_scr.shape)

    # gathered warped logit (speculative draft score)
    hit = valid & (cols == gid_ref[:, :1])
    any_hit = jnp.max(
        jnp.where(hit, 1.0, 0.0), axis=-1, keepdims=True
    ) > 0.0
    gval = jnp.sum(jnp.where(hit, warped, 0.0), axis=-1, keepdims=True)
    gat_new = jnp.where(any_hit, gval, gat_scr[:, :1])
    gat_scr[...] = jnp.broadcast_to(gat_new, gat_scr.shape)

    @pl.when(j == nb - 1)
    def _emit():
        norm = m_new + jnp.log(l_new)
        is_greedy = greedy_ref[:, :1] > 0
        tok = jnp.where(is_greedy, ami_new, gi_new)
        lp = jnp.where(is_greedy, amv_new / t - norm, gw_new - norm)
        tok_ref[...] = jnp.broadcast_to(tok, tok_ref.shape)
        lp_ref[...] = jnp.broadcast_to(lp, lp_ref.shape)
        argmax_ref[...] = jnp.broadcast_to(ami_new, argmax_ref.shape)
        gat_ref[...] = jnp.broadcast_to(gat_new - norm, gat_ref.shape)
        norm_ref[...] = jnp.broadcast_to(norm, norm_ref.shape)


def fused_sample_pallas(
    rng: jax.Array,
    x: jnp.ndarray,               # [R, E]
    w: jnp.ndarray,               # [E, V]
    temperature: jnp.ndarray,     # [R] f32
    greedy: jnp.ndarray,          # [R] bool
    exclude: Optional[jnp.ndarray] = None,     # [R] i32, -1 = none
    gather_ids: Optional[jnp.ndarray] = None,  # [R] i32
    soft_cap: Optional[float] = None,
    block_v: int = 2048,
    interpret: Optional[bool] = None,
):
    """Kernel wrapper; same result dict as the XLA path of
    ``ops/fused_sample.py`` (minus top-k, which the dispatch never routes
    here). The PRNG seed derives from ``rng`` on device — no host
    round-trip rides the dispatch."""
    R, E = x.shape
    V = w.shape[1]
    block_v = max(LANES, min(block_v, -(-V // LANES) * LANES))
    nb = -(-V // block_v)
    seed = jax.random.randint(
        rng, (1,), jnp.iinfo(jnp.int32).min, jnp.iinfo(jnp.int32).max,
        dtype=jnp.int32,
    )

    def _rows(v, dtype, fill):
        if v is None:
            arr = jnp.full((R, 1), fill, dtype)
        else:
            arr = v.astype(dtype).reshape(R, 1)
        return jnp.broadcast_to(arr, (R, LANES))

    operands = [
        seed,
        x,
        w,
        _rows(temperature, jnp.float32, 1.0),
        _rows(greedy.astype(jnp.int32), jnp.int32, 0),
        _rows(exclude, jnp.int32, -1),
        _rows(gather_ids, jnp.int32, -1),
    ]
    row_spec = pl.BlockSpec((R, LANES), lambda j, s: (0, 0))
    kernel = functools.partial(
        _kernel, nb=nb, block_v=block_v, vocab=V, soft_cap=soft_cap,
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((R, E), lambda j, s: (0, 0)),
                pl.BlockSpec((E, block_v), lambda j, s: (0, j)),
                row_spec, row_spec, row_spec, row_spec,
            ],
            out_specs=[row_spec] * 5,
            scratch_shapes=[
                pltpu.VMEM((R, LANES), jnp.float32),   # m
                pltpu.VMEM((R, LANES), jnp.float32),   # l
                pltpu.VMEM((R, LANES), jnp.float32),   # argmax value
                pltpu.VMEM((R, LANES), jnp.int32),     # argmax index
                pltpu.VMEM((R, LANES), jnp.float32),   # gumbel perturbed max
                pltpu.VMEM((R, LANES), jnp.float32),   # warped @ gumbel idx
                pltpu.VMEM((R, LANES), jnp.int32),     # gumbel index
                pltpu.VMEM((R, LANES), jnp.float32),   # gathered warped
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((R, LANES), jnp.int32),    # tokens
            jax.ShapeDtypeStruct((R, LANES), jnp.float32),  # logprobs
            jax.ShapeDtypeStruct((R, LANES), jnp.int32),    # argmax
            jax.ShapeDtypeStruct((R, LANES), jnp.float32),  # gathered_lp
            jax.ShapeDtypeStruct((R, LANES), jnp.float32),  # norm
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(
                # resident x + one head block (double-buffered) + row state
                4 * R * E + 2 * 4 * E * block_v + 16 * R * LANES * 4
                + 32 * 2 ** 20
            ),
        ),
        interpret=_interpret() if interpret is None else interpret,
        name="fused_sample",
    )(*operands)
    tok, lp, am, gat, norm = (o[:, 0] for o in outs)
    out = {
        "tokens": tok,
        "logprobs": lp,
        "argmax": am,
        "norm": norm,
    }
    if gather_ids is not None:
        out["gathered_lp"] = gat
    return out
