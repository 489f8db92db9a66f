"""The decode step's gated delta-rule update over the per-slot state of ALL
layers, in place (Pallas TPU kernel ``kda_decode``).

What ``ops/kda.py:step_update`` computes for one layer, for every head of
every row::

    S <- diag(a) S;  u = S^T k;  S <- S + (beta k) (v - u)^T;  o = S^T q

with the state read ONCE and written ONCE. XLA's form of the same update
cuts the layer's ``[B, H, Dk, Dv]`` out of the stacked state, reads it in
two fusions and writes a fresh array that is copied back: at the published
sizes (256 slots of 64 heads of 128 x 128 float32) that is 1 GiB a layer a
step, copied, beside a step that IS the state's bytes.

The state operand is the engine's whole ``[Lk, B, H, Dk, Dv]`` array,
aliased to the result; a layer index from scalar prefetch and the grid's
row pick one row's ``[H, Dk, Dv]`` (4 MiB, one piece of HBM) a step, which
the grid's pipeline fetches ahead of the step before and writes back behind
it. No layer's slice is ever materialised and what a call does not touch
stays where it is. A row that is not active comes with ``a`` 1 and ``beta
k`` 0 (``ops/kda.py:mixer_step``): its state is written back as it was.

Layout of the work: a head's ``S`` has its key channels on the sublanes
and its value channels on the lanes. Both sums (``u``, ``o``) run DOWN the
sublanes: vector adds and one 8 -> 1 reduce a lane tile. What varies a KEY
channel (``a``, ``k``, ``q``, ``beta k``) has to become a column spread
over the lanes; the rows of ``HEADS`` heads (4 a head) are turned in ONE
transpose of ``[4 x HEADS, Dk]`` and a head's four columns are static
lane slices of the result. ``v`` and ``o`` are rows as the model holds
them.

No gradient, no partitioning rule: ``kda_decode_applies`` says where the
engine runs it.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# heads whose small operands are turned to columns at once: a sublane tile
# of them, so that their ``v`` rows and their ``o`` are one aligned [8, Dv]
HEADS = 8
# the columns a head brings: a, k, q, beta k
_COLS = 4
LANES = 128


def _interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


def kda_decode_applies(cfg, mesh=None, platform=None) -> bool:
    """Whether a generation engine's decode steps update the delta-rule
    state with this kernel or with ``ops/kda.py:step_update``, from what it
    can observe: ONE TPU device, a float32 state of whole sublane tiles of
    heads, each ``128 x 128`` (a lane tile of value channels, and key
    channels that turn to columns in one tile)."""
    if platform is None:
        platform = jax.devices()[0].platform
    d = cfg.kda
    if d is None:
        return False
    return (
        platform == "tpu"
        and (mesh is None or mesh.size == 1)
        and d.head_dim == LANES
        and d.n_heads % HEADS == 0
    )


def _kernel(li_ref, small_ref, s_ref, o_ref, s_out_ref):
    """One row: ``small_ref [H / HEADS, 5 x HEADS, D]`` (a group's 4 x HEADS
    column operands, head-major, then its HEADS rows of ``v``), ``s_ref``,
    ``s_out_ref [H, Dk, Dv]``, ``o_ref [H / HEADS, HEADS, Dv]``."""
    del li_ref
    n_cols = _COLS * HEADS

    def group(gi, carry):
        tile = small_ref[gi]                              # [5 HEADS, D]
        cols = tile[:n_cols].T                            # [Dk, 4 HEADS]
        outs = []
        for j in range(HEADS):
            h = gi * HEADS + j
            a, k, q, bk = (
                cols[:, _COLS * j + c : _COLS * j + c + 1]
                for c in range(_COLS))
            s = s_ref[h] * a
            u = jnp.sum(s * k, axis=0, keepdims=True)     # [1, Dv]
            s = s + bk * (tile[n_cols + j : n_cols + j + 1] - u)
            s_out_ref[h] = s
            outs.append(jnp.sum(s * q, axis=0, keepdims=True))
        o_ref[gi] = jnp.concatenate(outs, axis=0)
        return carry

    jax.lax.fori_loop(0, small_ref.shape[0], group, 0)


def kda_decode(s_all, layer, q, k, v, a, beta):
    """``s_all [Lk, B, H, Dk, Dv]`` f32 (donated: updated in place),
    ``layer`` int32 scalar, then :func:`ops.kda.step_update`'s arguments
    (``q, k, v, a [B, H, D]``, ``beta [B, H]``). Returns ``(o [B, H, Dv],
    s_all)``."""
    _, B, H, Dk, Dv = s_all.shape
    G = H // HEADS
    # a group's small operands as ONE block: its heads' [a ; k ; q ; beta k]
    # head-major, then its heads' v
    cols = jnp.stack([a, k, q, beta[..., None] * k], axis=2)    # [B, H, 4, D]
    small = jnp.concatenate(
        [cols.reshape(B, G, _COLS * HEADS, Dk), v.reshape(B, G, HEADS, Dv)],
        axis=2)
    state = pl.BlockSpec(
        (None, None, H, Dk, Dv), lambda b, li: (li[0], b, 0, 0, 0))
    o, s_all = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[
                pl.BlockSpec(
                    (None, G, (_COLS + 1) * HEADS, Dk),
                    lambda b, li: (b, 0, 0, 0)),
                state,
            ],
            out_specs=[
                pl.BlockSpec((None, G, HEADS, Dv), lambda b, li: (b, 0, 0, 0)),
                state,
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, G, HEADS, Dv), jnp.float32),
            jax.ShapeDtypeStruct(s_all.shape, s_all.dtype),
        ],
        # operands: the scalar prefetch, the small ones, the state
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=4 * 4 * H * Dk * Dv + 16 * 2**20),
        interpret=_interpret(),
        name="kda_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1), small, s_all)
    return o.reshape(B, H, Dv), s_all
