"""The decode step's state-space update over the per-slot state of ALL
layers, in place (Pallas TPU kernel ``ssm_decode``).

What ``ops/ssm.py:step_update`` computes for one layer, ``S <- exp(dt A)
S + (dt x) B^T`` and ``y = S C + D x`` for every head of every row, with
the recurrent state read ONCE and written ONCE. XLA's form of the same
update is two fusions a layer, one that reduces the new state to ``y``
and one that writes it back: both read the state, 1.5 x the bytes of a
step that at the published sizes IS the state's bytes (PERF.md §6).

The state operand is the whole ``[Ls, B, H, P, N]`` array of the engine,
aliased to the result, and a layer index from scalar prefetch: a grid step
is a block of one row's heads of one layer, ``(layer, row, heads, :, :)``,
so no layer's slice is ever materialised and what the kernel does not
touch stays where it is. A row that is not ``active`` keeps its state
without being read: its step points at the block of the last active row
before it (a block whose index does not change is neither fetched nor
written back again) and skips the body.

Layout inside a step: a head's state is ``[P, N]`` with ``N`` on the lanes.
``B`` and ``C`` are lane vectors ``[1, N]``. ``exp(dt A)`` is a scalar a
(row, head) and comes from SMEM. What varies with ``p`` (``dt x``) comes
in TRANSPOSED, ``[P, heads]``, so that head ``h``'s column ``[P, 1]``
broadcasts along the lanes; ``y`` leaves the same way. XLA makes those
small transposes around the call (a 64th of the state's bytes). The
kernel is bound by its cross-lane work, not by the bytes: the column
broadcast and the sum over the lanes (PERF.md §6 PR 41: 0.66 ms a call
with both on the XLU and the decay as a column, 0.56 with the decay a
scalar and the sum on the MXU, against 0.41 ms of bytes; 16 / 32 / 64
heads a step read 0.58 / 0.56 / 0.79).

No gradient, no partitioning rule, one group of heads (``n_groups`` 1):
``ssm_decode_applies`` says where the engine runs it.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# heads of one grid step: 32 x [64, 128] f32 = 1 MiB in and 1 MiB out,
# double-buffered
HEAD_BLOCK = 32


def _interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


def ssm_decode_applies(cfg, mesh=None, platform=None) -> bool:
    """Whether a generation engine's decode steps update the recurrent
    state with this kernel or with ``ops/ssm.py:step_update``, from what it
    can observe: ONE TPU device, one group of heads, a float32 state whose
    head is whole tiles."""
    if platform is None:
        platform = jax.devices()[0].platform
    s = cfg.ssm
    return (
        platform == "tpu"
        and (mesh is None or mesh.size == 1)
        and s is not None
        and s.n_groups == 1
        and s.state_dtype == "float32"
        and s.head_dim % 8 == 0
        and s.d_state % 128 == 0
    )


def _kernel(li_ref, rows_ref, act_ref, s_ref, da_ref, dtx_ref, b_ref, c_ref,
            y_ref, o_ref):
    del li_ref
    j, r = pl.program_id(0), pl.program_id(1)
    hb = s_ref.shape[0]

    @pl.when(act_ref[r] != 0)
    def _():
        b = b_ref[...]                                  # [1, N]
        c = c_ref[...]
        ones = jnp.ones((c.shape[-1], 128), jnp.bfloat16)
        row = rows_ref[r]
        for h in range(hb):
            s = s_ref[h] * da_ref[row, j * hb + h] + dtx_ref[:, h : h + 1] * b
            o_ref[h] = s
            # y = sum over the lanes of s * c, on the idle MXU: the product
            # in two bfloat16 parts against ones (f32 accumulation) keeps
            # 16 bits of it, and y is rounded to the serving dtype next
            prod = s * c
            hi = prod.astype(jnp.bfloat16)
            lo = (prod - hi.astype(jnp.float32)).astype(jnp.bfloat16)
            tot = jnp.dot(hi, ones, preferred_element_type=jnp.float32)
            tot = tot + jnp.dot(lo, ones, preferred_element_type=jnp.float32)
            y_ref[:, h : h + 1] = tot[:, :1]


def ssm_decode(ssm_all, layer, x, dt, a, b, c, d_skip, active,
               head_block=None):
    """``ssm_all [Ls, B, H, P, N]`` f32 (donated: updated in place),
    ``layer`` int32 scalar, ``x [B, 1, H, P]``, ``dt [B, 1, H]``, ``a,
    d_skip [1, H]``, ``b, c [B, 1, N]``: :func:`ops.ssm.step_update`'s
    arguments with one group; ``active [B]``: rows that are not keep
    their state and get ``y`` 0. Returns ``(y [B, 1, H, P], ssm_all)``."""
    Ls, B, H, P, N = ssm_all.shape
    hb = head_block or min(HEAD_BLOCK, H)
    if H % hb:
        hb = H
    nb = H // hb
    x, dt = x[:, 0], dt[:, 0]
    # a row that is not active points at the last active row before it
    # (the first active one, if none is): its block is not moved
    idx = jnp.arange(B, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(active, idx, -1))
    rows = jnp.where(last >= 0, last, jnp.argmax(active).astype(jnp.int32))

    def by_block(v):            # [B, H, P] -> [B, nb, P, hb]
        return v.reshape(B, nb, hb, P).transpose(0, 1, 3, 2)

    da = jnp.exp(dt * a)                        # [B, H]: scalars, in SMEM
    dtx = by_block(dt[..., None] * x)

    small = pl.BlockSpec(
        (None, None, P, hb), lambda j, r, li, rows, act: (rows[r], j, 0, 0))
    vec = pl.BlockSpec(
        (None, 1, N), lambda j, r, li, rows, act: (rows[r], 0, 0))
    state = pl.BlockSpec(
        (None, None, hb, P, N),
        lambda j, r, li, rows, act: (li[0], rows[r], j, 0, 0))
    y, ssm_all = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nb, B),     # rows innermost: a skipped row keeps the block
            in_specs=[state, pl.BlockSpec(memory_space=pltpu.SMEM), small,
                      vec, vec],
            out_specs=[small, state],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, nb, P, hb), jnp.float32),
            jax.ShapeDtypeStruct(ssm_all.shape, ssm_all.dtype),
        ],
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
        name="ssm_decode",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), rows,
        active.astype(jnp.int32), ssm_all, da, dtx, b, c,
    )
    y = y.transpose(0, 1, 3, 2).reshape(B, H, P)
    # (a skipped row's y is whatever its buffer held: nobody reads it)
    y = jnp.where(active[:, None, None], y, 0.0) + d_skip[0][:, None] * x
    return y[:, None], ssm_all
