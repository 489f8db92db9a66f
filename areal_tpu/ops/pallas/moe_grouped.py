"""The routed experts as ONE grouped matmul over the stacked weights
(Pallas TPU kernel).

What ``ops/moe.py``'s three einsums do by computing every expert for every
row (``X / k`` times the needed FLOPs), done for the chosen pairs only: the
``T x k`` (row, expert) pairs are sorted by expert (stable), every expert's
run is padded to whole tiles of ``tm`` rows, and the kernel walks the
tiles: a grid step is ONE tile of ONE expert. Its weight operands are the
whole stacks ``[L, X, E, F]`` / ``[L, X, F, E]`` and a layer index: a
block's index is ``(layer, expert of the tile, 0, 0)``, read from scalar
prefetch, so

- a layer's slice of the stack is never materialised (XLA fuses a dynamic
  slice into an einsum and cannot fuse it into a custom call: a kernel
  handed the slice costs a copy of ``X x E x F`` for each of the three
  matrices, every layer-step; ``lax.ragged_dot`` paid it, PERF.md §6 PR 26);
- each expert that a row chose is read ONCE, whole (gate, up and down: three
  contiguous pieces of HBM), behind the matmuls of the tile before it (the
  grid's own double buffering); consecutive tiles of one expert keep its
  blocks, and an expert that no row chose has no tile and is never read;
- ``h = act(x W_gate) * (x W_up)`` stays in VMEM.

The grid is static: ``T k // tm + min(X, T k)`` tiles bound the padded
runs whatever the routing; the steps past the last real tile keep its
blocks (nothing is copied for them) and skip the body. A padding row
computes on row 0 and is read by nobody.

The sort, the gather of the padded sorted rows and the weighted sum of a
row's ``k`` copies (f32, in row order) are XLA's, around the call.

Two variants, chosen by the operands (``ops/moe.py`` says whose they are):
experts of TWO matrices (``w_gate`` None: ``h = act(x W_up)``), and a
SHARE of the experts the router chose among (``n_routed`` over the stack's
``X``): a pair on an expert that is not held comes with the index ``X``,
is sorted last and dropped (``with_skip``), and the row tile is sized by
the pairs expected here, ``T k X / n_routed``.

No gradient and no partitioning rule: the generation engine's forwards run
this on ONE device (``ops/moe.py:moe_grouped_applies``), the trainer keeps
the einsums.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.activations import ACT2FN

SUBLANES = 16        # rows of a bf16 tile
MAX_ROW_TILE = 128   # the MXU's height


def _interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


def row_tile(n_pairs: int, n_experts: int) -> int:
    """Rows of a tile. A tile costs the MXU the push of the expert's
    ``3 E F / 128^2`` weight tiles whatever ``tm <= 128`` is, about half
    of what reading them from HBM costs, so an expert's first tile hides
    under its weights' copy and every further one does not: the smallest
    power of two of whole bf16 tiles that holds the mean run
    (``n_pairs / n_experts``) twice, at most the MXU's height. A padded
    tile computes all its rows: at JoyAI's 256 rows of 8 (a mean run of
    8) tiles of 16 / 32 / 64 / 128 read 3.46 / 3.54 / 3.86 / 4.44 ms a
    layer on a v5e (PERF.md §6 PR 40)."""
    want = max(SUBLANES, 2 * -(-n_pairs // n_experts))
    tm = SUBLANES
    while tm < want and tm < MAX_ROW_TILE:
        tm *= 2
    return tm


def tile_plan(group_sizes: jnp.ndarray, n_tiles: int, tm: int):
    """The padded layout of runs of ``group_sizes`` ``[X]`` pairs (in
    expert order): ``(expert [n_tiles], first_row [X], n_active [1])``,
    all int32. Expert ``x``'s run starts at padded row ``first_row[x]``
    (a multiple of ``tm``) and takes ``ceil(size / tm)`` tiles; tile
    ``t < n_active`` is ``expert[t]``'s, later ones repeat the last."""
    sizes = group_sizes.astype(jnp.int32)
    tiles = -(-sizes // tm)
    tile_end = jnp.cumsum(tiles)
    n_active = tile_end[-1]
    t = jnp.minimum(
        jnp.arange(n_tiles, dtype=jnp.int32), jnp.maximum(n_active - 1, 0))
    expert = jnp.minimum(
        jnp.searchsorted(tile_end, t, side="right").astype(jnp.int32),
        sizes.shape[0] - 1,
    )
    return expert, (tile_end - tiles) * tm, n_active.reshape(1)


def _kernel(li_ref, expert_ref, n_active_ref, x_ref, *refs, act):
    del li_ref, expert_ref  # read by the index maps
    *wg_ref, wu_ref, wd_ref, o_ref = refs     # no gate: two matrices

    @pl.when(pl.program_id(0) < n_active_ref[0])
    def _():
        x = x_ref[...]                                          # [tm, E]
        h = act(jnp.dot(
            x, (wg_ref[0] if wg_ref else wu_ref)[...],
            preferred_element_type=jnp.float32))
        if wg_ref:
            h = h * jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        o_ref[...] = jnp.dot(
            h.astype(x.dtype), wd_ref[...],                     # [tm, F]
            preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit, static_argnames=(
        "activation", "tm", "interpret", "with_skip", "n_routed"))
def moe_grouped(
    x: jnp.ndarray,            # [T, E] rows
    top_idx: jnp.ndarray,      # [T, k] int32: the experts a row chose
    top_vals: jnp.ndarray,     # [T, k] f32: their combine weights
    group_sizes: jnp.ndarray,  # [X] pairs an expert serves
    w_gate: jnp.ndarray,       # [L, X, E, F] the STACK, never a slice;
                               # None: experts of two matrices
    w_up: jnp.ndarray,         # [L, X, E, F]
    w_down: jnp.ndarray,       # [L, X, F, E]
    layer: jnp.ndarray,        # scalar int32: which layer of the stack
    *,
    activation: str,
    tm: int = None,
    interpret: bool = None,
    with_skip: bool = False,
    n_routed: int = None,
) -> jnp.ndarray:
    """``sum_j top_vals[t, j] * expert(top_idx[t, j])(x[t])`` -> ``[T, E]``
    in ``x``'s dtype; every product accumulates in f32, ``h`` is rounded
    to ``x``'s dtype before the down projection (as the einsums round
    it), and the ``k`` copies of a row are weighted and summed in f32."""
    T, E = x.shape
    k = top_idx.shape[1]
    _, X, _, F = w_up.shape
    N = T * k
    tm = tm or row_tile(N, n_routed or X)
    weights = (w_up, w_down) if w_gate is None else (w_gate, w_up, w_down)
    n_w = len(weights)
    n_tiles = N // tm + min(X, N)
    sizes = group_sizes.astype(jnp.int32)
    expert, first_row, n_active = tile_plan(sizes, n_tiles, tm)
    # pair p = (row p // k, its j-th choice); ``order[s]`` is the pair at
    # sorted position s, ``dest[s]`` its padded row: its run's first row
    # plus its place in the run
    chosen = top_idx.reshape(N).astype(jnp.int32)
    order = jnp.argsort(chosen, stable=True).astype(jnp.int32)
    shift = first_row - (jnp.cumsum(sizes) - sizes)
    dest = jnp.arange(N, dtype=jnp.int32) + shift[chosen[order]]
    if with_skip:
        # sorted last; past the layout's end, where the scatter drops them
        dest = jnp.where(chosen[order] < X, dest, n_tiles * tm)
    src = jnp.zeros((n_tiles * tm,), jnp.int32).at[dest].set(
        order // k, mode="drop" if with_skip else None)

    def w_spec(shape):
        return pl.BlockSpec(
            (None, None) + shape, lambda t, li, ex, na: (li[0], ex[t], 0, 0))

    rows_spec = pl.BlockSpec(
        (tm, E),
        lambda t, li, ex, na: (jnp.minimum(t, jnp.maximum(na[0] - 1, 0)), 0))
    itemsize = w_up.dtype.itemsize
    y = pl.pallas_call(
        functools.partial(_kernel, act=ACT2FN[activation]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_tiles,),
            in_specs=[rows_spec, *[w_spec((E, F))] * (n_w - 1),
                      w_spec((F, E))],
            out_specs=rows_spec,
        ),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tm, E), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(
                # an expert's matrices, double-buffered; the tile's rows
                # in and out, twice; gate, up, h and y with temporaries
                2 * n_w * E * F * itemsize
                + 2 * tm * E * (x.dtype.itemsize + 4)
                + 4 * tm * (F + E) * 4
                + 16 * 2 ** 20
            ),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * n_w * n_tiles * tm * E * F,
            transcendentals=n_tiles * tm * F,
            bytes_accessed=(
                n_w * min(X, N) * E * F * itemsize
                + n_tiles * tm * E * (x.dtype.itemsize + 4)
            ),
        ),
        interpret=_interpret() if interpret is None else interpret,
        name="moe_grouped",
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32), expert, n_active,
        x[src], *weights,
    )
    # padded row of every pair, back in (row, choice) order
    at = jnp.zeros((N,), jnp.int32).at[order].set(dest)
    y = y[at]
    if with_skip:
        y = jnp.where((chosen < X)[:, None], y, 0.0)
    out = jnp.einsum(
        "tke,tk->te", y.reshape(T, k, E), top_vals.astype(jnp.float32))
    return out.astype(x.dtype)
