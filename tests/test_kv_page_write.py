"""The ``kv_page_write`` kernel (interpret mode) against the XLA scatter.

``models/transformer.py:_scatter_chunk_kv`` is the kernel's plain
reference: after the same writes the two pools are BIT-equal, and every
row no write touched still holds its sentinel. What Mosaic refuses the
interpreter cannot see: ``tests/test_tpu_compile.py`` compiles the kernel
for a described v5e, ``chip_smoke.py`` runs it on the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import transformer as tfm
from areal_tpu.ops import paged_attention as paged_ops
from areal_tpu.ops.pallas import kv_page_write

PAGE, L, P, M = 128, 2, 10, 3
SENTINEL = -3.0
# (streams, heads, width): the 1.5B cell's K/V, the OLMoE cell's, a latent
# row of five lane tiles
POOLS = {"kv2": (2, 2, 128), "kv16": (2, 16, 128), "latent640": (1, 1, 640)}

# each case: the chunk length C and one or more writes [(start, count) a
# row]; rows own pages 1-3, 4-6, 7-9 unless the case's table says otherwise
# (a table one page wide: positions past it clip to that page, as the
# scatter's do)
CASES = {
    # one token a row at a page's first row, the ends of its first tile,
    # and its last row, then the row after each (the next page's first)
    "c1_offsets": (1, [
        ([0, 15, 127], [1, 1, 1]),
        ([16, 16, 128], [1, 1, 1]),
    ], None),
    # admission-style runs: mid-tile start that crosses into the next
    # page; a whole page; a short run inside one page
    "c128_cross_page": (128, [([87, 128, 35], [128, 128, 60])], None),
    # verify-style: five tokens, a shorter valid prefix, across a page
    "c5_prefix": (5, [([14, 126, 40], [5, 3, 1])], None),
    # rows with no valid token whose table points at page 0, while
    # another row OWNS page 0 and writes it
    "invalid_at_page0": (1, [([5, 5, 5], [0, 1, 0])], [
        [0, 0, 0], [0, 4, 5], [0, 0, 0]]),
    "all_invalid": (1, [([0, 17, 300], [0, 0, 0])], None),
    # six rows are three grid steps of two: a step whose rows all have a
    # token takes the kernel's unrolled form, the one with a free slot in
    # it the loop, in one call, twice (the ring turns over)
    "c1_full_and_partial_steps": (1, [
        ([3, 127, 40, 0, 128, 16], [1, 1, 1, 0, 1, 1]),
        ([4, 128, 41, 0, 129, 17], [1, 0, 1, 1, 1, 1]),
    ], [[1], [2], [3], [0], [4], [5]]),
}


def _fresh(rng, shape, kind):
    S, H, W = POOLS[kind]
    ks = jnp.asarray(rng.standard_normal(shape + (H, W)), jnp.bfloat16)
    if S == 1:
        return ks, None
    return ks, jnp.asarray(rng.standard_normal(shape + (H, W)), jnp.bfloat16)


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("kind", list(POOLS))
def test_kernel_matches_scatter_bit_for_bit(kind, case):
    S, H, W = POOLS[kind]
    C, writes, table = CASES[case]
    B = len(writes[0][0])
    if table is None:
        table = np.arange(1, 1 + B * M).reshape(B, M)
    table = jnp.asarray(table, jnp.int32)
    rng = np.random.default_rng(len(kind) * 100 + len(case))
    pool = jnp.full((L, P, S, H, PAGE, W), SENTINEL, jnp.bfloat16)
    got = want = tfm.PagedKVCache(pages=pool)
    kernel = jax.jit(lambda c, *a: tfm._write_chunk_kv(c, *a, use_pallas=True))
    scatter = jax.jit(tfm._scatter_chunk_kv)
    n_rows = 0
    for start, count in writes:
        ks, vs = _fresh(rng, (L, B, C), kind)
        args = (ks, vs, table, jnp.asarray(start, jnp.int32),
                jnp.asarray(count, jnp.int32))
        got, want = kernel(got, *args), scatter(want, *args)
        n_rows += sum(count) * L * S * H
    bits = lambda c: np.asarray(
        jax.lax.bitcast_convert_type(c.pages, jnp.uint16))
    np.testing.assert_array_equal(bits(got), bits(want))
    # fresh rows are random (never the sentinel in all their values), so
    # the rows that changed are exactly the rows written
    touched = np.asarray(jnp.any(got.pages != SENTINEL, axis=-1))
    assert int(touched.sum()) == n_rows


def test_predicate_keeps_what_the_kernel_does_not_take():
    """One predicate, over what the caller can observe: an int8 pool, a
    pool under a mesh of several devices and a page that is not whole
    tiles keep the scatter whatever ``use_pallas`` says; the CPU keeps it
    unless a test asks for the kernel."""
    from jax.sharding import Mesh

    applies = paged_ops.kv_write_kernel_applies

    def pool(page, dtype):
        return jax.ShapeDtypeStruct((2, 4, 2, 2, page, 128), dtype)

    assert applies(True, pool(128, jnp.bfloat16))
    assert not applies(None, pool(128, jnp.bfloat16))       # CPU, auto
    assert not applies(True, pool(128, jnp.int8), quantized=True)
    assert not applies(True, pool(8, jnp.bfloat16))         # tile is 16 rows
    assert applies(True, pool(8, jnp.float32))              # tile is 8 rows
    devs = np.asarray(jax.devices()[:2])
    assert not applies(True, pool(128, jnp.bfloat16),
                       mesh=Mesh(devs, ("model",)))
    assert applies(True, pool(128, jnp.bfloat16),
                   mesh=Mesh(devs[:1], ("model",)))


@pytest.mark.parametrize(
    "batch,chunk,slab,want",
    [
        # the cells' decode steps: 16 / 32 / 128 KB slabs, a latent's 20
        (128, 1, 16 * 1024, (16, 1)),
        (64, 1, 32 * 1024, (16, 1)),
        (64, 1, 128 * 1024, (16, 1)),
        (256, 1, 20 * 1024, (16, 1)),
        # 256 KB slabs (32 kv heads x 128): the ring's 8 MiB hold 8 rows
        (64, 1, 256 * 1024, (8, 1)),
        # admission waves of 8 x 128 tokens: nine slabs a row at most
        (8, 128, 16 * 1024, (8, 9)),
        (8, 128, 128 * 1024, (1, 9)),
        # a verify pass of five tokens touches two tiles at most
        (12, 5, 16 * 1024, (4, 2)),
    ],
)
def test_write_plan(batch, chunk, slab, want):
    assert kv_page_write.write_plan(batch, chunk, slab, 16) == want


@pytest.mark.parametrize(
    "start,count,tiles",
    [(0, 0, 0), (5, 1, 1), (15, 2, 2), (0, 128, 8), (87, 128, 9),
     (16, 16, 1), (126, 5, 2)],
)
def test_tiles_of_run(start, count, tiles):
    assert kv_page_write.tiles_of_run(start, count, 16) == tiles
