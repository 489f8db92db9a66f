"""A page that several rows of a decode call name is read ONCE.

``ops/paged_attention.py:shared_prefix_step`` observes a call's page table
(rows whose tables agree on their leading whole pages form groups), the
``paged_decode_prefix`` program reads a group's pages once for all its
members, and the kernel over the rows' own pages goes on from the state it
leaves. Each case holds that path (interpret mode) to the XLA gather
reference, ``paged_decode_attention(use_pallas=False)``, and to the kernel
as it runs without sharing, over the same pool, table and lengths. What
Mosaic refuses the interpreter cannot see: ``tests/test_tpu_compile.py``
compiles both programs for a described v5e.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.ops import paged_attention as paged_ops
from areal_tpu.ops.pallas import paged_attention as pl_paged

L = 2
# rows of every case's call (free slots behind its groups) and pages of its
# pool: one shape a geometry, so one compiled program
ROWS, POOL, M = 24, 96, 8
# (query heads, kv heads, head dim, page)
GEOMETRIES = {
    # the bulk of the cases: the 1.5B cell's heads at a width and a page the
    # interpreter gets through quickly
    "12q2kv_p16": (12, 2, 32, 16),
    "12q2kv_p128": (12, 2, 128, 128),
    "16q16kv_p64": (16, 16, 128, 64),
    "28q4kv_p128": (28, 4, 128, 128),
}


def _tables(groups, page, rng, stale=False):
    """``(table [B, M], lens [B], n_pool_pages)`` of ``groups``: a list of
    ``(members, prompt tokens, [own tokens a member])``; members of a group
    name the same pages for the whole pages of their prompt and their own
    behind them (the page a prompt ends in is each member's copy). ``members
    == 0`` is a free slot. ``stale``: entries past a row's pages hold the
    page another group's prompt starts with, as a table that was another
    request's would."""
    rows, lens, nxt = [], [], 1
    for members, prompt, own in groups:
        n_shared = prompt // page
        shared = list(range(nxt, nxt + n_shared))
        nxt += n_shared
        for i in range(max(members, 1)):
            if members == 0:
                rows.append([]), lens.append(0)
                continue
            n = prompt + own[i]
            mine = -(-n // page) - n_shared
            rows.append(shared + list(range(nxt, nxt + mine)))
            nxt += mine
            lens.append(n)
    assert len(rows) <= ROWS, len(rows)
    rows += [[] for _ in range(ROWS - len(rows))]
    lens += [0] * (ROWS - len(lens))
    table = np.zeros((len(rows), M), np.int32)
    for b, r in enumerate(rows):
        assert len(r) <= M, (len(r), M)
        table[b, :len(r)] = r
        if stale:
            table[b, len(r):] = rng.integers(1, nxt, M - len(r))
    return table, np.asarray(lens, np.int32), nxt


def _group_sizes(sizes, prompt, page, rng):
    return [
        (n, prompt, [int(x) for x in rng.integers(0, 2 * page + page // 3, max(n, 1))])
        for n in sizes
    ]


# name -> (geometry, table builder(page, rng) -> groups, options)
CASES = {}
for size in (1, 2, 5, 16, 17):
    CASES[f"group_of_{size}"] = (
        "12q2kv_p16", lambda page, rng, n=size: _group_sizes(
            [n, 1, 0, 3], 2 * page + page // 8 + 1, page, rng), {})
for pages_of_prompt in (0, 1, 2, 3):
    CASES[f"prefix_of_{pages_of_prompt}_pages"] = (
        "12q2kv_p16", lambda page, rng, n=pages_of_prompt: _group_sizes(
            [4, 2], n * page + page // 2 - 1, page, rng), {})
CASES["prompt_ends_on_a_page"] = (
    "12q2kv_p16",
    lambda page, rng: [(3, 2 * page, [0, 1, page]), (2, page, [0, 0])], {})
CASES["groups_loners_and_free_slots"] = (
    "12q2kv_p16", lambda page, rng: _group_sizes(
        [0, 3, 1, 0, 1, 6, 1, 2, 0], page + 3 * page // 4, page, rng), {})
CASES["12q_2kv_page_128"] = (
    "12q2kv_p128", lambda page, rng: _group_sizes(
        [3, 1, 2, 0], page + 30, page, rng), {})
CASES["n_rep_1_page_64"] = (
    "16q16kv_p64", lambda page, rng: _group_sizes(
        [5, 1, 2, 0], 3 * page + 9, page, rng), {})
CASES["28q_4kv"] = (
    "28q4kv_p128", lambda page, rng: _group_sizes(
        [4, 1, 2], page + 30, page, rng), {})
CASES["soft_cap"] = (
    "12q2kv_p16", lambda page, rng: _group_sizes(
        [3, 2, 1], 2 * page + 1, page, rng), {"soft_cap": 30.0})
CASES["stale_entries_past_a_row"] = (
    "12q2kv_p16", lambda page, rng: _group_sizes(
        [4, 1, 3], page + page // 2, page, rng), {"stale": True})
CASES["later_entries_collide"] = (
    "12q2kv_p16", lambda page, rng: _group_sizes(
        [2, 2], 3 * page + 5, page, rng), {"collide": True})
CASES["rows_not_active"] = (
    "12q2kv_p16", lambda page, rng: _group_sizes(
        [5, 3, 2, 0], 2 * page + 3, page, rng), {"inactive": [1, 3, 5, 6, 8]})
CASES["more_groups_than_blocks"] = (
    "12q2kv_p16", lambda page, rng: _group_sizes(
        [2] * 8, page + 5, page, rng), {})


@functools.partial(jax.jit, static_argnames=("how", "soft_cap"))
def _attend(q, k, v, pool, table, lens, active, *, how, soft_cap=None):
    """The step's attention as ``decode_step_paged`` runs it (``shared``:
    rows in the step's order, back in slot order), as the kernel runs
    without sharing (``kernel``) or as the XLA gather reference. One
    program a shape: the cases of one geometry share theirs."""
    page = pool.shape[4]
    kw = dict(soft_cap=soft_cap, use_pallas=how != "reference")
    if how != "shared":
        return paged_ops.paged_decode_attention(
            q, k, v, pool, jnp.int32(1), table, lens, **kw)
    plan, own_table, own_lens = paged_ops.shared_prefix_step(
        table, lens, active, page)
    order = jnp.argsort(own_lens)
    inverse = jnp.argsort(order)
    prefix = paged_ops.prefix_pass(plan, table, page, order, inverse)
    out = paged_ops.paged_decode_attention(
        q[order], k[order], v[order], pool, jnp.int32(1), own_table[order],
        own_lens[order], shared=prefix, **kw)
    return out[inverse]


@pytest.mark.parametrize("case", list(CASES))
def test_shared_prefix_matches_reference_and_kernel(case):
    geometry, groups, opt = CASES[case]
    Hq, Hkv, D, page = GEOMETRIES[geometry]
    rng = np.random.default_rng(sum(map(ord, case)))
    table, lens, P = _tables(
        groups(page, rng), page, rng, stale=opt.get("stale", False))
    if opt.get("collide"):
        # two groups whose first page differs and whose LATER shared
        # entries are one page: not one group, whatever the later entries
        table[2:4, 1:3] = table[0, 1:3]
    assert P <= POOL, P
    B = len(lens)
    key = jax.random.split(jax.random.PRNGKey(B * M + Hq), 4)
    pool = jax.random.normal(
        key[0], (L, POOL, 2, Hkv, page, D), jnp.bfloat16)
    q = jax.random.normal(key[1], (B, Hq, D), jnp.bfloat16)
    k = jax.random.normal(key[2], (B, Hkv, D), jnp.bfloat16)
    v = jax.random.normal(key[3], (B, Hkv, D), jnp.bfloat16)

    # rows that are not active (held out of a chunk, finished inside it,
    # freed with the device's length still theirs) sit in no group: the
    # groups of three and two are left with one active row, which reads alone
    active = np.ones(B, bool)
    active[opt.get("inactive", [])] = False
    seats, blocks = pl_paged.prefix_plan(B)
    plan = pl_paged.shared_prefix(table, lens, active, page, seats, blocks)
    want_pages = _brute_force_shared(table, lens * active, page)
    # with a block for every row the plan seats every group there is; with
    # the call's own (a block for every four rows) the first of them, and
    # the rows of the others read their pages themselves
    roomy = pl_paged.shared_prefix(table, lens, active, page, seats, B)
    assert roomy.pages.tolist() == want_pages.tolist()
    seated = plan.seat < seats * blocks
    assert seated.any() == want_pages.any()
    assert (plan.pages == np.where(seated, want_pages, 0)).all()
    if case == "more_groups_than_blocks":
        assert plan.pages.tolist() == [1] * 12 + [0] * 12
    if opt.get("collide"):
        assert (plan.seat[:2] // seats != plan.seat[2:4] // seats).all()
    if "inactive" in opt:
        assert plan.pages.tolist()[:10] == [2, 0, 2, 0, 2, 0, 0, 0, 0, 0]

    got, ref, plain = (
        _attend(q, k, v, pool, jnp.asarray(table), jnp.asarray(lens),
                jnp.asarray(active), how=how, soft_cap=opt.get("soft_cap"))
        for how in ("shared", "reference", "kernel"))
    live = lens > 0
    for name, other in (("reference", ref), ("kernel", plain)):
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[live],
            np.asarray(other, np.float32)[live],
            atol=2e-2, rtol=2e-2, err_msg=name)
    # bf16 outputs of one float32 state: nearly every value is the same
    same = np.asarray(got)[live] == np.asarray(plain)[live]
    assert same.mean() > 0.75, same.mean()


def _brute_force_shared(table, lens, page):
    """Leading whole pages each row shares with some other row, where at
    least one of those rows shares no MORE with a third (the members of a
    group read as one; a row whose partners all belong to longer groups
    reads alone)."""
    B = len(lens)
    whole = lens // page

    def run(b, c):
        n = 0
        while (n < min(whole[b], whole[c]) and table[b, n] == table[c, n]):
            n += 1
        return n

    best = [max([run(b, c) for c in range(B) if c != b] or [0])
            for b in range(B)]
    out = np.zeros(B, np.int64)
    for b in range(B):
        if best[b] and any(
            c != b and best[c] == best[b] and run(b, c) >= best[b]
            for c in range(B)
        ):
            out[b] = best[b]
    return out


# (slots, pages of a grid step) of the own-pages program; the prefix
# program's step is its own, 8 pages (the table's width here)
@pytest.mark.parametrize("sb,kp", [(1, 2), (2, 4)])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_plan_and_census_match_brute_force(seed, sb, kp):
    """The host twins: what the plan seats and what the two programs read
    and compute, against counts made row by row."""
    rng = np.random.default_rng(seed)
    page = 16
    groups = [
        (int(n), int(rng.integers(1, 4 * page)),
         [int(x) for x in rng.integers(0, 3 * page, max(int(n), 1))])
        for n in rng.integers(0, 5, 6)
    ]
    table, lens, _ = _tables(groups, page, rng, stale=True)
    active = rng.random(len(lens)) < 0.85
    seats, blocks = pl_paged.prefix_plan(len(lens))
    plan = pl_paged.shared_prefix(table, lens, active, page, seats, blocks)
    want = _brute_force_shared(table, lens * active, page)
    seated = plan.seat < seats * blocks
    # a seated row reads what the brute force says; the others nothing
    assert (plan.pages[seated] == want[seated]).all()
    assert (plan.pages[~seated] == 0).all()
    # every seat holds the row that names it, blocks hold one group each
    for g in range(blocks):
        members = [r for r in plan.rows[g] if r < len(lens)]
        assert [plan.seat[r] // seats for r in members] == [g] * len(members)
        if not members:
            assert plan.n[g] == 0
            continue
        assert plan.n[g] == plan.pages[members[0]]
        for r in members:
            assert (table[r, :plan.n[g]] == table[members[0], :plan.n[g]]).all()
    # the same plan from the step's arithmetic
    dev = pl_paged.shared_prefix(
        jnp.asarray(table), jnp.asarray(lens), jnp.asarray(active), page,
        seats, blocks, xp=jnp)
    for a, b in zip(plan, dev):
        assert np.array_equal(a, np.asarray(b))

    counts = pl_paged.shared_counts(
        table, lens, active, page, sb, kp, -(-M // kp))
    named = int((-(-lens // page)).sum())
    own = lens - plan.pages * page
    read = int((-(-own // page)).sum() + plan.n.sum())
    assert counts["kv_pages_named"] == named
    assert counts["kv_pages_read"] == read
    assert counts["kv_shared_rows"] == int(seated.sum())
    assert counts["kv_shared_groups"] == int((plan.n > 0).sum())
    span = kp * page
    prefix_span = min(pl_paged.PAGES_PER_STEP, M) * page
    prefix_steps = [-(-int(plan.n[g]) * page // prefix_span)
                    for g in range(blocks)]
    prefix_positions = sum(
        int((plan.rows[g] < len(lens)).sum()) * prefix_span * prefix_steps[g]
        for g in range(blocks))
    assert counts["kernel_positions"] == prefix_positions + (
        pl_paged.kernel_positions(np.sort(own), sb, span))
    own_steps, own_total = pl_paged.kernel_steps(
        np.sort(own), sb, span, -(-M // kp))
    assert counts["kernel_steps_active"] == own_steps + sum(prefix_steps)
    assert counts["kernel_steps"] == own_total + blocks * -(-M // 8)


@pytest.mark.parametrize("run", [3, 8, 9, 21, 40])
def test_census_is_handed_the_columns_the_longest_shared_run_needs(run):
    """The census cuts the table it hands the plan to 8, 16, ... columns
    where no two rows that reach further agree on all of them: its counts
    are those of the plan over the whole table, whatever the longest run."""
    rng = np.random.default_rng(run)
    page, B, width = 4, 8, 48
    table = rng.permutation(np.arange(1, B * width + 1)).reshape(
        B, width).astype(np.int32)
    table[1:4, :run] = table[0, :run]         # a group of four on ``run`` pages
    table[5, :2] = table[4, :2]               # and one of two on two
    lens = np.array([run * page + 7, run * page, 44 * page + 1,
                     run * page + 2 * page, 3 * page, 2 * page + 1, 0, 9])
    active = lens > 0
    seats, blocks = pl_paged.prefix_plan(B)
    plan = pl_paged.shared_prefix(table, lens, active, page, seats, blocks)
    assert plan.pages.tolist() == [run] * 4 + [2, 2, 0, 0]
    whole = np.where(active, lens // page, 0)
    cut = pl_paged._columns_compared(table, whole)
    assert cut == (8 if run < 8 else 16 if run < 16 else 32 if run < 32 else 44)
    counts = pl_paged.shared_counts(table, lens, active, page, 1, 2, width // 2)
    own = lens - plan.pages * page
    assert counts["kv_pages_read"] == int((-(-own // page)).sum()) + run + 2
    assert counts["kv_pages_named"] == int((-(-lens // page)).sum())
    assert (counts["kv_shared_groups"], counts["kv_shared_rows"]) == (2, 6)
