"""The engine's page policy (``GenerationEngine``'s docstring): every kind's
pages are taken as the slot grows, and when the pool runs dry slots are
held out of a chunk, then preempted — and a client sees nothing of it.

CPU, test-size models, float32, greedy: an engine with a roomy pool is the
reference for every token and log-prob. ``ADMIT_HORIZON = 0`` on an engine
makes admission look no further than the newcomer's first chunk, which is
what drives the dry rule hard in a small test; the default horizon is
there to keep it rare."""

import functools

import numpy as np
import pytest

import jax

from areal_tpu.base import metrics as metrics_mod
from areal_tpu.base import tracing
from areal_tpu.gen.engine import GenerationEngine, GenRequest
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig
from tests.test_granite_hybrid import CFG as STATE_CFG
from tests.test_ouro import CFG as LOOPED_CFG
from tests.test_smallthinker import CFG as WINDOW_CFG

FULL_CFG = ModelConfig(
    n_layers=2, n_q_heads=4, n_kv_heads=2, head_dim=8, hidden_dim=32,
    intermediate_dim=64, vocab_size=128, dtype="float32",
)
TOL = 1e-4

# the four stacks: (config, page, a pool too small to reserve whole outputs)
STACKS = {
    "full": (FULL_CFG, 8, 24),
    # one full + three window kinds a period: four tables a slot
    "window": (WINDOW_CFG, 4, 100),
    # per-slot recurrent state beside the pages, snapshots in the registry
    "state": (STATE_CFG, 8, 24),
    # three passes over two layers: six cache layers behind two of weights
    "looped": (LOOPED_CFG, 8, 24),
}
COUNTERS = ("pages_taken_growing", "slots_held", "preemptions",
            "preempted_tokens_recomputed")


@pytest.fixture(scope="module")
def weights():
    return {
        kind: tfm.init_params(cfg, jax.random.key(1))
        for kind, (cfg, _, _) in STACKS.items()
    }


def _requests():
    """Twelve requests, two thirds of them over one shared preamble."""
    rng = np.random.default_rng(0)
    base = [int(x) for x in rng.integers(1, 128, 19)]
    reqs = []
    for i in range(12):
        own = [int(x) for x in rng.integers(1, 128, int(rng.integers(3, 14)))]
        reqs.append((f"r{i}", (base if i % 3 else []) + own,
                     int(rng.integers(30, 150))))
    return reqs


def _closed_loop(kind, params, n_pages, horizon=None, clients=4, steps=4):
    """Four clients, each submitting its next request when its last is
    done. ``pool.n_unpromised >= 0`` after every step; drained, the pool
    holds nothing but what the registry files. Returns the outputs by
    rid, the engine, and the rids the dry rule held / preempted."""
    cfg, page, _ = STACKS[kind]
    eng = GenerationEngine(
        cfg, params, max_slots=clients, max_seqlen=192, page_size=page,
        n_pages=n_pages, max_new_tokens_cap=160, admit_buckets=(1, 2, 4))
    if horizon is not None:
        eng.ADMIT_HORIZON = horizon
    todo, outs, held, preempted = _requests(), {}, set(), set()

    def submit():
        rid, prompt, g = todo.pop(0)
        eng.submit(GenRequest(
            rid=rid, input_ids=prompt, max_new_tokens=g, greedy=True))

    for _ in range(clients):
        submit()
    n = 0
    while eng.n_pending() or eng.n_running():
        for o in eng.step(steps):
            assert o.rid not in outs
            outs[o.rid] = o
            if todo:
                submit()
        assert eng.pool.n_unpromised >= 0
        held |= {eng._slots[b].rid for b in eng._held_out}
        preempted |= set(eng._carried)
        n += 1
        assert n < 1000, "the closed loop does not end"
    assert eng.pool.reserved == 0 and not eng._held.any()
    assert not eng._carried and not eng._held_out
    assert eng.pool.n_free == eng.n_pages - eng.pool.n_cached_only
    eng.prefix.clear()
    assert eng.pool.n_free == eng.n_pages
    return outs, eng, held, preempted


@pytest.fixture(scope="module")
def roomy_runs(weights):
    """``roomy_runs(kind)``: the stack's run through a pool that seats
    whole outputs, made when a case first asks for its kind (a case pays
    for the run it compares with, not for the four stacks')."""
    return functools.cache(
        lambda kind: _closed_loop(kind, weights[kind], None))


@pytest.fixture(scope="module")
def roomy(roomy_runs):
    """... ``roomy(kind)``: its outputs by rid, the reference for every
    other run."""
    return lambda kind: roomy_runs(kind)[0]


def _assert_same(outs, want):
    for rid, prompt, g in _requests():
        got = outs[rid]
        assert len(got.output_ids) == len(got.output_logprobs) == g
        assert got.finish_reason == "length"
        assert got.output_ids == want[rid].output_ids, rid
        np.testing.assert_allclose(
            got.output_logprobs, want[rid].output_logprobs, atol=TOL,
            err_msg=rid)
        assert got.t_submit <= got.t_admit <= got.t_first <= got.t_done


@pytest.mark.parametrize("kind", list(STACKS))
def test_dry_pool_holds_and_preempts_and_nobody_sees_it(kind, weights, roomy):
    """(a) + (b): admission that looks no further than a first chunk on a
    pool that cannot reserve whole outputs: slots are held, requests are
    preempted and re-admitted, and every request still ends with exactly
    its ``max_new_tokens``, the roomy engine's tokens and log-probs."""
    outs, eng, held, preempted = _closed_loop(
        kind, weights[kind], STACKS[kind][2], horizon=0)
    _assert_same(outs, roomy(kind))
    st = eng.stats
    assert st["preemptions"] >= 1 and st["slots_held"] >= 1
    assert held and preempted
    assert st["admitted"] == 12 + st["preemptions"]
    assert 0 < st["preempted_tokens_recomputed"] <= st["prefill_tokens"]


@pytest.mark.parametrize("kind", list(STACKS))
def test_default_horizon_keeps_the_dry_rule_out(kind, weights, roomy):
    """The same pool under the admission rule as shipped: nobody is held,
    nobody preempted, the outputs are the roomy engine's."""
    outs, eng, held, preempted = _closed_loop(
        kind, weights[kind], STACKS[kind][2])
    _assert_same(outs, roomy(kind))
    assert not held and not preempted
    assert (eng.stats["slots_held"], eng.stats["preemptions"],
            eng.stats["preempted_tokens_recomputed"]) == (0, 0, 0)
    assert eng.stats["pages_taken_growing"] > 0


@pytest.mark.parametrize("kind", ["full", "state", "looped"])
def test_roomy_pool_counts_nothing_but_growth(kind, roomy_runs):
    stats = roomy_runs(kind)[1].stats
    assert [stats[k] for k in COUNTERS[1:]] == [0, 0, 0]
    # every page past a prompt's own was taken by a running slot
    page = STACKS[kind][1]
    assert stats["pages_taken_growing"] == sum(
        -(-(len(p) - 1 + g) // page) - -(-(len(p) - 1) // page)
        for _, p, g in _requests())


# ------------------------------------------------------------------ #
# (c) seats
# ------------------------------------------------------------------ #


def test_seats_more_than_whole_output_reservation(weights):
    """Six requests of 8 pages each when whole (prompt + output = 128
    positions at a page of 16) at different distances from their end, as a
    running population is: prompts of 7, 5, 3, 1, 1, 1 pages. Reserving
    whole outputs, 32 pages seat ``32 // 8 = 4``. Taken as they grow they
    hold 17 pages now, 26 with the two pages of look-ahead each reserves,
    and never more than 30 at once (the first ends after 16 positions, the
    second after 48, the third after 80): FIVE are seated. The sixth
    would make 34 of it 16 positions on: it waits."""
    page, n_pages = 16, 32
    eng = GenerationEngine(
        FULL_CFG, weights["full"], max_slots=8, max_seqlen=128,
        page_size=page, n_pages=n_pages, max_new_tokens_cap=128,
        enable_prefix_cache=False)
    rng = np.random.default_rng(5)
    sizes = []
    for i, a in enumerate((7, 5, 3, 1, 1, 1)):
        g = 128 - a * page
        sizes.append(-(-(a * page + g) // page))
        eng.submit(GenRequest(
            rid=f"s{i}", max_new_tokens=g, greedy=True,
            input_ids=[int(x) for x in rng.integers(1, 128, a * page + 1)]))
    assert set(sizes) == {8}
    whole_output_seats = n_pages // 8
    tracing.drain()
    outs = eng.step(16)
    (first,) = [s["attrs"] for s in tracing.drain()
                if s["name"] == "gen_engine/chunk"]
    assert whole_output_seats == 4
    assert (first["slots_running"], first["slots_held"]) == (5, 0)
    assert eng.n_pending() == 1
    outs += eng.run_until_done(decode_steps=16)
    assert sorted(len(o.output_ids) for o in outs) == [
        16, 48, 80, 112, 112, 112]
    assert eng.stats["preemptions"] == 0 and eng.stats["slots_held"] == 0


# ------------------------------------------------------------------ #
# (e) the counters against hand-made schedules
# ------------------------------------------------------------------ #


def _hand_engine(params, n_pages, gens, **kw):
    """Requests A, B, ... of one page of prompt (9 tokens, 8 prefilled) at
    a page of 8, chunks of 8 steps: a slot takes one page a chunk. At
    admission a slot gets its prompt's page and 4 reserved (the look-ahead
    of 32 positions); ``ADMIT_HORIZON = 0`` admits on that alone."""
    eng = GenerationEngine(
        FULL_CFG, params, max_slots=3, max_seqlen=96, page_size=8,
        n_pages=n_pages, max_new_tokens_cap=80, **kw)
    eng.ADMIT_HORIZON = 0
    rng = np.random.default_rng(3)
    for rid, g in zip("ABC", gens):
        eng.submit(GenRequest(
            rid=rid, max_new_tokens=g, greedy=True,
            input_ids=[int(x) for x in rng.integers(1, 128, 9)]))
    return eng


def _roomy_hand(params, gens):
    eng = _hand_engine(params, None, gens)
    return {o.rid: o for o in eng.run_until_done(decode_steps=8)}


# pool, outputs, then what the schedule below says: the counters and the
# (slots_running, slots_held, preemptions, pages_taken_growing) of every
# chunk span
HAND = {
    # 17 pages, A and B of 64 tokens, C of 32. Admitted: 3 x (1 + 4) = 15.
    # Chunks 1-4: three slots a page each; C ends (5 pages back, its
    # prompt's page stays with the registry). Chunks 5-7: A and B a page
    # each, 16 held after chunk 7, the last unpromised page (the
    # registry's) promised to A, the older. Chunk 8: A takes its ninth
    # page, B has none to take: HELD for one chunk; A ends. Chunk 9: B.
    "hold": (17, (64, 64, 32), (20, 1, 0, 0), [
        (3, 0, 0, 3)] * 4 + [(2, 0, 0, 2)] * 3 + [(1, 1, 0, 1), (1, 0, 0, 1)]),
    # 12 pages, A and B of 64 tokens. Admitted: 2 x (1 + 4) = 10. Chunks
    # 1-5: a page each, 12 held after chunk 5. Chunk 6: neither can take
    # its seventh page, every slot would be held: A (as short as B, the
    # older) is PREEMPTED with 40 tokens, its 6 full pages filed and
    # released; B takes one and asks the registry for a batch, which
    # costs A its filed pages. Chunks 7-8: B, which ends. Chunk 9: A is
    # admitted again, prefills its 48 positions AGAIN, and runs 3 chunks.
    "preempt": (12, (64, 64), (16, 0, 1, 48), [
        (2, 0, 0, 2)] * 5 + [(1, 0, 1, 1)] + [(1, 0, 0, 1)] * 5),
}


@pytest.mark.parametrize("case", list(HAND))
def test_counters_read_what_the_schedule_says(case, weights):
    n_pages, gens, counters, chunks = HAND[case]
    params = weights["full"]
    want = _roomy_hand(params, gens)
    before = {
        name: metrics_mod.counters.get(name) for name in (
            metrics_mod.GEN_PAGES_TAKEN_GROWING, metrics_mod.GEN_SLOTS_HELD,
            metrics_mod.GEN_PREEMPTIONS,
            metrics_mod.GEN_PREEMPTED_TOKENS_RECOMPUTED)}
    eng = _hand_engine(params, n_pages, gens)
    tracing.drain()
    outs = {o.rid: o for o in eng.run_until_done(decode_steps=8)}
    spans = tracing.drain()
    assert tuple(eng.stats[k] for k in COUNTERS) == counters
    assert tuple(
        metrics_mod.counters.get(name) - was for name, was in before.items()
    ) == counters
    seen = [s["attrs"] for s in spans if s["name"] == "gen_engine/chunk"
            and "slots_running" in s.get("attrs", {})]
    assert [(a["slots_running"], a["slots_held"], a["preemptions"],
             a["pages_taken_growing"]) for a in seen] == chunks
    assert all(a["slots"] == a["slots_running"] for a in seen)
    admits = [s["attrs"] for s in spans if s["name"] == "gen_engine/admit"]
    assert sum(a["preempted_tokens_recomputed"] for a in admits) == counters[3]
    for rid, g in zip("ABC", gens):
        assert outs[rid].output_ids == want[rid].output_ids
        np.testing.assert_allclose(
            outs[rid].output_logprobs, want[rid].output_logprobs, atol=TOL)
        assert outs[rid].finish_reason == "length"


# ------------------------------------------------------------------ #
# (d) cancel, pause, update_params while a slot is held or a request
# waits preempted
# ------------------------------------------------------------------ #


def _to_state(params, state):
    """``held``: the hold schedule after chunk 8 (A done, B held with 56
    tokens, nothing running). ``preempted``: the preempt schedule after
    chunk 6 (B running with 48 tokens, A waiting with its 40)."""
    n_pages, gens, _, _ = HAND["hold" if state == "held" else "preempt"]
    eng = _hand_engine(params, n_pages, gens)
    done = []
    for _ in range(8 if state == "held" else 6):
        done += eng.step(8)
    if state == "held":
        assert sorted(o.rid for o in done) == ["A", "C"]
        (b,) = eng._held_out
        assert eng._slots[b].rid == "B" and eng.n_pending() == 0
    else:
        assert not done and list(eng._carried) == ["A"]
        assert [r.rid for r in eng._pending] == ["A"]
        assert len(eng._pending[0].input_ids) == 9 + 40
        assert eng._pending[0].max_new_tokens == 24
    return eng, gens


def _drained(eng):
    assert eng.n_running() == 0 and eng.n_pending() == 0
    assert eng.pool.reserved == 0 and not eng._held.any()
    assert not eng._carried and not eng._held_out
    eng.prefix.clear()
    assert eng.pool.n_free == eng.n_pages


@pytest.mark.parametrize("state", ["held", "preempted"])
@pytest.mark.parametrize("action", ["pause", "cancel", "update_params"])
def test_interventions_return_pages_and_partial_output_once(
        state, action, weights):
    params = weights["full"]
    eng, gens = _to_state(params, state)
    want = _roomy_hand(params, gens)
    rid, n_had = ("B", 56) if state == "held" else ("A", 40)
    # what a streaming caller sees of it meanwhile
    toks, lps = eng.partial_outputs([rid])[rid]
    assert toks == want[rid].output_ids[:n_had] and len(lps) == n_had
    if action == "pause":
        outs = {o.rid: o for o in eng.pause()}
        assert sorted(outs) == (["B"] if state == "held" else ["A", "B"])
        assert outs[rid].finish_reason == "interrupted"
        assert outs[rid].output_ids == want[rid].output_ids[:n_had]
        np.testing.assert_allclose(
            outs[rid].output_logprobs, want[rid].output_logprobs[:n_had],
            atol=TOL)
        assert outs[rid].t_first is not None
        assert eng.pause() == []            # once
        eng.resume()
        assert eng.run_until_done(decode_steps=8) == []
    elif action == "cancel":
        assert eng.cancel(rid) is True
        assert eng.cancel(rid) is False     # once
        assert rid not in eng.partial_outputs()
        rest = eng.run_until_done(decode_steps=8)
        assert [o.rid for o in rest] == ([] if state == "held" else ["B"])
    else:
        eng.update_params(eng.params, version=7)
        # the preempted request's filed pages went with the old weights'
        assert len(eng.prefix) == 0 and eng.pool.n_cached_only == 0
        rest = {o.rid: o for o in eng.run_until_done(decode_steps=8)}
        assert sorted(rest) == (["B"] if state == "held" else ["A", "B"])
        assert rest[rid].output_ids == want[rid].output_ids
        np.testing.assert_allclose(
            rest[rid].output_logprobs, want[rid].output_logprobs, atol=TOL)
        assert (rest[rid].finish_reason, rest[rid].version) == ("length", 7)
    _drained(eng)


def test_a_request_that_could_not_run_alone_is_refused(weights):
    eng = GenerationEngine(
        FULL_CFG, weights["full"], max_slots=2, max_seqlen=128, page_size=8,
        n_pages=6)
    with pytest.raises(ValueError, match="could not run even alone"):
        eng.submit(GenRequest(
            rid="big", input_ids=list(range(1, 10)), max_new_tokens=48))
    eng.submit(GenRequest(
        rid="fits", input_ids=list(range(1, 10)), max_new_tokens=40,
        greedy=True))
    (out,) = eng.run_until_done(decode_steps=8)
    assert len(out.output_ids) == 40


@pytest.mark.parametrize("pipelined", [False, True])
def test_pipelined_chunks_settle_before_the_dry_rule_acts(
        pipelined, weights, roomy):
    """The dispatch-ahead path takes pages for the chunk in flight too;
    where the pool is short it resolves that chunk first, so holding and
    preempting act on what the device has done."""
    cfg, page, n_pages = STACKS["full"]
    eng = GenerationEngine(
        cfg, weights["full"], max_slots=4, max_seqlen=192, page_size=page,
        n_pages=n_pages, max_new_tokens_cap=160, admit_buckets=(1, 2, 4),
        pipeline_chunks=pipelined)
    eng.ADMIT_HORIZON = 0
    reqs = _requests()[:8]
    for rid, prompt, g in reqs:
        eng.submit(GenRequest(
            rid=rid, input_ids=prompt, max_new_tokens=g, greedy=True))
    outs = {}
    while eng.n_pending() or eng.n_running() or eng.has_inflight:
        for o in eng.step(4):
            assert o.rid not in outs
            outs[o.rid] = o
        assert eng.pool.n_unpromised >= 0
    assert eng.stats["preemptions"] + eng.stats["slots_held"] >= 1
    for rid, _, g in reqs:
        assert outs[rid].output_ids == roomy("full")[rid].output_ids
        np.testing.assert_allclose(
            outs[rid].output_logprobs, roomy("full")[rid].output_logprobs,
            atol=TOL)
    _drained(eng)
