"""The serial section of a chunk boundary (ISSUE 51): from the end of one
chunk's ``gen_engine/flag_wait`` to the return of the next chunk's
``_dispatch_chunk`` every stretch that touches the device lies under a
leaf span of its own, the census runs BEHIND the enqueue, and it counts
what it counted in front of it. Held WITHOUT a clock: by
``tracing.live_spans()`` at the wrapped calls and by the ring's order."""

import collections
import threading

import numpy as np
import pytest

import jax

from areal_tpu.base import metrics as metrics_mod
from areal_tpu.base import tracing
from areal_tpu.gen import engine as engine_mod
from areal_tpu.gen.engine import GenerationEngine, GenRequest
from areal_tpu.models import transformer as tfm
from tests import test_granite_hybrid as granite
from tests import test_smallthinker as smallthinker
from tests.test_gen_engine import CFG as PLAIN

STEPS = 4
# what wraps each call, innermost (the issue's table)
LEAF = {
    "device_get": "gen_engine/harvest/pull",
    "_dispatch_chunk": "gen_engine/dispatch/enqueue",
    "_run_extends": "gen_engine/admit/prefill",
    "_copy_state": "gen_engine/admit/prefill",
    "_take_snapshots": "gen_engine/admit/prefill",
    "_seat": "gen_engine/dispatch/seat",
    "_kernel_counts": "gen_engine/census",
}
# a new span's parent in the ring
PARENT = {
    "gen_engine/harvest/pull": "gen_engine/harvest",
    "gen_engine/admit/prefill": "gen_engine/admit",
    "gen_engine/dispatch/seat": "gen_engine/dispatch",
    "gen_engine/dispatch/enqueue": "gen_engine/dispatch",
    "gen_engine/census": "gen_engine/chunk",
    "gen_engine/dispatch": "gen_engine/chunk",
    "gen_engine/admit": "gen_engine/chunk",
    "gen_engine/flag_wait": "gen_engine/chunk",
    "gen_engine/harvest": "gen_engine/chunk",
}


@pytest.fixture(scope="module")
def models():
    return {
        "plain": (PLAIN, tfm.init_params(PLAIN, jax.random.key(5)), {}),
        "windowed": (smallthinker.CFG, smallthinker._weights(smallthinker.CFG),
                     {"page_size": smallthinker.PAGE, "n_pages": 96}),
        "stateful": (granite.CFG, granite.seeded_params(granite.CFG), {}),
    }


def _engine(models, model, pipelined, kernel=False, **kw):
    cfg, params, over = models[model]
    kw = {"max_slots": 4, "max_seqlen": 128, "max_new_tokens_cap": 48,
          "page_size": 8, "admit_buckets": (1, 2, 4), "seed": 3,
          "pipeline_chunks": pipelined, **over, **kw}
    eng = GenerationEngine(cfg, params, **kw)
    if kernel:
        # the paged kernel's census (``_kernel_counts`` with the shared
        # plan's host twin) runs where the kernel does: interpret mode here
        eng._decode_use_pallas = True
    return eng


def _submit(eng, vocab, n=6, group=3, seed=11, **kw):
    """``n`` requests in groups on one prompt (prefix hits, snapshots), of
    lengths that end in different chunks (harvests in most of them)."""
    rng = np.random.RandomState(seed)
    kw = {"temperature": 1.0, **kw}
    for i in range(n):
        if i % group == 0:
            prompt = rng.randint(1, vocab, 17 + 3 * i).tolist()
        eng.submit(GenRequest(
            rid=f"r{i}", input_ids=list(prompt),
            max_new_tokens=5 + 4 * (i % 4), **kw))


def _innermost_here():
    me = threading.current_thread().name
    mine = [s["name"] for s in tracing.live_spans() if s["thread"] == me]
    return mine[-1] if mine else None


@pytest.mark.parametrize("model,pipelined", [
    ("plain", False), ("plain", True), ("windowed", False),
    ("stateful", False)])
def test_no_hole_in_the_serial_section(models, model, pipelined, monkeypatch):
    eng = _engine(models, model, pipelined)
    seen = collections.Counter()
    enqueued = [0]

    def wrap(owner, name):
        orig = getattr(owner, name)

        def inner(*a, **k):
            assert _innermost_here() == LEAF[name], (name, _innermost_here())
            if name == "_kernel_counts":
                # only after the chunk it counts is on the device's queue
                assert enqueued[0] == seen["_kernel_counts"] + 1
            seen[name] += 1
            out = orig(*a, **k)
            if name == "_dispatch_chunk":
                enqueued[0] += 1
            return out

        monkeypatch.setattr(owner, name, inner)

    for name in LEAF:
        wrap(engine_mod.jax if name == "device_get" else eng, name)
    tracing.drain()
    _submit(eng, models[model][0].vocab_size)
    outs = eng.run_until_done(decode_steps=STEPS)
    assert len(outs) == 6
    monkeypatch.undo()

    # every wrapped call was reached (the state copies and snapshots where
    # the model has per-slot state)
    stateful = {"_copy_state", "_take_snapshots"}
    for name in LEAF:
        assert (seen[name] > 0) == (model == "stateful" or name not in stateful), name
    assert seen["_kernel_counts"] == seen["_dispatch_chunk"] == enqueued[0]

    # the ring: every record of the section hangs where the table says,
    # and a chunk's census closes behind its dispatch and (where the chunk
    # waits for its own flags) in front of its flag wait
    ring = tracing.drain()
    by_id = {r["span_id"]: r for r in ring}
    order = {r["span_id"]: i for i, r in enumerate(ring)}
    children = collections.defaultdict(dict)
    for r in ring:
        if r["name"] in PARENT:
            assert by_id[r["parent_id"]]["name"] == PARENT[r["name"]], r["name"]
            if PARENT[r["name"]] == "gen_engine/chunk":
                children[r["parent_id"]].setdefault(r["name"], r)
    n_census = 0
    for chunk_id, kids in children.items():
        if "gen_engine/census" not in kids:
            # a chunk that seated nobody dispatched and counted nothing
            assert "slots" not in by_id[chunk_id].get("attrs", {})
            continue
        n_census += 1
        at = order[kids["gen_engine/census"]["span_id"]]
        assert order[kids["gen_engine/dispatch"]["span_id"]] < at
        if not pipelined:
            assert at < order[kids["gen_engine/flag_wait"]["span_id"]]
        assert by_id[chunk_id]["attrs"]["slots"] >= 1
    assert n_census == enqueued[0]
    enqueues = [r for r in ring if r["name"] == "gen_engine/dispatch/enqueue"]
    assert all(
        r["attrs"]["table_width"] == by_id[r["parent_id"]]["attrs"]["table_width"]
        for r in enqueues)
    # a pull moves what its rows WROTE: ``rows`` harvested slots, ``blocks``
    # of the engine's block of positions gathered (padding included), and
    # ``bytes`` no more than those blocks hold (8 B a position here)
    pulls = [r["attrs"] for r in ring if r["name"] == "gen_engine/harvest/pull"]
    assert pulls and all(p["bytes"] > 0 for p in pulls)
    assert all(1 <= p["rows"] <= eng.B for p in pulls)
    assert all(p["blocks"] >= eng._pull_counts[0] for p in pulls)
    assert all(
        p["bytes"] == p["blocks"] * eng._pull_block * 8 for p in pulls)
    assert sum(p["rows"] for p in pulls) == 6
    prefills = [r for r in ring if r["name"] == "gen_engine/admit/prefill"]
    assert prefills and all(r["attrs"]["programs"] >= 2 for r in prefills)


@pytest.mark.parametrize("model,pipelined,kernel", [
    ("plain", False, True), ("plain", True, True),
    ("windowed", False, False), ("stateful", True, False)])
def test_census_behind_the_enqueue_counts_what_it_counted_in_front(
        models, model, pipelined, kernel, monkeypatch):
    """``_census`` run IN FRONT of the enqueue (where the parent computed
    it) on the same state gives the attributes and the ``engine.stats``
    sums that the one behind the enqueue then gives, value for value."""
    eng = _engine(models, model, pipelined, kernel=kernel)
    seat, chunk_fn = eng._seat, eng._chunk_fn
    dispatch_chunk, census = eng._dispatch_chunk, eng._census
    now, checked = {}, []

    def seat_(span, chunk_attrs):
        now["running"] = seat(span, chunk_attrs)
        return now["running"]

    def chunk_fn_(n_steps, *a, **k):
        now["steps"] = n_steps
        return chunk_fn(n_steps, *a, **k)

    def dispatch_chunk_(chunk, W, warp_idx):
        stats0, room0 = dict(eng.stats), eng._room
        in_front = {}
        census(now["steps"], W, list(now["running"]), in_front)
        now["want"] = (in_front, dict(eng.stats), eng._room)
        eng.stats.clear()
        eng.stats.update(stats0)
        eng._room = room0
        return dispatch_chunk(chunk, W, warp_idx)

    def census_(decode_steps, W, running, chunk_attrs):
        census(decode_steps, W, running, chunk_attrs)
        in_front, stats, room = now.pop("want")
        assert (decode_steps, running) == (now["steps"], now["running"])
        assert {k: chunk_attrs[k] for k in in_front} == in_front
        assert dict(eng.stats) == stats and eng._room == room
        checked.append(in_front)

    monkeypatch.setattr(eng, "_seat", seat_)
    monkeypatch.setattr(eng, "_chunk_fn", chunk_fn_)
    monkeypatch.setattr(eng, "_dispatch_chunk", dispatch_chunk_)
    monkeypatch.setattr(eng, "_census", census_)
    _submit(eng, models[model][0].vocab_size)
    assert len(eng.run_until_done(decode_steps=STEPS)) == 6
    assert len(checked) >= 4
    keys = set().union(*checked)
    assert {"slots", "resident_tokens", "cache_bytes_per_token",
            "layer_passes", "loop_passes", "cache_layers"} <= keys
    if kernel:
        assert {"kernel_positions", "kernel_steps", "kv_pages_named",
                "kv_pages_read", "kv_shared_rows"} <= keys
        # (a group of three on one prompt: the shared plan engaged)
        assert any(c["kv_pages_read"] < c["kv_pages_named"] for c in checked)
    if model == "windowed":
        assert {"window_resident_tokens", "cache_bytes_per_token_window",
                "moe_grouped_rows", "moe_dense_rows"} <= keys
    if model == "stateful":
        assert {"state_slots", "state_bytes_per_slot", "state_layers"} <= keys


NEW_SPANS = ("gen_engine/harvest/pull", "gen_engine/admit/prefill",
             "gen_engine/dispatch/seat", "gen_engine/dispatch/enqueue",
             "gen_engine/census")


def test_spans_off_same_tokens_and_counters_only(models, monkeypatch):
    """``AREAL_TRACE_SPANS=0``: a seeded run samples the same tokens, and
    the new spans leave their ``<name>_s`` / ``<name>_n`` sums and nothing
    else: no ring record, no live span."""
    def run():
        eng = _engine(models, "plain", False)
        _submit(eng, PLAIN.vocab_size)
        outs = eng.run_until_done(decode_steps=STEPS)
        return {o.rid: (o.output_ids, o.output_logprobs) for o in outs}

    tracing.drain()
    on = run()
    assert {r["name"] for r in tracing.drain()} >= set(NEW_SPANS)
    monkeypatch.setenv("AREAL_TRACE_SPANS", "0")
    n0 = {n: metrics_mod.counters.get(f"{n}_n") for n in NEW_SPANS}
    live = []
    seat = GenerationEngine._seat
    monkeypatch.setattr(
        GenerationEngine, "_seat",
        lambda self, *a: live.append(tracing.live_spans()) or seat(self, *a))
    off = run()
    assert off == on
    assert tracing.drain() == [] and live and not any(live)
    for n in NEW_SPANS:
        assert metrics_mod.counters.get(f"{n}_n") > n0[n], n
        assert metrics_mod.counters.get(f"{n}_s") > 0.0
