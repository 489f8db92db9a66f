"""The harvest pulls what the finished rows WROTE (ISSUE 60): blocks of
their tokens, log-probs and routing gathered on the device, not every
slot's buffers at their whole cap. Held against the whole-buffer pull:
at every ``_pull_outputs`` the state's buffers are read whole
(``np.asarray``), cut at ``n_gen``, and must equal what the call returns,
element for element and dtype for dtype; what the callers then hand out
(``GenOutput``s of a harvest, of ``pause``, of a preempted request;
``partial_outputs``; ``partial_routing``) must be those rows. ONE test,
its cases the engine kinds x the five callers, and a harvest at a cap of
1,024 where the bytes a pull moves are held far under the buffers'."""

import numpy as np
import pytest

import jax

from areal_tpu.base import tracing
from areal_tpu.gen import engine as engine_mod
from areal_tpu.gen.engine import GenerationEngine, GenRequest
from areal_tpu.models import transformer as tfm
from benchmark import weights as bench_weights
from tests import test_gen_engine as dense
from tests import test_joyai_flash as latent
from tests import test_nemotron_h as state
from tests import test_olmoe as routed

STEPS = 4
# engine kind -> (config, weights, constructor arguments)
KINDS = {
    "dense": lambda: (
        dense.CFG, tfm.init_params(dense.CFG, jax.random.key(5)), {}),
    "latent": lambda: (latent.CFG, latent._weights(latent.CFG), {}),
    # per-slot recurrent state seeded from snapshots, its routing recorded
    "state": lambda: (
        state.CFG, state.seeded_params(state.CFG),
        {"record_routing": True}),
    "routing": lambda: (
        routed.CFG, bench_weights.make_weights(
            jax.eval_shape(
                lambda: tfm.init_params(routed.CFG, jax.random.key(0))),
            20260927, jax.numpy.float32),
        {"record_routing": True}),
}
PATHS = ("harvest", "pause", "partial_outputs", "partial_routing", "preempt")
# a cap of 48 in blocks of 16 with programs of 1 and 2 blocks: a row of 33
# or 40 tokens is three blocks, so in every case some pull takes the
# largest program more than once; a cap of 1,024 in blocks of 128 as the
# cells' are
GEOMETRY = {"cap48": (48, (1, 2)), "cap1024": (1024, engine_mod._PULL_COUNTS)}
CASES = [(k, p, "cap48") for k in KINDS for p in PATHS] + [
    (k, "harvest", "cap1024") for k in KINDS]


@pytest.fixture(scope="module")
def models():
    made = {}
    return lambda kind: made.setdefault(kind, KINDS[kind]())


def _submit(eng, vocab, n=6, seed=11):
    """Two groups of three on one prompt each (prefix hits, snapshots), of
    lengths that end in different chunks."""
    rng = np.random.RandomState(seed)
    for i in range(n):
        if i % 3 == 0:
            prompt = rng.randint(1, vocab, 17 + 3 * i).tolist()
        eng.submit(GenRequest(
            rid=f"r{i}", input_ids=list(prompt), temperature=1.0,
            max_new_tokens=(5, 40, 18, 9, 33, 22)[i % 6]))


def _watch(eng, monkeypatch):
    """Every ``_pull_outputs`` held to the whole buffers; ``rows[rid]`` is
    what the newest pull gave of the request in that slot."""
    pull, rows = eng._pull_outputs, {}

    def held_to_the_whole_buffers(slots, flags=None):
        st, slots = eng.state, list(slots)
        n_gen = np.asarray(st.n_gen)
        whole = {
            "out_tokens": np.asarray(st.out_tokens),
            "out_logprobs": np.asarray(st.out_logprobs),
        }
        if st.out_routing is not None:
            whole["out_routing"] = np.asarray(st.out_routing).reshape(
                eng.B, eng.G, -1, eng.cfg.moe.top_k)
        host = pull(slots, flags)
        np.testing.assert_array_equal(host["n_gen"][slots], n_gen[slots])
        np.testing.assert_array_equal(
            host["active"][slots], np.asarray(st.active)[slots])
        np.testing.assert_array_equal(
            host["max_gen"][slots], np.asarray(st.max_gen)[slots])
        for key, buf in whole.items():
            assert sorted(host[key]) == sorted(slots)
            for b in slots:
                got, want = host[key][b], buf[b, : n_gen[b]]
                assert got.dtype == want.dtype and got.shape == want.shape
                np.testing.assert_array_equal(got, want)
        if st.out_routing is None:
            assert host["out_routing"] == {}
        for b in slots:
            rows[eng._slots[b].rid] = {
                key: buf[b, : n_gen[b]] for key, buf in whole.items()}
        return host

    monkeypatch.setattr(eng, "_pull_outputs", held_to_the_whole_buffers)
    return rows


def _assert_output(out, rows, carried=None):
    """``out`` holds the rows of its request's last pull (behind what it
    carried from a preempted tenure), bit for bit."""
    row = rows[out.rid]
    toks, lps = row["out_tokens"].tolist(), row["out_logprobs"].tolist()
    routing = row.get("out_routing")
    if carried is not None:
        toks, lps = carried["tokens"] + toks, carried["logprobs"] + lps
        if routing is not None:
            routing = np.concatenate([carried["routing"], routing])
    assert out.output_ids == toks and out.output_logprobs == lps
    if routing is None:
        assert out.output_routing is None
    else:
        assert out.output_routing.dtype == np.int32
        np.testing.assert_array_equal(out.output_routing, routing)


@pytest.mark.parametrize("kind,path,geometry", CASES)
def test_pull_of_what_was_written_equals_the_whole_buffers(
        models, kind, path, geometry, monkeypatch):
    cfg, params, over = models(kind)
    cap, counts = GEOMETRY[geometry]
    monkeypatch.setattr(engine_mod, "_PULL_COUNTS", counts)
    eng = GenerationEngine(
        cfg, params, max_slots=4, max_seqlen=128, max_new_tokens_cap=cap,
        page_size=8, admit_buckets=(1, 2, 4), seed=3,
        enable_prefix_cache=True, **over)
    assert eng._pull_block == (16 if cap == 48 else 128)
    assert eng._pull_counts[-1] == (2 if cap == 48 else 32)
    # every count of blocks is a program of the engine's start, counted by
    # ``n_jit_entries`` (the drivers' ``jit_entries_added_in_window``)
    assert eng.program_sizes()["pull()"] == len(eng._pull_counts)
    assert eng.n_jit_entries() == len(eng._pull_counts) + 1   # + activity
    rows = _watch(eng, monkeypatch)
    tracing.drain()
    _submit(eng, cfg.vocab_size)
    outs, carried = [], {}
    for _ in range(3):
        outs += eng.step(STEPS)
    running = [s.rid for s in eng._slots if s is not None]
    assert len(running) >= 2

    if path == "pause":
        paused = eng.pause()
        assert {o.rid for o in paused} >= set(running)
        assert any(o.finish_reason == "interrupted" for o in paused)
        outs += paused
    elif path == "partial_outputs":
        for got in (eng.partial_outputs(), eng.partial_outputs(running[:1])):
            assert got and set(got) <= set(running)
            for rid, (toks, lps) in got.items():
                assert toks == rows[rid]["out_tokens"].tolist()
                assert lps == rows[rid]["out_logprobs"].tolist()
    elif path == "partial_routing":
        for rid in running:
            got = eng.partial_routing(rid)
            if eng.state.out_routing is None:
                assert got is None
            else:
                assert len(got) > 0 and got.dtype == np.int32
                np.testing.assert_array_equal(got, rows[rid]["out_routing"])
    elif path == "preempt":
        with eng._lock:
            b = max(range(eng.B), key=lambda b: (
                eng._slots[b] is not None, eng._lens_host[b]))
            rid = eng._slots[b].rid
            eng._preempt(b)
            eng._set_activity(off=[b], drop=[b])
        kept = eng._carried[rid]
        assert kept["tokens"] == rows[rid]["out_tokens"].tolist()
        assert kept["logprobs"] == rows[rid]["out_logprobs"].tolist()
        if eng.state.out_routing is not None:
            np.testing.assert_array_equal(
                kept["routing"], rows[rid]["out_routing"])
        carried[rid] = {
            "tokens": list(kept["tokens"]),
            "logprobs": list(kept["logprobs"]),
            "routing": kept["routing"]}
        assert len(kept["tokens"]) > 0
    if path != "pause":
        outs += eng.run_until_done(decode_steps=STEPS)
        assert sorted(o.rid for o in outs) == [f"r{i}" for i in range(6)]
        assert all(o.finish_reason == "length" for o in outs)
    for o in outs:
        _assert_output(o, rows, carried.get(o.rid))

    # the pulls built nothing (the admission and chunk programs of a
    # fresh engine are built by their first use, as before): every count
    # of blocks they took, the largest more than once, was there
    sizes = eng.program_sizes()
    assert sizes["pull()"] == len(eng._pull_counts)
    assert eng.n_jit_entries() == sum(sizes.values())
    pulls = [r["attrs"] for r in tracing.drain()
             if r["name"] == "gen_engine/harvest/pull"]
    assert pulls
    a_position = 8 + (
        0 if eng.state.out_routing is None
        else 4 * eng.state.out_routing.shape[-1])
    flags = eng.B * 9       # n_gen, active, max_gen where the caller had none
    for p in pulls:
        assert p["rows"] >= 1 and p["blocks"] >= 0
        assert p["blocks"] == 0 or p["blocks"] in eng._pull_counts or (
            p["blocks"] > eng._pull_counts[-1])
        assert 0 < p["bytes"] <= (
            p["blocks"] * eng._pull_block * a_position + flags)
    if cap == 48:
        assert any(p["blocks"] > eng._pull_counts[-1] for p in pulls)
    else:
        # four rows of at most 40 tokens: a block each, padded to 8
        assert all(p["blocks"] == 8 and p["bytes"] == 8 * 128 * a_position
                   for p in pulls)
        assert all(
            p["bytes"] * 4 <= eng.B * eng.G * a_position for p in pulls)
