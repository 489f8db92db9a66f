"""``benchmark/idle_partition`` (ISSUE 51): every idle nanosecond of a
traced window goes to ONE program span, the innermost open on the thread
that runs ``gen_engine/chunk``; and the nine readers that stand on it and
on the span ring. Synthetic planes with known answers (those of
``tests/test_program_spans.py``), no clock, no subprocess."""

import time
import types

import pytest

from areal_tpu.base import tracing
from benchmark import idle_partition, program_spans
from benchmark.run import load_reader
from tests.test_program_spans import MS, OPS, _loaded

CHUNK = idle_partition.HOLDER
# device busy [10,40) and [60,70) of a 100 ms window (OPS): idle is
# [0,10) [40,60) [70,100) = 60 ms
IDLE_MS = 60.0


def _ms(parts):
    return {k: pytest.approx(v * 1e3) for k, v in parts.items() if v}


@pytest.mark.parametrize("spans,want_ms", [
    # nested: the child takes its part, the parent what is left of it
    ([[CHUNK, 5 * MS, 90 * MS], ["gen_engine/admit", 40 * MS, 15 * MS],
      ["gen_engine/admit/prefill", 45 * MS, 5 * MS]],
     {CHUNK: 5 + 5 + 25, "gen_engine/admit": 10,
      "gen_engine/admit/prefill": 5, "outside": 5 + 5}),
    # siblings under one parent, a hole between them is the parent's
    ([[CHUNK, 0.0, 100 * MS], ["gen_engine/flag_wait", 40 * MS, 5 * MS],
      ["gen_engine/harvest", 50 * MS, 10 * MS]],
     {CHUNK: 10 + 5 + 30, "gen_engine/flag_wait": 5,
      "gen_engine/harvest": 10}),
    # an idle interval that straddles a span's edges: [40,60) under a span
    # that opens at 50 and one that closes at 45
    ([[CHUNK, 30 * MS, 15 * MS], [CHUNK, 50 * MS, 25 * MS]],
     {CHUNK: 5 + 10 + 5, "outside": 10 + 5 + 25}),
    # a second thread's spans are ignored, whatever they cover
    ([[CHUNK, 40 * MS, 20 * MS, "engine"],
      ["train_pipe/pack", 0.0, 100 * MS, "packer"],
      ["gen_engine/admit", 42 * MS, 4 * MS, "engine"]],
     {CHUNK: 16, "gen_engine/admit": 4, "outside": 40}),
    # three levels, the innermost wins; a span wholly over busy time gets 0
    ([[CHUNK, 0.0, 100 * MS], ["gen_engine/dispatch", 70 * MS, 20 * MS],
      ["gen_engine/dispatch/enqueue", 75 * MS, 10 * MS],
      ["gen_engine/census", 15 * MS, 10 * MS]],
     {CHUNK: 10 + 20 + 10, "gen_engine/dispatch": 10,
      "gen_engine/dispatch/enqueue": 10}),
])
def test_partition_gives_each_idle_instant_to_the_innermost_span(
        spans, want_ms):
    got = idle_partition.partition_loaded(_loaded(OPS, spans))
    assert _ms(got) == want_ms
    # the parts sum to the window's idle exactly
    assert sum(got.values()) == pytest.approx(IDLE_MS / 1e3, abs=1e-12)


def test_parts_and_the_rest_add_up_to_the_idle():
    spans = [
        [CHUNK, 0.0, 95 * MS], ["gen_engine/admit", 1 * MS, 8 * MS],
        ["gen_engine/admit/prefill", 3 * MS, 4 * MS],
        ["gen_engine/dispatch", 40 * MS, 6 * MS],
        ["gen_engine/dispatch/seat", 40 * MS, 2 * MS],
        ["gen_engine/dispatch/enqueue", 43 * MS, 3 * MS],
        ["gen_engine/census", 46 * MS, 4 * MS],
        ["gen_engine/flag_wait", 50 * MS, 22 * MS],
        ["gen_engine/harvest", 72 * MS, 10 * MS],
        ["gen_engine/harvest/pull", 73 * MS, 3 * MS],
    ]
    parts = idle_partition.partition_loaded(_loaded(OPS, spans))
    by_part = {p: idle_partition.part_seconds(parts, p) * 1e3
               for p in list(idle_partition.PARTS) + [idle_partition.REST]}
    assert by_part == {
        "flag_wait": pytest.approx(10 + 2), "harvest": pytest.approx(10),
        "admit_plan": pytest.approx(4), "admit_prefill": pytest.approx(4),
        "dispatch": pytest.approx(6),
        # the caller's loop [95,100), the chunk's self time, the census
        idle_partition.REST: pytest.approx(5 + 1 + 1 + 13 + 4),
    }
    assert sum(by_part.values()) == pytest.approx(IDLE_MS)
    # plan + prefill is what the accepted gen.admit_idle_share reads
    assert by_part["admit_plan"] + by_part["admit_prefill"] == pytest.approx(
        1e3 * program_spans.idle_seconds(
            _loaded(OPS, spans), ["gen_engine/admit"]))


@pytest.mark.parametrize("case", [
    "clocks_disagree", "no_such_span", "no_window", "not_traced"])
def test_partition_returns_none_never_a_number(case):
    spans = [[CHUNK, 40 * MS, 20 * MS]]
    if case == "clocks_disagree":
        loaded = _loaded(OPS, spans, window=(5000 * MS, 5100 * MS))
        assert idle_partition.partition_loaded(loaded) is None
    elif case == "no_such_span":    # no thread runs the engine's chunks
        loaded = _loaded(OPS, [["train_pipe/pack", 40 * MS, 20 * MS]])
        assert idle_partition.partition_loaded(loaded) is None
        assert idle_partition.partition_loaded(_loaded(OPS, [])) is None
    elif case == "no_window":
        loaded = _loaded(OPS, spans)
        loaded["raw"]["host_spans"] = []
        assert idle_partition.partition_loaded(loaded) is None
    else:
        bench = types.SimpleNamespace(trace=None, trace_dir="/nonexistent")
        assert idle_partition.partition(bench) is None
        assert idle_partition.part_share(bench, "harvest") is None


NEW_READERS = [
    "gen.boundary_host_ms", "gen.idle_flag_wait_share",
    "gen.idle_harvest_share", "gen.idle_admit_plan_share",
    "gen.idle_admit_prefill_share", "gen.idle_dispatch_share",
    "gen.idle_outside_step_share", "gen.kv_read_once_share",
    "gen.step_longest_ms", "gen.harvest_pull_kb",
]


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_returns_none_on_a_bench_without_its_spans(name):
    """A run that was not traced, over a window in which the program
    recorded no span of the engine's (a program from before them)."""
    now = time.perf_counter()
    bench = types.SimpleNamespace(
        trace=None, trace_dir="/nonexistent",
        t_open=now, t_trace=now, t_close=now + 1e-9)
    assert load_reader(name).read(bench) is None


def _ring_bench(record):
    """A bench whose window holds the ring records ``record()`` makes."""
    t_open = time.perf_counter()
    record()
    return types.SimpleNamespace(
        trace=None, t_open=t_open, t_trace=t_open,
        t_close=time.perf_counter())


def _fake(monkeypatch, records):
    """The ring's window as hand-made records: no clock in the answer."""
    monkeypatch.setattr(
        program_spans, "window_spans",
        lambda bench, name, traced_only=False: [
            r for r in records if r["name"] == name])


def test_boundary_host_ms_is_flag_wait_end_to_next_enqueue_end(monkeypatch):
    records = []
    for k in range(25):
        t = 1.0 * k
        # chunk k: enqueue ends at t+0.10, its flag wait at t+0.90; the
        # next enqueue ends 0.20 later
        records.append({"name": "gen_engine/dispatch/enqueue",
                        "t0": t + 0.08, "dur_s": 0.02})
        records.append({"name": "gen_engine/flag_wait",
                        "t0": t + 0.30, "dur_s": 0.60})
    reader = load_reader("gen.boundary_host_ms")
    _fake(monkeypatch, records)
    assert reader.read(None) == pytest.approx(200.0)
    # a chunk that seated nobody has neither span: the boundary runs on to
    # the next enqueue, and one wait is never counted twice
    del records[20:22]
    assert reader.read(None) == pytest.approx(
        (22 * 200.0 + 1200.0) / 23)
    # under 20 boundaries: no number
    _fake(monkeypatch, records[:30])
    assert reader.read(None) is None


def test_kv_read_once_share_and_step_longest_read_the_chunk_spans():
    def record():
        for named, read in ((100, 40), (60, 60), (40, 20)):
            with tracing.span("gen_engine/chunk", steps=16) as attrs:
                attrs.update(kv_pages_named=named, kv_pages_read=read)
        with tracing.span("gen_engine/chunk", steps=16):
            time.sleep(0.02)        # the XLA gather path: no such counts

    bench = _ring_bench(record)
    assert load_reader("gen.kv_read_once_share").read(bench) == (
        pytest.approx(100.0 * (1 - 120 / 200)))
    longest = load_reader("gen.step_longest_ms").read(bench)
    spans = program_spans.window_spans(bench, "gen_engine/chunk")
    assert longest == pytest.approx(1e3 * max(s["dur_s"] for s in spans))
    assert longest >= 20.0


def test_harvest_pull_kb_is_the_mean_of_the_pull_spans_bytes():
    """ISSUE 60: mean KB of the window's ``gen_engine/harvest/pull`` spans;
    a span without ``bytes`` (a program from before PR 51) is no reading."""
    def record():
        for nbytes in (8192, 1024 * 57, 20_709_376):
            with tracing.span("gen_engine/harvest"):
                with tracing.span("gen_engine/harvest/pull") as attrs:
                    attrs.update(bytes=nbytes, rows=1, blocks=8)
        with tracing.span("gen_engine/harvest/pull"):
            pass

    reader = load_reader("gen.harvest_pull_kb")
    assert reader.read(_ring_bench(record)) == pytest.approx(
        (8192 + 1024 * 57 + 20_709_376) / 3 / 1e3)
    assert reader.read(_ring_bench(lambda: None)) is None

