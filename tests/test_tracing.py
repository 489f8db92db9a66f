"""Tracing plane: env-gated profiler tracing (≈ the reference's
REAL_DUMP_TRACE gating) + the distributed span plane
(docs/observability.md "Distributed tracing") — trace identity,
wire-context propagation, exception-exit spans, the bounded completed-
span ring, and the fileroot flush that feeds tracejoin."""

import glob
import json
import os
import threading

import jax.numpy as jnp
import pytest

from areal_tpu.base import constants, tracing
from areal_tpu.base import metrics as metrics_mod
from areal_tpu.base.trace_analyzer import profile_data_available


def test_disabled_is_free(monkeypatch):
    """Without AREAL_DUMP_TRACE ``maybe_trace`` starts no profiler; an
    annotation needs no variable and no session."""
    monkeypatch.delenv(constants.TRACE_ENV, raising=False)
    assert not tracing.trace_enabled()
    with tracing.maybe_trace("noop"):
        pass
    with tracing.annotate("noop"):
        pass


def _host_event_names(trace_dir):
    import jax

    from areal_tpu.base.trace_analyzer import find_xplane_files

    names = set()
    for f in find_xplane_files(str(trace_dir)):
        for plane in jax.profiler.ProfileData.from_file(f).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    names.update(ev.name for ev in line.events)
    return names


@pytest.mark.skipif(
    not profile_data_available(),
    reason="jax.profiler.ProfileData not available in this jax build",
)
@pytest.mark.parametrize("spans_on", [True, False])
def test_span_is_on_the_profiler_clock(monkeypatch, tmp_path, spans_on):
    """One clock: in ANY profiler session (AREAL_DUMP_TRACE unset) a span
    is a host-plane event named PROFILER_PREFIX + its name, beside the
    device's ops. AREAL_TRACE_SPANS=0 stays counters only."""
    import jax

    monkeypatch.delenv(constants.TRACE_ENV, raising=False)
    monkeypatch.setenv("AREAL_TRACE_SPANS", "1" if spans_on else "0")
    with jax.profiler.trace(str(tmp_path)):
        with tracing.span("unit/one_clock", k=1):
            jnp.ones((8, 8)).sum().block_until_ready()
    want = tracing.PROFILER_PREFIX + "unit/one_clock"
    assert tracing.PROFILER_PREFIX == "areal/"
    assert (want in _host_event_names(tmp_path)) == spans_on
    assert metrics_mod.counters.get("unit/one_clock_n") >= 1


def test_trace_dumps_profile(monkeypatch, tmp_path):
    monkeypatch.setenv(constants.TRACE_ENV, "1")
    monkeypatch.setenv("AREAL_FILEROOT", str(tmp_path))
    assert tracing.trace_enabled()
    assert tracing.trace_step() == 3
    monkeypatch.setenv("AREAL_TRACE_STEP", "7")
    assert tracing.trace_step() == 7
    with tracing.maybe_trace("unit"):
        with tracing.annotate("mfc:actor_train"):
            jnp.ones((8, 8)).sum().block_until_ready()
    dumped = glob.glob(str(tmp_path / "traces" / "unit" / "**" / "*"),
                       recursive=True)
    assert any(os.path.isfile(f) for f in dumped), dumped


# --------------------------------------------------------------------- #
# Span plane: identity + wire context
# --------------------------------------------------------------------- #


@pytest.fixture(autouse=True)
def _clean_ring():
    tracing.drain()
    yield
    tracing.drain()


class TestTraceIdentity:
    def test_id_formats(self):
        tid, sid = tracing.new_trace_id(), tracing.new_span_id()
        assert len(tid) == 32 and int(tid, 16) >= 0
        assert len(sid) == 16 and int(sid, 16) >= 0

    def test_traceparent_roundtrip(self):
        with tracing.activate() as tid:
            tp = tracing.traceparent()
            assert tp == f"00-{tid}-{'0' * 16}-01"
            assert tracing.parse_traceparent(tp) == (tid, None)
            with tracing.span("t/x"):
                tid2, psid = tracing.parse_traceparent(tracing.traceparent())
                assert tid2 == tid and psid is not None
        assert tracing.traceparent() is None

    @pytest.mark.parametrize("bad", [
        None, 7, "", "nonsense", "00-zz-ff-01",
        "00-" + "a" * 31 + "-" + "b" * 16 + "-01",   # short trace id
        "00-" + "a" * 32 + "-" + "b" * 15 + "-01",   # short span id
        "00-" + "a" * 32 + "-" + "b" * 16,           # missing flags
    ])
    def test_parse_tolerates_malformed(self, bad):
        assert tracing.parse_traceparent(bad) is None

    def test_wire_context_carries_qid(self):
        assert tracing.wire_context() is None  # no active context
        with tracing.activate(qid="q7") as tid:
            w = tracing.wire_context()
            assert w["qid"] == "q7"
            assert tracing.parse_traceparent(w["traceparent"])[0] == tid
            assert tracing.current_qid() == "q7"
        assert tracing.current_qid() is None

    def test_activate_continues_wire_context(self):
        with tracing.activate(qid="q1") as tid:
            with tracing.span("t/client"):
                wire = tracing.wire_context()
        # "server side": same trace id, parent = the client span, qid rides
        with tracing.activate(wire) as tid2:
            assert tid2 == tid
            assert tracing.current_qid() == "q1"
            with tracing.span("t/server"):
                pass
        spans = {s["name"]: s for s in tracing.drain()}
        client, server = spans["t/client"], spans["t/server"]
        assert server["trace_id"] == client["trace_id"] == tid
        assert server["parent_id"] == client["span_id"]
        assert server["attrs"]["qid"] == "q1"

    def test_activate_degrades_to_fresh_root(self):
        with tracing.activate({"traceparent": "garbage"}) as tid:
            assert len(tid) == 32  # malformed wire → new trace, no crash


class TestSpanRecords:
    def test_span_nesting_and_attrs(self):
        with tracing.activate() as tid:
            with tracing.span("t/outer", rid="r1") as attrs:
                attrs["late"] = 5
                with tracing.span("t/inner"):
                    pass
        recs = {s["name"]: s for s in tracing.drain()}
        outer, inner = recs["t/outer"], recs["t/inner"]
        assert outer["trace_id"] == inner["trace_id"] == tid
        assert inner["parent_id"] == outer["span_id"]
        assert outer["attrs"] == {"rid": "r1", "late": 5}
        assert outer["dur_s"] >= 0 and not outer["error"]

    def test_exception_exit_recorded(self):
        """The satellite regression: a span whose body raises must land in
        the ring stamped error=True with the exception type — not vanish."""
        before = metrics_mod.counters.get(metrics_mod.TRACE_SPAN_ERRORS)
        with pytest.raises(ValueError):
            with tracing.span("t/boom", rid="r9"):
                raise ValueError("nope")
        (rec,) = [s for s in tracing.drain() if s["name"] == "t/boom"]
        assert rec["error"] is True and rec["exc"] == "ValueError"
        assert rec["attrs"]["rid"] == "r9"
        assert (
            metrics_mod.counters.get(metrics_mod.TRACE_SPAN_ERRORS)
            == before + 1
        )
        # the live registry must not leak the aborted span
        assert all(s["name"] != "t/boom" for s in tracing.live_spans())

    def test_span_counters_always_accumulate(self, monkeypatch):
        monkeypatch.setenv(constants.TRACE_SPANS_ENV, "0")
        before_s = metrics_mod.counters.get("t/off_s")
        before_n = metrics_mod.counters.get("t/off_n")
        with tracing.span("t/off"):
            pass
        assert metrics_mod.counters.get("t/off_s") >= before_s
        assert metrics_mod.counters.get("t/off_n") == before_n + 1
        assert tracing.drain() == []  # disabled: nothing recorded
        assert tracing.wire_context(qid="q") is None
        with tracing.activate() as tid:
            assert tid is None

    def test_ring_bounded_with_drop_counter(self, monkeypatch):
        monkeypatch.setenv(constants.TRACE_RING_ENV, "16")
        before = metrics_mod.counters.get(metrics_mod.TRACE_DROPPED)
        for i in range(40):
            with tracing.span("t/ring"):
                pass
        spans = tracing.drain()
        assert len(spans) == 16
        assert metrics_mod.counters.get(metrics_mod.TRACE_DROPPED) \
            == before + 24

    def test_recent_spans_survive_drain(self):
        with tracing.span("t/recent"):
            pass
        tracing.drain()
        assert any(
            s["name"] == "t/recent" for s in tracing.recent_spans(50)
        )

    @pytest.mark.parametrize("then", ["drain", "flush"])
    def test_spans_since_reads_without_draining(self, tmp_path, then):
        """The in-process read of a window leaves the ring as the flusher
        would have found it, and windows by monotonic start."""
        import time

        with tracing.span("t/before"):
            pass
        t_mid = time.perf_counter()
        for i in range(3):
            with tracing.span("t/after", i=i):
                pass
        t_end = time.perf_counter()
        with tracing.span("t/late"):
            pass
        got = tracing.spans_since(t_mid, t_end)
        assert [s["name"] for s in got] == ["t/after"] * 3
        assert [s["attrs"]["i"] for s in got] == [0, 1, 2]
        assert all(t_mid <= s["t0"] <= t_end for s in got)
        assert [s["name"] for s in tracing.spans_since(t_mid)][-1] == "t/late"
        assert tracing.spans_since(t_mid) == tracing.spans_since(t_mid)
        if then == "drain":
            assert [s["name"] for s in tracing.drain()] == (
                ["t/before"] + ["t/after"] * 3 + ["t/late"])
        else:
            assert tracing.flush("w/0", root=str(tmp_path)) == 5
            lines = (tmp_path / "w_0.jsonl").read_text().splitlines()
            assert [json.loads(l)["name"] for l in lines][1:4] == ["t/after"] * 3
        assert tracing.spans_since(0.0) == []


class TestCompileListener:
    """``tracing.listen_for_compiles``: every executable JAX builds is the
    ``compile/*`` counters and one ``compile/program`` record under the
    span that paid for it (docs/observability.md "What a start cost")."""

    @pytest.fixture(autouse=True)
    def _builds_are_builds(self, no_persistent_cache):
        """These cases' subject is what a BUILD reports (three stages with
        seconds each, ``cache_hit`` None where no cache was asked): outside
        the run's compile cache."""

    @staticmethod
    def _fresh(scale):
        import jax

        def unit_program(x):
            return x * scale + 1.0

        return jax.jit(unit_program)    # a new function: never built before

    @staticmethod
    def _compile_counters():
        return {k: v for k, v in metrics_mod.counters.snapshot().items()
                if k.startswith("compile/")}

    def test_program_built_under_a_span_is_its_child(self):
        import numpy as np

        assert tracing.listen_for_compiles()
        fn, x = self._fresh(2.0), np.ones(4, np.float32)
        with tracing.span("t/builds") as attrs:
            fn(x)
        spans = tracing.drain()
        (built,) = [s for s in spans if s["name"] == "compile/program"]
        (outer,) = [s for s in spans if s["name"] == "t/builds"]
        assert built["parent_id"] == outer["span_id"]
        assert built["trace_id"] == outer["trace_id"]
        a = built["attrs"]
        assert a["fun_name"] == "jit(unit_program)"
        assert a["cache_hit"] is None       # the CPU tests cache nothing
        stages = [a["trace_s"], a["lower_s"], a["backend_s"]]
        assert all(t > 0 for t in stages)
        assert built["dur_s"] == pytest.approx(sum(stages))
        assert built["dur_s"] <= outer["dur_s"]
        assert outer["t0"] <= built["t0"]
        assert attrs == {"compiled": 1, "compile_s": built["dur_s"]}
        # the second call builds nothing: no record, no stamp on its span
        with tracing.span("t/builds") as attrs:
            fn(x)
        assert attrs == {}
        assert [s["name"] for s in tracing.drain()] == ["t/builds"]

    def test_stamp_lands_on_the_innermost_open_span(self):
        import numpy as np

        tracing.listen_for_compiles()
        fn = self._fresh(3.0)
        with tracing.span("t/outer") as outer:
            with tracing.span("t/inner") as inner:
                fn(np.ones(4, np.float32))
        assert inner["compiled"] == 1 and "compiled" not in outer
        recs = {s["name"]: s for s in tracing.drain()}
        assert recs["compile/program"]["parent_id"] == recs["t/inner"]["span_id"]

    def test_a_program_counts_its_top_level_trace_once(self):
        """A jitted function that calls jitted functions fires a trace
        event for each of them, then its own, which contains theirs: the
        program's ``trace_s`` is its own, never the sum."""
        import jax
        import numpy as np
        from jax._src import monitoring

        tracing.listen_for_compiles()
        inner = self._fresh(5.0)

        @jax.jit
        def calls_inner(x):
            return inner(x) + inner(x + 1.0)

        traces = []

        def on_dur(key, dur, fun_name="", **kw):
            if key.endswith("jaxpr_trace_duration"):
                traces.append((fun_name, dur))

        monitoring.register_event_duration_secs_listener(on_dur)
        before = self._compile_counters()
        try:
            with tracing.span("t/nested"):
                calls_inner(np.ones(4, np.float32))
        finally:
            monitoring.unregister_event_duration_listener(on_dur)
        after = self._compile_counters()
        (built,) = [s for s in tracing.drain() if s["name"] == "compile/program"]
        assert built["attrs"]["fun_name"] == "jit(calls_inner)"
        assert traces[-1][0] == "calls_inner"
        assert "unit_program" in [n for n, _ in traces[:-1]]
        assert built["attrs"]["trace_s"] == traces[-1][1]
        assert sum(d for _, d in traces) > traces[-1][1]
        assert after["compile/programs"] == before.get("compile/programs", 0) + 1
        assert after["compile/trace_s"] - before.get("compile/trace_s", 0) \
            == pytest.approx(traces[-1][1])

    def test_one_listener_however_often_it_is_asked_for(self):
        import numpy as np

        for _ in range(3):      # two engines and a worker in one process
            assert tracing.listen_for_compiles()
        before = self._compile_counters().get("compile/programs", 0)
        with tracing.span("t/once") as attrs:
            self._fresh(7.0)(np.ones(4, np.float32))
        assert attrs["compiled"] == 1
        assert len([s for s in tracing.drain()
                    if s["name"] == "compile/program"]) == 1
        assert self._compile_counters()["compile/programs"] == before + 1

    def test_spans_off_keeps_the_counters_and_no_record(self, monkeypatch):
        import numpy as np

        tracing.listen_for_compiles()
        monkeypatch.setenv(constants.TRACE_SPANS_ENV, "0")
        before = self._compile_counters()
        with tracing.span("t/off_builds") as attrs:
            self._fresh(11.0)(np.ones(4, np.float32))
        after = self._compile_counters()
        assert attrs == {} and tracing.drain() == []
        assert after["compile/programs"] == before.get("compile/programs", 0) + 1
        for k in ("compile/trace_s", "compile/lower_s", "compile/backend_s"):
            assert after[k] > before.get(k, 0.0)


    def test_configure_starts_it_only_where_jax_already_is(self):
        """``compile_cache.configure`` is called by JAX-free parents too:
        it must not import JAX, and where JAX is there it starts the
        listener before the process's first program."""
        import subprocess
        import sys

        code = (
            "import sys\n"
            "from areal_tpu.base import compile_cache, metrics, tracing\n"
            "compile_cache.configure()\n"
            "assert 'jax' not in sys.modules\n"
            "assert tracing.listen_for_compiles() is False\n"
            "import jax, numpy as np\n"
            "compile_cache.configure()\n"
            "jax.jit(lambda x: x + 1)(np.ones(3, np.float32))\n"
            "assert metrics.counters.get('compile/programs') == 1\n"
            "assert tracing.drain()[-1]['name'] == 'compile/program'\n"
        )
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        p = subprocess.run(
            [sys.executable, "-c", code], cwd=root, capture_output=True,
            text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert p.returncode == 0, p.stderr[-2000:]


class TestFlush:
    def test_flush_appends_worker_stamped_jsonl(self, tmp_path):
        with tracing.span("t/flush", rid="r1"):
            pass
        n = tracing.flush("gw/0", root=str(tmp_path))
        assert n == 1
        assert tracing.flush("gw/0", root=str(tmp_path)) == 0  # drained
        path = tmp_path / "gw_0.jsonl"
        recs = [json.loads(l) for l in path.read_text().splitlines()]
        assert recs[0]["worker"] == "gw/0"
        assert recs[0]["name"] == "t/flush"
        assert recs[0]["pid"] == os.getpid()
        # append, not truncate: a second flush adds lines
        with tracing.span("t/flush2"):
            pass
        tracing.flush("gw/0", root=str(tmp_path))
        assert len(path.read_text().splitlines()) == 2

    def test_span_flusher_gated_off_by_default(self, monkeypatch):
        monkeypatch.delenv(constants.TRACE_FLUSH_ENV, raising=False)
        assert tracing.SpanFlusher.maybe_start("w") is None

    def test_span_flusher_final_drain(self, tmp_path, monkeypatch):
        monkeypatch.setenv("AREAL_FILEROOT", str(tmp_path))
        monkeypatch.setenv(constants.TRACE_FLUSH_ENV, "30")
        t = tracing.SpanFlusher.maybe_start("bg/1")
        assert isinstance(t, threading.Thread)
        with tracing.span("t/bg"):
            pass
        t.stop()  # final drain flushes without waiting out the interval
        path = tmp_path / "trace_spans" / "bg_1.jsonl"
        assert path.exists()
        assert any(
            json.loads(l)["name"] == "t/bg"
            for l in path.read_text().splitlines()
        )
