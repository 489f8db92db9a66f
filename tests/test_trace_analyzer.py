"""xplane trace analyzer (VERDICT r4 #9): a real jax.profiler trace is
captured and classified into the reference's device-time buckets
(``realhf/base/monitor.py:404-610``: compute / p2p_comm / coll_comm /
memoryIO / idle / misc) via jaxlib's ProfileData reader."""

import json
import os

import pytest

from areal_tpu.base.trace_analyzer import (
    BUCKETS,
    TraceAnalyzerUnavailable,
    analyze_profile_data,
    analyze_xspace,
    classify,
    find_xplane_files,
    profile_data_available,
)

# jax version drift: older/newer jaxlib builds may not ship the
# ProfileData XSpace reader at all — everything that parses a trace
# skips (classification tables and the graceful-degradation paths still
# run everywhere).
needs_profile_data = pytest.mark.skipif(
    not profile_data_available(),
    reason="jax.profiler.ProfileData not available in this jax build",
)


def test_classify_tables():
    assert classify("fusion.123", "convolution") == "compute"
    assert classify("all-reduce.5") == "coll_comm"
    assert classify("fusion.2", "all-reduce fusion") == "coll_comm"
    assert classify("collective-permute.1") == "p2p_comm"
    assert classify("copy.3") == "memoryIO"
    assert classify("dynamic-update-slice.9") == "memoryIO"
    assert classify("custom-call.pallas") == "compute"


class _Ev:
    def __init__(self, name, start_ns, duration_ns, **stats):
        self.name, self.start_ns, self.duration_ns = name, start_ns, duration_ns
        self.stats = list(stats.items())


class _Named:
    def __init__(self, name, **kw):
        self.name = name
        self.__dict__.update(kw)


def test_nested_events_count_self_time_and_empty_planes_are_skipped():
    """A ``while`` covers its body on the op line: each op is charged its
    self time, busy is the union, idle the rest of the line's span. The
    op-less plane libtpu adds to a CPU trace is not a device."""
    ops = [
        _Ev("while.1", 0, 100),            # covers the next two
        _Ev("fusion.2", 10, 30),
        _Ev("copy.3", 50, 20),
        _Ev("fusion.4", 150, 20),
    ]
    pd = _Named("xspace", planes=[
        _Named("/device:CUSTOM:Megascale Trace", lines=[]),
        _Named("/device:TPU:0", lines=[
            _Named("XLA Ops", events=ops),
            _Named("XLA Modules", events=[_Ev("jit_f", 0, 170)]),
        ]),
    ])
    (s,) = analyze_profile_data(pd)
    assert s.plane == "/device:TPU:0" and s.n_events == 4
    per_op = {n: sec for n, sec, _, _ in s.top_ops}
    assert per_op == pytest.approx({
        "while.1": 50e-9, "fusion.2": 30e-9, "copy.3": 20e-9,
        "fusion.4": 20e-9})
    busy = sum(v for k, v in s.buckets_s.items() if k != "idle")
    assert busy == pytest.approx(120e-9)           # the union, not 170
    assert s.buckets_s["idle"] == pytest.approx(50e-9)
    assert s.buckets_s["memoryIO"] == pytest.approx(20e-9)
    assert s.device_total_s == pytest.approx(170e-9)


@pytest.fixture(scope="module")
def trace_dir(tmp_path_factory):
    import jax
    import jax.numpy as jnp

    d = str(tmp_path_factory.mktemp("trc"))
    x = jnp.ones((256, 256))
    f = jax.jit(lambda a: a @ a)
    f(x).block_until_ready()  # compile OUTSIDE the trace window
    with jax.profiler.trace(d):
        for _ in range(3):
            x = f(x)
        x.block_until_ready()
    return d


@needs_profile_data
def test_analyze_real_trace(trace_dir):
    files = find_xplane_files(trace_dir)
    assert files, "profiler produced no xplane file"
    summaries = analyze_xspace(files[0])
    assert summaries, "no device/op plane found"
    s = summaries[0]
    assert s.n_events > 0
    assert s.device_total_s > 0
    # the matmul dominates compute
    assert s.buckets_s["compute"] > 0
    names = [n for n, *_ in s.top_ops]
    assert any("dot" in n for n in names), names
    # buckets are exhaustive: their sum is the device total
    assert abs(sum(s.buckets_s.values()) - s.device_total_s) < 1e-9
    d = s.as_dict()
    assert set(d["buckets_pct"]) == set(BUCKETS)


@needs_profile_data
def test_cli_on_real_trace(trace_dir, capsys):
    from areal_tpu.apps.trace_analyze import main

    assert main([trace_dir, "--top", "5"]) == 0
    out = capsys.readouterr().out
    assert "compute" in out and "idle" in out

    assert main([trace_dir, "--json"]) == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed[0]["device_total_s"] > 0


def test_cli_no_trace(tmp_path, capsys):
    from areal_tpu.apps.trace_analyze import main

    assert main([str(tmp_path)]) == 1


def test_unavailable_degrades_gracefully(tmp_path, monkeypatch, capsys):
    """jax builds without ProfileData: parsing raises the typed error and
    the CLI reports instead of crashing with AttributeError."""
    from areal_tpu.base import trace_analyzer as ta

    def _unavailable():
        raise TraceAnalyzerUnavailable("no ProfileData in this build")

    monkeypatch.setattr(ta, "_profile_data", _unavailable)
    d = tmp_path / "plugins" / "profile" / "run0"
    d.mkdir(parents=True)
    f = d / "host.xplane.pb"
    f.write_bytes(b"")
    with pytest.raises(TraceAnalyzerUnavailable):
        ta.analyze_xspace(str(f))

    from areal_tpu.apps.trace_analyze import main

    assert main([str(tmp_path)]) == 1
    assert "ProfileData" in capsys.readouterr().err


@needs_profile_data
def test_tpu_plane_counts_only_op_lines():
    """Review finding r5: a real TPU device plane carries 'XLA Modules' /
    'Steps' lines spanning the SAME wall time as the op line — only the op
    line may contribute to device_total_s."""
    import jax.profiler as jp

    from areal_tpu.base.trace_analyzer import analyze_profile_data

    txt = """
planes {
  name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops"
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 500000 }
  }
  lines {
    id: 2 name: "XLA Modules"
    events { metadata_id: 3 offset_ps: 0 duration_ps: 1500000 }
  }
  lines {
    id: 3 name: "Steps"
    events { metadata_id: 4 offset_ps: 0 duration_ps: 1500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "all-reduce.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_train_step" } }
  event_metadata { key: 4 value { id: 4 name: "train_step" } }
}
"""
    (s,) = analyze_profile_data(jp.ProfileData.from_text_proto(txt))
    # 1.0 us fusion + 0.5 us all-reduce; module/step spans NOT re-counted
    assert abs(s.device_total_s - 1.5e-6) < 1e-12
    assert abs(s.buckets_s["compute"] - 1.0e-6) < 1e-12
    assert abs(s.buckets_s["coll_comm"] - 0.5e-6) < 1e-12
    assert s.n_events == 2
    names = [n for n, *_ in s.top_ops]
    assert "jit_train_step" not in names and "train_step" not in names


@needs_profile_data
def test_cli_compare(trace_dir, capsys):
    from areal_tpu.apps.trace_analyze import main

    assert main([trace_dir, "--compare", trace_dir, "--top", "5"]) == 0
    out = capsys.readouterr().out
    assert "B/A" in out and "device" in out
    # identical traces compare at ratio 1.000
    assert "  1.000" in out
