"""The per-layer metrics of ``setup_s`` (``benchmark/layer_metrics/start.*``):
what a start spent building programs, read from the program's ``compile/*``
counters less the ``compile/program`` records that started once the window
was open (PERF.md §3, layer ``start-up``)."""

import json
import os
import subprocess
import sys
import time
import types

import jax
import numpy as np
import pytest

from areal_tpu.base import metrics as metrics_mod
from areal_tpu.base import tracing
from benchmark.run import REHEARSAL_EXIT, ROOT, load_reader

NAMES = (
    "start.program_build_s", "start.trace_lower_s",
    "start.cache_hit_share", "start.programs_built",
)


@pytest.fixture
def counters(monkeypatch):
    """A registry of the test's own in the process's place: the totals a
    reader sees are then what the test put there."""
    reg = metrics_mod.CounterRegistry()
    monkeypatch.setattr(metrics_mod, "counters", reg)
    tracing.drain()
    yield reg
    tracing.drain()


def _bench(t_open):
    return types.SimpleNamespace(t_open=t_open)


def _build_a_program(scale):
    fn = jax.jit(lambda x: x * scale + 1.0)     # a new function: never built
    fn(np.ones(3, np.float32)).block_until_ready()


@pytest.mark.parametrize("name, want", zip(NAMES, (27.5, 25.0, 98.0, 50.0)))
def test_reader_reads_the_starts_totals(counters, name, want):
    for key, v in (("programs", 50), ("trace_s", 20.0), ("lower_s", 5.0),
                   ("backend_s", 2.5), ("cache_hits", 49), ("cache_misses", 1),
                   ("cache_load_s", 1.0), ("cache_saved_s", 300.0)):
        counters.add("compile/" + key, v)
    assert load_reader(name).read(_bench(time.perf_counter())) == want


@pytest.mark.parametrize("name", NAMES)
def test_reader_leaves_out_what_was_built_after_the_window_opened(
        counters, name, no_persistent_cache):
    """The counters run on past the start (the check against the reference
    builds programs after the window); the ring's records since ``t_open``
    say by how much. A run that caches nothing has no hit share (outside
    the test run's own compile cache: ``no_persistent_cache``)."""
    tracing.listen_for_compiles()
    _build_a_program(2.0)
    at_open = counters.snapshot()
    t_open = time.perf_counter()
    with tracing.span("t/window"):
        _build_a_program(3.0)
    assert counters.get("compile/programs") == at_open["compile/programs"] + 1
    stages = [at_open[f"compile/{k}"] for k in ("trace_s", "lower_s", "backend_s")]
    want = {
        "start.program_build_s": sum(stages),
        "start.trace_lower_s": sum(stages[:2]),
        "start.cache_hit_share": None,     # the CPU tests cache nothing
        "start.programs_built": at_open["compile/programs"],
    }[name]
    got = load_reader(name).read(_bench(t_open))
    assert got == (want if want is None else pytest.approx(want, abs=1e-9))
    assert want is None or want > 0


@pytest.mark.parametrize("name", NAMES)
def test_reader_finds_nothing_in_a_program_without_the_listener(
        counters, name):
    """Laid over the parent commit the readers return None and do not
    raise: no ``compile/*`` counter was ever written there."""
    counters.add("gen_engine/admit_n", 3)
    assert load_reader(name).read(_bench(0.0)) is None


def test_selfcheck_passes_with_the_new_entries():
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.selfcheck", "--no-cells"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    for name in NAMES:
        assert f"ok   reader {name}: unit, layer, moves, source agree" in p.stdout


@pytest.mark.parametrize("cell", ["r1d-1p5b.rollout", "r1d-1p5b.ppo_train"])
def test_rehearsal_reports_the_start_metrics(cell, tmp_path):
    """End to end on the CPU, the compile cache on in a directory of the
    test's own: a traced rehearsal's line carries all four names."""
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell,
         "--seed", str(2**31 + 35), "--seconds", "3", "--trace", "1",
         "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")},
    )
    assert p.returncode == REHEARSAL_EXIT, p.stdout[-3000:] + p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True
    assert set(NAMES) <= set(last["counts_only"])
