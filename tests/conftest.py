"""Test harness: force an 8-device virtual CPU mesh before jax import.

Mirrors the reference's CPU-only test strategy (``realhf/base/testing.py``):
the whole stack must be testable without TPU hardware. An 8-device host
platform replaces the reference's 8-process gloo trick (SURVEY.md §4).

The harness asks for a compile cache (``areal_tpu/base/compile_cache.py``:
a CPU run caches nothing unless ``JAX_COMPILATION_CACHE_DIR`` asks): one
fresh directory a test run, made here, inherited by the xdist workers and
by every subprocess world a test launches, removed when the session ends,
so a program that many cases build is compiled once a run. A test whose
SUBJECT is compiling or caching takes ``no_persistent_cache``.
"""

import os
import shutil
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"  # tests always run on the CPU
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("AREAL_FILEROOT", "/tmp/areal_tpu_test")
# The run's compile cache. The process that is no xdist worker makes the
# directory (fresh a run: what a case costs must not depend on what an
# earlier run left) unless the caller named one, and its children inherit
# the variable. At test sizes a CPU program compiles in well under JAX's
# one-second threshold for storing an entry, hence the two floors.
_RUN_CACHE = None
if ("PYTEST_XDIST_WORKER" not in os.environ
        and "JAX_COMPILATION_CACHE_DIR" not in os.environ):
    _RUN_CACHE = tempfile.mkdtemp(prefix="areal_tpu_xla_cache_")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _RUN_CACHE
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
# Data-plane pipelining (docs/pipelined_data_plane.md) defaults OFF under
# the CPU harness: with JAX_PLATFORMS=cpu the "device" IS the host, so
# dispatch-ahead depth and the background packer thread only oversubscribe
# the cores the multi-process e2e worlds already share (~35% wall-time
# regression measured on test_experiment_e2e). Production (TPU) keeps the
# ON defaults, so every other tier-1 test runs the SERIAL side of both
# mechanisms (ROADMAP D14). The tests that turn the default side back on,
# all in tests/test_data_pipeline.py:
# test_forward_pipeline_identical_and_overlapped (AREAL_FWD_PIPELINE=2
# against 0, both meshes), test_forward_explicit_depth_overrides_env,
# test_train_batches_pipelined_matches_serial (AREAL_TRAIN_PREFETCH 1
# against 0), test_trainer_worker_defers_stats_fetch and
# test_env_knob_parsing (the unset defaults).
os.environ.setdefault("AREAL_FWD_PIPELINE", "0")
os.environ.setdefault("AREAL_TRAIN_PREFETCH", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# belt and braces: the config wins even if something re-exported the
# variable between the line above and this import
jax.config.update("jax_platforms", "cpu")

import asyncio
import contextlib
import inspect

import numpy as np
import pytest


def pytest_unconfigure(config):
    if _RUN_CACHE is not None:
        shutil.rmtree(_RUN_CACHE, ignore_errors=True)


@contextlib.contextmanager
def persistent_cache_off():
    """This process compiles every program itself while the block runs:
    for a test that counts compiles, reads ``cache_hit``, or compiles for
    a described device whose executables must not land in the run's
    directory. (A subprocess the test launches is not covered: hand it an
    ``env`` without ``JAX_COMPILATION_CACHE_DIR``, or one of its own.)"""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@pytest.fixture
def no_persistent_cache():
    with persistent_cache_off():
        yield


@contextlib.contextmanager
def multihost_world_lock():
    """Serialize multi-process CPU worlds ACROSS pytest processes.

    An N-process gloo world is timing-sensitive (bounded collectives,
    coordinator rendezvous); two suites launching worlds concurrently on
    a shared CI box starve each other into spurious timeouts — the
    standalone test_multihost failures noted in the PR-8 log. A
    system-wide flock makes world launches mutually exclusive; the lock
    file lives in the shared tempdir so unrelated pytest invocations
    contend on the same lock."""
    import fcntl

    path = os.path.join(tempfile.gettempdir(), "areal_tpu_multihost.lock")
    with open(path, "w") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(f, fcntl.LOCK_UN)


def pytest_pyfunc_call(pyfuncitem):
    """Minimal async-test support (pytest-asyncio is not in the image)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            k: pyfuncitem.funcargs[k] for k in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(fn(**kwargs))
        return True
    return None


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True)
def _seed():
    from areal_tpu.base import seeding

    seeding.set_random_seed(1, "test")
    yield


@pytest.fixture
def check_moe_grouped_serves_the_same(monkeypatch):
    """``check(make_engine, prompts)``: an expert model's engine at test
    size serves ``prompts`` (greedy) once with the routed experts on the
    einsums and once on the ``moe_grouped`` kernel (interpret mode), by
    patching the rule the engine asks (``ops/moe.py:moe_grouped_applies``:
    a test's patch, not a switch of the program). The tokens are the same,
    and ``moe_grouped_rows`` / ``moe_dense_rows`` add up to rows x expert
    layers x steps on the chunks' spans, tokens x expert layers of the
    prefill chunks on admission's, and to their sum on ``engine.stats``."""
    from areal_tpu.base import tracing
    from areal_tpu.gen.engine import GenRequest
    from areal_tpu.ops import moe as moe_ops

    def check(make_engine, prompts, n_new=10, steps=4):
        served = {}
        for grouped in (False, True):
            monkeypatch.setattr(
                moe_ops, "moe_grouped_applies", lambda *a, **k: grouped)
            eng = make_engine()
            tracing.drain()
            for i, p in enumerate(prompts):
                eng.submit(GenRequest(
                    rid=f"r{i}", input_ids=p, max_new_tokens=n_new,
                    temperature=0.0))
            outs = {o.rid: o.output_ids for o in eng.run_until_done(steps)}
            spans = [s for s in tracing.drain() if s["name"] in (
                "gen_engine/chunk", "gen_engine/admit")]
            ran, idle = (
                ("moe_grouped_rows", "moe_dense_rows") if grouped
                else ("moe_dense_rows", "moe_grouped_rows"))
            layers = eng.cfg.n_moe_layers
            chunks = [s["attrs"] for s in spans if "slots" in s["attrs"]]
            assert chunks and all(
                c[ran] == eng.B * layers * c["steps"] and c[idle] == 0
                for c in chunks)
            admits = [s["attrs"] for s in spans
                      if s["name"] == "gen_engine/admit"]
            prefilled = sum(a[ran] for a in admits)
            # whole prefill chunks of whole row buckets, every expert layer
            assert prefilled >= layers * sum(len(p) - 1 for p in prompts)
            assert prefilled % (layers * eng.admit_chunk) == 0
            assert all(a[idle] == 0 for a in admits)
            assert eng.stats[ran] == prefilled + sum(c[ran] for c in chunks)
            assert eng.stats[idle] == 0
            served[grouped] = outs
        assert all(len(v) == n_new for v in served[True].values())
        assert served[True] == served[False]

    return check


@pytest.fixture
def decode_tokens_paged():
    """``decode(params, cfg, cache, tokens [B, C], table, lens, n_new,
    **kw)``: the rows with ``n_new > 0`` teacher-forced through
    ``decode_step_paged``, a position a step, on a pool and page table the
    test laid out itself. Returns ``(logits [B, C, V], cache)``: ``logits[:,
    i]`` is the distribution of the token after ``tokens[:, i]``."""
    import jax.numpy as jnp

    from areal_tpu.models import transformer as tfm

    def decode(params, cfg, cache, tokens, table, lens, n_new, **kw):
        active = jnp.asarray(n_new) > 0
        lens = jnp.asarray(lens)
        logits = []
        for c in range(tokens.shape[1]):
            step, cache, lens = tfm.decode_step_paged(
                params, cfg, cache, tokens[:, c], table, lens, active, **kw)
            logits.append(step)
        return jnp.stack(logits, axis=1), cache

    return decode
