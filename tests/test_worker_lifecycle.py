"""Worker lifecycle: death watch + heartbeats (≈ reference worker_base poll
loop + the 300 s experiment_status timeout in rollout/generation workers)."""

import os
import subprocess
import sys
import time

import pytest

from areal_tpu.base import name_resolve
from areal_tpu.system import worker_base
from areal_tpu.system.worker_base import (
    ExperimentStatusWatch,
    Heartbeat,
    last_heartbeat,
)

EXP, TRIAL = "lifecycle-test", "t0"


class TestStatusWatch:
    def test_running_keeps_alive(self):
        worker_base.mark_experiment_running(EXP, TRIAL)
        w = ExperimentStatusWatch(EXP, TRIAL, timeout=0.1, poll_interval=0.0)
        assert w.alive()
        time.sleep(0.2)
        assert w.alive()  # status present: timeout never starts

    def test_stopped_kills_immediately(self):
        worker_base.mark_experiment_running(EXP, TRIAL)
        w = ExperimentStatusWatch(EXP, TRIAL, timeout=300, poll_interval=0.0)
        assert w.alive()
        worker_base.mark_experiment_stopped(EXP, TRIAL)
        assert not w.alive()
        assert not w.alive()  # latched

    def test_missing_key_kills_after_timeout(self):
        key = worker_base.names.experiment_status(EXP, TRIAL)
        try:
            name_resolve.delete(key)
        except name_resolve.NameEntryNotFoundError:
            pass
        w = ExperimentStatusWatch(EXP, TRIAL, timeout=0.2, poll_interval=0.0)
        assert w.alive()          # grace period
        time.sleep(0.3)
        assert not w.alive()      # launcher never appeared / died silently

    def test_heartbeat_publishes(self):
        t_start = time.time()
        hb = Heartbeat(EXP, TRIAL, "unit_worker", interval=0.05).start()
        time.sleep(0.15)
        hb.stop()
        t = last_heartbeat(EXP, TRIAL, "unit_worker")
        # this heartbeat's stamp, by order: no older one, none from ahead
        assert t is not None and t_start <= t <= time.time()


class TestHangWatchdog:
    def test_dumps_stacks_and_live_spans_for_hung_step(self, caplog):
        import logging as logging_mod
        import threading

        from areal_tpu.base import metrics as metrics_mod
        from areal_tpu.base import tracing
        from areal_tpu.system.worker_base import HangWatchdog

        release = threading.Event()
        started = threading.Event()

        def hung_step():
            # an artificially hung "step" holding a data-plane span open —
            # the dump must attribute the hang to it
            with tracing.span("train_pipe/dispatch_hung"):
                started.set()
                release.wait(10)

        t = threading.Thread(target=hung_step, name="hung-step", daemon=True)
        t.start()
        assert started.wait(5)
        before = metrics_mod.counters.get("guard/watchdog_dumps")
        dumps = []
        wd = HangWatchdog(
            "test", timeout_s=0.15, poll_interval=0.05,
            on_dump=lambda stalled: dumps.append(stalled),
        )
        with caplog.at_level(
            logging_mod.ERROR, logger="areal_tpu.worker_base"
        ):
            wd.start()
            deadline = time.time() + 5
            while not dumps and time.time() < deadline:
                time.sleep(0.02)
            wd.stop()
        release.set()
        t.join(timeout=5)
        assert wd.dumps >= 1
        assert (
            metrics_mod.counters.get("guard/watchdog_dumps")
            >= before + wd.dumps
        )
        log = caplog.text
        assert "no heartbeat" in log and "thread stacks" in log
        assert "hung-step" in log                  # the wedged thread
        assert "train_pipe/dispatch_hung" in log   # the open span

    def test_bump_keeps_watchdog_quiet(self):
        from areal_tpu.system.worker_base import HangWatchdog

        wd = HangWatchdog("quiet", timeout_s=0.2, poll_interval=0.02)
        wd.start()
        t0 = time.time()
        while time.time() - t0 < 0.5:
            wd.bump()
            time.sleep(0.02)
        wd.stop()
        assert wd.dumps == 0


_CHILD = r"""
import os, sys, time
sys.path.insert(0, {repo!r})
os.environ["AREAL_NAME_RESOLVE_ROOT"] = {root!r}
from areal_tpu.base import name_resolve
name_resolve.reconfigure(
    name_resolve.NameResolveConfig(type="file", root={root!r})
)
from areal_tpu.system.worker_base import ExperimentStatusWatch, Heartbeat

hb = Heartbeat("killtest", "t0", "child", interval=0.05).start()
watch = ExperimentStatusWatch("killtest", "t0", timeout=2.0, poll_interval=0.0)
# the worker loop: spin while the experiment lives, exit 0 when it dies
while watch.alive():
    time.sleep(0.05)
hb.stop()
sys.exit(0)
"""


@pytest.mark.slow
def test_orphaned_worker_exits_when_experiment_dies(tmp_path):
    """Kill-the-trainer scenario across real processes: the launcher-side
    status flip (here: key deletion simulating launcher death after the
    grace window / explicit stop) makes every worker exit cleanly."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    root = str(tmp_path / "nr")
    script = _CHILD.format(repo=repo, root=root)

    # launcher-side name_resolve over the same file backend (a direct
    # repository instance — the module default stays in-memory for the
    # other tests in this process)
    ns = name_resolve.FileNameRecordRepository(root)
    from areal_tpu.base import names

    status_key = names.experiment_status("killtest", "t0")
    ns.add(status_key, "running", replace=True)

    procs = [
        subprocess.Popen([sys.executable, "-c", script])
        for _ in range(2)
    ]
    # wait for the workers to come up (heartbeat visible launcher-side)
    hb_key = names.worker_status("killtest", "t0", "child")
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            ns.get(hb_key)
            break
        except name_resolve.NameEntryNotFoundError:
            time.sleep(0.1)
    else:
        pytest.fail("no heartbeat from child workers")
    assert all(p.poll() is None for p in procs)  # workers running

    ns.add(status_key, "stopped", replace=True)  # trainer/launcher death
    for p in procs:
        assert p.wait(timeout=15) == 0           # clean, prompt exit
