"""Elastic-multihost unit tests (docs/fault_tolerance.md "Elastic
multihost") — the fast, in-process side: world-epoch records, liveness
leases + key hygiene, bounded-timeout collectives, supervisor culprit
decisions (driven end-to-end with jax-free stub ranks), seeded chaos
schedules, the fault-point/doc catalog sync, and the trainer's surgical
recovery. The real N-process jax worlds live in
tests/test_elastic_multihost.py (slow)."""

import json
import os
import re
import sys
import textwrap
import threading
import time

import pytest

from areal_tpu.apps.launcher import WorldSupervisor, WorldSupervisorConfig
from areal_tpu.base import faults, name_resolve, names
from areal_tpu.base import metrics as metrics_mod
from areal_tpu.parallel import elastic
from tools import chaos

EXP, TRIAL = "elastic_test", "t0"


@pytest.fixture(autouse=True)
def _memory_name_resolve():
    prev = name_resolve.default_repository()
    name_resolve.set_repository(name_resolve.MemoryNameRecordRepository())
    yield
    name_resolve.set_repository(prev)


@pytest.fixture(autouse=True)
def _faults_reset():
    yield
    faults.reset()


# --------------------------------------------------------------------- #
# world-epoch record
# --------------------------------------------------------------------- #


def test_world_record_roundtrip():
    ws = elastic.WorldState(epoch=3, coordinator="127.0.0.1:1234",
                            num_processes=4)
    elastic.write_world(EXP, TRIAL, ws)
    got = elastic.read_world(EXP, TRIAL)
    assert got == ws
    # replace semantics: the supervisor bumps in place
    elastic.write_world(EXP, TRIAL, elastic.WorldState(4, "127.0.0.1:9", 4))
    assert elastic.read_world(EXP, TRIAL).epoch == 4


def test_read_world_tolerates_absent_and_malformed():
    assert elastic.read_world(EXP, TRIAL) is None
    name_resolve.add(names.elastic_world(EXP, TRIAL), "{not json",
                     replace=True)
    assert elastic.read_world(EXP, TRIAL) is None


def test_wait_for_world_min_epoch_and_timeout():
    elastic.write_world(EXP, TRIAL, elastic.WorldState(1, "c:1", 2))
    assert elastic.wait_for_world(EXP, TRIAL, min_epoch=1, timeout=1).epoch == 1
    with pytest.raises(TimeoutError):
        elastic.wait_for_world(EXP, TRIAL, min_epoch=2, timeout=0.3,
                               poll_s=0.05)


# --------------------------------------------------------------------- #
# leases + key hygiene (the dead-rank sweep satellite)
# --------------------------------------------------------------------- #


def test_lease_publish_and_read():
    lease = elastic.RankLease(EXP, TRIAL, 2, interval_s=30.0)
    lease.start()
    lease.set_epoch(5)
    try:
        got = elastic.read_leases(EXP, TRIAL)
        assert got[2]["epoch"] == 5
        assert got[2]["pid"] == os.getpid()
    finally:
        lease.stop()


def test_sweep_rank_keys_removes_all_residue():
    """Dead-rank keys (lease, heartbeat, telemetry snapshot) must be swept
    on the world-epoch bump instead of accumulating across reformations."""
    worker = elastic.rank_worker_name(1)
    name_resolve.add(names.elastic_lease(EXP, TRIAL, 1), "{}", replace=True)
    name_resolve.add(names.worker_status(EXP, TRIAL, worker), "123",
                     replace=True)
    name_resolve.add(names.telemetry(EXP, TRIAL, worker), "{}", replace=True)
    # an unrelated rank's keys must survive the sweep
    name_resolve.add(names.elastic_lease(EXP, TRIAL, 0), "{}", replace=True)
    assert elastic.sweep_rank_keys(EXP, TRIAL, 1) == 3
    assert elastic.read_leases(EXP, TRIAL) == {0: {}}
    with pytest.raises(name_resolve.NameEntryNotFoundError):
        name_resolve.get(names.worker_status(EXP, TRIAL, worker))
    with pytest.raises(name_resolve.NameEntryNotFoundError):
        name_resolve.get(names.telemetry(EXP, TRIAL, worker))
    # idempotent: a second sweep finds nothing
    assert elastic.sweep_rank_keys(EXP, TRIAL, 1) == 0


def test_timeout_reports_roundtrip_and_sweep():
    elastic.report_timeout(EXP, TRIAL, 0, 1, "barrier timed out")
    elastic.report_timeout(EXP, TRIAL, 0, 3, "allgather timed out")
    elastic.report_timeout(EXP, TRIAL, 1, 2, "next epoch")
    assert sorted(elastic.read_timeout_reports(EXP, TRIAL, 0)) == [1, 3]
    elastic.sweep_timeout_reports(EXP, TRIAL, upto_epoch=0)
    assert elastic.read_timeout_reports(EXP, TRIAL, 0) == {}
    assert sorted(elastic.read_timeout_reports(EXP, TRIAL, 1)) == [2]


# --------------------------------------------------------------------- #
# bounded-timeout collectives
# --------------------------------------------------------------------- #


def test_guard_runs_and_returns():
    g = elastic.CollectiveGuard(timeout_s=5.0)
    assert g.run(lambda: 42, "test") == 42


def test_guard_timeout_within_deadline():
    g = elastic.CollectiveGuard(timeout_s=0.3)
    before = metrics_mod.counters.get(metrics_mod.FT_COLLECTIVE_TIMEOUTS)
    let_go, returned = threading.Event(), threading.Event()

    def wedged():
        let_go.wait(60)
        returned.set()

    with pytest.raises(elastic.CollectiveTimeoutError):
        g.run(wedged, "wedged")
    # raised by the guard's deadline while the collective was STILL wedged
    # (an order of events: no bound on this test's own seconds)
    assert not returned.is_set()
    let_go.set()
    assert (
        metrics_mod.counters.get(metrics_mod.FT_COLLECTIVE_TIMEOUTS)
        == before + 1
    )
    # the worker thread is wedged; reset installs a fresh one
    g.reset()
    assert g.run(lambda: "fresh", "after-reset") == "fresh"


def test_guard_abort_condemns_epoch():
    g = elastic.CollectiveGuard(timeout_s=5.0)
    g.abort()
    with pytest.raises(elastic.CollectiveTimeoutError):
        g.run(lambda: 1, "condemned")
    g.reset()
    assert g.run(lambda: 1, "recovered") == 1


def test_guard_classifies_transport_errors():
    g = elastic.CollectiveGuard(timeout_s=5.0)

    def boom():
        raise ConnectionResetError("peer died")

    with pytest.raises(elastic.CollectiveFailedError):
        g.run(boom, "transport")

    def bug():
        raise ValueError("a real bug")

    with pytest.raises(ValueError):  # program bugs propagate unchanged
        g.run(bug, "bug")


def test_guard_fault_point_injects_timeout():
    """The collective.timeout fault point deterministically scripts a
    timeout without real wedging (used by the chaos harness)."""
    g = elastic.CollectiveGuard(timeout_s=30.0)
    ran = []
    faults.inject("collective.timeout", action="trip", times=1,
                  label="barrier:x")
    with pytest.raises(elastic.CollectiveTimeoutError):
        g.run(lambda: ran.append(1), "barrier:x")
    assert not ran  # the collective body never executed
    assert g.run(lambda: "ok", "barrier:x") == "ok"  # rule exhausted


def test_as_world_failure_classification():
    assert elastic.as_world_failure(ValueError("x")) is None
    wf = elastic.as_world_failure(ConnectionError("reset"))
    assert isinstance(wf, elastic.CollectiveFailedError)
    original = elastic.CollectiveTimeoutError("t")
    assert elastic.as_world_failure(original) is original

    from jax.errors import JaxRuntimeError as XlaRuntimeError

    assert isinstance(
        elastic.as_world_failure(XlaRuntimeError("gloo died")),
        elastic.CollectiveFailedError,
    )
    # deterministic rank-local XLA errors must NOT trigger reforms — an
    # OOM or shape bug reproduces identically after every rebuild
    assert elastic.as_world_failure(
        XlaRuntimeError("RESOURCE_EXHAUSTED: out of memory on device")
    ) is None
    assert elastic.as_world_failure(
        XlaRuntimeError("INVALID_ARGUMENT: shapes do not match")
    ) is None


# --------------------------------------------------------------------- #
# supervisor culprit decisions
# --------------------------------------------------------------------- #


def test_decide_culprits_exited_only():
    assert WorldSupervisor.decide_culprits(
        {2: -9}, {0: {}, 1: {}}, alive=[0, 1, 3]
    ) == [2]
    # clean exits are never culprits
    assert WorldSupervisor.decide_culprits({3: 0}, {}, alive=[0, 1, 2]) == []


def test_decide_culprits_wedged_only_after_deadline():
    reports = {0: {}, 1: {}}
    alive = [0, 1, 2]
    assert WorldSupervisor.decide_culprits(
        {}, reports, alive, wedge_deadline_passed=False
    ) == []
    assert WorldSupervisor.decide_culprits(
        {}, reports, alive, wedge_deadline_passed=True
    ) == [2]


def test_decide_culprits_mixed_counts_once():
    # a rank that exited AND reported (died while reforming) counts once
    assert WorldSupervisor.decide_culprits(
        {1: -6, 2: 1}, {1: {}, 0: {}}, alive=[0, 3],
        wedge_deadline_passed=True,
    ) == [1, 2, 3]


# --------------------------------------------------------------------- #
# supervisor end-to-end with jax-free stub ranks
# --------------------------------------------------------------------- #

_STUB = textwrap.dedent(
    """
    import json, os, sys, time
    rank = int(sys.argv[1]); root = sys.argv[2]; mode = sys.argv[3]
    sys.path.insert(0, sys.argv[4])
    from areal_tpu.base import name_resolve, names
    from areal_tpu.parallel import elastic
    name_resolve.reconfigure(
        name_resolve.NameResolveConfig(type="file", root=root))
    EXP, TRIAL = "elastic_test", "t0"
    lease = elastic.RankLease(EXP, TRIAL, rank, interval_s=0.1).start()
    while True:
        ws = elastic.read_world(EXP, TRIAL)
        if ws is None:
            time.sleep(0.05); continue
        lease.set_epoch(ws.epoch)
        if ws.epoch == 0:
            if mode == "die":
                os._exit(3)
            if mode == "worldfail":
                os._exit(77)   # EXIT_WORLD_FAILED: explicit escalation
            if mode == "preempted":
                os._exit(75)   # EXIT_PREEMPTED: slice reclaimed
            if mode == "hang":
                time.sleep(600)
            if mode == "survivor":
                # a survivor's bounded collective "timed out": report and
                # wait for the next epoch, like WorldEpochManager.reform
                elastic.report_timeout(EXP, TRIAL, 0, rank, "stub timeout")
                ws = elastic.wait_for_world(EXP, TRIAL, min_epoch=1,
                                            timeout=120)
                lease.set_epoch(ws.epoch)
        # any rank at epoch >= 1 (or a plain rank at epoch 0) finishes
        if ws.epoch >= 1 or mode == "normal":
            time.sleep(0.3)   # outlive one supervisor poll
            os._exit(0)
        time.sleep(0.05)
    """
)


def _stub_world(tmp_path, modes, **cfg_kw):
    """A WorldSupervisor over jax-free stub ranks; returns (rc, sup)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    nr_root = str(tmp_path / "nr")
    stub = tmp_path / "stub.py"
    stub.write_text(_STUB)
    # the supervisor process reads/writes the same file-backed repo
    name_resolve.set_repository(
        name_resolve.make_repository(
            name_resolve.NameResolveConfig(type="file", root=nr_root)
        )
    )
    sup = WorldSupervisor(
        WorldSupervisorConfig(
            experiment_name=EXP,
            trial_name=TRIAL,
            num_processes=len(modes),
            rank_cmd=lambda r: [
                sys.executable, str(stub), str(r), nr_root, modes[r], repo
            ],
            poll_s=0.05,
            exit_grace_s=0.1,
            # the wedge window (timeout + grace after the FIRST report)
            # must outlast the spread in stub start-up under a loaded,
            # six-worker test run: at 0.5 + 0.5 s a slow-starting survivor
            # was SIGKILLed as wedged (rank_restarts == 2)
            collective_timeout_s=cfg_kw.pop("collective_timeout_s", 2.0),
            report_grace_s=cfg_kw.pop("report_grace_s", 2.0),
            reform_timeout_s=60.0,
            **cfg_kw,
        )
    )
    # the test's OWN waiting bounds (this one, the survivors' wait for the
    # next epoch, the reform) have room for a machine six workers share: a
    # passing world ends when its ranks do, a few seconds in. The lease
    # deadlines above are the program's and are what the cases are about
    rc = sup.start().run(timeout=240.0)
    return rc, sup


def test_supervisor_recovers_dead_rank(tmp_path):
    before = metrics_mod.counters.get(metrics_mod.FT_RANK_RESTARTS)
    rc, sup = _stub_world(tmp_path, {0: "survivor", 1: "die", 2: "survivor"})
    assert rc == 0
    assert sup.rank_restarts == 1 and sup.epoch == 1
    assert len(sup.recovery_times) == 1
    assert (
        metrics_mod.counters.get(metrics_mod.FT_RANK_RESTARTS) == before + 1
    )
    # hygiene: the relaunched rank's lease exists at the final epoch only
    leases = elastic.read_leases(EXP, TRIAL)
    assert sorted(leases) == [0, 1, 2]
    assert all(d["epoch"] == 1 for d in leases.values())
    # consumed timeout reports were swept on the bump
    assert elastic.read_timeout_reports(EXP, TRIAL, 0) == {}


def test_supervisor_kills_wedged_rank_after_deadline(tmp_path):
    rc, sup = _stub_world(
        tmp_path, {0: "survivor", 1: "hang", 2: "survivor"}
    )
    assert rc == 0
    assert sup.rank_restarts == 1 and sup.epoch == 1


def test_supervisor_clean_world_no_reform(tmp_path):
    rc, sup = _stub_world(tmp_path, {0: "normal", 1: "normal"})
    assert rc == 0
    assert sup.rank_restarts == 0 and sup.epoch == 0


def test_supervisor_escalates_on_exit_world_failed(tmp_path):
    """EXIT_WORLD_FAILED (77) is a rank explicitly giving up on surgical
    recovery — the supervisor must escalate to restart-the-world, not
    hand the rank a fresh reform budget."""
    rc, sup = _stub_world(tmp_path, {0: "survivor", 1: "worldfail"})
    assert rc == 1
    assert sup.rank_restarts == 0 and sup.epoch == 0


def test_supervisor_stops_on_preemption(tmp_path):
    """EXIT_PREEMPTED means the slice is being reclaimed: the rank's
    state is its committed checkpoint — relaunching would burn the
    preemption grace window on churn."""
    from areal_tpu.system import worker_base

    rc, sup = _stub_world(tmp_path, {0: "survivor", 1: "preempted"})
    assert rc == worker_base.EXIT_PREEMPTED
    assert sup.rank_restarts == 0 and sup.epoch == 0


def test_supervisor_budget_exhaustion(tmp_path):
    # every relaunch dies again at epoch... the stub dies only at epoch 0;
    # use a mode map where rank 1 dies at every epoch via max_rank_restarts=0
    rc, sup = _stub_world(
        tmp_path, {0: "survivor", 1: "die"}, max_rank_restarts=0
    )
    assert rc == 1
    assert sup.rank_restarts == 0


# --------------------------------------------------------------------- #
# seeded chaos schedules
# --------------------------------------------------------------------- #


def test_schedule_deterministic_and_bounded():
    a = chaos.make_schedule(7, 4, 4, 20, 5)
    b = chaos.make_schedule(7, 4, 4, 20, 5)
    assert a == b and len(a) == 4
    for i, ev in enumerate(a):
        assert ev["kind"] in ("kill", "hang")
        assert 0 <= ev["rank"] < 4
        assert ev["epoch"] == i
        assert 1 <= ev["step"] < 20


def test_schedule_events_guaranteed_to_fire():
    """Each epoch's fault step must be reachable from the previous
    fault's committed-checkpoint resume point."""
    for seed in range(20):
        sched = chaos.make_schedule(seed, 5, 4, 24, 4)
        resume = 0
        for ev in sched:
            assert ev["step"] >= resume, (seed, sched)
            resume = (ev["step"] // 4) * 4


# --------------------------------------------------------------------- #
# catalog sync: FAULT_POINTS vs docs/fault_tolerance.md
# --------------------------------------------------------------------- #


def test_fault_point_catalog_matches_docs_table():
    """The injection-point table in docs/fault_tolerance.md and the
    FAULT_POINTS registry must name exactly the same points — the same
    loud-drift contract as the arealint mesh catalog."""
    doc = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", "fault_tolerance.md",
    )
    with open(doc) as f:
        text = f.read()
    # rows look like: | `gen.http`          | where ... | kwargs |
    documented = set(
        re.findall(r"^\|\s*`([a-z_.]+)`\s*\|", text, flags=re.MULTILINE)
    )
    assert documented == set(faults.FAULT_POINTS), (
        "docs/fault_tolerance.md injection-point table drifted from "
        f"base/faults.py FAULT_POINTS: doc-only={documented - set(faults.FAULT_POINTS)}, "
        f"registry-only={set(faults.FAULT_POINTS) - documented}"
    )


# --------------------------------------------------------------------- #
# metrics + obs surfacing
# --------------------------------------------------------------------- #


def test_elastic_counters_registered():
    from areal_tpu.system.telemetry import _ft_catalog

    cat = _ft_catalog()
    for key in (
        metrics_mod.FT_RANK_RESTARTS,
        metrics_mod.FT_WORLD_EPOCHS,
        metrics_mod.FT_COLLECTIVE_TIMEOUTS,
    ):
        assert key in cat  # zero-filled into every fleet/ record
    assert (
        metrics_mod.METRIC_KINDS[metrics_mod.RECOVERY_TIME_S]
        == metrics_mod.KIND_HISTOGRAM
    )
    reg = metrics_mod.CounterRegistry()
    reg.observe(metrics_mod.RECOVERY_TIME_S, 12.5)
    assert reg.histogram_summaries()[metrics_mod.RECOVERY_TIME_S]["count"] == 1


def test_obs_has_supervisor_headline_row():
    from areal_tpu.apps.obs import _ROLE_HEADLINE

    label, key = _ROLE_HEADLINE["supervisor"]
    assert key == metrics_mod.FT_RANK_RESTARTS


def test_exit_world_failed_code_distinct():
    from areal_tpu.system import worker_base

    assert worker_base.EXIT_WORLD_FAILED == 77
    assert len({
        worker_base.EXIT_PREEMPTED,
        worker_base.EXIT_WATCHDOG,
        worker_base.EXIT_WORLD_FAILED,
    }) == 3


# --------------------------------------------------------------------- #
# trainer surgical recovery (fake world manager, real engines)
# --------------------------------------------------------------------- #


def test_trainer_elastic_recover_rolls_back_and_republishes(
    tmp_path, monkeypatch
):
    """_elastic_recover must: reform, swap in factory-built engines,
    restore the committed recover checkpoint (identical step), and
    republish under a NEW monotonic version so the manager cannot drop
    the announce."""
    monkeypatch.setenv("AREAL_FILEROOT", str(tmp_path))
    from areal_tpu.base import constants
    from tests import test_fault_tolerance as ft
    from tests.test_fault_tolerance import _tiny_trainer

    constants.set_experiment_trial_names(ft.EXP, ft.TRIAL)
    worker, eng, stream = _tiny_trainer()
    worker.step = 4
    worker.samples_consumed = 8
    worker.save_recover_checkpoint()
    ckpt_step = worker.step
    # the run moved on past the checkpoint before the world failed
    worker.step = 6
    eng.version = 9

    class _FakeWorld:
        epoch = 2

    class _FakeMgr:
        world = _FakeWorld()

        def __init__(self):
            self.reform_reasons = []

        def reform(self, reason):
            self.reform_reasons.append(reason)
            return self.world

    mgr = _FakeMgr()
    _, fresh_eng, _ = _tiny_trainer()

    def factory():
        return fresh_eng, None, None, None

    worker._elastic_recover(
        mgr, factory, elastic.CollectiveFailedError("peer died")
    )
    assert mgr.reform_reasons  # the world actually reformed
    assert worker.actor_engine is fresh_eng  # engines rebuilt
    assert worker.step == ckpt_step  # identical resume step
    # republished under a NEW version the fleet cannot drop
    assert worker.actor_engine.version > 9
    v = name_resolve.get(names.model_version(ft.EXP, ft.TRIAL, "actor"))
    assert int(v.split(":")[0]) == worker.actor_engine.version

    # and WITHOUT a committed checkpoint, survivors reset to the fresh
    # start the relaunched rank will take — keeping the pre-failure step
    # would desynchronize every step-keyed collective branch
    worker.step = 6
    worker.samples_consumed = 12
    import unittest.mock as mock

    with mock.patch.object(
        type(worker), "load_recover_checkpoint", return_value=False
    ):
        worker._elastic_recover(
            mgr, factory, elastic.CollectiveFailedError("peer died again")
        )
    assert worker.step == 0 and worker.samples_consumed == 0
