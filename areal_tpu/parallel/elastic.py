"""Elastic multihost: surgical rank-level kill-and-recover.

The restart-the-world posture (``apps/launcher.py`` recover loop) burns the
whole fleet for one bad rank. This module gives the multihost trainer world
a *world epoch* protocol instead (docs/fault_tolerance.md "Elastic
multihost"):

- **detection** — every host-side ``multihost`` collective runs through a
  :class:`CollectiveGuard`: a bounded-timeout, abortable execution, so a
  rank wedged inside ``barrier``/``allreduce_*`` surfaces as a
  :class:`CollectiveTimeoutError` within the configured deadline, and a
  rank that *died* surfaces even faster (the gloo/DCN transport errors the
  moment a peer's sockets reset). Each rank additionally publishes a
  liveness **lease** through ``name_resolve`` next to its heartbeat.
- **reformation** — on detection, a surviving rank reports a per-epoch
  timeout record, *parks* its distributed-runtime objects, clears the JAX
  backends/caches (all device state on this rank is gone — rollback to the
  last committed recover checkpoint is mandatory), and waits for the
  launcher-side supervisor (``apps/launcher.py::WorldSupervisor``) to bump
  the monotonic **world epoch** record with a fresh coordinator port. It
  then re-enters ``jax.distributed`` initialization at the new epoch while
  the supervisor relaunches only the dead/wedged rank with the same
  ``--process-id``.
- **proof** — ``tools/chaos.py`` drives seeded kill/hang schedules against
  the N-process CPU fault world and asserts the end-state invariants
  (``make chaos``; slow soak in ``tests/test_elastic_multihost.py``).

Three hard-won runtime facts this module encodes (each cost a prototype;
see the chaos harness for the living proof):

1. The distributed client/service must be built by *us*, not
   ``jax.distributed.initialize``: heartbeat-based death propagation is
   effectively disabled (huge intervals) and ``shutdown_on_destruction``
   is off, because the default error path is ``LOG(FATAL)`` — the
   coordination service noticing a dead peer would terminate every
   *survivor*, which is exactly the restart-the-world behavior this module
   exists to remove. Failure detection authority belongs to the
   CollectiveGuard and the supervisor alone.
2. Old-epoch runtime objects are **parked, never destroyed**
   (:data:`_parked`): destroying the rank-0 service closes sockets that
   surviving clients' error-poll threads are blocked on, and that poll
   failure is a hard ``LOG(FATAL)``. The park leaks a few idle threads and
   one port per reformation — bounded by ``elastic_max_reforms``, then the
   launcher's restart-the-world loop takes over.
3. Rank processes must leave via :func:`hard_exit`: interpreter teardown
   destroys the parked objects in arbitrary order and trips the same
   fatal. State is flushed first; the commit protocol makes the hard exit
   safe.
"""

import dataclasses
import json
import os
import queue
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

from areal_tpu.base import constants, faults, logging, name_resolve, names
from areal_tpu.base import metrics as metrics_mod
from areal_tpu.parallel import multihost

logger = logging.getLogger("areal_tpu.elastic")

# Effectively-disabled heartbeat timeout for the coordination service and
# clients (fact 1 above): failure detection is ours, not theirs.
_HEARTBEAT_TIMEOUT_S = 10 * 365 * 24 * 3600

# Strong references to previous epochs' distributed-runtime objects
# (fact 2 above). Never cleared during the process lifetime.
_parked: List[object] = []


class WorldFailureError(RuntimeError):
    """Base class: the current world epoch is condemned; the holder must
    reform (or die and be relaunched)."""


class CollectiveTimeoutError(WorldFailureError):
    """A bounded host collective overran its deadline — some peer is
    wedged (or the abort flag condemned the epoch mid-wait)."""


class CollectiveFailedError(WorldFailureError):
    """The collective transport failed outright — a peer died (connection
    reset) or the runtime is torn."""


class ReformBudgetError(WorldFailureError):
    """More reformations than ``elastic_max_reforms`` in one incarnation:
    escalate to restart-the-world."""


# XLA status prefixes that mark DETERMINISTIC rank-local program errors
# (an OOM or a shape/argument bug reproduces identically after a reform):
# classifying them as world failures would burn the whole reform budget —
# epoch bump + engine rebuild + restore across the fleet, per retry — on
# an error that recovery cannot fix.
_LOCAL_ERROR_MARKERS = ("RESOURCE_EXHAUSTED", "INVALID_ARGUMENT")


def as_world_failure(err: BaseException) -> Optional[WorldFailureError]:
    """Classify an exception as a world failure, or None.

    ``WorldFailureError`` passes through; a ``JaxRuntimeError`` (the gloo
    transport erroring the instant a dead peer's sockets reset — the FAST
    detection path — or a device collective failing mid-step) and plain
    ``ConnectionError``s wrap into :class:`CollectiveFailedError` —
    EXCEPT XLA statuses that mark deterministic rank-local errors (OOM,
    invalid arguments). Those, and everything else (a genuine program
    bug), return None and must propagate unchanged."""
    if isinstance(err, WorldFailureError):
        return err
    import jax  # deferred: elastic is importable without a backend

    if isinstance(err, jax.errors.JaxRuntimeError):
        msg = str(err)
        if any(m in msg for m in _LOCAL_ERROR_MARKERS):
            return None
        return CollectiveFailedError(f"runtime failure (peer death?): {err}")
    if isinstance(err, ConnectionError):
        return CollectiveFailedError(f"runtime failure (peer death?): {err}")
    return None


@dataclasses.dataclass
class WorldState:
    """The supervisor-owned world-epoch record in name_resolve."""

    epoch: int
    coordinator: str          # host:port for this epoch's jax coordinator
    num_processes: int

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, raw: str) -> "WorldState":
        d = json.loads(raw)
        return cls(
            epoch=int(d["epoch"]),
            coordinator=str(d["coordinator"]),
            num_processes=int(d["num_processes"]),
        )


def write_world(experiment_name: str, trial_name: str, ws: WorldState) -> None:
    name_resolve.add(
        names.elastic_world(experiment_name, trial_name),
        ws.to_json(),
        replace=True,
    )


def read_world(experiment_name: str, trial_name: str) -> Optional[WorldState]:
    try:
        raw = name_resolve.get(names.elastic_world(experiment_name, trial_name))
    except name_resolve.NameEntryNotFoundError:
        return None
    try:
        return WorldState.from_json(raw)
    except (ValueError, KeyError, TypeError):
        logger.warning("malformed elastic world record: %r", raw)
        return None


def wait_for_world(
    experiment_name: str,
    trial_name: str,
    min_epoch: int = 0,
    timeout: Optional[float] = 300.0,
    poll_s: float = 0.2,
) -> WorldState:
    """Block until the world record shows ``epoch >= min_epoch``."""
    deadline = None if timeout is None else time.monotonic() + timeout
    while True:
        ws = read_world(experiment_name, trial_name)
        if ws is not None and ws.epoch >= min_epoch:
            return ws
        if deadline is not None and time.monotonic() > deadline:
            raise TimeoutError(
                f"no world record with epoch >= {min_epoch} within {timeout}s"
            )
        time.sleep(poll_s)


# --------------------------------------------------------------------- #
# Liveness leases + key hygiene
# --------------------------------------------------------------------- #


def rank_worker_name(rank: int) -> str:
    """Canonical worker name of one trainer rank — its heartbeat and
    telemetry snapshots publish under this (and are swept by
    :func:`sweep_rank_keys` when the rank dies)."""
    return f"trainer/rank{rank}"


class RankLease:
    """Background thread refreshing this rank's liveness lease: JSON
    ``{epoch, time, pid}`` under ``elastic/lease/<rank>``. The supervisor
    reads leases as an auxiliary liveness/progress signal (the
    authoritative ones are process exit and timeout reports) and to know
    when every rank is live at a new epoch."""

    def __init__(
        self,
        experiment_name: str,
        trial_name: str,
        rank: int,
        interval_s: Optional[float] = None,
    ):
        self.key = names.elastic_lease(experiment_name, trial_name, rank)
        self.interval_s = (
            interval_s
            if interval_s is not None
            else constants.elastic_lease_interval_s()
        )
        self._epoch = -1
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def set_epoch(self, epoch: int) -> None:
        with self._lock:
            self._epoch = epoch
        self.publish_once()

    def publish_once(self) -> None:
        with self._lock:
            epoch = self._epoch
        try:
            name_resolve.add(
                self.key,
                json.dumps(
                    {"epoch": epoch, "time": time.time(), "pid": os.getpid()}
                ),
                replace=True,
            )
        except Exception:
            logger.warning("lease publish failed", exc_info=True)

    def _loop(self):
        while not self._stop.wait(self.interval_s):
            self.publish_once()

    def start(self) -> "RankLease":
        if self._thread is None:
            self.publish_once()
            self._thread = threading.Thread(
                target=self._loop, name="elastic-lease", daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None


def read_leases(experiment_name: str, trial_name: str) -> Dict[int, dict]:
    """``{rank: lease dict}`` for every published lease (malformed ones
    skipped)."""
    root = names.elastic_lease_root(experiment_name, trial_name)
    out: Dict[int, dict] = {}
    try:
        keys = name_resolve.find_subtree(root)
    except name_resolve.NameEntryNotFoundError:
        return out
    for k in keys:
        try:
            rank = int(k.rsplit("/", 1)[1])
            d = json.loads(name_resolve.get(k))
        except (ValueError, IndexError, name_resolve.NameEntryNotFoundError):
            continue
        if isinstance(d, dict):
            out[rank] = d
    return out


def sweep_rank_keys(experiment_name: str, trial_name: str, rank: int) -> int:
    """Delete a dead rank's name_resolve residue — its liveness lease and
    its heartbeat/telemetry snapshots — so reformations don't accumulate
    ghost entries that the ops CLI and the fleet aggregator would keep
    rendering. Returns the number of keys actually removed."""
    worker = rank_worker_name(rank)
    removed = 0
    for key in (
        names.elastic_lease(experiment_name, trial_name, rank),
        names.worker_status(experiment_name, trial_name, worker),
        names.telemetry(experiment_name, trial_name, worker),
    ):
        try:
            name_resolve.delete(key)
            removed += 1
        except name_resolve.NameEntryNotFoundError:
            pass
    return removed


def sweep_timeout_reports(
    experiment_name: str, trial_name: str, upto_epoch: int
) -> None:
    """Drop timeout-report subtrees for epochs ``<= upto_epoch`` (they are
    consumed by the supervisor's reform decision and dead weight after)."""
    for e in range(max(upto_epoch + 1, 0)):
        name_resolve.clear_subtree(
            names.elastic_timeout_root(experiment_name, trial_name, e)
        )


def report_timeout(
    experiment_name: str, trial_name: str, epoch: int, rank: int, reason: str
) -> None:
    """Publish this rank's survivor report for ``epoch`` (idempotent)."""
    name_resolve.add(
        names.elastic_timeout(experiment_name, trial_name, epoch, rank),
        json.dumps({"time": time.time(), "reason": reason[:500]}),
        replace=True,
    )


def read_timeout_reports(
    experiment_name: str, trial_name: str, epoch: int
) -> Dict[int, dict]:
    root = names.elastic_timeout_root(experiment_name, trial_name, epoch)
    out: Dict[int, dict] = {}
    try:
        keys = name_resolve.find_subtree(root)
    except name_resolve.NameEntryNotFoundError:
        return out
    for k in keys:
        try:
            rank = int(k.rsplit("/", 1)[1])
            out[rank] = json.loads(name_resolve.get(k))
        except (ValueError, IndexError, name_resolve.NameEntryNotFoundError):
            continue
    return out


# --------------------------------------------------------------------- #
# Bounded-timeout collectives
# --------------------------------------------------------------------- #


class CollectiveGuard:
    """Run host-side collectives with a deadline and an abort flag.

    One dedicated worker thread executes collectives strictly in order
    (two collectives racing on one communicator is undefined behavior);
    submitters wait bounded. On timeout/abort the submitter raises and the
    worker thread is *abandoned* to the wedged call — :meth:`reset` (run
    during reformation) installs a fresh thread; the wedged one unblocks
    (with a transport error, swallowed) once the supervisor kills the
    culprit rank, or parks forever next to the parked runtime objects.

    Transport errors from the collective body are classified as
    :class:`CollectiveFailedError` (a dead peer resets its sockets — this
    is the *fast* detection path); everything else propagates unchanged.
    """

    def __init__(self, timeout_s: Optional[float] = None):
        self.timeout_s = (
            timeout_s if timeout_s is not None
            else constants.collective_timeout_s()
        )
        self.aborted = threading.Event()
        self._submit_lock = threading.Lock()
        self._jobs: "queue.Queue" = queue.Queue()
        self._thread: Optional[threading.Thread] = None
        self.timeouts = 0

    def _ensure_thread(self):
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._work, args=(self._jobs,),
                name="collective-guard", daemon=True,
            )
            self._thread.start()

    @staticmethod
    def _work(jobs: "queue.Queue"):
        while True:
            item = jobs.get()
            if item is None:
                return
            fn, box, done = item
            try:
                box["value"] = fn()
            except BaseException as e:  # noqa: BLE001 — classified by run()
                box["error"] = e
            done.set()

    def abort(self) -> None:
        """Condemn the epoch: every in-flight and future ``run`` raises
        until :meth:`reset`."""
        self.aborted.set()

    def reset(self) -> None:
        """Fresh thread + queue for a new epoch; the old thread (possibly
        wedged inside a dead world's collective) is abandoned."""
        old_jobs = self._jobs
        self._jobs = queue.Queue()
        self._thread = None
        self.aborted.clear()
        old_jobs.put(None)  # stops the old thread iff it ever unblocks

    @staticmethod
    def _classify(err: BaseException, label: str) -> BaseException:
        wf = as_world_failure(err)
        if wf is not None:
            return CollectiveFailedError(f"collective {label}: {wf}")
        return err

    def run(self, fn: Callable, label: str = "collective"):
        """Execute ``fn`` (a host collective) with the guard's deadline."""
        if faults.maybe_trip("collective.timeout", label=label):
            self.timeouts += 1
            metrics_mod.counters.add(metrics_mod.FT_COLLECTIVE_TIMEOUTS)
            raise CollectiveTimeoutError(
                f"collective {label}: timeout injected (fault point)"
            )
        with self._submit_lock:
            if self.aborted.is_set():
                raise CollectiveTimeoutError(
                    f"collective {label}: world epoch condemned"
                )
            self._ensure_thread()
            box: dict = {}
            done = threading.Event()
            self._jobs.put((fn, box, done))
            deadline = time.monotonic() + self.timeout_s
            while not done.wait(timeout=0.1):
                if self.aborted.is_set():
                    raise CollectiveTimeoutError(
                        f"collective {label}: aborted while in flight"
                    )
                if time.monotonic() > deadline:
                    self.timeouts += 1
                    metrics_mod.counters.add(
                        metrics_mod.FT_COLLECTIVE_TIMEOUTS
                    )
                    raise CollectiveTimeoutError(
                        f"collective {label} exceeded {self.timeout_s:.1f}s "
                        "deadline — peer wedged or dead"
                    )
            if "error" in box:
                raise self._classify(box["error"], label)
            return box["value"]


# --------------------------------------------------------------------- #
# World-epoch manager (the rank side of the protocol)
# --------------------------------------------------------------------- #


@dataclasses.dataclass
class ElasticConfig:
    experiment_name: str
    trial_name: str
    num_processes: int
    process_id: int
    collective_timeout_s: Optional[float] = None  # None -> knob default
    lease_interval_s: Optional[float] = None
    init_timeout_s: float = 120.0
    join_timeout_s: float = 300.0
    epoch_poll_s: float = 0.2
    max_reforms: Optional[int] = None

    def resolved_max_reforms(self) -> int:
        return (
            self.max_reforms
            if self.max_reforms is not None
            else constants.elastic_max_reforms()
        )


def _reset_orbax_barrier_counters() -> None:
    """Re-zero orbax's process-global barrier-name counters.

    Orbax makes multihost barrier names unique with module-level
    ``itertools.count()`` counters — monotonic over the *process*
    lifetime. After a surgical reform, survivors carry advanced counters
    while the relaunched rank starts at zero, so the very first
    checkpoint restore of the new epoch fails with a
    ``sync_global_devices name mismatch``. Every rank resets the counters
    when it joins an epoch: survivor or fresh, the sequence restarts from
    zero together (checkpoint traffic is SPMD-lockstep, so the counters
    stay aligned from there)."""
    try:
        import itertools

        from orbax.checkpoint.multihost import counters as _oc
    except ImportError:
        return
    for name, val in list(vars(_oc).items()):
        if isinstance(val, itertools.count):
            setattr(_oc, name, itertools.count())


class WorldEpochManager:
    """One rank's view of the elastic world: joins epochs, guards
    collectives, publishes its lease, and reforms on world failure.

    Usage (see ``tools/chaos.py`` for the full pattern)::

        mgr = WorldEpochManager(ElasticConfig(...))
        mgr.join()                       # blocks for the supervisor record
        while True:
            try:
                ... build engine, restore committed ckpt, train ...
                break
            except elastic.WorldFailureError:
                mgr.reform()             # detach -> wait epoch+1 -> rejoin
                continue                 # rebuild + re-restore (mandatory)
        mgr.stop(); elastic.hard_exit(0)
    """

    def __init__(self, cfg: ElasticConfig):
        self.cfg = cfg
        self.world: Optional[WorldState] = None
        self.guard = CollectiveGuard(cfg.collective_timeout_s)
        self.lease = RankLease(
            cfg.experiment_name, cfg.trial_name, cfg.process_id,
            interval_s=cfg.lease_interval_s,
        )
        self.reforms = 0

    # -- epoch membership ------------------------------------------------

    def join(self) -> WorldState:
        """Join the current world epoch (or, after a detach, the next
        one): wait for the supervisor's record, bring up the distributed
        runtime, and start/refresh the lease."""
        min_epoch = 0 if self.world is None else self.world.epoch + 1
        ws = wait_for_world(
            self.cfg.experiment_name, self.cfg.trial_name,
            min_epoch=min_epoch, timeout=self.cfg.join_timeout_s,
            poll_s=self.cfg.epoch_poll_s,
        )
        if ws.num_processes != self.cfg.num_processes:
            raise WorldFailureError(
                f"world record says {ws.num_processes} processes, "
                f"configured for {self.cfg.num_processes}"
            )
        self._install(ws)
        _reset_orbax_barrier_counters()
        self.world = ws
        self.lease.start()
        self.lease.set_epoch(ws.epoch)
        multihost.set_collective_guard(self.guard)
        multihost.mark_initialized(True)
        logger.info(
            "rank %d joined world epoch %d at %s (%d processes)",
            self.cfg.process_id, ws.epoch, ws.coordinator, ws.num_processes,
        )
        return ws

    def _install(self, ws: WorldState) -> None:
        """Bring up this rank's coordination client for one epoch, with
        death-propagation disabled (module docstring, fact 1). The
        coordination SERVICE is hosted by the supervisor
        (:func:`host_service`), never by a rank: a SIGKILLed rank 0 taking
        the service socket with it would fatal every survivor's parked
        poll thread — the exact cascade surgical recovery exists to
        prevent. A connect failure is fatal to this process by XLA design
        (``LOG(FATAL)``) — the supervisor observes the exit and relaunches
        us, which is the correct recovery anyway."""
        import jax  # deferred: elastic is importable without a backend

        from jax._src import distributed as jdist
        from jax._src.lib import _jax as xe

        st = jdist.global_state
        client = xe.get_distributed_runtime_client(
            ws.coordinator, self.cfg.process_id,
            init_timeout=int(self.cfg.init_timeout_s),
            heartbeat_timeout=_HEARTBEAT_TIMEOUT_S,
            shutdown_on_destruction=False,
            use_compression=True,
            # a rank that dies must not take the survivors with it: the
            # service does not propagate a recoverable task's failure
            recoverable=True,
        )
        client.connect()
        st.client = client
        st.process_id = self.cfg.process_id
        st.num_processes = ws.num_processes
        st.coordinator_address = ws.coordinator
        # sanity: the backend formed after this install must see the world
        n = jax.process_count()
        if n != ws.num_processes:
            raise WorldFailureError(
                f"backend sees {n} processes, world record says "
                f"{ws.num_processes}"
            )

    def detach(self) -> None:
        """Leave the current epoch: park the runtime objects (module
        docstring, fact 2), drop every backend and compilation cache.
        EVERY device array and jitted executable on this rank is invalid
        after this — the caller must rebuild engines and restore from the
        last committed recover checkpoint."""
        import jax

        import jax.extend as jex
        from jax._src import distributed as jdist

        self.guard.abort()
        st = jdist.global_state
        if st.client is not None:
            _parked.append(st.client)
            st.client = None
        if st.service is not None:
            _parked.append(st.service)
            st.service = None
        jex.backend.clear_backends()
        jax.clear_caches()
        self.guard.reset()
        multihost.mark_initialized(False)
        logger.warning(
            "rank %d detached from world epoch %s (%d runtime objects "
            "parked)", self.cfg.process_id,
            self.world.epoch if self.world else "?", len(_parked),
        )

    def reform(self, reason: str = "world failure") -> WorldState:
        """Full survivor-side reformation: report, detach, wait for the
        supervisor's epoch bump, rejoin. Raises :class:`ReformBudgetError`
        past the per-incarnation budget (escalate to restart-the-world)."""
        if self.reforms + 1 > self.cfg.resolved_max_reforms():
            raise ReformBudgetError(
                f"{self.reforms} reformations already in this incarnation "
                f"(budget {self.cfg.resolved_max_reforms()}); escalating"
            )
        epoch = self.world.epoch if self.world is not None else 0
        logger.warning(
            "rank %d reforming out of epoch %d: %s",
            self.cfg.process_id, epoch, reason,
        )
        try:
            report_timeout(
                self.cfg.experiment_name, self.cfg.trial_name,
                epoch, self.cfg.process_id, reason,
            )
        except Exception:
            logger.warning("timeout report failed", exc_info=True)
        self.detach()
        ws = self.join()
        self.reforms += 1
        # NOT counted here: ft/world_epochs and the recovery_time_s
        # histogram belong to the supervisor alone (base/metrics.py) —
        # every surviving rank counting its own reform would multiply the
        # fleet totals by the survivor count
        return ws

    def stop(self) -> None:
        self.lease.stop()
        multihost.set_collective_guard(None)


def host_service(port: int, num_processes: int):
    """Supervisor-side: bring up (and park, process-lifetime) the
    coordination service for one world epoch. Lives in the supervisor —
    the one process the fault model assumes survives — so no rank death
    can close a service socket that surviving clients poll (the
    ``LOG(FATAL)`` cascade of module-docstring fact 2). Old epochs'
    services stay parked next to the clients; ports leak one per
    reformation, bounded by the reform budget."""
    from jax._src.lib import _jax as xe

    service = xe.get_distributed_runtime_service(
        f"[::]:{port}", num_processes,
        heartbeat_timeout=_HEARTBEAT_TIMEOUT_S,
        shutdown_timeout=5,
    )
    _parked.append(service)
    return service


def hard_exit(code: int = 0) -> None:
    """The only safe way out of a process that ever joined an elastic
    world: flush stdio and ``os._exit`` (module docstring, fact 3 — normal
    interpreter teardown destroys parked runtime objects in arbitrary
    order and the coordination-service poll threads LOG(FATAL) on the
    closing sockets)."""
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
