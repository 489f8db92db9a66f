"""Trainer worker: the training side of sync SFT and async PPO.

TPU-native counterpart of the reference's master worker + model workers +
function executor (``realhf/system/{master_worker,model_worker,
function_executor,model_function_call}.py``, ~3k LoC). On TPU every model
role is ONE pjit program over the trainer mesh, so the ZMQ request-reply
mesh, the flush/syn-ack ordering protocol, and the NCCL redistribution plane
collapse into a plain in-process call sequence over the MFC graph
(SURVEY.md §2.2 "Data redistribution plane"):

    rollout stream → [ref_inf, actor_inf(prox), critic_inf] → actor/critic train

What is kept from the reference, semantically intact:
- epoch/step accounting + save/ckpt/eval frequency control
  (``EpochStepTimeFreqCtl``),
- the trainer→fleet weight-sync channel: save HF snapshot →
  ``name_resolve`` version bump (``model_worker.py:787-812``),
- the ``training_samples`` counter feeding the manager's staleness gate,
- RecoverInfo dumps for restart-the-world recovery.
"""

import dataclasses
import logging
import os
import time
from typing import Dict, List, Optional

import numpy as np

from areal_tpu.api.data import MicroBatchSpec, SequenceSample
from areal_tpu.api.model import PPOHyperparameters, make_interface
from areal_tpu.experiments import graphs
from areal_tpu.system import worker_base
from areal_tpu.system.buffer import SequenceBuffer, record_batch_consumption
from areal_tpu.system.function_executor import FunctionExecutor
from areal_tpu.base import constants, hbm, name_resolve, names, recover, tracing
from areal_tpu.base import metrics as metrics_mod
from areal_tpu.base.metrics import MetricLogger
from areal_tpu.base.timeutil import EpochStepTimeFreqCtl
from areal_tpu.parallel import multihost
from areal_tpu.train.engine import TrainEngine

logger = logging.getLogger("areal_tpu.trainer_worker")


@dataclasses.dataclass
class TrainerControl:
    """Save/eval/ckpt cadence (≈ ``ExperimentSaveEvalControl``,
    ``cli_args.py:702``)."""

    total_train_steps: int = 100
    save_freq_steps: Optional[int] = None        # HF export for the user
    ckpt_freq_steps: Optional[int] = 50          # recover checkpoint
    ckpt_freq_secs: Optional[float] = 600.0
    weight_sync_freq_steps: int = 1              # fleet weight push cadence
    # device-scalar stats are pulled to host once per this many steps (ONE
    # batched device_get), not once per step — each pull is a full host
    # round trip that stalls the dispatch pipeline. Inactive (per-step
    # fetch) when AREAL_TRAIN_PREFETCH is off.
    stats_log_freq_steps: int = 8
    # guardrail plane: after this many CONSECUTIVE anomalous steps (each
    # one's optimizer update was already skipped on-device), roll the engine
    # back to the last committed recover checkpoint — persistent anomalies
    # mean the live params/opt state are themselves suspect. 0 disables.
    guard_rollback_steps: int = 3
    # hang watchdog threshold for the train loop (None/0 = disabled)
    watchdog_timeout_secs: Optional[float] = None


class AsyncPPOTrainerWorker:
    """Consumes the rollout stream, runs the PPO MFC sequence per step."""

    def __init__(
        self,
        experiment_name: str,
        trial_name: str,
        actor_engine: TrainEngine,
        stream,                              # PullerStreamDataset-like
        hp: PPOHyperparameters,
        control: TrainerControl,
        train_batch_size: int = 32,          # items/step; per-HOST when multihost
        mb_spec: Optional[MicroBatchSpec] = None,
        ref_engine: Optional[TrainEngine] = None,
        critic_engine: Optional[TrainEngine] = None,
        reward_engine: Optional[TrainEngine] = None,
        hf_family: str = "qwen2",
        metric_logger: Optional[MetricLogger] = None,
        ema_ref_eta: Optional[float] = None,
        graph=None,
        interfaces=None,
        max_head_offpolicyness: Optional[int] = None,
        buffer_capacity: int = 16384,
    ):
        # the engines' constructors started it; stand-in engines did not
        tracing.listen_for_compiles()
        self.experiment_name = experiment_name
        self.trial_name = trial_name
        self.actor_engine = actor_engine
        self.ref_engine = ref_engine
        self.critic_engine = critic_engine
        self.stream = stream
        self.hp = hp
        self.control = control
        self.train_batch_size = train_batch_size
        self.mb_spec = mb_spec or MicroBatchSpec(max_tokens_per_mb=16384)
        self.hf_family = hf_family
        self.metrics = metric_logger
        # per-step HBM gauges + warn/kill thresholds (≈ the reference's
        # per-MFC GPU memory log + REAL_GPU_MEMORY_KILL_THRESHOLD,
        # realhf/system/model_worker.py:1507-1610); HBMPressureError kills
        # the worker loudly so launcher recovery takes over
        self._hbm = hbm.HBMMonitor(tag="trainer")

        # The training step is a declared dataflow graph (critic on/off,
        # EMA-ref, custom algorithms = graph config, not trainer edits).
        # Callers may inject their own (graph, interfaces) pair.
        if graph is None:
            graph, interfaces = graphs.build_ppo_graph(
                hp,
                use_ref=ref_engine is not None,
                use_critic=critic_engine is not None,
                ema_ref_eta=ema_ref_eta,
                mb_spec=self.mb_spec,
                hf_family=hf_family,
                use_reward_model=reward_engine is not None,
            )
        engines = {"actor": actor_engine}
        if ref_engine is not None:
            engines["ref"] = ref_engine
        if critic_engine is not None:
            engines["critic"] = critic_engine
        if reward_engine is not None:
            engines["reward"] = reward_engine
        self.executor = FunctionExecutor(
            graph, engines, interfaces, default_mb_spec=self.mb_spec
        )
        self.actor_if = self.executor.interfaces.get("actor_train")
        self.step = 0
        self.samples_consumed = 0
        # keys the graph needs from the rollout stream (everything else the
        # MFCs produce themselves) — used for loud intake validation
        self._required_keys = {
            k
            for m in self.executor.graph.mfcs
            for k in m.input_keys
            if k not in self.executor.graph.producers
        }
        # staleness-ordered intake; over-stale samples never reach the
        # optimizer (reference discards by version window on arrival)
        self._buffer = SequenceBuffer(
            capacity=buffer_capacity, max_version_lag=max_head_offpolicyness
        )
        self._ckpt_ctl = EpochStepTimeFreqCtl(
            freq_step=control.ckpt_freq_steps, freq_sec=control.ckpt_freq_secs
        )
        # deferred-stats buffer: (step, wall_time, stats-with-device-scalars)
        # triples awaiting the per-logging-interval device_get
        self._pending_stats: List = []
        self._counters_before = metrics_mod.counters.snapshot()
        # guardrail plane: consecutive anomalous steps observed at stats
        # flush time; at control.guard_rollback_steps the engines roll back
        # to the last committed recover checkpoint
        self._consec_anomalies = 0
        self.preempted = False
        self._watchdog = None  # set by run() while its loop is live

    def _bump_watchdog(self):
        if self._watchdog is not None:
            self._watchdog.bump()

    # ------------------------------------------------------------------ #
    # weight sync + counters (the async critical path, §3.5)
    # ------------------------------------------------------------------ #

    def publish_weights(self):
        version = self.actor_engine.version
        path = os.path.join(
            constants.get_param_sync_root(), f"v{version}"
        )
        # join (and surface any failure of) the previous publish first so
        # versions announce in order and a disk-full stops the world loudly
        self._join_publish()

        def announce():
            name_resolve.add(
                names.model_version(
                    self.experiment_name, self.trial_name, "actor"
                ),
                f"{version}:{path}",
                replace=True,
            )
            logger.info("published weights v%d -> %s", version, path)

        # the param gather is collective and runs in the main flow (donated
        # buffers are invalidated by the next train step); the safetensors
        # write + announce land in a background thread so the train loop
        # keeps stepping while the file is written (r5, VERDICT r4 #3 —
        # the serving side symmetrically overlaps its read)
        self._publish_thread = self.actor_engine.save_hf(
            path, self.hf_family, async_write=True, post_write=announce
        )
        return path

    def _join_publish(self):
        t = getattr(self, "_publish_thread", None)
        if t is not None:
            t.join()
            self._publish_thread = None
            if t._areal_exc is not None:
                # surfaced, never swallowed: a failed export means the fleet
                # would keep serving a version the trainer believes it
                # published — stop the world loudly and observably
                metrics_mod.counters.add(metrics_mod.FT_PUBLISH_FAILURES)
                raise RuntimeError(
                    "background weight publish failed"
                ) from t._areal_exc

    def _bump_training_samples(self, n: int):
        # n is this host's count; the staleness gate needs the global one
        self.samples_consumed += int(multihost.allreduce_sum(np.int64(n)))
        if multihost.is_main():
            name_resolve.add(
                names.training_samples(self.experiment_name, self.trial_name),
                str(self.samples_consumed),
                replace=True,
            )

    # ------------------------------------------------------------------ #
    # data intake
    # ------------------------------------------------------------------ #

    def _intake(self, samples: List[SequenceSample]):
        """Validate + buffer arrivals. A trajectory missing a key the graph
        needs is dropped with an ERROR — silently intersecting keys across
        the batch would strip (e.g.) ref logprobs from everyone and zero the
        KL penalty without a trace."""
        version = self.actor_engine.version
        for s in samples:
            missing = self._required_keys - set(s.keys)
            if missing:
                logger.error(
                    "malformed rollout %s: missing required keys %s "
                    "(has %s) — dropped",
                    s.ids, sorted(missing), sorted(s.keys),
                )
                continue
            self._buffer.put(s, current_version=version)

    def _collect_batch(self, timeout: float = 600.0) -> Optional[SequenceSample]:
        """Multi-host note: the train step is collective, so EITHER every
        host proceeds or none does — the have-data decisions are allreduced
        in a fixed sequence every loop iteration, so hosts never diverge into
        mismatched collectives. (Single-host: the allreduces are identities.)
        """
        t0 = time.time()
        while True:
            while len(self._buffer) < self.train_batch_size:
                self._intake(
                    self.stream.get_batch(
                        self.train_batch_size - len(self._buffer), timeout=0.2
                    )
                )
                if time.time() - t0 > timeout:
                    break
            if not multihost.allreduce_min(np.int64(bool(len(self._buffer)))):
                return None  # some host is starved; everyone keeps its buffer
            batch = self._buffer.pop_batch(
                self.train_batch_size, current_version=self.actor_engine.version
            )
            if multihost.allreduce_min(np.int64(bool(batch))):
                # groups consumed this step — the staleness gate's unit
                # (the manager's running/trained counters are per rollout
                # TASK, i.e. per prompt group, not per sequence; bumping
                # with sequence counts made expected_version advance
                # group_size x too fast and over-tightened the gate)
                self._last_batch_groups = len(batch)
                break
            # some host's queue was entirely over-stale: put ours back
            # (re-checked against the window) and refill together
            for s in batch:
                self._buffer.put(s, current_version=self.actor_engine.version)
            if multihost.allreduce_max(np.int64(time.time() - t0 > timeout)):
                return None  # agreed timeout: all hosts give up together
        # consumption histograms only past the commit point — batches
        # re-put above (starved/over-stale sibling) must not double-count
        record_batch_consumption(batch, self.actor_engine.version)
        # only the keys the train MFCs consume — agent extras like
        # packed_prompts/birth_time stay out of the device batch
        # (≈ MFC input_keys, realhf/api/core/dfg.py:56)
        return SequenceSample.gather(batch, keys=self._required_keys)

    # ------------------------------------------------------------------ #
    # one training step = one MFC-graph traversal
    # ------------------------------------------------------------------ #

    def train_step(self, sample: SequenceSample) -> Dict[str, float]:
        """One level-ordered traversal of the declared MFC graph
        (ref_inf/critic_inf/actor_inf → actor_train/critic_train by
        default; see ``experiments/graphs.build_ppo_graph``)."""
        return self.executor.run(sample)

    def run_step(self) -> Optional[Dict[str, float]]:
        sample = self._collect_batch()
        if sample is None:
            return None
        t0 = time.perf_counter()
        # AREAL_DUMP_TRACE=1 dumps ONE profiled step (AREAL_TRACE_STEP) with
        # per-MFC TraceAnnotations from the executor
        # (≈ realhf/system/model_worker.py:79-94 torch-profiler gating)
        if tracing.trace_enabled() and self.step == tracing.trace_step():
            with tracing.maybe_trace(f"ppo_step{self.step}"):
                stats = self.train_step(sample)
        else:
            stats = self.train_step(sample)
        stats["timeperf/e2e"] = time.perf_counter() - t0
        if "flops" in stats:  # per-step throughput line (≈ flops_counter)
            stats["tflops_per_sec"] = (
                stats.pop("flops") / max(stats["timeperf/e2e"], 1e-9) / 1e12
            )
        # data-plane observability: this step's pipeline counter deltas
        # (dispatch-ahead depth, device-idle gap, pack/put/fetch spans)
        stats.update({
            f"pipe/{k}": v
            for k, v in metrics_mod.counters.delta(self._counters_before).items()
        })
        # peaks are lifetime maxima — clear per step so the next step's
        # reported depth reflects ITS forwards, not an earlier step's
        metrics_mod.counters.clear(metrics_mod.PIPE_FWD_MAX_IN_FLIGHT)
        self._counters_before = metrics_mod.counters.snapshot()
        n_tokens = sum(
            sum(inner) for inner in sample.seqlens[sample.main_key()]
        )
        stats["n_tokens"] = n_tokens
        stats["n_seqs_consumed"] = sum(
            len(inner) for inner in sample.seqlens[sample.main_key()]
        )
        stats.update(self._hbm.check())
        self._bump_training_samples(
            int(getattr(self, "_last_batch_groups", 0))
        )
        self.step += 1
        metrics_mod.counters.add(metrics_mod.TRAIN_STEPS)

        if self.step % self.control.weight_sync_freq_steps == 0:
            self.publish_weights()
        if (
            self.control.save_freq_steps
            and self.step % self.control.save_freq_steps == 0
        ):
            save_dir = os.path.join(constants.get_save_root(), f"step{self.step}")
            if self.actor_if is not None:
                self.actor_if.save(self.actor_engine, save_dir)
            else:  # custom graph without an "actor_train" node
                self.actor_engine.save_hf(save_dir, self.hf_family)
            self._bump_watchdog()  # a slow HF export is not a hang
        # process 0's timer decides for everyone: save_recover_checkpoint
        # contains collectives, so a wall-clock boundary straddled across
        # hosts must not split the control flow (machine-checked:
        # arealint's host-divergence-collective flags this branch if the
        # main_decides routing is ever removed)
        if multihost.main_decides(self._ckpt_ctl.check(steps=1)):
            self.save_recover_checkpoint()
            self._bump_watchdog()  # a slow committed save is not a hang
        # Deferred stats: device scalars in `stats` are NOT pulled here —
        # they queue (with this step's wall-clock, for honest jsonl
        # timestamps) and flush as ONE device_get per logging interval, so
        # the train loop never blocks on a per-step host round trip.
        self._pending_stats.append((self.step, time.time(), stats))
        from areal_tpu.train.engine import train_prefetch_enabled

        flush_every = (
            max(self.control.stats_log_freq_steps, 1)
            if train_prefetch_enabled()
            else 1
        )
        if len(self._pending_stats) >= flush_every:
            self.flush_stats()
        return stats

    def flush_stats(self):
        """Pull every pending step's device scalars in ONE transfer and log
        them with their original per-step timestamps. This is also where the
        guardrail plane runs its host-side accounting: ``guard/step_ok``
        rides the same deferred fetch (no extra round trip), so anomaly
        detection lags at most one logging interval behind the device —
        acceptable because the poisoned updates were already skipped
        on-device; the host only decides about ROLLBACK."""
        if not self._pending_stats:
            return
        import jax

        from areal_tpu.train.engine import host_stats_view

        pending, self._pending_stats = self._pending_stats, []
        metrics_mod.counters.add(metrics_mod.PIPE_STATS_FLUSHES, 1)
        with tracing.span("train_pipe/stats_fetch_deferred"):
            fetched = jax.device_get([s for (_, _, s) in pending])
        for (step, wall, _), stats in zip(pending, fetched):
            host = host_stats_view(stats)
            # step_ok is the minibatch-mean of the on-device finite-ness
            # flag: < 1.0 means at least one minibatch's update was skipped
            ok = float(host.get("guard/step_ok", 1.0))
            if ok < 1.0:
                self._consec_anomalies += 1
                metrics_mod.counters.add(metrics_mod.GUARD_ANOMALOUS_STEPS)
                metrics_mod.counters.add(metrics_mod.GUARD_SKIPPED_UPDATES)
                logger.warning(
                    "step %d: non-finite loss/grad_norm (step_ok=%.2f); "
                    "optimizer update was skipped on device "
                    "(%d consecutive anomalous steps)",
                    step, ok, self._consec_anomalies,
                )
            else:
                self._consec_anomalies = 0
            if self.metrics is not None and multihost.is_main():
                self.metrics.log(
                    {k: v for k, v in host.items() if np.isscalar(v)},
                    step, prefix="ppo", wall_time=wall,
                )
        k = self.control.guard_rollback_steps
        if k and self._consec_anomalies >= k:
            self._rollback_to_committed()
        # fleet telemetry rides the same once-per-logging-interval cadence:
        # one name_resolve sweep + merge, folded into the jsonl/tb sinks
        if pending:
            self._maybe_log_fleet(pending[-1][0], pending[-1][1])

    def telemetry_gauges(self) -> Dict[str, float]:
        """Instantaneous trainer gauges for the telemetry plane: intake
        queue depths plus the HBM gauges (kill checks stay in run_step —
        a telemetry read must never kill the worker)."""
        g: Dict[str, float] = {
            "buffer_depth": float(len(self._buffer)),
            "buffer_dropped_stale": float(self._buffer.n_dropped_stale),
            "buffer_dropped_capacity": float(self._buffer.n_dropped_capacity),
            "samples_consumed": float(self.samples_consumed),
        }
        if hasattr(self.stream, "qsize"):
            try:
                g["stream_qsize"] = float(self.stream.qsize())
            except Exception:
                pass
        try:
            g.update({k: float(v) for k, v in self._hbm.check(kill=False).items()})
        except Exception:
            pass
        return g

    def _maybe_log_fleet(self, step: int, wall: float):
        """Pull every worker's published telemetry snapshot, merge by
        metric kind, and fold the ``fleet/`` namespace into the metric
        sinks. The trainer substitutes its LIVE registry for its own
        published snapshot so this interval's consumption histograms land
        in the same record. No-op (zero cost) when the telemetry knob is
        off or this is not the main host."""
        if self.metrics is None or not multihost.is_main():
            return
        if constants.telemetry_export_interval() <= 0:
            return
        from areal_tpu.system import telemetry

        local = telemetry.build_snapshot(
            "trainer", "trainer", step=self.step,
            gauges=self.telemetry_gauges(),
        )
        try:
            scalars = telemetry.collect_fleet_scalars(
                self.experiment_name, self.trial_name, local_snapshot=local
            )
        except Exception:
            logger.warning("fleet telemetry aggregation failed", exc_info=True)
            return
        if scalars:
            self.metrics.log(scalars, step, prefix="fleet", wall_time=wall)

    def _rollback_to_committed(self) -> bool:
        """K consecutive anomalous steps: the live params/opt state are
        suspect even though each poisoned update was skipped (e.g. the
        anomaly source is the data path or an earlier corruption) — restore
        the engines from the last COMMITTED recover checkpoint and republish
        the restored weights so the fleet stops sampling from a trainer
        whose next publish would have been poisoned."""
        root = os.path.join(constants.get_recover_root(), "trainer")
        actor_path = os.path.join(root, "actor")
        critic_path = os.path.join(root, "critic")
        # FULLY validate every engine's checkpoint (manifest presence AND
        # checksums, promoting an unswapped committed sibling) before
        # touching ANY engine: a raise after the actor restore would leave
        # a reverted actor paired with a live critic several versions
        # ahead (silently corrupting the value baseline)
        try:
            self.actor_engine.validate_checkpoint(actor_path)
            if self.critic_engine is not None:
                self.critic_engine.validate_checkpoint(critic_path)
        except (FileNotFoundError, ValueError) as e:
            metrics_mod.counters.add(metrics_mod.GUARD_ROLLBACK_FAILED)
            logger.error(
                "anomaly rollback wanted but not every engine has a "
                "restorable committed recover checkpoint (%s); continuing "
                "with current params", e,
            )
            self._consec_anomalies = 0
            return False
        live_version = self.actor_engine.version
        # both pre-validated above: a raise here is unexpected corruption
        # mid-restore, and stopping the world beats training on a mix of
        # restored and live ticks — so no catch
        self.actor_engine.load_checkpoint(actor_path)
        if self.critic_engine is not None:
            self.critic_engine.load_checkpoint(critic_path)
        restored_version = self.actor_engine.version
        # The restored weights must be REPUBLISHED under a NEW version: the
        # manager's check_new_params ignores version <= its current one, so
        # announcing the restored (older) number would be silently dropped
        # and the fleet would keep serving the suspect weights.
        self.actor_engine.version = max(live_version, restored_version) + 1
        self._consec_anomalies = 0
        metrics_mod.counters.add(metrics_mod.GUARD_ROLLBACKS)
        worker_base.flight_dump(
            "train_guard_rollback",
            {
                "live_version": live_version,
                "restored_version": restored_version,
                "republished_version": self.actor_engine.version,
            },
        )
        logger.warning(
            "rolled back to committed checkpoint (engine step %d, restored "
            "v%d, republishing as v%d) after %d consecutive anomalous steps",
            self.actor_engine._step, restored_version,
            self.actor_engine.version, self.control.guard_rollback_steps,
        )
        # trajectories buffered or in flight were generated by the suspect
        # policy — drop them before the restored params train on them (the
        # same stale-data hazard load_recover_checkpoint handles)
        stale = self._buffer.clear()
        if hasattr(self.stream, "clear"):
            stale += self.stream.clear()
        if stale:
            metrics_mod.counters.add(
                metrics_mod.FT_STALE_DROPPED_ON_RECOVER, stale
            )
            logger.warning(
                "dropped %d suspect buffered/in-flight trajectories on "
                "rollback", stale,
            )
        self.publish_weights()
        return True

    def run(self, shutdown=None, elastic=None, engine_factory=None):
        """Main loop. ``shutdown`` (a :class:`worker_base.GracefulShutdown`)
        makes SIGTERM/SIGINT end the loop through
        :meth:`_handle_preemption`: commit a recover checkpoint, republish
        ``model_version``, set ``self.preempted`` so the caller exits with
        the distinct preemption code.

        ``elastic`` (a :class:`parallel.elastic.WorldEpochManager`) +
        ``engine_factory`` (rebuilds the actor/ref/critic/reward engines)
        turn a world failure — a peer rank dead or wedged, surfaced as a
        bounded-collective timeout or a transport error — into *surgical
        recovery* instead of a crash: reform into the next world epoch,
        rebuild the engines, roll back to the last committed recover
        checkpoint, and keep training (docs/fault_tolerance.md "Elastic
        multihost")."""
        from areal_tpu.system import worker_base

        watchdog = None
        if self.control.watchdog_timeout_secs:
            watchdog = worker_base.HangWatchdog(
                "trainer", timeout_s=self.control.watchdog_timeout_secs
            ).start()
        # run_step bumps this around its own legitimate long stalls
        # (periodic committed save, HF export) so a slow checkpoint is
        # never mistaken for a hang; the remaining un-bumpable stall is
        # the first-step jit compile — size the timeout above it
        self._watchdog = watchdog
        try:
            while self.step < self.control.total_train_steps:
                try:
                    # process 0 decides for everyone: SIGTERM lands on each
                    # host at a slightly different instant, and a host-local
                    # branch into the (collective-bearing) preemption save
                    # while siblings are mid-train-step would deadlock the
                    # pod — the same rule as the ckpt timer below
                    # (multihost.main_decides; machine-checked by arealint
                    # host-divergence-collective). Cost: one extra per-step
                    # allgather on multihost (free single-host), marginal
                    # next to _collect_batch's existing allreduces.
                    if shutdown is not None and multihost.main_decides(
                        shutdown.should_stop()
                    ):
                        # the preemption save is a legitimate long stall:
                        # the watchdog must not dump (or, abort-gated, kill
                        # us) mid-commit of the very checkpoint preemption
                        # exists to produce
                        if watchdog is not None:
                            watchdog.stop()
                        self._handle_preemption(shutdown)
                        break
                    if watchdog is not None:
                        watchdog.bump()
                    if self.run_step() is None:
                        logger.warning(
                            "no data from rollout stream; stopping"
                        )
                        break
                except Exception as e:  # noqa: BLE001 — classified below
                    if elastic is None or engine_factory is None:
                        raise
                    from areal_tpu.parallel import elastic as elastic_mod

                    wf = elastic_mod.as_world_failure(e)
                    if wf is None:
                        raise
                    # a reform (waiting out the supervisor's epoch bump +
                    # relaunch, then an engine rebuild + orbax restore) is
                    # a legitimate long stall far beyond any per-step
                    # watchdog budget: STOP the watchdog — an abort-gated
                    # one would os._exit a healthy survivor mid-recovery,
                    # turning one dead rank into two — and re-arm a fresh
                    # one once the world is whole again
                    if watchdog is not None:
                        watchdog.stop()
                        watchdog = None
                        self._watchdog = None
                    self._elastic_recover(elastic, engine_factory, wf)
                    if self.control.watchdog_timeout_secs:
                        watchdog = worker_base.HangWatchdog(
                            "trainer",
                            timeout_s=self.control.watchdog_timeout_secs,
                        ).start()
                        self._watchdog = watchdog
        finally:
            if watchdog is not None:
                watchdog.stop()
            self._watchdog = None
            # trailing deferred stats must land in the jsonl before exit
            # (``apps/obs`` and post-mortems read it) — best-effort: after a device-side
            # crash the pending device_get raises again, and that secondary
            # failure must not mask the original exception from run_step.
            # Then the final version must land before exit — and a crashed
            # run_step must not leave the daemon writer to be killed
            # mid-file on interpreter teardown.
            try:
                self.flush_stats()
            except Exception:
                logger.exception("deferred stats flush failed at exit")
            finally:
                self._join_publish()
        return self.step

    def _handle_preemption(self, shutdown):
        """Graceful-stop path: inside the deadline, commit a recover
        checkpoint (atomic — dying mid-save leaves the previous one) and
        republish ``model_version`` so the restarted world converges on the
        committed state, not whatever the dying run last announced."""
        self.preempted = True
        # start the deadline clock on hosts whose own signal has not landed
        # yet (process 0 decided for everyone)
        shutdown.request()
        metrics_mod.counters.add(metrics_mod.FT_PREEMPTIONS)
        t0 = time.monotonic()
        logger.warning(
            "preemption: saving recover checkpoint at step %d "
            "(%.0fs deadline)", self.step, shutdown.remaining(),
        )
        try:
            self.flush_stats()  # guard accounting + jsonl before the save
        except Exception:
            logger.exception("stats flush failed during preemption")
        self.save_recover_checkpoint()
        self.publish_weights()
        self._join_publish()
        took = time.monotonic() - t0
        if shutdown.remaining() <= 0:
            logger.error(
                "preemption save took %.1fs and overran the %.0fs deadline "
                "— the checkpoint is committed, but raise %s if the "
                "scheduler hard-killed us first",
                took, shutdown.deadline_s, constants.PREEMPT_DEADLINE_ENV,
            )
        else:
            logger.info(
                "preemption save committed in %.1fs (%.0fs to spare)",
                took, shutdown.remaining(),
            )

    def _elastic_recover(self, elastic, engine_factory, failure):
        """Surgical world recovery: reform into the next epoch, rebuild
        every engine (all device state died with the old epoch's backend),
        roll back to the last committed recover checkpoint so every rank —
        survivors and the relaunched one alike — resumes on an identical
        step, and republish the restored weights under a NEW monotonic
        version (the manager drops non-advancing announces; the gen fleet
        keeps serving the last published weights throughout the reform).
        Raises (-> restart-the-world) past the reform budget."""
        logger.error(
            "world failure at step %d: %s — attempting surgical recovery",
            self.step, failure,
        )
        live_version = self.actor_engine.version
        # pending deferred stats hold device arrays of the dead backend;
        # their steps re-execute after rollback anyway
        dropped_stats = len(self._pending_stats)
        self._pending_stats = []
        self._consec_anomalies = 0
        try:
            # the in-flight background export writes host arrays gathered
            # BEFORE the failure; join it so it cannot interleave with the
            # post-recovery republish (a failed one is superseded anyway)
            self._join_publish()
        except RuntimeError:
            logger.warning(
                "in-flight weight publish failed during the world failure; "
                "superseded by the post-recovery republish"
            )
        elastic.reform(str(failure))
        actor, ref, critic, reward = engine_factory()
        self.actor_engine = actor
        self.ref_engine = ref
        self.critic_engine = critic
        engines = {"actor": actor}
        if ref is not None:
            engines["ref"] = ref
        if critic is not None:
            engines["critic"] = critic
        if reward is not None:
            engines["reward"] = reward
        self.executor = FunctionExecutor(
            self.executor.graph, engines, self.executor.interfaces,
            default_mb_spec=self.mb_spec,
        )
        self.actor_if = self.executor.interfaces.get("actor_train")
        recovered = self.load_recover_checkpoint(publish=False)
        if not recovered:
            # no committed checkpoint anywhere (shared FS: every rank —
            # survivor or relaunched — reads the same absence): the
            # relaunched rank starts at step 0 with fresh engines, so
            # survivors must RESET to the identical fresh start; keeping
            # their pre-failure step would desynchronize every step-keyed
            # collective branch (save cadence, loop bound) and wedge the
            # reformed world
            logger.error(
                "no committed recover checkpoint after reform; world "
                "restarts from step 0 with freshly initialized engines"
            )
            self.step = 0
            self.samples_consumed = 0
        # buffered trajectories predate the rollback — the policy that
        # produced them is ahead of the restored step (same hazard as the
        # guardrail rollback); load_recover_checkpoint cleared the stream
        stale = self._buffer.clear()
        if stale:
            metrics_mod.counters.add(
                metrics_mod.FT_STALE_DROPPED_ON_RECOVER, stale
            )
        # COLLECTIVE version agreement + ONE publish. A survivor-local
        # bump would desynchronize the world: the relaunched rank runs
        # trainer_main's startup (one publish), and survivors running an
        # extra publish would issue a gather with no matching participant
        # — and their engine versions would diverge from the relaunched
        # rank's restored number. The allreduce hands every rank the same
        # base (the survivors' pre-failure live version wins), so the
        # fleet sees one new monotonic version the manager cannot drop.
        self._agree_version_and_publish(floor=live_version)
        self._counters_before = metrics_mod.counters.snapshot()
        logger.warning(
            "surgical recovery complete: epoch %d, resumed at step %d "
            "(v%d, %d pending stats dropped, %d buffered trajectories "
            "dropped)",
            elastic.world.epoch, self.step, self.actor_engine.version,
            dropped_stats, stale,
        )

    def _agree_version_and_publish(self, floor: int = 0):
        """Elastic-world version convergence: every rank of the (re)formed
        world calls this at the same point of its flow — survivors from
        :meth:`_elastic_recover`, the relaunched rank from the launcher's
        elastic startup. One allreduce agrees on the highest version any
        rank has seen (``floor`` carries a survivor's pre-failure live
        version; the relaunched rank contributes its restored number),
        every rank adopts ``agreed + 1``, and ONE publish announces it —
        strictly above anything the fleet saw, so the manager's
        non-advancing check cannot drop it."""
        base = int(
            multihost.allreduce_max(
                np.int64(max(floor, self.actor_engine.version))
            )
        )
        self.actor_engine.version = base + 1
        self.publish_weights()
        self._join_publish()

    # ------------------------------------------------------------------ #
    # recovery (≈ master_worker.__recover_save:585)
    # ------------------------------------------------------------------ #

    def save_recover_checkpoint(self):
        root = os.path.join(constants.get_recover_root(), "trainer")
        self.actor_engine.save_checkpoint(os.path.join(root, "actor"))
        if self.critic_engine is not None:
            self.critic_engine.save_checkpoint(os.path.join(root, "critic"))
        step_info = recover.StepInfo(
            epoch=0, epoch_step=self.step, global_step=self.step
        )
        info = recover.RecoverInfo(
            recover_start=step_info,
            last_step_info=step_info,
            ckpt_ctl_states={"trainer": self._ckpt_ctl.state_dict()},
            samples_consumed=self.samples_consumed,
            model_version=self.actor_engine.version,
        )
        if multihost.is_main():
            recover.dump(info)
        multihost.barrier("recover_ckpt")

    def load_recover_checkpoint(self, publish: bool = True) -> bool:
        """Restart-the-world resume (the load side of
        ``save_recover_checkpoint``): restore engine state + step counters,
        republish ``model_version`` and ``training_samples`` so the manager
        and the gen fleet converge on the RESTORED version (not whatever the
        crashed run last announced), and drop in-flight trajectories — they
        were generated against pre-crash weights/counters.

        ``publish=False`` (elastic callers): skip the version republish —
        the elastic paths publish exactly once through
        :meth:`_agree_version_and_publish` so survivors and a relaunched
        rank issue identical collective sequences."""
        root = os.path.join(constants.get_recover_root(), "trainer")
        info = recover.load()
        if info is None:
            return False
        actor_path = os.path.join(root, "actor")
        critic_path = os.path.join(root, "critic")
        load_critic = self.critic_engine is not None and os.path.exists(
            critic_path
        )
        try:
            # validate EVERY engine's manifest+checksums BEFORE restoring
            # ANY: a raise after the actor restore would pair a restored
            # actor with a fresh/live critic and then publish that mix as
            # if it were a coherent tick. An uncommitted (crashed mid-save)
            # or corrupt dir raises here and the trial starts fresh — which
            # cannot happen when the crash hit DURING a save, because the
            # commit protocol only replaces the previous checkpoint by an
            # atomic rename after the new one is fully on disk.
            self.actor_engine.validate_checkpoint(actor_path)
            if load_critic:
                self.critic_engine.validate_checkpoint(critic_path)
            self.actor_engine.load_checkpoint(actor_path)
            if load_critic:
                self.critic_engine.load_checkpoint(critic_path)
        except (FileNotFoundError, ValueError) as e:
            logger.error(
                "recover checkpoint not restorable (%s); starting fresh", e
            )
            return False
        self.step = info.recover_start.global_step
        self.samples_consumed = info.samples_consumed
        # the ENGINE checkpoint's version is authoritative everywhere the
        # version is republished below (publish_weights reads
        # actor_engine.version); RecoverInfo's copy exists for
        # cross-checking only — a mismatch means the info file and the
        # engine checkpoint are from different ticks, and a stale
        # RecoverInfo value must never win (tested in
        # tests/test_fault_tolerance.py)
        if info.model_version != self.actor_engine.version:
            logger.warning(
                "RecoverInfo model_version %d != engine checkpoint version "
                "%d; republishing the engine's",
                info.model_version, self.actor_engine.version,
            )
        ctl_state = info.ckpt_ctl_states.get("trainer")
        if ctl_state:
            self._ckpt_ctl.load_state_dict(ctl_state)
        # stale in-flight trajectories: anything the pullers buffered was
        # born before the restart — drop it on the floor, loudly
        stale = 0
        if hasattr(self.stream, "clear"):
            stale = self.stream.clear()
        if stale:
            metrics_mod.counters.add(
                metrics_mod.FT_STALE_DROPPED_ON_RECOVER, stale
            )
            logger.warning(
                "dropped %d stale in-flight trajectories on recover", stale
            )
        # converge the fleet on the restored state: training_samples feeds
        # the staleness gate; publish_weights re-exports + re-announces the
        # restored model_version (joined so the announce lands before the
        # first train step)
        if multihost.is_main():
            name_resolve.add(
                names.training_samples(self.experiment_name, self.trial_name),
                str(self.samples_consumed),
                replace=True,
            )
        if publish:
            self.publish_weights()
            self._join_publish()
        logger.info(
            "recovered trainer at step %d (v%d, %d samples consumed)",
            self.step, self.actor_engine.version, self.samples_consumed,
        )
        return True


class SFTTrainerWorker:
    """Sync supervised loop (≈ ``main_sft.py`` path; BASELINE config #1).
    ``interface_name`` selects the training objective — "sft" (next-token)
    or "reward" (Bradley-Terry paired RM, ≈ the reference's rw experiment)."""

    def __init__(
        self,
        experiment_name: str,
        trial_name: str,
        engine: TrainEngine,
        dataset,
        control: TrainerControl,
        batch_size: int = 32,
        mb_spec: Optional[MicroBatchSpec] = None,
        eval_dataset=None,
        hf_family: str = "qwen2",
        metric_logger: Optional[MetricLogger] = None,
        shuffle_seed: int = 1,
        interface_name: str = "sft",
        interface_kwargs: Optional[Dict] = None,
    ):
        tracing.listen_for_compiles()
        self.experiment_name = experiment_name
        self.trial_name = trial_name
        self.engine = engine
        self.dataset = dataset
        self.eval_dataset = eval_dataset
        self.control = control
        self.batch_size = batch_size
        self.mb_spec = mb_spec or MicroBatchSpec(max_tokens_per_mb=16384)
        self.hf_family = hf_family
        self.metrics = metric_logger
        self.interface = make_interface(interface_name, **(interface_kwargs or {}))
        self._log_prefix = interface_name
        self._hbm = hbm.HBMMonitor(tag=interface_name)
        self.step = 0
        self.epoch = 0
        self._shuffle_seed = shuffle_seed

    def _batches(self, dataset, order):
        """Batch-sized gathered chunks of ``dataset`` in the given index
        order — materializing a whole split as ONE sample OOMs at any
        realistic size (each chunk is packed/micro-batched by the engine)."""
        for lo in range(0, len(order), self.batch_size):
            items = [dataset[i] for i in order[lo : lo + self.batch_size]]
            if items:
                yield SequenceSample.gather(items)

    def _epoch_batches(self):
        idx = np.random.RandomState(self._shuffle_seed + self.epoch).permutation(
            len(self.dataset)
        )
        yield from self._batches(self.dataset, list(idx))

    def _eval_batches(self):
        yield from self._batches(self.eval_dataset, range(len(self.eval_dataset)))

    def run(self):
        if len(self.dataset) == 0:
            logger.warning("empty SFT dataset; nothing to train")
            return 0
        from areal_tpu.base import flops as flops_mod

        while self.step < self.control.total_train_steps:
            for batch in self._epoch_batches():
                t0 = time.perf_counter()
                stats = self.interface.train_step(self.engine, batch, self.mb_spec)
                dt = time.perf_counter() - t0
                lens = [
                    int(n)
                    for inner in batch.seqlens[batch.main_key()]
                    for n in inner
                ]
                stats["tflops_per_sec"] = (
                    flops_mod.train_flops(self.engine.cfg, sum(lens), lens)
                    / max(dt, 1e-9) / 1e12
                )
                stats.update(self._hbm.check())
                self.step += 1
                if self.metrics is not None:
                    self.metrics.log(stats, self.step, prefix=self._log_prefix)
                if (
                    self.control.save_freq_steps
                    and self.step % self.control.save_freq_steps == 0
                ):
                    self.engine.save_hf(
                        os.path.join(constants.get_save_root(), f"step{self.step}"),
                        self.hf_family,
                    )
                if self.step >= self.control.total_train_steps:
                    break
            self.epoch += 1
            if self.eval_dataset is not None:
                ev = self.interface.evaluate(self.engine, list(self._eval_batches()))
                logger.info("epoch %d eval: %s", self.epoch, ev)
                if self.metrics is not None:
                    self.metrics.log(ev, self.step, prefix=f"{self._log_prefix}_eval")
        return self.step
