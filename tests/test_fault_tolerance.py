"""Scripted fault-tolerance scenarios (docs/fault_tolerance.md).

Acceptance scenarios for the fleet health & fault-tolerance subsystem:

(a) killing one gen server mid-run loses zero samples — its rollouts
    requeue and the run completes,
(b) a weight update with one dead server still bumps surviving servers to
    the new version and evicts the dead one,
(c) an evicted server is re-admitted after its health probe succeeds and
    serves at the current version,
(d) a trainer restarted from a recover checkpoint resumes with matching
    step counters and republishes ``model_version``.

Gen servers are scriptable HTTP stubs (no model) so scenarios are fast and
deterministic; failures come from ``areal_tpu.base.faults`` injection or
from flipping a stub into dead mode.
"""

import asyncio
import os

import numpy as np
import pytest
from aiohttp import web
from aiohttp.test_utils import TestServer

from areal_tpu.api.agent import Agent, GenerationFailedError
from areal_tpu.api.data import SequenceSample
from areal_tpu.api.model import GenerationHyperparameters
from areal_tpu.base import faults, name_resolve, names
from areal_tpu.base import metrics as metrics_mod
from areal_tpu.gen.client import GenAPIClient, RetryPolicy
from areal_tpu.system.fleet import CLOSED, HALF_OPEN, OPEN, FleetHealth
from areal_tpu.system.gserver_manager import (
    GserverManager,
    GserverManagerConfig,
    serve_manager,
)
from areal_tpu.system.rollout_worker import RolloutWorker
from areal_tpu.base import network

EXP, TRIAL = "ft", "t0"


# --------------------------------------------------------------------- #
# scriptable stub gen server
# --------------------------------------------------------------------- #


class ScriptableGenServer:
    """HTTP stub with the gen-server surface. ``dead=True`` makes every
    endpoint return 500 (a crashed-but-listening process); closing the
    TestServer models a fully dead host (connection refused)."""

    def __init__(self, n_tokens: int = 4):
        self.n_tokens = n_tokens
        self.dead = False
        self.version = 0
        self.generate_calls = []
        self.update_calls = []
        self.app = web.Application()
        self.app.router.add_post("/generate", self._generate)
        self.app.router.add_post(
            "/update_weights_from_disk", self._update
        )
        self.app.router.add_get("/health", self._health)
        self.runner: TestServer = None
        self.url: str = None

    async def start(self):
        self.runner = TestServer(self.app)
        await self.runner.start_server()
        self.url = str(self.runner.make_url("")).rstrip("/")
        return self.url

    async def stop(self):
        await self.runner.close()

    async def _generate(self, request):
        d = await request.json()
        if self.dead:
            return web.json_response({"error": "dead"}, status=500)
        self.generate_calls.append(d)
        n = d["sampling_params"]["max_new_tokens"]
        n = min(n, self.n_tokens)
        return web.json_response(
            {
                "rid": d["rid"],
                "output_ids": list(range(1, n + 1)),
                "output_logprobs": [-0.1] * n,
                "finish_reason": "stop",
                "version": self.version,
            }
        )

    async def _update(self, request):
        d = await request.json()
        if self.dead:
            return web.json_response({"error": "dead"}, status=500)
        self.update_calls.append(d)
        self.version = d.get("version", self.version)
        return web.json_response(
            {"success": True, "message": "ok", "num_paused_requests": 0}
        )

    async def _health(self, request):
        if self.dead:
            return web.json_response({"status": "dead"}, status=500)
        return web.json_response({"status": "ok"})


class EchoAgent(Agent):
    """Minimal agent: one obs/act round trip, builds a trivial sample."""

    def __init__(self, n: int = 2, max_new_tokens: int = 8):
        self.gconfig = GenerationHyperparameters(
            n=n, max_new_tokens=max_new_tokens
        )

    async def collect_trajectory(self, prompt, env, obs_queue, act_queue):
        qid = prompt.ids[0]
        prompt_ids = np.asarray(prompt.data["packed_prompts"]).tolist()
        await obs_queue.put((qid, prompt_ids, self.gconfig))
        act = await act_queue.get()
        if act.error is not None:
            raise GenerationFailedError(act.error)
        seqlens = [len(s) for s in act.seqs]
        return [
            SequenceSample.from_default(
                ids=[qid],
                seqlens=[sum(seqlens)],
                data={
                    "packed_input_ids": np.concatenate(
                        [np.asarray(s, np.int64) for s in act.seqs]
                    )
                },
            )
        ]


class ListPusher:
    def __init__(self):
        self.items = []

    def push(self, data):
        self.items.append(data)
        return True


def _prompt(i: int) -> SequenceSample:
    return SequenceSample.from_default(
        ids=[f"q{i}"],
        seqlens=[4],
        data={"packed_prompts": np.asarray([1, 2, 3, 4], np.int64)},
    )


class ListDataset:
    def __init__(self, n):
        self.items = [_prompt(i) for i in range(n)]

    def __len__(self):
        return len(self.items)

    def __getitem__(self, i):
        return self.items[i]


class NullEnv:
    async def reset(self):
        pass

    async def step(self, action):
        return None, [1.0], None, None


@pytest.fixture(autouse=True)
def _ft_reset():
    faults.reset()
    name_resolve.reset()
    yield
    faults.reset()


def _mcfg(**kw) -> GserverManagerConfig:
    base = dict(
        experiment_name=EXP, trial_name=TRIAL, train_batch_size=4,
        max_head_offpolicyness=100, max_concurrent_rollouts=16,
        health_fail_threshold=3, health_probe_cooldown=0.1,
        health_check_interval=0.05, heartbeat_interval=1000.0,
    )
    base.update(kw)
    return GserverManagerConfig(**base)


# --------------------------------------------------------------------- #
# (a) kill one server mid-run: zero samples lost
# --------------------------------------------------------------------- #


async def test_kill_server_mid_run_loses_zero_samples(tmp_path):
    s0, s1 = ScriptableGenServer(), ScriptableGenServer()
    await s0.start()
    await s1.start()
    manager = GserverManager(_mcfg(), server_urls=[s0.url, s1.url])
    mgr_port = network.find_free_port()
    mgr_runner = await serve_manager(manager, "127.0.0.1", mgr_port)

    n_samples = 8
    pusher = ListPusher()
    worker = RolloutWorker(
        experiment_name=EXP, trial_name=TRIAL, worker_index=0, n_workers=1,
        n_pullers=1, agent=EchoAgent(), env=NullEnv(),
        dataset=ListDataset(n_samples), max_concurrent_tasks=4,
        pusher=pusher, manager_url=f"http://127.0.0.1:{mgr_port}",
    )
    # speed: tiny client backoff via the PRM's session default is fine; the
    # stub answers instantly. Kill s0 once the run is underway.
    run = asyncio.get_event_loop().create_task(worker.run_async())
    try:
        for _ in range(100):
            await asyncio.sleep(0.02)
            if worker.accepted_cnt >= 2:
                break
        assert worker.accepted_cnt >= 2, "run never got underway"
        s0.dead = True  # kill mid-run: in-flight rollouts on s0 now fail

        for _ in range(1500):  # up to ~30s
            await asyncio.sleep(0.02)
            if worker.accepted_cnt >= n_samples:
                break
    finally:
        run.cancel()
        await asyncio.gather(run, return_exceptions=True)

    # zero samples lost: every prompt produced a trajectory despite the kill
    assert worker.accepted_cnt >= n_samples
    assert worker.dropped_cnt == 0
    assert len(pusher.items) >= n_samples
    pushed_qids = {d["ids"][0] for d in pusher.items}
    assert pushed_qids == {f"q{i}" for i in range(n_samples)}
    # the failure was observed and handled through the requeue machinery:
    # either whole-sample requeues or chunk-level re-scheduling (both routes
    # end with the dead server evicted from routing)
    assert manager.fleet.get(s0.url).total_failures > 0
    assert manager.fleet.is_healthy(s1.url)

    await mgr_runner.cleanup()
    await s0.stop()
    await s1.stop()


async def test_push_fault_requeues_without_duplicates():
    """The rollout.push injection point fires pre-delivery, so the requeue
    it triggers retries the sample without duplicating pushed samples."""
    s0 = ScriptableGenServer()
    await s0.start()
    manager = GserverManager(_mcfg(), server_urls=[s0.url])
    mgr_port = network.find_free_port()
    mgr_runner = await serve_manager(manager, "127.0.0.1", mgr_port)
    pusher = ListPusher()
    worker = RolloutWorker(
        experiment_name=EXP, trial_name=TRIAL, worker_index=0, n_workers=1,
        n_pullers=1, agent=EchoAgent(), env=NullEnv(),
        dataset=ListDataset(3), max_concurrent_tasks=2,
        pusher=pusher, manager_url=f"http://127.0.0.1:{mgr_port}",
    )
    # pin to ONE dataset epoch: load_next_data wraps epochs and clears
    # _used_qids, so a fast enough loop can legitimately re-roll q0 before
    # the accepted_cnt>=3 check below fires — that duplicate is epoch-wrap
    # behavior, not the requeue duplication this test is about
    orig_load = worker.load_next_data

    def _load_single_epoch():
        s = orig_load()  # the epoch wrap happens INSIDE load_next_data
        return None if worker._epoch > 0 else s

    worker.load_next_data = _load_single_epoch
    rule = faults.inject("rollout.push", qid="q1", times=1)
    run = asyncio.get_event_loop().create_task(worker.run_async())
    try:
        for _ in range(500):
            await asyncio.sleep(0.02)
            if worker.accepted_cnt >= 3:
                break
    finally:
        run.cancel()
        await asyncio.gather(run, return_exceptions=True)
    assert rule.fired == 1
    assert worker.requeued_cnt == 1 and worker.dropped_cnt == 0
    qids = [d["ids"][0] for d in pusher.items]
    assert sorted(qids) == ["q0", "q1", "q2"]  # q1 exactly once
    await mgr_runner.cleanup()
    await s0.stop()


async def test_push_crash_still_releases_manager_slot():
    """An UNEXPECTED pusher crash (not the scripted fault point) must not
    skip finish_rollout: the manager's capacity slot is released, the
    undelivered sample is requeued, and the retry goes through — the
    lifecycle-rule triage fix for the allocate/finish pairing
    (rollout.manager-slot in tools/arealint/resources.py)."""
    s0 = ScriptableGenServer()
    await s0.start()
    manager = GserverManager(_mcfg(), server_urls=[s0.url])
    mgr_port = network.find_free_port()
    mgr_runner = await serve_manager(manager, "127.0.0.1", mgr_port)

    class CrashOncePusher(ListPusher):
        def __init__(self):
            super().__init__()
            self.crashes = 0

        def push(self, data):
            if self.crashes == 0:
                self.crashes += 1
                raise RuntimeError("zmq push exploded")
            return super().push(data)

    pusher = CrashOncePusher()
    worker = RolloutWorker(
        experiment_name=EXP, trial_name=TRIAL, worker_index=0, n_workers=1,
        n_pullers=1, agent=EchoAgent(), env=NullEnv(),
        dataset=ListDataset(2), max_concurrent_tasks=2,
        pusher=pusher, manager_url=f"http://127.0.0.1:{mgr_port}",
    )
    orig_load = worker.load_next_data

    def _load_single_epoch():
        s = orig_load()
        return None if worker._epoch > 0 else s

    worker.load_next_data = _load_single_epoch
    run = asyncio.get_event_loop().create_task(worker.run_async())
    try:
        for _ in range(500):
            await asyncio.sleep(0.02)
            if worker.accepted_cnt >= 2 and not worker._tasks:
                break
    finally:
        run.cancel()
        await asyncio.gather(run, return_exceptions=True)
    assert pusher.crashes == 1
    # nothing was delivered before the crash, so the sample requeued and
    # retried (no duplicates), and every allocated slot was released
    assert worker.requeued_cnt == 1 and worker.dropped_cnt == 0
    assert worker.accepted_cnt >= 2
    qids = sorted(d["ids"][0] for d in pusher.items)
    assert qids == ["q0", "q1"]
    assert manager.rollout_stat.running == 0, (
        "a push-path crash leaked a manager capacity slot"
    )
    await mgr_runner.cleanup()
    await s0.stop()


async def test_deterministic_push_crash_exhausts_attempts():
    """A sample whose push ALWAYS crashes (e.g. unserializable metadata)
    must exhaust max_rollout_attempts and be dropped — the retry counter
    resets only after a fully delivered round, so a deterministic
    post-collect failure cannot requeue forever."""
    s0 = ScriptableGenServer()
    await s0.start()
    manager = GserverManager(_mcfg(), server_urls=[s0.url])
    mgr_port = network.find_free_port()
    mgr_runner = await serve_manager(manager, "127.0.0.1", mgr_port)

    class AlwaysCrashPusher(ListPusher):
        def push(self, data):
            raise RuntimeError("metadata not serializable")

    worker = RolloutWorker(
        experiment_name=EXP, trial_name=TRIAL, worker_index=0, n_workers=1,
        n_pullers=1, agent=EchoAgent(), env=NullEnv(),
        dataset=ListDataset(1), max_concurrent_tasks=1,
        pusher=AlwaysCrashPusher(),
        manager_url=f"http://127.0.0.1:{mgr_port}",
        max_rollout_attempts=3,
    )
    orig_load = worker.load_next_data

    def _load_single_epoch():
        s = orig_load()
        return None if worker._epoch > 0 else s

    worker.load_next_data = _load_single_epoch
    run = asyncio.get_event_loop().create_task(worker.run_async())
    try:
        for _ in range(500):
            await asyncio.sleep(0.02)
            if worker.dropped_cnt >= 1:
                break
    finally:
        run.cancel()
        await asyncio.gather(run, return_exceptions=True)
    assert worker.dropped_cnt == 1
    assert worker.requeued_cnt == 2  # attempts 1..2 requeued, 3rd dropped
    assert manager.rollout_stat.running == 0
    await mgr_runner.cleanup()
    await s0.stop()


# --------------------------------------------------------------------- #
# (b) weight update with one dead server: survivors bump, corpse evicted
# --------------------------------------------------------------------- #


async def test_weight_update_partial_failure_bumps_survivors(tmp_path):
    s0, s1, s2 = (ScriptableGenServer() for _ in range(3))
    for s in (s0, s1, s2):
        await s.start()
    manager = GserverManager(
        _mcfg(), server_urls=[s0.url, s1.url, s2.url]
    )
    await s1.stop()  # s1 is a dead host: connection refused

    ckpt = tmp_path / "v1"
    ckpt.mkdir()
    name_resolve.add(
        names.model_version(EXP, TRIAL, "actor"), f"1:{ckpt}", replace=True
    )
    path = await manager.check_new_params()
    assert path == str(ckpt)
    # version advanced despite the dead server
    assert manager.version == 1
    for s in (s0, s2):
        assert len(s.update_calls) == 1
        assert s.update_calls[0]["version"] == 1
    # the dead server was evicted and is out of routing + future fan-outs
    assert manager.fleet.get(s1.url).state == OPEN
    assert set(manager.fleet.healthy_urls()) == {s0.url, s2.url}
    assert manager.fleet.get(s0.url).acked_version == 1

    # no hot-loop: the next poll tick is a no-op (version already current)
    assert await manager.check_new_params() is None
    assert len(s0.update_calls) == 1

    await s0.stop()
    await s2.stop()


# --------------------------------------------------------------------- #
# (c) evicted server re-admitted after successful probe, at current version
# --------------------------------------------------------------------- #


async def test_evicted_server_readmitted_after_probe(tmp_path):
    s0, s1 = ScriptableGenServer(), ScriptableGenServer()
    await s0.start()
    await s1.start()
    manager = GserverManager(_mcfg(), server_urls=[s0.url, s1.url])

    # publish v1; s1 plays dead for the update → evicted
    s1.dead = True
    ckpt = tmp_path / "v1"
    ckpt.mkdir()
    name_resolve.add(
        names.model_version(EXP, TRIAL, "actor"), f"1:{ckpt}", replace=True
    )
    await manager.check_new_params()
    assert manager.fleet.get(s1.url).state == OPEN
    assert manager.fleet.healthy_urls() == [s0.url]
    assert s1.version == 0  # still stale

    # probe while still dead: breaker stays open, no re-admission
    await asyncio.sleep(0.15)  # past probe_cooldown
    await manager.run_health_checks(wait_probes=True)
    assert manager.fleet.get(s1.url).state == OPEN
    assert metrics_mod.counters.get("ft/probe_failures") >= 1

    # server comes back: probe + catch-up load → re-admitted at current v
    s1.dead = False
    await asyncio.sleep(0.15)
    await manager.run_health_checks(wait_probes=True)
    h = manager.fleet.get(s1.url)
    assert h.state == CLOSED
    assert h.acked_version == 1
    assert s1.version == 1  # catch-up update really reached the server
    assert set(manager.fleet.healthy_urls()) == {s0.url, s1.url}
    assert metrics_mod.counters.get("ft/readmissions") >= 1

    await s0.stop()
    await s1.stop()


# --------------------------------------------------------------------- #
# (d) trainer restart from recover checkpoint
# --------------------------------------------------------------------- #


def _tiny_trainer(eng=None):
    """A real (tiny) AsyncPPOTrainerWorker — engine checkpoints must round-
    trip through the actual save/load path."""
    from areal_tpu.api.model import PPOHyperparameters
    from areal_tpu.models.config import ModelConfig
    from areal_tpu.parallel.mesh import ParallelConfig
    from areal_tpu.system.trainer_worker import (
        AsyncPPOTrainerWorker,
        TrainerControl,
    )
    from areal_tpu.train.engine import OptimizerConfig, TrainEngine

    if eng is None:
        cfg = ModelConfig(
            n_layers=1, n_q_heads=2, n_kv_heads=1, head_dim=8, hidden_dim=16,
            intermediate_dim=32, vocab_size=64, dtype="float32",
            use_attention_bias=True,  # qwen2-exportable (publish_weights)
        )
        eng = TrainEngine(
            cfg, ParallelConfig(data=1, fsdp=1, model=1),
            OptimizerConfig(lr=1e-4),
        )
        eng.init_random(0)
        eng.setup_optimizer(10)

    class _EmptyStream:
        def get_batch(self, n, timeout=0.1):
            return []

        def clear(self):
            self.cleared = True
            return 3  # pretend 3 stale trajectories were buffered

    stream = _EmptyStream()
    worker = AsyncPPOTrainerWorker(
        experiment_name=EXP, trial_name=TRIAL, actor_engine=eng,
        stream=stream,
        hp=PPOHyperparameters(disable_value=True, kl_ctl=0.0),
        control=TrainerControl(total_train_steps=10),
        train_batch_size=2, hf_family="qwen2",
    )
    return worker, eng, stream


def test_trainer_recover_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("AREAL_FILEROOT", str(tmp_path))
    import jax

    from areal_tpu.base import constants

    constants.set_experiment_trial_names(EXP, TRIAL)
    name_resolve.reset()
    w1, eng1, _ = _tiny_trainer()
    # simulate a run that did 7 steps and consumed 28 samples
    w1.step = 7
    w1.samples_consumed = 28
    eng1.version = 7
    w1.save_recover_checkpoint()
    saved = np.asarray(jax.tree.leaves(eng1.params)[0]).copy()

    # restart-the-world: a fresh worker. The engine object is reused with
    # scrambled state (fresh seed, zeroed counters) — constructing a second
    # TrainEngine only re-pays jit compile, it would not strengthen the
    # restore proof (the checkpoint round-trips through disk either way).
    eng1.init_random(1)
    eng1.version = 0
    w2, eng2, stream2 = _tiny_trainer(eng=eng1)
    assert w2.step == 0 and eng2.version == 0
    assert not np.allclose(
        saved, np.asarray(jax.tree.leaves(eng2.params)[0])
    )
    assert w2.load_recover_checkpoint()

    # (d) matching step counters
    assert w2.step == 7
    assert w2.samples_consumed == 28
    assert eng2.version == 7
    # params actually restored (not merely counters)
    np.testing.assert_allclose(
        saved, np.asarray(jax.tree.leaves(eng2.params)[0])
    )

    # stale in-flight trajectories were dropped
    assert getattr(stream2, "cleared", False)

    # model_version republished so the fleet converges on the restored run
    raw = name_resolve.get(names.model_version(EXP, TRIAL, "actor"))
    version, _, path = raw.partition(":")
    assert int(version) == 7
    assert os.path.isdir(path)
    # training_samples republished for the staleness gate
    assert int(name_resolve.get(names.training_samples(EXP, TRIAL))) == 28


# --------------------------------------------------------------------- #
# (e) trainer survivability: atomic checkpoint commit protocol
# --------------------------------------------------------------------- #


def test_commit_protocol_resolves_newest_committed(tmp_path):
    """Every crash window of commit_checkpoint is recoverable: an
    uncommitted staging dir is discarded; a committed staging dir (crash
    between manifest fsync and rename) is promoted over an older
    committed canonical dir."""
    from areal_tpu.base import recover

    path = str(tmp_path / "ckpt")
    # canonical: committed at step 3
    os.makedirs(path)
    recover.write_manifest(path, {"step": 3, "version": 3})
    # crashed newer save: committed staging (manifest landed, rename didn't)
    newer = recover.staging_path(path, "s5")
    os.makedirs(newer)
    recover.write_manifest(newer, {"step": 5, "version": 5})
    # and an uncommitted staging leftover (no manifest)
    os.makedirs(recover.staging_path(path, "s6"))

    assert recover.resolve_committed(path) == path
    m = recover.read_manifest(path)
    assert (m["step"], m["version"]) == (5, 5)  # the newer one won
    # strays cleaned
    assert not os.path.exists(newer)
    assert not os.path.exists(recover.staging_path(path, "s6"))

    # nothing committed at all -> None
    bare = str(tmp_path / "bare")
    os.makedirs(recover.staging_path(bare, "s1"))
    assert recover.resolve_committed(bare) is None


def test_ckpt_crash_mid_save_preserves_previous_committed(
    tmp_path, monkeypatch
):
    """Acceptance: a crash injected via the ``ckpt.save`` fault point
    mid-save leaves the previous committed checkpoint loadable, and the
    restarted trainer resumes from it."""
    monkeypatch.setenv("AREAL_FILEROOT", str(tmp_path))
    import jax

    from areal_tpu.base import constants, recover

    constants.set_experiment_trial_names(EXP, TRIAL)
    name_resolve.reset()
    w1, eng1, _ = _tiny_trainer()
    w1.step = 5
    w1.samples_consumed = 20
    eng1.version = 5
    w1.save_recover_checkpoint()  # commit #1
    committed = np.asarray(jax.tree.leaves(eng1.params)[0]).copy()
    actor_dir = os.path.join(
        constants.get_recover_root(), "trainer", "actor"
    )
    assert recover.is_committed(actor_dir)

    # the run advances, then dies mid-save of the NEXT checkpoint
    eng1.init_random(3)
    eng1._step += 7
    w1.step = 12
    eng1.version = 12
    faults.inject("ckpt.save", times=1)
    with pytest.raises(faults.FaultInjected):
        w1.save_recover_checkpoint()
    faults.reset()
    # the staged-but-uncommitted dir must not shadow the committed one
    assert recover.is_committed(actor_dir)
    assert recover.read_manifest(actor_dir)["version"] == 5

    # restart-the-world: scrambled engine, fresh worker
    eng1.init_random(9)
    eng1.version = 0
    w2, eng2, _ = _tiny_trainer(eng=eng1)
    assert w2.load_recover_checkpoint()
    assert w2.step == 5 and eng2.version == 5
    np.testing.assert_array_equal(
        committed, np.asarray(jax.tree.leaves(eng2.params)[0])
    )
    # and the fleet converges on the COMMITTED version
    raw = name_resolve.get(names.model_version(EXP, TRIAL, "actor"))
    assert int(raw.partition(":")[0]) == 5


def test_uncommitted_recover_checkpoint_falls_back_to_fresh_start(
    tmp_path, monkeypatch
):
    """A recover dir that only ever got an UNCOMMITTED save (crash on the
    very first checkpoint) is skipped: load_recover_checkpoint returns
    False instead of restoring garbage."""
    monkeypatch.setenv("AREAL_FILEROOT", str(tmp_path))
    from areal_tpu.base import constants

    constants.set_experiment_trial_names(EXP, TRIAL)
    name_resolve.reset()
    w1, eng1, _ = _tiny_trainer()
    w1.step = 2
    faults.inject("ckpt.save", times=1)
    with pytest.raises(faults.FaultInjected):
        w1.save_recover_checkpoint()
    faults.reset()
    # RecoverInfo may exist from other tests' layout — write one explicitly
    # to prove the engine checkpoint validation is what gates the recover
    from areal_tpu.base import recover as recover_mod

    recover_mod.dump(recover_mod.RecoverInfo(samples_consumed=8))
    import jax

    before = np.asarray(jax.tree.leaves(eng1.params)[0]).copy()
    w2, _, _ = _tiny_trainer(eng=eng1)
    assert not w2.load_recover_checkpoint()
    # validation runs BEFORE any restore: a failed recover must leave the
    # engine exactly as it was (no partially-restored mixed state)
    np.testing.assert_array_equal(
        before, np.asarray(jax.tree.leaves(eng1.params)[0])
    )
    assert w2.step == 0 and w2.samples_consumed == 0


# --------------------------------------------------------------------- #
# (f) guardrail plane: K consecutive anomalies -> rollback to committed
# --------------------------------------------------------------------- #


def test_consecutive_anomalies_roll_back_to_committed(tmp_path, monkeypatch):
    monkeypatch.setenv("AREAL_FILEROOT", str(tmp_path))
    import jax
    import time as time_mod

    from areal_tpu.base import constants

    constants.set_experiment_trial_names(EXP, TRIAL)
    name_resolve.reset()
    w, eng, _ = _tiny_trainer()
    w.step = 4
    eng.version = 4
    w.save_recover_checkpoint()  # the rollback target
    committed = np.asarray(jax.tree.leaves(eng.params)[0]).copy()

    # params drift (simulating steps whose updates slipped through or an
    # optimizer-state corruption the skip-guard cannot undo)
    eng.init_random(7)
    eng.version = 6

    k = w.control.guard_rollback_steps
    assert k >= 2
    before_rb = metrics_mod.counters.get(metrics_mod.GUARD_ROLLBACKS)
    # k-1 anomalies: counted, but NO rollback yet
    w._pending_stats = [
        (i, time_mod.time(), {"guard/step_ok": 0.0}) for i in range(k - 1)
    ]
    w.flush_stats()
    assert w._consec_anomalies == k - 1
    assert metrics_mod.counters.get(metrics_mod.GUARD_ROLLBACKS) == before_rb
    # a clean step in between resets the streak
    w._pending_stats = [(k, time_mod.time(), {"guard/step_ok": 1.0})]
    w.flush_stats()
    assert w._consec_anomalies == 0
    # k consecutive anomalies: rollback fires
    w._pending_stats = [
        (k + 1 + i, time_mod.time(), {"guard/step_ok": 0.0})
        for i in range(k)
    ]
    w.flush_stats()
    w._join_publish()
    assert (
        metrics_mod.counters.get(metrics_mod.GUARD_ROLLBACKS) == before_rb + 1
    )
    assert w._consec_anomalies == 0
    np.testing.assert_array_equal(
        committed, np.asarray(jax.tree.leaves(eng.params)[0])
    )
    # the restored weights republish under a NEW (monotonic) version: the
    # manager ignores version <= its current one, so re-announcing the
    # restored number (4) while the fleet sits at 6 would be silently
    # dropped and the fleet would keep serving the suspect weights
    assert eng.version == 7
    raw = name_resolve.get(names.model_version(EXP, TRIAL, "actor"))
    assert int(raw.partition(":")[0]) == 7
    assert metrics_mod.counters.get(metrics_mod.GUARD_ANOMALOUS_STEPS) >= k
    # trajectories buffered against the suspect policy were dropped
    # (_EmptyStream.clear pretends 3 were in flight)
    assert (
        metrics_mod.counters.get(metrics_mod.FT_STALE_DROPPED_ON_RECOVER) >= 3
    )


# --------------------------------------------------------------------- #
# (g) preemption plane: signal.term -> committed ckpt + distinct exit code
# --------------------------------------------------------------------- #


def test_preemption_commits_checkpoint_and_sets_distinct_code(
    tmp_path, monkeypatch
):
    monkeypatch.setenv("AREAL_FILEROOT", str(tmp_path))
    from areal_tpu.base import constants, recover
    from areal_tpu.system import worker_base

    constants.set_experiment_trial_names(EXP, TRIAL)
    name_resolve.reset()
    w, eng, _ = _tiny_trainer()
    w.step = 3
    eng.version = 3
    before = metrics_mod.counters.get(metrics_mod.FT_PREEMPTIONS)

    shutdown = worker_base.GracefulShutdown(deadline_s=30.0, install=False)
    faults.inject("signal.term", action="trip", times=1)
    w.run(shutdown=shutdown)

    assert w.preempted
    assert metrics_mod.counters.get(metrics_mod.FT_PREEMPTIONS) == before + 1
    # the recover checkpoint is COMMITTED (manifest present, right tick)
    actor_dir = os.path.join(
        constants.get_recover_root(), "trainer", "actor"
    )
    m = recover.read_manifest(actor_dir)
    assert m is not None and m["version"] == 3
    # model_version republished before exit
    raw = name_resolve.get(names.model_version(EXP, TRIAL, "actor"))
    assert int(raw.partition(":")[0]) == 3
    # the exit code the launcher maps to restart-the-world is distinct
    assert worker_base.EXIT_PREEMPTED not in (0, 1)
    assert worker_base.EXIT_PREEMPTED != worker_base.EXIT_WATCHDOG


def test_graceful_shutdown_handles_real_sigterm():
    import signal

    from areal_tpu.system import worker_base

    shutdown = worker_base.GracefulShutdown(deadline_s=5.0)
    try:
        assert not shutdown.should_stop()
        os.kill(os.getpid(), signal.SIGTERM)
        assert shutdown.should_stop()
        assert shutdown.remaining() <= 5.0
    finally:
        shutdown.uninstall()


# --------------------------------------------------------------------- #
# (h) satellites: stale RecoverInfo version, publish-failure surfacing
# --------------------------------------------------------------------- #


def test_stale_recover_info_version_cannot_win(tmp_path, monkeypatch):
    """The ENGINE checkpoint's version is authoritative: a tampered/stale
    RecoverInfo.model_version must not be what gets republished."""
    monkeypatch.setenv("AREAL_FILEROOT", str(tmp_path))
    from areal_tpu.base import constants
    from areal_tpu.base import recover as recover_mod

    constants.set_experiment_trial_names(EXP, TRIAL)
    name_resolve.reset()
    w1, eng1, _ = _tiny_trainer()
    w1.step = 6
    eng1.version = 6
    w1.save_recover_checkpoint()
    # tamper: RecoverInfo claims an older model_version (e.g. an info file
    # surviving from an earlier tick than the engine checkpoint)
    info = recover_mod.load()
    info.model_version = 2
    recover_mod.dump(info)

    eng1.version = 0
    w2, eng2, _ = _tiny_trainer(eng=eng1)
    assert w2.load_recover_checkpoint()
    assert eng2.version == 6  # engine checkpoint won
    raw = name_resolve.get(names.model_version(EXP, TRIAL, "actor"))
    assert int(raw.partition(":")[0]) == 6  # ...everywhere it republishes


def test_publish_failure_surfaces_on_join_and_counts(tmp_path, monkeypatch):
    monkeypatch.setenv("AREAL_FILEROOT", str(tmp_path))
    from areal_tpu.base import constants
    from areal_tpu.models import hf as hf_conv

    constants.set_experiment_trial_names(EXP, TRIAL)
    name_resolve.reset()
    w, _, _ = _tiny_trainer()

    def boom(*a, **k):
        raise OSError("disk full")

    monkeypatch.setattr(hf_conv, "save_hf_checkpoint", boom)
    before = metrics_mod.counters.get(metrics_mod.FT_PUBLISH_FAILURES)
    w.publish_weights()
    with pytest.raises(RuntimeError, match="publish failed"):
        w._join_publish()
    assert (
        metrics_mod.counters.get(metrics_mod.FT_PUBLISH_FAILURES)
        == before + 1
    )


# --------------------------------------------------------------------- #
# retry plane units: client backoff + fault harness semantics
# --------------------------------------------------------------------- #


async def test_client_retries_through_transient_fault():
    s = ScriptableGenServer()
    await s.start()
    # first 2 attempts of this generate fail at the injection point, the
    # 3rd succeeds — the caller never sees the fault
    rule = faults.inject("gen.http", url=s.url, op="generate", times=2)
    before = metrics_mod.counters.get("ft/client_retries")
    async with GenAPIClient(
        timeout=5.0,
        retry=RetryPolicy(max_attempts=3, backoff_base_s=0.01),
    ) as c:
        res = await c.generate(
            s.url, rid="r1", input_ids=[1, 2], sampling_params={
                "max_new_tokens": 4,
            },
        )
    assert res.output_ids == [1, 2, 3, 4]
    assert rule.fired == 2
    assert metrics_mod.counters.get("ft/client_retries") - before == 2
    await s.stop()


async def test_client_retry_exhaustion_raises():
    s = ScriptableGenServer()
    await s.start()
    faults.inject("gen.http", url=s.url, op="generate")  # forever
    async with GenAPIClient(
        timeout=5.0,
        retry=RetryPolicy(max_attempts=3, backoff_base_s=0.01),
    ) as c:
        with pytest.raises(ConnectionError):
            await c.generate(
                s.url, rid="r1", input_ids=[1], sampling_params={
                    "max_new_tokens": 1,
                },
            )
    await s.stop()


def test_faults_zero_overhead_when_unconfigured():
    assert not faults.active()
    # no rules: maybe_fail is a no-op (and must not allocate/raise)
    faults.maybe_fail("gen.http", url="http://x", op="generate")
    rule = faults.inject("gen.http", url="http://x", after=1, times=1)
    faults.maybe_fail("gen.http", url="http://other")  # filtered: no match
    assert rule.seen == 0
    faults.maybe_fail("gen.http", url="http://x")  # skipped by `after`
    with pytest.raises(faults.FaultInjected):
        faults.maybe_fail("gen.http", url="http://x")
    faults.maybe_fail("gen.http", url="http://x")  # `times` exhausted
    assert (rule.seen, rule.fired) == (3, 1)
    faults.reset()
    assert not faults.active()


# --------------------------------------------------------------------- #
# breaker unit semantics
# --------------------------------------------------------------------- #


def test_breaker_state_machine():
    t = [0.0]
    fleet = FleetHealth(
        ["http://a", "http://b"], fail_threshold=2, probe_cooldown_s=5.0,
        clock=lambda: t[0],
    )
    assert fleet.healthy_urls() == ["http://a", "http://b"]
    assert not fleet.observe_failure("http://a")   # 1 of 2
    fleet.observe_success("http://a")              # success resets the count
    assert not fleet.observe_failure("http://a")
    assert fleet.observe_failure("http://a")       # 2 consecutive → evicted
    assert fleet.get("http://a").state == OPEN
    assert fleet.healthy_urls() == ["http://b"]
    # cooldown gates probing
    assert fleet.probe_candidates() == []
    t[0] = 6.0
    assert fleet.probe_candidates() == ["http://a"]
    fleet.begin_probe("http://a")
    assert fleet.get("http://a").state == HALF_OPEN
    fleet.probe_failed("http://a")
    assert fleet.get("http://a").state == OPEN
    t[0] = 20.0
    fleet.begin_probe("http://a")
    fleet.readmit("http://a", acked_version=3)
    assert fleet.get("http://a").state == CLOSED
    assert fleet.get("http://a").acked_version == 3
    assert fleet.min_acked_version() == -1  # "b" never acked anything
    fleet.ack_version("http://b", 5)
    assert fleet.min_acked_version() == 3


# --------------------------------------------------------------------- #
# satellites: pusher send-timeout, drain cancellation
# --------------------------------------------------------------------- #


def test_pusher_drops_instead_of_hanging():
    """SNDHWM hit + dead puller: push must time out and count the drop, not
    block the rollout worker forever."""
    from areal_tpu.base import network
    from areal_tpu.system.push_pull_stream import ZMQJsonPusher

    port = network.find_free_port()  # nobody ever binds: no puller at all
    pusher = ZMQJsonPusher("127.0.0.1", port, hwm=1, send_timeout_ms=100)
    before = metrics_mod.counters.get("ft/push_drops")
    # every push RETURNS (it never blocks: a push that waited for the dead
    # puller would not get here, and this test's own seconds are no
    # bound); zmq buffers ~hwm messages, the rest are dropped and counted
    results = [pusher.push({"i": i}) for i in range(3)]
    assert not all(results)
    assert pusher.drop_cnt == results.count(False) >= 1
    assert metrics_mod.counters.get("ft/push_drops") - before == pusher.drop_cnt
    pusher.close()


async def test_drain_cancels_timed_out_tasks():
    s0 = ScriptableGenServer()
    await s0.start()
    manager = GserverManager(_mcfg(), server_urls=[s0.url])
    mgr_port = network.find_free_port()
    mgr_runner = await serve_manager(manager, "127.0.0.1", mgr_port)

    class StuckAgent(Agent):
        async def collect_trajectory(self, prompt, env, obs_queue, act_queue):
            await asyncio.sleep(3600)  # never finishes

    worker = RolloutWorker(
        experiment_name=EXP, trial_name=TRIAL, worker_index=0, n_workers=1,
        n_pullers=1, agent=StuckAgent(), env=NullEnv(),
        dataset=ListDataset(2), max_concurrent_tasks=2,
        pusher=ListPusher(), manager_url=f"http://127.0.0.1:{mgr_port}",
    )
    run = asyncio.get_event_loop().create_task(worker.run_async())
    for _ in range(200):
        await asyncio.sleep(0.01)
        if len(worker._tasks) == 2:
            break
    assert len(worker._tasks) == 2
    run.cancel()
    await asyncio.gather(run, return_exceptions=True)

    before = metrics_mod.counters.get("ft/drain_abandoned")
    await worker.drain(timeout=0.1)
    # timed-out tasks were cancelled and awaited, not left running
    assert all(t.done() for t in worker._tasks.values()) or not worker._tasks
    assert metrics_mod.counters.get("ft/drain_abandoned") - before == 2
    await mgr_runner.cleanup()
    await s0.stop()
