"""Local multiprocess launcher + worker entry functions.

Counterpart of the reference's local scheduler + Ray launcher + recover loop
(``realhf/scheduler/local/client.py``, ``training/utils.py:119``,
``apps/main.py:226-288``): each worker role runs as a spawned subprocess;
the launcher watches them and, on a failure with ``recover_mode=auto``,
kills the world and restarts it up to ``recover_retries`` times
(restart-the-world elasticity, like the reference).

Worker processes rendezvous through the file-backed name_resolve under the
experiment fileroot — the same mechanism the reference uses on NFS.
"""

import contextlib
import dataclasses
import glob
import json
import logging
import multiprocessing as mp
import os
import signal
import sys
import time
from typing import Dict, List, Optional

logger = logging.getLogger("areal_tpu.launcher")


def _setup_worker_env(cfg, device: str = ""):
    """Common per-process setup: fileroot, name_resolve, devices, compile
    cache, seeding. Never initialises a JAX backend: run_async_ppo calls
    it in the parent, which must leave the chips to its children."""
    import os

    from areal_tpu.base import compile_cache

    compile_cache.configure()
    if cfg.fileroot:
        os.environ["AREAL_FILEROOT"] = cfg.fileroot
    os.environ.setdefault(
        "AREAL_NAME_RESOLVE_ROOT",
        os.path.join(cfg.fileroot or "/tmp/areal_tpu", "name_resolve"),
    )
    if device == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")
    from areal_tpu.base import constants, name_resolve, seeding

    # cross-process rendezvous: the TCP server when one is advertised
    # (multi-node, no shared FS — AREAL_NAME_RESOLVE_RPC=host:port), else
    # the shared-filesystem backend (the in-memory default only works
    # within one process)
    rpc_addr = constants.name_resolve_rpc()
    if rpc_addr:
        name_resolve.reconfigure(
            name_resolve.NameResolveConfig(type="rpc", root=rpc_addr)
        )
    else:
        name_resolve.reconfigure(
            name_resolve.NameResolveConfig(
                type="file", root=constants.name_resolve_root()
            )
        )

    constants.set_experiment_trial_names(cfg.experiment_name, cfg.trial_name)
    if cfg.fileroot:
        constants.set_fileroot(cfg.fileroot)
    seeding.set_random_seed(cfg.seed, "worker")


def _announce_devices(role: str):
    """One stdout line per chip-owning process: which devices it opened.
    The first backend touch of the process happens here, after the
    launcher handed it its chips through the environment."""
    import jax

    from areal_tpu.base import constants

    devs = jax.local_devices()
    print(json.dumps({"areal_devices": {
        "role": role,
        "pid": os.getpid(),
        "platform": devs[0].platform,
        "ids": [d.id for d in devs],
        "coords": [list(getattr(d, "coords", ())) for d in devs],
        "visible_chips": constants.tpu_visible_devices(),
    }}), flush=True)


def _load_engine(spec, is_critic=False, with_optimizer=True, total_steps=100):
    from areal_tpu.train.engine import TrainEngine

    cfg = spec.model_config(is_critic=is_critic)
    eng = TrainEngine(
        cfg,
        spec.parallel_config(),
        spec.optimizer if with_optimizer else None,
        param_dtype=getattr(spec, "param_dtype", "float32"),
    )
    if spec.path:
        eng.load_hf(spec.path, init_critic_head=is_critic)
    else:
        eng.init_random(0)
    if with_optimizer:
        eng.setup_optimizer(total_steps)
    return eng


# --------------------------------------------------------------------------- #
# worker mains (multiprocessing spawn targets)
# --------------------------------------------------------------------------- #


def gen_server_main(cfg, server_idx: int):
    import asyncio

    _setup_worker_env(cfg, cfg.gen.device)
    _announce_devices(f"gen_server/{server_idx}")
    import jax

    from areal_tpu.base import constants, name_resolve, names, network
    from areal_tpu.gen.engine import GenerationEngine
    from areal_tpu.gen.server import serve
    from areal_tpu.models import hf as hf_conv

    mcfg = cfg.actor.model_config()
    mesh = None
    tp = getattr(cfg.gen, "tp_size", 1)
    if tp > 1:
        import numpy as np
        from jax.sharding import Mesh

        # the launcher handed this process its own chips through the
        # environment (plan_chips): local devices start from 0 in that set
        devs = jax.local_devices()
        if tp > len(devs):
            raise ValueError(
                f"gen server {server_idx} needs {tp} devices but this "
                f"process sees {len(devs)}; lower gen.tp_size"
            )
        mesh = Mesh(np.array(devs[:tp]), ("model",))
    if cfg.actor.path:
        _, host_params = hf_conv.load_hf_checkpoint(cfg.actor.path)
    else:
        from areal_tpu.models import transformer as tfm

        host_params = tfm.init_params(mcfg, jax.random.key(0))
    engine = GenerationEngine(
        mcfg,
        host_params,  # cast + TP-shard happen inside (prepare_params)
        max_slots=cfg.gen.max_slots,
        max_seqlen=cfg.gen.max_seqlen,
        max_new_tokens_cap=cfg.gen.max_new_tokens_cap,
        stop_token_ids=cfg.gen.stop_token_ids,
        seed=cfg.seed + server_idx,
        page_size=cfg.gen.page_size,
        n_pages=cfg.gen.n_pages,
        kv_dtype=cfg.gen.kv_dtype,
        mesh=mesh,
    )

    async def main():
        from areal_tpu.system.worker_base import (
            ExperimentStatusWatch,
            Heartbeat,
            TelemetryExporter,
        )

        port = network.find_free_port()
        host = "127.0.0.1"
        from areal_tpu.base import constants as _constants

        runner = await serve(
            engine, host, port, decode_steps=cfg.gen.decode_steps_per_chunk,
            metrics_dump_path=os.path.join(
                _constants.get_log_root(), f"gen_server_{server_idx}.json"
            ),
        )
        name_resolve.add(
            names.gen_server(cfg.experiment_name, cfg.trial_name, server_idx),
            f"http://{host}:{port}",
            replace=True,
        )
        # orphan protection: exit when the experiment dies
        # (≈ reference generation_server.py:209-222)
        watch = ExperimentStatusWatch(cfg.experiment_name, cfg.trial_name)
        hb = Heartbeat(
            cfg.experiment_name, cfg.trial_name, f"gen_server/{server_idx}"
        ).start()
        tele = TelemetryExporter(
            cfg.experiment_name, cfg.trial_name,
            f"gen_server/{server_idx}", "gen_server",
            step_fn=lambda: max(engine.version, 0),
            gauges_fn=lambda: {
                "gen_running": float(engine.n_running()),
                "gen_pending": float(engine.n_pending()),
                # HBM-headroom gauges (docs/observability.md): the fleet
                # aggregator sums these per server; kv_dtype itself is a
                # string and lives on /metrics_json instead
                "kv_pool_bytes": float(engine.kv_pool_bytes()),
                "kv_pool_occupancy": engine.kv_pool_occupancy(),
                # admission/autoscale signal: excludes evictable
                # prefix-cache-only pages
                "kv_pool_demand_occupancy": (
                    engine.kv_pool_demand_occupancy()
                ),
                "n_pages_free": float(engine.pool.n_free),
            },
        ).maybe_start()
        while watch.alive():
            await asyncio.sleep(1.0)
        tele.stop()
        hb.stop()
        await runner.cleanup()

    asyncio.run(main())


def gserver_manager_main(cfg):
    import asyncio

    _setup_worker_env(cfg, "cpu")
    from areal_tpu.base import name_resolve, names, network
    from areal_tpu.system.gserver_manager import (
        GserverManager,
        GserverManagerConfig,
        serve_manager,
    )

    gconfig_n = cfg.gconfig.n if not isinstance(cfg.gconfig, dict) else cfg.gconfig.get("n", 1)
    mcfg = GserverManagerConfig(
        experiment_name=cfg.experiment_name,
        trial_name=cfg.trial_name,
        model_name="actor",
        # the staleness gate counts SEQUENCES (the trainer bumps
        # training_samples by groups x gconfig.n), so the divisor must be
        # sequences per train step too (≈ reference train_rpcs[0].n_seqs)
        train_batch_size=cfg.train_batch_size * gconfig_n,
        max_head_offpolicyness=cfg.manager.max_head_offpolicyness,
        max_concurrent_rollouts=cfg.manager.max_concurrent_rollouts,
        schedule_policy=cfg.manager.schedule_policy,
    )

    async def main():
        from areal_tpu.system.worker_base import (
            ExperimentStatusWatch,
            Heartbeat,
            TelemetryExporter,
        )

        manager = GserverManager(mcfg)
        # wait for all advertised gen servers
        for i in range(cfg.gen.n_servers):
            name_resolve.wait(
                names.gen_server(cfg.experiment_name, cfg.trial_name, i),
                timeout=300,
            )
        manager.discover_servers()
        await serve_manager(manager, "127.0.0.1", network.find_free_port())
        watch = ExperimentStatusWatch(cfg.experiment_name, cfg.trial_name)
        hb = Heartbeat(cfg.experiment_name, cfg.trial_name, "gserver_manager").start()
        tele = TelemetryExporter(
            cfg.experiment_name, cfg.trial_name,
            "gserver_manager", "manager",
            step_fn=lambda: max(manager.version, 0),
            gauges_fn=lambda: {
                "rollouts_running": float(manager.rollout_stat.running),
                "rollouts_submitted": float(manager.rollout_stat.submitted),
                "rollouts_accepted": float(manager.rollout_stat.accepted),
            },
            # per-server breaker states feed the fleet/ servers_* tallies
            # and the ops CLI's breaker column
            server_states_fn=lambda: {
                u: s["state"] for u, s in manager.fleet.snapshot().items()
            },
        ).maybe_start()
        while watch.alive():
            await asyncio.sleep(1.0)
        tele.stop()
        hb.stop()

    asyncio.run(main())


def gateway_main(cfg):
    """Serving-gateway worker (docs/serving.md): OpenAI-compatible API +
    continuous-batching scheduler over the discovered gen servers, with
    an optional autoscaler resizing the ROUTED subset live (and mirroring
    every add/remove to the gserver manager so RL sticky routing follows)."""
    import asyncio

    _setup_worker_env(cfg, "cpu")
    from areal_tpu.base import constants as _constants
    from areal_tpu.base import name_resolve, names, network
    from areal_tpu.gateway.api import (
        ByteFallbackCodec,
        GatewayConfig,
        GatewayServer,
        HFTokenizerCodec,
        serve_gateway,
    )
    from areal_tpu.gateway.autoscaler import (
        Autoscaler,
        AutoscalerConfig,
        ScaleSignals,
    )
    from areal_tpu.gateway.qos import TenantSpec
    from areal_tpu.gateway.scheduler import ContinuousBatchScheduler
    from areal_tpu.system import telemetry

    gspec = cfg.gateway

    async def main():
        from areal_tpu.system.worker_base import (
            ExperimentStatusWatch,
            Heartbeat,
            TelemetryExporter,
        )

        # discovered fleet = scale-out ceiling; routed set starts full
        all_urls = []
        for i in range(cfg.gen.n_servers):
            name_resolve.wait(
                names.gen_server(cfg.experiment_name, cfg.trial_name, i),
                timeout=300,
            )
            all_urls.append(
                name_resolve.get(
                    names.gen_server(cfg.experiment_name, cfg.trial_name, i)
                )
            )
        # spec value 0 defers to the env knobs — for NAMED tenants too,
        # or listing a tenant in tenant_weights would silently strip its
        # rate limit while the anonymous tenant kept one
        rate = gspec.rate_tokens_per_s or _constants.gateway_rate_tps()
        burst = gspec.burst_tokens or _constants.gateway_burst()
        tenants = {
            name: TenantSpec(
                name=name, weight=w,
                rate_tokens_per_s=rate, burst_tokens=burst,
                default_deadline_s=gspec.default_deadline_s,
            )
            for name, w in gspec.tenant_weights.items()
        }
        scheduler = ContinuousBatchScheduler(
            list(all_urls),
            tenants,
            default_tenant=TenantSpec(
                name=gspec.default_tenant,
                rate_tokens_per_s=rate,
                burst_tokens=burst,
                default_deadline_s=gspec.default_deadline_s,
            ),
            max_queue=gspec.max_queue if gspec.max_queue >= 0 else None,
            admit_occupancy=(
                gspec.admit_occupancy if gspec.admit_occupancy >= 0 else None
            ),
            hedge_enabled=gspec.hedge,
        )
        await scheduler.start()
        tok_path = cfg.tokenizer_path or cfg.actor.path
        codec = (
            HFTokenizerCodec(tok_path) if tok_path
            else ByteFallbackCodec(cfg.actor.model_config().vocab_size)
        )
        gw = GatewayServer(
            scheduler, codec,
            GatewayConfig(
                model_id=cfg.experiment_name,
                default_tenant=gspec.default_tenant,
                api_keys=dict(gspec.api_keys),
                require_api_key=gspec.require_api_key,
                max_tokens_cap=cfg.gen.max_new_tokens_cap,
            ),
        )
        port = gspec.port or _constants.gateway_port() or network.find_free_port()
        runner = await serve_gateway(gw, "127.0.0.1", port)
        name_resolve.add(
            names.gateway(cfg.experiment_name, cfg.trial_name),
            f"http://127.0.0.1:{port}",
            replace=True,
        )

        autoscaler_task = None
        if gspec.autoscale:
            mgr_url = None

            async def _sync_manager(url: str, add: bool):
                nonlocal mgr_url
                from areal_tpu.gen.client import GenAPIClient

                if mgr_url is None:
                    try:
                        mgr_url = name_resolve.get(
                            names.gserver_manager(
                                cfg.experiment_name, cfg.trial_name
                            )
                        )
                    except name_resolve.NameEntryNotFoundError:
                        return
                try:
                    async with GenAPIClient(timeout=10.0) as c:
                        await c.post_json(
                            mgr_url,
                            "/add_server" if add else "/remove_server",
                            {"url": url}, op="autoscale",
                        )
                except Exception:
                    logger.exception("manager routed-set sync failed")

            def fetch_signals():
                scalars = telemetry.collect_fleet_scalars(
                    cfg.experiment_name, cfg.trial_name
                ) or {}
                routed = scheduler.server_urls()
                # occupancy averages over the ROUTED set: idle unrouted
                # servers report ~0 and would dilute routed-pool
                # saturation below the grow threshold
                sig = ScaleSignals.from_fleet_scalars(
                    scalars, routed=len(routed),
                    n_gen_servers=max(len(routed), 1),
                )
                # the gateway's own queue is live, not telemetry-lagged
                sig.queue_depth = float(scheduler.queue_depth())
                return sig

            def grow(n: int) -> int:
                routed = scheduler.server_urls()
                spare = [u for u in all_urls if u not in routed][:n]
                if spare:
                    scheduler.set_servers(routed + spare)
                    for u in spare:
                        t = asyncio.get_event_loop().create_task(
                            _sync_manager(u, add=True)
                        )
                        _bg_tasks.add(t)
                        t.add_done_callback(_bg_tasks.discard)
                return len(spare)

            def shrink(n: int) -> int:
                routed = scheduler.server_urls()
                n = min(n, max(len(routed) - gspec.min_servers, 0))
                victims = routed[len(routed) - n:] if n else []
                if victims:
                    scheduler.set_servers(
                        [u for u in routed if u not in victims]
                    )
                    for u in victims:
                        t = asyncio.get_event_loop().create_task(
                            _sync_manager(u, add=False)
                        )
                        _bg_tasks.add(t)
                        t.add_done_callback(_bg_tasks.discard)
                return len(victims)

            _bg_tasks: set = set()
            autoscaler = Autoscaler(
                AutoscalerConfig(
                    min_servers=gspec.min_servers,
                    max_servers=cfg.gen.n_servers,
                    interval_s=gspec.autoscale_interval_s,
                    cooldown_s=gspec.autoscale_cooldown_s,
                ),
                fetch_signals, grow, shrink,
            )
            autoscaler_task = asyncio.get_event_loop().create_task(
                autoscaler.run()
            )

        brownout_task = None
        if gspec.brownout:
            from areal_tpu.gateway.brownout import (
                BrownoutConfig,
                wire_brownout,
            )

            controller = wire_brownout(
                BrownoutConfig(
                    interval_s=gspec.brownout_interval_s,
                    min_hold_s=gspec.brownout_min_hold_s,
                    clamp_max_tokens=gspec.brownout_clamp_max_tokens,
                    weight_floor=gspec.brownout_weight_floor,
                ),
                scheduler, gw.config,
            )
            brownout_task = asyncio.get_event_loop().create_task(
                controller.run()
            )

        watch = ExperimentStatusWatch(cfg.experiment_name, cfg.trial_name)
        hb = Heartbeat(cfg.experiment_name, cfg.trial_name, "gateway").start()
        tele = TelemetryExporter(
            cfg.experiment_name, cfg.trial_name, "gateway", "gateway",
            gauges_fn=lambda: {
                "gw_queue_depth": float(scheduler.queue_depth()),
                "gw_inflight": float(scheduler.inflight()),
                "gw_routed_servers": float(len(scheduler.server_urls())),
            },
        ).maybe_start()
        while watch.alive():
            await asyncio.sleep(1.0)
        tele.stop()
        hb.stop()
        if autoscaler_task is not None:
            autoscaler_task.cancel()
        if brownout_task is not None:
            brownout_task.cancel()
        await scheduler.stop()
        await runner.cleanup()

    asyncio.run(main())


def rollout_worker_main(cfg, worker_idx: int):
    import asyncio

    _setup_worker_env(cfg, "cpu")
    from areal_tpu.api.agent import make_agent
    from areal_tpu.api.dataset import DatasetUtility, make_dataset
    from areal_tpu.api.env import make_env
    from areal_tpu.api.model import GenerationHyperparameters
    from areal_tpu.system.rollout_worker import RolloutWorker

    util = DatasetUtility(
        seed=cfg.dataset.seed,
        dp_rank=worker_idx,
        world_size=cfg.rollout.n_workers,
    )
    dataset = make_dataset(
        cfg.dataset.name, util, path=cfg.dataset.path,
        max_length=cfg.dataset.max_length,
    )
    env_args = dict(cfg.rollout.env_args)
    if hasattr(dataset, "load_metadata") and "dataset_metadata" not in env_args:
        env_args["dataset_metadata"] = dataset.load_metadata()
    env = make_env(cfg.rollout.env, **env_args)
    agent_args = dict(cfg.rollout.agent_args)
    gconfig = cfg.gconfig
    if isinstance(gconfig, dict):
        gconfig = GenerationHyperparameters(**gconfig)
    agent = make_agent(cfg.rollout.agent, gconfig=gconfig, **agent_args)
    worker = RolloutWorker(
        experiment_name=cfg.experiment_name,
        trial_name=cfg.trial_name,
        worker_index=worker_idx,
        n_workers=cfg.rollout.n_workers,
        n_pullers=1,
        agent=agent,
        env=env,
        dataset=dataset,
        new_tokens_per_chunk=cfg.rollout.new_tokens_per_chunk,
        max_concurrent_tasks=cfg.rollout.max_concurrent_tasks,
    )
    from areal_tpu.system.worker_base import (
        ExperimentStatusWatch,
        Heartbeat,
        TelemetryExporter,
    )

    watch = ExperimentStatusWatch(cfg.experiment_name, cfg.trial_name)
    hb = Heartbeat(
        cfg.experiment_name, cfg.trial_name, f"rollout_worker/{worker_idx}"
    ).start()
    tele = TelemetryExporter(
        cfg.experiment_name, cfg.trial_name,
        f"rollout_worker/{worker_idx}", "rollout",
        step_fn=lambda: worker.push_cnt,
        gauges_fn=lambda: {
            "rollout_tasks_running": float(worker.n_tasks()),
            "rollout_requeued": float(worker.requeued_cnt),
            "rollout_dropped": float(worker.dropped_cnt),
        },
    ).maybe_start()
    try:
        asyncio.run(worker.run_async(should_stop=lambda: not watch.alive()))
    finally:
        tele.stop()
        hb.stop()


def _load_ppo_engines(cfg, total_steps):
    """actor / optional ref / optional critic from an experiment config —
    ONE place for the gating rules shared by the sync and async recipes."""
    actor = _load_engine(cfg.actor, total_steps=total_steps)
    ref = None
    if cfg.use_ref_model and (cfg.ppo.kl_ctl != 0 or cfg.ema_ref_eta is not None):
        ref = _load_engine(cfg.actor, with_optimizer=False)
    critic = None
    if cfg.critic is not None and not cfg.ppo.disable_value:
        critic = _load_engine(cfg.critic, is_critic=True, total_steps=total_steps)
    reward = None
    if getattr(cfg, "reward", None) is not None:
        reward = _load_engine(cfg.reward, is_critic=True, with_optimizer=False)
    return actor, ref, critic, reward


def trainer_main(cfg):
    _setup_worker_env(cfg, cfg.trainer_device)
    # pod-scale runs: each host's launcher sets AREAL_COORDINATOR/_NUM_
    # PROCESSES/_PROCESS_ID (or AREAL_COORDINATOR=auto on Cloud TPU) and the
    # trainer joins the jax.distributed world before building its mesh.
    # With AREAL_ELASTIC on, the world comes up through the world-epoch
    # protocol instead: a WorldSupervisor owns the epoch record, this rank
    # joins it, and a rank death/hang mid-run reforms the world surgically
    # rather than crashing it (docs/fault_tolerance.md "Elastic multihost").
    from areal_tpu.base import constants
    from areal_tpu.parallel import multihost

    elastic_mgr = None
    try:
        n_ranks = constants.multihost_num_processes()
    except KeyError:
        n_ranks = 0
    if constants.elastic_enabled() and n_ranks > 1:
        from areal_tpu.parallel import elastic as elastic_mod

        multihost.enable_cpu_collectives()
        elastic_mgr = elastic_mod.WorldEpochManager(
            elastic_mod.ElasticConfig(
                experiment_name=cfg.experiment_name,
                trial_name=cfg.trial_name,
                num_processes=n_ranks,
                process_id=constants.multihost_process_id(),
            )
        )
        elastic_mgr.join()
    else:
        if constants.elastic_enabled():
            # elastic mode needs a WorldSupervisor-managed multi-rank
            # world (AREAL_NUM_PROCESSES + a supervisor writing the
            # world-epoch record); the single-process local launcher has
            # neither — waiting for a record nobody writes would stall
            # every recover attempt for the full join timeout
            logger.warning(
                "AREAL_ELASTIC set but no multi-rank world "
                "(AREAL_NUM_PROCESSES absent or 1); running the standard "
                "restart-the-world path"
            )
        multihost.maybe_initialize_from_env()
    _announce_devices("trainer")
    from areal_tpu.base.metrics import MetricLogger
    from areal_tpu.system.stream_dataset import PullerStreamDataset
    from areal_tpu.system.trainer_worker import (
        AsyncPPOTrainerWorker,
        TrainerControl,
    )

    from areal_tpu.system import worker_base

    # preemption plane: SIGTERM/SIGINT (how a preemptible slice ends a
    # trial) flips a flag the train loop polls; the worker then commits a
    # recover checkpoint within the deadline and we exit EXIT_PREEMPTED,
    # which run_async_ppo maps to "preempted, restart-the-world"
    shutdown = worker_base.GracefulShutdown.from_env()
    watchdog_timeout = worker_base.watchdog_timeout_from_env()
    total = cfg.control.total_train_steps
    # bind the puller first so rollout workers can rendezvous while the
    # engines load/compile
    stream = PullerStreamDataset(
        cfg.experiment_name, cfg.trial_name, 0, offline_dataset_size=10_000
    )
    actor, ref, critic, reward = _load_ppo_engines(cfg, total)
    worker = AsyncPPOTrainerWorker(
        experiment_name=cfg.experiment_name,
        trial_name=cfg.trial_name,
        actor_engine=actor,
        stream=stream,
        hp=cfg.ppo,
        control=TrainerControl(
            total_train_steps=total,
            save_freq_steps=cfg.control.save_freq_steps,
            ckpt_freq_steps=cfg.control.ckpt_freq_steps,
            ckpt_freq_secs=cfg.control.ckpt_freq_secs,
            weight_sync_freq_steps=cfg.control.weight_sync_freq_steps,
            watchdog_timeout_secs=watchdog_timeout,
        ),
        train_batch_size=cfg.train_batch_size,
        mb_spec=cfg.mb_spec,
        ref_engine=ref,
        critic_engine=critic,
        reward_engine=reward,
        hf_family=cfg.hf_family,
        metric_logger=MetricLogger(constants.get_log_root()),
        ema_ref_eta=cfg.ema_ref_eta,
        max_head_offpolicyness=cfg.manager.max_head_offpolicyness,
    )
    recovered = False
    if elastic_mgr is not None:
        # elastic startup (initial OR a relaunched rank rejoining a live
        # trial): restore without publishing, then the COLLECTIVE version
        # agreement + single publish — the exact sequence survivors run
        # in _elastic_recover, so a relaunched rank's collectives line up
        # with theirs and every rank adopts the same new version. The
        # restore is UNCONDITIONAL (not gated on recover_mode): survivors
        # always restore during a reform, and a relaunched rank skipping
        # the (collective) restore would desynchronize the new epoch;
        # recover_mode keeps governing only the outer restart-the-world
        # loop.
        recovered = worker.load_recover_checkpoint(publish=False)
        worker._agree_version_and_publish(floor=0)
    else:
        if cfg.recover_mode in ("auto", "resume"):
            # a successful recover republishes the restored model_version
            # + training_samples itself (load_recover_checkpoint)
            recovered = worker.load_recover_checkpoint()
        if not recovered:
            # publish v0 weights so the fleet starts from the trainer's
            # init
            worker.publish_weights()
    tele = None
    if multihost.is_main():
        tele = worker_base.TelemetryExporter(
            cfg.experiment_name, cfg.trial_name, "trainer", "trainer",
            step_fn=lambda: worker.step,
            gauges_fn=worker.telemetry_gauges,
        ).maybe_start()
    rc = 0
    try:
        worker.run(
            shutdown=shutdown,
            elastic=elastic_mgr,
            # surgical recovery rebuilds the engines from scratch (every
            # device array died with the old world epoch) and re-restores
            # them from the committed recover checkpoint
            engine_factory=(
                (lambda: _load_ppo_engines(cfg, total))
                if elastic_mgr is not None
                else None
            ),
        )
    except Exception:
        if elastic_mgr is None:
            raise
        # an elastic rank must not unwind through normal interpreter
        # teardown (parked runtime objects LOG(FATAL) on destruction);
        # EXIT_WORLD_FAILED tells the supervisor/launcher to escalate to
        # restart-the-world
        logger.exception("trainer rank failed beyond surgical recovery")
        rc = worker_base.EXIT_WORLD_FAILED
    finally:
        if tele is not None:
            tele.stop()
    if worker.preempted:
        rc = worker_base.EXIT_PREEMPTED
    if elastic_mgr is not None:
        elastic_mgr.stop()
        from areal_tpu.parallel import elastic as elastic_mod

        elastic_mod.hard_exit(rc)
    if rc:
        sys.exit(rc)


def evaluator_main(cfg, stop_event=None):
    """Checkpoint-watching evaluator role (≈ ``scheduler/evaluator.py:160``):
    polls the save root, scores each new ``step{N}`` export on a held-out
    set, appends to eval_result.jsonl + metric logs. ``stop_event`` (an
    mp.Event) requests a graceful exit — one final sweep runs after it is
    set so the LAST checkpoint is always evaluated."""
    _setup_worker_env(cfg, cfg.evaluator.device)
    from areal_tpu.api.dataset import DatasetUtility, make_dataset
    from areal_tpu.base import constants
    from areal_tpu.base.metrics import MetricLogger
    from areal_tpu.system.evaluator import (
        AutomaticEvaluator,
        make_generation_eval_fn,
    )

    spec = cfg.evaluator
    ds_spec = spec.dataset or cfg.dataset
    tokenizer = None
    tok_path = getattr(cfg, "tokenizer_path", None)
    if not tok_path and getattr(cfg, "rollout", None) is not None:
        # async experiments configure the tokenizer on the rollout agent
        tok_path = cfg.rollout.agent_args.get("tokenizer_path")
    if tok_path:
        import transformers

        tokenizer = transformers.AutoTokenizer.from_pretrained(tok_path)
    util = DatasetUtility(
        seed=ds_spec.seed, dp_rank=0, world_size=1, tokenizer=tokenizer
    )
    dataset = make_dataset(
        ds_spec.name, util, path=ds_spec.path, max_length=ds_spec.max_length
    )
    decode_fn = None
    if tokenizer is not None:
        decode_fn = lambda ids: tokenizer.decode(ids, skip_special_tokens=True)
    eval_fn = make_generation_eval_fn(
        cfg.actor.model_config(),
        cfg.actor.parallel_config(),
        dataset,
        spec.gconfig,
        decode_fn=decode_fn,
        max_prompts=spec.max_prompts,
    )
    ev = AutomaticEvaluator(
        constants.get_save_root(),
        eval_fn,
        os.path.join(constants.get_log_root(), "eval_result.jsonl"),
        metric_logger=MetricLogger(constants.get_log_root()),
        poll_interval=spec.poll_interval,
    )
    from areal_tpu.system.worker_base import ExperimentStatusWatch

    watch = ExperimentStatusWatch(cfg.experiment_name, cfg.trial_name)

    def should_stop():
        if stop_event is not None and stop_event.is_set():
            return True
        return not watch.alive()

    ev.run(should_stop=should_stop)


ROLE_MAINS = {
    "gen_server": gen_server_main,
    "gserver_manager": gserver_manager_main,
    "gateway": gateway_main,
    "rollout_worker": rollout_worker_main,
    "trainer": trainer_main,
    "evaluator": evaluator_main,
}


# --------------------------------------------------------------------------- #
# orchestration
# --------------------------------------------------------------------------- #


@contextlib.contextmanager
def _child_env(overrides: Dict[str, str]):
    """Spawned children inherit the parent env at exec, and the TPU library
    reads chip visibility when it loads — before any code of ours runs in
    the child. So whatever a child must see (``JAX_PLATFORMS=cpu`` for a
    CPU-designated worker, its chips for a chip owner) is put into the
    parent env around ``Process.start()`` and taken out again."""
    saved = {k: os.environ.get(k) for k in overrides}  # arealint: ok(save/restore around child spawn, not a knob read)
    os.environ.update(overrides)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


_CPU_ENV = {"JAX_PLATFORMS": "cpu"}


def local_chip_ids() -> List[int]:
    """The accelerator chips this host offers, found WITHOUT JAX: the
    launcher parent must never initialise a backend (a process that has
    holds the chips, and every child that needs one then fails or hangs).
    Empty when the run is held to the CPU."""
    from areal_tpu.base import constants

    if constants.jax_platforms().startswith("cpu"):
        return []
    visible = constants.tpu_visible_devices()
    if visible is not None:
        return visible
    n = len(glob.glob("/dev/accel[0-9]*")) or len(glob.glob("/dev/vfio/[0-9]*"))
    return list(range(n))


def chip_owners(cfg) -> List["tuple[str, int]"]:
    """(process name, chips it needs) for every chip-owning process of the
    async-PPO world, in spawn order."""
    from areal_tpu.base import constants

    owners = []
    if cfg.gen.device != "cpu":
        owners += [
            (f"gen_server/{i}", cfg.gen.tp_size)
            for i in range(cfg.gen.n_servers)
        ]
    if cfg.trainer_device != "cpu":
        try:
            n_ranks = max(1, constants.multihost_num_processes())
        except KeyError:
            n_ranks = 1
        world = cfg.actor.parallel_config().world_size
        owners.append(("trainer", max(1, world // n_ranks)))
    ev = getattr(cfg, "evaluator", None)
    if ev is not None and ev.enabled and ev.device != "cpu":
        owners.append(("evaluator", 1))
    return owners


# TPU_CHIPS_PER_PROCESS_BOUNDS by block size. On a v5e 2x2 host two
# processes with "1,2,1" blocks {0,1} and {2,3} ran side by side; "2,1,1"
# did not start (chip probe, PR 21). 4 and 8 follow libtpu's documented
# pattern and have not been tried.
_CHIP_BLOCK_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}
_SLICE_BUILDER_PORT = 8571    # libtpu's default is 8471, for every process
_MESH_CONTROLLER_PORT = 8476


def plan_chips(
    owners: List["tuple[str, int]"], chip_ids: List[int]
) -> Dict[str, Dict[str, str]]:
    """Decide chip ownership in the parent: each owner gets a disjoint,
    contiguous block of the host's chips, as the environment its process
    must be started with (``jax.local_devices()`` in the child then starts
    from 0 within its own block). No chips (a CPU run) plans nothing:
    virtual CPU devices are per-process. A layout that asks for more
    chips than the host has raises here, at launch — not as a hang or a
    lock-file error from the second process."""
    if not chip_ids:
        return {}
    need = sum(n for _, n in owners)
    if need > len(chip_ids):
        raise ValueError(
            f"layout needs {need} chips ("
            + ", ".join(f"{name}: {n}" for name, n in owners)
            + f") but this host has {len(chip_ids)}; a chip belongs to one "
            "process at a time — put roles on the CPU (gen.device=cpu / "
            "trainer_device=cpu), lower gen.n_servers / gen.tp_size / the "
            "trainer's parallel degree, or use sync-ppo (one process)"
        )
    odd = [(name, n) for name, n in owners if n not in _CHIP_BLOCK_BOUNDS]
    if odd and len(owners) > 1:
        raise ValueError(
            f"a process that shares the host owns a block of "
            f"{sorted(_CHIP_BLOCK_BOUNDS)} chips, got {odd}"
        )
    plan, lo = {}, 0
    for slot, (name, n) in enumerate(owners):
        ids = chip_ids[lo : lo + n]
        lo += n
        if n == len(chip_ids):
            plan[name] = {}  # the whole host: nothing to restrict
            continue
        # libtpu's own variables for a process that opens a sub-block of
        # the host's chips: the block's shape, one process in it, and a
        # slice-builder and a mesh-controller port of its OWN. Left at
        # the default, every process's slice builder uses port 8471
        # (libtpu's argv, four-chip probe, PR 21). Two such processes came
        # up side by side in four probes and died in one run with "Mesh
        # build failed, duplicate coordinate assignment" naming all four
        # chips for a 1x2 mesh — what a builder joined by its sibling's
        # chips would say. jax's own multi-process TPU test sets these two
        # per process as well.
        plan[name] = {
            "TPU_VISIBLE_DEVICES": ",".join(str(i) for i in ids),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": _CHIP_BLOCK_BOUNDS[n],
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_ADDRESSES": f"localhost:{_SLICE_BUILDER_PORT + slot}",
            "TPU_PROCESS_PORT": str(_SLICE_BUILDER_PORT + slot),
            "TPU_MESH_CONTROLLER_ADDRESS":
                f"localhost:{_MESH_CONTROLLER_PORT + slot}",
            "TPU_MESH_CONTROLLER_PORT": str(_MESH_CONTROLLER_PORT + slot),
        }
    return plan


def _spawn_all(cfg) -> Dict[str, mp.Process]:
    ctx = mp.get_context("spawn")
    procs: Dict[str, mp.Process] = {}
    chips = plan_chips(chip_owners(cfg), local_chip_ids())

    def start(name, p, force_cpu):
        with _child_env(_CPU_ENV if force_cpu else chips.get(name, {})):
            p.start()
        procs[name] = p
        logger.info("started %s (pid %d)", name, p.pid)

    gen_cpu = cfg.gen.device == "cpu"
    for i in range(cfg.gen.n_servers):
        start(
            f"gen_server/{i}",
            ctx.Process(target=gen_server_main, args=(cfg, i), daemon=True),
            gen_cpu,
        )
    start(
        "gserver_manager",
        ctx.Process(target=gserver_manager_main, args=(cfg,), daemon=True),
        True,
    )
    if getattr(cfg, "gateway", None) is not None and cfg.gateway.enabled:
        start(
            "gateway",
            ctx.Process(target=gateway_main, args=(cfg,), daemon=True),
            True,
        )
    for i in range(cfg.rollout.n_workers):
        start(
            f"rollout_worker/{i}",
            ctx.Process(target=rollout_worker_main, args=(cfg, i), daemon=True),
            True,
        )
    start(
        "trainer",
        ctx.Process(target=trainer_main, args=(cfg,), daemon=True),
        cfg.trainer_device == "cpu",
    )
    if getattr(cfg, "evaluator", None) is not None and cfg.evaluator.enabled:
        start(
            "evaluator",
            ctx.Process(target=evaluator_main, args=(cfg,), daemon=True),
            cfg.evaluator.device == "cpu",
        )
    return procs


# --------------------------------------------------------------------------- #
# Elastic world supervision (docs/fault_tolerance.md "Elastic multihost")
# --------------------------------------------------------------------------- #


@dataclasses.dataclass
class WorldSupervisorConfig:
    """Config for one supervised N-rank elastic trainer world."""

    experiment_name: str
    trial_name: str
    num_processes: int
    # argv for rank r's process (the rank body must run the
    # parallel/elastic.py join/reform protocol; see tools/chaos.py)
    rank_cmd: "object" = None                 # Callable[[int], List[str]]
    rank_env: Optional[dict] = None           # extra env for every rank
    poll_s: float = 0.25
    # must match the ranks' AREAL_COLLECTIVE_TIMEOUT_S: the hang-path
    # grace is derived from it (see run())
    collective_timeout_s: float = 120.0
    # coalescing window for simultaneous rank exits
    exit_grace_s: float = 1.0
    # extra margin on top of collective_timeout_s before an alive,
    # unreported rank is declared wedged (covers the spread between the
    # first and last survivor reaching its collective deadline)
    report_grace_s: float = 10.0
    # total rank relaunches before the supervisor gives up and lets the
    # launcher's restart-the-world loop take over
    max_rank_restarts: int = 8
    # bound on detect -> every rank live at the new epoch
    reform_timeout_s: float = 300.0
    log_dir: Optional[str] = None             # per-rank stdout capture


class WorldSupervisor:
    """Launcher-side owner of the elastic world-epoch protocol.

    Spawns ``num_processes`` rank subprocesses, then watches two failure
    signals, handled differently:

    - **rank exit** (a dead rank): reform immediately — sweep the dead
      ranks' name_resolve residue, bump the monotonic world epoch with a
      fresh coordinator port, relaunch ONLY the dead ranks with the same
      ``--process-id``. Nobody is killed: survivors detect the broken
      world on their own (transport error or bounded-collective timeout),
      detach, and rejoin at the new epoch in place.
    - **timeout reports with no exit** (a wedged rank): surviving ranks'
      bounded collectives expired and they reported; the wedged rank is
      the alive rank that did NOT report. Because a slow-to-detect
      survivor is indistinguishable from a wedged rank until its own
      collective deadline passes, the supervisor waits a full
      ``collective_timeout_s + report_grace_s`` after the first report
      before SIGKILLing the non-reporters (a hung rank never exits on
      its own) and reforming as above.

    Counters: ``ft/rank_restarts``, ``ft/world_epochs``, and a
    ``recovery_time_s`` histogram (detection -> every rank's lease live at
    the new epoch). The supervisor is the ONLY writer of the world record
    AND the host of every epoch's coordination service
    (``elastic.host_service``) — so no rank death can close a service
    socket that surviving clients poll, there is no leader election, and
    a dead rank 0 recovers exactly like any other rank.
    """

    def __init__(self, cfg: WorldSupervisorConfig):
        self.cfg = cfg
        self.epoch = -1
        self.procs: Dict[int, "object"] = {}
        self.rank_restarts = 0
        self.recovery_times: List[float] = []
        self._log_files: Dict[int, object] = {}

    # -- spawning --------------------------------------------------------

    def _spawn_rank(self, rank: int):
        import subprocess

        from areal_tpu.base import constants

        env = dict(os.environ)
        env.update(constants.get_env_vars(
            AREAL_NUM_PROCESSES=self.cfg.num_processes,
            AREAL_PROCESS_ID=rank,
        ))
        # per-world overrides win over inherited/forwarded values
        env.update(self.cfg.rank_env or {})
        stdout = None
        if self.cfg.log_dir:
            os.makedirs(self.cfg.log_dir, exist_ok=True)
            prev = self._log_files.pop(rank, None)
            if prev is not None:
                try:  # a relaunch must not leak the old incarnation's fd
                    prev.close()
                except OSError:
                    pass
            f = open(
                os.path.join(self.cfg.log_dir, f"rank{rank}.log"), "ab"
            )
            self._log_files[rank] = f
            stdout = f
        self.procs[rank] = subprocess.Popen(
            self.cfg.rank_cmd(rank), env=env,
            stdout=stdout, stderr=subprocess.STDOUT if stdout else None,
        )
        logger.info(
            "world rank %d spawned (pid %d)", rank, self.procs[rank].pid
        )

    def _write_world(self):
        from areal_tpu.base import network
        from areal_tpu.parallel import elastic as elastic_mod

        port = network.find_free_port()
        # the supervisor hosts the epoch's coordination service itself —
        # see the class docstring; the service must be up before the
        # record is visible, or a fast rank's connect would race it
        elastic_mod.host_service(port, self.cfg.num_processes)
        elastic_mod.write_world(
            self.cfg.experiment_name, self.cfg.trial_name,
            elastic_mod.WorldState(
                epoch=self.epoch,
                coordinator=f"127.0.0.1:{port}",
                num_processes=self.cfg.num_processes,
            ),
        )
        logger.info(
            "world epoch %d published (coordinator port %d)",
            self.epoch, port,
        )

    def start(self):
        """Publish epoch 0 and spawn every rank. When the telemetry knob
        is on, the supervisor also exports its own snapshots (role
        ``supervisor``, step = world epoch) so ``ft/rank_restarts`` /
        ``ft/world_epochs`` and the ``recovery_time_s`` histogram reach
        the ``fleet/`` aggregate and the obs CLI's supervisor row."""
        from areal_tpu.system import worker_base

        self.epoch = 0
        self._write_world()
        for r in range(self.cfg.num_processes):
            self._spawn_rank(r)
        self._tele = worker_base.TelemetryExporter(
            self.cfg.experiment_name, self.cfg.trial_name,
            "world_supervisor", "supervisor",
            step_fn=lambda: self.epoch,
            gauges_fn=lambda: {
                "world_epoch": float(self.epoch),
                "ranks_alive": float(sum(
                    1 for p in self.procs.values() if p.poll() is None
                )),
            },
        ).maybe_start()
        return self

    # -- failure handling ------------------------------------------------

    @staticmethod
    def decide_culprits(
        exited: Dict[int, int],
        reports: Dict[int, dict],
        alive: List[int],
        wedge_deadline_passed: bool = False,
    ) -> List[int]:
        """Who must be relaunched: every non-zero exit always; *alive*
        ranks without a survivor report only once the wedge deadline
        (collective timeout + grace since the first report) has passed —
        before that, a slow-to-detect survivor is indistinguishable from a
        wedged rank. Clean exits (code 0) are never culprits."""
        culprits = {r for r, code in exited.items() if code != 0}
        if wedge_deadline_passed:
            culprits |= {r for r in alive if r not in reports}
        return sorted(culprits)

    def _reform(
        self,
        culprits: List[int],
        exited: Dict[int, int],
        reports: Dict[int, dict],
        detect_t: float,
    ) -> None:
        import signal as signal_mod

        from areal_tpu.base import metrics as metrics_mod
        from areal_tpu.parallel import elastic as elastic_mod

        logger.warning(
            "world epoch %d failed: exited=%s reports=%s -> culprits=%s",
            self.epoch, exited, sorted(reports), culprits,
        )
        for r in culprits:
            p = self.procs.get(r)
            if p is not None and p.poll() is None:
                logger.warning("SIGKILLing wedged rank %d (pid %d)", r, p.pid)
                p.send_signal(signal_mod.SIGKILL)
                p.wait()
        # lease hygiene: dead ranks' keys must not accumulate across
        # reformations (regression-tested in tests/test_elastic.py)
        for r in culprits:
            elastic_mod.sweep_rank_keys(
                self.cfg.experiment_name, self.cfg.trial_name, r
            )
        elastic_mod.sweep_timeout_reports(
            self.cfg.experiment_name, self.cfg.trial_name, self.epoch
        )
        self.epoch += 1
        self._write_world()
        for r in culprits:
            self._spawn_rank(r)
        self.rank_restarts += len(culprits)
        metrics_mod.counters.add(metrics_mod.FT_RANK_RESTARTS, len(culprits))
        metrics_mod.counters.add(metrics_mod.FT_WORLD_EPOCHS)
        # recovery completes when every rank's lease is live at the new
        # epoch (the world actually re-formed, not merely re-published)
        deadline = time.monotonic() + self.cfg.reform_timeout_s
        while time.monotonic() < deadline:
            leases = elastic_mod.read_leases(
                self.cfg.experiment_name, self.cfg.trial_name
            )
            at_epoch = [
                r for r, d in leases.items()
                if d.get("epoch") == self.epoch
            ]
            if len(at_epoch) >= self.cfg.num_processes:
                break
            if any(
                p.poll() is not None and p.returncode != 0
                for p in self.procs.values()
            ):
                break  # the new epoch is already failing; next loop turn
            time.sleep(self.cfg.poll_s)
        took = time.monotonic() - detect_t
        self.recovery_times.append(took)
        metrics_mod.counters.observe(metrics_mod.RECOVERY_TIME_S, took)
        logger.warning(
            "world reformed into epoch %d in %.1fs (%d rank restarts total)",
            self.epoch, took, self.rank_restarts,
        )

    def run(self, timeout: Optional[float] = None) -> int:
        """Supervise until every rank exits 0 (returns 0), the restart
        budget is exhausted, or ``timeout`` expires (returns 1 after
        tearing the world down)."""
        from areal_tpu.parallel import elastic as elastic_mod

        t0 = time.monotonic()
        first_report_t: Optional[float] = None
        try:
            while True:
                if timeout is not None and time.monotonic() - t0 > timeout:
                    logger.error("world supervision timed out")
                    return 1
                codes = {r: p.poll() for r, p in self.procs.items()}
                if all(c == 0 for c in codes.values()):
                    return 0
                exited = {
                    r: c for r, c in codes.items()
                    if c is not None and c != 0
                }
                # Two exit codes end supervision instead of triggering a
                # relaunch: EXIT_WORLD_FAILED (a rank explicitly
                # escalating — its reform budget is spent; a fresh budget
                # would multiply the churn the code exists to stop) and
                # EXIT_PREEMPTED (the slice is being reclaimed — the rank
                # committed its recover checkpoint and relaunching it just
                # burns the preemption grace window on churn).
                from areal_tpu.system import worker_base as wb

                gave_up = [
                    r for r, c in exited.items()
                    if c == wb.EXIT_WORLD_FAILED
                ]
                if gave_up:
                    logger.error(
                        "rank(s) %s exited EXIT_WORLD_FAILED: escalating "
                        "to restart-the-world", gave_up,
                    )
                    return 1
                preempted = [
                    r for r, c in exited.items()
                    if c == wb.EXIT_PREEMPTED
                ]
                if preempted:
                    logger.warning(
                        "rank(s) %s exited EXIT_PREEMPTED: world preempted"
                        " — state is the committed checkpoint; not "
                        "relaunching", preempted,
                    )
                    return wb.EXIT_PREEMPTED
                reports = elastic_mod.read_timeout_reports(
                    self.cfg.experiment_name, self.cfg.trial_name, self.epoch
                )
                if not exited and not reports:
                    first_report_t = None
                    time.sleep(self.cfg.poll_s)
                    continue
                if self.rank_restarts >= self.cfg.max_rank_restarts:
                    logger.error(
                        "rank-restart budget (%d) exhausted; giving up on "
                        "surgical recovery", self.cfg.max_rank_restarts,
                    )
                    return 1
                if exited:
                    # dead-rank path: reform NOW, relaunch only the dead.
                    # Survivors detect the broken world on their own
                    # (transport error / bounded timeout) and rejoin —
                    # nobody gets killed on a guess.
                    detect_t = time.monotonic()
                    time.sleep(self.cfg.exit_grace_s)  # coalesce siblings
                    exited = {
                        r: p.returncode
                        for r, p in self.procs.items()
                        if p.poll() is not None and p.returncode != 0
                    }
                    reports = elastic_mod.read_timeout_reports(
                        self.cfg.experiment_name, self.cfg.trial_name,
                        self.epoch,
                    )
                    alive = [
                        r for r, p in self.procs.items() if p.poll() is None
                    ]
                    culprits = self.decide_culprits(
                        exited, reports, alive, wedge_deadline_passed=False
                    )
                    self._reform(culprits, exited, reports, detect_t)
                    first_report_t = None
                    continue
                # hang path: reports but no exit. A wedged rank can only
                # be told apart from a slow-to-detect survivor after every
                # survivor's own collective deadline had a chance to fire.
                if first_report_t is None:
                    first_report_t = time.monotonic()
                alive = [
                    r for r, p in self.procs.items() if p.poll() is None
                ]
                deadline_passed = (
                    time.monotonic() - first_report_t
                    > self.cfg.collective_timeout_s + self.cfg.report_grace_s
                )
                if deadline_passed or all(r in reports for r in alive):
                    culprits = self.decide_culprits(
                        {}, reports, alive,
                        wedge_deadline_passed=deadline_passed,
                    )
                    self._reform(culprits, {}, reports, first_report_t)
                    first_report_t = None
                    continue
                time.sleep(self.cfg.poll_s)
        finally:
            self.terminate()

    def terminate(self):
        tele = getattr(self, "_tele", None)
        if tele is not None:
            tele.stop()
            self._tele = None
        for r, p in self.procs.items():
            if p.poll() is None:
                p.kill()
        for p in self.procs.values():
            try:
                p.wait(timeout=10)
            except Exception:
                pass
        for f in self._log_files.values():
            try:
                f.close()
            except OSError:
                pass
        self._log_files.clear()


def run_async_ppo(cfg) -> int:
    """Launch the full async-PPO world; restart on failure per recover_mode.
    Returns the trainer's exit code of the final attempt."""
    attempts = 1 + (cfg.recover_retries if cfg.recover_mode == "auto" else 0)
    # the launcher owns the experiment lifecycle record: workers poll it and
    # self-terminate when it goes away (system/worker_base.py)
    _setup_worker_env(cfg, "")
    from areal_tpu.system import worker_base

    for attempt in range(attempts):
        if attempt > 0:
            logger.warning("recover attempt %d/%d", attempt, attempts - 1)
            cfg = dataclasses.replace(cfg, recover_mode="resume")
        worker_base.mark_experiment_running(cfg.experiment_name, cfg.trial_name)
        procs = _spawn_all(cfg)
        trainer = procs["trainer"]
        failed = False
        try:
            while trainer.is_alive():
                trainer.join(timeout=5)
                for name, p in procs.items():
                    # the evaluator is best-effort: its death never restarts
                    # the world (matching the reference's detached eval jobs)
                    if name in ("trainer", "evaluator"):
                        continue
                    if not p.is_alive():
                        logger.error("%s died (exit %s)", name, p.exitcode)
                        failed = True
                        break
                if failed:
                    break
        finally:
            # graceful first: flip the status so watchers exit on their own,
            # then terminate stragglers
            worker_base.mark_experiment_stopped(cfg.experiment_name, cfg.trial_name)
            deadline = time.time() + 5
            for name, p in procs.items():
                if name != "evaluator":
                    p.join(timeout=max(0.1, deadline - time.time()))
            for name, p in procs.items():
                if name != "evaluator" and p.is_alive():
                    p.terminate()
            ev = procs.get("evaluator")
            if ev is not None:
                # the evaluator notices the stop on its next poll and runs a
                # final sweep so the LAST checkpoint is always scored — give
                # it real time before terminating
                ev.join(timeout=300)
            for p in procs.values():
                if p.is_alive():
                    p.terminate()
            for p in procs.values():
                p.join(timeout=10)
            # SIGKILL escalation: the trainer's GracefulShutdown turns
            # SIGTERM into a (possibly minutes-long) preemption save, and a
            # straggler outliving the join would overlap the next attempt's
            # freshly spawned world (same staging dirs, same devices). The
            # commit protocol makes the hard kill safe: the previous
            # committed checkpoint survives a death mid-save.
            for name, p in procs.items():
                if p.is_alive():
                    logger.warning(
                        "%s survived terminate(); escalating to kill", name
                    )
                    p.kill()
                    p.join(timeout=10)
        if trainer.exitcode == 0 and not failed:
            return 0
        if trainer.exitcode == worker_base.EXIT_PREEMPTED and not failed:
            # NOT a crash: the trainer committed a recover checkpoint inside
            # its deadline — restart-the-world resumes it (recover_mode
            # auto), or the code propagates so an outer scheduler can.
            # (With `failed` set, exit 75 just means OUR teardown SIGTERMed
            # the trainer after a sibling died — that is the crash path.)
            logger.warning(
                "trainer preempted (exit %d): recover checkpoint committed; "
                "restart-the-world", worker_base.EXIT_PREEMPTED,
            )
        if cfg.recover_mode != "auto":
            break
    rc = trainer.exitcode if trainer.exitcode is not None else 1
    if failed and rc == worker_base.EXIT_PREEMPTED:
        # a sibling worker's crash triggered the teardown; reporting the
        # trainer's teardown-induced exit code would tell an outer
        # scheduler "state intact, try again" about a reproducible crash
        rc = 1
    return rc


def run_sync_ppo(cfg) -> int:
    """Sync PPO runs in-process: generation happens on the trainer's own
    mesh/params (no fleet, no weight publish); the evaluator (if enabled)
    runs as a side process on host 0."""
    _setup_worker_env(cfg, cfg.trainer_device)
    from areal_tpu.parallel import multihost

    multihost.maybe_initialize_from_env()
    from areal_tpu.api.dataset import DatasetUtility, make_dataset
    from areal_tpu.base import constants
    from areal_tpu.base.metrics import MetricLogger
    from areal_tpu.system.sync_trainer import SyncPPOTrainerWorker
    from areal_tpu.system.trainer_worker import TrainerControl

    from areal_tpu.system import worker_base

    if multihost.is_main():
        worker_base.mark_experiment_running(cfg.experiment_name, cfg.trial_name)
    ev_proc = ev_stop = None
    if cfg.evaluator.enabled and multihost.is_main():
        if cfg.evaluator.device != "cpu" and local_chip_ids():
            # this process owns the chips by now: a child that wants one
            # would fail or hang
            raise ValueError(
                "sync-ppo trains and generates in the process that holds "
                "the accelerator; its evaluator child must run on the CPU "
                f"(evaluator.device=cpu, got {cfg.evaluator.device!r})"
            )
        ctx = mp.get_context("spawn")
        ev_stop = ctx.Event()
        with _child_env(_CPU_ENV):
            ev_proc = ctx.Process(
                target=evaluator_main, args=(cfg, ev_stop), daemon=True
            )
            ev_proc.start()

    tokenizer = None
    if cfg.tokenizer_path:
        import transformers

        tokenizer = transformers.AutoTokenizer.from_pretrained(cfg.tokenizer_path)
    util = DatasetUtility(
        seed=cfg.dataset.seed, dp_rank=0, world_size=1, tokenizer=tokenizer
    )
    dataset = make_dataset(
        cfg.dataset.name, util, path=cfg.dataset.path,
        max_length=cfg.dataset.max_length,
    )
    total = cfg.control.total_train_steps
    actor, ref, critic, _ = _load_ppo_engines(cfg, total)
    decode_fn = None
    if tokenizer is not None:
        decode_fn = lambda ids: tokenizer.decode(ids, skip_special_tokens=True)
    worker = SyncPPOTrainerWorker(
        experiment_name=cfg.experiment_name,
        trial_name=cfg.trial_name,
        actor_engine=actor,
        dataset=dataset,
        hp=cfg.ppo,
        ghp=cfg.gconfig,
        control=TrainerControl(
            total_train_steps=total,
            save_freq_steps=cfg.control.save_freq_steps,
        ),
        batch_size=cfg.batch_size,
        mb_spec=cfg.mb_spec,
        ref_engine=ref,
        critic_engine=critic,
        ema_ref_eta=cfg.ema_ref_eta,
        decode_fn=decode_fn,
        hf_family=cfg.hf_family,
        metric_logger=MetricLogger(constants.get_log_root()),
        seed=cfg.seed,
    )
    try:
        worker.run()
    finally:
        if multihost.is_main():
            worker_base.mark_experiment_stopped(cfg.experiment_name, cfg.trial_name)
        if ev_proc is not None:
            # graceful stop: the evaluator runs one final sweep so the last
            # checkpoint export is always scored
            ev_stop.set()
            ev_proc.join(timeout=300)
            if ev_proc.is_alive():
                ev_proc.terminate()
                ev_proc.join(timeout=10)
    return 0


def _run_supervised(cfg, *, is_critic: bool, interface_name: str,
                    dataset_kwargs=None, interface_kwargs=None) -> int:
    """Shared body of the in-process supervised recipes (SFT / paired-RW):
    one trainer program, no fleet — only the objective differs."""
    _setup_worker_env(cfg, "")
    from areal_tpu.api.data import MicroBatchSpec
    from areal_tpu.api.dataset import DatasetUtility, make_dataset
    from areal_tpu.base import constants
    from areal_tpu.base.metrics import MetricLogger
    from areal_tpu.system.trainer_worker import SFTTrainerWorker, TrainerControl

    dataset_kwargs = dataset_kwargs or {}
    tokenizer = None
    if cfg.tokenizer_path:
        import transformers

        tokenizer = transformers.AutoTokenizer.from_pretrained(cfg.tokenizer_path)
    util = DatasetUtility(
        seed=cfg.dataset.seed, dp_rank=0, world_size=1, tokenizer=tokenizer
    )
    dataset = make_dataset(
        cfg.dataset.name, util, path=cfg.dataset.path,
        max_length=cfg.dataset.max_length, **dataset_kwargs,
    )
    eval_ds = None
    if cfg.eval_dataset is not None:
        eval_ds = make_dataset(
            cfg.eval_dataset.name, util, path=cfg.eval_dataset.path,
            max_length=cfg.eval_dataset.max_length, **dataset_kwargs,
        )
    engine = _load_engine(
        cfg.model, is_critic=is_critic, total_steps=cfg.control.total_train_steps
    )
    worker = SFTTrainerWorker(
        experiment_name=cfg.experiment_name,
        trial_name=cfg.trial_name,
        engine=engine,
        dataset=dataset,
        eval_dataset=eval_ds,
        control=TrainerControl(
            total_train_steps=cfg.control.total_train_steps,
            save_freq_steps=cfg.control.save_freq_steps,
        ),
        batch_size=cfg.batch_size,
        mb_spec=MicroBatchSpec(max_tokens_per_mb=cfg.max_tokens_per_mb),
        hf_family=cfg.hf_family,
        metric_logger=MetricLogger(constants.get_log_root()),
        interface_name=interface_name,
        interface_kwargs=interface_kwargs,
    )
    worker.run()
    return 0


def run_rw(cfg) -> int:
    """Paired reward-model training (≈ the reference's rw experiment):
    critic-architecture model + Bradley-Terry pairwise loss over
    ``rw_paired`` data; exports HF checkpoints usable as the "reward"
    engine in RM-scored PPO."""
    return _run_supervised(
        cfg,
        is_critic=True,
        interface_name="reward",
        dataset_kwargs={"max_pairs_per_prompt": cfg.max_pairs_per_prompt},
        interface_kwargs={"max_pairs_per_prompt": cfg.max_pairs_per_prompt},
    )




def run_sft(cfg) -> int:
    """SFT runs in-process: one trainer program, no fleet."""
    return _run_supervised(cfg, is_critic=False, interface_name="sft")
