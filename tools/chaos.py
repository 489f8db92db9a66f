"""Chaos soak harness for the elastic multihost world (``make chaos``).

Drives the N-process CPU fault world (the same world as
``tests/test_multihost.py``, now elastic) through a *seeded* schedule of
rank kills and hangs, with an optional tiny generation fleet serving
traffic throughout, and asserts end-state invariants:

- **loss-trajectory continuity**: the faulted N-process run's per-step
  losses (last write wins across rollbacks) match an unfaulted
  single-process run over the same global batch — surgical recovery plus
  committed-checkpoint rollback must be *semantically invisible*;
- **no version regression**: the world epoch only advances and the gen
  engine's weight version never moves backward;
- **no leaked state**: gen slots/pages all freed, exactly one liveness
  lease + heartbeat per live rank (dead ranks' keys swept on epoch bump);
- **bounded recovery**: every reformation (detection -> all ranks live at
  the new epoch) under the configured bound;
- **accounting**: ``ft/rank_restarts`` == scheduled faults,
  ``ft/world_epochs`` == reformations.

Two entry modes::

    python -m tools.chaos --seed 1 --faults 2        # scenario runner
    python -m tools.chaos --run-rank 2 --spec s.json # one rank (internal)

The runner writes a JSON report and exits 0 iff every invariant holds.
Scenario scripting rides ``base/faults.py`` (``rank.kill`` / ``rank.hang``
trip points armed per (rank, epoch, step)); the supervisor is
``apps/launcher.py::WorldSupervisor``; the rank-side protocol is
``parallel/elastic.py``.
"""

import argparse
import dataclasses
import glob
import json
import logging
import os
import random
import signal
import sys
import threading
import time
from typing import Dict, List, Optional

logger = logging.getLogger("tools.chaos")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------- #
# Seeded fault schedules
# --------------------------------------------------------------------- #


def make_schedule(
    seed: int,
    n_faults: int,
    num_ranks: int,
    steps: int,
    ckpt_every: int,
) -> List[Dict]:
    """Deterministic fault schedule: one event per world epoch.

    Every event is guaranteed to *fire*: epoch ``e``'s fault step is drawn
    at or after the resume point of epoch ``e`` (the committed-checkpoint
    floor of the previous fault), so the rolled-back world always reaches
    it. Same seed -> identical schedule, run to run."""
    rng = random.Random(seed)
    events: List[Dict] = []
    resume = 0
    for epoch in range(n_faults):
        lo = max(resume, 1)
        if lo >= steps:
            break  # no room for another guaranteed-firing fault
        step = rng.randrange(lo, steps)
        events.append({
            "kind": rng.choice(["kill", "hang"]),
            "rank": rng.randrange(num_ranks),
            "epoch": epoch,
            "step": step,
        })
        resume = (step // ckpt_every) * ckpt_every
    return events


# --------------------------------------------------------------------- #
# Rank body (subprocess entry: --run-rank R --spec spec.json)
# --------------------------------------------------------------------- #


def run_rank(rank: int, spec_path: str) -> int:
    with open(spec_path) as f:
        spec = json.load(f)
    n = int(spec["num_processes"])
    local_devices = int(spec["local_devices"])

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")  # arealint: ok(rank-process XLA bootstrap append, not a knob read — same pattern as tests/multihost_train_script.py)
        + f" --xla_force_host_platform_device_count={local_devices}"
    )
    # the CPU "device" IS the host: dispatch-ahead depth only oversubscribes
    # the cores N rank processes already share (same rationale as
    # tests/conftest.py)
    os.environ.setdefault("AREAL_FWD_PIPELINE", "0")
    os.environ.setdefault("AREAL_TRAIN_PREFETCH", "0")

    import jax

    jax.config.update("jax_platforms", "cpu")

    from areal_tpu.base import faults, name_resolve
    from areal_tpu.parallel import elastic, multihost

    if n > 1:
        # gloo needs a distributed client; single-process (the baseline)
        # must NOT set it or backend creation fails on a None client
        multihost.enable_cpu_collectives()
        # serialize device dispatch: async-dispatched computations with
        # gloo collectives execute concurrently, and rank-dependent
        # execution order can wedge the transport (mismatched-preamble
        # aborts) — the exact flake class the elastic world must not
        # confuse with real faults
        jax.config.update("jax_cpu_enable_async_dispatch", False)
    name_resolve.reconfigure(
        name_resolve.NameResolveConfig(type="file", root=spec["nr_root"])
    )

    # arm this rank's scheduled faults (trip-style; epoch kwarg keeps a
    # relaunched incarnation from re-firing an older epoch's event)
    for ev in spec["schedule"]:
        if ev["rank"] == rank:
            faults.inject(
                "rank.kill" if ev["kind"] == "kill" else "rank.hang",
                action="trip", times=1,
                step=ev["step"], epoch=ev["epoch"],
            )

    elastic_on = n > 1
    mgr = None
    if elastic_on:
        mgr = elastic.WorldEpochManager(
            elastic.ElasticConfig(
                experiment_name=spec["experiment"],
                trial_name=spec["trial"],
                num_processes=n,
                process_id=rank,
                collective_timeout_s=float(spec["collective_timeout_s"]),
                lease_interval_s=float(spec["lease_interval_s"]),
                max_reforms=int(spec.get("max_reforms", 16)),
            )
        )
        mgr.join()
    assert jax.device_count() == n * local_devices, (
        jax.device_count(), n, local_devices
    )

    from areal_tpu.base import tracing
    from areal_tpu.system import worker_base
    from areal_tpu.system.worker_base import Heartbeat

    worker_name = (
        elastic.rank_worker_name(rank) if elastic_on else f"baseline/rank{rank}"
    )
    hb = None
    if elastic_on:
        hb = Heartbeat(
            spec["experiment"], spec["trial"], worker_name, interval=1.0,
        ).start()
    # the black box the scenario runner asserts exists per injected fault
    flight = worker_base.FlightRecorder(
        worker_name, root=spec.get("flight_root")
    ).install()

    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.models.config import ModelConfig
    from areal_tpu.ops import ppo as ppo_ops
    from areal_tpu.parallel.mesh import ParallelConfig
    from areal_tpu.train.engine import (
        OptimizerConfig,
        TrainEngine,
        vmapped_forward,
    )

    mcfg = ModelConfig(
        n_layers=2, n_q_heads=4, n_kv_heads=2, head_dim=8, hidden_dim=32,
        intermediate_dim=64, vocab_size=128, dtype="float32",
    )

    def build_engine() -> TrainEngine:
        eng = TrainEngine(
            mcfg,
            parallel=ParallelConfig.from_str(spec["parallel"]),
            optimizer=OptimizerConfig(lr=1e-3, lr_scheduler_type="constant"),
        )
        eng.init_random(0)
        eng.setup_optimizer(total_train_steps=1000)
        return eng

    def sft_loss(params, cfg_, arrays):
        logits = vmapped_forward(params, cfg_, arrays)
        lp = jax.vmap(ppo_ops.gather_packed_shifted_log_probs)(
            logits, arrays["input_ids"], arrays["segment_ids"]
        )
        seg = arrays["segment_ids"]
        has_next = (seg > 0) & ~jax.vmap(ppo_ops.is_segment_end)(seg)
        mask = has_next & ~arrays["prompt_mask"]
        return -jnp.sum(jnp.where(mask, lp, 0.0)) / jnp.maximum(
            mask.sum(), 1
        ), {}

    # identical GLOBAL batch in every configuration; this process takes a
    # strided slice of the items (same construction as the multihost test
    # world, so the single-process baseline is trajectory-comparable)
    rng = np.random.default_rng(0)
    n_items = int(spec["n_items"])
    seqlens = [int(x) for x in rng.integers(6, 14, size=n_items)]
    ids_all = rng.integers(0, 128, size=sum(seqlens)).astype(np.int64)
    pmask = np.concatenate(
        [np.r_[np.ones(2, np.bool_), np.zeros(m - 2, np.bool_)]
         for m in seqlens]
    )
    offs = np.cumsum([0] + seqlens)
    mine = list(range(rank, n_items, n))
    sample = SequenceSample.from_default(
        ids=mine,
        seqlens=[seqlens[i] for i in mine],
        data={
            "packed_input_ids": np.concatenate(
                [ids_all[offs[i]:offs[i + 1]] for i in mine]
            ),
            "prompt_mask": np.concatenate(
                [pmask[offs[i]:offs[i + 1]] for i in mine]
            ),
        },
    )

    steps = int(spec["steps"])
    ckpt_every = int(spec["ckpt_every"])
    ckpt_path = os.path.join(spec["ckpt_root"], "world")
    losses: Dict[int, float] = {}
    reforms = 0

    while True:
        # spanned so even an incarnation that trips its fault before the
        # first train step leaves span evidence in the flight dump
        with tracing.span("chaos/restore", rank=rank):
            eng = build_engine()
            try:
                eng.load_checkpoint(ckpt_path)
            except (FileNotFoundError, ValueError):
                pass  # nothing committed yet: every rank starts fresh
        try:
            for step in range(eng._step, steps):
                epoch = mgr.world.epoch if mgr is not None else 0
                if faults.maybe_trip("rank.kill", step=step, epoch=epoch):
                    logger.warning(
                        "chaos: rank.kill tripped (rank %d step %d epoch %d)",
                        rank, step, epoch,
                    )
                    flight.dump(
                        "rank.kill",
                        {"rank": rank, "step": step, "epoch": epoch},
                    )
                    os.kill(os.getpid(), signal.SIGKILL)  # hard death
                if faults.maybe_trip("rank.hang", step=step, epoch=epoch):
                    logger.warning(
                        "chaos: rank.hang tripped (rank %d step %d epoch %d)",
                        rank, step, epoch,
                    )
                    flight.dump(
                        "rank.hang",
                        {"rank": rank, "step": step, "epoch": epoch},
                    )
                    while True:  # wedged, not dead: lease keeps beating
                        time.sleep(60)
                stats = eng.train_batch(
                    sample, MicroBatchSpec(n_mbs=1), sft_loss
                )
                losses[step] = float(stats["loss"])
                if (step + 1) % ckpt_every == 0 and step + 1 < steps:
                    eng.save_checkpoint(ckpt_path)
            multihost.barrier("chaos_done")
            break
        except Exception as e:  # noqa: BLE001 — classified just below
            wf = elastic.as_world_failure(e)
            if wf is None or mgr is None:
                import traceback

                traceback.print_exc()
                elastic.hard_exit(1)
            try:
                mgr.reform(str(wf))
            except elastic.WorldFailureError:
                elastic.hard_exit(77)
            reforms += 1
            continue  # rebuild + re-restore from the committed checkpoint

    out = {
        "rank": rank,
        "final_step": steps,
        "losses": {str(k): v for k, v in sorted(losses.items())},
        "reforms": reforms,
        "final_epoch": mgr.world.epoch if mgr is not None else 0,
    }
    tmp = os.path.join(spec["out_root"], f"rank{rank}.json.tmp")
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.replace(tmp, os.path.join(spec["out_root"], f"rank{rank}.json"))
    if hb is not None:
        hb.stop()
    if mgr is not None:
        mgr.stop()
        elastic.hard_exit(0)
    return 0


# --------------------------------------------------------------------- #
# Tiny generation fleet probe (serves throughout the chaos run)
# --------------------------------------------------------------------- #


class GenFleetProbe(threading.Thread):
    """A tiny in-process generation server + a client hammering it while
    the trainer world is being killed and reformed next door — proving the
    serving side keeps answering from the last published weights and leaks
    nothing. End state lands in ``self.result``."""

    def __init__(self, interval_s: float = 0.5):
        super().__init__(name="chaos-gen-fleet", daemon=True)
        self.interval_s = interval_s
        self.stop_event = threading.Event()
        self.result: Dict = {}

    def run(self):
        import asyncio

        asyncio.run(self._main())

    async def _main(self):
        import asyncio

        import jax

        from areal_tpu.base import network
        from areal_tpu.gen.client import GenAPIClient
        from areal_tpu.gen.engine import GenerationEngine
        from areal_tpu.gen.server import serve
        from areal_tpu.models import transformer as tfm
        from areal_tpu.models.config import ModelConfig

        cfg = ModelConfig(
            n_layers=2, n_q_heads=4, n_kv_heads=2, head_dim=8,
            hidden_dim=32, intermediate_dim=64, vocab_size=128,
            dtype="float32",
        )
        eng = GenerationEngine(
            cfg, tfm.init_params(cfg, jax.random.key(7)),
            max_slots=2, max_seqlen=64,
        )
        v0 = eng.version
        port = network.find_free_port()
        runner = await serve(eng, "127.0.0.1", port, decode_steps=2)
        url = f"http://127.0.0.1:{port}"
        ok = failed = 0
        i = 0
        async with GenAPIClient(timeout=30.0) as client:
            while not self.stop_event.is_set():
                i += 1
                try:
                    r = await client.generate(
                        url, f"probe{i}", [1 + (i % 96), 2, 3],
                        {"max_new_tokens": 4, "greedy": True},
                    )
                    ok += 1 if r.output_ids else 0
                except Exception:
                    failed += 1
                await asyncio.sleep(self.interval_s)
        # drain: every slot/page must come home
        for _ in range(100):
            if eng.n_running() == 0 and eng.n_pending() == 0:
                break
            await asyncio.sleep(0.1)
        self.result = {
            "requests": i,
            "ok": ok,
            "failed": failed,
            "slots_running": eng.n_running(),
            "pending": eng.n_pending(),
            "pages_leaked": (
                eng.n_pages - eng.pool.n_free - eng.prefix.n_reclaimable()
            ),
            "version_regressed": eng.version < v0,
        }
        await runner.cleanup()


# --------------------------------------------------------------------- #
# Serving-plane soak (``make chaos-serve``)
# --------------------------------------------------------------------- #


@dataclasses.dataclass
class ServeChaosConfig:
    """Knobs for the serving-plane survivability soak (``--serve``)."""

    max_new_tokens: int = 12
    storm_requests: int = 5
    storm_deadline_s: float = 0.5
    wedge_delay_s: float = 6.0
    drain_timeout_s: float = 30.0
    run_arealint: bool = True


def run_serve_scenario(cfg: ServeChaosConfig) -> Dict:
    """Serving-plane survivability soak: two tiny identical-weight gen
    servers behind the real gateway scheduler, driven through scripted
    faults (docs/serving.md "Survivability"):

    A. **backend death mid-stream** (``gw.backend_die_midstream``): the
       stream resumes on the surviving backend and the final token
       sequence is EXACTLY the unfaulted greedy reference.
    B. **backend wedge pre-first-chunk** (``gw.backend_wedge``): the
       hedge opens on the second backend, wins, and the tokens still
       match the reference.
    C. **deadline storm** (``gw.deadline_storm``): queued requests age
       out against their deadlines and are shed IN QUEUE — zero engine
       admissions, full token-bucket refund, fair-clock restored.
    D. **brownout walk**: synthetic pressure drives the ladder up level
       by level (clamp -> shed light tenants -> admit nothing) and hysteresis + dwell walk it back down, restoring
       every lever.

    End state must leak nothing: no running slots, no pending requests,
    zero unaccounted KV pages, empty queue, settled buckets — and
    ``tools.arealint`` still exits 0."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import asyncio

    return asyncio.run(_serve_soak(cfg))


async def _serve_soak(cfg: ServeChaosConfig) -> Dict:
    import asyncio

    import jax

    from areal_tpu.base import faults, network
    from areal_tpu.base import metrics as metrics_mod
    from areal_tpu.gateway.autoscaler import ScaleSignals
    from areal_tpu.gateway.brownout import BrownoutConfig, wire_brownout
    from areal_tpu.gateway.qos import TenantSpec
    from areal_tpu.gateway.scheduler import (
        ContinuousBatchScheduler,
        GatewayRequest,
        RateLimited,
    )
    from areal_tpu.gen.client import GenAPIClient
    from areal_tpu.gen.engine import GenerationEngine
    from areal_tpu.gen.server import serve as serve_gen
    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import ModelConfig

    mcfg = ModelConfig(
        n_layers=2, n_q_heads=4, n_kv_heads=2, head_dim=8,
        hidden_dim=32, intermediate_dim=64, vocab_size=128,
        dtype="float32",
    )
    # IDENTICAL weights on both backends: greedy decode is then
    # deterministic across them, which is what makes "token-exact resume
    # after backend death" a checkable invariant
    params = tfm.init_params(mcfg, jax.random.key(7))
    engines = [
        GenerationEngine(mcfg, params, max_slots=2, max_seqlen=64)
        for _ in range(2)
    ]
    runners, urls = [], []
    for eng in engines:
        port = network.find_free_port()
        runners.append(
            await serve_gen(eng, "127.0.0.1", port, decode_steps=2)
        )
        urls.append(f"http://127.0.0.1:{port}")

    sched = ContinuousBatchScheduler(
        list(urls),
        tenants={
            # near-zero refill: the post-shed bucket level proves REFUNDS,
            # not refill, restored the balance
            "lim": TenantSpec(
                name="lim", rate_tokens_per_s=0.01, burst_tokens=10_000.0
            ),
            "cheap": TenantSpec(name="cheap", weight=0.5),
        },
        default_tenant=TenantSpec(name="anonymous"),
        metrics_poll_interval=0.5,
        hedge_min_delay_s=30.0,  # scenario B lowers it explicitly
        deadline_sweep_interval_s=0.1,
    )
    await sched.start()

    violations: List[str] = []
    report: Dict = {"scenarios": {}}
    prompt = [5, 6, 7]
    sp = {"max_new_tokens": cfg.max_new_tokens, "greedy": True}

    async def collect(req):
        sched.submit(req)
        toks: List[int] = []
        last = {}
        async for ev in sched.events(req):
            toks.extend(ev.get("token_ids", []))
            last = ev
        return toks, last.get("finish_reason")

    def counter(name) -> float:
        return metrics_mod.counters.get(name)

    async with GenAPIClient(timeout=60.0) as cl:
        try:
            # warm BOTH backends (absorb jit compile) so latency
            # estimates and hedge timing are not dominated by the first
            # request's compilation
            for u in urls:
                sched.set_servers([u])
                await collect(
                    GatewayRequest.build("anonymous", prompt, dict(sp))
                )
            sched.set_servers(list(urls))
            # drop compile-dominated warmup TTFTs so the live p95 (the
            # hedge-delay floor) reflects steady-state latency
            metrics_mod.counters.clear(metrics_mod.GW_TTFT_S)

            # unfaulted greedy reference
            ref_toks, ref_fin = await collect(
                GatewayRequest.build("anonymous", prompt, dict(sp))
            )
            if len(ref_toks) != cfg.max_new_tokens:
                violations.append(
                    f"reference run produced {len(ref_toks)} tokens, "
                    f"expected {cfg.max_new_tokens}"
                )

            # A: kill the backend mid-stream -> token-exact resume
            resumes0 = counter(metrics_mod.GW_STREAM_RESUMES)
            faults.inject(
                "gw.backend_die_midstream", action="fail", times=1, after=2
            )
            a_toks, a_fin = await collect(
                GatewayRequest.build("anonymous", prompt, dict(sp))
            )
            faults.reset()
            resumed = counter(metrics_mod.GW_STREAM_RESUMES) - resumes0
            report["scenarios"]["die_midstream"] = {
                "tokens_match": a_toks == ref_toks,
                "finish": a_fin,
                "stream_resumes": resumed,
            }
            if a_toks != ref_toks:
                violations.append(
                    f"resume after backend death not token-exact: "
                    f"{a_toks} != {ref_toks}"
                )
            if resumed < 1:
                violations.append("backend death triggered no stream resume")
            await sched.poll_capacity()  # re-admit the 'dead' backend

            # B: wedge the primary pre-first-chunk -> the hedge wins
            hedges0 = counter(metrics_mod.GW_HEDGES)
            wins0 = counter(metrics_mod.GW_HEDGE_WINS)
            sched.hedge_min_delay_s = 1.0
            faults.inject(
                "gw.backend_wedge", action="delay",
                delay_s=cfg.wedge_delay_s, times=1,
            )
            b_toks, b_fin = await collect(
                GatewayRequest.build("anonymous", prompt, dict(sp))
            )
            faults.reset()
            sched.hedge_min_delay_s = 30.0
            hedged = counter(metrics_mod.GW_HEDGES) - hedges0
            won = counter(metrics_mod.GW_HEDGE_WINS) - wins0
            report["scenarios"]["wedge_hedge"] = {
                "tokens_match": b_toks == ref_toks,
                "finish": b_fin,
                "hedges": hedged,
                "hedge_wins": won,
            }
            if b_toks != ref_toks:
                violations.append(
                    f"hedged stream not token-exact: {b_toks} != {ref_toks}"
                )
            if hedged < 1 or won < 1:
                violations.append(
                    f"wedge did not produce a winning hedge "
                    f"(hedges={hedged}, wins={won})"
                )

            # C: deadline storm — zero dispatch capacity, queued requests
            # age out in the fair queue and never touch a backend
            shed0 = counter(metrics_mod.GW_DEADLINE_SHED)
            admitted0 = [eng.stats["admitted"] for eng in engines]
            faults.inject("gw.deadline_storm", action="trip", times=100_000)
            storm = [
                GatewayRequest.build(
                    "lim", prompt, dict(sp),
                    deadline_s=cfg.storm_deadline_s,
                )
                for _ in range(cfg.storm_requests)
            ]
            results = await asyncio.gather(
                *(collect(r) for r in storm), return_exceptions=True
            )
            faults.reset()
            bad = [r for r in results if isinstance(r, BaseException)]
            if bad:
                violations.append(f"storm stream raised: {bad[0]!r}")
                results = [
                    r for r in results if not isinstance(r, BaseException)
                ]
            sched._wake.set()
            shed = counter(metrics_mod.GW_DEADLINE_SHED) - shed0
            admitted_delta = [
                eng.stats["admitted"] - a0
                for eng, a0 in zip(engines, admitted0)
            ]
            bucket = sched._bucket("lim")
            report["scenarios"]["deadline_storm"] = {
                "finishes": [fin for _, fin in results],
                "deadline_shed": shed,
                "backend_admissions": admitted_delta,
                "bucket_available": bucket.available,
            }
            if any(fin != "deadline" for _, fin in results):
                violations.append(
                    f"storm finishes {[f for _, f in results]} "
                    "(expected all 'deadline')"
                )
            if shed != cfg.storm_requests:
                violations.append(
                    f"gw/deadline_shed advanced {shed}, expected "
                    f"{cfg.storm_requests}"
                )
            if any(admitted_delta):
                violations.append(
                    f"deadline-shed requests reached a backend: "
                    f"admissions {admitted_delta}"
                )
            if bucket.available < bucket.burst - 1.0:
                violations.append(
                    f"token bucket not refunded after storm: "
                    f"{bucket.available} / {bucket.burst}"
                )
            # rollback must leave 'lim' with NO residual service debt:
            # its finish tag may not sit past the global virtual clock,
            # so its next push starts exactly where an innocent tenant's
            # would
            vft = sched._wfq._last_vft.get("lim", 0.0)
            if vft > sched._wfq._vtime + 1e-6:
                violations.append(
                    f"fair-queue clock not restored after storm: "
                    f"lim vft {vft} > vtime {sched._wfq._vtime}"
                )

            # D: brownout walk — up the ladder level by level on synthetic
            # pressure, back down under hysteresis + dwell
            trans0 = counter(metrics_mod.GW_BROWNOUT_TRANSITIONS)
            bcfg = BrownoutConfig(min_hold_s=5.0, clamp_max_tokens=8)
            fake_t = [0.0]

            class _GwCfg:
                brownout_max_tokens = None

            gw_cfg = _GwCfg()
            ctrl = wire_brownout(
                bcfg, sched, gw_cfg, clock=lambda: fake_t[0]
            )
            sig = [ScaleSignals(routed=2, healthy=2)]
            ctrl.fetch_signals = lambda: sig[0]

            async def walk(kv, advance=6.0):
                fake_t[0] += advance
                sig[0] = dataclasses.replace(sig[0], kv_occupancy=kv)
                return await ctrl.step_once()

            levels = [await walk(kv) for kv in (0.92, 0.975)]
            # level 2: a below-floor tenant is shed with an honest hint
            shed_ok = pause_ok = False
            try:
                sched.submit(
                    GatewayRequest.build("cheap", prompt, dict(sp))
                )
            except RateLimited as e:
                shed_ok = e.retry_after_s > 0
            levels.append(await walk(0.995))
            clamp_at_top = gw_cfg.brownout_max_tokens
            # level 3: nobody new gets in
            try:
                sched.submit(
                    GatewayRequest.build("anonymous", prompt, dict(sp))
                )
            except RateLimited as e:
                pause_ok = e.retry_after_s > 0
            # hysteresis: barely below the level-3 entry is NOT enough to
            # step down, even after the dwell
            held = await walk(0.985)
            down = [await walk(0.10) for _ in range(3)]
            transitions = counter(
                metrics_mod.GW_BROWNOUT_TRANSITIONS
            ) - trans0
            report["scenarios"]["brownout_walk"] = {
                "up": levels,
                "held_at": held,
                "down": down,
                "clamp_at_top": clamp_at_top,
                "clamp_after": gw_cfg.brownout_max_tokens,
                "shed_429": shed_ok,
                "pause_429": pause_ok,
                "transitions": transitions,
            }
            if levels != [1, 2, 3]:
                violations.append(f"brownout escalation walked {levels}")
            if held != 3:
                violations.append(
                    f"hysteresis failed: stepped to {held} on a barely-"
                    "recovered signal"
                )
            if down != [2, 1, 0]:
                violations.append(f"brownout de-escalation walked {down}")
            if clamp_at_top != bcfg.clamp_max_tokens:
                violations.append("level 1 did not clamp max_tokens")
            if gw_cfg.brownout_max_tokens is not None:
                violations.append("recovery did not remove the clamp")
            if not shed_ok:
                violations.append(
                    "level 2 did not shed the below-floor tenant"
                )
            if not pause_ok:
                violations.append("level 3 admitted a new request")
            if transitions != 6:
                violations.append(
                    f"counted {transitions} brownout transitions, "
                    "expected 6 (3 up + 3 down; the held step is free)"
                )
            if sched.admit_paused or sched.shed_weight_floor:
                violations.append("brownout levers left engaged at level 0")
        finally:
            faults.reset()
            # drain: every slot, page and charge must come home
            deadline = time.monotonic() + cfg.drain_timeout_s
            while time.monotonic() < deadline:
                if all(
                    eng.n_running() == 0 and eng.n_pending() == 0
                    for eng in engines
                ) and sched.inflight() == 0 and sched.queue_depth() == 0:
                    break
                await asyncio.sleep(0.2)
            leaks = {
                "slots_running": [eng.n_running() for eng in engines],
                "pending": [eng.n_pending() for eng in engines],
                "pages_leaked": [
                    eng.n_pages - eng.pool.n_free
                    - eng.prefix.n_reclaimable()
                    for eng in engines
                ],
                "gateway_queue": sched.queue_depth(),
                "gateway_inflight": sched.inflight(),
            }
            report["leaks"] = leaks
            if any(leaks["slots_running"]) or any(leaks["pending"]):
                violations.append(f"engine slots leaked: {leaks}")
            if any(leaks["pages_leaked"]):
                violations.append(
                    f"KV pages leaked: {leaks['pages_leaked']}"
                )
            if leaks["gateway_queue"] or leaks["gateway_inflight"]:
                violations.append(
                    f"gateway queue/inflight not drained: {leaks}"
                )
            await sched.stop()
            for r in runners:
                await r.cleanup()

    if cfg.run_arealint:
        import subprocess

        rc = subprocess.call(
            [sys.executable, "-m", "tools.arealint"], cwd=_REPO_ROOT,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )
        report["arealint_rc"] = rc
        if rc != 0:
            violations.append(f"arealint exited {rc}")

    report["violations"] = [v for v in violations if v]
    report["ok"] = not report["violations"]
    return report


# --------------------------------------------------------------------- #
# Scenario runner
# --------------------------------------------------------------------- #


@dataclasses.dataclass
class ChaosConfig:
    seed: int = 1
    n_faults: int = 1
    num_ranks: int = 4
    local_devices: int = 2
    parallel: str = "d2f2m2"
    steps: int = 10
    ckpt_every: int = 3
    n_items: int = 12
    collective_timeout_s: float = 30.0
    lease_interval_s: float = 1.0
    report_grace_s: float = 6.0
    recovery_bound_s: float = 240.0
    loss_rtol: float = 2e-4
    timeout_s: float = 900.0
    with_gen: bool = True
    root: Optional[str] = None           # scenario dir (default: mkdtemp)
    schedule: Optional[List[Dict]] = None  # explicit (tests); else seeded


def _rank_cmd(spec_path: str):
    def cmd(rank: int) -> List[str]:
        return [
            sys.executable, "-m", "tools.chaos",
            "--run-rank", str(rank), "--spec", spec_path,
        ]
    return cmd


def run_scenario(cfg: ChaosConfig) -> Dict:
    """Run one seeded chaos scenario end to end; returns the report dict
    (``report["ok"]`` is the overall verdict, ``report["violations"]``
    names every failed invariant)."""
    import tempfile

    from areal_tpu.apps.launcher import WorldSupervisor, WorldSupervisorConfig
    from areal_tpu.base import name_resolve
    from areal_tpu.base import metrics as metrics_mod
    from areal_tpu.parallel import elastic

    root = cfg.root or tempfile.mkdtemp(prefix="areal_chaos_")
    nr_root = os.path.join(root, "name_resolve")
    out_root = os.path.join(root, "out")
    ckpt_root = os.path.join(root, "ckpt")
    log_dir = os.path.join(root, "logs")
    flight_root = os.path.join(root, "flight")
    for d in (nr_root, out_root, ckpt_root, log_dir, flight_root):
        os.makedirs(d, exist_ok=True)

    schedule = (
        cfg.schedule
        if cfg.schedule is not None
        else make_schedule(
            cfg.seed, cfg.n_faults, cfg.num_ranks, cfg.steps, cfg.ckpt_every
        )
    )
    experiment, trial = "chaos", f"seed{cfg.seed}"
    spec = {
        "experiment": experiment,
        "trial": trial,
        "nr_root": nr_root,
        "out_root": out_root,
        "ckpt_root": ckpt_root,
        "num_processes": cfg.num_ranks,
        "local_devices": cfg.local_devices,
        "parallel": cfg.parallel,
        "steps": cfg.steps,
        "ckpt_every": cfg.ckpt_every,
        "n_items": cfg.n_items,
        "collective_timeout_s": cfg.collective_timeout_s,
        "lease_interval_s": cfg.lease_interval_s,
        "schedule": schedule,
        "flight_root": flight_root,
    }
    spec_path = os.path.join(root, "spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f, indent=2)

    # the baseline: the SAME global batch, single process, all devices,
    # no faults — the trajectory the chaotic world must reproduce
    base_spec = dict(
        spec,
        num_processes=1,
        local_devices=cfg.local_devices * cfg.num_ranks,
        schedule=[],
        ckpt_root=os.path.join(root, "ckpt_base"),
        out_root=os.path.join(root, "out_base"),
    )
    for d in (base_spec["ckpt_root"], base_spec["out_root"]):
        os.makedirs(d, exist_ok=True)
    base_spec_path = os.path.join(root, "spec_base.json")
    with open(base_spec_path, "w") as f:
        json.dump(base_spec, f, indent=2)

    import subprocess

    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    t_base = time.monotonic()
    with open(os.path.join(log_dir, "baseline.log"), "wb") as bl:
        rc_base = subprocess.call(
            _rank_cmd(base_spec_path)(0), env=env,
            stdout=bl, stderr=subprocess.STDOUT,
        )
    baseline = None
    if rc_base == 0:
        with open(os.path.join(base_spec["out_root"], "rank0.json")) as f:
            baseline = json.load(f)

    # point the runner's own name_resolve at the scenario root (restored
    # on exit so an embedding test suite keeps its repository)
    prev_repo = name_resolve.default_repository()
    name_resolve.set_repository(
        name_resolve.make_repository(
            name_resolve.NameResolveConfig(type="file", root=nr_root)
        )
    )
    probe = None
    restarts_before = metrics_mod.counters.get(metrics_mod.FT_RANK_RESTARTS)
    epochs_before = metrics_mod.counters.get(metrics_mod.FT_WORLD_EPOCHS)
    try:
        if cfg.with_gen:
            probe = GenFleetProbe()
            probe.start()
        sup = WorldSupervisor(
            WorldSupervisorConfig(
                experiment_name=experiment,
                trial_name=trial,
                num_processes=cfg.num_ranks,
                rank_cmd=_rank_cmd(spec_path),
                rank_env={
                    "PYTHONPATH": env["PYTHONPATH"],
                    "AREAL_FILEROOT": root,
                },
                collective_timeout_s=cfg.collective_timeout_s,
                report_grace_s=cfg.report_grace_s,
                max_rank_restarts=max(len(schedule) * 2, 4),
                log_dir=log_dir,
            )
        )
        t0 = time.monotonic()
        sup.start()
        rc_world = sup.run(timeout=cfg.timeout_s)
        world_wall = time.monotonic() - t0
        if probe is not None:
            probe.stop_event.set()
            probe.join(timeout=60)

        ranks = {}
        for r in range(cfg.num_ranks):
            p = os.path.join(out_root, f"rank{r}.json")
            if os.path.exists(p):
                with open(p) as f:
                    ranks[r] = json.load(f)
        leases = elastic.read_leases(experiment, trial)
        status_keys = name_resolve.find_subtree(
            f"areal_tpu/{experiment}/{trial}/worker_status"
        )
    finally:
        name_resolve.set_repository(prev_repo)

    flight_dumps: List[Dict] = []
    for p in sorted(glob.glob(os.path.join(flight_root, "*.json"))):
        try:
            with open(p) as f:
                flight_dumps.append(json.load(f))
        except (OSError, ValueError):
            flight_dumps.append({"reason": "unreadable", "path": p})

    report = {
        "root": root,
        "seed": cfg.seed,
        "schedule": schedule,
        "baseline_rc": rc_base,
        "baseline_wall_s": round(time.monotonic() - t_base, 1),
        "world_rc": rc_world,
        "world_wall_s": round(world_wall, 1),
        "rank_restarts": sup.rank_restarts,
        "world_epochs": sup.epoch,
        "recovery_times_s": [round(t, 1) for t in sup.recovery_times],
        "ranks_reported": sorted(ranks),
        "flight_dumps": [
            {
                "worker": d.get("worker"),
                "reason": d.get("reason"),
                "extra": d.get("extra"),
                "spans": len(d.get("spans") or []),
                "log_lines": len(d.get("log_tail") or []),
            }
            for d in flight_dumps
        ],
        "gen": probe.result if probe is not None else None,
        "counters": {
            "ft/rank_restarts": metrics_mod.counters.get(
                metrics_mod.FT_RANK_RESTARTS
            ) - restarts_before,
            "ft/world_epochs": metrics_mod.counters.get(
                metrics_mod.FT_WORLD_EPOCHS
            ) - epochs_before,
        },
    }
    report["violations"] = _violations(
        cfg, schedule, baseline, ranks, leases, status_keys, sup,
        rc_world, probe, flight_dumps,
    )
    report["ok"] = rc_world == 0 and not report["violations"]
    return report


def _violations(
    cfg, schedule, baseline, ranks, leases, status_keys, sup, rc_world,
    probe, flight_dumps=(),
) -> List[str]:
    v: List[str] = []
    if rc_world != 0:
        v.append(f"world did not complete cleanly (rc={rc_world})")
    if baseline is None:
        v.append("baseline run failed")
    missing = [r for r in range(cfg.num_ranks) if r not in ranks]
    if missing:
        v.append(f"ranks {missing} reported no output")
    if v:
        return v
    # loss continuity vs the unfaulted baseline: every loss any rank
    # recorded must match the baseline at that step (a relaunched rank
    # only has steps from its resume point on — the union must still
    # cover the whole run), and the FINAL step must match on every rank.
    base_losses = baseline["losses"]
    covered = set()
    for r, out in ranks.items():
        for step_s, fl in out["losses"].items():
            bl = base_losses.get(step_s)
            if bl is None:
                v.append(f"rank {r} recorded unknown step {step_s}")
                break
            covered.add(step_s)
            if abs(fl - bl) > cfg.loss_rtol * max(1.0, abs(bl)):
                v.append(
                    f"rank {r} step {step_s}: loss {fl} != baseline {bl} "
                    "(trajectory diverged across recovery)"
                )
                break
        if str(cfg.steps - 1) not in out["losses"]:
            v.append(f"rank {r} did not reach the final step")
    missing_steps = sorted(set(base_losses) - covered, key=int)
    if missing_steps:
        v.append(f"no rank recorded steps {missing_steps}")
    # accounting: every scheduled fault fired -> one rank restart + one
    # world epoch each
    if sup.rank_restarts != len(schedule):
        v.append(
            f"rank_restarts={sup.rank_restarts}, scheduled faults="
            f"{len(schedule)}"
        )
    if sup.epoch != len(schedule):
        v.append(f"world_epochs={sup.epoch}, expected {len(schedule)}")
    # bounded recovery
    slow = [t for t in sup.recovery_times if t > cfg.recovery_bound_s]
    if slow:
        v.append(f"recovery times over bound {cfg.recovery_bound_s}s: {slow}")
    # flight recorder: every injected rank fault must leave a black box
    # with span, counter-delta, and log-tail evidence
    # (docs/observability.md "Crash flight recorder")
    for ev in schedule:
        reason = f"rank.{ev['kind']}"
        match = [
            d for d in flight_dumps
            if d.get("reason") == reason
            and (d.get("extra") or {}).get("rank") == ev["rank"]
            and (d.get("extra") or {}).get("epoch") == ev["epoch"]
        ]
        if not match:
            v.append(f"no flight-recorder dump for injected fault {ev}")
            continue
        d = match[0]
        if not d.get("spans"):
            v.append(f"flight dump for {ev} has no span evidence")
        if not d.get("counters"):
            v.append(f"flight dump for {ev} has no counter deltas")
        if not d.get("log_tail"):
            v.append(f"flight dump for {ev} has no log tail")
    # lease/heartbeat hygiene: exactly one lease per rank, all at the
    # final epoch; no ghost heartbeat keys from dead incarnations
    if sorted(leases) != list(range(cfg.num_ranks)):
        v.append(f"leases for ranks {sorted(leases)} (hygiene leak?)")
    stale = [
        r for r, d in leases.items() if d.get("epoch") != sup.epoch
    ]
    if stale:
        v.append(f"leases at stale epochs for ranks {stale}")
    rank_status = [k for k in status_keys if "/trainer/rank" in k]
    if len(rank_status) != cfg.num_ranks:
        v.append(
            f"{len(rank_status)} rank heartbeat keys for "
            f"{cfg.num_ranks} ranks: {rank_status}"
        )
    # the serving side never stopped answering and leaked nothing
    if probe is not None:
        g = probe.result
        if not g:
            v.append("gen fleet probe produced no result")
        else:
            if g["failed"]:
                v.append(f"gen fleet failed {g['failed']} requests")
            if g["ok"] < 1:
                v.append("gen fleet served no successful request")
            if g["slots_running"] or g["pending"]:
                v.append(
                    f"gen slots leaked: running={g['slots_running']} "
                    f"pending={g['pending']}"
                )
            if g["pages_leaked"]:
                v.append(f"gen pages leaked: {g['pages_leaked']}")
            if g["version_regressed"]:
                v.append("gen weight version regressed")
    return v


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="tools.chaos", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    p.add_argument("--run-rank", type=int, default=None,
                   help="internal: run one rank body")
    p.add_argument("--spec", default=None, help="internal: rank spec json")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--faults", type=int, default=1)
    p.add_argument("--ranks", type=int, default=4)
    p.add_argument("--local-devices", type=int, default=2)
    p.add_argument("--parallel", default="d2f2m2")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--ckpt-every", type=int, default=3)
    p.add_argument("--collective-timeout", type=float, default=30.0)
    p.add_argument("--recovery-bound", type=float, default=240.0)
    p.add_argument("--timeout", type=float, default=900.0)
    p.add_argument("--no-gen", action="store_true",
                   help="skip the serving-side probe")
    p.add_argument("--serve", action="store_true",
                   help="run the serving-plane survivability soak instead "
                        "of the training-world scenario")
    p.add_argument("--out", default=None, help="write the report JSON here")
    args = p.parse_args(argv)

    if args.run_rank is not None:
        if not args.spec:
            p.error("--run-rank requires --spec")
        return run_rank(args.run_rank, args.spec)

    if args.serve:
        report = run_serve_scenario(ServeChaosConfig())
        text = json.dumps(report, indent=2)
        print(text)
        if args.out:
            with open(args.out, "w") as f:
                f.write(text + "\n")
        if report["ok"]:
            print("CHAOS-SERVE OK: all invariants hold", file=sys.stderr)
            return 0
        print(
            f"CHAOS-SERVE FAILED: {len(report['violations'])} violation(s)",
            file=sys.stderr,
        )
        return 1

    cfg = ChaosConfig(
        seed=args.seed,
        n_faults=args.faults,
        num_ranks=args.ranks,
        local_devices=args.local_devices,
        parallel=args.parallel,
        steps=args.steps,
        ckpt_every=args.ckpt_every,
        collective_timeout_s=args.collective_timeout,
        recovery_bound_s=args.recovery_bound,
        timeout_s=args.timeout,
        with_gen=not args.no_gen,
    )
    report = run_scenario(cfg)
    text = json.dumps(report, indent=2)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if report["ok"]:
        print("CHAOS OK: all invariants hold", file=sys.stderr)
        return 0
    print(
        f"CHAOS FAILED: {len(report['violations'])} violation(s)",
        file=sys.stderr,
    )
    return 1


if __name__ == "__main__":
    sys.exit(main())
