"""Process-global experiment context (≈ ``realhf/base/constants.py``).

Holds the (experiment, trial) identity, filesystem roots, and debug env-var
knobs. Unlike the reference there is no per-model 3D-parallel "model scope" —
on TPU the parallel context is the ambient ``jax.sharding.Mesh`` managed by
:mod:`areal_tpu.parallel.mesh`.
"""

import getpass
import logging as _logging  # stdlib only — base/logging.py imports US
import os
from typing import Optional

_logger = _logging.getLogger("areal_tpu.constants")

_experiment_name: Optional[str] = None
_trial_name: Optional[str] = None

# Env-var knobs (AREAL_* ≈ the reference's REAL_*).
TRACE_ENV = "AREAL_DUMP_TRACE"          # jax.profiler traces per MFC
MEMORY_KILL_ENV = "AREAL_HBM_KILL_THRESHOLD"
MEMORY_WARN_ENV = "AREAL_HBM_WARN_THRESHOLD"
# Host↔device data-plane pipelining (docs/pipelined_data_plane.md). Both
# default ON; "0"/"false"/"off" disables, an integer sets the depth.
FWD_PIPELINE_ENV = "AREAL_FWD_PIPELINE"       # dispatch-ahead forward()
TRAIN_PREFETCH_ENV = "AREAL_TRAIN_PREFETCH"   # minibatch prefetch + deferred stats
# Trainer survivability (docs/fault_tolerance.md "Trainer survivability").
TRAIN_GUARD_ENV = "AREAL_TRAIN_GUARD"         # on-device finite-ness guard (default on)
PREEMPT_DEADLINE_ENV = "AREAL_PREEMPT_DEADLINE_S"  # SIGTERM -> ckpt-save budget
WATCHDOG_TIMEOUT_ENV = "AREAL_WATCHDOG_TIMEOUT_S"  # 0/unset disables the watchdog
WATCHDOG_ABORT_ENV = "AREAL_WATCHDOG_ABORT"   # dump AND exit so the scheduler restarts
# Fleet telemetry plane (docs/observability.md): per-worker counter/
# histogram snapshot export interval.
TELEMETRY_EXPORT_ENV = "AREAL_TELEMETRY_EXPORT"
# Distributed request tracing + crash flight recorder
# (docs/observability.md "Distributed tracing").
TRACE_SPANS_ENV = "AREAL_TRACE_SPANS"        # span ring + trace-id propagation
TRACE_RING_ENV = "AREAL_TRACE_RING"          # completed-span ring capacity
TRACE_FLUSH_ENV = "AREAL_TRACE_FLUSH_S"      # dedicated span-flush period
TRACE_LOG_TAIL_ENV = "AREAL_TRACE_LOG_TAIL"  # flight-recorder log-tail lines
# KV-pool quantization (docs/performance.md "KV quantization").
KV_DTYPE_ENV = "AREAL_KV_DTYPE"         # paged KV pool storage dtype
# Elastic multihost (docs/fault_tolerance.md "Elastic multihost").
ELASTIC_ENV = "AREAL_ELASTIC"                    # surgical rank recovery
COLLECTIVE_TIMEOUT_ENV = "AREAL_COLLECTIVE_TIMEOUT_S"  # bounded host collectives
ELASTIC_LEASE_INTERVAL_ENV = "AREAL_ELASTIC_LEASE_INTERVAL_S"
ELASTIC_MAX_REFORMS_ENV = "AREAL_ELASTIC_MAX_REFORMS"  # then restart-the-world
# Serving gateway (docs/serving.md): OpenAI-compatible frontend knobs.
GATEWAY_PORT_ENV = "AREAL_GATEWAY_PORT"          # 0 = pick a free port
GATEWAY_RATE_TPS_ENV = "AREAL_GW_RATE_TPS"       # per-tenant token bucket
GATEWAY_BURST_ENV = "AREAL_GW_BURST"             # token-bucket burst size
GATEWAY_MAX_QUEUE_ENV = "AREAL_GW_MAX_QUEUE"     # gateway queue cap
GATEWAY_ADMIT_OCC_ENV = "AREAL_GW_ADMIT_OCCUPANCY"  # KV-pool admit gate
GATEWAY_HEDGE_ENV = "AREAL_GW_HEDGE"             # hedged dispatch on/off
GATEWAY_DEADLINE_S_ENV = "AREAL_GW_DEADLINE_S"   # default request deadline


# --------------------------------------------------------------------- #
# Knob catalog.
#
# Every AREAL_* env knob is READ here (or through a tolerant
# ``worker_base._env_*`` parser) — enforced statically by the ``env-knob``
# rule of ``tools/arealint`` — so each knob has exactly one documented
# default and the ``get_env_vars`` forwarding list below can't silently
# drift from reality. Modules expose semantics (what a knob means); this
# module owns parsing (how it is read).
# --------------------------------------------------------------------- #

_OFF_STRINGS = ("", "0", "false", "off", "no", "n")


def env_str(name: str, default: Optional[str] = None) -> Optional[str]:
    return os.environ.get(name, default)


def env_flag(name: str, default: bool = False) -> bool:
    """Boolean knob: unset -> ``default``; ""/"0"/"false"/"off" -> False;
    anything else -> True."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw.strip().lower() not in _OFF_STRINGS


def env_float(name: str, default: float) -> float:
    """Tolerant float knob: malformed values fall back to the default
    (logged) instead of crashing a worker at startup."""
    raw = os.environ.get(name)
    if raw is None or raw.strip() == "":
        return default
    try:
        return float(raw)
    except ValueError:
        _logger.warning(
            "ignoring malformed %s=%r (using %s)", name, raw, default
        )
        return default


def env_int(name: str, default: int) -> int:
    raw = os.environ.get(name)
    if raw is None or raw.strip() == "":
        return default
    try:
        return int(raw)
    except ValueError:
        _logger.warning(
            "ignoring malformed %s=%r (using %s)", name, raw, default
        )
        return default


def env_knob(name: str, default_depth: int) -> int:
    """Pipeline-depth knob: unset/"true"/"on" -> the default depth,
    "false"/"off" -> 0 (disabled), an integer -> exactly that depth (so
    "1" really means depth 1, the serial discipline — not "enabled")."""
    v = os.environ.get(name)
    if v is None or v.strip().lower() in ("", "true", "on"):
        return default_depth
    if v.strip().lower() in ("false", "off"):
        return 0
    try:
        return max(int(v), 0)
    except ValueError:
        return default_depth


def log_level() -> str:
    """``AREAL_LOG_LEVEL``: root log level for every areal logger."""
    return (env_str("AREAL_LOG_LEVEL", "INFO") or "INFO").upper()


def hbm_warn_threshold() -> float:
    """``AREAL_HBM_WARN_THRESHOLD`` (default 0.92): fraction of
    bytes_limit past which the HBM monitor logs a warning."""
    return env_float(MEMORY_WARN_ENV, 0.92)


def hbm_kill_threshold() -> float:
    """``AREAL_HBM_KILL_THRESHOLD`` (default 1.0 = disabled): fraction of
    bytes_limit past which the worker raises HBMPressureError."""
    return env_float(MEMORY_KILL_ENV, 1.0)


def hbm_fallback_interval() -> float:
    """``AREAL_HBM_FALLBACK_INTERVAL`` (default 1.0s): min seconds between
    jax.live_arrays() walks on platforms without memory_stats()."""
    return env_float("AREAL_HBM_FALLBACK_INTERVAL", 1.0)


def hbm_check_secs() -> float:
    """``AREAL_HBM_CHECK_SECS`` (default 30.0): wall-clock period of the
    gen server's HBM kill check (memory_stats can be a full RPC)."""
    return env_float("AREAL_HBM_CHECK_SECS", 30.0)


def name_resolve_root() -> str:
    """``AREAL_NAME_RESOLVE_ROOT``: shared-FS root of the file-backed
    name-resolve repository."""
    return env_str(
        "AREAL_NAME_RESOLVE_ROOT", "/tmp/areal_tpu/name_resolve"
    )


def name_resolve_rpc() -> Optional[str]:
    """``AREAL_NAME_RESOLVE_RPC``: ``host:port`` of the TCP name-resolve
    server (multi-node without a shared FS); None -> file backend."""
    return env_str("AREAL_NAME_RESOLVE_RPC")


def trace_enabled() -> bool:
    """``AREAL_DUMP_TRACE``: collect jax.profiler traces per step/MFC."""
    return env_flag(TRACE_ENV, False)


def trace_step() -> int:
    """``AREAL_TRACE_STEP`` (default 3): which training step the trainers
    dump (tracing every step would grow unboundedly)."""
    return env_int("AREAL_TRACE_STEP", 3)


def debug_checks_enabled() -> bool:
    """``AREAL_DEBUG_CHECKS``: extra device-side shape/degenerate-input
    checks in the pallas kernels (read at TRACE time)."""
    return env_flag("AREAL_DEBUG_CHECKS", False)


def flash_bwd_pipeline_enabled() -> bool:
    """``AREAL_FLASH_BWD_PIPELINE`` (default off): cross-block software
    pipelining in the fused flash-attention backward."""
    return env_flag("AREAL_FLASH_BWD_PIPELINE", False)


def decode_pipeline_enabled() -> bool:
    """``AREAL_DECODE_PIPELINE`` (default off): harvest decode chunks one
    late so the per-chunk host sync overlaps the next chunk's compute."""
    return env_flag("AREAL_DECODE_PIPELINE", False)


def kv_dtype() -> Optional[str]:
    """``AREAL_KV_DTYPE`` (default unset = serving dtype, i.e. raw bf16
    pages): paged-KV pool storage dtype for generation engines. ``"int8"``
    stores quantized pages with per-(page-slot, kv-head) scales — half the
    decode HBM KV traffic, 2x resident pages at fixed pool HBM
    (docs/performance.md "KV quantization"). Default stays the serving
    dtype: the int8 pool is unjudged, no benchmark cell has run it
    (ROADMAP D1). Unknown values
    fall back to unset (logged), not crash — same contract as the other
    tolerant knobs. An explicit ``cfg.kv_dtype`` / engine argument
    overrides this knob."""
    raw = env_str(KV_DTYPE_ENV)
    if raw is None or not raw.strip():
        return None
    v = raw.strip().lower()
    if v == "int8":
        return "int8"
    if v in ("bf16", "bfloat16"):
        return "bf16"
    _logger.warning(
        "ignoring unknown %s=%r (using the serving dtype)", KV_DTYPE_ENV, raw
    )
    return None


def gateway_port() -> int:
    """``AREAL_GATEWAY_PORT`` (default 0 = pick a free port): TCP port the
    OpenAI-compatible serving gateway binds (docs/serving.md)."""
    return env_int(GATEWAY_PORT_ENV, 0)


def gateway_rate_tps() -> float:
    """``AREAL_GW_RATE_TPS`` (default 0 = unlimited): default per-tenant
    token-bucket refill rate in tokens/second (prompt + budgeted new
    tokens are charged at admission; unused budget is refunded at
    completion). Per-tenant overrides come from the gateway config."""
    return env_float(GATEWAY_RATE_TPS_ENV, 0.0)


def gateway_burst() -> float:
    """``AREAL_GW_BURST`` (default 0 = 4x the refill rate, itself 0 =
    unlimited): default per-tenant token-bucket burst capacity."""
    return env_float(GATEWAY_BURST_ENV, 0.0)


def gateway_max_queue() -> int:
    """``AREAL_GW_MAX_QUEUE`` (default 256): gateway-wide cap on queued
    (not yet dispatched) requests; past it new requests get 429."""
    return env_int(GATEWAY_MAX_QUEUE_ENV, 256)


def gateway_admit_occupancy() -> float:
    """``AREAL_GW_ADMIT_OCCUPANCY`` (default 0.95): KV-pool occupancy
    fraction past which the gateway stops dispatching to a server (the
    request waits in the fair queue instead of deep-queuing behind a
    full pool)."""
    return env_float(GATEWAY_ADMIT_OCC_ENV, 0.95)


def gateway_hedge() -> bool:
    """``AREAL_GW_HEDGE`` (default on): hedge a still-unstarted request to
    a second healthy backend once its time-to-first-token exceeds the live
    ``gw/ttft_s`` p95 (docs/serving.md "Survivability"). The loser is
    cancelled; hedge volume is capped per tenant."""
    return env_flag(GATEWAY_HEDGE_ENV, True)


def gateway_deadline_s() -> float:
    """``AREAL_GW_DEADLINE_S`` (default 0 = none): default per-request
    deadline in seconds for tenants without an explicit
    ``default_deadline_s`` in their spec. Clients override per request via
    the ``timeout`` body field or ``X-Request-Deadline`` header."""
    return env_float(GATEWAY_DEADLINE_S_ENV, 0.0)


def jax_platforms() -> str:
    """``JAX_PLATFORMS`` (JAX's own variable): "cpu" holds every process to
    the CPU — the test harness and CPU-designated workers set it."""
    return (env_str("JAX_PLATFORMS") or "").strip().lower()


def tpu_visible_devices() -> Optional[list]:
    """``TPU_VISIBLE_DEVICES`` (libtpu's own variable): the chip ids this
    process may open, or None when unrestricted. The launcher sets it per
    chip-owning child (``apps/launcher.plan_chips``)."""
    raw = env_str("TPU_VISIBLE_DEVICES")
    if not raw:
        return None
    return [int(x) for x in raw.split(",") if x.strip()]


COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"  # JAX's own variable
# the one fixed fallback: inside the checkout, git-ignored (the directory
# is part of the cache key, so a path that moves never hits)
_DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache",
)


def compile_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR``: where the persistent XLA compile
    cache lives. Set from outside, JAX reads it itself and the program
    sets no other; unset, every process uses ``<checkout>/
    .jax_compile_cache`` (see ``base/compile_cache.py``)."""
    return env_str(COMPILE_CACHE_ENV) or _DEFAULT_COMPILE_CACHE_DIR


# What of the environment may shape a PROGRAM (``base/program_store.py``
# keys a built program by it): the compiler's own flags and EVERY variable
# of this package's, so that a flag a later change reads inside a traced
# function is keyed before anyone thinks of it, but those that say where
# files live, who talks to whom and what is logged, traced or watched (a
# restarted worker gets new ones and must still find its programs).
_PROGRAM_ENV = ("XLA_FLAGS", "LIBTPU_INIT_ARGS")
_NOT_PROGRAM_ENV = (      # prefixes of ``AREAL_*`` names
    "AREAL_FILEROOT", "AREAL_JOBDIR", "AREAL_NAME_RESOLVE_", "AREAL_LOG_LEVEL",
    "AREAL_COORDINATOR", "AREAL_NUM_PROCESSES", "AREAL_PROCESS_ID",
    "AREAL_GATEWAY_", "AREAL_GW_", "AREAL_TRACE_", TRACE_ENV,
    "AREAL_TELEMETRY_", "AREAL_WATCHDOG_", "AREAL_HBM_",
)


def dumps_ir() -> bool:
    """``JAX_DUMP_IR_TO`` (JAX's own variable): the process writes the
    lowered program of every jit to a directory, ``chip_smoke.py``'s check
    of the kernels from outside. Such a process wants every program
    LOWERED: the program store leaves it on ``jax.jit``'s own path."""
    return bool(env_str("JAX_DUMP_IR_TO"))


def program_env() -> tuple:
    """``(name, value)`` of every variable set that may shape a program
    (JAX's own settings are keyed from ``jax.config``, not from here)."""
    return tuple(sorted(
        (k, v) for k, v in os.environ.items()
        if k in _PROGRAM_ENV or (
            k.startswith("AREAL_") and not k.startswith(_NOT_PROGRAM_ENV))
    ))


def native_disabled() -> bool:
    """``AREAL_DISABLE_NATIVE``: skip building/loading the C packer
    extension (pure-python fallback)."""
    return env_flag("AREAL_DISABLE_NATIVE", False)


DEFAULT_TELEMETRY_INTERVAL_S = 15.0


def telemetry_export_interval() -> float:
    """``AREAL_TELEMETRY_EXPORT`` (default off): per-worker telemetry
    snapshot export period in seconds. Unset/"0"/"false"/"off" disables
    the exporter entirely (zero overhead); "true"/"on" enables it at the
    default 15 s; a number sets the period explicitly."""
    raw = env_str(TELEMETRY_EXPORT_ENV)
    if raw is None or raw.strip().lower() in _OFF_STRINGS:
        return 0.0
    if raw.strip().lower() in ("true", "on", "1"):
        # "1" means "enabled", not a 1-second firehose: sub-default
        # periods must be asked for explicitly (e.g. "0.5")
        return DEFAULT_TELEMETRY_INTERVAL_S
    val = env_float(TELEMETRY_EXPORT_ENV, DEFAULT_TELEMETRY_INTERVAL_S)
    return max(val, 0.0)


DEFAULT_TRACE_RING = 4096


def trace_spans_enabled() -> bool:
    """``AREAL_TRACE_SPANS`` (default on): stamp every ``tracing.span``
    with W3C-style trace/span IDs, record its completion into the bounded
    per-process ring, and propagate trace context over the HTTP/SSE plane
    (docs/observability.md "Distributed tracing"). "0"/"off" reverts
    spans to bare counter accumulation; what a span costs on and off
    is in ``PERF.md`` §6 (PR 24)."""
    return env_flag(TRACE_SPANS_ENV, True)


def trace_ring_size() -> int:
    """``AREAL_TRACE_RING`` (default 4096): capacity of the per-process
    completed-span ring. The oldest spans are overwritten (counted in
    ``trace/dropped``); both the fileroot span flusher and the flight
    recorder read this ring. Floored at 16 so a typo'd "0" cannot turn
    the flight recorder's span evidence off silently."""
    return max(16, env_int(TRACE_RING_ENV, DEFAULT_TRACE_RING))


def trace_flush_interval() -> float:
    """``AREAL_TRACE_FLUSH_S`` (default 0 = ride the telemetry exporter):
    period of a dedicated span-flush thread draining the completed-span
    ring to ``<fileroot>/trace_spans/<worker>.jsonl``. At the default 0
    there is no dedicated thread — the ring is flushed on every telemetry
    snapshot publish and once on worker stop."""
    return max(0.0, env_float(TRACE_FLUSH_ENV, 0.0))


def trace_log_tail() -> int:
    """``AREAL_TRACE_LOG_TAIL`` (default 200): number of recent log lines
    the flight recorder retains in memory for its crash dump (0 disables
    the log-tail handler)."""
    return max(0, env_int(TRACE_LOG_TAIL_ENV, 200))


def watchdog_abort_enabled() -> bool:
    """``AREAL_WATCHDOG_ABORT``: a stale heartbeat dumps stacks AND exits
    (os._exit) so the scheduler restarts the world."""
    return env_flag(WATCHDOG_ABORT_ENV, False)


def function_call_enabled() -> bool:
    """``AREAL_ENABLE_FUNCTION_CALL``: route math/code verification to the
    remote sandboxed function-call service."""
    return env_flag("AREAL_ENABLE_FUNCTION_CALL", False)


def functioncall_service_domain() -> str:
    """``AREAL_FUNCTIONCALL_SERVICE_DOMAIN``: base URL of the remote
    verification service ("" = unset)."""
    return env_str("AREAL_FUNCTIONCALL_SERVICE_DOMAIN", "") or ""


def functioncall_concurrency_override() -> Optional[int]:
    """``AREAL_FUNCTIONCALL_CONCURRENCY``: explicit per-process request
    cap; None -> derive from the shared budget / DP split."""
    raw = env_str("AREAL_FUNCTIONCALL_CONCURRENCY")
    if raw is None or raw.strip() == "":
        return None
    try:
        return int(raw)
    except ValueError:
        return None


def functioncall_dp() -> int:
    """``AREAL_FUNCTIONCALL_DP`` (default 16): data-parallel caller count
    the shared sandbox budget is split across."""
    return env_int("AREAL_FUNCTIONCALL_DP", 16)


def elastic_enabled() -> bool:
    """``AREAL_ELASTIC`` (default off): surgical rank-level recovery for
    the multihost trainer world — bounded host collectives, world-epoch
    reformation on rank death/hang, supervisor-driven relaunch of only the
    dead rank (docs/fault_tolerance.md "Elastic multihost")."""
    return env_flag(ELASTIC_ENV, False)


def collective_timeout_s() -> float:
    """``AREAL_COLLECTIVE_TIMEOUT_S`` (default 120): deadline for one
    host-side ``multihost`` collective when elastic mode is on. Past it
    the collective raises ``CollectiveTimeoutError`` instead of hanging —
    size it well above the slowest legitimate collective (a multihost
    checkpoint barrier), or stragglers read as wedged ranks."""
    return env_float(COLLECTIVE_TIMEOUT_ENV, 120.0)


def elastic_lease_interval_s() -> float:
    """``AREAL_ELASTIC_LEASE_INTERVAL_S`` (default 2): refresh cadence of
    the per-rank liveness lease in name_resolve. The supervisor treats a
    lease older than 5x this as stale (auxiliary signal only; process
    exit and timeout reports are the authoritative ones)."""
    return env_float(ELASTIC_LEASE_INTERVAL_ENV, 2.0)


def elastic_max_reforms() -> int:
    """``AREAL_ELASTIC_MAX_REFORMS`` (default 8): world reformations one
    trainer incarnation will attempt before giving up and escalating to
    restart-the-world (the launcher's recover_mode loop)."""
    return env_int(ELASTIC_MAX_REFORMS_ENV, 8)


def multihost_coordinator() -> Optional[str]:
    """``AREAL_COORDINATOR``: jax.distributed coordinator ``host:port``,
    or "auto" for Cloud-TPU topology autodetection; None -> single host."""
    return env_str("AREAL_COORDINATOR")


def multihost_num_processes() -> int:
    """``AREAL_NUM_PROCESSES``: world size for explicit-coordinator
    jax.distributed bring-up (required when AREAL_COORDINATOR is set to
    an address)."""
    return int(os.environ["AREAL_NUM_PROCESSES"])


def multihost_process_id() -> int:
    """``AREAL_PROCESS_ID``: this process's rank for explicit-coordinator
    jax.distributed bring-up."""
    return int(os.environ["AREAL_PROCESS_ID"])


def set_experiment_trial_names(experiment_name: str, trial_name: str):
    global _experiment_name, _trial_name
    _experiment_name = experiment_name
    _trial_name = trial_name


def experiment_name() -> str:
    if _experiment_name is None:
        raise RuntimeError("experiment name not set")
    return _experiment_name


def trial_name() -> str:
    if _trial_name is None:
        raise RuntimeError("trial name not set")
    return _trial_name


def get_fileroot() -> str:
    return os.environ.get(
        "AREAL_FILEROOT", f"/tmp/areal_tpu/{getpass.getuser()}"
    )


def trace_root() -> str:
    """``AREAL_FILEROOT`` for trace output, defaulting to the historical
    shared ``/tmp/areal_tpu`` — NOT the per-user ``get_fileroot`` default,
    so ``traces/<tag>`` stays where docs/performance.md and existing
    tooling expect it."""
    return env_str("AREAL_FILEROOT", "/tmp/areal_tpu")


def set_fileroot(path: str):
    os.environ["AREAL_FILEROOT"] = path


def get_log_root() -> str:
    p = os.path.join(get_fileroot(), "logs", experiment_name(), trial_name())
    os.makedirs(p, exist_ok=True)
    return p


def get_save_root() -> str:
    p = os.path.join(get_fileroot(), "checkpoints", experiment_name(), trial_name())
    os.makedirs(p, exist_ok=True)
    return p


def get_cache_root() -> str:
    p = os.path.join(get_fileroot(), "cache", experiment_name(), trial_name())
    os.makedirs(p, exist_ok=True)
    return p


def get_param_sync_root() -> str:
    """Directory for trainer→generation weight-sync snapshots
    (≈ the reference's param_realloc dir, ``model_worker.py:787-800``)."""
    p = os.path.join(get_save_root(), "weight_sync")
    os.makedirs(p, exist_ok=True)
    return p


def get_recover_root() -> str:
    p = os.path.join(get_save_root(), "recover")
    os.makedirs(p, exist_ok=True)
    return p


def get_trace_span_root() -> str:
    """Directory the per-worker span flushers append their jsonl rings
    under — ``system/tracejoin.py`` merges every file here into one
    Chrome-``trace_event`` timeline (docs/observability.md "Distributed
    tracing"). Keyed by fileroot only (not experiment/trial): the span
    records carry their own worker identity, and the obs CLI points at a
    fileroot the same way."""
    p = os.path.join(get_fileroot(), "trace_spans")
    os.makedirs(p, exist_ok=True)
    return p


def get_flight_root() -> str:
    """Directory flight-recorder crash dumps land in (one JSON per dump;
    docs/fault_tolerance.md "Flight recorder")."""
    p = os.path.join(get_fileroot(), "flight")
    os.makedirs(p, exist_ok=True)
    return p


def get_env_vars(**extra) -> dict:
    """Env vars to forward to spawned workers."""
    keys = [
        "AREAL_FILEROOT",
        "AREAL_LOG_LEVEL",
        "AREAL_NAME_RESOLVE_ROOT",
        "AREAL_NAME_RESOLVE_RPC",
        "AREAL_HBM_WARN_THRESHOLD",
        "AREAL_HBM_FALLBACK_INTERVAL",
        "AREAL_HBM_CHECK_SECS",
        "AREAL_TRACE_STEP",
        "AREAL_DEBUG_CHECKS",
        "AREAL_FLASH_BWD_PIPELINE",
        "AREAL_DECODE_PIPELINE",
        KV_DTYPE_ENV,
        "AREAL_DISABLE_NATIVE",
        "AREAL_ENABLE_FUNCTION_CALL",
        "AREAL_FUNCTIONCALL_SERVICE_DOMAIN",
        "AREAL_FUNCTIONCALL_CONCURRENCY",
        "AREAL_FUNCTIONCALL_DP",
        TRACE_ENV,
        MEMORY_KILL_ENV,
        FWD_PIPELINE_ENV,
        TRAIN_PREFETCH_ENV,
        TRAIN_GUARD_ENV,
        PREEMPT_DEADLINE_ENV,
        WATCHDOG_TIMEOUT_ENV,
        WATCHDOG_ABORT_ENV,
        TELEMETRY_EXPORT_ENV,
        TRACE_SPANS_ENV,
        TRACE_RING_ENV,
        TRACE_FLUSH_ENV,
        TRACE_LOG_TAIL_ENV,
        ELASTIC_ENV,
        COLLECTIVE_TIMEOUT_ENV,
        ELASTIC_LEASE_INTERVAL_ENV,
        ELASTIC_MAX_REFORMS_ENV,
        GATEWAY_PORT_ENV,
        GATEWAY_RATE_TPS_ENV,
        GATEWAY_BURST_ENV,
        GATEWAY_MAX_QUEUE_ENV,
        GATEWAY_ADMIT_OCC_ENV,
        GATEWAY_HEDGE_ENV,
        GATEWAY_DEADLINE_S_ENV,
        "JAX_PLATFORMS",
        "XLA_FLAGS",
        "TPU_VISIBLE_DEVICES",
        COMPILE_CACHE_ENV,
    ]
    out = {k: os.environ[k] for k in keys if k in os.environ}
    out.update({k: str(v) for k, v in extra.items()})
    return out
