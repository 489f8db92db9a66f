"""Distributed request tracing + env-gated jax.profiler tracing.

Two planes share this module (docs/observability.md "Distributed
tracing"):

**Profiler plane** (the original layer): set ``AREAL_DUMP_TRACE=1`` and
every block wrapped in :func:`maybe_trace` dumps an xplane/chrome trace
under ``$AREAL_FILEROOT/traces/<tag>`` (inspect with xprof /
tensorboard-plugin-profile) — the TPU-native counterpart of the
reference's ``REAL_DUMP_TRACE`` torch-profiler gating
(``realhf/system/model_worker.py:79-94,828-909``).

**Span plane** (always on unless ``AREAL_TRACE_SPANS=0``): every
:func:`span` is also a ``jax.profiler.TraceAnnotation`` named
``PROFILER_PREFIX + name`` (``areal/gen_engine/admit``), so in ANY
profiler session — ``maybe_trace``, the benchmark's, an operator's
``jax.profiler.start_trace`` — the program's spans sit on the host plane
of the xplane, on the same clock as the device's ops. Every span carries
a W3C-traceparent-style identity —

    ``00-<32-hex trace id>-<16-hex span id>-01``

— propagated across processes through one ``trace`` body field on every
internal HTTP hop (and the standard ``traceparent`` header at the
gateway's external ``/v1/*`` intake). Completed spans land in a bounded
per-process ring, flushed as jsonl through the fileroot
(``constants.get_trace_span_root()``); ``system/tracejoin.py`` merges
every worker's flushes into one Chrome-``trace_event`` timeline and
``apps/obs.py --trace <request-id|qid>`` renders a single request's span
tree. The ring additionally feeds the crash flight recorder
(``system/worker_base.FlightRecorder``) its recent-span evidence.

**Compile listener** (:func:`listen_for_compiles`): ONE pair of
``jax.monitoring`` listeners a process turns every executable JAX builds
or loads from its cache into the ``compile/*`` counters and, with the
span plane on, one ``compile/program`` record under the span that paid
for it (docs/observability.md "What a start cost"); a program the
program store handed over built (``base/program_store.py``) is booked
the same way by :func:`program_loaded`.

Context flows through :mod:`contextvars`, so one event loop serving many
concurrent requests keeps each request's trace identity isolated without
any per-request plumbing beyond the ``with tracing.activate(...)`` at
the hop boundary.
"""

import collections
import contextlib
import contextvars
import json
import os
import sys
import threading
import time
import uuid
from typing import Dict, List, Optional, Tuple

from areal_tpu.base import constants
from areal_tpu.base import metrics as metrics_mod

# Live-span registry: every open tracing.span is visible here, so the hang
# watchdog (system/worker_base.HangWatchdog) can report WHAT a wedged worker
# was doing (e.g. "train_pipe/dispatch open for 1800s") alongside raw thread
# stacks — without any profiler attached. A record holds its span's attrs
# dict too: the compile listener bumps ``compiled`` / ``compile_s`` on the
# innermost span open where a program was built.
_live_lock = threading.Lock()
_live: List[dict] = []

# Completed-span ring: bounded (AREAL_TRACE_RING), drained by flush().
_ring_lock = threading.Lock()
_ring: collections.deque = collections.deque()
# Recent span ends for the flight recorder — NEVER drained by flush(), so
# a crash dump still has span evidence right after a telemetry publish.
_RECENT_CAP = 256
_recent: collections.deque = collections.deque(maxlen=_RECENT_CAP)

# The active trace context for this task/thread: (trace_id, span_id).
# span_id may be "" at a fresh root (no span opened yet).
_ctx: contextvars.ContextVar = contextvars.ContextVar(
    "areal_trace_ctx", default=None
)
# The RL query id riding the active context (joins the breaker's
# last_failure_reason qid against trace ids; docs/serving.md).
_qid: contextvars.ContextVar = contextvars.ContextVar(
    "areal_trace_qid", default=None
)

_flush_lock = threading.Lock()

# What a span is called in a profiler trace: this prefix + its name. One
# constant, so a reader picks the program's events out of the host plane
# by prefix (benchmark/program_spans.py) and nothing else there changes.
PROFILER_PREFIX = "areal/"


def live_spans() -> List[Dict[str, object]]:
    """Snapshot of currently-open spans: name, seconds open, thread name.
    Oldest first (the outermost wedged span is the interesting one)."""
    now = time.perf_counter()
    with _live_lock:
        return [
            {
                "name": r["name"],
                "elapsed_s": now - r["t0"],
                "thread": r["thread"],
            }
            for r in _live
        ]


def trace_enabled() -> bool:
    return constants.trace_enabled()


def trace_dir(tag: str) -> str:
    return os.path.join(constants.trace_root(), "traces", tag)


@contextlib.contextmanager
def maybe_trace(tag: str):
    """Wrap a step in ``jax.profiler.trace`` when AREAL_DUMP_TRACE is set."""
    if not trace_enabled():
        yield
        return
    import jax

    d = trace_dir(tag)
    os.makedirs(d, exist_ok=True)
    with jax.profiler.trace(d):
        yield


def trace_step() -> int:
    """Which training step the trainers dump (tracing every step would grow
    unboundedly; the reference profiles a fixed early step the same way)."""
    return constants.trace_step()


def annotate(name: str):
    """Named region in whatever profiler session is running (per-MFC
    attribution in the executor; every :func:`span`). Outside a session a
    ``TraceAnnotation`` is a flag check. A process that never imported
    jax has no profiler to annotate for, and a span must not be what
    imports it."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name)


# --------------------------------------------------------------------- #
# Trace identity + context propagation
# --------------------------------------------------------------------- #


def spans_enabled() -> bool:
    return constants.trace_spans_enabled()


def new_trace_id() -> str:
    return uuid.uuid4().hex


def new_span_id() -> str:
    return uuid.uuid4().hex[:16]


def current() -> Optional[Dict[str, str]]:
    """The active context as ``{"trace_id", "span_id"}``, or None."""
    c = _ctx.get()
    if c is None:
        return None
    return {"trace_id": c[0], "span_id": c[1]}


def current_qid() -> Optional[str]:
    """The RL qid riding the active context (None outside RL hops)."""
    return _qid.get()


def traceparent() -> Optional[str]:
    """W3C-style header value for the active context, or None. A root
    context with no span open yet carries the all-zero parent span id —
    the receiving side treats it as "same trace, no parent span"."""
    c = _ctx.get()
    if c is None:
        return None
    return f"00-{c[0]}-{c[1] or '0' * 16}-01"


def parse_traceparent(value) -> Optional[Tuple[str, Optional[str]]]:
    """``(trace_id, parent_span_id)`` from a traceparent string; tolerant
    — anything malformed degrades to None (a trace must never break a
    request). The all-zero span id maps to parent None."""
    if not isinstance(value, str):
        return None
    parts = value.strip().split("-")
    if len(parts) != 4:
        return None
    _ver, tid, sid, _flags = parts
    try:
        int(tid, 16), int(sid, 16)
    except ValueError:
        return None
    if len(tid) != 32 or len(sid) != 16:
        return None
    return tid, (None if sid == "0" * 16 else sid)


def wire_context(qid: Optional[str] = None) -> Optional[dict]:
    """Client side of a hop: the single ``trace`` body field internal
    HTTP clients attach — ``{"traceparent": ..., "qid": ...}`` (qid only
    when one rides the context). None when the span plane is off or no
    context is active, so the field is simply absent from the payload."""
    if not spans_enabled():
        return None
    tp = traceparent()
    q = qid if qid is not None else _qid.get()
    if tp is None and q is None:
        return None
    out: Dict[str, object] = {"traceparent": tp}
    if q is not None:
        out["qid"] = q
    return out


@contextlib.contextmanager
def activate(
    wire=None,
    trace_id: Optional[str] = None,
    parent_span_id: Optional[str] = None,
    qid: Optional[str] = None,
):
    """Activate a trace context for the current task/thread.

    Server side of a hop: pass the request's ``trace`` body field (dict)
    or ``traceparent`` header (str) as ``wire`` — malformed/absent wire
    context degrades to rooting a NEW trace. Root side (gateway intake,
    rollout worker): pass nothing and a fresh trace id is minted. Yields
    the active trace id."""
    if not spans_enabled():
        yield None
        return
    q = qid
    if isinstance(wire, dict):
        parsed = parse_traceparent(wire.get("traceparent"))
        if q is None and wire.get("qid") is not None:
            q = str(wire["qid"])
    else:
        parsed = parse_traceparent(wire)
    if parsed is not None:
        tid, psid = parsed
    else:
        tid, psid = trace_id or new_trace_id(), parent_span_id
    tok = _ctx.set((tid, psid or ""))
    qtok = _qid.set(q) if q is not None else None
    try:
        yield tid
    finally:
        _ctx.reset(tok)
        if qtok is not None:
            _qid.reset(qtok)


# --------------------------------------------------------------------- #
# Spans
# --------------------------------------------------------------------- #


def _new_record(name: str, t0: float, attrs: Dict[str, object]) -> dict:
    """A record that joins the active trace: child of the context's
    current span, or the root of a fresh trace."""
    c = _ctx.get()
    return {
        "name": name, "t0": t0, "thread": threading.current_thread().name,
        "trace_id": c[0] if c else new_trace_id(),
        "parent_id": (c[1] or None) if c else None,
        "span_id": new_span_id(), "attrs": attrs,
    }


def _record_end(
    rec: dict, wall_end: float, dur: float, exc: Optional[BaseException],
) -> None:
    out = {
        "name": rec["name"],
        "trace_id": rec["trace_id"],
        "span_id": rec["span_id"],
        "parent_id": rec["parent_id"],
        "start": wall_end - dur,
        # the same instant on time.perf_counter(): what in-process readers
        # (spans_since) window by; wall-clock `start` is for tracejoin
        "t0": rec["t0"],
        "dur_s": dur,
        "thread": rec["thread"],
        "pid": os.getpid(),
        "error": exc is not None,
    }
    if exc is not None:
        out["exc"] = type(exc).__name__
    if rec["attrs"]:
        out["attrs"] = rec["attrs"]
    cap = constants.trace_ring_size()
    with _ring_lock:
        while len(_ring) >= cap:
            _ring.popleft()
            metrics_mod.counters.add(metrics_mod.TRACE_DROPPED)
        _ring.append(out)
    _recent.append(out)
    metrics_mod.counters.add(metrics_mod.TRACE_SPANS)
    if exc is not None:
        metrics_mod.counters.add(metrics_mod.TRACE_SPAN_ERRORS)


@contextlib.contextmanager
def span(name: str, **attrs):
    """Data-plane span: always accumulates host wall time into
    ``metrics.counters`` under ``<name>_s`` (plus a ``<name>_n`` call
    count).

    With the span plane on (default), the span is also a profiler
    annotation named ``PROFILER_PREFIX + name`` (see :func:`annotate`),
    and it joins the active distributed trace — child of the context's
    current span, or the root of a fresh trace — and its completion is
    recorded into the bounded ring *including exception exits*: a span
    whose body raises is stamped ``error=True`` with the exception type,
    never lost. Keyword ``attrs`` (plus any riding qid) land in the
    record for tracejoin / obs ``--trace`` to render. Yields the mutable
    attrs dict so a body can add attributes discovered mid-span."""
    if not spans_enabled():
        # counters-only path (AREAL_TRACE_SPANS=0): a clock read and two
        # counter adds — no annotation, no live-span registration, no
        # ring record.
        t0 = time.perf_counter()
        try:
            yield attrs
        finally:
            dt = time.perf_counter() - t0
            metrics_mod.counters.add(f"{name}_s", dt)
            metrics_mod.counters.add(f"{name}_n", 1.0)
        return
    t0 = time.perf_counter()
    rec = _new_record(name, t0, attrs)
    ctx_tok = _ctx.set((rec["trace_id"], rec["span_id"]))
    q = _qid.get()
    if q is not None:
        attrs.setdefault("qid", q)
    with _live_lock:
        _live.append(rec)
    exc: Optional[BaseException] = None
    try:
        with annotate(PROFILER_PREFIX + name):
            yield attrs
    except BaseException as e:  # noqa: BLE001 — stamped + re-raised
        exc = e
        raise
    finally:
        with _live_lock:
            try:
                _live.remove(rec)
            except ValueError:
                pass
        _ctx.reset(ctx_tok)
        dt = time.perf_counter() - t0
        metrics_mod.counters.add(f"{name}_s", dt)
        metrics_mod.counters.add(f"{name}_n", 1.0)
        _record_end(rec, time.time(), dt, exc)


# --------------------------------------------------------------------- #
# Compile listener: what building programs cost, and which span paid
# --------------------------------------------------------------------- #

_JAX_COMPILE = "/jax/core/compile/"
_JAX_CACHE = "/jax/compilation_cache/"
COMPILE_RECORD = "compile/program"

_listen_lock = threading.Lock()
_listening = False
# The program this thread is building: JAX reports its stages one event
# each, in order, on the thread that compiles — ``traces`` {function name:
# seconds}, ``lower`` (program name, seconds), ``cache_hit`` — and
# ``backend_compile_duration`` closes it.
_building = threading.local()


def listen_for_compiles() -> bool:
    """Register the process's ONE pair of ``jax.monitoring`` listeners
    (idempotent: two engines in a process share it). Never what imports
    JAX: before JAX is imported it does nothing and returns False, and
    the next caller tries again. Called by both engines' constructors
    before their first device work, by the workers at start, and by
    ``compile_cache.configure`` where JAX is already there. The listeners
    run only when JAX traces, lowers or compiles: a step that builds
    nothing never reaches them."""
    global _listening
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    with _listen_lock:
        if not _listening:
            import jax.monitoring

            jax.monitoring.register_event_listener(_on_jax_event)
            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_duration
            )
            _listening = True
    return True


def _on_jax_event(event: str, **_kw) -> None:
    if event == _JAX_CACHE + "cache_hits":
        _building.cache_hit = True
        metrics_mod.counters.add(metrics_mod.COMPILE_CACHE_HITS)
    elif event == _JAX_CACHE + "cache_misses":
        _building.cache_hit = False
        metrics_mod.counters.add(metrics_mod.COMPILE_CACHE_MISSES)


def _on_jax_duration(event: str, duration: float, fun_name: str = "",
                     **_kw) -> None:
    if event == _JAX_COMPILE + "jaxpr_trace_duration":
        # a function that calls other jitted functions reports theirs
        # first and its own, which contains them, last: kept by name, the
        # program takes the one of its own name and never the sum
        _building.__dict__.setdefault("traces", {})[fun_name] = duration
    elif event == _JAX_COMPILE + "jaxpr_to_mlir_module_duration":
        _building.lower = (fun_name, duration)
    elif event == _JAX_CACHE + "cache_retrieval_time_sec":
        metrics_mod.counters.add(metrics_mod.COMPILE_CACHE_LOAD_S, duration)
    elif event == _JAX_CACHE + "compile_time_saved_sec":
        metrics_mod.counters.add(metrics_mod.COMPILE_CACHE_SAVED_S, duration)
    elif event == _JAX_COMPILE + "backend_compile_duration":
        _program_built(fun_name, duration)


def _program_built(fun_name: str, backend_s: float) -> None:
    """One executable was built, or loaded from the persistent cache
    (``fun_name`` is JAX's, ``jit(chunk)``): counters always; with the
    span plane on, one ``compile/program`` record, child of the innermost
    span open on this thread, whose ``compiled`` / ``compile_s`` it
    bumps."""
    built = _building.__dict__
    traces = built.pop("traces", {})
    lower_name, lower_s = built.pop("lower", ("", 0.0))
    if lower_name != fun_name:      # lowered earlier, by other hands
        lower_s = 0.0
    # "jit(chunk)" was traced as "chunk"
    trace_s = traces.get(fun_name[fun_name.find("(") + 1:-1], 0.0)
    cache_hit = built.pop("cache_hit", None)    # None: no cache consulted
    more = {}
    if built.get("store_miss") == fun_name:     # built to be stored
        built["store_miss"] = bool(cache_hit)
        more["stored"] = False
    _book_program(fun_name, trace_s, lower_s, backend_s, cache_hit, **more)


def program_missed(fun_name: str) -> None:
    """The program store is about to build ``fun_name`` on this thread as
    JAX always did (a miss): its record says ``stored: false``."""
    metrics_mod.counters.add(metrics_mod.COMPILE_STORE_MISSES)
    _building.store_miss = fun_name


def missed_from_cache() -> bool:
    """After the build :func:`program_missed` announced: whether JAX's
    persistent cache handed the executable over (it was not compiled
    here)."""
    return _building.__dict__.pop("store_miss", None) is True


def program_loaded(fun_name: str, load_s: float) -> None:
    """One BUILT program was taken from the program store
    (``base/program_store.py``): booked as a program built from a cache
    (one of ``compile/programs`` and of ``compile/cache_hits``, its load
    the backend's seconds, no trace and no lowering), its record
    ``stored``."""
    metrics_mod.counters.add(metrics_mod.COMPILE_STORE_HITS)
    metrics_mod.counters.add(metrics_mod.COMPILE_CACHE_HITS)
    _book_program(fun_name, 0.0, 0.0, load_s, True, stored=True)


def _book_program(fun_name: str, trace_s: float, lower_s: float,
                  backend_s: float, cache_hit: Optional[bool],
                  **more) -> None:
    add = metrics_mod.counters.add
    add(metrics_mod.COMPILE_PROGRAMS)
    add(metrics_mod.COMPILE_TRACE_S, trace_s)
    add(metrics_mod.COMPILE_LOWER_S, lower_s)
    add(metrics_mod.COMPILE_BACKEND_S, backend_s)
    if not spans_enabled():
        return
    dur = trace_s + lower_s + backend_s
    rec = _new_record(COMPILE_RECORD, time.perf_counter() - dur, {
        "fun_name": fun_name, "trace_s": trace_s, "lower_s": lower_s,
        "backend_s": backend_s, "cache_hit": cache_hit, **more,
    })
    if rec["parent_id"] is not None:
        with _live_lock:
            for r in reversed(_live):
                if r["span_id"] == rec["parent_id"]:
                    a = r["attrs"]
                    a["compiled"] = a.get("compiled", 0) + 1
                    a["compile_s"] = a.get("compile_s", 0.0) + dur
                    break
    _record_end(rec, time.time(), dur, None)


# --------------------------------------------------------------------- #
# Ring drain / fileroot flush
# --------------------------------------------------------------------- #


def drain() -> List[dict]:
    """Take every completed span out of the ring (oldest first)."""
    with _ring_lock:
        out = list(_ring)
        _ring.clear()
    return out


def spans_since(t0: float, t1: Optional[float] = None) -> List[dict]:
    """The completed spans still in the ring whose monotonic start
    (``rec["t0"]``, ``time.perf_counter`` seconds) lies in ``[t0, t1]``,
    oldest first, WITHOUT taking them out: the flusher's next
    :func:`drain` finds what it would have found. For in-process readers
    of a window (the benchmark's per-layer metrics); what a flush already
    wrote out, or the ring overwrote (``trace/dropped``), is not here."""
    with _ring_lock:
        return [
            s for s in _ring
            if s["t0"] >= t0 and (t1 is None or s["t0"] <= t1)
        ]


def recent_spans(n: int = _RECENT_CAP) -> List[dict]:
    """The last ``n`` completed spans — survives flushes (the flight
    recorder's span evidence)."""
    return list(_recent)[-n:]


def _flush_path(worker_name: str, root: Optional[str] = None) -> str:
    safe = worker_name.replace("/", "_").replace(os.sep, "_") or "worker"
    return os.path.join(
        root or constants.get_trace_span_root(), f"{safe}.jsonl"
    )


def flush(worker_name: str, root: Optional[str] = None) -> int:
    """Drain the ring and append the spans, stamped with this worker's
    identity, to ``<fileroot>/trace_spans/<worker>.jsonl``. Returns the
    span count written. Rides the telemetry exporter's publish cadence
    (plus worker stop); ``AREAL_TRACE_FLUSH_S`` adds a dedicated thread
    for workers that don't export telemetry."""
    spans = drain()
    if not spans:
        return 0
    path = _flush_path(worker_name, root)
    with _flush_lock:
        with open(path, "a") as f:
            for s in spans:
                f.write(json.dumps({"worker": worker_name, **s}) + "\n")
    return len(spans)


class SpanFlusher(threading.Thread):
    """Dedicated background flusher for workers without a telemetry
    exporter — started by :meth:`maybe_start` only when
    ``AREAL_TRACE_FLUSH_S`` > 0."""

    def __init__(self, worker_name: str, interval_s: float):
        super().__init__(name=f"span-flush-{worker_name}", daemon=True)
        self.worker_name = worker_name
        self.interval_s = interval_s
        # NOT named _stop: threading.Thread's join() internals call a
        # private _stop() method that an Event attribute would shadow
        self._stop_ev = threading.Event()

    @classmethod
    def maybe_start(cls, worker_name: str) -> Optional["SpanFlusher"]:
        interval = constants.trace_flush_interval()
        if interval <= 0 or not spans_enabled():
            return None
        t = cls(worker_name, interval)
        t.start()
        return t

    def run(self) -> None:
        while not self._stop_ev.wait(self.interval_s):
            flush(self.worker_name)

    def stop(self) -> None:
        self._stop_ev.set()
        if self.is_alive():
            self.join(timeout=5)
        flush(self.worker_name)  # final drain: no span left behind
