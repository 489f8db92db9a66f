"""Offline xplane trace analysis: per-kernel device-time buckets.

Counterpart of the reference's CUDA kernel-time classifier
(``realhf/base/monitor.py:404-610``: COMPUTE / P2P_COMM / COLL_COMM /
MEM / IDLE / MISC buckets over a chrome trace), rebuilt for the TPU
profiler: ``jax.profiler.trace`` dumps serialized XSpace protos
(``*.xplane.pb``), parsed here with jaxlib's bundled ``ProfileData``
reader — no tensorflow/tensorboard dependency.

Classification prefers the ``hlo_category`` stat the TPU op profiler
attaches to each XLA-op event (e.g. "convolution", "all-reduce fusion",
"copy"); name heuristics cover events without it (CPU traces, custom
pallas calls). Op events nest on a line (a ``while`` covers its body, a
fusion its parts), so each event is charged its SELF time — its duration
minus what its children cover — and busy time is the union of the
intervals: the buckets add up to busy, never to more. Idle = line span
minus busy — the device waiting on the host or on collectives-in-flight.
Planes without a single op event (libtpu's empty "Megascale Trace"
plane in a CPU trace) are skipped.

CLI::

    python -m areal_tpu.apps.trace_analyze /tmp/areal_trace [--top 20]
"""

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple


class TraceAnalyzerUnavailable(RuntimeError):
    """The installed jax/jaxlib does not bundle the ``ProfileData`` XSpace
    reader (``jax.profiler.ProfileData`` appeared in jaxlib 0.4.x and has
    moved between releases). Callers that can degrade (the CLI, pytest)
    catch/skip on this instead of crashing on AttributeError
    deep inside an analysis pass."""


def _profile_data():
    """The ``ProfileData`` class, or raise :class:`TraceAnalyzerUnavailable`."""
    try:
        import jax.profiler as jp

        return jp.ProfileData
    except (ImportError, AttributeError) as e:
        raise TraceAnalyzerUnavailable(
            f"jax.profiler.ProfileData unavailable in this jax build: {e!r}"
        ) from e


def profile_data_available() -> bool:
    try:
        _profile_data()
        return True
    except TraceAnalyzerUnavailable:
        return False

# bucket keys mirror monitor.py's CUDAKernelTimeCategory values
COMPUTE, P2P, COLL, MEM, IDLE, MISC = (
    "compute", "p2p_comm", "coll_comm", "memoryIO", "idle", "misc"
)
BUCKETS = (COMPUTE, P2P, COLL, MEM, IDLE, MISC)

# substring tables (lowercased match), ordered like the reference's
# from_name: MEM and COMM are the easily-identified ones, compute is the
# residual bulk
_MEM_KEYS = (
    "copy", "dynamic-update-slice", "dynamic_update_slice", "memset",
    "transpose", "bitcast", "reshape", "d2d", "h2d", "d2h", "infeed",
    "outfeed",
)
_P2P_KEYS = ("collective-permute", "collective_permute", "send", "recv")
_COLL_KEYS = (
    "all-reduce", "all_reduce", "all-gather", "all_gather",
    "reduce-scatter", "reduce_scatter", "all-to-all", "all_to_all",
    "psum", "allreduce",
)
_MISC_KEYS = ("thunk", "listener", "barrier", "tuple", "call-start")


def classify(name: str, hlo_category: Optional[str] = None) -> str:
    """Bucket one device event. ``hlo_category`` (TPU op profiler stat)
    wins; the name tables are the fallback (monitor.py:414-425 order)."""
    for s in ((hlo_category or "").lower(), name.lower()):
        if not s:
            continue
        if any(k in s for k in _P2P_KEYS):
            return P2P
        if any(k in s for k in _COLL_KEYS):
            return COLL
        if any(k in s for k in _MEM_KEYS):
            return MEM
        if any(k in s for k in _MISC_KEYS):
            return MISC
    return COMPUTE


@dataclasses.dataclass
class TraceSummary:
    device_total_s: float
    buckets_s: Dict[str, float]
    top_ops: List[Tuple[str, float, int, str]]  # name, seconds, count, bucket
    n_events: int
    plane: str

    def as_dict(self) -> dict:
        tot = self.device_total_s or 1.0
        return {
            "plane": self.plane,
            "device_total_s": round(self.device_total_s, 6),
            "n_events": self.n_events,
            "buckets_s": {k: round(v, 6) for k, v in self.buckets_s.items()},
            "buckets_pct": {
                k: round(v / tot, 4) for k, v in self.buckets_s.items()
            },
            "top_ops": [
                {"name": n, "seconds": round(s, 6), "count": c, "bucket": b}
                for n, s, c, b in self.top_ops
            ],
        }

    def format_table(self, top: int = 15) -> str:
        tot = self.device_total_s or 1.0
        lines = [
            f"plane: {self.plane}   device time: "
            f"{self.device_total_s * 1e3:.2f} ms   events: {self.n_events}",
            "",
            f"{'bucket':<12} {'seconds':>12} {'share':>8}",
        ]
        for k in BUCKETS:
            v = self.buckets_s.get(k, 0.0)
            lines.append(f"{k:<12} {v:>12.6f} {v / tot:>7.1%}")
        lines += ["", f"{'top op':<48} {'seconds':>10} {'count':>7}  bucket"]
        for n, s, c, b in self.top_ops[:top]:
            lines.append(f"{n[:48]:<48} {s:>10.6f} {c:>7}  {b}")
        return "\n".join(lines)


def _is_device_plane(name: str) -> bool:
    return "/device:" in name.lower() or "tpu" in name.lower()


def _op_lines(plane):
    """XLA-op event lines ONLY. A real TPU device plane carries 'XLA Ops'
    plus 'XLA Modules' / 'Steps' lines whose spans COVER the same wall
    time — summing every line would double/triple-count device_total_s.
    When an op line exists, everything else on the plane is dropped; the
    CPU PJRT plane (no such line) falls through to all lines, with op
    events identified by their ``hlo_op`` stat instead."""
    lines = list(plane.lines)
    ops = [
        ln for ln in lines
        if "xla ops" in ln.name.lower() or "xla op" == ln.name.lower()
    ]
    return ops if ops else lines


def analyze_xspace(path: str) -> List[TraceSummary]:
    """One summary per device plane in the XSpace file (CPU traces: the
    PJRT client plane stands in for the device). Raises
    :class:`TraceAnalyzerUnavailable` when this jax build has no
    ProfileData reader."""
    return analyze_profile_data(_profile_data().from_file(path))


def self_times_ns(events: List[Tuple[float, float]]) -> List[float]:
    """Self time of each ``(start_ns, duration_ns)`` event of ONE line, in
    the order given: its duration minus what the events lying inside it
    cover (a child lies wholly inside its parent; a partial overlap is
    clipped to the parent's end, so the self times of a line always add
    up to the union of its intervals)."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    out = [0.0] * len(events)
    stack: List[List[float]] = []   # [index, end]
    for i in order:
        start, dur = events[i]
        while stack and stack[-1][1] <= start:
            stack.pop()
        end = start + dur
        if stack:
            end = min(end, stack[-1][1])
            out[int(stack[-1][0])] -= end - start
        out[i] += end - start
        stack.append([i, end])
    return out


def _summarize_plane(plane, is_device: bool) -> TraceSummary:
    buckets = {k: 0.0 for k in BUCKETS}
    per_op: Dict[str, List] = {}
    n_events = 0
    span_lo, span_hi, busy = None, None, 0.0
    for line in _op_lines(plane):
        kept = []   # (name, bucket, start_ns, duration_ns)
        for ev in line.events:
            dur_ns = float(ev.duration_ns or 0.0)
            name = ev.name
            if dur_ns <= 0.0 or name.startswith(("end:", "$")):
                continue
            try:
                stats = dict(ev.stats)
            except Exception:
                stats = {}
            # device planes (TPU): every timed event is device work.
            # CPU-fallback plane: the client threads mix compiler and
            # dispatcher spans with op execution — only events stamped
            # with an hlo_op stat are actual op work
            if not is_device and "hlo_op" not in stats:
                continue
            cat = stats.get("hlo_category")
            bucket = classify(name, cat if isinstance(cat, str) else None)
            kept.append((name, bucket, float(ev.start_ns or 0.0), dur_ns))
        selfs = self_times_ns([(t0, d) for _, _, t0, d in kept])
        for (name, bucket, t0, dur_ns), self_ns in zip(kept, selfs):
            buckets[bucket] += self_ns / 1e9
            busy += self_ns / 1e9
            n_events += 1
            span_lo = t0 if span_lo is None else min(span_lo, t0)
            span_hi = (
                t0 + dur_ns if span_hi is None else max(span_hi, t0 + dur_ns)
            )
            rec = per_op.setdefault(name, [0.0, 0, bucket])
            rec[0] += self_ns / 1e9
            rec[1] += 1
    if span_lo is not None:
        buckets[IDLE] = max((span_hi - span_lo) / 1e9 - busy, 0.0)
    top = sorted(
        ((n, s, c, b) for n, (s, c, b) in per_op.items()),
        key=lambda t: -t[1],
    )[:50]
    return TraceSummary(
        device_total_s=busy + buckets[IDLE],
        buckets_s=buckets,
        top_ops=top,
        n_events=n_events,
        plane=plane.name,
    )


def analyze_profile_data(pd) -> List[TraceSummary]:
    planes = list(pd.planes)

    def with_ops(chosen, is_device: bool) -> List[TraceSummary]:
        summaries = (_summarize_plane(p, is_device) for p in chosen)
        return [s for s in summaries if s.n_events]

    # CPU fallback: the XLA client threadpool plane holds the op events
    return with_ops(
        [p for p in planes if _is_device_plane(p.name)], True
    ) or with_ops(
        [p for p in planes
         if any("pjrtcpuclient" in ln.name.lower() for ln in p.lines)],
        False,
    )


def find_xplane_files(root: str) -> List[str]:
    """Newest profile run's .xplane.pb files under a trace dir."""
    files = glob.glob(
        os.path.join(root, "**", "*.xplane.pb"), recursive=True
    )
    if not files:
        return []
    # jax writes plugins/profile/<timestamp>/<host>.xplane.pb
    newest_dir = max(os.path.dirname(f) for f in files)
    return sorted(f for f in files if os.path.dirname(f) == newest_dir)
