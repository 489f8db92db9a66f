"""Device idle, PARTITIONED by the program's spans.

``program_spans.idle_under`` answers "how much idle lay under spans of
these names", a union: two names that nest count their overlap once, and
nothing says which of them the host was inside. This module gives every
idle nanosecond of the traced window to ONE name: the INNERMOST
``areal/...`` span open at that instant on the thread that runs the
engine's ``gen_engine/chunk`` spans, or ``outside`` where none is open
there (the caller's loop between two ``engine.step`` calls). The parts sum
to the window's idle exactly.

For a reader under ``layer_metrics/``:

* ``partition(bench)``: ``{span name or "outside": idle seconds}``, or
  ``None`` on the conditions under which ``program_spans.idle_under``
  returns ``None``: the run was not traced, the trace holds no
  ``gen_engine/chunk`` span (a program from before the spans, a cell that
  drives no engine), or host and device clocks disagree.
* ``part_share(bench, part)``: the idle of one of ``PARTS`` (a set of
  names that one reader reports together), or of ``REST`` (everything that
  is in none of them: ``outside``, the chunk span's own self time, any
  other span), as a percentage of the traced window. The parts and the
  rest add up to the device's idle share.

By hand: ``python -m benchmark.idle_partition <trace dir>`` prints the
window, the idle, every leaf's idle seconds largest first (pull and
release apart, seat and enqueue apart), the parts as the readers report
them, and the longest gaps with what each lay under and the programs
before and after it.
"""

import functools
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark import program_spans, trace_reduce

HOLDER = "gen_engine/chunk"
OUTSIDE = "outside"
REST = "outside_step"
# what one reader reports together; ``REST`` is whatever is in none
PARTS: Dict[str, Tuple[str, ...]] = {
    "flag_wait": ("gen_engine/flag_wait",),
    "harvest": ("gen_engine/harvest", "gen_engine/harvest/pull"),
    "admit_plan": ("gen_engine/admit",),
    "admit_prefill": ("gen_engine/admit/prefill",),
    "dispatch": ("gen_engine/dispatch", "gen_engine/dispatch/seat",
                 "gen_engine/dispatch/enqueue"),
}
Segment = Tuple[float, float, str]


# ------------------------------------------------------------------ #
# the trace: the program's spans with the thread each ran on
# ------------------------------------------------------------------ #

def thread_events(path: str, prefix: str = program_spans.PREFIX) -> List[List]:
    """[name without prefix, start ns, duration ns, thread] of every
    host-plane event whose name starts with ``prefix``; ``thread`` is the
    event's line (one a host thread), told apart across planes."""
    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}#{i}:{line.name}"
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append([ev.name[len(prefix):], float(ev.start_ns),
                                float(ev.duration_ns), thread])
    return out


@functools.lru_cache(maxsize=1)
def load(trace_dir: str) -> Dict:
    """``program_spans.load``'s device side with ``thread_events`` as the
    spans, of the newest xplane under ``trace_dir``."""
    files = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return {"raw": program_spans.load(trace_dir)["raw"],
            "spans": thread_events(files[-1])}


def holder_events(spans: Sequence[Sequence]) -> List[Sequence]:
    """The events of the thread that runs the most ``HOLDER`` spans (a
    span without a thread is on thread 0); ``[]`` where none does."""
    by_thread: Dict[object, List] = {}
    for ev in spans:
        by_thread.setdefault(ev[3] if len(ev) > 3 else 0, []).append(ev[:3])
    held = {t: sum(1 for e in evs if e[0] == HOLDER)
            for t, evs in by_thread.items()}
    if not any(held.values()):
        return []
    return by_thread[max(held, key=held.get)]


def innermost(events: Sequence[Sequence]) -> List[Segment]:
    """One thread's nested events as disjoint, sorted (lo, hi, name)
    segments, each under the innermost event open there. Time under no
    event is in no segment."""
    out: List[Segment] = []
    stack: List[Tuple[float, str]] = []     # (end, name)
    at = 0.0

    def emit(hi: float, name: str):
        nonlocal at
        if hi > at:
            out.append((at, hi, name))
            at = hi

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            emit(*stack.pop())
        if stack:
            emit(start, stack[-1][1])
        at = max(at, start)
        stack.append((start + dur, name))
    while stack:
        emit(*stack.pop())
    return out


def idle_by_name(idle: Sequence[program_spans.Interval],
                 segs: Sequence[Segment]) -> Dict[str, float]:
    """Seconds of the (sorted, disjoint) ``idle`` intervals under each
    segment name, and under ``OUTSIDE`` what lies under none."""
    by_name: Dict[str, List[program_spans.Interval]] = {}
    for lo, hi, name in segs:
        by_name.setdefault(name, []).append((lo, hi))
    out = {name: program_spans.overlap_ns(idle, ivs) / 1e9
           for name, ivs in by_name.items()}
    out = {name: s for name, s in out.items() if s > 0.0}
    total = sum(b - a for a, b in idle) / 1e9
    out[OUTSIDE] = max(total - sum(out.values()), 0.0)
    return out


def partition_loaded(loaded: Dict) -> Optional[Dict[str, float]]:
    """``partition`` on what ``load`` gives (or a synthetic stand-in)."""
    events = holder_events(loaded["spans"])
    wi = program_spans.window_and_idle(loaded["raw"])
    if wi is None or not events:
        return None
    return idle_by_name(wi[2], innermost(events))


def partition(bench) -> Optional[Dict[str, float]]:
    if bench.trace is None:
        return None
    return partition_loaded(load(bench.trace_dir))


def part_seconds(parts: Dict[str, float], part: str) -> float:
    """Idle seconds of one of ``PARTS``, or of ``REST``."""
    if part != REST:
        return sum(parts.get(n, 0.0) for n in PARTS[part])
    named = {n for names in PARTS.values() for n in names}
    return sum(s for n, s in parts.items() if n not in named)


def part_share(bench, part: str) -> Optional[float]:
    parts = partition(bench)
    if parts is None or bench.trace["window_s"] <= 0:
        return None
    return 100.0 * part_seconds(parts, part) / bench.trace["window_s"]


# ------------------------------------------------------------------ #

def longest_gaps(loaded: Dict, n: int = 8) -> List[Dict]:
    """The ``n`` longest idle intervals: seconds, what they lay under (by
    leaf, seconds) and the programs that ran before and after."""
    _, _, idle = program_spans.window_and_idle(loaded["raw"])
    segs = innermost(holder_events(loaded["spans"]))
    mods = sorted(
        (e for ln in loaded["raw"]["planes"][0]["lines"]
         if ln["name"] == trace_reduce.MODULES_LINE for e in ln["events"]),
        key=lambda e: e[1])
    out = []
    for lo, hi in sorted(idle, key=lambda ab: ab[0] - ab[1])[:n]:
        under = idle_by_name([(lo, hi)], segs)
        mid = (lo + hi) / 2     # (a program's event ends a little after its last op)
        before = [m[0] for m in mods if m[1] < mid]
        after = [m[0] for m in mods if m[1] >= mid]
        out.append({
            "s": (hi - lo) / 1e9,
            "between": f"{before[-1] if before else '-'} > "
                       f"{after[0] if after else '-'}",
            "under_s": dict(sorted(under.items(), key=lambda kv: -kv[1])),
        })
    return out


def main(argv):
    loaded = load(argv[0])
    parts = partition_loaded(loaded)
    if parts is None:
        raise SystemExit(
            f"no window annotation, no device op, clocks that disagree, or "
            f"no {HOLDER} span: nothing to partition")
    w0, w1, idle = program_spans.window_and_idle(loaded["raw"])
    print(json.dumps({
        "window_s": (w1 - w0) / 1e9,
        "idle_s": sum(b - a for a, b in idle) / 1e9,
        "idle_by_leaf_s": dict(sorted(parts.items(), key=lambda kv: -kv[1])),
        "idle_by_part_s": {
            p: part_seconds(parts, p) for p in list(PARTS) + [REST]},
        "longest_gaps": longest_gaps(loaded),
    }, indent=1))


if __name__ == "__main__":
    main(sys.argv[1:])
