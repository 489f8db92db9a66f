"""The program's own spans, for the per-layer readers.

``areal_tpu.base.tracing.span`` is a ``jax.profiler.TraceAnnotation`` named
``areal/<span name>`` (the prefix is ``tracing.PROFILER_PREFIX``), so in a
``--trace 1`` run the program's spans lie on the host plane of the same
xplane as the device's ops, on one clock. Each completed span is also a
record in the program's ring (``tracing.spans_since``), with its start on
``time.perf_counter``: the clock ``bench.t_open`` / ``t_trace`` / ``t_close``
are on. This module reads both; ``trace_reduce`` keeps reading the
benchmark's ``bench/...`` annotations and nothing else of the host plane,
so the numbers it gives do not move.

For a reader under ``layer_metrics/``:

* ``idle_under(bench, names)``: seconds inside the traced window in which
  no op ran on the (first) device AND a program span of one of ``names``
  was open on some host thread. ``None`` when the run was not traced, when
  no such span is in the trace (a program from before the spans existed),
  or when host and device clocks disagree (device events outside the
  ``bench/trace_window`` annotation: ``trace_reduce.reduce`` then falls
  back to the device's own clock, and an overlap with host spans would
  mean nothing). Never a guess.
* ``window_spans(bench, name, traced_only=False)``: the ring's records of
  that name that started inside the window (or its traced part), oldest
  first, each with its ``span_id`` / ``parent_id``, ``dur_s`` and
  ``attrs``. ``[]`` where the program has no such read. The ring keeps
  the newest 4096 spans (``AREAL_TRACE_RING``); a 40 s window of either
  kind of cell is about 1,100, so the whole window is there.
  ``window_attr_values(bench, name, field)`` flattens a list attribute
  (``gen_engine/harvest``'s ``stamps``: one ``[t_submit, t_admit, t_first,
  t_done]`` per finished request).

By hand: ``python -m benchmark.program_spans <trace dir> [name,name,...]``
prints the window, the device's idle seconds, the idle seconds under each
program span name, under the union of the names given, and under none.
"""

import functools
import glob
import json
import os
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from areal_tpu.base import tracing
from benchmark import trace_reduce

# a program from before the prefix existed annotated nothing: any value
# finds no event there, and the readers then return None
PREFIX = getattr(tracing, "PROFILER_PREFIX", "areal/")
Interval = Tuple[float, float]


# ------------------------------------------------------------------ #
# the trace: program spans and device idle on one clock
# ------------------------------------------------------------------ #

def host_events(path: str, prefix: str = PREFIX) -> List[List]:
    """[name without prefix, start ns, duration ns] of every host-plane
    event whose name starts with ``prefix``, from every thread's line."""
    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append([ev.name[len(prefix):], float(ev.start_ns),
                                float(ev.duration_ns)])
    return out


@functools.lru_cache(maxsize=1)
def load(trace_dir: str) -> Dict:
    """``{"raw": trace_reduce's plain lists, "spans": host_events}`` of the
    newest xplane under ``trace_dir`` (the one ``trace_reduce`` reduces)."""
    files = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return {"raw": trace_reduce.load_xplane(files[-1]),
            "spans": host_events(files[-1])}


def window_and_idle(raw: Dict) -> Optional[Tuple[float, float, List[Interval]]]:
    """(window start, window end, idle intervals of the first device) in
    ns, the window being the ``bench/trace_window`` annotation; ``None``
    without that annotation, without device ops, or when the device's
    events lie outside it (clocks that disagree)."""
    win = [s for s in raw["host_spans"] if s[0] == trace_reduce.WINDOW_SPAN]
    if not win or not raw["planes"]:
        return None
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    ops = [e for ln in raw["planes"][0]["lines"]
           if ln["name"] == trace_reduce.OPS_LINE for e in ln["events"]]
    if not ops:
        return None
    if max(s + d for _, s, d in ops) < w0 or min(s for _, s, _ in ops) > w1:
        return None
    busy = trace_reduce.union(
        [(max(s, w0), min(s + d, w1)) for _, s, d in ops])
    edges = [(w0, w0)] + busy + [(w1, w1)]
    idle = [(a, b) for (_, a), (b, _) in zip(edges, edges[1:]) if b > a]
    return w0, w1, idle


def overlap_ns(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Total length of the intersection of two sorted, disjoint lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_seconds(loaded: Dict, names: Iterable[str]) -> Optional[float]:
    """``idle_under`` on what ``load`` gives (or a synthetic stand-in)."""
    names = set(names)
    covered = trace_reduce.union(
        [(s, s + d) for n, s, d in loaded["spans"] if n in names])
    wi = window_and_idle(loaded["raw"])
    if wi is None or not covered:
        return None
    return overlap_ns(wi[2], covered) / 1e9


def idle_under(bench, names: Iterable[str]) -> Optional[float]:
    if bench.trace is None:
        return None
    return idle_seconds(load(bench.trace_dir), names)


def idle_share_under(bench, names: Iterable[str]) -> Optional[float]:
    """``idle_under`` as a percentage of the traced window."""
    idle = idle_under(bench, names)
    if idle is None or bench.trace["window_s"] <= 0:
        return None
    return 100.0 * idle / bench.trace["window_s"]


# ------------------------------------------------------------------ #
# the ring: completed spans of the window, on perf_counter
# ------------------------------------------------------------------ #

def window_spans(bench, name: str, traced_only: bool = False) -> List[Dict]:
    read = getattr(tracing, "spans_since", None)
    lo = bench.t_trace if traced_only else bench.t_open
    if read is None or lo is None or bench.t_close is None:
        return []
    return [s for s in read(lo, bench.t_close) if s["name"] == name]


def window_attr_values(bench, name: str, field: str) -> List[float]:
    """The window's spans of that name carry a list under ``attrs[field]``
    (one value per request the span finished): all of them, flattened."""
    return [x for s in window_spans(bench, name)
            for x in s.get("attrs", {}).get(field, ())]


# ------------------------------------------------------------------ #

def main(argv):
    loaded = load(argv[0])
    wi = window_and_idle(loaded["raw"])
    if wi is None:
        raise SystemExit("no window annotation, no device op, or clocks "
                         "that disagree: nothing to attribute")
    w0, w1, idle = wi
    every = sorted({n for n, _, _ in loaded["spans"]})
    out = {
        "window_s": (w1 - w0) / 1e9,
        "idle_s": sum(b - a for a, b in idle) / 1e9,
        "idle_under_s": {n: idle_seconds(loaded, [n]) for n in every},
        "idle_under_no_program_span_s":
            sum(b - a for a, b in idle) / 1e9 - (idle_seconds(loaded, every) or 0.0),
    }
    if len(argv) > 1:
        out["idle_under_union_s"] = {argv[1]: idle_seconds(loaded, argv[1].split(","))}
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main(sys.argv[1:])
