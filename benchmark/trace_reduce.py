"""From a profiler trace to the numbers the benchmark reports.

Two stages, so that the second can be checked on a small recorded trace
(``python -m benchmark.selfcheck``) without a chip and without the
profiler's file format:

* ``load_xplane`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
  into plain lists: for every device plane its lines and their events
  (label, start ns, duration ns), and from the host planes the benchmark's
  own ``bench/...`` annotations, which are on the same clock. On this
  chip an op event's name is the whole HLO instruction, kilobytes long,
  and a Mosaic (Pallas) kernel shows under the name of the JAX scope that
  called it (``%closed_call.13``), never under its kernel function's name.
  ``op_label`` turns that into ``<program>/<%name> <opcode> <result>
  <- <largest operand>``, and marks Mosaic kernels ``tpu_custom_call``,
  so a reader finds "the Mosaic kernels of program X" by pattern.
* ``reduce`` computes: the traced window (the ``bench/trace_window``
  annotation), device busy time as the UNION of the op intervals inside it
  (ops nest: a ``while`` covers its body, so durations must not be
  summed), each op's self time (its duration minus what its children
  cover), each program's time, and the idle gaps, each labelled with what
  the host was doing (the innermost ``bench/`` span over the gap's middle)
  and which programs ran before and after it.

The program's own trace analysis (``areal_tpu/base/trace_analyzer.py``)
takes idle as line span minus summed durations; with nested ops that
counts busy time twice and can report negative idle, so it is not used.
"""

import bisect
import functools
import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench/trace_window"
SPAN_PREFIX = "bench/"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MIN_GAP_NS = 20_000.0


# ------------------------------------------------------------------ #
# stage 1: file -> plain lists
# ------------------------------------------------------------------ #

_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_INSTRUCTION = re.compile(r"^(\(.*?\)|\S+)\s+([\w\-]+)\((.*)$", re.S)


def program_name(module_event: str) -> str:
    """``jit_chunk(896149494975456816)`` -> ``jit_chunk``."""
    return module_event.split("(")[0]


@functools.lru_cache(maxsize=None)
def op_label(hlo: str) -> str:
    """``%fusion.230 = bf16[128,1536]{...} fusion(bf16[28,8960,1536]{...}
    %x, ...), kind=...`` -> ``%fusion.230 fusion bf16[128,1536] <-
    bf16[28,8960,1536]``. Layouts go; the largest operand stays, because it
    says which weight or pool the op streams."""
    name, _, rest = hlo.partition(" = ")
    plain = _LAYOUT.sub("", rest)
    m = _INSTRUCTION.match(plain)
    result, opcode, args = m.groups() if m else ("", "?", plain)
    if 'custom_call_target="' in plain:
        opcode += ":" + plain.split('custom_call_target="')[1].split('"')[0]

    def elems(shape):
        n = 1
        for d in shape[shape.index("[") + 1:-1].split(","):
            n *= int(d) if d else 1
        return n

    operands = _SHAPE.findall(args.split("), ")[0])
    big = max(operands, key=elems, default="")
    out = " ".join(x for x in (
        name, opcode, ",".join(_SHAPE.findall(result)[:2]),
        ("<- " + big) if big else "") if x)
    return out[:160]


def _labelled(lines: List[Dict]) -> List[Dict]:
    """Shorten op names and put the program each op ran in before them."""
    mods = sorted(
        (e for ln in lines if ln["name"] == MODULES_LINE for e in ln["events"]),
        key=lambda e: e[1])
    starts = [e[1] for e in mods]
    for ln in lines:
        if ln["name"] == MODULES_LINE:
            ln["events"] = [[program_name(n), s, d] for n, s, d in ln["events"]]
        elif ln["name"] == OPS_LINE:
            out = []
            for n, s, d in ln["events"]:
                i = bisect.bisect_right(starts, s) - 1
                inside = i >= 0 and s < mods[i][1] + mods[i][2]
                prog = program_name(mods[i][0]) if inside else "?"
                out.append([prog + "/" + op_label(n), s, d])
            ln["events"] = out
    return lines


def load_xplane(path: str) -> Dict:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    raw = {"planes": [], "host_spans": []}
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = []
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                events = [
                    [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                    for ev in line.events
                ]
                lines.append({"name": line.name, "events": events})
            raw["planes"].append(
                {"name": plane.name, "lines": _labelled(lines)})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        raw["host_spans"].append(
                            [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                        )
    return raw


def load_xplane_dir(trace_dir: str) -> Dict:
    files = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return load_xplane(files[-1])


def load_recorded(path: str) -> Dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def save_recorded(raw: Dict, path: str, t0_ns: float, t1_ns: float):
    """Cut ``raw`` to the events that start inside [t0, t1) and store it."""
    def cut(events):
        return [e for e in events if t0_ns <= e[1] < t1_ns]

    small = {
        "planes": [
            {"name": p["name"],
             "lines": [{"name": ln["name"], "events": cut(ln["events"])}
                       for ln in p["lines"]]}
            for p in raw["planes"]
        ],
        "host_spans": [
            e for e in raw["host_spans"]
            if e[0] == WINDOW_SPAN or t0_ns <= e[1] < t1_ns
        ],
    }
    with gzip.open(path, "wt") as f:
        json.dump(small, f, separators=(",", ":"))


# ------------------------------------------------------------------ #
# stage 2: plain lists -> numbers
# ------------------------------------------------------------------ #

def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def self_times(events: Sequence[Sequence]) -> Dict[str, List[float]]:
    """name -> [self seconds, count] for one line of possibly nested events
    (a child lies wholly inside its parent)."""
    out: Dict[str, List[float]] = {}
    stack: List[List] = []      # [name, end, self_ns]

    def close(item):
        rec = out.setdefault(item[0], [0.0, 0])
        rec[0] += max(item[2], 0.0) / 1e9
        rec[1] += 1

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    while stack:
        close(stack.pop())
    return out


def _line(plane: Dict, name: str) -> Optional[Dict]:
    return next((ln for ln in plane["lines"] if ln["name"] == name), None)


def _clip(events, w0, w1):
    out = []
    for name, start, dur in events:
        lo, hi = max(start, w0), min(start + dur, w1)
        if hi > lo:
            out.append([name, lo, hi - lo])
    return out


def _label(gap_mid: float, spans: List[Sequence], modules: List[Sequence]) -> str:
    inner = None
    for name, start, dur in spans:
        if start <= gap_mid < start + dur and name != WINDOW_SPAN:
            if inner is None or dur < inner[2]:
                inner = (name, start, dur)
    host = inner[0][len(SPAN_PREFIX):] if inner else "no bench span"
    before = after = "-"
    for name, start, dur in modules:        # sorted by start
        if start + dur <= gap_mid:
            before = name
        elif start >= gap_mid:
            after = name
            break
    return f"{host}: {before} > {after}"


def reduce(raw: Dict) -> Dict:
    planes = raw["planes"]
    if not planes:
        raise ValueError("the trace has no device plane: nothing ran on a TPU")
    spans = raw["host_spans"]
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    all_ops = [e for p in planes for e in (_line(p, OPS_LINE) or {"events": []})["events"]]
    if not all_ops:
        raise ValueError("no operation ran on the device inside the trace")
    dev_lo = min(e[1] for e in all_ops)
    dev_hi = max(e[1] + e[2] for e in all_ops)
    if win:
        w0, w1 = win[0][1], win[0][1] + win[0][2]
        clock_note = "window is the bench/trace_window annotation"
        if dev_hi < w0 or dev_lo > w1:
            # host and device clocks disagree: fall back to the device's own
            w0, w1 = dev_lo, dev_hi
            clock_note = ("device events lie outside the host annotation; "
                          "window taken from the first to the last device op")
    else:
        w0, w1 = dev_lo, dev_hi
        clock_note = "no window annotation; first to last device op"

    busy, op_self, op_total, modules, gap_labels = [], {}, {}, {}, {}
    n_events = 0
    for i, plane in enumerate(planes):
        ops = _clip((_line(plane, OPS_LINE) or {"events": []})["events"], w0, w1)
        n_events += len(ops)
        merged = union([(s, s + d) for _, s, d in ops])
        busy.append(sum(b - a for a, b in merged) / 1e9)
        for name, rec in self_times(ops).items():
            tot = op_self.setdefault(name, [0.0, 0])
            tot[0] += rec[0]
            tot[1] += rec[1]
        for name, _, d in ops:
            tot = op_total.setdefault(name, [0.0, 0])
            tot[0] += d / 1e9
            tot[1] += 1
        mods = sorted(
            _clip((_line(plane, MODULES_LINE) or {"events": []})["events"], w0, w1),
            key=lambda e: e[1])
        for name, _, d in mods:
            tot = modules.setdefault(name, [0.0, 0])
            tot[0] += d / 1e9
            tot[1] += 1
        if i == 0:      # gaps of the first chip, labelled
            edges = [(w0, w0)] + merged + [(w1, w1)]
            for (_, a), (b, _) in zip(edges, edges[1:]):
                if b - a >= MIN_GAP_NS:
                    lab = _label((a + b) / 2, spans, mods)
                    rec = gap_labels.setdefault(lab, [0.0, 0])
                    rec[0] += (b - a) / 1e9
                    rec[1] += 1
    n = len(planes)
    by_time = lambda d: sorted(  # noqa: E731
        ([k, v[0] / n] for k, v in d.items()), key=lambda kv: -kv[1])
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / n,
        "device_ops": by_time(op_self),
        "idle_gaps": sorted(
            ([k, v[0]] for k, v in gap_labels.items()), key=lambda kv: -kv[1]),
        "op_total_s": {k: [v[0] / n, v[1] // n] for k, v in op_total.items()},
        "modules": {k: [v[0] / n, v[1] // n] for k, v in modules.items()},
        "planes": [p["name"] for p in planes],
        "n_events": n_events,
        "clock_note": clock_note,
    }


def op_seconds(reduced: Dict, pattern: str) -> Tuple[float, int]:
    """Summed device seconds and count of the ops whose name matches."""
    rx = re.compile(pattern)
    hits = [v for k, v in reduced["op_total_s"].items() if rx.search(k)]
    return sum(v[0] for v in hits), sum(v[1] for v in hits)
