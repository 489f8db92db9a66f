"""Run one cell of BENCHMARK.json once, in this process, on this machine.

    python -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the contract's JSON object; the line
before it is free-form information (counts, medians, the correctness
numbers). Nothing here knows a cell's, a configuration's or a metric's
name: the cell names its configuration and traffic file, the traffic file
names its driver, and every per-layer metric is a reader of its own under
``layer_metrics/``. See README.md.
"""

import time

_PROCESS_T0 = time.perf_counter()   # set-up is counted from here

import argparse
import collections
import contextlib
import importlib.util
import json
import os
import shutil
import sys
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(ROOT, ".bench_work")    # git-ignored; traces land here
REHEARSAL_EXIT = 3


def load_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench_json: Dict, workload: str):
    cells = {c["name"]: c for c in bench_json["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no cell {workload!r}; cells: {sorted(cells)}")
    cell = cells[workload]
    cfg = next(c for c in bench_json["configs"] if c["name"] == cell["config"])
    return cell, load_json(ROOT, cfg["file"]), load_json(
        HERE, "traffic", cell["traffic"] + ".json"
    )


def applies(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_reader(name: str):
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + name.replace(".", "_").replace("-", "_"),
        path,
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def merge(base: Dict, over: Dict) -> Dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


class Bench:
    """What a driver and the metric readers share: the cell's data, the
    window's clock, spans, counters and samples, and the profiler."""

    def __init__(self, args, cell, arch, mix, peaks):
        self.cell, self.arch, self.mix = cell, arch, mix
        self.peaks = peaks
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.rehearse = args.rehearse
        self.trace_wanted = bool(args.trace)
        self.trace_seconds = min(
            float(mix.get("trace_seconds", 4.0)), self.seconds
        )
        self.trace_dir = os.path.join(WORK, "trace", cell["name"])
        self._spans: List = []
        self.counters: Dict[str, float] = collections.defaultdict(float)
        self.samples: Dict[str, List[float]] = collections.defaultdict(list)
        self.facts: Dict[str, Any] = {}     # driver -> readers
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.t_trace: Optional[float] = None
        self.trace: Optional[Dict] = None   # reduced trace
        self._tracing = False
        self._window_annotation = None
        self._compiles = 0
        self.setup_s: Optional[float] = None
        self.compiles_in_window = 0
        self.marks: List = []
        self.mark("jax_ready")
        if self.trace_wanted:
            self._drain()       # compiled now, not inside the window

    @staticmethod
    def _drain():
        import jax

        jax.block_until_ready(jax.numpy.zeros(()) + 1)

    # -- clock ------------------------------------------------------- #

    def mark(self, name: str):
        """A point of set-up, in seconds since the process started (info)."""
        self.marks.append([name, round(time.perf_counter() - _PROCESS_T0, 3)])

    def window_open(self):
        """End of set-up: everything after this is measured."""
        self.mark("window_open")
        self._compiles = 0
        self.samples.clear()
        self.counters.clear()
        self.t_open = time.perf_counter()
        self.setup_s = self.t_open - _PROCESS_T0

    def window_due(self) -> bool:
        return time.perf_counter() - self.t_open < self.seconds

    def poll(self):
        """Between steps: starts the profiler for the last
        ``trace_seconds`` of the window of a traced run."""
        if (not self.trace_wanted or self._tracing or self.trace is not None
                or self.t_open is None):
            return
        now = time.perf_counter()
        if now - self.t_open < self.seconds - self.trace_seconds:
            return
        import jax

        # let what was dispatched before drain, so that the trace holds the
        # device work of the traced steps and of no earlier one
        self._drain()
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._tracing = True
        self.t_trace = time.perf_counter()
        self._window_annotation = jax.profiler.TraceAnnotation(
            "bench/trace_window"
        )
        self._window_annotation.__enter__()

    def window_close(self):
        """Call after the last work of the window has been drained."""
        self.t_close = time.perf_counter()
        self.compiles_in_window = self._compiles
        if self._tracing:
            import jax

            from benchmark import trace_reduce

            self._window_annotation.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._tracing = False
            raw = trace_reduce.load_xplane_dir(self.trace_dir)
            # a rehearsal drives the profiler too, but the CPU has no
            # device plane to reduce
            if raw["planes"] or not self.rehearse:
                self.trace = trace_reduce.reduce(raw)

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    # -- spans, counters --------------------------------------------- #

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        if self._tracing:
            import jax

            with jax.profiler.TraceAnnotation("bench/" + name):
                yield
        else:
            yield
        self._spans.append((name, t0, time.perf_counter() - t0))

    def spans(self, name: str, traced_only: bool = False) -> List[float]:
        """Durations (s) of the window's spans of that name."""
        return [d for _, d in self.span_records(name, traced_only)]

    def span_records(self, name: str, traced_only: bool = False) -> List:
        """(start, duration) of the window's spans of that name; with
        ``traced_only``, of the part of the window the profiler saw."""
        lo = self.t_trace if traced_only else self.t_open
        if lo is None or self.t_close is None:
            return []
        return [(t, d) for n, t, d in self._spans
                if n == name and t >= lo and t + d <= self.t_close + 1e-9]

    def note_compile(self):
        self._compiles += 1


def _configure_jax():
    """Compile cache at a fixed place inside the checkout (or where
    JAX_COMPILATION_CACHE_DIR says), every program cached however small."""
    from areal_tpu.base import compile_cache

    cache_dir = compile_cache.configure()
    import jax

    if cache_dir is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax, cache_dir


def _check_device(jax, cell, peaks: Dict, rehearse: bool) -> Dict:
    dev = jax.devices()
    info = {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}
    if rehearse:
        return info
    if info["platform"] != "tpu" or info["kind"] not in peaks:
        raise SystemExit(
            f"no accelerator with published peaks: {info}; "
            f"known kinds: {sorted(peaks)}")
    if len(dev) < cell["chips"]:
        raise SystemExit(f"cell wants {cell['chips']} chips, found {len(dev)}")
    info["count"] = cell["chips"]
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny preset on the CPU: control flow only, no "
                    "device metric, always exits non-zero")
    args = ap.parse_args(argv)

    bench_json = load_json(ROOT, "BENCHMARK.json")
    if args.seconds is None:
        args.seconds = bench_json["run_seconds"]
    cell, arch, mix = find_cell(bench_json, args.workload)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        arch = merge(arch, load_json(HERE, "rehearse.json")["arch"])
        mix = merge(mix, mix.get("rehearse", {}))
    sys.path.insert(0, ROOT)
    jax, cache_dir = _configure_jax()
    peaks = load_json(HERE, "peaks.json")
    device = _check_device(jax, cell, peaks, args.rehearse)

    bench = Bench(args, cell, arch, mix, peaks.get(device["kind"]))
    import jax.monitoring

    def on_duration(event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            bench.note_compile()

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    driver = importlib.import_module("benchmark.drivers." + mix["driver"])
    result = driver.run(bench)    # {"attempted","failed","end_to_end","check","info"}

    correct = bool(result["check"].get("correct")) and (
        bench.compiles_in_window == 0)
    stats = jax.local_devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    info = {
        "cell": cell["name"], "seed": args.seed, "window_s": bench.window_s,
        "compiles_in_window": bench.compiles_in_window,
        "compile_cache": cache_dir, "check": result["check"],
        "setup_marks": bench.marks,
        **result.get("info", {}),
    }
    out: Dict[str, Any] = {
        "correct": correct, "attempted": int(result["attempted"]),
        "failed": int(result["failed"]), "metrics": {}, "device": device,
    }
    e2e = dict(result["end_to_end"], setup_s=bench.setup_s)
    if not args.trace:
        for m in bench_json["end_to_end"]:
            if applies(m, cell["name"]) and e2e.get(m["name"]) is not None:
                out["metrics"][m["name"]] = {
                    "value": float(e2e[m["name"]]), "unit": m["unit"]}
    else:
        info["end_to_end_of_traced_run"] = e2e
        for m in bench_json["per_layer"]:
            if not applies(m, cell["name"]):
                continue
            reader = load_reader(m["name"])
            if reader.UNIT != m["unit"]:
                raise SystemExit(
                    f"{m['name']}: reader says {reader.UNIT}, "
                    f"BENCHMARK.json says {m['unit']}")
            value = reader.read(bench)
            if value is not None:
                out["metrics"][m["name"]] = {
                    "value": float(value), "unit": m["unit"]}
        if bench.trace is not None:
            device["busy_s"] = bench.trace["busy_s"]
            device["window_s"] = bench.trace["window_s"]
            out["breakdown"] = {
                "device_ops": bench.trace["device_ops"][:10],
                "idle_gaps": bench.trace["idle_gaps"][:10],
            }
            info["trace"] = {k: bench.trace[k] for k in (
                "planes", "n_events", "modules", "clock_note")}
    print(json.dumps({"info": info}), flush=True)
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "correct": correct,
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "counts_only": sorted(out["metrics"])}))
        return REHEARSAL_EXIT
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
