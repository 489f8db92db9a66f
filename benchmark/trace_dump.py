"""Look at one trace by hand: planes, lines, the commonest event names and
a few events' stats. ``python -m benchmark.trace_dump <trace dir> [out.json]``

Also cuts the recorded sample that ``selfcheck`` reduces: with a third
argument ``--record <path.json.gz>`` it stores the first 60 ms of the
trace after its window opens.
"""

import collections
import glob
import json
import os
import sys


def describe(path: str) -> dict:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    out = {"file": path, "planes": []}
    for plane in pd.planes:
        p = {"name": plane.name, "lines": []}
        for line in plane.lines:
            events = list(line.events)
            names = collections.Counter(e.name for e in events)
            dur = collections.Counter()
            for e in events:
                dur[e.name] += e.duration_ns
            sample = []
            for e in events[:3] + [
                e for e in events if "kernel" in e.name.lower()
                or "custom" in e.name.lower() or "pallas" in e.name.lower()
            ][:4]:
                try:
                    stats = {k: str(v)[:160] for k, v in dict(e.stats).items()}
                except Exception as ex:     # a stat the reader cannot decode
                    stats = {"error": repr(ex)}
                sample.append({"name": e.name, "start_ns": e.start_ns,
                               "duration_ns": e.duration_ns, "stats": stats})
            p["lines"].append({
                "name": line.name, "n_events": len(events),
                "first_start_ns": events[0].start_ns if events else None,
                "last_end_ns": (events[-1].start_ns + events[-1].duration_ns)
                if events else None,
                "top_by_time": [[n, d / 1e9, names[n]]
                                for n, d in dur.most_common(25)],
                "sample": sample,
            })
        out["planes"].append(p)
    return out


def main(argv):
    files = sorted(glob.glob(
        os.path.join(argv[0], "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise SystemExit(f"no .xplane.pb under {argv[0]}")
    info = describe(files[-1])
    info["bytes"] = os.path.getsize(files[-1])
    text = json.dumps(info, indent=1)
    if len(argv) > 1 and not argv[1].startswith("--"):
        with open(argv[1], "w") as f:
            f.write(text)
    else:
        print(text)
    if "--record" in argv:
        from benchmark import trace_reduce

        raw = trace_reduce.load_xplane(files[-1])
        win = [s for s in raw["host_spans"] if s[0] == trace_reduce.WINDOW_SPAN]
        t0 = win[0][1] if win else 0.0
        trace_reduce.save_recorded(
            raw, argv[argv.index("--record") + 1], t0, t0 + 60e6)


if __name__ == "__main__":
    main(sys.argv[1:])
