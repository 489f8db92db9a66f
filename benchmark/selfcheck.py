"""Checks of the yardstick itself, on the CPU: ``python -m benchmark.selfcheck``

1. BENCHMARK.json against the files it names: every configuration,
   traffic mix, driver and per-layer reader is there, and each reader's
   unit, layer, source and ``moves`` agree with its entry.
2. ``trace_reduce`` on a synthetic trace with known answers (nested ops,
   overlapping intervals, a labelled gap) and on the recorded chip trace
   under ``testdata/``, against the numbers stored beside it.
3. The traffic generator: the same seed gives the same work, another seed
   the same sizes in another order.
4. The FLOP and byte arithmetic against hand-worked values, and the
   resident count of a decode chunk (``resident.py``) on a hand-made
   population (its cases in full: ``python -m pytest benchmark/tests``).
5. Every cell with ``--rehearse`` (tiny preset, control flow only), unless
   ``--no-cells`` is given.

Exit code 0 only if every check passed.
"""

import json
import os
import subprocess
import sys

from benchmark import flops, resident, trace_reduce, traffic_gen
from benchmark.run import HERE, REHEARSAL_EXIT, ROOT, applies, load_json, load_reader

FAILED = []


def check(cond: bool, what: str):
    print(("ok   " if cond else "FAIL ") + what)
    if not cond:
        FAILED.append(what)


def check_files(bench_json):
    e2e = {m["name"]: m for m in bench_json["end_to_end"]}
    for cfg in bench_json["configs"]:
        arch = load_json(ROOT, cfg["file"])
        check(arch.get("source") == cfg["source"], f"config {cfg['name']}: source agrees")
        check(arch.get("reduced") == cfg["reduced"], f"config {cfg['name']}: reduced agrees")
        check(os.path.exists(os.path.join(HERE, "reference", arch["reference"] + ".py")),
              f"config {cfg['name']}: reference {arch['reference']} exists")
    for cell in bench_json["workloads"]:
        mix = load_json(HERE, "traffic", cell["traffic"] + ".json")
        check(os.path.exists(os.path.join(HERE, "drivers", mix["driver"] + ".py")),
              f"cell {cell['name']}: driver {mix['driver']} exists")
        check(any(applies(m, cell["name"]) for m in bench_json["per_layer"]),
              f"cell {cell['name']}: has a per-layer metric")
    for m in bench_json["per_layer"]:
        r = load_reader(m["name"])
        check((r.UNIT, r.LAYER, r.MOVES, r.SOURCE)
              == (m["unit"], m["layer"], m["moves"], m["source"]),
              f"reader {m['name']}: unit, layer, moves, source agree")
        cells = [c["name"] for c in bench_json["workloads"] if applies(m, c["name"])]
        check(all(applies(e2e[m["moves"]], c) for c in cells),
              f"reader {m['name']}: {m['moves']} is reported wherever it is")


def check_trace_reduce():
    ms = 1e6
    raw = {
        "planes": [{"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["while", 10 * ms, 40 * ms],            # covers the next two
                ["fusion.1", 10 * ms, 10 * ms],
                ["kernel_a", 25 * ms, 20 * ms],
                ["copy", 60 * ms, 10 * ms],
                ["late", 95 * ms, 20 * ms],             # clipped at 100
            ]},
            {"name": "XLA Modules", "events": [
                ["jit_a", 10 * ms, 40 * ms], ["jit_b", 60 * ms, 10 * ms],
                ["jit_c", 95 * ms, 20 * ms]]},
        ]}],
        "host_spans": [
            ["bench/trace_window", 0.0, 100 * ms],
            ["bench/outer", 0.0, 100 * ms], ["bench/inner", 48 * ms, 14 * ms],
        ],
    }
    r = trace_reduce.reduce(raw)
    near = lambda a, b: abs(a - b) < 1e-9   # noqa: E731
    check(near(r["window_s"], 0.100), "synthetic: window is the annotation")
    check(near(r["busy_s"], 0.055), "synthetic: busy is the union (40+10+5 ms), not the sum")
    ops = dict(r["device_ops"])
    check(near(ops["while"], 0.010) and near(ops["kernel_a"], 0.020),
          "synthetic: self time leaves a parent what its children do not cover")
    gaps = dict(r["idle_gaps"])
    check(near(gaps.get("inner: jit_a > jit_b", 0), 0.010),
          "synthetic: a gap is labelled with the innermost span and its neighbours")
    check(near(sum(gaps.values()), 0.045), "synthetic: gaps add up to window - busy")
    check(near(trace_reduce.op_seconds(r, "kernel")[0], 0.020), "synthetic: op_seconds by pattern")

    rec = os.path.join(HERE, "testdata", "trace_small.json.gz")
    exp = os.path.join(HERE, "testdata", "trace_small.expected.json")
    if not os.path.exists(rec):
        check(False, "recorded trace present under testdata/")
        return
    got = trace_reduce.reduce(trace_reduce.load_recorded(rec))
    want = load_json(exp)
    for k in ("window_s", "busy_s", "n_events"):
        check(abs(got[k] - want[k]) <= 1e-9 * max(1.0, abs(want[k])),
              f"recorded: {k} = {want[k]}")
    check([n for n, _ in got["device_ops"][:5]] == want["top_ops"],
          "recorded: the five heaviest ops, in order")
    check(0.0 < got["busy_s"] <= got["window_s"], "recorded: 0 < busy <= window")


def check_traffic():
    for name in sorted(os.listdir(os.path.join(HERE, "traffic"))):
        mix = load_json(HERE, "traffic", name)
        if "clients" in mix:
            a = traffic_gen.RequestStream(mix, 7, 1000)
            b = traffic_gen.RequestStream(mix, 7, 1000)
            c = traffic_gen.RequestStream(mix, 2**31 + 9, 1000)
            n = mix["n_groups"] * mix["group_size"]
            ra, rb, rc = ([next(s) for _ in range(n)] for s in (a, b, c))
            check([(r.prompt, r.max_new_tokens) for r in ra]
                  == [(r.prompt, r.max_new_tokens) for r in rb],
                  f"{name}: same seed, same stream")
            k = mix["group_size"]
            groups = lambda rs: [  # noqa: E731
                (len(rs[i].prompt), sorted(r.max_new_tokens for r in rs[i:i + k]))
                for i in range(0, n, k)]
            check(groups(ra) == groups(rc)
                  and [r.max_new_tokens for r in ra] != [r.max_new_tokens for r in rc],
                  f"{name}: another seed, the same groups in the same order, "
                  "permuted within")
            check(all(ra[i].prompt == ra[i - i % mix["group_size"]].prompt for i in range(n)),
                  f"{name}: a group shares its prompt")
            check([(len(r.prompt), r.max_new_tokens) for r in a.initial()]
                  == [(len(r.prompt), r.max_new_tokens) for r in c.initial()],
                  f"{name}: the opening population has the same sizes for every seed")
        if "max_tokens_per_batch" in mix:
            a = traffic_gen.train_batches(mix, 7, 1000)
            c = traffic_gen.train_batches(mix, 2**31 + 9, 1000)
            check([sorted(b.seqlens) for b in a] == [sorted(b.seqlens) for b in c]
                  and [b.seqlens for b in a] != [b.seqlens for b in c],
                  f"{name}: another seed, the same batches in the same order, "
                  "permuted within")
            check(all(sum(b.seqlens) <= mix["max_tokens_per_batch"] and len(b.seqlens) >= 1
                      for b in a), f"{name}: every batch fits its budget")


def check_flops():
    a = load_json(HERE, "configs", "r1d-qwen-1p5b.json")
    check(flops.param_count(a) == 1_777_088_000, "1.5B: 1,777,088,000 parameters")
    check(flops.kv_bytes_per_token(a) == 28_672, "1.5B: 28,672 B of KV a token")
    check(flops.attention_forward_flops(a, [1000]) == 2 * 2 * 500_000 * 128 * 12 * 28,
          "attention forward FLOPs of one 1000-token sequence")
    check(flops.train_flops(a, [10]) > 3 * flops.forward_flops(a, [10]) - 1,
          "train >= 3x forward")


def check_resident():
    def group(n, plen, tok):
        return [{"req": traffic_gen.Request("r", [tok] * plen, 4096), "chunks": 0}
                for _ in range(n)]

    rows = group(16, 641, 1) + group(3, 300, 2) + group(1, 900, 3)
    per_slot, distinct = resident.ChunkResident(128, 16).count(rows)
    check(per_slot == 16 * 648 + 3 * 307 + 907,
          "resident: per slot, prompt - 1 + half a chunk a row")
    check(per_slot - distinct == (15 * 5 + 2 * 2) * 128,
          "resident: a group's whole prompt pages count once, a lone row's all")
    check(all(r["chunks"] == 1 for r in rows), "resident: the count advances the rows")


def check_cells(bench_json):
    for cell in bench_json["workloads"]:
        p = subprocess.run(
            [sys.executable, "-m", "benchmark.run", "--workload", cell["name"],
             "--seed", str(2**31 + 11), "--seconds", "3", "--trace", "1", "--rehearse"],
            cwd=ROOT, capture_output=True, text=True, timeout=900,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        last = (p.stdout.strip().splitlines() or ["{}"])[-1]
        ok = p.returncode == REHEARSAL_EXIT and json.loads(last).get("correct") is True
        check(ok, f"rehearsal of {cell['name']} (exit {p.returncode})")
        if not ok:
            print(p.stdout[-2000:], p.stderr[-2000:])


def main(argv):
    bench_json = load_json(ROOT, "BENCHMARK.json")
    check_files(bench_json)
    check_trace_reduce()
    check_traffic()
    check_flops()
    check_resident()
    if "--no-cells" not in argv:
        check_cells(bench_json)
    print(f"{len(FAILED)} failed")
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
