"""How often the start found a program BUILT: of the programs the engines
asked the program store for before the window opened
(``areal_tpu/base/program_store.py``), the share it held
(``compile/store_hits`` / (hits + misses)). 0 on a first run, near 100 on
a warm one: a start that reads 100 here ran no engine program's Python or
lowering (``start.trace_lower_s`` is then what the benchmark's own
programs cost). The counters run for the life of the process, so the
``compile/program`` records that started at or after ``bench.t_open`` are
taken off, as ``start_counters.py`` does for its totals: ``stored: true``
is a hit, ``stored: false`` a miss. ``None`` where the program has no
store (the parent of PR 61) or the store is off."""

UNIT = "%"
LAYER = "start-up"
MOVES = "setup_s"
SOURCE = "program_counter"
RECORD = "compile/program"


def read(bench):
    from areal_tpu.base import metrics, tracing

    now = metrics.counters.snapshot()
    hits = now.get("compile/store_hits", 0.0)
    misses = now.get("compile/store_misses", 0.0)
    for rec in tracing.spans_since(bench.t_open):
        stored = (rec.get("attrs") or {}).get("stored")
        if rec["name"] == RECORD and stored is not None:
            if stored:
                hits -= 1
            else:
                misses -= 1
    if hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)
