"""How warm the start was: of the programs built before the window opened
that the persistent compile cache was asked for, the share it held
(``compile/cache_hits`` / (hits + misses), ``start_counters.py``). 100 on
a warm start, near 0 on the first run after the cache directory was
removed. ``None`` where the run caches nothing."""

from benchmark.layer_metrics import start_counters

UNIT = "%"
LAYER = "start-up"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(bench):
    t = start_counters.start_totals(bench)
    if t is None or t["cache_hits"] + t["cache_misses"] <= 0:
        return None
    return 100.0 * t["cache_hits"] / (t["cache_hits"] + t["cache_misses"])
