"""What a start spent building programs, for the ``start.*`` readers.

The program's compile listener (``areal_tpu.base.tracing``) counts every
executable JAX builds or loads from its persistent cache on the process's
``metrics.counters`` (``compile/programs``, ``compile/trace_s``,
``compile/lower_s``, ``compile/backend_s``, ``compile/cache_hits``,
``compile/cache_misses``) and leaves one ``compile/program`` record a
program in its span ring. The counters run for the life of the process; a
start ends where the window opens. So: the totals when the run ends, less
the records that started at or after ``bench.t_open`` (none inside the
window of a correct run; the comparison with the plain reference after it
builds a few). Not a reader itself: no entry of ``BENCHMARK.json`` names
it.
"""

from typing import Dict, Optional

STAGES = ("trace_s", "lower_s", "backend_s")
RECORD = "compile/program"


def start_totals(bench) -> Optional[Dict[str, float]]:
    """``{"programs", "trace_s", "lower_s", "backend_s", "cache_hits",
    "cache_misses"}`` of the start, or None where the program keeps no
    such counters (one from before the listener existed)."""
    from areal_tpu.base import metrics, tracing

    now = metrics.counters.snapshot()
    if "compile/programs" not in now:
        return None
    out = {k: now.get("compile/" + k, 0.0) for k in (
        "programs", *STAGES, "cache_hits", "cache_misses")}
    for rec in tracing.spans_since(bench.t_open):
        if rec["name"] != RECORD:
            continue
        a = rec["attrs"]
        out["programs"] -= 1
        for k in STAGES:
            out[k] -= a[k]
        if a["cache_hit"] is not None:
            out["cache_hits" if a["cache_hit"] else "cache_misses"] -= 1
    return out
