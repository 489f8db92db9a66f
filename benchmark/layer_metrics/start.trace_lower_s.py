"""Seconds the start spent tracing programs in Python and lowering them to
MLIR, summed over every program built before the window opened: the share
of ``start.program_build_s`` that a warm compile cache does NOT skip (the
cache is keyed by the lowered module). From the program's
``compile/trace_s`` + ``compile/lower_s`` counters
(``start_counters.py``)."""

from benchmark.layer_metrics import start_counters

UNIT = "s"
LAYER = "start-up"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(bench):
    t = start_counters.start_totals(bench)
    if t is None:
        return None
    return t["trace_s"] + t["lower_s"]
