"""Seconds the start spent making programs runnable: Python tracing,
lowering to MLIR, and the backend's share (an XLA compile, or the load of
a cached executable), summed over every program built before the window
opened. From the program's ``compile/trace_s`` + ``compile/lower_s`` +
``compile/backend_s`` counters (``start_counters.py``). What is left of
``setup_s`` is imports, the backend's start, weights and execution."""

from benchmark.layer_metrics import start_counters

UNIT = "s"
LAYER = "start-up"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(bench):
    t = start_counters.start_totals(bench)
    if t is None:
        return None
    return sum(t[k] for k in start_counters.STAGES)
