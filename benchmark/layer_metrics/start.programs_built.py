"""Executables the start built or loaded before the window opened: the
program's ``compile/programs`` counter (``start_counters.py``). Each is a
trace, a lowering and a compile or a cache load; eager one-op programs
count like any other."""

from benchmark.layer_metrics import start_counters

UNIT = "programs"
LAYER = "start-up"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(bench):
    t = start_counters.start_totals(bench)
    return None if t is None else t["programs"]
