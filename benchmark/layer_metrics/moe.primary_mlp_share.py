"""Share of the device's busy time spent in the (primary) experts'
matmuls of a ``smallthinker`` model: device time of the ops that stream
the stacked expert weights (decode chunks and admission prefill alike)
over the busy union, both in the traced part of the window. How the ops
are found: ``benchmark/hybrid_flops.py``."""

from benchmark import hybrid_flops

UNIT = "%"
LAYER = "model step"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.trace["busy_s"] <= 0:
        return None
    seconds = hybrid_flops.primary_op_seconds(bench)
    if seconds is None:
        return None
    return 100.0 * seconds / bench.trace["busy_s"]
