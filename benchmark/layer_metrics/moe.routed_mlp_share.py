"""Share of the device's busy time spent in the ROUTED experts' matmuls:
device time of the ops that stream the stacked routed-expert weights
(decode chunks and admission prefill alike; the shared expert and the
leading dense layer are not among them) over the busy union, both in the
traced part of the window. How the ops are found:
``benchmark/mla_flops.py``."""

from benchmark import mla_flops

UNIT = "%"
LAYER = "model step"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.trace["busy_s"] <= 0:
        return None
    seconds = mla_flops.routed_op_seconds(bench)
    if seconds is None:
        return None
    return 100.0 * seconds / bench.trace["busy_s"]
