"""Share of the device's busy time spent in the top-1 expert matmuls of
decode: device time of the ops of the decode-chunk program (``jit_chunk``)
whose largest operand is the stacked experts ``[L, X, hidden,
moe_intermediate_size]`` (XLA's fusions or the ``moe_grouped`` kernel;
``benchmark/cca_flops.py``), over the busy union, both in the traced part
of the window."""

from benchmark import cca_flops

UNIT = "%"
LAYER = "model step"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.trace["busy_s"] <= 0:
        return None
    seconds = cca_flops.expert_op_seconds(bench)
    if seconds is None:
        return None
    return 100.0 * seconds / bench.trace["busy_s"]
