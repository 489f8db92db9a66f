"""Share of the device's busy time spent streaming the layer stack of a
LOOPED model in decode: device time of the ops of the decode-chunk program
(``jit_chunk``) whose largest operand is the stack or a layer's slice of
it, over the busy union, both in the traced part of the window. How the
ops are found: ``benchmark/loop_flops.py``."""

from benchmark import loop_flops

UNIT = "%"
LAYER = "model step"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.trace["busy_s"] <= 0:
        return None
    seconds = loop_flops.weight_op_seconds(bench, program="jit_chunk")
    if seconds is None:
        return None
    return 100.0 * seconds / bench.trace["busy_s"]
