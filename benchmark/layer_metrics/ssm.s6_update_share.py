"""Share of the device's busy time spent in the Mamba-1 step's update of
the per-slot recurrent state in decode: device time of the decode-chunk
program's (``jit_chunk``) ops whose largest operand is that state (XLA's
fusions: the selective scan has no kernel; ``benchmark/yoco_flops.py``),
over the busy union, both in the traced part of the window."""

from benchmark import yoco_flops

UNIT = "%"
LAYER = "model step"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.trace["busy_s"] <= 0:
        return None
    seconds = yoco_flops.state_update_seconds(bench)
    if seconds is None:
        return None
    return 100.0 * seconds / bench.trace["busy_s"]
