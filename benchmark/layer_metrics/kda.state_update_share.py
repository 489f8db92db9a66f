"""Share of the device's busy time spent updating the per-slot delta-rule
state in decode: device time of the decode-chunk program's (``jit_chunk``)
state update, the ``kda_decode`` kernel by name or XLA's ops over the
state array by their largest operand (``benchmark/kda_flops.py``), over
the busy union, both in the traced part of the window. A program without
such layers reads nothing."""

from benchmark import kda_flops

UNIT = "%"
LAYER = "model step"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.trace["busy_s"] <= 0:
        return None
    seconds = kda_flops.state_update_seconds(bench)
    if seconds is None:
        return None
    return 100.0 * seconds / bench.trace["busy_s"]
