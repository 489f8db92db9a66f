"""Share of the device's busy time spent in the paged-decode kernel, both
of its programs (the full layers' ``%paged_decode`` and the window
layers' ``%paged_decode_window``): device time of those events of the
decode-chunk program (``jit_chunk``), found by name, over the busy union,
both in the traced part of the window. A trace without such a kernel
reads nothing."""

from benchmark import hybrid_flops, trace_reduce

UNIT = "%"
LAYER = "model step"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.trace["busy_s"] <= 0:
        return None
    seconds, count = trace_reduce.op_seconds(
        bench.trace, hybrid_flops.DECODE_KERNEL)
    if count <= 0:
        return None
    return 100.0 * seconds / bench.trace["busy_s"]
