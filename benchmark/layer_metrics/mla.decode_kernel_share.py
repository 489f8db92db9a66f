"""Share of the device's busy time spent in the latent decode kernel:
device time of the ``%mla_decode`` events of the decode-chunk program
(``jit_chunk``) over the busy union, both in the traced part of the
window. A trace without such a kernel reads nothing."""

from benchmark import mla_flops, trace_reduce

UNIT = "%"
LAYER = "model step"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.trace["busy_s"] <= 0:
        return None
    seconds, count = trace_reduce.op_seconds(bench.trace, mla_flops.MLA_KERNEL)
    if count <= 0:
        return None
    return 100.0 * seconds / bench.trace["busy_s"]
