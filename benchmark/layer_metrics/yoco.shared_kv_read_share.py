"""Share of the device's busy time spent reading the ONE full attention
layer's keys and values: device time of the paged kernel's full program
(``%paged_decode``, by name: the full layer's call and the seven
cross-attention layers' that share its cache) in the decode-chunk program
(``jit_chunk``), over the busy union, both in the traced part of the
window. Sharing the cache saves memory, not reads: this is what the reads
cost. A trace without such a kernel, or a configuration of another family,
reads nothing."""

from benchmark import trace_reduce, yoco_flops

UNIT = "%"
LAYER = "model step"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.trace["busy_s"] <= 0:
        return None
    if bench.arch.get("model_type") != "phi4flash":
        return None
    seconds, count = trace_reduce.op_seconds(
        bench.trace, yoco_flops.FULL_KERNEL)
    if count <= 0:
        return None
    return 100.0 * seconds / bench.trace["busy_s"]
