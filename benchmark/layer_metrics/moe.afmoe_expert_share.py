"""Share of the device's busy time spent in the ROUTED experts of an
``afmoe`` model: device time of the ops that stream the expert stack
(``bf16[6,128,2048,1024]`` / ``[6,128,1024,2048]`` at the benchmark's cut,
one layer's slice of it, or ``%moe_grouped`` by name; decode chunks and
admission prefill alike; the shared expert, the router and the dense
layers' MLPs are not among them) over the busy union, both in the traced
part of the window. How the ops are found: ``benchmark/afmoe_flops.py``."""

from benchmark import afmoe_flops

UNIT = "%"
LAYER = "model step"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.trace["busy_s"] <= 0:
        return None
    seconds = afmoe_flops.expert_op_seconds(bench)
    if seconds is None:
        return None
    return 100.0 * seconds / bench.trace["busy_s"]
