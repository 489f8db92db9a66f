"""Share of the device's busy time spent in the routed experts HELD here
(a model whose every block's second branch is an expert layer, two stacks
of them: the attention layers' and the linear layers'): device time of the
ops that stream a routed stack ``[layers of the kind, held, hidden,
width]`` (the ``moe_grouped`` kernel, or XLA's einsums; decode chunks and
admission prefill alike; the router and the shared expert are not among
them), over the busy union, both in the traced part of the window. How the
ops are found: ``benchmark/kda_flops.py``. A program without such stacks
reads nothing."""

from benchmark import kda_flops

UNIT = "%"
LAYER = "model step"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.trace["busy_s"] <= 0:
        return None
    seconds = kda_flops.expert_op_seconds(bench)
    if seconds is None:
        return None
    return 100.0 * seconds / bench.trace["busy_s"]
