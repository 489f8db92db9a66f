"""Share of the device's busy time spent in the routed experts HELD here
and in the latent projections around them: device time of the ops that
stream the expert blocks' routed stacks ``[blocks, held, ...]`` (the
``moe_grouped`` kernel, or XLA's einsums) or the latent down- and
up-projection (decode chunks and admission prefill alike; the router and
the shared expert are not among them), over the busy union, both in the
traced part of the window: what of ``gen.decode_step_device_ms`` the
rank's share of the experts is. How the ops are found:
``benchmark/latent_moe_flops.py``. A program without such stacks reads
nothing."""

from benchmark import latent_moe_flops

UNIT = "%"
LAYER = "model step"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.trace["busy_s"] <= 0:
        return None
    routed = latent_moe_flops.routed_op_seconds(bench)
    if routed is None:
        return None
    latent = latent_moe_flops.latent_op_seconds(bench) or 0.0
    return 100.0 * (routed + latent) / bench.trace["busy_s"]
