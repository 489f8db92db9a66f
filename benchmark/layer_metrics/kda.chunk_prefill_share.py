"""Share of the device's busy time spent in admission's chunked program
(``jit_extend``: a prompt's chunks of 128 tokens through every layer, the
delta-rule layers in their CHUNKED form, ``ops/kda.py:scan_chunked``), over
the busy union, both in the traced part of the window: what of
``gen.decode_step_device_ms`` is prefill. With the prefix cache on, a
group's first member runs its whole prompt through it and the other
fifteen the tokens behind the snapshot's page-aligned boundary. A program
without linear layers reads nothing."""

from benchmark import kda_flops

UNIT = "%"
LAYER = "model step"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.trace["busy_s"] <= 0:
        return None
    seconds = kda_flops.prefill_program_seconds(bench)
    if seconds is None:
        return None
    return 100.0 * seconds / bench.trace["busy_s"]
