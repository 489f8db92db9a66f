"""Share of the traced window in which the device ran nothing while the
innermost program span open on the engine's thread was
``gen_engine/dispatch`` (the warp operand, the table width), its child
``gen_engine/dispatch/seat`` (the page policy: holds, preemptions, pages
taken) or ``gen_engine/dispatch/enqueue`` (picking the chunk program and
enqueueing it with its table). One of six parts that add up to the
device's idle share (``benchmark/idle_partition.py``)."""

from benchmark import idle_partition

UNIT = "%"
LAYER = "gen engine scheduler"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    return idle_partition.part_share(bench, "dispatch")
