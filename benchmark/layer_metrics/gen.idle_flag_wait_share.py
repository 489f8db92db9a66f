"""Share of the traced window in which the device ran nothing while the
innermost program span open on the engine's thread was
``gen_engine/flag_wait``: the device has finished the chunk, the host has
not heard yet (the flag copy's way back, the wake-up of the waiting
thread). One of six parts that add up to the device's idle share
(``benchmark/idle_partition.py``: every idle nanosecond goes to ONE
span, the innermost)."""

from benchmark import idle_partition

UNIT = "%"
LAYER = "gen engine scheduler"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    return idle_partition.part_share(bench, "flag_wait")
