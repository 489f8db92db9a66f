"""Share of the traced window in which the device ran nothing and the
engine's thread was in none of the five named parts of a chunk boundary:
outside every program span (the caller's loop between two
``engine.step`` calls: the driver here, the gen server's loop in a
deployment), in ``gen_engine/chunk``'s own self time, or under any other
span (``gen_engine/census``, ``gen_engine/weight_swap``). The last of six
parts that add up to the device's idle share
(``benchmark/idle_partition.py``)."""

from benchmark import idle_partition

UNIT = "%"
LAYER = "gen engine scheduler"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    return idle_partition.part_share(bench, idle_partition.REST)
