"""Share of the traced window in which the device ran nothing while the
innermost program span open on the engine's thread was
``gen_engine/harvest`` (the host's release work: the finished slots'
outputs to lists, their pages back, the registry) or its child
``gen_engine/harvest/pull`` (the one ``device_get`` of their outputs).
One of six parts that add up to the device's idle share
(``benchmark/idle_partition.py``)."""

from benchmark import idle_partition

UNIT = "%"
LAYER = "gen engine scheduler"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    return idle_partition.part_share(bench, "harvest")
