"""Share of the traced window in which the device ran nothing while the
innermost program span open on the engine's thread was
``gen_engine/admit/prefill``: the stretch of an admission that builds and
dispatches its device programs (the prefill waves, the state copies, the
commit), so the gaps BETWEEN a wave's programs and before its first. One
of six parts that add up to the device's idle share
(``benchmark/idle_partition.py``)."""

from benchmark import idle_partition

UNIT = "%"
LAYER = "gen engine scheduler"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    return idle_partition.part_share(bench, "admit_prefill")
