"""Share of the traced window in which the device ran nothing while the
innermost program span open on the engine's thread was
``gen_engine/admit`` ITSELF: admission's planning (prefix lookup, page
taking, ``_make_free``, the registry's inserts), not the stretch that
dispatches its device programs (``gen.idle_admit_prefill_share``; the two
add up to ``gen.admit_idle_share``). One of six parts that add up to the
device's idle share (``benchmark/idle_partition.py``)."""

from benchmark import idle_partition

UNIT = "%"
LAYER = "gen engine scheduler"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    return idle_partition.part_share(bench, "admit_plan")
