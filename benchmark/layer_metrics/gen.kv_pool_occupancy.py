"""Mean share of KV-pool pages held (slots and prefix cache), sampled
from ``engine.kv_pool_occupancy()`` after every ``engine.step``."""

UNIT = "%"
LAYER = "gen engine scheduler"
MOVES = "rollout_tokens_per_s"
SOURCE = "program_counter"


def read(bench):
    xs = bench.samples.get("kv_pool_occupancy")
    if not xs:
        return None
    return 100.0 * sum(xs) / len(xs)
