"""Share of admitted prompt tokens served from shared prefix pages.

Source: the engine's own counters (``GenerationEngine.stats``), differenced
over the window: prefix_hit_tokens / (prefix_hit_tokens + prefill_tokens).
"""

UNIT = "%"
LAYER = "gen engine scheduler"
MOVES = "rollout_tokens_per_s"
SOURCE = "program_counter"


def read(bench):
    hit = bench.counters.get("prefix_hit_tokens")
    cold = bench.counters.get("prefill_tokens")
    if hit is None or cold is None or hit + cold <= 0:
        return None
    return 100.0 * hit / (hit + cold)
