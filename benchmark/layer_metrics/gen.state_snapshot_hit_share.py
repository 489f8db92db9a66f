"""Share of the window's admissions whose recurrent state was seeded
from a SNAPSHOT in the prefix cache (and not by prefilling the prompt).

Source: the engine's own counters (``GenerationEngine.stats``), differenced
over the window: state_snapshot_hits / admitted. It should sit where the
share of admissions that hit the prefix cache does: every member of a
GRPO group after the first."""

UNIT = "%"
LAYER = "gen engine scheduler"
MOVES = "rollout_tokens_per_s"
SOURCE = "program_counter"


def read(bench):
    hits = bench.counters.get("state_snapshot_hits")
    admitted = bench.counters.get("admitted")
    if hits is None or not admitted:
        return None
    return 100.0 * hits / admitted
