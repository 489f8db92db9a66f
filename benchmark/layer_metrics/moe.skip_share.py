"""Share of the window's routed rows (running slots x expert layers x
decode steps) whose router chose the SKIP: they read no expert.

Source: the engine's own counters (``GenerationEngine.stats``), differenced
over the window: moe_skip_rows / moe_rows. With a router that has no
preference it sits near 1 / (experts + 1)."""

UNIT = "%"
LAYER = "expert MLP"
MOVES = "rollout_tokens_per_s"
SOURCE = "program_counter"


def read(bench):
    skipped = bench.counters.get("moe_skip_rows")
    rows = bench.counters.get("moe_rows")
    if skipped is None or not rows:
        return None
    return 100.0 * skipped / rows
