"""Of the layers x positions that admission prefilled in the window, the
share it did NOT run: the cross-decoder (gated memory units, cross
attention) keeps nothing of a position whose logits nobody reads, so
admission stops behind the last layer that writes a cache or a state.

Source: the engine's own counters (``GenerationEngine.stats``), differenced
over the window: admit_token_layers_skipped / (run + skipped). A program
without these counters reads nothing."""

UNIT = "%"
LAYER = "gen engine scheduler"
MOVES = "rollout_tokens_per_s"
SOURCE = "program_counter"


def read(bench):
    run = bench.counters.get("admit_token_layers_run")
    skipped = bench.counters.get("admit_token_layers_skipped")
    if run is None or skipped is None or run + skipped <= 0:
        return None
    return 100.0 * skipped / (run + skipped)
