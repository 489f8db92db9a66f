"""Share of the (row, expert) pairs that landed on experts HELD here: of
the pairs the running rows chose in the window's decode chunks (rows x
expert blocks x steps x experts a token), those whose expert is one of
this rank's. A quarter where routing over the experts of four ranks is
even; what the rank computes of a step's routed work, and what the absent
exchange would bring it from the other ranks' rows.

Source: the engine's own counters (``GenerationEngine.stats``), differenced
over the window by the driver: moe_pairs_held / moe_pairs. A program that
counts no such pairs reads nothing."""

UNIT = "%"
LAYER = "expert MLP"
MOVES = "rollout_tokens_per_s"
SOURCE = "program_counter"


def read(bench):
    pairs = bench.counters.get("moe_pairs")
    held = bench.counters.get("moe_pairs_held")
    if not pairs or held is None:
        return None
    return 100.0 * held / pairs
