"""The share of the resident keys and values of a window's decode chunks
that rows hold IN COMMON: ``100 x (1 - sum(distinct) / sum(per_slot))``
over the window's chunks, both counted by the driver from the requests it
submitted (``benchmark/resident.py``: rows whose submitted prompts are
equal share that prompt's whole pages while they run together).

It is what the traffic and the order of admission OFFER a kernel that
reads a page once a step however many rows' tables name it; today's
kernel reads it once a row. The decode-attention rooflines count the
distinct bytes, so this share is also how far each of them stands under
its per-slot reading. A count the driver makes itself, no clock in it."""

UNIT = "%"
LAYER = "gen engine scheduler"
MOVES = "rollout_tokens_per_s"
SOURCE = "host_clock"


def read(bench):
    per_slot = sum(bench.facts.get("chunk_resident_tokens", []))
    distinct = bench.facts.get("chunk_distinct_tokens")
    if per_slot <= 0 or not distinct:
        return None
    return 100.0 * (1.0 - sum(distinct) / per_slot)
