"""Resident context of a decode chunk, counted from the TRAFFIC: once a
slot (what a kernel that reads a page for every row whose table names it
moves) and once a distinct page (the least ANY kernel must move). For the
rollout drivers' ``one_step`` and the decode-attention roofline readers.

A roofline's numerator is the least work any implementation of the cell's
traffic must do. Rows of one GRPO group submit the same prompt token for
token, so its whole pages hold the same keys and values for every row of
the group that is running, and a step has to read them once. Which rows
those are is the traffic's and the order of admission's; nothing here
reads the program (not ``PagePool``'s reference counts, not a span), so
the same run reads the same roofline whatever kernel serves it. The
per-slot count lives on in ``gen.kv_shared_share``.

An attention kernel added later must carry a name the readers' patterns
match (``paged_decode*``, ``mla_decode*``): they find their kernels by
name, and a call they cannot see is time left out of a share that then
reads over 100 %.
"""

from typing import Dict, Optional, Sequence, Tuple


class ChunkResident:
    """``count(running)`` once a chunk, with the records of the running
    requests (``{"req": traffic_gen.Request, "chunks": chunks so far}``):
    it returns ``(per_slot, distinct)`` tokens and advances ``chunks``.

    ``per_slot``: each row's prompt (less the token the first step feeds)
    plus what it has generated, midway through the chunk. ``distinct``:
    ``per_slot`` less, for every set of ``m >= 2`` running rows whose
    submitted prompts are equal, ``(m - 1) x n x page_size`` with ``n =
    (len(prompt) - 1) // page_size`` whole pages. Rows are keyed by the
    prompt they SUBMITTED: the opening population's rows (prompt + progress
    of their own generator) share with nobody, as is true of them."""

    def __init__(self, page_size: int, decode_steps: int):
        self.page_size, self.decode_steps = page_size, decode_steps
        self._prompt_ids: Dict[Tuple[int, ...], int] = {}

    def _prompt_id(self, rec: Dict) -> int:
        # hashed once a request, not once a chunk: the window pays this
        if "prompt_id" not in rec:
            rec["prompt_id"] = self._prompt_ids.setdefault(
                tuple(rec["req"].prompt), len(self._prompt_ids))
        return rec["prompt_id"]

    def count(self, running: Sequence[Dict]) -> Tuple[int, int]:
        steps = self.decode_steps
        per_slot = shared = 0
        seen = set()
        for rec in running:
            r = rec["req"]
            per_slot += len(r.prompt) - 1 + min(
                r.max_new_tokens, rec["chunks"] * steps + steps // 2)
            rec["chunks"] += 1
            pid = self._prompt_id(rec)
            if pid in seen:     # the second and later rows of a prompt
                shared += (len(r.prompt) - 1) // self.page_size
            else:
                seen.add(pid)
        return per_slot, per_slot - shared * self.page_size


def traced_ratio(bench) -> Optional[float]:
    """``distinct / per_slot`` over the chunks of the traced part of the
    window (one entry of each list per ``engine.step`` span), for a reader
    to multiply its FULL-attention bytes with. ``None`` (the reader then
    reads nothing, never the per-slot share) where the run was not traced
    or the driver recorded no distinct count."""
    k = len(bench.span_records("engine.step", traced_only=True))
    per_slot = bench.facts.get("chunk_resident_tokens", [])
    distinct = bench.facts.get("chunk_distinct_tokens", [])
    if k <= 0 or min(len(per_slot), len(distinct)) < k or sum(per_slot[-k:]) <= 0:
        return None
    return sum(distinct[-k:]) / sum(per_slot[-k:])
