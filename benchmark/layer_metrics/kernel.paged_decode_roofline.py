"""The paged-decode kernel's share of its roofline, which is HBM
bandwidth: the least time to read the resident keys and values of the
running slots, EACH DISTINCT PAGE ONCE (from the requests' lengths and
the prompts they share, at the stored width; the benchmark's own
arithmetic in ``benchmark/resident.py`` and ``benchmark/flops.py``), over
the summed device time of the kernel's events in the traced part of the
window.

The numerator is the traffic's, not the program's: rows of one GRPO group
that run together hold the same whole prompt pages, and the least any
kernel must read of them a step is one copy. Today's kernel reads a page
for every row whose table names it, so the reading stands under the
per-slot one by ``gen.kv_shared_share``.

The kernel is found BY NAME, ``%paged_decode`` inside the decode-chunk
program (``jit_chunk``), as the other decode rooflines find theirs; the
chunk's other Mosaic calls (``%kv_page_write``, ``%fused_sample``) are no
part of it. An attention kernel added later must carry a name this
pattern matches (``paged_decode*``), or its time is not counted and the
reading is impossible."""

from benchmark import trace_reduce

UNIT = "%"
LAYER = "decode kernels"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"
KERNEL = r"^jit_chunk/%paged_decode"


def read(bench):
    if bench.trace is None or bench.peaks is None:
        return None
    seconds, _ = trace_reduce.op_seconds(bench.trace, KERNEL)
    k = len(bench.span_records("engine.step", traced_only=True))
    distinct = bench.facts.get("chunk_distinct_tokens", [])
    if seconds <= 0 or k <= 0 or len(distinct) < k:
        return None
    tokens_read = sum(distinct[-k:]) * bench.facts["decode_steps"]
    least = tokens_read * bench.facts["kv_bytes_per_token"] / (
        bench.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
