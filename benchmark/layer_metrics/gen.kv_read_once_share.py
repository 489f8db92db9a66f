"""What the PROGRAM read once: ``100 x (1 - sum kv_pages_read / sum
kv_pages_named)`` over the window's ``gen_engine/chunk`` spans
(``gen/engine.py:_kernel_counts``: pages the running rows' tables hold
under their lengths, and page copies the decode kernel's programs start,
at each chunk's first step). It stands beside the driver's
``gen.kv_shared_share``, which says what the traffic OFFERED; the program
counts a row's last partial page whole, so it reads a little lower. A
count, no clock in it; ``None`` where the chunks carry no such attributes
(the XLA gather path, a program from before them)."""

from benchmark import program_spans

UNIT = "%"
LAYER = "gen engine scheduler"
MOVES = "rollout_tokens_per_s"
SOURCE = "program_span"


def read(bench):
    attrs = [c.get("attrs", {})
             for c in program_spans.window_spans(bench, "gen_engine/chunk")]
    named = sum(a.get("kv_pages_named", 0) for a in attrs)
    if named <= 0:
        return None
    return 100.0 * (1.0 - sum(a.get("kv_pages_read", 0) for a in attrs) / named)
