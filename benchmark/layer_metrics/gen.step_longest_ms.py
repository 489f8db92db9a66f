"""The longest ``gen_engine/chunk`` span that started in the window: one
``engine.step`` as the program timed it. A run that stalled (one
``flag_wait`` of seconds, a compile inside the window) shows it here, in
its own result line, whatever the driver prints. ``None`` where the
window's ring holds no such span."""

from benchmark import program_spans

UNIT = "ms"
LAYER = "gen engine scheduler"
MOVES = "rollout_tokens_per_s"
SOURCE = "program_span"


def read(bench):
    chunks = program_spans.window_spans(bench, "gen_engine/chunk")
    if not chunks:
        return None
    return 1e3 * max(c["dur_s"] for c in chunks)
