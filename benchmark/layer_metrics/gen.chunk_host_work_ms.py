"""Host work of one ``engine.step``: the mean, over the window's
``gen_engine/chunk`` spans that dispatched a chunk, of the span's duration
minus its ``gen_engine/flag_wait`` child (the wait for the device). What is
left is admission, the chunk's dispatch, the harvest and the bookkeeping
between them: time the host adds to every chunk. From the program's span
ring (``tracing.spans_since``), not from the benchmark's wrapper."""

from benchmark import program_spans

UNIT = "ms"
LAYER = "gen engine scheduler"
MOVES = "rollout_norm_latency_p90_ms"
SOURCE = "program_span"


def read(bench):
    waits = {}
    for w in program_spans.window_spans(bench, "gen_engine/flag_wait"):
        waits[w["parent_id"]] = waits.get(w["parent_id"], 0.0) + w["dur_s"]
    work = [
        c["dur_s"] - waits[c["span_id"]]
        for c in program_spans.window_spans(bench, "gen_engine/chunk")
        if c["span_id"] in waits
    ]
    if len(work) < 20:
        return None
    return 1e3 * sum(work) / len(work)
