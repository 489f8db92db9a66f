"""What one harvest moves to the host: the mean, over the window's
``gen_engine/harvest/pull`` spans, of their ``bytes`` attribute, in KB
(1,000 B). One span a chunk boundary that had a finished slot: the one
``device_get`` of ``GenerationEngine._pull_outputs``. Where the pull takes
every slot's output buffers at their whole cap this reads ``slots x cap x
8 B`` and more (4,000-40,000 KB in the cells); where it takes what the
finished rows wrote, a few hundred. From the program's span ring
(``tracing.spans_since``); ``None`` where the window's ring holds no such
span with ``bytes`` (a program from before PR 51, a window without a
harvest)."""

from benchmark import program_spans

UNIT = "KB"
LAYER = "gen engine scheduler"
MOVES = "rollout_tokens_per_s"
SOURCE = "program_span"


def read(bench):
    pulled = [
        s["attrs"]["bytes"]
        for s in program_spans.window_spans(bench, "gen_engine/harvest/pull")
        if "bytes" in (s.get("attrs") or {})
    ]
    if not pulled:
        return None
    return sum(pulled) / len(pulled) / 1e3
