"""Host time the device waits out at a chunk boundary: the mean, over the
window's chunks, of the time from the END of chunk k's
``gen_engine/flag_wait`` (the host knows the chunk is done) to the END of
chunk k+1's ``gen_engine/dispatch/enqueue`` (the next chunk is on the
device's queue): harvest, the caller's loop, admission, seating, the
enqueue. Unlike ``gen.chunk_host_work_ms`` it leaves out what the host
does BEHIND the enqueue, in the chunk's shadow (the census), which costs
no token. From the program's span ring (``tracing.spans_since``:
``t0 + dur_s`` on ``time.perf_counter``); ``None`` under 20 boundaries or
where the program has no such spans."""

from benchmark import program_spans

UNIT = "ms"
LAYER = "gen engine scheduler"
MOVES = "rollout_tokens_per_s"
SOURCE = "program_span"


def read(bench):
    ends = sorted(
        (s["t0"] + s["dur_s"], kind)
        for kind, name in enumerate(
            ("gen_engine/flag_wait", "gen_engine/dispatch/enqueue"))
        for s in program_spans.window_spans(bench, name))
    gaps, waited = [], None
    for t, is_enqueue in ends:
        if not is_enqueue:
            waited = t
        elif waited is not None:
            gaps.append(t - waited)
            waited = None
    if len(gaps) < 20:
        return None
    return 1e3 * sum(gaps) / len(gaps)
