"""90th percentile, over the requests submitted and completed inside the
window (the population of ``rollout_norm_latency_p90_ms``), of
``t_first - t_submit``: from ``submit`` to the resolve of the first
chunk that ran the request, the moment a caller could first see a token.
The engine stamps every request (``GenOutput.t_submit`` / ``t_admit`` /
``t_first`` / ``t_done``, host ``perf_counter``, no device work) and its
``gen_engine/harvest`` span carries the finished requests' four stamps as
``stamps``; read from the program's span ring. Requests of the opening
population are left out: their first chunk waited for the set-up's
prefill of every slot at once, which is not a cost of the window."""

from benchmark import program_spans
from benchmark.stats import percentile

UNIT = "ms"
LAYER = "gen engine scheduler"
MOVES = "rollout_norm_latency_p90_ms"
SOURCE = "program_counter"
STAMP = 2       # [t_submit, t_admit, t_first, t_done]


def read(bench):
    xs = [
        1e3 * (st[STAMP] - st[0])
        for st in program_spans.window_attr_values(
            bench, "gen_engine/harvest", "stamps")
        if st[0] >= bench.t_open
    ]
    if len(xs) < 20:
        return None
    return percentile(xs, 90)
