"""90th percentile of the wall time of one ``engine.step(decode_steps)``
call, from the benchmark's own span around it: admission, the chunk's
dispatch, the wait for its flags, the harvest."""

from benchmark.stats import percentile

UNIT = "ms"
LAYER = "gen engine scheduler"
MOVES = "rollout_norm_latency_p90_ms"
SOURCE = "program_span"


def read(bench):
    xs = bench.spans("engine.step")
    if len(xs) < 20:
        return None
    return 1e3 * percentile(xs, 90)
