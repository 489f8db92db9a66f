"""Device busy time per decode step: the union of device-op intervals in
the traced part of the window over the decode steps executed in it
(admission prefill included: it is what a step costs the chip)."""

UNIT = "ms"
LAYER = "model step"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None:
        return None
    chunks = len(bench.span_records("engine.step", traced_only=True))
    steps = chunks * bench.facts.get("decode_steps", 0)
    if steps <= 0:
        return None
    return 1e3 * bench.trace["busy_s"] / steps
