"""Share of the device's busy time spent in the inference forward (the
proximal log-prob recompute, program ``jit_fwd``), from the device trace.

Not from host spans: ``actor.train_step`` returns at dispatch, so the next
``actor.inference`` span waits out the previous optimizer step, and a
host-clock share reads 77 % where the device spends 19 % (chip run, PR 23).
"""

UNIT = "%"
LAYER = "PPO interface"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
PROGRAM = "jit_fwd"


def read(bench):
    if bench.trace is None or bench.trace["busy_s"] <= 0:
        return None
    seconds = bench.trace["modules"].get(PROGRAM)
    if not seconds:
        return None
    return 100.0 * seconds[0] / bench.trace["busy_s"]
