"""Share of the device's busy time that a decode step spends preparing q, k
and v inside the convolved latent beyond the projections: the ops of the
decode-chunk program (``jit_chunk``) whose largest operand is the per-slot
carry or the stacked convolution weights (``benchmark/cca_flops.py``; a
kernel named ``cca_step`` by its name, if one is ever written), over the
busy union, both in the traced part of the window. What XLA fuses into an
op with a larger operand (the projections' matmuls) is not in it."""

from benchmark import cca_flops

UNIT = "%"
LAYER = "model step"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.trace["busy_s"] <= 0:
        return None
    seconds = cca_flops.prep_seconds(bench)
    if seconds is None:
        return None
    return 100.0 * seconds / bench.trace["busy_s"]
