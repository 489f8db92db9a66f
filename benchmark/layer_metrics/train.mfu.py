"""Model FLOP/s utilisation of the PPO iteration: the operations the
train step (forward + backward) and the inference forward require for the
real sequence lengths, recompute not counted (``benchmark/flops.py``),
over the window and the chip's published bf16 peak."""

UNIT = "%"
LAYER = "trainer"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def read(bench):
    fl = bench.counters.get("model_flops")
    if not fl or bench.peaks is None:
        return None
    return 100.0 * fl / bench.window_s / bench.peaks["bf16_flops_per_s"]
