"""Share of the traced window in which the device ran nothing while the
PPO interface computed advantages: device idle under the program's
``ppo/prepare`` spans (``PPOActorInterface._prepare``: reward shaping, GAE
and normalisation as eager one-op programs, then one ``device_get``), over
the traced window (``benchmark/program_spans.py``)."""

from benchmark import program_spans

UNIT = "%"
LAYER = "PPO interface"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
SPANS = ("ppo/prepare",)


def read(bench):
    return program_spans.idle_share_under(bench, SPANS)
