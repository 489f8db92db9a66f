"""Share of the traced window in which the device ran nothing while the
trainer packed a batch and put it on the device: device idle under the
program's ``train_pipe/pack`` and ``train_pipe/put`` spans
(``TrainEngine.prepare_train_batch``; on the packer thread when the
prefetcher runs), over the traced window (``benchmark/program_spans.py``)."""

from benchmark import program_spans

UNIT = "%"
LAYER = "trainer"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
SPANS = ("train_pipe/pack", "train_pipe/put")


def read(bench):
    return program_spans.idle_share_under(bench, SPANS)
