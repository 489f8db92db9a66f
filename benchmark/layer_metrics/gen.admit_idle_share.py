"""Share of the traced window in which the device ran nothing while the
engine was admitting requests: device idle that lies under the program's
``gen_engine/admit`` spans (page allocation, prefix lookup, building and
dispatching the prefill programs), over the traced window. The program's
span is on the device's clock (``benchmark/program_spans.py``)."""

from benchmark import program_spans

UNIT = "%"
LAYER = "gen engine scheduler"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"
SPANS = ("gen_engine/admit",)


def read(bench):
    return program_spans.idle_share_under(bench, SPANS)
