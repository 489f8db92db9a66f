"""The decode step's state update's share of its roofline, which is HBM
bandwidth: the least time to read and to write ONCE each the recurrent
state of every running slot in every state-space layer
(``state_slots`` of the traced ``gen_engine/chunk`` spans, running slots x
steps as dispatched, x 36 layers x 2,097,152 B x 2 at the published sizes;
``benchmark/ssm_flops.py``), over the device time of the update inside
the decode-chunk program (``jit_chunk``): the ``ssm_decode`` kernel by
name, or XLA's fusions over the state array, both in the traced part of
the window.

The same work whatever implements it. ``state_slots`` counts a chunk AS
DISPATCHED: a slot that ends inside a chunk is counted to the chunk's end,
and an implementation that skips its rows from there on (``ssm_decode``
does) moves no bytes for them, so the reading is HIGH by the share of
dispatched slot-steps that did not run: ``info.state_slots`` over
``info.tokens_in_window`` less one, since every slot-step that ran
generated one token: 0.95-0.99 % of the count in this PR's cell (my chip
runs, PR 41: 94,720 dispatched for 93,790-93,832 tokens), 0.8 points of a
reading of 79 %. With that said it stays under 100 % for any
implementation that reads and writes the state of a running slot at least
once a step: a chunk is counted only if it started inside the traced part
(its device work then lies inside it too). A program whose chunks carry
no ``state_slots`` reads nothing."""

from benchmark import program_spans, ssm_flops

UNIT = "%"
LAYER = "decode kernels"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.peaks is None:
        return None
    seconds = ssm_flops.state_update_seconds(bench)
    slot_steps = sum(
        c.get("attrs", {}).get("state_slots", 0)
        for c in program_spans.window_spans(
            bench, "gen_engine/chunk", traced_only=True))
    if slot_steps <= 0 or not seconds:
        return None
    least = (
        slot_steps * ssm_flops.layers_of(bench.arch, "mamba")
        * ssm_flops.state_bytes_per_slot_layer(bench.arch) * 2
        / bench.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
