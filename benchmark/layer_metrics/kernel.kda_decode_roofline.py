"""The decode step's delta-rule update's share of its roofline, which is
HBM bandwidth: the least time to read and to write ONCE each the state of
every running slot in every linear layer, beside the row's small operands
(``state_slots`` of the traced ``gen_engine/chunk`` spans, running slots x
steps as dispatched, x 3 layers x (2 x 4,194,304 + 164,096) B at the
published sizes; ``benchmark/kda_flops.py``), over the device time of the
update inside the decode-chunk program (``jit_chunk``): the ``kda_decode``
kernel by name, or XLA's ops over the state array, both in the traced part
of the window.

The same work whatever implements it. ``state_slots`` counts a chunk AS
DISPATCHED (a slot that ends inside a chunk is counted to the chunk's
end, and ``kda_decode`` reads and writes such a row's state all the same:
it is handed a decay of 1 and writes back what it read), so the count is
what the kernel moved. It stays under 100 % for any implementation that
reads and writes the state of a slot at least once a step: a chunk is
counted only if it started inside the traced part (its device work then
lies inside it too). A program whose chunks carry no ``state_slots``, or
without linear layers, reads nothing."""

from benchmark import kda_flops, program_spans

UNIT = "%"
LAYER = "decode kernels"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.peaks is None:
        return None
    seconds = kda_flops.state_update_seconds(bench)
    slot_steps = sum(
        c.get("attrs", {}).get("state_slots", 0)
        for c in program_spans.window_spans(
            bench, "gen_engine/chunk", traced_only=True))
    if slot_steps <= 0 or not seconds:
        return None
    least = (
        slot_steps * kda_flops.layers_of(bench.arch, "kda")
        * kda_flops.decode_bytes_per_slot_layer(bench.arch)
        / bench.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
