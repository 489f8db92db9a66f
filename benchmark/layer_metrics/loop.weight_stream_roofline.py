"""The layer stack's matmuls' share of their roofline in decode, which is
HBM bandwidth, in a model whose stack is LOOPED: the least time to read
one layer's seven matrices once a run of the layer (``layer_passes`` on
the engine's ``gen_engine/chunk`` spans: steps x passes x layers as
dispatched; 102,760,448 B a layer at the 2.6B's widths,
``benchmark/loop_flops.py``), over the device time of the ops that stream
the stack inside the decode-chunk program (``jit_chunk``), found by their
largest operand, both in the traced part of the window. Admission prefill
(``jit_extend``) and the head are on neither side.

It cannot pass 100 %: an op that multiplies by a layer's matrix reads it
once at least, and a chunk is counted only if it started inside the traced
part (its device work then lies inside it too: the window's last work is
drained before the trace stops). A program whose chunks carry no
``layer_passes`` reads nothing."""

import jax.numpy as jnp

from benchmark import loop_flops, program_spans

UNIT = "%"
LAYER = "model step"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"
PROGRAM = "jit_chunk"


def read(bench):
    if bench.trace is None or bench.peaks is None:
        return None
    seconds = loop_flops.weight_op_seconds(bench, program=PROGRAM)
    runs = sum(
        c.get("attrs", {}).get("layer_passes", 0)
        for c in program_spans.window_spans(
            bench, "gen_engine/chunk", traced_only=True))
    if runs <= 0 or not seconds:
        return None
    itemsize = jnp.dtype(bench.arch["serving_dtype"]).itemsize
    least = runs * loop_flops.layer_weight_bytes(bench.arch, itemsize) / (
        bench.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
