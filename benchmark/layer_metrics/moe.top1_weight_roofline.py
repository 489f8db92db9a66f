"""The top-1 expert matmuls' share of their roofline in decode, which is
HBM bandwidth: the least time to read the weights of the experts that the
traced chunks' routing TOUCHED (the engine's ``moe_experts_hit`` census on
its ``gen_engine/chunk`` spans: distinct experts with a row, summed over
layers and steps; the skip is no expert and is not in it) at the stored
width, 3 x 2,048 x 2,048 x 2 B an expert at the published sizes, over the
device time of the ops that stream the stacked experts
(``benchmark/cca_flops.py``) inside the decode-chunk program
(``jit_chunk``), both in the traced part of the window.

It cannot pass 100 %: an op that computes an expert's output reads that
expert's three matrices once at least, the census counts an expert of a
layer-step once at most, and a chunk is counted only if it started inside
the traced part."""

import jax.numpy as jnp

from benchmark import cca_flops, program_spans

UNIT = "%"
LAYER = "expert MLP"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.peaks is None:
        return None
    hit = sum(
        c.get("attrs", {}).get("moe_experts_hit", 0)
        for c in program_spans.window_spans(
            bench, "gen_engine/chunk", traced_only=True))
    seconds = cca_flops.expert_op_seconds(bench)
    if hit <= 0 or not seconds:
        return None
    itemsize = jnp.dtype(bench.arch["serving_dtype"]).itemsize
    least = hit * cca_flops.expert_weight_bytes(bench.arch, itemsize) / (
        bench.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
