"""The routed experts' share of their roofline in decode, which is HBM
bandwidth, in an ``afmoe`` model: the least time to read the weights of the
experts that the traced chunks' routing TOUCHED (the engine's
``moe_experts_hit`` census on its ``gen_engine/chunk`` spans: distinct
experts with a token, summed over the expert layers and the steps; not 128
a layer by assumption) at the stored width (3 x hidden x
``moe_intermediate_size`` an expert; ``benchmark/afmoe_flops.py``), over
the device time of the ops that stream the expert stack inside the
decode-chunk program (``jit_chunk``), both in the traced part of the
window. Admission prefill (``jit_extend``) is on neither side.

It cannot pass 100 %: an op that computes an expert's output reads that
expert's three matrices once at least, the census counts an expert of a
layer-step once at most, and a chunk is counted only if it started inside
the traced part. While the program computes every expert for every token
(the dense dispatch) it reads all 128 whatever the census says, so this
reads at most the hit share."""

import jax.numpy as jnp

from benchmark import afmoe_flops, program_spans

UNIT = "%"
LAYER = "expert MLP"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"
PROGRAM = "jit_chunk"


def read(bench):
    if bench.trace is None or bench.peaks is None:
        return None
    hit = sum(
        c.get("attrs", {}).get("moe_experts_hit", 0)
        for c in program_spans.window_spans(
            bench, "gen_engine/chunk", traced_only=True))
    seconds = afmoe_flops.expert_op_seconds(bench, program=PROGRAM)
    if hit <= 0 or not seconds:
        return None
    itemsize = jnp.dtype(bench.arch["serving_dtype"]).itemsize
    least = hit * afmoe_flops.expert_bytes(bench.arch, itemsize) / (
        bench.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
