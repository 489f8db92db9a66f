"""The held routed experts' matmuls' share of their roofline in decode,
which is HBM bandwidth: the least time to read the three matrices of the
HELD experts that the traced chunks' routing touched (the engine's
``moe_held_experts_hit`` census on its ``gen_engine/chunk`` spans: held
experts with a row, summed over the layers and the steps; never an expert
of another rank) at the stored width (3 x ``hidden_size`` x
``moe_intermediate_size`` an expert; ``benchmark/kda_flops.py``), over the
device time of the ops that stream the held routed stacks inside the
decode-chunk program (``jit_chunk``), both in the traced part of the
window. Admission prefill (``jit_extend``) is on neither side.

It cannot pass 100 %: an op that computes an expert's output reads that
expert's three matrices once at least, the census counts an expert of a
layer-step once at most, and a chunk is counted only if it started inside
the traced part. A program whose chunks carry no such census reads
nothing."""

import jax.numpy as jnp

from benchmark import kda_flops, program_spans

UNIT = "%"
LAYER = "expert MLP"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"
PROGRAM = "jit_chunk"


def read(bench):
    if bench.trace is None or bench.peaks is None:
        return None
    hit = sum(
        c.get("attrs", {}).get("moe_held_experts_hit", 0)
        for c in program_spans.window_spans(
            bench, "gen_engine/chunk", traced_only=True))
    seconds = kda_flops.expert_op_seconds(bench, program=PROGRAM)
    if hit <= 0 or not seconds:
        return None
    itemsize = jnp.dtype(bench.arch["serving_dtype"]).itemsize
    least = hit * kda_flops.held_expert_bytes(bench.arch, itemsize) / (
        bench.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
