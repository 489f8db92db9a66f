"""The paged-decode kernel's share of its roofline, which is HBM
bandwidth, in a model whose stack is LOOPED: the least time to read what
the traced decode chunks' steps must read (every resident token of the
running slots in every CACHE layer, ``total_ut_steps x num_hidden_layers``
of them: ``resident_tokens`` on the engine's ``gen_engine/chunk`` spans,
exact on the host at the chunk's first step, times the chunk's ``steps``;
at the stored width, ``benchmark/loop_flops.py``), over the summed device
time of the kernel's events inside the decode-chunk program
(``jit_chunk``), found BY NAME (``%paged_decode``), both in the traced
part of the window. ``kernel.paged_decode_roofline`` cannot read such a
cell: its bytes a token count one pass.

It cannot pass 100 %: lengths only grow inside a chunk and a slot that
finishes keeps its length until it is refilled, so the bytes are a lower
bound of what the kernel read, and every call that read them is in the
time. A program whose chunks carry no ``cache_layers``, or whose trace has
no such kernel, reads nothing."""

import jax.numpy as jnp

from benchmark import loop_flops, program_spans, trace_reduce

UNIT = "%"
LAYER = "decode kernels"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.peaks is None:
        return None
    if "total_ut_steps" not in bench.arch:
        return None
    seconds, _ = trace_reduce.op_seconds(bench.trace, loop_flops.DECODE_KERNEL)
    itemsize = jnp.dtype(bench.arch["serving_dtype"]).itemsize
    per_token = loop_flops.kv_bytes_per_token(bench.arch, itemsize)
    tokens_read = 0
    for c in program_spans.window_spans(
            bench, "gen_engine/chunk", traced_only=True):
        attrs = c.get("attrs", {})
        if attrs.get("cache_layers") != loop_flops.cache_layers(bench.arch):
            continue
        tokens_read += attrs.get("steps", 0) * attrs.get("resident_tokens", 0)
    if seconds <= 0 or tokens_read <= 0:
        return None
    return 100.0 * tokens_read * per_token / (
        bench.peaks["hbm_bytes_per_s"]) / seconds
