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
no such kernel, reads nothing.

The numerator counts a DISTINCT page once: the per-slot count above times
``distinct / per_slot`` of the traced chunks, which is the traffic's and
not the program's (``benchmark/resident.py``: rows of a GRPO group that
run together hold the same whole prompt pages; today's kernel reads them
once a row, so the reading stands under the per-slot one by about
``gen.kv_shared_share``). A kernel added later must carry a name the
pattern matches (``paged_decode*``), or its time is not counted.
"""

import jax.numpy as jnp

from benchmark import loop_flops, program_spans, resident, trace_reduce

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
    ratio = resident.traced_ratio(bench)
    if seconds <= 0 or tokens_read <= 0 or ratio is None:
        return None
    return 100.0 * tokens_read * ratio * per_token / (
        bench.peaks["hbm_bytes_per_s"]) / seconds
