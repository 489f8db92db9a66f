"""The paged-decode kernel's share of its roofline, which is HBM
bandwidth, in a model whose layers come in KINDS: the least time to read
what the traced decode chunks' steps must read (the full layers every
resident token of the running slots, the window layers ``min(len,
window)`` of each: ``resident_tokens`` and ``window_resident_tokens`` on
the engine's ``gen_engine/chunk`` spans, exact on the host at the chunk's
first step, times the chunk's ``steps``; at the stored width, 2 x Hkv x D
a token a layer: ``benchmark/hybrid_flops.py``), over the summed device
time of the kernel's events inside the decode-chunk program
(``jit_chunk``), found BY NAME (``%paged_decode``: the full layers'
program and the window layers' ``%paged_decode_window``), both in the
traced part of the window.

It cannot pass 100 %: lengths only grow inside a chunk and a slot that
finishes keeps its length until it is refilled, so the bytes are a lower
bound of what the kernel read (the part of a window's edge page before
the window, which it also copies, is not counted), and every call that
read them is in the time. A program whose chunks carry no
``window_resident_tokens``, or whose trace has no such kernel, reads
nothing.

The FULL layers' part of the numerator counts a distinct page once (the
window layers' stays per slot: ``hybrid_flops.resident_bytes``): times
``distinct / per_slot`` of the traced chunks, which is the traffic's and
not the program's (``benchmark/resident.py``: rows of a GRPO group that
run together hold the same whole prompt pages; today's kernel reads them
once a row, so the reading stands under the per-slot one by about
``gen.kv_shared_share``). A kernel added later must carry a name the
pattern matches (``paged_decode*``), or its time is not counted.
"""

import jax.numpy as jnp

from benchmark import hybrid_flops, program_spans, resident, trace_reduce

UNIT = "%"
LAYER = "decode kernels"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.peaks is None:
        return None
    if "sliding_window_layout" not in bench.arch:
        return None
    seconds, _ = trace_reduce.op_seconds(
        bench.trace, hybrid_flops.DECODE_KERNEL)
    itemsize = jnp.dtype(bench.arch["serving_dtype"]).itemsize
    ratio = resident.traced_ratio(bench)
    if ratio is None:
        return None
    least_bytes = 0
    for c in program_spans.window_spans(
            bench, "gen_engine/chunk", traced_only=True):
        attrs = c.get("attrs", {})
        if "window_resident_tokens" not in attrs:
            continue
        least_bytes += attrs.get("steps", 0) * hybrid_flops.resident_bytes(
            bench.arch, attrs["resident_tokens"],
            attrs["window_resident_tokens"], itemsize, ratio)
    if seconds <= 0 or least_bytes <= 0:
        return None
    return 100.0 * least_bytes / bench.peaks["hbm_bytes_per_s"] / seconds
