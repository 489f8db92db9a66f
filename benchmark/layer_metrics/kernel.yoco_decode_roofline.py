"""The paged-decode kernel's share of its roofline, which is HBM
bandwidth, in a decoder-hybrid-decoder: the least time to read what the
traced decode chunks' steps must read of the cache (the full layer's
resident positions once for itself and once for every cross-attention
layer that shares them, each window layer's ``min(len, window)``:
``resident_tokens`` and ``window_resident_tokens`` on the engine's
``gen_engine/chunk`` spans, exact on the host at the chunk's first step,
times the chunk's ``steps``; at the stored width:
``benchmark/yoco_flops.py``), over the summed device time of BOTH of the
kernel's programs inside the decode-chunk program (``jit_chunk``), found
by name, both in the traced part of the window.

It cannot pass 100 %: lengths only grow inside a chunk and a slot that
finishes keeps its length until it is refilled, so the bytes are a lower
bound of what the kernel read, and every call that read them is in the
time. A configuration of another family, a program whose chunks carry no
``window_resident_tokens``, or a trace without the kernel reads
nothing.

The FULL layer's part of the numerator, each of its readers', counts a
distinct page once (the window layers' stays per slot:
``yoco_flops.resident_bytes``): times
``distinct / per_slot`` of the traced chunks, which is the traffic's and
not the program's (``benchmark/resident.py``: rows of a GRPO group that
run together hold the same whole prompt pages; today's kernel reads them
once a row, so the reading stands under the per-slot one by about
``gen.kv_shared_share``). A kernel added later must carry a name the
pattern matches (``paged_decode*``), or its time is not counted.
"""

import jax.numpy as jnp

from benchmark import program_spans, resident, trace_reduce, yoco_flops

UNIT = "%"
LAYER = "decode kernels"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.peaks is None:
        return None
    if bench.arch.get("model_type") != "phi4flash":
        return None
    seconds = sum(
        trace_reduce.op_seconds(bench.trace, rx)[0]
        for rx in (yoco_flops.FULL_KERNEL, yoco_flops.WINDOW_KERNEL))
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
        least_bytes += attrs.get("steps", 0) * yoco_flops.resident_bytes(
            bench.arch, attrs["resident_tokens"],
            attrs["window_resident_tokens"], itemsize, ratio)
    if seconds <= 0 or least_bytes <= 0:
        return None
    return 100.0 * least_bytes / bench.peaks["hbm_bytes_per_s"] / seconds
