"""The paged-decode kernel's share of its roofline, which is HBM bandwidth,
in an ``afmoe`` model (window and full layers in a period that runs across
the dense and the expert stack): ``kernel.hybrid_decode_roofline``'s
arithmetic on the family's own keys (``benchmark/afmoe_flops.py``). The
least time to read what the traced decode chunks' steps must read (the full
layers every resident token of the running slots, counted once a DISTINCT
page: ``distinct / per_slot`` of the traced chunks,
``benchmark/resident.py``; the window layers ``min(len, window)`` of each
slot: ``resident_tokens`` and ``window_resident_tokens`` on the engine's
``gen_engine/chunk`` spans, times the chunk's ``steps``; 2 x Hkv x D a
token a layer), over the summed device time of ``jit_chunk/%paged_decode*``
found BY NAME (the full layers' two programs and the window layers'
``%paged_decode_window``), both in the traced part of the window.

It cannot pass 100 %: lengths only grow inside a chunk, the bytes are a
lower bound of what the kernel read, and every call that read them is in
the time. A configuration of another family, a program whose chunks carry
no ``window_resident_tokens``, or a trace without such a kernel reads
nothing."""

import jax.numpy as jnp

from benchmark import afmoe_flops, program_spans, resident, trace_reduce

UNIT = "%"
LAYER = "decode kernels"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.peaks is None:
        return None
    if not afmoe_flops.is_afmoe(bench.arch):
        return None
    seconds, _ = trace_reduce.op_seconds(
        bench.trace, afmoe_flops.DECODE_KERNEL)
    ratio = resident.traced_ratio(bench)
    if ratio is None:
        return None
    itemsize = jnp.dtype(bench.arch["serving_dtype"]).itemsize
    least_bytes = 0
    for c in program_spans.window_spans(
            bench, "gen_engine/chunk", traced_only=True):
        attrs = c.get("attrs", {})
        if "window_resident_tokens" not in attrs:
            continue
        least_bytes += attrs.get("steps", 0) * afmoe_flops.resident_bytes(
            bench.arch, attrs["resident_tokens"],
            attrs["window_resident_tokens"], itemsize, ratio)
    if seconds <= 0 or least_bytes <= 0:
        return None
    return 100.0 * least_bytes / bench.peaks["hbm_bytes_per_s"] / seconds
