"""The paged-decode kernel's share of its roofline, which is HBM
bandwidth: the least time to read the resident keys and values of the
running slots (from the requests' lengths, at the stored width; the
benchmark's own arithmetic in ``benchmark/flops.py``), over the summed
device time of the kernel's events in the traced part of the window.

The trace does not carry the kernel function's name (PERF.md, Findings,
PR 23): the events are found as the Mosaic custom calls
(``tpu_custom_call``) inside the decode-chunk program (``jit_chunk``),
which has no other Mosaic kernel while the fused sampler is off."""

from benchmark import trace_reduce

UNIT = "%"
LAYER = "decode kernels"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"
KERNEL = r"^jit_chunk/.*tpu_custom_call"


def read(bench):
    if bench.trace is None or bench.peaks is None:
        return None
    seconds, count = trace_reduce.op_seconds(bench.trace, KERNEL)
    k = len(bench.span_records("engine.step", traced_only=True))
    resident = bench.facts.get("chunk_resident_tokens", [])
    if seconds <= 0 or k <= 0 or len(resident) < k:
        return None
    tokens_read = sum(resident[-k:]) * bench.facts["decode_steps"]
    least = tokens_read * bench.facts["kv_bytes_per_token"] / (
        bench.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
