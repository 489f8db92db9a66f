"""The flash-attention kernels' share of their roofline, which is compute:
the least time for the attention FLOPs the traced iterations require
(forward of the inference pass, forward + backward of the train step, sum
of len^2 within sequences, recompute not counted) at the chip's bf16 peak,
over the summed device time of the forward and backward kernels' events
(recomputed forwards included in the time, not in the need).

The trace does not carry the kernel functions' names: the events are found
as the Mosaic custom calls (``tpu_custom_call``) inside the train-step and
inference programs (``jit_train_step``, ``jit_fwd``), whose only Mosaic
kernels are the flash forward and backward."""

from benchmark import flops, trace_reduce

UNIT = "%"
LAYER = "train kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"
KERNEL = r"^jit_(train_step|fwd)/.*tpu_custom_call"


def read(bench):
    if bench.trace is None or bench.peaks is None:
        return None
    seconds, count = trace_reduce.op_seconds(bench.trace, KERNEL)
    k = len(bench.span_records("iteration", traced_only=True))
    per_iter = bench.facts.get("iteration_seqlens", [])
    if seconds <= 0 or k <= 0 or len(per_iter) < k:
        return None
    seqlens = [l for it in per_iter[-k:] for l in it]
    need = flops.flash_train_flops(bench.arch, seqlens) + (
        flops.attention_forward_flops(bench.arch, seqlens))
    return 100.0 * need / bench.peaks["bf16_flops_per_s"] / seconds
