"""The latent decode kernel's share of its roofline, which is HBM
bandwidth (60 FLOP a byte against a ridge of 240 on this chip): the least
time to read the resident latents of the traced decode chunks, EACH
LATENT ONCE though it is key and value (``kv_lora_rank`` +
``qk_rope_head_dim`` values a token a layer at the stored width: 1,152 B;
``benchmark/mla_flops.py``; the program's padding of the row to 640 is not
counted), over the summed device time of the ``%mla_decode`` kernel's
events inside the decode-chunk program (``jit_chunk``), both in the traced
part of the window.

Resident tokens are the engine's own count on its ``gen_engine/chunk``
spans (``resident_tokens``: exact on the host at the chunk's first step),
times the chunk's steps: lengths only grow inside a chunk and a slot that
finishes keeps its length until it is refilled, so this is a lower bound
of what the kernel read and the share cannot pass 100 %. A program whose
chunks carry no such count, or whose trace has no ``%mla_decode``, reads
nothing.

The numerator counts a DISTINCT page once: the per-slot count above times
``distinct / per_slot`` of the traced chunks, which is the traffic's and
not the program's (``benchmark/resident.py``: rows of a GRPO group that
run together hold the same whole prompt pages; today's kernel reads them
once a row, so the reading stands under the per-slot one by about
``gen.kv_shared_share``). A kernel added later must carry a name the
pattern matches (``mla_decode*``), or its time is not counted.
"""

import jax.numpy as jnp

from benchmark import mla_flops, program_spans, resident, trace_reduce

UNIT = "%"
LAYER = "decode kernels"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.peaks is None:
        return None
    if "kv_lora_rank" not in bench.arch:
        return None
    seconds, _ = trace_reduce.op_seconds(bench.trace, mla_flops.MLA_KERNEL)
    tokens_read = 0
    for c in program_spans.window_spans(
            bench, "gen_engine/chunk", traced_only=True):
        attrs = c.get("attrs", {})
        tokens_read += attrs.get("resident_tokens", 0) * attrs.get("steps", 0)
    ratio = resident.traced_ratio(bench)
    if seconds <= 0 or tokens_read <= 0 or ratio is None:
        return None
    itemsize = jnp.dtype(bench.arch["serving_dtype"]).itemsize
    least = tokens_read * ratio * mla_flops.latent_bytes_per_token(
        bench.arch, itemsize) / bench.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
