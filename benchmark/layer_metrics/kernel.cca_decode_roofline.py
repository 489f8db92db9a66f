"""The paged decode kernel's share of its roofline, which is HBM bandwidth,
in a cell whose cache is the convolved latent's keys and values: the least
time to read once every resident position's key and value of every layer
at every step (``decode_steps`` x ``resident_tokens`` of the traced
``gen_engine/chunk`` spans x 16,384 B at the published sizes;
``benchmark/cca_flops.py``) over the device time of
``jit_chunk/%paged_decode`` BY NAME, both in the traced part of the window.

``kernel.paged_decode_roofline`` divides by every Mosaic call of the
program; this cell's decode chunk runs three kernels (``paged_decode``,
``kv_page_write``, ``moe_grouped``), so the name is asked for.
``resident_tokens`` is the chunk's FIRST step's: the later steps read up to
15 positions a slot more, so the reading is low by under 1 %. It cannot
pass 100 %: a step's kernel reads each distinct resident key and value
once at least, and a chunk is counted only if it started inside the
traced part.

The numerator counts a DISTINCT page once: the per-slot count above times
``distinct / per_slot`` of the traced chunks, which is the traffic's and
not the program's (``benchmark/resident.py``: rows of a GRPO group that
run together hold the same whole prompt pages; today's kernel reads them
once a row, so the reading stands under the per-slot one by about
``gen.kv_shared_share``). A kernel added later must carry a name the
pattern matches (``paged_decode*``), or its time is not counted.
"""

import jax.numpy as jnp

from benchmark import cca_flops, program_spans, resident

UNIT = "%"
LAYER = "decode kernels"
MOVES = "rollout_tokens_per_s"
SOURCE = "device_trace"


def read(bench):
    if bench.trace is None or bench.peaks is None:
        return None
    seconds = cca_flops.paged_decode_seconds(bench)
    chunks = program_spans.window_spans(
        bench, "gen_engine/chunk", traced_only=True)
    positions = sum(
        c.get("attrs", {}).get("resident_tokens", 0)
        * c.get("attrs", {}).get("steps", bench.facts.get("decode_steps", 0))
        for c in chunks)
    ratio = resident.traced_ratio(bench)
    if positions <= 0 or not seconds or ratio is None:
        return None
    itemsize = jnp.dtype(bench.arch["serving_dtype"]).itemsize
    least = positions * ratio * cca_flops.kv_bytes_per_token(bench.arch, itemsize) / (
        bench.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
