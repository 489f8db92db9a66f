"""How many times the resident KV the paged-decode kernel computes over:
the sum of ``kernel_positions`` over the sum of ``resident_tokens`` of the
window's ``gen_engine/chunk`` spans that carry both. The kernel's body
(QK dot, softmax, PV dot, and the zero stores for pages a row does not
hold) runs for a whole block of rows as far as its longest row reaches,
in page blocks of ``kp * page`` positions; the engine counts that with
the kernel's own block plan over the lengths it holds on the host, at
the chunk's first step. 1.0 would be no padding at all; rows of one
length still round up to the page block. From the program's span ring
(``tracing.spans_since``); a program whose chunks carry no
``kernel_positions`` reads nothing."""

from benchmark import program_spans

UNIT = "x"
LAYER = "decode kernels"
MOVES = "rollout_tokens_per_s"
SOURCE = "program_span"


def read(bench):
    positions = resident = 0
    for c in program_spans.window_spans(bench, "gen_engine/chunk"):
        attrs = c.get("attrs", {})
        if "kernel_positions" in attrs:
            positions += attrs["kernel_positions"]
            resident += attrs["resident_tokens"]
    if resident <= 0:
        return None
    return positions / resident
