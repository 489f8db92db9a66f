"""Share of the expert slots that routing touched: the sum of
``moe_experts_hit`` (distinct experts with a token, over layers and decode
steps) over the sum of ``moe_expert_slots`` (layers x steps x experts) of
the window's ``gen_engine/chunk`` spans. Every row of the batch routes,
free slots too. At 100 % a decode step has to read every expert's
weights, and a grouped matmul could save FLOPs but no bytes. From the
program's span ring; a program whose chunks carry no census reads
nothing."""

from benchmark import program_spans

UNIT = "%"
LAYER = "expert MLP"
MOVES = "rollout_tokens_per_s"
SOURCE = "program_counter"


def read(bench):
    hit = slots = 0
    for c in program_spans.window_spans(bench, "gen_engine/chunk"):
        attrs = c.get("attrs", {})
        if "moe_expert_slots" in attrs:
            hit += attrs["moe_experts_hit"]
            slots += attrs["moe_expert_slots"]
    if slots <= 0:
        return None
    return 100.0 * hit / slots
