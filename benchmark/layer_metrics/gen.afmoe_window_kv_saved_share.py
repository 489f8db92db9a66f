"""Share of the cache a one-kind pool would hold resident that the window
layers of an ``afmoe`` model do not: ``1 - (bytes resident in all kinds) /
(bytes a token in every layer x resident tokens)``, the mean over the
window's ``gen_engine/chunk`` spans (``gen.window_kv_saved_share``'s
arithmetic on the family's own keys: ``benchmark/afmoe_flops.py``). Bytes
resident: the full layers every resident token of the running slots, the
window layers ``min(len, sliding_window)`` of each (``resident_tokens``,
``window_resident_tokens`` on the span, exact on the host at the chunk's
first step). 0 % while no slot has passed the window. A configuration of
another family or a program whose chunks carry no
``window_resident_tokens`` reads nothing."""

from benchmark import afmoe_flops, program_spans

UNIT = "%"
LAYER = "gen engine scheduler"
MOVES = "rollout_tokens_per_s"
SOURCE = "program_counter"


def read(bench):
    if not afmoe_flops.is_afmoe(bench.arch):
        return None
    every = sum(afmoe_flops.kv_bytes_per_token_by_kind(bench.arch).values())
    shares = []
    for c in program_spans.window_spans(bench, "gen_engine/chunk"):
        attrs = c.get("attrs", {})
        if attrs.get("resident_tokens", 0) <= 0 or (
                "window_resident_tokens" not in attrs):
            continue
        held = afmoe_flops.resident_bytes(
            bench.arch, attrs["resident_tokens"],
            attrs["window_resident_tokens"])
        shares.append(1.0 - held / (every * attrs["resident_tokens"]))
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
