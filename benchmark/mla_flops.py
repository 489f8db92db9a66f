"""Bytes and operations of latent attention and of a routed-expert MLP
beside a shared one, from shapes alone, and which ops of a device trace
stream the routed experts' weights. For the ``kernel.mla_decode_roofline``,
``mla.*`` and ``moe.routed_*`` readers under ``layer_metrics/`` and for the
driver of latent-cache cells (``drivers/rollout_latent_inproc.py``).

``arch`` is a configuration file's dict with the keys of a ``deepseek_v3``
style ``config.json``: ``kv_lora_rank`` + ``qk_rope_head_dim`` values are
what the mathematics keeps of a token in a layer (the latent is key and
value at once for every head), ``moe_intermediate_size`` is the width of
ONE routed expert, ``n_routed_experts`` how many an expert layer has, and
the first ``first_k_dense_replace`` layers have none.

Every figure counts what the ALGORITHM needs. The program stores a latent
row padded to whole 128-lane tiles (640 for 576); the padding is the
program's cost and is on neither side of a roofline here, so a share
computed from these bytes can only read low, never over 100 %.

Finding the routed-expert ops: as ``benchmark/moe_flops.py`` does, by the
largest operand. The stacked routed weights are ``[Lx, X, E, F]`` (gate,
up) and ``[Lx, X, F, E]`` (down) in the stored dtype, with ``Lx`` the
number of EXPERT layers (the dense layers are a stack of their own); an
op that works on one layer's slice of them (``[X, E, F]``, should the
compiler cut the slice out first) streams the same bytes and is counted
too. Loop and call ops carry the same tensors in their tuples and cover
their bodies, so they are left out. The shared expert's matrices
(``[Lx, E, F]``) match neither shape.
"""

import re
from typing import Optional

_SHORT = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}
_COVERING = ("while", "call", "conditional", "async-start", "async-done")
MLA_KERNEL = r"^jit_chunk/%mla_decode\b"


def latent_bytes_per_token(arch: dict, itemsize: int = 2) -> int:
    """What the cache must hold of one token, all layers: one latent and
    one shared rotary key a layer."""
    return arch["num_hidden_layers"] * (
        arch["kv_lora_rank"] + arch["qk_rope_head_dim"]) * itemsize


def mla_decode_flops(arch: dict, resident_tokens: int) -> float:
    """Absorbed decode of one new token a slot over ``resident_tokens``
    cached ones, all layers: every head multiplies its query with the
    whole latent row (scores) and its probabilities with the latent
    (values), 2 FLOP a multiply-add."""
    per_token_layer = 2 * arch["num_attention_heads"] * (
        arch["kv_lora_rank"] + arch["qk_rope_head_dim"] + arch["kv_lora_rank"])
    return float(per_token_layer) * arch["num_hidden_layers"] * resident_tokens


def routed_expert_bytes(arch: dict, itemsize: int = 2) -> int:
    """One routed expert's gate, up and down matrices at the stored width."""
    return 3 * arch["hidden_size"] * arch["moe_intermediate_size"] * itemsize


def n_expert_layers(arch: dict) -> int:
    return arch["num_hidden_layers"] - arch.get("first_k_dense_replace", 0)


def routed_op_pattern(arch: dict, program: Optional[str] = None) -> "re.Pattern":
    """Labels (``trace_reduce.op_label`` behind ``<program>/``) of the ops
    whose largest operand is the stacked routed-expert weights, or one
    layer's slice of them."""
    Lx, X = n_expert_layers(arch), arch["n_routed_experts"]
    E, F = arch["hidden_size"], arch["moe_intermediate_size"]
    dt = _SHORT[arch["serving_dtype"]]
    prog = re.escape(program) if program else r"[^/]+"
    return re.compile(
        rf"^{prog}/\S+ (?!(?:{'|'.join(_COVERING)})\b)\S+ .*"
        rf"<- {dt}\[(?:{Lx},)?{X},(?:{E},{F}|{F},{E})\]$")


def routed_op_seconds(bench, program: Optional[str] = None) -> Optional[float]:
    """Summed device seconds, inside the traced window, of the ops that
    stream the routed experts' weights; only those of ``program`` if
    given. ``None`` for a configuration without such experts or a run
    without a trace."""
    if bench.trace is None or "n_routed_experts" not in bench.arch:
        return None
    rx = routed_op_pattern(bench.arch, program)
    hits = [v[0] for k, v in bench.trace["op_total_s"].items() if rx.search(k)]
    return sum(hits) if hits else None
