"""Bytes of a sparse-expert MLP from shapes alone, and which ops of a
device trace are its matmuls. For the ``moe.*`` readers under
``layer_metrics/``.

``arch`` is a configuration file's dict with the keys of the model's
public ``config.json``: ``intermediate_size`` is the width of ONE expert
(OLMoE), ``num_experts`` how many a layer has.

Finding the expert ops. The program runs them under
``jax.named_scope("moe_experts")``, but on this chip a trace event is
named by its HLO instruction WITHOUT the metadata (no ``op_name``; the
event's stats are offsets and durations only: my chip run, PR 26), and an
XLA fusion is ``%fusion.N`` whatever scope it came from. What does
identify them is what they stream: the layer stack's expert weights,
``[L, X, E, F]`` (gate, up) and ``[L, X, F, E]`` (down) in the stored
dtype, are the largest operand of every op that multiplies by them, and
``trace_reduce.op_label`` keeps exactly that (``... <- bf16[8,64,2048,
1024]``). Loop and call ops carry the same tensors in their tuples and
cover their bodies, so they are left out. A Pallas kernel in their place
would be found the same way. A program without experts has no such op,
and ``expert_op_seconds`` returns ``None``.
"""

import re
from typing import Optional

_SHORT = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}
_COVERING = ("while", "call", "conditional", "async-start", "async-done")


def expert_weight_bytes(arch: dict, itemsize: int = 2) -> int:
    """One expert's gate, up and down matrices at the stored width."""
    return 3 * arch["hidden_size"] * arch["intermediate_size"] * itemsize


def expert_op_pattern(arch: dict, program: Optional[str] = None) -> "re.Pattern":
    """Labels (``trace_reduce.op_label`` behind ``<program>/``) of the ops
    whose largest operand is the stacked expert weights."""
    L, X = arch["num_hidden_layers"], arch["num_experts"]
    E, F = arch["hidden_size"], arch["intermediate_size"]
    dt = _SHORT[arch["serving_dtype"]]
    prog = re.escape(program) if program else r"[^/]+"
    return re.compile(
        rf"^{prog}/\S+ (?!(?:{'|'.join(_COVERING)})\b)\S+ .*"
        rf"<- {dt}\[{L},{X},(?:{E},{F}|{F},{E})\]$")


def expert_op_seconds(bench, program: Optional[str] = None) -> Optional[float]:
    """Summed device seconds, inside the traced window, of the ops that
    stream the expert weights; only those of ``program`` if given."""
    if bench.trace is None or "num_experts" not in bench.arch:
        return None
    rx = expert_op_pattern(bench.arch, program)
    hits = [v[0] for k, v in bench.trace["op_total_s"].items() if rx.search(k)]
    return sum(hits) if hits else None
