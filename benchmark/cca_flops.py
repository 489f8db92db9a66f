"""Bytes of a model whose attention runs inside a convolved latent (family
``zaya``: CCA) behind a top-1 expert layer, from shapes alone, and which
ops of a device trace are the decode step's paged kernel, its CCA
preparation and its expert matmuls. For the driver of such cells
(``drivers/rollout_cca_inproc.py``) and for the ``kernel.cca_decode_
roofline``, ``cca.*`` and ``moe.top1_*`` readers under ``layer_metrics/``.

``arch`` is a configuration file's dict with the keys of the family's
public ``config.json``. Every layer holds keys and values of
``num_key_value_heads x head_dim`` (after the convolutions, the mean, the
norm and the rotary embedding: what the page pool stores), so the cache is
a plain GQA cache's; beside it a SLOT keeps the carry of the two
convolutions and the value shift, ``(cca_time0 + cca_time1 - 2) x (Hq +
Hkv) x D + Hkv / 2 x D`` values a layer whatever its context.

Finding the ops, as ``benchmark/moe_flops.py`` and ``ssm_flops.py`` do
(this chip's xplane keeps no ``named_scope``): the paged kernel BY NAME
(``jit_chunk/%paged_decode``); the expert matmuls by their largest operand,
the stacked experts ``[L, X, hidden, moe_intermediate_size]`` (XLA's
fusions, or the ``moe_grouped`` kernel, whose operands they are); the CCA
preparation by the kernel's name if one is ever written (``%cca_step``),
else by the ops whose largest operand is the engine's per-slot carry ``[L,
slots, W]`` or the stacked convolution weights. Loop and call ops carry
the same arrays in their tuples and cover their bodies, so they are left
out. The expert readers of ``moe_flops.py`` read ``intermediate_size``,
which this family's config does not have.
"""

import re
from typing import Optional

_COVERING = ("while", "call", "conditional", "async-start", "async-done")
_SHORT = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}
PAGED_KERNEL = r"^jit_chunk/%paged_decode"
PREP_KERNEL = r"^jit_chunk/%cca_step"


def latent_heads(arch: dict) -> int:
    return arch["num_attention_heads"] + arch["num_key_value_heads"]


def kv_bytes_per_token(arch: dict, itemsize: int = 2) -> int:
    """What one resident token takes of the page pool: a key and a value
    of every kv head in every layer."""
    return (arch["num_hidden_layers"] * 2 * arch["num_key_value_heads"]
            * arch["head_dim"] * itemsize)


def carry_width(arch: dict) -> int:
    """Values a slot carries in ONE layer."""
    D = arch["head_dim"]
    return ((arch["cca_time0"] + arch["cca_time1"] - 2) * latent_heads(arch) * D
            + arch["num_key_value_heads"] // 2 * D)


def carry_bytes_per_slot(arch: dict, itemsize: int = 2) -> int:
    return arch["num_hidden_layers"] * carry_width(arch) * itemsize


def expert_weight_bytes(arch: dict, itemsize: int = 2) -> int:
    """Gate, up and down of ONE expert of one layer."""
    return 3 * arch["hidden_size"] * arch["moe_intermediate_size"] * itemsize


def _largest_operand(program: str, shapes) -> "re.Pattern":
    return re.compile(
        rf"^{re.escape(program)}/\S+ (?!(?:{'|'.join(_COVERING)})\b)\S+ .*"
        rf"<- (?:{'|'.join(shapes)})$")


def expert_op_pattern(arch: dict, program: str) -> "re.Pattern":
    L, X = arch["num_hidden_layers"], arch["num_experts"]
    E, F = arch["hidden_size"], arch["moe_intermediate_size"]
    dt = _SHORT[arch["serving_dtype"]]
    return _largest_operand(program, [
        rf"{dt}\[{L},{X},{E},{F}\]", rf"{dt}\[{L},{X},{F},{E}\]"])


def prep_op_pattern(arch: dict, slots: int, program: str) -> "re.Pattern":
    L, D, H = arch["num_hidden_layers"], arch["head_dim"], latent_heads(arch)
    dt = _SHORT[arch["serving_dtype"]]
    return _largest_operand(program, [
        rf"{dt}\[{L},{slots},{carry_width(arch)}\]",
        rf"{dt}\[{L},{arch['cca_time1']},{H},{D},{D}\]",
        rf"{dt}\[{L},{arch['cca_time0']},{H * D}\]",
    ])


def _seconds(bench, kernel: Optional[str], rx) -> Optional[float]:
    if bench.trace is None or "cca_time0" not in bench.arch:
        return None
    from benchmark import trace_reduce

    if kernel is not None:
        seconds, count = trace_reduce.op_seconds(bench.trace, kernel)
        if count > 0:
            return seconds
    if rx is None:
        return None
    hits = [v[0] for k, v in bench.trace["op_total_s"].items() if rx.search(k)]
    return sum(hits) if hits else None


def paged_decode_seconds(bench) -> Optional[float]:
    """Device seconds of the decode chunk's paged kernel, by its name."""
    return _seconds(bench, PAGED_KERNEL, None)


def expert_op_seconds(bench, program: str = "jit_chunk") -> Optional[float]:
    """Device seconds of the ops that stream the stacked experts."""
    if "moe_intermediate_size" not in bench.arch:
        return None
    return _seconds(bench, None, expert_op_pattern(bench.arch, program))


def prep_seconds(bench, program: str = "jit_chunk") -> Optional[float]:
    """Device seconds of the decode step's CCA preparation."""
    if "cca_time0" not in bench.arch:
        return None
    return _seconds(bench, PREP_KERNEL, prep_op_pattern(
        bench.arch, bench.mix["clients"], program))
