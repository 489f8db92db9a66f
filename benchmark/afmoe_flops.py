"""Bytes of the cache, bytes a decode step must read of it, and the routed
experts' matrices of the ``afmoe`` family (Trinity), from shapes alone and
from the family's OWN keys. For the driver of such cells
(``drivers/rollout_afmoe_inproc.py``) and the ``kernel.afmoe_decode_roofline``,
``moe.afmoe_expert_share``, ``moe.afmoe_weight_roofline`` and
``gen.afmoe_window_kv_saved_share`` readers under ``layer_metrics/``.

``arch`` is a configuration file's dict with the keys of the family's
public ``config.json``: ``layer_types[l]`` is ``"sliding_attention"`` where
layer ``l`` attends over ``sliding_window`` positions and
``"full_attention"`` where over all, counted over the MODEL's layers; the
first ``num_dense_layers`` are dense, the rest hold ``num_experts`` routed
experts of ``moe_intermediate_size`` (the shared expert and the dense
layers' MLPs are not among the routed ops).

Every figure counts what the ALGORITHM needs (``benchmark/hybrid_flops.py``
says the same of its family): a full layer reads every resident key and
value of a slot, a window layer those of the last ``sliding_window``
positions and no more, so a share computed from these bytes can only read
low, never over 100 %. A page of the pool holds ``page`` tokens of ONE
position of the layers' period in EVERY period
(``areal_tpu/models/transformer.py:PagedKVCache``): ``periods x 2 x Hkv x
page x D x itemsize`` bytes whatever kind it serves, and the periods run
over the model's layers, across the dense and the expert stack.

Finding the routed experts' ops: by the largest operand, as
``benchmark/moe_flops.py`` does: the expert stack ``[Lx, X, E, F]`` (gate,
up) and ``[Lx, X, F, E]`` (down) with ``Lx = num_hidden_layers -
num_dense_layers`` (what the ``moe_grouped`` kernel is handed), one layer's
slice ``[X, ...]`` of it (what the einsums read), or the kernel by NAME
(``%moe_grouped``). Loop and call ops carry the same tensors in their
tuples and cover their bodies, so they are left out.
"""

import re
from typing import Dict, Optional, Sequence

# what reads no key of a family: a token's bytes in one layer, the dtypes'
# short names, the ops that cover their bodies, the kernel's name
from benchmark.hybrid_flops import (
    _COVERING, _SHORT, DECODE_KERNEL, _token_layer_bytes)

_KINDS = {"full_attention": "full", "sliding_attention": "window"}


def _layer_types(arch: dict):
    return list(arch["layer_types"][: arch["num_hidden_layers"]])


def is_afmoe(arch: dict) -> bool:
    return "layer_types" in arch and "num_dense_layers" in arch and (
        "moe_intermediate_size" in arch and "sliding_window" in arch)


def period(arch: dict) -> int:
    """The shortest period of ``layer_types`` that divides the depth."""
    kinds = _layer_types(arch)
    n = len(kinds)
    return next(
        p for p in range(1, n + 1)
        if n % p == 0 and all(kinds[i] == kinds[i % p] for i in range(n)))


def n_layers_by_kind(arch: dict) -> Dict[str, int]:
    kinds = [_KINDS[t] for t in _layer_types(arch)]
    return {k: kinds.count(k) for k in ("full", "window")}


def kv_bytes_per_token_by_kind(arch: dict, itemsize: int = 2) -> Dict[str, int]:
    """What the cache holds of one token: in the full layers for as long as
    its request runs, in the window layers for ``sliding_window``
    positions."""
    one = _token_layer_bytes(arch, itemsize)
    return {k: n * one for k, n in n_layers_by_kind(arch).items()}


def page_bytes(arch: dict, page: int, itemsize: int = 2) -> int:
    """One page of the pool: ``page`` tokens of one position of the period
    in every period."""
    periods = arch["num_hidden_layers"] // period(arch)
    return periods * page * _token_layer_bytes(arch, itemsize)


def resident_bytes(
    arch: dict, resident_tokens: int, window_resident_tokens: int,
    itemsize: int = 2, distinct_ratio: float = 1.0,
) -> float:
    """Bytes one decode step must read: the full layers over every
    resident token, the window layers over ``sum(min(len, window))``.
    ``distinct_ratio`` (``benchmark/resident.py``) scales the FULL layers'
    part only: a row's window leaves its prompt behind."""
    by_kind = kv_bytes_per_token_by_kind(arch, itemsize)
    return (by_kind["full"] * resident_tokens * distinct_ratio
            + by_kind["window"] * window_resident_tokens)


def decode_step_bytes(arch: dict, lens: Sequence[int], itemsize: int = 2) -> int:
    """:func:`resident_bytes` from the slots' lengths."""
    w = arch["sliding_window"]
    return resident_bytes(
        arch, sum(lens), sum(min(n, w) for n in lens), itemsize)


def n_expert_layers(arch: dict) -> int:
    return arch["num_hidden_layers"] - arch["num_dense_layers"]


def expert_bytes(arch: dict, itemsize: int = 2) -> int:
    """One routed expert's gate, up and down matrices at the stored width."""
    return 3 * arch["hidden_size"] * arch["moe_intermediate_size"] * itemsize


def expert_op_pattern(arch: dict, program: Optional[str] = None) -> "re.Pattern":
    """Labels (``trace_reduce.op_label`` behind ``<program>/``) of the ops
    that stream the routed experts: the kernel by name, or an op whose
    largest operand is the expert stack or one layer's slice of it."""
    Lx, X = n_expert_layers(arch), arch["num_experts"]
    E, F = arch["hidden_size"], arch["moe_intermediate_size"]
    dt = _SHORT[arch["serving_dtype"]]
    prog = re.escape(program) if program else r"[^/]+"
    return re.compile(
        rf"^{prog}/(?:%moe_grouped\S* |"
        rf"\S+ (?!(?:{'|'.join(_COVERING)})\b)\S+ .*"
        rf"<- {dt}\[(?:{Lx},)?{X},(?:{E},{F}|{F},{E})\]$)")


def expert_op_seconds(bench, program: Optional[str] = None) -> Optional[float]:
    """Summed device seconds, inside the traced window, of the ops that
    stream the routed experts' weights; only those of ``program`` if
    given. ``None`` for a configuration of another family or a run without
    a trace."""
    if bench.trace is None or not is_afmoe(bench.arch):
        return None
    rx = expert_op_pattern(bench.arch, program)
    hits = [v[0] for k, v in bench.trace["op_total_s"].items() if rx.search(k)]
    return sum(hits) if hits else None
