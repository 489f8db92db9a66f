"""Bytes of a cache whose layers come in KINDS (window and full attention
in one stack), bytes a decode step must read of it, and the expert
matrices of the ``smallthinker`` family's keys, from shapes alone. For the
driver of such cells (``drivers/rollout_hybrid_inproc.py``) and for the
``kernel.hybrid_decode_roofline``, ``attn.decode_kernel_share``,
``gen.window_kv_saved_share`` and ``moe.primary_*`` readers under
``layer_metrics/``.

``arch`` is a configuration file's dict with the keys of the family's
public ``config.json``: ``sliding_window_layout[l]`` is 1 where layer ``l``
attends over ``sliding_window_size`` positions and 0 where over all;
``moe_ffn_hidden_size`` is the width of ONE expert and
``moe_num_primary_experts`` how many a layer has (every layer has them).

Every figure counts what the ALGORITHM needs: a full layer has to read
every resident key and value of a slot, a window layer those of the last
``sliding_window_size`` positions and no more. What the program reads
beyond that (the part of the edge page that lies before the window, pages
it zeroes for shorter rows of a block) is its cost and on neither side of
a roofline here, so a share computed from these bytes can only read low,
never over 100 %.

How the program lays such a cache out (and so how many pages a pool of
given bytes has): a page holds ``page`` tokens of ONE position of the
layout's period in EVERY period, so its bytes are ``periods x 2 x Hkv x
page x D x itemsize`` whatever kind it serves
(``areal_tpu/models/transformer.py:PagedKVCache``). The period is the
shortest one the layout repeats with.

Finding the expert ops: as ``benchmark/moe_flops.py`` does, by the largest
operand: the stacked weights ``[L, X, E, F]`` (gate, up) and ``[L, X, F,
E]`` (down), or as the scan over periods views them ``[L / p, p, X, ...]``,
or one layer's slice ``[X, ...]``. Loop and call ops carry the same
tensors in their tuples and cover their bodies, so they are left out.
"""

import re
from typing import Dict, Optional, Sequence

_SHORT = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}
_COVERING = ("while", "call", "conditional", "async-start", "async-done")
# both programs of the kernel: the full layers' and the window layers'
DECODE_KERNEL = r"^jit_chunk/%paged_decode"


def _layout(arch: dict):
    L = arch["num_hidden_layers"]
    return list(zip(arch["sliding_window_layout"][:L], arch["rope_layout"][:L]))


def period(arch: dict) -> int:
    """The shortest period of the per-layer layout that divides the depth."""
    layout = _layout(arch)
    n = len(layout)
    return next(
        p for p in range(1, n + 1)
        if n % p == 0 and all(layout[i] == layout[i % p] for i in range(n)))


def n_layers_by_kind(arch: dict) -> Dict[str, int]:
    n_window = sum(w for w, _ in _layout(arch))
    return {"full": len(_layout(arch)) - n_window, "window": n_window}


def _token_layer_bytes(arch: dict, itemsize: int) -> int:
    """A key and a value of one token in one layer."""
    hkv = arch.get("num_key_value_heads") or arch["num_attention_heads"]
    return 2 * hkv * arch["head_dim"] * itemsize


def kv_bytes_per_token_by_kind(arch: dict, itemsize: int = 2) -> Dict[str, int]:
    """What the cache holds of one token: in the full layers for as long as
    its request runs, in the window layers for ``sliding_window_size``
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

    ``distinct_ratio`` (``benchmark/resident.py``: distinct over per-slot
    resident tokens) scales the FULL layers' part only: rows of a group
    share their prompt's pages there for as long as they run. The window
    layers' part stays per slot: a row's window leaves its prompt behind
    after ``sliding_window_size`` generated tokens, and until then what it
    shares is a part of ``min(len, window)`` that this count does not
    follow. Left at 1, it is what the cache HOLDS a step's worth of."""
    by_kind = kv_bytes_per_token_by_kind(arch, itemsize)
    return (by_kind["full"] * resident_tokens * distinct_ratio
            + by_kind["window"] * window_resident_tokens)


def decode_step_bytes(arch: dict, lens: Sequence[int], itemsize: int = 2) -> int:
    """:func:`resident_bytes` from the slots' lengths."""
    w = arch["sliding_window_size"]
    return resident_bytes(
        arch, sum(lens), sum(min(n, w) for n in lens), itemsize)


def primary_expert_bytes(arch: dict, itemsize: int = 2) -> int:
    """One expert's gate, up and down matrices at the stored width."""
    return 3 * arch["hidden_size"] * arch["moe_ffn_hidden_size"] * itemsize


def primary_op_pattern(arch: dict, program: Optional[str] = None) -> "re.Pattern":
    """Labels (``trace_reduce.op_label`` behind ``<program>/``) of the ops
    whose largest operand is the stacked expert weights, in any of the
    three views the module docstring names."""
    L, X = arch["num_hidden_layers"], arch["moe_num_primary_experts"]
    E, F = arch["hidden_size"], arch["moe_ffn_hidden_size"]
    p = period(arch)
    dt = _SHORT[arch["serving_dtype"]]
    prog = re.escape(program) if program else r"[^/]+"
    lead = rf"(?:{L},|{L // p},{p},|{p},)?"
    return re.compile(
        rf"^{prog}/\S+ (?!(?:{'|'.join(_COVERING)})\b)\S+ .*"
        rf"<- {dt}\[{lead}{X},(?:{E},{F}|{F},{E})\]$")


def primary_op_seconds(bench, program: Optional[str] = None) -> Optional[float]:
    """Summed device seconds, inside the traced window, of the ops that
    stream the experts' weights; only those of ``program`` if given.
    ``None`` for a configuration without such experts or a run without a
    trace."""
    if bench.trace is None or "moe_num_primary_experts" not in bench.arch:
        return None
    rx = primary_op_pattern(bench.arch, program)
    hits = [v[0] for k, v in bench.trace["op_total_s"].items() if rx.search(k)]
    return sum(hits) if hits else None
