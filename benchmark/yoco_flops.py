"""Bytes of a decoder-hybrid-decoder's cache and state (family
``phi4flash``), what a decode step must read of them, and where its ops are
in a trace, from shapes alone. For the driver of such cells
(``drivers/rollout_yoco_inproc.py``) and for the ``yoco.*``,
``kernel.yoco_decode_roofline`` and ``ssm.s6_update_share`` readers under
``layer_metrics/``.

``arch`` is a configuration file's dict with the keys of the family's
public ``config.json``; the layout (which layer is a Mamba-1 layer, a
window layer, THE full layer, a gated memory unit, a cross-attention layer)
and the Mamba sizes the published file leaves out come from
``benchmark/reference/phi4flash.py``, the one place that states them.

The cache: sixteen attention layers, NINE cache layers. Each window layer
keeps the last ``sliding_window`` positions of a slot; the one full layer
keeps all of them, and the cross-attention layers behind it read ITS keys
and values with their own queries and keep nothing. So a decode step reads
the full layer's resident positions once for that layer and once more for
every cross layer (sharing the cache saves memory, not reads), and each
window layer's ``min(len, window)``. Every figure counts what the
ALGORITHM needs: what the program reads beyond it (the part of a window's
edge page before the window, pages of shorter rows of a block) is its cost
and on neither side of a roofline here, so a share computed from these
bytes can only read low.

How the program lays the cache out: a page holds ``page`` positions of ONE
cache layer (``areal_tpu/models/transformer.py:PagedKVCache``: the cache's
period is its nine layers, one period deep), so one free list feeds the
window layers and the full one.

Finding the ops: the paged kernel's two programs by name
(``%paged_decode`` is the full program: the full layer's call and the
cross layers'; ``%paged_decode_window`` the window layers'); the Mamba-1
state update (XLA's) by its largest operand, the engine's float32 state of
all layers or one layer's slice of it, whatever shape the compiler views
it in.
"""

import math
import re
from typing import Dict, Optional

from benchmark.reference import phi4flash as ref

STATE_ITEMSIZE = 4                      # the recurrent state is float32
_COVERING = ("while", "call", "conditional", "async-start", "async-done")
FULL_KERNEL = r"^jit_chunk/%paged_decode(?:\.\d+)? "
WINDOW_KERNEL = r"^jit_chunk/%paged_decode_window"


def layers_of(arch: dict) -> Dict[str, int]:
    """Layers of each kind: ``mamba``, ``window``, ``full``, ``gmu``,
    ``cross``."""
    out = dict.fromkeys(("mamba", "window", "full", "gmu", "cross"), 0)
    for kind, window in ref.layer_kinds(arch):
        if kind == "attention":
            kind = "full" if window is None else "window"
        out[kind] += 1
    return out


def token_layer_bytes(arch: dict, itemsize: int = 2) -> int:
    """A key and a value of one position in one cache layer."""
    return (2 * arch["num_key_value_heads"] * ref.sizes(arch)["head_dim"]
            * itemsize)


def page_bytes(arch: dict, page: int, itemsize: int = 2) -> int:
    """One page of the pool: ``page`` positions of one cache layer."""
    return page * token_layer_bytes(arch, itemsize)


def kv_bytes_per_token_by_kind(arch: dict, itemsize: int = 2) -> Dict[str, int]:
    """What the cache holds of one position: in the full layer for as long
    as its request runs, in the window layers for ``sliding_window``
    positions."""
    n, one = layers_of(arch), token_layer_bytes(arch, itemsize)
    return {"full": n["full"] * one, "window": n["window"] * one}


def state_bytes_per_slot(arch: dict, itemsize: int = 2) -> int:
    """One slot's recurrent AND convolution state, all Mamba layers."""
    sz = ref.sizes(arch)
    return layers_of(arch)["mamba"] * sz["d_inner"] * (
        sz["d_state"] * STATE_ITEMSIZE + (sz["d_conv"] - 1) * itemsize)


def shared_kv_readers(arch: dict) -> int:
    """Layers that read the full layer's keys and values a step: itself
    and every cross-attention layer."""
    n = layers_of(arch)
    return n["full"] + n["cross"]


def resident_bytes(arch: dict, resident_tokens: int,
                   window_resident_tokens: int, itemsize: int = 2,
                   distinct_ratio: float = 1.0) -> float:
    """Bytes one decode step must read of the cache: the full layer's
    resident positions once a READER, each window layer's ``sum(min(len,
    window))``.

    ``distinct_ratio`` (``benchmark/resident.py``: distinct over per-slot
    resident tokens) scales the FULL layer's part only, for each of its
    readers: rows of a group share their prompt's pages there for as long
    as they run. The window layers' part stays per slot: a row's window
    leaves its prompt behind after ``sliding_window`` generated tokens."""
    one = token_layer_bytes(arch, itemsize)
    return one * (
        shared_kv_readers(arch) * resident_tokens * distinct_ratio
        + layers_of(arch)["window"] * window_resident_tokens)


_OPERAND = re.compile(r"<- f32\[([\d,]+)\]$")


def state_update_seconds(bench, program: str = "jit_chunk") -> Optional[float]:
    """Summed device seconds, inside the traced window, of the ops of the
    decode chunk whose largest operand is the recurrent state: all layers
    of all slots or one layer's slice, in any view. ``None`` for a
    configuration of another family, a run without a trace, or a trace
    without such ops."""
    if bench.trace is None or bench.arch.get("model_type") != "phi4flash":
        return None
    sz = ref.sizes(bench.arch)
    one = bench.mix["clients"] * sz["d_inner"] * sz["d_state"]
    sizes = {one, layers_of(bench.arch)["mamba"] * one}
    hits = []
    for label, (seconds, _) in bench.trace["op_total_s"].items():
        head, _, rest = label.partition(" ")
        if not head.startswith(program + "/") or rest.split(" ")[0].split(
                ":")[0] in _COVERING:
            continue
        m = _OPERAND.search(label)
        if m and math.prod(int(d) for d in m.group(1).split(",")) in sizes:
            hits.append(seconds)
    return sum(hits) if hits else None
