"""Bytes of a model with STATE-SPACE layers beside attention layers
(``granitemoehybrid``), from shapes alone, and which ops of a device trace
are the decode step's state update. For the driver of such cells
(``drivers/rollout_state_inproc.py``) and for the ``ssm.*`` and
``kernel.ssm_decode_roofline`` readers under ``layer_metrics/``.

``arch`` is a configuration file's dict with the keys of the family's
public ``config.json``: ``layer_types`` names each layer ``mamba`` or
``attention``. Only attention layers hold keys and values
(``benchmark/flops.py:kv_bytes_per_token`` would count all 40 layers: a
pool sized with it would get a tenth of the pages that fit). A
state-space layer keeps, for each SLOT and whatever its context, ``mamba_
n_heads x mamba_d_head x mamba_d_state`` float32 values (2,097,152 B at
the published sizes).

What the ALGORITHM needs of a decode step: every running slot's recurrent
state of every state-space layer read once and written once
(``state_slots`` on the engine's ``gen_engine/chunk`` spans: running slots
x steps AS DISPATCHED, so a slot that ends inside a chunk is counted to the
chunk's end: about 1 % over what ran in the cell this PR adds, which the
reader's docstring reckons). Whatever implements the update does that
work.

Finding the update's ops: the ``ssm_decode`` kernel BY NAME where the
program runs it; else, as ``benchmark/moe_flops.py`` finds its ops, by the
largest operand, here the engine's whole state array ``f32[state layers,
slots, heads, head dim, state]`` (XLA's form of the update is two fusions a
layer over it). Loop and call ops carry the same array in their tuples and
cover their bodies, so they are left out.
"""

import re
from typing import Optional

_COVERING = ("while", "call", "conditional", "async-start", "async-done")
STATE_KERNEL = r"^jit_chunk/%ssm_decode"
STATE_ITEMSIZE = 4      # the configuration's ``state_dtype``: float32


def layers_of(arch: dict, kind: str) -> int:
    return sum(
        t == kind for t in arch["layer_types"][: arch["num_hidden_layers"]])


def kv_bytes_per_token(arch: dict, itemsize: int = 2) -> int:
    """What one resident token takes of the page pool: a key and a value
    in every ATTENTION layer."""
    Hq = arch["num_attention_heads"]
    Hkv = arch.get("num_key_value_heads") or Hq
    D = arch.get("head_dim") or arch["hidden_size"] // Hq
    return layers_of(arch, "attention") * 2 * Hkv * D * itemsize


def state_bytes_per_slot_layer(arch: dict) -> int:
    """One slot's recurrent state in ONE state-space layer."""
    return (arch["mamba_n_heads"] * arch["mamba_d_head"]
            * arch["mamba_d_state"] * STATE_ITEMSIZE)


def state_bytes_per_slot(arch: dict, itemsize: int = 2) -> int:
    """One slot's recurrent AND convolution state, all layers."""
    conv = (arch["mamba_d_conv"] - 1) * (
        arch["mamba_n_heads"] * arch["mamba_d_head"]
        + 2 * arch["mamba_n_groups"] * arch["mamba_d_state"]) * itemsize
    return layers_of(arch, "mamba") * (
        state_bytes_per_slot_layer(arch) + conv)


def state_op_pattern(arch: dict, slots: int, program: str) -> "re.Pattern":
    """Labels (``trace_reduce.op_label`` behind ``<program>/``) of the ops
    whose largest operand is the engine's whole recurrent-state array."""
    dims = ",".join(str(d) for d in (
        layers_of(arch, "mamba"), slots, arch["mamba_n_heads"],
        arch["mamba_d_head"], arch["mamba_d_state"]))
    return re.compile(
        rf"^{re.escape(program)}/\S+ (?!(?:{'|'.join(_COVERING)})\b)\S+ .*"
        rf"<- f32\[{dims}\]$")


def state_update_seconds(bench, program: str = "jit_chunk") -> Optional[float]:
    """Summed device seconds, inside the traced window, of the decode
    step's state update. ``None`` for a configuration without state-space
    layers, a run without a trace, or a trace without such ops."""
    if bench.trace is None or "mamba_n_heads" not in bench.arch:
        return None
    from benchmark import trace_reduce

    seconds, count = trace_reduce.op_seconds(bench.trace, STATE_KERNEL)
    if count > 0:
        return seconds
    rx = state_op_pattern(bench.arch, bench.mix["clients"], program)
    hits = [v[0] for k, v in bench.trace["op_total_s"].items() if rx.search(k)]
    return sum(hits) if hits else None
