"""Bytes of a LOOPED stack (the layers run ``total_ut_steps`` times over one
set of weights, and the cache holds a token once a pass), from shapes
alone, and which ops of a device trace stream the layer stack. For the
driver of such cells (``drivers/rollout_looped_inproc.py``) and for the
``kernel.looped_decode_roofline`` and ``loop.weight_stream_*`` readers
under ``layer_metrics/``.

``arch`` is a configuration file's dict with the keys of the family's
public ``config.json`` (``ouro``): ``total_ut_steps`` passes over
``num_hidden_layers`` layers of weights, so ``total_ut_steps x
num_hidden_layers`` layers of K/V a token. ``benchmark/flops.py:
kv_bytes_per_token`` counts ONE pass: a pool sized with it would get four
times the pages that fit, and a roofline on it would read a quarter.

Every figure counts what the ALGORITHM needs: a decode step has to read
every resident key and value of every cache layer once, and each run of a
layer (``layer_passes``: steps x passes x layers) has to read that
layer's seven matrices once. Reading the stack ONCE a step and keeping it
on the chip for the other three passes is not possible here (0.82 GB
against 128 MiB of VMEM), so the weights' bytes are counted a pass.

Finding the weight-stream ops: as ``benchmark/moe_flops.py`` does, by the
largest operand, which ``trace_reduce.op_label`` keeps: the stacked
matrices ``[L, E, Hq*D]`` (q; k and v ``[L, E, Hkv*D]``), ``[L, Hq*D, E]``
(o), ``[L, E, F]`` (gate, up), ``[L, F, E]`` (down), or one layer's slice
of them without the leading ``L``, or that slice with its head axis split
off (``[Hq, D, E]``, ``[E, Hq, D]``: the compiler copies a layer's
``[2048, 2048]`` matrix out of the stack for two of q, k, v and the
projection reads the copy in that view; my chip run, PR 37: 0.036 s of
0.737 in a traced part). Loop and call ops carry the same tensors in
their tuples and cover their bodies, so they are left out. The head ``[E,
V]`` is not the stack and is not counted on either side.
"""

import re
from typing import Optional

_SHORT = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}
_COVERING = ("while", "call", "conditional", "async-start", "async-done")
# the paged-decode kernel inside the decode-chunk program, by its NAME (not
# "every Mosaic call": the chunk also holds kv_page_write and fused_sample)
DECODE_KERNEL = r"^jit_chunk/%paged_decode"


def _dims(arch: dict):
    E = arch["hidden_size"]
    Hq = arch["num_attention_heads"]
    Hkv = arch.get("num_key_value_heads") or Hq
    D = arch.get("head_dim") or E // Hq
    return E, Hq, Hkv, D, arch["intermediate_size"]


def passes(arch: dict) -> int:
    return int(arch["total_ut_steps"])


def cache_layers(arch: dict) -> int:
    """Layers of K/V the cache holds of one token: one a pass a layer."""
    return passes(arch) * arch["num_hidden_layers"]


def kv_bytes_per_token(arch: dict, itemsize: int = 2) -> int:
    """What one resident token takes of the pool, and what a decode step
    must read of it: a key and a value in every cache layer."""
    E, Hq, Hkv, D, F = _dims(arch)
    return cache_layers(arch) * 2 * Hkv * D * itemsize


def layer_weight_bytes(arch: dict, itemsize: int = 2) -> int:
    """One layer's matmul weights (q, k, v, o, gate, up, down) at the
    stored width: what one run of the layer must read."""
    E, Hq, Hkv, D, F = _dims(arch)
    return (2 * E * Hq * D + 2 * E * Hkv * D + 3 * E * F) * itemsize


def weight_op_pattern(arch: dict, program: Optional[str] = None) -> "re.Pattern":
    """Labels (``trace_reduce.op_label`` behind ``<program>/``) of the ops
    whose largest operand is the layer stack or one layer's slice of it."""
    E, Hq, Hkv, D, F = _dims(arch)
    L = arch["num_hidden_layers"]
    dt = _SHORT[arch["serving_dtype"]]
    prog = re.escape(program) if program else r"[^/]+"
    mats = sorted({
        f"{E},{Hq * D}", f"{E},{Hkv * D}", f"{Hq * D},{E}",
        f"{E},{F}", f"{F},{E}"})
    split = sorted({
        f"{Hq},{D},{E}", f"{Hkv},{D},{E}", f"{E},{Hq},{D}", f"{E},{Hkv},{D}"})
    return re.compile(
        rf"^{prog}/\S+ (?!(?:{'|'.join(_COVERING)})\b)\S+ .*"
        rf"<- {dt}\[(?:(?:{L},)?(?:{'|'.join(mats)})|{'|'.join(split)})\]$")


def weight_op_seconds(bench, program: Optional[str] = None) -> Optional[float]:
    """Summed device seconds, inside the traced window, of the ops that
    stream the layer stack; only those of ``program`` if given. ``None``
    for a configuration that is not looped or a run without a trace."""
    if bench.trace is None or "total_ut_steps" not in bench.arch:
        return None
    rx = weight_op_pattern(bench.arch, program)
    hits = [v[0] for k, v in bench.trace["op_total_s"].items() if rx.search(k)]
    return sum(hits) if hits else None
