"""Operations and bytes of a model with gated DELTA-RULE linear-attention
layers beside attention layers (family ``solar_open2``), from shapes alone,
and which ops of a device trace are the decode step's state update, the
admission's chunked program and the held experts. For the driver of such
cells (``drivers/rollout_kda_inproc.py``) and for the ``kda.*``,
``kernel.kda_decode_roofline`` and ``moe.kda_*`` readers under
``layer_metrics/``.

``arch`` is a configuration file's dict with the keys of the family's
public ``config.json``: ``gqa_layers`` names the attention layers, every
other layer is linear (``linear_attn_config``: ``num_heads`` heads of
``head_dim``, a key and a value head one width, convolutions of
``short_conv_kernel_size`` taps over q, k and v); every layer's second
branch is ``n_routed_experts`` HELD experts of three matrices ``hidden_size
x moe_intermediate_size`` (the router scores ``n_routed_experts x
expert_parallel_size``). Only attention layers hold keys and values; a
linear layer keeps, for each SLOT and whatever its context, ``heads x
head_dim x head_dim`` float32 values (4,194,304 B at the published sizes).

What the ALGORITHM needs of a decode step: every running slot's state of
every linear layer read once and written once (``state_slots`` on the
engine's ``gen_engine/chunk`` spans: running slots x steps AS DISPATCHED),
beside the row's q, k, v, decay and beta (5 x heads x head_dim float32 in,
heads x head_dim out: 0.16 MB against 8.4 MB, counted). Whatever implements
the update does that work.

The update is bound by those bytes (7 operations a state entry against 8
bytes), so the kernel's roofline is the memory's and no count of its
operations is kept; admission's chunked form (``ops/kda.py:scan_chunked``)
is plain XLA with no kernel of its own, read as a share of device time.

Finding the update's ops: the ``kda_decode`` kernel BY NAME where the
program runs it; else, as ``benchmark/ssm_flops.py`` finds its own, by the
largest operand, the engine's whole state array ``f32[linear layers,
slots, heads, head dim, head dim]``. The held experts' ops: the
``moe_grouped`` kernel or XLA's einsums by THEIR largest operand, a
stack ``[layers of the kind, held, hidden, width]`` (or its transpose) in
the stored dtype: one stack a kind of layer (``params["layers"]``,
``params["kda_layers"]``).
"""

import re
from typing import Optional

_SHORT = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}
_COVERING = ("while", "call", "conditional", "async-start", "async-done")
STATE_KERNEL = r"^jit_chunk/%kda_decode"
STATE_ITEMSIZE = 4      # the configuration's ``state_dtype``: float32
PREFILL_PROGRAM = "jit_extend"


def is_kda(arch: dict) -> bool:
    return "linear_attn_config" in arch and "gqa_layers" in arch


def layers_of(arch: dict, kind: str) -> int:
    """Layers of ``kind``: "attn" (``gqa_layers``) or "kda" (the rest)."""
    n_attn = sum(l < arch["num_hidden_layers"] for l in arch["gqa_layers"])
    return n_attn if kind == "attn" else arch["num_hidden_layers"] - n_attn


def _lin(arch: dict):
    lin = arch["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


def kv_bytes_per_token(arch: dict, itemsize: int = 2) -> int:
    """What one resident token takes of the page pool: a key and a value
    in every ATTENTION layer."""
    return (layers_of(arch, "attn") * 2 * arch["num_key_value_heads"]
            * arch["head_dim"] * itemsize)


def state_bytes_per_slot_layer(arch: dict) -> int:
    """One slot's recurrent state in ONE linear layer."""
    H, D, _ = _lin(arch)
    return H * D * D * STATE_ITEMSIZE


def state_bytes_per_slot(arch: dict, itemsize: int = 2) -> int:
    """One slot's recurrent AND convolution state, all layers."""
    H, D, K = _lin(arch)
    return layers_of(arch, "kda") * (
        state_bytes_per_slot_layer(arch) + (K - 1) * 3 * H * D * itemsize)


def decode_bytes_per_slot_layer(arch: dict) -> int:
    """What one slot's one-token update moves in ONE linear layer: the
    state read and written, q, k, v and the decay a channel and beta a head
    in, the output out (float32)."""
    H, D, _ = _lin(arch)
    return 2 * state_bytes_per_slot_layer(arch) + (
        (4 * H * D + H) + H * D) * STATE_ITEMSIZE


def held_expert_bytes(arch: dict, itemsize: int = 2) -> int:
    """One held expert's gate, up and down matrices at the stored width."""
    return 3 * arch["hidden_size"] * arch["moe_intermediate_size"] * itemsize


def _pattern(arch: dict, dt: str, shapes, program: Optional[str]):
    prog = re.escape(program) if program else r"[^/]+"
    dims = "|".join(",".join(str(d) for d in s) for s in shapes)
    return re.compile(
        rf"^{prog}/\S+ (?!(?:{'|'.join(_COVERING)})\b)\S+ .*"
        rf"<- {dt}\[(?:{dims})\]$")


def _seconds(bench, rx) -> Optional[float]:
    hits = [v[0] for k, v in bench.trace["op_total_s"].items() if rx.search(k)]
    return sum(hits) if hits else None


def state_update_seconds(bench, program: str = "jit_chunk") -> Optional[float]:
    """Summed device seconds, inside the traced window, of the decode
    step's state update. ``None`` for a configuration without linear
    layers, a run without a trace, or a trace without such ops."""
    if bench.trace is None or not is_kda(bench.arch):
        return None
    from benchmark import trace_reduce

    seconds, count = trace_reduce.op_seconds(bench.trace, STATE_KERNEL)
    if count > 0:
        return seconds
    H, D, _ = _lin(bench.arch)
    return _seconds(bench, _pattern(
        bench.arch, "f32",
        [(layers_of(bench.arch, "kda"), bench.mix["clients"], H, D, D)],
        program))


def prefill_program_seconds(bench) -> Optional[float]:
    """Device seconds, inside the traced window, of admission's chunked
    program (``jit_extend``: the prompt's chunks through every layer, the
    delta-rule layers in their chunked form). ``None`` without a trace,
    linear layers, or such a program in the trace."""
    if bench.trace is None or not is_kda(bench.arch):
        return None
    hit = bench.trace.get("modules", {}).get(PREFILL_PROGRAM)
    return hit[0] if hit else None


def expert_op_seconds(bench, program: Optional[str] = None) -> Optional[float]:
    """Summed device seconds, inside the traced window, of the ops that
    stream the held routed experts' stacks (either kind of layer's); only
    ``program``'s if given."""
    if bench.trace is None or not is_kda(bench.arch):
        return None
    arch = bench.arch
    held, E, F = (arch["n_routed_experts"], arch["hidden_size"],
                  arch["moe_intermediate_size"])
    shapes = []
    for kind in ("attn", "kda"):
        n = layers_of(arch, kind)
        shapes += [(n, held, E, F), (n, held, F, E)]
    return _seconds(bench, _pattern(
        arch, _SHORT[arch["serving_dtype"]], shapes, program))
