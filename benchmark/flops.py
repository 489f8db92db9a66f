"""Operations and bytes the algorithm needs, from shapes alone.

The benchmark's own copy of the arithmetic (the program has one in
``areal_tpu/base/flops.py``; a later PR may change the program and may not
change the yardstick). ``arch`` is a configuration file's dict with the
keys of the model's public ``config.json``. Recomputed operations (remat)
are never counted: these are the operations the mathematics requires.

One departure from the program's counter: the embedding lookup is a
gather, not a matrix multiplication, so the embedding table is left out of
the matmul parameters (the LM head stays in). At a 152k vocabulary that is
0.23 B of 1.78 B parameters: the program's ``2 * param_count`` overstates
the forward by 15 % at these widths.
"""

from typing import Sequence


def _dims(arch: dict):
    E = arch["hidden_size"]
    Hq = arch["num_attention_heads"]
    Hkv = arch.get("num_key_value_heads") or Hq
    D = arch.get("head_dim") or E // Hq
    return (E, Hq, Hkv, D, arch["intermediate_size"], arch["vocab_size"],
            arch["num_hidden_layers"])


def matmul_params(arch: dict) -> int:
    """Parameters every token is multiplied with: the layers' projections
    and the LM head (untied or tied, it is applied once)."""
    E, Hq, Hkv, D, F, V, L = _dims(arch)
    attn = E * Hq * D + 2 * E * Hkv * D + Hq * D * E
    return L * (attn + 3 * E * F) + E * V


def param_count(arch: dict) -> int:
    """All parameters (embedding, projections, biases, norms, head)."""
    E, Hq, Hkv, D, F, V, L = _dims(arch)
    head = 0 if arch.get("tie_word_embeddings") else E * V
    biases = L * (Hq * D + 2 * Hkv * D)
    return matmul_params(arch) - E * V + head + V * E + biases + (2 * L + 1) * E


def kv_bytes_per_token(arch: dict, itemsize: int = 2) -> int:
    E, Hq, Hkv, D, F, V, L = _dims(arch)
    return 2 * L * Hkv * D * itemsize


def attention_forward_flops(arch: dict, seqlens: Sequence[int]) -> float:
    """Causal attention, forward: QK^T and PV, 2 FLOP a multiply-add, half
    the square."""
    E, Hq, Hkv, D, F, V, L = _dims(arch)
    return float(sum(2 * 2 * (l * l / 2) * D * Hq for l in seqlens) * L)


def forward_flops(arch: dict, seqlens: Sequence[int]) -> float:
    return 2.0 * matmul_params(arch) * sum(seqlens) + attention_forward_flops(
        arch, seqlens
    )


def train_flops(arch: dict, seqlens: Sequence[int]) -> float:
    """Forward + backward: matmuls 3x their forward, attention 3.5x (its
    backward recomputes QK^T and makes dQ, dK, dV, dP: 2.5x)."""
    return 3 * 2.0 * matmul_params(arch) * sum(seqlens) + 3.5 * (
        attention_forward_flops(arch, seqlens)
    )


def flash_train_flops(arch: dict, seqlens: Sequence[int]) -> float:
    """What the flash forward and backward kernels of one training step
    must do (no recompute counted)."""
    return 3.5 * attention_forward_flops(arch, seqlens)
