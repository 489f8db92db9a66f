"""Analytic FLOP accounting for throughput logging.

TPU-native counterpart of the reference's per-MFC FLOPs counter
(``realhf/system/flops_counter.py:15``, formulas in
``realhf/base/monitor.py:288-350``): the trainer divides these by wall
time to log TFLOP/s per step. The benchmark's ``train.mfu`` does not read
this module: ``benchmark/flops.py`` keeps arithmetic of its own.

The attention term uses true per-sequence lengths (packed varlen batches:
cost scales with sum of len² within segments, not T²).
"""

import dataclasses
from typing import Optional, Sequence

from areal_tpu.models.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    bf16_flops: float        # FLOP/s, one chip
    hbm_bytes_per_s: float   # bytes/s, one chip
    source: str


# The ONE table of published peaks, keyed by ``jax.devices()[0].device_kind``.
# MFU and roofline shares are only defined against a row of this table: a
# device that is not here is an error, never a default.
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12,
        hbm_bytes_per_s=819e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
        "16 GB HBM2e at 819 GB/s per chip",
    ),
}


def device_peaks(device_kind: str) -> DevicePeaks:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)} — add a sourced row to "
            "areal_tpu/base/flops.py:DEVICE_PEAKS"
        ) from None


def param_count(cfg: ModelConfig, activated: bool = False) -> int:
    """Total parameter count (embeddings included once). With ``activated``,
    MoE layers count only the ``top_k`` experts a token actually routes
    through — the per-token FLOP proxy (total ≠ activated for MoE)."""
    E, D = cfg.hidden_dim, cfg.head_dim
    L, V, F = cfg.n_layers, cfg.vocab_size, cfg.intermediate_dim
    H = cfg.n_q_heads
    if cfg.mla is not None:
        # latent attention: q through its latent, one kv latent + rotary
        # key, the up-projection to per-head nope keys and values, o_proj
        m = cfg.mla
        attn = (
            E * m.q_lora_rank + m.q_lora_rank * H * D + E * m.latent_dim
            + m.kv_lora_rank * H * (m.qk_nope_head_dim + m.v_head_dim)
            + H * m.v_head_dim * E
        )
    else:
        attn = E * (H * D) + 2 * E * (cfg.n_kv_heads * D) + (H * D) * E
        if cfg.attn_gate:
            attn += E * (H * D)     # the gate's projection, as wide as q's
    if cfg.mlp_type == "gated":
        mlp = 3 * E * F
    elif cfg.mlp_type == "moe":
        n_active = cfg.moe.top_k if activated else cfg.moe.num_experts
        n_active += cfg.moe.n_shared_experts    # every token takes these
        mlp = n_active * 3 * E * cfg.expert_dim + E * cfg.moe.num_experts
    else:
        mlp = 2 * E * F
    # an expert model's leading dense layers are SwiGLUs of F
    layers = cfg.n_dense_layers * (attn + 3 * E * F) + (
        L - cfg.n_dense_layers
    ) * (attn + mlp)
    if cfg.kda is not None:
        # delta-rule layers in the attention layers' place (``ops/kda.py``):
        # q, k, v and the output projection at the mixer's width, the
        # decay's and the gate's rank pairs, beta a head (the convolution's
        # taps, the norms and the decay's vectors are no matmul)
        d = cfg.kda
        mixer = 4 * E * d.d_inner + 2 * d.head_dim * (E + d.d_inner) + E * d.n_heads
        layers += cfg.n_kda_layers * (mixer - attn)
    head = E if cfg.is_critic else (0 if cfg.tied_embedding else E * V)
    return V * E + layers + head


def matmul_param_count(cfg: ModelConfig, activated: bool = False) -> int:
    """The parameters a token is multiplied with: :func:`param_count` less
    the ``V x E`` embedding table, whose lookup is a gather. A tied table
    is the head's matmul and stays counted once; an untied head or a
    critic's scalar head leaves the table out."""
    n = param_count(cfg, activated)
    if cfg.tied_embedding and not cfg.is_critic:
        return n
    return n - cfg.vocab_size * cfg.hidden_dim


def _matmul_uses(cfg: ModelConfig) -> int:
    """Multiplications a token takes with a parameter, summed over the
    parameters (routed experts: the ``top_k`` it takes): :func:`matmul_
    param_count` for a model whose stack runs once. A looped stack
    (``cfg.n_passes``) multiplies with every layer once a PASS and with the
    head once, so the layers' share is counted ``n_passes`` times."""
    n = matmul_param_count(cfg, activated=True)
    if cfg.n_passes == 1:
        return n
    E, V = cfg.hidden_dim, cfg.vocab_size
    head = E if cfg.is_critic else E * V
    return cfg.n_passes * (n - head) + head


def _attention_forward_flops(
    cfg: ModelConfig, seqlens: Optional[Sequence[int]]
) -> float:
    """Causal attention forward: 2 matmuls x 2 FLOP/MAC x causal half.
    Without ``seqlens`` the term is omitted (matmul-dominated models)."""
    if not seqlens:
        return 0.0
    D, H = cfg.head_dim, cfg.n_q_heads
    # once a layer of CACHE: a looped stack attends once a pass
    return sum(2 * 2 * (l * l / 2) * D * H for l in seqlens) * cfg.cache_layers


def _delta_rule_forward_flops(cfg: ModelConfig, n_tokens: int) -> float:
    """The delta-rule recurrence a token (``ops/kda.py:step_update``): over
    each head's ``Dk x Dv`` state one decay and three multiply-adds (``S^T
    k``, the rank-one write, ``S^T q``), 7 FLOP an entry, whatever the
    chunking (the chunked form's pair terms are ``chunk / D`` of that
    again and are left out, as the flash kernels' recompute is)."""
    if cfg.kda is None:
        return 0.0
    d = cfg.kda
    return 7.0 * d.n_heads * d.head_dim**2 * cfg.n_kda_layers * n_tokens


def forward_flops(
    cfg: ModelConfig,
    n_tokens: int,
    seqlens: Optional[Sequence[int]] = None,
) -> float:
    fwd = 2 * _matmul_uses(cfg) * n_tokens
    return fwd + _attention_forward_flops(cfg, seqlens) + (
        _delta_rule_forward_flops(cfg, n_tokens))


def train_flops(
    cfg: ModelConfig,
    n_tokens: int,
    seqlens: Optional[Sequence[int]] = None,
) -> float:
    """Total FLOPs for ONE forward+backward over ``n_tokens`` packed tokens
    (backward ≈ 2x forward for matmuls; attention backward ≈ 2.5x its
    forward). ``seqlens`` sharpens the attention term."""
    fwd = 2 * _matmul_uses(cfg) * n_tokens
    return 3 * fwd + 3.5 * _attention_forward_flops(cfg, seqlens) + (
        3 * _delta_rule_forward_flops(cfg, n_tokens))
