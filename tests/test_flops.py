"""``base/flops.py`` against a hand count at the ``r1d-qwen-1p5b`` widths
(``benchmark/configs/r1d-qwen-1p5b.json``): the trainer's ``tflops_per_sec``
line counts the parameters a token is MULTIPLIED with; the embedding
lookup is a gather and contributes nothing (ISSUE 28, ROADMAP D11)."""

import dataclasses

import pytest

from areal_tpu.base import flops
from areal_tpu.models.config import ModelConfig

R1D_1P5B = ModelConfig(
    n_layers=28, n_q_heads=12, n_kv_heads=2, head_dim=128, hidden_dim=1536,
    intermediate_dim=8960, vocab_size=151936, use_attention_bias=True,
)

# one layer: q and o are 1536 x 1536, k and v 1536 x 256 each; a gated MLP
# is three 1536 x 8960 matrices
ATTN = 1536 * 1536 + 2 * 1536 * 256 + 1536 * 1536       # 5,505,024
MLP = 3 * 1536 * 8960                                   # 41,287,680
LAYERS = 28 * (ATTN + MLP)                              # 1,310,195,712
TABLE = 151936 * 1536                                   # 233,373,696
HEAD = 1536 * 151936                                    # 233,373,696
SEQLENS = [1024, 512, 2048, 512]
N_TOKENS = sum(SEQLENS)
# causal attention forward: QK^T and PV, 2 FLOP a multiply-add, half the
# square, 12 query heads x 128, 28 layers
ATTN_FWD = sum(2 * 2 * (l * l / 2) * 128 * 12 for l in SEQLENS) * 28


def test_param_count_keeps_the_table():
    assert LAYERS == 1_310_195_712 and TABLE == 233_373_696
    assert flops.param_count(R1D_1P5B) == TABLE + LAYERS + HEAD


@pytest.mark.parametrize(
    "overrides,matmul_params",
    [
        ({}, LAYERS + HEAD),                        # untied: the table is a gather
        ({"tied_embedding": True}, LAYERS + TABLE), # tied: it IS the head's matmul
        ({"is_critic": True}, LAYERS + 1536),       # critic: a 1536 x 1 value head
    ],
    ids=["untied", "tied", "critic"],
)
def test_flops_count_matmul_parameters_only(overrides, matmul_params):
    cfg = dataclasses.replace(R1D_1P5B, **overrides)
    assert flops.matmul_param_count(cfg) == matmul_params
    fwd = 2 * matmul_params * N_TOKENS
    assert flops.forward_flops(cfg, N_TOKENS) == fwd
    assert flops.forward_flops(cfg, N_TOKENS, SEQLENS) == fwd + ATTN_FWD
    assert flops.train_flops(cfg, N_TOKENS) == 3 * fwd
    assert flops.train_flops(cfg, N_TOKENS, SEQLENS) == 3 * fwd + 3.5 * ATTN_FWD


def test_embedding_gather_contributes_zero():
    """A wider vocabulary changes the count by the HEAD's matmul alone:
    twice ``2 * E * dV`` would mean the table is counted as a matmul."""
    wider = dataclasses.replace(R1D_1P5B, vocab_size=151936 + 1000)
    delta = flops.forward_flops(wider, 1) - flops.forward_flops(R1D_1P5B, 1)
    assert delta == 2 * 1536 * 1000
