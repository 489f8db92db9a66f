"""HF ↔ areal_tpu checkpoint converters for all supported model families.

TPU-native counterpart of the reference's ``realhf/api/from_hf/*`` registry
(llama/qwen2/qwen3/gpt2/gemma/mistral/mixtral, ~1390 LoC; olmoe,
joyai_llm_flash, smallthinker, ouro, granitemoehybrid, phi4flash,
nemotron_h, afmoe and solar_open2 are added here) consumed by
``ReaLModel.from_/to_{family}`` (``realhf/impl/model/nn/real_llm_api.py:898``).

Design: converters are pure functions over ``Dict[str, np.ndarray]`` (flat HF
state dicts) ↔ our stacked-layer pytrees. IO helpers read/write safetensors +
config.json. torch never appears on this path — HF tensors arrive as numpy
(the safetensors reader yields numpy directly).

Note the torch/HF ``nn.Linear`` convention stores weights ``[out, in]``; ours
are ``[in, out]`` (right-multiplication ``x @ w``), so linear weights are
transposed on the way through. GPT-2's ``Conv1D`` is already ``[in, out]``.
"""

import dataclasses
import json
import os
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from areal_tpu.models.config import (
    CCAConfig, KDAConfig, MLAConfig, ModelConfig, MoEConfig, SSMConfig,
)

HFState = Dict[str, np.ndarray]


@dataclasses.dataclass
class HFFamily:
    name: str
    hf_model_type: str
    config_from_hf: Callable[[Dict[str, Any]], ModelConfig]
    config_to_hf: Callable[[ModelConfig], Dict[str, Any]]
    params_from_hf: Callable[[HFState, ModelConfig], Dict[str, Any]]
    params_to_hf: Callable[[Dict[str, Any], ModelConfig], HFState]


HF_FAMILIES: Dict[str, HFFamily] = {}


def register_hf_family(family: HFFamily):
    HF_FAMILIES[family.name] = family


# --------------------------------------------------------------------------- #
# Llama-like families (llama, mistral, qwen2, qwen3, gemma)
# --------------------------------------------------------------------------- #


def _rope_fields(hf: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    rs = hf.get("rope_scaling") or None
    if rs:
        typ = rs.get("rope_type", rs.get("type"))
        if typ in ("default", None):
            return out
        out["rotary_scaling_type"] = typ
        out["rotary_scaling_factor"] = rs.get("factor", 1.0)
        if typ == "llama3":
            out["rotary_low_freq_factor"] = rs.get("low_freq_factor", 1.0)
            out["rotary_high_freq_factor"] = rs.get("high_freq_factor", 4.0)
            out["rotary_original_max_position"] = rs.get(
                "original_max_position_embeddings", 8192
            )
    return out


def _llama_like_config_from_hf(
    hf: Dict[str, Any],
    *,
    qkv_bias: bool = False,
    qk_layernorm: bool = False,
    gemma: bool = False,
    sliding_window: bool = False,
) -> ModelConfig:
    n_q = hf["num_attention_heads"]
    head_dim = hf.get("head_dim") or hf["hidden_size"] // n_q
    return ModelConfig(
        n_layers=hf["num_hidden_layers"],
        n_q_heads=n_q,
        n_kv_heads=hf.get("num_key_value_heads") or n_q,
        head_dim=head_dim,
        hidden_dim=hf["hidden_size"],
        intermediate_dim=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        n_positions=hf.get("max_position_embeddings", 32768),
        layer_norm_type="gemma" if gemma else "rms",
        layer_norm_epsilon=hf.get("rms_norm_eps", 1e-6),
        use_attention_bias=qkv_bias or bool(hf.get("attention_bias", False)),
        qk_layernorm=qk_layernorm,
        sliding_window=(hf.get("sliding_window") if sliding_window else None),
        rotary_base=hf.get("rope_theta", 10000.0),
        activation_function={"gelu_pytorch_tanh": "gelu_pytorch_tanh"}.get(
            hf.get("hidden_act", "silu"), hf.get("hidden_act", "silu")
        ),
        tied_embedding=bool(hf.get("tie_word_embeddings", False)) or gemma,
        normalize_embed=gemma,
        **_rope_fields(hf),
    )


def _llama_like_config_to_hf(cfg: ModelConfig, model_type: str) -> Dict[str, Any]:
    hf: Dict[str, Any] = {
        "model_type": model_type,
        "architectures": [_ARCH_NAMES.get(model_type, "LlamaForCausalLM")],
        "hidden_size": cfg.hidden_dim,
        "intermediate_size": cfg.intermediate_dim,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.n_positions,
        "rms_norm_eps": cfg.layer_norm_epsilon,
        "rope_theta": cfg.rotary_base,
        "hidden_act": cfg.activation_function,
        "tie_word_embeddings": cfg.tied_embedding,
        "attention_bias": cfg.use_attention_bias,
    }
    if cfg.sliding_window is not None:
        hf["sliding_window"] = cfg.sliding_window
    if cfg.rotary_scaling_type is not None:
        rs = {"rope_type": cfg.rotary_scaling_type, "factor": cfg.rotary_scaling_factor}
        if cfg.rotary_scaling_type == "llama3":
            rs.update(
                low_freq_factor=cfg.rotary_low_freq_factor,
                high_freq_factor=cfg.rotary_high_freq_factor,
                original_max_position_embeddings=cfg.rotary_original_max_position,
            )
        hf["rope_scaling"] = rs
    return hf


_ARCH_NAMES = {
    "llama": "LlamaForCausalLM",
    "mistral": "MistralForCausalLM",
    "qwen2": "Qwen2ForCausalLM",
    "qwen3": "Qwen3ForCausalLM",
    "gemma": "GemmaForCausalLM",
    "gpt2": "GPT2LMHeadModel",
    "mixtral": "MixtralForCausalLM",
    "olmoe": "OlmoeForCausalLM",
    "joyai_llm_flash": "DeepseekV3ForCausalLM",
}

# How a family names its expert block in a checkpoint:
# (block, router, gate, up, down) under ``model.layers.{i}.``
_MIXTRAL_MOE = ("block_sparse_moe", "gate", "w1", "w3", "w2")
_OLMOE_MOE = ("mlp", "gate", "gate_proj", "up_proj", "down_proj")


def _stack(sd: HFState, pattern: str, n_layers: int, transpose: bool = False):
    mats = []
    for i in range(n_layers):
        m = np.asarray(sd[pattern.format(i=i)])
        mats.append(m.T if transpose else m)
    return np.stack(mats)


def _llama_like_params_from_hf(
    sd: HFState, cfg: ModelConfig, moe_names=_MIXTRAL_MOE
) -> Dict[str, Any]:
    L = cfg.n_layers
    p = "model.layers.{i}."
    attn: Dict[str, Any] = {
        "wq": _stack(sd, p + "self_attn.q_proj.weight", L, True),
        "wk": _stack(sd, p + "self_attn.k_proj.weight", L, True),
        "wv": _stack(sd, p + "self_attn.v_proj.weight", L, True),
        "wo": _stack(sd, p + "self_attn.o_proj.weight", L, True),
    }
    if cfg.use_attention_bias:
        attn["bq"] = _stack(sd, p + "self_attn.q_proj.bias", L)
        attn["bk"] = _stack(sd, p + "self_attn.k_proj.bias", L)
        attn["bv"] = _stack(sd, p + "self_attn.v_proj.bias", L)
    if cfg.qk_layernorm:
        attn["q_norm"] = _stack(sd, p + "self_attn.q_norm.weight", L)
        attn["k_norm"] = _stack(sd, p + "self_attn.k_norm.weight", L)
    if cfg.mlp_type == "moe":
        block, router, *experts = moe_names

        def stack_experts(name):    # [L, X, in, out]
            return np.stack([
                np.stack([
                    np.asarray(
                        sd[f"model.layers.{i}.{block}.experts.{j}.{name}.weight"]
                    ).T
                    for j in range(cfg.moe.num_experts)
                ])
                for i in range(L)
            ])

        mlp = {
            "router": _stack(sd, p + f"{block}.{router}.weight", L, True),
            **dict(zip(("w_gate", "w_up", "w_down"), map(stack_experts, experts))),
        }
    else:
        mlp = {
            "w_gate": _stack(sd, p + "mlp.gate_proj.weight", L, True),
            "w_up": _stack(sd, p + "mlp.up_proj.weight", L, True),
            "w_down": _stack(sd, p + "mlp.down_proj.weight", L, True),
        }
    params: Dict[str, Any] = {
        "embed": {"weight": np.asarray(sd["model.embed_tokens.weight"])},
        "layers": {
            "ln1": {"weight": _stack(sd, p + "input_layernorm.weight", L)},
            "attn": attn,
            "ln2": {"weight": _stack(sd, p + "post_attention_layernorm.weight", L)},
            "mlp": mlp,
        },
        "final_ln": {"weight": np.asarray(sd["model.norm.weight"])},
    }
    if cfg.is_critic:
        pass  # critic head is never loaded from a CausalLM checkpoint
    elif not cfg.tied_embedding:
        params["head"] = {"weight": np.asarray(sd["lm_head.weight"]).T}
    return params


def _llama_like_params_to_hf(
    params: Dict[str, Any], cfg: ModelConfig, moe_names=_MIXTRAL_MOE
) -> HFState:
    sd: HFState = {"model.embed_tokens.weight": np.asarray(params["embed"]["weight"])}
    lp = params["layers"]
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        sd[p + "input_layernorm.weight"] = np.asarray(lp["ln1"]["weight"][i])
        sd[p + "post_attention_layernorm.weight"] = np.asarray(lp["ln2"]["weight"][i])
        a = lp["attn"]
        sd[p + "self_attn.q_proj.weight"] = np.asarray(a["wq"][i]).T
        sd[p + "self_attn.k_proj.weight"] = np.asarray(a["wk"][i]).T
        sd[p + "self_attn.v_proj.weight"] = np.asarray(a["wv"][i]).T
        sd[p + "self_attn.o_proj.weight"] = np.asarray(a["wo"][i]).T
        if cfg.use_attention_bias:
            sd[p + "self_attn.q_proj.bias"] = np.asarray(a["bq"][i])
            sd[p + "self_attn.k_proj.bias"] = np.asarray(a["bk"][i])
            sd[p + "self_attn.v_proj.bias"] = np.asarray(a["bv"][i])
        if cfg.qk_layernorm:
            sd[p + "self_attn.q_norm.weight"] = np.asarray(a["q_norm"][i])
            sd[p + "self_attn.k_norm.weight"] = np.asarray(a["k_norm"][i])
        m = lp["mlp"]
        if cfg.mlp_type == "moe":
            block, router, gate, up, down = moe_names
            sd[p + f"{block}.{router}.weight"] = np.asarray(m["router"][i]).T
            for j in range(cfg.moe.num_experts):
                e = p + f"{block}.experts.{j}."
                sd[e + f"{gate}.weight"] = np.asarray(m["w_gate"][i, j]).T
                sd[e + f"{down}.weight"] = np.asarray(m["w_down"][i, j]).T
                sd[e + f"{up}.weight"] = np.asarray(m["w_up"][i, j]).T
        else:
            sd[p + "mlp.gate_proj.weight"] = np.asarray(m["w_gate"][i]).T
            sd[p + "mlp.up_proj.weight"] = np.asarray(m["w_up"][i]).T
            sd[p + "mlp.down_proj.weight"] = np.asarray(m["w_down"][i]).T
    sd["model.norm.weight"] = np.asarray(params["final_ln"]["weight"])
    if cfg.is_critic:
        pass
    elif not cfg.tied_embedding:
        sd["lm_head.weight"] = np.asarray(params["head"]["weight"]).T
    return sd


def _register_llama_like(name: str, **cfg_kwargs):
    register_hf_family(
        HFFamily(
            name=name,
            hf_model_type=name,
            config_from_hf=lambda hf, kw=cfg_kwargs: _llama_like_config_from_hf(
                hf, **kw
            ),
            config_to_hf=lambda cfg, n=name: _llama_like_config_to_hf(cfg, n),
            params_from_hf=_llama_like_params_from_hf,
            params_to_hf=_llama_like_params_to_hf,
        )
    )


_register_llama_like("llama")
_register_llama_like("mistral", sliding_window=True)
_register_llama_like("qwen2", qkv_bias=True)
_register_llama_like("qwen3", qk_layernorm=True)
_register_llama_like("gemma", gemma=True)


# --------------------------------------------------------------------------- #
# Mixtral (llama-like + MoE)
# --------------------------------------------------------------------------- #


def _mixtral_config_from_hf(hf: Dict[str, Any]) -> ModelConfig:
    base = _llama_like_config_from_hf(hf, sliding_window=True)
    return dataclasses.replace(
        base,
        mlp_type="moe",
        moe=MoEConfig(
            num_experts=hf["num_local_experts"],
            top_k=hf["num_experts_per_tok"],
            aux_loss_coeff=hf.get("router_aux_loss_coef", 0.0),
            norm_topk_prob=True,
        ),
    )


def _mixtral_config_to_hf(cfg: ModelConfig) -> Dict[str, Any]:
    hf = _llama_like_config_to_hf(cfg, "mixtral")
    hf["num_local_experts"] = cfg.moe.num_experts
    hf["num_experts_per_tok"] = cfg.moe.top_k
    return hf


register_hf_family(
    HFFamily(
        name="mixtral",
        hf_model_type="mixtral",
        config_from_hf=_mixtral_config_from_hf,
        config_to_hf=_mixtral_config_to_hf,
        params_from_hf=_llama_like_params_from_hf,
        params_to_hf=_llama_like_params_to_hf,
    )
)


# --------------------------------------------------------------------------- #
# OLMoE (llama-like + MoE without renormalised top-k + full-width q/k norm)
# --------------------------------------------------------------------------- #


def _olmoe_config_from_hf(hf: Dict[str, Any]) -> ModelConfig:
    """``intermediate_size`` is the width of ONE expert; ``q_norm`` /
    ``k_norm`` are RMSNorms over the whole projection (``qk_norm_over=
    "full"``); the top-k router weights are used as the softmax gives them
    unless ``norm_topk_prob``; ``clip_qkv`` (null in the released configs)
    is refused rather than dropped."""
    if hf.get("clip_qkv") is not None:
        raise ValueError("olmoe: clip_qkv is not supported")
    base = _llama_like_config_from_hf(hf, qk_layernorm=True)
    return dataclasses.replace(
        base,
        qk_norm_over="full",
        mlp_type="moe",
        moe=MoEConfig(
            num_experts=hf["num_experts"],
            top_k=hf["num_experts_per_tok"],
            aux_loss_coeff=hf.get("router_aux_loss_coef", 0.0),
            norm_topk_prob=bool(hf.get("norm_topk_prob", False)),
        ),
    )


def _olmoe_config_to_hf(cfg: ModelConfig) -> Dict[str, Any]:
    hf = _llama_like_config_to_hf(cfg, "olmoe")
    del hf["head_dim"]      # not a key of OlmoeConfig: hidden / heads
    hf.update(
        num_experts=cfg.moe.num_experts,
        num_experts_per_tok=cfg.moe.top_k,
        norm_topk_prob=cfg.moe.norm_topk_prob,
        router_aux_loss_coef=cfg.moe.aux_loss_coeff,
        clip_qkv=None,
    )
    return hf


register_hf_family(
    HFFamily(
        name="olmoe",
        hf_model_type="olmoe",
        config_from_hf=_olmoe_config_from_hf,
        config_to_hf=_olmoe_config_to_hf,
        params_from_hf=lambda sd, cfg: _llama_like_params_from_hf(
            sd, cfg, _OLMOE_MOE
        ),
        params_to_hf=lambda params, cfg: _llama_like_params_to_hf(
            params, cfg, _OLMOE_MOE
        ),
    )
)


# --------------------------------------------------------------------------- #
# SmallThinker (window and full layers in one stack; the full layers carry
# no positional encoding; a softmax router that reads the layer's normed
# INPUT, before attention; ReGLU experts, no shared expert, no dense layer)
# --------------------------------------------------------------------------- #

_SMALLTHINKER_MOE = ("block_sparse_moe", "primary_router", "gate", "up", "down")


def _layout_period(layout: List[Tuple[int, int]]) -> int:
    """The shortest period of a per-layer layout that divides its length:
    found from the layout, never assumed."""
    n = len(layout)
    return next(
        p for p in range(1, n + 1)
        if n % p == 0 and all(layout[i] == layout[i % p] for i in range(n))
    )


def _smallthinker_config_from_hf(hf: Dict[str, Any]) -> ModelConfig:
    """Every key of the published config is read. Layer ``l`` is a window
    layer of ``sliding_window_size`` where ``sliding_window_layout[l]`` is
    1 and full where 0; it applies rotary where ``rope_layout[l]`` is 1 and
    NO positional encoding where 0. The router takes the output of
    ``input_layernorm`` (``router_on_layer_input``); its weights are the
    softmax over the top ``moe_num_active_primary_experts`` logits, which
    is the softmax over all of them, top-k, renormalised. What the family
    does not do is refused, never guessed: a non-null ``rope_scaling``, a
    layout shorter than the depth or with an entry that is not 0 / 1,
    ``moe_primary_router_apply_softmax`` false (a sigmoid router) or
    ``norm_topk_prob`` false."""
    L = hf["num_hidden_layers"]
    if hf.get("rope_scaling") is not None:
        raise ValueError("smallthinker: rope_scaling is not supported")
    if not hf.get("moe_primary_router_apply_softmax", False):
        raise ValueError(
            "smallthinker: moe_primary_router_apply_softmax false (a sigmoid "
            "router) is not supported"
        )
    if not hf.get("norm_topk_prob", True):
        raise ValueError("smallthinker: norm_topk_prob false is not supported")
    layouts = {}
    for key in ("sliding_window_layout", "rope_layout"):
        layout = hf.get(key)
        if layout is None:
            layout = [0 if key == "sliding_window_layout" else 1] * L
        layout = list(layout)
        if len(layout) < L:
            raise ValueError(
                f"smallthinker: {key} has {len(layout)} entries for {L} layers"
            )
        if any(v not in (0, 1) for v in layout):
            raise ValueError(f"smallthinker: {key} entries must be 0 or 1")
        layouts[key] = layout[:L]
    window = hf.get("sliding_window_size")
    if any(layouts["sliding_window_layout"]) and not window:
        raise ValueError("smallthinker: window layers need sliding_window_size")
    per_layer = list(zip(layouts["sliding_window_layout"], layouts["rope_layout"]))
    period = _layout_period(per_layer)
    n_q = hf["num_attention_heads"]
    return ModelConfig(
        n_layers=L,
        n_q_heads=n_q,
        n_kv_heads=hf.get("num_key_value_heads") or n_q,
        head_dim=hf.get("head_dim") or hf["hidden_size"] // n_q,
        hidden_dim=hf["hidden_size"],
        # the model has no dense MLP; the key is the width of ONE expert
        intermediate_dim=hf["moe_ffn_hidden_size"],
        vocab_size=hf["vocab_size"],
        n_positions=hf.get("max_position_embeddings", 16384),
        layer_norm_epsilon=hf.get("rms_norm_eps", 1e-6),
        rotary_base=hf.get("rope_theta", 10000.0),
        layer_pattern=tuple(
            (window if w else None, bool(r)) for w, r in per_layer[:period]
        ),
        activation_function="relu",
        mlp_type="moe",
        moe=MoEConfig(
            num_experts=hf["moe_num_primary_experts"],
            top_k=hf["moe_num_active_primary_experts"],
            norm_topk_prob=True,
            expert_dim=hf["moe_ffn_hidden_size"],
            router_on_layer_input=True,
        ),
        tied_embedding=bool(hf.get("tie_word_embeddings", False)),
    )


def _smallthinker_config_to_hf(cfg: ModelConfig) -> Dict[str, Any]:
    """The published keys, key for key (``model_name`` is the checkpoint's
    own label and not the model's to know: the 21B's is written)."""
    kinds = [cfg.layer_kinds[l % cfg.period] for l in range(cfg.n_layers)]
    windows = {w for w, _ in kinds if w is not None}
    if len(windows) > 1:
        raise ValueError("smallthinker: one sliding_window_size for all layers")
    return {
        "model_type": "smallthinker",
        "architectures": ["SmallThinkerForCausalLM"],
        "model_name": "smallthinker_21b_instruct",
        "head_dim": cfg.head_dim,
        "hidden_size": cfg.hidden_dim,
        "max_position_embeddings": cfg.n_positions,
        "moe_ffn_hidden_size": cfg.expert_dim,
        "moe_num_active_primary_experts": cfg.moe.top_k,
        "moe_num_primary_experts": cfg.moe.num_experts,
        "moe_primary_router_apply_softmax": True,
        "norm_topk_prob": cfg.moe.norm_topk_prob,
        "num_attention_heads": cfg.n_q_heads,
        "num_hidden_layers": cfg.n_layers,
        "num_key_value_heads": cfg.n_kv_heads,
        "rms_norm_eps": cfg.layer_norm_epsilon,
        "rope_layout": [int(r) for _, r in kinds],
        "rope_scaling": None,
        "rope_theta": cfg.rotary_base,
        "sliding_window_layout": [int(w is not None) for w, _ in kinds],
        "sliding_window_size": windows.pop() if windows else None,
        "tie_word_embeddings": cfg.tied_embedding,
        "vocab_size": cfg.vocab_size,
    }


register_hf_family(
    HFFamily(
        name="smallthinker",
        hf_model_type="smallthinker",
        config_from_hf=_smallthinker_config_from_hf,
        config_to_hf=_smallthinker_config_to_hf,
        params_from_hf=lambda sd, cfg: _llama_like_params_from_hf(
            sd, cfg, _SMALLTHINKER_MOE
        ),
        params_to_hf=lambda params, cfg: _llama_like_params_to_hf(
            params, cfg, _SMALLTHINKER_MOE
        ),
    )
)


# --------------------------------------------------------------------------- #
# Ouro (a LOOPED stack: the layers run ``total_ut_steps`` times over one set
# of weights, the model's final norm after every pass; four norms a layer,
# one on each branch's output; an exit gate that the published threshold
# never lets fire)
# --------------------------------------------------------------------------- #


def _ouro_config_from_hf(hf: Dict[str, Any]) -> ModelConfig:
    """Every key of the published config is read. ``total_ut_steps`` is the
    number of passes; ``early_exit_threshold`` 1.0 (as published) means no
    token leaves the loop before the last pass, which is the only thing
    this family computes. What it does not do is refused, never guessed:
    a threshold below 1 (rows of one batch would take different numbers of
    passes), ``use_sliding_window`` true or a non-null ``sliding_window``,
    a non-null ``rope_scaling``, a ``layer_types`` entry (of the first
    ``num_hidden_layers``) other than ``full_attention``, or fewer entries
    than layers. ``max_window_layers`` only says from which layer a window
    would apply and shapes nothing while there is none."""
    L = hf["num_hidden_layers"]
    if float(hf.get("early_exit_threshold", 1.0)) < 1.0:
        raise ValueError(
            "ouro: early_exit_threshold below 1 (tokens leaving the loop at "
            "different passes) is not supported"
        )
    if hf.get("use_sliding_window", False) or hf.get("sliding_window") is not None:
        raise ValueError("ouro: a sliding window is not supported")
    if hf.get("rope_scaling") is not None:
        raise ValueError("ouro: rope_scaling is not supported")
    layer_types = hf.get("layer_types")
    if layer_types is not None:
        if len(layer_types) < L:
            raise ValueError(
                f"ouro: layer_types has {len(layer_types)} entries for "
                f"{L} layers"
            )
        if any(t != "full_attention" for t in layer_types[:L]):
            raise ValueError(
                "ouro: every layer_types entry must be 'full_attention'"
            )
    return dataclasses.replace(
        _llama_like_config_from_hf(hf),
        norm_branch_out=True,
        n_passes=int(hf["total_ut_steps"]),
        exit_gate=True,
    )


def _ouro_config_to_hf(cfg: ModelConfig) -> Dict[str, Any]:
    """The published keys, key for key."""
    return {
        "model_type": "ouro",
        "architectures": ["OuroForCausalLM"],
        "head_dim": cfg.head_dim,
        "hidden_act": cfg.activation_function,
        "hidden_size": cfg.hidden_dim,
        "intermediate_size": cfg.intermediate_dim,
        "layer_types": ["full_attention"] * cfg.n_layers,
        "max_position_embeddings": cfg.n_positions,
        "max_window_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_q_heads,
        "num_hidden_layers": cfg.n_layers,
        "num_key_value_heads": cfg.n_kv_heads,
        "rms_norm_eps": cfg.layer_norm_epsilon,
        "rope_scaling": None,
        "rope_theta": cfg.rotary_base,
        "sliding_window": None,
        "tie_word_embeddings": cfg.tied_embedding,
        "total_ut_steps": cfg.n_passes,
        "early_exit_threshold": 1.0,
        "use_sliding_window": False,
        "vocab_size": cfg.vocab_size,
    }


_OURO_BRANCH_NORMS = (
    ("attn_out_ln", "input_layernorm_2"),
    ("mlp_out_ln", "post_attention_layernorm_2"),
)


def _ouro_params_from_hf(sd: HFState, cfg: ModelConfig) -> Dict[str, Any]:
    params = _llama_like_params_from_hf(sd, cfg)
    for ours, theirs in _OURO_BRANCH_NORMS:
        params["layers"][ours] = {"weight": _stack(
            sd, "model.layers.{i}." + theirs + ".weight", cfg.n_layers)}
    params["exit_gate"] = {
        "weight": np.asarray(sd["model.early_exit_gate.weight"]).T,
        "bias": np.asarray(sd["model.early_exit_gate.bias"]),
    }
    return params


def _ouro_params_to_hf(params: Dict[str, Any], cfg: ModelConfig) -> HFState:
    sd = _llama_like_params_to_hf(params, cfg)
    for ours, theirs in _OURO_BRANCH_NORMS:
        for i in range(cfg.n_layers):
            sd[f"model.layers.{i}.{theirs}.weight"] = np.asarray(
                params["layers"][ours]["weight"][i])
    sd["model.early_exit_gate.weight"] = np.asarray(
        params["exit_gate"]["weight"]).T
    sd["model.early_exit_gate.bias"] = np.asarray(params["exit_gate"]["bias"])
    return sd


register_hf_family(
    HFFamily(
        name="ouro",
        hf_model_type="ouro",
        config_from_hf=_ouro_config_from_hf,
        config_to_hf=_ouro_config_to_hf,
        params_from_hf=_ouro_params_from_hf,
        params_to_hf=_ouro_params_to_hf,
    )
)


# --------------------------------------------------------------------------- #
# granitemoehybrid (state-space layers beside attention layers without
# positions; four multipliers; a dense MLP where ``num_local_experts`` is 0)
# --------------------------------------------------------------------------- #

_GRANITE_MIXERS = {"mamba": "ssm", "attention": "attn"}


def _granite_config_from_hf(hf: Dict[str, Any]) -> ModelConfig:
    """Every key of the published config is read. What this family does not
    compute is refused, never guessed: routed experts (``num_local_experts``
    > 0), positions other than ``"nope"``, a non-null ``rope_scaling``, a
    norm other than ``rmsnorm``, a ``layer_types`` entry other than
    ``mamba`` / ``attention`` (or a list that is not whole periods with both
    kinds), ``mamba_n_groups`` that does not divide the heads, and
    ``mamba_expand x hidden_size`` other than ``mamba_n_heads x
    mamba_d_head``. ``rope_theta`` shapes nothing without positions and is
    written back as published (10000)."""
    if int(hf.get("num_local_experts", 0) or 0) > 0:
        raise ValueError(
            "granitemoehybrid: routed experts (num_local_experts > 0) are "
            "not supported"
        )
    if hf.get("position_embedding_type", "nope") != "nope":
        raise ValueError(
            "granitemoehybrid: position_embedding_type "
            f"{hf.get('position_embedding_type')!r} is not supported "
            "(only 'nope')"
        )
    if hf.get("rope_scaling") is not None:
        raise ValueError("granitemoehybrid: rope_scaling is not supported")
    if hf.get("normalization_function", "rmsnorm") != "rmsnorm":
        raise ValueError(
            "granitemoehybrid: normalization_function "
            f"{hf.get('normalization_function')!r} is not supported"
        )
    L = hf["num_hidden_layers"]
    layer_types = list(hf["layer_types"])[:L]
    if len(layer_types) < L or any(
        t not in _GRANITE_MIXERS for t in layer_types
    ):
        raise ValueError(
            "granitemoehybrid: layer_types must name every layer 'mamba' or "
            f"'attention', got {hf['layer_types']!r}"
        )
    mixers = [_GRANITE_MIXERS[t] for t in layer_types]
    period = next(
        p for p in range(1, L + 1)
        if L % p == 0 and mixers == mixers[:p] * (L // p)
    )
    n_heads, d_head = hf["mamba_n_heads"], hf["mamba_d_head"]
    if n_heads % hf.get("mamba_n_groups", 1):
        raise ValueError(
            f"granitemoehybrid: mamba_n_groups={hf['mamba_n_groups']} does "
            f"not divide mamba_n_heads={n_heads}"
        )
    if hf.get("mamba_expand", 2) * hf["hidden_size"] != n_heads * d_head:
        raise ValueError(
            "granitemoehybrid: mamba_expand x hidden_size must equal "
            "mamba_n_heads x mamba_d_head"
        )
    head_dim = hf["hidden_size"] // hf["num_attention_heads"]
    n_kv = hf["num_key_value_heads"]
    return ModelConfig(
        n_layers=L,
        n_q_heads=hf["num_attention_heads"],
        n_kv_heads=n_kv,
        head_dim=head_dim,
        hidden_dim=hf["hidden_size"],
        intermediate_dim=hf["shared_intermediate_size"],
        vocab_size=hf["vocab_size"],
        n_positions=hf.get("max_position_embeddings", 131072),
        layer_norm_epsilon=hf.get("rms_norm_eps", 1e-5),
        use_attention_bias=bool(hf.get("attention_bias", False)),
        softmax_scale=float(hf["attention_multiplier"]),
        apply_rotary=False,
        rotary_base=float(hf.get("rope_theta", 10000)),
        activation_function=hf.get("hidden_act", "silu"),
        tied_embedding=bool(hf.get("tie_word_embeddings", True)),
        embedding_multiplier=float(hf.get("embedding_multiplier", 1.0)),
        residual_multiplier=float(hf.get("residual_multiplier", 1.0)),
        logits_scaling=float(hf.get("logits_scaling", 1.0)),
        ssm=SSMConfig(
            n_heads=n_heads,
            head_dim=d_head,
            d_state=hf["mamba_d_state"],
            n_groups=hf.get("mamba_n_groups", 1),
            d_conv=hf.get("mamba_d_conv", 4),
            chunk_size=hf.get("mamba_chunk_size", 256),
            conv_bias=bool(hf.get("mamba_conv_bias", True)),
            proj_bias=bool(hf.get("mamba_proj_bias", False)),
        ),
        stack_plan=((L // period, tuple(mixers[:period])),),
    )


def _granite_config_to_hf(cfg: ModelConfig) -> Dict[str, Any]:
    """The published keys, key for key."""
    s = cfg.ssm
    back = {v: k for k, v in _GRANITE_MIXERS.items()}

    def whole(x: float):
        return int(x) if float(x).is_integer() else x

    return {
        "model_type": "granitemoehybrid",
        "architectures": ["GraniteMoeHybridForCausalLM"],
        "attention_bias": cfg.use_attention_bias,
        "attention_multiplier": cfg.softmax_scale,
        "embedding_multiplier": whole(cfg.embedding_multiplier),
        "hidden_act": cfg.activation_function,
        "hidden_size": cfg.hidden_dim,
        "intermediate_size": cfg.intermediate_dim,
        "layer_types": [back[m] for m in cfg.mixers],
        "logits_scaling": whole(cfg.logits_scaling),
        "mamba_chunk_size": s.chunk_size,
        "mamba_conv_bias": s.conv_bias,
        "mamba_d_conv": s.d_conv,
        "mamba_d_head": s.head_dim,
        "mamba_d_state": s.d_state,
        "mamba_expand": s.d_inner // cfg.hidden_dim,
        "mamba_n_groups": s.n_groups,
        "mamba_n_heads": s.n_heads,
        "mamba_proj_bias": s.proj_bias,
        "max_position_embeddings": cfg.n_positions,
        "normalization_function": "rmsnorm",
        "num_attention_heads": cfg.n_q_heads,
        "num_experts_per_tok": 0,
        "num_hidden_layers": cfg.n_layers,
        "num_key_value_heads": cfg.n_kv_heads,
        "num_local_experts": 0,
        "position_embedding_type": "nope",
        "residual_multiplier": cfg.residual_multiplier,
        "rms_norm_eps": cfg.layer_norm_epsilon,
        "rope_scaling": None,
        "rope_theta": whole(cfg.rotary_base),
        "shared_intermediate_size": cfg.intermediate_dim,
        "tie_word_embeddings": cfg.tied_embedding,
        "vocab_size": cfg.vocab_size,
    }


# (ours, the published name under ``model.layers.{i}.mamba.``, transposed)
_GRANITE_MIXER = (
    ("b_in", "in_proj.bias", False),
    ("conv_b", "conv1d.bias", False),
    ("dt_bias", "dt_bias", False),
    ("A_log", "A_log", False),
    ("D", "D", False),
    ("gate_norm", "norm.weight", False),
    ("w_out", "out_proj.weight", True),
    ("b_out", "out_proj.bias", False),
)


def _granite_params_from_hf(sd: HFState, cfg: ModelConfig) -> Dict[str, Any]:
    """A stack a kind of layer, each in the order its layers run. The
    published MLP holds gate and up in ONE matrix (``shared_mlp.
    input_linear``, gate first); the convolution's weight is ``[channels,
    1, taps]``, ours ``[taps, channels]``."""
    F = cfg.intermediate_dim
    ids = cfg.layer_ids

    def get(i, name, transpose=False):
        m = np.asarray(sd[f"model.layers.{i}.{name}"])
        return m.T if transpose else m

    def stack(kind, name, transpose=False, fn=None):
        return np.stack([
            (fn or (lambda m: m))(get(i, name, transpose)) for i in ids[kind]
        ])

    def common(kind):
        return {
            "ln1": {"weight": stack(kind, "input_layernorm.weight")},
            "ln2": {"weight": stack(kind, "post_attention_layernorm.weight")},
            "mlp": {
                "w_gate": stack(kind, "shared_mlp.input_linear.weight", True,
                                lambda m: m[:, :F]),
                "w_up": stack(kind, "shared_mlp.input_linear.weight", True,
                              lambda m: m[:, F:]),
                "w_down": stack(kind, "shared_mlp.output_linear.weight", True),
            },
        }

    attn = {
        ours: stack("attn", f"self_attn.{theirs}_proj.weight", True)
        for ours, theirs in (("wq", "q"), ("wk", "k"), ("wv", "v"), ("wo", "o"))
    }
    if cfg.use_attention_bias:
        for ours, theirs in (("bq", "q"), ("bk", "k"), ("bv", "v")):
            attn[ours] = stack("attn", f"self_attn.{theirs}_proj.bias")
    optional = {
        "b_in": cfg.ssm.proj_bias, "b_out": cfg.ssm.proj_bias,
        "conv_b": cfg.ssm.conv_bias,
    }
    mixer = {
        ours: stack("ssm", "mamba." + theirs, t)
        for ours, theirs, t in _GRANITE_MIXER
        if optional.get(ours, True)
    }
    mixer["conv_w"] = stack(
        "ssm", "mamba.conv1d.weight", fn=lambda m: m[:, 0, :].T)
    # the published in_proj is [z ; xBC ; dt] in one matrix; ours keeps
    # the three apart (``ops/ssm.py:_split_in``)
    s_ = cfg.ssm
    for name, lo, hi in (
        ("w_z", 0, s_.d_inner),
        ("w_xbc", s_.d_inner, s_.d_inner + s_.conv_dim),
        ("w_dt", s_.d_inner + s_.conv_dim, s_.in_dim),
    ):
        mixer[name] = stack(
            "ssm", "mamba.in_proj.weight", True,
            lambda m, lo=lo, hi=hi: m[:, lo:hi])
    params: Dict[str, Any] = {
        "embed": {"weight": np.asarray(sd["model.embed_tokens.weight"])},
        "layers": {**common("attn"), "attn": attn},
        "ssm_layers": {**common("ssm"), "ssm": mixer},
        "final_ln": {"weight": np.asarray(sd["model.norm.weight"])},
    }
    if not cfg.tied_embedding:
        params["head"] = {"weight": np.asarray(sd["lm_head.weight"]).T}
    return params


def _granite_params_to_hf(params: Dict[str, Any], cfg: ModelConfig) -> HFState:
    sd: HFState = {
        "model.embed_tokens.weight": np.asarray(params["embed"]["weight"]),
        "model.norm.weight": np.asarray(params["final_ln"]["weight"]),
    }
    if not cfg.tied_embedding:
        sd["lm_head.weight"] = np.asarray(params["head"]["weight"]).T
    ids = cfg.layer_ids
    for kind, tree in (("attn", "layers"), ("ssm", "ssm_layers")):
        lp = params[tree]
        for at, i in enumerate(ids[kind]):
            p = f"model.layers.{i}."
            sd[p + "input_layernorm.weight"] = np.asarray(
                lp["ln1"]["weight"][at])
            sd[p + "post_attention_layernorm.weight"] = np.asarray(
                lp["ln2"]["weight"][at])
            m = lp["mlp"]
            sd[p + "shared_mlp.input_linear.weight"] = np.concatenate(
                [np.asarray(m["w_gate"][at]).T, np.asarray(m["w_up"][at]).T])
            sd[p + "shared_mlp.output_linear.weight"] = np.asarray(
                m["w_down"][at]).T
            if kind == "attn":
                a = lp["attn"]
                for ours, theirs in (
                    ("wq", "q"), ("wk", "k"), ("wv", "v"), ("wo", "o")
                ):
                    sd[p + f"self_attn.{theirs}_proj.weight"] = np.asarray(
                        a[ours][at]).T
                if cfg.use_attention_bias:
                    for ours, theirs in (("bq", "q"), ("bk", "k"), ("bv", "v")):
                        sd[p + f"self_attn.{theirs}_proj.bias"] = np.asarray(
                            a[ours][at])
                continue
            x = lp["ssm"]
            for ours, theirs, t in _GRANITE_MIXER:
                if ours in x:
                    w = np.asarray(x[ours][at])
                    sd[p + "mamba." + theirs] = w.T if t else w
            sd[p + "mamba.in_proj.weight"] = np.concatenate([
                np.asarray(x[name][at]).T for name in ("w_z", "w_xbc", "w_dt")
            ])
            sd[p + "mamba.conv1d.weight"] = np.ascontiguousarray(
                np.asarray(x["conv_w"][at]).T[:, None, :])
    return sd


register_hf_family(
    HFFamily(
        name="granitemoehybrid",
        hf_model_type="granitemoehybrid",
        config_from_hf=_granite_config_from_hf,
        config_to_hf=_granite_config_to_hf,
        params_from_hf=_granite_params_from_hf,
        params_to_hf=_granite_params_to_hf,
    )
)


# --------------------------------------------------------------------------- #
# nemotron_h (Nemotron-3: blocks of ONE branch, a Mamba-2 mixer, an attention
# without positions or an expert layer; experts of two matrices with squared
# ReLU in a latent; an expert-parallel rank's share of the experts)
# --------------------------------------------------------------------------- #

_NEMOTRON_BLOCKS = {"M": "ssm", "*": "attn", "E": "moe"}


def _runs_plan(kinds: List[str]):
    """``kinds`` (a block a layer) as a stack plan: greedily, the period
    whose repeats cover most from here on (at least two repeats), else the
    block alone, joined to the run of single blocks before it."""
    plan: List[Tuple[int, Tuple[str, ...]]] = []
    i, n = 0, len(kinds)
    while i < n:
        best = (0, 1, 1)                # (covered, period, repeats)
        for p in range(1, (n - i) // 2 + 1):
            r = 1
            while kinds[i + r * p : i + (r + 1) * p] == kinds[i : i + p]:
                r += 1
            if r >= 2 and r * p > best[0]:
                best = (r * p, p, r)
        _, p, r = best
        if r == 1 and plan and plan[-1][0] == 1:
            plan[-1] = (1, plan[-1][1] + (kinds[i],))
        else:
            plan.append((r, tuple(kinds[i : i + p])))
        i += r * p
    return tuple(plan)


def _nemotron_config_from_hf(hf: Dict[str, Any]) -> ModelConfig:
    """Every key of the published config is read; what the program does not
    compute is refused by name: a block other than ``M`` / ``*`` / ``E`` in
    ``hybrid_override_pattern`` (``-``, a dense feed-forward block), a
    group-limited router (``n_group`` / ``topk_group`` other than 1),
    multi-token-prediction layers, projection or attention biases, another
    activation than silu in the mixer, ``expand x hidden_size`` other than
    the mixer's heads. ``rope_theta`` and ``partial_rotary_factor`` shape
    nothing (the family applies no positional encoding) and are written
    back as published; so are the initialisation's keys (``time_step_*``,
    ``rescale_prenorm_residual``) and ``mtp_hybrid_override_pattern``.

    An expert-parallel rank's share is three keys that no published config
    has: ``n_routed_experts`` counts the experts HELD, of the
    ``n_routed_experts x expert_parallel_size`` the router scores, from
    ``expert_parallel_rank x n_routed_experts`` on (``MoEConfig.n_held``)."""
    pattern = hf["hybrid_override_pattern"]
    L = hf["num_hidden_layers"]
    if len(pattern) != L or any(c not in _NEMOTRON_BLOCKS for c in pattern):
        raise ValueError(
            "nemotron_h: hybrid_override_pattern names num_hidden_layers "
            "blocks, each 'M' (Mamba-2), '*' (attention) or 'E' (experts); "
            f"'-' (a dense feed-forward block) is not supported; got "
            f"{pattern!r} for {L} layers")
    if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise ValueError(
            f"nemotron_h: n_group={hf.get('n_group')} / topk_group="
            f"{hf.get('topk_group')}: a group-limited router is not "
            "supported (only 1 / 1)")
    if int(hf.get("num_nextn_predict_layers", 0) or 0) > 0:
        raise ValueError(
            "nemotron_h: num_nextn_predict_layers > 0: the multi-token-"
            "prediction module is not loaded (no generation path drafts "
            "from one); set it to 0")
    for key in ("attention_bias", "mamba_proj_bias", "mlp_bias", "use_bias"):
        if hf.get(key, False):
            raise ValueError(f"nemotron_h: {key} is not supported")
    if hf.get("mamba_hidden_act", "silu") != "silu":
        raise ValueError(
            f"nemotron_h: mamba_hidden_act {hf['mamba_hidden_act']!r} is "
            "not supported (only 'silu')")
    n_heads, d_head = hf["mamba_num_heads"], hf["mamba_head_dim"]
    if hf.get("expand", 2) * hf["hidden_size"] != n_heads * d_head:
        raise ValueError(
            "nemotron_h: expand x hidden_size must equal mamba_num_heads x "
            "mamba_head_dim")
    held = hf["n_routed_experts"]
    ranks = int(hf.get("expert_parallel_size", 1))
    rank = int(hf.get("expert_parallel_rank", 0))
    if not 0 <= rank < ranks:
        raise ValueError(
            f"nemotron_h: expert_parallel_rank {rank} of {ranks} ranks")
    return ModelConfig(
        n_layers=L,
        n_q_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["head_dim"],
        hidden_dim=hf["hidden_size"],
        intermediate_dim=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        n_positions=hf.get("max_position_embeddings", 262144),
        layer_norm_epsilon=hf.get("layer_norm_epsilon", 1e-5),
        apply_rotary=False,
        rotary_base=float(hf.get("rope_theta", 10000)),
        activation_function=hf.get("mlp_hidden_act", "relu2"),
        mlp_type="moe",
        tied_embedding=bool(hf.get("tie_word_embeddings", False)),
        moe=MoEConfig(
            num_experts=held * ranks,
            top_k=hf["num_experts_per_tok"],
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
            expert_dim=hf["moe_intermediate_size"],
            n_shared_experts=hf.get("n_shared_experts", 0),
            shared_dim=hf.get("moe_shared_expert_intermediate_size"),
            scoring="sigmoid",
            selection_bias=True,
            gated=False,
            latent_dim=hf.get("moe_latent_size"),
            n_held=None if ranks == 1 else held,
            held_offset=rank * held,
        ),
        ssm=SSMConfig(
            n_heads=n_heads,
            head_dim=d_head,
            d_state=hf["ssm_state_size"],
            n_groups=hf.get("n_groups", 1),
            d_conv=hf.get("conv_kernel", 4),
            chunk_size=hf.get("chunk_size", 128),
            conv_bias=bool(hf.get("use_conv_bias", True)),
            norm_per_group=True,
        ),
        stack_plan=_runs_plan([_NEMOTRON_BLOCKS[c] for c in pattern]),
        one_branch=True,
    )


def _nemotron_config_to_hf(cfg: ModelConfig) -> Dict[str, Any]:
    """The published keys; the initialisation's as the family publishes
    them."""
    s, m = cfg.ssm, cfg.moe
    back = {v: k for k, v in _NEMOTRON_BLOCKS.items()}
    held, first = m.held
    out = {
        "model_type": "nemotron_h",
        "architectures": ["NemotronHForCausalLM"],
        "attention_bias": False,
        "chunk_size": s.chunk_size,
        "conv_kernel": s.d_conv,
        "expand": s.d_inner // cfg.hidden_dim,
        "head_dim": cfg.head_dim,
        "hidden_size": cfg.hidden_dim,
        "hybrid_override_pattern": "".join(back[k] for k in cfg.mixers),
        "intermediate_size": cfg.intermediate_dim,
        "layer_norm_epsilon": cfg.layer_norm_epsilon,
        "mamba_head_dim": s.head_dim,
        "mamba_hidden_act": "silu",
        "mamba_num_heads": s.n_heads,
        "mamba_proj_bias": False,
        "max_position_embeddings": cfg.n_positions,
        "mlp_bias": False,
        "mlp_hidden_act": cfg.activation_function,
        "moe_intermediate_size": cfg.expert_dim,
        "moe_latent_size": m.latent_dim,
        "moe_shared_expert_intermediate_size": m.shared_width(cfg.expert_dim),
        "moe_shared_expert_overlap": False,
        "mtp_hybrid_override_pattern": "*E",
        "n_group": 1,
        "n_groups": s.n_groups,
        "n_routed_experts": held,
        "n_shared_experts": m.n_shared_experts,
        "norm_eps": cfg.layer_norm_epsilon,
        "norm_topk_prob": m.norm_topk_prob,
        "num_attention_heads": cfg.n_q_heads,
        "num_experts_per_tok": m.top_k,
        "num_hidden_layers": cfg.n_layers,
        "num_key_value_heads": cfg.n_kv_heads,
        "num_logits_to_keep": 1,
        "num_nextn_predict_layers": 0,
        "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True,
        "residual_in_fp32": False,
        "rope_theta": int(cfg.rotary_base),
        "routed_scaling_factor": (
            int(m.routed_scaling_factor)
            if float(m.routed_scaling_factor).is_integer()
            else m.routed_scaling_factor),
        "sliding_window": None,
        "ssm_state_size": s.d_state,
        "tie_word_embeddings": cfg.tied_embedding,
        "time_step_floor": 0.0001,
        "time_step_max": 0.1,
        "time_step_min": 0.001,
        "topk_group": 1,
        "use_bias": False,
        "use_conv_bias": s.conv_bias,
        "use_mamba_kernels": True,
        "vocab_size": cfg.vocab_size,
    }
    if not m.holds_all:
        out["expert_parallel_size"] = m.num_experts // held
        out["expert_parallel_rank"] = first // held
    return out


# (ours, the published name under ``backbone.layers.{i}.mixer.``, transposed)
_NEMOTRON_MIXER = (
    ("conv_b", "conv1d.bias", False),
    ("dt_bias", "dt_bias", False),
    ("A_log", "A_log", False),
    ("D", "D", False),
    ("gate_norm", "norm.weight", False),
    ("w_out", "out_proj.weight", True),
)
_NEMOTRON_ATTN = (("wq", "q"), ("wk", "k"), ("wv", "v"), ("wo", "o"))
_NEMOTRON_MOE = (
    ("router", "gate.weight", True),
    ("b_router", "gate.e_score_correction_bias", False),
    ("latent_down", "fc1_latent_proj.weight", True),
    ("latent_up", "fc2_latent_proj.weight", True),
    ("shared_up", "shared_experts.up_proj.weight", True),
    ("shared_down", "shared_experts.down_proj.weight", True),
)
_NEMOTRON_EXPERT = (("w_up", "up_proj"), ("w_down", "down_proj"))


def _nemotron_in_proj(s: SSMConfig):
    """The published ``in_proj`` is ``[z ; x ; B ; C ; dt]`` in one matrix;
    ours keeps ``[z], [x ; B ; C], [dt]`` apart (``ops/ssm.py:_split_in``)."""
    return (("w_z", 0, s.d_inner),
            ("w_xbc", s.d_inner, s.d_inner + s.conv_dim),
            ("w_dt", s.d_inner + s.conv_dim, s.in_dim))


def _nemotron_params_from_hf(sd: HFState, cfg: ModelConfig) -> Dict[str, Any]:
    """A stack a kind of block, each in the order its blocks run. Of the
    checkpoint's experts (all that the router scores, or the rank's own)
    the tree takes those HELD: ``experts.{held_offset + j}`` where the
    checkpoint has it, else ``experts.{j}``."""
    ids = cfg.layer_ids

    def get(i, name, transpose=False):
        m = np.asarray(sd[f"backbone.layers.{i}.{name}"])
        return m.T if transpose else m

    def stack(kind, name, transpose=False, fn=None):
        return np.stack([
            (fn or (lambda m: m))(get(i, name, transpose)) for i in ids[kind]
        ])

    def norm(kind):
        return {"weight": stack(kind, "norm.weight")}

    attn = {ours: stack("attn", f"mixer.{theirs}_proj.weight", True)
            for ours, theirs in _NEMOTRON_ATTN}
    mixer = {ours: stack("ssm", "mixer." + theirs, t)
             for ours, theirs, t in _NEMOTRON_MIXER
             if ours != "conv_b" or cfg.ssm.conv_bias}
    mixer["conv_w"] = stack(
        "ssm", "mixer.conv1d.weight", fn=lambda m: m[:, 0, :].T)
    for name, lo, hi in _nemotron_in_proj(cfg.ssm):
        mixer[name] = stack(
            "ssm", "mixer.in_proj.weight", True,
            lambda m, lo=lo, hi=hi: m[:, lo:hi])
    moe = cfg.moe
    n_held, first = moe.held
    absent = set()
    if moe.latent_dim is None:
        absent |= {"latent_down", "latent_up"}
    if not moe.n_shared_experts:
        absent |= {"shared_up", "shared_down"}
    mlp = {ours: stack("moe", "mixer." + theirs, t)
           for ours, theirs, t in _NEMOTRON_MOE if ours not in absent}
    for ours, theirs in _NEMOTRON_EXPERT:
        def expert(i, j):
            whole = f"backbone.layers.{i}.mixer.experts.{first + j}.{theirs}.weight"
            own = f"backbone.layers.{i}.mixer.experts.{j}.{theirs}.weight"
            return np.asarray(sd[whole if whole in sd else own]).T

        mlp[ours] = np.stack([
            np.stack([expert(i, j) for j in range(n_held)])
            for i in ids["moe"]])
    params: Dict[str, Any] = {
        "embed": {"weight": np.asarray(sd["backbone.embeddings.weight"])},
        "layers": {"ln1": norm("attn"), "attn": attn},
        "ssm_layers": {"ln1": norm("ssm"), "ssm": mixer},
        "moe_layers": {"ln1": norm("moe"), "mlp": mlp},
        "final_ln": {"weight": np.asarray(sd["backbone.norm_f.weight"])},
    }
    if not cfg.tied_embedding:
        params["head"] = {"weight": np.asarray(sd["lm_head.weight"]).T}
    return params


def _nemotron_params_to_hf(params: Dict[str, Any], cfg: ModelConfig) -> HFState:
    """The experts are written under their place among ALL the router
    scores (``experts.{held_offset + j}``)."""
    sd: HFState = {
        "backbone.embeddings.weight": np.asarray(params["embed"]["weight"]),
        "backbone.norm_f.weight": np.asarray(params["final_ln"]["weight"]),
    }
    if not cfg.tied_embedding:
        sd["lm_head.weight"] = np.asarray(params["head"]["weight"]).T
    ids = cfg.layer_ids
    first = cfg.moe.held[1]

    def put(p, table, tree, at):
        for ours, theirs, t in table:
            if ours in tree:
                w = np.asarray(tree[ours][at])
                sd[p + theirs] = w.T if t else w

    for kind, name in (("attn", "layers"), ("ssm", "ssm_layers"),
                       ("moe", "moe_layers")):
        lp = params[name]
        for at, i in enumerate(ids[kind]):
            p = f"backbone.layers.{i}."
            sd[p + "norm.weight"] = np.asarray(lp["ln1"]["weight"][at])
            p += "mixer."
            if kind == "attn":
                for ours, theirs in _NEMOTRON_ATTN:
                    sd[p + f"{theirs}_proj.weight"] = np.asarray(
                        lp["attn"][ours][at]).T
            elif kind == "ssm":
                x = lp["ssm"]
                put(p, _NEMOTRON_MIXER, x, at)
                sd[p + "in_proj.weight"] = np.concatenate([
                    np.asarray(x[n][at]).T
                    for n, _, _ in _nemotron_in_proj(cfg.ssm)])
                sd[p + "conv1d.weight"] = np.ascontiguousarray(
                    np.asarray(x["conv_w"][at]).T[:, None, :])
            else:
                m = lp["mlp"]
                put(p, _NEMOTRON_MOE, m, at)
                for ours, theirs in _NEMOTRON_EXPERT:
                    for j in range(m[ours].shape[1]):
                        sd[p + f"experts.{first + j}.{theirs}.weight"] = (
                            np.asarray(m[ours][at, j]).T)
    return sd


register_hf_family(
    HFFamily(
        name="nemotron_h",
        hf_model_type="nemotron_h",
        config_from_hf=_nemotron_config_from_hf,
        config_to_hf=_nemotron_config_to_hf,
        params_from_hf=_nemotron_params_from_hf,
        params_to_hf=_nemotron_params_to_hf,
    )
)


# --------------------------------------------------------------------------- #
# solar_open2 (Upstage's Solar Open 2: gated delta-rule linear attention,
# Kimi Delta Attention's layout, 3:1 with gated softmax attention WITHOUT
# positions; two-branch blocks whose second branch is an expert layer in
# every block: sigmoid router with a selection bias, one shared expert)
# --------------------------------------------------------------------------- #


def _solar2_config_from_hf(hf: Dict[str, Any]) -> ModelConfig:
    """Every key of the published config is read; what the program does not
    compute is refused by the key's name: ``kda_use_full_proj`` (the
    decay's and the gate's projections go through a rank of one head's
    width), a non-null ``linear_attn_config.num_kv_heads`` (one k/v head a
    q head), ``use_rope`` (the attention layers apply no positional
    encoding: ``rope_theta`` and ``partial_rotary_factor`` shape nothing
    and are written back as published), ``first_k_dense_replace`` > 0 (every
    block's second branch is the expert layer; ``intermediate_size`` then
    shapes nothing), a ``gqa_layers`` that ``gqa_interval`` does not
    reproduce (layer ``l`` is softmax attention where ``l % (gqa_interval
    + 1) == 0``).

    An expert-parallel rank's share is read the way ``nemotron_h`` reads
    it: ``n_routed_experts`` counts the experts HELD, of ``n_routed_experts
    x expert_parallel_size`` that the router scores, from
    ``expert_parallel_rank x n_routed_experts`` on."""
    lin = hf["linear_attn_config"]
    L = hf["num_hidden_layers"]
    if hf.get("kda_use_full_proj", False):
        raise ValueError(
            "solar_open2: kda_use_full_proj true (full-width projections of "
            "the decay and the output gate) is not supported")
    if lin.get("num_kv_heads") is not None:
        raise ValueError(
            "solar_open2: linear_attn_config.num_kv_heads="
            f"{lin['num_kv_heads']!r}: grouped k/v heads in the linear "
            "layers are not supported (only null: one a q head)")
    if hf.get("use_rope", False):
        raise ValueError(
            "solar_open2: use_rope true is not supported (the attention "
            "layers apply no positional encoding)")
    if int(hf.get("first_k_dense_replace", 0) or 0) > 0:
        raise ValueError(
            "solar_open2: first_k_dense_replace > 0 (leading dense layers) "
            "is not supported")
    every = int(hf["gqa_interval"]) + 1
    gqa = [l for l in range(L) if l % every == 0]
    if list(hf.get("gqa_layers", gqa)) != gqa or L % every:
        raise ValueError(
            f"solar_open2: gqa_layers={hf.get('gqa_layers')!r} is not what "
            f"gqa_interval={hf['gqa_interval']} gives over {L} layers "
            f"({gqa}: one attention layer, then gqa_interval linear layers, "
            "in whole periods)")
    held = hf["n_routed_experts"]
    ranks = int(hf.get("expert_parallel_size", 1))
    rank = int(hf.get("expert_parallel_rank", 0))
    if not 0 <= rank < ranks:
        raise ValueError(
            f"solar_open2: expert_parallel_rank {rank} of {ranks} ranks")
    return ModelConfig(
        n_layers=L,
        n_q_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["head_dim"],
        hidden_dim=hf["hidden_size"],
        intermediate_dim=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        n_positions=hf.get("max_position_embeddings", 1048576),
        layer_norm_epsilon=hf.get("rms_norm_eps", 1e-5),
        apply_rotary=False,
        rotary_base=float(hf.get("rope_theta", 10000)),
        attn_gate=bool(hf.get("use_gqa_gate", False)),
        mlp_type="moe",
        tied_embedding=bool(hf.get("tie_word_embeddings", False)),
        moe=MoEConfig(
            num_experts=held * ranks,
            top_k=hf["num_experts_per_tok"],
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
            expert_dim=hf["moe_intermediate_size"],
            n_shared_experts=hf.get("n_shared_experts", 0),
            scoring="sigmoid",
            selection_bias=True,
            n_held=None if ranks == 1 else held,
            held_offset=rank * held,
        ),
        kda=KDAConfig(
            n_heads=lin["num_heads"],
            head_dim=lin["head_dim"],
            d_conv=lin.get("short_conv_kernel_size", 4),
            neg_eigval=bool(hf.get("kda_allow_neg_eigval", False)),
        ),
        stack_plan=((L // every, (("attn", None),) + ("kda",) * (every - 1)),),
    )


def _solar2_config_to_hf(cfg: ModelConfig) -> Dict[str, Any]:
    d, m = cfg.kda, cfg.moe
    held, first = m.held
    every = cfg.n_layers // cfg.n_attn_layers
    whole = lambda x: int(x) if float(x).is_integer() else x
    out = {
        "model_type": "solar_open2",
        "architectures": ["SolarOpen2ForCausalLM"],
        "partial_rotary_factor": 1,
        "linear_attn_config": {
            "short_conv_kernel_size": d.d_conv, "head_dim": d.head_dim,
            "num_heads": d.n_heads, "num_kv_heads": None},
        "hidden_size": cfg.hidden_dim,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_q_heads,
        "head_dim": cfg.head_dim,
        "num_key_value_heads": cfg.n_kv_heads,
        "vocab_size": cfg.vocab_size,
        "intermediate_size": cfg.intermediate_dim,
        "moe_intermediate_size": cfg.expert_dim,
        "rms_norm_eps": cfg.layer_norm_epsilon,
        "rope_theta": whole(cfg.rotary_base),
        "tie_word_embeddings": cfg.tied_embedding,
        "max_position_embeddings": cfg.n_positions,
        "first_k_dense_replace": 0,
        "use_rope": False,
        "gqa_interval": every - 1,
        "gqa_layers": cfg.layer_ids["attn"],
        "use_gqa_gate": cfg.attn_gate,
        "kda_use_full_proj": False,
        "kda_allow_neg_eigval": d.neg_eigval,
        "n_routed_experts": held,
        "n_shared_experts": m.n_shared_experts,
        "norm_topk_prob": m.norm_topk_prob,
        "routed_scaling_factor": whole(m.routed_scaling_factor),
        "num_experts_per_tok": m.top_k,
    }
    if not m.holds_all:
        out["expert_parallel_size"] = m.num_experts // held
        out["expert_parallel_rank"] = first // held
    return out


# (ours, the name under ``model.layers.{i}.``, transposed); ASSUMED from
# Kimi Linear's published modelling code (the family's own is not public
# where this was written: ``benchmark/configs/solar-open2-l4-ep8.json``)
_SOLAR2_NORMS = (("ln1", "input_layernorm"), ("ln2", "post_attention_layernorm"))
_SOLAR2_ATTN = (
    ("wq", "self_attn.q_proj.weight", True),
    ("wk", "self_attn.k_proj.weight", True),
    ("wv", "self_attn.v_proj.weight", True),
    ("wo", "self_attn.o_proj.weight", True),
    ("wg", "self_attn.g_proj.weight", True),
)
_SOLAR2_KDA = (
    ("w_fa", "self_attn.f_a_proj.weight", True),
    ("w_fb", "self_attn.f_b_proj.weight", True),
    ("dt_bias", "self_attn.dt_bias", False),
    ("A_log", "self_attn.A_log", False),
    ("w_beta", "self_attn.b_proj.weight", True),
    ("w_ga", "self_attn.g_a_proj.weight", True),
    ("w_gb", "self_attn.g_b_proj.weight", True),
    ("o_norm", "self_attn.o_norm.weight", False),
    ("wo", "self_attn.o_proj.weight", True),
)
_SOLAR2_QKV = ("q", "k", "v")
_SOLAR2_MOE = (
    ("router", "block_sparse_moe.gate.weight", True),
    ("b_router", "block_sparse_moe.gate.e_score_correction_bias", False),
    ("shared_gate", "block_sparse_moe.shared_experts.gate_proj.weight", True),
    ("shared_up", "block_sparse_moe.shared_experts.up_proj.weight", True),
    ("shared_down", "block_sparse_moe.shared_experts.down_proj.weight", True),
)


def _solar2_params_from_hf(sd: HFState, cfg: ModelConfig) -> Dict[str, Any]:
    """A stack a kind of layer, each in the order its layers run, both
    with the norms and the expert layer. The linear layers' three
    projections and three convolutions are laid side by side (``w_qkv``,
    ``conv_w``: ``ops/kda.py``). Of the checkpoint's experts the tree takes
    those HELD, as ``nemotron_h``'s loader does."""
    ids = cfg.layer_ids
    n_held, first = cfg.moe.held

    def get(i, name, transpose=False):
        m = np.asarray(sd[f"model.layers.{i}.{name}"])
        return m.T if transpose else m

    def stack(kind, table):
        return {ours: np.stack([get(i, theirs, t) for i in ids[kind]])
                for ours, theirs, t in table
                if ours not in ("wg",) or cfg.attn_gate}

    def common(kind):
        mlp = stack(kind, [
            row for row in _SOLAR2_MOE
            if not row[0].startswith("shared") or cfg.moe.n_shared_experts])
        for ours, theirs in _JOYAI_EXPERT.items():
            def expert(i, j):
                p = f"model.layers.{i}.block_sparse_moe.experts."
                whole = f"{p}{first + j}.{theirs}.weight"
                return np.asarray(
                    sd[whole if whole in sd else f"{p}{j}.{theirs}.weight"]).T

            mlp[ours] = np.stack([
                np.stack([expert(i, j) for j in range(n_held)])
                for i in ids[kind]])
        return {
            **{ours: {"weight": np.stack(
                [get(i, theirs + ".weight") for i in ids[kind]])}
               for ours, theirs in _SOLAR2_NORMS},
            "mlp": mlp}

    kda = stack("kda", _SOLAR2_KDA)
    kda["w_qkv"] = np.stack([
        np.concatenate(
            [get(i, f"self_attn.{x}_proj.weight", True) for x in _SOLAR2_QKV],
            axis=1)
        for i in ids["kda"]])
    kda["conv_w"] = np.stack([
        np.concatenate(
            [get(i, f"self_attn.{x}_conv1d.weight")[:, 0, :].T
             for x in _SOLAR2_QKV], axis=1)
        for i in ids["kda"]])
    params: Dict[str, Any] = {
        "embed": {"weight": np.asarray(sd["model.embed_tokens.weight"])},
        "layers": {**common("attn"), "attn": stack("attn", _SOLAR2_ATTN)},
        "kda_layers": {**common("kda"), "kda": kda},
        "final_ln": {"weight": np.asarray(sd["model.norm.weight"])},
    }
    if not cfg.tied_embedding:
        params["head"] = {"weight": np.asarray(sd["lm_head.weight"]).T}
    return params


def _solar2_params_to_hf(params: Dict[str, Any], cfg: ModelConfig) -> HFState:
    """The experts are written under their place among ALL the router
    scores (``experts.{held_offset + j}``)."""
    sd: HFState = {
        "model.embed_tokens.weight": np.asarray(params["embed"]["weight"]),
        "model.norm.weight": np.asarray(params["final_ln"]["weight"]),
    }
    if not cfg.tied_embedding:
        sd["lm_head.weight"] = np.asarray(params["head"]["weight"]).T
    first = cfg.moe.held[1]
    C = cfg.kda.d_inner

    def put(p, table, tree, at):
        for ours, theirs, t in table:
            if ours in tree:
                w = np.asarray(tree[ours][at])
                sd[p + theirs] = w.T if t else w

    for kind, name in (("attn", "layers"), ("kda", "kda_layers")):
        lp = params[name]
        for at, i in enumerate(cfg.layer_ids[kind]):
            p = f"model.layers.{i}."
            for ours, theirs in _SOLAR2_NORMS:
                sd[p + theirs + ".weight"] = np.asarray(lp[ours]["weight"][at])
            if kind == "attn":
                put(p, _SOLAR2_ATTN, lp["attn"], at)
            else:
                x = lp["kda"]
                put(p, _SOLAR2_KDA, x, at)
                for n, which in enumerate(_SOLAR2_QKV):
                    cut = slice(n * C, (n + 1) * C)
                    sd[p + f"self_attn.{which}_proj.weight"] = np.asarray(
                        x["w_qkv"][at])[:, cut].T
                    sd[p + f"self_attn.{which}_conv1d.weight"] = (
                        np.ascontiguousarray(
                            np.asarray(x["conv_w"][at])[:, cut].T[:, None, :]))
            m = lp["mlp"]
            put(p, _SOLAR2_MOE, m, at)
            for ours, theirs in _JOYAI_EXPERT.items():
                for j in range(m[ours].shape[1]):
                    sd[p + "block_sparse_moe.experts."
                       f"{first + j}.{theirs}.weight"] = np.asarray(
                           m[ours][at, j]).T
    return sd


register_hf_family(
    HFFamily(
        name="solar_open2",
        hf_model_type="solar_open2",
        config_from_hf=_solar2_config_from_hf,
        config_to_hf=_solar2_config_to_hf,
        params_from_hf=_solar2_params_from_hf,
        params_to_hf=_solar2_params_to_hf,
    )
)


# --------------------------------------------------------------------------- #
# phi4flash (Phi-4-mini-flash-reasoning: a decoder-hybrid-decoder. Mamba-1
# layers beside window layers, one full layer whose K/V the cross layers of
# the second half share, gated memory units fed by the last Mamba layer,
# differential attention throughout; no positions, LayerNorms with bias)
# --------------------------------------------------------------------------- #

# what the published config leaves to its class's defaults
_PHI4FLASH_DEFAULTS = {
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_dt_rank": "auto", "mamba_conv_bias": True,
    "mamba_proj_bias": False,
}


def _phi4flash_plan(L: int, every: int, window: Optional[int]):
    """The published layout as a stack plan: a state-space layer every
    ``every`` layers from layer 0 up to layer ``L / 2``; attention between
    them, inside ``window`` below ``L / 2``, FULL at ``L / 2 + 1``; gated
    memory units and cross attention from ``L / 2 + 2``."""
    if every != 2 or L % 4 or L < 8:
        raise ValueError(
            "phi4flash: mb_per_layer must be 2 and num_hidden_layers a "
            f"multiple of 4, at least 8 (got {every}, {L})"
        )
    return (
        (L // 4, ("ssm", ("attn", window))),
        (1, ("ssm", ("attn", None))),
        (L // 4 - 1, ("gmu", "cross")),
    )


def _phi4flash_config_from_hf(hf: Dict[str, Any]) -> ModelConfig:
    for key, want in (("mlp_bias", False), ("lm_head_bias", False),
                      ("hidden_act", "silu")):
        if hf.get(key, want) != want:
            raise ValueError(f"phi4flash: {key}={hf[key]!r} is not supported")
    if not hf.get("sliding_window"):
        raise ValueError("phi4flash: sliding_window must be given")
    opt = {**_PHI4FLASH_DEFAULTS, **{
        k: hf[k] for k in _PHI4FLASH_DEFAULTS if k in hf}}
    if opt["mamba_proj_bias"]:
        raise ValueError("phi4flash: mamba_proj_bias is not supported")
    E, Hq = hf["hidden_size"], hf["num_attention_heads"]
    rank = opt["mamba_dt_rank"]
    return ModelConfig(
        n_layers=hf["num_hidden_layers"],
        n_q_heads=Hq,
        n_kv_heads=hf.get("num_key_value_heads") or Hq,
        head_dim=E // Hq,
        hidden_dim=E,
        intermediate_dim=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        n_positions=hf.get("max_position_embeddings", 262144),
        layer_norm_type="layer",
        layer_norm_epsilon=hf.get("layer_norm_eps", 1e-5),
        use_attention_bias=True,
        use_attn_proj_bias=True,
        apply_rotary=False,
        activation_function="silu",
        tied_embedding=bool(hf.get("tie_word_embeddings", True)),
        embd_pdrop=float(hf.get("embd_pdrop", 0.0)),
        resid_pdrop=float(hf.get("resid_pdrop", 0.0)),
        diff_attn=True,
        ssm=SSMConfig(
            n_heads=1,
            head_dim=opt["mamba_expand"] * E,
            d_state=opt["mamba_d_state"],
            d_conv=opt["mamba_d_conv"],
            conv_bias=bool(opt["mamba_conv_bias"]),
            dt_rank=-(-E // 16) if rank == "auto" else int(rank),
        ),
        stack_plan=_phi4flash_plan(
            hf["num_hidden_layers"], hf.get("mb_per_layer", 2),
            int(hf["sliding_window"])),
    )


def _phi4flash_config_to_hf(cfg: ModelConfig) -> Dict[str, Any]:
    s = cfg.ssm
    window = next(w for w, _ in cfg.layer_kinds if w is not None)
    return {
        "model_type": "phi4flash",
        "architectures": ["Phi4FlashForCausalLM"],
        "embd_pdrop": cfg.embd_pdrop,
        "hidden_act": cfg.activation_function,
        "hidden_size": cfg.hidden_dim,
        "intermediate_size": cfg.intermediate_dim,
        "layer_norm_eps": cfg.layer_norm_epsilon,
        "max_position_embeddings": cfg.n_positions,
        "mb_per_layer": 2,
        "num_attention_heads": cfg.n_q_heads,
        "num_hidden_layers": cfg.n_layers,
        "num_key_value_heads": cfg.n_kv_heads,
        "resid_pdrop": cfg.resid_pdrop,
        "sliding_window": window,
        "tie_word_embeddings": cfg.tied_embedding,
        "mlp_bias": False,
        "lm_head_bias": False,
        "vocab_size": cfg.vocab_size,
        "mamba_d_state": s.d_state,
        "mamba_d_conv": s.d_conv,
        "mamba_expand": s.d_inner // cfg.hidden_dim,
        "mamba_dt_rank": s.dt_rank,
        "mamba_conv_bias": s.conv_bias,
        "mamba_proj_bias": False,
    }


# (ours under ``attn``, the published name under ``model.layers.{i}.attn.``)
_PHI4FLASH_DIFF = (
    ("subln", "subln.weight"), ("lam_q1", "lambda_q1"),
    ("lam_k1", "lambda_k1"), ("lam_q2", "lambda_q2"), ("lam_k2", "lambda_k2"),
)
# (ours under ``ssm``, the published name, transposed)
_PHI4FLASH_MIXER = (
    ("w_xproj", "x_proj.weight", True), ("w_dt", "dt_proj.weight", True),
    ("dt_bias", "dt_proj.bias", False), ("A_log", "A_log", True),
    ("D", "D", False), ("w_out", "out_proj.weight", True),
    ("conv_b", "conv1d.bias", False),
)


def _phi4flash_params_from_hf(sd: HFState, cfg: ModelConfig) -> Dict[str, Any]:
    """A stack a kind of layer, each in the order its layers run. The
    published ``Wqkv`` is ``[q ; k ; v]`` in one matrix (a cross layer's
    holds ``q`` alone), ``mlp.fc1`` ``[gate ; up]``, the Mamba ``in_proj``
    ``[x ; z]``; ``A_log`` is ``[channels, state]``, ours ``[state,
    channels]``; the convolution's weight ``[channels, 1, taps]``, ours
    ``[taps, channels]``."""
    F, ids = cfg.intermediate_dim, cfg.layer_ids
    nq = cfg.n_q_heads * cfg.head_dim
    nkv = cfg.n_kv_heads * cfg.head_dim
    C = cfg.ssm.d_inner

    def stack(kind, name, transpose=False, fn=None):
        out = []
        for i in ids[kind]:
            m = np.asarray(sd[f"model.layers.{i}.{name}"])
            m = m.T if transpose else m
            out.append(fn(m) if fn else m)
        return np.stack(out)

    def block(kind, name, mixer):
        return {
            "ln1": {"weight": stack(kind, "input_layernorm.weight"),
                    "bias": stack(kind, "input_layernorm.bias")},
            "ln2": {"weight": stack(kind, "post_attention_layernorm.weight"),
                    "bias": stack(kind, "post_attention_layernorm.bias")},
            "mlp": {
                "w_gate": stack(kind, "mlp.fc1.weight", True,
                                lambda m: m[:, :F]),
                "w_up": stack(kind, "mlp.fc1.weight", True,
                              lambda m: m[:, F:]),
                "w_down": stack(kind, "mlp.fc2.weight", True),
            },
            name: mixer,
        }

    def attention(kind, parts):
        a = {"wo": stack(kind, "attn.out_proj.weight", True),
             "bo": stack(kind, "attn.out_proj.bias")}
        for w, b, lo, hi in parts:
            a[w] = stack(kind, "attn.Wqkv.weight", True,
                         lambda m, lo=lo, hi=hi: m[:, lo:hi])
            a[b] = stack(kind, "attn.Wqkv.bias",
                         fn=lambda m, lo=lo, hi=hi: m[lo:hi])
        for ours, theirs in _PHI4FLASH_DIFF:
            a[ours] = stack(kind, "attn." + theirs)
        return a

    mixer = {
        ours: stack("ssm", "attn." + theirs, t)
        for ours, theirs, t in _PHI4FLASH_MIXER
        if ours != "conv_b" or cfg.ssm.conv_bias
    }
    mixer["conv_w"] = stack(
        "ssm", "attn.conv1d.weight", fn=lambda m: m[:, 0, :].T)
    mixer["w_x"] = stack("ssm", "attn.in_proj.weight", True, lambda m: m[:, :C])
    mixer["w_z"] = stack("ssm", "attn.in_proj.weight", True, lambda m: m[:, C:])
    params: Dict[str, Any] = {
        "embed": {"weight": np.asarray(sd["model.embed_tokens.weight"])},
        "layers": block("attn", "attn", attention("attn", (
            ("wq", "bq", 0, nq), ("wk", "bk", nq, nq + nkv),
            ("wv", "bv", nq + nkv, nq + 2 * nkv)))),
        "ssm_layers": block("ssm", "ssm", mixer),
        "gmu_layers": block("gmu", "gmu", {
            "w_in": stack("gmu", "attn.in_proj.weight", True),
            "w_out": stack("gmu", "attn.out_proj.weight", True)}),
        "cross_layers": block(
            "cross", "attn", attention("cross", (("wq", "bq", 0, nq),))),
        "final_ln": {
            "weight": np.asarray(sd["model.final_layernorm.weight"]),
            "bias": np.asarray(sd["model.final_layernorm.bias"])},
    }
    if not cfg.tied_embedding:
        params["head"] = {"weight": np.asarray(sd["lm_head.weight"]).T}
    return params


def _phi4flash_params_to_hf(params: Dict[str, Any], cfg: ModelConfig) -> HFState:
    sd: HFState = {
        "model.embed_tokens.weight": np.asarray(params["embed"]["weight"]),
        "model.final_layernorm.weight": np.asarray(params["final_ln"]["weight"]),
        "model.final_layernorm.bias": np.asarray(params["final_ln"]["bias"]),
    }
    if not cfg.tied_embedding:
        sd["lm_head.weight"] = np.asarray(params["head"]["weight"]).T
    trees = {"attn": "layers", "ssm": "ssm_layers", "gmu": "gmu_layers",
             "cross": "cross_layers"}
    for kind, layer_ids in cfg.layer_ids.items():
        lp = params[trees[kind]]
        for at, i in enumerate(layer_ids):
            p = f"model.layers.{i}."

            def put(name, value, transpose=False):
                m = np.asarray(value[at])
                sd[p + name] = m.T if transpose else m

            for ours, theirs in (("ln1", "input_layernorm"),
                                 ("ln2", "post_attention_layernorm")):
                put(theirs + ".weight", lp[ours]["weight"])
                put(theirs + ".bias", lp[ours]["bias"])
            m = lp["mlp"]
            sd[p + "mlp.fc1.weight"] = np.concatenate(
                [np.asarray(m["w_gate"][at]).T, np.asarray(m["w_up"][at]).T])
            put("mlp.fc2.weight", m["w_down"], True)
            if kind in ("attn", "cross"):
                a = lp["attn"]
                names = ("q", "k", "v") if kind == "attn" else ("q",)
                sd[p + "attn.Wqkv.weight"] = np.concatenate(
                    [np.asarray(a["w" + n][at]).T for n in names])
                sd[p + "attn.Wqkv.bias"] = np.concatenate(
                    [np.asarray(a["b" + n][at]) for n in names])
                put("attn.out_proj.weight", a["wo"], True)
                put("attn.out_proj.bias", a["bo"])
                for ours, theirs in _PHI4FLASH_DIFF:
                    put("attn." + theirs, a[ours])
            elif kind == "gmu":
                put("attn.in_proj.weight", lp["gmu"]["w_in"], True)
                put("attn.out_proj.weight", lp["gmu"]["w_out"], True)
            else:
                x = lp["ssm"]
                for ours, theirs, t in _PHI4FLASH_MIXER:
                    if ours in x:
                        put("attn." + theirs, x[ours], t)
                sd[p + "attn.in_proj.weight"] = np.concatenate(
                    [np.asarray(x["w_x"][at]).T, np.asarray(x["w_z"][at]).T])
                sd[p + "attn.conv1d.weight"] = np.ascontiguousarray(
                    np.asarray(x["conv_w"][at]).T[:, None, :])
    return sd


register_hf_family(
    HFFamily(
        name="phi4flash",
        hf_model_type="phi4flash",
        config_from_hf=_phi4flash_config_from_hf,
        config_to_hf=_phi4flash_config_to_hf,
        params_from_hf=_phi4flash_params_from_hf,
        params_to_hf=_phi4flash_params_to_hf,
    )
)


# --------------------------------------------------------------------------- #
# JoyAI-LLM-Flash (the ``deepseek_v3`` equations: latent attention, sigmoid
# router with a correction bias, a shared expert, leading dense layers, a
# multi-token-prediction module)
# --------------------------------------------------------------------------- #

def _joyai_config_from_hf(hf: Dict[str, Any]) -> ModelConfig:
    """Key for key a ``deepseek_v3`` config. What the program does not
    implement is refused, not guessed: a group-limited router (``n_group``
    / ``topk_group`` other than 1), any ``rope_scaling``, rotary pairs other
    than ``(2i, 2i+1)`` (``rope_interleave`` false), a router other
    than sigmoid ``noaux_tc``, experts on other than every layer after the
    dense ones, attention bias, queries without a latent. Keys that shape
    nothing here: ``ep_size`` (how a deployment spreads the experts),
    ``qk_head_dim`` and ``head_dim`` (nope + rope and HF's name for the
    rotary width: checked against the keys they repeat),
    ``num_key_value_heads`` (latent attention has no key heads of its own:
    every query head has its key, up-projected from the one latent)."""
    def must(key, ok, default=None):
        v = hf.get(key, default)
        if v not in ok:
            raise ValueError(
                f"joyai_llm_flash: {key}={v!r} is not supported "
                f"(implemented: {ok})"
            )

    must("n_group", (1,), 1)
    must("topk_group", (1,), 1)
    must("rope_scaling", (None,))
    must("rope_interleave", (True,), True)
    must("scoring_func", ("sigmoid",))
    must("topk_method", ("noaux_tc",))
    must("moe_layer_freq", (1,), 1)
    must("attention_bias", (False,), False)
    must("hidden_act", ("silu",), "silu")
    n_q = hf["num_attention_heads"]
    if not hf.get("q_lora_rank"):
        raise ValueError("joyai_llm_flash: q_lora_rank is required")
    nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    must("qk_head_dim", (nope + rope, None))
    must("head_dim", (rope, None))
    n_dense = hf.get("first_k_dense_replace", 0)
    if not 0 <= n_dense < hf["num_hidden_layers"]:
        raise ValueError(
            "joyai_llm_flash: first_k_dense_replace must leave an expert layer"
        )
    return ModelConfig(
        n_layers=hf["num_hidden_layers"],
        n_q_heads=n_q,
        n_kv_heads=n_q,
        head_dim=nope + rope,
        hidden_dim=hf["hidden_size"],
        intermediate_dim=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        n_positions=hf.get("max_position_embeddings", 131072),
        layer_norm_epsilon=hf.get("rms_norm_eps", 1e-6),
        rotary_base=hf.get("rope_theta", 10000.0),
        tied_embedding=bool(hf.get("tie_word_embeddings", False)),
        mla=MLAConfig(
            q_lora_rank=hf["q_lora_rank"],
            kv_lora_rank=hf["kv_lora_rank"],
            qk_nope_head_dim=nope,
            qk_rope_head_dim=rope,
            v_head_dim=hf["v_head_dim"],
        ),
        mlp_type="moe",
        n_dense_layers=n_dense,
        n_mtp_layers=hf.get("num_nextn_predict_layers", 0),
        moe=MoEConfig(
            num_experts=hf["n_routed_experts"],
            top_k=hf["num_experts_per_tok"],
            routed_scaling_factor=hf.get("routed_scaling_factor", 1.0),
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
            expert_dim=hf["moe_intermediate_size"],
            n_shared_experts=hf.get("n_shared_experts") or 0,
            scoring="sigmoid",
            selection_bias=True,
        ),
    )


def _joyai_config_to_hf(cfg: ModelConfig) -> Dict[str, Any]:
    m, moe = cfg.mla, cfg.moe
    return {
        "model_type": "joyai_llm_flash",
        "architectures": [_ARCH_NAMES["joyai_llm_flash"]],
        "attention_bias": False,
        "ep_size": 1,
        "first_k_dense_replace": cfg.n_dense_layers,
        "head_dim": m.qk_rope_head_dim,
        "hidden_act": cfg.activation_function,
        "hidden_size": cfg.hidden_dim,
        "intermediate_size": cfg.intermediate_dim,
        "kv_lora_rank": m.kv_lora_rank,
        "max_position_embeddings": cfg.n_positions,
        "moe_intermediate_size": cfg.expert_dim,
        "moe_layer_freq": 1,
        "n_group": 1,
        "n_routed_experts": moe.num_experts,
        "n_shared_experts": moe.n_shared_experts,
        "norm_topk_prob": moe.norm_topk_prob,
        "num_attention_heads": cfg.n_q_heads,
        "num_experts_per_tok": moe.top_k,
        "num_hidden_layers": cfg.n_layers,
        "num_key_value_heads": cfg.n_kv_heads,
        "num_nextn_predict_layers": cfg.n_mtp_layers,
        "q_lora_rank": m.q_lora_rank,
        "qk_head_dim": cfg.head_dim,
        "qk_nope_head_dim": m.qk_nope_head_dim,
        "qk_rope_head_dim": m.qk_rope_head_dim,
        "rms_norm_eps": cfg.layer_norm_epsilon,
        "rope_interleave": True,
        "rope_scaling": None,
        "rope_theta": cfg.rotary_base,
        "routed_scaling_factor": moe.routed_scaling_factor,
        "scoring_func": "sigmoid",
        "tie_word_embeddings": cfg.tied_embedding,
        "topk_group": 1,
        "topk_method": "noaux_tc",
        "v_head_dim": m.v_head_dim,
        "vocab_size": cfg.vocab_size,
    }


# our leaf -> the checkpoint's name under ``model.layers.{i}.`` (matrices
# are transposed on the way, gains and the bias are not)
_JOYAI_ATTN = {
    "wq_a": "self_attn.q_a_proj.weight",
    "q_a_norm": "self_attn.q_a_layernorm.weight",
    "wq_b": "self_attn.q_b_proj.weight",
    "wkv_a": "self_attn.kv_a_proj_with_mqa.weight",
    "kv_a_norm": "self_attn.kv_a_layernorm.weight",
    "wkv_b": "self_attn.kv_b_proj.weight",
    "wo": "self_attn.o_proj.weight",
}
_JOYAI_MLP = {
    "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
    "w_down": "mlp.down_proj.weight",
    "router": "mlp.gate.weight",
    "b_router": "mlp.gate.e_score_correction_bias",
    "shared_gate": "mlp.shared_experts.gate_proj.weight",
    "shared_up": "mlp.shared_experts.up_proj.weight",
    "shared_down": "mlp.shared_experts.down_proj.weight",
}
_JOYAI_EXPERT = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}
_JOYAI_NORMS = {"ln1": "input_layernorm", "ln2": "post_attention_layernorm"}
# a family's three tables: (norms, attention leaves, MLP leaves)
_JOYAI_NAMES = (_JOYAI_NORMS, _JOYAI_ATTN, _JOYAI_MLP)


def _is_vector(leaf: str) -> bool:
    return leaf.endswith("_norm") or leaf.startswith("b")


def _joyai_stack_from_hf(sd: HFState, ids: List[int], moe: Optional[MoEConfig],
                         names=_JOYAI_NAMES):
    """The layers ``ids`` of the checkpoint as one stack; ``moe`` None for
    dense layers. ``names``: the family's tables (``afmoe`` has the same
    two stacks under other names, with two more norms and a gate)."""
    norms, attn_names, mlp_names = names

    def stack(name, leaf):
        return np.stack([
            np.asarray(sd[f"model.layers.{i}.{name}"]).T if not _is_vector(leaf)
            else np.asarray(sd[f"model.layers.{i}.{name}"])
            for i in ids
        ])

    def gain(name):
        return {"weight": np.stack(
            [np.asarray(sd[f"model.layers.{i}.{name}.weight"]) for i in ids]
        )}

    attn = {leaf: stack(name, leaf) for leaf, name in attn_names.items()}
    if moe is None:
        mlp = {leaf: stack(mlp_names[leaf], leaf)
               for leaf in ("w_gate", "w_up", "w_down")}
    else:
        mlp = {"router": stack(mlp_names["router"], "router"),
               "b_router": stack(mlp_names["b_router"], "b_router")}
        for leaf, name in _JOYAI_EXPERT.items():
            mlp[leaf] = np.stack([
                np.stack([
                    np.asarray(
                        sd[f"model.layers.{i}.mlp.experts.{j}.{name}.weight"]
                    ).T
                    for j in range(moe.num_experts)
                ])
                for i in ids
            ])
        if moe.n_shared_experts:
            for leaf in ("shared_gate", "shared_up", "shared_down"):
                mlp[leaf] = stack(mlp_names[leaf], leaf)
    return {**{ours: gain(theirs) for ours, theirs in norms.items()},
            "attn": attn, "mlp": mlp}


def _joyai_stack_to_hf(sd: HFState, stack, ids: List[int], names=_JOYAI_NAMES):
    norms, attn_names, mlp_names = names
    for n, i in enumerate(ids):
        p = f"model.layers.{i}."
        for ours, theirs in norms.items():
            sd[p + theirs + ".weight"] = np.asarray(stack[ours]["weight"][n])
        for leaf, name in attn_names.items():
            a = np.asarray(stack["attn"][leaf][n])
            sd[p + name] = a if _is_vector(leaf) else a.T
        mlp = stack["mlp"]
        for leaf, a in mlp.items():
            if "router" in mlp and leaf in _JOYAI_EXPERT:
                for j in range(a.shape[1]):
                    sd[p + f"mlp.experts.{j}.{_JOYAI_EXPERT[leaf]}.weight"] = (
                        np.asarray(a[n, j]).T)
            else:
                a = np.asarray(a[n])
                sd[p + mlp_names[leaf]] = a if _is_vector(leaf) else a.T


def _joyai_params_from_hf(sd: HFState, cfg: ModelConfig,
                          names=_JOYAI_NAMES) -> Dict[str, Any]:
    L, nd = cfg.n_layers, cfg.n_dense_layers
    params: Dict[str, Any] = {
        "embed": {"weight": np.asarray(sd["model.embed_tokens.weight"])},
    }
    if nd:
        params["dense_layers"] = _joyai_stack_from_hf(
            sd, list(range(nd)), None, names)
    params["layers"] = _joyai_stack_from_hf(
        sd, list(range(nd, L)), cfg.moe, names)
    params["final_ln"] = {"weight": np.asarray(sd["model.norm.weight"])}
    if cfg.n_mtp_layers:
        # the modules follow the stack as layers L, L+1, ...; their copies
        # of the embedding, the final norm and the head (``embed_tokens``,
        # ``shared_head.*``) are the model's own and are not read
        ids = list(range(L, L + cfg.n_mtp_layers))
        params["mtp"] = {
            "e_norm": {"weight": np.stack(
                [np.asarray(sd[f"model.layers.{i}.enorm.weight"]) for i in ids])},
            "h_norm": {"weight": np.stack(
                [np.asarray(sd[f"model.layers.{i}.hnorm.weight"]) for i in ids])},
            "eh_proj": np.stack(
                [np.asarray(sd[f"model.layers.{i}.eh_proj.weight"]).T
                 for i in ids]),
            "block": _joyai_stack_from_hf(sd, ids, cfg.moe),
        }
    if not cfg.is_critic and not cfg.tied_embedding:
        params["head"] = {"weight": np.asarray(sd["lm_head.weight"]).T}
    return params


def _joyai_params_to_hf(params: Dict[str, Any], cfg: ModelConfig,
                        names=_JOYAI_NAMES) -> HFState:
    L, nd = cfg.n_layers, cfg.n_dense_layers
    sd: HFState = {
        "model.embed_tokens.weight": np.asarray(params["embed"]["weight"]),
        "model.norm.weight": np.asarray(params["final_ln"]["weight"]),
    }
    if nd:
        _joyai_stack_to_hf(sd, params["dense_layers"], list(range(nd)), names)
    _joyai_stack_to_hf(sd, params["layers"], list(range(nd, L)), names)
    if not cfg.is_critic and not cfg.tied_embedding:
        sd["lm_head.weight"] = np.asarray(params["head"]["weight"]).T
    if cfg.n_mtp_layers:
        mtp = params["mtp"]
        ids = list(range(L, L + cfg.n_mtp_layers))
        _joyai_stack_to_hf(sd, mtp["block"], ids)
        for n, i in enumerate(ids):
            p = f"model.layers.{i}."
            sd[p + "enorm.weight"] = np.asarray(mtp["e_norm"]["weight"][n])
            sd[p + "hnorm.weight"] = np.asarray(mtp["h_norm"]["weight"][n])
            sd[p + "eh_proj.weight"] = np.asarray(mtp["eh_proj"][n]).T
            sd[p + "embed_tokens.weight"] = sd["model.embed_tokens.weight"]
            sd[p + "shared_head.norm.weight"] = sd["model.norm.weight"]
            if "lm_head.weight" in sd:
                sd[p + "shared_head.head.weight"] = sd["lm_head.weight"]
    return sd


register_hf_family(
    HFFamily(
        name="joyai_llm_flash",
        hf_model_type="joyai_llm_flash",
        config_from_hf=_joyai_config_from_hf,
        config_to_hf=_joyai_config_to_hf,
        params_from_hf=_joyai_params_from_hf,
        params_to_hf=_joyai_params_to_hf,
    )
)


# --------------------------------------------------------------------------- #
# afmoe (Arcee's Trinity: gated attention; window layers with rotary and
# full layers without positions in a period counted ACROSS two leading dense
# layers and the expert layers; four norms a layer; a sigmoid router with a
# choice-only bias, a shared expert; muP's scaled embedding)
# --------------------------------------------------------------------------- #

_AFMOE_KINDS = ("sliding_attention", "full_attention")


def _afmoe_config_from_hf(hf: Dict[str, Any]) -> ModelConfig:
    """Every key of the published config is read. Layer ``l`` is a window
    layer of ``sliding_window`` positions WITH rotary where ``layer_types[l]``
    is ``sliding_attention`` and a full layer with NO positional encoding
    where ``full_attention``; the first ``num_dense_layers`` layers are
    SwiGLUs of ``intermediate_size``, the rest route ``num_experts_per_tok``
    of ``num_experts`` experts of ``moe_intermediate_size`` by the sigmoid
    of the router's logits plus ``expert_bias`` (the CHOICE only), weighted
    by the scores alone, renormalised (``route_norm``) and times
    ``route_scale``, beside ``num_shared_experts`` shared ones;
    ``mup_enabled`` multiplies the embedding by ``sqrt(hidden_size)``.
    ``load_balance_coeff`` is carried (``moe.aux_loss_coeff``): the
    published model moves ``expert_bias`` by it and has no loss term; this
    trainer never updates the bias and adds its switch-style balance loss
    times the coefficient where a caller asks for the auxiliary loss.
    ``use_grouped_mm`` (which kernel the published code runs the experts
    through) shapes nothing here. What the family does not do is refused,
    never guessed: a group-limited router (``n_group``, ``topk_group``,
    ``num_expert_groups``, ``num_limited_groups`` other than 1), a
    ``score_func`` other than ``sigmoid``, a non-null ``rope_scaling``, a
    ``layer_types`` entry of another name or too few of them, a
    ``layer_types`` that ``global_attn_every_n_layers`` does not reproduce,
    window layers without ``sliding_window``, attention bias, an
    activation other than ``silu``, ``num_dense_layers`` that leaves no
    expert layer. ``tie_word_embeddings`` is read as given."""
    def must(key, ok, default=None):
        v = hf.get(key, default)
        if v not in ok:
            raise ValueError(
                f"afmoe: {key}={v!r} is not supported (implemented: {ok})")

    for key in ("n_group", "topk_group", "num_expert_groups",
                "num_limited_groups"):
        must(key, (1,), 1)
    must("score_func", ("sigmoid",), "sigmoid")
    must("rope_scaling", (None,))
    must("hidden_act", ("silu",), "silu")
    must("attention_bias", (False,), False)
    L = hf["num_hidden_layers"]
    every = hf.get("global_attn_every_n_layers", 4)
    layer_types = hf.get("layer_types")
    if layer_types is None:
        layer_types = [
            _AFMOE_KINDS[(l + 1) % every == 0] for l in range(L)]
    layer_types = list(layer_types)
    if len(layer_types) < L:
        raise ValueError(
            f"afmoe: layer_types has {len(layer_types)} entries for {L} layers")
    layer_types = layer_types[:L]
    for t in layer_types:
        if t not in _AFMOE_KINDS:
            raise ValueError(f"afmoe: layer_types entry {t!r} is not supported")
    if layer_types != [_AFMOE_KINDS[(l + 1) % every == 0] for l in range(L)]:
        raise ValueError(
            "afmoe: layer_types is not a full layer every "
            f"global_attn_every_n_layers={every}")
    window = hf.get("sliding_window")
    if "sliding_attention" in layer_types and not window:
        raise ValueError("afmoe: window layers need sliding_window")
    per_layer = [
        (window, True) if t == "sliding_attention" else (None, False)
        for t in layer_types]
    period = _layout_period(per_layer)
    n_dense = hf.get("num_dense_layers", 0)
    if not 0 <= n_dense < L:
        raise ValueError("afmoe: num_dense_layers must leave an expert layer")
    n_q = hf["num_attention_heads"]
    return ModelConfig(
        n_layers=L,
        n_q_heads=n_q,
        n_kv_heads=hf.get("num_key_value_heads") or n_q,
        head_dim=hf.get("head_dim") or hf["hidden_size"] // n_q,
        hidden_dim=hf["hidden_size"],
        intermediate_dim=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        n_positions=hf.get("max_position_embeddings", 131072),
        layer_norm_epsilon=hf.get("rms_norm_eps", 1e-5),
        norm_branch_out=True,
        qk_layernorm=True,
        attn_gate=True,
        rotary_base=hf.get("rope_theta", 10000.0),
        layer_pattern=tuple(per_layer[:period]),
        mlp_type="moe",
        n_dense_layers=n_dense,
        moe=MoEConfig(
            num_experts=hf["num_experts"],
            top_k=hf["num_experts_per_tok"],
            routed_scaling_factor=hf.get("route_scale", 1.0),
            aux_loss_coeff=hf.get("load_balance_coeff", 0.0),
            norm_topk_prob=bool(hf.get("route_norm", True)),
            expert_dim=hf["moe_intermediate_size"],
            n_shared_experts=hf.get("num_shared_experts") or 0,
            scoring="sigmoid",
            selection_bias=True,
        ),
        tied_embedding=bool(hf.get("tie_word_embeddings", False)),
        normalize_embed=bool(hf.get("mup_enabled", False)),
    )


def _afmoe_config_to_hf(cfg: ModelConfig) -> Dict[str, Any]:
    """The published keys, key for key."""
    kinds = [cfg.layer_kinds[l % cfg.period] for l in range(cfg.n_layers)]
    windows = {w for w, _ in kinds if w is not None}
    if len(windows) > 1 or any((w is None) == r for w, r in kinds):
        raise ValueError(
            "afmoe: window layers of one sliding_window with rotary, full "
            "layers without positions")
    full = [l for l, (w, _) in enumerate(kinds) if w is None]
    moe = cfg.moe
    return {
        "model_type": "afmoe",
        "architectures": ["AfmoeForCausalLM"],
        "global_attn_every_n_layers": full[0] + 1 if full else cfg.n_layers + 1,
        "head_dim": cfg.head_dim,
        "hidden_act": cfg.activation_function,
        "hidden_size": cfg.hidden_dim,
        "intermediate_size": cfg.intermediate_dim,
        "layer_types": [_AFMOE_KINDS[w is None] for w, _ in kinds],
        "load_balance_coeff": moe.aux_loss_coeff,
        "max_position_embeddings": cfg.n_positions,
        "moe_intermediate_size": cfg.expert_dim,
        "mup_enabled": cfg.normalize_embed,
        "n_group": 1,
        "num_attention_heads": cfg.n_q_heads,
        "num_dense_layers": cfg.n_dense_layers,
        "num_expert_groups": 1,
        "num_experts": moe.num_experts,
        "num_experts_per_tok": moe.top_k,
        "num_hidden_layers": cfg.n_layers,
        "num_key_value_heads": cfg.n_kv_heads,
        "num_limited_groups": 1,
        "num_shared_experts": moe.n_shared_experts,
        "rms_norm_eps": cfg.layer_norm_epsilon,
        "rope_scaling": None,
        "rope_theta": cfg.rotary_base,
        "route_norm": moe.norm_topk_prob,
        "route_scale": moe.routed_scaling_factor,
        "score_func": "sigmoid",
        "sliding_window": windows.pop() if windows else None,
        "tie_word_embeddings": cfg.tied_embedding,
        "topk_group": 1,
        "use_grouped_mm": True,
        "vocab_size": cfg.vocab_size,
    }


# our leaf -> the checkpoint's name under ``model.layers.{i}.`` (matrices
# are transposed on the way, gains and the bias are not)
_AFMOE_NORMS = {
    "ln1": "input_layernorm", "attn_out_ln": "post_attention_layernorm",
    "ln2": "pre_mlp_layernorm", "mlp_out_ln": "post_mlp_layernorm",
}
_AFMOE_ATTN = {
    "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
    "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
    "wg": "self_attn.gate_proj.weight",
    "q_norm": "self_attn.q_norm.weight", "k_norm": "self_attn.k_norm.weight",
}
_AFMOE_MLP = {
    "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
    "w_down": "mlp.down_proj.weight",
    "router": "mlp.router.gate.weight", "b_router": "mlp.expert_bias",
    "shared_gate": "mlp.shared_experts.gate_proj.weight",
    "shared_up": "mlp.shared_experts.up_proj.weight",
    "shared_down": "mlp.shared_experts.down_proj.weight",
}


_AFMOE_NAMES = (_AFMOE_NORMS, _AFMOE_ATTN, _AFMOE_MLP)


register_hf_family(
    HFFamily(
        name="afmoe",
        hf_model_type="afmoe",
        config_from_hf=_afmoe_config_from_hf,
        config_to_hf=_afmoe_config_to_hf,
        # two stacks as ``joyai_llm_flash``'s, under this family's names
        # (no prediction module: ``n_mtp_layers`` is 0)
        params_from_hf=lambda sd, cfg: _joyai_params_from_hf(
            sd, cfg, _AFMOE_NAMES),
        params_to_hf=lambda params, cfg: _joyai_params_to_hf(
            params, cfg, _AFMOE_NAMES),
    )
)


# --------------------------------------------------------------------------- #
# GPT-2
# --------------------------------------------------------------------------- #


def _gpt2_config_from_hf(hf: Dict[str, Any]) -> ModelConfig:
    n_head = hf["n_head"]
    return ModelConfig(
        n_layers=hf["n_layer"],
        n_q_heads=n_head,
        n_kv_heads=n_head,
        head_dim=hf["n_embd"] // n_head,
        hidden_dim=hf["n_embd"],
        intermediate_dim=hf.get("n_inner") or 4 * hf["n_embd"],
        vocab_size=hf["vocab_size"],
        n_positions=hf["n_positions"],
        layer_norm_type="layer",
        layer_norm_epsilon=hf.get("layer_norm_epsilon", 1e-5),
        use_attention_bias=True,
        use_attn_proj_bias=True,
        apply_rotary=False,
        abs_position_embedding=True,
        activation_function="gelu_new",
        mlp_type="fc",
        use_mlp_bias=True,
        tied_embedding=True,
    )


def _gpt2_config_to_hf(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "model_type": "gpt2",
        "architectures": ["GPT2LMHeadModel"],
        "n_layer": cfg.n_layers,
        "n_head": cfg.n_q_heads,
        "n_embd": cfg.hidden_dim,
        "n_inner": cfg.intermediate_dim,
        "vocab_size": cfg.vocab_size,
        "n_positions": cfg.n_positions,
        "layer_norm_epsilon": cfg.layer_norm_epsilon,
        "activation_function": "gelu_new",
    }


def _gpt2_params_from_hf(sd: HFState, cfg: ModelConfig) -> Dict[str, Any]:
    L, E = cfg.n_layers, cfg.hidden_dim
    # strip HF's "transformer." prefix if present
    if any(k.startswith("transformer.") for k in sd):
        sd = {
            k[len("transformer."):]: v
            for k, v in sd.items()
            if k.startswith("transformer.")
        }
    # c_attn is fused qkv with Conv1D layout [in, 3E]
    wq, wk, wv, bq, bk, bv = [], [], [], [], [], []
    for i in range(L):
        w = np.asarray(sd[f"h.{i}.attn.c_attn.weight"])
        b = np.asarray(sd[f"h.{i}.attn.c_attn.bias"])
        wq.append(w[:, :E]); wk.append(w[:, E : 2 * E]); wv.append(w[:, 2 * E :])
        bq.append(b[:E]); bk.append(b[E : 2 * E]); bv.append(b[2 * E :])
    p = "h.{i}."
    return {
        "embed": {"weight": np.asarray(sd["wte.weight"])},
        "pos_embed": {"weight": np.asarray(sd["wpe.weight"])},
        "layers": {
            "ln1": {
                "weight": _stack(sd, p + "ln_1.weight", L),
                "bias": _stack(sd, p + "ln_1.bias", L),
            },
            "attn": {
                "wq": np.stack(wq), "wk": np.stack(wk), "wv": np.stack(wv),
                "bq": np.stack(bq), "bk": np.stack(bk), "bv": np.stack(bv),
                "wo": _stack(sd, p + "attn.c_proj.weight", L),
                "bo": _stack(sd, p + "attn.c_proj.bias", L),
            },
            "ln2": {
                "weight": _stack(sd, p + "ln_2.weight", L),
                "bias": _stack(sd, p + "ln_2.bias", L),
            },
            "mlp": {
                "w_fc": _stack(sd, p + "mlp.c_fc.weight", L),
                "b_fc": _stack(sd, p + "mlp.c_fc.bias", L),
                "w_proj": _stack(sd, p + "mlp.c_proj.weight", L),
                "b_proj": _stack(sd, p + "mlp.c_proj.bias", L),
            },
        },
        "final_ln": {
            "weight": np.asarray(sd["ln_f.weight"]),
            "bias": np.asarray(sd["ln_f.bias"]),
        },
    }


def _gpt2_params_to_hf(params: Dict[str, Any], cfg: ModelConfig) -> HFState:
    sd: HFState = {
        "transformer.wte.weight": np.asarray(params["embed"]["weight"]),
        "transformer.wpe.weight": np.asarray(params["pos_embed"]["weight"]),
        "transformer.ln_f.weight": np.asarray(params["final_ln"]["weight"]),
        "transformer.ln_f.bias": np.asarray(params["final_ln"]["bias"]),
    }
    lp = params["layers"]
    for i in range(cfg.n_layers):
        p = f"transformer.h.{i}."
        a = lp["attn"]
        sd[p + "ln_1.weight"] = np.asarray(lp["ln1"]["weight"][i])
        sd[p + "ln_1.bias"] = np.asarray(lp["ln1"]["bias"][i])
        sd[p + "ln_2.weight"] = np.asarray(lp["ln2"]["weight"][i])
        sd[p + "ln_2.bias"] = np.asarray(lp["ln2"]["bias"][i])
        sd[p + "attn.c_attn.weight"] = np.concatenate(
            [np.asarray(a["wq"][i]), np.asarray(a["wk"][i]), np.asarray(a["wv"][i])],
            axis=1,
        )
        sd[p + "attn.c_attn.bias"] = np.concatenate(
            [np.asarray(a["bq"][i]), np.asarray(a["bk"][i]), np.asarray(a["bv"][i])]
        )
        sd[p + "attn.c_proj.weight"] = np.asarray(a["wo"][i])
        sd[p + "attn.c_proj.bias"] = np.asarray(a["bo"][i])
        m = lp["mlp"]
        sd[p + "mlp.c_fc.weight"] = np.asarray(m["w_fc"][i])
        sd[p + "mlp.c_fc.bias"] = np.asarray(m["b_fc"][i])
        sd[p + "mlp.c_proj.weight"] = np.asarray(m["w_proj"][i])
        sd[p + "mlp.c_proj.bias"] = np.asarray(m["b_proj"][i])
    return sd


register_hf_family(
    HFFamily(
        name="gpt2",
        hf_model_type="gpt2",
        config_from_hf=_gpt2_config_from_hf,
        config_to_hf=_gpt2_config_to_hf,
        params_from_hf=_gpt2_params_from_hf,
        params_to_hf=_gpt2_params_to_hf,
    )
)


# --------------------------------------------------------------------------- #
# zaya (attention inside a convolved latent, CCA; a top-1 expert layer behind
# an MLP router with state and a skip; learned residual scaling; tied head)
# --------------------------------------------------------------------------- #


def _zaya_config_from_hf(hf: Dict[str, Any]) -> ModelConfig:
    """Every key of the published config is read. Every layer is
    ``hybrid``: an attention sublayer inside the convolved latent, then an
    expert sublayer. What the family can say and this program does not do
    is refused, never guessed: a ``layer_types`` entry other than
    ``hybrid`` (``hybrid_sliding``: window layers of this attention kind),
    a non-null ``sliding_window``, biases on the projections or the head,
    another ``rope_type`` than ``default``, fewer entries than layers."""
    L = hf["num_hidden_layers"]
    types = hf.get("layer_types") or ["hybrid"] * L
    if len(types) < L or any(t != "hybrid" for t in types[:L]):
        raise ValueError(
            "zaya: every layer_types entry (one a layer) must be 'hybrid'; "
            "window layers of this attention kind are not supported")
    if hf.get("sliding_window") is not None:
        raise ValueError("zaya: a sliding window is not supported")
    if hf.get("attention_bias", False) or hf.get("lm_head_bias", False):
        raise ValueError("zaya: biases on the projections or the head")
    rope = (hf.get("rope_parameters") or {}).get("hybrid") or {}
    if rope.get("rope_type", "default") != "default":
        raise ValueError("zaya: rope_type other than 'default'")
    factor = float(rope.get(
        "partial_rotary_factor", hf.get("partial_rotary_factor", 1.0)))
    head_dim = hf["head_dim"]
    return ModelConfig(
        n_layers=L,
        n_q_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        head_dim=head_dim,
        hidden_dim=hf["hidden_size"],
        # (shapes nothing: every layer's MLP is the experts')
        intermediate_dim=hf["moe_intermediate_size"],
        vocab_size=hf["vocab_size"],
        n_positions=hf.get("max_position_embeddings", 32768),
        layer_norm_epsilon=hf.get("rms_norm_eps", 1e-5),
        rotary_base=float(rope.get("rope_theta", 10000.0)),
        rotary_dim=int(head_dim * factor),
        activation_function=hf.get("hidden_act", "silu"),
        tied_embedding=bool(hf.get("tie_word_embeddings", True)),
        cca=CCAConfig(
            time0=int(hf.get("cca_time0", 2)), time1=int(hf.get("cca_time1", 2))),
        residual_scaling=True,
        mlp_type="moe",
        moe=MoEConfig(
            num_experts=hf["num_experts"],
            top_k=hf["num_experts_per_tok"],
            norm_topk_prob=False,
            expert_dim=hf["moe_intermediate_size"],
            selection_bias=True,
            router_dim=hf["router_hidden_size"],
            skip_expert=True,
        ),
    )


def _zaya_config_to_hf(cfg: ModelConfig) -> Dict[str, Any]:
    """The published keys, key for key (``hybrid_sliding``'s rotary entry
    is the family's constant: no layer of that type is supported, so
    nothing of the model depends on it)."""
    factor = cfg.rot_dim / cfg.head_dim

    def rope(theta):
        return {"partial_rotary_factor": factor, "rope_theta": theta,
                "rope_type": "default"}

    theta = cfg.rotary_base
    return {
        "model_type": "zaya",
        "attention_bias": False,
        "cca_time0": cfg.cca.time0,
        "cca_time1": cfg.cca.time1,
        "head_dim": cfg.head_dim,
        "hidden_act": cfg.activation_function,
        "hidden_size": cfg.hidden_dim,
        "layer_types": ["hybrid"] * cfg.n_layers,
        "lm_head_bias": False,
        "max_position_embeddings": cfg.n_positions,
        "moe_intermediate_size": cfg.expert_dim,
        "num_attention_heads": cfg.n_q_heads,
        "num_experts": cfg.moe.num_experts,
        "num_experts_per_tok": cfg.moe.top_k,
        "num_hidden_layers": cfg.n_layers,
        "num_key_value_heads": cfg.n_kv_heads,
        "partial_rotary_factor": factor,
        "rms_norm_eps": cfg.layer_norm_epsilon,
        "rope_parameters": {
            "hybrid": rope(int(theta) if float(theta).is_integer() else theta),
            "hybrid_sliding": rope(10000),
            "rope_type": "default",
        },
        "router_hidden_size": cfg.moe.router_dim,
        "sliding_window": None,
        "tie_word_embeddings": cfg.tied_embedding,
        "vocab_size": cfg.vocab_size,
    }


# (ours, theirs, transposed): per-layer leaves that map one to one. The
# names on the right were written from memory of the family's public
# modelling file (``benchmark/configs/zaya1-8b-l16.json``,
# ``assumed.from_memory.weight_names``)
_ZAYA_ATTN = (
    ("wq", "self_attn.q_proj.weight", True),
    ("wk", "self_attn.k_proj.weight", True),
    ("wo", "self_attn.o_proj.weight", True),
    ("conv0_b", "self_attn.conv_qk.0.bias", False),
    ("conv1_b", "self_attn.conv_qk.1.bias", False),
    ("k_temp", "self_attn.temp", False),
)
_ZAYA_ROUTER = (
    ("router_in", "mlp.router.down_proj.weight", True),
    ("b_router_in", "mlp.router.down_proj.bias", False),
    ("router_mix", "mlp.router.eda_scale", False),
    ("router_norm", "mlp.router.norm.weight", False),
    ("router_w1", "mlp.router.mlp.0.weight", True),
    ("b_router1", "mlp.router.mlp.0.bias", False),
    ("router_w2", "mlp.router.mlp.2.weight", True),
    ("b_router2", "mlp.router.mlp.2.bias", False),
    ("router", "mlp.router.mlp.4.weight", True),
    ("b_router", "mlp.router.balancing_biases", False),
)
_ZAYA_RES = (
    ("attn_res", "res_scale_attn"), ("mlp_res", "res_scale_mlp"))
_ZAYA_RES_LEAVES = (
    ("a_r", "residual_scale"), ("b_r", "residual_bias"),
    ("a_h", "hidden_states_scale"), ("b_h", "hidden_states_bias"))
_ZAYA_EXPERT = (
    ("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj"))


def _zaya_params_from_hf(sd: HFState, cfg: ModelConfig) -> Dict[str, Any]:
    """The two value projections (current token, previous token) side by
    side in ``wv``; the depthwise convolution ``[C, 1, taps]`` as ``[taps,
    C]``; the grouped one ``[C, D, taps]`` (a head's outputs by inputs) as
    ``[taps, heads, D in, D out]``."""
    L, D = cfg.n_layers, cfg.head_dim
    H = cfg.n_q_heads + cfg.n_kv_heads
    pre = "model.layers.{i}."

    def stack(name, transpose=False, fn=None):
        m = _stack(sd, pre + name, L, transpose)
        return m if fn is None else np.stack([fn(x) for x in m])

    attn = {o: stack(t, tr) for o, t, tr in _ZAYA_ATTN}
    attn["wv"] = np.concatenate([
        stack("self_attn.val_proj1.weight", True),
        stack("self_attn.val_proj2.weight", True)], axis=-1)
    attn["conv0_w"] = stack(
        "self_attn.conv_qk.0.weight", fn=lambda w: w[:, 0, :].T)
    attn["conv1_w"] = stack(
        "self_attn.conv_qk.1.weight",
        fn=lambda w: w.reshape(H, D, D, -1).transpose(3, 0, 2, 1))
    mlp = {o: stack(t, tr) for o, t, tr in _ZAYA_ROUTER}
    for ours, theirs in _ZAYA_EXPERT:
        mlp[ours] = np.stack([
            _stack(sd, pre + f"mlp.experts.{e}.{theirs}.weight", L, True)
            for e in range(cfg.moe.num_experts)], axis=1)
    layers = {
        "ln1": {"weight": stack("input_layernorm.weight")},
        "ln2": {"weight": stack("post_attention_layernorm.weight")},
        "attn": attn, "mlp": mlp,
    }
    for ours, theirs in _ZAYA_RES:
        layers[ours] = {
            o: stack(f"{theirs}.{t}") for o, t in _ZAYA_RES_LEAVES}
    return {
        "embed": {"weight": np.asarray(sd["model.embed_tokens.weight"])},
        "layers": layers,
        "final_ln": {"weight": np.asarray(sd["model.norm.weight"])},
    }


def _zaya_params_to_hf(params: Dict[str, Any], cfg: ModelConfig) -> HFState:
    L, D = cfg.n_layers, cfg.head_dim
    half = cfg.n_kv_heads // 2 * D
    lay = jax_to_numpy(params["layers"])
    sd: HFState = {
        "model.embed_tokens.weight": np.asarray(params["embed"]["weight"]),
        "model.norm.weight": np.asarray(params["final_ln"]["weight"]),
    }
    for i in range(L):
        pre = f"model.layers.{i}."

        def put(name, m, transpose=False):
            sd[pre + name] = np.ascontiguousarray(m.T if transpose else m)

        a, m = lay["attn"], lay["mlp"]
        put("input_layernorm.weight", lay["ln1"]["weight"][i])
        put("post_attention_layernorm.weight", lay["ln2"]["weight"][i])
        for ours, theirs, tr in _ZAYA_ATTN:
            put(theirs, a[ours][i], tr)
        put("self_attn.val_proj1.weight", a["wv"][i][:, :half], True)
        put("self_attn.val_proj2.weight", a["wv"][i][:, half:], True)
        put("self_attn.conv_qk.0.weight", a["conv0_w"][i].T[:, None, :])
        w1 = a["conv1_w"][i]                        # [taps, H, in, out]
        put("self_attn.conv_qk.1.weight",
            w1.transpose(1, 3, 2, 0).reshape(-1, D, w1.shape[0]))
        for ours, theirs, tr in _ZAYA_ROUTER:
            put(theirs, m[ours][i], tr)
        for ours, theirs in _ZAYA_EXPERT:
            for e in range(cfg.moe.num_experts):
                put(f"mlp.experts.{e}.{theirs}.weight", m[ours][i, e], True)
        for ours, theirs in _ZAYA_RES:
            for o, t in _ZAYA_RES_LEAVES:
                put(f"{theirs}.{t}", lay[ours][o][i])
    return sd


register_hf_family(
    HFFamily(
        name="zaya",
        hf_model_type="zaya",
        config_from_hf=_zaya_config_from_hf,
        config_to_hf=_zaya_config_to_hf,
        params_from_hf=_zaya_params_from_hf,
        params_to_hf=_zaya_params_to_hf,
    )
)


# --------------------------------------------------------------------------- #
# Checkpoint IO (safetensors + config.json)
# --------------------------------------------------------------------------- #


def family_for_model_type(model_type: str) -> HFFamily:
    for fam in HF_FAMILIES.values():
        if fam.hf_model_type == model_type:
            return fam
    raise KeyError(f"No converter registered for HF model_type={model_type!r}")


def load_hf_checkpoint(path: str):
    """Read an HF checkpoint dir -> (ModelConfig, params pytree of numpy)."""
    from safetensors.numpy import load_file

    with open(os.path.join(path, "config.json")) as f:
        hf_cfg = json.load(f)
    fam = family_for_model_type(hf_cfg["model_type"])
    cfg = fam.config_from_hf(hf_cfg)
    sd: HFState = {}
    shards = sorted(
        f for f in os.listdir(path) if f.endswith(".safetensors")
    )
    if not shards:
        raise FileNotFoundError(f"No .safetensors shards under {path}")
    for shard in shards:
        sd.update(load_file(os.path.join(path, shard)))
    # critic/reward checkpoints: the scalar value head rides as
    # ``score.weight [1, E]`` (the HF SequenceClassification convention)
    # plus an ``is_critic`` marker in config.json — family converters only
    # handle the CausalLM surface
    if hf_cfg.get("is_critic"):
        import dataclasses as _dc

        cfg = _dc.replace(cfg, is_critic=True)
    params = fam.params_from_hf(sd, cfg)
    if cfg.is_critic and "score.weight" in sd:
        params["head"] = {"weight": np.asarray(sd["score.weight"]).T}
    return cfg, params


def save_hf_checkpoint(params, cfg: ModelConfig, family: str, path: str):
    """Write params as an HF checkpoint dir (model.safetensors + config.json)."""
    from safetensors.numpy import save_file

    fam = HF_FAMILIES[family]
    os.makedirs(path, exist_ok=True)
    host_params = jax_to_numpy(params)
    sd = fam.params_to_hf(host_params, cfg)
    hf_cfg = fam.config_to_hf(cfg)
    if cfg.is_critic:
        # value head [E, 1] -> score.weight [1, E]; marker for the loader
        sd["score.weight"] = np.asarray(host_params["head"]["weight"]).T
        hf_cfg["is_critic"] = True
    # safetensors writes the *raw buffer*, silently corrupting non-contiguous
    # views (our converters emit transposed views of the stacked params).
    sd = {k: np.ascontiguousarray(v) for k, v in sd.items()}
    save_file(sd, os.path.join(path, "model.safetensors"))
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf_cfg, f, indent=2)


def jax_to_numpy(params):
    import jax

    return jax.tree.map(lambda x: np.asarray(x), params)
