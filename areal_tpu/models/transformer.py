"""The flagship model: a packed-varlen transformer as functional JAX.

TPU-native counterpart of ``ReaLModel`` (``realhf/impl/model/nn/real_llm_api.py:100``)
and its blocks (``real_llm_base.py:111-403``). Key departures from the
reference, all deliberate TPU-first choices:

- **No pipeline stages, no TP modules.** Parameters are one pytree with layer
  params *stacked* on a leading axis; the forward is a single ``lax.scan``
  over layers. Parallelism is declarative: ``param_logical_axes`` returns
  logical sharding axes per leaf, and ``areal_tpu.parallel`` maps them onto a
  device mesh for pjit. This replaces the reference's ``parallelism/`` +
  ``pipe_runner`` (~3k LoC) with metadata.
- **Packed data plane.** The training/inference forward consumes a padded
  packed token axis ``[T]`` with ``segment_ids`` (0 = pad), mirroring the
  reference's cu_seqlens varlen batches with static shapes for XLA.
- **Decode path** keeps a per-layer KV cache ``[L, B, S, Hkv, D]`` carried
  through the same layer scan (continuous-batching generation engine builds
  on this; ≈ ``real_llm_generate.py``).

Params are stored fp32 (optimizer master copy) and cast to ``cfg.dtype``
(default bf16) inside the forward — standard mixed precision; the MXU eats
bf16.
"""

import dataclasses
import functools
import itertools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.models.config import ModelConfig
from areal_tpu.ops import attention as attn_ops
from areal_tpu.ops import cca as cca_ops
from areal_tpu.ops import kda as kda_ops
from areal_tpu.ops import norms
from areal_tpu.ops import ssm as ssm_ops
from areal_tpu.ops.activations import ACT2FN
from areal_tpu.ops.rotary import RotaryConfig, apply_rotary, rotary_cos_sin

Params = Dict[str, Any]


# --------------------------------------------------------------------------- #
# Initialization & sharding metadata
# --------------------------------------------------------------------------- #


def _split(rng, n):
    return list(jax.random.split(rng, n))


def init_params(cfg: ModelConfig, rng: jax.Array, dtype=jnp.float32) -> Params:
    """Random init (normal(0.02), zeros for biases/norm-offsets, ones for
    norm gains — gemma stores gains as deltas so they init to 0 there)."""
    E, D = cfg.hidden_dim, cfg.head_dim
    Hq, Hkv, F, V, L = (
        cfg.n_q_heads,
        cfg.n_kv_heads,
        cfg.intermediate_dim,
        cfg.vocab_size,
        # (leading dense layers are a stack of their own, built last)
        cfg.n_attn_layers - cfg.n_dense_layers,
    )
    std = 0.02
    rngs = iter(_split(rng, 64))

    def w(shape):
        return (jax.random.normal(next(rngs), shape, jnp.float32) * std).astype(dtype)

    ln_gain = jnp.zeros if cfg.layer_norm_type == "gemma" else jnp.ones

    def ln(extra_bias: bool, n: int = L):
        p = {"weight": ln_gain((n, E), dtype)}
        if extra_bias:
            p["bias"] = jnp.zeros((n, E), dtype)
        return p

    has_ln_bias = cfg.layer_norm_type == "layer"
    if cfg.mla is not None:
        return _init_latent(cfg, rng, dtype)
    def attn_params(n):
        attn: Dict[str, Any] = {
            "wq": w((n, E, Hq * D)),
            "wk": w((n, E, Hkv * D)),
            "wv": w((n, E, Hkv * D)),
            "wo": w((n, Hq * D, E)),
        }
        if cfg.use_attention_bias:
            attn["bq"] = jnp.zeros((n, Hq * D), dtype)
            attn["bk"] = jnp.zeros((n, Hkv * D), dtype)
            attn["bv"] = jnp.zeros((n, Hkv * D), dtype)
        if cfg.use_attn_proj_bias:
            attn["bo"] = jnp.zeros((n, E), dtype)
        if cfg.qk_layernorm:
            # per head [L, D] (qwen3) or over the whole projection (olmoe)
            full = cfg.qk_norm_full
            attn["q_norm"] = jnp.ones((n, Hq * D if full else D), dtype)
            attn["k_norm"] = jnp.ones((n, Hkv * D if full else D), dtype)
        if cfg.attn_gate:
            attn["wg"] = w((n, E, Hq * D))
        return attn

    attn = attn_params(L)

    def diff_params(n):
        # a differential pair's norm gain over the value row and the four
        # vectors of its ``lambda`` (:func:`_diff_combine`), normal(0, 0.1)
        # as published
        return {
            "subln": jnp.ones((n, 2 * D), dtype),
            **{name: w((n, D)) * (0.1 / std) for name in _LAMBDAS},
        }
    if cfg.cca is not None:
        # the two convolutions over [q ; k] (``ops/cca.py``): taps first,
        # the newest last; the second is one D x D block a head, input-
        # major. Fan-in scale, not normal(0.02), under which the
        # convolved path would vanish beside the mean it is added to
        c, C = cfg.cca, cfg.cca_latent_dim
        attn["conv0_w"] = w((L, c.time0, C)) * (c.time0 ** -0.5 / std)
        attn["conv0_b"] = jnp.zeros((L, C), dtype)
        attn["conv1_w"] = w((L, c.time1, Hq + Hkv, D, D)) * (
            (c.time1 * D) ** -0.5 / std)
        attn["conv1_b"] = jnp.zeros((L, C), dtype)
        attn["k_temp"] = jnp.ones((L, Hkv), dtype)

    def moe_mlp(n):
        # router over ALL the experts, matrices of those HELD here, in
        # the width they read (``MoEConfig``)
        moe = cfg.moe
        X, F = moe.num_experts, cfg.expert_dim
        Xh, W = moe.held[0], moe.latent_dim or E
        mlp = {"router": w((n, E, X))}
        if moe.gated:
            mlp["w_gate"] = w((n, Xh, W, F))
        mlp["w_up"] = w((n, Xh, W, F))
        mlp["w_down"] = w((n, Xh, F, W))
        if moe.selection_bias:
            mlp["b_router"] = jnp.zeros((n, X), dtype)
        if moe.router_dim is not None:
            # the stateful MLP router (``ops/moe.py:_route_mlp``); its
            # last layer keeps the name ``router``, one output more where
            # the family has a skip
            R, n_out = moe.router_dim, X + int(moe.skip_expert)
            mlp.update(
                router_in=w((n, E, R)), b_router_in=jnp.zeros((n, R), dtype),
                router_mix=jnp.ones((n, R), dtype),
                router_norm=jnp.ones((n, R), dtype),
                router_w1=w((n, R, R)), b_router1=jnp.zeros((n, R), dtype),
                router_w2=w((n, R, R)), b_router2=jnp.zeros((n, R), dtype),
                router=w((n, R, n_out)), b_router=jnp.zeros((n, n_out), dtype),
            )
        if moe.latent_dim is not None:
            mlp["latent_down"] = w((n, E, W))
            mlp["latent_up"] = w((n, W, E))
        Fs = moe.shared_width(F)
        if Fs:
            if moe.gated:
                mlp["shared_gate"] = w((n, E, Fs))
            mlp["shared_up"] = w((n, E, Fs))
            mlp["shared_down"] = w((n, Fs, E))
        return mlp

    if cfg.mlp_type == "gated":
        mlp: Dict[str, Any] = {
            "w_gate": w((L, E, F)),
            "w_up": w((L, E, F)),
            "w_down": w((L, F, E)),
        }
    elif cfg.mlp_type == "fc":
        mlp = {"w_fc": w((L, E, F)), "w_proj": w((L, F, E))}
        if cfg.use_mlp_bias:
            mlp["b_fc"] = jnp.zeros((L, F), dtype)
            mlp["b_proj"] = jnp.zeros((L, E), dtype)
    elif cfg.mlp_type == "moe":
        mlp = None if cfg.one_branch else moe_mlp(L)
    else:
        raise ValueError(cfg.mlp_type)

    params: Params = {
        "embed": {"weight": w((V, E))},
        "layers": {
            "ln1": ln(has_ln_bias),
            "attn": attn,
            "ln2": ln(has_ln_bias),
            "mlp": mlp,
        },
        "final_ln": {
            "weight": (ln_gain((E,), dtype)),
            **({"bias": jnp.zeros((E,), dtype)} if has_ln_bias else {}),
        },
    }
    if cfg.one_branch:
        # blocks of ONE branch: an attention block is its mixer and one
        # norm, the expert blocks are a stack of their own
        del params["layers"]["ln2"], params["layers"]["mlp"]
        n_moe = cfg.n_mixers("moe")
        params["moe_layers"] = {
            "ln1": ln(has_ln_bias, n_moe), "mlp": moe_mlp(n_moe)}
    if cfg.norm_branch_out:
        params["layers"]["attn_out_ln"] = ln(has_ln_bias)
        params["layers"]["mlp_out_ln"] = ln(has_ln_bias)
    if cfg.residual_scaling:
        # how a branch joins the residual (:func:`_add_branch`)
        for name in _RES_SCALE.values():
            params["layers"][name] = {
                "a_r": jnp.ones((L, E), dtype), "b_r": jnp.zeros((L, E), dtype),
                "a_h": jnp.ones((L, E), dtype), "b_h": jnp.zeros((L, E), dtype),
            }
    if cfg.exit_gate:
        # carried, never read by a forward (``ModelConfig.exit_gate``)
        params["exit_gate"] = {
            "weight": w((E, 1)), "bias": jnp.zeros((1,), dtype)}
    def block(n, name, mixer, experts=False):
        # a layer of another mixer: its own norms and the dense MLP (or,
        # ``experts``, the model's expert layer)
        return {
            "ln1": ln(has_ln_bias, n),
            name: mixer,
            "ln2": ln(has_ln_bias, n),
            "mlp": moe_mlp(n) if experts else {
                "w_gate": w((n, E, F)),
                "w_up": w((n, E, F)),
                "w_down": w((n, F, E)),
            },
        }

    if cfg.ssm is not None and cfg.ssm.selective:
        # Mamba-1 (``ops/ssm.py``): ``A_log = log(1..N)`` a channel (the
        # published S4D-real initialisation), kept ``[N, C]``
        s, Ls = cfg.ssm, cfg.n_ssm_layers
        C_, N_ = s.d_inner, s.d_state
        dt0 = jnp.exp(jax.random.uniform(
            next(rngs), (Ls, C_), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        mixer = {
            "w_x": w((Ls, E, C_)),
            "w_z": w((Ls, E, C_)),
            "conv_w": w((Ls, s.d_conv, C_)),
            "w_xproj": w((Ls, C_, s.dt_rank + 2 * N_)),
            "w_dt": w((Ls, s.dt_rank, C_)) * (s.dt_rank ** -0.5 / std),
            "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N_ + 1, dtype=jnp.float32))[:, None],
                (Ls, N_, C_)).astype(dtype),
            "D": jnp.ones((Ls, C_), dtype),
            "w_out": w((Ls, C_, E)),
        }
        if s.conv_bias:
            mixer["conv_b"] = jnp.zeros((Ls, C_), dtype)
        params["ssm_layers"] = block(Ls, "ssm", mixer)
    elif cfg.ssm is not None:
        # the state-space layers, a stack of their own (another SHAPE than
        # an attention layer); ``A``, ``dt`` and ``D`` start in the
        # published initialisation's ranges, not at normal(0.02), which
        # would make every head forget in two tokens
        s, Ls = cfg.ssm, cfg.n_ssm_layers
        u = jax.random.uniform
        dt0 = jnp.exp(u(next(rngs), (Ls, s.n_heads), jnp.float32,
                        jnp.log(1e-3), jnp.log(1e-1)))
        mixer: Dict[str, Any] = {
            # the input projection's three parts (``ops/ssm.py:_split_in``)
            "w_z": w((Ls, E, s.d_inner)),
            "w_xbc": w((Ls, E, s.conv_dim)),
            "w_dt": w((Ls, E, s.n_heads)),
            "conv_w": w((Ls, s.d_conv, s.conv_dim)),
            "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype),
            "A_log": jnp.log(
                u(next(rngs), (Ls, s.n_heads), jnp.float32, 1.0, 16.0)
            ).astype(dtype),
            "D": jnp.ones((Ls, s.n_heads), dtype),
            "gate_norm": jnp.ones((Ls, s.d_inner), dtype),
            "w_out": w((Ls, s.d_inner, E)),
        }
        if s.conv_bias:
            mixer["conv_b"] = jnp.zeros((Ls, s.conv_dim), dtype)
        if s.proj_bias:
            mixer["b_in"] = jnp.zeros((Ls, s.in_dim), dtype)
            mixer["b_out"] = jnp.zeros((Ls, E), dtype)
        params["ssm_layers"] = (
            {"ln1": ln(has_ln_bias, Ls), "ssm": mixer} if cfg.one_branch
            else block(Ls, "ssm", mixer))
    if cfg.kda is not None:
        # the delta-rule layers (``ops/kda.py``), a stack of their own;
        # ``A_log`` and ``dt_bias`` in the published initialisation's
        # ranges, as the state-space layers' above
        d, Lk = cfg.kda, cfg.n_kda_layers
        C_, R_ = d.d_inner, d.head_dim
        dt0 = jnp.exp(jax.random.uniform(
            next(rngs), (Lk, C_), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        params["kda_layers"] = block(Lk, "kda", {
            # q, k and v side by side, and ONE convolution over the three
            "w_qkv": w((Lk, E, d.conv_dim)),
            "conv_w": w((Lk, d.d_conv, d.conv_dim)),
            # the decay a key channel, through a rank (f), and beta a head
            "w_fa": w((Lk, E, R_)),
            "w_fb": w((Lk, R_, C_)),
            "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype),
            "A_log": jnp.log(jax.random.uniform(
                next(rngs), (Lk, d.n_heads), jnp.float32, 1.0, 16.0)
            ).astype(dtype),
            "w_beta": w((Lk, E, d.n_heads)),
            # the output gate, through a rank (g), and the head norm
            "w_ga": w((Lk, E, R_)),
            "w_gb": w((Lk, R_, C_)),
            "o_norm": jnp.ones((Lk, d.head_dim), dtype),
            "wo": w((Lk, C_, E)),
        }, experts=cfg.mlp_type == "moe")
    if cfg.diff_attn:
        attn.update(diff_params(L))
    n_gmu, n_cross = cfg.n_mixers("gmu"), cfg.n_mixers("cross")
    if n_gmu:
        params["gmu_layers"] = block(n_gmu, "gmu", {
            "w_in": w((n_gmu, E, cfg.ssm.d_inner)),
            "w_out": w((n_gmu, cfg.ssm.d_inner, E)),
        })
    if n_cross:
        # queries and the output projection only: keys and values are
        # another layer's
        cross = {"wq": w((n_cross, E, Hq * D)), "wo": w((n_cross, Hq * D, E))}
        if cfg.use_attention_bias:
            cross["bq"] = jnp.zeros((n_cross, Hq * D), dtype)
        if cfg.use_attn_proj_bias:
            cross["bo"] = jnp.zeros((n_cross, E), dtype)
        if cfg.diff_attn:
            cross.update(diff_params(n_cross))
        params["cross_layers"] = block(n_cross, "attn", cross)
    if cfg.abs_position_embedding:
        params["pos_embed"] = {"weight": w((cfg.n_positions, E))}
    if cfg.is_critic:
        params["head"] = {"weight": w((E, 1))}
    elif not cfg.tied_embedding:
        params["head"] = {"weight": w((E, V))}
    if cfg.n_dense_layers:
        # an expert model's leading dense layers: attention as the expert
        # layers', a SwiGLU of ``intermediate_dim`` where those route
        nd = cfg.n_dense_layers
        dense = block(nd, "attn", attn_params(nd))
        if cfg.norm_branch_out:
            dense["attn_out_ln"] = ln(has_ln_bias, nd)
            dense["mlp_out_ln"] = ln(has_ln_bias, nd)
        params = {"embed": params.pop("embed"), "dense_layers": dense, **params}
    return params


def _latent_spec(cfg: ModelConfig) -> Params:
    """The parameter tree of a latent-attention model (``cfg.mla``) as
    ``(shape, logical axes)`` leaves: ONE description that both
    :func:`init_params` and :func:`param_logical_axes` read. Stacks, in
    the order they run: ``dense_layers`` (``cfg.n_dense_layers`` leading
    layers whose MLP is a SwiGLU of ``intermediate_dim``), ``layers`` (the
    rest: experts if the model has them), and ``mtp`` (the multi-token-
    prediction modules: each one more block of the last kind behind
    ``eh_proj`` over [``e_norm`` (embedding) ; ``h_norm`` (hidden)])."""
    E, H, V = cfg.hidden_dim, cfg.n_q_heads, cfg.vocab_size
    m = cfg.mla
    qk, kv_up = cfg.head_dim, m.qk_nope_head_dim + m.v_head_dim

    def stack(n, mlp_type):
        gain = ((n, E), ("layer", "embed"))
        attn = {
            "wq_a": ((n, E, m.q_lora_rank), ("layer", "embed", None)),
            "q_a_norm": ((n, m.q_lora_rank), ("layer", None)),
            "wq_b": ((n, m.q_lora_rank, H * qk), ("layer", None, "heads")),
            "wkv_a": ((n, E, m.latent_dim), ("layer", "embed", None)),
            "kv_a_norm": ((n, m.kv_lora_rank), ("layer", None)),
            "wkv_b": ((n, m.kv_lora_rank, H * kv_up), ("layer", None, "heads")),
            "wo": ((n, H * m.v_head_dim, E), ("layer", "heads", "embed")),
        }
        if mlp_type == "moe":
            X, F = cfg.moe.num_experts, cfg.expert_dim
            mlp = {
                "router": ((n, E, X), ("layer", "embed", None)),
                "w_gate": ((n, X, E, F), ("layer", "expert", "embed", None)),
                "w_up": ((n, X, E, F), ("layer", "expert", "embed", None)),
                "w_down": ((n, X, F, E), ("layer", "expert", None, "embed")),
            }
            if cfg.moe.selection_bias:
                mlp["b_router"] = ((n, X), ("layer", None))
            Fs = cfg.moe.n_shared_experts * F
            if Fs:
                mlp["shared_gate"] = ((n, E, Fs), ("layer", "embed", "mlp"))
                mlp["shared_up"] = ((n, E, Fs), ("layer", "embed", "mlp"))
                mlp["shared_down"] = ((n, Fs, E), ("layer", "mlp", "embed"))
        else:
            F = cfg.intermediate_dim
            mlp = {
                "w_gate": ((n, E, F), ("layer", "embed", "mlp")),
                "w_up": ((n, E, F), ("layer", "embed", "mlp")),
                "w_down": ((n, F, E), ("layer", "mlp", "embed")),
            }
        return {"ln1": {"weight": gain}, "attn": attn,
                "ln2": {"weight": gain}, "mlp": mlp}

    spec: Params = {"embed": {"weight": ((V, E), ("vocab", "embed"))}}
    if cfg.n_dense_layers:
        spec["dense_layers"] = stack(cfg.n_dense_layers, "gated")
    spec["layers"] = stack(cfg.n_layers - cfg.n_dense_layers, cfg.mlp_type)
    spec["final_ln"] = {"weight": ((E,), ("embed",))}
    if cfg.n_mtp_layers:
        n = cfg.n_mtp_layers
        spec["mtp"] = {
            "e_norm": {"weight": ((n, E), ("layer", "embed"))},
            "h_norm": {"weight": ((n, E), ("layer", "embed"))},
            "eh_proj": ((n, 2 * E, E), ("layer", None, "embed")),
            "block": stack(n, cfg.mlp_type),
        }
    if cfg.is_critic:
        spec["head"] = {"weight": ((E, 1), ("embed", None))}
    elif not cfg.tied_embedding:
        spec["head"] = {"weight": ((E, V), ("embed", "vocab"))}
    return spec


def _is_spec_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def _init_latent(cfg: ModelConfig, rng: jax.Array, dtype) -> Params:
    """:func:`init_params` of a latent-attention model: matrices
    normal(0.02), gains one, the router's correction bias zero."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        _latent_spec(cfg), is_leaf=_is_spec_leaf
    )
    out = []
    for key, (path, (shape, _)) in zip(_split(rng, len(leaves)), leaves):
        names = [k.key for k in path]
        if any(n.startswith("ln") or n.endswith("_ln") or n.endswith("_norm")
               for n in names):
            out.append(jnp.ones(shape, dtype))
        elif names[-1].startswith("b"):
            out.append(jnp.zeros(shape, dtype))
        else:
            out.append(
                (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)
            )
    return jax.tree_util.tree_unflatten(treedef, out)


def param_logical_axes(cfg: ModelConfig) -> Params:
    """Logical sharding axes per parameter leaf (same tree structure as
    ``init_params``). ``None`` entries are replicated. ``areal_tpu.parallel``
    maps logical names → mesh axes (e.g. ``embed→fsdp``, ``heads/mlp/vocab→model``)."""
    if cfg.mla is not None:
        return jax.tree.map(
            lambda leaf: leaf[1], _latent_spec(cfg), is_leaf=_is_spec_leaf
        )
    has_ln_bias = cfg.layer_norm_type == "layer"

    def ln():
        p = {"weight": ("layer", "embed")}
        if has_ln_bias:
            p["bias"] = ("layer", "embed")
        return p

    attn: Dict[str, Any] = {
        "wq": ("layer", "embed", "heads"),
        "wk": ("layer", "embed", "heads"),
        "wv": ("layer", "embed", "heads"),
        "wo": ("layer", "heads", "embed"),
    }
    if cfg.use_attention_bias:
        attn["bq"] = ("layer", "heads")
        attn["bk"] = ("layer", "heads")
        attn["bv"] = ("layer", "heads")
    if cfg.use_attn_proj_bias:
        attn["bo"] = ("layer", "embed")
    if cfg.qk_layernorm:
        # a full-width gain is laid out like the projection it scales
        width = "heads" if cfg.qk_norm_full else None
        attn["q_norm"] = ("layer", width)
        attn["k_norm"] = ("layer", width)
    if cfg.attn_gate:
        attn["wg"] = ("layer", "embed", "heads")
    if cfg.cca is not None:
        # no tensor-parallel split of the convolved latent (the engine
        # refuses a mesh for this family); the trainer replicates these
        attn["conv0_w"] = ("layer", None, None)
        attn["conv0_b"] = ("layer", None)
        attn["conv1_w"] = ("layer", None, None, None, None)
        attn["conv1_b"] = ("layer", None)
        attn["k_temp"] = ("layer", None)

    if cfg.mlp_type == "gated":
        mlp: Dict[str, Any] = {
            "w_gate": ("layer", "embed", "mlp"),
            "w_up": ("layer", "embed", "mlp"),
            "w_down": ("layer", "mlp", "embed"),
        }
    elif cfg.mlp_type == "fc":
        mlp = {"w_fc": ("layer", "embed", "mlp"), "w_proj": ("layer", "mlp", "embed")}
        if cfg.use_mlp_bias:
            mlp["b_fc"] = ("layer", "mlp")
            mlp["b_proj"] = ("layer", "embed")
    else:  # moe
        # Expert parallelism: the expert dim takes the `model` mesh axis, so
        # the per-expert F dim must stay unsharded (one mesh axis can map to
        # at most one dim of a param). How each dispatch behaves under a
        # sharded expert dim is in ``ops/moe.py``.
        moe = cfg.moe
        mlp = {
            "router": ("layer", "embed", None),
            "w_up": ("layer", "expert", "embed", None),
            "w_down": ("layer", "expert", None, "embed"),
        }
        if moe.gated:
            mlp["w_gate"] = ("layer", "expert", "embed", None)
        if moe.selection_bias:
            mlp["b_router"] = ("layer", None)
        if moe.router_dim is not None:
            mlp.update(
                router_in=("layer", "embed", None), router=("layer", None, None),
                router_w1=("layer", None, None), router_w2=("layer", None, None),
                **{k: ("layer", None) for k in (
                    "b_router_in", "router_mix", "router_norm", "b_router1",
                    "b_router2", "b_router")},
            )
        if moe.latent_dim is not None:
            # the latent is whole on every shard: the experts' "embed"
            # axis is the latent's, which the projections do not split
            mlp["latent_down"] = ("layer", "embed", None)
            mlp["latent_up"] = ("layer", None, "embed")
            mlp["w_up"] = ("layer", "expert", None, None)
            mlp["w_down"] = ("layer", "expert", None, None)
        if moe.n_shared_experts:
            if moe.gated:
                mlp["shared_gate"] = ("layer", "embed", "mlp")
            mlp["shared_up"] = ("layer", "embed", "mlp")
            mlp["shared_down"] = ("layer", "mlp", "embed")

    axes: Params = {
        "embed": {"weight": ("vocab", "embed")},
        "layers": {"ln1": ln(), "attn": attn, "ln2": ln(), "mlp": mlp},
        "final_ln": {
            "weight": ("embed",),
            **({"bias": ("embed",)} if has_ln_bias else {}),
        },
    }
    if cfg.one_branch:
        del axes["layers"]["ln2"], axes["layers"]["mlp"]
        axes["moe_layers"] = {"ln1": ln(), "mlp": mlp}
    if cfg.norm_branch_out:
        axes["layers"]["attn_out_ln"] = ln()
        axes["layers"]["mlp_out_ln"] = ln()
    if cfg.residual_scaling:
        for name in _RES_SCALE.values():
            axes["layers"][name] = {
                k: ("layer", "embed") for k in ("a_r", "b_r", "a_h", "b_h")}
    if cfg.exit_gate:
        axes["exit_gate"] = {"weight": ("embed", None), "bias": (None,)}
    dense_mlp = {
        "w_gate": ("layer", "embed", "mlp"),
        "w_up": ("layer", "embed", "mlp"),
        "w_down": ("layer", "mlp", "embed"),
    }
    if cfg.diff_attn:
        diff_axes = {k: ("layer", None) for k in ("subln", *_LAMBDAS)}
        attn.update(diff_axes)
    if cfg.ssm is not None and cfg.ssm.selective:
        # (no tensor-parallel split: as below)
        mixer = {
            "w_x": ("layer", "embed", None), "w_z": ("layer", "embed", None),
            "conv_w": ("layer", None, None),
            "w_xproj": ("layer", None, None), "w_dt": ("layer", None, None),
            "dt_bias": ("layer", None), "A_log": ("layer", None, None),
            "D": ("layer", None), "w_out": ("layer", None, "embed"),
        }
        if cfg.ssm.conv_bias:
            mixer["conv_b"] = ("layer", None)
        axes["ssm_layers"] = {
            "ln1": ln(), "ssm": mixer, "ln2": ln(), "mlp": dense_mlp}
    elif cfg.ssm is not None:
        # no tensor-parallel split of the mixer (its heads, the
        # convolution's channels and the state would all have to follow
        # one; the engine refuses a mesh for this family)
        mixer = {
            "w_z": ("layer", "embed", None),
            "w_xbc": ("layer", "embed", None),
            "w_dt": ("layer", "embed", None),
            "conv_w": ("layer", None, None),
            "dt_bias": ("layer", None),
            "A_log": ("layer", None),
            "D": ("layer", None),
            "gate_norm": ("layer", None),
            "w_out": ("layer", None, "embed"),
        }
        if cfg.ssm.conv_bias:
            mixer["conv_b"] = ("layer", None)
        if cfg.ssm.proj_bias:
            mixer["b_in"] = ("layer", None)
            mixer["b_out"] = ("layer", "embed")
        axes["ssm_layers"] = (
            {"ln1": ln(), "ssm": mixer} if cfg.one_branch
            else {"ln1": ln(), "ssm": mixer, "ln2": ln(), "mlp": dense_mlp})
    if cfg.kda is not None:
        # no tensor-parallel split of the mixer either (its heads, the
        # convolution's channels and the state would all have to follow
        # one; the engine refuses a mesh for this family)
        mixer = {
            "w_qkv": ("layer", "embed", None), "conv_w": ("layer", None, None),
            "w_fa": ("layer", "embed", None), "w_fb": ("layer", None, None),
            "dt_bias": ("layer", None), "A_log": ("layer", None),
            "w_beta": ("layer", "embed", None),
            "w_ga": ("layer", "embed", None), "w_gb": ("layer", None, None),
            "o_norm": ("layer", None), "wo": ("layer", None, "embed"),
        }
        axes["kda_layers"] = {
            "ln1": ln(), "kda": mixer, "ln2": ln(),
            "mlp": mlp if cfg.mlp_type == "moe" else dense_mlp}
    if cfg.n_mixers("gmu"):
        axes["gmu_layers"] = {
            "ln1": ln(), "ln2": ln(), "mlp": dense_mlp,
            "gmu": {"w_in": ("layer", "embed", None),
                    "w_out": ("layer", None, "embed")},
        }
    if cfg.n_mixers("cross"):
        cross = {"wq": ("layer", "embed", "heads"),
                 "wo": ("layer", "heads", "embed")}
        if cfg.use_attention_bias:
            cross["bq"] = ("layer", "heads")
        if cfg.use_attn_proj_bias:
            cross["bo"] = ("layer", "embed")
        if cfg.diff_attn:
            cross.update(diff_axes)
        axes["cross_layers"] = {
            "ln1": ln(), "attn": cross, "ln2": ln(), "mlp": dense_mlp}
    if cfg.abs_position_embedding:
        axes["pos_embed"] = {"weight": (None, "embed")}
    if cfg.is_critic:
        axes["head"] = {"weight": ("embed", None)}
    elif not cfg.tied_embedding:
        axes["head"] = {"weight": ("embed", "vocab")}
    if cfg.n_dense_layers:
        axes["dense_layers"] = {
            **{k: v for k, v in axes["layers"].items() if k != "mlp"},
            "mlp": dense_mlp}
    return axes


# --------------------------------------------------------------------------- #
# Layer forward pieces (shared by packed / batched / decode paths)
# --------------------------------------------------------------------------- #


def _norm(cfg: ModelConfig, p, x):
    if cfg.layer_norm_type == "layer":
        return norms.layer_norm(x, p["weight"], p.get("bias"), cfg.layer_norm_epsilon)
    return norms.rms_norm(
        x, p["weight"], cfg.layer_norm_epsilon, plus_one=cfg.layer_norm_type == "gemma"
    )


def _cast(cfg: ModelConfig, p):
    """The weights in the serving dtype (an integer leaf, a layer's place
    in the model that :func:`_scan_plan` hands it, stays as it is)."""
    dt = jnp.dtype(cfg.dtype)
    return jax.tree.map(
        lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        p)


def _qkv(cfg: ModelConfig, p, x):
    """x: [..., E] -> q [..., Hq, D], k/v [..., Hkv, D] (rope NOT yet applied).

    The q/k norm is one of two kinds (``cfg.qk_norm_over``): over each
    head's ``D`` after the split into heads (qwen3), or over the WHOLE
    projected vector before the split (olmoe). Under tensor parallelism
    the projection's last axis is sharded over heads, and the full-width
    norm's mean of squares is a reduction over that axis: GSPMD completes
    it with one all-reduce of a scalar a token (tested on the CPU mesh)."""
    D = cfg.head_dim
    eps = cfg.layer_norm_epsilon
    full = cfg.qk_norm_full

    def proj(w, b, h, full_gain=None):
        y = x @ w
        if b is not None:
            y = y + b
        if full_gain is not None:
            y = norms.rms_norm(y, full_gain, eps)
        return y.reshape(*x.shape[:-1], h, D)

    q = proj(p["wq"], p.get("bq"), cfg.n_q_heads, p["q_norm"] if full else None)
    k = proj(p["wk"], p.get("bk"), cfg.n_kv_heads, p["k_norm"] if full else None)
    v = proj(p["wv"], p.get("bv"), cfg.n_kv_heads)
    if cfg.qk_layernorm and not full:
        q = norms.rms_norm(q, p["q_norm"], eps)
        k = norms.rms_norm(k, p["k_norm"], eps)
    return q, k, v


def _rotary_cfg(cfg: ModelConfig) -> RotaryConfig:
    return RotaryConfig(
        dim=cfg.rot_dim,
        base=cfg.rotary_base,
        scaling_type=cfg.rotary_scaling_type,
        scaling_factor=cfg.rotary_scaling_factor,
        low_freq_factor=cfg.rotary_low_freq_factor,
        high_freq_factor=cfg.rotary_high_freq_factor,
        original_max_position=cfg.rotary_original_max_position,
        max_position=cfg.n_positions,
    )


def _cos_sin(cfg: ModelConfig, positions):
    """The rotary tables at ``positions``, or ``(None, None)`` for a model
    none of whose layer kinds is rotary."""
    if any(rotary for _, rotary in cfg.layer_kinds):
        return rotary_cos_sin(_rotary_cfg(cfg), positions, jnp.float32)
    return None, None


def _qkv_roped(cfg: ModelConfig, p, x, cos, sin, rotary=None):
    """:func:`_qkv` with the positions applied: what attention takes.
    ``rotary``: the layer kind's own (``cfg.layer_kinds``), where the
    layers of the stack differ; else the model's ``apply_rotary``."""
    if cfg.mla is not None:
        return _mla_expanded(cfg, p, x, cos, sin)
    q, k, v = _qkv(cfg, p, x)
    if cfg.apply_rotary if rotary is None else rotary:
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
    return q, k, v


def _cca_qkv_roped(cfg: ModelConfig, p, h, cos, sin, positions,
                   carry=None, n_valid=None):
    """:func:`_qkv_roped` of a model whose attention runs inside a
    convolved latent (``cfg.cca``; ``ops/cca.py``), over ``h [B, T, E]``:
    ``(q, k, v, carry)``. The rows continue ``carry [B, W]`` (None:
    nothing) and hand back theirs after their first ``n_valid [B]``
    tokens. The rotary embedding comes LAST, after the convolutions, the
    mean and the norm: what a cache holds is ``k`` after all of it."""
    q, k, v, carry = cca_ops.qkv(cfg, p, h, positions, carry, n_valid)
    return apply_rotary(q, cos, sin), apply_rotary(k, cos, sin), v, carry


def _cca_qkv_step(cfg: ModelConfig, p, h, cos, sin, positions, carry, active):
    """:func:`_cca_qkv_roped` over ONE token a row (``h [B, E]``, a decode
    step): rows where ``active`` is false keep their carry."""
    q, k, v, carry = _cca_qkv_roped(
        cfg, p, h[:, None], cos[:, None], sin[:, None], positions[:, None],
        carry, active.astype(jnp.int32))
    return q[:, 0], k[:, 0], v[:, 0], carry


def _router_state0(cfg: ModelConfig, x):
    """What a stateful router (``cfg.moe.router_dim``) reads as the state
    before the first layer: zeros ``[..., router_dim]``, fp32, one a row
    of ``x``. It rides every forward's layer scan beside ``x``. None for
    every other model."""
    if cfg.moe is None or cfg.moe.router_dim is None:
        return None
    return jnp.zeros((*x.shape[:-1], cfg.moe.router_dim), jnp.float32)


# --------------------------------------------------------------------------- #
# Latent attention (``cfg.mla``; the ``deepseek_v3`` equations)
# --------------------------------------------------------------------------- #


def latent_pool_width(cfg: ModelConfig) -> int:
    """Width of a token's row in the latent page pool: ``kv_lora_rank +
    qk_rope_head_dim`` values (576 as published), padded with zeros to a
    whole number of 128-lane tiles (640). The chip stores a minor dimension
    in whole tiles whatever the array says (Mosaic: "Slice shape along
    dimension 5 must be aligned to tiling (128), but is 576", against a
    memref already laid out 640 wide), so the padding costs the bytes
    either way; declaring it keeps the page one DMA and the reported pool
    size true."""
    return -(-cfg.mla.latent_dim // 128) * 128


def _rope_pairs(x, cos, sin):
    """Rotary on ``x [..., heads, rope_dim]``. The published pairs are
    ``(2i, 2i+1)`` (``rope_interleave``): the vector is first put in
    evens-then-odds order and then rotated half-split, which is that
    rotation followed by one fixed permutation. Queries and keys get the
    same permutation, so every score is the published one."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rotary(x, cos, sin)


def _mla_project(cfg: ModelConfig, p, x, cos, sin):
    """x ``[..., E]`` -> ``q_nope [..., H, nope]``, ``q_rope [..., H,
    rope]`` (rotated), ``c_kv [..., rank]`` (normed) and ``k_rope [...,
    rope]`` (rotated; ONE for all heads)."""
    m, eps = cfg.mla, cfg.layer_norm_epsilon
    with jax.named_scope("mla_q_latent"):
        c_q = norms.rms_norm(x @ p["wq_a"], p["q_a_norm"], eps)
        q = (c_q @ p["wq_b"]).reshape(*x.shape[:-1], cfg.n_q_heads, cfg.head_dim)
    with jax.named_scope("mla_kv_latent"):
        kv_a = x @ p["wkv_a"]
        c_kv = norms.rms_norm(kv_a[..., : m.kv_lora_rank], p["kv_a_norm"], eps)
        k_rope = kv_a[..., None, m.kv_lora_rank :]
    q_rope = _rope_pairs(q[..., m.qk_nope_head_dim :], cos, sin)
    k_rope = _rope_pairs(k_rope, cos, sin)[..., 0, :]
    return q[..., : m.qk_nope_head_dim], q_rope, c_kv, k_rope


def _mla_expanded(cfg: ModelConfig, p, x, cos, sin):
    """The EXPANDED form: per-head keys and values up-projected from the
    latent, for the paths that attend over the tokens at hand (trainer,
    dense-cache prefill and decode). ``q, k [..., H, nope + rope]``;
    ``v`` is padded with zeros from ``v_head_dim`` to the key's width,
    because the attention kernels take one head width (:func:`_attn_out`
    drops the padding's share again)."""
    m = cfg.mla
    q_nope, q_rope, c_kv, k_rope = _mla_project(cfg, p, x, cos, sin)
    H = cfg.n_q_heads
    with jax.named_scope("mla_kv_up"):
        kv = (c_kv @ p["wkv_b"]).reshape(
            *x.shape[:-1], H, m.qk_nope_head_dim + m.v_head_dim
        )
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [
            kv[..., : m.qk_nope_head_dim],
            jnp.broadcast_to(
                k_rope[..., None, :], (*x.shape[:-1], H, m.qk_rope_head_dim)
            ),
        ],
        axis=-1,
    )
    v = kv[..., m.qk_nope_head_dim :]
    pad = cfg.head_dim - m.v_head_dim
    if pad:
        v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, pad)])
    return q, k, v


def _mla_absorbed(cfg: ModelConfig, p, x, cos, sin):
    """The ABSORBED form, for attention over the latent page pool:
    ``q [..., H, W]`` and the token's own ``latent [..., W]`` with ``W =``
    :func:`latent_pool_width`. The key up-projection ``W_uk`` is folded
    into the query (``q_lat = q_nope W_uk^T``), so a head's score against
    any cached token is ``q . latent`` with ``q = [q_lat ; q_rope ; 0]``
    and ``latent = [c_kv ; k_rope ; 0]``: multi-query attention over ONE
    stream that is key and, in its first ``kv_lora_rank`` values, value."""
    m = cfg.mla
    q_nope, q_rope, c_kv, k_rope = _mla_project(cfg, p, x, cos, sin)
    w_uk = p["wkv_b"].reshape(m.kv_lora_rank, cfg.n_q_heads, -1)[
        ..., : m.qk_nope_head_dim
    ]
    with jax.named_scope("mla_absorb_q"):
        q_lat = jnp.einsum("...hd,rhd->...hr", q_nope, w_uk)
    pad = latent_pool_width(cfg) - m.latent_dim
    q = jnp.concatenate(
        [q_lat, q_rope, jnp.zeros((*q_rope.shape[:-1], pad), q_rope.dtype)],
        axis=-1,
    )
    latent = jnp.concatenate(
        [c_kv, k_rope, jnp.zeros((*k_rope.shape[:-1], pad), k_rope.dtype)],
        axis=-1,
    )
    return q, latent


def _mla_absorbed_out(cfg: ModelConfig, p, ctx):
    """``ctx [..., H, rank]`` (probabilities over the latents) -> the
    heads' values ``[..., H, v_head_dim]`` through ``W_uv``."""
    m = cfg.mla
    w_uv = p["wkv_b"].reshape(m.kv_lora_rank, cfg.n_q_heads, -1)[
        ..., m.qk_nope_head_dim :
    ]
    with jax.named_scope("mla_absorb_out"):
        return jnp.einsum("...hr,rhd->...hd", ctx, w_uv)


def _attn_scope(window: Optional[int]) -> str:
    """``jax.named_scope`` of a layer kind's attention, in a model whose
    layers come in kinds (``cfg.layer_pattern``)."""
    return "attn_full" if window is None else "attn_window"


def _attn_scale(cfg: ModelConfig) -> float:
    return cfg.softmax_scale or cfg.head_dim ** -0.5


def _mlp(cfg: ModelConfig, p, x, layer_in=None, routed=None,
         router_state=None):
    """Returns (out, aux_loss, routing, router_state) — aux is the MoE
    load-balancing/z loss (``jnp`` scalar, 0 for dense MLPs); routing the
    experts each token chose, ``[..., top_k]`` int32 (``None`` for dense
    MLPs); ``router_state`` in and out: a stateful router's vector of the
    previous and of this layer (:func:`_router_state0`; None for every
    other model). ``layer_in``:
    the layer's normed INPUT (what its attention read), which every
    forward hands over: a router that reads it instead of ``x``
    (``MoEConfig.router_on_layer_input``) gets it from here. ``routed``:
    ``(stacks, index)`` where the routed matrices did not come with ``p``
    (:func:`_hold_routed`); a layer without a router ignores it."""
    act = ACT2FN[cfg.activation_function]
    # a leading dense layer of an expert model is told by its tree: it has
    # no router
    if cfg.mlp_type == "gated" or (
        cfg.mlp_type == "moe" and "router" not in p
    ):
        out = (act(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
        return out, jnp.float32(0.0), None, None
    if cfg.mlp_type == "fc":
        h = x @ p["w_fc"]
        if "b_fc" in p:
            h = h + p["b_fc"]
        h = act(h)
        h = h @ p["w_proj"]
        if "b_proj" in p:
            h = h + p["b_proj"]
        return h, jnp.float32(0.0), None, None
    # moe
    from areal_tpu.ops.moe import moe_mlp

    res = moe_mlp(
        cfg, p, x,
        router_input=layer_in if cfg.moe.router_on_layer_input else None,
        routed=routed, router_state=router_state,
    )
    return res if len(res) == 4 else (*res, None)


def _attn_params(lp):
    """A layer's attention weights, with the layer's place in the model
    where :func:`_scan_plan` handed it (differential attention)."""
    p = lp["attn"]
    return {**p, "index": lp["index"]} if "index" in lp else p


def _attn_out(p, ctx, h=None):
    """ctx: [..., H, D] -> [..., E]. Where a value head is narrower than a
    key head (latent attention in its expanded form pads ``v`` with zeros
    up to the key's width for the one-width kernels), the padding's share
    of ``ctx`` is dropped here. ``h``: the normed input the layer's q/k/v
    projections read; a gated layer (``p["wg"]``, ``cfg.attn_gate``)
    projects its gate from it, ``W_o (ctx * sigmoid(W_g h))``, the sigmoid
    in float32."""
    dv = p["wo"].shape[-2] // ctx.shape[-2]
    if dv != ctx.shape[-1]:
        ctx = ctx[..., :dv]
    ctx = ctx.reshape(*ctx.shape[:-2], -1)
    if "wg" in p:
        with jax.named_scope("attn_gate"):
            gate = jax.nn.sigmoid((h @ p["wg"]).astype(jnp.float32))
            ctx = ctx * gate.astype(ctx.dtype)
    y = ctx @ p["wo"]
    if "bo" in p:
        y = y + p["bo"]
    return y


# the learned residual scaling of each branch (``cfg.residual_scaling``), by
# the name :func:`_add_branch` is handed
_RES_SCALE = {"attn_out_ln": "attn_res", "mlp_out_ln": "mlp_res"}


def _add_branch(cfg: ModelConfig, lp, name: str, x, branch):
    """``x + branch``: a layer's attention or MLP output onto the residual,
    through the branch's own norm ``lp[name]`` first where the model has
    one (``cfg.norm_branch_out``). With ``cfg.residual_scaling`` the sum
    is ``a_r * (x + b_r) + a_h * (branch + b_h)``, four learned vectors a
    branch: the published form defers it to the next sublayer's entry
    (and the final norm's), which is the same function."""
    if cfg.norm_branch_out:
        branch = _norm(cfg, lp[name], branch)
    if cfg.residual_multiplier != 1.0:
        branch = branch * jnp.asarray(cfg.residual_multiplier, branch.dtype)
    if cfg.residual_scaling:
        s = lp[_RES_SCALE[name]]
        return s["a_r"] * (x + s["b_r"]) + s["a_h"] * (branch + s["b_h"])
    return x + branch


def _ffn(cfg: ModelConfig, lp, x, layer_in=None, routed=None,
         router_state=None):
    """The block's feed-forward part onto the residual, behind its mixer:
    ``x + mlp(norm(x))``. Returns ``(x, aux, routing, router_state)``
    (:func:`_mlp`'s). A block of ONE branch (``cfg.one_branch``) has none
    behind its mixer: ``x`` as it came."""
    if cfg.one_branch:
        return x, jnp.float32(0.0), None, None
    m, aux, routing, r = _mlp(
        cfg, lp["mlp"], _norm(cfg, lp["ln2"], x), layer_in, routed,
        router_state=router_state)
    return _add_branch(cfg, lp, "mlp_out_ln", x, m), aux, routing, r


def _moe_block(cfg: ModelConfig, lp, x, routed=None):
    """A block that is an expert layer ALONE (a "moe" position of a plan
    of one-branch blocks): ``x + experts(norm(x))``. ``routed``: the held
    stacks, where they did not come with ``lp`` (:func:`_hold_routed`);
    the block's index in them is ``lp["index"]``
    (:func:`_scan_plan`). Returns ``(x, aux, routing)``."""
    index = lp["index"]
    lp = _cast(cfg, {k: v for k, v in lp.items() if k != "index"})
    m, aux, routing, _ = _mlp(
        cfg, lp["mlp"], _norm(cfg, lp["ln1"], x),
        routed=None if routed is None else (routed, index))
    return _add_branch(cfg, lp, "mlp_out_ln", x, m), aux, routing


def _layer_stacks(params: Params):
    """The model's runs of identical layers, in the order they run: the
    leading dense layers of an expert model, if it has them, then
    ``layers``."""
    if "dense_layers" in params:
        return [params["dense_layers"], params["layers"]]
    return [params["layers"]]


_ROUTED = ("w_gate", "w_up", "w_down")


def _hold_routed(params: Params) -> Tuple[Params, Params]:
    """``(params, stacks)``: the tree with the routed experts' matrices
    ``[L, X, ...]`` taken OUT of the expert stack, and those matrices. The
    layer scans then cut a layer's slice of everything else, and the
    stacks reach the layer whole, beside its index in them
    (:func:`_routed_at`), as ``cache.pages`` and ``li`` do: the
    grouped-matmul kernel indexes them itself. (A slice of them handed to
    a custom call is a COPY of ``X x E x F`` for each, every layer-step;
    into an einsum XLA fuses it, which is why only a forward that runs the
    kernel asks for this.) Under a plan of one-branch blocks the expert
    stack is ``moe_layers`` and a block's index in it comes with its slice
    (:func:`_scan_plan`); experts of two matrices have no ``w_gate``. A
    model whose delta-rule layers hold experts too (``kda_layers``, a
    second expert stack beside ``layers``') has THEIR stacks under
    ``stacks["kda"]``, and such a layer's index in them is its index among
    the delta-rule layers."""
    def take(tree):
        layers = params[tree]
        mlp = layers["mlp"]
        rest = {k: v for k, v in mlp.items() if k not in _ROUTED}
        return ({**layers, "mlp": rest},
                {k: mlp[k] for k in _ROUTED if k in mlp})

    tree = "moe_layers" if "moe_layers" in params else "layers"
    held, stacks = take(tree)
    out = {**params, tree: held}
    if "router" in params.get("kda_layers", {}).get("mlp", {}):
        out["kda_layers"], stacks["kda"] = take("kda_layers")
    return out, stacks


def _routed_at(cfg: ModelConfig, routed: Optional[Params], li, j: int):
    """:func:`_mlp`'s ``routed`` for the layer at position ``j`` of the
    period, from the running ``li`` of the engine's forwards (the layer's
    slice of the pool's leading axis: its cache layer, or its period):
    the held stacks and the layer's index in the expert stack. (None
    for a block of one branch: an attention block holds no experts.)"""
    if routed is None or cfg.one_branch:
        return None
    layer = (li * len(cfg.layer_kinds) + j) % cfg.n_layers
    return routed, layer - cfg.n_dense_layers


def _scan_periods(layers, carry, stack, xs=(), unroll=1, at=0, xs_at=0):
    """:func:`_scan_layers` of a stack whose layers come in a PERIOD of
    kinds (``cfg.layer_pattern``): ``layers[j]`` is the layer function of
    position ``j``, with what is static about its kind (window, rotary,
    which page table) closed over. ONE scan runs over the periods, its
    body the ``p`` positions in order; results come back on the layer
    axis.

    Each layer's weights are cut out of the stack ``[L, ...]`` by its own
    dynamic index ``period * p + j``, one layer at a time, as a plain scan
    over the stack does: XLA fuses such a slice into the matmul that reads
    it. (Handing the scan the stack viewed as ``[L / p, p, ...]`` makes
    the compiler materialise a whole period's slice, whose positions have
    several readers: 3 x 0.96 GB of copies a period at the 21B's widths,
    and 1.9 GB over the chip's memory in the decode chunk.)

    ``at``, ``xs_at`` (:func:`_scan_across`): the whole periods start at
    entry ``at`` of the stack and entry ``xs_at`` of ``xs``."""
    p = len(layers)
    n_periods = (jax.tree.leaves(stack)[0].shape[0] - at) // p

    def cut(tree, period, j):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(
                a, period * p + j, 0, keepdims=False),
            tree)

    def body(carry, period):
        ys = []
        for j, layer in enumerate(layers):
            lp = cut(stack, period, j + at)
            carry, y = layer(
                carry, (lp, *cut(tuple(xs), period, j + xs_at)) if xs else lp)
            ys.append(y)
        return carry, jax.tree.map(lambda *a: jnp.stack(a), *ys)

    carry, ys = jax.lax.scan(
        body, carry, jnp.arange(n_periods, dtype=jnp.int32), unroll=unroll
    )
    return carry, jax.tree.map(
        lambda a: a.reshape(a.shape[0] * p, *a.shape[2:]), ys
    )


def _scan_across(layers, carry, params: Params, xs=(), unroll=1):
    """:func:`_scan_periods` of a model whose period of layer kinds is
    counted over the MODEL's layers and whose layers are TWO stacks of
    different shape (``afmoe``: ``n_dense_layers`` dense layers, then the
    expert layers), so that the expert stack starts wherever in the period
    the dense run ends. The dense layers and as many expert layers as
    complete their period run one by one, each as the kind of its place in
    the model, its weights cut from its own stack at a static index; the
    rest of the expert stack is whole periods and ONE scan over them.
    ``xs`` and the stacked results are over all the model's layers; a
    result that the dense layers give as ``None`` (their routing) has the
    expert layers' alone, as in :func:`_scan_layers`. The running period
    ``li`` of the engine's forwards counts on through both parts, so a
    layer's pages, table and index in the expert stack
    (:func:`_routed_at`) are those of its place in the model."""
    p = len(layers)
    dense, experts = params["dense_layers"], params["layers"]
    nd = jax.tree.leaves(dense)[0].shape[0]
    head = (-nd) % p            # expert layers that complete the period
    outs = []
    for l in range(nd + head):
        stack, i = (dense, l) if l < nd else (experts, l - nd)
        lp = jax.tree.map(lambda a: a[i], stack)
        xs_l = tuple(x[l] for x in xs)
        carry, y = layers[l % p](carry, (lp, *xs_l) if xs else lp)
        outs.append(y)
    rest = None
    if jax.tree.leaves(experts)[0].shape[0] > head:
        carry, rest = _scan_periods(
            layers, carry, experts, xs, unroll, at=head, xs_at=nd + head)
    joined = []
    for m, parts in enumerate(zip(*outs)):
        parts = [y[None] for y in parts if y is not None]
        if rest is not None and rest[m] is not None:
            parts.append(rest[m])
        joined.append(jnp.concatenate(parts, axis=0) if parts else None)
    return carry, tuple(joined)


def _scan_layers(layer, carry, params: Params, xs=(), unroll=1):
    """``lax.scan`` of ``layer(carry, (lp, *xs_l))`` over every stack of
    :func:`_layer_stacks`, one scan a stack (a model of one stack is ONE
    scan, as ever). ``xs``: arrays with a leading axis over ALL layers,
    cut to each stack's run. The stacked results (a tuple) are joined on
    the layer axis; a member that a stack gives as ``None`` (a dense
    layer's routing) is left out of the join. ``layer`` is one function,
    or a list of them, one a position of the model's period of layer
    kinds (:func:`_scan_periods`; a list of one is that one)."""
    if isinstance(layer, (list, tuple)):
        if len(layer) > 1:
            if "dense_layers" in params:
                return _scan_across(layer, carry, params, xs, unroll)
            return _scan_periods(layer, carry, params["layers"], xs, unroll)
        (layer,) = layer
    stacks = _layer_stacks(params)
    outs, at = [], 0
    for st in stacks:
        n = jax.tree.leaves(st)[0].shape[0]
        sl = tuple(x[at : at + n] for x in xs) if len(stacks) > 1 else xs
        carry, ys = jax.lax.scan(
            layer, carry, (st, *sl) if xs else st, unroll=unroll
        )
        outs.append(ys)
        at += n
    if len(outs) == 1:
        return carry, outs[0]
    joined = []
    for parts in zip(*outs):
        parts = [y for y in parts if y is not None]
        joined.append(jnp.concatenate(parts, axis=0) if parts else None)
    return carry, tuple(joined)


def _scan_passes(cfg: ModelConfig, layer, carry, params: Params, xs=(),
                 unroll=1):
    """:func:`_scan_layers`, ``cfg.n_passes`` times over ONE set of
    weights: what every forward runs its stack through. A model of one
    pass gets :func:`_scan_layers` and nothing around it. A looped stack
    gets an outer ``lax.scan`` over the passes whose body is that scan:
    the weights are closed over (a loop invariant, read again each pass,
    never stacked ``n_passes`` times, and autodiff sums a weight's
    gradient over its uses), the carry runs on from pass to pass (``x``,
    or ``(x, li)`` with ``li`` the cache layer ``t * L + l`` that the next
    layer reads and writes), and pass ``t > 0`` starts from the model's
    final norm of pass ``t - 1``'s output; the caller applies that norm
    after the last pass, as for any model. ``xs`` and the stacked results
    have a leading axis over the CACHE layers ``[T * L, ...]``, pass-major,
    and are cut to a pass's ``L`` here."""
    T = cfg.n_passes
    if T == 1:
        return _scan_layers(layer, carry, params, xs, unroll)
    final_ln = _cast(cfg, params["final_ln"])

    def between(t, x):
        # (one norm of the residual computed and dropped at pass 0: a
        # select is cheaper in a scan body than a conditional)
        return jnp.where(t > 0, _norm(cfg, final_ln, x), x)

    def one_pass(carry, inp):
        t, xs_t = inp
        with jax.named_scope("loop_pass"):
            if isinstance(carry, tuple):
                carry = (between(t, carry[0]), *carry[1:])
            else:
                carry = between(t, carry)
            return _scan_layers(layer, carry, params, xs_t, unroll)

    carry, ys = jax.lax.scan(
        one_pass, carry,
        (jnp.arange(T, dtype=jnp.int32),
         tuple(x.reshape(T, x.shape[0] // T, *x.shape[1:]) for x in xs)),
    )
    return carry, jax.tree.map(
        lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), ys
    )


# the weight stack of each mixer kind (``ModelConfig.stack_plan``)
_STACKS = {"attn": "layers", "ssm": "ssm_layers", "gmu": "gmu_layers",
           "cross": "cross_layers", "moe": "moe_layers", "kda": "kda_layers"}


def _scan_plan(cfg: ModelConfig, fns, carry, params: Params, xs=None,
               unroll=1, writers_only: bool = False):
    """:func:`_scan_periods` of a model whose layers differ in weight SHAPE
    (``cfg.stack_plan``: state-space, attention, gated-memory and
    cross-attention layers): a stack a kind (``_STACKS``), each in the
    order its layers run, and ONE scan a SEGMENT of the plan over its
    repeats (a segment that runs once is its body, no scan). The body runs
    the period's positions in order and cuts each position's weights (and
    its slice of ``xs[kind]``, arrays over the layers of that kind) from
    the stack of its kind by its index IN that stack, one layer at a time,
    as a plain scan over one stack does (no copy of a period's weights:
    :func:`_scan_periods` says what that cost). The period is taken as its
    RUNS of alike positions (five state-space layers, the attention layer,
    four more), each a scan unrolled in full: the layer is traced once a
    run, not once a position (three traces for ten: 49 s of 67 s of
    tracing and lowering at the start of a 40-layer model's engine;
    PERF.md section 6, PR 41), and the compiler still gets the period as
    straight-line code.

    ``fns[kind](position)`` is the layer function ``(carry, inp) -> (carry,
    y)`` of a :class:`StackPosition` of that kind. What later layers read
    of earlier ones (the memory, the shared K/V, a running cache layer)
    rides ``carry``, which crosses the segments. Under differential
    attention a layer's slice also holds ``"index"``, its place in the
    model (what ``lambda``'s constant follows); an expert block's holds
    ``"index"``, its place in ITS stack (where the grouped-matmul kernel
    finds its experts in the held stacks). ``writers_only``: stop
    after the last segment that writes a cache or a state (admission keeps
    nothing of what the readers behind it compute). Returns ``(carry,
    ys)``, ``ys[kind]`` stacked over the layers of that kind that ran."""
    xs = xs or {}
    ids = cfg.layer_ids
    stacks = {}
    for kind, tree in _STACKS.items():
        if kind not in ids:
            continue
        stack = params[tree]
        if cfg.diff_attn:
            stack = {**stack, "index": jnp.asarray(ids[kind], jnp.int32)}
        if kind == "moe":
            stack = {**stack, "index": jnp.arange(len(ids[kind]), dtype=jnp.int32)}
        stacks[kind] = (stack, *xs[kind]) if xs.get(kind) else stack
    plan = cfg.plan
    if writers_only:
        last = max(
            i for i, (_, period) in enumerate(plan)
            if any(pos.mixer in ("ssm", "kda", "attn") for pos in period))
        plan = plan[: last + 1]
    base = dict.fromkeys(_STACKS, 0)    # layers of each kind so far
    outs = {kind: [] for kind in _STACKS}
    for reps, period in plan:
        per = {k: sum(pos.mixer == k for pos in period) for k in _STACKS}
        # ("ssm", 5), ("attn", 1), ("ssm", 4)
        runs = [(pos, len(list(g))) for pos, g in itertools.groupby(period)]

        def body(carry, rep, per=per, runs=runs, base=dict(base)):
            ys = {k: [] for k in per if per[k]}
            done = dict.fromkeys(per, 0)
            for pos, n in runs:
                kind = pos.mixer
                first = rep * per[kind] + (base[kind] + done[kind])
                done[kind] += n
                fn = fns[kind](pos)

                def layer(carry, k, kind=kind, first=first, fn=fn):
                    inp = jax.tree.map(
                        lambda a: jax.lax.dynamic_index_in_dim(
                            a, first + k, 0, keepdims=False),
                        stacks[kind],
                    )
                    return fn(carry, inp)

                carry, y = jax.lax.scan(
                    layer, carry, jnp.arange(n, dtype=jnp.int32), unroll=n)
                ys[kind].append(y)
            return carry, {
                kind: jax.tree.map(lambda *a: jnp.concatenate(a), *parts)
                for kind, parts in ys.items()}

        if reps == 1 and len(plan) > 1:
            carry, ys = body(carry, 0)
        else:
            carry, ys = jax.lax.scan(
                body, carry, jnp.arange(reps, dtype=jnp.int32), unroll=unroll)
            ys = jax.tree.map(
                lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), ys)
        for kind, y in ys.items():
            outs[kind].append(y)
            base[kind] += reps * per[kind]
    return carry, {
        kind: jax.tree.map(lambda *a: jnp.concatenate(a), *parts)
        if len(parts) > 1 else parts[0]
        for kind, parts in outs.items() if parts}


def _run_stack(cfg: ModelConfig, layer, carry, params: Params, xs=(),
               unroll=1, fns=None, plan_xs=None, writers_only=False):
    """What every forward runs its layers through: :func:`_scan_passes`,
    or :func:`_scan_plan` for a model with a stack plan (``fns``,
    ``plan_xs``, ``writers_only``: its arguments). Returns ``(carry, ys,
    ys_ssm, ys_moe)``: the attention layers' stacked results, the
    recurrent layers' (state-space or delta-rule) and the expert blocks'
    (None for a model without them)."""
    if cfg.plan is None:
        carry, ys = _scan_passes(cfg, layer, carry, params, xs, unroll)
        return carry, ys, None, None
    carry, ys = _scan_plan(
        cfg, fns, carry, params, plan_xs, unroll, writers_only)
    return carry, ys["attn"], ys[cfg.recurrent], ys.get("moe")


def _ssm_block(cfg: ModelConfig, lp, x, mixer, routed=None):
    """One layer of the model's recurrent kind (``cfg.recurrent``:
    state-space, or delta-rule): ``x + mixer(norm(x))``, then the
    feed-forward part as in an attention layer (:func:`_ffn`; ``routed``:
    its argument). ``mixer(p, h)`` returns ``(out, state)``, and where the
    model has gated memory units a third, the memory (``ops/ssm.py``).
    Returns ``(x, state, memory or None, (aux, routing))``."""
    h = _norm(cfg, lp["ln1"], x)
    out, st, *mem = mixer(lp[cfg.recurrent], h)
    x = _add_branch(cfg, lp, "attn_out_ln", x, out.astype(x.dtype))
    x, aux, routing, _ = _ffn(cfg, lp, x, h, routed)
    return x, st, (mem[0] if mem else None), (aux, routing)


def _rec_chunk(cfg: ModelConfig, positions, state=None, n_valid=None,
               memory: bool = False):
    """``mixer(p, h)`` of the model's recurrent kind over many tokens a
    row (``ops/ssm.py`` / ``ops/kda.py``: ``mixer_chunk``)."""
    if cfg.kda is not None:
        return lambda p, h: kda_ops.mixer_chunk(
            cfg, p, h, positions, state, n_valid=n_valid)
    return lambda p, h: ssm_ops.mixer_chunk(
        cfg, p, h, positions, state, n_valid=n_valid, memory=memory)


def _rec_step(cfg: ModelConfig, state, active, update=None,
              memory: bool = False):
    """``mixer(p, h)`` of the model's recurrent kind over ONE token a row
    (``mixer_step``)."""
    if cfg.kda is not None:
        return lambda p, h: kda_ops.mixer_step(
            cfg, p, h, state, active, update=update)
    return lambda p, h: ssm_ops.mixer_step(
        cfg, p, h, state, active, update=update, memory=memory)


def _in_layer_order(cfg: ModelConfig, attn_part, rec_part):
    """What the attention layers and the recurrent layers each stacked
    over THEIR layers (a router's choices, its loss), as one array over
    the model's layers in the order they run."""
    ids = cfg.layer_ids
    order = np.argsort(ids["attn"] + ids[cfg.recurrent])
    return jnp.concatenate([attn_part, rec_part])[order]


def _gmu_block(cfg: ModelConfig, lp, x, memory):
    """One gated memory unit: ``x + W_out (silu(W_in norm(x)) * M)``, ``M``
    the last state-space layer's scan output at the same positions (float32
    ``[..., d_inner]``), then the MLP."""
    lp = _cast(cfg, lp)
    h = _norm(cfg, lp["ln1"], x)
    with jax.named_scope("gmu"):
        gate = jax.nn.silu((h @ lp["gmu"]["w_in"]).astype(jnp.float32))
        out = (gate * memory).astype(x.dtype) @ lp["gmu"]["w_out"]
    x = _add_branch(cfg, lp, "attn_out_ln", x, out)
    return _ffn(cfg, lp, x)[0]


def _plan_fns(cfg: ModelConfig, attn, ssm_layer, remat: bool = False,
              routed=None, moe_ys=None):
    """:func:`_scan_plan`'s ``fns`` of a forward: ``attn(position)`` makes
    the layer of an "attn" or a "cross" position; the state-space layer and
    the gated memory unit are one function each wherever they stand. The
    unit is the same in every forward: what the readers read (memory, k,
    v: :func:`_shared0`) ENDS the carry, whatever else it holds. So is the
    expert block (:func:`_moe_block`; ``routed``: its argument): ``x``
    STARTS every carry, and ``moe_ys(aux, routing)`` is what the forward
    keeps of it (None: nothing). ``remat``: both under ``jax.checkpoint``
    (the trainer's forward)."""

    def moe_layer(carry, lp):
        x, *rest = carry if isinstance(carry, tuple) else (carry,)
        x, aux, routing = _moe_block(cfg, lp, x, routed)
        return ((x, *rest) if isinstance(carry, tuple) else x), (
            None if moe_ys is None else moe_ys(aux, routing))

    def gmu_layer(carry, lp):
        x, *rest = carry
        return (_gmu_block(cfg, lp, x, rest[-3]), *rest), None

    if remat:
        gmu_layer = jax.checkpoint(gmu_layer, prevent_cse=False)
        moe_layer = jax.checkpoint(moe_layer, prevent_cse=False)
    return {"attn": attn, "cross": attn, "moe": lambda pos: moe_layer,
            "ssm": lambda pos: ssm_layer, "kda": lambda pos: ssm_layer,
            "gmu": lambda pos: gmu_layer}


def _readers(cfg: ModelConfig) -> bool:
    """The plan has layers that read what earlier layers computed (gated
    memory units, cross attention): every forward's carry then holds the
    memory and the shared K/V (:func:`_shared0`)."""
    return any(m in ("gmu", "cross") for m in cfg.mixers)


def _shared0(cfg: ModelConfig, lead, dtype):
    """What the readers read before any layer has written it: ``(memory
    [*lead, d_inner] float32, k, v [*lead, rows, width])``, zeros; ``()``
    for a model without readers."""
    if not _readers(cfg):
        return ()
    _, heads, width = kv_page_geometry(cfg)
    kv = jnp.zeros((*lead, heads, width), dtype)
    return (jnp.zeros((*lead, cfg.ssm.d_inner), jnp.float32), kv, kv)


def _pool_of(cfg: ModelConfig, table, li, j: int = 0, pos=None):
    """Where a layer's pages are: ``(index on the pool's leading axis, page
    table [B, M])``. Without a plan ``li`` is the running period and ``j``
    the layer's (static) position in it. Under a plan ``li`` is the
    running CACHE LAYER (the "attn" layers in the order they run): cache
    layer ``c`` is position ``c % period`` of period ``c // period``, a
    cross layer reads its ``source``'s."""
    if pos is None:
        return li, _kind_table(table, j)
    p = cfg.period
    if pos.mixer == "cross":
        return pos.source // p, _kind_table(table, pos.source % p)
    if p == 1:
        return li, table
    return li // p, jax.lax.dynamic_index_in_dim(table, li % p, 0, False)


def _plan_scope(cfg: ModelConfig, pos):
    """``jax.named_scope`` name of a plan position's attention where the
    plan's attention layers differ (None: no scope, as ever)."""
    if pos is None or (len(cfg.layer_kinds) == 1 and not _readers(cfg)):
        return None
    return "attn_cross" if pos.mixer == "cross" else _attn_scope(pos.window)


def _q_only(cfg: ModelConfig, p, x, cos, sin):
    """A cross-attention layer's queries ``[..., Hq, D]``: keys and values
    are another layer's."""
    q = x @ p["wq"]
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(*x.shape[:-1], cfg.n_q_heads, cfg.head_dim)
    return apply_rotary(q, cos, sin) if cfg.apply_rotary else q


# ``cfg.kv_heads_per_row`` kv heads side by side in ONE row of a cache: the
# cache, the paged kernel and the write kernel then see ``Hkv / r`` heads of
# ``r * D`` (a head of 64 fills half a 128-lane tile; two fill it, and the
# kernels take a full-lane head only). A query head carries its values in
# the part of the row that is its own kv head's and zeros in the rest, so
# its scores are the published ones; of the context it reads that part.


def _row_part(cfg: ModelConfig, dtype):
    """``[Hq, r]`` one-hot: which part of its kv row a query head reads.
    Differential pairs: head ``h`` is half ``h % 2`` of pair ``h // 2``
    (``q1`` the even heads, ``q2`` the odd), pair ``i`` reads kv pair ``i
    // (pairs a kv pair)``, and a kv pair is one row ``[k1 ; k2]``: so head
    ``h`` reads half ``h % 2`` of row ``h // (Hq / rows)``, the row the
    kernels' grouping gives it anyway."""
    if cfg.diff_attn:
        part = jnp.arange(cfg.n_q_heads) % 2
    else:
        part = (jnp.arange(cfg.n_q_heads) // cfg.n_rep) % cfg.kv_heads_per_row
    return jax.nn.one_hot(part, cfg.kv_heads_per_row, dtype=dtype)


def _pack_qkv(cfg: ModelConfig, q, k, v):
    """``k``, ``v`` None: a cross layer's queries alone."""
    r = cfg.kv_heads_per_row
    if r == 1:
        return q, k, v
    sel = _row_part(cfg, q.dtype)
    q = (q[..., None, :] * sel[:, :, None]).reshape(*q.shape[:-1], -1)

    def rows(a):
        return None if a is None else a.reshape(
            *a.shape[:-2], a.shape[-2] // r, -1)

    return q, rows(k), rows(v)


def _unpack_ctx(cfg: ModelConfig, ctx, p=None):
    """The packed context back as the model's heads: of each head the part
    of the value row that is its own kv head's; under differential
    attention the pairs' combination (``p``: the layer's attention weights
    and its ``"index"``), which keeps the whole row."""
    r = cfg.kv_heads_per_row
    if r == 1:
        return ctx
    if cfg.diff_attn:
        return _diff_combine(cfg, p, ctx)
    sel = _row_part(cfg, ctx.dtype)
    ctx = ctx.reshape(*ctx.shape[:-1], r, -1)
    return (ctx * sel[:, :, None]).sum(axis=-2)


_LAMBDAS = ("lam_q1", "lam_k1", "lam_q2", "lam_k2")


def diff_lambda_init(index):
    """``lambda``'s constant of layer ``index`` (0-based), as published."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(index, jnp.float32))


def _diff_combine(cfg: ModelConfig, p, ctx):
    """Differential attention's second half. ``ctx [..., Hq, 2 D]``: head
    ``2 i`` is ``a1 = softmax(q1 k1^T) [v1 ; v2]`` of pair ``i``, head ``2 i
    + 1`` is ``a2 = softmax(q2 k2^T) [v1 ; v2]`` (what the packing of
    :func:`_pack_qkv` computes). Returns ``(1 - lam0) rms(a1 - lam a2) w``,
    ``[..., Hq / 2, 2 D]``, with ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) +
    lam0`` and ``lam0`` :func:`diff_lambda_init` of the layer."""
    f32 = jnp.float32
    lam0 = diff_lambda_init(p["index"])
    lq1, lk1, lq2, lk2 = (p[name].astype(f32) for name in _LAMBDAS)
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
    with jax.named_scope("diff_combine"):
        a = ctx.astype(f32)
        d = a[..., 0::2, :] - lam * a[..., 1::2, :]
        out = norms.rms_norm(d, p["subln"], cfg.layer_norm_epsilon)
        return ((1.0 - lam0) * out).astype(ctx.dtype)


def _dense_qkv(cfg: ModelConfig, q, k, v):
    """What the forwards WITHOUT a page pool (the trainer's, the dense
    cache's) attend with: the model's heads as they are, or under
    differential attention the packed rows (:func:`_pack_qkv`), which is
    how a pair's softmax comes to read the whole value row."""
    return _pack_qkv(cfg, q, k, v) if cfg.diff_attn else (q, k, v)


def _dense_ctx(cfg: ModelConfig, p, ctx):
    return _diff_combine(cfg, p, ctx) if cfg.diff_attn else ctx


# --------------------------------------------------------------------------- #
# Packed forward (training / logprob inference)
# --------------------------------------------------------------------------- #


def _embed(cfg: ModelConfig, params: Params, input_ids, positions):
    x = _cast(cfg, params["embed"]["weight"])[input_ids]
    if cfg.normalize_embed:
        x = x * jnp.asarray(cfg.hidden_dim**0.5, x.dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    if cfg.abs_position_embedding:
        x = x + _cast(cfg, params["pos_embed"]["weight"])[positions]
    return x


def head_weight(cfg: ModelConfig, params: Params):
    """The LM-head weight ``[E, V]`` in serving dtype, for a matmul XLA
    compiles (:func:`_head`: the materialised path, ``apply_head``). Tied
    embeddings transpose on the fly — a lazy view XLA fuses into the
    consumer, but a ``V x E`` copy every step as a kernel's operand: the
    fused epilogue takes :func:`head_operand` instead."""
    w, vocab_rows = head_operand(cfg, params)
    return w.T if vocab_rows else w


def head_operand(cfg: ModelConfig, params: Params):
    """``(weight, vocab_rows)``: the LM head in serving dtype in the layout
    the parameter tree stores it, for the fused epilogue
    (``ops/fused_sample.py``), which streams either over vocabulary
    blocks. Untied: ``params["head"]["weight"]``, ``[E, V]``, ``False``.
    Tied: the embedding itself, ``[V, E]``, ``True`` (no transpose, no
    copy). ``cfg.logits_scaling`` and the soft cap, when configured, are
    the consumer's to apply (``fused_sample`` takes both as arguments)."""
    if cfg.tied_embedding:
        return _cast(cfg, params["embed"]["weight"]), True
    return _cast(cfg, params["head"]["weight"]), False


def _head(cfg: ModelConfig, params: Params, x):
    if cfg.is_critic:
        return (x @ _cast(cfg, params["head"]["weight"])).astype(jnp.float32)
    logits = (x @ head_weight(cfg, params)).astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if cfg.final_logits_soft_cap is not None:
        c = cfg.final_logits_soft_cap
        logits = c * jnp.tanh(logits / c)
    return logits


def apply_head(cfg: ModelConfig, params: Params, x):
    """Public :func:`_head`: full logits from final-norm hidden states —
    the engine's sorted-fallback rows (top-p slots under the fused
    epilogue) materialize ONLY their own rows' logits through this."""
    return _head(cfg, params, x)


def forward_packed(
    params: Params,
    cfg: ModelConfig,
    input_ids: jnp.ndarray,     # [T] int32
    segment_ids: jnp.ndarray,   # [T] int32, 0 = padding
    positions: jnp.ndarray,     # [T] int32, restart per segment
    *,
    remat: bool = True,
    with_aux: bool = False,
    with_head: bool = True,
    with_routing: bool = False,
    with_mtp: bool = False,
) -> jnp.ndarray:
    """Full forward over a packed token axis. Returns ``[T, vocab]`` logits
    (fp32) or ``[T, 1]`` values for critics; with ``with_aux`` returns
    ``(out, aux_loss)`` where aux is the summed MoE router loss over layers.
    ``with_routing`` (MoE models) appends the experts every token chose in
    every layer, int32 ``[L, T, top_k]``.
    ``with_head=False`` returns the final-norm HIDDEN states ``[T, E]``
    instead — the chunked-loss path applies the head per token block so the
    ``[T, vocab]`` logits (4 GB f32 at 32k x 32k) never materialize.
    ``with_mtp`` (``cfg.n_mtp_layers`` > 0) appends the multi-token-
    prediction modules' logits, fp32 ``[n_mtp, T, vocab]``: row ``k`` at
    position ``i`` is the distribution of token ``i + k + 2`` (the last
    ``k + 1`` positions of a segment have no such input and are garbage).
    Padding rows are garbage — mask downstream with ``segment_ids > 0``."""
    x = _embed(cfg, params, input_ids, positions)
    cos, sin = _cos_sin(cfg, positions)

    def _attend(q, k, v, window):
        return attn_ops.packed_attention(
            q,
            k,
            v,
            segment_ids,
            softmax_scale=cfg.softmax_scale,
            soft_cap=cfg.attn_logits_soft_cap,
            sliding_window=window,
            use_flash=cfg.flash_enabled(),
            flash_block_size=cfg.flash_block_size,
            flash_block_size_k=cfg.flash_block_size_k,
            max_seqlen=cfg.attn_max_seqlen,
        )

    # a router that reads the layer's input needs it after attention: the
    # norm is recomputed there (one RMSNorm) rather than carried across
    # the attention kernel, which the split checkpointing below cuts at
    def _pre(x, lp, rotary, cross=False):
        h = _norm(cfg, lp["ln1"], x)
        if cfg.cca is not None:
            # one row that holds every document: a token at position 0
            # of its own resets the convolutions and the value shift
            q, k, v, _ = _cca_qkv_roped(
                cfg, lp["attn"], h[None], cos[None], sin[None],
                positions[None])
            return q[0], k[0], v[0]
        if cross:
            return _pack_qkv(
                cfg, _q_only(cfg, lp["attn"], h, cos, sin), None, None)
        return _dense_qkv(
            cfg, *_qkv_roped(cfg, lp["attn"], h, cos, sin, rotary))

    # ``r``: a stateful router's vector of the layer before (None for
    # every other model: the carry is then ``x`` alone, as ever)
    r0 = _router_state0(cfg, x)
    # ... and where the plan has readers, the memory and the shared K/V
    shared0 = _shared0(cfg, x.shape[:1], x.dtype)

    def _post(x, ctx, lp, r):
        layer_in = (
            _norm(cfg, lp["ln1"], x)
            if cfg.moe is not None and cfg.moe.router_on_layer_input
            else None
        )
        ctx = _dense_ctx(cfg, _attn_params(lp), ctx)
        # (the gate's input likewise: one RMSNorm again, not carried)
        gate_in = _norm(cfg, lp["ln1"], x) if cfg.attn_gate else None
        x = _add_branch(
            cfg, lp, "attn_out_ln", x, _attn_out(lp["attn"], ctx, gate_in))
        x, aux, routing, r = _ffn(cfg, lp, x, layer_in, router_state=r)
        return (x if r0 is None else (x, r)), (aux, routing)

    policy = cfg.remat_policy if remat else "none"
    dots = jax.checkpoint_policies.dots_with_no_batch_dims_saveable

    if policy not in ("dots_attn", "full", "dots", "none"):
        raise ValueError(f"unknown remat_policy {policy!r}")

    def make_layer(kind, pos=None):
        window, rotary = kind
        scope = (
            _plan_scope(cfg, pos) if cfg.layer_pattern is None
            else _attn_scope(window))
        cross = pos is not None and pos.mixer == "cross"

        def attend(q, k, v):
            if scope is None:
                return _attend(q, k, v, window)
            with jax.named_scope(scope):
                return _attend(q, k, v, window)

        if policy == "dots_attn" and not shared0:
            # Split checkpointing that leaves the attention kernel OUTSIDE
            # the remat region: jax.checkpoint cannot save a custom_vjp's
            # residuals, so a whole-layer checkpoint re-runs the full flash
            # forward inside the backward just to regenerate (out, lse) —
            # ~25% of a long-context step. Here attention residuals (q, k,
            # v, out, lse) are saved (~180 MB/layer at 32k for a 768-wide
            # model) and only the cheap projection/MLP matmul inputs are
            # recomputed. The bf16 param cast stays INSIDE each region —
            # hoisting it would turn every layer's cast param tree into
            # saved residuals.
            pre = jax.checkpoint(
                lambda x, lp: _pre(x, _cast(cfg, lp), rotary),
                policy=dots, prevent_cse=False,
            )
            post = jax.checkpoint(
                lambda x, ctx, lp, r: _post(x, ctx, _cast(cfg, lp), r),
                policy=dots, prevent_cse=False,
            )

            def layer(carry, lp):
                x, r = (carry, None) if r0 is None else carry
                q, k, v = pre(x, lp)
                return post(x, attend(q, k, v), lp, r)

            return layer

        def layer(carry, lp):
            x, r = (carry, None) if r0 is None else carry
            lp = _cast(cfg, lp)
            q, k, v = _pre(x, lp, rotary)
            return _post(x, attend(q, k, v), lp, r)

        def reader_layer(carry, lp):
            # a plan with readers: the carry is ``(x, memory, k, v)``; a
            # cross layer attends the shared K/V, the layer that exports
            # them leaves its own there
            x, mem, ks, vs = carry
            lp = _cast(cfg, lp)
            q, k, v = _pre(x, lp, rotary, cross)
            if cross:
                k, v = ks, vs
            elif pos.exports:
                ks, vs = k, v
            x, y = _post(x, attend(q, k, v), lp, None)
            return (x, mem, ks, vs), y

        if shared0:
            layer = reader_layer
        if policy == "full":
            return jax.checkpoint(layer, prevent_cse=False)
        if policy == "dots" or (policy == "dots_attn" and shared0):
            return jax.checkpoint(layer, policy=dots, prevent_cse=False)
        return layer

    def ssm_layer(carry, lp):
        # one row that holds every document: a token at position 0 of its
        # own resets the state and the convolution
        x, *shared = carry if shared0 else (carry,)
        lp = _cast(cfg, lp)
        x, _, mem, (aux, chosen) = _ssm_block(
            cfg, lp, x[None],
            _rec_chunk(cfg, positions[None], memory=bool(shared0)))
        if shared0:
            return (x[0], mem[0], *shared[1:]), None
        # (a delta-rule layer holds a router where the model has one)
        return x[0], None if chosen is None else (aux, chosen[0])

    if policy != "none":
        ssm_layer = jax.checkpoint(ssm_layer, prevent_cse=False)
    layers = [make_layer(kind) for kind in cfg.layer_kinds]
    layer = layers[-1]      # the block a multi-token-prediction module is

    def attn_fn(pos):
        return make_layer((pos.window, cfg.apply_rotary), pos)

    x, (auxes, routing), rec_ys, moe_ys = _run_stack(
        cfg, layers, (x, *shared0) if shared0 else (
            x if r0 is None else (x, r0)), params,
        unroll=cfg.layer_scan_unroll or 1,
        fns=_plan_fns(cfg, attn_fn, ssm_layer, remat=policy != "none",
                      moe_ys=lambda aux, routing: (aux, routing)),
    )
    if moe_ys is not None:
        auxes, routing = moe_ys     # the expert blocks': the plan's router
    elif rec_ys is not None:        # a router in the recurrent layers too
        auxes = jnp.concatenate([auxes, rec_ys[0]])
        routing = _in_layer_order(cfg, routing, rec_ys[1])
    if r0 is not None or shared0:
        x, *_ = x
    stack_out = x
    x = _norm(cfg, _cast(cfg, params["final_ln"]), x)
    out = _head(cfg, params, x) if with_head else x
    res = (out,)
    if with_aux:
        res += (jnp.sum(auxes),)
    if with_routing:
        if routing is None:
            raise ValueError("with_routing: the model has no router")
        res += (routing,)
    if with_mtp:
        if not cfg.n_mtp_layers:
            raise ValueError("with_mtp: the model has no such module")
        res += (_mtp_logits(
            params, cfg, stack_out, input_ids, positions, layer
        ),)
    return res if len(res) > 1 else out


def _mtp_logits(params, cfg, h, input_ids, positions, layer):
    """The multi-token-prediction modules in sequence (the ``deepseek_v3``
    description). Module ``k`` takes the previous depth's hidden states
    ``h [T, E]`` (depth 0: the stack's output BEFORE the final norm) and
    the embedding of the token ``k + 1`` places on: ``h' = [e_norm(Emb(t_
    {i+k+1})) ; h_norm(h_i)] @ eh_proj``, then ONE block of the stack's
    last kind at the same positions, then the model's own final norm and
    head. The modules own ``e_norm``, ``h_norm``, ``eh_proj`` and the
    block; the embedding, the final norm and the head are shared."""
    mtp = params["mtp"]
    out = []
    for k in range(cfg.n_mtp_layers):
        mp = _cast(cfg, jax.tree.map(lambda a: a[k], mtp))
        nxt = jnp.roll(input_ids, -(k + 1))
        e = _embed(cfg, params, nxt, positions)
        with jax.named_scope("mtp_eh_proj"):
            h = jnp.concatenate(
                [_norm(cfg, mp["e_norm"], e), _norm(cfg, mp["h_norm"], h)],
                axis=-1,
            ) @ mp["eh_proj"]
        h, _ = layer(h, jax.tree.map(lambda a: a[k], mtp["block"]))
        out.append(
            _head(cfg, params, _norm(cfg, _cast(cfg, params["final_ln"]), h))
        )
    return jnp.stack(out)


def chunked_next_token_logprobs(
    params: Params,
    cfg: ModelConfig,
    hidden: jnp.ndarray,       # [T, E] final-norm hidden (with_head=False)
    input_ids: jnp.ndarray,    # [T]
    segment_ids: jnp.ndarray,  # [T]
    chunk: int = 4096,
) -> jnp.ndarray:
    """Next-token logprobs ``[T]`` without ever materializing ``[T, vocab]``
    logits: a remat'd ``lax.scan`` over token blocks applies the LM head,
    log-softmaxes, and gathers the label per block — forward peak memory
    ``[chunk, vocab]``, and the backward recomputes each block's logits
    instead of keeping 4 GB of f32 logits alive at the 32k protocol shape
    (the head matmul recompute is ~2 TFLOP vs ~8 GB of HBM round trips).
    Semantics match ``ops.ppo.gather_packed_shifted_log_probs``."""
    from areal_tpu.ops import ppo as ppo_ops

    T = hidden.shape[0]
    if T % chunk:
        # round DOWN to a divisor of T — falling back to one [T, vocab]
        # block would re-materialize exactly the logits this path exists
        # to avoid
        chunk = next(c for c in range(min(chunk, T), 0, -1) if T % c == 0)
    nc = T // chunk
    nxt = jnp.concatenate([input_ids[1:], jnp.zeros((1,), input_ids.dtype)])

    def block(_, blk):
        h_c, ids_c = blk
        logits = _head(cfg, params, h_c)              # [chunk, V] f32
        logp = jax.nn.log_softmax(logits, axis=-1)
        lp = jnp.take_along_axis(logp, ids_c[:, None], axis=-1)[:, 0]
        return None, lp

    _, lps = jax.lax.scan(
        jax.checkpoint(block, prevent_cse=False),
        None,
        (hidden.reshape(nc, chunk, -1), nxt.reshape(nc, chunk)),
    )
    lp = lps.reshape(T)
    has_next = (segment_ids > 0) & ~ppo_ops.is_segment_end(segment_ids)
    return jnp.where(has_next, lp, 0.0)


# --------------------------------------------------------------------------- #
# KV-cache decode path (generation engine)
# --------------------------------------------------------------------------- #


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """Per-layer KV cache: ``k, v: [L, B, S, Hkv, D]``; ``lens: [B]`` counts
    valid entries per slot (0 = free slot). ``L`` is ``cfg.cache_layers``:
    a looped stack (``cfg.n_passes``) holds a token once a PASS, pass ``t``
    of layer ``l`` at ``t * n_layers + l``; a stack plan holds its
    self-attention layers alone. Under differential attention a row is a
    PAIR of kv heads, as in the page pool (``cfg.kv_heads_per_row``)."""

    k: jnp.ndarray
    v: jnp.ndarray
    lens: jnp.ndarray
    # what a row keeps beside its keys and values: the state-space
    # layers' state (``cfg.ssm``: :class:`SSMState`), or the convolved
    # latent's carry (``cfg.cca``: :class:`CCAState`); else None
    ssm: Optional[Any] = None

    @classmethod
    def empty(cls, cfg: ModelConfig, batch: int, capacity: int) -> "KVCache":
        heads, width = cfg.n_kv_heads, cfg.head_dim
        if cfg.diff_attn:
            # a pair of kv heads a row, as in the page pool
            _, heads, width = kv_page_geometry(cfg)
        shape = (cfg.cache_layers, batch, capacity, heads, width)
        dt = jnp.dtype(cfg.dtype)
        return cls(
            k=jnp.zeros(shape, dt),
            v=jnp.zeros(shape, dt),
            lens=jnp.zeros((batch,), jnp.int32),
            ssm=row_state_empty(cfg, batch),
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SSMState:
    """What the state-space layers keep of a ROW (a slot of the generation
    engine, a row of the dense cache) in place of keys and values: ``ssm
    [Ls, B, G, K, N, 128]`` the recurrent state of every head of every
    state-space layer, float32 (``cfg.ssm.state_dtype``), its channels
    minor in ``K`` lane tiles, and ``conv [Ls, B, (d_conv - 1) x C]`` the
    convolution's last inputs in the serving dtype, flat (``ops/ssm.py``
    says why of both).
    It has one size however long the row's context is, so it is allocated
    by row and not by page: a prefix cannot be shared by pointing at it,
    only by copying a snapshot of it (``gen/engine.py``)."""

    ssm: jnp.ndarray
    conv: jnp.ndarray

    @classmethod
    def empty(cls, cfg: ModelConfig, batch: int) -> "SSMState":
        ssm, conv = ssm_ops.state_shapes(cfg, batch)
        return cls(
            ssm=jnp.zeros(ssm, jnp.dtype(cfg.ssm.state_dtype)),
            conv=jnp.zeros(conv, jnp.dtype(cfg.dtype)),
        )

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CCAState:
    """What attention inside a convolved latent (``cfg.cca``) keeps of a
    ROW beside its keys and values: ``carry [L, B, W]``, a layer's last
    inputs of the two convolutions and the shifted half of the last
    token's value projection (``ModelConfig.cca_carry_dim``;
    ``ops/cca.py``), flat, in the serving dtype. A few KB a layer whatever
    the row's length. Like :class:`SSMState` it is allocated by row and
    shared through the prefix cache only as a copy; unlike it, it is small
    enough for the snapshot table to hold two entries a slot
    (``gen/engine.py``)."""

    carry: jnp.ndarray

    @classmethod
    def empty(cls, cfg: ModelConfig, batch: int) -> "CCAState":
        return cls(carry=jnp.zeros(
            (cfg.n_layers, batch, cfg.cca_carry_dim), jnp.dtype(cfg.dtype)))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DeltaState:
    """What the delta-rule layers (``cfg.kda``) keep of a ROW in place of
    keys and values: ``s [Lk, B, H, Dk, Dv]`` a matrix a head of every such
    layer, float32 (``ops/kda.py:STATE_DTYPE``), and ``conv [Lk, B, (d_conv -
    1) x 3 H D]`` the convolutions' last inputs in the serving dtype, flat
    (``ops/kda.py``). Allocated by row and shared through the prefix cache
    only as a copy, as :class:`SSMState` is."""

    s: jnp.ndarray
    conv: jnp.ndarray

    @classmethod
    def empty(cls, cfg: ModelConfig, batch: int) -> "DeltaState":
        s, conv = kda_ops.state_shapes(cfg, batch)
        return cls(
            s=jnp.zeros(s, kda_ops.STATE_DTYPE),
            conv=jnp.zeros(conv, jnp.dtype(cfg.dtype)),
        )


def row_state_empty(cfg: ModelConfig, batch: int):
    """The per-row state of ``batch`` rows, of the model's kind
    (:class:`SSMState`, :class:`DeltaState`, :class:`CCAState`), or None
    for a model whose rows keep keys and values only."""
    if cfg.ssm is not None:
        return SSMState.empty(cfg, batch)
    if cfg.kda is not None:
        return DeltaState.empty(cfg, batch)
    if cfg.cca is not None:
        return CCAState.empty(cfg, batch)
    return None


def row_state_bytes(cfg: ModelConfig) -> int:
    """What one row's per-row state takes, all layers (0: the model keeps
    none)."""
    if cfg.ssm is not None:
        return ssm_ops.state_bytes_per_slot(cfg)
    if cfg.kda is not None:
        return kda_ops.state_bytes_per_slot(cfg)
    if cfg.cca is not None:
        return (cfg.n_layers * cfg.cca_carry_dim
                * jnp.dtype(cfg.dtype).itemsize)
    return 0


def row_state_layers(cfg: ModelConfig) -> int:
    """The layers that keep per-row state (0: the model keeps none)."""
    if cfg.recurrent is not None:
        return cfg.n_mixers(cfg.recurrent)
    return cfg.n_layers if cfg.cca is not None else 0


def row_state_kind(cfg: ModelConfig) -> Optional[str]:
    """What the per-row state is, for a message (None: the model keeps
    none)."""
    if cfg.ssm is not None:
        return "state-space layers"
    if cfg.kda is not None:
        return "delta-rule layers"
    return "attention in a convolved latent" if cfg.cca is not None else None


def recurrent_heads(cfg: ModelConfig, state):
    """One row's recurrent state ``[layers, ...]`` (the first array of
    :class:`SSMState` or :class:`DeltaState`, cut at a row) head by head
    as the equations write it: ``[Ls, H, P, N]`` turned from the layout
    the slots keep (``ops/ssm.py``), or ``[Lk, H, Dk, Dv]`` as it is."""
    if cfg.kda is not None:
        return state
    c = cfg.ssm
    state = state.transpose(0, 1, 2, 4, 3)
    return state.reshape(len(state), c.n_heads, c.head_dim, c.d_state)


def prefill(
    params: Params,
    cfg: ModelConfig,
    cache: KVCache,
    input_ids: jnp.ndarray,   # [B, S] right-padded prompts
    prompt_lens: jnp.ndarray, # [B]
) -> Tuple[jnp.ndarray, KVCache]:
    """Batched prompt processing; fills the cache at positions [0, len) and
    returns fp32 logits of the *last* prompt token per slot: ``[B, vocab]``.

    Attention dispatch: with flash enabled (TPU), rows flatten onto one
    packed ``[B*S]`` token axis with one segment per row and run through the
    varlen flash kernel — O(S) memory per row, so protocol-length (32k)
    prompts prefill without ever materializing the ``[B, H, S, S]`` score
    tensor the dense path below builds (that path stays: it is the right
    tool for small-S CPU tests and autodiff checks)."""
    B, S = input_ids.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    valid = positions < prompt_lens[:, None]
    x = _embed(cfg, params, input_ids, positions)
    cos, sin = _cos_sin(cfg, positions)
    idx = jnp.arange(S)
    use_flash = cfg.flash_enabled()
    if use_flash:
        # one segment per row, padding tail INCLUDED in the segment: a valid
        # q (pos < len) never attends the tail anyway (causal, tail is
        # later), and padded q rows produce finite garbage that the `keep`
        # mask + last-token gather below never read.
        flat_seg = jnp.broadcast_to(
            (jnp.arange(B, dtype=jnp.int32) + 1)[:, None], (B, S)
        ).reshape(B * S)
        mask = None
    else:
        # causal & in-prompt mask, [B, S, S]
        mask = (idx[None, :, None] >= idx[None, None, :]) & valid[:, None, :]
    scale = cfg.softmax_scale or cfg.head_dim**-0.5

    def make_layer(kind, pos=None):
        window, rotary = kind
        kind_mask = mask
        if mask is not None and window is not None:
            kind_mask = mask & (
                idx[None, :, None] - idx[None, None, :] < window)
        return functools.partial(layer, window, rotary, kind_mask, pos)

    r0 = _router_state0(cfg, x)
    # a plan with readers: the carry is ``(x, memory, k, v)``
    shared0 = _shared0(cfg, (B, S), x.dtype)

    def layer(window, rotary, mask, pos, carry, lp):
        x, r = (carry, None) if r0 is None else carry
        if shared0:
            x, mem, ks, vs = carry
        cross = pos is not None and pos.mixer == "cross"
        lp = _cast(cfg, lp)
        h = _norm(cfg, lp["ln1"], x)
        cc = None
        if cfg.cca is not None:
            q, k, v, cc = _cca_qkv_roped(
                cfg, lp["attn"], h, cos, sin, positions, None, prompt_lens)
        elif cross:
            q, _, _ = _pack_qkv(
                cfg, _q_only(cfg, lp["attn"], h, cos, sin), None, None)
            k, v = ks, vs
        else:
            q, k, v = _dense_qkv(cfg, *_qkv_roped(
                cfg, lp["attn"], h, cos, sin, rotary))  # [B, S, H, D]
            if shared0 and pos.exports:
                ks, vs = k, v
        if use_flash:
            H, D = q.shape[-2:]
            ctx = attn_ops.packed_attention(
                q.reshape(B * S, H, D),
                k.reshape(B * S, -1, D),
                v.reshape(B * S, -1, D),
                flat_seg,
                softmax_scale=scale,
                soft_cap=cfg.attn_logits_soft_cap,
                sliding_window=window,
                use_flash=True,
                max_seqlen=S,
            ).reshape(B, S, H, D)
        else:
            n_rep = q.shape[2] // k.shape[2]
            kk = jnp.repeat(k, n_rep, axis=2)
            vv = jnp.repeat(v, n_rep, axis=2)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk, preferred_element_type=jnp.float32) * scale
            if cfg.attn_logits_soft_cap is not None:
                c = cfg.attn_logits_soft_cap
                scores = c * jnp.tanh(scores / c)
            scores = jnp.where(mask[:, None], scores, attn_ops._NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1).astype(vv.dtype)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
        ctx = _dense_ctx(cfg, _attn_params(lp), ctx)
        x = _add_branch(
            cfg, lp, "attn_out_ln", x,
            _attn_out(lp["attn"], ctx.astype(x.dtype), h))
        x, _, _, r = _ffn(cfg, lp, x, h, router_state=r)
        if shared0:
            return (x, mem, ks, vs), (None if cross else (k, v, cc))
        return (x if r0 is None else (x, r)), (k, v, cc)

    def ssm_layer(carry, lp):
        x, *shared = carry if shared0 else (carry,)
        x, st, mem, _ = _ssm_block(
            cfg, _cast(cfg, lp), x,
            _rec_chunk(cfg, positions, n_valid=prompt_lens,
                       memory=bool(shared0)))
        return ((x, mem, *shared[1:]) if shared0 else x), st

    def attn_fn(pos):
        return make_layer((pos.window, cfg.apply_rotary), pos)

    x, (ks, vs, cc), ssm, _ = _run_stack(
        cfg, [make_layer(kind) for kind in cfg.layer_kinds],
        (x, *shared0) if shared0 else (x if r0 is None else (x, r0)), params,
        fns=_plan_fns(cfg, attn_fn, ssm_layer),
    )
    if r0 is not None or shared0:
        x, *_ = x
    if cc is not None:
        ssm = (cc,)
    cap = cache.k.shape[2]
    pad = cap - S
    if pad < 0:
        raise ValueError("prompt longer than cache capacity")
    ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    keep = (jnp.arange(cap)[None, :] < prompt_lens[:, None])[None, :, :, None, None]
    cache = KVCache(
        k=jnp.where(keep, ks.astype(cache.k.dtype), cache.k),
        v=jnp.where(keep, vs.astype(cache.v.dtype), cache.v),
        lens=prompt_lens.astype(jnp.int32),
        ssm=None if ssm is None else type(cache.ssm)(*ssm),
    )
    x = _norm(cfg, _cast(cfg, params["final_ln"]), x)
    last = jnp.take_along_axis(
        x, jnp.maximum(prompt_lens - 1, 0)[:, None, None], axis=1
    )[:, 0]
    return _head(cfg, params, last), cache


def decode_step(
    params: Params,
    cfg: ModelConfig,
    cache: KVCache,
    tokens: jnp.ndarray,       # [B] current tokens
    active: Optional[jnp.ndarray] = None,  # [B] bool; inactive slots untouched
) -> Tuple[jnp.ndarray, KVCache]:
    """One decode step for every cache slot. Returns fp32 logits ``[B, vocab]``
    and the updated cache (lens incremented where ``active``)."""
    B = tokens.shape[0]
    if active is None:
        active = jnp.ones((B,), bool)
    positions = cache.lens  # position of the new token
    x = _embed(cfg, params, tokens, positions)  # [B, E]
    cos, sin = _cos_sin(cfg, positions)
    write_at = cache.lens  # [B]
    new_lens = jnp.where(active, cache.lens + 1, cache.lens)

    r0 = _router_state0(cfg, x)
    # a plan with readers: the carry is ``(x, memory, k cache, v cache)``,
    # the last two the exporting layer's whole rows ``[B, S, rows, width]``
    shared0 = _shared0(cfg, (B,), x.dtype)
    if shared0:
        shared0 = (shared0[0], cache.k[0], cache.v[0])

    def layer(kind, pos, carry, inputs):
        x, r = (carry, None) if r0 is None else carry
        if shared0:
            x, mem, ks, vs = carry
        cross = pos is not None and pos.mixer == "cross"
        window, rotary = kind
        lp, *kv = (inputs,) if cross else inputs
        lp = _cast(cfg, lp)
        h = _norm(cfg, lp["ln1"], x)
        # q: [B, Hq, D]; k/v: [B, Hkv, D]
        if cross:
            q, _, _ = _pack_qkv(
                cfg, _q_only(cfg, lp["attn"], h, cos, sin), None, None)
            kc, vc, cc = ks, vs, ()
        else:
            kc, vc, *cc = kv
            if cfg.cca is not None:
                q, k, v, new = _cca_qkv_step(
                    cfg, lp["attn"], h, cos, sin, positions, cc[0], active)
                cc = (new,)
            else:
                q, k, v = _dense_qkv(cfg, *_qkv_roped(
                    cfg, lp["attn"], h, cos, sin, rotary))
            # write new K/V at write_at (only for active slots)
            slot = jnp.arange(kc.shape[1])[None, :, None, None]  # [1, S, 1, 1]
            put = (slot == write_at[:, None, None, None]) & active[:, None, None, None]
            kc = jnp.where(put, k[:, None].astype(kc.dtype), kc)
            vc = jnp.where(put, v[:, None].astype(vc.dtype), vc)
            if shared0 and pos.exports:
                ks, vs = kc, vc
        ctx = attn_ops.decode_attention(
            q,
            kc,
            vc,
            new_lens,
            softmax_scale=cfg.softmax_scale,
            soft_cap=cfg.attn_logits_soft_cap,
            sliding_window=window,
        )
        ctx = _dense_ctx(cfg, _attn_params(lp), ctx)
        x = _add_branch(
            cfg, lp, "attn_out_ln", x,
            _attn_out(lp["attn"], ctx.astype(x.dtype), h))
        x, _, _, r = _ffn(cfg, lp, x, h, router_state=r)
        if shared0:
            return (x, mem, ks, vs), (None if cross else (kc, vc))
        return (x if r0 is None else (x, r)), (kc, vc, *cc)

    def ssm_layer(carry, inputs):
        x, *shared = carry if shared0 else (carry,)
        lp, s, cv = inputs
        x, st, mem, _ = _ssm_block(
            cfg, _cast(cfg, lp), x,
            _rec_step(cfg, (s, cv), active, memory=bool(shared0)))
        return ((x, mem, *shared[1:]) if shared0 else x), st

    def attn_fn(pos):
        return functools.partial(layer, (pos.window, cfg.apply_rotary), pos)

    x, (ks, vs, *cc), ssm, _ = _run_stack(
        cfg,
        [functools.partial(layer, kind, None) for kind in cfg.layer_kinds],
        (x, *shared0) if shared0 else (x if r0 is None else (x, r0)), params,
        xs=(cache.k, cache.v) + (
            (cache.ssm.carry,) if cfg.cca is not None else ()),
        fns=_plan_fns(cfg, attn_fn, ssm_layer),
        plan_xs=None if cfg.recurrent is None else {
            "attn": (cache.k, cache.v),
            cfg.recurrent: tuple(jax.tree.leaves(cache.ssm))},
    )
    if r0 is not None or shared0:
        x, *_ = x
    if cc:
        ssm = tuple(cc)
    cache = KVCache(
        k=ks, v=vs, lens=new_lens,
        ssm=None if ssm is None else type(cache.ssm)(*ssm))
    x = _norm(cfg, _cast(cfg, params["final_ln"]), x)
    return _head(cfg, params, x), cache


# --------------------------------------------------------------------------- #
# Paged KV generation (page-pool cache; see areal_tpu/gen/pages.py)
# --------------------------------------------------------------------------- #


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKVCache:
    """KV page pool: ``pages [L, P, 2, Hkv, page, D]`` — K and V INTERLEAVED
    per page (index 0 = K, 1 = V), so one page is ONE contiguous block and
    the decode kernel fetches a page's K and V with a single DMA, and the
    HEAD dim comes before the token dim so pages DMA straight into the
    kernel's ``[Hkv, S, D]`` compute layout with NO in-VMEM transpose
    (per-body relayouts of the KV block, not bandwidth or DMA count,
    bounded scattered-page decode — measured round 3). Slot state (page
    tables, lengths) lives with the generation engine — the pool itself
    has no per-sequence structure, which is exactly what lets prompts
    share pages (counterpart of SGLang's radix-cache memory, SURVEY
    §2.1).

    How fresh tokens get in (:func:`_write_chunk_kv`, once a step after
    the layer scan): the head-before-token layout that spares the decode
    kernel its transpose makes a token's rows STRIDED, ``S * Hkv`` rows of
    ``D`` a layer, and on a TPU v5e XLA's row scatter pays 70 ns for each
    of them whatever their number (1.0 ms of a 16.6 ms decode step at
    1.5B widths, 3-6 % of every K/V rollout cell; ledger, PR 30). So on
    the chip a raw-dtype pool on one device is written by the
    ``kv_page_write`` kernel (``ops/pallas/kv_page_write.py``): the pool's
    16-row tile of all streams and heads at once, read, merged and
    written back IN PLACE, many in flight. The layout stays. An int8 pool
    (a second array with another tile), a pool under a mesh and every
    other platform keep the XLA scatter, :func:`_scatter_chunk_kv`, which
    is also the kernel's plain reference.

    ``scales`` (int8 mode, docs/performance.md "KV quantization"): pages
    store int8 values and a parallel ``[L, P, 2, Hkv, page]`` f32 array
    carries one dequant scale per (page slot, kv head) — page-structured
    exactly like the pool, so page tables, TP's kv-head sharding, and
    radix prefix sharing address both arrays with the same indices and
    shared pages share their scales for free. Quantization happens at the
    post-scan scatter (:func:`_scatter_chunk_kv`, which an int8 pool
    always takes); dequant is fused into
    every paged-attention entry point so int8 pages are read straight from
    HBM and widened in-register — a bf16 copy of the pool never exists.
    ``scales is None`` = raw serving-dtype pages (the default).

    A LATENT pool (``cfg.mla``, :func:`kv_page_geometry`): ``pages [L, P,
    1, 1, page, W]``, ONE stream and no head axis (the two unit axes keep
    the page walk, the scatter and the kernel's copies those of the K/V
    pool). A token's row in a layer is ``[c_kv ; k_rope ; 0]``, ``W =``
    :func:`latent_pool_width`: key for all query heads and, in its first
    ``kv_lora_rank`` values, their value. Always in the serving dtype.

    LAYER KINDS (``cfg.layer_pattern``: window and full layers in one
    stack): the leading axis is the model's PERIODS, not its layers. A
    page holds ``page`` tokens of ONE position of the period in EVERY
    period (``pages [L / p, P, 2, Hkv, page, D]``), so every page has one
    byte size whatever kind of layer it serves, ONE free list feeds all
    kinds, and how the pool's bytes divide between kinds is decided by the
    traffic. A slot has one page table a position of the period (``table
    [p, B, M]`` where a model of one kind has ``[B, M]``): layer ``l``
    reads and writes ``pages[l // p]`` through ``table[l % p]``. What that
    buys: a window position's page that lies wholly behind ``len -
    window`` is of no further use to its slot and goes back to the free
    list while the request still runs (``gen/engine.py``); nothing here
    reads a table entry before a row's first visible position. A model of
    one kind is the same layout with ``p = 1``.

    A STACK PLAN (``cfg.stack_plan``): the same layout over the plan's
    CACHE layers, its self-attention layers in the order they run (state-
    space layers, gated memory units and cross-attention layers hold
    none). Their kinds are the shortest period of their windows
    (``cfg.layer_kinds``): where they are alike, one table and a page the
    same positions in every cache layer (``granitemoehybrid``); where
    eight window layers stand before ONE full layer (``phi4flash``) the
    period is all nine, one period deep (``pages [1, P, 2, rows, page,
    width]``, a page ``page`` positions of one cache layer, a table a cache
    layer ``[9, B, M]``), so the window layers give their pages back behind
    the window while the full layer's stay. A cross-attention layer reads
    its source layer's pages through that layer's table with its own
    queries and writes nothing (:func:`_pool_of`).

    A LOOPED stack (``cfg.n_passes``: the layers run several times over
    one set of weights): a token's key and value of a layer differ from
    pass to pass, so the leading axis is ``cfg.cache_layers = n_passes x
    n_layers`` behind ``n_layers`` layers of weights, pass-major: pass
    ``t``, layer ``l`` reads and writes ``pages[t * n_layers + l]``
    (:func:`_scan_passes`' running ``li``). One page table a slot, one
    page the same positions in every cache layer: the engine, the prefix
    registry and both kernels take the axis as given."""

    pages: jnp.ndarray
    scales: Optional[jnp.ndarray] = None

    @property
    def quantized(self) -> bool:
        return self.scales is not None

    @classmethod
    def empty(
        cls,
        cfg: ModelConfig,
        n_pages: int,
        page_size: int,
        kv_dtype: Optional[str] = None,
    ) -> "PagedKVCache":
        """``kv_dtype``: normalized pool storage dtype — ``"int8"`` builds
        the quantized pool + scales pair, anything else (None) stores raw
        ``cfg.dtype`` pages."""
        streams, heads, width = kv_page_geometry(cfg)
        shape = (cfg.n_periods, n_pages, streams, heads, page_size, width)
        if kv_dtype == "int8":
            if cfg.mla is not None:
                raise ValueError("a latent page pool cannot be int8")
            return cls(
                pages=jnp.zeros(shape, jnp.int8),
                scales=jnp.zeros(shape[:-1], jnp.float32),
            )
        return cls(pages=jnp.zeros(shape, jnp.dtype(cfg.dtype)))


def kv_page_geometry(cfg: ModelConfig) -> Tuple[int, int, int]:
    """``(streams, heads, width)`` of what the page pool holds of one token
    in one layer, as the model declares it: a K and a V of ``n_kv_heads x
    head_dim``, or one latent row (:func:`latent_pool_width`). Pool bytes,
    page counts and the kernel's block plan follow this, not ``2 * Hkv *
    D``."""
    if cfg.mla is not None:
        return 1, 1, latent_pool_width(cfg)
    r = cfg.kv_heads_per_row
    return 2, cfg.n_kv_heads // r, cfg.head_dim * r


def _write_chunk_kv(
    cache: PagedKVCache, ks, vs, table, start, count,
    use_pallas: Optional[bool] = None, mesh=None,
) -> PagedKVCache:
    """Every layer's fresh K/V into the pool, ONCE, after the layer scan:
    row ``b``'s tokens ``c < count[b]`` of the chunk land at positions
    ``start[b] + c`` of its pages (``count`` 0: a free or finished slot, a
    padding row; nothing of it is written). Both step functions end
    here: one token a row (``decode_step_paged``), admission's chunks
    (``extend_paged``).

    Which path runs where (``ops/paged_attention.py:
    kv_write_kernel_applies``, one predicate over what is observable, no
    flag): on a TPU a raw-dtype pool on one device takes the tile-copy
    kernel ``kv_page_write`` (``ops/pallas/kv_page_write.py``), in place;
    an int8 pool, a pool under a mesh, any other platform and the CPU
    tests take :func:`_scatter_chunk_kv`, the kernel's plain reference,
    which leaves the same bits."""
    from areal_tpu.ops import paged_attention as paged_ops

    if table.ndim == 3:
        # a table a position of the period: the fresh K/V of the layers at
        # position j of every period land in the pages of table j
        p = table.shape[0]

        def of_kind(a, j):
            if a is None:
                return None
            return a.reshape(a.shape[0] // p, p, *a.shape[1:])[:, j]

        # the kinds are alike in shape, and what a kind costs a START is
        # tracing and lowering the kernel (0.8 s on a chip's host): under
        # ``jit`` that is paid for the first kind and found again for the
        # others (nine cache layers: 7.2 s of tracing a write program and
        # 6 of a decode chunk's 9.4; PERF.md section 6, PR 48); the
        # compiler inlines the calls: the same kernel calls, and the
        # index arithmetic the kinds share computed once
        write_kind = _write_kind_jit if paged_ops.kv_write_kernel_applies(
            use_pallas, cache.pages, cache.quantized, mesh
        ) else _write_chunk_kv
        for j in range(p):
            cache = write_kind(
                cache, of_kind(ks, j), of_kind(vs, j), table[j], start,
                count, use_pallas, mesh,
            )
        return cache
    pages = cache.pages
    if not paged_ops.kv_write_kernel_applies(
        use_pallas, pages, cache.quantized, mesh
    ):
        return _scatter_chunk_kv(cache, ks, vs, table, start, count)
    from areal_tpu.ops.pallas import kv_page_write

    fresh = jnp.stack([ks] if vs is None else [ks, vs], axis=3)
    return PagedKVCache(
        pages=kv_page_write.write(pages, fresh, table, start, count)
    )


_write_kind_jit = jax.jit(_write_chunk_kv, static_argnums=(6, 7))


def _scatter_chunk_kv(cache: PagedKVCache, ks, vs, table, start, count):
    """ONE XLA scatter of every layer's fresh K/V into the pool: the plain
    reference of the ``kv_page_write`` kernel and the path of everything
    that kernel does not take (:func:`_write_chunk_kv`).

    ks/vs ``[L, B, C, Hkv, D]``; ``start``/``count`` ``[B]``: chunk token
    ``c`` of row ``b`` is position ``start[b] + c`` and is written where
    ``c < count[b]``. Runs AFTER the
    layer scan — the pool never rides the scan carry (which streamed the
    whole multi-GB pool through stacked scan outputs every step; measured
    ~30 ms/step at a 1.5B/64-slot decode, round-3 xprof).

    What it costs on a TPU v5e: the scatter pays per ROW, serially, 70 ns
    for every 256-byte row whatever the row count, and the bytes are
    nothing (ledger, PR 30, one decode step: 28 x 128 x 2 x 2 = 14,336
    rows in 1.00 ms at 1.5B; 16 x 64 x 2 x 4 = 8,192 in 0.58 ms at 7B-l16;
    8 x 64 x 2 x 16 = 16,384 in 1.14 ms at OLMoE-l8: 70 / 70 / 69 ns a row
    for 3.7 MB that the chip's HBM moves in 4.5 us). That is why the chip
    does not run it where the kernel applies.

    The scatter runs on a FLAT ``[L*P*2*Hkv*page, D]`` row view: flattening
    every dim but the minor one is a layout-preserving bitcast, and a 2D
    row scatter keeps the default layout — the earlier multi-dim scatter
    was assigned a PERMUTED pool layout by XLA, forcing two full-pool
    relayout copies per decode step around the (default-layout) attention
    kernel (~11 ms/step at a 1.5B/64-slot profile; HLO ``copy.14/.27``).

    Int8 mode (``cache.scales`` present): each token's K/V row quantizes
    symmetrically over its head_dim (scale = amax/127 per (token, kv head,
    K|V)) and the scale lands in the parallel scales array through the
    SAME flat row indices — one extra [rows] scatter of scalars, no
    second index computation. Per-row scales make incremental page fills
    exact: a new token never forces requantizing its page's earlier
    residents.

    A latent pool has one stream: ``ks`` is the latents ``[L, B, C, 1, W]``
    and ``vs`` is ``None``."""
    L, B, C, Hkv, D = ks.shape
    P, S_, _, page = cache.pages.shape[1:5]
    M = table.shape[1]
    positions = start[:, None] + jnp.arange(C)[None, :]
    valid = jnp.arange(C)[None, :] < count[:, None]
    page_idx = jnp.take_along_axis(
        table, jnp.clip(positions // page, 0, M - 1), axis=1
    )                                                   # [B, C]
    off = positions % page                              # [B, C]
    dt = cache.pages.dtype
    if cache.scales is not None:
        kf = ks.astype(jnp.float32)
        vf = vs.astype(jnp.float32)
        amax = jnp.stack(
            [jnp.max(jnp.abs(kf), axis=-1), jnp.max(jnp.abs(vf), axis=-1)],
            axis=3,
        )                                               # [L, B, C, 2, Hkv]
        scale = jnp.where(amax > 0.0, amax / 127.0, 1.0)
        kv = jnp.clip(
            jnp.round(
                jnp.stack([kf, vf], axis=3) / scale[..., None]
            ),
            -127.0, 127.0,
        ).astype(jnp.int8)                              # [L, B, C, 2, Hkv, D]
    else:
        scale = None
        kv = jnp.stack(
            [ks] if vs is None else [ks, vs], axis=3
        ).astype(dt)                                    # [L, B, C, 2, Hkv, D]
    # flat row = (((l*P + p)*2 + kv)*Hkv + h)*page + off
    n_rows = L * P * S_ * Hkv * page
    base = page_idx[None] + P * jnp.arange(L)[:, None, None]     # [L, B, C]
    kvi = jnp.arange(S_)[None, None, None, :, None]
    hi = jnp.arange(Hkv)[None, None, None, None, :]
    rows = ((base[..., None, None] * S_ + kvi) * Hkv + hi) * page \
        + off[None, :, :, None, None]                   # [L, B, C, 2, Hkv]
    rows = jnp.where(valid[None, :, :, None, None], rows, n_rows)  # => drop
    flat = cache.pages.reshape(n_rows, D)
    flat = flat.at[rows].set(kv, mode="drop")
    if scale is None:
        return PagedKVCache(pages=flat.reshape(cache.pages.shape))
    flat_s = cache.scales.reshape(n_rows)
    flat_s = flat_s.at[rows].set(scale, mode="drop")
    return PagedKVCache(
        pages=flat.reshape(cache.pages.shape),
        scales=flat_s.reshape(cache.scales.shape),
    )


def _extend_layers(
    params: Params,
    cfg: ModelConfig,
    cache: PagedKVCache,
    tokens: jnp.ndarray,     # [B, C]
    table: jnp.ndarray,      # [B, M]
    start: jnp.ndarray,      # [B]
    n_new: jnp.ndarray,      # [B]
    skip_pool: bool = False,
    moe_grouped: bool = False,
    ssm: Optional[Any] = None,
    slots: Optional[jnp.ndarray] = None,
):
    """The multi-token layer scan over the page pool (chunked prefill).
    Returns ``(ks, vs, ssm_rows)``; the caller writes the KV. Under a
    stack plan with gated memory units and cross attention the segments
    behind the last layer that writes a cache or a state are NOT run:
    admission keeps nothing of them (no logits are computed here: the last
    prompt token is fed to the first decode step), which is the
    decoder-hybrid-decoder's saving at prefill, whole.
    ``moe_grouped`` (STATIC): the routed experts run as the
    grouped-matmul kernel over the whole stack (``ops/moe.py``; the caller
    asks ``moe_grouped_applies``).

    State-space layers (``cfg.ssm``): row ``b`` continues the state of
    slot ``slots[b]`` of ``ssm`` (the engine's per-slot state, read and
    not written here) over its ``n_new[b]`` tokens; a token at position 0
    starts from nothing whatever the slot held. ``ssm_rows``: ``(ssm [Ls,
    B, G, K, N, 128], conv [Ls, B, (d_conv - 1) x C])`` after them, for the
    caller to put back; None for a model without such layers.

    Attention inside a convolved latent (``cfg.cca``): the same, with
    ``ssm`` the per-slot carry (:class:`CCAState`) and ``ssm_rows`` ``(carry
    [L, B, W],)``."""
    from areal_tpu.ops import paged_attention as paged_ops

    routed = None
    if moe_grouped:
        params, routed = _hold_routed(params)

    B, C = tokens.shape
    positions = start[:, None] + jnp.arange(C)[None, :]
    x = _embed(cfg, params, tokens, positions)
    cos, sin = _cos_sin(cfg, positions)
    kinds = cfg.layer_kinds

    def _attend(q, k, v, li, j, pos=None):
        kw = dict(
            softmax_scale=_attn_scale(cfg),
            soft_cap=cfg.attn_logits_soft_cap,
            sliding_window=kinds[j][0] if pos is None else pos.window,
            scales=cache.scales,
        )
        li, tbl = _pool_of(cfg, table, li, j, pos)
        scope = _plan_scope(cfg, pos)
        if scope is None:
            return paged_ops.paged_extend_attention(
                q, k, v, cache.pages, li, tbl, start, n_new,
                skip_pool=skip_pool, **kw,
            )
        with jax.named_scope(scope):
            return paged_ops.paged_extend_attention(
                q, k, v, cache.pages, li, tbl, start, n_new,
                skip_pool=skip_pool, **kw,
            )

    shared0 = _shared0(cfg, (B, C), x.dtype)

    def ssm_layer(carry, lp):
        x, li, si, *shared = carry
        # the barrier keeps the rows' gather a result of its own: the
        # gather of ONE row is a slice to the chip's compiler, and the
        # layout the scan's matmuls ask of that slice it then gave to the
        # whole state, a copy of all 36 layers of it (PERF.md §6 PR 42)
        state_all, conv_all = jax.tree.leaves(ssm)
        rows = jax.lax.optimization_barrier(state_all[si, slots])
        x, st, mem, _ = _ssm_block(
            cfg, _cast(cfg, lp), x,
            _rec_chunk(cfg, positions, (rows, conv_all[si, slots]),
                       n_valid=n_new, memory=bool(shared0)),
            # (a delta-rule layer's experts: its index in THEIR stacks)
            routed=None if routed is None or cfg.kda is None else (
                routed["kda"], si))
        if shared0:
            shared = (mem, *shared[1:])
        return (x, li, si + 1, *shared), st

    def layer(j, carry, lp, pos=None):
        # ``li``: which slice of the pool's leading axis the layer's pages
        # are in: its layer, or (layer kinds) its period (``rest``: the
        # running index of the state-space layers, which have their own;
        # under a plan ``li`` is the running cache layer, :func:`_pool_of`)
        x, li, *rest = carry                          # pool NOT in the scan
        lp = _cast(cfg, lp)
        h = _norm(cfg, lp["ln1"], x)
        cc = None
        cross = pos is not None and pos.mixer == "cross"
        if cfg.mla is not None:
            # absorbed form, chunk and pool alike: multi-query attention
            # of [B, C, H, W] queries over the latents, whose head is the
            # value (``vs`` stays None: the pool has one stream)
            q, latent = _mla_absorbed(cfg, lp["attn"], h, cos, sin)
            k, v = latent[..., None, :], None
            ctx = _attend(q, k, k[..., : cfg.mla.kv_lora_rank], li, j)
            ctx = _mla_absorbed_out(cfg, lp["attn"], ctx)
        elif cfg.cca is not None:
            # the rows continue their slots' carry (a token at position 0
            # starts from nothing whatever the slot held)
            q, k, v, cc = _cca_qkv_roped(
                cfg, lp["attn"], h, cos, sin, positions,
                ssm.carry[li, slots], n_new)
            ctx = _attend(q, k, v, li, j)
        elif cross:
            # the layer's own queries over the shared K/V: the pool's
            # pages of the source layer and, of this chunk, what that
            # layer left in the carry
            q, _, _ = _pack_qkv(
                cfg, _q_only(cfg, lp["attn"], h, cos, sin), None, None)
            ctx = _unpack_ctx(
                cfg, _attend(q, *rest[-2:], li, j, pos), _attn_params(lp))
        else:
            # [B, C, H(kv), D]
            q, k, v = _pack_qkv(cfg, *_qkv_roped(
                cfg, lp["attn"], h, cos, sin,
                kinds[j][1] if pos is None else cfg.apply_rotary))
            ctx = _unpack_ctx(
                cfg, _attend(q, k, v, li, j, pos), _attn_params(lp))
            if pos is not None and pos.exports:
                rest = (*rest[:-2], k, v)
        x = _add_branch(
            cfg, lp, "attn_out_ln", x,
            _attn_out(lp["attn"], ctx.astype(x.dtype), h))
        x, _, _, r = _ffn(
            cfg, lp, x, h, _routed_at(cfg, routed, li, j),
            router_state=None if r0 is None else rest[0])
        if r0 is not None:
            rest = (r,)
        if cross:
            return (x, li, *rest), None
        step = int(j == len(kinds) - 1) if pos is None else 1
        return (x, li + step, *rest), (k, v, cc)

    zero = jnp.int32(0)
    # after ``x`` and ``li``: a stateful router's vector, or the running
    # index of the state-space layers (no model has both), and behind
    # that what a plan's readers read (:func:`_shared0`)
    r0 = _router_state0(cfg, x)
    rest0 = () if r0 is None else (r0,)

    def attn_fn(pos):
        return functools.partial(layer, 0, pos=pos)

    _, (ks, vs, cc), ssm_rows, _ = _run_stack(
        cfg, [functools.partial(layer, j) for j in range(len(kinds))],
        (x, zero, *rest0) if cfg.recurrent is None else (
            x, zero, zero, *shared0),
        params,
        fns=_plan_fns(cfg, attn_fn, ssm_layer, routed=routed),
        writers_only=True,
    )
    return ks, vs, ssm_rows if cc is None else (cc,)


def _kind_table(table, j: int):
    """The page table of position ``j`` of the period: ``table [p, B, M]``
    of a model with layer kinds, or the one table ``[B, M]``."""
    return table[j] if table.ndim == 3 else table


def extend_paged_kv(
    params: Params,
    cfg: ModelConfig,
    cache: PagedKVCache,
    tokens: jnp.ndarray,     # [B, C] chunk of prompt tokens
    table: jnp.ndarray,      # [B, M] page table
    start: jnp.ndarray,      # [B] tokens already resident per slot
    n_new: jnp.ndarray,      # [B] valid tokens in this chunk (<= C)
    skip_pool: bool = False,
    moe_grouped: bool = False,
    ssm: Optional[Any] = None,
    slots: Optional[jnp.ndarray] = None,
):
    """Chunked prefill, the computing half: attend the chunk causally over
    everything resident (pool part + intra-chunk part, merged inside the
    op) and return every layer's fresh ``(ks, vs)`` ``[L, B, C, Hkv, D]``
    (``vs`` None for a latent pool); the pool is read, not written
    (:func:`_write_chunk_kv` is the other half, and the engine runs it as
    a program of its own, ``gen/engine.py:_kv_write_fn``). Logits are not
    computed: admission feeds the last prompt token to the first decode
    step instead. ``skip_pool`` (STATIC): every row starts at position 0,
    so the pool scan is dead weight (see ``paged_extend_attention``).
    ``moe_grouped`` (STATIC): see :func:`_extend_layers`. ``ssm``,
    ``slots`` (a model with state-space layers): the per-slot state and
    each row's slot; the result is then ``(ks, vs, ssm_rows)``, the rows'
    state after the chunk (:func:`_extend_layers`)."""
    ks, vs, ssm_rows = _extend_layers(
        params, cfg, cache, tokens, table, start, n_new, skip_pool=skip_pool,
        moe_grouped=moe_grouped, ssm=ssm, slots=slots,
    )
    if ssm_rows is not None:
        return ks, vs, ssm_rows
    return ks, vs


def extend_paged(
    params: Params,
    cfg: ModelConfig,
    cache: PagedKVCache,
    tokens: jnp.ndarray,
    table: jnp.ndarray,
    start: jnp.ndarray,
    n_new: jnp.ndarray,
    skip_pool: bool = False,
    use_pallas: Optional[bool] = None,
    mesh=None,
    ssm: Optional[Any] = None,
    slots: Optional[jnp.ndarray] = None,
    moe_grouped: bool = False,
):
    """Both halves of chunked prefill in one call: :func:`extend_paged_kv`,
    then the chunk's KV into the pages (:func:`_write_chunk_kv`, whose
    path ``use_pallas`` / ``mesh`` choose; the chunk's attention is
    XLA's). With per-slot state (state-space layers, a convolved
    latent's carry): ``(cache, ssm)``, the rows' state put back at
    ``slots``."""
    ks, vs, *rows = extend_paged_kv(
        params, cfg, cache, tokens, table, start, n_new, skip_pool=skip_pool,
        ssm=ssm, slots=slots, moe_grouped=moe_grouped,
    )
    cache = _write_chunk_kv(
        cache, ks, vs, table, start, n_new, use_pallas, mesh
    )
    if not rows:
        return cache
    return cache, put_ssm_rows(ssm, slots, *rows)


def put_ssm_rows(ssm, slots, rows):
    """``rows`` (the arrays of the per-slot state, :class:`SSMState`'s
    ``(ssm, conv)`` or :class:`CCAState`'s ``(carry,)``, over ``[L, n,
    ...]``) into the per-slot state at ``slots [n]``; a slot index past
    the last is dropped (a padding row)."""
    return jax.tree.map(
        lambda a, v: a.at[:, slots].set(v.astype(a.dtype), mode="drop"),
        ssm, type(ssm)(*rows))


def _length_order(lens: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(order, inverse)``: the batch's rows by ascending resident length
    (stable: equal lengths keep slot order, so a batch of one length is
    left as it is) and the permutation that puts them back."""
    order = jnp.argsort(lens)
    return order, jnp.argsort(order)


def decode_step_paged(
    params: Params,
    cfg: ModelConfig,
    cache: PagedKVCache,
    tokens: jnp.ndarray,       # [B] current tokens
    table: jnp.ndarray,        # [B, M]
    lens: jnp.ndarray,         # [B] resident tokens (write position)
    active: jnp.ndarray,       # [B] bool
    use_pallas: Optional[bool] = None,
    mesh=None,
    return_hidden: bool = False,
    with_routing: bool = False,
    moe_grouped: bool = False,
    ssm: Optional[Any] = None,
    ssm_update=None,
) -> Tuple[jnp.ndarray, PagedKVCache, jnp.ndarray]:
    """One decode step over the page pool. Returns (fp32 logits ``[B, V]``,
    cache, new lens — incremented where active). The pool is read-only in
    the layer scan; each layer's fresh K/V merges into attention as the
    self token and lands in the pool in one post-scan write
    (:func:`_write_chunk_kv`: the tile-copy kernel on the chip, the XLA
    scatter elsewhere).

    The layer scan runs on the rows ORDERED BY ``lens`` (one sort a step,
    hoisted out of the scan): the paged kernel works through every block
    of consecutive rows as far as its longest row reaches
    (``ops/pallas/paged_attention.py``), so rows of like length share a
    block. A row's result does not depend on its neighbours; the hidden
    states and the fresh K/V go back to slot order after the scan, and
    everything the caller sees is in slot order.

    ``use_pallas`` threads through to the attention dispatch. ``mesh``
    (TP serving) routes the kernel through ``shard_map`` over the kv-head
    axis — each model shard runs Pallas on its local pool slice —
    because bare ``pallas_call`` has no GSPMD partitioning rule and would
    otherwise force a full-pool all-gather.

    ``return_hidden=True`` (STATIC) returns the final-norm HIDDEN states
    ``[B, E]`` in place of logits for the fused epilogue
    (``ops/fused_sample.py``; the engine asks for it where
    ``fused_sample_applies`` says so), which streams the head itself — the
    ``[B, V]`` logits never materialize.

    ``with_routing=True`` (STATIC, MoE models) appends a fourth result:
    the experts every slot's token chose in every layer that has a router
    (``cfg.n_moe_layers``: leading dense layers have none), int32
    ``[L, B, top_k]`` in slot order, free and finished slots included
    (they run through the experts like any other row).

    ``moe_grouped`` (STATIC): the routed experts run as the grouped-matmul
    kernel over the whole stack (``ops/moe.py``; the engine asks
    ``moe_grouped_applies`` with the rows of this step).

    ``ssm`` (a model with state-space layers): the per-slot state, row
    ``b`` slot ``b``'s. It rides the layer scan's CARRY and every
    state-space layer reads and writes its own slice of it in place (a
    donated argument stays one buffer); rows that are not ``active`` keep
    theirs. The step's rows stay in slot order for such a model (the state
    is by slot; its attention layers are a twentieth of the step). The
    new state is appended to the result. ``ssm_update``: what stands in
    for ``ops/ssm.py:step_update`` on the state of ALL layers
    (``update(ssm_all, layer, x, dt, a, b, c, d, active=) -> (y,
    ssm_all)``: the ``ssm_decode`` kernel).

    Attention inside a convolved latent (``cfg.cca``): ``ssm`` is the
    per-slot carry (:class:`CCAState`). Its rows go through the scan in
    the step's length order with the layers' weights (each layer reads
    and returns its own ``[B, W]``), rows that are not ``active`` keep
    theirs, and the new carry is appended to the result in slot order."""
    from areal_tpu.ops import paged_attention as paged_ops

    routed = None
    if moe_grouped:
        params, routed = _hold_routed(params)
    new_lens = jnp.where(active, lens + 1, lens)
    kinds = cfg.layer_kinds
    # the scan's rows, by length (``_o``); slot order again after it. Where
    # the kernel runs and the step orders its own rows, what the full
    # layers' table shows of rows that name the same pages is observed
    # here, once: those pages go through the prefix pass, ``own_o`` is what
    # is left of each row, and the rows go by THAT length (by length under
    # window layers, which walk the whole row)
    full = [j for j, (window, _) in enumerate(kinds) if window is None]
    prefix = own_o = None
    n_kv, page, width = cache.pages.shape[3:]
    if paged_ops.shared_prefix_applies(
        use_pallas, width, n_kv, page, cache.pages.dtype,
        full_kinds=len(full), quantized=cache.scales is not None,
        latent=cfg.mla is not None, slot_order=cfg.recurrent is not None,
        mesh=mesh,
    ):
        shared_table = _kind_table(table, full[0])
        plan, *own = paged_ops.shared_prefix_step(
            shared_table, lens, active, page)
        order, inverse = _length_order(
            own[1] if len(full) == len(kinds) else lens)
        prefix = paged_ops.prefix_pass(
            plan, shared_table, page, order, inverse)
        own_o = [a[order] for a in own]
    elif cfg.recurrent is None:
        order, inverse = _length_order(lens)
    else:
        order = inverse = jnp.arange(lens.shape[0])
    table_o = table[order] if table.ndim == 2 else table[:, order]
    lens_o = lens[order]
    x = _embed(cfg, params, tokens[order], lens_o)    # [B, E]
    cos, sin = _cos_sin(cfg, lens_o)

    def attend(q, k, v, li, tbl, **kw):
        if prefix is not None and kw["sliding_window"] is None:
            tbl, n = own_o
            kw["shared"] = prefix
        else:
            n = lens_o
        return paged_ops.paged_decode_attention(
            q, k, v, cache.pages, li, tbl, n, **kw)

    def ssm_layer(carry, lp):
        x, li, si, st, *shared = carry
        state_all, conv_all = jax.tree.leaves(st)
        conv_l = jax.lax.dynamic_index_in_dim(conv_all, si, 0, keepdims=False)
        if ssm_update is None:
            ssm_l = jax.lax.dynamic_index_in_dim(
                state_all, si, 0, keepdims=False)
            update = None
        else:
            # the kernel takes the state of all layers and the layer's
            # index, and gives the whole back, updated in place
            ssm_l = state_all

            def update(whole, *args, **kw):
                return ssm_update(whole, si, *args, **kw)

        x, (ssm_l, conv_l), mem, (_, chosen) = _ssm_block(
            cfg, _cast(cfg, lp), x,
            _rec_step(cfg, (ssm_l, conv_l), active, update=update,
                      memory=bool(shared0)),
            routed=None if routed is None or cfg.kda is None else (
                routed["kda"], si))
        if ssm_update is None:
            ssm_l = jax.lax.dynamic_update_index_in_dim(
                state_all, ssm_l, si, 0)
        st = type(st)(
            ssm_l, jax.lax.dynamic_update_index_in_dim(conv_all, conv_l, si, 0))
        if shared0:
            shared = (mem, *shared[1:])
        return (x, li, si + 1, st, *shared), (
            None, None, chosen if with_routing else None)

    def layer(j, carry, lp, pos=None):
        # ``li``: the layer, or (layer kinds) the period: the slice of the
        # pool's leading axis that holds the layer's pages (under a plan
        # the running cache layer, :func:`_pool_of`)
        x, li, *rest = carry                          # pool NOT in the scan
        window, rotary = kinds[j] if pos is None else (
            pos.window, cfg.apply_rotary)
        cc = None
        cross = pos is not None and pos.mixer == "cross"
        if cfg.cca is not None:
            lp, cc = lp
        lp = _cast(cfg, lp)
        h = _norm(cfg, lp["ln1"], x)
        kw = dict(
            softmax_scale=_attn_scale(cfg),
            soft_cap=cfg.attn_logits_soft_cap,
            sliding_window=window,
            use_pallas=use_pallas,
            mesh=mesh,
            scales=cache.scales,
        )
        if cfg.mla is not None:
            # absorbed form straight from the latent pages: q [B, H, W]
            # against ONE stream that is key and value (``mla_decode``)
            q, latent = _mla_absorbed(cfg, lp["attn"], h, cos, sin)
            k, v = latent[:, None], None
            ctx = paged_ops.paged_decode_attention(
                q, k, None, cache.pages, li, table_o, lens_o,
                value_width=cfg.mla.kv_lora_rank, **kw,
            )
            ctx = _mla_absorbed_out(cfg, lp["attn"], ctx)
        elif cfg.cca is not None:
            q, k, v, cc = _cca_qkv_step(
                cfg, lp["attn"], h, cos, sin, lens_o, cc, active_o)
            ctx = attend(q, k, v, li, table_o, **kw)
        elif _plan_scope(cfg, pos) is not None:
            # a plan whose attention layers differ: a window, a full or a
            # cross layer, each through its own cache layer's table. A
            # cross layer brings its queries alone: the pages are its
            # source's, this token's K/V what that layer left in the carry
            if cross:
                q, _, _ = _pack_qkv(
                    cfg, _q_only(cfg, lp["attn"], h, cos, sin), None, None)
                k, v = rest[-2:]
            else:
                q, k, v = _pack_qkv(
                    cfg, *_qkv_roped(cfg, lp["attn"], h, cos, sin, rotary))
            pool_i, tbl = _pool_of(cfg, table_o, li, j, pos)
            with jax.named_scope(_plan_scope(cfg, pos)):
                ctx = paged_ops.paged_decode_attention(
                    q, k, v, cache.pages, pool_i, tbl, lens_o, **kw)
            ctx = _unpack_ctx(cfg, ctx, _attn_params(lp))
            if pos.exports:
                rest = (*rest[:-2], k, v)
        elif cfg.layer_pattern is None:
            q, k, v = _pack_qkv(
                cfg, *_qkv_roped(cfg, lp["attn"], h, cos, sin))  # q [B, H, D]
            ctx = _unpack_ctx(
                cfg, attend(q, k, v, li, table_o, **kw), _attn_params(lp))
        else:
            q, k, v = _qkv_roped(cfg, lp["attn"], h, cos, sin, rotary)
            with jax.named_scope(_attn_scope(window)):
                ctx = attend(q, k, v, li, _kind_table(table_o, j), **kw)
        x = _add_branch(
            cfg, lp, "attn_out_ln", x,
            _attn_out(lp["attn"], ctx.astype(x.dtype), h))
        x, _, routing, r = _ffn(
            cfg, lp, x, h, _routed_at(cfg, routed, li, j),
            router_state=None if r0 is None else rest[0])
        if r0 is not None:
            rest = (r,)
        if cross:
            return (x, li, *rest), None
        step = int(j == len(kinds) - 1) if pos is None else 1
        return (
            (x, li + step, *rest),
            (k, v, routing if with_routing else None, cc),
        )

    zero = jnp.int32(0)
    # after ``x`` and ``li``: a stateful router's vector, or the
    # state-space layers' running index and state (no model has both), and
    # behind those what a plan's readers read (:func:`_shared0`)
    r0 = _router_state0(cfg, x)
    rest0 = () if r0 is None else (r0,)
    shared0 = _shared0(cfg, x.shape[:1], x.dtype)
    active_o = active[order]

    def attn_fn(pos):
        return functools.partial(layer, 0, pos=pos)

    (x, *rest), (ks, vs, routing, cc), rec_ys, moe_routing = _run_stack(
        cfg, [functools.partial(layer, j) for j in range(len(kinds))],
        (x, zero, *rest0) if cfg.recurrent is None else (
            x, zero, zero, ssm, *shared0),
        params, xs=() if cfg.cca is None else (ssm.carry[:, order],),
        fns=_plan_fns(
            cfg, attn_fn, ssm_layer, routed=routed,
            moe_ys=(lambda aux, routing: routing) if with_routing else None),
    )
    if moe_routing is not None:
        routing = moe_routing       # the expert blocks': the plan's router
    elif rec_ys is not None and rec_ys[2] is not None:
        routing = _in_layer_order(cfg, routing, rec_ys[2])
    x, ks = x[inverse], ks[:, inverse]
    cache = _write_chunk_kv(
        cache, ks[:, :, None],
        None if vs is None else vs[:, inverse][:, :, None],
        table, lens, active.astype(jnp.int32), use_pallas, mesh,
    )
    if with_routing:
        if routing is None:
            raise ValueError("with_routing: the model has no router")
        extra = (routing[:, inverse],)
    else:
        extra = ()
    if cfg.recurrent is not None:
        extra += (rest[2],)
    if cc is not None:
        extra += (CCAState(cc[:, inverse]),)
    x = _norm(cfg, _cast(cfg, params["final_ln"]), x)
    if return_hidden:
        return (x, cache, new_lens) + extra
    return (_head(cfg, params, x), cache, new_lens) + extra
