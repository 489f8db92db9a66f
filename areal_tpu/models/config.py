"""Model architecture config.

TPU-native counterpart of ``ReaLModelConfig`` (``realhf/api/core/model_api.py:340``)
and ``ReaLMoEConfig`` (``:294``). One dataclass covers every supported HF
family (llama, qwen2, qwen3, mistral, gemma, gpt2, mixtral, olmoe,
joyai_llm_flash, smallthinker, ouro, granitemoehybrid, zaya, phi4flash,
nemotron_h, afmoe, solar_open2) via feature switches, exactly like the reference's single in-house architecture.
"""

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts settings (≈ ``ReaLMoEConfig``)."""

    num_experts: int = 8
    top_k: int = 2
    routed_scaling_factor: float = 1.0
    aux_loss_coeff: float = 0.0
    z_loss_coeff: float = 0.0
    input_jitter_eps: Optional[float] = None
    norm_topk_prob: bool = True
    # What the HF family sets and a user never does (``ops/moe.py``):
    # width of ONE expert (None = the model's ``intermediate_dim``);
    # shared experts, applied to every token and added to the routed sum,
    # as one SwiGLU of ``n_shared_experts * expert_dim``; the router's
    # scores, "softmax" over all logits or "sigmoid" of each; and whether
    # the router carries a correction bias that is added to the scores for
    # the CHOICE of experts only, never to the combine weights.
    expert_dim: Optional[int] = None
    n_shared_experts: int = 0
    scoring: str = "softmax"
    selection_bias: bool = False
    # The router reads the layer's normed INPUT (what attention reads),
    # not the normed residual the experts read (``smallthinker``: "router
    # placed before attention"; every forward hands ``moe_mlp`` that
    # tensor beside the experts' own).
    router_on_layer_input: bool = False
    # The router is an MLP with STATE (``zaya``; ``ops/moe.py:_route_mlp``):
    # a down-projection of the normed residual to ``router_dim``, plus the
    # previous layer's such vector times a learned gain, then an RMSNorm
    # and three GELU layers to the logits. The vector rides every
    # forward's layer scan beside the residual. None: one matmul.
    router_dim: Optional[int] = None
    # One more router output after the experts': a row that chooses it
    # takes NO expert and passes through, scaled by its router weight.
    # ``top_idx == num_experts`` marks it wherever routing is reported.
    skip_expert: bool = False
    # Experts of TWO matrices, ``W_down act(W_up u)``, no gate matrix
    # (``nemotron_h``: squared ReLU); the shared expert likewise.
    gated: bool = True
    # Width of the shared expert where it is not ``n_shared_experts *
    # expert_dim`` (None: that).
    shared_dim: Optional[int] = None
    # LatentMoE: the routed experts live in a latent of this width behind
    # ONE down- and ONE up-projection a layer (``latent_down [E, latent]``,
    # ``latent_up [latent, E]``, no norm, bias or activation): ``W_up sum_j
    # w_j expert_j(W_dn h)``. The router and the shared expert read the
    # hidden-width ``h``. None: the experts read ``h`` itself.
    latent_dim: Optional[int] = None
    # An expert-parallel rank's SHARE: of the ``num_experts`` the router
    # scores, this weight tree holds ``n_held`` (None: all), experts
    # ``held_offset .. held_offset + n_held - 1``. The router keeps its
    # width and its ``top_k``, the combine weights are normalised over
    # everything a row chose, and the routed sum runs over the chosen
    # experts HELD here alone: a partial result, as the rank computes it
    # before the exchange (``ops/moe.py``). Nothing stands in for the
    # absent ranks.
    n_held: Optional[int] = None
    held_offset: int = 0

    @property
    def held(self) -> Tuple[int, int]:
        """``(count, offset)`` of the experts the weight tree holds."""
        n = self.num_experts if self.n_held is None else self.n_held
        return n, self.held_offset

    def shared_width(self, expert_dim: int) -> int:
        """Width of the shared expert (0: none)."""
        if not self.n_shared_experts:
            return 0
        return self.shared_dim or self.n_shared_experts * expert_dim

    @property
    def holds_all(self) -> bool:
        return self.held == (self.num_experts, 0)


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (the ``deepseek_v3`` equations; family
    ``joyai_llm_flash``). Queries come through a latent of ``q_lora_rank``;
    keys and values are up-projections of ONE latent of ``kv_lora_rank`` a
    token, beside one rotary key of ``qk_rope_head_dim`` shared by every
    head. A query/key head is ``[nope ; rope]`` (the model's ``head_dim``
    is their sum), a value head ``v_head_dim``. The rotary pairs are
    ``(2i, 2i+1)`` (``rope_interleave``; the family refuses the other
    pairing, which no published model of it uses)."""

    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def latent_dim(self) -> int:
        """What the cache must hold of a token in a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class CCAConfig:
    """Compressed convolutional attention (family ``zaya``; ``ops/cca.py``
    has the equations). The query and key projections of a token, side by
    side (``latent_dim`` channels, one ``head_dim`` a head), pass through
    two causal convolutions over the sequence, a depthwise one of
    ``time0`` taps and one of ``time1`` taps that mixes the channels of
    each head, before a mean of the two projections is added, each head
    is scaled to a fixed norm (the keys times a learned temperature a kv
    head) and the rotary embedding is applied. The values of the second
    half of the kv heads are the PREVIOUS token's. What a row carries from
    token to token beside its keys and values is therefore the
    convolutions' last inputs and that one value projection:
    ``models/transformer.CCAState``."""

    time0: int = 2
    time1: int = 2


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """State-space layers; ``ops/ssm.py`` has the equations of BOTH
    recurrences, and the HF family chooses (a user never does):

    - ``dt_rank`` None: the Mamba-2 recurrence as ``granitemoehybrid`` lays
      it out. ``n_heads`` heads of ``head_dim``, each with a recurrent
      state of ``[head_dim, d_state]`` that decays by ONE scalar a head and
      token; ``n_groups`` groups of heads share one ``B`` and ``C`` of
      ``d_state``; a causal depthwise convolution of width ``d_conv`` over
      ``[x ; B ; C]``; a gated RMSNorm before the output projection, over
      all of ``d_inner`` or (``norm_per_group``: ``nemotron_h``) over each
      group's ``d_inner / n_groups`` channels by itself.
    - ``dt_rank`` a rank: Mamba-1's selective scan (``phi4flash``). ONE
      head of ``head_dim = d_inner`` channels, each with a state of
      ``d_state`` that decays by its own ``exp(dt[c] A[c, n])``; ``dt``
      comes through a projection of ``dt_rank``; the convolution runs over
      ``x`` alone, and ``B``, ``C`` are read from the convolved ``x``; no
      norm before the output projection.

    ``chunk_size`` is the program's own (any chunking computes the same
    function; the selective scan runs token by token and does not read
    it). ``state_dtype``: what the recurrent state is kept and accumulated
    in; only float32 is supported (a 16-bit state is rounded once a token
    for thousands of tokens while the trainer's scan accumulates in
    float32: another configuration)."""

    n_heads: int
    head_dim: int
    d_state: int
    n_groups: int = 1
    d_conv: int = 4
    chunk_size: int = 256
    conv_bias: bool = True
    proj_bias: bool = False
    state_dtype: str = "float32"
    dt_rank: Optional[int] = None
    norm_per_group: bool = False

    @property
    def selective(self) -> bool:
        """Mamba-1's selective scan (the class docstring)."""
        return self.dt_rank is not None

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: ``[x ; B ; C]``, or ``x``
        alone under the selective scan."""
        if self.selective:
            return self.d_inner
        return self.d_inner + 2 * self.n_groups * self.d_state

    @property
    def in_dim(self) -> int:
        """Width of the input projection: ``[z ; xBC ; dt]``, or ``[x ;
        z]``."""
        if self.selective:
            return 2 * self.d_inner
        return self.d_inner + self.conv_dim + self.n_heads


@dataclasses.dataclass(frozen=True)
class KDAConfig:
    """Gated delta-rule linear attention (family ``solar_open2``; Kimi Delta
    Attention's layout; ``ops/kda.py`` has the equations). ``n_heads``
    heads, each with a recurrent state ``S`` of ``head_dim x head_dim`` (a
    key and a value head are one width, one k/v head a q head) that decays
    by its own factor a key CHANNEL and token and is corrected by a delta
    rule; q, k and v each pass a causal depthwise convolution of ``d_conv``
    taps; the decay's and the output gate's projections go through a rank
    of ``head_dim`` (the published ``kda_use_full_proj: false``).
    ``neg_eigval``: the delta rule's ``beta`` is ``2 x sigmoid``
    (eigenvalues of ``I - beta k k^T`` in [-1, 1]) where it is ``sigmoid``
    otherwise. ``chunk_size`` is the program's own (any chunking computes
    the same function; the tests lower it so that a tiny model's prompt
    crosses chunks). The state is float32 (``ops/kda.py:STATE_DTYPE``)."""

    n_heads: int
    head_dim: int
    d_conv: int = 4
    neg_eigval: bool = False
    chunk_size: int = 64

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolutions run over: ``[q ; k ; v]``."""
        return 3 * self.d_inner


MIXERS = ("ssm", "attn", "gmu", "cross", "moe", "kda")


@dataclasses.dataclass(frozen=True)
class StackPosition:
    """One position of a segment's period, as ``ModelConfig.plan`` resolves
    it: its ``mixer``; an attention layer's ``window`` (None: full) and
    whether later cross-attention layers read the K/V it ``exports``; a
    cross-attention layer's ``source``, the cache layer it reads (an
    attention layer's own cache layer follows the order the layers run:
    the forwards count it). ``"moe"`` is no mixer: a block that is an
    expert layer ALONE (``ModelConfig.one_branch``)."""

    mixer: str
    window: Optional[int] = None
    exports: bool = False
    source: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    n_layers: int
    n_q_heads: int
    n_kv_heads: int
    head_dim: int
    hidden_dim: int
    intermediate_dim: int
    vocab_size: int
    n_positions: int = 32768

    # Norms
    layer_norm_type: str = "rms"      # "rms" | "gemma" (=(1+w) rms) | "layer" (gpt2)
    layer_norm_epsilon: float = 1e-5
    # A norm on each BRANCH's output before it is added to the residual
    # (``ouro``: ``x + norm(attn(norm(x)))``, ``x + norm(mlp(norm(x)))``),
    # four norms a layer: ``layers.attn_out_ln`` / ``layers.mlp_out_ln``
    # beside ``ln1`` / ``ln2``. The HF family sets it, a user never does.
    norm_branch_out: bool = False

    # A LOOPED stack (``ouro``'s ``total_ut_steps``): the ``n_layers``
    # layers run ``n_passes`` times over ONE set of weights, the model's
    # final norm after EVERY pass, the head after the last. A token's key
    # and value of a layer differ from pass to pass, so every cache holds
    # ``cache_layers`` layers of them: pass ``t``, layer ``l`` reads and
    # writes cache layer ``t * n_layers + l``. ``exit_gate``: the family's
    # ``Linear(hidden, 1)`` on each pass's output is part of the weight
    # tree (``params["exit_gate"]``), loaded, carried and written back; no
    # forward reads it (every token takes every pass).
    n_passes: int = 1
    exit_gate: bool = False

    # Attention
    use_attention_bias: bool = False       # qkv projection bias (qwen2, gpt2)
    use_attn_proj_bias: bool = False       # output projection bias (gpt2)
    qk_layernorm: bool = False             # q/k RMSNorm before rotary
    # What one q/k norm spans; the HF family sets it, a user never does.
    # "head": each head's ``head_dim`` after the split into heads, gains
    # ``[L, D]`` (qwen3). "full": the whole projected vector BEFORE the
    # split, gains ``[L, Hq*D]`` / ``[L, Hkv*D]`` (olmoe).
    qk_norm_over: str = "head"
    # Gated attention (``afmoe``): ``W_o (ctx * sigmoid(W_g a))``, a fifth
    # projection ``layers.attn.wg [L, E, Hq * D]`` of the SAME normed input
    # the q/k/v projections read, channel by channel on the heads' context
    # before the output projection. The HF family sets it, a user never does.
    attn_gate: bool = False
    sliding_window: Optional[int] = None
    # A PERIOD of layer kinds, where the layers of one stack differ in what
    # is static about their attention (``smallthinker``): layer ``l`` is
    # kind ``layer_pattern[l % period]``, each kind ``(sliding window or
    # None, rotary or not)``. None = every layer alike (``sliding_window``,
    # ``apply_rotary``). The weight tree is one stack either way (all
    # layers have one shape); the page pool holds a page of one position of
    # the period in every period and a slot has one table a position
    # (``models/transformer.PagedKVCache``, ``gen/engine.py``).
    layer_pattern: Optional[Tuple[Tuple[Optional[int], bool], ...]] = None
    # A stack whose layers differ in their MIXER (``granitemoehybrid``,
    # ``phi4flash``): ``stack_plan`` is its SEGMENTS in the order they run,
    # each ``(repeats, period)``, a period of positions repeated; a
    # position names its mixer and, for "attn", its sliding window (None:
    # full): ``"ssm"`` or ``("attn", 512)``. The mixers:
    #   "ssm"   a state-space layer (``ssm``); its context is a per-SLOT
    #           state (``models/transformer.SSMState``), and its scan
    #           output BEFORE the gate is the MEMORY later "gmu" layers read
    #   "attn"  self attention; the only kind that holds K/V
    #           (``cache_layers`` counts these alone)
    #   "gmu"   a gated memory unit: ``W_out (silu(W_in h) * M)`` with ``M``
    #           the last state-space layer's memory at the same position
    #   "cross" attention with the layer's own queries over the K/V of the
    #           LAST "attn" layer of an earlier segment, which it shares
    #           and never writes
    #   "kda"   a gated delta-rule linear-attention layer (``kda``); its
    #           context is a per-SLOT state too (``DeltaState``). A plan has
    #           "ssm" or "kda" layers, not both
    # The kinds differ in weight SHAPE, so the weight tree holds a stack a
    # kind, each in the order its layers run (``params["layers"]`` the
    # "attn" layers, ``"ssm_layers"``, ``"gmu_layers"``, ``"cross_layers"``,
    # ``"kda_layers"``)
    # and ONE scan a segment cuts each position's weights from the stack
    # of its kind (``models/transformer._scan_plan``). The cache's layer
    # kinds (``layer_kinds``) follow the "attn" layers' windows.
    #
    # ``one_branch`` (``nemotron_h``; the HF family sets it): every block
    # is ``x + f(norm(x))`` with ONE ``f`` and one norm, where every other
    # plan's block is a mixer and then a feed-forward part with a norm
    # each. A position's name then says which branch the block has: "ssm"
    # and "attn" the mixer alone, and
    #   "moe"   the expert layer alone (``moe``, ``mlp_type`` "moe"; the
    #           stack ``params["moe_layers"]``, ``ln1`` and ``mlp``).
    # Such a plan holds its router THERE. A plan of two-branch blocks over
    # "attn" and "kda" layers may hold one in EVERY block's second branch
    # (``mlp_type`` "moe": ``solar_open2``; both stacks then carry ``ln2``
    # and the expert ``mlp``), share included (``MoEConfig.n_held``).
    ssm: Optional[SSMConfig] = None
    kda: Optional[KDAConfig] = None
    stack_plan: Optional[Tuple[Tuple[int, Tuple[Any, ...]], ...]] = None
    one_branch: bool = False
    # Differential attention (``phi4flash``): heads in PAIRS; a pair's two
    # softmaxes read the two halves of one kv row's key and both read its
    # whole value, and the pair's context is their difference under a
    # learned ``lambda``, normed (``models/transformer._diff_combine``).
    # ``n_q_heads`` / ``n_kv_heads`` / ``head_dim`` are the published ones
    # (40 / 20 / 64); the caches hold ``kv_heads_per_row`` = 2 kv heads a
    # row.
    diff_attn: bool = False
    attn_logits_soft_cap: Optional[float] = None
    softmax_scale: Optional[float] = None  # default head_dim ** -0.5
    # Latent attention in place of the q/k/v projections (None = those).
    # ``head_dim`` is then nope + rope and ``n_kv_heads == n_q_heads``.
    mla: Optional[MLAConfig] = None
    # Attention inside a convolved latent (``CCAConfig``). Every layer is
    # paged attention over the ONE page pool, and beside it a slot keeps a
    # small carry (``models/transformer.CCAState``).
    cca: Optional[CCAConfig] = None

    # Rotary (apply_rotary False => learned absolute positions, gpt2)
    apply_rotary: bool = True
    rotary_base: float = 10000.0
    rotary_dim: Optional[int] = None       # default head_dim
    rotary_scaling_type: Optional[str] = None
    rotary_scaling_factor: float = 1.0
    rotary_low_freq_factor: float = 1.0
    rotary_high_freq_factor: float = 4.0
    rotary_original_max_position: int = 8192

    # MLP
    activation_function: str = "silu"
    mlp_type: str = "gated"                # "gated" (swiglu) | "fc" (gpt2) | "moe"
    use_mlp_bias: bool = False             # gpt2
    moe: Optional[MoEConfig] = None
    # An expert model's leading layers that are dense SwiGLUs of
    # ``intermediate_dim`` instead (``first_k_dense_replace``): the stack is
    # then two runs of identical layers, ``params["dense_layers"]`` before
    # ``params["layers"]``.
    n_dense_layers: int = 0
    # Multi-token-prediction modules after the stack (``params["mtp"]``,
    # ``models/transformer.mtp_logits``): each is a projection of
    # [norm(embedding of the next token) ; norm(hidden)] and one block as
    # the stack's last kind. No generation or training path runs them.
    n_mtp_layers: int = 0

    # Embeddings / head
    tied_embedding: bool = False
    normalize_embed: bool = False          # gemma: scale embeds by sqrt(hidden)
    # ``granitemoehybrid``'s multipliers: the embedding times
    # ``embedding_multiplier``, every branch times ``residual_multiplier``
    # before it joins the residual, the logits DIVIDED by ``logits_scaling``
    # (its ``attention_multiplier`` is ``softmax_scale``)
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # ``zaya``: a branch joins the residual as ``a_r * (x + b_r) + a_h *
    # (branch + b_h)``, four learned vectors a sublayer
    # (``layers.attn_res`` / ``layers.mlp_res``), in place of ``x + branch``
    residual_scaling: bool = False
    final_logits_soft_cap: Optional[float] = None
    abs_position_embedding: bool = False   # gpt2 learned positions

    # Dropout (SFT only; PPO runs with 0 like the reference)
    embd_pdrop: float = 0.0
    resid_pdrop: float = 0.0
    attn_pdrop: float = 0.0

    # Head
    is_critic: bool = False                # scalar value head instead of LM head

    # Compute dtype for activations (params kept fp32 master in the optimizer)
    dtype: str = "bfloat16"

    # Paged-KV pool storage dtype for generation engines (docs/performance.md
    # "KV quantization"): None = serving ``dtype`` (raw bf16 pages, the
    # default: the int8 pool has run in no benchmark cell, ROADMAP D1);
    # "int8" stores quantized pages with per-(page-slot, kv-head) scales in a
    # parallel scales array, halving decode's HBM KV traffic and doubling
    # resident pages at fixed pool HBM. The AREAL_KV_DTYPE env knob
    # (base/constants.py) overrides a None here; an explicit engine argument
    # overrides both.
    kv_dtype: Optional[str] = None

    # Attention backend: None = auto (Pallas flash on TPU, XLA dense on CPU,
    # where pallas only runs interpreted); True/False force it.
    use_flash_attention: Optional[bool] = None

    # STATIC upper bound on any packed segment's length (e.g. max prompt +
    # max new tokens). When set, the flash kernels' grid is as long as the
    # band it leaves and not as the causal triangle — fewer empty steps
    # when packing many short sequences. The train engine rejects batches
    # that violate the bound.
    attn_max_seqlen: Optional[int] = None

    # Flash-attention block size override (None = the kernels' rule,
    # ``ops/pallas/flash_attention.flash_blocks``, which reads the call's
    # shapes: 1024 x 1024 at T >= 8192; under it 256 x 1024 forward and
    # 256 x 256 backward, 512 x 512 under a window or ``attn_max_seqlen``).
    # Bigger score tiles amortize the kernels' per-step work at very long
    # context; may need more VMEM.
    flash_block_size: Optional[int] = None
    # Separate K-block size (None = the rule's, or ``flash_block_size``
    # where that is set). Rectangular tiles trade the steps' fixed work
    # against the band's cover.
    flash_block_size_k: Optional[int] = None

    # Cross-entropy in token blocks of this size (None = dense): the LM
    # head + log-softmax + label gather run per block under remat, so the
    # [T, vocab] logits (4 GB f32 at the 32k protocol shape) never
    # materialize. Trades one extra head matmul in the backward for ~8 GB
    # of HBM round trips per step.
    loss_chunk_size: Optional[int] = None

    # Layer-stack execution: 1 = lax.scan over stacked layers (one trace,
    # fast compiles — the default); an int N or True unrolls the scan (full
    # unroll removes the per-layer dynamic-update-slice bookkeeping XLA
    # emits for scan carries/residuals — measured ~20% step-time win on a
    # 12-layer model at 4k tokens — at the cost of layer-count-proportional
    # compile time; prefer it for models up to a few dozen layers).
    layer_scan_unroll: int = 1

    # Rematerialization policy for the training backward pass:
    #   "full" — checkpoint whole layers (max memory savings, ~1/3 extra
    #            FLOPs; the 32k-context default),
    #   "dots" — save matmul outputs, recompute elementwise (small memory
    #            cost, near-zero recompute on MXU),
    #   "dots_attn" — "dots" for the projections/MLP but the attention
    #            kernel stays un-rematted (its q/k/v/out/lse residuals are
    #            saved): a whole-layer checkpoint re-runs the flash forward
    #            inside the backward, ~25% of a long-context step. Costs
    #            ~4 packed activations per layer of extra HBM.
    #   "none" — save everything (fastest when activations fit HBM; right
    #            for small models / short contexts).
    remat_policy: str = "full"

    def flash_enabled(self) -> bool:
        if self.use_flash_attention is None:
            import jax

            return jax.devices()[0].platform == "tpu"
        return self.use_flash_attention

    @property
    def layer_kinds(self) -> Tuple[Tuple[Optional[int], bool], ...]:
        """``(window, rotary)`` of each position of the CACHE's period: one
        entry for a model whose layers are alike. Under a ``stack_plan``
        the shortest period of its "attn" layers' windows (one kind where
        they are alike; where they are not, as many kinds as the period is
        long, each with a page table of its own)."""
        if self.layer_pattern is not None:
            return self.layer_pattern
        if self.stack_plan is not None:
            wins = [p.window for p in self.positions if p.mixer == "attn"]
            period = next(
                n for n in range(1, len(wins) + 1)
                if len(wins) % n == 0 and wins == wins[:n] * (len(wins) // n))
            return tuple((w, self.apply_rotary) for w in wins[:period])
        return ((self.sliding_window, self.apply_rotary),)

    @property
    def period(self) -> int:
        return len(self.layer_kinds)

    @property
    def cache_layers(self) -> int:
        """Layers of K/V (or latents) a cache holds of one token: a looped
        stack keeps them a PASS, ``n_passes`` behind every weight layer.
        What the pools' leading axes, the engine's bytes and tiles and
        the attention FLOPs (``base/flops.py``) read. State-space layers
        hold no K/V and are not among them."""
        return self.n_passes * self.n_attn_layers

    @property
    def kv_heads_per_row(self) -> int:
        """kv heads laid side by side in ONE row of the page pool
        (``models/transformer._pack_qkv``): two where state-space layers
        stand beside attention heads of at most half a 128-lane tile (the
        published 64: the paged kernels take a full-lane head only), else
        one. Every older family keeps the layout it had. The same packing
        serves DIFFERENTIAL pairs (``diff_attn``): the two kv heads of a
        row are then a pair's ``k1`` and ``k2``, a query keeps its values
        in its own half, and the whole row of the value is what both
        softmaxes of the pair read; such a model's dense cache and
        trainer's forward pack the same way."""
        paired = (
            (self.ssm is not None or self.diff_attn)
            and self.head_dim * 2 <= 128 and self.n_kv_heads % 2 == 0
        )
        return 2 if paired else 1

    @functools.cached_property
    def plan(self) -> Optional[Tuple[Tuple[int, Tuple[StackPosition, ...]], ...]]:
        """``stack_plan`` resolved: ``(repeats, positions)`` a segment (the
        class :class:`StackPosition`); None for a model of one mixer."""
        if self.stack_plan is None:
            return None
        segs: List = []
        n_attn, last_attn = 0, None    # cache layers so far; (segment,
        exported = False               # position, cache layer) of the newest
        for reps, period in self.stack_plan:
            mixers = [m for m, _ in period]
            if "cross" in mixers and not exported:
                # the one attention layer whose K/V the cross layers read
                si, at, _ = last_attn
                segs[si][1][at] = dataclasses.replace(
                    segs[si][1][at], exports=True)
                exported = True
            source = None if last_attn is None else last_attn[2]
            segs.append((reps, [
                StackPosition(m, w, source=source if m == "cross" else None)
                for m, w in period]))
            if "attn" in mixers:
                n_attn += reps * mixers.count("attn")
                last_attn = (
                    len(segs) - 1,
                    len(mixers) - 1 - mixers[::-1].index("attn"), n_attn - 1)
        return tuple((reps, tuple(out)) for reps, out in segs)

    @property
    def positions(self) -> Tuple[StackPosition, ...]:
        """:attr:`plan`, a layer at a time, in the order the layers run."""
        return tuple(
            p for reps, period in self.plan for _ in range(reps)
            for p in period)

    @property
    def mixers(self) -> Tuple[str, ...]:
        """The mixer kind of every layer, in the order the layers run."""
        if self.stack_plan is None:
            return ("attn",) * self.n_layers
        return tuple(
            m for reps, period in self.stack_plan for _ in range(reps)
            for m, _ in period)

    @property
    def layer_ids(self) -> Dict[str, List[int]]:
        """Of each kind of block the model has, its layers' places in the
        model: entry ``i`` of a kind's weight stack is layer
        ``layer_ids[kind][i]``."""
        mixers = self.mixers
        return {
            kind: [i for i, m in enumerate(mixers) if m == kind]
            for kind in MIXERS if kind in mixers}

    def n_mixers(self, kind: str) -> int:
        return sum(m == kind for m in self.mixers)

    @property
    def n_ssm_layers(self) -> int:
        return self.n_mixers("ssm")

    @property
    def n_kda_layers(self) -> int:
        return self.n_mixers("kda")

    @property
    def recurrent(self) -> Optional[str]:
        """The plan's mixer kind that keeps a per-slot recurrent state,
        "ssm" or "kda" (None: the model has neither)."""
        if self.ssm is not None:
            return "ssm"
        return "kda" if self.kda is not None else None

    @property
    def n_attn_layers(self) -> int:
        """Layers of self attention: those that hold K/V."""
        return self.n_mixers("attn")

    @property
    def n_periods(self) -> int:
        """Leading axis of the page pool: a page holds one position of the
        period in every period, of every pass."""
        return self.cache_layers // self.period

    @property
    def n_rep(self) -> int:
        return self.n_q_heads // self.n_kv_heads

    @property
    def qk_norm_full(self) -> bool:
        """The q/k norm spans the whole projection (olmoe), not one head."""
        return self.qk_layernorm and self.qk_norm_over == "full"

    @property
    def rot_dim(self) -> int:
        if self.mla is not None:
            return self.mla.qk_rope_head_dim
        return self.rotary_dim if self.rotary_dim is not None else self.head_dim

    @property
    def cca_latent_dim(self) -> int:
        """Channels the convolutions run over: ``[q ; k]`` of one token."""
        return (self.n_q_heads + self.n_kv_heads) * self.head_dim

    @property
    def cca_carry_dim(self) -> int:
        """What a row carries a layer: the last ``time0 - 1`` inputs of
        the first convolution, the last ``time1 - 1`` of the second, and
        the shifted half of the last token's value projection."""
        c = self.cca
        return (
            (c.time0 + c.time1 - 2) * self.cca_latent_dim
            + self.n_kv_heads // 2 * self.head_dim
        )

    @property
    def n_moe_layers(self) -> int:
        """Layers with a router (0 for a dense model)."""
        if self.one_branch:
            return self.n_mixers("moe")
        return self.n_layers - self.n_dense_layers if self.mlp_type == "moe" else 0

    @property
    def expert_dim(self) -> int:
        if self.moe is not None and self.moe.expert_dim is not None:
            return self.moe.expert_dim
        return self.intermediate_dim

    def __post_init__(self):
        if self.n_q_heads % self.n_kv_heads != 0:
            raise ValueError("n_q_heads must be divisible by n_kv_heads")
        if self.qk_norm_over not in ("head", "full"):
            raise ValueError(
                f"qk_norm_over must be 'head' or 'full', got "
                f"{self.qk_norm_over!r}"
            )
        if self.mlp_type == "moe" and self.moe is None:
            object.__setattr__(self, "moe", MoEConfig())
        if self.n_dense_layers and not (
            self.mlp_type == "moe" and self.n_dense_layers < self.n_layers
        ):
            raise ValueError(
                "n_dense_layers: leading dense layers of an expert model, "
                "fewer than n_layers"
            )
        if self.n_passes < 1:
            raise ValueError("n_passes: the stack runs at least once")
        if (self.recurrent is None) != (self.stack_plan is None) or (
            self.ssm is not None and self.kda is not None
        ):
            raise ValueError(
                "stack_plan comes with ONE recurrent mixer's settings, ssm "
                "or kda, and they with it")
        if self.stack_plan is not None:
            def position(p):
                mixer, window = (p, None) if isinstance(p, str) else p
                return (mixer, None if window is None else int(window))

            try:
                plan = tuple(
                    (int(reps), tuple(position(p) for p in period))
                    for reps, period in self.stack_plan)
            except (TypeError, ValueError):
                plan = ()
            object.__setattr__(self, "stack_plan", plan)
            flat = [p for reps, period in plan for p in period * reps]
            kinds = [m for m, _ in flat]
            if (
                not plan or len(flat) != self.n_layers
                or any(reps < 1 or not period for reps, period in plan)
                or any(m not in MIXERS for m in kinds)
                or any(w is not None and (m != "attn" or w < 1)
                       for m, w in flat)
                or "attn" not in kinds or self.recurrent not in kinds
                or ("kda" if self.ssm is not None else "ssm") in kinds
            ):
                raise ValueError(
                    "stack_plan: segments (repeats, period) of 'ssm' or "
                    "'kda' (the kind whose settings the model has: ssm, "
                    "kda), ('attn', window or None), 'gmu', 'cross' and "
                    "'moe' positions (the recurrent kind and attention both "
                    "present, a window on 'attn' alone) that make up "
                    f"n_layers, got {self.stack_plan!r}"
                )
            delta = self.kda is not None
            if delta and (
                self.one_branch or set(kinds) != {"attn", "kda"}
                or self.mlp_type not in ("gated", "moe")
            ):
                raise ValueError(
                    "stack_plan: 'kda' layers stand beside 'attn' layers "
                    "alone, in blocks of TWO branches (a mixer, then a "
                    "gated MLP or, mlp_type 'moe', an expert layer in "
                    "every block, a share of the experts included); with "
                    "'gmu', 'cross' or 'moe' positions or one_branch they "
                    "are not supported"
                )
            if not delta and (
                ("moe" in kinds) != (self.mlp_type == "moe") or (
                    "moe" in kinds and not self.one_branch
                ) or (self.one_branch and ("gmu" in kinds or "cross" in kinds))
            ):
                raise ValueError(
                    "stack_plan: a plan over 'ssm' layers holds a router in "
                    "'moe' positions (mlp_type 'moe', blocks of one branch: "
                    "one_branch) and nowhere else, and blocks of one branch "
                    "are 'ssm', 'attn' and 'moe'; a router in every block's "
                    "second branch is for a plan over 'attn' and 'kda' "
                    "layers"
                )
            for si, (reps, period) in enumerate(plan):
                here = [m for m, _ in period]
                before = [m for _, per in plan[:si] for m, _ in per]
                if "gmu" in here and "ssm" not in before or (
                    "cross" in here and "attn" not in before
                ) or (
                    ("gmu" in here or "cross" in here)
                    and ("ssm" in here or "attn" in here)
                ):
                    raise ValueError(
                        "stack_plan: a 'gmu' position reads the memory of a "
                        "state-space layer, and a 'cross' position the K/V "
                        "of an attention layer, of an EARLIER segment; a "
                        "segment does not hold both a reader and what it "
                        "reads"
                    )
            if "cross" in kinds:
                first = next(
                    i for i, (_, per) in enumerate(plan)
                    if any(m == "cross" for m, _ in per))
                src = [
                    (reps, w) for reps, per in plan[:first]
                    for m, w in per if m == "attn"][-1]
                if src[0] != 1 or src[1] is not None:
                    raise ValueError(
                        "stack_plan: the attention layer whose K/V the "
                        "'cross' positions share is ONE full layer (the "
                        "last 'attn' position of a segment that runs once)"
                    )
            s, d = self.ssm, self.kda
            if (
                self.n_passes > 1 or self.exit_gate or self.mla is not None
                or self.n_dense_layers or self.n_mtp_layers
                or self.layer_pattern is not None
                or self.sliding_window is not None
                or self.abs_position_embedding
                or self.norm_branch_out or self.is_critic
            ):
                raise ValueError(
                    "stack_plan: a model of one pass; with a looped "
                    "stack, latent attention, learned positions, "
                    "branch norms or a value head it is not supported, and "
                    "its attention layers' windows are named in the plan, "
                    "not by layer_pattern or sliding_window"
                )
            if d is not None and (
                d.d_conv < 2
                or min(d.n_heads, d.head_dim, d.chunk_size) < 1
                or (self.moe is not None and (
                    self.moe.router_on_layer_input
                    or self.moe.router_dim is not None
                    or self.moe.skip_expert))
                or self.residual_scaling or self.diff_attn
            ):
                raise ValueError(
                    "kda: heads, a head width and a chunk of at least one "
                    "and a convolution of at least two taps; with a "
                    "router on the layer's input, a stateful router, a skip "
                    "output, learned residual scaling or differential "
                    "attention it is not supported"
                )
            if s is not None and (s.n_heads % s.n_groups or (s.selective and (
                    s.n_heads != 1 or s.n_groups != 1 or s.proj_bias))):
                raise ValueError(
                    f"ssm: n_groups={s.n_groups} does not divide "
                    f"n_heads={s.n_heads}, or a selective scan (dt_rank) "
                    "with more than one head of d_inner channels, or with "
                    "projection biases"
                )
            if s is not None and s.state_dtype != "float32":
                raise ValueError(
                    f"ssm: state_dtype {s.state_dtype!r}: the recurrent "
                    "state is float32 (a 16-bit state is another "
                    "configuration, not supported)"
                )
        if self.one_branch and self.stack_plan is None:
            raise ValueError("one_branch: blocks of one branch need a stack_plan")
        if self.moe is not None:
            m = self.moe
            n, off = m.held
            if n < 1 or off < 0 or off + n > m.num_experts or (
                not m.holds_all and (m.skip_expert or m.router_dim is not None)
            ) or (m.latent_dim is not None and m.latent_dim < 1):
                raise ValueError(
                    f"moe: the experts held ({n} from {off}) lie among the "
                    f"{m.num_experts} the router scores; a share of them "
                    "with a skip output or a stateful router is not "
                    "supported"
                )
        if self.diff_attn and self.softmax_scale is None:
            # the packed rows are twice a head wide: the scale is the
            # published head's, said once here for every attention path
            object.__setattr__(self, "softmax_scale", self.head_dim ** -0.5)
        if self.diff_attn and (
            self.kv_heads_per_row != 2 or self.n_q_heads % 2
            or self.n_q_heads // 2 % (self.n_kv_heads // 2)
            or self.mla is not None or self.cca is not None
            or self.qk_layernorm or self.n_passes > 1
            or self.attn_logits_soft_cap is not None
        ):
            raise ValueError(
                "diff_attn: pairs of query heads over pairs of kv heads of "
                "at most 64 (two to a 128-lane row); with latent or "
                "convolved attention, q/k norms, a soft cap or a looped "
                "stack it is not supported"
            )
        if (self.n_passes > 1 or self.exit_gate) and (
            self.mla is not None or self.n_dense_layers or self.n_mtp_layers
            or self.layer_pattern is not None
        ):
            raise ValueError(
                f"n_passes={self.n_passes}: a looped stack is one run of "
                "alike layers; with latent attention, leading dense layers, "
                "multi-token-prediction modules or a period of layer kinds "
                "it is not supported (no published model has both)"
            )
        if self.layer_pattern is not None:
            object.__setattr__(
                self, "layer_pattern",
                tuple((w, bool(r)) for w, r in self.layer_pattern),
            )
            if (
                not self.layer_pattern
                or self.n_layers % len(self.layer_pattern)
                or self.n_mtp_layers
                or self.mla is not None or self.abs_position_embedding
            ):
                raise ValueError(
                    "layer_pattern: a period that divides n_layers (counted "
                    "over the model's layers, across leading dense layers), "
                    "with rotary or no positions; with latent attention or "
                    "prediction modules it is not supported"
                )
        if self.attn_gate and (
            self.mla is not None or self.cca is not None
            or self.ssm is not None or self.diff_attn
        ):
            raise ValueError(
                "attn_gate: a gate on the context of plain q/k/v attention "
                "(also beside 'kda' layers in a stack plan); with latent, "
                "convolved or differential attention or a plan over 'ssm' "
                "layers it is not supported"
            )
        if self.n_dense_layers and self.mla is None and (
            self.moe.router_on_layer_input or self.residual_scaling
            or self.cca is not None or self.use_attn_proj_bias
        ):
            raise ValueError(
                "n_dense_layers: leading dense layers without latent "
                "attention, with a router on the layer's input, learned "
                "residual scaling, convolved attention or an output bias, "
                "are not supported (no published model has both)"
            )
        if self.cca is not None:
            if (
                self.mla is not None or self.ssm is not None
                or self.layer_pattern is not None or self.n_passes > 1
                or self.sliding_window is not None or self.qk_layernorm
                or self.use_attention_bias or self.abs_position_embedding
                or self.n_kv_heads % 2 or self.n_mtp_layers
                or min(self.cca.time0, self.cca.time1) < 1
            ):
                raise ValueError(
                    "cca: one pass of alike full-attention layers with an "
                    "even number of kv heads (half of them hold the previous "
                    "token's values) and convolutions of at least one tap; "
                    "with latent attention, state-space layers, layer kinds, "
                    "a looped stack, a window, q/k norms or biases, learned "
                    "positions or prediction modules it is not supported"
                )
        if self.moe is not None and self.moe.router_dim is not None and (
            self.moe.scoring != "softmax" or self.moe.router_on_layer_input
            or self.n_dense_layers or self.n_passes > 1 or self.n_mtp_layers
        ):
            raise ValueError(
                "moe.router_dim: the stateful MLP router scores by softmax "
                "over the residual it is given, in a stack of alike layers "
                "run once"
            )
        if self.mla is not None:
            m = self.mla
            if self.head_dim != m.qk_nope_head_dim + m.qk_rope_head_dim:
                raise ValueError("mla: head_dim must be nope + rope")
            if self.n_kv_heads != self.n_q_heads or m.v_head_dim > self.head_dim:
                raise ValueError(
                    "mla: n_kv_heads == n_q_heads and v_head_dim <= head_dim"
                )
