"""Mixture-of-experts MLP (top-k router + experts).

TPU-native counterpart of ``realhf/impl/model/modules/moe/`` (router.py,
experts.py, token_dispatcher.py, layer.py — ~700 LoC). ONE dispatch:
every expert is computed for every token and the router's combine weights
(zero where an expert was not chosen) are folded into the activations
before the down projection, so the result is one contraction over
(expert, width) and no ``[T, X, E]`` tensor is ever formed. It is correct
under any sharding of the expert axis (the contraction runs over the
sharded expert dim: expert parallelism by one psum), differentiates
without special cases, and XLA fuses each layer's slice of the stacked
weights straight into the matmuls.

Why not a grouped matmul. Until PR 26 a second path sorted the token
copies by expert and ran ``lax.ragged_dot`` (the reference's
permute-tokens-per-expert scheme, O(T·K) FLOPs instead of O(T·X)). Timed
on a TPU v5e at OLMoE-1B-7B widths (64 experts of 2048 x 1024, 8 a token,
bf16, experts alone, ms a layer; chip runs of PR 26, PERF.md §6):

    tokens T            64     1024     4096   | 1024 fwd+bwd  4096 fwd+bwd
    this path          1.14    4.40    17.4    |    22.6          77.2
    ``ragged_dot``     5.01    6.39    12.1    |    31.3          56.2

XLA lowers ``ragged_dot`` to a Mosaic kernel of its own, but each of its
three calls a layer takes a materialised copy of that layer's slice of
the stacked weights (3 x the expert bytes moved), and the kernel reaches
about a quarter of the MXU's peak. This path runs at 96 % of the peak at
T = 4096 and at the weights' HBM time at T = 64, where every expert is
hit anyway (decode at 64 slots: P(an expert gets no token) = 0.03 %). So
``ragged_dot`` loses 4.4 x in decode and 1.4 x in a 1024-token admission
wave, and wins 1.4 x only at the trainer's 4096 tokens, where it brought
a ``custom_vmap`` rule that could not be differentiated outside ``vmap``
and, under a sharded expert axis, an all-gather of every expert's
weights that the code cannot see coming. One path was kept. The 8 x
wasted FLOPs at large T are what a real grouped-matmul kernel would win
back (ROADMAP); ``ragged_dot`` as this JAX lowers it wins a sixth of them.

At 256 experts and 8 a token (``joyai_llm_flash``) the same path computes
32 x the needed FLOPs. From shapes alone, at 2048 x 768 experts in bf16 on
a v5e (197 TFLOP/s, 819 GB/s): a layer's routed weights are 2.42 GB,
2.95 ms to stream; its dispatch is 6 * 2048 * 768 * 256 = 2.4 GFLOP a
row, so 128 rows cost 1.6 ms of MXU under the weights' 2.95 ms (free),
256 rows 3.1 ms (level with them) and a 1024-token admission wave 12.6 ms
(4.3 x the bytes). PERF.md has what the chip says. Still one path.

Router runs in fp32 (matches the reference's fp32 router,
``moe/router.py``). Two kinds of score (``MoEConfig.scoring``): a softmax
over all logits (mixtral, olmoe), or a sigmoid of each (``deepseek_v3``'s
``noaux_tc``), where the experts are chosen by score PLUS a learned
correction bias ``b_router`` and weighted by the score alone. Shared
experts (``n_shared_experts``) are one more SwiGLU applied to every token
and added to the routed sum. Where the family says so
(``MoEConfig.router_on_layer_input``: ``smallthinker``) the router reads
ANOTHER tensor than the experts: the layer's normed input, computed a
whole attention earlier; the caller hands it in as ``router_input``.
"""

import jax
import jax.numpy as jnp

from areal_tpu.ops.activations import ACT2FN

# the expert matmuls run under this scope: their HLO ``op_name`` carries it
# (dumps, profilers that keep metadata). A v5e's xplane keeps none, so the
# benchmark's ``moe.*`` readers find these ops by what they stream
# (``benchmark/moe_flops.py``)
EXPERTS_SCOPE = "moe_experts"
SHARED_SCOPE = "moe_shared_expert"
EARLY_ROUTER_SCOPE = "moe_router_early"


def _route(cfg, router_w, x, bias=None):
    """fp32 router. Returns (top_vals [T, K] — the combine weights, scaled
    and, if the family asks, renormalised —, top_idx [T, K], probs [T, X],
    logits [T, X]). ``bias`` [X] moves the choice and not the weights."""
    moe = cfg.moe
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    if moe.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, top_idx = jax.lax.top_k(
            scores if bias is None else scores + bias.astype(jnp.float32),
            moe.top_k,
        )
        top_vals = jnp.take_along_axis(scores, top_idx, axis=-1)
        # the load-balance loss wants a distribution over experts
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
        if moe.norm_topk_prob:
            top_vals = top_vals / (
                jnp.sum(top_vals, axis=-1, keepdims=True) + 1e-20
            )
        return top_vals * moe.routed_scaling_factor, top_idx, probs, logits
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, moe.top_k)
    if moe.norm_topk_prob:
        top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    return top_vals * moe.routed_scaling_factor, top_idx, probs, logits


def _aux_loss(cfg, chosen, probs, logits):
    """Switch-style load-balance + z loss (≈ ``moe/router.py``) in fp32.
    ``chosen`` [T, X] is 1 where the token chose the expert."""
    moe = cfg.moe
    frac_tokens = jnp.mean(chosen, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = moe.num_experts * jnp.sum(frac_tokens * frac_probs)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return moe.aux_loss_coeff * aux + moe.z_loss_coeff * z


def moe_mlp(cfg, p, x, router_input=None):
    """x: [..., E] -> (out [..., E], aux_loss, top_idx [..., K]).

    ``router_input`` ``[..., E]``: what the router reads where that is not
    ``x`` (``cfg.moe.router_on_layer_input``; required then, refused
    otherwise, so that no path can feed the router the wrong tensor).

    ``top_idx`` are the experts each token chose (largest weight first):
    the generation engine counts them (``moe_experts_hit``) and can hand
    them to the trainer, whose recompute counts how often it agrees
    (``ppo/inference``'s ``router_agree``).

    The aux loss includes padding tokens (the layer has no mask); with
    packed batches the padding fraction is small and its router logits are
    the uniform x=0 output, so the bias is negligible. Under the train
    engine's ``vmap`` over packed rows it is a per-row loss, and the engine
    takes the mean over rows.
    """
    act = ACT2FN[cfg.activation_function]
    lead = x.shape[:-1]
    xt = x.reshape(-1, x.shape[-1])
    if (router_input is not None) != cfg.moe.router_on_layer_input:
        raise ValueError(
            "moe_mlp: router_input goes with cfg.moe.router_on_layer_input"
        )
    if router_input is None:
        top_vals, top_idx, probs, logits = _route(
            cfg, p["router"], xt, p.get("b_router")
        )
    else:
        with jax.named_scope(EARLY_ROUTER_SCOPE):
            top_vals, top_idx, probs, logits = _route(
                cfg, p["router"],
                router_input.reshape(-1, router_input.shape[-1]),
                p.get("b_router"),
            )
    onehot = jax.nn.one_hot(top_idx, cfg.moe.num_experts, dtype=jnp.float32)
    chosen = onehot.sum(axis=1)                                  # [T, X]
    combine = (top_vals[:, :, None] * onehot).sum(axis=1)        # [T, X]
    with jax.named_scope(EXPERTS_SCOPE):
        h = act(jnp.einsum("te,xef->txf", xt, p["w_gate"])) * jnp.einsum(
            "te,xef->txf", xt, p["w_up"]
        )
        h = h * combine.astype(h.dtype)[:, :, None]
        out = jnp.einsum("txf,xfe->te", h, p["w_down"])
    if "shared_gate" in p:
        with jax.named_scope(SHARED_SCOPE):
            out = out + (
                act(xt @ p["shared_gate"]) * (xt @ p["shared_up"])
            ) @ p["shared_down"]
    aux = _aux_loss(cfg, chosen, probs, logits)
    return (
        out.reshape(*lead, -1),
        aux,
        top_idx.reshape(*lead, cfg.moe.top_k),
    )
