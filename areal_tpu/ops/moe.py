"""Mixture-of-experts MLP (top-k router + experts).

TPU-native counterpart of ``realhf/impl/model/modules/moe/`` (router.py,
experts.py, token_dispatcher.py, layer.py — ~700 LoC). The router, the
top-k, the aux loss and the shared expert are one piece of code; after the
router's choice the routed experts run through one of TWO dispatches that
share nothing, because what each needs excludes the other.

The dense dispatch (the trainer, ``ppo/inference``, a mesh, and every
program of the generation engine that hands the experts few rows): every
expert is computed for every token and the router's combine weights (zero
where an expert was not chosen) are folded into the activations before the
down projection, so the result is one contraction over (expert, width) and
no ``[T, X, E]`` tensor is ever formed. It is correct under any sharding of
the expert axis (the contraction runs over the sharded expert dim: expert
parallelism by one psum), differentiates without special cases, and XLA
fuses each layer's slice of the stacked weights straight into the matmuls.
It computes ``X / k`` times the needed FLOPs (8 x at OLMoE's 64 experts,
32 x at ``joyai_llm_flash``'s 256): its MXU time is ``T / 240`` of the time
the layer's routed weights take from HBM on a v5e (197 TFLOP/s over
819 GB/s), whatever ``X`` and ``k`` are, and does not hide under it.

The grouped dispatch (``ops/pallas/moe_grouped.py``; the generation
engine's ``decode_step_paged`` and ``_extend_layers``, where
:func:`moe_grouped_applies`): the ``T k`` chosen (row, expert) pairs sorted
by expert, every run padded to whole row tiles, one Pallas kernel over the
WHOLE stacked weights and a layer index that reads each expert a row chose
once and no other. The caller must hand it the stack (``routed``): a
kernel handed a layer's slice costs a copy of ``X x E x F`` for each of
the three matrices every layer-step, which is what ``lax.ragged_dot`` paid
until PR 26 removed it (5.01 ms against 1.14 at OLMoE's 64 rows, 12.1
against 17.4 at 4096; PERF.md §6). It has no gradient and no partitioning
rule (ROADMAP C12).

Experts alone on a TPU v5e, ms a layer, one layer of a 4- or 8-layer stack,
bf16 (chip runs of PR 40, PERF.md §6; ``bytes``: the layer's routed
weights at 819 GB/s):

    experts                      rows   hit    bytes   dense   grouped (tile)
    256 of 2048 x 768, 8 a row      8    23 %   2.95    3.24    0.80 (16)
    256 of 2048 x 768, 8 a row    128    97 %   2.95    3.26    3.30 (16)
    256 of 2048 x 768, 8 a row    256   100 %   2.95    3.90    3.46 (16)
    256 of 2048 x 768, 8 a row    512   100 %   2.95    6.53    3.82 (32)
    256 of 2048 x 768, 8 a row   1024   100 %   2.95   12.93    4.43 (64)
    64 of 2048 x 1024, 8 a row     64   100 %   0.98    1.14    1.18 (16)
    64 of 2048 x 1024, 8 a row    128   100 %   0.98    1.14    1.22 (32)
    64 of 2048 x 1024, 8 a row   1024   100 %   0.98    4.39    2.30 (128)
    64 of 2560 x 768, 6 a row     112   100 %   0.92    1.04    1.16 (32)
    64 of 2560 x 768, 6 a row    1024   100 %   0.92    4.05    2.08 (128)

    128 held of 512, 1024 x 2688, 22 a row   8  30 %   0.51    1.67    0.31 (16)
    (two matrices in a latent: PR 53)       64  94 %   1.62    1.70    1.62 (16)
                                           128 100 %   1.72    1.70    1.83 (16)
                                           192 100 %   1.72    1.76    1.99 (32)
                                           384 100 %   1.72    2.92    2.32 (64)
                                          1024 100 %   1.72    7.51    3.68 (128)

    128 of 2048 x 1024, 8 a row, 1 shared      8  40 %   0.78    2.28    0.93
    (a sigmoid router; both with the router   32  86 %   1.69    2.29    1.93
    and the shared expert, 0.04-0.09 ms:      80  99 %   1.94    2.25    2.24
    PR 55)                                   128 100 %   1.96    2.26    2.30
                                             144 100 %   1.96    2.26    2.34
                                             192 100 %   1.97    2.27    2.36
                                             256 100 %   1.97    2.62    2.41
                                             512 100 %   1.97    4.46    2.81
                                            1024 100 %   1.97    8.80    3.49

``grouped`` includes the sort, the gather and the weighted sum around the
call (PR 53's rows: both with the router and the latent projections, 0.24-
0.47 ms timed alone, taken off). At 128 experts of 2048 x 1024 the two
cross later, at ~220 rows (0.9 of the ridge): between the rule's 144 and
there the einsums are 3-4 % ahead; no program of the benchmark has such
rows at that shape and the rule was left as it is (PERF.md section 7,
open after PR 55). The dense dispatch sits on its bytes up to about half the ridge and
climbs with the rows from there; the kernel climbs a quarter as fast (the
padded rows it moves), so the two cross at 0.58 of the ridge at 256
experts (139 rows) and at 0.67 at 64 (161), and below the crossing the
einsums are ahead by 1-11 %. Where few rows leave most experts unread the
kernel reads the hit ones only (8 rows of 256 experts: 4.1 x). That is
the rule of :func:`moe_grouped_applies`: rows at or over ``GROUPED_FROM``
of the ridge, or at most ``GROUPED_FROM`` of the experts hit. Experts of
TWO matrices (no gate: one product less between the matmuls, which XLA
folds into them) sit on their bytes up to the ridge itself: 1.76 ms for
1.72 of bytes at 192 rows, 7.15e-3 ms a row from there, which is the MXU's
time alone, so they cross the kernel at 1.15 of the ridge (~280 rows:
``GROUPED_FROM_UNGATED``).

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

Three switches of ``nemotron_h`` (LatentMoE), each the HF family's:
experts of TWO matrices, ``W_down act(W_up u)`` (``MoEConfig.gated``
False; squared ReLU, the shared expert likewise); experts that live in a
LATENT (``latent_dim``): one down-projection ``u = W_dn h`` before the
routed experts and one up-projection behind their weighted sum, the router
and the shared expert reading ``h``; and an expert-parallel rank's SHARE
(``n_held``, ``held_offset``): the router scores all ``num_experts`` and
keeps ``top_k`` of them, the combine weights are normalised over all the
row chose, and the routed sum runs over the chosen experts whose matrices
are HERE: ``w_up [Xh, ...]``. The dense dispatch contracts over the held
slice of the combine weights; the grouped one sorts the pairs on held
experts and drops the rest (they get the index past the last held expert,
as a skipped row does). What the other ranks would add is absent, here as
in the plain reference, and nothing stands in for them or their exchange.

A router with STATE (``MoEConfig.router_dim``: ``zaya``) is an MLP
(:func:`_route_mlp`): the residual projected down to ``router_dim``, plus
the previous layer's such vector times a learned gain, an RMSNorm, three
GELU layers, a softmax; the correction bias moves the choice and not the
weight, which is not renormalised. The vector is handed in and back
(``router_state``) and rides the callers' layer scans. Its last output
(``MoEConfig.skip_expert``) is no expert: a row that chooses it reads none
and passes through times its weight, and is ``num_experts`` in ``top_idx``.
"""

from typing import Optional

import jax
import jax.numpy as jnp

from areal_tpu.ops import norms
from areal_tpu.ops.activations import ACT2FN

# the expert matmuls run under this scope: their HLO ``op_name`` carries it
# (dumps, profilers that keep metadata). A v5e's xplane keeps none, so the
# benchmark's ``moe.*`` readers find these ops by what they stream
# (``benchmark/moe_flops.py``)
EXPERTS_SCOPE = "moe_experts"
SHARED_SCOPE = "moe_shared_expert"
EARLY_ROUTER_SCOPE = "moe_router_early"
ROUTER_MLP_SCOPE = "moe_router_mlp"
SKIP_SCOPE = "moe_skip"
LATENT_DOWN_SCOPE = "moe_latent_down"
LATENT_UP_SCOPE = "moe_latent_up"


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


def _route_mlp(cfg, p, x, state):
    """The stateful MLP router, fp32: :func:`_route`'s results and the
    layer's router vector ``[T, router_dim]`` for the next layer.
    ``state``: the previous layer's (zeros before the first)."""
    moe, f32 = cfg.moe, jnp.float32
    with jax.named_scope(ROUTER_MLP_SCOPE):
        r = x.astype(f32) @ p["router_in"].astype(f32) + p[
            "b_router_in"].astype(f32)
        r = r + p["router_mix"].astype(f32) * state
        h = norms.rms_norm(r, p["router_norm"], cfg.layer_norm_epsilon)
        for w, b in (("router_w1", "b_router1"), ("router_w2", "b_router2")):
            h = jax.nn.gelu(
                h @ p[w].astype(f32) + p[b].astype(f32), approximate=False)
        logits = h @ p["router"].astype(f32)
        probs = jax.nn.softmax(logits, axis=-1)
        _, top_idx = jax.lax.top_k(
            probs + p["b_router"].astype(f32), moe.top_k)
        top_vals = jnp.take_along_axis(probs, top_idx, axis=-1)
        if moe.norm_topk_prob:
            top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    return top_vals * moe.routed_scaling_factor, top_idx, probs, logits, r


def _aux_loss(cfg, chosen, probs, logits):
    """Switch-style load-balance + z loss (≈ ``moe/router.py``) in fp32.
    ``chosen`` [T, X] is 1 where the token chose the expert."""
    moe = cfg.moe
    frac_tokens = jnp.mean(chosen, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = moe.num_experts * jnp.sum(frac_tokens * frac_probs)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return moe.aux_loss_coeff * aux + moe.z_loss_coeff * z


# one TPU v5e: bf16 FLOP/s over HBM bytes/s (197e12 / 819e9), the rows at
# which the dense dispatch's MXU time equals its weights' HBM time
RIDGE_ROWS = 240
# the dense dispatch keeps the rows under this share of the ridge (the two
# cross at 0.58 of it at 256 experts and 0.67 at 64: the module's table),
# and the programs that hit no more than this share of the experts
GROUPED_FROM = 0.6
# ... and experts of two matrices up to this share of it (the table's last
# block: dense 1.76 against 1.99 at 192 rows, 2.92 against 2.32 at 384)
GROUPED_FROM_UNGATED = 1.15


def _platform() -> str:
    return jax.devices()[0].platform


def moe_grouped_applies(
    cfg, params, mesh=None, rows: int = 0, platform: Optional[str] = None
) -> bool:
    """Whether a program of the generation engine that hands the routed
    experts ``rows`` rows a layer runs them as the grouped-matmul kernel
    (``ops/pallas/moe_grouped.py``) or as the dense einsums, from what the
    engine can observe (as ``kv_write_kernel_applies`` and
    ``fused_sample_applies`` say it of their kernels). The kernel serves:
    a model with a router; ONE TPU device (``pallas_call`` has no
    partitioning rule: under a mesh of several the einsums contract over
    the sharded expert axis); routed stacks stored in the serving dtype
    (a lazy cast of an operand of a custom call is a copy of the stack);
    and ``rows`` at or over ``GROUPED_FROM`` of the chip's ridge
    (``GROUPED_FROM_UNGATED`` for experts of two matrices). The
    dense dispatch's MXU time is ``rows / RIDGE_ROWS`` of its weights' HBM
    time whatever ``X`` and ``k`` are, and does not hide under it; the
    kernel's time is the HBM time of the experts HIT, ``1 - (1 - k / X) **
    rows`` of them, so the few rows of a small batch that leave most
    experts unread go to the kernel as well. ``params`` is the engine's
    tree as it serves it (arrays or their shapes); ``platform`` defaults
    to the first device's."""
    if cfg.mlp_type != "moe" or rows <= 0:
        return False
    if platform is None:
        platform = _platform()
    moe = cfg.moe
    hit = 1.0 - (1.0 - moe.top_k / moe.num_experts) ** rows
    rows_from = GROUPED_FROM if moe.gated else GROUPED_FROM_UNGATED
    return (
        platform == "tpu"
        and (mesh is None or mesh.size == 1)
        and _routed_dtype(cfg, params) == jnp.dtype(cfg.dtype)
        and (rows >= rows_from * RIDGE_ROWS or hit <= GROUPED_FROM)
    )


def _routed_dtype(cfg, params):
    """What the routed experts' stacks are stored in: ``layers``', or under
    a stack plan the expert blocks' own (``moe_layers``); experts of two
    matrices have no ``w_gate``."""
    mlp = params["layers" if cfg.stack_plan is None else "moe_layers"]["mlp"]
    return mlp["w_gate" if "w_gate" in mlp else "w_up"].dtype


def moe_mlp(cfg, p, x, router_input=None, routed=None, router_state=None):
    """x: [..., E] -> (out [..., E], aux_loss, top_idx [..., K]), and with
    a stateful router (``cfg.moe.router_dim``) a fourth: the layer's
    router vector ``[..., router_dim]``, fp32. ``router_state``: the
    previous layer's (required then; zeros before the first layer).

    ``router_input`` ``[..., E]``: what the router reads where that is not
    ``x`` (``cfg.moe.router_on_layer_input``; required then, refused
    otherwise, so that no path can feed the router the wrong tensor).

    ``routed`` ``(stacks, index)``: the routed matrices as the WHOLE
    stacks ``[L, X, ...]`` and this layer's index in them, where ``p``
    came without them (the generation engine's forwards, where
    :func:`moe_grouped_applies`): the chosen (row, expert) pairs run
    through the grouped-matmul kernel. Without it ``p`` holds the layer's
    own ``[X, ...]`` and every expert is computed for every row. The two
    share everything up to the router's choice and nothing after it.

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
    X = cfg.moe.num_experts
    state = None
    if cfg.moe.router_dim is not None:
        top_vals, top_idx, probs, logits, state = _route_mlp(
            cfg, p, xt, router_state.reshape(-1, cfg.moe.router_dim))
    elif router_input is None:
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
    # (a choice of the skip, index X, is a row of zeros: no expert's)
    onehot = jax.nn.one_hot(top_idx, X, dtype=jnp.float32)
    chosen = onehot.sum(axis=1)                                  # [T, X]
    moe = cfg.moe
    n_held, first = moe.held
    u = xt
    if moe.latent_dim is not None:
        with jax.named_scope(LATENT_DOWN_SCOPE):
            u = xt @ p["latent_down"]
    if routed is not None:
        from areal_tpu.ops.pallas.moe_grouped import moe_grouped

        stacks, index = routed
        local, sizes = top_idx, chosen.sum(axis=0)
        if not moe.holds_all:
            # a pair on an expert of another rank: the index past the last
            # held one, sorted last and dropped
            local = jnp.where(
                (top_idx >= first) & (top_idx < first + n_held),
                top_idx - first, n_held)
            sizes = sizes[first : first + n_held]
        with jax.named_scope(EXPERTS_SCOPE):
            out = moe_grouped(
                u, local, top_vals, sizes,
                stacks.get("w_gate"), stacks["w_up"], stacks["w_down"], index,
                activation=cfg.activation_function,
                with_skip=moe.skip_expert or not moe.holds_all,
                n_routed=X,
            )
    else:
        combine = (top_vals[:, :, None] * onehot).sum(axis=1)    # [T, X]
        if not moe.holds_all:
            combine = combine[:, first : first + n_held]
        with jax.named_scope(EXPERTS_SCOPE):
            if moe.gated:
                h = act(jnp.einsum("te,xef->txf", u, p["w_gate"])) * jnp.einsum(
                    "te,xef->txf", u, p["w_up"]
                )
            else:
                h = act(jnp.einsum("te,xef->txf", u, p["w_up"]))
            h = h * combine.astype(h.dtype)[:, :, None]
            out = jnp.einsum("txf,xfe->te", h, p["w_down"])
    if moe.latent_dim is not None:
        with jax.named_scope(LATENT_UP_SCOPE):
            out = out @ p["latent_up"]
    if "shared_up" in p:
        with jax.named_scope(SHARED_SCOPE):
            if moe.gated:
                hs = act(xt @ p["shared_gate"]) * (xt @ p["shared_up"])
            else:
                hs = act(xt @ p["shared_up"])
            out = out + hs @ p["shared_down"]
    if cfg.moe.skip_expert:
        with jax.named_scope(SKIP_SCOPE):
            skipped = jnp.where(top_idx == X, top_vals, 0.0).sum(axis=1)
            out = out + skipped.astype(xt.dtype)[:, None] * xt
            # the balance loss is over everything the router can choose
            chosen = jax.nn.one_hot(
                top_idx, X + 1, dtype=jnp.float32).sum(axis=1)
    aux = _aux_loss(cfg, chosen, probs, logits)
    res = (
        out.reshape(*lead, -1),
        aux,
        top_idx.reshape(*lead, cfg.moe.top_k),
    )
    if state is not None:
        res += (state.reshape(*lead, -1),)
    return res
