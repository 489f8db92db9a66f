"""The pjit train/inference engine.

TPU-native counterpart of the reference's ``PipelinableEngine`` contract
(``realhf/api/core/model_api.py:514``: train_batch / eval_batch / forward)
and its Megatron backend (``realhf/impl/model/backend/megatron.py``). What
the reference assembles from DDP grad buckets + ZeRO-1 DistributedOptimizer +
1F1B pipeline schedules, XLA gives as: one jitted step over a mesh with
sharded params (fsdp axis) and sharded batch rows (data axes); optax handles
the optimizer; grad accumulation is a host loop over micro-batches with a
jitted accumulate step (shapes are bucketed by the packer, so each bucket
compiles once).

Losses/outputs are supplied by interfaces as pure functions
``(params, cfg, arrays) -> (loss, stats)`` — the analogue of the reference's
``loss_fn`` argument to ``train_batch``.
"""

import collections
import dataclasses
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from areal_tpu.api.data import MicroBatchSpec, SequenceSample
from areal_tpu.base import constants, faults, program_store, recover, tracing
from areal_tpu.base import metrics as metrics_mod
from areal_tpu.models.config import ModelConfig
from areal_tpu.models import transformer as tfm
from areal_tpu.parallel import multihost
from areal_tpu.parallel.mesh import (
    ParallelConfig,
    batch_pspec,
    make_mesh,
    param_shardings,
)
from areal_tpu.train import batching

LossFn = Callable[[Any, ModelConfig, Dict[str, jnp.ndarray]], Tuple[jnp.ndarray, Dict]]
OutputFn = Callable[[Any, ModelConfig, Dict[str, jnp.ndarray]], jnp.ndarray]


def fwd_pipeline_depth() -> int:
    """Micro-batches kept in flight by :meth:`TrainEngine.forward` (the
    dispatch-ahead window). Default 2: dispatch mb i+1 before fetching mb i,
    so the device never idles on the host's fetch→unpack round trip. 0/1 =
    the serial path."""
    return constants.env_knob(constants.FWD_PIPELINE_ENV, 2)


def train_prefetch_enabled() -> bool:
    """Gates BOTH halves of the train-side pipeline: background pack+put
    prefetch of minibatch n+1 under the in-flight step for minibatch n, and
    the deferred (per-logging-interval, not per-step) stats fetch."""
    return constants.env_knob(constants.TRAIN_PREFETCH_ENV, 1) > 0


def train_guard_enabled() -> bool:
    """On-device finite-ness guard inside the jitted train step (default
    on): a non-finite loss or grad norm makes the step SELECT the old
    params/opt state instead of applying the poisoned update, and report
    ``guard/step_ok`` in the stats the trainer already fetches — no extra
    host round trip (its cost on the chip: not measured, ROADMAP S6). Read
    at jit-build time; toggling requires a fresh engine."""
    return constants.env_knob(constants.TRAIN_GUARD_ENV, 1) > 0


def host_stats_view(host: Dict[str, Any]) -> Dict[str, float]:
    """Normalize an already-fetched stats dict: 0-d leaves become python
    floats, everything else passes through. ONE definition shared by the
    blocking fetch below and the trainer's deferred flush, so the two paths
    can never drift in how they render scalars."""
    return {
        k: (float(v) if np.ndim(v) == 0 else v) for k, v in host.items()
    }


def fetch_stats_dict(stats: Dict[str, Any]) -> Dict[str, float]:
    """Pull every device scalar in one transfer (a per-scalar ``float()``
    is one blocking device->host sync)."""
    metrics_mod.counters.add(metrics_mod.PIPE_STATS_FETCH_BLOCKING, 1)
    with tracing.span("train_pipe/stats_fetch"):
        # arealint: ok(the ONE designed stats sync — a single batched pull, deferred to the logging interval by fetch_stats=False on the hot path)
        host = jax.device_get(stats)
    return host_stats_view(host)


def mean_stats_dicts(all_stats: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Mean per-key over a list of stats dicts WITHOUT a device pull: device
    scalars are averaged by a (tiny, async) on-device stack+mean, host
    scalars by numpy. Interfaces use this to merge per-minibatch stats while
    deferring the single blocking ``device_get`` to the trainer's logging
    interval (``np.mean`` over jax scalars would implicitly block)."""
    if len(all_stats) == 1:
        return dict(all_stats[0])
    out: Dict[str, Any] = {}
    for k in all_stats[0]:
        vs = [s[k] for s in all_stats]
        if any(isinstance(v, jax.Array) for v in vs):
            out[k] = jnp.mean(
                jnp.stack([jnp.asarray(v, jnp.float32) for v in vs])
            )
        else:
            out[k] = float(np.mean(vs))
    return out


@dataclasses.dataclass
class PreparedTrainBatch:
    """Host-prepared input of one optimizer step: stacked device buffers
    (transfer already dispatched) + normalized per-micro-batch loss weights.
    Produced by :meth:`TrainEngine.prepare_train_batch`, consumed by
    :meth:`TrainEngine.train_prepared` — the seam the minibatch prefetcher
    pipelines across."""

    stacked: Dict[str, jax.Array]
    weights: np.ndarray
    n_mbs: int


@dataclasses.dataclass
class OptimizerConfig:
    """≈ the reference's ``OptimizerConfig`` (``realhf/api/cli_args.py:173``)."""

    type: str = "adam"
    lr: float = 2e-5
    weight_decay: float = 0.05
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-5
    gradient_clipping: float = 1.0
    lr_scheduler_type: str = "constant"   # constant | linear | cosine
    warmup_steps_proportion: float = 0.001
    min_lr_ratio: float = 0.0


def vmapped_forward(
    params, cfg: ModelConfig, arrays: Dict[str, jnp.ndarray],
    with_aux: bool = False, with_head: bool = True,
    with_routing: bool = False,
):
    """Model forward over ``[D, T]`` packed buffers -> ``[D, T, vocab|1]``.
    With ``with_aux``, returns ``(out, aux)`` where aux is the MoE router
    loss (0 for non-MoE models): each row's own loss (``ops/moe.py``), and
    this returns their mean. ``with_routing`` (MoE models) appends the
    experts every token chose, ``[D, L, T, top_k]``.

    ``spmd_axis_name`` tells any shard_map inside (the context-parallel
    attention ring) that the vmapped row axis lives on the data axes —
    without it the ring would silently all-gather rows/heads every layer."""
    out = jax.vmap(
        lambda ids, seg, pos: tfm.forward_packed(
            params, cfg, ids, seg, pos, with_aux=with_aux,
            with_head=with_head, with_routing=with_routing,
        ),
        spmd_axis_name=("data", "fsdp"),
    )(arrays["input_ids"], arrays["segment_ids"], arrays["positions"])
    if with_aux:
        logits, aux, *rest = out
        return (logits, jnp.mean(aux), *rest)
    return out


def vmapped_next_token_logprobs(
    params, cfg, arrays, with_aux: bool = False, with_routing: bool = False
):
    """Token-aligned next-token logprobs over ``[D, T]`` packed buffers —
    the shared primitive behind the SFT loss, the PPO logprob-recompute
    MFC, and the PPO actor loss. Honors ``cfg.loss_chunk_size``: the LM
    head + softmax + gather run per token block under remat so the
    ``[T, vocab]`` logits (4 GB f32 at the 32k protocol shape) never
    materialize on ANY of those paths. Returns ``lp``, or ``(lp, aux)`` /
    ``(lp, routing)`` / ``(lp, aux, routing)`` as asked."""
    from areal_tpu.ops import ppo as ppo_ops

    chunked = bool(cfg.loss_chunk_size)
    out = vmapped_forward(
        params, cfg, arrays, with_aux=with_aux, with_head=not chunked,
        with_routing=with_routing,
    )
    head_in, *extra = out if (with_aux or with_routing) else (out,)
    if chunked:
        lp = jax.vmap(
            lambda h, ids, seg: tfm.chunked_next_token_logprobs(
                params, cfg, h, ids, seg, chunk=cfg.loss_chunk_size
            )
        )(head_in, arrays["input_ids"], arrays["segment_ids"])
    else:
        lp = jax.vmap(ppo_ops.gather_packed_shifted_log_probs)(
            head_in, arrays["input_ids"], arrays["segment_ids"]
        )
    return (lp, *extra) if extra else lp


class TrainEngine:
    """Owns mesh + sharded params (+ optional optimizer state) for one model."""

    def __init__(
        self,
        model_cfg: ModelConfig,
        parallel: ParallelConfig = ParallelConfig(),
        optimizer: Optional[OptimizerConfig] = None,
        mesh=None,
        param_dtype: str = "float32",
    ):
        # one listener a process, live before the first device work below
        tracing.listen_for_compiles()
        with tracing.span("train_engine/start"):
            self.cfg = model_cfg
            self.parallel = parallel
            # fp32 master params by default; "bfloat16" halves param+grad memory
            # (fits ~1B-param models with Adam on one 16GB chip) at some
            # optimizer-precision cost
            self.param_dtype = jnp.dtype(param_dtype)
            self.mesh = mesh if mesh is not None else make_mesh(parallel)
            self.optimizer_cfg = optimizer
            self.params = None
            self.opt_state = None
            self.tx = None
            self.hf_family = None
            self._step = 0
            self.version = 0
            self._jit_cache: Dict[Any, Callable] = {}
            self._param_shardings = param_shardings(
                self.mesh, tfm.param_logical_axes(model_cfg)
            )
            self._batch_sharding = NamedSharding(self.mesh, batch_pspec())
            # stacked micro-batches [n_mbs, D, T, ...]: rows still spread over
            # the data axes, tokens over ctx, the micro-batch axis unsharded
            self._stacked_sharding = NamedSharding(
                self.mesh, P(None, ("data", "fsdp"), "ctx")
            )
            from areal_tpu.ops import attention as attn_ops

            if parallel.ctx > 1:
                # context parallelism: packed attention rings the token axis
                # over this mesh (process-global — every engine in a CP
                # experiment must share the same mesh topology; conflicting
                # shapes raise in set_context_parallel)
                if parallel.ctx & (parallel.ctx - 1):
                    raise ValueError(f"ctx must be a power of two, got {parallel.ctx}")
                attn_ops.set_context_parallel(self.mesh, "ctx")
            elif attn_ops.get_context_parallel() is not None:
                raise ValueError(
                    "a context-parallel engine is active in this process: every "
                    "train engine must use the same ctx topology (got ctx=1); "
                    "match the parallel specs or clear_context_parallel() first"
                )

    # ------------------------------------------------------------------ #
    # Initialization
    # ------------------------------------------------------------------ #

    @property
    def n_rows(self) -> int:
        """Global packed-batch rows (over every process's devices)."""
        return self.parallel.data * self.parallel.fsdp

    @property
    def n_local_rows(self) -> int:
        """Rows this process materializes (per-host batch feeding; the full
        batch never exists on any one host — ≈ the reference's per-DP-rank
        dataloaders, ``realhf/system/model_worker.py`` fetch path)."""
        nproc = jax.process_count()
        if self.n_rows % nproc != 0:
            raise ValueError(
                f"{self.n_rows} batch rows not divisible by {nproc} processes"
            )
        return self.n_rows // nproc

    def init_random(self, seed: int = 0):
        with tracing.span("train_engine/start/params"):
            # a named function: the program is ``jit(init_params)`` in the
            # compile records, a partial would be ``jit(<unknown>)``
            def init_params(key):
                return tfm.init_params(self.cfg, key, dtype=self.param_dtype)

            init = jax.jit(init_params, out_shardings=self._param_shardings)
            self.params = init(jax.random.key(seed))
        return self

    def load_hf(self, path: str, init_critic_head: bool = False):
        """Load a HF checkpoint. With ``init_critic_head``, a CausalLM's
        [E, V] lm head is dropped and a random [E, 1] value head inserted
        HOST-side (the critic's sharding tree always includes "head", so
        patching after device_put would trip a pytree mismatch on
        tied-embedding families — ≈ the reference's init_critic_from_actor).
        A checkpoint that already carries a TRAINED value head (critic/RM
        exports: ``score.weight`` + ``is_critic``) keeps it — re-randomizing
        would silently score rollouts with noise.
        """
        import json
        import os

        from areal_tpu.models import hf as hf_conv

        with tracing.span("train_engine/start/checkpoint_read"):
            cfg, host_params = hf_conv.load_hf_checkpoint(path)
        with open(os.path.join(path, "config.json")) as f:
            model_type = json.load(f)["model_type"]
        self.hf_family = hf_conv.family_for_model_type(model_type).name
        if init_critic_head:
            head = host_params.get("head", {}).get("weight")
            if head is not None and head.shape == (self.cfg.hidden_dim, 1):
                pass  # trained critic/RM checkpoint: keep its head
            else:
                host_params.pop("head", None)
                rng = np.random.default_rng(0)
                host_params["head"] = {
                    "weight": (
                        rng.standard_normal((self.cfg.hidden_dim, 1)) * 0.02
                    ).astype(np.float32)
                }
        return self.load_params(host_params)

    def load_params(self, host_params):
        with tracing.span("train_engine/start/params"):
            host_params = jax.tree.map(
                lambda x: np.asarray(x, self.param_dtype), host_params
            )
            if multihost.is_multihost():
                # every process holds the full host copy (loaded from shared
                # FS); each materializes only its addressable shards
                self.params = jax.tree.map(
                    lambda x, s: jax.make_array_from_callback(
                        x.shape, s, lambda idx: x[idx]
                    ),
                    host_params,
                    self._param_shardings,
                )
            else:
                self.params = jax.device_put(
                    host_params, self._param_shardings
                )
        return self

    def save_hf(self, path: str, family: str, async_write: bool = False,
                post_write=None):
        """HF checkpoint export. The param gather is collective (every host
        calls in) and must finish before the next donated train step; the
        file write is pure host IO. ``async_write=True`` returns a daemon
        ``threading.Thread`` (main host; None elsewhere) doing the write +
        ``post_write()`` in the background — the weight-publish fast path
        (r5, VERDICT r4 #3). A failure inside the thread is stored on
        ``thread._areal_exc``; the joiner must check and re-raise so a
        disk-full does not silently freeze the fleet's weight version.

        The export is COMMITTED like the Orbax checkpoints: safetensors land
        in a staging dir that is atomically renamed over ``path`` with a
        manifest, so a gen server (or a restarted trainer re-announcing the
        version) can never observe a half-written snapshot."""
        import threading

        from areal_tpu.models import hf as hf_conv

        host_params = multihost.gather_params_to_host(self.params)
        abs_path = os.path.abspath(path)
        step, version = self._step, self.version

        def _write():
            staging = recover.prepare_staging(abs_path, "hf")
            hf_conv.save_hf_checkpoint(host_params, self.cfg, family, staging)
            recover.commit_checkpoint(staging, abs_path, {
                "step": step, "version": version, "format": "hf",
            })
            if post_write is not None:
                post_write()

        if async_write:
            multihost.barrier("save_hf")  # collectives done; IO floats free
            if not multihost.is_main():
                return None

            def _guarded():
                try:
                    _write()
                except BaseException as e:  # surfaced by the joiner
                    t._areal_exc = e

            t = threading.Thread(
                target=_guarded, name=f"save_hf:{path}", daemon=True
            )
            t._areal_exc = None
            t.start()
            return t
        if multihost.is_main():
            _write()
        multihost.barrier("save_hf")  # sync: file exists for every host
        return None

    # ------------------------------------------------------------------ #
    # Optimizer
    # ------------------------------------------------------------------ #

    def setup_optimizer(self, total_train_steps: int):
        assert self.optimizer_cfg is not None
        oc = self.optimizer_cfg
        warmup = max(1, int(oc.warmup_steps_proportion * total_train_steps))
        end = oc.lr * oc.min_lr_ratio
        if oc.lr_scheduler_type == "cosine":
            sched = optax.schedules.warmup_cosine_decay_schedule(
                0.0, oc.lr, warmup, max(total_train_steps, warmup + 1), end
            )
        elif oc.lr_scheduler_type == "linear":
            sched = optax.schedules.join_schedules(
                [
                    optax.schedules.linear_schedule(0.0, oc.lr, warmup),
                    optax.schedules.linear_schedule(
                        oc.lr, end, max(total_train_steps - warmup, 1)
                    ),
                ],
                [warmup],
            )
        else:
            sched = optax.schedules.join_schedules(
                [optax.schedules.linear_schedule(0.0, oc.lr, warmup), lambda _: oc.lr],
                [warmup],
            )
        self._lr_sched = sched

        # host-side mirror of the schedule: optax schedules return device
        # scalars, and a device->host pull per step would block the dispatch
        # queue
        def lr_host(step: int) -> float:
            import math

            if step < warmup:
                return oc.lr * step / warmup
            if oc.lr_scheduler_type == "cosine":
                total = max(total_train_steps, warmup + 1)
                frac = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
                return end + 0.5 * (oc.lr - end) * (1 + math.cos(math.pi * frac))
            if oc.lr_scheduler_type == "linear":
                total = max(total_train_steps - warmup, 1)
                frac = min((step - warmup) / total, 1.0)
                return oc.lr + (end - oc.lr) * frac
            return oc.lr

        self._lr_host = lr_host

        def decay_mask(params):
            return jax.tree.map(lambda x: x.ndim >= 2, params)

        self.tx = optax.chain(
            optax.clip_by_global_norm(oc.gradient_clipping),
            optax.adamw(
                learning_rate=sched,
                b1=oc.beta1,
                b2=oc.beta2,
                eps=oc.eps,
                weight_decay=oc.weight_decay,
                mask=decay_mask,
            ),
        )
        # The state is BORN at its canonical shardings: every copy of the
        # param tree inside it (Adam moments) takes its param's sharding,
        # everything else (optax scalar counts) replicates over the mesh.
        # Left to jit's own choice, a one-device run gets
        # SingleDeviceSharding leaves while the train step outputs
        # NamedSharding ones — the aval mismatch (sharding-in-types) forced
        # a FULL second train-step compile on round 2 (VERDICT r3 weak #1)
        # — and re-pinning them after the fact copies the whole state
        # through the host while the first copy is still alive, which a
        # 1.5B model's 6.6 GiB of bf16 moments do not survive on a 16 GB
        # chip (RESOURCE_EXHAUSTED in setup_optimizer, chip run, PR 21).
        # One jit with out_shardings also touches only local devices in a
        # multi-process world: no transfer, no collective.
        with tracing.span("train_engine/start/optimizer"):
            repl = NamedSharding(self.mesh, P())
            opt_shardings = optax.tree_map_params(
                self.tx,
                lambda _, sharding: sharding,
                jax.eval_shape(self.tx.init, self.params),
                self._param_shardings,
                transform_non_params=lambda _: repl,
            )
            # arealint: ok(one-time optimizer-state init at setup, not a per-step rebuild)
            self.opt_state = jax.jit(
                self.tx.init, out_shardings=opt_shardings
            )(self.params)
        return self

    # ------------------------------------------------------------------ #
    # Jitted step builders (cached per loss/output fn)
    # ------------------------------------------------------------------ #

    def _get_jitted(self, kind: str, fn) -> Callable:
        # The cache holds a strong reference to fn so CPython cannot recycle
        # its id for a different function while the entry lives. Interfaces
        # must pass *stable* callables (built once per interface), otherwise
        # every call re-traces.
        key = (kind, id(fn))
        if key in self._jit_cache:
            return self._jit_cache[key][1]
        cfg = self.cfg
        if kind == "train_step":
            # ONE dispatch per optimizer step: micro-batch grad accumulation
            # via lax.scan over stacked [n_mbs, D, T] buffers, the optax
            # update fused in, and scalar stats merged on device. Params and
            # optimizer state are donated — XLA aliases them in place, so no
            # param-sized copies and no extra dispatch latency (the reference
            # reaches the same shape via Megatron DDP grad buckets +
            # DistributedOptimizer, ``realhf/impl/model/backend/megatron.py``).
            guard = train_guard_enabled()

            def train_step(params, opt_state, stacked, weights):
                def loss_of(p, arrays, w):
                    loss, stats = fn(p, cfg, arrays)
                    return loss * w, (loss, stats)

                grad_fn = jax.value_and_grad(loss_of, has_aux=True)

                def eval_mb(arrays, w):
                    (_, (loss, stats)), g = grad_fn(params, arrays, w)
                    # A zero-weight micro-batch (multihost all-padding fill)
                    # contributes nothing — and losses that divide by the
                    # action-token count can be 0/0 = nan on an empty mask,
                    # so the nan must be SELECTED out (``w * nan`` is still
                    # nan), or the finite-ness guard below would veto real
                    # updates over legitimately-empty micro-batches.
                    live = w > 0
                    g = jax.tree.map(
                        lambda x: jnp.where(live, x, jnp.zeros_like(x)), g
                    )
                    loss = jnp.where(live, loss, 0.0)
                    stats = jax.tree.map(
                        lambda s: jnp.where(live, s, jnp.zeros_like(s)), stats
                    )
                    return g, loss, stats

                n_mbs = weights.shape[0]
                if n_mbs == 1:
                    arrays = jax.tree.map(lambda x: x[0], stacked)
                    grads, loss, stats = eval_mb(arrays, weights[0])
                    losses = loss[None]
                    statss = jax.tree.map(lambda s: s[None], stats)
                else:
                    def body(acc, xs):
                        arrays, w = xs
                        g, loss, stats = eval_mb(arrays, w)
                        return jax.tree.map(jnp.add, acc, g), (loss, stats)

                    zeros = jax.tree.map(
                        lambda x: jnp.zeros(x.shape, jnp.float32), params
                    )
                    grads, (losses, statss) = jax.lax.scan(
                        body, zeros, (stacked, weights)
                    )
                # accumulation stays f32; the update sees param-dtype grads
                # so optimizer-state dtypes never drift (bf16 params + n_mbs
                # > 1 would otherwise promote Adam moments to f32 and break
                # donation on the next call)
                grads = jax.tree.map(
                    lambda g, p: g.astype(p.dtype), grads, params
                )
                gnorm = optax.global_norm(grads)
                updates, new_opt_state = self.tx.update(
                    grads, opt_state, params
                )
                new_params = optax.apply_updates(params, updates)
                out = {"loss": jnp.sum(losses * weights), "grad_norm": gnorm}
                if guard:
                    # poisoned step (NaN loss, exploding/overflowed grads):
                    # keep the pre-step params AND opt state (skipping the
                    # Adam moment/count advance too), flag it in the stats
                    # the caller already fetches — zero extra host syncs
                    ok = jnp.isfinite(gnorm) & jnp.isfinite(jnp.sum(losses))
                    new_params = jax.tree.map(
                        lambda n, o: jnp.where(ok, n, o), new_params, params
                    )
                    new_opt_state = jax.tree.map(
                        lambda n, o: jnp.where(ok, n, o),
                        new_opt_state, opt_state,
                    )
                    out["guard/step_ok"] = ok.astype(jnp.float32)
                # micro-batch scalar stats -> weighted means (weights are
                # already normalized to sum 1 by the caller)
                for k, v in statss.items():
                    if v.ndim == 1:
                        out[k] = jnp.sum(v * weights)
                return new_params, new_opt_state, out

            # Donated-state outputs pinned to the CANONICAL shardings
            # (params at their logical-axis shardings, opt state where
            # tx.init put it): round 1's outputs are round 2's donated
            # inputs, and any drift between GSPMD's inferred output
            # shardings and the init-time ones forces a silent full
            # recompile of the step on round 2 (the single-device variant
            # of this is optax's count scalars; the multi-device variant
            # shows up under dp/fsdp meshes; neither cost is measured in
            # this round's record).
            # The scalar-stats output stays UNSPECIFIED on purpose: pinning
            # it replicated slowed the step in an earlier round (capture
            # deleted in PR 21: not measured), and stats never feed back
            # as inputs, so they cannot cause recompiles.
            opt_sh = jax.tree.map(lambda x: x.sharding, self.opt_state)
            jitted = self._stored_jit(
                train_step, kind, fn, guard,
                donate_argnums=(0, 1),
                out_shardings=(self._param_shardings, opt_sh, None),
            )
        elif kind == "forward":

            def fwd(params, arrays):
                return fn(params, cfg, arrays)

            jitted = self._stored_jit(fwd, kind, fn)
        elif kind == "eval":

            def ev(params, arrays):
                return fn(params, cfg, arrays)

            jitted = self._stored_jit(ev, kind, fn)
        else:
            raise ValueError(kind)
        self._jit_cache[key] = (fn, jitted)
        return jitted

    def _stored_jit(self, step, kind: str, fn, *more, **jit_kw):
        """``jax.jit`` of one of this engine's programs, through the
        program store (``base/program_store.py``). Its key holds what the
        program reads beside its arguments: the model, the caller's
        function and the optimizer (each by what it is and what its
        closures hold: the hyper-parameters, the schedule), the mesh and
        the process's context-parallel ring. A
        Mosaic kernel under a multi-device mesh must be wrapped in
        ``shard_map`` (GSPMD cannot partition it): traced with the mesh
        known."""
        from areal_tpu.ops import attention as attn_ops

        return program_store.stored_jit(
            attn_ops.trace_on_mesh(self.mesh, step),
            name="train/" + kind,
            built_from=(
                self.cfg, fn, more, self.tx, self.parallel,
                str(self.param_dtype), self.mesh,
                attn_ops.get_context_parallel(),
            ),
            **jit_kw,
        )

    def n_jit_entries(self) -> int:
        """Total jax-level specializations across this engine's jitted
        programs. Stable across identical-shape rounds once warm: a
        growing count means the next round would eat a compile."""
        from areal_tpu.base import jitcache

        return jitcache.total_cache_size(j for (_, j) in self._jit_cache.values())

    def _put_batch(self, packed: batching.PackedBatch) -> Dict[str, jnp.ndarray]:
        return multihost.global_from_local(
            packed.arrays, self._batch_sharding, self.n_rows, rows_axis=0
        )

    def _put_stacked(
        self, packed: List[batching.PackedBatch]
    ) -> Dict[str, jnp.ndarray]:
        """Stack per-micro-batch host buffers to [n_mbs, D_local, T, ...] and
        ship them in one transfer (global view [n_mbs, D, T, ...])."""
        keys = packed[0].arrays.keys()
        stacked = {
            k: np.stack([pb.arrays[k] for pb in packed]) for k in keys
        }
        return multihost.global_from_local(
            stacked, self._stacked_sharding, self.n_rows, rows_axis=1
        )

    def _make_micro_batches(
        self,
        sample: SequenceSample,
        mb_spec: MicroBatchSpec,
        capacity=None,
        weight_fn=None,
    ):
        """Split + pack this host's sample into micro-batches.

        Multi-host: every process enters the same jit dispatch, so the
        micro-batch COUNT and buffer CAPACITY must agree globally even
        though each host packs its own (differently-sized) local rows. All
        agreements ride TWO consolidated allgather rounds (each is a DCN
        round trip): round 1 carries [longest-sequence, mb-count] together;
        round 2 carries [capacity, per-mb weights] together — repacking at
        a larger agreed capacity only adds padding, so weights computed on
        the first packing stay valid. Extra rounds happen only in the rare
        case hosts disagree on the count after round 1.

        Returns ``(mbs, packed, weights)`` where weights (summed across
        hosts, one per packed mb, or None when ``weight_fn`` is None) are
        computed in the same round as the capacity agreement.
        """
        bound = self.cfg.attn_max_seqlen
        longest = 0
        if bound is not None:
            # every sequence of every (possibly grouped) item; agreed
            # globally below so all hosts raise together instead of
            # desyncing the collectives
            longest = max(
                (l for lens in sample.seqlens.values() for ln in lens for l in ln),
                default=0,
            )
        n_rows = self.n_local_rows

        def try_split(n_parts):
            # a LOCAL raise (over-long sequence on this host only) would
            # leave the other hosts blocked in the next gather; return the
            # error and raise collectively after the agreement round
            try:
                return batching.split_into_micro_batches(
                    sample, n_parts, mb_spec.max_tokens_per_mb, n_rows
                ), None
            except ValueError as e:
                return None, e

        mbs, split_err = try_split(mb_spec.n_mbs)
        n_empty = 0
        if multihost.is_multihost():
            # round 1: longest sequence + mb count in ONE gather (-1 count
            # signals a failed local split so every host raises together)
            g1 = multihost.allgather_rows(np.asarray(
                [longest, -1 if mbs is None else len(mbs)], np.int64
            ))
            longest = int(g1[:, 0].max())
            counts = g1[:, 1]
            if (counts < 0).any():
                raise split_err if split_err is not None else RuntimeError(
                    "micro-batch split failed on another host"
                )
            g = int(counts.max())
            # fixed-point on the part count: identical gather sequence on
            # every host (the gathered vector is the same everywhere, so
            # all hosts take the same branch each iteration). Converges on
            # the first try unless re-splitting at the agreed count
            # produces even more parts on some host.
            for _ in range(7):
                if (counts == g).all():
                    break
                if len(mbs) < g:
                    mbs, split_err = try_split(g)
                counts = multihost.allgather_rows(
                    np.int64(-1 if mbs is None else len(mbs))
                )
                if (counts < 0).any():
                    raise split_err if split_err is not None else RuntimeError(
                        "micro-batch split failed on another host"
                    )
                g = int(counts.max())
            if not (counts == g).all():
                raise RuntimeError(
                    f"micro-batch count did not converge: {counts.tolist()}"
                )
            n_empty = g - len(mbs)  # host has fewer items than the agreement
        elif split_err is not None:
            raise split_err
        if bound is not None and longest > bound:
            raise ValueError(
                f"batch contains a {longest}-token sequence but "
                f"attn_max_seqlen={bound}: the flash kernels would "
                "silently truncate its attention span. Raise the bound or "
                "drop over-long sequences at intake."
            )
        cap = capacity or mb_spec.max_tokens_per_mb
        packed = [
            batching.pack_sequences(mb, n_rows, capacity=cap) for mb in mbs
        ]
        cap_local = cap if cap is not None else max(
            (pb.capacity for pb in packed), default=0
        )
        # round 2: capacity + weights in ONE gather (weights depend only on
        # mb CONTENT, not padding, so pre-repack values are final)
        w_local = None
        if weight_fn is not None:
            # arealint: ok(weight_fn reads the host-side packed numpy buffers — no device value crosses here)
            w_local = [float(weight_fn(pb)) for pb in packed]
            w_local += [0.0] * n_empty          # padding mbs carry no loss
        weights = None
        if multihost.is_multihost() and (cap is None or w_local is not None):
            g2 = multihost.allgather_rows(
                np.asarray([float(cap_local)] + (w_local or []), np.float64)
            )
            cap_local = int(g2[:, 0].max())
            if w_local is not None:
                weights = g2[:, 1:].sum(axis=0)
        elif w_local is not None:
            weights = np.asarray(w_local, np.float64)
        if cap is None:
            cap = cap_local
            packed = [
                pb
                if pb.capacity == cap
                else batching.pack_sequences(mb, n_rows, capacity=cap)
                for mb, pb in zip(mbs, packed)
            ]
        for _ in range(n_empty):
            packed.append(batching.empty_like(packed[0]))
        return mbs, packed, weights

    def _flash_pair_counts(
        self, packed: List[batching.PackedBatch]
    ) -> Dict[str, float]:
        """How tightly the flash kernels' pair lists cover this step's
        packed rows (``flash_pairs``, ``flash_interior_pairs``,
        ``flash_fill`` on the ``train_pipe/pack`` record): the host knows
        the rows' segment layout here, and the blocks come from the rule
        the kernels' wrapper asks (`flash_attention.flash_blocks`), for the
        model's heads a kv head and its first layer kind's window.
        Nothing where the span plane is off or the model runs no flash
        kernel."""
        cfg = self.cfg
        if not (tracing.spans_enabled() and cfg.flash_enabled()):
            return {}
        from areal_tpu.ops.pallas import flash_attention

        window = cfg.layer_kinds[0][0]
        block_q, block_k, specialize = flash_attention.flash_blocks(
            packed[0].capacity, cfg.n_q_heads // cfg.n_kv_heads,
            sliding_window=window,
            max_seqlen=cfg.attn_max_seqlen, block_q=cfg.flash_block_size,
            block_k=cfg.flash_block_size_k,
        )
        return flash_attention.pair_counts(
            np.stack([pb.arrays["segment_ids"] for pb in packed]),
            block_q, block_k, specialize, window, cfg.attn_max_seqlen,
        )

    # ------------------------------------------------------------------ #
    # PipelinableEngine API (≈ model_api.py:514)
    # ------------------------------------------------------------------ #

    def prepare_train_batch(
        self,
        sample: SequenceSample,
        mb_spec: MicroBatchSpec,
        loss_weight_fn: Callable[[batching.PackedBatch], float] = None,
    ) -> "PreparedTrainBatch":
        """The HOST half of one optimizer step: micro-batch split + packing
        + the stacked ``device_put``. Split out of :meth:`train_batch` so a
        prefetcher can run it for minibatch n+1 while the jitted step for
        minibatch n is still in flight (the transfer is async — it overlaps
        device compute, and the result handle is ready immediately).
        """
        if loss_weight_fn is None:
            loss_weight_fn = batching.count_action_tokens
        # Per-mb loss weights must be identical on every process (they enter
        # the jit replicated), and the loss each mb computes inside pjit is
        # already GLOBAL over all hosts' rows — so weight by the global
        # action-token count of each micro-batch (gathered in the same
        # round as the capacity agreement).
        with tracing.span("train_pipe/pack") as attrs:
            _, packed, weights = self._make_micro_batches(
                sample, mb_spec, weight_fn=loss_weight_fn
            )
            attrs.update(self._flash_pair_counts(packed))
        weights = np.asarray(weights, np.float32)
        total_w = weights.sum() or 1.0
        weights = weights / total_w
        with tracing.span("train_pipe/put"):
            stacked = self._put_stacked(packed)
        return PreparedTrainBatch(
            stacked=stacked, weights=weights, n_mbs=len(packed)
        )

    def train_prepared(  # arealint: hot (per-minibatch PPO step dispatch)
        self,
        prep: "PreparedTrainBatch",
        loss_fn: LossFn,
        fetch_stats: bool = True,
    ) -> Dict[str, Any]:
        """The DEVICE half: dispatch the jitted step on an already-prepared
        batch. Non-blocking with ``fetch_stats=False`` (outputs are async
        futures; params/opt-state handles are valid for the next dispatch
        immediately)."""
        assert self.tx is not None, "call setup_optimizer() first"
        if faults.maybe_trip("train.step", step=self._step):
            # poison this optimizer step on-device (non-finite loss weights
            # -> non-finite loss/grads): the guard plane must catch it and
            # select the update away without any host-side special-casing
            prep = PreparedTrainBatch(
                stacked=prep.stacked,
                weights=prep.weights * np.inf,
                n_mbs=prep.n_mbs,
            )
        step = self._get_jitted("train_step", loss_fn)
        with tracing.span("train_pipe/dispatch"):
            self.params, self.opt_state, out = step(
                self.params, self.opt_state, prep.stacked,
                jnp.asarray(prep.weights),
            )
        lr = self._lr_host(self._step)
        self._step += 1
        out = dict(out)
        out["lr"] = lr
        out["n_mbs"] = prep.n_mbs
        return fetch_stats_dict(out) if fetch_stats else out

    def train_batch(  # arealint: hot (one optimizer step per call)
        self,
        sample: SequenceSample,
        mb_spec: MicroBatchSpec,
        loss_fn: LossFn,
        loss_weight_fn: Callable[[batching.PackedBatch], float] = None,
        version_steps: Optional[int] = None,
        fetch_stats: bool = True,
    ) -> Dict[str, Any]:
        """One optimizer step over the sample — ONE jit dispatch: grads are
        accumulated across micro-batches by a ``lax.scan`` inside the
        compiled step and the optax update is fused in. Micro-batch grads
        are weighted by ``loss_weight_fn`` (default: action-token count) and
        normalized by the total weight — i.e. a global token-mean loss, like
        the reference.

        Device->host transfers are batched into ONE ``device_get`` at the
        end (each pull is a blocking device->host sync).
        With ``fetch_stats=False`` the scalar stats stay on device — callers
        looping over minibatches fetch once at the end via
        :func:`fetch_stats_dict`.
        """
        prep = self.prepare_train_batch(sample, mb_spec, loss_weight_fn)
        return self.train_prepared(prep, loss_fn, fetch_stats=fetch_stats)

    def train_batches_pipelined(  # arealint: hot (the PPO minibatch loop)
        self,
        samples: Sequence[SequenceSample],
        mb_spec: MicroBatchSpec,
        loss_fn: LossFn,
        loss_weight_fn: Callable[[batching.PackedBatch], float] = None,
        fetch_stats: bool = False,
    ) -> List[Dict[str, Any]]:
        """One optimizer step per sample (the PPO minibatch loop), with the
        pack + ``device_put`` of minibatch n+1 prefetched on a background
        packer thread (one-deep queue) while the jitted step for minibatch n
        is in flight — the host never sits between a finished step and the
        next dispatch doing packing the device could have overlapped.

        Multi-host: the packer thread's prepares run host collectives (the
        micro-batch agreements ride ``process_allgather``, itself a global
        device computation) while the consumer thread dispatches the global
        jitted step — TWO threads enqueueing global computations interleave
        nondeterministically per process, which multi-controller JAX
        forbids (mismatched collective order deadlocks the pod). So
        multi-host runs take the serial loop: prepare and dispatch stay on
        one thread in a fixed global order, and the async jit dispatch
        still overlaps device compute with the NEXT prepare's host work.
        With ``AREAL_TRAIN_PREFETCH`` off this likewise degrades to exactly
        the serial per-sample :meth:`train_batch` loop.
        """
        samples = list(samples)
        if not samples:
            return []
        if not train_prefetch_enabled() or multihost.is_multihost():
            return [
                self.train_batch(
                    s, mb_spec, loss_fn, loss_weight_fn=loss_weight_fn,
                    fetch_stats=fetch_stats,
                )
                for s in samples
            ]
        metrics_mod.counters.add(metrics_mod.PIPE_PREFETCHED_MINIBATCHES,
                                 max(len(samples) - 1, 0))
        prefetcher = batching.Prefetcher(
            samples,
            lambda s: self.prepare_train_batch(s, mb_spec, loss_weight_fn),
        )
        try:
            return [
                self.train_prepared(prep, loss_fn, fetch_stats=fetch_stats)
                for prep in prefetcher
            ]
        finally:
            # a consumer-side raise (HBM kill, jit error) must not leave the
            # packer thread blocked on the queue holding device buffers
            prefetcher.close()

    def eval_batch(
        self, sample: SequenceSample, mb_spec: MicroBatchSpec, loss_fn: LossFn
    ) -> Dict[str, float]:
        _, packed, weights = self._make_micro_batches(
            sample, mb_spec,
            weight_fn=lambda pb: (pb.arrays["segment_ids"] > 0).sum(),
        )
        ev = self._get_jitted("eval", loss_fn)
        # weights rode the capacity-agreement gather; ONE device pull for
        # all losses (each is a blocking device->host sync)
        losses = [ev(self.params, self._put_batch(pb))[0] for pb in packed]
        losses = np.asarray(jax.device_get(losses), np.float64)
        # all-padding mbs can yield nan means; their weight is 0
        tot = float(np.sum(np.where(weights > 0, losses * weights, 0.0)))
        return {"loss": tot / max(weights.sum(), 1)}

    def forward(  # arealint: hot (dispatch-ahead inference loop)
        self,
        sample: SequenceSample,
        mb_spec: MicroBatchSpec,
        output_fn: OutputFn,
        pipeline_depth: Optional[int] = None,
    ) -> List[np.ndarray]:
        """Token-aligned inference (logprob recompute, critic values, …).
        ``output_fn`` runs fully inside jit (e.g. forward + logprob gather so
        the [T, vocab] logits never leave the device). Returns one array per
        sequence, in the sample's original (item, seq) order — the micro-batch
        split reorders items, so results are matched back via item ids.

        Dispatch-ahead pipeline (``AREAL_FWD_PIPELINE``, default depth 2):
        up to ``pipeline_depth`` micro-batches stay in flight — mb i+1 is
        dispatched BEFORE mb i's result is fetched, so the device works
        through the queue while the host blocks in ``fetch_local_rows`` and
        unpacks rows. Results are byte-identical to the serial path (same
        jitted program, same inputs, only the host-side fetch order moves);
        ``self._last_forward_events`` records the (dispatch|fetch, mb)
        sequence and ``metrics.counters`` the realized depth, so tests
        can PROVE overlap rather than infer it."""
        depth = fwd_pipeline_depth() if pipeline_depth is None else pipeline_depth
        with tracing.span("fwd_pipe/pack"):
            mbs, packed, _ = self._make_micro_batches(sample, mb_spec)
        fwd = self._get_jitted("forward", output_fn)
        by_key: Dict[Any, np.ndarray] = {}
        events: List[Tuple[str, int]] = []
        # device-idle-gap accounting: wall time spent with NOTHING dispatched
        #-but-unfetched while more micro-batches remained — the host-side
        # serialization the pipeline exists to remove
        idle_gap = 0.0
        drained_at: Optional[float] = None

        def dispatch(i: int, pb):
            nonlocal idle_gap, drained_at
            with tracing.span("fwd_pipe/put"):
                dev_in = self._put_batch(pb)
            with tracing.span("fwd_pipe/dispatch"):
                out_dev = fwd(self.params, dev_in)
            if drained_at is not None:
                # compute queue was empty from the previous fetch until this
                # dispatch landed: pure host-serialization time
                idle_gap += time.perf_counter() - drained_at
                drained_at = None
            events.append(("dispatch", i))
            return out_dev

        def collect(i: int, pb, out_dev, n_in_flight: int):
            nonlocal drained_at
            with tracing.span("fwd_pipe/fetch"):
                out = multihost.fetch_local_rows(out_dev, self.n_local_rows)
            events.append(("fetch", i))
            if n_in_flight == 0 and i + 1 < len(packed):
                drained_at = time.perf_counter()
            if i >= len(mbs):
                # trailing multi-host padding batch: every process had to
                # dispatch it, but it carries no local rows
                return
            mb = mbs[i]
            with tracing.span("fwd_pipe/unpack"):
                for p, arr in zip(pb.placements, pb.unpack(out)):
                    by_key[(mb.ids[p.item_idx], p.seq_idx)] = arr

        max_in_flight = 0
        # iterate over `packed` (not zip with mbs) — trailing multi-host
        # padding batches have no local mb but every process must dispatch
        in_flight: "collections.deque" = collections.deque()
        for i, pb in enumerate(packed):
            in_flight.append((i, pb, dispatch(i, pb)))
            max_in_flight = max(max_in_flight, len(in_flight))
            if len(in_flight) >= max(depth, 1):
                j, jpb, jout = in_flight.popleft()
                collect(j, jpb, jout, len(in_flight))
        while in_flight:
            j, jpb, jout = in_flight.popleft()
            collect(j, jpb, jout, len(in_flight))

        self._last_forward_events = events
        metrics_mod.counters.add(metrics_mod.PIPE_FWD_DISPATCHED, len(packed))
        metrics_mod.counters.peak(
            metrics_mod.PIPE_FWD_MAX_IN_FLIGHT, max_in_flight
        )
        metrics_mod.counters.add(
            metrics_mod.PIPE_FWD_DEVICE_IDLE_GAP_S, idle_gap
        )

        outs: List[np.ndarray] = []
        main = sample.main_key()
        for i, item_id in enumerate(sample.ids):
            for j in range(len(sample.seqlens[main][i])):
                outs.append(by_key[(item_id, j)])
        return outs

    # ------------------------------------------------------------------ #
    # Checkpointing (orbax)
    # ------------------------------------------------------------------ #

    def _ckpt_state(self, with_optim: bool):
        state = {
            "params": self.params, "step": self._step, "version": self.version
        }
        if with_optim and self.opt_state is not None:
            state["opt_state"] = self.opt_state
        return state

    def save_checkpoint(self, path: str, with_optim: bool = True):
        """Atomic committed save: Orbax writes into a staging dir, then a
        ``COMMIT.json`` manifest (step, version, per-tree structural
        checksums) is fsynced and the staging dir renamed over ``path`` —
        a preemption at ANY instant leaves the previous committed
        checkpoint restorable (the old ``rmtree``-then-save destroyed it
        for the whole duration of the save)."""
        import os

        import orbax.checkpoint as ocp

        path = os.path.abspath(path)
        # the staging tag must agree across hosts (all processes write
        # shards into one dir): derive it from the step, not a nonce
        tag = f"s{self._step}"
        # main-only clean + barrier: concurrent rmtrees on a shared FS race
        # each other and the distributed orbax save
        if multihost.is_main():
            recover.prepare_staging(path, tag)
        multihost.barrier("ckpt_stage")
        staging = recover.staging_path(path, tag)
        state = self._ckpt_state(with_optim)
        with ocp.StandardCheckpointer() as ckptr:
            ckptr.save(staging, state)
        multihost.barrier("ckpt_saved")
        if multihost.is_main():
            faults.maybe_fail("ckpt.save", path=path)  # die "mid-save"
            recover.commit_checkpoint(staging, path, {
                "step": self._step,
                "version": self.version,
                "with_optim": "opt_state" in state,
                "checksums": {
                    k: recover.tree_checksum(v) for k, v in state.items()
                },
            })
        multihost.barrier("ckpt_commit")

    def validate_checkpoint(self, path: str, with_optim: bool = True) -> dict:
        """Validate WITHOUT restoring: resolve the newest committed dir at
        ``path`` (promoting a committed-but-unswapped staging sibling) and
        check the manifest's structural checksums against this engine's
        state tree. Returns the manifest. Callers restoring SEVERAL engines
        must validate all of them first — a raise after the first restore
        would leave the engines on mixed ticks. Raises ``FileNotFoundError``
        (nothing committed) or ``ValueError`` (incompatible/corrupt)."""
        import os

        path = os.path.abspath(path)
        if multihost.is_main():
            # promotes a committed-but-unswapped sibling and counts the
            # fallback (guard/ckpt_fallbacks) inside resolve_committed
            recover.resolve_committed(path)
        multihost.barrier("ckpt_resolve")
        manifest = recover.read_manifest(path)
        if manifest is None:
            raise FileNotFoundError(
                f"no committed checkpoint at {path} (missing or crashed "
                "before its COMMIT manifest landed)"
            )
        state = self._ckpt_state(with_optim)
        saved_sums = manifest.get("checksums", {})
        for k, v in state.items():
            want = saved_sums.get(k)
            if want is not None and want != recover.tree_checksum(v):
                raise ValueError(
                    f"checkpoint {path} is incompatible with this engine: "
                    f"param-tree checksum mismatch on {k!r} (model/optimizer "
                    "config drift or a corrupt save)"
                )
        return manifest

    def load_checkpoint(self, path: str, with_optim: bool = True):
        """Restore from the newest COMMITTED checkpoint at ``path``:
        uncommitted staging leftovers are skipped (and cleaned), a
        committed-but-unswapped staging dir from a crash mid-commit is
        promoted, and the manifest's structural checksums are validated
        against this engine's state tree before Orbax touches anything
        (:meth:`validate_checkpoint`)."""
        import os

        import orbax.checkpoint as ocp

        path = os.path.abspath(path)
        self.validate_checkpoint(path, with_optim)
        state = self._ckpt_state(with_optim)
        state["step"], state["version"] = 0, 0
        with ocp.StandardCheckpointer() as ckptr:
            restored = ckptr.restore(path, state)
        self.params = restored["params"]
        self._step = int(restored["step"])
        self.version = int(restored["version"])
        if with_optim and self.opt_state is not None:
            self.opt_state = restored["opt_state"]
        return self
