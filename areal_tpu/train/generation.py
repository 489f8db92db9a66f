"""On-mesh batched generation for sync-PPO (generate on the TRAINER's params).

Counterpart of the reference's generate MFC in sync PPO
(``realhf/impl/model/interface/ppo_interface.py:301`` +
``realhf/impl/model/nn/real_llm_generate.py``): the same weights that will be
updated this step produce the rollouts, with no weight-publish hop. Where the
reference reshards params between train and generate topologies
(param realloc), the TPU version just runs prefill + a ``lax.scan`` decode
loop under the SAME mesh/shardings as training — one jit per shape bucket.

The async fleet path (``areal_tpu/gen/engine.py``) stays separate: it owns
slot scheduling, interruption, and weight hot-swap. This module is the
simple, synchronous, whole-batch loop.
"""

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from areal_tpu.api.model import GenerationHyperparameters
from areal_tpu.gen.sampling import SamplingParams, sample_tokens
from areal_tpu.models import transformer as tfm
from areal_tpu.ops import attention as attn_ops


def _next_pow2(n: int, lo: int = 64) -> int:
    v = lo
    while v < n:
        v *= 2
    return v


@dataclasses.dataclass
class SyncGenOutput:
    """One sequence: prompt + generation, token-aligned logprobs."""

    tokens: np.ndarray        # [plen + n_gen] int64
    gen_logprobs: np.ndarray  # [n_gen] f32 (logprob of each generated token)
    no_eos: bool              # truncated (hit max_new_tokens / capacity)


class SyncGenerator:
    """Whole-batch generation on a TrainEngine's mesh + params."""

    def __init__(self, engine):
        self.engine = engine
        self._jit: Dict[Tuple[int, int, int, int, int], object] = {}
        mesh = engine.mesh
        self._batch_sharding = NamedSharding(mesh, P(("data", "fsdp"), None))
        self._row_sharding = NamedSharding(mesh, P(("data", "fsdp")))
        self._rep = NamedSharding(mesh, P())

    def _gen_fn(self, B: int, Sp: int, S: int, max_new: int, n_stop: int):
        key = (B, Sp, S, max_new, n_stop)
        if key in self._jit:
            return self._jit[key]
        cfg = self.engine.cfg
        batch_p = NamedSharding(
            self.engine.mesh, P(None, ("data", "fsdp"), None, None, None)
        )

        def gen(params, input_ids, prompt_lens, rng, sp, min_gen, stop_ids, active0):
            cache = tfm.KVCache.empty(cfg, B, S)
            cache = tfm.KVCache(
                k=jax.lax.with_sharding_constraint(cache.k, batch_p),
                v=jax.lax.with_sharding_constraint(cache.v, batch_p),
                lens=cache.lens,
            )
            logits, cache = tfm.prefill(params, cfg, cache, input_ids, prompt_lens)

            def sample_and_record(rng, logits, state):
                (cache, last, active, stopped, n_gen, out_t, out_lp) = state
                rng, sub = jax.random.split(rng)
                tok, lp = sample_tokens(sub, logits, sp)
                tok = jnp.where(active, tok, last)
                rows = jnp.arange(B)
                idx = jnp.clip(n_gen, 0, max_new - 1)
                out_t = out_t.at[rows, idx].set(jnp.where(active, tok, out_t[rows, idx]))
                out_lp = out_lp.at[rows, idx].set(jnp.where(active, lp, out_lp[rows, idx]))
                n_gen = n_gen + active.astype(jnp.int32)
                hit_stop = (
                    active
                    & jnp.any(tok[:, None] == stop_ids[None, :], axis=1)
                    & (n_gen >= min_gen)
                )
                stopped = stopped | hit_stop
                active = active & ~hit_stop & (n_gen < max_new) & (cache.lens < S)
                return rng, (cache, tok, active, stopped, n_gen, out_t, out_lp)

            state = (
                cache,
                jnp.zeros((B,), jnp.int32),
                active0,
                jnp.zeros((B,), bool),
                jnp.zeros((B,), jnp.int32),
                jnp.zeros((B, max_new), jnp.int32),
                jnp.zeros((B, max_new), jnp.float32),
            )
            rng, state = sample_and_record(rng, logits, state)

            def body(carry, _):
                rng, state = carry
                cache, last, active, stopped, n_gen, out_t, out_lp = state
                logits, cache = tfm.decode_step(params, cfg, cache, last, active=active)
                rng, state = sample_and_record(
                    rng, logits, (cache, last, active, stopped, n_gen, out_t, out_lp)
                )
                return (rng, state), None

            (rng, state), _ = jax.lax.scan(body, (rng, state), None, length=max_new - 1)
            _, _, _, stopped, n_gen, out_t, out_lp = state
            return out_t, out_lp, n_gen, ~stopped  # never hit EOS => truncated

        jitted = jax.jit(
            # the prefill's flash kernel needs shard_map under a
            # multi-device mesh
            attn_ops.trace_on_mesh(self.engine.mesh, gen),
            in_shardings=(
                self.engine._param_shardings,
                self._batch_sharding,   # input_ids
                self._row_sharding,     # prompt_lens
                self._rep,              # rng
                SamplingParams(          # per-slot sampling params
                    temperature=self._row_sharding,
                    top_p=self._row_sharding,
                    top_k=self._row_sharding,
                ),
                self._rep,              # min_gen
                self._rep,              # stop_ids
                self._row_sharding,     # active0
            ),
        )
        self._jit[key] = jitted
        return jitted

    def generate(  # arealint: hot (sync-PPO whole-batch generation)
        self,
        prompts: Sequence[Sequence[int]],
        ghp: GenerationHyperparameters,
        seed: int = 0,
    ) -> List[List[SyncGenOutput]]:
        """Generate ``ghp.n`` samples per prompt. Returns one group (list of
        :class:`SyncGenOutput`) per input prompt, in order."""
        eng = self.engine
        n_prompts = len(prompts)
        expanded: List[Sequence[int]] = [p for p in prompts for _ in range(ghp.n)]
        n_rows = eng.n_rows
        B = -(-len(expanded) // n_rows) * n_rows  # pad to the mesh
        Sp = _next_pow2(max(len(p) for p in expanded))
        max_new = ghp.max_new_tokens
        S = -(-(Sp + max_new) // 128) * 128
        stop = list(ghp.stop_token_ids) or [-1]

        input_ids = np.zeros((B, Sp), np.int32)
        plens = np.ones((B,), np.int32)  # padding slots prefill 1 dummy token
        active0 = np.zeros((B,), bool)
        for i, p in enumerate(expanded):
            input_ids[i, : len(p)] = p
            plens[i] = len(p)
            active0[i] = True
        temp = 0.0 if ghp.greedy else ghp.temperature
        sp = SamplingParams(
            temperature=jnp.asarray(np.full((B,), temp, np.float32)),
            top_p=jnp.asarray(np.full((B,), ghp.top_p, np.float32)),
            top_k=jnp.asarray(np.full((B,), min(ghp.top_k, 1 << 30), np.int32)),
        )
        fn = self._gen_fn(B, Sp, S, max_new, len(stop))
        # arealint: ok(the single whole-batch fetch after the decode scan — sync generation's one designed sync point)
        out_t, out_lp, n_gen, truncated = jax.device_get(
            fn(
                eng.params,
                jnp.asarray(input_ids),
                jnp.asarray(plens),
                jax.random.key(seed),
                sp,
                jnp.int32(ghp.min_new_tokens),
                jnp.asarray(stop, jnp.int32),
                jnp.asarray(active0),
            )
        )
        groups: List[List[SyncGenOutput]] = []
        for i in range(n_prompts):
            group = []
            for j in range(ghp.n):
                k = i * ghp.n + j
                g = int(n_gen[k])
                group.append(
                    SyncGenOutput(
                        tokens=np.concatenate(
                            [np.asarray(expanded[k], np.int64), out_t[k, :g].astype(np.int64)]
                        ),
                        gen_logprobs=out_lp[k, :g].astype(np.float32),
                        no_eos=bool(truncated[k]),
                    )
                )
            groups.append(group)
        return groups
