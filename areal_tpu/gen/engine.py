"""Slot-based continuous-batching generation engine over a paged KV pool.

TPU-native counterpart of the reference's generation stack: continuous
batching (``real_llm_generate.py:670`` inflight batching), chunked
interruptible generation (the SGLang ``InterruptAllReq`` patch +
``partial_rollout.py``), weight hot-reload (``update_weights_from_disk``),
and SGLang's radix/paged KV memory. Redesigned for XLA:

- KV memory is a POOL of fixed-size pages (``models/transformer.PagedKVCache``
  + ``gen/pages.py``); each slot holds a page table, so HBM scales with the
  tokens actually resident — not ``max_slots x max_seqlen`` slabs — and
  prompts SHARE pages for their longest common page-aligned prefix (a radix
  tree over pages; one prefill serves a
  whole GRPO group; the reason gserver routing is sticky per qid). The pool
  can store INT8 (``kv_dtype``/``cfg.kv_dtype``/``AREAL_KV_DTYPE``): pages
  quantize at the post-scan scatter, scales ride a parallel pytree, and
  dequant fuses into every paged-attention path — half the decode KV bytes,
  itemsize-ratio x pages at the same pool HBM (docs/performance.md "KV
  quantization").
- LAYER KINDS (``cfg.layer_pattern``: window and full layers in one stack):
  still ONE pool and ONE free list, pages of one byte size (a page holds
  ``page`` tokens of one position of the period in every period), and a
  slot has one table a position of the period. A model of one kind is the
  same code with one position a period: no second allocator. A stack plan
  whose attention layers differ (``phi4flash``: eight window layers, one
  full layer that seven cross-attention layers share) is the same code
  with a kind a CACHE layer and one period; beside the pool it keeps the
  state-space layers' per-slot state, as ``granitemoehybrid`` does.
- PAGES ARE TAKEN AS A SLOT GROWS, in every kind (``GenerationEngine``'s
  docstring has the whole policy): admission takes the prompt's pages and
  RESERVES one look-ahead; every chunk takes the pages it writes from the
  slot's reservation and tops the reservation up for the chunk after; a
  window kind also gives back, WHILE the request runs, the pages wholly
  behind ``len - window``. ``PagePool.n_unpromised >= 0`` at all times, so
  taking inside a dispatched chunk never fails. When the pool runs dry the
  engine stops admitting, then HOLDS the youngest slots out of a chunk,
  then PREEMPTS the smallest (its tokens kept, its pages filed in the
  prefix cache, the request back at the head of the queue): a client sees
  nothing of either but time.
- Admission = CHUNKED PREFILL: prompts stream through a fixed
  ``[n_rows, page]`` extend program, so compile count is bounded by the
  admit-row buckets alone — never by prompt length. A chunk is TWO
  programs: ``jit_extend`` runs the layers over the pool and returns the
  chunk's fresh K/V, ``jit_kv_write`` puts it into the pages (one program
  for every bucket and table width, so the write kernel is traced and
  lowered once a start).
- Decode: a jitted ``lax.scan`` chunk of N steps; stop-token detection and
  per-slot caps run on device, so the host syncs once per chunk.
- Interruption: the host stops issuing chunks and harvests partial outputs;
  clients re-submit with accumulated tokens (the reference's
  chunked-generation protocol, ``partial_rollout.py:106-114``).
- Weight update: swap the params pytree between chunks (the jitted programs
  are parametric in params). The prefix cache is invalidated — KV from old
  weights must not seed new-policy generations; in-flight slots keep their
  old-KV context, which is exactly the partial-rollout staleness the
  version_start/version_end tags account for.
- Tensor parallelism: pass a ``mesh`` with a ``model`` axis and the engine
  serves SHARDED — params split per ``GEN_RULES`` (the trainer's TP axes,
  embed replicated), the KV page pool splits on its kv-head axis, and the
  jitted extend/decode programs carry explicit in/out shardings so GSPMD
  partitions attention per head group and psums the projections, exactly
  where the reference's per-TP-group SGLang servers put NCCL
  (``realhf/system/generation_server.py:150``). Sampling runs replicated
  after one logits all-gather. This is what lets one server hold a 7B
  model across 4 v5e chips (bf16 weights ~3.5 GB/chip + KV pool).

Thread-safety: ``submit`` arrives on the server's asyncio thread while
``step`` runs in an executor thread — ALL mutable engine state
(slots, page pool, device state, request metadata) is guarded by one RLock.
"""

import dataclasses
import logging
import math
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from areal_tpu.base import constants, program_store, tracing
from areal_tpu.base import metrics as metrics_mod
from areal_tpu.gen.pages import PagePool, PrefixRegistry
from areal_tpu.gen.pages import _ids as _held_ids
from areal_tpu.gen.sampling import SamplingParams, sample_tokens
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig
from areal_tpu.ops import fused_sample as fused_ops
from areal_tpu.ops import moe as moe_ops
from areal_tpu.ops import paged_attention as paged_ops

logger = logging.getLogger("areal_tpu.gen.engine")

# Serving-side sharding rules: tensor parallelism only. Params shard over
# the ``model`` mesh axis exactly where the trainer's TP does (heads / mlp /
# vocab / expert logical axes); the ``embed`` logical axis stays REPLICATED
# — FSDP-style gathering is a training trade (params live once, gathered
# per layer) that would put an all-gather in every decode step here.
# Counterpart of the reference's per-TP-group SGLang servers
# (``realhf/api/cli_args.py:266`` SGLang tp_size,
# ``realhf/system/generation_server.py:150``).
from areal_tpu.parallel.mesh import DEFAULT_RULES as _TRAIN_RULES

GEN_RULES: Dict[str, Optional[str]] = {**_TRAIN_RULES, "embed": None}


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class GenState:
    cache: tfm.PagedKVCache
    lens: jnp.ndarray           # [B] i32 resident tokens per slot
    last_tokens: jnp.ndarray    # [B] i32 token to feed next decode
    active: jnp.ndarray         # [B] bool
    n_gen: jnp.ndarray          # [B] i32
    min_gen: jnp.ndarray        # [B] i32 suppress stop below this count
    max_gen: jnp.ndarray        # [B] i32
    stop_ids: jnp.ndarray       # [B, K] i32 per-slot stop tokens (-1 = unused)
    out_tokens: jnp.ndarray     # [B, G] i32
    out_logprobs: jnp.ndarray   # [B, G] f32
    sp: SamplingParams
    rng: jax.Array
    # MoE routing record (``record_routing``; None otherwise): the experts
    # the decode step that produced out_tokens[b, i] chose in every layer,
    # a token's ``[L, top_k]`` FLAT: the chip tiles an array's two minor
    # axes to (8, 128), and ``[.., 5, 22]`` then takes 9.3 x its bytes
    # (3.0 GB of temporaries in the decode chunk of 192 slots x 4,096)
    out_routing: Optional[jnp.ndarray] = None   # [B, G, L x top_k] i32
    # a model with state-space layers (``cfg.ssm``; None otherwise): what
    # those layers keep of each SLOT in place of keys and values, ``ssm
    # [Ls, B, G, K, N, 128]`` float32 and ``conv [Ls, B, (d_conv - 1) x C]``. Allocated
    # by slot, not by page; zeroed or seeded from a snapshot at admission
    # (never inherited from the slot's last tenant), carried between
    # admission's chunks, updated in place by every decode step.
    # A model whose attention runs inside a convolved latent (``cfg.cca``)
    # keeps its carry here the same way, BESIDE keys and values in every
    # layer (``tfm.CCAState``: ``carry [L, B, W]``, a few KB a layer); a
    # model with delta-rule layers (``cfg.kda``) a matrix a head of each
    # (``tfm.DeltaState``: ``s [Lk, B, H, Dk, Dv]`` float32 and ``conv``).
    ssm: Optional[Any] = None
    # the prefix cache's SNAPSHOTS of that state, ``[Ls, n_snapshots,
    # ...]``: entry ``i`` is the state after exactly the tokens of the
    # registry node that files it (``PrefixRegistry``), so a prompt that
    # hits there is seeded by one copy instead of a prefill
    snaps: Optional[Any] = None


@dataclasses.dataclass
class GenRequest:
    rid: str
    input_ids: List[int]
    max_new_tokens: int = 256
    min_new_tokens: int = 0
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 1 << 30
    greedy: bool = False
    stop_token_ids: List[int] = dataclasses.field(default_factory=list)
    # stamped by ``GenerationEngine.submit`` (host ``perf_counter``
    # seconds); rides the request to its slot and onto the ``GenOutput``
    t_submit: Optional[float] = None


@dataclasses.dataclass
class GenOutput:
    """One finished (or ``pause()``-interrupted) request.

    The four timestamps are host ``time.perf_counter`` seconds, taken by
    the engine with no device work: ``t_submit`` in ``submit``,
    ``t_admit`` when the request got its slot and pages, ``t_first`` at
    the resolve of the first chunk that ran the slot, ``t_done`` at its
    harvest. ``t_first`` is the moment a caller could first see a token
    (``partial_outputs`` after that chunk), so it is an upper bound on
    time to first token at chunk granularity, not the device's time of
    the first decode step; an interrupted request whose first chunk was
    never resolved gets ``t_done`` there if it has tokens, else ``None``.
    ``t_admit - t_submit`` is queue wait, ``t_first - t_submit`` time to
    first token, ``t_done - t_first`` decoding."""

    rid: str
    output_ids: List[int]
    output_logprobs: List[float]
    finish_reason: str            # "stop" | "length" | "interrupted"
    version: int = 0
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    # engines built with ``record_routing`` (MoE models): int32
    # ``[len(output_ids), n_layers, top_k]``, the experts chosen by the
    # decode step that produced each output token, i.e. the routing of the
    # INPUT token at position ``prompt_len - 1 + i`` of the sequence (the
    # trainer's ``routed_experts`` key wants it at that position)
    output_routing: Optional[np.ndarray] = None
    # prompt tokens this request did NOT prefill: served from shared pages
    # (and, with recurrent state, a snapshot) of the prefix cache
    prefix_hit_tokens: int = 0


# what the harvest's gather (``GenerationEngine._pull_outputs``) takes of a
# slot's output buffers: BLOCKS of this many positions (of the largest
# divisor of the cap that 128 has, so a cap that is no multiple of 128
# still splits evenly), one program a COUNT of blocks, the counts powers of
# two from the first to the second (fewer pad to the first; more take the
# largest program several times before the one pull). At 1 KB a block of
# tokens and log-probs (up to 56 KB with a routing record) the first costs
# nothing to pad to; the second is four rows at a cap of 15,360
_PULL_BLOCK = 128
_PULL_COUNTS = (8, 512)
# a per-slot state of at least this many bytes is a matrix a head (a
# recurrent or a delta-rule state: 13-76 MB a slot at the published sizes)
# and gets the few snapshots that memory allows; a smaller one is a
# convolution's carry (86 KB) and gets two a slot. Nothing lies between:
# the widest carry is a twelfth of the threshold, the smallest matrix state
# thirteen times it
_SNAPSHOT_MATRIX_STATE_BYTES = 2**20


def _page_ids(page, kind: Optional[int] = None):
    """Of one entry of a prefix hit (a page id, or with layer kinds the
    page of every kind, -1 where a window kind's is gone): the pool pages
    it holds, or the page of ``kind`` (-1: none)."""
    if kind is None:
        return _held_ids(page)
    return int(page) if isinstance(page, (int, np.integer)) else int(page[kind])


def _finish_reason(n_gen, max_gen) -> str:
    """length-vs-stop classification, shared by every harvest site."""
    return "length" if n_gen >= max_gen else "stop"


def _resolve_kv_dtype(kv_dtype: Optional[str], serving_dtype: str) -> str:
    """Normalize a KV-pool dtype request: None/"bf16"/"bfloat16"/the
    serving dtype itself -> the serving dtype string (raw unquantized
    pages — "bf16" reads as "not quantized", which under a float32 CPU
    test config means float32 pages); "int8" -> quantized pool. Anything
    else is a config error, raised here at engine construction, not deep
    inside a trace."""
    if kv_dtype is None:
        return serving_dtype
    v = kv_dtype.strip().lower()
    if v == "int8":
        return "int8"
    if v in ("bf16", "bfloat16", serving_dtype):
        return serving_dtype
    raise ValueError(
        f"unsupported kv_dtype {kv_dtype!r}: expected 'int8', 'bf16', or "
        f"the serving dtype ({serving_dtype!r})"
    )


@dataclasses.dataclass
class _SlotInfo:
    """A running request's slot. Its pages are the entries of its tables
    that ``GenerationEngine._held`` marks (one reference each, whether the
    slot took the page fresh or borrowed it from the prefix registry); its
    cap, its end, its place in the order of admission and what it has
    reserved are the engine's ``_n_total``, ``_n_end``, ``_seq`` and
    ``_reserved`` (arrays over the slots)."""

    rid: str
    t_submit: Optional[float] = None    # the GenOutput's timestamps
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    prefix_hit_tokens: int = 0


class GenerationEngine:
    """Continuous batching over a paged KV pool (the module's docstring).

    THE PAGE POLICY, one for every layer kind and every model:

    - *Admission takes the prompt, reserves one look-ahead.* A request
      gets a slot when the pool can give it its prompt's pages (shared
      prefix pages borrowed, the rest fresh) and promise it the pages its
      next chunk can write (``_reserved`` / ``PagePool.reserved``;
      a window kind: its whole claim, the window and a look-ahead, which
      is all it ever holds), AND the pool still covers what the running
      slots and the newcomer will hold at every point of the next
      ``ADMIT_HORIZON`` positions, each slot growing a position a step up
      to its ``max_new_tokens`` and freeing its pages there
      (``_spare_pages``). ``n_total``, prompt + whole output, is a slot's
      cap, never its holding.
    - *A chunk takes what it writes* (``_seat``): before a decode chunk
      every slot takes, from its reservation, the pages up to the last
      position the chunk can write, and its reservation is topped up for
      the chunk after. INVARIANT: ``pool.n_unpromised >= 0`` at all times
      (what is promised is backed by pages that are free or held by the
      prefix registry alone), so taking inside a dispatched chunk never
      fails. The takes of one chunk boundary are summed and the registry
      is asked once, for a batch.
    - *The dry rule.* When a slot's reservation cannot be made to cover
      its next chunk (nothing free, nothing held by the registry alone),
      in this order: (a) nothing new is admitted (the queue waits);
      (b) the slot is HELD out of the chunk, youngest first: it keeps its
      pages and its device state and runs again when a finishing slot has
      freed pages; (c) if every slot would be held, the slot with the
      fewest positions is PREEMPTED: its tokens and log-probs are pulled
      to the host, its full pages are filed in the prefix registry and
      released, and the request goes back to the HEAD of the queue as
      prompt + generated-so-far. Re-admitted it is an ordinary prefix hit
      (a re-prefill where its pages were evicted meanwhile, or where the
      model keeps per-slot recurrent state: no snapshot stands at its last
      position), and its ``GenOutput`` carries ALL its tokens and
      log-probs under the original's ``t_submit`` / ``t_admit`` /
      ``t_first``. Some slot always runs, so every request ends.
    - *What a client can observe*: nothing but time. No finish reason is
      new, no output is shorter, ``partial_outputs`` and ``pause`` hand out
      a held or preempted request's tokens like any other's. A request
      that could not run even alone in the pool is refused at ``submit``.

    WHAT A CHUNK BOUNDARY MOVES TO THE HOST, two pulls a step: the chunk's
    FLAGS (``active``, ``n_gen``, ``max_gen``, ``lens`` of every slot and
    the chunk's counts, a few KB, their copy started at dispatch), and,
    where a slot finished, what the finished slots WROTE: their tokens,
    log-probs and (``record_routing``) routing in blocks of
    ``_pull_block`` positions, ``ceil(n_gen / block)`` a slot by the
    flags' ``n_gen``, gathered on the device by one program built with
    the engine and pulled under ``gen_engine/harvest/pull`` (its ``bytes``,
    ``rows``, ``blocks``). Never the ``[slots, max_new_tokens_cap]``
    buffers themselves. ``pause``, ``partial_outputs``,
    ``partial_routing`` and a preemption read through the same
    ``_pull_outputs``; they hold no fresh flags, so they pull
    ``n_gen`` / ``active`` / ``max_gen`` (``[slots]`` each) first.

    Counters (``stats``, the chunk and admit spans, ``metrics``):
    ``pages_taken_growing``, ``slots_held`` (slot-chunks held out),
    ``preemptions``, ``preempted_tokens_recomputed``; ``slots_running`` on
    every chunk span."""

    # Positions ahead over which admission checks that the pool covers the
    # running slots' growth (``_spare_pages``). Fitted on the Ouro cell's
    # traffic at pools of 0.35-1 x its own (PERF.md section 6, PR 43: twice
    # the shortest that held no slot): long enough that the dry rule stays
    # rare, short enough that a request whose ``max_new_tokens`` is a
    # far-off cap is not counted at its cap.
    ADMIT_HORIZON = 512

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        max_slots: int = 8,
        max_seqlen: int = 2048,
        max_new_tokens_cap: int = 1024,
        stop_token_ids: Sequence[int] = (),
        admit_buckets: Sequence[int] = (1, 2, 4, 8),
        seed: int = 0,
        page_size: int = 128,
        n_pages: Optional[int] = None,
        kv_dtype: Optional[str] = None,
        enable_prefix_cache: bool = True,
        mesh: Optional[Mesh] = None,
        admit_chunk_tokens: Optional[int] = None,
        pipeline_chunks: Optional[bool] = None,
        fused_sample: Optional[bool] = None,
        record_routing: bool = False,
        state_snapshots: Optional[int] = None,
    ):
        # one listener a process, live before the first device work below
        tracing.listen_for_compiles()
        with tracing.span("gen_engine/start", max_slots=max_slots) as start:
            self.cfg = cfg
            self.mesh = mesh
            # MoE models: every chunk returns a routing census with its
            # harvest flags (``_fold_chunk_aux``); ``record_routing`` also keeps
            # each output token's chosen experts for ``GenOutput``
            self._moe = cfg.mlp_type == "moe"
            if record_routing and not self._moe:
                raise ValueError("record_routing: the model has no router")
            self._decode_use_pallas: Optional[bool] = None
            # KV-pool storage dtype (docs/performance.md "KV quantization"):
            # explicit argument > cfg.kv_dtype > AREAL_KV_DTYPE > serving dtype
            kd = kv_dtype if kv_dtype is not None else (
                cfg.kv_dtype if cfg.kv_dtype is not None else constants.kv_dtype()
            )
            self.kv_dtype = _resolve_kv_dtype(kd, cfg.dtype)
            self.kv_quantized = self.kv_dtype == "int8"
            if cfg.mla is not None:
                # what the latent pool does not do is refused, not approximated
                if self.kv_quantized:
                    raise NotImplementedError(
                        "latent attention: the page pool holds latents in the "
                        "serving dtype; an int8 pool is not supported"
                    )
                if mesh is not None and mesh.shape.get("model", 1) > 1:
                    raise NotImplementedError(
                        "latent attention: a latent page has no head axis to "
                        "shard; tensor-parallel serving is not supported"
                    )
            # per-slot state beside the page pool: the state-space or the
            # delta-rule layers' recurrent state, or the convolved latent's
            # carry (the ``state_*`` counters are of whichever the model
            # has: ``tfm.row_state_*`` say what, how large, in how many
            # layers)
            kind = tfm.row_state_kind(cfg)
            self._stateful = kind is not None
            if self._stateful:
                # what has no test beside per-slot state is refused, not
                # approximated
                if self.kv_quantized:
                    raise NotImplementedError(
                        f"{kind}: an int8 page pool beside per-slot state "
                        "is not supported"
                    )
                if mesh is not None and mesh.size > 1:
                    raise NotImplementedError(
                        f"{kind}: the per-slot state has no sharding; a "
                        "mesh of several devices is not supported"
                    )
            if mesh is not None:
                if "model" not in mesh.axis_names:
                    raise ValueError(
                        "generation mesh needs a 'model' axis, got "
                        f"{mesh.axis_names}"
                    )
                tp = mesh.shape["model"]
                # bare pallas_call has no GSPMD partitioning rule, so >1-way
                # 'model' serving routes the decode kernel through shard_map
                # over the kv-head axis (ops/paged_attention.py) — r5, replaces
                # the r3 XLA-gather pin; _decode_use_pallas stays None (auto)
                from areal_tpu.parallel.mesh import check_tp_divisibility

                check_tp_divisibility(cfg, tp, role="generation")
                self._repl = NamedSharding(mesh, P())
                # pool [L, P, 2, Hkv, page, D]: shard the kv-head dim (a latent
                # pool's is 1 and the mesh's model axis too, checked above); the
                # int8 pool's scales [L, P, 2, Hkv, page] extend the same
                # Hkv-axis TP split (scales are per kv head, so each model
                # shard holds exactly its local heads' scales)
                self._pages_sh = NamedSharding(
                    mesh, P(None, None, None, "model", None, None)
                )
                self._scales_sh = NamedSharding(
                    mesh, P(None, None, None, "model", None)
                )
                from areal_tpu.parallel.mesh import param_shardings

                self._param_sh = param_shardings(
                    mesh, tfm.param_logical_axes(cfg), GEN_RULES
                )
            with tracing.span("gen_engine/start/params"):
                self.params = self.prepare_params(params)
            self.B = max_slots
            self.page = page_size
            self.M = -(-max_seqlen // page_size)      # table width (pages/slot)
            self.S = self.M * page_size
            self.G = max_new_tokens_cap
            self.version = 0
            # prefill streams through [n_rows, admit_chunk] extend programs;
            # bigger chunks amortize the per-chunk attention over resident KV
            # (31.5k prompt at chunk 128 = 246 waves each re-reading the whole
            # prefix; at 2048 = 16 waves) at the cost of padding short prompts
            # up to one chunk. Default: one page (exact, best for short prompts).
            if admit_chunk_tokens is None:
                self.admit_chunk = page_size
            else:
                self.admit_chunk = max(
                    page_size, -(-admit_chunk_tokens // page_size) * page_size
                )
            self.admit_buckets = sorted(admit_buckets)
            self.global_stop_ids = list(stop_token_ids)
            self.max_stop_ids = 8
            self.enable_prefix_cache = enable_prefix_cache
            # dense-equivalent pool by default, sized at the SERVING-dtype HBM
            # budget: a quantized pool's smaller elements buy more pages for
            # the same bytes (int8 under bf16 serving = 2x n_pages — the whole
            # point: more resident slots/longer prefixes at fixed HBM), never
            # a smaller footprint by surprise. Pass n_pages to cap bytes.
            bytes_ratio = jnp.dtype(cfg.dtype).itemsize if self.kv_quantized else 1
            # layer kinds: the sliding window of each position of the model's
            # period (None: full attention); one page table a position
            self._windows = [w for w, _ in cfg.layer_kinds]
            self._windowed = any(w is not None for w in self._windows)
            self._full_kinds = np.array([w is None for w in self._windows])
            self._n_full = int(self._full_kinds.sum())
            K = len(self._windows)
            self.n_pages = (
                n_pages if n_pages is not None
                else self.B * self.M * bytes_ratio * K
            )
            self.pool = PagePool(self.n_pages, page_size)
            # snapshots of the per-slot state in the prefix cache: 8 of a
            # recurrent state (76 MB each at the published sizes, 13 MB of
            # a delta-rule model's); of a state under a megabyte (the
            # convolved latent's carry: 86 KB) two a slot: an admission
            # files at most one, so the runs the running slots filed keep
            # theirs and as many runs again whose tenants have left (the
            # least recently used goes first; a run is a page at least)
            if state_snapshots is None:
                matrix = (
                    tfm.row_state_bytes(cfg) >= _SNAPSHOT_MATRIX_STATE_BYTES)
                state_snapshots = (
                    8 if matrix else min(2 * self.B, self.n_pages))
            self.n_snapshots = (
                max(int(state_snapshots), 1)
                if self._stateful and enable_prefix_cache else 0)
            self.prefix = PrefixRegistry(
                self.pool, self._windows,
                n_snapshots=self.n_snapshots if self._stateful else None,
            )
            # positions a dispatch may run ahead of the host's lengths: the
            # chunk's tokens and, in pipelined mode, the chunk still in flight,
            # at ``step``'s default of 16 decode steps: what admission
            # reserves for a slot's first chunk and a window kind's claim is
            # sized by (a longer chunk takes what it needs beyond that from
            # the pool at large, or its slot is held: ``_seat``)
            self._lookahead = 2 * 16
            # the most pages a slot holds in a window kind at once: positions
            # ``[n + 1 - window, n + lookahead)`` wherever they lie in their
            # pages
            self._window_claim = [
                None if w is None
                else (w + self._lookahead - 2) // page_size + 2
                for w in self._windows
            ]

            def make_state() -> GenState:
                return GenState(
                    cache=tfm.PagedKVCache.empty(
                        cfg, self.n_pages, page_size,
                        kv_dtype="int8" if self.kv_quantized else None,
                    ),
                    lens=jnp.zeros((self.B,), jnp.int32),
                    last_tokens=jnp.zeros((self.B,), jnp.int32),
                    active=jnp.zeros((self.B,), bool),
                    n_gen=jnp.zeros((self.B,), jnp.int32),
                    min_gen=jnp.zeros((self.B,), jnp.int32),
                    max_gen=jnp.zeros((self.B,), jnp.int32),
                    stop_ids=jnp.full((self.B, self.max_stop_ids), -1, jnp.int32),
                    out_tokens=jnp.zeros((self.B, self.G), jnp.int32),
                    out_logprobs=jnp.zeros((self.B, self.G), jnp.float32),
                    sp=SamplingParams.filled(self.B),
                    rng=jax.random.key(seed),
                    out_routing=(
                        jnp.zeros(
                            (self.B, self.G, cfg.n_moe_layers * cfg.moe.top_k),
                            jnp.int32,
                        )
                        if record_routing
                        else None
                    ),
                    ssm=tfm.row_state_empty(cfg, self.B),
                    snaps=(
                        tfm.row_state_empty(cfg, self.n_snapshots)
                        if self.n_snapshots else None
                    ),
                )

            with tracing.span("gen_engine/start/state"):
                if mesh is None:
                    self._state_sh = None
                    self.state = make_state()
                else:
                    # the KV pool shards on its Hkv axis; everything else
                    # replicates. Creating the state UNDER jit with
                    # out_shardings lands each pool shard directly on its
                    # device — no transient full-size buffer.
                    sh = jax.tree.map(
                        lambda _: self._repl, jax.eval_shape(make_state)
                    )
                    sh = dataclasses.replace(
                        sh,
                        cache=tfm.PagedKVCache(
                            pages=self._pages_sh,
                            scales=(
                                self._scales_sh if self.kv_quantized else None
                            ),
                        ),
                    )
                    self._state_sh = sh
                    # arealint: ok(one-time engine-state materialization at construction)
                    self.state = jax.jit(make_state, out_shardings=sh)()
                # the dry rule's one device program, built and run here (a
                # no-op) so that holding a slot compiles nothing later
                self._jit_activity = self._activity_fn()
                self._set_activity(build=True)
                # likewise the harvest's gather, every count it comes in
                self._pull_block = math.gcd(self.G, _PULL_BLOCK)
                self._pull_counts = [_PULL_COUNTS[0]]
                while self._pull_counts[-1] < min(
                        _PULL_COUNTS[1], self.B * self.G // self._pull_block):
                    self._pull_counts.append(2 * self._pull_counts[-1])
                self._jit_pull = self._pull_fn()
                for n in self._pull_counts:
                    self._jit_pull(
                        self._out_buffers(), np.zeros((2, n), np.int32))
            self.accepting = True  # False = decode only, no new admissions
            self.paused = False
            self._slots: List[Optional[_SlotInfo]] = [None] * self.B
            # one table a layer kind; ``_table_host`` is the first (the only
            # one of a model of one kind), ``_held`` which entries a slot holds
            # a reference through, ``_win_lo`` / ``_win_hi`` the pages of a
            # window kind released so far / taken so far
            self._tables_host = np.zeros((K, self.B, self.M), np.int32)
            self._table_host = self._tables_host[0]
            self._held = np.zeros((K, self.B, self.M), bool)
            self._win_lo = np.zeros((K, self.B), np.int64)
            self._win_hi = np.zeros((K, self.B), np.int64)
            # of each slot: its CAP in pages a kind (prompt + whole output:
            # never its holding), the positions it holds when its output is
            # whole, and a kind's pages promised to it and not yet taken (a
            # full kind's next chunk, a window kind's claim)
            self._n_total = np.zeros((self.B,), np.int64)   # 0: a free slot
            self._n_end = np.zeros((self.B,), np.int64)
            self._seq = np.zeros((self.B,), np.int64)   # order of admission
            self._reserved = np.zeros((K, self.B), np.int64)
            # slots held out of the chunks by the dry rule (inactive on the
            # device meanwhile), the admission count that orders the slots,
            # and what a preempted request had generated before it lost its
            # slot, by rid: prepended to what its later tenures generate
            self._held_out: set = set()
            self._admit_seq = 0
            self._room: Optional[int] = None   # ``kv_pool_demand_occupancy``
            # admission's device programs dispatched so far (the
            # ``gen_engine/admit/prefill`` span's ``programs``)
            self._admit_programs = 0
            self._carried: Dict[str, dict] = {}
            # window kinds' pages that more than one slot holds, counted once
            # for every holder past the first: each is a page promised
            # (``pool.reserved``) to whichever holder gives the shared page up
            # while another still reads it, and then needs one of its own
            self._deposits = 0
            # host mirror of per-slot resident lengths: admission knows them
            # exactly, each chunk's sync refreshes them — lets decode chunks
            # run width-limited (see _table_width) without extra device pulls
            self._lens_host = np.zeros((self.B,), np.int64)
            # host mirror of "does this slot warp" (top-p/top-k active): when
            # no resident slot warps, the decode chunk skips the [B, V] sort —
            # the most expensive op of a step at a 152k vocab
            self._warp_host = np.zeros((self.B,), bool)
            # fused-epilogue routing mirrors: under the fused sampler a slot
            # only needs the sorted fallback for machinery the online pass
            # lacks — top-p, or top-k wider than the online buffer
            # (_fused_warp_host); plain top-k slots up to TOPK_MAX stay fused
            # through the online top-k buffer (_fused_topk_host)
            self._fused_warp_host = np.zeros((self.B,), bool)
            self._fused_topk_host = np.zeros((self.B,), bool)
            self._pending: List[GenRequest] = []
            self._req_meta: Dict[str, GenRequest] = {}
            # chunk pipelining (step() docstring): harvest one chunk late so
            # the per-chunk host sync overlaps the next chunk's compute
            self._pipeline = (
                pipeline_chunks
                if pipeline_chunks is not None
                else constants.decode_pipeline_enabled()
            )
            # fused sampling epilogue (docs/performance.md "Fused sampling
            # epilogue"): decode chunks return final-norm hidden states
            # and the sampler streams the LM head over vocab blocks — the
            # [B, V] logits (and their sort) leave the per-token path. Exact
            # for greedy, distribution-exact otherwise; top-p (and top-k >
            # TOPK_MAX) slots keep the sorted path via the warp-row bucket.
            # No flag: the rule reads what this engine can observe (one TPU
            # device, a head stored in the serving dtype, tied or not, a
            # policy); the argument pins either side (tests, the CPU's
            # streamed XLA pass).
            self.fused = (
                fused_sample
                if fused_sample is not None
                else fused_ops.fused_sample_applies(cfg, self.params, mesh)
            )
            self._prev_flags = None           # chunk k's undonated flag outputs
            self._prev_running: tuple = ()    # (slot, epoch) pairs at k's dispatch
            self._steps_ahead = 0   # token-advance bound of the in-flight chunk
            # admission generation per slot: stale flags from a chunk dispatched
            # before the slot turned over must never harvest its NEW occupant
            self._slot_epoch = np.zeros((self.B,), np.int64)
            # Two-tier locking: `_lock` guards device state / slots / pool and is
            # held by step() for a whole decode chunk; `_pending_lock` guards
            # ONLY the intake queue so submit() on the server's asyncio thread
            # never blocks behind a running chunk. free_slots/n_running read the
            # slot list without a lock (GIL-atomic snapshot; metrics may lag one
            # chunk, which is fine).
            self._lock = threading.RLock()
            self._pending_lock = threading.Lock()
            self._jit_extend: Dict[int, Any] = {}
            self._jit_kv_write: Dict[int, Any] = {}
            self._jit_commit: Dict[int, Any] = {}
            self._jit_state: Dict[Any, Any] = {}
            self._jit_chunk: Dict[int, Any] = {}
            # observability
            self.stats = {
                "prefill_tokens": 0,        # prompt tokens actually computed
                "prefix_hit_tokens": 0,     # prompt tokens served from shared pages
                "prefix_hits": 0,
                "admitted": 0,
                # per kernel-run chunk, at its first step: KV positions the
                # paged-decode kernel computes over / KV tokens resident
                "kernel_positions": 0,
                "resident_tokens": 0,
                # ... and its grid steps that reach a page / all its grid
                # steps / the reached steps whose copies a reached step of
                # another block starts (the prefetch chain's block crossings)
                "kernel_steps_active": 0,
                "kernel_steps": 0,
                "kernel_steps_chained": 0,
                # ... and, of the full layers' call: the pages the rows'
                # tables hold under their lengths / the page copies the
                # kernel's programs start (fewer where rows name the same
                # pages: those go through the prefix program once a block)
                # / its blocks in use / the rows seated in them
                "kv_pages_named": 0,
                "kv_pages_read": 0,
                "kv_shared_groups": 0,
                "kv_shared_rows": 0,
                # pool tiles the ``kv_page_write`` kernel writes: of a decode
                # chunk as dispatched, of an admission wave's prefill; stays 0
                # where the XLA scatter writes the pool (``_kv_write_rows``)
                "kv_write_tiles": 0,
                # layer kinds: pages of window kinds released behind the window
                # while their request ran (at admission's chunks and before
                # decode chunks), and the window kinds' share of the resident
                # tokens (sum over running slots of min(len, window), a chunk)
                "window_pages_released": 0,
                "window_resident_tokens": 0,
                # MoE models, per decode chunk (every row of the batch routes,
                # free slots too: the expert matmuls read what they route to):
                # distinct experts with a token, summed over layers and steps /
                # layers x steps x experts / the most tokens one expert got in
                # one layer-step (max, not summed)
                "moe_experts_hit": 0,
                "moe_expert_slots": 0,
                "moe_load_max": 0,
                # rows x expert layers x steps (decode chunks) or x chunks
                # (admission's prefill programs) as dispatched, by where the
                # routed experts ran them: the grouped-matmul kernel, or the
                # dense einsums (``_moe_grouped``: the predicate the program
                # was built with); both 0 for a model without a router
                "moe_grouped_rows": 0,
                "moe_dense_rows": 0,
                # fused chunks, rows x steps as dispatched: sampled by the
                # fused head-and-sample pass / sent to the sorted path (top-p,
                # top-k past the online buffer); both 0 on a materialised engine
                "fused_rows": 0,
                "sampler_fallback_rows": 0,
                # steps x ``cfg.n_passes`` (runs of the whole stack) and
                # steps x passes x layers (runs of one layer: each reads that
                # layer's weights once) of the decode chunks as dispatched
                "loop_passes": 0,
                "layer_passes": 0,
                # a model with state-space layers: running slots x steps of
                # the decode chunks as dispatched (each reads and writes one
                # slot's state once a layer); snapshots of that state filed
                # in the prefix cache, admissions seeded from one, the bytes
                # copied in and out, and snapshots dropped to make room or
                # with their node
                "state_slots": 0,
                "state_snapshots_taken": 0,
                "state_snapshot_hits": 0,
                "state_snapshot_bytes": 0,
                "state_snapshot_evictions": 0,
                # the page policy (class docstring): pages slots took while
                # running (every kind, decode chunks and admission's), slot-
                # chunks the dry rule held out, requests it preempted, and
                # the positions their re-admission prefilled again
                "pages_taken_growing": 0,
                "slots_held": 0,
                "preemptions": 0,
                "preempted_tokens_recomputed": 0,
            }
            # what a chunk's routing census carries behind its first three
            # (``_fold_chunk_aux``), of the decode chunks' routing (active
            # rows x expert layers x steps): with a skip output, rows in
            # all and rows that took the skip; on an expert-parallel
            # rank's SHARE of the experts, (row, expert) pairs chosen, the
            # pairs that landed on experts held here, and the held experts
            # with a row (of ``n_held`` a layer-step)
            self._census_extra = ()
            if self._moe and cfg.moe.skip_expert:
                self._census_extra = ("moe_rows", "moe_skip_rows")
            elif self._moe and not cfg.moe.holds_all:
                self._census_extra = (
                    "moe_pairs", "moe_pairs_held", "moe_held_experts_hit")
            self.stats.update(dict.fromkeys(self._census_extra, 0))
            start.update(n_pages=self.n_pages, pool_bytes=self.kv_pool_bytes())

    # ------------------------------------------------------------------ #
    # Client API
    # ------------------------------------------------------------------ #

    def submit(self, req: GenRequest):
        # runs on the server's asyncio thread, so the span inherits the
        # request's activated trace context — the engine-layer hop of the
        # distributed trace (chunk spans are batch-level and root their own)
        with tracing.span(
            "gen_engine/submit", rid=req.rid, prompt_len=len(req.input_ids)
        ):
            need = len(req.input_ids) - 1 + min(req.max_new_tokens, self.G)
            if need > self.S:
                raise ValueError(
                    f"prompt {len(req.input_ids)} + max_new "
                    f"{req.max_new_tokens} exceeds per-slot capacity {self.S}"
                )
            # the dry rule's last resort is one slot alone in the pool
            n_total = -(-need // self.page)
            alone = sum(
                n_total if claim is None else min(n_total, claim)
                for claim in self._window_claim)
            if alone > self.n_pages:
                raise ValueError(
                    f"prompt {len(req.input_ids)} + max_new "
                    f"{req.max_new_tokens} needs {alone} pages, the pool "
                    f"has {self.n_pages}: it could not run even alone"
                )
            req.t_submit = time.perf_counter()
            with self._pending_lock:
                self._pending.append(req)
                self._req_meta[req.rid] = req

    def free_slots(self) -> int:
        return sum(s is None for s in self._slots)

    def n_running(self) -> int:
        return sum(s is not None for s in self._slots)

    def n_pending(self) -> int:
        with self._pending_lock:
            return len(self._pending)

    def n_compiles(self) -> int:
        """Total jitted specializations (stability tested: bounded by the
        admit buckets + decode chunk sizes, NOT by prompt lengths;
        the dry rule's one program and the harvest's gather, built with
        the engine, are not among them)."""
        return (
            len(self._jit_extend) + len(self._jit_kv_write)
            + len(self._jit_commit) + len(self._jit_state)
            + len(self._jit_chunk)
        )

    def n_jit_entries(self) -> int:
        """Jax-level cache entries across the engine's jitted programs
        (counts re-specializations the python-level ``n_compiles`` cannot
        see, e.g. layout or sharding drift on donated state)."""
        from areal_tpu.base import jitcache

        return jitcache.total_cache_size(
            j
            for d in (self._jit_extend, self._jit_kv_write, self._jit_commit,
                      self._jit_state, self._jit_chunk,
                      {(): self._jit_activity}, {(): self._jit_pull})
            for j in d.values()
        )

    def program_sizes(self) -> Dict[str, int]:
        """``n_jit_entries`` by program: the jax-level specializations of
        each admission, write, commit and chunk program, under the key the
        engine files it by (``"extend(2, 64, False)"``). Two readings around a
        window name the program that was specialised inside it."""
        from areal_tpu.base import jitcache

        return {
            f"{name}{key}": jitcache.cache_size(fn)
            for name, d in (("extend", self._jit_extend),
                            ("kv_write", self._jit_kv_write),
                            ("commit", self._jit_commit),
                            ("state", self._jit_state),
                            ("chunk", self._jit_chunk),
                            ("activity", {(): self._jit_activity}),
                            ("pull", {(): self._jit_pull}))
            for key, fn in d.items()
        }

    def table_widths(self) -> List[int]:
        """The page-table widths (pages) the admission and chunk programs
        come in, narrowest first: a warm-up has to reach each."""
        return sorted({
            self._table_width(p * self.page) for p in range(1, self.M + 1)
        })

    def kv_pool_bytes(self) -> int:
        """Configured KV-pool HBM footprint (pages + quant scales),
        computed from shapes — no device pull. The serving gauge the
        fleet aggregator watches for HBM headroom."""
        cfg, quantized = self.cfg, self.kv_quantized
        # what the MODEL says a token holds (K and V heads, or one padded
        # latent row), not 2 * Hkv * D
        streams, heads, width = tfm.kv_page_geometry(cfg)
        elems = cfg.n_periods * self.n_pages * streams * heads * self.page
        item = 1 if quantized else jnp.dtype(cfg.dtype).itemsize
        total = elems * width * item
        if quantized:
            total += elems * 4  # one f32 scale per (token slot, head, K|V)
        return total

    def cache_bytes_per_token(self) -> int:
        """What one resident token really takes in the pool, every layer,
        padding and an int8 pool's scales included (with layer kinds: a
        token that is resident in every kind)."""
        return sum(self.cache_bytes_per_token_by_kind().values())

    def cache_bytes_per_token_by_kind(self) -> Dict[str, int]:
        """:meth:`cache_bytes_per_token` split by layer kind: ``full``
        layers hold a token for as long as its request runs, ``window``
        layers for ``sliding_window`` positions."""
        one = self.kv_pool_bytes() // (self.n_pages * self.page)
        n_window = sum(w is not None for w in self._windows)
        out = {"full": one * (len(self._windows) - n_window)}
        if n_window:
            out["window"] = one * n_window
        return out

    def kv_pool_occupancy(self) -> float:
        """Fraction of pool pages currently held (slots + prefix cache) or
        promised to a running slot (its next chunk's pages, a window
        kind's claim; counted against the free ones; what the registry
        alone holds backs a promise too and is held either way). Pages
        are taken as slots grow, so this is what the slots have WRITTEN
        and a look-ahead, not what their outputs may come to."""
        free = max(self.pool.n_free - self.pool.reserved, 0)
        return 1.0 - free / max(self.n_pages, 1)

    def kv_pool_demand_occupancy(self) -> float:
        """One less the share of the pool that the NEXT ADMISSION can
        have — the signal external gates (the serving gateway) should use.
        Pages the prefix cache alone holds count as free (the next
        admission would evict them: a cache-warm idle server must not read
        as full); pages promised to running slots do not; and neither do
        the pages the running slots will grow into over the next
        ``ADMIT_HORIZON`` positions, less what those that end there give
        back (``_spare_pages``, as of the last chunk boundary): the engine
        itself admits against that, so a server whose slots are young and
        growing does not read as empty."""
        room = self.pool.n_unpromised
        if self._room is not None and self.n_running():
            room = min(room, self._room)
        return 1.0 - max(room, 0) / max(self.n_pages, 1)

    def _observe_occupancy(self):
        """Fold the current pool occupancy into the telemetry histogram —
        host arithmetic riding a chunk dispatch the engine already pays."""
        occ = self.kv_pool_occupancy()
        self._room = int(self._spare_pages().min())
        metrics_mod.counters.observe(metrics_mod.GEN_KV_POOL_OCCUPANCY, occ)

    def prepare_params(self, params):
        """Cast a (host or device) param pytree to the serving dtype and,
        under a mesh (TP serving), place each leaf on its mesh shard. Numpy
        leaves cast on host so no full-size unsharded buffer ever lands on
        one device."""
        dt = jnp.dtype(self.cfg.dtype)
        params = jax.tree.map(
            lambda x: x if x.dtype == dt else x.astype(dt), params
        )
        if self.mesh is not None:
            return jax.device_put(params, self._param_sh)
        return jax.tree.map(jnp.asarray, params)

    def update_params(self, params, version: Optional[int] = None):
        """Hot weight swap between decode chunks (≈ interrupt + reload).
        Invalidates the prefix cache: prompt KV computed under old weights
        must not seed new generations."""
        if self.mesh is not None:
            params = jax.device_put(params, self._param_sh)
        # the span covers the swap itself (what a chunk boundary pays),
        # not the wait for the running chunk to release the lock
        with self._lock, tracing.span("gen_engine/weight_swap") as attrs:
            self.params = params
            self.version = version if version is not None else self.version + 1
            self.prefix.clear()
            attrs["version"] = self.version

    def partial_outputs(
        self, rids: Optional[Sequence[str]] = None
    ) -> Dict[str, Tuple[List[int], List[float]]]:
        """Accumulated (tokens, logprobs) so far for running slots — the
        per-chunk harvest the streaming endpoint emits between finishes.

        ONE ``_pull_outputs`` serves every requested slot (same batching
        rule as ``_harvest``): their ``n_gen`` first, then the blocks they
        wrote. Callers off the event loop only: the pull blocks on
        any in-flight chunk. A request the dry rule preempted reads as it
        would have: what it generated before is kept on the host, whether
        it waits for a slot or runs in one again."""
        with self._lock:
            wanted = None if rids is None else set(rids)
            sel = [
                (b, s.rid)
                for b, s in enumerate(self._slots)
                if s is not None and (wanted is None or s.rid in wanted)
            ]
            out: Dict[str, Tuple[List[int], List[float]]] = {
                rid: (list(c["tokens"]), list(c["logprobs"]))
                for rid, c in self._carried.items()
                if wanted is None or rid in wanted
            }
            if not sel:
                return out
            host = self._pull_outputs([b for b, _ in sel])
            for b, rid in sel:
                toks, lps = out.get(rid, ([], []))
                out[rid] = (
                    toks + host["out_tokens"][b].tolist(),
                    lps + host["out_logprobs"][b].tolist(),
                )
            return out

    def recurrent_state(self, rid: str) -> Optional[Tuple[int, np.ndarray]]:
        """What the state-space (or delta-rule) layers hold of the running
        request ``rid``: ``(n, ssm)`` with ``ssm [Ls, H, P, N]`` float32 (the
        selective scan: one head of ``d_inner`` channels; delta-rule layers:
        ``[Lk, H, Dk, Dv]``) the recurrent state
        after the prompt and all but the last of the ``n`` tokens generated
        so far (the last is fed at the next step), both from ONE state
        pytree. For a check from outside that the state is what the
        recurrence says (the benchmark's, a test's): head by head as the
        equations write it, so the ONE pulled row is turned here from the
        layout the slots keep (``[Ls, G, K, N, 128]``, ``ops/ssm.py``).
        ``None`` for a request that holds no slot or a model without such
        layers. The pull blocks on any in-flight chunk, as
        ``partial_outputs``'s does."""
        with self._lock:
            st = self.state
            if self.cfg.recurrent is None:
                return None
            for b, s in enumerate(self._slots):
                if s is not None and s.rid == rid:
                    n, ssm = jax.device_get(
                        (st.n_gen[b], jax.tree.leaves(st.ssm)[0][:, b]))
                    return int(n), tfm.recurrent_heads(
                        self.cfg, np.asarray(ssm))
            return None

    def partial_routing(self, rid: str) -> Optional[np.ndarray]:
        """The experts the decode steps chose for the tokens the running
        request ``rid`` has generated so far, int32 ``[n, L, top_k]``, on
        a ``record_routing`` engine (``None`` otherwise, or for a request
        that holds no slot). For a check from outside that reads a running
        request's state (:meth:`recurrent_state`) GIVEN the program's
        routing; one ``_pull_outputs``, which blocks on any in-flight
        chunk."""
        with self._lock:
            if self.state.out_routing is None:
                return None
            for b, s in enumerate(self._slots):
                if s is not None and s.rid == rid:
                    return self._pull_outputs([b])["out_routing"][b]
            return None

    def cancel(self, rid: str) -> bool:
        """Abort a request (client disconnected): drop it from the pending
        queue, or release its slot + pages mid-generation. Safe against
        in-flight pipelined chunks — the harvested slot is ``None`` so
        stale flags skip it (same guard as slot turnover), and the
        dispatched chunk's writes to the released pages are sequenced
        before any new occupant's prefill by the state data dependency.
        Returns False when the rid is unknown (already finished)."""
        with self._pending_lock:
            for i, r in enumerate(self._pending):
                if r.rid == rid:
                    del self._pending[i]
                    self._req_meta.pop(rid, None)
                    # (a preempted request waits here with its tokens)
                    self._carried.pop(rid, None)
                    return True
        with self._lock:
            for b, s in enumerate(self._slots):
                if s is not None and s.rid == rid:
                    self._free_slot(b)
                    self._carried.pop(rid, None)
                    with self._pending_lock:
                        self._req_meta.pop(rid, None)
                    # deactivate on device so later chunks stop feeding the
                    # slot (one small scatter; cancels are rare)
                    self.state = dataclasses.replace(
                        self.state,
                        active=self.state.active.at[b].set(False),
                        lens=self.state.lens.at[b].set(0),
                    )
                    return True
        return False

    def pause(self) -> List[GenOutput]:
        """Stop generating and harvest all running slots as interrupted:
        held slots among them, and the requests the dry rule preempted
        that wait for a slot again (each with everything it generated;
        they leave the queue, as a running request leaves its slot)."""
        with self._lock:
            self.paused = True
            self._prev_flags, self._prev_running = None, ()
            self._steps_ahead = 0
            outs = []
            if any(s is not None for s in self._slots):
                # ONE pull for every occupied slot, of what each wrote (a
                # per-slot fetch is one blocking device->host sync each)
                host_state = self._pull_outputs(
                    [b for b, s in enumerate(self._slots) if s is not None]
                )
                for b, s in enumerate(self._slots):
                    if s is None:
                        continue
                    # pipelined mode can hold finished-but-unharvested
                    # slots; they must NOT be reported interrupted (the
                    # client would pointlessly resubmit a complete sample).
                    # A held slot is inactive on the device and not done
                    reason = (
                        "interrupted"
                        if host_state["active"][b] or b in self._held_out
                        else _finish_reason(
                            host_state["n_gen"][b], host_state["max_gen"][b]
                        )
                    )
                    outs.append(
                        self._harvest(b, reason, host_state=host_state)
                    )
                # ONE batched deactivation (the harvested slots were still
                # active on device; a per-slot .at[b].set dispatch costs a
                # round trip each)
                self.state = dataclasses.replace(
                    self.state,
                    active=jnp.zeros_like(self.state.active),
                    lens=jnp.zeros_like(self.state.lens),
                )
            if self._carried:
                # what is left there now waits preempted in the queue
                with self._pending_lock:
                    waiting = [
                        r for r in self._pending if r.rid in self._carried]
                    self._pending[:] = [
                        r for r in self._pending
                        if r.rid not in self._carried]
                    for r in waiting:
                        self._req_meta.pop(r.rid, None)
                now = time.perf_counter()
                for r in waiting:
                    c = self._carried.pop(r.rid)
                    outs.append(GenOutput(
                        rid=r.rid, output_ids=c["tokens"],
                        output_logprobs=c["logprobs"],
                        finish_reason="interrupted", version=self.version,
                        t_submit=r.t_submit, t_admit=c["t_admit"],
                        t_first=c["t_first"] or now, t_done=now,
                        output_routing=c["routing"],
                        prefix_hit_tokens=c["prefix_hit_tokens"],
                    ))
            return outs

    def resume(self):
        with self._lock:
            self.paused = False

    # ------------------------------------------------------------------ #
    # A slot's pages, by layer kind
    # ------------------------------------------------------------------ #

    def _free_slot(self, b: int) -> _SlotInfo:
        """Release slot ``b``: every page of every kind it holds a
        reference through, and what it had reserved and not taken."""
        info = self._slots[b]
        self._slots[b] = None
        self._held_out.discard(b)
        held = self._held[:, b]
        for j, w in enumerate(self._windows):
            if w is not None:
                self._give_up(j, self._tables_host[j, b][held[j]])
        self.pool.release(self._tables_host[:, b][held].tolist())
        self.pool.reserved -= int(self._reserved[:, b].sum())
        self._reserved[:, b] = 0
        self._n_total[b] = self._n_end[b] = 0
        held[:] = False
        self._tables_host[:, b] = 0
        self._lens_host[b] = 0
        self._warp_host[b] = False
        self._fused_warp_host[b] = False
        self._fused_topk_host[b] = False
        return info

    def _give_up(self, j: int, pages) -> None:
        """Before a slot releases ``pages`` of window kind ``j``: those
        that another slot still holds were paid for by a deposit when the
        second holder came (``_deposits``), and one is spent now."""
        if len(pages):
            n = int((self.pool.n_slot_holders(pages) > 1).sum())
            self._deposits -= n
            self.pool.reserved -= n

    def _window_want(self, b: int, j: int) -> int:
        """Pages of window kind ``j`` that slot ``b`` may still have to
        take: its claim (the most it holds at once: the window, a page and
        the look-ahead) less what it holds from the window's edge on, and
        never more than the pages it has not reached yet. (A prompt
        longer than that is held whole for the length of its admission;
        it wants nothing until its chunks have given enough back.)"""
        n_total = int(self._n_total[b])
        lo = int(self._win_lo[j, b])
        want = min(self._window_claim[j], n_total - lo) - int(
            self._held[j, b, lo:].sum())
        return max(min(want, n_total - int(self._win_hi[j, b])), 0)

    def _release_behind(self, b: int, n_lo: int) -> int:
        """Slot ``b`` stands at position ``n_lo``: in every window kind,
        give up the pages that lie wholly behind ``n_lo + 1 - window`` (no
        later query sees them; to the free list, unless the registry or a
        sibling still holds them) and re-size the kind's reservation to
        what the slot may still have to take (``_window_want``). Returns
        the pages released.

        The invariant survives: a released page that nobody else holds
        goes free (or stays with the registry alone), which backs the page
        the window needs at its other end; one that a sibling still reads
        was paid for by that sibling's deposit (``_give_up``)."""
        released = 0
        for j, w in enumerate(self._windows):
            if w is None:
                continue
            lo = max(n_lo + 1 - w, 0) // self.page
            if lo <= self._win_lo[j, b]:
                continue
            idx = np.arange(self._win_lo[j, b], lo)
            idx = idx[self._held[j, b, idx]]
            self._give_up(j, self._tables_host[j, b, idx])
            self.pool.release(self._tables_host[j, b, idx].tolist())
            if self.enable_prefix_cache:
                self.prefix.note_given_up(self._tables_host[j, b, idx])
            self._held[j, b, idx] = False
            self._tables_host[j, b, idx] = 0
            self._win_lo[j, b] = lo
            released += len(idx)
            want = self._window_want(b, j)
            self.pool.reserved += want - int(self._reserved[j, b])
            self._reserved[j, b] = want
        self.stats["window_pages_released"] += released
        return released

    def _pages_ahead(self, slots: Sequence[int], span: int) -> np.ndarray:
        """``[kinds, len(slots)]``: the pages a kind that each of ``slots``
        has yet to take before it can write ``span`` positions on from
        where the host knows it to stand (never past its cap)."""
        hi = np.minimum(
            -(-(self._lens_host[slots] + span) // self.page),
            self._n_total[slots])
        return np.maximum(hi[None, :] - self._win_hi[:, slots], 0)

    def _promise(self, slots: Sequence[int], pages: np.ndarray) -> List[int]:
        """Raise the reservations of ``slots`` to cover ``pages [kinds,
        len(slots)]``, the difference out of the unpromised pages, the
        first of ``slots`` first. Returns the slots for which the pool has
        not got it: theirs stay as they were."""
        extra = np.maximum(pages - self._reserved[:, slots], 0)
        each = extra.sum(axis=0)
        refused = []
        left = self.pool.n_unpromised
        if each.sum() > left:
            for i in np.nonzero(each)[0]:
                if each[i] <= left:
                    left -= each[i]
                else:
                    refused.append(int(slots[i]))
                    extra[:, i] = 0
        self._reserved[:, slots] += extra
        self.pool.reserved += int(extra.sum())
        return refused

    def _take(self, slots: Sequence[int], pages: np.ndarray) -> int:
        """Each of ``slots`` takes ``pages [kinds, len(slots)]`` out of its
        reservation (``_promise`` has seen to it that they are there).
        Never fails: what is reserved is backed by pages that are free or
        held by the registry alone (``PagePool.n_unpromised >= 0``), and
        the registry is asked for them here, once, for a batch (a walk of
        the whole tree once the queue of given-up pages is empty). Returns
        the pages taken."""
        n_taken = int(pages.sum())
        if n_taken == 0:
            return 0
        self._make_free(n_taken)
        # every (kind, slot) that takes, once a page it takes: the entries
        # of its table from where it stands
        kinds, at = np.nonzero(pages)
        n = pages[kinds, at]
        first = np.repeat(np.cumsum(n) - n, n)
        kinds, of = np.repeat(kinds, n), np.repeat(np.asarray(slots)[at], n)
        entries = self._win_hi[kinds, of] + np.arange(n_taken) - first
        self._tables_host[kinds, of, entries] = self.pool.alloc(n_taken)
        self._held[kinds, of, entries] = True
        self._win_hi[:, slots] += pages
        self._reserved[:, slots] -= pages
        self.pool.reserved -= n_taken
        self.stats["pages_taken_growing"] += n_taken
        metrics_mod.counters.add(metrics_mod.GEN_PAGES_TAKEN_GROWING, n_taken)
        if self.kv_quantized:
            # these pages' KV lands int8 at the post-scan scatter
            metrics_mod.counters.add(
                metrics_mod.GEN_KVQ_PAGES_QUANTIZED, n_taken)
        return n_taken

    def _make_free(self, n: int) -> None:
        """See to it that ``n`` pages are on the free list, at the
        registry's cost (a walk of the whole tree once the queue of
        given-up pages is empty: ask for a batch, not a page)."""
        if self.pool.n_free < n:
            self.prefix.evict_lru(max(n, 64))

    def _roll_windows(self, b: int, n_lo: int, n_hi: int) -> int:
        """A chunk of slot ``b``'s ADMISSION is about to write positions
        ``[n_lo, n_hi)``: window kinds give up what lies behind ``n_lo``
        and take, from their reservation, the pages up to ``n_hi`` (a full
        kind holds its prompt's pages since it was admitted). Returns the
        pages released."""
        released = self._release_behind(b, n_lo)
        take = self._pages_ahead([b], n_hi - int(self._lens_host[b]))
        if self._promise([b], take):
            raise RuntimeError(
                f"slot {b}: a chunk of admission runs {n_hi - n_lo} "
                f"positions, past what a window kind reserves "
                f"({self._lookahead}), and the pool has no page to spare")
        self._take([b], take)
        return released

    # ------------------------------------------------------------------ #
    # Who runs the next chunk: growth, and the dry rule
    # ------------------------------------------------------------------ #

    def _seat(self, span: int, chunk_attrs: dict) -> List[int]:
        """The slots that run the next chunk, which can write ``span``
        positions past the host's lengths, each with the pages it writes
        TAKEN; the rest are held out of it (class docstring, the dry
        rule). Oldest first: a slot whose reservation, raised out of the
        unpromised pages if need be, covers its part of the chunk runs; a
        slot for which the pool has not got that is held, the youngest
        therefore first; when every slot would be held, the one with the
        fewest positions is preempted and the rest try again. Then the
        reservations of the running slots are topped up for the chunk
        after, as far as the pool goes. One walk of the prefix tree at
        most (the takes are summed), one device call at most (held, woken
        and preempted slots change their ``active`` together)."""
        occupied = np.nonzero(self._n_total)[0]
        released = sum(
            self._release_behind(b, int(self._lens_host[b]))
            for b in occupied) if self._windowed else 0
        occupied = occupied[np.argsort(self._seq[occupied])]
        preempted: List[int] = []
        while True:
            take = self._pages_ahead(occupied, span)
            held = self._promise(occupied, take)
            if len(held) < len(occupied) or not held:
                break
            if len(held) == 1:
                raise RuntimeError(
                    f"slot {held[0]} alone cannot take the pages of a chunk "
                    f"of {span} positions: the pool ({self.n_pages} pages) "
                    "is too small for it")
            # every slot would be held: the smallest gives way
            b = min(held, key=lambda b: self._lens_host[b])
            self._preempt(b)
            occupied = occupied[occupied != b]
            preempted.append(b)
        if held:
            runs = ~np.isin(occupied, held)
            occupied, take = occupied[runs], take[:, runs]
        running = occupied.tolist()
        n_taken = self._take(running, take)
        # the chunk after this one writes at most ``span`` further on (a
        # slot for which the pool has not got that asks again, out of what
        # is free then, when that chunk is seated)
        self._promise(
            running,
            self._pages_ahead(running, 2 * span) * self._full_kinds[:, None])
        was = self._held_out
        self._held_out = set(held)
        self._set_activity(
            off=(self._held_out - was) | set(preempted),
            on=was - self._held_out, drop=preempted)
        if held:
            self.stats["slots_held"] += len(held)
            metrics_mod.counters.add(metrics_mod.GEN_SLOTS_HELD, len(held))
        chunk_attrs.update(
            slots_running=len(running), slots_held=len(held),
            preemptions=len(preempted), pages_taken_growing=n_taken)
        if self._windowed:
            chunk_attrs["window_pages_released"] = released
        return sorted(running)

    def _preempt(self, b: int) -> None:
        """Slot ``b`` gives way (the dry rule's last step): what it has
        generated is pulled to the host and kept under its rid, its full
        pages are filed in the prefix registry (so that its re-admission
        is a prefix hit while they last; not for a model with per-slot
        recurrent state, where no snapshot stands at its last position
        and pages alone are no hit) and released, and the request goes
        back to the HEAD of the queue as prompt + generated-so-far with
        what is left of its ``max_new_tokens``. The caller deactivates the
        slot on the device."""
        info = self._slots[b]
        host = self._pull_outputs([b])
        n = int(host["n_gen"][b])
        with self._pending_lock:
            req = self._req_meta[info.rid]
        toks = host["out_tokens"][b].tolist()
        ids = list(req.input_ids) + toks
        c = self._carried.setdefault(info.rid, {
            "tokens": [], "logprobs": [], "routing": None,
            "t_admit": info.t_admit, "t_first": None,
            "prefix_hit_tokens": info.prefix_hit_tokens,
        })
        c["tokens"] += toks
        c["logprobs"] += host["out_logprobs"][b].tolist()
        c["t_first"] = c["t_first"] or info.t_first
        routing = host["out_routing"].get(b)
        if routing is not None:
            c["routing"] = routing if c["routing"] is None else (
                np.concatenate([c["routing"], routing]))
        n_full = (len(ids) - 1) // self.page      # pages wholly written
        if self.enable_prefix_cache and not self._stateful and n_full:
            self.prefix.insert(ids, self._registry_pages(b, n_full))
        self._free_slot(b)
        again = dataclasses.replace(
            req, input_ids=ids,
            max_new_tokens=int(host["max_gen"][b]) - n,
            min_new_tokens=max(req.min_new_tokens - n, 0),
        )
        with self._pending_lock:
            self._pending.insert(0, again)
            self._req_meta[info.rid] = again
        self.stats["preemptions"] += 1
        metrics_mod.counters.add(metrics_mod.GEN_PREEMPTIONS)

    def _activity_fn(self):
        """``(state, off [B], on [B], drop [B])`` -> state, donated: the
        slots of ``off`` stop being fed by the chunks (held, preempted),
        those of ``on`` are fed again (a held slot's ``active`` was true
        when it was held and nothing ran it since), those of ``drop``
        (preempted) also read as empty."""

        def activity(state: GenState, off, on, drop):
            return dataclasses.replace(
                state,
                active=(state.active & ~off) | on,
                lens=jnp.where(drop, 0, state.lens),
            )

        return program_store.stored_jit(
            activity, name="gen/activity", donate_argnums=(0,),
            **self._jit_sharding(3, with_params=False),
        )

    def _set_activity(self, off=(), on=(), drop=(), build=False) -> None:
        masks = np.zeros((3, self.B), bool)
        for m, slots in zip(masks, (off, on, drop)):
            m[list(slots)] = True
        if masks.any() or build:
            self.state = self._jit_activity(
                self.state, *(jnp.asarray(m) for m in masks))

    @property
    def _horizon(self) -> np.ndarray:
        """Admission's look into the pool's future, in positions from now."""
        return np.arange(
            0, self.ADMIT_HORIZON + 1, max(self.page // 4, 1), dtype=np.int64)

    def _spare_pages(self) -> np.ndarray:
        """Pages the pool has to spare at each point of admission's
        horizon (0, a quarter page, ... ``ADMIT_HORIZON`` positions from
        now) if nobody new is admitted: what is unpromised now, and what
        the slots make of it (``_slots_over_horizon``)."""
        return self.pool.n_unpromised + self._slots_over_horizon()

    def _slots_over_horizon(self, unless_spare: Optional[int] = None):
        """What the slots add to the pool's spare pages at each point of
        the horizon (mostly negative): less what each will have taken by
        then in its full kinds (a position a step and a look-ahead, up to
        its cap; what it has reserved is part of that and unpromised no
        more, so it is added back), plus the pages that the slots which
        have ended by then hold alone. Window kinds hold their claims
        throughout (they are promised already). A slot that ends early
        only leaves more.

        ``unless_spare`` (admission's short cut for a roomy pool): where
        the unpromised pages cover that many beside the MOST the slots can
        take over the horizon, a page a page of positions each, that bound
        is returned in the exact sum's place."""
        occupied = np.nonzero(self._n_total)[0]
        if not len(occupied) or not self._n_full:
            return np.zeros(self._horizon.shape, np.int64)
        if unless_spare is not None:
            most = len(occupied) * self._n_full * (
                (self.ADMIT_HORIZON + self._lookahead) // self.page + 1)
            if self.pool.n_unpromised - most >= unless_spare:
                return np.full(self._horizon.shape, -most, np.int64)
        full = int(np.argmax(self._full_kinds))
        need, alive = self._growth(
            self._lens_host[occupied] + self._steps_ahead,
            self._n_end[occupied], self._n_total[occupied],
            self._win_hi[full, occupied])
        # pages each slot holds alone (the registry aside): free, or the
        # registry's alone, once it has ended
        alone = self.pool.n_slot_holders(self._tables_host[self._held]) == 1
        slots = np.repeat(
            np.tile(np.arange(self.B), len(self._windows)),
            self._held.sum(axis=2).ravel())
        alone = np.bincount(slots[alone], minlength=self.B)[occupied]
        return (
            int(self._reserved[full, occupied].sum()) * self._n_full
            - need.sum(0) + (~alive * alone[:, None]).sum(0))

    def _growth(self, n, n_end, n_total, hi):
        """``[slots, horizon]``: the pages, over all full kinds, that
        slots at positions ``n`` holding ``hi`` pages a kind (cap
        ``n_total``, whole at ``n_end`` positions) have yet to take at
        each point of the horizon, 0 from where they have ended (one
        point of grace); and where they still run."""
        grid = self._horizon
        at = np.asarray(n)[:, None] + grid[None, :]
        alive = at < np.asarray(n_end)[:, None] + grid[min(1, len(grid) - 1)]
        cover = np.minimum(
            -(-(at + self._lookahead) // self.page),
            np.asarray(n_total)[:, None]) - np.asarray(hi)[:, None]
        return np.where(alive, np.maximum(cover, 0), 0) * self._n_full, alive

    def _registry_pages(self, slot: int, n: int) -> List:
        """The slot's first ``n`` table entries as the prefix registry
        files them: page ids, or with layer kinds one list a page, the
        page of every kind (-1 where a window kind's has been given up)."""
        if len(self._windows) == 1:
            return self._table_host[slot, :n].tolist()
        return np.where(
            self._held[:, slot, :n], self._tables_host[:, slot, :n], -1
        ).T.tolist()

    def _table_arg(self, rows, width: int):
        """The page tables of ``rows`` (slots, or all with ``slice(None)``)
        cut to ``width`` entries, as the jitted programs take them: ``[n,
        width]``, or with layer kinds ``[kinds, n, width]``. Always a COPY:
        on a CPU backend ``jnp.asarray`` may alias the host buffer, a
        dispatched program reads it later, and the tables of running slots
        change between dispatches (``_roll_windows``)."""
        if len(self._windows) > 1:
            return self._tables_host[:, rows, :width].copy()
        return self._table_host[rows, :width].copy()

    # ------------------------------------------------------------------ #
    # Admission: chunked prefill through the page pool
    # ------------------------------------------------------------------ #

    def _table_width(self, max_pos: int) -> int:
        """Static page-table width for a program that touches positions up
        to ``max_pos``: enough pages, rounded up to a power of two, floored
        at 32. The XLA gather that backs paged attention then reads
        O(resident) pages instead of the full table — at a 256-page (32k)
        table this turns chunked prefill from quadratic to ~linear HBM
        traffic — while jit specializations stay bounded by log2 width
        buckets (never by prompt length)."""
        need = -(-max_pos // self.page)
        w = 32
        while w < need:
            w *= 2
        return min(w, self.M)

    def _extend_fn(self, n_rows: int, width: int, skip_pool: bool = False):
        """The COMPUTING half of a prefill chunk: ``(params, state, tokens,
        table_rows, start, n_new)`` to the chunk's fresh K/V of every layer,
        ``(ks, vs)``. The pool is read and NOT written here: that is
        ``_kv_write_fn``'s program, which every bucket, table width and
        ``skip_pool`` share (the write kernel is traced and lowered once a start, not once a
        program: a dozen of them were 8 s of a 41 s set-up; PERF.md §6, PR
        31), so the rows come out padded to ITS batch."""
        key = (n_rows, width, skip_pool)
        if key in self._jit_extend:
            return self._jit_extend[key]
        pad = self._kv_write_batch(n_rows) - n_rows
        # the routed experts see every token of the wave's chunk
        grouped = self._moe_grouped(n_rows * self.admit_chunk)

        def pad_rows(x):
            return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))

        if self._stateful:

            def extend(params, state, tokens, table, start, n_new, slots):
                # the rows continue their slots' state (read here, put
                # back by the write program beside the fresh K/V)
                ks, vs, rows = tfm.extend_paged_kv(
                    params, self.cfg, state.cache, tokens, table, start,
                    n_new, skip_pool=skip_pool, ssm=state.ssm, slots=slots,
                    moe_grouped=grouped)
                return jax.tree.map(pad_rows, (ks, vs)), rows

        else:

            def extend(params, state, *chunk):
                return jax.tree.map(pad_rows, tfm.extend_paged_kv(
                    params, self.cfg, state.cache, *chunk,
                    skip_pool=skip_pool, moe_grouped=grouped))

        sharding_kw = self._jit_sharding(5 if self._stateful else 4)
        sharding_kw.pop("out_shardings", None)
        jitted = program_store.stored_jit(
            extend, name="gen/extend", key=key,
            built_from=self._built_from(), **sharding_kw)
        self._jit_extend[key] = jitted
        return jitted

    def _ssm_update(self):
        """What a decode step's state-space layers update the per-slot
        state with: the ``ssm_decode`` kernel where it applies
        (``ops/pallas/ssm_decode.py:ssm_decode_applies``: one TPU device,
        one group of heads), which reads and writes the state once; else
        ``None``, which is ``ops/ssm.py:step_update`` (XLA: two fusions a
        layer that both read the state)."""
        from areal_tpu.ops.pallas import kda_decode, ssm_decode

        if self.cfg.ssm is not None and ssm_decode.ssm_decode_applies(
                self.cfg, self.mesh):
            return ssm_decode.ssm_decode
        # the delta-rule layers' likewise (``ops/kda.py:step_update``)
        if kda_decode.kda_decode_applies(self.cfg, self.mesh):
            return kda_decode.kda_decode
        return None

    def _moe_grouped(self, rows: int) -> bool:
        """Whether a program that hands the routed experts ``rows``
        rows a layer runs them as the ``moe_grouped`` kernel
        (``ops/moe.py:moe_grouped_applies``, over this engine's model, tree
        and mesh): what the program is built with AND what its dispatches
        are counted by (``moe_grouped_rows`` / ``moe_dense_rows``)."""
        return moe_ops.moe_grouped_applies(
            self.cfg, self.params, self.mesh, rows)

    def _count_moe_rows(self, rows: int, runs: int) -> Dict[str, int]:
        """``rows x expert layers x runs`` (``runs``: decode steps x passes,
        or 1 for a prefill chunk) onto ``moe_grouped_rows`` or
        ``moe_dense_rows``, by :meth:`_moe_grouped` of ``rows``. Returns
        both counts of this dispatch (one is 0) for its span."""
        n = rows * self.cfg.n_moe_layers * runs
        grouped = self._moe_grouped(rows)
        counts = {
            "moe_grouped_rows": n if grouped else 0,
            "moe_dense_rows": 0 if grouped else n,
        }
        for name, v in counts.items():
            self.stats[name] += v
        return counts

    def _kv_write_batch(self, n_rows: int) -> int:
        """Rows of the write program that takes a wave of ``n_rows``: the
        top bucket where the ``kv_page_write`` kernel writes (ONE program
        holds it; a padding row, ``n_new`` 0, costs the kernel a skipped
        loop), the wave's own where the XLA scatter does (it pays for
        every row it is given, dropped or not)."""
        if self._stateful:
            # the rows' recurrent state is put back by the same program,
            # and a padding row of THAT is 76 MB at the published sizes
            return n_rows
        return self.admit_buckets[-1] if self._kv_write_rows() else n_rows

    def _kv_write_fn(self, n_rows: int):
        """The WRITING half of a prefill chunk: ``(state, fresh, table_rows
        [n_rows, M], start, n_new)`` to the state with the fresh K/V in
        its pages (``tfm._write_chunk_kv``: the path of the decode
        step's write), the state donated."""
        if n_rows in self._jit_kv_write:
            return self._jit_kv_write[n_rows]
        write_kw = dict(use_pallas=self._decode_use_pallas, mesh=self.mesh)

        def kv_write(state: GenState, fresh, table_rows, start, n_new,
                     state_rows=None, slots=None):
            ks, vs = fresh
            state = dataclasses.replace(
                state, cache=tfm._write_chunk_kv(
                    state.cache, ks, vs, table_rows, start, n_new, **write_kw))
            if state_rows is not None:
                state = dataclasses.replace(
                    state,
                    ssm=tfm.put_ssm_rows(state.ssm, slots, state_rows))
            return state

        sharding_kw = {}
        if self.mesh is not None:
            sharding_kw = dict(
                in_shardings=(self._state_sh, None) + (self._repl,) * 3
                + ((None, self._repl) if self._stateful else ()),
                out_shardings=self._state_sh,
            )
        jitted = program_store.stored_jit(
            kv_write, name="gen/kv_write", key=n_rows,
            built_from=self._built_from(), donate_argnums=(0,), **sharding_kw)
        self._jit_kv_write[n_rows] = jitted
        return jitted

    def _built_from(self) -> tuple:
        """What the model's programs (extend, kv_write, chunk) read of this
        engine that their arguments and static keys do not show: the key
        of a stored program (``base/program_store.py``) holds it. The
        kernel choices (``_ssm_update``, ``_moe_grouped``,
        ``_kv_write_rows``) are functions of these, of the arguments and
        of the source."""
        return (
            self.cfg, self.B, self.page, self.admit_chunk,
            tuple(self.admit_buckets), self._decode_use_pallas, self._moe,
            self._stateful, self.fused, self.kv_dtype, self.mesh,
        )

    def _jit_sharding(self, n_host_args: int, with_params: bool = True):
        """in/out sharding kwargs for the engine's jitted programs (empty
        without a mesh): params on their TP shards, state on its (pool
        sharded, rest replicated) shardings, host-side arrays replicated."""
        if self.mesh is None:
            return {}
        ins = (self._param_sh,) if with_params else ()
        ins += (self._state_sh,) + (self._repl,) * n_host_args
        return {"in_shardings": ins, "out_shardings": self._state_sh}

    def _commit_fn(self, n_rows: int):
        if n_rows in self._jit_commit:
            return self._jit_commit[n_rows]

        def commit(state: GenState, slots, last_toks, lens, temp, top_p,
                   top_k, min_gen, max_gen, stop_ids):
            return dataclasses.replace(
                state,
                lens=state.lens.at[slots].set(lens, mode="drop"),
                last_tokens=state.last_tokens.at[slots].set(last_toks, mode="drop"),
                active=state.active.at[slots].set(True, mode="drop"),
                n_gen=state.n_gen.at[slots].set(0, mode="drop"),
                min_gen=state.min_gen.at[slots].set(min_gen, mode="drop"),
                max_gen=state.max_gen.at[slots].set(max_gen, mode="drop"),
                stop_ids=state.stop_ids.at[slots].set(stop_ids, mode="drop"),
                out_tokens=state.out_tokens.at[slots].set(0, mode="drop"),
                out_logprobs=state.out_logprobs.at[slots].set(0.0, mode="drop"),
                sp=SamplingParams(
                    temperature=state.sp.temperature.at[slots].set(temp, mode="drop"),
                    top_p=state.sp.top_p.at[slots].set(top_p, mode="drop"),
                    top_k=state.sp.top_k.at[slots].set(top_k, mode="drop"),
                ),
            )

        jitted = program_store.stored_jit(
            commit, name="gen/commit", key=n_rows, donate_argnums=(0,),
            **self._jit_sharding(9, with_params=False),
        )
        self._jit_commit[n_rows] = jitted
        return jitted

    def _row_bucket(self, n: int) -> int:
        return next(
            b for b in self.admit_buckets
            if b >= min(n, self.admit_buckets[-1])
        )

    def _run_extends(self, rows: List[dict]):
        """Stream each row's tokens through fixed [n_rows, admit_chunk]
        extend programs (rows: dicts with tokens/start/table_row). Each
        wave's program sees only the table prefix its positions can touch
        (``_table_width``)."""
        if not rows:
            return
        C = self.admit_chunk
        # a wave runs as many chunks as its LONGEST row has: rows of like
        # length share a wave (the rows of one call never read each other's
        # pages, so their order is free; an opening population of contexts
        # from 128 to 2,560 tokens ran 190 chunk programs in arrival order
        # and runs 120 so: PERF.md section 6, PR 43)
        rows = sorted(rows, key=lambda r: len(r["tokens"]))
        i = 0
        while i < len(rows):
            n = self._row_bucket(len(rows) - i)
            chunk_rows = rows[i : i + n]
            i += len(chunk_rows)
            max_t = max(len(r["tokens"]) for r in chunk_rows)
            n_chunks = max(1, -(-max_t // C))
            # the write program's rows: the wave's, or padded to one batch
            nw = self._kv_write_batch(n)
            slots = [r["slot"] for r in chunk_rows]

            def wave_tables():
                # the wave's rows of every kind's table, padded to the
                # write program's batch: a fresh array a call (a dispatched
                # program may still read the last one)
                t = np.zeros(
                    self._tables_host.shape[:1] + (nw, self.M), np.int32)
                t[:, : len(slots)] = self._tables_host[:, slots]
                return t if len(self._windows) > 1 else t[0]

            starts0 = np.zeros((nw,), np.int32)
            all_tokens = np.zeros((n, n_chunks * C), np.int32)
            counts = np.zeros((nw,), np.int32)
            for j, r in enumerate(chunk_rows):
                starts0[j] = r["start"]
                all_tokens[j, : len(r["tokens"])] = r["tokens"]
                counts[j] = len(r["tokens"])
            tables = wave_tables()
            for c in range(n_chunks):
                n_new = np.clip(counts - c * C, 0, C)
                if not n_new.any():
                    break
                if self._windowed:
                    # a prompt longer than a window goes through the same
                    # path: its window kinds' pages go back as the chunks
                    # pass, and the tables of the wave follow
                    for j, b in enumerate(slots):
                        if n_new[j]:
                            at = int(starts0[j]) + c * C
                            self._roll_windows(b, at, at + int(n_new[j]))
                    tables = wave_tables()
                max_pos = int(np.max(starts0 + np.minimum(counts, (c + 1) * C)))
                W = self._table_width(max_pos)
                # cold-prompt first waves start every row at position 0:
                # the pool prefix is empty, so the extend program can skip
                # the page gather + pool scan entirely (STATIC flag — jit
                # key includes it; at short-prompt admission the dead pool
                # scan cost as much as the intra-chunk attention)
                skip_pool = c == 0 and not starts0.any()
                rows_per_tile = self._kv_write_rows()
                if rows_per_tile:
                    from areal_tpu.ops.pallas import kv_page_write

                    self.stats["kv_write_tiles"] += self.cfg.cache_layers * sum(
                        kv_page_write.tiles_of_run(
                            int(s0) + c * C, int(k), rows_per_tile
                        )
                        for s0, k in zip(starts0, n_new)
                    )
                start = starts0 + c * C
                if self._moe:
                    self._count_moe_rows(n * C, 1)
                state_args = ()
                if self._stateful:
                    # each row's slot; a padding row's is past the last
                    # (read clipped, its state dropped on the way back)
                    slot_arr = np.full((n,), self.B, np.int32)
                    slot_arr[: len(slots)] = slots
                    state_args = (jnp.asarray(slot_arr),)
                fresh = self._extend_fn(n, W, skip_pool)(
                    self.params, self.state,
                    jnp.asarray(all_tokens[:, c * C : (c + 1) * C]),
                    jnp.asarray(tables[..., :n, :W]),
                    jnp.asarray(start[:n]),
                    jnp.asarray(n_new[:n]),
                    *state_args,
                )
                if self._stateful:
                    fresh, state_rows = fresh
                    state_args = (state_rows,) + state_args
                self.state = self._kv_write_fn(nw)(
                    self.state, fresh, jnp.asarray(tables),
                    jnp.asarray(start), jnp.asarray(n_new), *state_args,
                )
                self._admit_programs += 2

    # ------------------------------------------------------------------ #
    # A slot's recurrent state (models with state-space layers)
    # ------------------------------------------------------------------ #

    def _state_copy_fn(self, n_rows: int, into_slots: bool):
        """``(state, dst [n], src [n])`` -> state, donated. ``into_slots``:
        slot ``dst[i]``'s state becomes snapshot ``src[i]``, or ZERO where
        ``src[i] < 0`` (every admitted request goes through here before
        its first chunk: a slot never starts from what its last tenant
        left). Otherwise snapshot ``dst[i]`` becomes slot ``src[i]``'s
        state. A ``dst`` past the end is a padding row."""
        key = (n_rows, into_slots)
        if key in self._jit_state:
            return self._jit_state[key]

        @jax.named_scope("state_snapshot_copy")
        def copy(state: GenState, dst, src):
            if into_slots:
                if state.snaps is None:
                    rows = jax.tree.map(
                        lambda a: jnp.zeros(
                            (a.shape[0], n_rows) + a.shape[2:], a.dtype),
                        state.ssm)
                else:
                    keep = src >= 0
                    rows = jax.tree.map(
                        lambda a: jnp.where(
                            keep.reshape((1, -1) + (1,) * (a.ndim - 2)),
                            a[:, jnp.maximum(src, 0)], 0),
                        state.snaps)
                return dataclasses.replace(
                    state, ssm=tfm.put_ssm_rows(
                        state.ssm, dst, jax.tree.leaves(rows)))
            return dataclasses.replace(
                state, snaps=tfm.put_ssm_rows(
                    state.snaps, dst,
                    [a[:, src] for a in jax.tree.leaves(state.ssm)]))

        jitted = program_store.stored_jit(
            copy, name="gen/state_copy", key=key, donate_argnums=(0,),
            **self._jit_sharding(2, with_params=False),
        )
        self._jit_state[key] = jitted
        return jitted

    def _copy_state(self, pairs: List[Tuple[int, int]], into_slots: bool):
        """Run :meth:`_state_copy_fn` over ``(dst, src)`` pairs in row
        buckets; counts the snapshot bytes moved."""
        per = tfm.row_state_bytes(self.cfg)
        i = 0
        while i < len(pairs):
            n = self._row_bucket(len(pairs) - i)
            group = pairs[i : i + n]
            i += len(group)
            dst = np.full((n,), self.B + self.n_snapshots, np.int32)
            src = np.full((n,), -1 if into_slots else 0, np.int32)
            dst[: len(group)] = [d for d, _ in group]
            src[: len(group)] = [s_ for _, s_ in group]
            self.state = self._state_copy_fn(n, into_slots)(
                self.state, jnp.asarray(dst), jnp.asarray(src))
            self._admit_programs += 1
            self.stats["state_snapshot_bytes"] += per * sum(
                1 for _, s_ in group if s_ >= 0 or not into_slots)

    def _take_snapshots(self, rows: List[dict]):
        """File the state of each row's slot, which stands at the row's
        page-aligned boundary, in the snapshot entry reserved for it."""
        pairs = [(r["snap_to"], r["slot"]) for r in rows
                 if r.get("snap_to") is not None]
        if pairs:
            self._copy_state(pairs, into_slots=False)
            self.stats["state_snapshots_taken"] += len(pairs)

    def _run_state_waves(self, misses: List[dict], hits: List[dict]):
        """Admission's waves for a model with per-slot recurrent state.
        A row's prompt is ``[start, boundary)`` then ``[boundary, end)``:
        ``boundary`` is where its page sharing ends (its longest page-
        aligned prefix) if a snapshot is to be taken there (``snap_to``),
        else ``start``. Order: cold prompts start from zero and run to
        their boundary, their snapshots are taken; ONLY THEN are the
        prefix hits seeded (a member of a group that arrived in one wave
        with the group's first reads what that first has just written),
        run to their own boundary (a partial hit) and snapshotted; the
        tails of all rows run last."""

        def part(rows, lo, hi):
            return [
                dict(r, tokens=r["ids"][r[lo]:r[hi]], start=r[lo])
                for r in rows if r[hi] > r[lo]
            ]

        self._copy_state([(r["slot"], -1) for r in misses], into_slots=True)
        self._run_extends(part(misses, "start", "boundary"))
        self._take_snapshots(misses)
        self._copy_state(
            [(r["slot"], r["snap_from"]) for r in hits], into_slots=True)
        self.stats["state_snapshot_hits"] += len(hits)
        self._run_extends(part(hits, "start", "boundary"))
        self._take_snapshots(hits)
        self._run_extends(part(misses + hits, "boundary", "end"))

    def _admit(self):
        """``_admit_pending`` under its span: the host's share of a chunk
        boundary that the device most often waits out (page allocation,
        prefix lookup, building and dispatching the prefill programs)."""
        with tracing.span("gen_engine/admit") as attrs:
            st = self.stats
            before = (
                st["admitted"], st["prefill_tokens"], st["prefix_hit_tokens"],
                st["kv_write_tiles"], st["window_pages_released"],
                st["moe_grouped_rows"], st["moe_dense_rows"],
                st["state_snapshots_taken"], st["state_snapshot_hits"],
                st["state_snapshot_bytes"], st["state_snapshot_evictions"],
                st["preempted_tokens_recomputed"],
            )
            self._admit_pending()
            attrs.update(
                admitted=st["admitted"] - before[0],
                prefill_tokens=st["prefill_tokens"] - before[1],
                prefix_hit_tokens=st["prefix_hit_tokens"] - before[2],
                pending_left=self.n_pending(),
                # of the prefilled tokens, those a preempted request had
                # computed once already
                preempted_tokens_recomputed=(
                    st["preempted_tokens_recomputed"] - before[11]),
            )
            if self._kv_write_rows():
                # tiles the wave's prefill wrote through the kernel
                attrs["kv_write_tiles"] = st["kv_write_tiles"] - before[3]
            if self._windowed:
                # window kinds' pages that prompts longer than the window
                # gave back as their chunks passed
                attrs["window_pages_released"] = (
                    st["window_pages_released"] - before[4])
            if self._moe:
                # tokens x expert layers of the wave's prefill chunks, by
                # where their routed experts ran
                attrs["moe_grouped_rows"] = st["moe_grouped_rows"] - before[5]
                attrs["moe_dense_rows"] = st["moe_dense_rows"] - before[6]
            if self._stateful:
                # snapshots of the recurrent state filed by this wave,
                # admissions it seeded from one, bytes copied in and out,
                # snapshots dropped (for room, or with their node)
                for i, name in enumerate((
                    "state_snapshots_taken", "state_snapshot_hits",
                    "state_snapshot_bytes", "state_snapshot_evictions",
                ), start=7):
                    attrs[name] = st[name] - before[i]

    def _admit_pending(self):
        if not self.accepting:
            return
        free = [b for b, s in enumerate(self._slots) if s is None]
        if not free:
            return
        self.prefix.pinned.clear()
        admitted: List[Tuple[GenRequest, int, dict]] = []
        misses: List[dict] = []
        hits: List[dict] = []
        deferred_inserts: List[Tuple[List[int], int, int, Optional[int]]] = []
        still_pending: List[GenRequest] = []
        over_horizon = None     # ``_slots_over_horizon``, kept current
        with self._pending_lock:
            take = self._pending[: len(free) + 8]  # small lookahead
            del self._pending[: len(take)]
        while take and free:
            r = take.pop(0)
            ids = list(r.input_ids)
            plen_eff = len(ids) - 1               # prefilled positions
            max_gen = min(r.max_new_tokens, self.G)
            n_total = -(-(plen_eff + max_gen) // self.page)
            n_shared_full = plen_eff // self.page
            shared: List = []
            if self.enable_prefix_cache and n_shared_full > 0:
                shared = self.prefix.lookup(ids, n_shared_full) or []
            # (recurrent state: the hit ends at a node with a snapshot)
            snap_from = self.prefix.hit_snapshot if shared else None
            # a kind's pages taken now: the prompt's, and of a window kind
            # no more than its claim (a prompt longer than the window never
            # holds more than the window's worth). The rest is taken as the
            # prompt's chunks and then the output move on (``_roll_windows``,
            # ``_seat``); ``n_total`` is the slot's cap, not its holding
            n_prompt = -(-plen_eff // self.page)
            take_to = [
                max(n_prompt if claim is None else min(n_prompt, claim),
                    len(shared))
                for claim in self._window_claim
            ]
            n_owned = sum(take_to) - len(take_to) * len(shared)
            # the pool's spare pages over admission's horizon, as the
            # slots seated so far leave it (the hit's pages are borrowed)
            if over_horizon is None:
                over_horizon = self._slots_over_horizon(
                    unless_spare=n_owned + sum(
                        c or (self.ADMIT_HORIZON + self._lookahead)
                        // self.page + 1 for c in self._window_claim))
            spare = self.pool.n_unpromised + over_horizon
            self._admit_seq += 1
            info = _SlotInfo(rid=r.rid, t_submit=r.t_submit)
            self._make_free(n_owned)
            slot = free[0]
            self._slots[slot] = info
            self._lens_host[slot] = plen_eff
            self._n_total[slot] = n_total
            self._n_end[slot] = plen_eff + max_gen
            self._seq[slot] = self._admit_seq
            self._win_lo[:, slot] = 0
            self._win_hi[:, slot] = take_to
            tables = self._tables_host[:, slot]
            tables[:] = 0
            self._held[:, slot] = False
            n_deposit = 0
            for j, to in enumerate(take_to):
                got = np.asarray(
                    [_page_ids(page, j) for page in shared], np.int64)
                tables[j, : len(shared)] = np.maximum(got, 0)
                self._held[j, slot, : len(shared)] = got >= 0
                self._held[j, slot, len(shared):to] = True
                if self._windows[j] is not None and len(got):
                    # a window kind's page that another slot holds too
                    n_deposit += int((
                        self.pool.n_slot_holders(got[got >= 0]) > 1).sum())
            # what is promised with the slot: a full kind's first chunk, a
            # window kind's claim
            ahead = self._pages_ahead([slot], self._lookahead)[:, 0]
            want = [
                int(ahead[j]) if w is None else self._window_want(slot, j)
                for j, w in enumerate(self._windows)
            ]
            # ... and what the newcomer takes of the pool over the horizon:
            # its pages now, a full kind's as it grows, none once it ended
            grows, alive = self._growth(
                [plen_eff], [plen_eff + max_gen], [n_total], [n_prompt])
            want_full = int(ahead[self._full_kinds].sum())
            takes = np.where(
                alive[0],
                grows[0] + n_owned + n_deposit + sum(want) - want_full, 0)
            if (
                self.pool.n_free < n_owned
                or self.pool.n_unpromised - n_owned < sum(want) + n_deposit
                or (spare - takes).min() < 0
            ):
                # pool pressure: resident slots / registry hold everything,
                # or it is promised to running slots, or they will have
                # grown into it; retry on a later step
                self._slots[slot] = None
                self._lens_host[slot] = 0
                self._n_total[slot] = self._n_end[slot] = 0
                self._held[:, slot] = False
                tables[:] = 0
                if shared:
                    self.pool.release(
                        [p for page in shared for p in _page_ids(page)])
                still_pending.append(r)
                break
            owned = self.pool.alloc(n_owned)
            free.pop(0)
            # (the newcomer among the slots: ``_slots_over_horizon``'s term)
            over_horizon = over_horizon + np.where(
                alive[0], want_full - grows[0], n_owned)
            self._slot_epoch[slot] += 1
            self._reserved[:, slot] = want
            info.t_admit = time.perf_counter()
            self.pool.reserved += sum(want) + n_deposit
            self._deposits += n_deposit
            at = 0
            for j, to in enumerate(take_to):
                n_new_pages = to - len(shared)
                tables[j, len(shared):to] = owned[at : at + n_new_pages]
                at += n_new_pages
            covered = len(shared) * self.page
            info.prefix_hit_tokens = covered
            row = {
                "tokens": ids[covered:plen_eff],
                "start": covered,
                "slot": slot,
            }
            # recurrent state: a chain that grows gets a snapshot of the
            # state at its new end, taken when the row's chunks stand
            # exactly there (``_run_state_waves``)
            snap_to = None
            if (
                self._stateful and self.enable_prefix_cache
                and n_shared_full > len(shared)
            ):
                snap_to = self.prefix.alloc_snapshot()
            if self._stateful:
                row.update(
                    ids=ids, end=plen_eff, snap_from=snap_from,
                    snap_to=snap_to,
                    boundary=(
                        covered if snap_to is None
                        else n_shared_full * self.page),
                )
            if shared:
                self.stats["prefix_hits"] += 1
                self.stats["prefix_hit_tokens"] += covered
                hits.append(row)
                if self.enable_prefix_cache and n_shared_full > len(shared):
                    # partial hit (e.g. shared system preamble): register the
                    # divergent tail for future siblings — but only AFTER the
                    # extend waves run. This slot's pages are written in wave
                    # 2; inserting now would let a same-cycle borrower (also
                    # wave 2) read them before they are written.
                    deferred_inserts.append(
                        (ids, slot, n_shared_full, snap_to))
            else:
                misses.append(row)
                if self.enable_prefix_cache and n_shared_full > 0:
                    # cold prompt: register immediately — its pages are
                    # written in wave 1, so same-cycle group members can
                    # borrow them in wave 2
                    self.prefix.insert(
                        ids, self._registry_pages(slot, n_shared_full),
                        snapshot=snap_to)
                    if any(c is not None and c < n_shared_full
                           for c in self._window_claim):
                        # a prompt longer than a window kind's claim: that
                        # kind's later pages are taken as the chunks pass,
                        # so the chain just filed has holes there. Filed
                        # again behind the waves, the pages come home
                        # (``PrefixRegistry.insert``), and the siblings of
                        # LATER cycles find every page their window reads
                        deferred_inserts.append(
                            (ids, slot, n_shared_full, None))
            self.stats["prefill_tokens"] += len(row["tokens"])
            self.stats["admitted"] += 1
            if r.rid in self._carried and row["tokens"]:
                # a preempted request back in a slot: these positions were
                # computed once before
                self.stats["preempted_tokens_recomputed"] += len(row["tokens"])
                metrics_mod.counters.add(
                    metrics_mod.GEN_PREEMPTED_TOKENS_RECOMPUTED,
                    len(row["tokens"]))
            if self.kv_quantized and owned:
                # these pages' KV lands int8 at the post-scan scatter
                metrics_mod.counters.add(
                    metrics_mod.GEN_KVQ_PAGES_QUANTIZED, len(owned)
                )
            admitted.append((r, slot, row))
        still_pending.extend(take)  # slots/pool ran out: back in line
        if still_pending:
            with self._pending_lock:
                self._pending[:0] = still_pending
        if not admitted:
            return
        # everything of the wave that dispatches device programs, under one
        # span: the prefill waves, then the commit of the slots' state
        with tracing.span("gen_engine/admit/prefill") as attrs:
            before = self._admit_programs
            # wave 1: unique prompts compute their KV; wave 2: prefix
            # borrowers extend only their tails (their shared pages were
            # written by wave 1 or by earlier admissions)
            if self._stateful:
                self._run_state_waves(misses, hits)
            else:
                self._run_extends(misses)
                self._run_extends(hits)
            self._commit_admitted(admitted)
            attrs["programs"] = self._admit_programs - before
        for ins_ids, slot, n_full, snap in deferred_inserts:
            self.prefix.insert(
                ins_ids, self._registry_pages(slot, n_full), snapshot=snap)
        self.stats["state_snapshot_evictions"] = (
            self.prefix.snapshot_evictions)

    def _commit_admitted(
        self, admitted: List[Tuple[GenRequest, int, dict]]
    ) -> None:
        """Commit the admitted requests' slot state in row buckets."""
        i = 0
        while i < len(admitted):
            n = self._row_bucket(len(admitted) - i)
            group = admitted[i : i + n]
            i += len(group)
            K = self.max_stop_ids
            slots = np.full((n,), self.B, np.int32)   # pad rows dropped
            last_toks = np.zeros((n,), np.int32)
            lens = np.zeros((n,), np.int32)
            temp = np.ones((n,), np.float32)
            top_p = np.ones((n,), np.float32)
            top_k = np.full((n,), 1 << 30, np.int32)
            min_gen = np.zeros((n,), np.int32)
            max_gen = np.zeros((n,), np.int32)
            stop_ids = np.full((n, K), -1, np.int32)
            for j, (r, slot, _) in enumerate(group):
                ids = r.input_ids
                slots[j] = slot
                last_toks[j] = ids[-1]
                lens[j] = len(ids) - 1
                self._warp_host[slot] = (
                    r.top_p < 1.0 or r.top_k < self.cfg.vocab_size
                ) and not r.greedy and r.temperature > 0.0
                sampled = not r.greedy and r.temperature > 0.0
                topk_on = r.top_k < self.cfg.vocab_size
                self._fused_warp_host[slot] = sampled and (
                    r.top_p < 1.0
                    or (topk_on and r.top_k > fused_ops.TOPK_MAX)
                )
                self._fused_topk_host[slot] = (
                    sampled and r.top_p >= 1.0
                    and topk_on and r.top_k <= fused_ops.TOPK_MAX
                )
                temp[j] = 0.0 if r.greedy else r.temperature
                top_p[j] = r.top_p
                top_k[j] = min(r.top_k, 1 << 30)
                min_gen[j] = r.min_new_tokens
                max_gen[j] = min(r.max_new_tokens, self.G)
                merged = list(
                    dict.fromkeys(self.global_stop_ids + list(r.stop_token_ids))
                )[:K]
                stop_ids[j, : len(merged)] = merged
            commit = self._commit_fn(n)
            self.state = commit(
                self.state, jnp.asarray(slots), jnp.asarray(last_toks),
                jnp.asarray(lens), jnp.asarray(temp), jnp.asarray(top_p),
                jnp.asarray(top_k), jnp.asarray(min_gen), jnp.asarray(max_gen),
                jnp.asarray(stop_ids),
            )
            self._admit_programs += 1

    # ------------------------------------------------------------------ #
    # Decode
    # ------------------------------------------------------------------ #

    def _chunk_fn(self, n_steps: int, width: int, warp_bucket: int,
                  fused: bool = False, with_topk: bool = False):
        """``warp_bucket`` (STATIC jit key): power-of-two capacity of the
        per-slot warping-index operand, 0 = no resident slot warps. The
        top-p/top-k sort — the most expensive op of a decode step at a
        152k vocab — runs over the warping slots ONLY
        (``warp_logits_rows``); one top-p request no longer drags the
        whole batch through a ``[B, V]`` sort, and greedy-only traffic
        skips it entirely. Specializations stay bounded by log2 buckets.

        ``fused`` (STATIC, the fused epilogue: ``self.fused``, by
        ``ops/fused_sample.py:fused_sample_applies``): the step returns
        final-norm hidden states and ``ops/fused_sample.py`` streams the
        LM head over vocab blocks — the ``[B, V]`` logits never
        materialize. Under fused routing the warp bucket holds only the
        slots the online pass cannot serve (top-p, top-k > TOPK_MAX);
        those rows materialize their OWN logits rows and keep the sorted
        reference sampler. ``with_topk`` (STATIC) carries the online
        top-k buffer for resident plain-top-k slots."""
        key = (n_steps, width, warp_bucket, fused, with_topk)
        if key in self._jit_chunk:
            return self._jit_chunk[key]
        cfg = self.cfg

        def one_step(state: GenState, params, table, warp_rows):
            head_out, cache, new_lens, *routing = tfm.decode_step_paged(
                params, cfg, state.cache, state.last_tokens, table,
                state.lens, state.active,
                use_pallas=self._decode_use_pallas,
                mesh=self.mesh,
                return_hidden=fused,
                with_routing=self._moe,
                moe_grouped=self._moe_grouped(self.B),
                ssm=state.ssm,
                ssm_update=self._ssm_update(),
            )
            ssm = routing.pop() if self._stateful else None
            if self.mesh is not None:
                # one explicit all-gather of the [B, V] logits (fused: the
                # much smaller [B, E] hidden states): sampling (sort-based
                # top-k/top-p) runs replicated instead of through
                # compiler-chosen per-op resharding
                head_out = jax.lax.with_sharding_constraint(
                    head_out, self._repl
                )
            rng, sub = jax.random.split(state.rng)
            if fused:
                sp = state.sp
                greedy_rows = sp.temperature <= 0.0
                topk_arg = None
                if with_topk:
                    # inactive rows (and rows past the buffer) carry a
                    # sentinel > TOPK_MAX so fused_sample ignores them
                    topk_arg = jnp.where(
                        (sp.top_k <= fused_ops.TOPK_MAX) & ~greedy_rows,
                        sp.top_k, jnp.int32(1 << 30),
                    )
                head, vocab_rows = tfm.head_operand(cfg, params)
                out = fused_ops.fused_sample(
                    sub, head_out, head, sp.temperature, greedy_rows,
                    soft_cap=cfg.final_logits_soft_cap,
                    topk=topk_arg, mesh=self.mesh,
                    logits_scale=cfg.logits_scaling, vocab_rows=vocab_rows,
                )
                tokens, lp = out["tokens"], out["logprobs"]
                if warp_bucket > 0:
                    # sorted fallback for the warp-bucket rows: materialize
                    # ONLY their logits rows through the head and run the
                    # reference sampler on them; padding indices (== B)
                    # clip on the gather and drop on the scatter
                    rng, sub2 = jax.random.split(rng)
                    safe = jnp.clip(warp_rows, 0, self.B - 1)
                    row_logits = tfm.apply_head(
                        cfg, params, head_out[safe]
                    )
                    sub_sp = SamplingParams(
                        temperature=sp.temperature[safe],
                        top_p=sp.top_p[safe],
                        top_k=sp.top_k[safe],
                    )
                    w_tok, w_lp = sample_tokens(
                        sub2, row_logits, sub_sp, warp=True
                    )
                    tokens = tokens.at[warp_rows].set(w_tok, mode="drop")
                    lp = lp.at[warp_rows].set(w_lp, mode="drop")
            elif warp_bucket == 0:
                tokens, lp = sample_tokens(
                    sub, head_out, state.sp, warp=False
                )
            else:
                tokens, lp = sample_tokens(
                    sub, head_out, state.sp, warp=True, warp_rows=warp_rows
                )
            tokens = jnp.where(state.active, tokens, state.last_tokens)
            rows = jnp.arange(tokens.shape[0])
            idx = jnp.clip(state.n_gen, 0, state.out_tokens.shape[1] - 1)
            out_tokens = state.out_tokens.at[rows, idx].set(
                jnp.where(state.active, tokens, state.out_tokens[rows, idx])
            )
            out_logprobs = state.out_logprobs.at[rows, idx].set(
                jnp.where(state.active, lp, state.out_logprobs[rows, idx])
            )
            n_gen = state.n_gen + state.active.astype(jnp.int32)
            hit_stop = jnp.any(
                tokens[:, None] == state.stop_ids, axis=1
            ) & (n_gen >= state.min_gen)
            active = state.active & ~hit_stop & (n_gen < state.max_gen)
            census = None
            out_routing = state.out_routing
            if self._moe:
                (routing,) = routing                      # [L, B, top_k]
                # tokens per (layer, expert) of this step, every row counted
                load = jax.nn.one_hot(
                    routing, cfg.moe.num_experts, dtype=jnp.int32
                ).sum(axis=(1, 2))
                census = jnp.stack([(load > 0).sum(), load.max()])
                if cfg.moe.skip_expert:
                    # (the one-hot above has no column for the skip, which
                    # is no expert: it is in neither count.) Of the rows
                    # that run: routed in all, and routed to the skip
                    n_rows = state.active.sum() * routing.shape[0]
                    skipped = (
                        (routing == cfg.moe.num_experts).any(axis=-1)
                        & state.active[None]).sum()
                    census = jnp.concatenate(
                        [census, jnp.stack([n_rows, skipped])])
                elif not cfg.moe.holds_all:
                    # the rank's share: pairs of the rows that run, those
                    # on held experts, and the held experts any row hit
                    n_held, first = cfg.moe.held
                    held = (routing >= first) & (routing < first + n_held)
                    n_pairs = (
                        state.active.sum() * routing.shape[0]
                        * routing.shape[2])
                    census = jnp.concatenate([census, jnp.stack([
                        n_pairs, (state.active[None, :, None] & held).sum(),
                        (load[:, first : first + n_held] > 0).sum()])])
                if out_routing is not None:
                    out_routing = out_routing.at[rows, idx].set(jnp.where(
                        state.active[:, None],
                        routing.transpose(1, 0, 2).reshape(
                            routing.shape[1], -1),
                        out_routing[rows, idx],
                    ))
            return dataclasses.replace(
                state,
                cache=cache,
                lens=new_lens,
                last_tokens=tokens,
                active=active,
                n_gen=n_gen,
                out_tokens=out_tokens,
                out_logprobs=out_logprobs,
                rng=rng,
                out_routing=out_routing,
                ssm=ssm,
            ), census

        def flags_of(state: GenState, census):
            # harvest flags ride as UNDONATED aux outputs: the pipelined
            # step pulls them AFTER dispatching the next chunk (whose
            # donation consumes the state buffers). An MoE model adds its
            # routing census [experts hit, expert slots, largest load]:
            # three integers reduced from what the steps already computed
            flags = (state.active, state.n_gen, state.max_gen, state.lens)
            if census is not None:
                slots = n_steps * cfg.n_moe_layers * cfg.moe.num_experts
                flags += (jnp.stack([
                    census[:, 0].sum(), jnp.int32(slots), census[:, 1].max(),
                    *(census[:, i].sum() for i in range(2, census.shape[1])),
                ]).astype(jnp.int32),)
            return flags

        def chunk(params, state, table, warp_rows):
            def body(s, _):
                return one_step(s, params, table, warp_rows)

            state, census = jax.lax.scan(body, state, None, length=n_steps)
            return state, flags_of(state, census)

        sharding_kw = self._jit_sharding(2)
        if sharding_kw:
            # output is now (state, flags): the flag tuple replicates (it
            # is pulled to host) — a bare state out_sharding would be a
            # structure mismatch on meshed engines
            sharding_kw = dict(sharding_kw)
            sharding_kw["out_shardings"] = (
                sharding_kw["out_shardings"],
                (self._repl,) * (5 if self._moe else 4),
            )
        jitted = program_store.stored_jit(
            chunk, name="gen/chunk", key=key,
            built_from=self._built_from(), donate_argnums=(1,), **sharding_kw)
        self._jit_chunk[key] = jitted
        return jitted

    def _fold_chunk_aux(self, aux: tuple, chunk_attrs: dict):
        """What a resolved chunk carries after its four harvest flags: an
        MoE model's routing census (one ``[3]`` vector, and behind it what
        ``_census_extra`` names), else nothing."""
        if aux:
            hit, slots, load_max, *skip = (int(v) for v in aux[0])
            for name, v in zip(self._census_extra, skip):
                chunk_attrs[name] = v
                self.stats[name] += v
            chunk_attrs["moe_experts_hit"] = hit
            chunk_attrs["moe_expert_slots"] = slots
            chunk_attrs["moe_load_max"] = load_max
            self.stats["moe_experts_hit"] += hit
            self.stats["moe_expert_slots"] += slots
            self.stats["moe_load_max"] = max(
                self.stats["moe_load_max"], load_max
            )

    def _warp_bucket(self, n: int) -> int:
        """Power-of-two capacity bucket for the warping-slot index operand
        (0 = nothing warps): jit specializations stay bounded by log2
        buckets, never by the exact warping count."""
        if n <= 0:
            return 0
        w = 1
        while w < n:
            w *= 2
        return min(w, self.B)

    def _warp_operand(self, decode_steps: int, running: List[int],
                      chunk_attrs: dict):
        """Pick the per-slot warp operand of one dispatch, and whether its
        chunk program carries the online top-k buffer.

        The host knows exactly which resident slots warp (``_warp_host``,
        set at admission), so the chunk receives their indices padded to a
        power-of-two bucket — the sampling sort covers those rows only,
        instead of one top-p request forcing the whole batch through the
        ``[B, V]`` sort (the old static ``warp=True`` key did exactly
        that)."""
        # fused routing (the fused epilogue, ``fused_sample_applies``): the
        # chunk narrows the fallback bucket to the slots the online pass
        # cannot serve (_fused_warp_host — top-p, top-k > TOPK_MAX); plain
        # top-k slots ride the online buffer instead of the sort.
        mirror = self._fused_warp_host if self.fused else self._warp_host
        warp_slots = [b for b in running if mirror[b]]
        wb = self._warp_bucket(len(warp_slots))
        warp_idx = np.full((wb,), self.B, np.int32)  # padding => scatter-drop
        warp_idx[: len(warp_slots)] = warp_slots
        with_topk = self.fused and any(
            self._fused_topk_host[b] for b in running)
        if self.fused:
            metrics_mod.counters.add(
                metrics_mod.GEN_FUSED_SAMPLE_STEPS, decode_steps
            )
            # rows x steps as dispatched, by where they are sampled: the
            # fused pass, or the sorted path over their own logits rows
            fallback = len(warp_slots) * decode_steps
            if fallback:
                metrics_mod.counters.add(
                    metrics_mod.GEN_SAMPLER_FALLBACK_ROWS, fallback
                )
            chunk_attrs["fused_rows"] = len(running) * decode_steps - fallback
            chunk_attrs["sampler_fallback_rows"] = fallback
            self.stats["fused_rows"] += chunk_attrs["fused_rows"]
            self.stats["sampler_fallback_rows"] += fallback
        return with_topk, wb, warp_idx

    def _dispatch_chunk(self, chunk, W: int, warp_idx) -> tuple:
        """Dispatch one decode chunk and START its harvest-flag D2H copy
        in the same breath: ``copy_to_host_async`` enqueues the transfer
        directly behind the chunk on the device stream, so by the time
        anyone resolves the flags (immediately in unpipelined mode, one
        chunk later in pipelined mode) the bytes are already on — or on
        their way to — the host, and the resolve needs NO fresh
        host->device round trip. This is the flags' version of the
        ``_steps_ahead`` output protocol: start the copy at dispatch,
        consume it later."""
        self.state, flags = chunk(
            self.params, self.state,
            jnp.asarray(self._table_arg(slice(None), W)),
            jnp.asarray(warp_idx),
        )
        for f in flags:
            f.copy_to_host_async()
        return flags

    def _resolve_flags(self, flags: tuple) -> tuple:
        """Materialize a dispatched chunk's flag tuple on host. The copy
        was started at dispatch, so in pipelined steady state this is a
        buffer read, not a device sync — the ``blocked`` counter records
        every resolve that still had to wait (the event-log proof the
        zero-blocking-sync test pins at 0)."""
        metrics_mod.counters.add(metrics_mod.GEN_CHUNK_FLAG_FETCHES)
        blocked = not all(f.is_ready() for f in flags)
        if blocked:
            metrics_mod.counters.add(metrics_mod.GEN_CHUNK_FLAG_BLOCKED)
        with tracing.span("gen_engine/flag_wait", blocked=blocked):
            # arealint: ok(resolving the dispatch-ahead flag copy, not a pull)
            return tuple(np.asarray(f) for f in flags)

    def _pull_fn(self):
        """``(buffers, at [2, n])`` -> of each output buffer the ``n``
        blocks of ``_pull_block`` positions that ``at`` names, a block
        ``(slot, first position)``: ``[n, block]`` of tokens and log-probs,
        ``[n, block, L x top_k]`` of a routing record. A LOOP of slices,
        not one gather: the chip's gather wants its operand in another
        layout and first copies the whole buffer to get it (0.35-0.5 GB of
        temporaries for a routing record of 192 x 4,096 x 110, which is
        what the row gather before this one cost a harvest); a slice reads
        the buffer where it lies."""
        block = self._pull_block

        def pull(buffers, at):
            def one(at):
                return tuple(
                    jax.lax.dynamic_slice(
                        buf, (at[0], at[1]) + (0,) * (buf.ndim - 2),
                        (1, block) + buf.shape[2:])[0]
                    for buf in buffers)

            return jax.lax.map(one, at.T)

        return program_store.stored_jit(pull, name="gen/pull", key=block)

    def _out_buffers(self) -> tuple:
        st = self.state
        routing = () if st.out_routing is None else (st.out_routing,)
        return (st.out_tokens, st.out_logprobs) + routing

    def _pull_outputs(
        self, slots: Sequence[int], flags: Optional[tuple] = None,
    ) -> dict:
        """What ``slots`` have generated, to the host: of each its tokens
        and log-probs (on a ``record_routing`` engine its routing record
        too, a token's back to ``[L, top_k]``) cut to its ``n_gen``, under
        ``out_tokens`` / ``out_logprobs`` / ``out_routing`` BY SLOT, beside
        every slot's ``n_gen`` / ``active`` / ``max_gen``. ONE pull of what
        those slots WROTE, not of every slot's buffers at their whole cap
        (4-40 MB a chunk boundary at 64-256 slots and caps of
        4,096-15,360, to read the few rows that finished; PERF.md section
        6, PR 60): the buffers are read in blocks of ``_pull_block``
        positions, ``ceil(n_gen / block)`` a slot, so a long row does not
        widen the short ones beside it; the list of blocks is padded to one
        of ``_pull_counts`` (every program built with the engine: nothing
        compiles here), gathered on the device and pulled. ``flags``:
        ``(n_gen, active, max_gen)`` where the caller holds them fresh on
        the host (the harvest: its chunk's resolved flags); a caller that
        does not (a chunk may be in flight) leaves them out and pays a
        second small pull for them first."""
        st, block = self.state, self._pull_block
        slots, buffers = list(slots), self._out_buffers()
        with tracing.span("gen_engine/harvest/pull") as attrs:
            nbytes = 0
            if flags is None:
                flags = jax.device_get((st.n_gen, st.active, st.max_gen))
                nbytes = sum(a.nbytes for a in flags)
            n_gen, active, max_gen = flags
            counts = [-(-int(n_gen[b]) // block) for b in slots]
            at = np.stack([
                np.repeat(slots, counts),
                block * np.concatenate(
                    [np.arange(k) for k in counts] + [np.arange(0)]),
            ]).astype(np.int32)
            # the largest program as often as it takes, then the smallest
            # that holds the rest; what pads it (block 0 of slot 0) lies
            # behind every block that was asked for
            most, parts = self._pull_counts[-1], []
            for i in range(0, at.shape[1], most):
                part = at[:, i : i + most]
                n = next(c for c in self._pull_counts if c >= part.shape[1])
                parts.append(self._jit_pull(
                    buffers, np.pad(part, ((0, 0), (0, n - part.shape[1])))))
            got = jax.device_get(parts)
            attrs.update(
                bytes=nbytes + sum(a.nbytes for part in got for a in part),
                rows=len(slots), blocks=sum(len(part[0]) for part in got))
        out = {"n_gen": n_gen, "active": active, "max_gen": max_gen,
               "out_routing": {}}
        firsts = block * (np.cumsum(counts) - counts)
        for key, buf, *blocks in zip(
                ("out_tokens", "out_logprobs", "out_routing"), buffers, *got):
            # (one program's blocks as they came: no copy on the hot path)
            flat = (
                blocks[0] if len(blocks) == 1
                else np.concatenate(blocks) if blocks
                else np.zeros((0, block) + buf.shape[2:], buf.dtype)
            ).reshape((-1,) + buf.shape[2:])
            if key == "out_routing":
                flat = flat.reshape(len(flat), -1, self.cfg.moe.top_k)
            out[key] = {
                b: flat[first : first + int(n_gen[b])]
                for b, first in zip(slots, firsts)
            }
        return out

    def _harvest(self, b: int, reason: str, host_state: dict) -> GenOutput:
        """Release slot ``b`` and build its output from a host snapshot.

        Host bookkeeping only — callers batch BOTH device directions: one
        ``_pull_outputs`` for all finished slots and (in ``pause``, where
        slots are still active on device) one scatter deactivating them.
        The previous per-slot pull + per-slot ``.at[b].set`` dispatch cost
        two blocking host<->device syncs per finished slot (VERDICT r3
        weak #2; their cost on an attached chip is not measured). In
        ``step()``'s path the decode chunk already set
        ``active[b]=False`` on device, so no scatter is needed at all."""
        n = int(host_state["n_gen"][b])
        toks = host_state["out_tokens"][b].tolist()
        lps = host_state["out_logprobs"][b].tolist()
        routing = host_state["out_routing"].get(b)
        info = self._free_slot(b)
        with self._pending_lock:
            self._req_meta.pop(info.rid, None)
        t_done = time.perf_counter()
        t_first = info.t_first
        if t_first is None and n > 0:
            # pause() between a chunk's dispatch and its resolve
            t_first = t_done
        t_admit, hit = info.t_admit, info.prefix_hit_tokens
        c = self._carried.pop(info.rid, None)
        if c is not None:
            # the dry rule preempted it: what it generated in its earlier
            # tenures comes first, under the original's stamps
            toks, lps = c["tokens"] + toks, c["logprobs"] + lps
            if c["routing"] is not None:
                routing = np.concatenate([c["routing"], routing])
            t_admit, hit = c["t_admit"], c["prefix_hit_tokens"]
            t_first = c["t_first"] or t_first
        return GenOutput(
            rid=info.rid,
            output_ids=toks,
            output_logprobs=lps,
            finish_reason=reason,
            version=self.version,
            t_submit=info.t_submit,
            t_admit=t_admit,
            t_first=t_first,
            t_done=t_done,
            output_routing=routing,
            prefix_hit_tokens=hit,
        )

    def _dispatch(self, decode_steps: int, ahead: int,
                  chunk_attrs: dict) -> Tuple[Optional[tuple], List[int], int]:
        """Seat the slots (``_seat``: who runs, with the pages the chunk
        writes), pick the chunk program for them and dispatch it, under
        its span: only what the chunk program needs, because the device
        has nothing to run until ``_dispatch_chunk`` returns (what only
        counts is :meth:`_census`, behind the enqueue). ``ahead``: tokens
        already dispatched but not yet in ``_lens_host`` (pipelined mode).
        Returns the flag handles (``None``: no slot to run), the slots that
        run the chunk and its table width."""
        with tracing.span("gen_engine/dispatch") as attrs:
            # (the host's lengths may lag one chunk behind: a lower bound,
            # so no live page of a window kind goes)
            with tracing.span("gen_engine/dispatch/seat"):
                running = self._seat(ahead + decode_steps, chunk_attrs)
            if not running:
                return None, running, 0
            with_topk, wb, warp_idx = self._warp_operand(
                decode_steps, running, chunk_attrs
            )
            # width-limit the chunk to the pages this chunk can touch
            W = self._table_width(
                int(self._lens_host[running].max()) + ahead + decode_steps)
            attrs["table_width"] = W
            with tracing.span("gen_engine/dispatch/enqueue", table_width=W):
                chunk = self._chunk_fn(
                    decode_steps, W, wb, fused=self.fused, with_topk=with_topk)
                flags = self._dispatch_chunk(chunk, W, warp_idx)
            return flags, running, W

    def _census(self, decode_steps: int, W: int, running: List[int],
                chunk_attrs: dict) -> None:
        """Every count of the chunk just dispatched, under its span: onto
        the chunk span's attributes and ``self.stats``. None of it is an
        input of the chunk program, so it runs BEHIND the enqueue, while
        the device computes the chunk; it reads the host's lengths, tables
        and pool as ``_dispatch`` left them (nothing moves them before the
        chunk's flags are resolved)."""
        with tracing.span("gen_engine/census"):
            lens = self._lens_host[running]
            if self._windowed:
                w = max(w for w in self._windows if w is not None)
                chunk_attrs["window_resident_tokens"] = int(
                    np.minimum(lens, w).sum())
                self.stats["window_resident_tokens"] += chunk_attrs[
                    "window_resident_tokens"]
                for kind, n in self.cache_bytes_per_token_by_kind().items():
                    chunk_attrs["cache_bytes_per_token_" + kind] = n
            chunk_attrs["slots"] = len(running)
            # KV positions the decode kernel reads at the chunk's first
            # step: exact on the host (prompt - 1 + generated per slot; in
            # pipelined mode less the chunk still in flight)
            resident = int(lens.sum())
            chunk_attrs["resident_tokens"] = resident
            chunk_attrs["cache_bytes_per_token"] = self.cache_bytes_per_token()
            # a looped stack: the passes a step makes over its weights, the
            # layers of cache behind them, and the layer runs (each reads
            # one layer's weights once) of the chunk as dispatched
            cfg = self.cfg
            layer_passes = decode_steps * cfg.n_passes * cfg.n_layers
            chunk_attrs.update(
                loop_passes=cfg.n_passes, cache_layers=cfg.cache_layers,
                layer_passes=layer_passes)
            if self._stateful:
                # each running slot's state is read and written once a
                # state-space layer a step; as dispatched: a slot that ends
                # inside the chunk is counted to the chunk's end
                chunk_attrs.update(
                    state_slots=len(running) * decode_steps,
                    state_bytes_per_slot=tfm.row_state_bytes(cfg),
                    state_layers=tfm.row_state_layers(cfg))
                self.stats["state_slots"] += chunk_attrs["state_slots"]
            self.stats["loop_passes"] += decode_steps * cfg.n_passes
            self.stats["layer_passes"] += layer_passes
            if self._moe:
                # every row of the batch routes, free slots too
                chunk_attrs.update(self._count_moe_rows(
                    self.B, decode_steps * cfg.n_passes))
            counts = self._kernel_counts(W, running)
            if counts is not None:
                self.stats["resident_tokens"] += resident
            if self._kv_write_rows():
                # one tile a (cache layer, running slot, step), as dispatched: a
                # slot that finishes inside the chunk writes none after
                counts = dict(
                    counts or {},
                    kv_write_tiles=(
                        decode_steps * cfg.cache_layers * len(running)))
            if counts is not None:
                chunk_attrs.update(counts)
                for name, n in counts.items():
                    self.stats[name] = self.stats.get(name, 0) + n
            self._observe_occupancy()

    def _kv_write_rows(self) -> int:
        """Rows of the pool tile that the ``kv_page_write`` kernel copies,
        where fresh K/V reaches this engine's pool through it; 0 where the
        XLA scatter writes the pool (an int8 pool, a mesh, no TPU): the
        predicate the model's write applies
        (``ops/paged_attention.py:kv_write_kernel_applies``) over the same
        pool."""
        cache = self.state.cache
        if not paged_ops.kv_write_kernel_applies(
            self._decode_use_pallas, cache.pages, cache.quantized, self.mesh
        ):
            return 0
        from areal_tpu.ops.pallas import kv_page_write

        return kv_page_write.tile_rows(cache.pages.dtype)

    def _kernel_counts(
        self, W: int, running: List[int],
    ) -> Optional[Dict[str, int]]:
        """What the paged-decode kernel does at the first step of a chunk
        of table width ``W`` that the slots ``running`` run, from the
        kernel's own block plan over the host's lengths, sorted as
        ``decode_step_paged`` sorts its rows.
        ``kernel_positions``: KV positions its body runs over (over
        ``resident_tokens``, how many times the resident KV it computes);
        ``kernel_steps_active`` of ``kernel_steps`` grid steps reach a page
        and walk their table entries (the rest cost one test);
        ``kernel_steps_chained`` of the reached ones are the first of a
        block after the call's first, their copies started by the last
        reached step of an earlier block. Free slots
        count as empty (on the device a finished slot keeps its length
        until it is refilled). Where the step reads the pages several rows
        name once (``ops/paged_attention.py:shared_prefix_applies``) the
        full layers' call is two programs and the counts are of both
        (``ops/pallas/paged_attention.py:shared_counts``, from the table
        the chunk is dispatched with; only ``running`` rows, the ones active
        on the device, sit in a group): ``kv_pages_named`` pages under the
        rows' lengths, ``kv_pages_read`` page copies started,
        ``kv_shared_groups`` blocks of the prefix program in use with
        ``kv_shared_rows`` rows seated; ``1 - read / named`` is the
        program's side of the benchmark's ``gen.kv_shared_share``. ``None``
        where the chunk runs no such kernel: the XLA gather path."""
        cfg = self.cfg
        tp = self.mesh.shape["model"] if self.mesh is not None else 1
        pool_dtype = self.state.cache.pages.dtype
        streams, heads, width = tfm.kv_page_geometry(cfg)
        if cfg.mla is not None:
            applies = paged_ops.latent_kernel_applies(
                self._decode_use_pallas, cfg.mla.kv_lora_rank, self.page
            )
        else:
            applies = paged_ops.decode_kernel_applies(
                self._decode_use_pallas, width, heads, self.page,
                pool_dtype, tp,
            )
        if not applies:
            return None
        from areal_tpu.ops.pallas import paged_attention as pl_paged

        lens = np.sort(self._lens_host)
        by_kind = []
        # the full layers' call where rows of the chunk name the same pages
        # (the rule ``decode_step_paged`` applies): the prefix program and
        # the rows' own pages, from the table the chunk is dispatched with
        shares = paged_ops.shared_prefix_applies(
            self._decode_use_pallas, width, heads, self.page, pool_dtype,
            full_kinds=sum(w is None for w in self._windows),
            quantized=self.state.cache.quantized, latent=cfg.mla is not None,
            slot_order=cfg.recurrent is not None, mesh=self.mesh,
        )
        pages = int((-(-lens // self.page)).sum())
        shared = {"kv_pages_named": pages, "kv_pages_read": pages,
                  "kv_shared_groups": 0, "kv_shared_rows": 0}
        table = self._table_arg(slice(None), W) if shares else None
        active = np.zeros((self.B,), bool)
        active[running] = True
        for j, w in enumerate(self._windows):
            # (a kind's program has its own plan: a full-attention step is
            # fewer pages than a window step)
            sb, kp = pl_paged.block_plan(
                self.B, heads // tp, width, self.page, W, pool_dtype,
                streams=streams, windowed=w is not None,
            )
            span, nblk = kp * self.page, -(-W // kp)
            if w is None and shares:
                of = pl_paged.shared_counts(
                    table if table.ndim == 2 else table[j], self._lens_host,
                    active, self.page, sb, kp, nblk,
                    by_own=not self._windowed)
                shared = {k: of[k] for k in shared}
                by_kind.append((
                    of["kernel_positions"], of["kernel_steps_active"],
                    of["kernel_steps"], of["kernel_steps_chained"]))
                continue
            first = None if w is None else pl_paged.first_visible(lens, w)
            by_kind.append((
                pl_paged.kernel_positions(lens, sb, span, first),
                *pl_paged.kernel_steps(lens, sb, span, nblk, first),
                pl_paged.kernel_steps_chained(lens, sb, span, nblk, first),
            ))
        positions, active, total, chained = (sum(x) for x in zip(*by_kind))
        # a layer's call on average: a window layer computes over fewer
        # positions than are resident, so with layer kinds this can read
        # under the resident tokens
        counts = {
            "kernel_positions": positions // len(by_kind),
            "kernel_steps_active": active,
            "kernel_steps": total,
            "kernel_steps_chained": chained,
            **shared,
        }
        if self._windowed:
            for kind in ("full", "window"):
                of = [c[0] for c, w in zip(by_kind, self._windows)
                      if (w is None) == (kind == "full")]
                if of:
                    counts[f"kernel_positions_{kind}"] = of[0]
        return counts

    def _mark_first(self, slots) -> None:
        """``t_first`` for the slots whose first chunk just resolved."""
        now = time.perf_counter()
        for b in slots:
            info = self._slots[b]
            if info is not None and info.t_first is None:
                info.t_first = now

    def _harvest_finished(self, finished: List[int], flags: tuple,
                          chunk_attrs: dict) -> List[GenOutput]:
        """One output pull for every finished slot (of what each wrote:
        ``flags``, the chunk's resolved ``(n_gen, active, max_gen)``, say
        how much), then their release, under its span."""
        n_gen, _, max_gen = flags
        chunk_attrs["finished"] = len(finished)
        if not finished:
            return []
        with tracing.span(
            "gen_engine/harvest", finished=len(finished)
        ) as attrs:
            # the chunk already deactivated them on device, so no scatter
            # back
            host_state = self._pull_outputs(finished, flags)
            outs = [
                self._harvest(
                    b, _finish_reason(n_gen[b], max_gen[b]),
                    host_state=host_state,
                )
                for b in finished
            ]
            # the finished requests' stamps as on their GenOutput, so a
            # reader of the span ring has queue wait and time to first
            # token without a span per request: a few floats a chunk
            attrs["stamps"] = [
                [round(t, 6) for t in
                 (o.t_submit, o.t_admit, o.t_first, o.t_done)]
                for o in outs
            ]
            return outs

    def step(self, decode_steps: int = 16) -> List[GenOutput]:
        """Admit pending requests, run one decode chunk, harvest finished.

        Pipelined mode (``AREAL_DECODE_PIPELINE=1`` / ``pipeline_chunks``):
        the per-chunk host sync — one device->host round trip that the
        device idles through (VERDICT r4 #5; share of serving wall time on
        an attached chip not measured) — overlaps the NEXT chunk's
        compute: chunk k+1 is dispatched first, then chunk k's (already resolved, undonated)
        flag outputs are pulled and its finishes harvested, one chunk
        late. The harvest pulls what the finished slots WROTE
        (``_pull_outputs``: blocks of their tokens, log-probs and routing
        gathered on the device, sized by the flags' ``n_gen``; not every
        slot's buffers), but the gather's operand is the CURRENT state,
        the one the in-flight chunk returns, so a harvest-bearing step
        still waits that chunk out like the unpipelined path.
        """
        with self._lock:
            if self.paused:
                return []
            # batch-level chunk span: runs on the executor thread, so it
            # roots its own trace (per-request attribution joins at
            # submit/harvest); attrs carry the chunk's slot census
            with tracing.span(
                "gen_engine/chunk", steps=decode_steps
            ) as span_attrs:
                if self._pipeline:
                    return self._step_pipelined(decode_steps, span_attrs)
                self._admit()
                flags, running, W = self._dispatch(
                    decode_steps, 0, span_attrs)
                if flags is None:
                    return []
                self._census(decode_steps, W, running, span_attrs)
                # one host sync per chunk; the flag copy was enqueued at
                # dispatch, so the resolve costs no extra round trip
                flags = self._resolve_flags(flags)
                active, n_gen, max_gen, lens = flags[:4]
                self._fold_chunk_aux(flags[4:], span_attrs)
                # (a held slot's length stands still: the same number)
                self._lens_host[:] = lens
                self._mark_first(running)
                finished = [b for b in running if not active[b]]
                return self._harvest_finished(
                    finished, (n_gen, active, max_gen), span_attrs
                )

    def _step_pipelined(
        self, decode_steps: int, span_attrs: dict
    ) -> List[GenOutput]:
        self._admit()
        outs: List[GenOutput] = []
        if self._prev_flags is not None and self._pool_is_short(decode_steps):
            # the dry rule acts on what the device has done, not on what
            # is still in flight: the chunk before is settled first (this
            # boundary pays the sync the pipeline hides elsewhere)
            outs = self._settle(span_attrs)
        # _lens_host can be one in-flight chunk stale for continuing
        # slots: widen the bound by the TOKENS already dispatched
        new_flags, running, W = self._dispatch(
            decode_steps, self._steps_ahead, span_attrs
        )
        if running:
            self._census(decode_steps, W, running, span_attrs)
        new_running = tuple((b, int(self._slot_epoch[b])) for b in running)
        prev_flags = self._prev_flags
        if prev_flags is None:
            self._prev_flags, self._prev_running = new_flags, new_running
            self._steps_ahead = decode_steps if running else 0
            return outs
        outs = self._settle(span_attrs)
        self._prev_flags, self._prev_running = new_flags, new_running
        self._steps_ahead = decode_steps if running else 0
        return outs

    def _pool_is_short(self, decode_steps: int) -> bool:
        """Whether seating the next chunk would hold a slot out (or one
        is held out now): the pool cannot raise every slot's reservation
        to the pages that chunk writes."""
        if self._held_out:
            return True
        span = self._steps_ahead + decode_steps
        occupied = np.nonzero(self._n_total)[0]
        short = np.maximum(
            self._pages_ahead(occupied, span) - self._reserved[:, occupied], 0)
        return int(short.sum()) > self.pool.n_unpromised

    def _settle(self, span_attrs: dict) -> List[GenOutput]:
        """Pipelined mode: resolve the flags of the chunk dispatched one
        step ago and harvest its finishes. Nothing is in flight after."""
        prev_flags, prev_running = self._prev_flags, self._prev_running
        self._prev_flags, self._prev_running = None, ()
        self._steps_ahead = 0
        # chunk k's flags landed on host while k (and now k+1) computed:
        # the dispatch-ahead copy makes this resolve a buffer read in
        # steady state — zero blocking syncs at the chunk boundary
        prev_flags = self._resolve_flags(prev_flags)
        active, n_gen, max_gen, lens = prev_flags[:4]
        # (pipelined: the census of the chunk before this span's own)
        self._fold_chunk_aux(prev_flags[4:], span_attrs)
        # epoch check: a slot that turned over since chunk k's dispatch now
        # holds a DIFFERENT request — k's stale flags must not touch it
        same = [
            b for b, ep in prev_running
            if self._slots[b] is not None and self._slot_epoch[b] == ep
        ]
        for b in same:  # NOT fresh admissions (their lens is live)
            self._lens_host[b] = lens[b]
        self._mark_first(same)
        finished = [b for b in same if not active[b]]
        # the output gather reads the CURRENT state: it waits out the
        # in-flight chunk (same cost the unpipelined path pays every
        # chunk). The finished slots were inactive through chunk k+1, so
        # their outputs, and chunk k's ``n_gen`` of them, are final.
        return self._harvest_finished(
            finished, (n_gen, active, max_gen), span_attrs)

    @property
    def has_inflight(self) -> bool:
        """Pipelined mode: a dispatched chunk whose finishes have not been
        harvested yet (the run/serve loops must keep stepping)."""
        return self._prev_flags is not None

    def run_until_done(self, decode_steps: int = 16, timeout: float = 600.0):
        """Convenience loop: run until every submitted request finished."""
        outs = []
        t0 = time.time()
        while True:
            with self._lock:
                busy = (
                    self._pending or self.n_running() or self.has_inflight
                ) and not self.paused
            if not busy:
                break
            outs.extend(self.step(decode_steps))
            if time.time() - t0 > timeout:
                raise TimeoutError("generation did not finish in time")
        return outs
