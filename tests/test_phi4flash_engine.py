"""phi4flash through the generation engine: admission in chunks over NINE-like
cache layers (here three: two window kinds and the full one, a page table
each), the per-slot Mamba-1 state and its snapshots in the prefix cache,
window pages released while the full layer's grow, cross layers that read
pages they never wrote.

The model, weights and tolerance are ``tests/test_phi4flash.py``'s; every
comparison of served log-probabilities is against the plain token-by-token
reference or against the same engine with the prefix cache off."""

import functools
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import engine_contract
from areal_tpu.base import tracing
from areal_tpu.gen.engine import GenerationEngine, GenRequest
from areal_tpu.models import transformer as tfm
from benchmark.reference import phi4flash as ref
from tests.test_phi4flash import ARCH, CFG, TOL, seeded_params

PAGE = 8


@pytest.fixture(scope="module")
def params():
    return seeded_params(CFG)


def _engine(params, **kw):
    kw = {"max_slots": 4, "max_seqlen": 128, "max_new_tokens_cap": 48,
          "page_size": PAGE, "admit_buckets": (1, 2, 4), "seed": 3, **kw}
    return GenerationEngine(CFG, params, **kw)


@pytest.mark.parametrize("check", engine_contract.CHECKS)
def test_engine_contract(params, check):
    engine_contract.run(check, functools.partial(_engine, params), CFG, params)


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(1, ARCH["vocab_size"], n).tolist()


def _run(eng, prompts, max_new=10, steps=4):
    for i, p in enumerate(prompts):
        eng.submit(GenRequest(
            rid=str(i), input_ids=list(p), max_new_tokens=max_new, temperature=1.0))
    return {o.rid: o for o in eng.run_until_done(decode_steps=steps)}


def _assert_reference(params, prompt, out):
    want = np.asarray(ref.sequence_logprobs(
        params, ARCH, list(prompt) + out.output_ids))[len(prompt) - 1:]
    np.testing.assert_allclose(out.output_logprobs, want, atol=TOL)


@pytest.mark.parametrize("use_pallas", [None, False], ids=["kernels", "xla"])
def test_admission_in_chunks_then_paged_decode_is_the_reference(
        params, use_pallas):
    """Prompts of one to five pages (most past the window of 8), admitted
    a page a chunk, decoded 24 tokens: the window program, the full program
    and the write kernel (interpreted) or XLA's gather and scatter."""
    prompts = [_prompt(i, n) for i, n in enumerate((3, 9, 26, 41))]
    eng = _engine(params)
    eng._decode_use_pallas = use_pallas
    outs = _run(eng, prompts, max_new=24)
    for rid, o in outs.items():
        _assert_reference(params, prompts[int(rid)], o)
    assert eng.stats["prefill_tokens"] == sum(len(p) - 1 for p in prompts)


@pytest.mark.parametrize("n_prompts", [1, 3, 4])
def test_waves_without_a_bucket_of_one_are_the_reference(params, n_prompts):
    """The cell's admission buckets (``grpo16_closed128_out15k_yoco``: 2
    and 8; here 2 and 4): a wave of one is a row and a padding row, a wave
    of three a row past them, and each is the reference's, state and
    pages; half the admission programs of (1, 2, 4, 8) are built at a
    start."""
    prompts = [_prompt(20 + i, n) for i, n in enumerate((19, 3, 30, 12))]
    prompts = prompts[:n_prompts]
    eng = _engine(params, admit_buckets=(2, 4))
    outs = _run(eng, prompts, max_new=12)
    for rid, o in outs.items():
        _assert_reference(params, prompts[int(rid)], o)
    assert {k[0] for k in eng._jit_extend} <= {2, 4}
    # ... and a second wave over the first's snapshot and pages
    again = _run(eng, prompts[:1] * 2, max_new=6)
    for o in again.values():
        _assert_reference(params, prompts[0], o)


def test_the_write_kernel_is_traced_once_for_the_cache_kinds(params):
    """A table a cache kind (three here, nine in the published model): the
    write of a decode step traces ``kv_page_write`` for the FIRST kind and
    finds the trace again for the others (what a start pays for a kind is
    tracing and lowering the kernel), and leaves the pool the scatter
    leaves."""
    from areal_tpu.ops.pallas import kv_page_write

    K, B, M = CFG.period, 2, 4
    cache = tfm.PagedKVCache.empty(CFG, 16, PAGE)
    L = cache.pages.shape[0] * K
    shape = (L, B, 1) + cache.pages.shape[3:4] + cache.pages.shape[5:]
    ks = jax.random.normal(jax.random.key(1), shape, cache.pages.dtype)
    vs = jax.random.normal(jax.random.key(2), shape, cache.pages.dtype)
    table = jnp.arange(K * B * M, dtype=jnp.int32).reshape(K, B, M) % 16
    start, count = jnp.asarray([3, 9]), jnp.asarray([1, 1])
    traced = []
    plain = kv_page_write._write_kernel

    def counting(*a, **kw):
        traced.append(1)
        return plain(*a, **kw)

    kv_page_write._write_kernel = counting
    try:
        got = jax.jit(lambda c: tfm._write_chunk_kv(
            c, ks, vs, table, start, count, use_pallas=True))(cache)
    finally:
        kv_page_write._write_kernel = plain
    want = tfm._write_chunk_kv(
        cache, ks, vs, table, start, count, use_pallas=False)
    assert K == 3 and len(traced) == 1
    np.testing.assert_array_equal(got.pages, want.pages)


def test_group_through_a_snapshot_equals_the_prefix_cache_off(params):
    """A GRPO group of 6 over 4 slots whose prompt is past the window: the
    first member prefills, the rest are seeded from the snapshot of the
    three Mamba layers' state and borrow the pages of all three cache
    layers (a window kind's as far as they are still held)."""
    base = _prompt(0, 37)
    prompts = [base] * 6 + [base[:20] + _prompt(1, 9)]
    eng = _engine(params)
    outs = _run(eng, prompts)
    cold = _run(_engine(params, enable_prefix_cache=False), prompts)
    assert eng.stats["state_snapshot_hits"] == 5
    assert eng.stats["prefix_hit_tokens"] == 5 * 32
    assert outs["6"].prefix_hit_tokens == 0 and outs["1"].prefix_hit_tokens == 32
    for rid, o in outs.items():
        assert o.output_ids == cold[rid].output_ids
        np.testing.assert_allclose(
            o.output_logprobs, cold[rid].output_logprobs, atol=TOL)
        _assert_reference(params, prompts[int(rid)], o)
    spans = tracing.spans_since(0.0)
    chunk = [s["attrs"] for s in spans if s["name"] == "gen_engine/chunk"
             and "state_slots" in s.get("attrs", {})]
    assert chunk and chunk[-1]["state_layers"] == 3
    assert chunk[-1]["state_bytes_per_slot"] == 3 * (128 * 4 * 4 + 3 * 128 * 4)


def test_window_pages_go_back_in_every_window_layer_while_the_full_grow(
        params):
    """One request from 20 to 68 positions: both window kinds give back
    the pages wholly behind ``len - 8`` as it runs and hold two or three,
    the full kind holds them all; the cross layer has no table and no
    page of its own (three cache layers behind four attention layers)."""
    eng = _engine(params, max_slots=1, enable_prefix_cache=False)
    prompt = _prompt(7, 21)
    eng.submit(GenRequest(rid="a", input_ids=prompt, max_new_tokens=64))
    held, outs = [], []
    for _ in range(14):
        outs += eng.step(4)
        if eng._slots[0] is not None:
            held.append(eng._held[:, 0].sum(axis=1).tolist())
    assert eng._tables_host.shape[0] == CFG.cache_layers == 3
    full = [h[2] for h in held]
    assert full == sorted(full) and full[-1] >= 8
    assert all(h[0] == h[1] <= 4 for h in held)
    assert eng.stats["window_pages_released"] >= 2 * 6
    (out,) = outs + eng.run_until_done(decode_steps=4)
    _assert_reference(params, prompt, out)
    assert eng.pool.n_free == eng.n_pages


def test_recurrent_state_of_a_running_request_is_the_recurrence_s(params):
    prompt = _prompt(30, 45)
    eng = _engine(params)
    _run(eng, [prompt], max_new=4)              # files the snapshot
    eng.submit(GenRequest(
        rid="a", input_ids=prompt, max_new_tokens=40, temperature=1.0))
    for _ in range(4):
        eng.step(4)
    assert eng.stats["state_snapshot_hits"] == 1
    n, got = eng.recurrent_state("a")
    toks = eng.partial_outputs()["a"][0]
    fed = (prompt + toks)[:-1]
    want = ref.recurrent_state(params, ARCH, fed, "float32", len(fed))
    rounded = ref.recurrent_state(
        params, dict(ARCH, control_state_dtype="bfloat16"), fed, "float32",
        len(fed))

    def worst_layer(a):
        return (np.sqrt(((a - want) ** 2).sum((-2, -1)))
                / np.sqrt((want ** 2).sum((-2, -1)))).max()

    assert got.shape == (3, 1, 128, 4)          # [Ls, heads, d_inner, N]
    assert worst_layer(got[:, 0]) < 1e-5
    assert worst_layer(rounded) > 1e-3
    eng.run_until_done(decode_steps=4)


def test_a_reused_slot_starts_from_zero_state_and_an_empty_window(params):
    eng = _engine(params, max_slots=1, enable_prefix_cache=False)
    _run(eng, [_prompt(4, 40)], max_new=20)
    short = _prompt(5, 3)
    _assert_reference(params, short, _run(eng, [short])["0"])
    one = _prompt(6, 1)                       # nothing to prefill at all
    _assert_reference(params, one, _run(eng, [one])["0"])


@pytest.mark.parametrize("kw", [
    {"kv_dtype": "int8"}, {"mesh": "2"},
], ids=lambda kw: next(iter(kw)))
def test_engine_refuses_what_has_no_test_beside_recurrent_state(params, kw):
    if "mesh" in kw:
        from jax.sharding import Mesh
        kw = {"mesh": Mesh(np.asarray(jax.devices()[:2]), ("model",))}
    with pytest.raises(NotImplementedError, match="state-space"):
        _engine(params, **kw)


# ---- the programs the older families trace to -------------------------- #
#
# The stack plan replaced ``mixer_pattern`` and one scan helper: a
# ``granitemoehybrid`` model (a plan of one segment) and a ``smallthinker``
# model (no plan: a period of layer kinds over one stack) have to trace to
# the decode step they traced to before. The digests are of the step's
# jaxpr at the parent commit (PR 47), taken with this very function;
# granite's RE-PINNED by PR 54 (00804be9d33bcaa7 before it), on purpose:
# the step's convolution is ``ops/ssm.py:conv_step``, not the many-token
# form at one token.


def _decode_step_digest(cfg, **state):
    params = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.key(0)))
    K, B, M = cfg.period, 2, 4
    table = jnp.zeros((K, B, M) if K > 1 else (B, M), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, c, s: tfm.decode_step_paged(
        p, cfg, c, jnp.zeros((B,), jnp.int32), table,
        jnp.zeros((B,), jnp.int32), jnp.ones((B,), bool), use_pallas=False,
        ssm=s))(params, jax.eval_shape(
            lambda: tfm.PagedKVCache.empty(cfg, 8, 8)),
                jax.eval_shape(lambda: tfm.row_state_empty(cfg, B)))
    return hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]


def test_granite_and_smallthinker_trace_to_the_decode_step_they_did():
    from tests.test_granite_hybrid import CFG as GRANITE
    from tests.test_smallthinker import CFG as SMALLTHINKER
    assert _decode_step_digest(GRANITE) == "1bdc654d78706b85"
    assert _decode_step_digest(SMALLTHINKER) == "5838f7b0f9017cac"


def test_a_hit_ends_at_a_snapshot_whose_window_pages_are_all_there():
    """Per-slot state AND window kinds in one registry (first met by this
    family): a chain filed with a hole in a window kind (a prompt longer
    than the window's claim registers only what it held) is a hit only up
    to a snapshot whose borrower still finds every window page it reads;
    the state and the pages of a hit always stand at ONE position. (Before
    PR 48 the hit was cut to the usable pages and kept the LONGER prefix's
    snapshot: the tokens between ran twice through the state.)"""
    from areal_tpu.gen.pages import PagePool, PrefixRegistry

    pool = PagePool(64, 4)
    reg = PrefixRegistry(pool, [4, None], n_snapshots=4)
    ids = list(range(1, 25))                      # six pages of four
    short, long_ = reg.alloc_snapshot(), reg.alloc_snapshot()
    pages = [[int(a), int(b)] for a, b in zip(pool.alloc(6), pool.alloc(6))]
    reg.insert(ids[:8], pages[:2], snapshot=short)
    pages[5][0] = -1                  # the window kind's sixth page is gone
    reg.insert(ids, pages, snapshot=long_)
    # six pages matched, the snapshot at six needs window page 5: the hit
    # falls back to the snapshot at two pages, pages and state together
    hit = reg.lookup(ids + [99], 6)
    assert len(hit) == 2 and reg.hit_snapshot == short
    # ... and with that page there, the long snapshot serves
    pool2 = PagePool(64, 4)
    reg2 = PrefixRegistry(pool2, [4, None], n_snapshots=4)
    whole = [[int(a), int(b)]
             for a, b in zip(pool2.alloc(6), pool2.alloc(6))]
    snap = reg2.alloc_snapshot()
    reg2.insert(ids, whole, snapshot=snap)
    assert len(reg2.lookup(ids + [99], 6)) == 6 and reg2.hit_snapshot == snap


def test_a_prompt_longer_than_the_window_s_claim_is_still_shared(params):
    """A window kind holds at most its claim (the window and a look-ahead:
    6 pages of 8 here) when a prompt is admitted, so a prompt of 9 pages is
    filed with holes in the window kinds; its chain is filed again behind
    its chunks, the pages taken meanwhile come home, and a sibling of a
    later cycle is a HIT (seeded from the snapshot, every window page it
    reads there) that serves the reference's log-probs."""
    prompt = _prompt(40, 8 * 9 + 3)
    eng = _engine(params, max_seqlen=160)
    assert eng._window_claim[:2] == [6, 6]
    first = _run(eng, [prompt])["0"]
    second = _run(eng, [prompt, prompt[:70] + _prompt(41, 5)])
    assert first.prefix_hit_tokens == 0
    assert second["0"].prefix_hit_tokens == 72
    assert eng.stats["state_snapshot_hits"] == 1
    for o, p in ((first, prompt), (second["0"], prompt)):
        _assert_reference(params, p, o)
