"""Paged-KV generation engine: prefix sharing, pool accounting, bounded
compiles, and thread-safety under pause/submit racing step.

Counterpart of the capacity behaviors the reference inherits from SGLang
(radix cache sharing one prefill across a GRPO group, paged KV memory,
``patch/sglang/v0.4.6.post4.patch``).
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.gen.engine import GenerationEngine, GenRequest
from areal_tpu.gen.pages import OutOfPagesError, PagePool, PrefixRegistry
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig

CFG = ModelConfig(
    n_layers=2, n_q_heads=4, n_kv_heads=2, head_dim=8, hidden_dim=32,
    intermediate_dim=64, vocab_size=128, dtype="float32",
)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.key(5))


class TestPagePool:
    def test_alloc_release_refcount(self):
        pool = PagePool(4, page_size=8)
        a = pool.alloc(2)
        assert pool.n_free == 2
        pool.ref(a)                 # shared
        pool.release(a)             # one ref left
        assert pool.n_free == 2
        pool.release(a)
        assert pool.n_free == 4
        with pytest.raises(OutOfPagesError):
            pool.alloc(5)
        with pytest.raises(ValueError):
            pool.release(a)         # double free

    def test_prefix_registry_share_evict(self):
        pool = PagePool(8, page_size=4)
        reg = PrefixRegistry(pool)
        prompt = list(range(10))
        pages = pool.alloc(2)       # 2 full pages = first 8 tokens
        reg.insert(prompt, pages)
        assert pool.n_free == 6
        got = reg.lookup(prompt, 2)
        assert got == pages
        # radix: a shorter request hits the chain's prefix...
        one = reg.lookup(prompt, 1)
        assert one == pages[:1]
        pool.release(one)
        # ...and a prompt diverging in page 2 shares page 1 only
        sib = prompt[:4] + [99] * 6
        part = reg.lookup(sib, 2)
        assert part == pages[:1]
        pool.release(part)
        # a prompt diverging in page 1: cold miss
        assert reg.lookup([9] + prompt[1:], 2) is None
        pool.release(got)           # borrower done
        pool.release(pages)         # original owner done; registry ref remains
        assert pool.n_free == 6
        reg.evict_lru(8)            # need pages -> registry lets go
        assert pool.n_free == 8

    def test_prefix_registry_radix_extends_chains(self):
        """Sibling prompts extend the tree past the shared preamble, and LRU
        eviction drops leaves before their parents."""
        pool = PagePool(8, page_size=4)
        reg = PrefixRegistry(pool)
        pre = [1, 2, 3, 4]
        a = pre + [5, 6, 7, 8]
        b = pre + [9, 10, 11, 12]
        pa = pool.alloc(2)
        reg.insert(a, pa)                 # chain: pre -> a-tail
        shared = reg.lookup(b, 2)         # sibling: preamble page only
        assert shared == pa[:1]
        pb_tail = pool.alloc(1)
        reg.insert(b, shared + pb_tail)   # extend: pre -> b-tail
        assert len(reg) == 3
        full_b = reg.lookup(b, 2)
        assert full_b == [pa[0], pb_tail[0]]
        pool.release(full_b)
        pool.release(shared)
        pool.release(pa)
        pool.release(pb_tail)
        # all 3 pages held only by the tree (pool.n_free == 5 of 8). Demand
        # 7 free: the tree must give up 2 pages — the two LEAF tails — and
        # keep the shared preamble (their parent) resident.
        assert pool.n_free == 5
        evicted = reg.evict_lru(7)
        assert evicted == 2 and pool.n_free == 7 and len(reg) == 1
        got = reg.lookup(a, 1)
        assert got == pa[:1]       # the preamble page survived
        pool.release(got)
        # demand everything: the remaining parent goes too
        assert reg.evict_lru(8) == 1 and pool.n_free == 8 and len(reg) == 0

    def test_evict_skips_pages_borrowed_by_running_slots(self):
        """Evicting a page a resident slot still borrows frees nothing —
        the tree must keep it (hot prefixes survive transient pressure)
        instead of draining itself for zero freed pages."""
        pool = PagePool(4, page_size=4)
        reg = PrefixRegistry(pool)
        prompt = [1, 2, 3, 4, 5, 6, 7, 8]
        pages = pool.alloc(2)
        reg.insert(prompt, pages)
        borrowed = reg.lookup(prompt, 2)   # a running slot holds both pages
        assert borrowed == pages
        pool.release(pages)                # prefill owner done
        assert pool.n_free == 2
        # pressure: nothing evictable actually frees -> tree stays intact
        assert reg.evict_lru(4) == 0
        assert len(reg) == 2
        pool.release(borrowed)             # slot finishes
        assert reg.evict_lru(4) == 2 and pool.n_free == 4 and len(reg) == 0


class TestPrefixSharing:
    def test_one_prefill_serves_group_of_8(self, params):
        """8 identical prompts (a GRPO group): the prompt's full pages are
        computed ONCE; members 2-8 extend only the sub-page tail."""
        page = 8
        prompt = [int(x) for x in np.random.default_rng(0).integers(1, 128, 21)]
        # plen_eff = 20 = 2 full pages (16 tokens) + tail 4
        eng = GenerationEngine(
            CFG, params, max_slots=8, max_seqlen=64, page_size=page, seed=0,
        )
        for i in range(8):
            eng.submit(GenRequest(
                rid=f"g{i}", input_ids=prompt, max_new_tokens=4, greedy=True,
            ))
        outs = eng.run_until_done(decode_steps=4)
        assert len(outs) == 8
        # all members produced identical greedy outputs from the shared KV
        assert len({tuple(o.output_ids) for o in outs}) == 1
        # one slot computed the full 20; seven extended only the 4-token tail
        assert eng.stats["prefix_hits"] == 7
        assert eng.stats["prefix_hit_tokens"] == 7 * 16
        assert eng.stats["prefill_tokens"] == 20 + 7 * 4
        # registry entry survives for the NEXT group on the same prompt
        eng.submit(GenRequest(rid="late", input_ids=prompt, max_new_tokens=4,
                              greedy=True))
        late = eng.run_until_done(decode_steps=4)
        assert eng.stats["prefix_hits"] == 8
        assert late[0].output_ids == outs[0].output_ids

    def test_shared_pages_memory_accounting(self, params):
        """Group members don't pay for the shared prompt pages."""
        page = 8
        prompt = list(range(1, 18))   # plen_eff 16 = 2 full pages, no tail
        eng = GenerationEngine(
            CFG, params, max_slots=4, max_seqlen=64, page_size=page,
        )
        for i in range(4):
            eng.submit(GenRequest(
                rid=f"g{i}", input_ids=prompt, max_new_tokens=8, greedy=True,
            ))
        eng.step(decode_steps=1)
        # per slot: ceil((16+8)/8)=3 pages total; the 2 prompt pages are
        # shared, so members own only 1 — pool usage = 3 + 3*1 = 6 pages
        used = eng.n_pages - eng.pool.n_free
        assert used == 6
        eng.run_until_done(decode_steps=4)
        # slots released; only the registry's hold on the 2 prompt pages stays
        assert eng.n_pages - eng.pool.n_free == 2

    def test_weight_update_invalidates_prefix(self, params):
        prompt = list(range(1, 18))
        eng = GenerationEngine(
            CFG, params, max_slots=2, max_seqlen=64, page_size=8,
        )
        eng.submit(GenRequest(rid="a", input_ids=prompt, max_new_tokens=2,
                              greedy=True))
        eng.run_until_done(decode_steps=2)
        assert len(eng.prefix) == 2   # 2 full prompt pages resident
        eng.update_params(params, version=1)
        assert len(eng.prefix) == 0   # old-weight KV never seeds new rollouts
        eng.submit(GenRequest(rid="b", input_ids=prompt, max_new_tokens=2,
                              greedy=True))
        eng.run_until_done(decode_steps=2)
        assert eng.stats["prefix_hits"] == 0


class TestCapacity:
    def test_small_pool_defers_admission(self, params):
        """A pool smaller than slots x capacity admits what fits and keeps
        the rest pending instead of crashing — HBM is bounded by n_pages."""
        eng = GenerationEngine(
            CFG, params, max_slots=4, max_seqlen=64, page_size=8,
            n_pages=6, enable_prefix_cache=False,
        )
        # each request needs ceil((7+16)/8) = 3 pages -> only 2 fit
        for i in range(4):
            eng.submit(GenRequest(
                rid=f"r{i}", input_ids=list(range(1, 9)), max_new_tokens=16,
                greedy=True,
            ))
        eng.step(decode_steps=1)
        assert eng.n_running() == 2 and len(eng._pending) == 2
        outs = eng.run_until_done(decode_steps=8)   # turnover drains the rest
        assert len(outs) == 4
        assert eng.pool.n_free == 6

    def test_compile_count_stable_across_mixed_workload(self, params, rng):
        """Compile count is bounded by admit-row buckets + decode chunk —
        NOT by prompt lengths (chunked prefill kills the length dimension)."""
        eng = GenerationEngine(
            CFG, params, max_slots=4, max_seqlen=256, page_size=16,
        )
        for i, plen in enumerate([3, 9, 17, 33, 65, 100, 130, 7, 55, 23]):
            eng.submit(GenRequest(
                rid=f"m{i}",
                input_ids=[int(x) for x in rng.integers(1, 128, plen)],
                max_new_tokens=4, greedy=True,
            ))
        eng.run_until_done(decode_steps=4)
        # warm every admit-row bucket with varying arrival counts
        for n_batch in (1, 2, 3, 4):
            for i in range(n_batch):
                eng.submit(GenRequest(
                    rid=f"w{n_batch}-{i}",
                    input_ids=[int(x) for x in rng.integers(1, 128, 40)],
                    max_new_tokens=4, greedy=True,
                ))
            eng.run_until_done(decode_steps=4)
        warmed = eng.n_compiles()
        # hard bound: up to two extends per bucket (cold-prompt skip-pool
        # variant + pool variant) + one commit per bucket + one decode chunk
        assert warmed <= 3 * len(eng.admit_buckets) + 1
        # fresh prompt lengths never trigger new specializations
        for i, plen in enumerate([11, 29, 77, 128, 201]):
            eng.submit(GenRequest(
                rid=f"n{i}",
                input_ids=[int(x) for x in rng.integers(1, 128, plen)],
                max_new_tokens=4, greedy=True,
            ))
        eng.run_until_done(decode_steps=4)
        assert eng.n_compiles() == warmed


class TestThreadSafety:
    @pytest.mark.slow
    def test_pause_and_submit_racing_step(self, params, rng):
        """A server thread pausing/submitting while the step thread runs:
        no slot leaks, no double frees, every request resolves exactly once."""
        eng = GenerationEngine(
            CFG, params, max_slots=4, max_seqlen=64, page_size=8, seed=0,
        )
        results = {}
        errors = []
        stop = threading.Event()

        def stepper():
            try:
                while not stop.is_set():
                    for o in eng.step(decode_steps=2):
                        results[o.rid] = results.get(o.rid, 0) + 1
            except Exception as e:  # pragma: no cover
                errors.append(e)

        def chaos():
            try:
                for i in range(30):
                    eng.submit(GenRequest(
                        rid=f"c{i}",
                        input_ids=[int(x) for x in rng.integers(1, 128, 5)],
                        max_new_tokens=6, greedy=True,
                    ))
                    if i % 5 == 4:
                        for o in eng.pause():
                            results[o.rid] = results.get(o.rid, 0) + 1
                        eng.resume()
                    time.sleep(0.01)
            except Exception as e:  # pragma: no cover
                errors.append(e)

        t1 = threading.Thread(target=stepper)
        t2 = threading.Thread(target=chaos)
        t1.start(); t2.start()
        t2.join(timeout=120)
        # drain the rest
        deadline = time.time() + 120
        while (eng._pending or eng.n_running()) and time.time() < deadline:
            time.sleep(0.05)
        stop.set()
        t1.join(timeout=30)
        assert not errors, errors
        assert sum(results.values()) == 30           # each exactly once
        assert all(v == 1 for v in results.values())
        assert eng.n_running() == 0
        # every page accounted for (registry may hold prompt pages)
        eng.prefix.clear()
        assert eng.pool.n_free == eng.n_pages


class TestPallasPagedDecode:
    """Pallas paged-decode kernel parity vs the XLA gather path (interpret
    mode on CPU; the same kernel runs compiled on TPU). Both paths take the
    current token's K/V as SEPARATE operands (the pool is read-only during
    the layer scan) and fold its self-attention into the online softmax."""

    # (pages_per_step, slots_per_step): the default derives sb=4/kp=4 at
    # this shape -> a (1, 1) grid that never runs the double-buffer
    # prefetch pipeline; the (2, 2) and (1, 2) cases force multi-step
    # linearized grids (buffer-parity alternation, next-step zero guard,
    # cross-bb prefetch) — ADVICE r4: the pipeline must not be dead in CI.
    # interpret mode is slow on CPU: tier-1 keeps the (1,1)-grid default
    # and the (2,2) multi-step pipeline; the (1,2) cross-bb prefetch case
    # rides the slow sweep (runs unmarked + compiled on chip)
    # ``ends``: a table of 16 pages under the plan's own pages a step
    # (``block_plan``: 4 in the full-attention program, 8 in a window
    # program): the longest row of each block of 2 ends in the FIRST half of
    # what was a step of 8 pages (the full program's body stops there) or in
    # the SECOND; rows 2 and 3 share their leading pages, so the same table
    # also goes through the prefix program (3 shared pages or 5, of its step
    # of 8) and the own-pages program with ``carry``
    @pytest.mark.parametrize(
        "kp_sb,ends",
        [((8, 8), None), ((2, 2), None),
         pytest.param((1, 2), None, marks=pytest.mark.slow),
         ((None, 2), "first"), ((None, 2), "second")],
    )
    @pytest.mark.parametrize(
        "soft_cap,window", [(None, None), (5.0, None), (None, 6)]
    )
    def test_parity_vs_xla_and_dense(self, soft_cap, window, kp_sb, ends):
        from areal_tpu.ops import paged_attention as xla_paged
        from areal_tpu.ops.pallas import paged_attention as pl_paged

        rng = np.random.default_rng(0)
        B, Hq, Hkv, D, page, M, P, L = 4, 4, 2, 16, 8, 4, 20, 3
        if ends is not None:
            M, P = 16, 70
        layer = 1
        q = rng.normal(size=(B, Hq, D)).astype(np.float32)
        k_self = rng.normal(size=(B, Hkv, D)).astype(np.float32)
        v_self = rng.normal(size=(B, Hkv, D)).astype(np.float32)
        pool = rng.normal(size=(L, P, 2, Hkv, page, D)).astype(np.float32)
        # dense views in [P, page, Hkv, D] order for the numpy reference
        k_pages = np.swapaxes(pool[:, :, 0], 2, 3)
        v_pages = np.swapaxes(pool[:, :, 1], 2, 3)
        table = rng.permutation(P)[: B * M].reshape(B, M).astype(np.int32)
        lens = np.asarray([1, 9, 32, 0], np.int32)  # partial/full/empty pool
        if ends is not None:
            # 8 pages are 64 positions, the full program's step 32
            lens = np.asarray(
                {"first": [20, 9, 84, 70], "second": [40, 9, 114, 100]}[ends],
                np.int32)
            n_shared = {"first": 3, "second": 5}[ends]
            table[3, :n_shared] = table[2, :n_shared]
            assert pl_paged.block_plan(
                B, Hkv, D, page, M, pool.dtype, None, 2,
                windowed=window is not None,
            ) == (2, 4 if window is None else 8)

        got = pl_paged.decode(
            q, k_self, v_self, pool, jnp.int32(layer), table,
            lens, soft_cap=soft_cap, sliding_window=window,
            pages_per_step=kp_sb[0], slots_per_step=kp_sb[1],
        )
        want = xla_paged.paged_decode_attention(
            q, k_self, v_self, pool, jnp.int32(layer), table,
            lens, soft_cap=soft_cap, sliding_window=window, use_pallas=False,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5
        )
        if ends is not None and window is None:
            # the two programs of a step that reads shared pages once
            plan, own_table, own_lens = xla_paged.shared_prefix_step(
                jnp.asarray(table), jnp.asarray(lens), lens > 0, page)
            assert list(np.asarray(plan.pages)) == [0, 0, n_shared, n_shared]
            order = jnp.argsort(own_lens)
            inverse = jnp.argsort(order)
            both = xla_paged.paged_decode_attention(
                q[order], k_self[order], v_self[order], pool,
                jnp.int32(layer), own_table[order], own_lens[order],
                soft_cap=soft_cap, use_pallas=True,
                shared=xla_paged.prefix_pass(
                    plan, jnp.asarray(table), page, order, inverse),
            )[inverse]
            np.testing.assert_allclose(
                np.asarray(both), np.asarray(want), atol=2e-5)

        # dense reference: gather pool positions [0, len) + self at the end
        scale = D ** -0.5
        n_rep = Hq // Hkv
        for b in range(B):
            flat_k = np.concatenate(
                [k_pages[layer, table[b]].reshape(-1, Hkv, D)[: lens[b]],
                 k_self[b][None]]
            )
            flat_v = np.concatenate(
                [v_pages[layer, table[b]].reshape(-1, Hkv, D)[: lens[b]],
                 v_self[b][None]]
            )
            S = flat_k.shape[0]
            for h in range(Hq):
                g = h // n_rep
                s = flat_k[:, g] @ q[b, h] * scale
                if soft_cap is not None:
                    s = soft_cap * np.tanh(s / soft_cap)
                if window is not None:
                    pos = np.arange(S)
                    s = np.where(pos > lens[b] - window, s, -1e30)
                p = np.exp(s - s.max())
                p /= p.sum()
                ref = p @ flat_v[:, g]
                np.testing.assert_allclose(
                    np.asarray(got)[b, h], ref, atol=2e-5, err_msg=f"b{b}h{h}"
                )

    def test_rows_longer_than_a_step_agree_at_both_granules(self):
        """The long-row cells' guard: a call whose rows share nothing and
        all reach past 8 pages gives the same result (the order in which
        partial sums meet in the float32 state is all that differs) at 4
        pages a grid step, the full-attention program's plan, and at 8, and
        computes over the same positions but for a block's last 8 pages, at
        most 4 pages a block less (none where the block's longest row ends
        in their second half)."""
        from areal_tpu.ops import paged_attention as xla_paged
        from areal_tpu.ops.pallas import paged_attention as pl_paged

        rng = np.random.default_rng(4)
        B, Hq, Hkv, D, page, M, L, sb = 8, 4, 2, 16, 8, 32, 2, 2
        step, half = 8 * page, 4 * page
        lens = np.asarray([70, 120, 150, 100, 255, 130, 200, 180], np.int32)
        assert lens.min() > step
        q = rng.normal(size=(B, Hq, D)).astype(np.float32)
        k_self = rng.normal(size=(B, Hkv, D)).astype(np.float32)
        v_self = rng.normal(size=(B, Hkv, D)).astype(np.float32)
        pool = rng.normal(size=(L, B * M, 2, Hkv, page, D)).astype(np.float32)
        table = rng.permutation(B * M).reshape(B, M).astype(np.int32)
        seats, blocks = pl_paged.prefix_plan(B)
        assert not pl_paged.shared_prefix(
            table, lens, lens > 0, page, seats, blocks).pages.any()
        assert pl_paged.block_plan(
            B, Hkv, D, page, M, pool.dtype, None, sb) == (sb, 4)
        got = {
            kp: np.asarray(pl_paged.decode(
                q, k_self, v_self, pool, jnp.int32(1), table, lens,
                slots_per_step=sb, pages_per_step=kp))
            for kp in (4, 8)
        }
        want = np.asarray(xla_paged.paged_decode_attention(
            q, k_self, v_self, pool, jnp.int32(1), table, lens,
            use_pallas=False))
        np.testing.assert_allclose(got[4], got[8], atol=2e-5)
        np.testing.assert_allclose(got[4], want, atol=2e-5)
        # blocks (70, 120) and (255, 130) end in a second half, (150, 100)
        # and (200, 180) in a first
        was = pl_paged.kernel_positions(lens, sb, step)
        assert pl_paged.kernel_positions(lens, sb, half) == was - 2 * sb * half
        second = np.asarray([70, 120, 250, 100, 255, 130, 200, 230])
        assert pl_paged.kernel_positions(second, sb, half) == (
            pl_paged.kernel_positions(second, sb, step))


def _heavy_tailed_lens(rng, n, cap):
    """Resident lengths in random slot order: lognormal, a few empty, one
    at the cap (the decode kernel's worst block mate)."""
    lens = np.minimum(rng.lognormal(np.log(cap / 4), 0.9, size=n), cap)
    lens = lens.astype(np.int32)
    lens[rng.choice(n, 3, replace=False)] = 0
    lens[rng.integers(n)] = cap
    return lens


class TestLengthOrderedDecode:
    """``decode_step_paged`` runs its layer scan on the rows sorted by
    resident length (the Pallas kernel's blocks then hold rows of like
    length) and hands everything back in slot order: to the bit what the
    same step gives with the rows left in slot order."""

    # 12 rows: the kernel's plan is blocks of 4 (8 does not divide 12),
    # three of them, at half the interpreter's trace time of 8-row blocks
    B, PAGE = 12, 8

    def _step(self, cfg, params, cache, lens, active, width, **kw):
        rng = np.random.default_rng(7)
        table = rng.permutation(self.B * width).reshape(
            self.B, width).astype(np.int32)
        tokens = rng.integers(1, cfg.vocab_size, size=self.B).astype(np.int32)
        # one program, as the engine's chunk runs it (and half the time
        # of the op-by-op dispatch around the scan)
        return jax.jit(
            lambda p, c, *a: tfm.decode_step_paged(
                p, cfg, c, *a, use_pallas=True, **kw)
        )(params, cache, tokens, table, lens, active)

    @pytest.mark.parametrize(
        "variant,width",
        [("plain", 32), ("plain", 64), ("int8", 64), ("window", 32),
         ("window", 64), ("hidden", 64)],
    )
    def test_bit_equal_to_slot_order(self, params, monkeypatch, variant,
                                     width):
        import dataclasses

        from areal_tpu.ops.pallas import paged_attention as pl_paged

        cfg = CFG
        if variant == "window":
            cfg = dataclasses.replace(CFG, sliding_window=40)
        kw = {"return_hidden": True} if variant == "hidden" else {}
        rng = np.random.default_rng(width)
        cache = tfm.PagedKVCache.empty(
            cfg, self.B * width, self.PAGE,
            kv_dtype="int8" if variant == "int8" else None,
        )
        if variant == "int8":
            cache = tfm.PagedKVCache(
                pages=jnp.asarray(rng.integers(
                    -127, 128, size=cache.pages.shape), jnp.int8),
                scales=jnp.asarray(rng.uniform(
                    0.001, 0.02, size=cache.scales.shape), jnp.float32),
            )
        else:
            cache = tfm.PagedKVCache(pages=jnp.asarray(
                rng.normal(size=cache.pages.shape), jnp.float32))
        lens = _heavy_tailed_lens(rng, self.B, width * self.PAGE - 1)
        active = rng.random(self.B) < 0.8
        active[np.flatnonzero(lens == 0)[0]] = False    # empty AND inactive
        active[np.argmax(lens)] = True

        # the sort does something here: the kernel's plan computes over
        # fewer positions on the sorted rows
        sb, kp = pl_paged.block_plan(
            self.B, cfg.n_kv_heads, cfg.head_dim, self.PAGE, width,
            cache.pages.dtype,
        )
        span = kp * self.PAGE
        assert pl_paged.kernel_positions(np.sort(lens), sb, span) < (
            pl_paged.kernel_positions(lens, sb, span))

        got = self._step(cfg, params, cache, lens, active, width, **kw)
        monkeypatch.setattr(
            tfm, "_length_order",
            lambda lens: (jnp.arange(lens.shape[0]),) * 2,
        )
        want = self._step(cfg, params, cache, lens, active, width, **kw)
        np.testing.assert_array_equal(
            np.asarray(got[0]), np.asarray(want[0]))
        for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(
            np.asarray(got[2]), np.where(active, lens + 1, lens))
        np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))

    def test_equal_lengths_keep_slot_order(self):
        order, inverse = tfm._length_order(jnp.full((8,), 5, jnp.int32))
        np.testing.assert_array_equal(np.asarray(order), np.arange(8))
        np.testing.assert_array_equal(np.asarray(inverse), np.arange(8))
        order, inverse = tfm._length_order(
            jnp.asarray([7, 0, 3, 0, 7], jnp.int32))
        np.testing.assert_array_equal(np.asarray(order), [1, 3, 2, 0, 4])
        np.testing.assert_array_equal(
            np.asarray(order)[np.asarray(inverse)], np.arange(5))


class TestPagedDecodeStepGate:
    """The kernel against the XLA gather path at the shapes its step gate
    decides on: a grid step whose slot block reaches no page starts no copy,
    zeroes nothing, waits for nothing and runs no body, and the prefetch
    chain runs from each REACHED step to the next one in grid order, over
    whatever unreached steps and empty blocks lie between (``chained``: how
    many times it crosses into another block). Rows in ANY order: each case
    is also run with its rows sorted by length, to the bit the same result
    a row. Blocks of 2 slots x 2 pages of 8 (a span of 16 positions) unless
    the case says otherwise: the interpreter is slow."""

    PAGE = 8

    def _inputs(self, rng, B, width, variant, kw):
        """``(q, k_self, v_self, pool, table)`` of ``B`` rows with their own
        pages, 2 query heads on 1 kv head of 16; the program's arguments
        (an int8 pool's scales, a latent pool's value width) go into
        ``kw``."""
        Hq, Hkv, D, L, page = 2, 1, 16, 2, self.PAGE
        P = B * width
        latent = variant == "latent"
        q = rng.normal(size=(B, Hq, D)).astype(np.float32)
        k_self = rng.normal(size=(B, Hkv, D)).astype(np.float32)
        v_self = rng.normal(size=(B, Hkv, D)).astype(np.float32)
        pool = rng.normal(
            size=(L, P, 1 if latent else 2, Hkv, page, D)).astype(np.float32)
        table = rng.permutation(P).reshape(B, width).astype(np.int32)
        if variant == "int8":
            pool = rng.integers(-127, 128, size=pool.shape).astype(np.int8)
            kw["scales"] = jnp.asarray(rng.uniform(
                0.001, 0.02, size=pool.shape[:-1]), jnp.float32)
        if latent:
            v_self, kw["value_width"] = None, 8
        return q, k_self, v_self, pool, table

    @pytest.mark.parametrize(
        "lens,width,kp,sb,variant,chained",
        [
            # empty block | block with work | empty block: the prologue
            # starts a step of block 1, whose last step starts nothing
            pytest.param([0, 0, 5, 20, 0, 0], 4, 2, 2, "plain", 0,
                         id="empty-work-empty"),
            # one long row among empty ones: its block is active to the
            # end, every page of the other row zeroed
            pytest.param([0, 44, 0, 0], 6, 2, 2, "plain", 0, id="one-long"),
            # exactly at the span, one over, one under; a table whose last
            # page block is partial (5 pages in blocks of 2)
            pytest.param([16, 16, 17, 1, 15, 0, 32, 33], 5, 2, 2, "plain", 3,
                         id="span-edges"),
            # the cells' table: 40 pages in 5 blocks of 8, rows sorted as
            # ``decode_step_paged`` hands them over
            pytest.param([0, 0, 3, 60, 64, 65, 130, 319], 40, 8, 2, "plain",
                         2, id="table40-kp8"),
            # one slot a step (the OLMoE cell's plan)
            pytest.param([0, 20, 0, 33], 5, 2, 1, "plain", 1, id="sb1"),
            pytest.param([0, 0, 5, 20, 0, 36], 5, 2, 2, "int8", 1, id="int8"),
            pytest.param([0, 0, 5, 20, 0, 36], 5, 2, 2, "window", 1,
                         id="window"),
            # the chain over reached steps (PR 36). Rows in random order:
            # every block reaches its own number of steps
            pytest.param([37, 2, 0, 9, 48, 47, 1, 30, 16, 0, 5, 41], 6, 2, 2,
                         "plain", 5, id="random-order"),
            # empty blocks BETWEEN full ones: the chain jumps two blocks
            pytest.param([20, 33, 0, 0, 0, 0, 40, 7, 0, 0, 12, 1], 6, 2, 2,
                         "plain", 2, id="holes"),
            # nothing resident: the prologue finds no step and starts none
            pytest.param([0, 0, 0, 0], 4, 2, 2, "plain", 0, id="all-empty"),
            # ONE reached step in the whole call, started at grid step 0
            # from two blocks away
            pytest.param([0, 0, 0, 0, 0, 3, 0, 0], 4, 2, 2, "plain", 0,
                         id="one-step"),
            # a block that reaches every step (three: an ODD number) then
            # one that reaches one, then two: by the parity of the grid step
            # the second block's step would share the first's last buffer
            pytest.param([48, 40, 5, 2, 30, 16], 6, 2, 2, "plain", 2,
                         id="buffer-ordinal"),
            # a window program whose blocks START past step 0 (first
            # visible positions 35.. and 65..: steps 2 and 4), an empty
            # block between them and a short one after
            pytest.param([40, 44, 0, 0, 70, 75, 3, 9], 10, 2, 2, "window", 2,
                         id="window-late-start"),
            # the latent program: one stream, the value the key's head
            pytest.param([20, 33, 0, 0, 48, 7, 0, 0, 12, 1], 6, 2, 2,
                         "latent", 2, id="latent"),
            pytest.param([20, 33, 0, 0, 0, 0, 40, 7, 12, 1], 6, 2, 2, "int8",
                         2, id="int8-holes"),
            # one slot a step over a table of 32 pages in 4 blocks of 8
            # (the OLMoE cell's plan): a drain a slot before the chain
            pytest.param([0, 200, 70, 256, 0, 130], 32, 8, 1, "plain", 3,
                         id="sb1-table32-kp8"),
        ],
    )
    def test_parity_vs_xla(self, lens, width, kp, sb, variant, chained):
        from areal_tpu.ops import paged_attention as xla_paged
        from areal_tpu.ops.pallas import paged_attention as pl_paged

        rng = np.random.default_rng(len(lens) + width)
        lens = np.asarray(lens, np.int32)
        B, page = len(lens), self.PAGE
        latent = variant == "latent"
        assert pl_paged.block_plan(
            B, 1, 16, page, width, jnp.float32, kp, sb, 1 if latent else 2
        ) == (sb, kp)
        kw, first = {}, None
        if variant == "window":
            kw["sliding_window"] = 6
            first = pl_paged.first_visible(lens, 6)
        plan = (sb, kp * page, -(-width // kp), first)
        active, total = pl_paged.kernel_steps(lens, *plan)
        assert active < total          # the gate has steps to skip
        assert pl_paged.kernel_steps_chained(lens, *plan) == chained
        q, k_self, v_self, pool, table = self._inputs(
            rng, B, width, variant, kw)

        def kernel(rows):
            return np.asarray(pl_paged.decode(
                q[rows], k_self[rows], None if latent else v_self[rows],
                pool, jnp.int32(1), table[rows], lens[rows],
                pages_per_step=kp, slots_per_step=sb, **kw,
            ))

        got = kernel(np.arange(B))
        want = xla_paged.paged_decode_attention(
            q, k_self, v_self, pool, jnp.int32(1), table, lens,
            use_pallas=False, **kw,
        )
        np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)
        # sorted as ``decode_step_paged`` hands the rows over: another
        # chain, every row's result the same to the bit
        order = np.argsort(lens, kind="stable")
        if not np.array_equal(order, np.arange(B)):
            np.testing.assert_array_equal(kernel(order), got[order])

    @pytest.mark.parametrize("only", ["first", "last"])
    @pytest.mark.parametrize("blocks", [1, 16, 64])
    @pytest.mark.parametrize("variant", ["plain", "window", "latent", "int8"])
    def test_block_rows_addressed_by_block(self, variant, blocks, only):
        """q, the current token's K/V and the output are whole in VMEM for
        the call and a block reads and writes ITS rows of them (``bb * sb``):
        ONE block of the call reaches a step, the first or the last, every
        other row's result is its own current token's value, and each row's
        q and K/V are its own, so a block that took its neighbour's rows
        shows in every program of the kernel."""
        from areal_tpu.ops import paged_attention as xla_paged
        from areal_tpu.ops.pallas import paged_attention as pl_paged

        sb = 1 if blocks == 64 else 2
        B, width, kp = blocks * sb, 4, 2
        rng = np.random.default_rng(blocks)
        lens = np.zeros(B, np.int32)
        at = 0 if only == "first" else B - sb
        lens[at:at + sb] = [20, 5][:sb]
        kw = {"sliding_window": 6} if variant == "window" else {}
        assert pl_paged.kernel_steps(
            lens, sb, kp * self.PAGE, width // kp) == (2, blocks * 2)
        q, k_self, v_self, pool, table = self._inputs(
            rng, B, width, variant, kw)
        got = np.asarray(pl_paged.decode(
            q, k_self, v_self, pool, jnp.int32(1), table, lens,
            pages_per_step=kp, slots_per_step=sb, **kw))
        want = np.asarray(xla_paged.paged_decode_attention(
            q, k_self, v_self, pool, jnp.int32(1), table, lens,
            use_pallas=False, **kw))
        np.testing.assert_allclose(got, want, atol=2e-5)
        # a row with nothing resident attends to itself alone: its own value
        # (one kv head: every query head's result is that head's value)
        own = k_self[..., :8] if v_self is None else v_self
        empty = lens == 0
        np.testing.assert_allclose(
            got[empty], np.broadcast_to(own, got.shape)[empty], atol=1e-6)


class TestKernelPositions:
    """The block plan and the count of positions the kernel computes over
    (``kernel_positions`` on the engine's chunk span)."""

    @pytest.mark.parametrize(
        "batch,n_kv,dtype,want",
        [
            # R1-Distill-Qwen-1.5B, 12q/2kv x 128: the scratch is exactly
            # 16 MiB at 8 slots, and exactly 16 MiB is not over it
            # (of 8 pages a step: the slots are held to what 8 pages give,
            # the full-attention program's step is 4)
            (128, 2, "bfloat16", (8, 4)),
            # 7B widths, 28q/4kv x 128: 32 MiB at 8 slots, halved
            (64, 4, "bfloat16", (4, 4)),
            # an int8 pool: half the page bytes, but at 4 kv heads its
            # scale stripes take 8 slots over 16 MiB again
            (128, 2, "int8", (8, 8)),
            (64, 4, "int8", (4, 8)),
            # a batch that 8 does not divide; a table narrower than 8 pages
            (4, 2, "bfloat16", (4, 4)),
        ],
    )
    def test_block_plan(self, batch, n_kv, dtype, want):
        from areal_tpu.ops.pallas import paged_attention as pl_paged

        assert pl_paged.block_plan(batch, n_kv, 128, 128, 32, dtype) == want
        assert pl_paged.block_plan(batch, n_kv, 128, 128, 4, dtype)[1] == 4
        assert pl_paged.block_plan(batch, n_kv, 128, 128, 2, dtype)[1] == 2
        # a window program keeps 8 pages a step and an explicit
        # ``pages_per_step`` is taken as given, with the same slots; so does
        # a latent program (one stream)
        for kw in (dict(windowed=True), dict(pages_per_step=8)):
            assert pl_paged.block_plan(
                batch, n_kv, 128, 128, 32, dtype, **kw) == (want[0], 8)
        assert pl_paged.block_plan(
            batch, 1, 640, 128, 32, "bfloat16", streams=1)[1] == 8

    # (slots, positions of a grid step: 8 pages of 128, the full-attention
    # program's 4, and 4 pages of 64)
    @pytest.mark.parametrize(
        "sb,span",
        [(8, 1024), (4, 1024), (2, 64), (8, 512), (4, 512), (1, 512),
         (2, 256)],
    )
    def test_matches_brute_force(self, sb, span):
        from areal_tpu.ops.pallas import paged_attention as pl_paged

        rng = np.random.default_rng(sb)
        lens = _heavy_tailed_lens(rng, 64, 5000)
        for rows in (lens, np.sort(lens)):
            brute = 0
            for b0 in range(0, len(rows), sb):
                for j in range(-(-5000 // span)):
                    # the kernel's gate on the body of grid step (b0, j)
                    if j * span < rows[b0:b0 + sb].max():
                        brute += sb * span
            assert pl_paged.kernel_positions(rows, sb, span) == brute
            # half the pages a step: never more positions, and at most one
            # step of the smaller kind a block fewer
            if span == 512:
                was = pl_paged.kernel_positions(rows, sb, 2 * span)
                assert 0 <= was - brute <= len(rows) // sb * sb * span

    @pytest.mark.parametrize(
        "sb,span,nblk", [(8, 1024, 5), (4, 1024, 5), (1, 1024, 4), (2, 64, 79)]
    )
    def test_steps_match_brute_force(self, sb, span, nblk):
        from areal_tpu.ops.pallas import paged_attention as pl_paged

        rng = np.random.default_rng(sb)
        lens = _heavy_tailed_lens(rng, 64, min(5000, nblk * span))
        for rows in (lens, np.sort(lens)):
            # the kernel's gate on grid step (b0, j): issue, wait and body
            brute = sum(
                j * span < rows[b0:b0 + sb].max()
                for b0 in range(0, len(rows), sb) for j in range(nblk)
            )
            assert pl_paged.kernel_steps(rows, sb, span, nblk) == (
                brute, 64 // sb * nblk)
            assert pl_paged.kernel_positions(rows, sb, span) == (
                sb * span * brute)

    @pytest.mark.parametrize(
        "sb,span,nblk,window",
        [(8, 1024, 5, None), (4, 1024, 5, None), (1, 1024, 4, None),
         (2, 64, 79, None), (4, 1024, 16, 4096), (2, 64, 79, 200),
         (1, 64, 20, 130)],
    )
    def test_chain_matches_brute_force(self, sb, span, nblk, window):
        """The prefetch chain over reached steps: the host's four vectors
        (``reached_chain``), the count on the span
        (``kernel_steps_chained``) and the kernel's own walk on the scalar
        core (``_first_reached`` / ``_next_reached``, here over arrays in
        place of SMEM refs) against a Python loop over the grid, rows
        sorted, in random order and with blocks emptied in the middle."""
        from areal_tpu.ops.pallas import paged_attention as pl_paged

        rng = np.random.default_rng(sb + nblk)
        lens = _heavy_tailed_lens(rng, 64, min(5000, nblk * span))
        holes = lens.copy()
        holes[rng.integers(0, 64 // sb, 5)[:, None] * sb + np.arange(sb)] = 0
        plan = dict(sb=sb, S=span, nblk=nblk)
        for rows in (lens, np.sort(lens), holes, np.zeros(64, np.int32)):
            first = None
            if window is not None:
                first = pl_paged.first_visible(rows, window)
            # the grid in order, each step under the kernel's gate
            chain = []
            for b in range(64 // sb):
                blk = slice(b * sb, (b + 1) * sb)
                for j in range(nblk):
                    reached = j * span < rows[blk].max()
                    if window is not None:
                        reached &= (j + 1) * span > first[blk].min()
                    if reached:
                        chain.append((b, j))
            assert pl_paged.kernel_steps(rows, sb, span, nblk, first) == (
                len(chain), 64 // sb * nblk)
            # a step of another block than the one before it in the chain
            assert pl_paged.kernel_steps_chained(
                rows, sb, span, nblk, first
            ) == sum(a[0] != b[0] for a, b in zip(chain, chain[1:]))
            lo, hi, nxt, before = pl_paged.reached_chain(
                rows, sb, span, nblk, first)
            nb = 64 // sb
            for n, (b, j) in enumerate(chain):
                assert lo[b] <= j < hi[b]
                assert before[b] + j - lo[b] == n          # the ordinal
                if j + 1 < hi[b]:
                    after = (b, j + 1)
                elif nxt[b] < nb:
                    after = (nxt[b], lo[nxt[b]])
                else:
                    after = None
                assert after == (chain[n + 1] if n + 1 < len(chain) else None)
            # the kernel's walk, over the same scalars
            refs = (jnp.asarray(rows, jnp.int32),
                    None if first is None else jnp.asarray(first, jnp.int32))
            b0, j0, found = pl_paged._first_reached(*refs, 0, nb=nb, **plan)
            assert bool(found) == bool(chain)
            if chain:
                assert (int(b0), int(j0)) == chain[0]
                step = jax.jit(jax.vmap(lambda b, j: pl_paged._next_reached(
                    *refs, b, j, nb=nb, **plan)))
                bs, js, ok = (np.asarray(x) for x in step(
                    *jnp.asarray(chain, jnp.int32).T))
                assert ok[:-1].all() and not ok[-1]
                assert list(zip(bs[:-1], js[:-1])) == chain[1:]

    @pytest.mark.parametrize(
        "length,want", [(0, 0), (1, 4), (1024, 4), (1025, 8), (5120, 20)]
    )
    def test_steps_of_equal_lengths(self, length, want):
        from areal_tpu.ops.pallas import paged_attention as pl_paged

        lens = np.full(32, length)
        assert pl_paged.kernel_steps(lens, 8, 1024, 5) == (want, 20)

    def test_equal_lengths_round_up_to_the_span(self):
        from areal_tpu.ops.pallas import paged_attention as pl_paged

        lens = np.full(32, 1500)
        assert pl_paged.kernel_positions(lens, 8, 1024) == 32 * 2048
        assert pl_paged.kernel_positions(np.zeros(32, int), 8, 1024) == 0


class TestRadixPartialPrefix:
    def test_sibling_prompts_share_preamble_pages(self, params):
        """Two prompts with a common 2-page system preamble but different
        questions: the second admission borrows the preamble pages (partial
        radix hit) and still produces exactly the generations a cold engine
        would — the KV served from shared pages is the same."""
        page = 8
        rng = np.random.default_rng(3)
        pre = [int(x) for x in rng.integers(1, 128, 16)]   # 2 full pages
        qa = pre + [int(x) for x in rng.integers(1, 128, 5)]
        qb = pre + [int(x) for x in rng.integers(1, 128, 5)]

        eng = GenerationEngine(
            CFG, params, max_slots=2, max_seqlen=64, page_size=page, seed=0,
        )
        eng.submit(GenRequest(rid="a", input_ids=qa, max_new_tokens=4, greedy=True))
        out_a = eng.run_until_done(decode_steps=4)
        eng.submit(GenRequest(rid="b", input_ids=qb, max_new_tokens=4, greedy=True))
        out_b = eng.run_until_done(decode_steps=4)
        # b's admission partially hit a's preamble (2 pages = 16 tokens)
        assert eng.stats["prefix_hits"] == 1
        assert eng.stats["prefix_hit_tokens"] == 16
        # prefilled tokens: a's 20 (plen_eff) + b's 4 uncovered
        assert eng.stats["prefill_tokens"] == 20 + 4

        cold = GenerationEngine(
            CFG, params, max_slots=2, max_seqlen=64, page_size=page, seed=0,
        )
        cold.submit(GenRequest(rid="b2", input_ids=qb, max_new_tokens=4, greedy=True))
        ref_b = cold.run_until_done(decode_steps=4)
        assert out_b[0].output_ids == ref_b[0].output_ids
        assert out_a[0].output_ids != out_b[0].output_ids or qa == qb

    def test_partial_hit_registers_divergent_tail(self, params):
        """After a partial hit, the divergent tail joins the radix tree so a
        THIRD prompt identical to the second fully hits."""
        page = 8
        rng = np.random.default_rng(4)
        pre = [int(x) for x in rng.integers(1, 128, 16)]
        qb = pre + [int(x) for x in rng.integers(1, 128, 9)]  # 3 full pages

        eng = GenerationEngine(
            CFG, params, max_slots=2, max_seqlen=64, page_size=page, seed=0,
        )
        eng.submit(GenRequest(rid="a", input_ids=pre + [1, 2], max_new_tokens=2, greedy=True))
        eng.run_until_done(decode_steps=2)
        eng.submit(GenRequest(rid="b", input_ids=qb, max_new_tokens=2, greedy=True))
        eng.run_until_done(decode_steps=2)
        hits_before = eng.stats["prefix_hit_tokens"]
        eng.submit(GenRequest(rid="b-twin", input_ids=qb, max_new_tokens=2, greedy=True))
        outs = eng.run_until_done(decode_steps=2)
        # the twin borrows ALL 3 full pages (16 preamble + 8 tail)
        assert eng.stats["prefix_hit_tokens"] - hits_before == 24
        assert outs[0].finish_reason in ("stop", "length")


class TestProtocolLengthGeneration:
    """The published benchmark protocol is 32k context with ~31k generated
    tokens (reference benchmark/verl_v0_3_0_post1_76084d3/README.md:39-41).
    These tests run the paged engine at that table geometry on CPU: a
    ~31.5k-token prompt chunk-prefills through the pool and decode crosses
    page boundaries near the 32k edge."""

    def test_32k_table_deep_prompt_decode(self, params):
        S = 32768
        eng = GenerationEngine(
            CFG, params, max_slots=2, max_seqlen=S, max_new_tokens_cap=31744,
            page_size=128, n_pages=2 * (S // 128),
        )
        assert eng.M == S // 128  # 256-wide page table
        rng = np.random.default_rng(0)
        plen = 31500
        prompt = [int(x) for x in rng.integers(1, 128, size=plen)]
        eng.submit(GenRequest(
            rid="deep", input_ids=prompt, max_new_tokens=1200, greedy=True,
        ))
        outs = eng.run_until_done(decode_steps=64, timeout=1200.0)
        assert len(outs) == 1
        o = outs[0]
        # capacity: plen-1 prefilled + 1200 generated > 32640 = capped by
        # the slot budget? no: 31499 + 1200 = 32699 <= 32768 fits
        assert len(o.output_ids) == 1200
        assert o.finish_reason == "length"
        # slot released; only the radix registry's hold on the prompt's
        # full pages remains (246 pages for a 31499-token prefix)
        assert eng.n_pages - eng.pool.n_free == (plen - 1) // 128
        # prefill streamed the whole prompt through page-size chunks
        assert eng.stats["prefill_tokens"] == plen - 1

    def test_32k_geometry_matches_small_engine(self, params):
        """Table width must not change results: the same short request
        through a 256-wide-table engine and a 1-page-per-slot-ish engine."""
        big = GenerationEngine(
            CFG, params, max_slots=2, max_seqlen=32768, page_size=128,
            n_pages=512,
        )
        small = GenerationEngine(CFG, params, max_slots=2, max_seqlen=256)
        prompt = [5, 9, 2, 14, 3, 8, 1]
        for eng in (big, small):
            eng.submit(GenRequest(
                rid="x", input_ids=prompt, max_new_tokens=12, greedy=True
            ))
        ob = big.run_until_done(decode_steps=4)[0]
        os_ = small.run_until_done(decode_steps=4)[0]
        assert ob.output_ids == os_.output_ids

    def test_pool_pressure_at_long_context(self, params):
        """Two long requests against a pool that only fits ~1.2 of them:
        admission must defer (not corrupt) and both finish eventually."""
        eng = GenerationEngine(
            CFG, params, max_slots=2, max_seqlen=8192, page_size=128,
            n_pages=80,  # 80*128 = 10240 tokens: < 2 full slots
        )
        rng = np.random.default_rng(1)
        for i in range(2):
            prompt = [int(x) for x in rng.integers(1, 128, size=6000)]
            eng.submit(GenRequest(
                rid=f"r{i}", input_ids=prompt, max_new_tokens=64, greedy=True
            ))
        outs = eng.run_until_done(decode_steps=32, timeout=600.0)
        assert sorted(o.rid for o in outs) == ["r0", "r1"]
        assert all(len(o.output_ids) == 64 for o in outs)
        # every held page is accounted for by the radix registry (no slot
        # leaks); draining the registry returns the pool to full
        assert eng.n_pages - eng.pool.n_free == len(eng.prefix)
        eng.prefix.clear()
        assert eng.pool.n_free == eng.n_pages


def test_pool_scatter_matches_reference():
    """The flat-row pool scatter (layout-neutral form: a permuted-layout
    multi-dim scatter forced two full-pool relayout copies per decode
    step) must write active slots at (table[lens//page], lens%page) and
    leave inactive slots untouched."""
    from areal_tpu.models.transformer import PagedKVCache, _scatter_chunk_kv

    rng = np.random.default_rng(0)
    L, P, Hkv, page, D, B, M = 3, 10, 2, 8, 16, 4, 2
    pages = rng.normal(size=(L, P, 2, Hkv, page, D)).astype(np.float32)
    ks = rng.normal(size=(L, B, Hkv, D)).astype(np.float32)
    vs = rng.normal(size=(L, B, Hkv, D)).astype(np.float32)
    table = rng.permutation(P)[: B * M].reshape(B, M).astype(np.int32)
    lens = np.asarray([0, 7, 8, 15], np.int32)     # page starts/ends
    active = np.asarray([True, True, False, True])

    got = np.asarray(_scatter_chunk_kv(
        PagedKVCache(pages=jnp.asarray(pages)),
        jnp.asarray(ks[:, :, None]), jnp.asarray(vs[:, :, None]),
        jnp.asarray(table), jnp.asarray(lens),
        jnp.asarray(active.astype(np.int32)),
    ).pages)
    want = pages.copy()
    for b in range(B):
        if not active[b]:
            continue
        p_, o = table[b, lens[b] // page], lens[b] % page
        for l in range(L):
            want[l, p_, 0, :, o, :] = ks[l, b]
            want[l, p_, 1, :, o, :] = vs[l, b]
    np.testing.assert_array_equal(got, want)


def test_kv_write_kernel_and_scatter_leave_the_same_engine(params, monkeypatch):
    """The same requests through an engine whose KV writes take the
    ``kv_page_write`` kernel (interpret mode) and one whose writes take
    the XLA scatter: after several chunks, with admissions between them
    (prefix hits among them), the pools are bit-equal and so are the
    outputs. Attention is held to the XLA gather path in both, so the
    write is the only difference; ``kv_write_tiles`` is on the chunk and
    admit spans of the one and absent from the other's."""
    from areal_tpu.base import tracing
    from areal_tpu.ops import paged_attention as paged_ops

    monkeypatch.setattr(
        paged_ops, "decode_kernel_applies", lambda *a, **k: False
    )
    rng = np.random.default_rng(3)
    shared = rng.integers(1, 128, size=20).tolist()
    prompts = [
        shared + rng.integers(1, 128, size=n).tolist()
        for n in (3, 9, 14, 1)
    ] + [rng.integers(1, 128, size=n).tolist() for n in (5, 27, 12)]
    pools, outs, spans = {}, {}, {}
    for use_pallas in (True, False):
        eng = GenerationEngine(
            CFG, params, max_slots=4, max_seqlen=64, max_new_tokens_cap=16,
            page_size=8, n_pages=40, seed=0,
        )
        eng._decode_use_pallas = use_pallas
        assert bool(eng._kv_write_rows()) == use_pallas
        mark = time.perf_counter()
        got = {}
        for i, ids in enumerate(prompts):
            eng.submit(GenRequest(
                rid=f"r{i}", input_ids=ids, max_new_tokens=6 + i,
                greedy=True,
            ))
        for _ in range(12):          # 4 slots, 7 requests: two waves
            for o in eng.step(decode_steps=3):
                got[o.rid] = (list(o.output_ids), list(o.output_logprobs))
            if len(got) == len(prompts):
                break
        assert len(got) == len(prompts)
        pools[use_pallas] = np.asarray(eng.state.cache.pages)
        outs[use_pallas] = got
        spans[use_pallas] = [
            r for r in tracing.spans_since(mark)
            if r["name"] in ("gen_engine/chunk", "gen_engine/admit")
        ]
        assert (eng.stats["kv_write_tiles"] > 0) == use_pallas
    np.testing.assert_array_equal(pools[True], pools[False])
    assert outs[True] == outs[False]
    on = [r["attrs"] for r in spans[True]]
    assert all(
        "kv_write_tiles" in a for a in on if a.get("slots") or "admitted" in a
    )
    # a chunk: one tile a (layer, running slot, step)
    chunk = next(a for a in on if a.get("slots"))
    assert chunk["kv_write_tiles"] == CFG.n_layers * chunk["slots"] * 3
    # an admission: the tiles of 8 rows its prefilled tokens fall into
    admit = next(a for a in on if a.get("prefill_tokens"))
    assert admit["kv_write_tiles"] >= CFG.n_layers * (
        -(-admit["prefill_tokens"] // 8)
    )
    assert not any("kv_write_tiles" in r["attrs"] for r in spans[False])


@pytest.mark.parametrize("use_pallas", [True, False])
def test_admission_writes_through_one_program(params, use_pallas):
    """Admission's chunk is two programs: ``jit_extend`` (a bucket, a
    table width, ``skip_pool``) returns the fresh K/V and leaves the state
    alone, ``jit_kv_write`` puts it into the pages. Where the kernel
    writes, waves of every size share ONE write program (rows padded to
    the top bucket, so the kernel is traced and lowered once a start);
    where the scatter does, a wave's write has the wave's rows (a scatter
    pays for every row it is given)."""
    eng = GenerationEngine(
        CFG, params, max_slots=8, max_seqlen=64, max_new_tokens_cap=8,
        page_size=8, n_pages=60, seed=0,
    )
    eng._decode_use_pallas = use_pallas
    rng = np.random.default_rng(5)
    k = 0
    for wave in (1, 3, 2):              # buckets 1, 4, 2
        for _ in range(wave):
            eng.submit(GenRequest(
                rid=f"w{k}", input_ids=rng.integers(1, 128, size=11).tolist(),
                max_new_tokens=2, greedy=True,
            ))
            k += 1
        eng.run_until_done(decode_steps=2)
    top = eng.admit_buckets[-1]
    assert {key[0] for key in eng._jit_extend} == {1, 2, 4}
    assert sorted(eng._jit_kv_write) == ([top] if use_pallas else [1, 2, 4])
    sizes = eng.program_sizes()
    assert all(sizes[f"kv_write{n}"] == 1 for n in eng._jit_kv_write)
    assert eng.n_compiles() == (
        len(eng._jit_extend) + len(eng._jit_kv_write)
        + len(eng._jit_commit) + len(eng._jit_chunk)
    )


# --------------------------------------------------------------------- #
# A GRPO group's prompt pages are read once a step (the prefix program)
# --------------------------------------------------------------------- #

SHARED_COUNTERS = ("kv_pages_named", "kv_pages_read", "kv_shared_groups",
                   "kv_shared_rows")


def _group_run(params, *, prefix_cache, lengths, n_pages=None, horizon=None,
               steps=3, loner=True):
    """A group on one prompt of two whole pages and a tail (and, ``loner``,
    one request of its own), each member asking for its entry of
    ``lengths``, through an engine that runs the paged kernel (interpret
    mode). Returns outputs by rid, the engine, its decode-chunk spans and
    the rids the dry rule preempted."""
    from areal_tpu.base import tracing

    rng = np.random.default_rng(11)
    prompt = [int(x) for x in rng.integers(1, 128, 21)]
    eng = GenerationEngine(
        CFG, params, max_slots=8, max_seqlen=96, max_new_tokens_cap=64,
        page_size=8, n_pages=n_pages, seed=0,
        enable_prefix_cache=prefix_cache,
    )
    eng._decode_use_pallas = True
    if horizon is not None:
        eng.ADMIT_HORIZON = horizon
    mark = time.perf_counter()
    for i, g in enumerate(lengths):
        eng.submit(GenRequest(
            rid=f"g{i}", input_ids=prompt, max_new_tokens=g, greedy=True))
    if loner:
        eng.submit(GenRequest(
            rid="alone", input_ids=[int(x) for x in rng.integers(1, 128, 13)],
            max_new_tokens=7, greedy=True))
    outs, preempted, n = {}, set(), 0
    while eng.n_pending() or eng.n_running():
        for o in eng.step(steps):
            outs[o.rid] = o
        preempted |= set(eng._carried)
        n += 1
        assert n < 400
    chunks = [
        r["attrs"] for r in tracing.spans_since(mark)
        if r["name"] == "gen_engine/chunk" and r["attrs"].get("slots")
    ]
    return outs, eng, chunks, preempted


SHARED_GROUPS = {
    # five members that all run to one length beside a request of its own
    "group": dict(lengths=[9] * 5),
    # a member ends inside a chunk of three steps; the others go on
    "member_finishes_inside_a_chunk": dict(lengths=[4, 10, 10, 11]),
    # the group thins to one row, which then shares with nobody
    "thinned_to_one": dict(lengths=[2, 3, 14], loner=False),
    # a dry pool: members are held out and one is preempted (PR 43's dry
    # rule), re-admitted over the prompt's filed pages when the short member
    # has gone, and runs on beside another to the end
    "preempted_and_readmitted": dict(
        lengths=[16, 56, 60, 64], n_pages=21, horizon=0, loner=False),
}


@pytest.mark.parametrize("case", list(SHARED_GROUPS))
def test_shared_group_decodes_as_without_the_cache(params, case):
    """With the prefix cache the members' tables name the prompt's whole
    pages; the decode chunks read them once a group (``kv_pages_read <
    kv_pages_named``) and every member's tokens and log-probs are what the
    same requests give on an engine without the cache, where every row
    holds and reads its own copy."""
    opt = SHARED_GROUPS[case]
    outs, eng, chunks, preempted = _group_run(
        params, prefix_cache=True, **opt)
    want, cold, cold_chunks, _ = _group_run(
        params, prefix_cache=False, **{**opt, "n_pages": None})
    assert set(outs) == set(want)
    for rid, o in outs.items():
        assert o.output_ids == want[rid].output_ids, rid
        np.testing.assert_allclose(
            o.output_logprobs, want[rid].output_logprobs, atol=1e-4,
            err_msg=rid)
    assert all(all(k in a for k in SHARED_COUNTERS) for a in chunks)
    sharing = [a for a in chunks if a["kv_shared_rows"]]
    assert sharing
    for a in sharing:
        # the whole pages of the prompt that the members' tables name (two
        # where a member found both filed), copied once a group and not
        # once a member
        assert a["kv_shared_groups"] == 1 and a["kv_shared_rows"] >= 2
        assert a["kv_pages_read"] < a["kv_pages_named"]
    first = chunks[0]
    if "n_pages" not in opt:
        assert first["kv_shared_rows"] == len(opt["lengths"])
    assert first["kv_pages_named"] - first["kv_pages_read"] == 2 * (
        first["kv_shared_rows"] - 1)
    # without the cache no running row's table names another's page
    # in ANY chunk: a slot freed inside the run keeps its length on the
    # device over a row of zeros, and two of those are not a group
    assert all(a["kv_pages_read"] == a["kv_pages_named"] > 0
               and a["kv_shared_rows"] == a["kv_shared_groups"] == 0
               for a in cold_chunks)
    for k in SHARED_COUNTERS:
        assert eng.stats[k] == sum(a[k] for a in chunks)
    if case == "thinned_to_one":
        assert chunks[-1]["slots"] == 1
    if case == "preempted_and_readmitted":
        assert preempted and eng.stats["preemptions"] >= 1
        # the rows that RUN a chunk are its group, the member that came
        # back among them (two run the last chunk); a member held out of a
        # chunk is not active on the device and sits in no block
        assert any(a["slots_held"] for a in chunks)
        assert [a["kv_shared_rows"] for a in chunks] == [
            a["slots"] if a["slots"] > 1 else 0 for a in chunks]
        assert chunks[-1]["slots"] == 2
    if case == "group":
        from areal_tpu.gen.server import GenerationHTTPServer

        m = GenerationHTTPServer(eng)._metrics_dict()
        for k in SHARED_COUNTERS:
            assert m[f"engine_{k}"] == eng.stats[k]
        assert 0 < m["engine_kv_pages_read"] < m["engine_kv_pages_named"]
