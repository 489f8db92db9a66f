"""Host-side page accounting for the paged KV cache.

Counterpart of SGLang's radix-tree + block allocator that the reference gets
for free (``patch/sglang/v0.4.6.post4.patch``, SURVEY §2.1): the generation
engine's KV memory is a pool of fixed-size pages; slots hold page tables
instead of dense ``[S_max]`` slabs, so HBM scales with tokens actually
resident, and prompts SHARE pages for their longest common page-aligned
prefix through a radix tree (one prefill serves a whole GRPO group — the
reason gserver routing is sticky per qid — and prompts over one system
preamble share the preamble pages).

Device arrays live in the engine; this module is pure host bookkeeping
(free list, refcounts, prefix registry) — no jax imports. It is also
BYTE-AGNOSTIC: a page index addresses whatever the pool stores (raw
bf16 pages or int8 pages + their parallel scales array — docs/
performance.md "KV quantization"), so prefix sharing shares quantized
pages and their scales without this module knowing either exists.

LAYER KINDS (a model whose stack mixes window and full layers,
``models/transformer.PagedKVCache``): ONE pool and ONE free list, pages of
one byte size, and a slot has one page table a kind. The registry then
holds, for one page of prompt, the page of EVERY kind (a node's ``page``
is a tuple), so a group still prefills its prompt once. A window kind's
page that lies wholly behind a slot's window is released by the slot while
it runs; one that the registry (or a sibling) still holds stays resident
and is simply no longer that slot's. Pages that only the registry holds
are the pool's reserve: ``PagePool.n_cached_only`` counts them, a slot's
later needs are RESERVED against free + cached-only pages
(``PagePool.reserved``), and under pressure the registry gives back first
the window-kind pages of nodes whose other pages are still borrowed
(a running slot has moved past them: ``evict_lru``).
"""

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np


class OutOfPagesError(RuntimeError):
    pass


class PagePool:
    """Fixed pool of KV pages with refcounting (shared prompt pages)."""

    def __init__(self, n_pages: int, page_size: int):
        self.n_pages = n_pages
        self.page_size = page_size
        self._free: List[int] = list(range(n_pages - 1, -1, -1))
        self._ref = np.zeros(n_pages, np.int32)
        # pages the prefix registry holds a reference to, and how many of
        # them NOTHING else holds: those the registry can give back at once
        self._cached = np.zeros(n_pages, bool)
        self.n_cached_only = 0
        # pages promised to running slots and not yet taken (a window
        # kind's later pages, ``gen/engine.py``): always backed by free or
        # cached-only pages, so taking one never fails
        self.reserved = 0

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_unpromised(self) -> int:
        """Pages an admission may still take or reserve: free or held by
        the registry alone, less those already promised."""
        return len(self._free) + self.n_cached_only - self.reserved

    def _only_cached(self, p: int) -> bool:
        return bool(self._cached[p]) and self._ref[p] == 1

    def set_cached(self, p: int, cached: bool):
        """The registry took (or is about to drop) its reference to ``p``."""
        self.n_cached_only -= self._only_cached(p)
        self._cached[p] = cached
        self.n_cached_only += self._only_cached(p)

    def alloc(self, n: int) -> List[int]:
        """n fresh pages (refcount 1 each); raises OutOfPagesError."""
        if n > len(self._free):
            raise OutOfPagesError(
                f"need {n} pages, {len(self._free)} free of {self.n_pages}"
            )
        pages = [self._free.pop() for _ in range(n)]
        self._ref[pages] = 1
        return pages

    def ref(self, pages: Sequence[int]):
        """Share existing pages (+1 each)."""
        for p in pages:
            if self._ref[p] <= 0:
                raise ValueError(f"page {p} is free; cannot share")
            self.n_cached_only -= self._only_cached(p)
            self._ref[p] += 1

    def refcount(self, page: int) -> int:
        return int(self._ref[page])

    def n_slot_holders(self, pages) -> np.ndarray:
        """References to each of ``pages`` that are not the registry's."""
        pages = np.asarray(pages, np.int64)
        return self._ref[pages] - self._cached[pages]

    def release(self, pages: Sequence[int]):
        """Drop one reference per page; refcount 0 returns it to the pool."""
        for p in pages:
            if self._ref[p] <= 0:
                raise ValueError(f"double free of page {p}")
            self.n_cached_only -= self._only_cached(p)
            self._ref[p] -= 1
            if self._ref[p] == 0:
                self._cached[p] = False
                self._free.append(p)
            else:
                self.n_cached_only += self._only_cached(p)


@dataclasses.dataclass
class _RadixNode:
    # resident page (one ref held); with layer kinds a LIST, the page of
    # each kind, where -1 is a window kind's page that was given back
    page: Any
    children: Dict[Tuple[int, ...], "_RadixNode"]
    last_used: int                              # LRU tick
    # entry of the engine's snapshot pool that holds the per-slot state
    # (``tfm.SSMState``) after exactly the tokens up to and including this
    # node's page; None: no snapshot was taken here, or it was evicted
    snap: Optional[int] = None


def _ids(page) -> List[int]:
    """The pool pages of a node's (or a hit's) ``page``: itself, or the
    kinds' pages that are still there."""
    if isinstance(page, (int, np.integer)):
        return [int(page)]
    return [int(p) for p in page if p >= 0]


class PrefixRegistry:
    """Page-granular radix tree: prompt prefixes -> resident KV pages.

    The counterpart of SGLang's radix cache: each tree level is one page of
    prompt tokens (the child key is that page's token tuple), so any two
    prompts share pages for their longest common PAGE-ALIGNED prefix — a
    GRPO group shares the whole prompt, different questions over one system
    preamble share the preamble pages. The tree holds one refcount per
    resident page; lookups take another for the borrowing slot. Weight
    updates invalidate everything (KV from old params must not serve
    new-policy generations).
    """

    def __init__(
        self, pool: PagePool, windows: Sequence[Optional[int]] = (None,),
        n_snapshots: Optional[int] = None,
    ):
        """``windows``: the sliding window of each layer kind (``None``: a
        full-attention kind). More than one kind: a node's ``page`` (and
        every entry of a hit or an insert) is a list, one page a kind.

        ``n_snapshots`` (a model with per-slot recurrent state; None: no
        such model): entries of the engine's snapshot pool. Pages alone
        are then no hit: a borrower also needs the state after exactly
        the shared tokens, which exists only where a snapshot was filed
        (``alloc_snapshot`` + ``insert(snapshot=)``). ``lookup`` returns
        the chain down to the deepest node that HOLDS one. A snapshot
        goes with its node (``evict_lru``, ``clear``), or alone when the
        pool of them is full (least recently used first; ``pinned``
        entries, those an admission wave still reads or writes, stay)."""
        self.pool = pool
        self.windows = tuple(windows)
        self.stateful = n_snapshots is not None
        self._free_snaps: List[int] = list(range(n_snapshots or 0))[::-1]
        self._snap_nodes: Dict[int, _RadixNode] = {}
        self.pinned: set = set()
        self.snapshot_evictions = 0
        self.hit_snapshot: Optional[int] = None
        self._children: Dict[Tuple[int, ...], _RadixNode] = {}
        self._tick = 0
        self._n_nodes = 0
        # layer kinds: where a window kind's page is filed (node, kind), and
        # the pages a running slot has given up behind its window while
        # the registry kept them: the first to go under pressure, found
        # without a walk of the tree (``note_given_up``, ``evict_lru``)
        self._where: Dict[int, Tuple[_RadixNode, int]] = {}
        self._given_up: List[int] = []

    def _hold_node(self, node: _RadixNode, kinds: Sequence[int]):
        """Take the registry's reference to the node's pages of ``kinds``."""
        pages = [int(node.page[j]) for j in kinds]
        self._hold(pages)
        for j, p in zip(kinds, pages):
            if self.windows[j] is not None:
                self._where[p] = (node, j)

    def _give_back(self, node: _RadixNode, j: int):
        """Drop the node's page of window kind ``j``: the node stays, a
        hit that would need the page is cut short (``_usable``)."""
        p = int(node.page[j])
        self._where.pop(p, None)
        self._drop([p])
        node.page[j] = -1

    def note_given_up(self, pages: Sequence[int]):
        """A slot has released ``pages`` of a window kind behind its window.
        Those the registry holds stay resident (a sibling admitted later
        may borrow them) but are the first to go when pages are needed."""
        self._given_up.extend(int(p) for p in pages if int(p) in self._where)

    def _hold(self, pages: Sequence[int]):
        self.pool.ref(pages)  # arealint: owns(gen.kv-pages, the registry's own reference: a node keeps it until _drop)
        for p in pages:
            self.pool.set_cached(p, True)

    def _drop(self, pages: Sequence[int]):
        for p in pages:
            self.pool.set_cached(p, False)
        self.pool.release(pages)

    def _usable(self, pages: List, n: int) -> int:
        """The longest prefix ``m <= n`` of a matched chain that a borrower
        can use: its first new token sits at position ``m * page`` and a
        window kind's layers then read back to ``m * page + 1 - window``,
        so every page of that kind from there to ``m`` has to be there."""
        ps = self.pool.page_size
        for m in range(n, 0, -1):
            ok = True
            for j, w in enumerate(self.windows):
                if w is None:
                    continue
                first = max(m * ps + 1 - w, 0) // ps
                if any(pages[i][j] < 0 for i in range(first, m)):
                    ok = False
                    break
            if ok:
                return m
        return 0

    def __len__(self) -> int:
        return self._n_nodes  # resident pages held by the tree

    def _chunks(self, prompt_ids: Sequence[int], n_pages: int):
        ps = self.pool.page_size
        return [
            tuple(prompt_ids[i * ps : (i + 1) * ps]) for i in range(n_pages)
        ]

    def lookup(
        self, prompt_ids: Sequence[int], n_full_pages: int
    ) -> Optional[List[int]]:
        """Pages covering the LONGEST cached page-aligned prefix of the
        first ``n_full_pages`` pages (possibly fewer than requested), with a
        reference taken for the caller — or None on a cold miss. A
        stateful registry: the longest such prefix that ends in a node
        with a snapshot, whose entry is then ``hit_snapshot`` (pinned
        until the engine has copied it)."""
        self.hit_snapshot = None
        if n_full_pages <= 0:
            return None
        self._tick += 1
        pages: List[int] = []
        children = self._children
        snaps = []              # (pages up to a node with a snapshot, entry)
        for chunk in self._chunks(prompt_ids, n_full_pages):
            node = children.get(chunk)
            if node is None:
                break
            node.last_used = self._tick
            pages.append(node.page)
            children = node.children
            if node.snap is not None:
                snaps.append((len(pages), node.snap))
        windowed = len(self.windows) > 1
        if self.stateful:
            # the hit ends where the state stands: the longest prefix that
            # ends in a snapshot AND, with window kinds, still has every
            # page its borrower reads (cut shorter, the state seeded from
            # the snapshot would be ahead of the pages)
            n_state = 0
            for n, snap in reversed(snaps):
                if not windowed or self._usable(pages, n) == n:
                    n_state, self.hit_snapshot = n, snap
                    break
            pages = pages[:n_state]
        elif windowed:
            pages = pages[: self._usable(pages, len(pages))]
        if not pages:
            return None
        if self.hit_snapshot is not None:
            self.pinned.add(self.hit_snapshot)
        self.pool.ref([p for page in pages for p in _ids(page)])
        return pages

    def alloc_snapshot(self) -> Optional[int]:
        """An entry of the snapshot pool for a node about to be inserted,
        pinned; the least recently used node's if none is free, None if
        every entry is pinned."""
        if not self._free_snaps:
            loose = [
                (n.last_used, i) for i, n in self._snap_nodes.items()
                if i not in self.pinned
            ]
            if not loose:
                return None
            self._drop_snapshot(self._snap_nodes[min(loose)[1]])
            self.snapshot_evictions += 1
        idx = self._free_snaps.pop()
        self.pinned.add(idx)
        return idx

    def _drop_snapshot(self, node: _RadixNode):
        if node.snap is not None:
            del self._snap_nodes[node.snap]
            self._free_snaps.append(node.snap)
            node.snap = None

    def insert(self, prompt_ids: Sequence[int], pages: List[int],
               snapshot: Optional[int] = None):
        """Register a freshly covered page chain (shared prefix + newly
        prefilled pages). Existing nodes are kept — a racing identical
        prefill's duplicate page stays owned by its slot and is freed when
        that slot finishes; new nodes take their own reference.
        ``snapshot``: the entry (``alloc_snapshot``) that holds, or will
        hold before anyone is seeded from it, the state after the chain's
        last page; filed at that node unless it has one already (the
        entry is then free again)."""
        self._tick += 1
        children = self._children
        node = None
        for chunk, page in zip(self._chunks(prompt_ids, len(pages)), pages):
            node = children.get(chunk)
            if node is None:
                if isinstance(page, (int, np.integer)):
                    self._hold([int(page)])
                    node = _RadixNode(
                        page=page, children={}, last_used=self._tick)
                else:
                    node = _RadixNode(
                        page=[int(p) for p in page], children={},
                        last_used=self._tick)
                    self._hold_node(
                        node, [j for j, p in enumerate(page) if p >= 0])
                children[chunk] = node
                self._n_nodes += 1
            else:
                node.last_used = self._tick
                if len(self.windows) > 1:
                    # a window kind's page that was given back comes home
                    home = [j for j, p in enumerate(page)
                            if node.page[j] < 0 and p >= 0]
                    for j in home:
                        node.page[j] = int(page[j])
                    self._hold_node(node, home)
            children = node.children
        if snapshot is not None:
            if node is None or node.snap is not None:
                self.pinned.discard(snapshot)
                self._free_snaps.append(snapshot)
            else:
                node.snap = snapshot
                self._snap_nodes[snapshot] = node

    def n_reclaimable(self) -> int:
        """Pages held ONLY by the registry (refcount 1) — instantly
        evictable by the next admission under pool pressure. The
        admission-control occupancy signal subtracts these: raw occupancy
        counts cache an idle server would happily evict, which reads as
        "full" to an external admission gate and livelocks it."""
        out = 0
        stack = list(self._children.values())
        while stack:
            n = stack.pop()
            out += sum(self.pool.refcount(p) == 1 for p in _ids(n.page))
            stack.extend(n.children.values())
        return out

    def evict_lru(self, n_pages_needed: int) -> int:
        """Drop least-recently-used LEAVES (a node only goes after all its
        descendants) until the pool could satisfy ``n_pages_needed``. Nodes
        whose page is still borrowed by a running slot (refcount > 1) are
        SKIPPED, not dropped — releasing them frees nothing until the slot
        finishes, so evicting would drain hot prefixes under transient
        pressure without yielding a single page. One DFS collects every
        node; parents become evictable as their children go — O(tree)
        total, not O(tree) per page. Returns pages evicted."""
        if self.pool.n_free >= n_pages_needed:
            return 0
        import heapq

        # first what running slots gave up behind their windows and only
        # the registry still holds: no walk, newest last
        evicted = 0
        while self._given_up and self.pool.n_free < n_pages_needed:
            p = self._given_up.pop()
            at = self._where.get(p)
            if at is not None and self.pool.refcount(p) == 1:
                self._give_back(*at)
                evicted += 1
        if self.pool.n_free >= n_pages_needed:
            return evicted

        # one DFS: entry = [parent_children, key, node, n_live_children, idx]
        entries: List[list] = []
        parent_idx: Dict[int, int] = {}
        stack = [(self._children, k, n, None) for k, n in self._children.items()]
        while stack:
            pc, k, n, pidx = stack.pop()
            i = len(entries)
            entries.append([pc, k, n, len(n.children)])
            if pidx is not None:
                parent_idx[i] = pidx
            stack.extend((n.children, ck, cn, i) for ck, cn in n.children.items())
            if len(self.windows) > 1 and any(
                self.pool.refcount(p) > 1 for p in _ids(n.page)
            ):
                # a node some slot still borrows from: its window kinds'
                # pages that ONLY the registry holds lie behind that
                # slot's window (it gave them up while running). They go
                # first: a later hit that would need them is cut short
                # (``_usable``), nothing else is lost
                for j, w in enumerate(self.windows):
                    p = n.page[j]
                    if w is not None and p >= 0 and self.pool.refcount(p) == 1:
                        self._give_back(n, j)
                        evicted += 1
        if self.pool.n_free >= n_pages_needed:
            return evicted
        heap = [
            (e[2].last_used, i) for i, e in enumerate(entries) if e[3] == 0
        ]
        heapq.heapify(heap)
        while heap and self.pool.n_free < n_pages_needed:
            _, i = heapq.heappop(heap)
            pc, k, n, _ = entries[i]
            if any(self.pool.refcount(p) > 1 for p in _ids(n.page)):
                # borrowed by a resident slot: evicting frees nothing and
                # loses the prefix; leave this subtree alone
                continue
            evicted += self._unfile(n)
            del pc[k]
            pi = parent_idx.get(i)
            if pi is not None:
                entries[pi][3] -= 1
                if entries[pi][3] == 0:
                    heapq.heappush(heap, (entries[pi][2].last_used, pi))
        if self.pool.n_free < n_pages_needed:
            evicted += self._evict_stranded(n_pages_needed)
        return evicted

    def _evict_stranded(self, n_pages_needed: int) -> int:
        """The last resort of ``evict_lru``: a node that ONLY the registry
        holds while a slot holds one below it (the slot prefilled that
        stretch for itself, found it filed already, and filed its own
        pages further down: ``insert`` keeps existing nodes) never becomes
        a leaf while that slot runs. It goes with its subtree: the pages
        the registry alone holds come free, the slot keeps its own (they
        are merely no longer filed). This is what makes every page of
        ``PagePool.n_cached_only`` one that the registry can give back. A
        subtree with a snapshot that an admission wave still reads or
        writes stays."""
        evicted = 0
        stack = [(self._children, k, n) for k, n in self._children.items()]
        while stack and self.pool.n_free < n_pages_needed:
            pc, k, n = stack.pop()
            if any(self.pool.refcount(p) > 1 for p in _ids(n.page)):
                stack.extend((n.children, ck, cn)
                             for ck, cn in n.children.items())
                continue
            below, todo = [], [n]
            while todo:
                x = todo.pop()
                below.append(x)
                todo.extend(x.children.values())
            if any(x.snap in self.pinned for x in below if x.snap is not None):
                continue
            evicted += sum(self._unfile(x) for x in below)
            del pc[k]
        return evicted

    def _unfile(self, node: _RadixNode) -> int:
        """Drop the registry's hold on an evicted node's pages and its
        snapshot (the caller takes the node out of the tree). Returns the
        pages that came free: those nobody else held."""
        ids = _ids(node.page)
        freed = sum(self.pool.refcount(p) == 1 for p in ids)
        for p in ids:
            self._where.pop(p, None)
        self._drop(ids)
        if node.snap is not None:
            self._drop_snapshot(node)
            self.snapshot_evictions += 1
        self._n_nodes -= 1
        return freed

    def clear(self):
        """Invalidate everything (weight update)."""
        stack = list(self._children.values())
        while stack:
            n = stack.pop()
            self._drop(_ids(n.page))
            self._drop_snapshot(n)
            stack.extend(n.children.values())
        self._children = {}
        self._n_nodes = 0
        self._where.clear()
        self._given_up.clear()
        self.pinned.clear()
