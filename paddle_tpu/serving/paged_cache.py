"""Paged KV-cache subsystem: global page pools + host-side allocator
with REFCOUNTED pages and a shared-prefix page cache.

Serving memory layout (reference: the block_multi_head_attention tier of
the serving stack; TPU-native design: Ragged Paged Attention, arxiv
2604.15464 / vLLM block tables): K/V for ALL in-flight requests live in
one global pool of fixed-size token pages per layer —
``(L, num_pages, page_size, nkv, hd)`` — and each request holds an
ordered block table of page ids. HBM is sized by tokens actually in
flight instead of ``batch * longest_request``, which is what lets the
continuous-batching engine (inference/predictor.py) admit short requests
into the headroom long ones would otherwise pad-burn.

Pages are REFCOUNTED (vLLM-style copy-on-write sharing): a page lives in
more than one block table when requests share a prompt prefix, and it
returns to the free list only when its last reference drops. The
:class:`PrefixCache` hash-trie maps chains of FULL prompt pages (plus
one partial-page tail donor per chain) to the page ids that already hold
their KV, so an admission with a shared prefix maps existing pages into
its table instead of re-prefilling them — skipping both the prefill
FLOPs and the KV HBM for the shared span. The first PARTIAL page of a
shared span is copy-on-write: decode will append into it, so its shared
rows are device-copied into a privately owned page.

Everything here is HOST-side bookkeeping (free lists, refcounts, tries,
stats, tables); the device-side pool arrays are built by
``models/generate.init_paged_cache`` and updated functionally inside the
jitted prefill/decode programs. Page id 0 is RESERVED as the trash page:
the single jitted ragged-decode program runs every slot each step with
static shapes, and retired/empty slots route their (masked, garbage)
KV writes there instead of clobbering live pages.

A config whose layers mix sliding-window and full attention
(``LlamaConfig.layer_pattern``) has TWO pools, one per layer kind, each
with an allocator and a block table a row of its own
(:class:`PagedKVCache`). The full layers' is what the paragraphs above
describe. The sliding layers' holds a window and a chunk a row: a page is
granted when the context first reaches it and goes back to the free list
in the commit of the step that slid the window past it, so a 10k-token
row holds the same twenty-odd pages there as a 2k-token one.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .resilience import fault_point
from ..observability import hooks as _obs

#: page id never handed out by the allocator — the write target for
#: inactive rows of the static-shape decode program
TRASH_PAGE = 0


def _pool_scatter(pool: Dict, vals: Dict, dst):
    """The KV-import scatter program: write ``vals`` (per-array page
    payloads, shape ``(L, k, page, ...)``) into the pool at page ids
    ``dst`` — ONE donated jitted program so XLA updates the (GB-scale)
    pool buffers in place instead of re-materializing them. Shared by
    :meth:`PagedKVCache.restore_prefix` (drain/restore) and
    :meth:`PagedKVCache.import_request` (the prefill→decode handoff),
    and Mosaic-lowered by ``tools/aot_validate.py --config
    serving-cluster`` — one program, one lowering gate."""
    import jax.numpy as jnp
    return {name: arr.at[:, dst].set(jnp.asarray(vals[name])
                                     .astype(arr.dtype))
            for name, arr in pool.items()}


def _pool_move(pool: Dict, src_ids, dst_ids, src_pool: Optional[Dict] = None):
    """The FUSED page gather+scatter program (ISSUE 11): copy pages
    ``src_ids`` into pages ``dst_ids`` for every pool array in ONE
    donated jitted program — the device-to-device collapse of the
    ``_pool_gather`` → host numpy → ``_pool_scatter`` pair the PR 9
    handoff and PR 10 swap paths stage through host RAM. ``src_pool``
    None moves pages WITHIN the donated pool (defrag compaction — the
    gather is evaluated against the pre-update buffers, so overlapping
    src/dst ranges are safe); a separate ``src_pool`` moves pages
    ACROSS pools (the in-process prefill→decode handoff fast path —
    source read-only, destination donated). Mosaic-lowered by
    ``tools/aot_validate.py --config serving-lowbit``."""
    import jax.numpy as jnp
    src = pool if src_pool is None else src_pool
    return {name: arr.at[:, dst_ids].set(
        jnp.asarray(src[name])[:, src_ids].astype(arr.dtype))
        for name, arr in pool.items()}


def pool_partition_specs(pool: Dict, axis: str = "tp") -> Dict:
    """Per-array PartitionSpecs sharding a paged pool on its KV-HEAD
    axis: k/v pages are ``(L, P, page, nkv, hd)`` (head axis 3), the
    int8 tier's ks/vs scale pools ``(L, P, page, nkv)`` (head axis
    last). The ONE place this layout is written down — the engine's
    shard_map programs (inference/predictor.py) and the serving-tp
    lowering gate (tools/aot_validate.py) must agree on it by
    construction, not by parallel maintenance."""
    from jax.sharding import PartitionSpec as P
    return {name: (P(None, None, None, axis, None) if a.ndim == 5
                   else P(None, None, None, axis))
            for name, a in pool.items()}


class PoolExhausted(RuntimeError):
    """Raised when an allocation cannot be satisfied from the free list.

    Continuous batching treats this as back-pressure: the admission is
    deferred until running requests retire and recycle their pages."""


class BlockAllocator:
    """Host-side slot allocator over the global page pool, refcounted.

    Tracks a free list, per-page reference counts, and
    alloc/share/free/defrag stats. Page ids start at ``reserved``
    (default 1 — page 0 is the trash page). ``alloc`` hands out pages at
    refcount 1; ``share`` takes an additional reference on a live page
    (prefix sharing); ``free`` drops one reference and recycles the page
    only at zero — so ``allocs_total == frees_total`` at full teardown
    (every reference, allocated or shared, is dropped exactly once)."""

    def __init__(self, num_pages: int, reserved: int = 1):
        if num_pages <= reserved:
            raise ValueError(
                f"BlockAllocator: num_pages={num_pages} must exceed the "
                f"{reserved} reserved page(s)")
        self.num_pages = num_pages
        self.reserved = reserved
        # descending storage so list.pop() hands out ascending ids
        # (deterministic placement; tests rely on it)
        self._free: List[int] = list(range(num_pages - 1, reserved - 1, -1))
        self._refcount = np.zeros((num_pages,), np.int32)
        self.allocs_total = 0
        self.frees_total = 0
        self.shares_total = 0
        self.alloc_failures = 0
        self.defrags_total = 0
        self.peak_in_use = 0

    @property
    def num_usable(self) -> int:
        """Pages the allocator can ever hand out (pool minus reserved) —
        the consistent denominator for ``num_free``/``num_used``/
        ``utilization`` (the raw ``num_pages`` includes the trash page,
        which is neither free nor used)."""
        return self.num_pages - self.reserved

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return self.num_usable - len(self._free)

    @property
    def shared_pages(self) -> int:
        """Pages currently referenced more than once (prefix sharing)."""
        return int((self._refcount > 1).sum())

    def refcount(self, page: int) -> int:
        return int(self._refcount[page])

    def utilization(self) -> float:
        total = self.num_usable
        return self.num_used / total if total else 0.0

    def fragmentation(self) -> float:
        """Fraction of free pages sitting BELOW the highest used page —
        holes a compaction (:meth:`PagedKVCache.defrag`) would close.
        Shared (refcount>1) pages count as used like any other live
        page: they are movable (defrag remaps every table and the
        prefix trie atomically), so holes below them are closable."""
        if not self._free or self.num_used == 0:
            return 0.0
        free = set(self._free)
        top_used = max(i for i in range(self.reserved, self.num_pages)
                       if i not in free)
        holes = sum(1 for f in self._free if f < top_used)
        return holes / len(self._free)

    def alloc(self, n: int) -> List[int]:
        """Hand out ``n`` pages at refcount 1; raises
        :class:`PoolExhausted` (and counts the failure) when the free
        list is short."""
        if n < 0:
            raise ValueError(f"alloc of negative page count {n}")
        # resilience injection site: fires BEFORE any free-list
        # mutation, so an injected allocator fault leaves the
        # allocator's books consistent (the supervisor discards the
        # whole pool on recovery regardless)
        fault_point("alloc")
        if n > len(self._free):
            self.alloc_failures += 1
            raise PoolExhausted(
                f"need {n} pages, {len(self._free)} free "
                f"(pool {self.num_pages}, {self.reserved} reserved)")
        got = [self._free.pop() for _ in range(n)]
        for p in got:
            self._refcount[p] = 1
        self.allocs_total += n
        self.peak_in_use = max(self.peak_in_use, self.num_used)
        return got

    def share(self, pages: Sequence[int]):
        """Take one additional reference on each (live) page — the
        prefix-sharing primitive. Counted into ``allocs_total`` so every
        reference is matched by exactly one ``free``."""
        for p in pages:
            if not (self.reserved <= p < self.num_pages):
                raise ValueError(f"share of out-of-range page {p}")
            if self._refcount[p] < 1:
                raise ValueError(f"share of free page {p}")
        for p in pages:
            self._refcount[p] += 1
        self.allocs_total += len(pages)
        self.shares_total += len(pages)

    def free(self, pages: Sequence[int]):
        """Drop one reference per entry; a page recycles into the free
        list when its count reaches zero. Dropping more references than
        a page holds (including duplicates within one call) is a loud
        ``double free`` — the whole call is validated before any state
        changes."""
        fault_point("free")
        drops: Dict[int, int] = {}
        for p in pages:
            if not (self.reserved <= p < self.num_pages):
                raise ValueError(f"free of out-of-range page {p}")
            drops[p] = drops.get(p, 0) + 1
        for p, n in drops.items():
            if n > self._refcount[p]:
                raise ValueError(f"double free of page {p}")
        recycled = []
        for p in pages:
            self._refcount[p] -= 1
            if self._refcount[p] == 0:
                recycled.append(p)
        self._free.extend(recycled)
        self._free.sort(reverse=True)
        self.frees_total += len(pages)

    def stats(self) -> Dict[str, float]:
        return {
            "num_pages": self.num_pages,
            "num_reserved": self.reserved,
            "num_usable": self.num_usable,
            "num_used": self.num_used,
            "num_free": self.num_free,
            "shared_pages": self.shared_pages,
            "utilization": self.utilization(),
            "fragmentation": self.fragmentation(),
            "allocs_total": self.allocs_total,
            "frees_total": self.frees_total,
            "shares_total": self.shares_total,
            "alloc_failures": self.alloc_failures,
            "defrags_total": self.defrags_total,
            "peak_in_use": self.peak_in_use,
        }


class _TrieNode:
    __slots__ = ("page", "children", "tail", "tick")

    def __init__(self, page: Optional[int] = None):
        self.page = page
        self.children: Dict[bytes, "_TrieNode"] = {}
        # (page_id, token array) — ONE partial-page donor per chain: its
        # rows [0, len(tokens)) are immutable prompt KV (decode appends
        # strictly after them), the copy-on-write source
        self.tail: Optional[Tuple[int, np.ndarray]] = None
        self.tick = 0


class PrefixCache:
    """Hash-trie over FULL prompt pages (+ one partial tail per chain).

    A node at depth ``j`` keys the content of prompt page ``j`` given
    the pages before it (the dict key is the page's raw tokens; the
    chain from the root IS the context hash), and holds the pool page
    that already stores that span's KV. The trie owns one allocator
    reference per held page, so donor pages survive their original
    request's retirement; :meth:`evict` drops references LRU-first
    (tails, then leaf nodes — an inner node's KV is context for its
    descendants' reachability, so leaves go first) when the pool needs
    the room back.
    """

    def __init__(self, page_size: int):
        self.page_size = page_size
        self.root = _TrieNode()
        self._tick = 0
        self.evictions_total = 0

    def _bump(self, node: _TrieNode):
        self._tick += 1
        node.tick = self._tick

    def match(self, prompt: np.ndarray):
        """Longest shared span for ``prompt``: returns
        ``(full_page_ids, tail)`` where ``tail`` is ``(donor_page,
        rows)`` for a copy-on-write partial continuation or None. The
        span is capped at ``len(prompt) - 1`` tokens so at least one
        prompt token is always forwarded (its logits seed sampling)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        pg = self.page_size
        max_full = max(0, (prompt.size - 1) // pg)
        node, pages = self.root, []
        for j in range(max_full):
            child = node.children.get(
                prompt[j * pg:(j + 1) * pg].tobytes())
            if child is None:
                break
            node = child
            self._bump(node)
            pages.append(node.page)
        rem = prompt[len(pages) * pg:]
        limit = prompt.size - 1 - len(pages) * pg
        tail = None
        if rem.size == pg:
            # page-ALIGNED shared span: the span cap (not a mismatch)
            # stopped the walk, and the next full page may itself be a
            # trie child registered by an aligned donor — CoW all but
            # its last row (the maximal share: one token must forward)
            child = node.children.get(rem.tobytes())
            if child is not None:
                self._bump(child)
                tail = (int(child.page), pg - 1)
        if tail is None and node.tail is not None:
            donor, ttok = node.tail
            m = min(ttok.size, rem.size, limit)
            if m > 0:
                eq = ttok[:m] == rem[:m]
                t = int(m if eq.all() else np.argmax(~eq))
                if t > 0:
                    self._bump(node)
                    tail = (int(donor), t)
        return pages, tail

    def register(self, prompt: np.ndarray, pages: Sequence[int],
                 allocator: BlockAllocator):
        """Insert ``prompt``'s full pages (and partial tail, if any)
        into the trie, taking one allocator reference per page NEWLY
        covered (spans already in the trie — including ones this very
        request shared at admission — are left as-is)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        pg = self.page_size
        node = self.root
        for j in range(prompt.size // pg):
            key = prompt[j * pg:(j + 1) * pg].tobytes()
            child = node.children.get(key)
            if child is None:
                child = _TrieNode(page=int(pages[j]))
                allocator.share([child.page])
                node.children[key] = child
            node = child
            self._bump(node)
        rem = prompt.size % pg
        if rem and node.tail is None:
            k = prompt.size // pg
            node.tail = (int(pages[k]), prompt[k * pg:].copy())
            allocator.share([node.tail[0]])
            self._bump(node)

    def _candidates(self):
        """Evictable references: every tail, plus leaf nodes with no
        tail (inner nodes only become evictable once their subtree is
        gone — a child chain is unreachable without its ancestors).
        Each candidate carries its full chain-token path from the root
        (the trie's context hash) so an eviction hook can identify the
        span being dropped — the host tier's demotion key."""
        out = []
        stack = [(self.root, None, None, b"")]
        while stack:
            node, parent, key, path = stack.pop()
            if node.tail is not None:
                out.append((node.tick, 0, node, parent, key, True, path))
            elif parent is not None and not node.children:
                out.append((node.tick, 1, node, parent, key, False,
                            path))
            for k, c in node.children.items():
                stack.append((c, node, k, path + k))
        return out

    def evict(self, allocator: BlockAllocator, need: int,
              on_evict=None) -> int:
        """Drop trie references LRU-first until ``need`` pages actually
        returned to the free list (a dropped reference frees nothing
        while live block tables still share the page) or nothing
        evictable remains. Returns pages freed. One trie walk + sort
        serves a whole batch of drops; the walk repeats only when the
        candidate list ran dry and drops made new parents evictable —
        so reclaiming k pages from an n-node trie is O(n log n + k),
        not O(k * n log n), on the admission path.

        ``on_evict(chain_tokens, page_id)`` — if given — fires for
        every FULL page before its reference drops (the host tier's
        demote hook: the page bytes are still live when it runs).
        Partial-page tails never fire it."""
        start = allocator.num_free
        progressed = True
        while allocator.num_free - start < need and progressed:
            cands = self._candidates()
            cands.sort(key=lambda c: (c[0], c[1]))
            progressed = False
            for _, _, node, parent, key, is_tail, path in cands:
                if is_tail:
                    allocator.free([node.tail[0]])
                    node.tail = None
                else:
                    if on_evict is not None:
                        on_evict(np.frombuffer(path, np.int32),
                                 int(node.page))
                    allocator.free([node.page])
                    del parent.children[key]
                self.evictions_total += 1
                progressed = True
                if allocator.num_free - start >= need:
                    break
        return allocator.num_free - start

    def drop_all(self, allocator: BlockAllocator) -> int:
        """Release every trie reference (server reset / tests).
        ``need=num_pages`` can never be satisfied, so :meth:`evict`
        runs until no candidate remains — i.e. the trie is empty."""
        start = allocator.num_free
        self.evict(allocator, allocator.num_pages)
        return allocator.num_free - start

    def pages(self) -> List[int]:
        """Every page id the trie holds a reference on (defrag's
        used-set must include them — they are live storage even when no
        block table maps them)."""
        out = []
        stack = [self.root]
        while stack:
            node = stack.pop()
            if node.page is not None:
                out.append(node.page)
            if node.tail is not None:
                out.append(node.tail[0])
            stack.extend(node.children.values())
        return out

    def to_records(self) -> Dict:
        """Serialize the trie STRUCTURE for a drain checkpoint
        (ISSUE 8): ``nodes`` is a parent-before-child list of
        ``[parent_index, page_tokens, page_id]`` (parent ``-1`` = the
        root), ``tails`` a list of ``[node_index, tail_tokens,
        page_id]`` (node ``-1`` = a root tail). Page ids are the OLD
        pool's — :meth:`restore_records` remaps them into the restored
        pool. Pure host data, JSON-able."""
        nodes: List[list] = []
        tails: List[list] = []
        stack = [(self.root, -1)]
        while stack:
            node, idx = stack.pop()
            if node.tail is not None:
                tails.append([idx, node.tail[1].tolist(),
                              int(node.tail[0])])
            for key, child in node.children.items():
                nodes.append([idx,
                              np.frombuffer(key, np.int32).tolist(),
                              int(child.page)])
                stack.append((child, len(nodes) - 1))
        return {"nodes": nodes, "tails": tails}

    def restore_records(self, records: Dict, page_map: Dict[int, int],
                        allocator: BlockAllocator):
        """Rebuild the trie from :meth:`to_records` output under
        remapped page ids, taking ONE allocator reference per restored
        page reference (the same ownership contract
        :meth:`register` establishes). Restores into an EMPTY trie
        only — merging two tries would double-count references."""
        if self.root.children or self.root.tail is not None:
            raise ValueError("restore_records: the trie is not empty")
        built: List[_TrieNode] = []
        for parent, tokens, page in records["nodes"]:
            node = _TrieNode(page=page_map[int(page)])
            allocator.share([node.page])
            owner = self.root if parent < 0 else built[parent]
            owner.children[
                np.asarray(tokens, np.int32).tobytes()] = node
            built.append(node)
        for idx, tokens, page in records["tails"]:
            owner = self.root if idx < 0 else built[idx]
            owner.tail = (page_map[int(page)],
                          np.asarray(tokens, np.int32))
            allocator.share([owner.tail[0]])

    def remap_pages(self, remap: np.ndarray):
        """Rewrite held page ids after a defrag compaction."""
        stack = [self.root]
        while stack:
            node = stack.pop()
            if node.page is not None:
                node.page = int(remap[node.page])
            if node.tail is not None:
                node.tail = (int(remap[node.tail[0]]), node.tail[1])
            stack.extend(node.children.values())


class _TwinAllocator:
    """What the prefix trie is handed as its allocator by a cache with a
    sliding-layer pool: a reference on a full-pool page also takes one
    on the page's twin, the sliding-pool page that holds the same
    tokens, where the row that publishes the page still holds it
    (``offer``). So a page the trie holds keeps its sliding-layer half
    and sliding out of a row's window does not release it; dropping the
    trie's reference drops both halves. ``measure`` is the allocator
    whose free list an eviction is to fill."""

    def __init__(self, full: BlockAllocator, window: BlockAllocator):
        self.full, self.window, self.measure = full, window, full
        self.twin: Dict[int, int] = {}      # full page -> its twin
        self.offer: Dict[int, int] = {}

    num_pages = property(lambda self: self.measure.num_pages)
    num_free = property(lambda self: self.measure.num_free)

    def share(self, pages: Sequence[int]):
        self.full.share(pages)
        for p in pages:
            w = self.offer.get(int(p))
            if w is not None:
                self.window.share([w])
                self.twin[int(p)] = w

    def free(self, pages: Sequence[int]):
        self.full.free(pages)
        for p in pages:
            w = self.twin.pop(int(p), None)
            if w is not None:
                self.window.free([w])


class PagedKVCache:
    """Device page pools + per-slot block tables + the allocator.

    ``max_batch`` decode slots share one pool of ``num_pages`` pages of
    ``page_size`` tokens. Block tables are host numpy (tiny; shipped to
    the device each step as jitted-program arguments so shapes stay
    static). The pool arrays live in ``self.pool`` — a dict with the
    same keys as the dense cache (``k``/``v`` [+ ``ks``/``vs`` for the
    int8 tier]) — and are REPLACED functionally by the jitted programs
    (donated buffers update in place on device).

    ``enable_prefix_cache`` (default on) attaches a :class:`PrefixCache`
    so :meth:`admit_prompt` can map previously prefilled prompt pages
    into new admissions (refcounted sharing + copy-on-write tails).

    ``mesh`` (a 1-D ``("tp",)`` jax Mesh — see
    :func:`paddle_tpu.distributed.mesh.serving_mesh`): shard the pool
    arrays on the KV-HEAD axis across a tensor-parallel serving mesh.
    Each shard holds ``nkv/tp`` heads of every page (GQA with
    ``nkv < tp``: one replicated head per shard) while page IDS are the
    same everywhere — so ALL host-side bookkeeping in this module (the
    :class:`BlockAllocator`, refcounts, the :class:`PrefixCache` trie,
    block tables, defrag remaps) is replicated and runs UNCHANGED; only
    the device bytes split. ``pool_specs`` carries the per-array
    PartitionSpecs for the engine's shard_map programs, and
    ``pool_bytes_per_shard`` the adjusted page-byte accounting.

    A config with sliding layers (``self.window``: their window, else
    None) adds the second pool: ``self.pool`` holds its arrays under the
    ``_w`` names (``models/generate.KIND_SUFFIX``), ``window_allocator``
    its free list and ``window_tables`` each slot's page ids there, by
    the same logical page index as ``block_tables`` with the trash page
    where nothing is held. The pool is sized by the cache, not the
    caller: ``window_ring = ceil(window/page) + ceil(prefill_chunk/page)
    + 1`` pages is the most a row can hold (the window behind a chunk's
    first query, the chunk, one page of misalignment), times
    ``max_batch``, plus the trash page. The engine drives it:
    :meth:`window_extend` before a program writes new positions,
    :meth:`window_release` / :meth:`window_step` in the commit after.
    Admission reserves the full layers' pages for prompt and answer as
    ever; the ring needs no reservation, since no row can hold more
    than ``window_ring`` and only trie-held twins, which an allocation
    evicts under pressure, take the rest. ``prefill_chunk`` (tokens;
    None: whole prompts) only sizes that ring.

    A config with state-space layers (``self.state_layers`` of them) adds
    the third kind of cache, a pool of recurrent state addressed by SLOT:
    ``self.pool`` holds it as ``ssm`` / ``conv`` (``models/generate.
    init_paged_cache``), one slot a decode row, owned by whoever owns the
    row's block table. Admission (:meth:`admit`, :meth:`admit_prompt`)
    resets the slot: the row's first chunk, at context 0, starts from
    zeros whatever the slot's last tenant left (``models/hybrid.py:
    forward_chunk``), so a reset costs no program of its own.
    :meth:`release` and :meth:`evict_for_preempt` drop the slot with the
    row's pages; a preempted row's state is not kept, and its resume
    rebuilds it by prefilling prompt and answer again, as it rebuilds the
    pages. What walks PAGES to copy a row (prefix hits, drain/restore, the
    fabric's handoff, defrag) would leave its state behind and is refused
    by name: a prefix hit would need a snapshot of the state at the page
    boundary.

    A config with latent attention (``self.latent_layers`` of them) has ONE
    pool, of latents: ``self.pool`` holds ``c`` ``(layers, pages, page,
    kv_rank + rope_dim)`` (and ``cs``, a token's two dequant scales, on the
    int8 tier; ``models/generate.init_paged_cache``) in place of keys and
    values by head. Everything here deals in page ids and in a pool's
    arrays whole, so admission, block tables, release, preemption, the
    prefix trie and its copy-on-write run it as they run a K/V pool; a
    ``mesh`` is refused by name, since a latent has no head axis to
    shard."""

    def __init__(self, cfg, max_batch: int, max_len: int,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 kv_dtype=None, enable_prefix_cache: bool = True,
                 mesh=None, prefill_chunk: Optional[int] = None):
        from ..models import generate as _gen
        if max_len % page_size:
            max_len = (max_len // page_size + 1) * page_size
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.page_size = page_size
        self.pages_per_seq = max_len // page_size
        if num_pages is None:
            # worst case every slot runs a full-length request, +1 trash
            num_pages = 1 + max_batch * self.pages_per_seq
        self.num_pages = num_pages
        self.kv_dtype = kv_dtype
        self.mesh = mesh
        self.tp = None
        self.tp_axis = None
        self.pool_specs = None
        # 1-D ("tp",) or 2-D ("tp", "dp") serving mesh (ISSUE 17): the
        # pool shards on the head axis over tp only; its specs never
        # name the dp axis, so the pool is REPLICATED across dp — same
        # page ids on every dp shard, host bookkeeping unchanged.
        if mesh is not None:
            ax = "tp" if "tp" in mesh.axis_names else mesh.axis_names[0]
            if len(mesh.axis_names) > 2 or (
                    len(mesh.axis_names) == 2 and ax != "tp"):
                raise ValueError(
                    f"PagedKVCache: the serving mesh must be 1-D (tp) "
                    f"or 2-D (tp, dp), got axes {mesh.axis_names}")
            tp = int(mesh.shape[ax])
        else:
            ax, tp = None, None
        self.window = (cfg.sliding_window
                       if "sliding" in cfg.period else None)
        self.latent_layers = cfg.cache_layers().get("latent", 0)
        if self.latent_layers and mesh is not None:
            raise ValueError(
                "PagedKVCache: mesh (a sharded pool) is not supported on a "
                "config with latent attention: a latent has no head axis "
                "to shard (heads sharded over a replicated latent is not "
                "built)")
        self.state_layers = cfg.cache_layers().get("state", 0)
        if self.state_layers:
            for on, what in ((enable_prefix_cache, "enable_prefix_cache "
                              "(the prefix cache)"),
                             (mesh is not None, "mesh (a sharded pool)")):
                if on:
                    raise ValueError(
                        f"PagedKVCache: {what} is not supported on a "
                        f"config with state-space layers: a row's "
                        f"recurrent state lives beside its pages and is "
                        f"not copied, shared or sharded with them")
        # slots reset by an admission, and the most in use at once
        self.state_resets_total = 0
        self.state_slots_used_peak = 0
        self.window_ring = self.window_pages = None
        if self.window:
            self.window_ring = min(
                self.pages_per_seq,
                self.pages_for(self.window)
                + self.pages_for(prefill_chunk or max_len) + 1)
            self.window_pages = 1 + max_batch * self.window_ring
        # init_paged_cache(tp=...) validates head divisibility LOUDLY
        # (and expands the head extent on the GQA replication path)
        self.pool = _gen.init_paged_cache(cfg, num_pages, page_size,
                                          kv_dtype=kv_dtype, tp=tp,
                                          window_pages=self.window_pages,
                                          state_slots=max_batch)
        if mesh is not None:
            import jax
            from jax.sharding import NamedSharding
            self.tp = tp
            self.tp_axis = ax
            self.pool_specs = pool_partition_specs(self.pool, ax)
            self.pool = {
                n: jax.device_put(a, NamedSharding(mesh,
                                                   self.pool_specs[n]))
                for n, a in self.pool.items()}
        self.allocator = BlockAllocator(num_pages)
        self.prefix = PrefixCache(page_size) if enable_prefix_cache else None
        # whom the trie takes and drops its references through
        self._trie_alloc = self.allocator
        if self.window:
            self.window_allocator = BlockAllocator(self.window_pages)
            self._trie_alloc = _TwinAllocator(self.allocator,
                                              self.window_allocator)
            self.window_tables = np.full(
                (max_batch, self.pages_per_seq), TRASH_PAGE, np.int32)
            # a slot holds the window pages of logical pages
            # [_win_first, _win_next)
            self._win_first = np.zeros((max_batch,), np.int64)
            self._win_next = np.zeros((max_batch,), np.int64)
        self.cow_copies = 0
        self._cow_fn = None                     # jitted CoW row copier
        self._scatter_fn = None                 # jitted page-import scatter
        self._move_fn = None                    # fused same-pool page move
        self._move_from_fn = None               # fused cross-pool page move
        self.direct_moves_total = 0
        # TRASH_PAGE-filled tables: unassigned entries route to trash
        self.block_tables = np.full((max_batch, self.pages_per_seq),
                                    TRASH_PAGE, np.int32)
        self.lengths = np.zeros((max_batch,), np.int32)
        self.active = np.zeros((max_batch,), bool)
        self._slot_pages: List[List[int]] = [[] for _ in range(max_batch)]

    # ---- slot lifecycle (host) ----
    def pages_for(self, total_tokens: int) -> int:
        return -(-total_tokens // self.page_size)

    def ctx_cap_pages(self, n_pages: int) -> int:
        """Bucket a context page count UP to a power of two (capped at
        ``pages_per_seq``) — the shared compile-key rule for every
        gathered-context program (chunked prefill, prefix-cache resume,
        speculative verify), keeping the key space O(log(pages_per_seq))
        instead of linear. Extra gathered rows beyond the true context
        are ``kstart``-masked, so bucketing is parity-free."""
        if n_pages <= 0:
            return 0
        p2 = 1
        while p2 < n_pages:
            p2 *= 2
        return min(p2, self.pages_per_seq)

    def _check_admit(self, slot: int, total_tokens: int) -> int:
        if self.active[slot]:
            raise ValueError(f"slot {slot} already active")
        n = self.pages_for(total_tokens)
        if n > self.pages_per_seq:
            raise ValueError(
                f"request of {total_tokens} tokens needs {n} pages; the "
                f"cache holds max_len={self.max_len} "
                f"({self.pages_per_seq} pages) per request")
        return n

    def _alloc_with_evict(self, n: int) -> List[int]:
        """Allocate ``n`` pages, reclaiming prefix-cache references
        under pool pressure: trie-only pages are cache, not workload —
        admissions outrank them. One failed admission counts ONE
        ``alloc_failures`` (the eviction retry re-raises the original
        exception instead of re-attempting through the counter)."""
        try:
            return self.allocator.alloc(n)
        except PoolExhausted:
            if self.prefix is not None:
                self._evict_prefix(n - self.allocator.num_free)
            if n > self.allocator.num_free:
                raise
            return self.allocator.alloc(n)

    def _evict_prefix(self, need: int) -> int:
        """Reclaim ``need`` pages of prefix-trie references under pool
        pressure. The hierarchical host tier
        (:class:`~paddle_tpu.serving.host_tier.TieredKVCache`)
        overrides this to DEMOTE each dropped full page's bytes to
        host RAM before the reference goes — here they simply die and
        re-prefill on the next miss."""
        return self.prefix.evict(self._trie_alloc, need)

    # ---- the sliding layers' pool ----
    def _refuse_window(self, what: str):
        """The features that copy a row's pages as one list of ids."""
        if self.state_layers:
            raise ValueError(
                f"{what} is not supported on a config with state-space "
                f"layers: it walks pages and would leave the row's "
                f"recurrent state behind")
        if self.window:
            raise ValueError(
                f"{what} is not supported on a config with "
                f"sliding-window layers: it walks one pool's pages")

    def _window_alloc(self, n: int) -> List[int]:
        """``n`` pages of the sliding layers' pool. No row holds more
        than ``window_ring``, so what is missing is held by the trie
        alone: drop trie references (both halves go) until it fits, a
        ring's worth at a time so the trie is not walked every step."""
        wa = self.window_allocator
        if n > wa.num_free and self.prefix is not None:
            self._trie_alloc.measure = wa
            try:
                self.prefix.evict(self._trie_alloc,
                                  max(n, self.window_ring) - wa.num_free)
            finally:
                self._trie_alloc.measure = self.allocator
        return wa.alloc(n)

    def window_extend(self, slot: int, upto: int):
        """Grant ``slot`` the sliding-pool pages of every logical page
        below token position ``upto`` that it does not hold yet: call
        before a program writes positions up to ``upto - 1``."""
        last = min(self.pages_for(upto), self.pages_per_seq)
        nxt = int(self._win_next[slot])
        if last <= nxt:
            return
        self.window_tables[slot, nxt:last] = self._window_alloc(last - nxt)
        self._win_next[slot] = last

    def window_release(self, slot: int, pos: int) -> int:
        """Drop ``slot``'s references on the sliding-pool pages that lie
        wholly below what a query at position ``pos`` (and any later
        one) sees, ``pos - window + 1``. Returns the pages that went
        back to the free list; one the trie still holds stays."""
        first = min(max(pos - self.window + 1, 0) // self.page_size,
                    int(self._win_next[slot]))
        old = int(self._win_first[slot])
        if first <= old:
            return 0
        wa = self.window_allocator
        before = wa.num_free
        wa.free(self.window_tables[slot, old:first].tolist())
        self.window_tables[slot, old:first] = TRASH_PAGE
        self._win_first[slot] = first
        return wa.num_free - before

    def window_step(self, slots: np.ndarray) -> int:
        """After a decode commit advanced ``lengths[slots]``: the page
        the next token opens, and the pages the window slid past.
        Returns the pages released to the free list."""
        lens = self.lengths[slots]
        page = self.page_size
        opens = lens % page == 0
        passed = (np.maximum(lens - self.window + 1, 0) // page
                  > self._win_first[slots])
        freed = 0
        for s, n in zip(slots[opens | passed].tolist(),
                        lens[opens | passed].tolist()):
            freed += self.window_release(s, n)
            self.window_extend(s, n + 1)
        return freed

    def _window_twins(self, shared: List[int], tail, tokens: int):
        """The sliding-pool pages a prefix hit of ``tokens`` tokens
        needs: the twins of the matched pages from the one that holds
        position ``tokens - window + 1`` on, and the tail donor's. None
        where the trie does not hold one of them (its row had let it
        slide out before it published the prompt): the hit cannot be
        served."""
        twin = self._trie_alloc.twin
        p0 = max(tokens - self.window + 1, 0) // self.page_size
        need = shared[p0:] + ([tail[0]] if tail is not None else [])
        if any(int(f) not in twin for f in need):
            return None
        return p0, [twin[int(f)] for f in need]

    def _install(self, slot: int, pages: List[int]) -> np.ndarray:
        self._slot_pages[slot] = pages
        self.block_tables[slot] = TRASH_PAGE
        self.block_tables[slot, :len(pages)] = pages
        self.active[slot] = True
        if self.state_layers:
            # the slot's recurrent state is the new row's, from zero
            self.state_resets_total += 1
            self.state_slots_used_peak = max(self.state_slots_used_peak,
                                             int(self.active.sum()))
        return self.block_tables[slot]

    def admit(self, slot: int, total_tokens: int) -> np.ndarray:
        """Reserve pages for a request of ``total_tokens`` (prompt + new)
        on ``slot``; returns the slot's block-table row. Raises
        :class:`PoolExhausted` when the pool can't cover it. No prefix
        sharing — use :meth:`admit_prompt` to share prompt pages."""
        n = self._check_admit(slot, total_tokens)
        return self._install(slot, self._alloc_with_evict(n))

    def admit_prompt(self, slot: int, prompt,
                     total_tokens: int) -> Tuple[np.ndarray, int]:
        """Admit with prefix sharing: map the longest trie-matched span
        of ``prompt``'s pages into the slot's table (one extra reference
        each), copy-on-write the matched rows of the first partial page,
        and allocate fresh pages for the rest. Returns ``(block-table
        row, shared_tokens)`` — the first ``shared_tokens`` tokens of
        the prompt already have their KV in the mapped pages and must
        NOT be prefilled again."""
        n = self._check_admit(slot, total_tokens)
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if total_tokens < prompt.size:
            # the budget must cover the whole prompt — a shorter one
            # would let a trie match exceed the requested page count
            raise ValueError(
                f"admit_prompt: total_tokens={total_tokens} is smaller "
                f"than the {prompt.size}-token prompt it must contain")
        if self.prefix is None or prompt.size == 0:
            return self._install(slot, self._alloc_with_evict(n)), 0
        shared, tail = self.prefix.match(prompt)
        twins = None
        if self.window and (shared or tail is not None):
            # a hit maps both halves of a page, or nothing is shared
            twins = self._window_twins(
                shared, tail, len(shared) * self.page_size
                + (tail[1] if tail is not None else 0))
            if twins is None:
                shared, tail = [], None
        # pin the matched pages FIRST: the eviction a fresh-page alloc
        # may trigger must not recycle the span we are about to map
        self.allocator.share(shared)
        if twins is not None:
            self.window_allocator.share(twins[1])
        try:
            fresh = self._alloc_with_evict(n - len(shared))
        except PoolExhausted:
            if shared:
                self.allocator.free(shared)
            if twins is not None:
                self.window_allocator.free(twins[1])
            raise
        shared_tokens = len(shared) * self.page_size
        if tail is not None and fresh:
            donor, rows = tail
            self._cow_copy(donor, fresh[0], rows)
            shared_tokens += rows
            self.cow_copies += 1
        table = self._install(slot, shared + fresh)
        if twins is not None:
            p0, held = twins
            m = len(shared)
            self.window_tables[slot, p0:m] = held[:m - p0]
            self._win_first[slot], self._win_next[slot] = p0, m
            if tail is not None:
                # the donor's twin was pinned for the copy alone
                if fresh:
                    self.window_extend(slot, shared_tokens)
                    self._cow_copy(held[-1], self.window_tables[slot, m],
                                   tail[1], kind="sliding")
                self.window_allocator.free(held[-1:])
        return table, shared_tokens

    def _cow_copy(self, src_page: int, dst_page: int, rows: int,
                  kind: str = "full"):
        """Device-copy the first ``rows`` token rows of ``src_page``
        into ``dst_page`` for every array of the ``kind`` layers' pool
        (all its layers): the
        copy-on-write that lets an admission reuse a donor's partial
        prompt page without re-prefilling those rows, while decode
        appends into its OWN copy. Runs as ONE jitted program with the
        pool DONATED so XLA updates the buffers in place — an eager
        ``.at[].set`` would re-materialize the whole (GB-scale) pool to
        move at most one page of rows, on the admission hot path. The
        row count is a TRACED scalar (rows past it keep the dst page's
        values via a select), so every CoW admission shares a single
        compile instead of one per distinct share length."""
        import jax
        import jax.numpy as jnp
        if self._cow_fn is None:
            def f(pool, src, dst, rows):
                out = {}
                for name, arr in pool.items():
                    srcp = arr[:, src]                  # (L, page, ...)
                    dstp = arr[:, dst]
                    keep = jnp.arange(arr.shape[2]) < rows
                    keep = keep.reshape((1, -1) + (1,) * (srcp.ndim - 2))
                    out[name] = arr.at[:, dst].set(
                        jnp.where(keep, srcp, dstp))
                return out
            self._cow_fn = jax.jit(f, donate_argnums=(0,))
        from ..models.generate import KIND_SUFFIX, _of_kind
        mine = {n + KIND_SUFFIX[kind]: a
                for n, a in _of_kind(self.pool, kind).items()}
        self.pool.update(self._cow_fn(mine, jnp.int32(src_page),
                                      jnp.int32(dst_page),
                                      jnp.int32(rows)))

    def register_prefix(self, slot: int, prompt):
        """Publish a fully prefilled prompt's pages into the prefix
        trie (call once the whole prompt's KV is in the pool)."""
        if self.prefix is None:
            return
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0 or not self.active[slot]:
            return
        if self.window:
            # the trie takes both halves of a page where this row still
            # holds the sliding one
            lo, hi = int(self._win_first[slot]), int(self._win_next[slot])
            self._trie_alloc.offer = dict(zip(
                self._slot_pages[slot][lo:hi],
                self.window_tables[slot, lo:hi].tolist()))
        self.prefix.register(prompt, self._slot_pages[slot],
                             self._trie_alloc)

    def release(self, slot: int):
        """Retire a request: drop its page references (shared pages
        survive under the trie's or other tables' references)."""
        if self._slot_pages[slot]:
            self.allocator.free(self._slot_pages[slot])
        self._slot_pages[slot] = []
        if self.window:
            lo, hi = int(self._win_first[slot]), int(self._win_next[slot])
            self.window_allocator.free(
                self.window_tables[slot, lo:hi].tolist())
            self.window_tables[slot] = TRASH_PAGE
            self._win_first[slot] = self._win_next[slot] = 0
        self.block_tables[slot] = TRASH_PAGE
        self.lengths[slot] = 0
        self.active[slot] = False

    def evict_for_preempt(self, slot: int) -> int:
        """Preemption eviction: release ``slot``'s page references back
        to the pool and report how many pages actually reached the free
        list. Pages the prefix trie (or another table) still references
        survive under those references — the preemptor's own
        allocation reclaims trie-only copies through the usual
        evict-on-pressure path if the freed count alone doesn't cover
        it, and a later resume can map surviving trie pages straight
        back in. The slot's KV rows are NOT zeroed: freed pages carry
        finite garbage until their next tenant overwrites them, the
        same contract every release already relies on."""
        if not self.active[slot]:
            raise ValueError(f"evict_for_preempt of inactive slot {slot}")
        before = self.allocator.num_free
        self.release(slot)
        return self.allocator.num_free - before

    def free_slots(self) -> List[int]:
        return [i for i in range(self.max_batch) if not self.active[i]]

    def state_stats(self) -> Dict[str, int]:
        """The recurrent-state pool's gauges and counters (empty for a
        config without state-space layers)."""
        if not self.state_layers:
            return {}
        from ..models.generate import STATE_ARRAYS
        return {"state_slots": self.max_batch,
                "state_slots_used": int(self.active.sum()),
                "state_slots_used_peak": self.state_slots_used_peak,
                "state_pool_bytes": sum(
                    int(np.prod(self.pool[n].shape))
                    * np.dtype(self.pool[n].dtype).itemsize
                    for n in STATE_ARRAYS),
                "ssm_state_resets_total": self.state_resets_total}

    def latent_stats(self) -> Dict[str, int]:
        """The latent pool's gauges (empty for a config without latent
        attention): its bytes as the arrays' shapes give them, and the
        most pages in use at once."""
        if not self.latent_layers:
            return {}
        return {"latent_pool_bytes": sum(
                    int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                    for a in self.pool.values()),
                "latent_pool_used_peak": self.allocator.peak_in_use}

    def pages_held(self, slot: int) -> List[int]:
        """The page ids ``slot``'s block table currently references
        (copy) — e.g. the scheduler's preemption-feasibility
        accounting of pages pinned by non-victim requests."""
        return list(self._slot_pages[slot])

    def utilization(self) -> float:
        return self.allocator.utilization()

    def page_payload_bytes(self, k: int) -> int:
        """Device bytes of ``k`` pages across every pool array — what a
        host-staged :meth:`export_request` payload of that many pages
        would weigh (the handoff byte-accounting for the fused direct
        path, which never materializes those bytes)."""
        return sum(
            int(np.prod(a.shape[2:])) * a.shape[0] * k
            * np.dtype(a.dtype).itemsize for a in self.pool.values())

    @property
    def pool_bytes_per_shard(self) -> int:
        """Device bytes of pool arrays RESIDENT PER SHARD — the number
        the tp sharding exists to shrink. On the GQA replication path
        the global head extent is already expanded to ``tp`` (each kv
        head copied ``tp/nkv`` times), so dividing the global bytes by
        ``tp`` yields the honest per-shard bill: ``1/nkv`` of the
        unsharded pool, not ``1/tp``."""
        total = sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                    for a in self.pool.values())
        return total // (self.tp or 1)

    # ---- drain/restore (ISSUE 8): prefix-trie persistence ----
    def checkpoint_prefix(self) -> Optional[Dict]:
        """Checkpoint the prefix-cache trie for an engine drain: the
        trie structure (:meth:`PrefixCache.to_records`) plus the KV
        BYTES of every page the trie references, gathered from the
        device pools — the part of the pool worth persisting across a
        restart (in-flight sessions replay from the journal instead;
        their pages are recomputed). Returns None when the prefix
        cache is disabled or empty."""
        self._refuse_window("checkpoint_prefix (drain)")
        if self.prefix is None:
            return None
        ids = sorted(set(self.prefix.pages()))
        if not ids:
            return None
        sel = np.asarray(ids, np.int32)
        arrays = {name: np.asarray(arr[:, sel])
                  for name, arr in self.pool.items()}
        return {"page_ids": [int(p) for p in ids],
                "records": self.prefix.to_records(),
                "arrays": arrays}

    def restore_prefix(self, ckpt: Dict) -> int:
        """Restore a :meth:`checkpoint_prefix` into THIS (fresh)
        cache: allocate pages, write the saved KV bytes into the new
        pool at the remapped ids (one jitted donated scatter — the
        pool is not re-materialized eagerly), and rebuild the trie so
        future admissions prefix-HIT the restored pages. The bootstrap
        allocation references are dropped once the trie holds its own
        (alloc/free symmetry: the trie ends up owning exactly one
        reference per page, as :meth:`register_prefix` would leave
        it). Returns the number of pages restored."""
        self._refuse_window("restore_prefix (restore)")
        if self.prefix is None:
            raise ValueError(
                "restore_prefix into a cache with prefix caching "
                "disabled (enable_prefix_cache=False)")
        old_ids = [int(p) for p in ckpt["page_ids"]]
        fresh = self.allocator.alloc(len(old_ids))
        page_map = dict(zip(old_ids, fresh))
        self._scatter_pages(ckpt["arrays"], fresh)
        self.prefix.restore_records(ckpt["records"], page_map,
                                    self.allocator)
        self.allocator.free(fresh)      # the trie owns the pages now
        return len(fresh)

    def _scatter_pages(self, arrays: Dict, dst: Sequence[int]):
        """Write per-array page payloads into the pool at ids ``dst``
        through the shared donated :func:`_pool_scatter` program (one
        compile per payload shape; carried across supervisor rebuilds
        like the CoW copier)."""
        import jax
        import jax.numpy as jnp
        if self._scatter_fn is None:
            kw = {}
            if self.mesh is not None:
                # keep the pool's kv-head sharding through the donated
                # update: without the constraint the compiler may pick
                # a fresh layout and the next shard_map step would
                # silently pay a reshard of the whole pool
                from jax.sharding import NamedSharding
                kw["out_shardings"] = {
                    n: NamedSharding(self.mesh, self.pool_specs[n])
                    for n in self.pool}
            self._scatter_fn = jax.jit(_pool_scatter,
                                       donate_argnums=(0,), **kw)
        self.pool = self._scatter_fn(
            self.pool,
            {n: np.ascontiguousarray(a) for n, a in arrays.items()},
            jnp.asarray(np.asarray(dst, np.int32)))

    def _move_pages(self, src_ids: Sequence[int], dst_ids: Sequence[int],
                    src_cache: Optional["PagedKVCache"] = None):
        """Run the fused :func:`_pool_move` program: pages ``src_ids``
        (of this pool, or of ``src_cache``'s pool) copied into this
        pool's ``dst_ids`` in one donated device program — no host
        staging, no re-materialized pool. Compiled once per id-count
        (the `_scatter_pages` contract) and carried across supervisor
        rebuilds like the CoW/scatter programs."""
        import jax
        import jax.numpy as jnp
        kw = {}
        if self.mesh is not None:
            # keep the kv-head sharding through the donated update
            # (same reasoning as _scatter_pages)
            from jax.sharding import NamedSharding
            kw["out_shardings"] = {
                n: NamedSharding(self.mesh, self.pool_specs[n])
                for n in self.pool}
        src = jnp.asarray(np.asarray(src_ids, np.int32))
        dst = jnp.asarray(np.asarray(dst_ids, np.int32))
        t0 = _obs.generate_begin()
        if src_cache is None:
            if self._move_fn is None:
                self._move_fn = jax.jit(
                    lambda pool, s, d: _pool_move(pool, s, d),
                    donate_argnums=(0,), **kw)
            self.pool = self._move_fn(self.pool, src, dst)
        else:
            if self._move_from_fn is None:
                self._move_from_fn = jax.jit(
                    lambda pool, sp, s, d: _pool_move(
                        pool, s, d, src_pool=sp),
                    donate_argnums=(0,), **kw)
            self.pool = self._move_from_fn(self.pool, src_cache.pool,
                                           src, dst)
        self.direct_moves_total += 1
        _obs.serving_fused_latency("pool_move",
                                   t0, next(iter(self.pool.values())))

    def import_request_direct(self, slot: int,
                              src_cache: "PagedKVCache", src_slot: int,
                              total_tokens: int) -> np.ndarray:
        """The IN-PROCESS fast path of the prefill→decode handoff
        (ISSUE 11): admit ``slot`` and copy the source slot's live
        pages straight from ``src_cache``'s pool into freshly allocated
        pages through the fused :func:`_pool_move` — one donated device
        program instead of the ``export_request`` (device→host raw
        bytes) → ``import_request`` (host→device scatter) pair.
        Byte-identical to the host-staged handoff by construction (the
        same pool bytes land at the same logical positions); geometry
        is validated as loudly. The source slot is read-only — the
        exporting engine still owns it until ``finish_handoff``."""
        self._refuse_window("import_request_direct (the fabric's direct handoff)")
        if not src_cache.active[src_slot]:
            raise ValueError(
                f"import_request_direct: source slot {src_slot} is "
                f"inactive")
        length = int(src_cache.lengths[src_slot])
        if length <= 0:
            raise ValueError(
                f"import_request_direct: source slot {src_slot} has no "
                f"committed tokens — hand off only after prefill "
                f"completes")
        if src_cache.page_size != self.page_size:
            raise ValueError(
                f"import_request_direct: source page_size="
                f"{src_cache.page_size} != pool page_size="
                f"{self.page_size} — prefill and decode replicas must "
                f"share page geometry")
        if set(src_cache.pool) != set(self.pool):
            raise ValueError(
                f"import_request_direct: source arrays "
                f"{sorted(src_cache.pool)} != pool arrays "
                f"{sorted(self.pool)} — kv-dtype tiers of the two "
                f"replicas differ")
        for name, arr in self.pool.items():
            other = src_cache.pool[name]
            if (str(other.dtype) != str(arr.dtype)
                    or other.shape[0] != arr.shape[0]
                    or other.shape[2:] != arr.shape[2:]):
                raise ValueError(
                    f"import_request_direct: source {name} "
                    f"{other.dtype}{tuple(other.shape)} does not match "
                    f"pool page geometry {arr.dtype}"
                    f"{tuple(arr.shape)}")
        n = self._check_admit(slot, total_tokens)
        k = src_cache.pages_for(length)
        if k > n:
            raise ValueError(
                f"import_request_direct: source holds {k} pages but "
                f"total_tokens={total_tokens} only budgets {n}")
        src_ids = src_cache._slot_pages[src_slot][:k]
        pages = self._alloc_with_evict(n)
        try:
            self._move_pages(src_ids, pages[:k], src_cache=src_cache)
        except Exception:
            self.allocator.free(pages)
            raise
        return self._install(slot, pages)

    # ---- KV handoff (ISSUE 9): per-request page export/import ----
    def export_request(self, slot: int) -> Dict:
        """Export one ACTIVE slot's live KV pages as a serializable
        handoff payload — the prefill→decode transfer unit of the
        disaggregated cluster, generalizing :meth:`checkpoint_prefix`
        from trie chains to an ARBITRARY per-request block table. Only
        the pages covering ``lengths[slot]`` tokens travel (the tail
        reservation holds no KV yet); array bytes ride as raw uint8
        views + dtype/shape metadata so extension dtypes (bf16) and
        cross-host transports round-trip exactly. Pure read — the
        slot's pages, tables and refcounts are untouched."""
        self._refuse_window("export_request (the fabric's handoff)")
        if not self.active[slot]:
            raise ValueError(f"export_request of inactive slot {slot}")
        length = int(self.lengths[slot])
        if length <= 0:
            raise ValueError(
                f"export_request of slot {slot} with no committed "
                f"tokens — hand off only after prefill completes")
        k = self.pages_for(length)
        sel = np.asarray(self._slot_pages[slot][:k], np.int32)
        arrays: Dict[str, np.ndarray] = {}
        meta: Dict[str, Dict] = {}
        for name, arr in self.pool.items():
            a = np.ascontiguousarray(np.asarray(arr[:, sel]))
            arrays[name] = np.frombuffer(a.tobytes(), np.uint8)
            meta[name] = {"shape": list(a.shape), "dtype": str(a.dtype)}
        # integrity (ISSUE 13): per-array CRCs computed at export time
        # — import_request verifies before any scatter, so a payload
        # corrupted in transit is a loud CorruptionDetected at the
        # decode door, never a silently-wrong KV page
        from .resilience import payload_checksums
        return {"page_size": self.page_size, "num_pages": k,
                "length": length, "arrays": arrays, "meta": meta,
                "checksums": payload_checksums(arrays)}

    def import_request(self, slot: int, payload: Dict,
                       total_tokens: int) -> np.ndarray:
        """Admit ``slot`` with the full ``total_tokens`` page budget and
        scatter a :meth:`export_request` payload's KV bytes into the
        leading pages (the shared donated :func:`_pool_scatter`
        program) — the decode-side half of the prefill→decode handoff,
        BIT-identical to having prefilled in place (raw bytes in, raw
        bytes out; page ids differ but the block table makes content
        position-addressed). Geometry and dtype are validated LOUDLY
        before any allocation; returns the slot's block-table row.
        Callers set ``lengths[slot]`` from the payload. The payload's
        per-array checksums (stamped by :meth:`export_request`) are
        verified BEFORE any allocation or scatter — a corrupt or torn
        payload raises
        :class:`~paddle_tpu.serving.CorruptionDetected` with nothing
        committed (ISSUE 13)."""
        self._refuse_window("import_request (the fabric's handoff)")
        from .resilience import _np_dtype, verify_checksums
        verify_checksums(payload["arrays"], payload.get("checksums"),
                         "handoff_import")
        n = self._check_admit(slot, total_tokens)
        k = int(payload["num_pages"])
        if payload["page_size"] != self.page_size:
            raise ValueError(
                f"import_request: payload page_size="
                f"{payload['page_size']} != pool page_size="
                f"{self.page_size} — prefill and decode replicas must "
                f"share page geometry")
        if k > n:
            raise ValueError(
                f"import_request: payload holds {k} pages but "
                f"total_tokens={total_tokens} only budgets {n}")
        if set(payload["meta"]) != set(self.pool):
            raise ValueError(
                f"import_request: payload arrays "
                f"{sorted(payload['meta'])} != pool arrays "
                f"{sorted(self.pool)} — kv-dtype tiers of the two "
                f"replicas differ")
        arrays = {}
        for name, m in payload["meta"].items():
            if m["dtype"] != str(self.pool[name].dtype):
                raise ValueError(
                    f"import_request: payload {name} dtype "
                    f"{m['dtype']} != pool dtype "
                    f"{self.pool[name].dtype} — a silent cast would "
                    f"break the handoff bit-identity gate")
            a = np.frombuffer(bytes(payload["arrays"][name]),
                              _np_dtype(m["dtype"])).reshape(m["shape"])
            want = self.pool[name].shape
            got = tuple(a.shape)
            if got[0] != want[0] or got[1] != k or got[2:] != want[2:]:
                raise ValueError(
                    f"import_request: payload {name} shape {got} does "
                    f"not match pool page shape "
                    f"{(want[0], k) + tuple(want[2:])}")
            arrays[name] = a
        pages = self._alloc_with_evict(n)
        try:
            self._scatter_pages(arrays, pages[:k])
        except Exception:
            self.allocator.free(pages)
            raise
        return self._install(slot, pages)

    def defrag(self):
        """Compact used pages to the front of the pool: ONE donated
        fused gather+scatter (:func:`_pool_move` — ISSUE 11; the old
        implementation re-materialized every pool array with a
        full-pool ``jnp.take``, paying the whole pool's HBM to move a
        handful of pages) moves only the LIVE pages in place, block
        tables (and the prefix trie's held pages) are remapped on the
        host, and the free list becomes the contiguous tail. Shared
        pages move like any other — every reference (tables,
        ``_slot_pages``, trie nodes/tails) is rewritten atomically, so
        no live table is left pointing at a vacated id. Unused
        destination pages keep their (dead) contents — nothing
        references them. The move's id vectors pad to a power-of-two
        bucket with trash-page self-copies, bounding the compile count.
        Keeps long-running servers' pools dense after many
        admit/retire cycles (the allocator's ``fragmentation()`` stat
        measures the holes this closes)."""
        self._refuse_window("defrag")
        used = {p for pages in self._slot_pages for p in pages}
        if self.prefix is not None:
            used |= set(self.prefix.pages())
        used = sorted(used)
        remap = np.arange(self.num_pages, dtype=np.int32)
        moves = []                      # (src, dst) for pages that move
        for new_id, old_id in enumerate(used, start=self.allocator.reserved):
            remap[old_id] = new_id
            if old_id != new_id:
                moves.append((old_id, new_id))
        if moves:
            n = 1
            while n < len(moves):
                n *= 2
            moves += [(TRASH_PAGE, TRASH_PAGE)] * (n - len(moves))
            self._move_pages([m[0] for m in moves],
                             [m[1] for m in moves])
        self.block_tables = np.where(
            self.block_tables == TRASH_PAGE, TRASH_PAGE,
            remap[self.block_tables]).astype(np.int32)
        self._slot_pages = [[int(remap[p]) for p in pages]
                            for pages in self._slot_pages]
        alloc = self.allocator
        new_rc = np.zeros_like(alloc._refcount)
        for old_id in used:
            new_rc[remap[old_id]] = alloc._refcount[old_id]
        alloc._refcount = new_rc
        if self.prefix is not None:
            self.prefix.remap_pages(remap)
        first_free = alloc.reserved + len(used)
        alloc._free = list(range(self.num_pages - 1, first_free - 1, -1))
        alloc.defrags_total += 1
