"""Paged KV cache: refcounted page pool + free-list allocator + page
tables + prefix index.

The dense decode cache (``gpt_cached_apply``) charges every admitted
request ``S_max`` positions of HBM for its whole lifetime. Here the
cache is a pool of fixed-size pages shared by all slots; a request
holds ``ceil(len/page_size)`` pages and returns them at eviction, so
pool HBM tracks live tokens and a freed request's pages are reusable
immediately — the allocation granularity that makes continuous
batching admission-feasible mid-flight ("Ragged Paged Attention",
PAPERS.md).

Device state (``Pools``, held by ``PagePool.pools``): per-layer
key/value pools stacked ``[L, num_pages, page_size, NH, D]``, plus
per-page per-head dequant scales ``[L, num_pages, NH]`` when the pools
are int8. One page id addresses the same page row in every layer, so
the allocator hands out a single id per page regardless of depth.
``Pools`` is the ONE place that knows this format: the tick, the spec
verify tick, the COW copy and the KV handoff pass it whole and reach
its content through its methods.

Host state (``PageAllocator``): a LIFO free list over ids
``1..num_pages-1`` with a **refcount per allocated page**. ``alloc``
hands out pages at refcount 1; ``share`` lets a second holder (another
slot's page table, or the prefix index) alias the same page; ``free``
decrements and only returns the page to the free list at refcount 0.
**Page 0 is reserved as the null page**: inactive slots' table entries
point at it, decode-tick writes for inactive slots land in it, and
gathers through unallocated table entries read it (always masked).
LIFO reuse is deliberate — it maximizes the chance a test (or a bug)
sees a dirty page straight after free, which is exactly what the
no-cross-request-leakage test pins down.

Prefix index (``PrefixCache``): a hash-trie keyed on page-aligned
token chunks. A request's fully-written prompt pages are inserted as a
chain ``chunk -> page id``; admission walks the trie with the new
prompt and aliases every matched page instead of re-prefilling it.
Indexed pages are **immutable by construction** — writes only ever
target positions at or beyond the write frontier, and a page enters
the index only once the frontier has passed it — so sharing is safe
without copies, except for one case: a prompt that diverges from a
cached chunk mid-page can still reuse the agreeing positions by
**copy-on-write** (the engine copies the cached page into a fresh one
and overwrites from the divergence point). The index holds one
refcount per cached page; unreferenced cached pages (refcount 1, index
only) are evicted LRU leaf-first when the allocator runs dry.

Allocation, sharing and freeing are host-side bookkeeping only — no
device op; the tables are tiny int32 arrays shipped with each tick's
arguments.

Pools of other formats follow ``Pools`` / ``PagePool`` below, each the one
place that knows its format (``POOL_KINDS`` maps a ``cache_spec()``'s kind
to its pool): ``LatentPools`` / ``LatentPagePool`` (latent rows, indexer
keys, windowed latents), ``GroupedPools`` (fewer key/value heads than query
heads), ``WindowedKVPools`` / ``WindowedKVPagePool`` (grouped K/V pages of
full and of windowed layers) and ``StatePools`` / ``StatePagePool`` (pages
beside a recurrent state a slot). **A window's page space lives in one
place, ``WindowSpace``**: the second allocator and tables that hold only
the window, ``grow_slot`` over both spaces or neither, ``free_behind``,
``release_slot``, ``row_tables``, ``live_shares`` and its half of
``check_consistency``; ``LatentPagePool`` and ``WindowedKVPagePool`` mix it
in (ISSUE 57: it was ``LatentPagePool``'s own until a second kind of pool
needed it).
"""
from __future__ import annotations

import hashlib
import heapq
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..ops.gdn import (conv_slot_rows, gdn_chunk_rows, gdn_prep_rows,
                       gdn_step_rows, pack_state, unpack_state)
from ..ops.latent_attention import (
    index_scores, latent_attention, latent_scatter,
    selected_latent_attention, window_latent_attention)
from ..ops.paged_attention import (
    grouped_kv_scatter, grouped_paged_attention, paged_kv_scatter,
    ragged_paged_attention)
from ..ops.ssd import ssd_chunk_rows, ssd_prep_rows, ssd_step_rows

NULL_PAGE = 0

#: hex chars kept per chunk-chain hash (blake2b); 16 hex chars = 64
#: bits — collision-safe for any realistic mesh index size, and short
#: enough that a whole digest rides a consensus vote as plain JSON.
CHAIN_HASH_LEN = 16


def chain_hash(parent_hash: str, chunk) -> str:
    """Stable hash of one page-aligned chunk IN ITS CHAIN CONTEXT:
    ``blake2b(parent_hash_bytes || chunk_token_bytes)``. Two ranks that
    cached the same prompt prefix compute the same chain of hashes
    (never Python ``hash()`` — that is salted per process), which is
    what lets the mesh index match prefixes by digest without ever
    shipping token bytes."""
    h = hashlib.blake2b(digest_size=16)
    h.update(parent_hash.encode("ascii"))
    h.update(np.asarray(list(chunk), np.int64).tobytes())
    return h.hexdigest()[:CHAIN_HASH_LEN]


def chain_hashes(tokens, page_size: int) -> List[str]:
    """Chunk-hash chain of every FULL page of ``tokens`` — the key a
    router uses to ask "which rank has the longest cached prefix of
    this prompt". Matches the hashes :class:`PrefixCache` stores on its
    trie nodes, by construction."""
    toks = np.asarray(tokens).reshape(-1)
    ps = int(page_size)
    out: List[str] = []
    parent = ""
    for i in range(toks.shape[0] // ps):
        parent = chain_hash(parent, toks[i * ps:(i + 1) * ps])
        out.append(parent)
    return out


def _registry():
    from ..profiler import registry

    return registry()


class PageAllocator:
    """LIFO free-list over page ids 1..num_pages-1 (0 is the null page)
    with per-page refcounts for prefix sharing."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is reserved)")
        self.num_pages = int(num_pages)
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        # companion set: O(1) double-free detection (the list alone
        # would make release_slot O(pages_freed * free_list_len))
        self._free_set = set(self._free)
        self._ref: Dict[int, int] = {}       # allocated page -> refcount
        #: called with the list of pages whose LAST reference was just
        #: dropped (they are already back on the free list). The int8
        #: pool hooks this to queue a scale reset at free time instead
        #: of realloc time — a zero-freed page's stale running-max
        #: scale is scheduling history, not content (ISSUE 18).
        self.on_zero: Optional[Callable[[List[int]], None]] = None

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_allocated(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    def utilization(self) -> float:
        """Allocated fraction of the allocatable pool (null page excluded)."""
        return self.num_allocated / max(self.num_pages - 1, 1)

    def refcount(self, page: int) -> int:
        """Current refcount of ``page`` (0 when free/never allocated)."""
        return self._ref.get(int(page), 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n page ids at refcount 1, or None (and no state change) if the
        pool can't cover the request — admission control needs
        all-or-nothing."""
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(out)
        for i in out:
            self._ref[i] = 1
        return out

    def share(self, ids) -> None:
        """Add one reference to each (already allocated) page — a second
        page table or the prefix index now aliases it."""
        shared = 0
        for i in ids:
            i = int(i)
            if i == NULL_PAGE:
                raise ValueError("page 0 (null page) is not shareable")
            if i not in self._ref:
                raise ValueError(f"share of unallocated page {i}")
            self._ref[i] += 1
            shared += 1
        if shared:
            _registry().counter("cache_share/shares").add(shared)

    def free(self, ids) -> None:
        """Drop one reference per page; a page returns to the free list
        only when its refcount reaches 0. Freeing an unallocated page
        raises (double-free of the LAST reference is a bug; releasing a
        still-shared page is the normal sharing path)."""
        released = 0
        zeroed: List[int] = []
        for i in ids:
            i = int(i)
            if i == NULL_PAGE:
                raise ValueError("page 0 (null page) is not allocatable")
            if i in self._free_set or i not in self._ref:
                raise ValueError(f"double free of page {i}")
            self._ref[i] -= 1
            if self._ref[i] == 0:
                del self._ref[i]
                self._free.append(i)
                self._free_set.add(i)
                zeroed.append(i)
            else:
                released += 1
        if released:
            _registry().counter("cache_share/releases").add(released)
        if zeroed and self.on_zero is not None:
            self.on_zero(zeroed)


class _TrieNode:
    __slots__ = ("chunk", "page", "children", "first_ix", "parent",
                 "last_use", "hash", "depth")

    def __init__(self, chunk: Tuple[int, ...], page: int,
                 parent: Optional["_TrieNode"]):
        self.chunk = chunk
        self.page = page
        self.children: Dict[Tuple[int, ...], "_TrieNode"] = {}
        # chunk[0] -> child nodes: partial-match (COW) candidates. A
        # long-lived server accumulates one child per distinct suffix
        # under a shared-prompt node; scanning ALL of them per
        # admission would grow with history, while an LCP >= 1 match
        # must share the first token — so the common miss is one dict
        # probe.
        self.first_ix: Dict[int, List["_TrieNode"]] = {}
        self.parent = parent
        self.last_use = 0
        # chain hash + chain depth (root = depth 0): the digest the
        # mesh index publishes for this node (ISSUE 18)
        if parent is None:
            self.hash, self.depth = "", 0
        else:
            self.hash = chain_hash(parent.hash, chunk)
            self.depth = parent.depth + 1


class PrefixCache:
    """Hash-trie prefix index over page-aligned token chunks.

    Each node maps one ``page_size``-token chunk (in its parent's
    context) to the pool page holding that chunk's KV. The index owns
    one refcount per cached page; ``evict_for`` walks unreferenced
    leaves (refcount 1 — nobody but the index holds them) in LRU order
    when the allocator needs pages back. Lookup matches whole chunks
    along the trie, then optionally one **partial** chunk (longest
    common prefix against a child's tokens) for the engine's
    copy-on-write tail path.
    """

    def __init__(self, page_size: int, allocator: PageAllocator):
        self.page_size = int(page_size)
        self.allocator = allocator
        self._root = _TrieNode((), NULL_PAGE, None)
        self._clock = 0
        #: structural revision — bumps whenever the set of indexed
        #: chains changes (insert of a NEW node, any drop), so a
        #: publisher can skip recomputing/re-voting an unchanged
        #: digest on every heartbeat (ISSUE 18)
        self.rev = 0
        #: called as ``on_drop(chain_hash, n_tokens)`` when an indexed
        #: chain node is evicted, BEFORE its page goes back to the
        #: allocator — the hook a mesh-published rank uses to withdraw
        #: the digest from the board before the page is reclaimable
        #: (ISSUE 18: no routing to a stale digest).
        self.on_drop: Optional[Callable[[str, int], None]] = None

    def __len__(self) -> int:
        n, stack = 0, list(self._root.children.values())
        while stack:
            node = stack.pop()
            n += 1
            stack.extend(node.children.values())
        return n

    def _touch(self, node: _TrieNode) -> None:
        self._clock += 1
        node.last_use = self._clock

    def lookup(self, tokens: np.ndarray):
        """Longest cached prefix of ``tokens``, capped at ``len - 1``
        (at least the last prompt position must be recomputed — its
        logits seed decoding).

        Returns ``(full_pages, partial)`` where ``full_pages`` is the
        page id per fully-matched chunk (in order) and ``partial`` is
        ``(page_id, lcp_len)`` for a chunk whose first ``lcp_len``
        tokens agree with the remainder (COW candidate), or None."""
        toks = np.asarray(tokens).reshape(-1)
        usable = toks.shape[0] - 1
        ps = self.page_size
        pages: List[int] = []
        node = self._root
        while (len(pages) + 1) * ps <= usable:
            key = tuple(int(t) for t in
                        toks[len(pages) * ps:(len(pages) + 1) * ps])
            nxt = node.children.get(key)
            if nxt is None:
                break
            node = nxt
            self._touch(node)
            pages.append(node.page)
        partial = None
        rem = usable - len(pages) * ps
        if rem > 0:
            rem_toks = toks[len(pages) * ps:len(pages) * ps + rem]
            best, best_child = 0, None
            for child in node.first_ix.get(int(rem_toks[0]), []):
                lcp = 0
                for a, b in zip(child.chunk, rem_toks):
                    if a != b:
                        break
                    lcp += 1
                if lcp > best:
                    best, best_child = lcp, child
                    if lcp == rem:
                        break
            if best_child is not None:
                self._touch(best_child)
                partial = (best_child.page, best)
        return pages, partial

    def insert(self, tokens: np.ndarray, pages) -> int:
        """Register ``pages[i]`` as holding the KV of chunk ``i`` of
        ``tokens`` (which must cover ``len(pages)`` full chunks). Pages
        already cached under the same chunk chain are left alone (the
        first tenant wins). Returns how many pages were newly indexed
        (each takes one index refcount)."""
        toks = np.asarray(tokens).reshape(-1)
        ps = self.page_size
        if len(pages) * ps > toks.shape[0]:
            raise ValueError("insert needs one full chunk per page")
        parent = self._root
        new = 0
        for i, page in enumerate(pages):
            key = tuple(int(t) for t in toks[i * ps:(i + 1) * ps])
            node = parent.children.get(key)
            if node is None:
                node = _TrieNode(key, int(page), parent)
                parent.children[key] = node
                parent.first_ix.setdefault(key[0], []).append(node)
                self.allocator.share([int(page)])
                new += 1
            self._touch(node)
            parent = node
        if new:
            self.rev += 1
        return new

    def _evictable_leaves(self) -> List[_TrieNode]:
        out, stack = [], list(self._root.children.values())
        while stack:
            node = stack.pop()
            if node.children:
                stack.extend(node.children.values())
            elif self.allocator.refcount(node.page) == 1:
                out.append(node)
        return out

    def _drop(self, node: _TrieNode) -> None:
        parent = node.parent
        del parent.children[node.chunk]
        bucket = parent.first_ix[node.chunk[0]]
        bucket.remove(node)
        if not bucket:
            del parent.first_ix[node.chunk[0]]
        # withdraw-before-reclaim: the hook must run while the index
        # still holds its reference — a router acting on the stale
        # digest one instant later must never find the page recycled
        # under it without the withdrawal having been recorded first
        if self.on_drop is not None:
            self.on_drop(node.hash, node.depth * self.page_size)
        self.rev += 1
        self.allocator.free([node.page])

    def evict_for(self, n: int) -> int:
        """Free up to ``n`` pages by evicting unreferenced cached pages,
        LRU leaf-first (evicting a mid-chain node would orphan its
        children's pages). One DFS collects the candidates; dropping a
        leaf can only newly expose its own parent, so the frontier is
        maintained incrementally instead of re-walking the trie per
        page. Returns how many pages were actually freed."""
        frontier = [(nd.last_use, id(nd), nd)
                    for nd in self._evictable_leaves()]
        heapq.heapify(frontier)
        freed = 0
        while freed < n and frontier:
            _, _, victim = heapq.heappop(frontier)
            parent = victim.parent
            self._drop(victim)
            freed += 1
            if parent is not self._root and not parent.children and \
                    self.allocator.refcount(parent.page) == 1:
                heapq.heappush(frontier,
                               (parent.last_use, id(parent), parent))
        if freed:
            _registry().counter("cache_share/prefix_evictions").add(freed)
        return freed

    def digest(self) -> Dict[str, object]:
        """JSON-able digest of every cached chain node: chunk-hash ->
        token count (``depth * page_size``). Digests — never token or
        page bytes — are what a rank publishes to the mesh index
        (ISSUE 18): small enough to ride a consensus vote, stable
        across processes, and sufficient for a router to compute the
        longest published prefix of any prompt via
        :func:`chain_hashes`."""
        chains: Dict[str, int] = {}
        stack = list(self._root.children.values())
        while stack:
            node = stack.pop()
            chains[node.hash] = node.depth * self.page_size
            stack.extend(node.children.values())
        return {"page_size": self.page_size, "chains": chains}

    def chain_pages(self, tokens) -> Tuple[List[int], List[str]]:
        """Walk the trie along the FULL chunks of ``tokens`` and return
        ``(pages, hashes)`` of the matched chain — the export side of
        hot-chain migration (no ``len - 1`` cap, no partial/COW leg:
        only whole indexed pages can be shipped). Touches the matched
        nodes (a migrating chain is hot by definition)."""
        toks = np.asarray(tokens).reshape(-1)
        ps = self.page_size
        pages: List[int] = []
        hashes: List[str] = []
        node = self._root
        for i in range(toks.shape[0] // ps):
            key = tuple(int(t) for t in toks[i * ps:(i + 1) * ps])
            nxt = node.children.get(key)
            if nxt is None:
                break
            node = nxt
            self._touch(node)
            pages.append(node.page)
            hashes.append(node.hash)
        return pages, hashes

    def pages(self) -> List[int]:
        """Every page id the index currently holds a refcount on (one
        per node) — the prefix leg of ``PagePool.check_consistency``."""
        out, stack = [], list(self._root.children.values())
        while stack:
            node = stack.pop()
            out.append(node.page)
            stack.extend(node.children.values())
        return out

    def clear(self) -> int:
        """Drop every index entry (still-shared pages lose only the
        index's refcount and survive in their slots). Returns the
        number of entries dropped."""
        dropped = 0
        stack = list(self._root.children.values())
        order: List[_TrieNode] = []
        while stack:
            node = stack.pop()
            order.append(node)
            stack.extend(node.children.values())
        for node in reversed(order):     # children before parents
            self._drop(node)
            dropped += 1
        return dropped


class Pools(NamedTuple):
    """The page pools as the device holds them, and the only code that
    knows their format. A pytree: a program takes and returns it as ONE
    argument, and a ``None`` leaf contributes no parameter, so the
    un-quantized tick's parameters are ``k`` and ``v`` alone.

    k, v              ``[L, P, ps, NH, D]`` page pools (f32/bf16/int8)
    k_scale, v_scale  ``[L, P, NH]`` f32 per-page per-head dequant
                      scales of int8 pools (ISSUE 12); ``None`` otherwise

    Every array indexes layers on axis 0 and pages on axis 1, so the
    page-granular programs (copy, gather, write) are one ``tree.map``.
    The tick's ``lax.scan`` over layers CARRIES the whole tuple and its
    block calls ``scatter(layer, ...)`` and ``attend(layer, ...)``, the
    cache's write and read sides: the layer is one more index of their
    one scatter and one gather, no operation's result is a layer of a
    pool, and XLA updates the donated stacks in place (ROADMAP S3: as
    ``xs -> ys`` every step sliced 2 x a layer out and wrote 2 x a layer
    into a second stack).
    Page 0 (null) keeps scale 0 forever (masked contributions). Page
    CONTENT is deliberately never cleared on free (LIFO dirty reuse is
    a feature), but a recycled page's STALE SCALE would poison the
    running-max of its next tenant: ``PagePool`` lists fresh pages and
    the tick resets them first (``reset_scales``)."""

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None

    @classmethod
    def zeros(cls, num_layers: int, num_pages: int, page_size: int,
              num_heads: int, head_dim: int, dtype) -> "Pools":
        shape = (num_layers, num_pages, page_size, num_heads, head_dim)
        pools = cls(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
        if jnp.dtype(dtype) != jnp.int8:
            return pools
        sshape = (num_layers, num_pages, num_heads)
        return pools._replace(k_scale=jnp.zeros(sshape, jnp.float32),
                              v_scale=jnp.zeros(sshape, jnp.float32))

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def page_size(self) -> int:
        return self.k.shape[-3]

    def arrays(self) -> Dict[str, jax.Array]:
        """The arrays present, by field name (the KV handoff's keys)."""
        return {n: a for n, a in self._asdict().items() if a is not None}

    def reset_scales(self, pages) -> "Pools":
        """Restart the running-max scale of ``pages`` at 0 (pads with
        the null page, whose scale is 0 anyway). The identity without
        scales, or with ``pages`` None."""
        if pages is None or not self.quantized:
            return self
        return self._replace(k_scale=self.k_scale.at[:, pages].set(0.0),
                             v_scale=self.v_scale.at[:, pages].set(0.0))

    def copy_page(self, src, dst) -> "Pools":
        """Copy-on-write: duplicate page ``src`` into ``dst`` across all
        layers. The donor's scales travel with its content (dequantizing
        the copied int8 values needs the SAME scales; the engine
        un-lists ``dst`` from the fresh-page reset so the next tick
        cannot zero them)."""
        return jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), self)

    def gather_pages(self, pages) -> "Pools":
        """Pages ``pages`` of every layer: the export side of a handoff."""
        return jax.tree.map(lambda a: a[:, pages], self)

    def write_pages(self, pages, content: "Pools") -> "Pools":
        """``content`` (as ``gather_pages`` gave it) written at ``pages``."""
        return jax.tree.map(lambda a, c: a.at[:, pages].set(c), self,
                            content)

    # -- one layer of the stacks, by index, inside the tick's scan ------
    def scatter(self, layer, page, off, kk, vv) -> "Pools":
        """Write each token's key and value (``kk``/``vv`` [NT, 1, NH,
        D], the block's flat one-position rows) at its ``(layer, page,
        off)``, quantizing on write where there are scales. ``layer``
        may be traced; the result is the whole stacks."""
        k, k_scale = paged_kv_scatter(self.k, self.k_scale, page, off,
                                      kk[:, 0], layer=layer)
        v, v_scale = paged_kv_scatter(self.v, self.v_scale, page, off,
                                      vv[:, 0], layer=layer)
        return Pools(k, v, k_scale, v_scale)

    def attend(self, layer, q, page_table, pos0, true_len):
        """``ragged_paged_attention`` of ``q`` over ``layer``'s pages, in the
        spelling the platform picks where the tick is traced."""
        return ragged_paged_attention(
            q, self.k, self.v, page_table, pos0, true_len,
            k_scale=self.k_scale, v_scale=self.v_scale, layer=layer)


def _rows_of(tables: np.ndarray, rows) -> np.ndarray:
    out = np.zeros((len(rows), tables.shape[1]), np.int32)
    for i, slot in enumerate(rows):
        if slot is not None:
            out[i] = tables[slot]
    return out


class PagePool:
    """Device page pools for all layers + host page tables for all slots."""

    def __init__(self, num_layers: int, num_pages: int, page_size: int,
                 num_heads: int, head_dim: int, num_slots: int,
                 pages_per_slot: int, dtype=jnp.float32,
                 prefix_cache: bool = False, pools=None):
        self.num_layers = num_layers
        self.num_pages = num_pages
        self.page_size = page_size
        self.num_heads = num_heads
        self.head_dim = head_dim
        self.num_slots = num_slots
        self.pages_per_slot = pages_per_slot
        #: the device state, donated to and stored back from every
        #: dispatch that writes it (tick, COW copy, import)
        self.pools = pools if pools is not None else Pools.zeros(
            num_layers, num_pages, page_size, num_heads, head_dim, dtype)
        self.allocator = PageAllocator(num_pages)
        # pages allocated or zero-freed since the last tick, whose
        # scales that tick resets (``take_fresh``); scales only
        self._fresh: List[int] = []
        self.allocator.on_zero = self._on_zero_free
        # pages that arrived via cross-rank chain migration (ISSUE 18):
        # host-side provenance so a prefix hit on one can be counted as
        # a REMOTE hit (the evidence the bench asserts on)
        self.migrated_pages: set = set()
        self.prefix: Optional[PrefixCache] = (
            PrefixCache(page_size, self.allocator) if prefix_cache
            else None)
        # host copy of the per-slot page tables; rows of evicted slots
        # are zeroed (null page) so stale ids can never be gathered
        self.tables = np.zeros((num_slots, pages_per_slot), np.int32)
        # pages held per slot, in position order (prefix of the table row)
        self._held: List[List[int]] = [[] for _ in range(num_slots)]
        # auxiliary page tables (the spec-decode draft KV) drawing from
        # the SAME allocator: registered so check_consistency can
        # account for their holds (ISSUE 20)
        self._aux: List["AuxPageTable"] = []

    @classmethod
    def for_spec(cls, caches: dict, num_pages: int, page_size: int,
                 num_slots: int, pages_per_slot: int, chunk: int, dtype,
                 prefix_cache: bool) -> "PagePool":
        """The pool of a ``cache_spec()`` of this kind (``page_pool``)."""
        del chunk
        return cls(caches["layers"], num_pages, page_size, caches["heads"],
                   caches["head_dim"], num_slots, pages_per_slot,
                   dtype=dtype, prefix_cache=prefix_cache)

    # read-only views of the device state; a writer replaces ``pools``
    k = property(lambda self: self.pools.k)
    v = property(lambda self: self.pools.v)
    k_scale = property(lambda self: self.pools.k_scale)
    v_scale = property(lambda self: self.pools.v_scale)
    quantized = property(lambda self: self.pools.quantized)

    def live_shares(self) -> Dict[str, float]:
        """Allocated share of each kind of pool's pages (the engine's
        ``serving/live_pages{pool=}`` gauges)."""
        return {"kv": self.allocator.utilization()}

    def row_tables(self, rows) -> np.ndarray:
        """The page-table rows of a tick: one per entry of ``rows``, a
        slot or ``None`` (an all-null row)."""
        return _rows_of(self.tables, rows)

    def register_aux(self, aux: "AuxPageTable") -> None:
        """Register an auxiliary table whose pages come from this
        pool's allocator — its holds join the consistency audit."""
        self._aux.append(aux)

    #: what an engine may ask of its pool that this kind cannot do, each
    #: with why (``require``): K and V pools do all of it
    CANNOT: Dict[str, str] = {}

    @classmethod
    def require(cls, what: str, doing: str) -> None:
        """Refuses, in this kind of pool's own words, ``doing`` what needs
        ``what`` of it: ``"rewinds"`` (speculative decoding), ``"int8"``
        pages, a page ``"handoff"``."""
        if what in cls.CANNOT:
            raise NotImplementedError(cls.CANNOT[what].format(doing=doing))

    def free_behind(self, slot: int, frontier: int) -> int:
        """Pages of ``slot`` that no query at or past ``frontier`` can see,
        given back: none here, every layer sees its whole slot."""
        return 0

    @property
    def slot_capacity(self) -> int:
        return self.pages_per_slot * self.page_size

    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def slot_pages(self, slot: int) -> int:
        return len(self._held[slot])

    def _on_zero_free(self, pages: List[int]) -> None:
        """Allocator hook: runs when pages drop their LAST reference.
        ISSUE 18 quantizer fix — queue the int8 scale reset at free
        time, not at the next allocation: a page parked on the free
        list must not carry its old tenant's running-max scale as
        latent scheduling history (the PR 13 "tolerance-by-contract"
        residue). ``take_fresh``/``claim_fresh`` already dedupe, so
        re-listing a page the next ``_alloc`` will list again is
        harmless. Migration provenance ends with the last reference
        too: a recycled page id is not a migrated page."""
        if self.quantized:
            self._fresh.extend(pages)
        if self.migrated_pages:
            self.migrated_pages.difference_update(pages)

    def _alloc(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` pages, evicting unreferenced prefix-cache
        pages LRU-first when the free list alone can't cover it."""
        got = self.allocator.alloc(n)
        if got is None and self.prefix is not None:
            self.prefix.evict_for(n - self.allocator.num_free)
            got = self.allocator.alloc(n)
        if got is not None and self.quantized:
            self._fresh.extend(got)
        return got

    # -- int8 scale lifecycle (quantized pools only) -------------------
    def take_fresh(self, cap: int) -> Optional[np.ndarray]:
        """Drain the freshly-allocated-page list into a fixed-size
        int32 vector (padded with the null page, whose scale is 0
        anyway) for the next tick's in-program scale reset; None where
        there are no scales to reset. Allocations
        beyond ``cap`` — which a correctly-sized cap never produces —
        are reset eagerly here instead of silently dropped (a dropped
        reset would leave a stale running-max scale on a recycled
        page)."""
        if not self.quantized:
            return None
        fresh, self._fresh = self._fresh, []
        if len(fresh) > cap:
            self.pools = self.pools.reset_scales(
                np.asarray(fresh[cap:], np.int32))
            fresh = fresh[:cap]
        out = np.zeros(cap, np.int32)
        out[:len(fresh)] = fresh
        return out

    def claim_fresh(self, page: int) -> None:
        """Remove ``page`` from the pending-reset list — its scale was
        just written by a device op (the COW copy duplicates the donor
        page's scale; resetting it afterwards would dequantize the
        copied content at scale 0). EVERY occurrence goes: an
        alloc→preempt-release→realloc cycle inside one scheduler step
        lists the same id twice, and a surviving duplicate would still
        zero the copied scales on the next tick."""
        if self.quantized:
            page = int(page)
            self._fresh = [p for p in self._fresh if p != page]

    def grow_slot(self, slot: int, n_pages: int) -> bool:
        """Extend ``slot`` by ``n_pages`` fresh pages; False (untouched)
        when the pool can't cover it."""
        if n_pages <= 0:
            return True
        held = self._held[slot]
        if len(held) + n_pages > self.pages_per_slot:
            raise ValueError(
                f"slot {slot} would exceed pages_per_slot="
                f"{self.pages_per_slot}")
        got = self._alloc(n_pages)
        if got is None:
            return False
        self.tables[slot, len(held):len(held) + n_pages] = got
        held.extend(got)
        return True

    def share_into_slot(self, slot: int, pages) -> None:
        """Alias already-allocated ``pages`` (a cached prefix) into the
        next table positions of ``slot``, taking one refcount each."""
        if not len(pages):
            return
        held = self._held[slot]
        if len(held) + len(pages) > self.pages_per_slot:
            raise ValueError(
                f"slot {slot} would exceed pages_per_slot="
                f"{self.pages_per_slot}")
        self.allocator.share(pages)
        self.tables[slot, len(held):len(held) + len(pages)] = \
            np.asarray(pages, np.int32)
        held.extend(int(p) for p in pages)

    def shrink_slot(self, slot: int, keep_pages: int) -> int:
        """Release the slot's pages BEYOND the first ``keep_pages``
        (position order — the speculative-rewind path: rejected draft
        tail tokens truncate the slot's frontier, and pages past the
        new length go back to the pool). Refcount-safe like
        ``release_slot``: only this slot's reference is dropped, so a
        page the prefix index (or another slot) still holds survives;
        the zeroed table tail means a stale id can never be gathered.
        No-op when the slot already holds ``<= keep_pages``. Returns
        how many page references were dropped."""
        if keep_pages < 0:
            raise ValueError("keep_pages must be >= 0")
        held = self._held[slot]
        drop = held[keep_pages:]
        if not drop:
            return 0
        self.allocator.free(drop)
        del held[keep_pages:]
        self.tables[slot, keep_pages:] = NULL_PAGE
        return len(drop)

    def release_slot(self, slot: int) -> int:
        """Drop ``slot``'s reference on all of its pages (a page only
        returns to the pool at refcount 0 — the prefix index or another
        slot may still hold it); zero the slot's table row. Idempotent:
        a second release of the same slot is a no-op (``_finish`` and
        preemption may both reach it), while over-freeing an individual
        page still raises inside the allocator. Returns how many page
        references were dropped."""
        held = self._held[slot]
        n = len(held)
        if n:
            self.allocator.free(held)
        self._held[slot] = []
        self.tables[slot, :] = NULL_PAGE
        return n

    def drop_prefix_cache(self) -> int:
        """Flush the prefix index (frees every unshared cached page);
        no-op without a prefix cache. Returns entries dropped."""
        return self.prefix.clear() if self.prefix is not None else 0

    def check_consistency(self) -> List[str]:
        """Audit the host-side invariants that every refcount edge —
        grow/share/COW/shrink/release, prefix insert/evict, and the
        ISSUE 13 export/import handoff path — must preserve. Returns a
        list of violation strings (empty = consistent); the multihost
        chaos tests assert a SURVIVOR's pool passes this after a peer
        died mid-handoff."""
        out = []
        holds: Dict[int, int] = {}
        for slot, held in enumerate(self._held):
            row = self.tables[slot]
            for i, pg in enumerate(held):
                holds[pg] = holds.get(pg, 0) + 1
                if int(row[i]) != pg:
                    out.append(f"slot {slot} table[{i}]={int(row[i])} "
                               f"!= held page {pg}")
            for i in range(len(held), self.pages_per_slot):
                if int(row[i]) != NULL_PAGE:
                    out.append(f"slot {slot} table[{i}]="
                               f"{int(row[i])} past the held prefix")
            if NULL_PAGE in held:
                out.append(f"slot {slot} holds the null page")
        if self.prefix is not None:
            for pg in self.prefix.pages():
                holds[pg] = holds.get(pg, 0) + 1
        for ax, aux in enumerate(self._aux):
            for slot, held in enumerate(aux._held):
                row = aux.tables[slot]
                for i, pg in enumerate(held):
                    holds[pg] = holds.get(pg, 0) + 1
                    if int(row[i]) != pg:
                        out.append(f"aux {ax} slot {slot} table[{i}]="
                                   f"{int(row[i])} != held page {pg}")
                for i in range(len(held), aux.pages_per_slot):
                    if int(row[i]) != NULL_PAGE:
                        out.append(f"aux {ax} slot {slot} table[{i}]="
                                   f"{int(row[i])} past the held prefix")
                if NULL_PAGE in held:
                    out.append(f"aux {ax} slot {slot} holds the null page")
        alloc = self.allocator
        for pg, want in holds.items():
            have = alloc.refcount(pg)
            if have != want:
                out.append(f"page {pg} refcount {have} != {want} "
                           "(table rows + prefix index)")
            if pg in alloc._free_set:
                out.append(f"page {pg} is held AND on the free list")
        for pg in alloc._ref:
            if pg not in holds:
                out.append(f"page {pg} allocated (refcount "
                           f"{alloc._ref[pg]}) but held by no slot or "
                           "index entry")
        n_booked = len(alloc._free) + len(alloc._ref)
        if n_booked != alloc.num_pages - 1:
            out.append(f"free ({len(alloc._free)}) + allocated "
                       f"({len(alloc._ref)}) != allocatable "
                       f"({alloc.num_pages - 1})")
        if set(alloc._free) != alloc._free_set:
            out.append("free list and free set disagree")
        return out


class AuxPageTable:
    """Per-slot page tables for an auxiliary KV cache (the spec-decode
    DRAFT model, ISSUE 20) drawing pages from the SAME allocator as the
    target pool — one id space, one refcount economy, one residency
    ledger, so draft and target bytes genuinely compete and the
    engine's page-pressure ladder can reclaim draft pages before
    resorting to preemption.

    Differences from the primary tables:
      * allocations are NOT fresh-listed — the draft cache is a
        separate f32 device array indexed by these tables, so the
        target pool's int8 scale rows for a draft-held page are never
        read; the allocator's ``on_zero`` hook still fresh-lists the
        page when its last reference drops, which is exactly when the
        TARGET pool could next gather it.
      * no sharing/COW/prefix legs: draft pages are private to their
        slot (refcount stays 1), and the rewind path is plain
        ``shrink_slot``.
    """

    def __init__(self, pool: PagePool, num_slots: int,
                 pages_per_slot: Optional[int] = None):
        self.pool = pool
        self.page_size = pool.page_size
        self.num_slots = int(num_slots)
        self.pages_per_slot = int(pages_per_slot
                                  if pages_per_slot is not None
                                  else pool.pages_per_slot)
        self.tables = np.zeros((num_slots, self.pages_per_slot), np.int32)
        self._held: List[List[int]] = [[] for _ in range(num_slots)]
        pool.register_aux(self)

    def slot_pages(self, slot: int) -> int:
        return len(self._held[slot])

    def total_pages(self) -> int:
        """Pages currently held across all slots — the draft-pool-share
        numerator in the serving gauges and bench cells."""
        return sum(len(h) for h in self._held)

    def grow_slot(self, slot: int, n_pages: int) -> bool:
        """Extend ``slot`` by ``n_pages`` pages from the shared
        allocator (evicting unreferenced prefix-cache pages if that is
        what it takes — same economy as the primary tables). False and
        untouched when the pool can't cover it: draft growth is
        BEST-EFFORT by design; the engine skips speculation rather
        than escalate for draft bytes."""
        if n_pages <= 0:
            return True
        held = self._held[slot]
        if len(held) + n_pages > self.pages_per_slot:
            raise ValueError(
                f"aux slot {slot} would exceed pages_per_slot="
                f"{self.pages_per_slot}")
        alloc = self.pool.allocator
        got = alloc.alloc(n_pages)
        if got is None and self.pool.prefix is not None:
            self.pool.prefix.evict_for(n_pages - alloc.num_free)
            got = alloc.alloc(n_pages)
        if got is None:
            return False
        self.tables[slot, len(held):len(held) + n_pages] = got
        held.extend(got)
        return True

    def grow_to(self, slot: int, n_tokens: int) -> bool:
        """Ensure ``slot`` holds enough pages for ``n_tokens`` draft
        positions (no-op when it already does)."""
        return self.grow_slot(
            slot, self.pool.pages_for(n_tokens) - len(self._held[slot]))

    def shrink_slot(self, slot: int, keep_pages: int) -> int:
        """Release pages beyond the first ``keep_pages`` (the
        rejection-rewind / pressure-decay path). Returns pages freed."""
        if keep_pages < 0:
            raise ValueError("keep_pages must be >= 0")
        held = self._held[slot]
        drop = held[keep_pages:]
        if not drop:
            return 0
        self.pool.allocator.free(drop)
        del held[keep_pages:]
        self.tables[slot, keep_pages:] = NULL_PAGE
        return len(drop)

    def release_slot(self, slot: int) -> int:
        """Return all of ``slot``'s draft pages to the pool; idempotent."""
        held = self._held[slot]
        n = len(held)
        if n:
            self.pool.allocator.free(held)
        self._held[slot] = []
        self.tables[slot, :] = NULL_PAGE
        return n


def _from_spec(cls, *args, **kw):
    """``for_spec`` of a pool whose constructor takes the spec itself."""
    return cls(*args, **kw)


class LatentPools(NamedTuple):
    """The pools of a latent-attention model with a sparse indexer in its
    full layers and windowed layers between them (ISSUE 37;
    ``models/dots3.py``), as the device holds them. None is K or V:

    latent   ``[Lf, P, C + R, ps]``   a full layer's ``(c_kv, k_rope)``
    index_k  ``[Lf, P, D, ps]``       its indexer's keys
    window   ``[Lw, Pw, Cw + R, ps]`` a windowed layer's latents, in a
                                      page space of its own that holds only
                                      the window (``LatentPagePool``)

    A page's tokens lie along the last axis (``ops/latent_attention`` says
    why). A model with no indexer or no windowed layer (DeepSeek-V2,
    ``models/deepseek_v2.py``: dense latent attention in every layer) has
    ``index_k`` or ``window`` of no bytes: width 0, no layers.

    A pytree like ``Pools``: the tick takes and returns it as one donated
    argument and threads it through its layers. Like ``Pools`` it is the one
    place that knows its format: a forward writes through ``scatter_latent``,
    ``scatter_index`` and ``scatter_window`` and reads through
    ``index_scores``, ``attend_selected``, ``attend`` and ``attend_window``,
    each ``ops/latent_attention``'s function on the field it is for."""

    latent: jax.Array
    index_k: jax.Array
    window: jax.Array

    quantized = False

    @classmethod
    def zeros(cls, full_layers: int, num_pages: int, window_layers: int,
              window_pages: int, page_size: int, latent_width: int,
              index_width: int, window_width: int, dtype) -> "LatentPools":
        return cls(
            jnp.zeros((full_layers, num_pages, latent_width, page_size),
                      dtype),
            jnp.zeros((full_layers, num_pages, index_width, page_size),
                      dtype),
            jnp.zeros((window_layers, window_pages, window_width,
                       page_size), dtype))

    @property
    def page_size(self) -> int:
        return self.latent.shape[-1]

    def arrays(self) -> Dict[str, jax.Array]:
        return self._asdict()

    def reset_scales(self, pages) -> "LatentPools":
        return self

    # -- one layer of a stack, by index, inside the tick: the only code that
    # -- names the fields. ``page``/``off`` [NT] are each token's target,
    # -- ``touched`` the pages the tick writes (``latent_scatter``)
    def scatter_latent(self, layer, page, off, rows, touched):
        return self._replace(latent=latent_scatter(
            self.latent, page, off, rows, layer, touched))

    def scatter_index(self, layer, page, off, keys, touched):
        return self._replace(index_k=latent_scatter(
            self.index_k, page, off, keys, layer, touched))

    def scatter_window(self, layer, page, off, rows, touched):
        return self._replace(window=latent_scatter(
            self.window, page, off, rows, layer, touched))

    def index_scores(self, layer, q_i, w_i, page_table, pos0, true_len):
        return index_scores(q_i, w_i, self.index_k, layer, page_table, pos0,
                            true_len)

    def attend_selected(self, layer, q, page_table, pos0, true_len, keys,
                        thr, ties, c_width: int, scale: float):
        return selected_latent_attention(
            q, self.latent, layer, page_table, pos0, true_len, keys, thr,
            ties, c_width, scale)

    def attend(self, layer, q, page_table, pos0, true_len, c_width: int,
               scale: float):
        return latent_attention(q, self.latent, layer, page_table, pos0,
                                true_len, c_width, scale)

    def attend_window(self, layer, q, page_table, pos0, true_len,
                      window: int, c_width: int, scale: float):
        return window_latent_attention(
            q, self.window, layer, page_table, pos0, true_len, window,
            c_width, scale)


class WindowSpace:
    """A second page space beside a ``PagePool``'s own, for layers that see
    only a window (a mixin, before ``PagePool`` in a pool's bases): the
    pool's own pages (``allocator``, ``tables``) hold a page a ``page_size``
    tokens of a slot for as long as the slot lives, and the windowed layers'
    pages come from ``window_allocator`` / ``window_tables``, indexed by the
    same logical page of the slot, which hold **only the window**:
    ``free_behind(slot, frontier)`` gives back every page that no query at
    or past ``frontier`` can see, and the engine calls it as it dispatches.
    The window space is sized for every slot's worst case (``ceil((window -
    1 + chunk) / page_size) + 2`` pages a slot), so it never binds:
    admission, exhaustion and preemption are decided by the pool's own pages
    alone. A model without windowed layers (``window_layers`` 0) has no
    window space: no page of it is allocated, grown or freed.

    ``LatentPagePool`` (latent rows) and ``WindowedKVPagePool`` (K/V pages)
    are the two pools with one; ``PAGES`` is the name of the pool's own
    pages in the engine's gauges and ``POOLS`` what its refusals call it."""

    #: False keeps every windowed page for the slot's life (the window
    #: space is then as large as the pool's own): what the tests compare
    #: a freeing run with
    FREE_BEHIND = True
    PAGES = "kv"
    POOLS = "windowed pools"

    def _open_window_space(self, caches: dict, page_size: int,
                           num_slots: int, pages_per_slot: int,
                           chunk: int) -> int:
        """Sizes and opens the window space of a ``cache_spec()`` (before
        ``PagePool.__init__``: the device pools are made from the answer).
        -> the pages it has, the null page among them."""
        windowed = caches.get("window_layers", 0)
        self.window = int(caches.get("window", 0))
        held = -(-(self.window - 1 + chunk) // page_size) + 2
        self.window_pages_per_slot = 0 if not windowed else min(
            pages_per_slot, held) if self.FREE_BEHIND else pages_per_slot
        # (an allocator wants two pages; of no layers they are no bytes)
        window_pages = max(num_slots * self.window_pages_per_slot + 1, 2)
        self.window_allocator = PageAllocator(window_pages)
        self.window_tables = np.zeros((num_slots, pages_per_slot), np.int32)
        #: logical page -> page id of the window space, per slot
        self._window_held: List[Dict[int, int]] = [
            {} for _ in range(num_slots)]
        return window_pages

    def live_shares(self) -> Dict[str, float]:
        shares = {self.PAGES: self.allocator.utilization()}
        if self.window_pages_per_slot:
            shares["window"] = self.window_allocator.utilization()
        return shares

    def row_tables(self, rows):
        return (_rows_of(self.tables, rows),
                _rows_of(self.window_tables, rows))

    def slot_window_pages(self, slot: int) -> int:
        return len(self._window_held[slot])

    def grow_slot(self, slot: int, n_pages: int) -> bool:
        """Both page spaces or neither: ``n_pages`` more logical pages of
        the slot, each with a page of the pool's own and one of the
        windowed layers."""
        if n_pages <= 0:
            return True
        if not self.window_pages_per_slot:
            return super().grow_slot(slot, n_pages)
        first = len(self._held[slot])
        if self.window_allocator.num_free < n_pages \
                or not super().grow_slot(slot, n_pages):
            return False
        got = self.window_allocator.alloc(n_pages)
        self.window_tables[slot, first:first + n_pages] = got
        self._window_held[slot].update(zip(range(first, first + n_pages),
                                           got))
        return True

    def free_behind(self, slot: int, frontier: int) -> int:
        """Give back the slot's windowed pages that lie wholly behind the
        window of every query at or past position ``frontier`` (such a
        query sees positions ``> frontier - window``). Returns how many."""
        if not self.FREE_BEHIND or not self.window_pages_per_slot:
            return 0
        oldest = max(frontier - self.window + 1, 0) // self.page_size
        held = self._window_held[slot]
        gone = [i for i in held if i < oldest]
        if gone:
            self.window_allocator.free([held.pop(i) for i in gone])
            self.window_tables[slot, gone] = NULL_PAGE
        return len(gone)

    def release_slot(self, slot: int) -> int:
        held = self._window_held[slot]
        if held:
            self.window_allocator.free(list(held.values()))
        self._window_held[slot] = {}
        self.window_tables[slot, :] = NULL_PAGE
        return super().release_slot(slot)

    def share_into_slot(self, slot: int, pages) -> None:
        raise NotImplementedError(
            f"sharing pages into a slot of {self.POOLS}")

    def shrink_slot(self, slot: int, keep_pages: int) -> int:
        raise NotImplementedError(f"rewinding a slot of {self.POOLS}")

    def register_aux(self, aux) -> None:
        raise NotImplementedError(
            f"an auxiliary page table over {self.POOLS}")

    def check_consistency(self) -> List[str]:
        out = super().check_consistency()
        alloc = self.window_allocator
        seen: Dict[int, int] = {}
        for slot, held in enumerate(self._window_held):
            row = self.window_tables[slot]
            for i, pg in held.items():
                seen[pg] = seen.get(pg, 0) + 1
                if int(row[i]) != pg:
                    out.append(f"slot {slot} window table[{i}]="
                               f"{int(row[i])} != held page {pg}")
            for i in np.flatnonzero(row):
                if int(i) not in held:
                    out.append(f"slot {slot} window table[{int(i)}]="
                               f"{int(row[i])} is not held")
        for pg, n in seen.items():
            if n != 1 or alloc.refcount(pg) != 1:
                out.append(f"window page {pg} held {n} times, refcount "
                           f"{alloc.refcount(pg)}")
        if alloc.num_allocated != len(seen):
            out.append(f"window pages allocated {alloc.num_allocated} != "
                       f"held {len(seen)}")
        return out


class LatentPagePool(WindowSpace, PagePool):
    """``PagePool`` for ``LatentPools``: the full layers' pages are the
    pool's own, and the windowed layers' pages come from the second page
    space that ``WindowSpace`` keeps (sized, grown, freed behind the window
    and audited there: the one copy of that logic, which
    ``WindowedKVPagePool`` shares).

    What it lacks is refused by name: a prefix cache (a cached page would
    have to say which layers it serves: a windowed layer's page is gone
    once the window has passed; and without windowed layers
    ``share_into_slot`` and ``copy_page`` are still not written over latent
    pools), speculative rewinds (``shrink_slot``), auxiliary tables, int8
    pages and a page handoff (``CANNOT``)."""

    PAGES = "latent"
    POOLS = "latent and windowed pools"

    CANNOT = {
        "rewinds": (
            "{doing} over latent and windowed pools: the verify tick "
            "(serving/spec.py make_spec_tick) and the draft runner carry "
            "Pools of K and V, and a rejected draft would have to rewind "
            "pages a window has already given back"),
        "int8": (
            "int8 latent pools: the per-page per-head scales are K's and "
            "V's; a latent row has no head axis"),
        "handoff": (
            "{doing} over latent and windowed pools: a handoff moves Pools "
            "of K and V by page (serving/disagg.py), and a windowed layer's "
            "pages behind the window no longer exist to be moved"),
    }

    for_spec = classmethod(_from_spec)

    def __init__(self, caches: dict, num_pages: int, page_size: int,
                 num_slots: int, pages_per_slot: int, chunk: int,
                 dtype=jnp.float32, prefix_cache: bool = False):
        if jnp.dtype(dtype) == jnp.int8:
            self.require("int8", "kv_dtype='int8'")
        windowed = caches.get("window_layers", 0)
        if prefix_cache and windowed:
            raise NotImplementedError(
                "prefix_cache=True with windowed layers: PrefixCache shares "
                "a page into every layer's pool, and a windowed layer's "
                "page is given back once the window has passed it; pass "
                "prefix_cache=False (ROADMAP R4)")
        if prefix_cache:
            raise NotImplementedError(
                "prefix_cache=True over latent pools: sharing a cached page "
                "into a slot (share_into_slot) and copying a shared page "
                "before a write (LatentPools.copy_page) are not written "
                "for them; pass prefix_cache=False (ROADMAP R4)")
        window_pages = self._open_window_space(
            caches, page_size, num_slots, pages_per_slot, chunk)
        pools = LatentPools.zeros(
            caches["full_layers"], num_pages, windowed,
            window_pages, page_size, caches["latent_width"],
            caches.get("index_width", 0), caches.get("window_width", 0),
            dtype)
        super().__init__(caches["full_layers"] + windowed,
                         num_pages, page_size, 1, caches["latent_width"],
                         num_slots, pages_per_slot, dtype=dtype, pools=pools)


class GroupedPools(NamedTuple):
    """K/V pages of a model with fewer key/value heads than query heads
    (ISSUE 54, ``models/falcon_h1.py``: 20 query heads over 4), as the device
    holds them: ONE array ``[L, P, 2 KVH, ps, D]``, K's heads and then V's,
    **a page's positions on the sublanes** (``ops/paged_attention`` says why:
    ``Pools`` keeps the heads there and rounds them up to whole tiles, which
    four heads would pay four times over). No head is padded: a token is ``2
    KVH D`` numbers a layer. A pytree like ``Pools`` and the one place that
    knows this format: ``scatter`` writes a page at a time and ``attend``
    gives every key/value head its ``NH / KVH`` query heads in one product
    (``grouped_kv_scatter``, ``grouped_paged_attention``)."""

    kv: jax.Array

    quantized = False

    @classmethod
    def zeros(cls, num_layers: int, num_pages: int, page_size: int,
              kv_heads: int, head_dim: int, dtype) -> "GroupedPools":
        return cls(jnp.zeros((num_layers, num_pages, 2 * kv_heads, page_size,
                              head_dim), dtype))

    @property
    def page_size(self) -> int:
        return self.kv.shape[-2]

    def arrays(self) -> Dict[str, jax.Array]:
        return {"kv": self.kv}

    def scatter(self, layer, page, off, kk, vv, touched=None):
        """``kk``/``vv`` [NT, 1, KVH, D] at ``(layer, page, off)``;
        ``touched`` the pages the tick's tokens write to
        (``models/tick.TickRows.touched``)."""
        return GroupedPools(grouped_kv_scatter(
            self.kv, page, off, kk[:, 0], vv[:, 0], layer, touched))

    def attend(self, layer, q, page_table, pos0, true_len, window=None):
        """``window``: query ``i`` of a row sees positions ``pos0 + i -
        window < s <= pos0 + i`` alone, and entries of ``page_table``
        behind the window may be null (``WindowSpace``)."""
        return grouped_paged_attention(q, self.kv, page_table, pos0,
                                       true_len, layer, window=window)

    def rows_of(self, layer, pages):
        """``(k, v)`` of ``pages`` [n], each ``[n ps, KVH, D]``: the
        positions of those pages in order, what a check reads."""
        got = self.kv[layer, pages]                     # [n, 2 KVH, ps, D]
        kvh = got.shape[1] // 2
        flat = jnp.swapaxes(got, 1, 2).reshape(-1, 2 * kvh, got.shape[-1])
        return flat[:, :kvh], flat[:, kvh:]


class WindowedKVPools(NamedTuple):
    """Grouped K/V pages of a model whose layers are of two kinds (ISSUE 57,
    ``models/laguna.py``): full attention in some, attention under a sliding
    window in the others, **over one set of key/value heads** whatever the
    query heads of a layer (48 and 72 over 8), as the device holds them:

    kv      ``[Lf, P, 2 KVH, ps, D]``   the full layers' pages
    window  ``[Lw, Pw, 2 KVH, ps, D]``  the windowed layers', in a page space
                                        of its own that holds only the window
                                        (``WindowedKVPagePool``)

    both in ``GroupedPools``' format (no padded head). One pytree the tick
    donates, and the one place that knows the format: a forward writes and
    reads the full layers through ``scatter`` and ``attend`` and the windowed
    ones through ``scatter_window`` and ``attend_window``
    (``ops/paged_attention.grouped_paged_attention`` under ``window``)."""

    kv: GroupedPools
    window: GroupedPools

    quantized = False

    @classmethod
    def zeros(cls, full_layers: int, num_pages: int, window_layers: int,
              window_pages: int, page_size: int, kv_heads: int,
              head_dim: int, dtype) -> "WindowedKVPools":
        return cls(
            GroupedPools.zeros(full_layers, num_pages, page_size, kv_heads,
                               head_dim, dtype),
            GroupedPools.zeros(window_layers, window_pages, page_size,
                               kv_heads, head_dim, dtype))

    @property
    def page_size(self) -> int:
        return self.kv.page_size

    def arrays(self) -> Dict[str, jax.Array]:
        return {"kv": self.kv.kv, "window": self.window.kv}

    def reset_scales(self, pages) -> "WindowedKVPools":
        return self

    def scatter(self, layer, page, off, kk, vv, touched=None):
        return self._replace(kv=self.kv.scatter(layer, page, off, kk, vv,
                                                touched))

    def attend(self, layer, q, page_table, pos0, true_len):
        return self.kv.attend(layer, q, page_table, pos0, true_len)

    def scatter_window(self, layer, page, off, kk, vv, touched=None):
        return self._replace(window=self.window.scatter(
            layer, page, off, kk, vv, touched))

    def attend_window(self, layer, q, page_table, pos0, true_len,
                      window: int):
        return self.window.attend(layer, q, page_table, pos0, true_len,
                                  window)


class WindowedKVPagePool(WindowSpace, PagePool):
    """``PagePool`` for ``WindowedKVPools`` (a ``cache_spec()`` of kind
    ``"windowed_kv"``: ``full_layers``, ``window_layers``, ``window``,
    ``key_value_heads``, ``head_dim``): the full layers' pages are the pool's
    own, the windowed layers' come from ``WindowSpace``'s second page space,
    as a latent model's windows do. What it cannot do is refused by name
    (``CANNOT``), before anything is allocated."""

    POOLS = "full and windowed K/V pools"

    CANNOT = {
        "prefix": (
            "{doing} over windowed K/V layers: PrefixCache shares a page "
            "into every layer's pool, and a windowed layer's page is given "
            "back once the window has passed it (ROADMAP R4)"),
        "rewinds": (
            "{doing} over full and windowed K/V pools: the verify tick "
            "(serving/spec.py make_spec_tick) carries Pools of K and V, and "
            "a rejected draft would have to rewind pages a window has "
            "already given back"),
        "int8": (
            "int8 grouped pages: the per-page per-head scales and the "
            "quantized ragged kernel are Pools' own (ROADMAP R4)"),
        "handoff": (
            "{doing} over full and windowed K/V pools: a handoff moves "
            "Pools of K and V by page (serving/disagg.py), and a windowed "
            "layer's pages behind the window no longer exist to be moved"),
    }

    for_spec = classmethod(_from_spec)

    def __init__(self, caches: dict, num_pages: int, page_size: int,
                 num_slots: int, pages_per_slot: int, chunk: int,
                 dtype=jnp.float32, prefix_cache: bool = False):
        if jnp.dtype(dtype) == jnp.int8:
            self.require("int8", "kv_dtype='int8'")
        if prefix_cache:
            self.require("prefix", "prefix_cache=True")
        windowed = caches["window_layers"]
        window_pages = self._open_window_space(
            caches, page_size, num_slots, pages_per_slot, chunk)
        pools = WindowedKVPools.zeros(
            caches["full_layers"], num_pages, windowed, window_pages,
            page_size, caches["key_value_heads"], caches["head_dim"], dtype)
        super().__init__(caches["full_layers"] + windowed, num_pages,
                         page_size, caches["key_value_heads"],
                         caches["head_dim"], num_slots, pages_per_slot,
                         dtype=dtype, pools=pools)


class StatePools(NamedTuple):
    """The caches of a model whose layers are of two kinds (ISSUE 44,
    ``models/olmo_hybrid.py``; ISSUE 49, ``models/ling3.py``): attention over
    pages in some, a gated delta rule's recurrent state in the others, as
    the device holds them:

    kv     the attention layers' pages, in either format. ``Pools``: K and V
           pages, ``[Lf, P, ps, NHr, D]``; ``NHr`` is the heads rounded up
           to whole tiles of the pools' type (30 heads of bf16 ride at 32,
           two of zeros: the ragged kernel reads a head by a strided load
           when the heads fill tiles, and batches a float32 product
           otherwise). Or, for a ``cache_spec()`` that gives a
           ``latent_width``, ``LatentPools`` of latent rows ``[Lf, P, C + R,
           ps]`` with no indexer keys and no window space (latent attention
           beside the state: written and read through ``scatter_latent``
           and ``attend_latent``)
    state  ``[Ll, slots + 1, ...]`` float32: one state a linear layer and
           slot, whatever the context, in ``ops/gdn.pack_state``'s layout
    conv   ``[Ll, taps - 1, rows, C]``: the positions each linear layer's
           short convolution looks back on, the oldest first, a slot a row;
           ``rows`` is ``slots + 1`` rounded up to whole tiles
           (``ops/gdn.conv_slot_rows``: 48 for 40 slots; the rows past the
           last slot are never written). **One reader and one writer**:
           ``prep`` hands the layer's history to ``ops/gdn.gdn_prep_rows``
           with a group of rows and takes the pool back, as ``step`` and
           ``chunk`` do for the state. On the chip that is one Pallas call a
           row group through which the layer's block (3.3 MB) passes as it
           lies and is written **in place**: a row's slot is reached inside
           VMEM, so nothing gathers or scatters the pool outside it (the
           ``jax.numpy`` spelling's three row gathers and three row
           scatters a layer were 1.6 of the tick's 24.8 ms, a scatter 33 us
           for 0.9 MB; PERF.md section 6, PR 46). Off the chip the spelling
           reads and writes it a tap at a time, rows ``[n, C]`` by slot:
           rows are what a gather or scatter by slot moves as they lie (one
           scatter over the taps and slots together made XLA:TPU re-lay the
           whole array around every write, 24 copies of 34 MB a tick, and
           a slot's taps as one row of ``3 C`` cost more in re-laying the
           rows than it saved; PERF.md section 6, PR 44)

    Slot 0 of ``state`` and ``conv`` is the null slot, as page 0 is the null
    page: rows that carry no tenant's token read and write it. A pytree
    like ``Pools``, and the one place that knows this format: a forward
    writes and reads K/V through ``scatter`` and ``attend`` as a K/V model
    does (latent rows through ``scatter_latent`` and ``attend_latent`` as a
    latent model does), and passes a linear layer's rows through ``prep`` (the
    convolution over the carried history, SiLU, l2norm) and then ``step``
    (decode rows) or ``chunk`` (chunk rows), ``ops/gdn``'s functions on the
    field they are for. A tenant's first chunk enters at zero (``fresh``):
    nothing else clears a slot."""

    kv: "Pools | LatentPools"
    state: jax.Array
    conv: jax.Array

    quantized = False

    @classmethod
    def zeros(cls, caches: dict, num_pages: int, page_size: int,
              num_slots: int, dtype) -> "StatePools":
        tile = 8 * (4 // jnp.dtype(dtype).itemsize)
        if "latent_width" in caches:
            pages = LatentPools.zeros(caches["layers"], num_pages, 0, 2,
                                      page_size, caches["latent_width"], 0,
                                      0, dtype)
        elif caches.get("key_value_heads", caches["heads"]) \
                < caches["heads"]:
            pages = GroupedPools.zeros(caches["layers"], num_pages,
                                       page_size, caches["key_value_heads"],
                                       caches["head_dim"], dtype)
        else:
            pages = Pools.zeros(caches["layers"], num_pages, page_size,
                                -(-caches["heads"] // tile) * tile,
                                caches["head_dim"], dtype)
        return cls(
            pages,
            jnp.zeros((caches["state_layers"], num_slots + 1)
                      + cls.state_shape(caches), jnp.float32),
            jnp.zeros((caches["state_layers"], caches["conv_taps"] - 1,
                       conv_slot_rows(num_slots), caches["conv_width"]),
                      dtype))

    @staticmethod
    def state_shape(caches: dict) -> Tuple[int, ...]:
        """One slot's state of one layer (``ops/gdn.pack_state``'s layout)."""
        return pack_state(jnp.zeros(
            (caches["state_heads"], caches["key_dim"], caches["value_dim"]),
            jnp.float32)).shape

    @property
    def page_size(self) -> int:
        return self.kv.page_size

    def arrays(self) -> Dict[str, jax.Array]:
        return dict(self.kv.arrays(), state=self.state, conv=self.conv)

    def reset_scales(self, pages) -> "StatePools":
        return self

    # -- the full layers: K and V pages --------------------------------
    def _head_rows(self, a):
        pad = self.kv.k.shape[-2] - a.shape[-2]
        return jnp.pad(a, ((0, 0),) * (a.ndim - 2) + ((0, pad), (0, 0)))

    def scatter(self, layer, page, off, kk, vv, touched=None):
        """``touched``: the pages the tokens write to, for grouped pages,
        which are written a page at a time (``GroupedPools``)."""
        if isinstance(self.kv, GroupedPools):
            return self._replace(kv=self.kv.scatter(layer, page, off, kk, vv,
                                                    touched))
        return self._replace(kv=self.kv.scatter(
            layer, page, off, self._head_rows(kk), self._head_rows(vv)))

    def attend(self, layer, q, page_table, pos0, true_len):
        if isinstance(self.kv, GroupedPools):
            return self.kv.attend(layer, q, page_table, pos0, true_len)
        return self.kv.attend(layer, self._head_rows(q), page_table, pos0,
                              true_len)[..., :q.shape[-2], :]

    # -- the attention layers over latent pages ------------------------
    def scatter_latent(self, layer, page, off, rows, touched):
        return self._replace(kv=self.kv.scatter_latent(layer, page, off,
                                                       rows, touched))

    def attend_latent(self, layer, q, page_table, pos0, true_len,
                      c_width: int, scale: float):
        return self.kv.attend(layer, q, page_table, pos0, true_len, c_width,
                              scale)

    # -- the linear layers: a state and a history a slot ---------------
    def prep(self, layer, slots, x, taps, heads: int, key_dim: int,
             fresh=None, row_len=None):
        """``ops/gdn.gdn_prep_rows`` at ``layer``'s history: the rows' ``[q
        | k | v]`` projections ``x`` (``[n, C]`` decode rows, ``[n, w, C]``
        chunk rows of ``row_len`` tokens, ``fresh`` at a sequence's start)
        through the convolution after what their slots carry, SiLU and the
        l2norm. -> ``(q, k, v, the pools with the history the rows
        leave)``."""
        q, k, v, conv = gdn_prep_rows(x, taps, self.conv, layer, slots,
                                      fresh, row_len, heads, key_dim)
        return q, k, v, self._replace(conv=conv)

    def step(self, layer, slots, q, k, v, g, beta):
        """``ops/gdn.gdn_step_rows`` at ``layer``'s states."""
        o, state = gdn_step_rows(q, k, v, g, beta, self.state, layer, slots)
        return o, self._replace(state=state)

    def chunk(self, layer, slots, fresh, row_len, q, k, v, g, beta):
        """``ops/gdn.gdn_chunk_rows`` at ``layer``'s states."""
        o, state = gdn_chunk_rows(q, k, v, g, beta, self.state, layer,
                                  slots, fresh, row_len)
        return o, self._replace(state=state)

    def state_of(self, layer, slots, heads: int):
        """``[n, heads, dk, dv]`` float32: the states a check reads."""
        return unpack_state(self.state[layer, slots], heads)


class SSDStatePools(StatePools):
    """``StatePools`` of a model whose state follows the state-space rule of
    Mamba-2 (ISSUE 54, ``models/falcon_h1.py``; a ``cache_spec()`` with
    ``"rule": "ssd"``): the same three fields, the same null slot, the same
    ``fresh``; ``prep``, ``step`` and ``chunk`` are ``ops/ssd``'s functions
    on them. The state is ``[Ll, slots + 1, heads, N, P]`` float32 (the
    spec's ``key_dim`` the state's size ``N``, its ``value_dim`` a head's
    channels ``P``: a head's state transposed, ``ops/ssd`` says why), the
    history ``[Ll, taps - 1, rows, C]`` of ``C = [x | B | C]``."""

    __slots__ = ()

    @staticmethod
    def state_shape(caches: dict) -> Tuple[int, ...]:
        return (caches["state_heads"], caches["key_dim"],
                caches["value_dim"])

    def prep(self, layer, slots, x, taps, bias, fresh=None, row_len=None):
        """``ops/ssd.ssd_prep_rows`` at ``layer``'s history: the rows'
        ``[x | B | C]`` projections through the convolution after what their
        slots carry, its bias and SiLU. -> ``(the activated rows, the pools
        with the history the rows leave)``."""
        y, conv = ssd_prep_rows(x, taps, bias, self.conv, layer, slots,
                                fresh, row_len)
        return y, self._replace(conv=conv)

    def step(self, layer, slots, x, B, C, dt, A, D):
        """``ops/ssd.ssd_step_rows`` at ``layer``'s states."""
        y, state = ssd_step_rows(x, B, C, dt, A, D, self.state, layer, slots)
        return y, self._replace(state=state)

    def chunk(self, layer, slots, fresh, row_len, x, B, C, dt, A, D):
        """``ops/ssd.ssd_chunk_rows`` at ``layer``'s states."""
        y, state = ssd_chunk_rows(x, B, C, dt, A, D, self.state, layer,
                                  slots, fresh, row_len)
        return y, self._replace(state=state)

    def state_of(self, layer, slots, heads: int):
        """``[n, heads, P, N]`` float32, a head's state as the rule is
        written (``S`` in ``R^{P x N}``): the states a check reads."""
        del heads
        return jnp.swapaxes(self.state[layer, slots], -1, -2)


class StatePagePool(PagePool):
    """``PagePool`` for ``StatePools``: the attention layers' pages (K/V
    pages, or latent rows where the ``cache_spec()`` gives a
    ``latent_width``) are the pool's
    own, allocated, grown and freed as a K/V model's are; a slot's state and
    history are slot ``s + 1`` of their arrays for as long as the engine has
    slot ``s``, so nothing is allocated for them and they never bind.

    ``row_tables`` hands a tick each row's state slot beside its page table.
    A decode row whose slot has a chunk row in the same tick carries slot 0:
    the chunk row owns the state that tick. The tick itself tells the other
    dead decode rows (an empty slot, a slot between two chunks of its
    prompt) by their page: such a row's token has no page of its slot to be
    written to, **because a prefill chunk is whole pages** (``chunk %
    page_size == 0``, required here: a slot between chunks then holds
    exactly the pages of what it has prefilled).

    What a state cannot do yet is refused by name (``CANNOT``), before
    anything is allocated."""

    CANNOT = {
        "prefix": (
            "{doing} over a recurrent state: a cached page is of no use "
            "without the linear layers' states at that page's boundary, and "
            "no snapshot of them is kept (ROADMAP R5)"),
        "rewinds": (
            "{doing} over a recurrent state: a rejected draft would have to "
            "roll the state back, and no snapshot of it is kept; the verify "
            "tick (serving/spec.py make_spec_tick) carries Pools of K and V"),
        "int8": (
            "int8 pages beside a recurrent state: the scales' reset list "
            "and the quantized ragged kernel are Pools' own; StatePools "
            "pads its heads to whole tiles of a float type, and a latent "
            "row has no head axis"),
        "handoff": (
            "{doing} over a recurrent state: a handoff moves pages "
            "(serving/disagg.py: Pools of K and V); a slot's state and "
            "convolution history are not pages and nothing ships them"),
        "chunk_rows": (
            "{doing} over a recurrent state: under fifo two chunk rows of a "
            "tick are one prompt's consecutive chunks, and the second needs "
            "the state the first leaves; the rows of a tick run side by "
            "side"),
    }

    for_spec = classmethod(_from_spec)

    def __init__(self, caches: dict, num_pages: int, page_size: int,
                 num_slots: int, pages_per_slot: int, chunk: int,
                 dtype=jnp.float32, prefix_cache: bool = False):
        if jnp.dtype(dtype) == jnp.int8:
            self.require("int8", "kv_dtype='int8'")
        if prefix_cache:
            self.require("prefix", "prefix_cache=True")
        if chunk % page_size:
            raise ValueError(
                f"prefill_chunk {chunk} is not whole pages of {page_size}: "
                "a tick tells a slot between two chunks of its prompt by "
                "the page its decode row's token would need (StatePagePool)")
        kind = SSDStatePools if caches.get("rule") == "ssd" else StatePools
        pools = kind.zeros(caches, num_pages, page_size, num_slots, dtype)
        #: the pages' format, as the engine's gauges name it
        self.pages_kind = "latent" if "latent_width" in caches else "kv"
        heads, width = (1, caches["latent_width"]) \
            if self.pages_kind == "latent" \
            else (caches.get("key_value_heads", caches["heads"]),
                  caches["head_dim"])
        super().__init__(caches["layers"], num_pages, page_size, heads,
                         width, num_slots, pages_per_slot, dtype=dtype,
                         pools=pools)
        #: slots whose state a chunk row has entered since they were released
        self._stateful = np.zeros(num_slots, bool)
        _registry().gauge("serving/state_bytes").set(
            float(pools.state.nbytes + pools.conv.nbytes))

    def live_shares(self) -> Dict[str, float]:
        return {self.pages_kind: self.allocator.utilization(),
                "state": float(np.mean(self._stateful))}

    def row_tables(self, rows):
        """``(page tables [R, NPs], state slots [R])``: the rows are the
        engine's, a decode row a slot and then the chunk rows."""
        chunks = [s for s in rows[self.num_slots:] if s is not None]
        if len(rows) - self.num_slots > 1:
            self.require("chunk_rows", "prefill_chunks_per_tick > 1")
        self._stateful[chunks] = True
        slots = np.asarray(
            [0 if s is None or (i < self.num_slots and s in chunks)
             else s + 1 for i, s in enumerate(rows)], np.int32)
        return _rows_of(self.tables, rows), slots

    def release_slot(self, slot: int) -> int:
        self._stateful[slot] = False
        return super().release_slot(slot)

    def share_into_slot(self, slot: int, pages) -> None:
        self.require("prefix", "sharing pages into a slot")

    def shrink_slot(self, slot: int, keep_pages: int) -> int:
        self.require("rewinds", "rewinding a slot")

    def register_aux(self, aux) -> None:
        self.require("rewinds", "an auxiliary page table")

    def check_consistency(self) -> List[str]:
        out = super().check_consistency()
        for slot in np.flatnonzero(self._stateful):
            if not self._held[slot]:
                out.append(f"slot {int(slot)} holds a state and no page")
        for name, a, axis, want in (
                ("state", self.pools.state, 1, self.num_slots + 1),
                ("conv", self.pools.conv, 2,
                 conv_slot_rows(self.num_slots))):
            if a.shape[axis] != want:
                out.append(f"{name} holds {a.shape[axis]} slot rows, not "
                           f"{want} (the null slot and one a slot)")
        return out


#: the pool of each kind of ``cache_spec()`` (``models/tick.py``)
POOL_KINDS = {"kv": PagePool, "latent": LatentPagePool,
              "state": StatePagePool, "windowed_kv": WindowedKVPagePool}


def page_pool(caches: dict, num_pages: int, page_size: int, num_slots: int,
              pages_per_slot: int, chunk: int, dtype, prefix_cache: bool,
              rewinds: bool, chunk_rows: int = 1) -> PagePool:
    """The pool of a model's ``cache_spec()`` (``models/tick.py``), of the
    kind it names (``POOL_KINDS``); ``chunk`` the tokens of a prefill chunk
    (a window's pages are sized by it) and ``chunk_rows`` the chunk rows of
    a tick. ``rewinds`` says that the engine will shrink slots (speculative
    decoding); what a kind of pool cannot do it refuses before anything is
    allocated."""
    kind = POOL_KINDS[caches["kind"]]
    if rewinds:
        kind.require("rewinds", "speculative decoding")
    if chunk_rows > 1:
        kind.require("chunk_rows", "prefill_chunks_per_tick > 1")
    return kind.for_spec(caches, num_pages, page_size, num_slots,
                         pages_per_slot, chunk, dtype, prefix_cache)
