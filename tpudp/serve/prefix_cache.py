"""Prefix caching for ``tpudp.serve`` — block-granular KV pool + radix
tree reuse.

Real serving traffic repeats itself: one system prompt in front of
millions of requests, few-shot headers shared across a tenant, multi-turn
conversations whose every turn re-sends the whole history.  The engine
(PR 1-3) re-prefills those shared tokens per request — the dominant TTFT
cost for exactly the traffic the ROADMAP north star names.  This module
converts repeated prefills into KV block copies:

  * **Block-granular KV pool** — ONE preallocated ``(layers,
    cache_blocks, block_tokens, kv_heads, head_dim)`` :class:`KVCache`
    twin of the engine's slot arena, where ``block_tokens`` equals the
    engine's ``prefill_chunk`` so cache granularity aligns exactly with
    chunk boundaries.  A block holds the KV of one chunk of some token
    prefix.  Like everything else in the engine, shapes never depend on
    the workload: publishing and reusing blocks moves DATA through two
    fixed-shape programs, never reshapes anything.
  * **Radix tree over token prefixes** — each edge is one
    ``block_tokens``-token chunk; a node maps that chunk (in the context
    of its ancestors) to the pool block holding its KV.  Per-node
    ``refs`` count children plus explicit pins; a node with live
    references is NEVER evicted (evicting an interior node would orphan
    descendants whose KV is only meaningful in its context).  Eviction
    takes the least-recently-touched unreferenced leaf, under the
    ``cache_blocks`` budget — a logical clock, not wall time, so tests
    replay deterministically.
  * **Two compiled copy programs** — :func:`copy_block_in` (pool block ->
    arena slot rows, used at admission) and :func:`copy_block_out`
    (arena slot rows -> pool block, used at retirement).  Block id, slot
    index, and position are traced scalars, so each program compiles
    once per (arena, pool) geometry and cache churn never recompiles
    (``TRACE_COUNTS`` observes this; tests pin it).

Why copied KV is bit-identical to recomputed KV: prefill is a
deterministic function of the token prefix, and the engine publishes
ONLY chunk-prefilled positions (never decode/verify-produced KV) at the
same chunk alignment every request uses (chunks always start at
multiples of ``prefill_chunk`` from position 0).  A request that copies
blocks ``0..m-1`` and prefills the tail therefore lands exactly the
arena state it would have computed from scratch — no attention-math
changes anywhere, so greedy outputs stay bit-identical to
``generate()`` (``tests/test_prefix_cache.py`` referees, speculation and
step-failure rebuilds included).

The tree/pool metadata here is plain host-side Python (the same
host-schedules/device-computes split as the engine); the engine owns the
device calls so they run behind its fault-injection and watchdog seams.
"""

from __future__ import annotations

import functools

import jax
from jax import lax

from tpudp.models.generate import KVCache, page_type
from tpudp.serve.engine import TRACE_COUNTS


@functools.partial(jax.jit, donate_argnums=(0,))
def copy_block_in(cache, pool, block, slot, pos):
    """Copy pool block ``block`` into arena slot ``slot`` at positions
    ``[pos, pos + block_tokens)`` — the admission-time cache hit.  One
    ``dynamic_update_slice`` per (k, v); ``block``/``slot``/``pos`` are
    traced scalars, so this compiles once per (arena, pool) geometry no
    matter which blocks which requests reuse.  The arena is donated
    (XLA writes the rows in place); the pool is read-only here and
    stays valid."""
    TRACE_COUNTS["prefix_block_in"] += 1
    k = lax.dynamic_slice_in_dim(pool.k, block, 1, axis=1)
    v = lax.dynamic_slice_in_dim(pool.v, block, 1, axis=1)
    return KVCache(
        lax.dynamic_update_slice(cache.k, k, (0, slot, pos, 0, 0)),
        lax.dynamic_update_slice(cache.v, v, (0, slot, pos, 0, 0)))


@functools.partial(jax.jit, donate_argnums=(1,))
def copy_block_out(cache, pool, block, slot, pos):
    """Copy arena slot ``slot`` positions ``[pos, pos + block_tokens)``
    into pool block ``block`` — the retirement-time publish.  The POOL
    is donated (updated in place); the arena is read-only and stays
    valid, which is why a failed publish never forces an arena
    rebuild."""
    TRACE_COUNTS["prefix_block_out"] += 1
    layers, _, block_tokens, kv_heads, head_dim = pool.k.shape
    sizes = (layers, 1, block_tokens, kv_heads, head_dim)
    k = lax.dynamic_slice(cache.k, (0, slot, pos, 0, 0), sizes)
    v = lax.dynamic_slice(cache.v, (0, slot, pos, 0, 0), sizes)
    return KVCache(
        lax.dynamic_update_slice(pool.k, k, (0, block, 0, 0, 0)),
        lax.dynamic_update_slice(pool.v, v, (0, block, 0, 0, 0)))


class _Node:
    """One radix-tree edge: ``key`` (the chunk's token tuple) maps — in
    the context of ``parent``'s prefix — to pool block ``block``.
    ``refs`` counts children plus explicit pins; ``stamp`` is the
    logical-clock LRU touch."""

    __slots__ = ("key", "block", "parent", "children", "refs", "stamp")

    def __init__(self, key, block, parent):
        self.key = key
        self.block = block
        self.parent = parent
        self.children = {}
        self.refs = 0
        self.stamp = 0


class PrefixCache:
    """Block pool + radix index.  Pure host-side bookkeeping plus one
    device buffer (``pool``); the engine drives the copy programs.

    Invariants (``check()`` verifies them; tests call it liberally):

      * every tree node owns exactly one pool block; no block is both
        owned and free; owned + free == ``num_blocks``.
      * ``refs >= len(children)`` for every node (the excess is pins),
        and a node with ``refs > 0`` is never evicted — interior nodes
        are pinned by their children, so eviction only ever removes
        cold leaves and the tree stays prefix-closed (a cached block's
        ancestors are always cached too).
      * all metadata is deterministic: LRU uses a logical clock and the
        tree never holds device values, so a replayed workload evicts
        identically.
    """

    def __init__(self, cfg, num_blocks: int, block_tokens: int):
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        if block_tokens < 1:
            raise ValueError(
                f"block_tokens must be >= 1, got {block_tokens}")
        self.config = cfg
        self.num_blocks = num_blocks
        self.block_tokens = block_tokens
        self.pool = KVCache.zeros(cfg, num_blocks, block_tokens)
        self.evictions = 0
        self._root = _Node(None, -1, None)
        self._free = list(range(num_blocks - 1, -1, -1))
        self._by_block: dict[int, _Node] = {}
        self._clock = 0

    # -- introspection -------------------------------------------------

    @property
    def used_blocks(self) -> int:
        return self.num_blocks - len(self._free)

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def node_count(self) -> int:
        return len(self._by_block)

    # -- index operations ----------------------------------------------

    def _touch(self, node: _Node) -> None:
        self._clock += 1
        node.stamp = self._clock

    def _chunk_key(self, tokens, i: int) -> tuple:
        c = self.block_tokens
        return tuple(int(t) for t in tokens[i * c:(i + 1) * c])

    def lookup(self, tokens) -> list[int]:
        """Pool block ids covering the longest cached block-aligned
        prefix of ``tokens`` (possibly empty).  Touches every matched
        node, so a reused prefix stays warm against eviction."""
        out: list[int] = []
        cur = self._root
        for i in range(len(tokens) // self.block_tokens):
            nxt = cur.children.get(self._chunk_key(tokens, i))
            if nxt is None:
                break
            self._touch(nxt)
            out.append(nxt.block)
            cur = nxt
        return out

    def pin(self, block_ids) -> None:
        """Take a reference on each block's node: pinned blocks are
        never evicted (the engine pins a hit's blocks for the duration
        of the admission copies)."""
        for b in block_ids:
            self._by_block[b].refs += 1

    def unpin(self, block_ids) -> None:
        for b in block_ids:
            node = self._by_block.get(b)
            if node is not None:  # survived (flush drops all pins)
                node.refs -= 1

    def publish(self, tokens, n_blocks: int) -> list[tuple[int, int]]:
        """Insert-or-ref the first ``n_blocks`` chunks of ``tokens``.

        Existing nodes are just touched (their KV is already correct —
        prefill is deterministic, so re-publishing a prefix can never
        change a block's contents).  Missing nodes allocate a block
        (evicting a cold unreferenced leaf when the pool is full) and
        are returned as ``(block_id, token_start)`` pairs whose KV the
        caller must copy out of the arena.  Stops early — keeping the
        already-inserted prefix — when the budget is exhausted by
        referenced/pinned entries (nodes on the current insertion path
        are protected from the eviction scan, so an insert can never
        eat its own ancestors)."""
        new: list[tuple[int, int]] = []
        cur = self._root
        path: set[int] = set()
        for i in range(n_blocks):
            key = self._chunk_key(tokens, i)
            nxt = cur.children.get(key)
            if nxt is None:
                block = self._alloc(path)
                if block is None:
                    break
                nxt = _Node(key, block, cur)
                cur.children[key] = nxt
                cur.refs += 1
                self._by_block[block] = nxt
                new.append((block, i * self.block_tokens))
            self._touch(nxt)
            path.add(id(nxt))
            cur = nxt
        return new

    def _alloc(self, exclude_path: set) -> int | None:
        if self._free:
            return self._free.pop()
        victim = None
        for node in self._by_block.values():
            if node.refs or id(node) in exclude_path:
                continue
            if victim is None or node.stamp < victim.stamp:
                victim = node
        if victim is None:
            return None
        del victim.parent.children[victim.key]
        victim.parent.refs -= 1
        del self._by_block[victim.block]
        self.evictions += 1
        return victim.block

    def flush(self, reallocate: bool = False) -> None:
        """Drop every cached block (metadata only by default).  With
        ``reallocate=True`` the pool buffer is rebuilt too — required
        after a device call that had the pool donated may have failed
        mid-flight (the engine's step-failure containment), where the
        old buffer's validity is unknown."""
        self._root = _Node(None, -1, None)
        self._free = list(range(self.num_blocks - 1, -1, -1))
        self._by_block = {}
        if reallocate:
            self.pool = KVCache.zeros(self.config, self.num_blocks,
                                      self.block_tokens)

    def check(self) -> None:
        """Verify tree/pool consistency; raises ``RuntimeError`` on any
        violation (tests call this after every mutation storm)."""
        seen: dict[int, _Node] = {}
        stack = [self._root]
        while stack:
            node = stack.pop()
            if node.refs < len(node.children):
                raise RuntimeError(
                    f"node {node.key!r} refs {node.refs} below child "
                    f"count {len(node.children)}")
            for key, child in node.children.items():
                if child.parent is not node or child.key != key:
                    raise RuntimeError(
                        f"child {key!r} has inconsistent parent/key links")
                if not 0 <= child.block < self.num_blocks:
                    raise RuntimeError(
                        f"node {key!r} owns out-of-range block "
                        f"{child.block}")
                if child.block in seen:
                    raise RuntimeError(
                        f"block {child.block} owned by two nodes")
                seen[child.block] = child
                stack.append(child)
        if set(seen) != set(self._by_block):
            raise RuntimeError("block index disagrees with the tree")
        overlap = set(seen) & set(self._free)
        if overlap:
            raise RuntimeError(f"blocks {sorted(overlap)} both owned "
                               f"and free")
        if len(seen) + len(self._free) != self.num_blocks:
            raise RuntimeError(
                f"{len(seen)} owned + {len(self._free)} free != "
                f"{self.num_blocks} total")


# ---------------------------------------------------------------------------
# True paged attention (Engine(kv_pages=N)): the block pool + radix tree
# promoted from a COPY cache into the engine's one KV store.  The pool
# below is the only KV buffer a paged engine owns (no per-slot dense
# arena); slots reference pages through per-slot block tables, a cache
# hit is a table write + refcount bump (copy-on-write: the divergence
# page is re-prefilled into a fresh private page, shared pages are never
# written), and retirement publishes by TRANSFERRING page ownership to
# the radix tree — neither admission nor publish moves KV bytes.
# ---------------------------------------------------------------------------


class PagePool:
    """Refcounted KV page pool shared across every co-resident model of
    one KV geometry (``Engine(models=...)``) — the paged engine's
    allocator.  ``num_pages`` real pages plus ONE trailing SCRATCH page
    (index ``num_pages``) that absorbs the step programs' masked writes
    (inactive slots, the statically-unrolled spare page of a window
    that stayed inside one page) so no real block is ever clobbered.

    Refcount discipline (``check()`` verifies it): a page is free
    (rc absent, on the free list) or allocated (rc >= 1).  ``alloc()``
    hands out an exclusive page at rc=1; every additional holder — a
    slot's table mapping a cached page, the radix tree adopting a
    published page — takes ``share()``; every holder symmetrically
    ``release()``s, and rc hitting 0 returns the page to the free
    list.  All metadata is host-side and deterministic.
    """

    def __init__(self, cfg, num_pages: int, page_tokens: int,
                 kv_dtype: str | None = None, window_pages: int = 0):
        if num_pages < 1:
            raise ValueError(f"num_pages must be >= 1, got {num_pages}")
        if page_tokens < 1:
            raise ValueError(
                f"page_tokens must be >= 1, got {page_tokens}")
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None or 'int8', got {kv_dtype!r}")
        self.config = cfg
        self.num_pages = num_pages
        self.page_tokens = page_tokens
        self.kv_dtype = kv_dtype
        self.scratch = num_pages  # the +1 guard page (never allocated)
        # A family with sliding-window layers (generate.WindowedPages)
        # keeps those layers' K/V in a second pool of ``window_pages``
        # pages (+ its own scratch) beside this one.  A window page has
        # one holder, the slot whose window overlaps it (no prefix
        # sharing for such a family), so a free list is all its state.
        self.window_pages = window_pages
        self._wfree = list(range(window_pages - 1, -1, -1))
        self.pages = self._buffer()
        self._rc: dict[int, int] = {}
        self._free = list(range(num_pages - 1, -1, -1))

    def _buffer(self):
        # the page type is the config's (generate.page_type): K/V token
        # rows of kv_heads * head_dim values (KVPages: the form the paged
        # kernels read, NOT the dense arena's and the copy cache's
        # (..., kv_heads, head_dim) KVCache above), the same rows in int8
        # + a scale a head, or a latent family's LatentPages
        window = (self.window_pages + 1,) if self.window_pages else ()
        return page_type(self.config, self.kv_dtype).zeros(
            self.config, self.num_pages + 1, self.page_tokens, *window)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.num_pages - len(self._free)

    def page_bytes(self) -> int:
        """HBM bytes of one page across k/v (and scales in int8 mode) —
        the unit of the serve bench's fixed-byte capacity comparison."""
        total = sum(int(buf.size) * buf.dtype.itemsize
                    for buf in getattr(self.pages, "full", self.pages))
        return total // (self.num_pages + 1)

    @property
    def window_used_pages(self) -> int:
        return self.window_pages - len(self._wfree)

    def alloc_window(self) -> int:
        """One page of the window pool.  It is sized to what the slots'
        windows can overlap at once, so it cannot run dry under the
        engine's own rule (an IndexError here is a scheduler bug)."""
        return self._wfree.pop()

    def release_window(self, page: int) -> None:
        self._wfree.append(page)

    def check_window(self, mapped: list[int]) -> None:
        """Window-pool consistency against the pages the window tables
        map: each allocated page mapped exactly once, none both mapped
        and free."""
        if sorted(mapped + self._wfree) != list(range(self.window_pages)):
            raise RuntimeError(
                f"window pages mapped {sorted(mapped)} + free "
                f"{sorted(self._wfree)} are not the pool's "
                f"{self.window_pages}")

    def alloc(self) -> int | None:
        """One exclusive page (rc=1), or None when the pool is empty —
        the engine then evicts cold tree leaves / vacates a slot."""
        if not self._free:
            return None
        page = self._free.pop()
        self._rc[page] = 1
        return page

    def share(self, page: int) -> None:
        self._rc[page] += 1

    def release(self, page: int) -> None:
        rc = self._rc[page] - 1
        if rc:
            self._rc[page] = rc
        else:
            del self._rc[page]
            self._free.append(page)

    def reallocate(self) -> None:
        """Fresh device buffer + all pages freed: the engine's
        step-failure containment, where the failed call may have had
        the (donated) pool in flight and every page's validity is
        unknown."""
        self.pages = self._buffer()
        self._rc = {}
        self._free = list(range(self.num_pages - 1, -1, -1))
        self._wfree = list(range(self.window_pages - 1, -1, -1))

    def read_page(self, page: int) -> dict:
        """Host copies of one allocated page's slice of every pool
        buffer (k, v, and the int8 scales when present), keyed by
        field name and in the pool's stored form (``(layers,
        page_tokens, kv_heads * head_dim)`` token rows for k and v) —
        the unit of cross-host KV migration
        (``tpudp/serve/disagg.py``).  Read-only: shared pages (radix
        tree, other slots) are untouched."""
        import numpy as np

        if page not in self._rc:
            raise ValueError(f"read_page of unallocated page {page}")
        return {name: np.asarray(buf[:, page])
                for name, buf in zip(self.pages._fields, self.pages)}

    def write_page(self, page: int, arrays: dict) -> None:
        """Write one page's payload (as produced by :meth:`read_page`,
        typically on another host with an identical KV geometry) into
        an allocated page of THIS pool.  The caller must hold the page
        exclusively (rc=1, fresh from ``alloc()``) — writing a shared
        page would clobber a peer holder's bytes."""
        import jax.numpy as jnp
        import numpy as np

        if self._rc.get(page) != 1:
            raise ValueError(
                f"write_page needs exclusive page, got rc="
                f"{self._rc.get(page)} for page {page}")
        new = {}
        for name, buf in zip(self.pages._fields, self.pages):
            arr = np.asarray(arrays[name])
            want = buf.shape[:1] + buf.shape[2:]
            if arr.shape != want or arr.dtype != buf.dtype:
                raise ValueError(
                    f"page payload {name}: got {arr.shape}/{arr.dtype}, "
                    f"pool expects {want}/{buf.dtype}")
            new[name] = buf.at[:, page].set(jnp.asarray(arr))
        self.pages = self.pages._replace(**new)

    def check(self, expected_refs: dict[int, int] | None = None) -> None:
        """Pool consistency; with ``expected_refs`` (page -> reference
        count derived from the live tables and radix trees) also the
        table<->pool cross-check — no table maps a freed page, every
        allocated page's rc equals its holders."""
        if set(self._rc) & set(self._free):
            raise RuntimeError("pages both allocated and free")
        if len(self._rc) + len(self._free) != self.num_pages:
            raise RuntimeError(
                f"{len(self._rc)} allocated + {len(self._free)} free != "
                f"{self.num_pages} total")
        for page, rc in self._rc.items():
            if not 0 <= page < self.num_pages:
                raise RuntimeError(f"out-of-range page {page} allocated")
            if rc < 1:
                raise RuntimeError(f"page {page} held at rc {rc}")
        if expected_refs is not None and dict(self._rc) != expected_refs:
            raise RuntimeError(
                f"pool refcounts {dict(sorted(self._rc.items()))} "
                f"disagree with table/tree holders "
                f"{dict(sorted(expected_refs.items()))}")


class PageIndex:
    """Radix tree over token prefixes whose nodes OWN pool pages — the
    paged twin of :class:`PrefixCache`'s tree, with the pool external
    and shared.  A node holds one :class:`PagePool` reference on its
    page; slots mapping a cached page pin the node (so eviction can
    never take a mapped page) and take their own pool reference.
    Publishing ADOPTS the retiring slot's already-written pages
    (``pool.share``) instead of copying KV; eviction walks cold
    unreferenced leaves and ``pool.release``s their pages — on demand,
    under allocation pressure, rather than under a fixed block budget
    (the pool IS the budget)."""

    def __init__(self, pool: PagePool):
        self.pool = pool
        self.block_tokens = pool.page_tokens
        self.evictions = 0
        self._root = _Node(None, -1, None)
        self._by_block: dict[int, _Node] = {}
        self._clock = 0

    # -- shared tree mechanics (same shapes as PrefixCache) ------------

    @property
    def node_count(self) -> int:
        return len(self._by_block)

    def _touch(self, node: _Node) -> None:
        self._clock += 1
        node.stamp = self._clock

    def _chunk_key(self, tokens, i: int) -> tuple:
        c = self.block_tokens
        return tuple(int(t) for t in tokens[i * c:(i + 1) * c])

    def lookup(self, tokens) -> list[_Node]:
        """Nodes covering the longest cached block-aligned prefix of
        ``tokens`` (touching each, so reused prefixes stay warm).
        Returns NODES, not block ids — the paged admission pins the
        node and shares its page."""
        out: list[_Node] = []
        cur = self._root
        for i in range(len(tokens) // self.block_tokens):
            nxt = cur.children.get(self._chunk_key(tokens, i))
            if nxt is None:
                break
            self._touch(nxt)
            out.append(nxt)
            cur = nxt
        return out

    def pin(self, node: _Node) -> None:
        node.refs += 1

    def unpin(self, node: _Node) -> None:
        node.refs -= 1

    def adopt(self, tokens, pages: list[int]) -> int:
        """Insert-or-ref the first ``len(pages)`` chunks of ``tokens``,
        ADOPTING the caller's pages for chunks the tree lacks: a new
        node takes its own pool reference on ``pages[i]`` (the retiring
        slot's reference is released separately at vacate — ownership
        transfers, no KV moves).  Chunks already cached keep the
        tree's existing page (prefill is deterministic, so the two
        pages hold identical KV; the caller's duplicate simply drops to
        rc 0 at vacate).  Returns the number of newly adopted pages."""
        new = 0
        cur = self._root
        for i, page in enumerate(pages):
            key = self._chunk_key(tokens, i)
            nxt = cur.children.get(key)
            if nxt is None:
                nxt = _Node(key, page, cur)
                cur.children[key] = nxt
                cur.refs += 1
                self._by_block[page] = nxt
                self.pool.share(page)
                new += 1
            self._touch(nxt)
            cur = nxt
        return new

    def evict_node(self, node: _Node) -> None:
        """Unlink one unreferenced leaf and release its page — the ONE
        eviction bookkeeping sequence, shared by :meth:`evict_one` and
        the engine's cross-index victim scan (two copies of this
        five-step invariant would desynchronize the moment one grew a
        field)."""
        del node.parent.children[node.key]
        node.parent.refs -= 1
        del self._by_block[node.block]
        self.pool.release(node.block)
        self.evictions += 1

    def evict_one(self) -> bool:
        """Release the least-recently-touched unreferenced leaf's page
        back to the pool (False when every node is referenced — pinned
        by a live table or an interior parent).  The engine calls this
        under allocation pressure until ``alloc`` succeeds."""
        victim = None
        for node in self._by_block.values():
            if node.refs:
                continue
            if victim is None or node.stamp < victim.stamp:
                victim = node
        if victim is None:
            return False
        self.evict_node(victim)
        return True

    def flush(self) -> None:
        """Drop every cached node, releasing its page reference.  For
        containment — where the POOL was reallocated wholesale — use
        :meth:`reset` instead (the references died with the pool)."""
        for node in list(self._by_block.values()):
            self.pool.release(node.block)
        self.reset()

    def reset(self) -> None:
        """Metadata-only clear (the pool already dropped every
        reference, e.g. ``PagePool.reallocate`` after containment)."""
        self._root = _Node(None, -1, None)
        self._by_block = {}

    def tree_refs(self) -> dict[int, int]:
        """page -> pool references held by this tree (1 per node) —
        the engine's table<->pool cross-check input."""
        return {page: 1 for page in self._by_block}

    def check(self) -> None:
        """Tree-shape invariants (same contract as PrefixCache.check,
        minus pool-block accounting — the PagePool owns that side; the
        engine's ``check_paged`` composes both)."""
        seen: dict[int, _Node] = {}
        stack = [self._root]
        while stack:
            node = stack.pop()
            if node.refs < len(node.children):
                raise RuntimeError(
                    f"node {node.key!r} refs {node.refs} below child "
                    f"count {len(node.children)}")
            for key, child in node.children.items():
                if child.parent is not node or child.key != key:
                    raise RuntimeError(
                        f"child {key!r} has inconsistent parent/key links")
                if not 0 <= child.block < self.pool.num_pages:
                    raise RuntimeError(
                        f"node {key!r} owns out-of-range page "
                        f"{child.block}")
                if child.block in seen:
                    raise RuntimeError(
                        f"page {child.block} owned by two nodes")
                seen[child.block] = child
                stack.append(child)
        if set(seen) != set(self._by_block):
            raise RuntimeError("page index disagrees with the tree")
