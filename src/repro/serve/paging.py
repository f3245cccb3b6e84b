"""Paged KV cache pool + radix-style shared-prefix reuse.

``PagedCachePool`` scales :class:`repro.serve.cache.CachePool` from
whole-row slots to sub-slot *pages*: every attention K/V leaf trades its
``(..., num_slots, max_len, ...)`` row layout for a flat page pool
``(..., num_pages, page_size, ...)`` plus a host-side per-slot page table
``(num_slots, pages_per_slot) int32``. A slot's logical sequence position
``p`` lives at pool page ``table[slot, p // page_size]``, row
``p % page_size`` — the jitted decode block scatters new K/V through the
table and the ``paged_attention`` op gathers through it, so cache capacity
is no longer ``num_slots * max_len`` rows but however many pages are
actually written.

Which leaves get paged is *inferred*, exactly like the batch axes: the pool
eval_shapes ``init_cache`` at two ``max_len`` values and diffs the shapes.
A leaf whose sequence axis sits immediately after its batch axis is a KV
page leaf; everything else — mamba2 conv/ssm state, whisper's
``enc_len``-sized cross K/V, scalar ``pos`` — keeps the slot layout and the
inherited slot ops (the paged leaves are masked out of ``batch_axes`` so
``zero_slot`` / ``set_slot`` / row ``defrag`` never touch them).

Page 0 is a reserved scratch page: freeing a slot zeroes its table row on
the host, so the stale frozen-slot writes that the fused k-block keeps
issuing (idempotent rewrites of the last position) divert harmlessly into
page 0, and reads never see it because every gather is masked by
``kv_valid``. That makes table mutation a pure host-side operation — no
device scatter is needed to retire a request.

Shared-prefix reuse (``PrefixCache``) is a radix trie keyed by
``page_size``-token prompt chunks. At admission, a prompt walks the trie;
every fully matched chunk maps the node's page *read-only* into the new
slot's table (refcount bump, prefill for those tokens skipped entirely),
and a partial last-chunk match copies the divergence page (copy-on-write)
so the new request can extend it privately. Pages are refcounted across
slot tables and trie nodes; a page returns to the free heap only when the
count hits zero, and the trie evicts least-recently-matched leaves when the
pool runs dry. ``defrag_pages`` compacts live pages to the front of the
pool with a pure permutation — refcounts, tables and trie pointers are
remapped through the same LUT, and the PR-5 emission-count PRNG keys are
untouched, so sampled streams stay bit-identical across page defrags.

Quantized pages (``kv_dtype="int8"``): the pageable K/V leaves store int8
with a sibling f32 scale leaf per page (``k_scale``/``v_scale``, one scale
per page row per KV head — symmetric absmax over head_dim). Quantization
happens on scatter in the decode write path (``models.blocks``) and both
``paged_attention`` impls dequantize on read, so the scale leaves ride the
same page tables, CoW copies, LUT defrags and trie shares as the values
they scale. At the bf16 leaves the pool replaces, an int8 page plus its
scales costs ~(Dh+4)/(2*Dh) ≈ half the bytes — the default ``num_pages``
doubles accordingly, multiplying resident-request capacity ~2x at ~constant
pool bytes (``page_bytes`` exposes the exact accounting).
"""
from __future__ import annotations

import heapq
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import init_cache
from repro.dist import cache_shardings
from repro.serve.cache import CachePool, SlotError, _NO_BATCH


class PageError(RuntimeError):
    """Page pool exhausted (or invalid page transition)."""


def _page_axes(cfg, max_len: int, enc_len: Optional[int], batch_axes):
    """Pytree of sequence-axis indices for pageable leaves.

    A leaf is pageable iff varying ``max_len`` (with ``enc_len`` pinned)
    moves exactly one axis *and* that axis sits immediately after the leaf's
    batch axis — the ``(..., B, seq, heads, head_dim)`` KV layout shared by
    every attention family. Returns the sequence-axis index per leaf, or
    ``_NO_BATCH`` for leaves that stay in slot layout.
    """
    a = jax.eval_shape(lambda: init_cache(cfg, 2, max_len, enc_len=enc_len))
    b = jax.eval_shape(lambda: init_cache(cfg, 2, max_len + 1, enc_len=enc_len))

    def diff(x, y, bax):
        axes = [i for i, (p, q) in enumerate(zip(x.shape, y.shape)) if p != q]
        if len(axes) != 1 or bax == _NO_BATCH:
            return _NO_BATCH
        return axes[0] if axes[0] == bax + 1 else _NO_BATCH

    return jax.tree.map(diff, a, b, batch_axes)


def _with_scale_siblings(tree, axes, fn):
    """Rebuild ``tree`` (dict/list/tuple pytree), giving paged K/V dict
    leaves a ``<name>_scale`` sibling.

    ``fn(name, leaf, ax) -> (new_leaf, scale_or_None)`` decides both the
    leaf transform and whether a sibling is added (None: no sibling);
    ``axes`` is a same-structure tree (the *base* page axes) threaded
    through so ``fn`` can tell paged leaves apart. ``jax.tree.map`` cannot
    add keys, hence the explicit walk — scale leaves must live beside their
    parents inside the cache pytree so they ride the jitted decode block's
    carry like any other cache leaf.
    """
    if isinstance(tree, dict):
        out = {}
        for name in tree:
            sub, ax = tree[name], axes[name]
            if isinstance(sub, (dict, list, tuple)):
                out[name] = _with_scale_siblings(sub, ax, fn)
            else:
                leaf, scale = fn(name, sub, ax)
                out[name] = leaf
                if scale is not None:
                    out[name + "_scale"] = scale
        return out
    if isinstance(tree, (list, tuple)):
        vals = []
        for sub, ax in zip(tree, axes):
            if isinstance(sub, (dict, list, tuple)):
                vals.append(_with_scale_siblings(sub, ax, fn))
            else:
                vals.append(fn(None, sub, ax)[0])
        return type(tree)(vals)
    return fn(None, tree, axes)[0]


class _TrieNode:
    __slots__ = ("chunk", "page", "children", "parent", "tick")

    def __init__(self, chunk, page, parent):
        self.chunk = chunk          # tuple of page_size token ids (None: root)
        self.page = page            # pool page index holding this chunk's K/V
        self.children: Dict[tuple, "_TrieNode"] = {}
        self.parent = parent
        self.tick = 0


class PrefixCache:
    """Radix trie over ``page_size``-token prompt chunks -> shared pages.

    Host-only bookkeeping: the trie stores page *indices*; the K/V bytes
    live in the pool. Each node holds one refcount on its page (taken at
    insert, released at eviction), so a page stays alive while any trie
    node or slot table points at it.
    """

    def __init__(self, page_size: int):
        self.page_size = int(page_size)
        self.root = _TrieNode(None, None, None)
        self.n_nodes = 0
        self._tick = 0

    def _touch(self, node: _TrieNode) -> None:
        self._tick += 1
        node.tick = self._tick

    def _chunks(self, prompt: Sequence[int]) -> List[tuple]:
        P = self.page_size
        return [tuple(prompt[i * P:(i + 1) * P])
                for i in range(len(prompt) // P)]

    def match(self, prompt: Sequence[int]
              ) -> Tuple[List[int], Optional[Tuple[int, int]]]:
        """-> (full_pages, partial). ``full_pages`` are pool pages for the
        longest run of whole prompt chunks present in the trie; ``partial``
        is ``(page, lcp_len)`` for the best divergent-chunk match (the
        copy-on-write source), or None."""
        P = self.page_size
        node = self.root
        pages: List[int] = []
        chunks = self._chunks(prompt)
        depth = 0
        for ch in chunks:
            child = node.children.get(ch)
            if child is None:
                break
            node = child
            self._touch(node)
            pages.append(node.page)
            depth += 1
        rem = tuple(prompt[depth * P:(depth + 1) * P])
        best: Optional[Tuple[int, int]] = None
        best_node: Optional[_TrieNode] = None
        if rem:
            for ch, child in node.children.items():
                n = 0
                for x, y in zip(ch, rem):
                    if x != y:
                        break
                    n += 1
                if n and (best is None or n > best[1]):
                    best = (child.page, n)
                    best_node = child
            # touch only the winning candidate: refreshing every scanned
            # runner-up would keep cold losing branches perpetually "recent"
            # and skew evict_lru toward dropping genuinely hot leaves
            if best_node is not None:
                self._touch(best_node)
        return pages, best

    def insert_path(self, chunks: Sequence[tuple],
                    pages: Sequence[int]) -> List[int]:
        """Walk/extend the trie along ``chunks``; returns the page indices
        that were newly inserted (caller owns bumping their refcounts).
        Existing nodes are kept — their pages hold identical K/V content by
        construction, so the walk just descends through them."""
        node = self.root
        added: List[int] = []
        for ch, pg in zip(chunks, pages):
            child = node.children.get(ch)
            if child is None:
                child = _TrieNode(ch, int(pg), node)
                node.children[ch] = child
                self.n_nodes += 1
                added.append(int(pg))
            node = child
            self._touch(node)
        return added

    def iter_nodes(self):
        stack = list(self.root.children.values())
        while stack:
            node = stack.pop()
            yield node
            stack.extend(node.children.values())

    def evict_lru(self, evictable=None) -> Optional[int]:
        """Drop the least-recently-matched *leaf*; returns its page (caller
        owns the refcount decrement), or None when no leaf qualifies.

        ``evictable``: optional page predicate. Leaves whose page fails it
        (e.g. one a slot table still maps — dropping the node would free
        nothing) are skipped rather than evicted."""
        leaf = None
        for node in self.iter_nodes():
            if node.children or \
                    (evictable is not None and not evictable(node.page)):
                continue
            if leaf is None or node.tick < leaf.tick:
                leaf = node
        if leaf is None:
            return None
        del leaf.parent.children[leaf.chunk]
        self.n_nodes -= 1
        return leaf.page

    def remap(self, lut: np.ndarray) -> None:
        """Rewrite node pages through a defrag LUT (old page -> new page)."""
        for node in self.iter_nodes():
            node.page = int(lut[node.page])


class PagedCachePool(CachePool):
    """CachePool whose attention K/V leaves live in a shared page pool.

    Slot bookkeeping (allocate/free/owner/keys/row-defrag) is inherited; the
    paged leaves are carved out of ``batch_axes`` so every inherited slot op
    skips them, and this class adds the page-table layer on top.
    """

    def __init__(self, cfg, num_slots: int, max_len: int, *,
                 page_size: int, rules=None, enc_len: Optional[int] = None,
                 num_pages: Optional[int] = None, kv_dtype: str = "f32"):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if kv_dtype not in ("f32", "int8"):
            raise ValueError(
                f"kv_dtype must be 'f32' or 'int8', got {kv_dtype!r}")
        if getattr(cfg, "family", None) == "audio" and enc_len is None:
            enc_len = max_len      # pin enc_len so the max_len diff is clean
        super().__init__(cfg, num_slots, max_len, rules=rules,
                         enc_len=enc_len)
        self.page_size = int(page_size)
        self.pages_per_slot = -(-self.max_len // self.page_size)   # ceil
        base_pax = _page_axes(cfg, self.max_len, self.enc_len,
                              self.batch_axes)
        self.has_paged = any(ax != _NO_BATCH
                             for ax in jax.tree.leaves(base_pax))
        self.kv_dtype = kv_dtype
        self.quantized = kv_dtype == "int8" and self.has_paged
        # +1 for the reserved scratch page 0; default backing is full
        # capacity, so reserve() can always succeed after trie eviction. An
        # int8 page (+ its f32 row/head scales) costs ~half the bytes of the
        # bf16 page it replaces, so the quantized default doubles the
        # backing — ~2x resident capacity at ~constant pool bytes.
        if num_pages is None:
            num_pages = 1 + self.num_slots * self.pages_per_slot * \
                (2 if self.quantized else 1)
        self.num_pages = int(num_pages)
        if self.num_pages < 2:
            raise ValueError("num_pages must cover scratch + one real page")
        # _base_page_axes matches the init_cache structure (no scale leaves);
        # page_axes/batch_axes below match the *actual* pool cache, which in
        # quantized mode carries k_scale/v_scale siblings. A scale leaf is
        # its parent minus the trailing head_dim axis, so its page axis sits
        # at the same index (pax - 1) — every page op (copy_page, defrag
        # take, LUT permute) applies to it unchanged under the parent's pax.
        self._base_page_axes = base_pax
        self.page_axes = base_pax
        # paged leaves leave the slot world: inherited ops must skip them
        self.batch_axes = jax.tree.map(
            lambda bax, pax: _NO_BATCH if pax != _NO_BATCH else bax,
            self.batch_axes, base_pax)
        if self.quantized:
            self.page_axes = _with_scale_siblings(
                base_pax, base_pax,
                lambda name, pax, _: (pax, pax if self._quant_leaf(name, pax)
                                      else None))
            self.batch_axes = _with_scale_siblings(
                self.batch_axes, base_pax,
                lambda name, bax, pax: (bax, _NO_BATCH
                                        if self._quant_leaf(name, pax)
                                        else None))
        self._tables = np.zeros((self.num_slots, self.pages_per_slot),
                                np.int32)
        self._n_pages = np.zeros((self.num_slots,), np.int32)
        self._ref = np.zeros((self.num_pages,), np.int32)
        self._ref[0] = 1                      # scratch page is always live
        self._free_pages: List[int] = list(range(1, self.num_pages))
        self.prefix = PrefixCache(self.page_size)

    # ----------------------------------------------------------- construction
    @staticmethod
    def _quant_leaf(name, pax) -> bool:
        """Paged attention K/V value leaves are the ones that quantize (and
        grow a scale sibling); whisper's cross K/V keep slot layout (``pax ==
        _NO_BATCH``) and are excluded along with conv/ssm state."""
        return pax != _NO_BATCH and name in ("k", "v")

    def _pool_arrays(self):
        """The pool cache pytree (pre-sharding) — paged leaves in page-pool
        layout, int8 + f32 scale siblings when quantized."""
        cache = init_cache(self.cfg, self.num_slots, self.max_len,
                           enc_len=self.enc_len)

        def paged_shape(leaf, pax):
            return (leaf.shape[:pax - 1] + (self.num_pages, self.page_size)
                    + leaf.shape[pax + 1:])

        if not self.quantized:
            return jax.tree.map(
                lambda leaf, pax: leaf if pax == _NO_BATCH
                else jnp.zeros(paged_shape(leaf, pax), leaf.dtype),
                cache, self._base_page_axes)

        def f(name, leaf, pax):
            if pax == _NO_BATCH:
                return leaf, None
            shp = paged_shape(leaf, pax)
            if not self._quant_leaf(name, pax):
                return jnp.zeros(shp, leaf.dtype), None
            # scale = parent minus the trailing head_dim axis: one f32 per
            # (page, row, kv head). Unwritten rows dequantize to 0 * 1.0.
            return (jnp.zeros(shp, jnp.int8),
                    jnp.ones(shp[:-1], jnp.float32))

        return _with_scale_siblings(cache, self._base_page_axes, f)

    def make_cache(self):
        cache = self._pool_arrays()
        if self.rules is not None and self.rules.n_devices > 1:
            cache = jax.device_put(cache, cache_shardings(cache, self.rules))
        return cache

    def kv_leaf(self) -> jax.ShapeDtypeStruct:
        """Shape and dtype of a paged K leaf,
        ``(..., num_pages, page_size, Hkv, head_dim)``: what the paged
        decode kernel reads a page of."""
        shapes = jax.tree_util.tree_leaves_with_path(
            jax.eval_shape(self._pool_arrays))
        return next(leaf for (path, leaf), pax
                    in zip(shapes, jax.tree.leaves(self.page_axes))
                    if self._quant_leaf(path[-1].key, pax))

    def page_bytes(self) -> int:
        """Bytes one pool page costs across every paged leaf — scale
        siblings included — i.e. pool bytes / num_pages for the paged part.
        The capacity bench sizes matched-byte pools with this."""
        shapes = jax.eval_shape(self._pool_arrays)
        total = 0
        for leaf, pax in zip(jax.tree.leaves(shapes),
                             jax.tree.leaves(self.page_axes)):
            if pax == _NO_BATCH:
                continue
            n = int(np.prod(leaf.shape)) // leaf.shape[pax - 1]
            total += n * leaf.dtype.itemsize
        return total

    def set_slot(self, cache, slot: int, row_cache):
        # the batch=1 row cache comes from init_cache and has no scale
        # leaves; pad its structure with dummies (their batch_axes entries
        # are _NO_BATCH, so the inherited write skips them)
        if self.quantized:
            row_cache = _with_scale_siblings(
                row_cache, self._base_page_axes,
                lambda name, leaf, pax: (leaf, jnp.zeros(())
                                         if self._quant_leaf(name, pax)
                                         else None))
        return super().set_slot(cache, slot, row_cache)

    # ------------------------------------------------------------ bookkeeping
    @property
    def tables(self) -> np.ndarray:
        """(num_slots, pages_per_slot) int32 host page table. Entries past a
        slot's reserved count are 0 (the scratch page). Read-only."""
        return self._tables

    @property
    def free_page_count(self) -> int:
        return len(self._free_pages)

    def live_page_count(self) -> int:
        return int(np.sum(self._ref[1:] > 0))

    def _take_free_page(self) -> int:
        while True:
            if self._free_pages:
                return heapq.heappop(self._free_pages)
            # only leaves the trie *solely* owns (refcount == the trie's own
            # single reference) can yield a free page. Evicting a slot-held
            # leaf frees nothing — the old loop did exactly that, wiping the
            # whole trie on its way to the same PageError and destroying
            # every future prefix hit in the process.
            pg = self.prefix.evict_lru(evictable=lambda p: self._ref[p] <= 1)
            if pg is None:
                raise PageError("page pool exhausted")
            self._decref(pg)

    def _decref(self, page: int) -> None:
        self._ref[page] -= 1
        assert self._ref[page] >= 0, f"page {page} refcount underflow"
        if self._ref[page] == 0:
            heapq.heappush(self._free_pages, page)

    def reserve(self, slot: int, upto_len: int) -> None:
        """Grow ``slot``'s table to cover positions [0, min(upto_len,
        max_len)). Called before each fused k-block dispatch so the table is
        constant within a block."""
        if slot not in self._owner:
            raise SlotError(f"slot {slot} is not allocated")
        need = -(-min(int(upto_len), self.max_len) // self.page_size)
        n = int(self._n_pages[slot])
        while n < need:
            pg = self._take_free_page()
            self._ref[pg] += 1
            self._tables[slot, n] = pg
            n += 1
        self._n_pages[slot] = n

    def free(self, slot: int) -> None:
        if slot not in self._owner:
            raise SlotError(f"slot {slot} is not allocated")
        for i in range(int(self._n_pages[slot])):
            self._decref(int(self._tables[slot, i]))
        # stale frozen-slot writes (and any read) now divert to scratch
        self._tables[slot, :] = 0
        self._n_pages[slot] = 0
        super().free(slot)

    # -------------------------------------------------------- prefix sharing
    def map_prefix(self, slot: int, prompt: Sequence[int]
                   ) -> Tuple[int, Optional[Tuple[int, int]]]:
        """Map trie-shared prompt-prefix pages into ``slot``'s table.

        Returns ``(m, cow)``: ``m`` prompt tokens whose K/V is already in
        the mapped pages (prefill for them is skipped — the slot starts at
        ``lengths == m``), and ``cow = (src, dst)`` when the last matched
        chunk was partial: the caller must device-copy page ``src`` into the
        freshly allocated ``dst`` before decoding. The match is capped at
        ``len(prompt) - 1`` so the final prompt token is always consumed
        in-loop (it primes the first emission).
        """
        if slot not in self._owner:
            raise SlotError(f"slot {slot} is not allocated")
        if int(self._n_pages[slot]):
            raise PageError(f"slot {slot} already holds pages")
        full, partial = self.prefix.match(prompt)
        P = self.page_size
        m = len(full) * P + (partial[1] if partial else 0)
        m = min(m, len(prompt) - 1, self.max_len - 1)
        if m <= 0:
            return 0, None
        n_full, part = divmod(m, P)
        cow = None
        for i in range(n_full):
            pg = full[i]
            self._ref[pg] += 1
            self._tables[slot, i] = pg
        if part:
            src = full[n_full] if n_full < len(full) else partial[0]
            dst = self._take_free_page()
            self._ref[dst] += 1
            self._tables[slot, n_full] = dst
            cow = (src, dst)
        self._n_pages[slot] = n_full + (1 if part else 0)
        return m, cow

    def register_prefix(self, slot: int, prompt: Sequence[int],
                        written_len: int) -> int:
        """Publish ``slot``'s fully written whole-prompt pages to the trie.

        Idempotent — existing trie nodes are descended through, not
        replaced (their pages hold identical K/V by construction). Only
        pages entirely inside the prompt *and* entirely written
        (``written_len`` tokens consumed) are published. Returns the number
        of pages newly inserted."""
        if slot not in self._owner:
            raise SlotError(f"slot {slot} is not allocated")
        P = self.page_size
        limit = min(min(int(written_len), len(prompt)) // P,
                    int(self._n_pages[slot]))
        if limit <= 0:
            return 0
        chunks = self.prefix._chunks(prompt)[:limit]
        pages = [int(self._tables[slot, i]) for i in range(limit)]
        added = self.prefix.insert_path(chunks, pages)
        for pg in added:
            self._ref[pg] += 1                # the trie's own reference
        return len(added)

    # --------------------------------------------------------- n>1 fan-out
    def adopt_prompt_pages(self, src_slot: int, dst_slot: int,
                           n_tok: int) -> int:
        """Share ``src_slot``'s whole-prompt pages into ``dst_slot``'s table.

        Fan-out admission: the n streams of one request prefill the same
        prompt in lockstep, so every page that lies entirely inside the
        prompt holds identical K/V no matter which stream writes it — the
        siblings map the *same* refcounted pages (no bytes copied, no extra
        prefill residency) and only the boundary page (first divergent
        token) stays private. Returns the number of shared pages.
        """
        for s in (src_slot, dst_slot):
            if s not in self._owner:
                raise SlotError(f"slot {s} is not allocated")
        if int(self._n_pages[dst_slot]):
            raise PageError(f"slot {dst_slot} already holds pages")
        n_shared = min(int(n_tok) // self.page_size,
                       int(self._n_pages[src_slot]))
        for i in range(n_shared):
            pg = int(self._tables[src_slot, i])
            self._ref[pg] += 1
            self._tables[dst_slot, i] = pg
        self._n_pages[dst_slot] = n_shared
        return n_shared

    def map_cow_page(self, slot: int, index: int) -> int:
        """Allocate a fresh private page at ``table[slot, index]`` (the
        fan-out boundary-page CoW destination). Returns the new page; the
        caller owns the device ``copy_page`` into it."""
        if slot not in self._owner:
            raise SlotError(f"slot {slot} is not allocated")
        if int(self._n_pages[slot]) != index:
            raise PageError(
                f"slot {slot}: cow index {index} != next page "
                f"{int(self._n_pages[slot])}")
        dst = self._take_free_page()
        self._ref[dst] += 1
        self._tables[slot, index] = dst
        self._n_pages[slot] = index + 1
        return dst

    def pin_page(self, page: int) -> None:
        """Extra refcount hold — keeps a CoW source page off the eviction
        path while a fan-out admission is still issuing sibling copies."""
        self._ref[page] += 1

    def unpin_page(self, page: int) -> None:
        self._decref(page)

    def copy_page(self, cache, src: int, dst: int):
        """Device-copy pool page ``src`` into ``dst`` (copy-on-write)."""
        def f(leaf, pax):
            if pax == _NO_BATCH:
                return leaf
            ax = pax - 1                      # page axis replaced batch axis
            row = jax.lax.index_in_dim(leaf, src, axis=ax, keepdims=False)
            idx = (slice(None),) * ax + (dst,)
            return leaf.at[idx].set(row)
        return jax.tree.map(f, cache, self.page_axes)

    # ----------------------------------------------------------- page defrag
    def page_fragmentation(self) -> float:
        """Hole fraction of the occupied page span [1, max live page]."""
        live = np.flatnonzero(self._ref[1:] > 0) + 1
        if live.size == 0:
            return 0.0
        return 1.0 - live.size / int(live.max())

    def defrag_pages(self, cache):
        """Compact live pages to the front of the pool.

        Pure permutation along every page axis; tables, refcounts and trie
        pointers are remapped through the same LUT, so slot contents (and
        the emission-count PRNG stream) are unchanged. Returns the new
        cache pytree (may be ``cache`` itself when already compact)."""
        live = [0] + [int(p) for p in np.flatnonzero(self._ref[1:] > 0) + 1]
        dead = [p for p in range(self.num_pages) if self._ref[p] == 0]
        perm = np.asarray(live + dead, np.int32)
        if np.array_equal(perm, np.arange(self.num_pages)):
            return cache
        lut = np.empty((self.num_pages,), np.int32)
        lut[perm] = np.arange(self.num_pages, dtype=np.int32)
        perm_dev = jnp.asarray(perm)

        def f(leaf, pax):
            if pax == _NO_BATCH:
                return leaf
            return jnp.take(leaf, perm_dev, axis=pax - 1)

        new_cache = jax.tree.map(f, cache, self.page_axes)
        self._ref = self._ref[perm]
        self._tables = lut[self._tables]      # freed rows are 0 -> stay 0
        self.prefix.remap(lut)
        self._free_pages = list(range(len(live), self.num_pages))
        return new_cache

    def defrag(self, cache):
        """Slot-row defrag (inherited) + page-table row permutation."""
        new_cache, perm, mapping = super().defrag(cache)
        hp = np.asarray(perm)
        self._tables = self._tables[hp]
        self._n_pages = self._n_pages[hp]
        return new_cache, perm, mapping
