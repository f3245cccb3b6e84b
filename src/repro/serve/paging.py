"""The serving cache pool: request state in slots, attention K/V in pages,
and radix-style shared-prefix reuse.

A *slot* is one sequence of the engine's fused decode block. The pool owns
slot bookkeeping (allocate / free / row defrag, per-slot PRNG keys), page
bookkeeping (page tables, refcounts, the prefix trie, copy-on-write, page
defrag) and the pure-array operations on both; the engine owns the live
cache pytree itself (it is threaded through the jitted k-step decode block
as a carry).

Every leaf of the ``repro.models.init_cache`` pytree keeps one of two
layouts, *inferred* from shapes rather than hard-coded per family: the pool
eval_shapes ``init_cache`` at two batch sizes and at two ``max_len`` values
and diffs the shapes.

- A leaf whose sequence axis sits right after its batch axis is attention
  K/V. It trades its ``(L, num_slots, max_len, Hkv, Dh)`` rows for a page
  pool ``(L, num_pages, page_size, Hkv, Dh)``, stacked over the layers that
  read it, plus a host-side per-slot page table ``(num_slots,
  pages_per_slot) int32``. A slot's logical position ``p`` lives at pool
  page ``table[slot, p // page_size]``, row ``p % page_size``: the decode
  block scatters new K/V through the table and the ``paged_attention`` op
  gathers through it, so capacity is however many pages are written, not
  ``num_slots * max_len`` rows.
- Every other leaf with a batch axis — mamba2 conv/ssm state, whisper's
  ``enc_len``-sized cross K/V — keeps one row per slot (``zero_slot``,
  ``set_slot``, row ``defrag``). Batchless leaves (``pos``, MoE counts) are
  neither. A family without attention (mamba2) has no pages and no table.

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
remapped through the same LUT, and the emission-count PRNG keys are
untouched, so sampled streams stay bit-identical across page defrags.

Quantized pages (``kv_dtype="int8"``): the paged K/V leaves store int8
with a sibling f32 scale leaf per page (``k_scale``/``v_scale``, one scale
per page row per KV head — symmetric absmax over head_dim). Quantization
happens on scatter in the decode write path (``models.blocks``) and both
``paged_attention`` impls dequantize on read, so the scale leaves ride the
same page tables, CoW copies, LUT defrags and trie shares as the values
they scale. At the bf16 leaves the pool replaces, an int8 page plus its
scales costs ~(Dh+4)/(2*Dh) ≈ half the bytes — the default ``num_pages``
doubles accordingly, multiplying resident-request capacity ~2x at ~constant
pool bytes (``page_bytes`` exposes the exact accounting).

Sharding: with ``rules`` bound, the pool cache is laid out by
``repro.dist.cache_specs`` (pages@data and page rows@model, exactly where
the rows they replace put batch and sequence), so the serving engine runs
on the same production meshes as the trainer.

RNG state: each slot also carries a per-request PRNG key (``seed_slot`` /
``slot_keys``) consumed by the sampled decode path (``repro.serve.sampling``).
The key is request state, not slot state — it is seeded at admission, zeroed
on free, and follows the request through defrag, which is what makes sampled
token streams independent of slot placement.
"""
from __future__ import annotations

import heapq
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import init_cache
from repro.dist import cache_shardings

#: axis entry of a leaf that has no such axis (a batchless leaf's slot axis,
#: a slot-row leaf's page axis)
_NO_AXIS = -1


class SlotError(RuntimeError):
    """Invalid slot transition (double allocate/free)."""


class PageError(RuntimeError):
    """Page pool exhausted (or invalid page transition)."""


def _layout_axes(cfg, max_len: int, enc_len: Optional[int]):
    """-> ``(slot_axes, seq_axes)``, pytrees over ``init_cache``'s leaves.

    A leaf's batch axis is the one axis that moves with the batch size. A
    leaf pages iff varying ``max_len`` (with ``enc_len`` pinned) moves
    exactly the axis right after its batch axis — the ``(..., B, seq,
    heads, head_dim)`` K/V layout of every attention family: its entry in
    ``seq_axes`` is that sequence axis, and in ``slot_axes`` ``_NO_AXIS``.
    Every other leaf keeps its batch axis in ``slot_axes``."""
    shapes = lambda b, n: jax.eval_shape(
        lambda: init_cache(cfg, b, n, enc_len=enc_len))
    base, wide, deep = shapes(2, max_len), shapes(3, max_len), \
        shapes(2, max_len + 1)

    def moved(x, y):
        return [i for i, (p, q) in enumerate(zip(x.shape, y.shape)) if p != q]

    def seq(x, y, z):
        b = moved(x, y)
        assert len(b) <= 1, f"ambiguous batch axis for shape {x.shape}"
        return b[0] + 1 if b and moved(x, z) == [b[0] + 1] else _NO_AXIS

    def slot(x, y, z):
        b = moved(x, y)
        return b[0] if b and seq(x, y, z) == _NO_AXIS else _NO_AXIS

    return (jax.tree.map(slot, base, wide, deep),
            jax.tree.map(seq, base, wide, deep))


def _with_scale_siblings(tree, axes, fn):
    """Rebuild the dict pytree ``tree``, giving paged K/V leaves a
    ``<name>_scale`` sibling.

    ``fn(name, leaf, ax) -> (new_leaf, scale_or_None)`` decides both the
    leaf transform and whether a sibling is added (None: no sibling);
    ``axes`` is a same-structure tree (the *base* page axes) threaded
    through so ``fn`` can tell paged leaves apart. ``jax.tree.map`` cannot
    add keys, hence the explicit walk — scale leaves must live beside their
    parents inside the cache pytree so they ride the jitted decode block's
    carry like any other cache leaf.
    """
    out = {}
    for name, sub in tree.items():
        if isinstance(sub, dict):
            out[name] = _with_scale_siblings(sub, axes[name], fn)
            continue
        out[name], scale = fn(name, sub, axes[name])
        if scale is not None:
            out[name + "_scale"] = scale
    return out


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


class CachePool:
    """Slots and pages of the serving cache for ``num_slots`` sequences of
    up to ``max_len`` tokens, ``page_size`` tokens a page (module
    docstring)."""

    def __init__(self, cfg, num_slots: int, max_len: int, *,
                 page_size: int, rules=None, enc_len: Optional[int] = None,
                 num_pages: Optional[int] = None, kv_dtype: str = "f32"):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if page_size is None or page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if kv_dtype not in ("f32", "int8"):
            raise ValueError(
                f"kv_dtype must be 'f32' or 'int8', got {kv_dtype!r}")
        if getattr(cfg, "family", None) == "audio" and enc_len is None:
            enc_len = max_len      # pin enc_len so the max_len diff is clean
        self.cfg = cfg
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.enc_len = enc_len
        self.rules = rules
        self.page_size = int(page_size)
        slot_axes, seq_axes = _layout_axes(cfg, self.max_len, enc_len)
        self.has_paged = any(ax != _NO_AXIS
                             for ax in jax.tree.leaves(seq_axes))
        # a pool without attention K/V has no pages: empty tables
        self.pages_per_slot = -(-self.max_len // self.page_size) \
            if self.has_paged else 0
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
        if self.has_paged and self.num_pages < 2:
            raise ValueError("num_pages must cover scratch + one real page")
        # ``_seq_axes`` matches the init_cache structure (no scale leaves);
        # ``page_axes``/``batch_axes`` match the pool cache, which in
        # quantized mode carries k_scale/v_scale siblings. A scale leaf is
        # its parent minus the trailing head_dim axis, so its page axis sits
        # at the same index (pax - 1) — every page op (copy_page, defrag
        # take, LUT permute) applies to it unchanged under the parent's pax.
        self._seq_axes = seq_axes
        self.page_axes, self.batch_axes = seq_axes, slot_axes
        if self.quantized:
            sibling = lambda tree, ax_of: _with_scale_siblings(
                tree, seq_axes, lambda name, ax, pax: (
                    ax, ax_of(pax) if self._quant_leaf(name, pax) else None))
            self.page_axes = sibling(seq_axes, lambda pax: pax)
            self.batch_axes = sibling(slot_axes, lambda pax: _NO_AXIS)
        # slots: a min-heap keeps live slots packed at the front (lowest
        # index first) with O(log n) frees
        self._free: List[int] = list(range(self.num_slots))
        self._owner: Dict[int, str] = {}
        # per-slot PRNG key data (jax.random.PRNGKey rows) for sampled decode
        self._keys = np.zeros((self.num_slots, 2), np.uint32)
        # pages
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
        _NO_AXIS``) and are excluded along with conv/ssm state."""
        return pax != _NO_AXIS and name in ("k", "v")

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
                lambda leaf, pax: leaf if pax == _NO_AXIS
                else jnp.zeros(paged_shape(leaf, pax), leaf.dtype),
                cache, self._seq_axes)

        def f(name, leaf, pax):
            if pax == _NO_AXIS:
                return leaf, None
            shp = paged_shape(leaf, pax)
            if not self._quant_leaf(name, pax):
                return jnp.zeros(shp, leaf.dtype), None
            # scale = parent minus the trailing head_dim axis: one f32 per
            # (page, row, kv head). Unwritten rows dequantize to 0 * 1.0.
            return (jnp.zeros(shp, jnp.int8),
                    jnp.ones(shp[:-1], jnp.float32))

        return _with_scale_siblings(cache, self._seq_axes, f)

    def make_cache(self):
        """Fresh pool cache pytree; ownership passes to the caller."""
        cache = self._pool_arrays()
        if self.rules is not None and self.rules.n_devices > 1:
            cache = jax.device_put(cache, cache_shardings(cache, self.rules))
        return cache

    def kv_leaf(self) -> jax.ShapeDtypeStruct:
        """Shape and dtype of a paged K leaf,
        ``(L, num_pages, page_size, Hkv, head_dim)``: what the paged
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
            if pax == _NO_AXIS:
                continue
            n = int(np.prod(leaf.shape)) // leaf.shape[pax - 1]
            total += n * leaf.dtype.itemsize
        return total

    # ------------------------------------------------------- slot bookkeeping
    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def live_count(self) -> int:
        return len(self._owner)

    def live_slots(self) -> List[int]:
        return sorted(self._owner)

    def owner(self, slot: int) -> Optional[str]:
        return self._owner.get(slot)

    def _check(self, slot: int) -> None:
        if slot not in self._owner:
            raise SlotError(f"slot {slot} is not allocated")

    def allocate(self, request_id: str) -> int:
        if not self._free:
            raise SlotError("cache pool exhausted")
        slot = heapq.heappop(self._free)
        assert slot not in self._owner, "free list / owner map out of sync"
        self._owner[slot] = request_id
        return slot

    def free(self, slot: int) -> None:
        """Release ``slot`` and its references to its pages."""
        self._check(slot)
        for i in range(int(self._n_pages[slot])):
            self._decref(int(self._tables[slot, i]))
        # stale frozen-slot writes (and any read) now divert to scratch
        self._tables[slot, :] = 0
        self._n_pages[slot] = 0
        del self._owner[slot]
        self._keys[slot] = 0               # request key dies with the request
        heapq.heappush(self._free, slot)

    def seed_slot(self, slot: int, seed: int) -> None:
        """Bind a slot's PRNG key to a request seed (sampled decode). The
        key is per-request: it survives defrag along with the cache rows and
        is zeroed when the slot is freed.

        The key data is built on host — the threefry2x32 layout of
        ``jax.random.PRNGKey``, [seed >> 32, seed & 0xffffffff] — rather
        than materializing a device PRNGKey and fetching it back: seeding
        happens at admission, and a device round trip there would be an
        uncounted host sync per sampled request (the ``obs.sync_audit``
        boundary check caught exactly that)."""
        self._check(slot)
        self._keys[slot] = np.array([seed >> 32, seed & 0xFFFFFFFF],
                                    np.uint32)

    def set_slot_key(self, slot: int, key) -> None:
        """Bind a slot to pre-derived raw key data ((2,) uint32 threefry
        words). The n>1 fan-out path derives stream i's key as
        ``host_fold_in(base_key, i)`` — still host-only, same no-hidden-sync
        contract as :meth:`seed_slot`."""
        self._check(slot)
        self._keys[slot] = np.asarray(key, np.uint32).reshape(2)

    @property
    def slot_keys(self) -> np.ndarray:
        """(num_slots, 2) uint32 per-slot key data (zeros for greedy/free)."""
        return self._keys

    def fragmentation(self) -> float:
        """Hole fraction of the occupied slot span [0, max live slot]."""
        if not self._owner:
            return 0.0
        span = max(self._owner) + 1
        return 1.0 - len(self._owner) / span

    # ------------------------------------------------------- pure slot ops
    def zero_slot(self, cache, slot: int):
        """Zero one slot's rows (required for SSM state reuse; stale K/V
        pages are invisible behind per-slot kv_valid)."""
        def f(leaf, ax):
            if ax == _NO_AXIS:
                return leaf
            idx = (slice(None),) * ax + (slot,)
            return leaf.at[idx].set(jnp.zeros((), leaf.dtype))
        return jax.tree.map(f, cache, self.batch_axes)

    def set_slot(self, cache, slot: int, rows):
        """Write batch=1 ``rows`` — a dict sub-tree of the cache's slot-row
        leaves, such as whisper's prefilled cross K/V — into ``slot``."""
        def put(leaf, row, ax):
            if isinstance(row, dict):
                return dict(leaf, **{n: put(leaf[n], row[n], ax[n])
                                     for n in row})
            idx = (slice(None),) * ax + (slot,)
            return leaf.at[idx].set(
                jnp.take(row, 0, axis=ax).astype(leaf.dtype))
        return put(cache, rows, self.batch_axes)

    def defrag(self, cache) -> Tuple[object, List[int], Dict[int, int]]:
        """Compact live slots to the lowest indices, preserving contents.

        Returns ``(new_cache, perm, mapping)``: ``perm`` is the old-slot
        permutation applied along every slot-row axis and to the page
        tables (new slot i holds old slot ``perm[i]``) — callers must apply
        the same ``jnp.take(..., perm)`` to any per-slot side arrays
        (lengths, tokens, masks); ``mapping`` is old->new for the live
        slots only.
        """
        live = self.live_slots()
        perm = live + [s for s in range(self.num_slots) if s not in self._owner]
        mapping = {old: new for new, old in enumerate(live)}
        perm_dev = jnp.asarray(perm, jnp.int32)

        def f(leaf, ax):
            if ax == _NO_AXIS:
                return leaf
            return jnp.take(leaf, perm_dev, axis=ax)

        new_cache = jax.tree.map(f, cache, self.batch_axes)
        self._owner = {mapping[s]: rid for s, rid in self._owner.items()}
        # ascending range is already a valid min-heap
        self._free = list(range(len(live), self.num_slots))
        hp = np.asarray(perm)
        self._keys = self._keys[hp]          # keys follow their request
        self._tables = self._tables[hp]
        self._n_pages = self._n_pages[hp]
        return new_cache, perm, mapping

    def take_rows(self, per_slot, perm):
        """Apply a defrag permutation to a (num_slots, ...) device array."""
        return jnp.take(per_slot, jnp.asarray(perm, jnp.int32), axis=0)

    # ------------------------------------------------------- page bookkeeping
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
        constant within a block. A pool without pages reserves none."""
        self._check(slot)
        need = min(-(-int(upto_len) // self.page_size), self.pages_per_slot)
        n = int(self._n_pages[slot])
        while n < need:
            pg = self._take_free_page()
            self._ref[pg] += 1
            self._tables[slot, n] = pg
            n += 1
        self._n_pages[slot] = n

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
        self._check(slot)
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
        self._check(slot)
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
            self._check(s)
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
        self._check(slot)
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
            if pax == _NO_AXIS:
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
            if pax == _NO_AXIS:
                return leaf
            return jnp.take(leaf, perm_dev, axis=pax - 1)

        new_cache = jax.tree.map(f, cache, self.page_axes)
        self._ref = self._ref[perm]
        self._tables = lut[self._tables]      # freed rows are 0 -> stay 0
        self.prefix.remap(lut)
        self._free_pages = list(range(len(live), self.num_pages))
        return new_cache
