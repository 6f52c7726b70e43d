"""Block-paged KV pool with radix prefix sharing.

The serving engine's KV memory model (docs/advanced-guide/kv-cache.md):
ONE device-resident pool of fixed-size blocks (``TPU_LLM_KV_BLOCK``
tokens of K/V per layer each) replaces the per-slot contiguous slabs.
Every request owns a BLOCK TABLE — logical row ``p`` of its sequence
lives at pool row ``table[p // B] * B + p % B`` — and blocks are
refcounted so sibling prompts share every common prefix block in place
(vLLM's PagedAttention memory model; Kwon et al. 2023), while a radix
tree over token ids (SGLang's RadixAttention; Zheng et al. 2024)
generalizes the old whole-row prefix cache: a lookup returns the longest
block-aligned shared prefix across EVERYTHING ever published — sibling
prompts, finished conversations, mid-prompt splits — not just exact
whole-prompt rows.

Three host-side classes own the bookkeeping (all mutated only under the
CacheManager lock — see the threading note on CacheManager):

- :class:`BlockPool` — refcounts, free list, copy-on-write planning.
  The COW invariant this file is built around: **no write ever lands in
  a block with refcount > 1**. Shared blocks sit strictly below every
  writer's cursor (the radix shares only full, immutable prefix
  blocks; partial tail blocks are shared by COPY), and
  ``ensure_writable`` enforces the invariant mechanically for any
  future caller that breaks the construction.
- :class:`SlotTable` — one block table per engine slot, grown as the
  cursor advances ("allocate blocks as the cursor advances" replaces
  the old ``window + max(decode_chunk, chunk, verify_width)`` ring-slack
  arithmetic: the reservation is taken once at admission, blocks
  materialize lazily).
- :class:`RadixTree` — token-id trie at block granularity. Interior
  spans are multiples of the block size; exact-prompt entries attach a
  copied partial-tail block plus the stored last-token logits, so exact
  hits still skip prefill entirely (the PrefixCache contract).

Device-side helpers (pure jnp, traced into the engine's jitted
programs): ``gather_slots`` materializes the dense per-slot view from
the pool through the tables (the off-TPU path beside the Pallas
paged-attention kernel in gofr_tpu.ops.attention), ``scatter_rows``
writes freshly-computed K/V rows through the tables (indices computed
FROM DEVICE STATE, so speculative rollback and pipelined verifies can
never mis-aim a write), and the int8 row codec halves the decode HBM
stream when ``TPU_LLM_KV_INT8`` is on.
"""

from __future__ import annotations

import time
from typing import Any, NamedTuple

import numpy as np

__all__ = [
    "BlockPool",
    "SlotTable",
    "WindowTables",
    "RadixTree",
    "RadixMatch",
    "gather_slots",
    "gather_ring",
    "split_tables",
    "scatter_rows",
    "scatter_rows_by_kind",
    "copy_blocks",
    "gather_blocks_host",
    "stored_rows",
    "viewed_rows",
    "quantize_rows",
    "dequantize_rows",
]


class PoolExhausted(RuntimeError):
    """No free block and nothing evictable — callers queue, never crash."""


# ---------------------------------------------------------------------------
# Block pool (host bookkeeping)
# ---------------------------------------------------------------------------


class BlockPool:
    """Refcounted free-list over ``n_blocks`` device blocks of ``block``
    tokens each. Pure host bookkeeping: the device arrays live with the
    engine (donated through every jitted program); this class only
    decides WHICH pool rows a sequence may read and write.

    One pool serves the layers of ONE kind. A stack whose layers all attend
    alike has one; a mixed stack (window and full layers) has two, each over
    its own device arrays [L_kind, NB, B, W] with its own block ids: the
    full layers' grows with the context, the window layers' gives a block
    back as soon as every row of it lies behind the window of every query
    still to come (CacheManager.ensure), so a slot never holds more of it
    than WindowTables.bound blocks.

    Not internally locked — every caller goes through the CacheManager
    lock (one mutator at a time; the engine's scheduler thread owns all
    allocation, the collector only publishes/releases through the same
    lock)."""

    def __init__(self, n_blocks: int, block: int, block_bytes: int):
        if n_blocks < 1 or block < 1:
            raise ValueError(f"pool needs >= 1 block of >= 1 tokens, got {n_blocks}x{block}")
        self.n_blocks = int(n_blocks)
        self.block = int(block)
        self.block_bytes = int(block_bytes)
        self.refs = np.zeros(self.n_blocks, np.int32)
        # LIFO free stack: recently-freed blocks are re-used first (their
        # pool rows are likelier to still be in cache on host mirrors)
        self._free: list[int] = list(range(self.n_blocks - 1, -1, -1))
        # reservation accounting: blocks promised to admitted requests
        # but not yet materialized. alloc() draws down the caller's
        # reservation; available() subtracts promises from free blocks so
        # admission can never over-commit the pool.
        self.reserved = 0
        self.cow_copies = 0  # copy-on-write splits performed (telemetry)

    # -- queries ----------------------------------------------------------
    def blocks_in_use(self) -> int:
        return self.n_blocks - len(self._free)

    def blocks_shared(self) -> int:
        return int(np.count_nonzero(self.refs > 1))

    def available(self) -> int:
        """Free blocks not yet promised to anyone."""
        return len(self._free) - self.reserved

    def bytes_in_use(self) -> int:
        return self.blocks_in_use() * self.block_bytes

    # -- reservation ------------------------------------------------------
    def reserve(self, n: int) -> bool:
        """Promise ``n`` blocks to an admitted request. False = the pool
        cannot honor it right now (caller keeps the request queued)."""
        if n > self.available():
            return False
        self.reserved += n
        return True

    def unreserve(self, n: int) -> None:
        self.reserved = max(0, self.reserved - n)

    # -- alloc/free -------------------------------------------------------
    def alloc(self, n: int = 1, *, reserved: bool = False) -> list[int]:
        """Take ``n`` fresh blocks (refcount 1 each). ``reserved=True``
        draws down a prior reserve() promise instead of free headroom."""
        if n > len(self._free):
            raise PoolExhausted(f"need {n} blocks, {len(self._free)} free")
        if not reserved and n > self.available():
            raise PoolExhausted(
                f"need {n} unreserved blocks, {self.available()} available"
            )
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self.refs[b] = 1
        if reserved:
            self.reserved = max(0, self.reserved - n)
        return out

    def incref(self, blocks) -> None:
        for b in blocks:
            if self.refs[b] <= 0:
                raise ValueError(f"incref on free block {b}")
            self.refs[b] += 1

    def decref(self, blocks) -> int:
        """Drop one reference per block; fully-released blocks return to
        the free list. Returns how many blocks were freed."""
        freed = 0
        for b in blocks:
            if self.refs[b] <= 0:
                raise ValueError(f"decref on free block {b}")
            self.refs[b] -= 1
            if self.refs[b] == 0:
                self._free.append(b)
                freed += 1
        return freed

    def ensure_writable(self, block: int, *, reserved: bool = False) -> int | None:
        """Copy-on-write seam: writers call this for every block a write
        window touches. refcount 1 -> the block is private, write in
        place (returns None). refcount > 1 -> allocate a fresh block and
        return its id; the caller must device-copy the old contents and
        repoint its table BEFORE writing (the old block keeps serving its
        other readers untouched). This is what makes the "no write ever
        lands in a shared block" invariant mechanical rather than
        assumed."""
        if self.refs[block] <= 0:
            raise ValueError(f"write planned into free block {block}")
        if self.refs[block] == 1:
            return None
        fresh = self.alloc(1, reserved=reserved)[0]
        self.refs[block] -= 1  # writer's reference migrates to the copy
        self.cow_copies += 1
        return fresh


# ---------------------------------------------------------------------------
# Per-slot block tables
# ---------------------------------------------------------------------------


class SlotTable:
    """One engine slot's logical-row -> pool-block mapping.

    ``rows[j]`` is the pool block holding logical positions
    ``[j*B, (j+1)*B)``. Entries beyond ``hi`` are stale (whatever block
    id was there last — gathers read them, masks hide them, writes never
    touch them). ``shared`` counts leading table entries that reference
    radix-shared blocks (refcount > 1, read-only for this slot); every
    entry at index >= ``shared`` is private (refcount 1)."""

    __slots__ = ("rows", "hi", "shared", "reserved", "owner")

    def __init__(self, width: int):
        self.rows = np.zeros(width, np.int32)
        self.hi = 0  # table entries materialized
        self.shared = 0  # leading entries that are radix-shared (read-only)
        self.reserved = 0  # blocks promised at admission, not yet drawn
        self.owner: Any = None  # engine-side occupancy token

    def blocks(self) -> list[int]:
        return [int(b) for b in self.rows[: self.hi]]

    def private_blocks(self) -> list[int]:
        return [int(b) for b in self.rows[self.shared : self.hi]]


class WindowTables:
    """The window layers' blocks of a mixed stack: a pool of their own and
    one table a slot, of the SAME logical width as the full layers' (entry j
    names the block of positions [j*B, (j+1)*B)), of which only the entries
    [lo, hi) are live. `advance` materializes blocks up to the cursor and
    gives back every block that no query still to come can read; an entry
    below `lo` keeps naming its freed (perhaps re-used) block and is never
    dereferenced: the decode kernel walks only the pages that meet the band
    [hi - window, hi) (ops.attention._paged_decode_kernel), the prompt
    chunks gather the last `ring` positions (gather_ring), and both lie
    above `lo` by construction.

    What a slot can hold at most, `bound` blocks: the window's own keys
    below a program's first query, the rows one program appends
    (append_slack) and `margin` rows of safety below the window (the
    engine's merge slack: two decode chunks), in whole blocks, plus one
    for where the first block starts. The pool is `slots x bound` blocks,
    so it cannot run out and takes no reservation."""

    def __init__(self, slots: int, width: int, block: int, window: int,
                 append_slack: int, margin: int, block_bytes: int):
        self.block, self.window, self.margin = int(block), int(window), int(margin)
        self.bound = -(-(self.window - 1 + self.margin + int(append_slack)) // self.block) + 1
        self.pool = BlockPool(slots * self.bound, block, block_bytes)
        self.rows = np.zeros((slots, width), np.int32)
        self.lo = [0] * slots  # first live entry
        self.hi = [0] * slots  # one past the last
        self.upto = [0] * slots  # rows materialized at the last advance
        self.reclaimed = 0  # blocks given back behind the window
        self.peak = 0  # most blocks any slot has held

    def advance(self, slot: int, upto: int) -> tuple[bool, int]:
        """Rows [0, upto) of `slot` are about to be readable or written by
        the program being dispatched, whose first query stands at the
        previous `upto` or later: -> (the table changed, blocks freed)."""
        B = self.block
        keep = max(0, self.upto[slot] - (self.window - 1) - self.margin) // B
        lo, hi = self.lo[slot], self.hi[slot]
        freed = 0
        if keep > lo:
            gone = [int(b) for b in self.rows[slot, lo:min(keep, hi)]]
            freed = self.pool.decref(gone)
            self.reclaimed += freed
            lo = keep
        need = -(-max(0, int(upto)) // B)
        first = max(hi, lo)
        if need > first:
            self.rows[slot, first:need] = np.asarray(self.pool.alloc(need - first), np.int32)
            hi = need
        changed = (lo, hi) != (self.lo[slot], self.hi[slot])
        self.lo[slot], self.hi[slot] = lo, max(hi, lo)
        self.upto[slot] = max(self.upto[slot], int(upto))
        self.peak = max(self.peak, self.hi[slot] - lo)
        return changed, freed

    def release(self, slot: int) -> None:
        lo, hi = self.lo[slot], self.hi[slot]
        if hi > lo:
            self.pool.decref([int(b) for b in self.rows[slot, lo:hi]])
        self.lo[slot] = self.hi[slot] = self.upto[slot] = 0

    def held(self, slot: int) -> int:
        return self.hi[slot] - self.lo[slot]

    def stats(self) -> dict:
        return {
            "window": self.window,
            "pool_blocks": self.pool.n_blocks,
            "blocks_in_use": self.pool.blocks_in_use(),
            # what the same slots would hold had nothing been given back
            "blocks_unreclaimed": int(sum(self.hi)),
            "blocks_reclaimed": self.reclaimed,
            "bound_blocks_per_slot": self.bound,
            "peak_blocks_per_slot": self.peak,
            "block_bytes": self.pool.block_bytes,
        }


# ---------------------------------------------------------------------------
# Radix tree (block-granular prefix index)
# ---------------------------------------------------------------------------


class _End:
    """An exact published sequence ending at this node: the sub-block
    tail rows (COPIED into a radix-owned block at publish — the writer's
    own tail block keeps receiving decode rows) plus optional last-token
    logits for prefill-skipping exact hits."""

    __slots__ = ("tail_block", "tail_len", "logits", "nbytes", "last_use")

    def __init__(self, tail_block, tail_len, logits, nbytes):
        self.tail_block = tail_block  # pool block id or None
        self.tail_len = int(tail_len)
        self.logits = logits  # [1, vocab] device array or None
        self.nbytes = int(nbytes)
        self.last_use = time.monotonic()


class RadixNode:
    __slots__ = ("tokens", "blocks", "children", "parent", "refs", "ends", "last_use")

    def __init__(self, tokens: tuple, blocks: list[int], parent):
        self.tokens = tokens  # edge label; len % block == 0
        self.blocks = blocks  # one pool block per `block` tokens of the edge
        # keyed by the edge's FIRST whole block group (a tuple of `block`
        # token ids): two edges may share a first token yet diverge
        # mid-block, and sub-block prefixes are not shareable anyway —
        # group keys make every found child match at least one group
        self.children: dict[tuple, RadixNode] = {}
        self.parent = parent
        self.refs = 0  # long-lived pins (sessions)
        self.ends: dict[tuple, _End] = {}
        self.last_use = time.monotonic()

    def depth_tokens(self) -> int:
        n, node = 0, self
        while node.parent is not None:
            n += len(node.tokens)
            node = node.parent
        return n


class RadixMatch(NamedTuple):
    blocks: list[int]  # shared full prefix blocks, in order
    shared: int  # shared tokens (= len(blocks) * block)
    end: Any  # _End for an exact match, else None
    node: Any  # deepest fully-matched node (touch/pin target)


class RadixTree:
    """Token-id trie at block granularity over pool blocks.

    Every edge label is a multiple of ``block`` tokens and carries one
    pool block per group; exact published prompts additionally attach an
    ``_End`` (copied partial tail + stored logits). ``lookup`` is the
    generalization of the old ``PrefixCache.lookup_longest``: the
    longest shared prefix is found per-BLOCK against everything ever
    published, so sibling prompts share every common block, not just
    exact whole rows. Mutations happen only under the CacheManager lock.
    """

    def __init__(self, pool: BlockPool, block: int, capacity_bytes: int = 0):
        self.pool = pool
        self.block = int(block)
        # 0 = unbounded (pool pressure still evicts via evict_for)
        self.capacity_bytes = int(capacity_bytes)
        self.root = RadixNode((), [], None)
        self.owned_bytes = 0  # blocks + tails + logits the radix holds refs on
        self.nodes = 0
        self.hits = 0  # exact hits (lookup returned an end record)
        self.partial_hits = 0  # block-granular prefix hits
        self.misses = 0
        self.stores = 0
        self.evictions = 0

    # -- internals --------------------------------------------------------
    def _matched_groups(self, edge: tuple, tokens: list, at: int, limit: int) -> int:
        """Whole B-token groups of ``edge`` equal to tokens[at:], capped
        so a match never extends past ``limit`` tokens of the query."""
        B = self.block
        g = 0
        max_g = min(len(edge), limit - at) // B
        while g < max_g and tuple(tokens[at + g * B : at + (g + 1) * B]) == edge[g * B : (g + 1) * B]:
            g += 1
        return g

    def _charge(self, nbytes: int) -> None:
        self.owned_bytes += nbytes

    # -- queries ----------------------------------------------------------
    def lookup(self, tokens, *, max_shared: int | None = None) -> RadixMatch:
        """Longest block-aligned shared prefix of ``tokens``. When the
        FULL sequence (including its sub-block tail) was published with
        an end record, ``end`` carries it (exact hit: tail rows + stored
        logits). ``max_shared`` caps the shared prefix (the engine clamps
        to prompt_len - 1 so an exact-length partial hit still leaves one
        token to prefill for last-token logits)."""
        B = self.block
        n = len(tokens)
        limit = n if max_shared is None else min(n, max_shared)
        node, i, blocks = self.root, 0, []
        while i + B <= limit:
            child = node.children.get(tuple(tokens[i : i + B]))
            if child is None:
                break
            g = self._matched_groups(child.tokens, tokens, i, limit)
            blocks.extend(child.blocks[:g])
            i += g * B
            if g * B < len(child.tokens):
                # mid-edge divergence: the shared blocks are counted but
                # `node` stays the last FULLY matched node (exact checks
                # and pins anchor on whole nodes)
                break
            node = child
        now = time.monotonic()
        cur = node
        while cur is not None:  # touch the matched path (LRU recency)
            cur.last_use = now
            cur = cur.parent
        end = None
        full = n - n % B
        if i == full and node.depth_tokens() == full:
            end = node.ends.get(tuple(tokens[full:]))
            if end is not None:
                end.last_use = now
        if end is not None:
            self.hits += 1
        elif blocks:
            self.partial_hits += 1
        else:
            self.misses += 1
        return RadixMatch(blocks=[int(b) for b in blocks], shared=len(blocks) * B, end=end, node=node)

    # -- mutation ---------------------------------------------------------
    def insert(
        self,
        tokens,
        blocks: list[int],
        *,
        tail_block: int | None = None,
        tail_len: int = 0,
        logits=None,
        logits_nbytes: int = 0,
    ) -> tuple[RadixNode, tuple]:
        """Publish a sequence: adopt its FULL prefix blocks (one ref per
        block the tree does not already cover — existing prefix paths are
        deduplicated, the publisher's duplicate blocks simply retire with
        its slot) and attach an end record when a copied ``tail_block``
        (and/or ``logits``) is provided. Returns (leaf node, end key) —
        the session pin target."""
        B = self.block
        n = len(tokens)
        full = n - n % B
        node, i = self.root, 0
        while i < full:
            key = tuple(tokens[i : i + B])
            child = node.children.get(key)
            if child is None:
                take = blocks[i // B : full // B]
                new = RadixNode(tuple(tokens[i:full]), [int(b) for b in take], node)
                self.pool.incref(new.blocks)
                self._charge(len(new.blocks) * self.pool.block_bytes)
                node.children[key] = new
                self.nodes += 1
                node, i = new, full
                break
            g = self._matched_groups(child.tokens, tokens, i, full)
            if g * B == len(child.tokens):
                node, i = child, i + len(child.tokens)
                continue
            # split the edge at the divergence (group-aligned: a found
            # child always matches >= 1 whole group, so g >= 1)
            top = RadixNode(child.tokens[: g * B], child.blocks[:g], node)
            top.children[tuple(child.tokens[g * B : (g + 1) * B])] = child
            child.tokens = child.tokens[g * B :]
            child.blocks = child.blocks[g:]
            child.parent = top
            node.children[key] = top
            self.nodes += 1
            node, i = top, i + g * B
            # loop continues: either diverging sibling (child is None
            # next round -> new node) or i == full (done)
        key = tuple(tokens[full:])
        if (tail_block is not None or logits is not None) and key not in node.ends:
            nbytes = (self.pool.block_bytes if tail_block is not None else 0) + int(logits_nbytes)
            node.ends[key] = _End(tail_block, tail_len, logits, nbytes)
            self._charge(nbytes)
            self.stores += 1
        else:
            if tail_block is not None:
                # a concurrent publish beat us to this exact end: the
                # freshly-copied tail is unwanted — release it or it
                # leaks a pool block forever
                self.pool.decref([tail_block])
            # even a pure block publish is a store event: the blocks are
            # now discoverable by every future sibling prompt
            self.stores += 1
        node.last_use = time.monotonic()
        if self.capacity_bytes:
            self.evict_to(self.capacity_bytes)
        return node, key

    def pin(self, node: RadixNode) -> None:
        node.refs += 1

    def unpin(self, node: RadixNode) -> None:
        node.refs = max(0, node.refs - 1)

    def _evict_node(self, node: RadixNode) -> int:
        """Drop one unpinned leaf: deref its blocks and end records."""
        freed = 0
        for e in node.ends.values():
            if e.tail_block is not None:
                freed += self.pool.decref([e.tail_block])
            self.owned_bytes -= e.nbytes
        node.ends.clear()
        freed += self.pool.decref(node.blocks)
        self.owned_bytes -= len(node.blocks) * self.pool.block_bytes
        parent = node.parent
        if parent is not None:
            parent.children.pop(tuple(node.tokens[: self.block]), None)
        self.nodes -= 1
        self.evictions += 1
        return freed

    def _evictable_leaves(self) -> list[RadixNode]:
        out = []
        stack = list(self.root.children.values())
        while stack:
            n = stack.pop()
            if n.children:
                stack.extend(n.children.values())
            elif n.refs == 0:
                out.append(n)
        out.sort(key=lambda n: n.last_use)
        return out

    def evict_to(self, budget_bytes: int) -> int:
        """LRU-evict unpinned leaves until retained bytes fit the budget.
        Each sorted leaf batch is CONSUMED before re-walking (evicting a
        leaf can expose its parent as the next leaf, but a fresh DFS +
        sort per evicted node would make eviction quadratic under the
        manager lock)."""
        freed = 0
        while self.owned_bytes > budget_bytes:
            leaves = self._evictable_leaves()
            if not leaves:
                break
            for n in leaves:
                if self.owned_bytes <= budget_bytes:
                    break
                freed += self._evict_node(n)
        return freed

    def evict_for(self, n_blocks: int) -> int:
        """Free at least ``n_blocks`` pool blocks by evicting LRU leaves
        (pool pressure path). Returns blocks actually freed — derefing a
        still-shared block frees nothing, so callers re-check the pool.
        Batch-consumes each sorted leaf list like evict_to."""
        freed = 0
        while freed < n_blocks:
            leaves = self._evictable_leaves()
            if not leaves:
                break
            for n in leaves:
                if freed >= n_blocks:
                    break
                freed += self._evict_node(n)
        return freed

    def clear(self) -> None:
        stack = list(self.root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            for e in n.ends.values():
                if e.tail_block is not None:
                    self.pool.decref([e.tail_block])
            self.pool.decref(n.blocks)
        self.root = RadixNode((), [], None)
        self.owned_bytes = 0
        self.nodes = 0

    def stats(self) -> dict:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "partial_hits": self.partial_hits,
            "evictions": self.evictions,
            "stores": self.stores,
            "entries": self.nodes,
            "resident_bytes": self.owned_bytes,
            "capacity_bytes": self.capacity_bytes,
        }


# ---------------------------------------------------------------------------
# Device-side helpers (traced into the engine's jitted programs)
# ---------------------------------------------------------------------------


def _flat(a):
    """[L, NB, B, W] -> [L * NB * B, W]: a merge of the MAJOR dimensions; the
    row's width stays the minor one, so with a block of whole sublane tiles
    the pool's tiled layout is untouched. One row axis and no batch axis:
    a scatter over it lands in place, where XLA's TPU scatter given the
    layers as a batch axis ([L, NB * B, W].at[:, rows]) transposes the whole
    pool to bring the scattered axis outermost, and back."""
    return a.reshape(-1, a.shape[-1])


def stored_rows(rows):
    """Rows as a token keeps them [..., h, d] (kvcache.row_shapes) -> as the
    pool stores them [..., h * d], head h the columns [h * d, (h + 1) * d).
    For rows that were gathered or are about to be written, never the pool."""
    return rows.reshape(rows.shape[:-2] + (-1,))


def viewed_rows(rows, row):
    """stored_rows undone: [..., h * d] -> [..., h, d] for ``row`` = (h, d)."""
    return rows.reshape(rows.shape[:-1] + tuple(row))


def gather_slots(pool_k, pool_v, tables, lengths, *, rows, scales=None, dtype=None):
    """Materialize the dense per-slot KV view THROUGH the block tables:
    logical row ``p`` of slot ``s`` comes from pool block
    ``tables[s, p // B]``, row ``p % B``. This is the dense-gather
    fallback for the Pallas paged-attention kernel — bit-exact with the
    contiguous layout, because gathering a slot's blocks in table order
    reconstructs the same [capacity, h, d] slab the contiguous engine
    holds. Stale table entries (>= the slot's allocated watermark) gather
    whatever block the entry last named; every such row sits outside the
    sequence's valid length and is masked by the exact same positional
    masks the contiguous path uses.

    The pools are [L, NB, B, W] as stored; ``rows`` (kvcache.row_shapes)
    says what [h, d] the GATHERED rows of each are viewed as. Returns a
    models.transformer.KVCache of shape [L, S, MB*B, h, d]. With ``scales``
    (int8 pool), rows are dequantized to ``dtype``."""
    import jax.numpy as jnp

    from ..models.transformer import KVCache
    from ..ops.attention import take_pages

    def take(pool, row, sc):
        L, _, B, _ = pool.shape
        every_layer = jnp.arange(L, dtype=jnp.int32)[:, None, None]
        g = take_pages(pool, every_layer, tables)  # [L, S, MB, B, W]
        S, MB = tables.shape
        g = g.reshape((L, S, MB * B) + tuple(row))
        if sc is not None:
            s = jnp.take(sc, tables, axis=1, mode="clip").reshape(L, S, MB * B, row[0])
            g = g.astype(dtype) * s[..., None].astype(dtype)
        return g

    ks, vs = (None, None) if scales is None else (scales[0], scales[1])
    return KVCache(
        k=take(pool_k, rows[0], ks), v=take(pool_v, rows[1], vs), length=lengths
    )


def split_tables(tables):
    """A mixed stack's device tables [S, 2 * MB], the two kinds' side by
    side -> (the full layers' [S, MB], the window layers' [S, MB])."""
    MB = tables.shape[1] // 2
    return tables[:, :MB], tables[:, MB:]


def gather_ring(pool_k, pool_v, tables, lengths, ring: int, *, rows):
    """The LAST `ring` positions below each slot's `lengths`, gathered
    through the block tables as a rolling ring: row j of the view holds the
    last position congruent to j mod ring (ops.attention.ring_positions),
    which is the layout prefill_append's ring masks read. What a window
    layer's prompt chunk needs of its pool and no more: [L, S, ring, h, d]
    where gather_slots would materialize the table's whole width. A row
    that was never written (a position below 0) reads block 0 and is
    masked by position, as gather_slots' stale entries are."""
    import jax.numpy as jnp

    from ..models.transformer import KVCache
    from ..ops.attention import ring_positions

    pos = jnp.maximum(ring_positions(lengths, ring), 0)  # [S, ring]
    L, NB, B, _ = pool_k.shape
    blk = jnp.take_along_axis(tables, jnp.clip(pos // B, 0, tables.shape[1] - 1), axis=1)
    flat = jnp.clip(blk, 0, NB - 1) * B + pos % B  # [S, ring] rows of one layer's pool
    flat = flat[None] + jnp.arange(L, dtype=jnp.int32)[:, None, None] * (NB * B)

    def take(pool, row):
        return viewed_rows(jnp.take(_flat(pool), flat, axis=0), row)

    return KVCache(k=take(pool_k, rows[0]), v=take(pool_v, rows[1]), length=lengths)


def quantize_rows(rows, *, axis=-1):
    """Symmetric per-row/per-head int8: scale = max|x| / 127 over the
    head_dim axis. Returns (int8 rows, f32 scales without that axis)."""
    import jax.numpy as jnp

    amax = jnp.max(jnp.abs(rows.astype(jnp.float32)), axis=axis)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(
        jnp.round(rows.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def dequantize_rows(q, scale, dtype):
    return q.astype(dtype) * scale[..., None].astype(dtype)


def scatter_rows(pool_k, pool_v, tables, rows_k, rows_v, positions, valid, *, scales=None):
    """Write per-slot K/V rows through the block tables into the pools
    [L, NB, B, h * d] as stored. ``rows_k/v`` are [L, S, W, h, d] as a token
    keeps them (flattened here, the rows and never the pool),
    ``positions`` [S, W] logical row indices (computed
    from DEVICE state — lengths/cursors — so pipelined speculative
    verifies and rollbacks can never mis-aim a host-computed window),
    ``valid`` [S, W] bool. Invalid lanes push their flat index out of
    bounds and are DROPPED — the paged counterpart of the contiguous
    path's clamped-garbage writes, except nothing is written at all (a
    freed block may already belong to another slot). The engine
    guarantees every valid target block is private (refcount 1): shared
    radix blocks sit strictly below each writer's cursor and partial
    tails were copy-on-write'd at seed time.

    Returns the updated (pool_k, pool_v[, scales]) arrays."""
    import jax.numpy as jnp

    L, NB, B, _ = pool_k.shape
    bi = jnp.clip(positions // B, 0, tables.shape[1] - 1)
    blk = jnp.take_along_axis(tables, bi, axis=1)  # [S, W]
    # every layer's copy of a row, in the pool's merged row axis (_flat); an
    # invalid lane points past the LAST layer's rows, so it is dropped and
    # not written into the next layer
    layer0 = jnp.arange(L, dtype=jnp.int32)[:, None, None] * (NB * B)
    flat = jnp.where(valid, blk * B + positions % B + layer0, L * NB * B).reshape(-1)

    def put(pool, rows):
        rows = rows.astype(pool.dtype).reshape(-1, pool.shape[-1])
        return _flat(pool).at[flat].set(rows, mode="drop").reshape(pool.shape)

    if scales is None:
        return put(pool_k, stored_rows(rows_k)), put(pool_v, stored_rows(rows_v)), None
    qk, sk = quantize_rows(rows_k)
    qv, sv = quantize_rows(rows_v)
    k, v = put(pool_k, stored_rows(qk)), put(pool_v, stored_rows(qv))
    return k, v, jnp.stack([put(scales[0], sk), put(scales[1], sv)])


def scatter_rows_by_kind(pool_k, pool_v, kind_tables, kind_layers, rows_k, rows_v, positions, valid):
    """scatter_rows for a mixed stack: `pool_k` / `pool_v` are the kinds'
    pools (full, window), `kind_tables` their tables, `kind_layers` which
    layers of the whole stack each holds (models.transformer.layer_kinds);
    `rows_k/v` [L, S, W, h, d] carry EVERY layer's rows, and each kind's
    layers' go through that kind's table. Returns the (k pools, v pools)."""
    import jax.numpy as jnp

    done = [
        scatter_rows(
            pool_k[i], pool_v[i], kind_tables[i],
            rows_k[jnp.asarray(layers)], rows_v[jnp.asarray(layers)], positions, valid,
        )
        for i, layers in enumerate(kind_layers)
    ]
    return tuple(d[0] for d in done), tuple(d[1] for d in done)


def copy_blocks(pool_k, pool_v, srcs, dsts, *, scales=None):
    """Block-granular device copy (COW splits, radix tail publishes,
    session restores): pool block ``dsts[i]`` := block ``srcs[i]``.
    Pad lanes use dst == n_blocks (dropped). Returns updated arrays."""
    import jax.numpy as jnp

    def cp(a):
        rows = jnp.take(a, srcs, axis=1, mode="clip")
        return a.at[:, dsts].set(rows, mode="drop")

    k, v = cp(pool_k), cp(pool_v)
    if scales is None:
        return k, v, None
    rows = jnp.take(scales, srcs, axis=2, mode="clip")
    return k, v, scales.at[:, :, dsts].set(rows, mode="drop")


def gather_blocks_host(pool_k, pool_v, blocks, *, rows, scales=None):
    """Fetch specific pool blocks to host numpy (session spill / tests):
    returns (k [L, n, B, h, d], v [...], scales or None) as np arrays, the
    shape a block has outside this process whatever the pool stores
    (``rows``: kvcache.row_shapes)."""
    import jax.numpy as jnp

    idx = jnp.asarray(np.asarray(blocks, np.int32))
    k = viewed_rows(np.asarray(jnp.take(pool_k, idx, axis=1, mode="clip")), rows[0])
    v = viewed_rows(np.asarray(jnp.take(pool_v, idx, axis=1, mode="clip")), rows[1])
    s = (
        None
        if scales is None
        else np.asarray(jnp.take(scales, idx, axis=2, mode="clip"))
    )
    return k, v, s
