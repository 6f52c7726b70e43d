"""KV-cache subsystem: layout, residency, and reuse policy for LLM serving.

The serving engine (gofr_tpu.llm) used to hard-code one dense KV slab
[n_layers, slots, max_seq_len, hkv, hd] and pay a full prefill for every
request. This package owns the engine's memory model instead, providing
three pieces the same way vLLM's PagedAttention and SGLang's
RadixAttention own theirs — adapted to a TPU-resident, statically-shaped
engine where dynamic block tables would defeat XLA:

- **Window-bounded rolling caches.** For sliding-window models (Mistral)
  a slot never needs more than the last `window` K/V rows, so the slot
  cache becomes a RING of capacity C = window + decode_chunk: row index =
  absolute position mod C (ops.attention.ring_positions reconstructs
  absolute positions for masking), prefill ring-packs its rows with one
  gather, and the chunk merge wraps modulo C. Memory and decode bandwidth
  per slot drop from O(max_seq_len) to O(window); tokens are bit-identical
  to the dense path because attention sees exactly the same windowed keys.

- **Prefix cache.** Hash of the prompt (the shared prefix unit at this
  engine's wave-granular admission) -> the retained prefill artifacts:
  one KV row [L, 1, C, hkv, hd] pair plus the last-token logits, with
  reference counting (a pinned entry — looked up but not yet inserted —
  is never evicted) and LRU eviction under a byte budget. The engine
  consults it at admit: a hit skips the prefill wave entirely, assembling
  cached rows into the existing _insert_many scatter path and sampling
  the first token from the stored logits (greedy traffic reproduces the
  uncached tokens exactly; sampled traffic draws from the same logits).

- **Observability.** Hit/miss/eviction/store counters and resident-bytes
  gauges, registered with the metrics manager (Prometheus: app_kvcache_*)
  and surfaced through CacheManager.stats() -> engine.stats().

No counterpart in the reference repo (a Go web framework); this is the
serving-memory layer of the TPU north star (ROADMAP: long-context serving
end-to-end, prefix caching — VERDICT r5 levers #1 and #9).
"""

from __future__ import annotations

import os
import threading
import time
from collections import Counter, OrderedDict
from typing import Any, NamedTuple

import numpy as np

__all__ = ["CacheManager", "PrefixCache", "ring_pack", "SeedPlan"]

# Serializes metric registration across CacheManagers: ReplicatedLLMEngine
# builds N engines on parallel threads, and a bare has()/new_* pair racing
# itself emits the Manager's already-registered WARN — the exact noise the
# probe exists to avoid. Registration itself is idempotent either way.
_METRICS_REG_LOCK = threading.Lock()


def ring_pack(cache, capacity: int):
    """Re-layout a dense position-indexed prefill cache into a ring of
    `capacity`: row j of the result holds the last prompt position
    congruent to j mod capacity (ops.attention.ring_positions), i.e. the
    newest `capacity` rows survive and older ones — already outside every
    future window — are dropped. One gather per k/v (deterministic, unlike
    a duplicate-index scatter, whose write order XLA leaves unspecified).
    Never-written rows are zeroed so packed caches compare reproducibly.

    cache.k/.v: [L, b, s, hkv, hd] with rows at their absolute positions
    (right-padded prompts: rows >= length are pad junk and never gathered,
    because ring_positions only yields p <= length-1). Returns the same
    KVCache type with row axis `capacity` and lengths unchanged (absolute).
    """
    import jax.numpy as jnp

    from ..models.transformer import KVCache
    from ..ops import ring_positions

    s = cache.k.shape[2]
    pos = ring_positions(cache.length, capacity)  # [b, C]
    valid = pos >= 0
    idx = jnp.clip(pos, 0, s - 1)[None, :, :, None, None]

    def take(a):
        rows = jnp.take_along_axis(a, idx, axis=2)
        return jnp.where(valid[None, :, :, None, None], rows, 0).astype(a.dtype)

    return KVCache(k=take(cache.k), v=take(cache.v), length=cache.length)


class _Entry:
    """One retained prefix: device-resident KV row + last-token logits."""

    __slots__ = ("key", "k", "v", "length", "logits", "nbytes", "refs")

    def __init__(self, key, k, v, length, logits, nbytes):
        self.key = key
        self.k = k  # [L, 1, C, hkv, hd]
        self.v = v
        self.length = length  # int — absolute prompt length
        self.logits = logits  # [1, vocab] f32 last-token logits
        self.nbytes = nbytes
        self.refs = 0


class PrefixCache:
    """Prompt-prefix -> retained KV rows, refcounted, LRU-evicted.

    Thread-safe (the engine's scheduler thread mutates it while stats()
    and the metrics exporter read from others). Lookup PINS the entry
    (refs += 1) so eviction can never free rows an admission wave is
    about to insert; the engine releases the pin after _insert_many.
    Eviction is strict LRU over unpinned entries, triggered by put()
    whenever resident bytes exceed the budget. An entry larger than the
    whole budget is refused outright (storing it would evict everything
    and then itself be the next victim)."""

    def __init__(self, capacity_bytes: int, metrics=None, model: str = "llm"):
        self.capacity_bytes = int(capacity_bytes)
        self.metrics = metrics
        self.model = model
        self._entries: OrderedDict[bytes, _Entry] = OrderedDict()
        # distinct stored lengths, refcounted — lookup_longest probes per
        # DISTINCT length, and rebuilding this set by scanning every
        # entry would put an O(entries) walk on the scheduler thread for
        # each exact-miss admission
        self._lengths: Counter[int] = Counter()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.partial_hits = 0  # prefix-of-prompt hits (lookup_longest)
        self.evictions = 0
        self.stores = 0
        self.resident_bytes = 0

    @staticmethod
    def key_for(tokens) -> bytes:
        """Exact-content key: the int32 bytes of the token sequence. A
        dict keyed on the bytes themselves cannot collide (unlike a
        truncated digest), and Python hashes them once per lookup."""
        return np.asarray(tokens, np.int32).tobytes()

    def _count(self, event: str) -> None:
        if self.metrics is not None:
            self.metrics.increment_counter(
                "app_kvcache_events", 1.0, model=self.model, event=event
            )

    def _gauge(self) -> None:
        if self.metrics is not None:
            self.metrics.set_gauge(
                "app_kvcache_resident_bytes", float(self.resident_bytes),
                model=self.model, kind="prefix",
            )

    def lookup(self, key: bytes) -> _Entry | None:
        """Hit: move to MRU, pin, return the entry. Miss: count, None."""
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                self.misses += 1
                self._count("miss")
                return None
            self._entries.move_to_end(key)
            e.refs += 1
            self.hits += 1
        self._count("hit")
        return e

    def lookup_longest(
        self, tokens, *, allow_partial: bool = True
    ) -> tuple["_Entry | None", bool]:
        """(entry, exact) for the longest stored prompt that PREFIXES
        `tokens` — the chunked-prefill seam: an exact hit (exact=True)
        skips prefill entirely (stored last-token logits included); a
        partial hit returns a shorter prompt's entry whose KV rows seed
        the slot mid-prompt, so the engine's prefill cursor starts at
        entry.length instead of 0 and only the unshared chunks run.
        allow_partial=False restricts to the exact probe — callers that
        cannot consume a partial (rolling-layout engines, whose ring rows
        are laid out for the entry's own final length) must not pin
        entries, bump their LRU position, or count partial hits they
        will immediately discard.

        Works on the key bytes alone: key_for is the int32 token bytes,
        so the key of tokens[:L] is key[:4L] — one dict probe per
        DISTINCT stored prompt length (a handful), longest first. The
        full-prompt miss is counted exactly as lookup() counts it;
        partial hits land in their own counter so hit-rate math stays
        exact-hit-only."""
        key = self.key_for(tokens)
        e = self.lookup(key)  # counts the exact hit/miss
        if e is not None:
            return e, True
        if not allow_partial:
            return None, False
        n = len(key) // 4
        with self._lock:
            lengths = sorted(
                (ln for ln in self._lengths if ln < n), reverse=True
            )
        for length in lengths:
            with self._lock:
                e = self._entries.get(key[: 4 * length])
                if e is None:
                    continue
                self._entries.move_to_end(e.key)
                e.refs += 1
                self.partial_hits += 1
            self._count("partial_hit")
            return e, False
        return None, False

    def release(self, entry: _Entry) -> None:
        with self._lock:
            entry.refs -= 1

    def put(self, key: bytes, k, v, length: int, logits) -> bool:
        """Retain a freshly prefilled row; returns False when skipped
        (duplicate key or oversized entry)."""
        nbytes = int(k.nbytes) + int(v.nbytes) + int(logits.nbytes)
        with self._lock:
            if key in self._entries or nbytes > self.capacity_bytes:
                return False
            self._entries[key] = _Entry(key, k, v, int(length), logits, nbytes)
            self._lengths[int(length)] += 1
            self.resident_bytes += nbytes
            self.stores += 1
            evicted = 0
            while self.resident_bytes > self.capacity_bytes:
                victim = next(
                    (ky for ky, e in self._entries.items() if e.refs == 0), None
                )
                if victim is None:  # everything pinned: over budget, wait
                    break
                ve = self._entries.pop(victim)
                self.resident_bytes -= ve.nbytes
                self._lengths[ve.length] -= 1
                if not self._lengths[ve.length]:
                    del self._lengths[ve.length]
                self.evictions += 1
                evicted += 1
        self._count("store")
        for _ in range(evicted):
            self._count("eviction")
        self._gauge()
        return True

    def assemble(self, entries: list[_Entry], width: int, capacity: int):
        """Stack pinned entries into a prefill-shaped wave: (KVCache
        [L, width, capacity, ...], logits [width, vocab]). Padding rows
        repeat entry 0 — the engine's insert meta is idempotent over pads.
        Entries are stored TRIMMED to their prefill bucket (the byte
        budget should buy prefixes, not padding), so each is zero-padded
        back to the slot capacity here; the pad rows sit beyond every
        entry's valid length and are never attended."""
        import jax.numpy as jnp

        from ..models.transformer import KVCache

        es = list(entries) + [entries[0]] * (width - len(entries))

        def widen(a):
            pad = capacity - a.shape[2]
            if pad == 0:
                return a
            return jnp.pad(a, [(0, 0), (0, 0), (0, pad), (0, 0), (0, 0)])

        cache = KVCache(
            k=jnp.concatenate([widen(e.k) for e in es], axis=1),
            v=jnp.concatenate([widen(e.v) for e in es], axis=1),
            length=jnp.asarray([e.length for e in es], jnp.int32),
        )
        return cache, jnp.concatenate([e.logits for e in es], axis=0)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._lengths.clear()
            self.resident_bytes = 0
        self._gauge()

    def stats(self) -> dict:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "partial_hits": self.partial_hits,
                "evictions": self.evictions,
                "stores": self.stores,
                "entries": len(self._entries),
                "resident_bytes": self.resident_bytes,
                "capacity_bytes": self.capacity_bytes,
            }


class SeedPlan(NamedTuple):
    """Admission-time radix consult result (paged layout)."""

    blocks: list  # shared full prefix blocks, in table order
    shared: int  # tokens covered by `blocks` (block-aligned)
    exact: bool  # an end record matched the WHOLE prompt
    tail_src: int  # end record's copied tail block (-1 = none)
    tail_len: int  # valid rows in the tail block
    logits: Any  # stored last-token logits (exact hits skip prefill)


def paged_default():
    """Engine-level default for the paged layout: "auto" unless
    TPU_LLM_KV_PAGED=0 (the contiguous escape hatch / A-B lever).
    "auto" resolves per model in CacheManager: paged for
    global-attention models (same worst-case bytes as the dense slab,
    plus sharing) and for a MIXED stack (window and full layers: its
    window layers live in a pool of their own that gives blocks back
    behind the window, paged.WindowTables, so a slot holds O(window) of
    them; the ring cannot hold the full layers at all); the ROLLING ring
    for a model whose EVERY layer has the one window, where it engages —
    one pool for all layers reclaims nothing (its block ids are shared by
    the whole stack and a radix prefix may pin them), so auto-pagination
    there would trade the ring's O(window) slot bound for
    O(max_seq_len). Explicit kv_paged=True opts such a model in anyway
    (sessions/radix over window masks)."""
    return "auto" if os.environ.get("TPU_LLM_KV_PAGED", "1") != "0" else False


def mixed_kv_refusal(*, int8: bool, retain_bytes: int, session_bytes: int) -> str | None:
    """What a mixed stack's two pools are refused, as a sentence, or None:
    every path that was written for ONE pool of blocks says so here."""
    if int8:
        return (
            "an int8 KV pool is not written for a mixed stack (window and full "
            "layers): its scales are one array over one pool's blocks"
        )
    if retain_bytes > 0 or session_bytes > 0:
        return (
            "prefix sharing and sessions (prefix_cache_mb, session_mb) are not "
            "written for a mixed stack: the radix tree, the spill to the host "
            "and the hand-off between engines name blocks of ONE pool, and a "
            "window layer's blocks behind the window are gone; serve with "
            "prefix_cache_mb=0 and no session tier"
        )
    return None


def row_shapes(cfg) -> tuple[tuple, tuple]:
    """What ONE token of ONE layer keeps in the cache, as [heads, dim] of
    the cache's two arrays (`KVCache.k`, `KVCache.v`), read from the config
    here and nowhere else. GQA: keys and values, [n_kv_heads, head_dim]
    each. Latent attention (cfg.latent): ONE row [c_kv | k_rope] of
    kv_lora_rank + qk_rope_head_dim values and no array of values at all,
    kept as its two parts, `k` = the normalized latent [1, kv_lora_rank]
    (keys AND values of the absorbed form) and `v` = the shared rotated
    rope key [1, R], R = qk_rope_head_dim padded up to whole 128-lane rows
    (ops.latent_rope_width): the TPU tiles a bf16 array's last dim to 128
    lanes whatever it says, so the padding costs no memory that 576 columns
    in one array would save (576 tiles to 640 too), each part is a slab
    the decode kernel can copy and slice at lane 0, and every pool helper
    (gather_slots / scatter_rows / copy_blocks, host spill) moves a pair of
    arrays already.

    The contiguous cache keeps a row as these trailing dims [heads, dim].
    The PAGED POOL stores it as its decode kernel reads a page: flat,
    heads * dim columns, head h the columns [h * dim, (h + 1) * dim)
    (CacheManager.pool_arrays: [L, NB, B, heads * dim]), so that the array
    the engine owns is the kernel's HBM operand and the scatter's target
    with no layout in between. Who needs heads (a gathered view, rows about
    to be written, a block on its way out of the process) reshapes those
    rows (paged.viewed_rows / stored_rows), never the pool."""
    if getattr(cfg, "latent", False):
        from ..ops import latent_rope_width

        return (1, cfg.kv_lora_rank), (1, latent_rope_width(cfg.qk_rope_head_dim))
    return (cfg.n_kv_heads, cfg.head_dim), (cfg.n_kv_heads, cfg.head_dim)


class CacheManager:
    """Owns the serving engine's KV layout, residency, and reuse policy.

    Layout decision (static, at engine build):

    - **Paged** (``paged=True`` — the serving engine's default via
      ``TPU_LLM_KV_PAGED``): one pool of ``TPU_LLM_KV_BLOCK``-token
      blocks backs every slot through per-slot block tables
      (gofr_tpu.kvcache.paged). Blocks materialize as each cursor
      advances — the uniform contract that replaces the old per-feature
      ring-slack arithmetic (chunk shapes and speculative verify widths
      fold into ONE ``append_slack`` term of the admission reservation,
      computed here and nowhere else). A radix tree shares every common
      prefix block between sibling prompts (copy-on-write, refcounted),
      and an optional session tier (``TPU_LLM_SESSION_MB``) keeps idle
      conversations resident / spills them to host RAM
      (gofr_tpu.kvcache.sessions). ``TPU_LLM_KV_INT8`` stores blocks
      int8 (+ per-row scales), halving the decode HBM stream.

    - **Contiguous** (``paged=False``): the pre-paging layouts — a
      ROLLING ring of capacity ``window + append_slack`` for
      sliding-window models, the dense slab otherwise, and the
      whole-row PrefixCache. Kept as the A/B lever the
      paged==contiguous equality tests pin and as the fallback for
      stacks where the paged path is unavailable.

    `window=None` auto-adopts cfg.sliding_window; `window=0` forces
    dense masks (the rolling-vs-dense A/B lever). ``append_widths`` is
    every append width the engine can dispatch in one program (decode
    chunk, prefill chunk shapes, speculative verify width); its max is
    the single slack term both layouts budget for.

    Threading: construction and all paged mutation happen on the
    engine's SCHEDULER thread (the only thread allowed to touch the
    donated pool arrays); ``_plock`` protects the host bookkeeping
    against concurrent stats()/metrics readers.
    """

    def __init__(
        self,
        cfg,
        slots: int,
        max_seq_len: int,
        decode_chunk: int,
        *,
        window: int | None = None,
        prefill_chunk: int = 0,
        append_widths: tuple = (),
        prefix_cache_mb: float = 0.0,
        paged: bool = False,
        block: int | None = None,
        pool_blocks: int | None = None,
        kv_int8: bool | None = None,
        session_mb: float | None = None,
        host_cache_mb: float | None = None,
        metrics=None,
        model: str = "llm",
    ):
        import jax.numpy as jnp

        self.cfg = cfg
        self.slots = slots
        self.max_seq_len = max_seq_len
        # window and full layers in one stack: two kinds of paged state
        self.mixed = bool(getattr(cfg, "mixed", False))
        if self.mixed and not paged:
            raise ValueError(
                "a mixed stack (window and full layers) serves from the paged "
                "pools only: no contiguous layout holds both kinds"
            )
        w = cfg.sliding_window if window is None else window
        if w and w != cfg.sliding_window:
            raise ValueError(
                f"kv window {w} must match cfg.sliding_window "
                f"{cfg.sliding_window} (attention masks use the config)"
            )
        self.window = int(w or 0)
        # UNIFIED append-slack accounting: every width the engine can
        # append in one device program, maxed into one slack term. The
        # legacy prefill_chunk kwarg folds in for direct constructions.
        widths = tuple(int(x) for x in append_widths) + (
            int(decode_chunk), int(prefill_chunk or 0),
        )
        self.append_slack = max(widths)
        slack = self.append_slack
        would_roll = 0 < self.window and self.window + slack < max_seq_len
        # max_seq_len is what a REQUEST may hold (prompt + output:
        # LLMEngine.submit admits plen + max_new <= max_seq_len). A slot
        # holds the engine's own merge slack on top: while a request is
        # incomplete its cursor stays <= prompt + max_new + chunk
        # (chunk-granular rounding) and the end-of-chunk merge writes one
        # chunk more, so no layout ever clamp-overwrites a live row.
        self.slot_rows = max_seq_len + 2 * int(decode_chunk)
        # ...and what one of those rows holds (row_shapes: the one place)
        self.row_shapes = row_shapes(cfg)
        self.row_heads = self.row_shapes[0][0]
        self.row_values = sum(h * d for h, d in self.row_shapes)
        # A flat (paged / dense) slot is BUILT with those rows, rounded up
        # to whole flash key blocks of 128 where max_seq_len itself was
        # (ops.attention.chunk_prefill_why_not_flash): an engine whose
        # max_seq_len took the Pallas prefill kernel still takes it.
        flat_rows = self.slot_rows
        if max_seq_len % 128 == 0:
            flat_rows = -(-flat_rows // 128) * 128
        if session_mb is None:
            session_mb = float(os.environ.get("TPU_LLM_SESSION_MB", "0") or 0.0)
        if paged == "auto":
            # see paged_default(): windowed models where the rolling
            # ring engages keep its O(window) slot bound — UNLESS the
            # operator asked for the session tier, which only the paged
            # pool provides. Explicit kv_paged=True also overrides.
            paged = not would_roll or session_mb > 0
        self.paged = bool(paged)
        self.metrics = metrics
        self.model = model
        itemsize = jnp.dtype(cfg.dtype).itemsize

        if self.paged:
            self.rolling = False
            self.block = int(
                block if block is not None
                else os.environ.get("TPU_LLM_KV_BLOCK", "16")
            )
            if kv_int8 is None:
                kv_int8 = os.environ.get("TPU_LLM_KV_INT8", "0") not in ("", "0")
            self.int8 = bool(kv_int8)
            self.table_width = -(-flat_rows // self.block)
            self.capacity = self.table_width * self.block
            self.ring = 0
            kv_itemsize = 1 if self.int8 else itemsize
            if self.int8 and getattr(cfg, "latent", False):
                raise ValueError(
                    "an int8 KV pool is not supported with latent attention: "
                    "its per-(row, head) scales assume a key and a value array"
                )
            self.row_bytes = self.row_values * kv_itemsize + (
                2 * self.row_heads * 4 if self.int8 else 0
            )
            # the layers this pool's blocks hold: all of them, or a mixed
            # stack's FULL layers (the window layers' pool: window_tables)
            self.pool_layers = cfg.n_layers
            if self.mixed:
                from ..models.transformer import layer_kinds

                self.kind_layers = layer_kinds(cfg)
                self.pool_layers = len(self.kind_layers[0])
            self.block_bytes = self.pool_layers * self.block * self.row_bytes
            retain_bytes = int(prefix_cache_mb * 1024 * 1024)
            if host_cache_mb is None:
                host_cache_mb = float(
                    os.environ.get("TPU_LLM_HOST_CACHE_MB", "256") or 0.0
                )
            session_bytes = int(session_mb * 1024 * 1024)
            if pool_blocks is None:
                pool_blocks = int(os.environ.get("TPU_LLM_KV_POOL_BLOCKS", "0"))
            if not pool_blocks:
                # worst case with zero sharing: every slot grown to what
                # its request can hold (not to the table's rounded width),
                # plus the retained-prefix and session budgets
                pool_blocks = (
                    slots * self.blocks_for(self.slot_rows)
                    + -(-retain_bytes // self.block_bytes)
                    + -(-session_bytes // self.block_bytes)
                )
            from .paged import BlockPool, RadixTree, SlotTable

            self.pool = BlockPool(pool_blocks, self.block, self.block_bytes)
            self._slot_tables = [SlotTable(self.table_width) for _ in range(slots)]
            # the device tables' columns: a mixed stack's two kinds side by
            # side, [full | window] (paged.split_tables)
            self.table_cols = self.table_width * (2 if self.mixed else 1)
            self.window_tables = None
            if self.mixed:
                why = mixed_kv_refusal(
                    int8=self.int8, retain_bytes=retain_bytes, session_bytes=session_bytes
                )
                if why:
                    raise ValueError(why)
                from .paged import WindowTables

                # a step appends a prompt chunk and, where the row finishes
                # its prompt, the decode chunk behind it
                self.window_tables = WindowTables(
                    slots, self.table_width, self.block, cfg.window,
                    self.append_slack + int(decode_chunk), 2 * int(decode_chunk),
                    len(self.kind_layers[1]) * self.block * self.row_bytes,
                )
                # a prompt chunk's view of a window layer: a ring of the
                # window and the widest chunk (gather_ring)
                self.window_ring = cfg.window + self.append_slack
            self._tables_np = np.zeros((slots, self.table_cols), np.int32)
            self.tables_dirty = True
            # sharing is on whenever there is a retention budget OR the
            # session tier wants the radix as its index
            self.share = retain_bytes > 0 or session_bytes > 0
            self.radix = (
                RadixTree(self.pool, self.block, retain_bytes)
                if self.share else None
            )
            self.sessions = None
            if session_bytes > 0:
                from .sessions import HostOffload, SessionStore

                self.sessions = SessionStore(
                    session_bytes,
                    HostOffload(int(host_cache_mb * 1024 * 1024)),
                )
            # the old PrefixCache surface: None in paged mode — the radix
            # IS the prefix index (stats()["prefix"] maps its counters)
            self.prefix = None
            self.slot_bytes = 0  # dynamic: pool bytes in use (gauges)
        else:
            self.block = 0
            self.int8 = False
            self.pool = None
            self.radix = None
            self.sessions = None
            self.share = False
            self.rolling = would_roll
            self.capacity = self.window + slack if self.rolling else flat_rows
            # static arg for decode_chunk/attention: ring capacity, 0 = dense
            self.ring = self.capacity if self.rolling else 0
            self.row_bytes = self.row_values * itemsize
            self.slot_bytes = cfg.n_layers * slots * self.capacity * self.row_bytes
            self.prefix = (
                PrefixCache(int(prefix_cache_mb * 1024 * 1024), metrics, model)
                if prefix_cache_mb > 0
                else None
            )
        self._plock = threading.Lock()
        if metrics is not None:
            with _METRICS_REG_LOCK:
                if not metrics.has("app_kvcache_events"):
                    metrics.new_counter(
                        "app_kvcache_events",
                        "kv-cache events (event=hit|miss|store|eviction)",
                    )
                if not metrics.has("app_kvcache_resident_bytes"):
                    metrics.new_gauge(
                        "app_kvcache_resident_bytes",
                        "resident kv bytes (kind=slots|prefix)",
                    )
                if self.paged:
                    if not metrics.has("app_kvcache_blocks_in_use"):
                        metrics.new_gauge(
                            "app_kvcache_blocks_in_use",
                            "KV pool blocks with refcount > 0",
                        )
                    if not metrics.has("app_kvcache_blocks_shared"):
                        metrics.new_gauge(
                            "app_kvcache_blocks_shared",
                            "KV pool blocks with refcount > 1 (prefix sharing)",
                        )
                    if not metrics.has("app_kvcache_spilled_bytes"):
                        metrics.new_gauge(
                            "app_kvcache_spilled_bytes",
                            "session KV bytes spilled to the host tier",
                        )
                    if not metrics.has("app_kvcache_session_count"):
                        metrics.new_gauge(
                            "app_kvcache_session_count",
                            "sessions tracked (state=resident|spilled)",
                        )
                    if not metrics.has("app_kvcache_session_events"):
                        metrics.new_counter(
                            "app_kvcache_session_events",
                            "session lifecycle events "
                            "(event=publish|resume|spill|restore|expire)",
                        )
            self._update_gauges()
            if not self.paged:
                metrics.set_gauge(
                    "app_kvcache_resident_bytes", float(self.slot_bytes),
                    model=model, kind="slots",
                )

    # -- slot cache (contiguous layout + prefill scratch) -----------------
    def init_cache(self, rows: int):
        """A zeroed slot (or prefill-scratch) cache at the planned width."""
        from ..models.transformer import init_cache

        return init_cache(self.cfg, rows, self.capacity)

    def prefill_cache_len(self, bucket: int) -> int:
        """Row width the prefill op should build its cache at: the dense
        contiguous AND paged layouts pad straight to capacity (paged's
        insert scatter drops rows beyond each prompt's length, and one
        capacity-wide shape keeps the insert program family at one
        executable); the rolling layout keeps position-indexed rows
        (bucket wide) and ring-packs after."""
        return bucket if self.rolling else self.capacity

    def pack_prefill(self, cache):
        """Convert a freshly prefilled cache to the slot layout."""
        return ring_pack(cache, self.capacity) if self.rolling else cache

    # -- paged layout: pool geometry --------------------------------------
    def pool_shapes(self) -> tuple:
        """The pool's two arrays as stored, [L, NB, B, heads * dim] each: a
        row flat, as its decode kernel reads a page (row_shapes). A mixed
        stack has two pools: each of the two entries is then the pair (full
        layers' [L_full, NB, ...], window layers' [L_window, NB_window, ...])."""
        lead = (self.pool_layers, self.pool.n_blocks, self.block)
        shapes = tuple(lead + (h * d,) for h, d in self.row_shapes)
        if not self.mixed:
            return shapes
        wlead = (len(self.kind_layers[1]), self.window_tables.pool.n_blocks, self.block)
        return tuple((full, wlead + full[3:]) for full in shapes)

    def pool_arrays(self, jnp):
        """Zeroed device pool (KVCache pool-layout) + int8 scales (or
        None). The ENGINE owns these arrays — they are donated through
        every jitted program; this manager only does the bookkeeping.
        k / v are pool_shapes(); the scales [2, L, NB, B, heads]."""
        from ..models.transformer import KVCache

        k_shape, v_shape = self.pool_shapes()
        dtype = jnp.int8 if self.int8 else self.cfg.dtype
        if self.mixed:  # k and v are each (full layers' pool, window layers')
            return KVCache(
                k=tuple(jnp.zeros(s, dtype) for s in k_shape),
                v=tuple(jnp.zeros(s, dtype) for s in v_shape),
                length=jnp.zeros((self.slots,), jnp.int32),
            ), None
        cache = KVCache(
            k=jnp.zeros(k_shape, dtype),
            v=jnp.zeros(v_shape, dtype),
            length=jnp.zeros((self.slots,), jnp.int32),
        )
        scales = (
            jnp.zeros((2,) + k_shape[:3] + (self.row_heads,), jnp.float32)
            if self.int8 else None
        )
        return cache, scales

    def blocks_for(self, tokens: int) -> int:
        return -(-max(0, int(tokens)) // self.block)

    def reserve_tokens(self, prompt_len: int, max_new: int) -> int:
        """Worst-case rows a request can ever occupy: prompt + decode
        budget + ONE append-slack term (chunk-granular decode overshoot
        and transient speculative verify rows past the cursor), clamped
        to what a slot's request can hold (slot_rows: the default pool
        counts that much a slot, so a request at max_seq_len always fits
        it). The single place this arithmetic lives."""
        return min(prompt_len + max_new - 1 + self.append_slack, self.slot_rows)

    # -- paged layout: admission ------------------------------------------
    def lookup_seed(
        self, prompt_tokens, *, allow_partial: bool = True,
        count: bool = True,
    ) -> SeedPlan | None:
        """Radix consult for one prompt. Exact end records reproduce the
        old PrefixCache exact-hit contract (stored tail rows + logits —
        prefill skipped entirely); otherwise the longest block-aligned
        shared prefix is returned, CLAMPED to prompt_len - 1 so at least
        one token still runs through prefill (last-token logits).
        allow_partial=False restricts to exact probes (the wave
        scheduler has no mid-prompt append path). count=False skips the
        app_kvcache_events series (KV-handoff export probes are not
        admission traffic)."""
        if self.radix is None:
            return None
        with self._plock:
            m = self.radix.lookup(prompt_tokens)
            n = len(prompt_tokens)
            if m.end is not None and m.end.logits is not None:
                # prefill can only be skipped when the stored last-token
                # logits exist (session end records keep rows, not
                # logits — those degrade to the partial path below)
                if count:
                    self._count("hit")
                plan = SeedPlan(
                    blocks=m.blocks, shared=m.shared, exact=True,
                    tail_src=(
                        m.end.tail_block if m.end.tail_block is not None else -1
                    ),
                    tail_len=m.end.tail_len, logits=m.end.logits,
                )
            else:
                shared = min(m.shared, ((n - 1) // self.block) * self.block)
                if shared <= 0 or not allow_partial:
                    if count:
                        self._count("miss")
                    return None
                if count:
                    self._count("partial_hit")
                plan = SeedPlan(
                    blocks=m.blocks[: shared // self.block], shared=shared,
                    exact=False, tail_src=-1, tail_len=0, logits=None,
                )
            # PIN the plan's blocks (the PrefixCache lookup-pins-entry
            # contract): between this lookup and attach_seed, a LATER
            # request's reservation/restore in the same admission pass
            # may evict these very radix leaves — without the pin the
            # plan would reference freed (possibly re-allocated) blocks.
            # attach_seed adopts the refs; every discard path calls
            # release_plan.
            self.pool.incref(plan.blocks)
            if plan.tail_src >= 0:
                self.pool.incref([plan.tail_src])
            return plan

    def release_plan(self, plan: SeedPlan | None) -> None:
        """Drop an unconsumed seed plan's pins (blocked/stranded/failed
        admissions)."""
        if plan is None:
            return
        with self._plock:
            self.pool.decref(plan.blocks)
            if plan.tail_src >= 0:
                self.pool.decref([plan.tail_src])

    def _reserve_need(self, prompt_len: int, max_new: int, plan: SeedPlan | None) -> int:
        """Blocks a request still needs beyond its seed plan's shared
        prefix. The exact hit's tail COPY is already inside
        blocks_for(reserve_tokens) — the tail block is simply the first
        non-shared block."""
        need = self.blocks_for(self.reserve_tokens(prompt_len, max_new))
        need -= len(plan.blocks) if plan is not None else 0
        return max(0, need)

    def reserve_need(self, prompt_len: int, max_new: int, plan: SeedPlan | None) -> int:
        """Public view of the admission promise (the engine records it on
        the request so a stranded admission can hand the promise back)."""
        return self._reserve_need(prompt_len, max_new, plan)

    def unreserve(self, n: int) -> None:
        """Return an unconsumed admission promise to the pool (stranded
        requests re-queued by admission recovery)."""
        if n > 0:
            with self._plock:
                self.pool.unreserve(n)

    def admit_reserve(self, prompt_len: int, max_new: int, plan: SeedPlan | None) -> bool:
        """Promise pool blocks for a request's worst case (minus what a
        seed plan already shares). False = the pool cannot host it yet —
        the engine keeps it queued (and may spill sessions to make
        room). Radix retention is reclaimed automatically: retained-only
        blocks are exactly the evictable slack."""
        need = self._reserve_need(prompt_len, max_new, plan)
        with self._plock:
            if self.pool.available() < need and self.radix is not None:
                self.radix.evict_for(need - self.pool.available())
            return self.pool.reserve(need)

    def attach_seed(
        self, slot: int, plan: SeedPlan | None, owner,
        prompt_len: int, max_new: int,
    ) -> dict:
        """Point a slot's table at its seed plan's shared blocks
        (refcount++, read-only for this slot) and move the admission
        promise onto the slot's books. Returns the device work the
        ENGINE must dispatch: ``copies`` (src, dst) block pairs — the
        exact hit's partial tail is shared by COPY, never in place,
        which is what keeps the copy-on-write invariant trivial — and
        ``seed_len`` for the device length scatter (exact hits only;
        append paths carry their cursor in the pack)."""
        with self._plock:
            st = self._slot_tables[slot]
            self._release_slot_locked(slot)
            st.owner = owner
            st.reserved = self._reserve_need(prompt_len, max_new, plan)
            copies: list[tuple[int, int]] = []
            seed_len = 0
            if plan is not None:
                # ADOPT the plan's pins as the slot's references (no
                # extra incref — lookup_seed already took them)
                shared = plan.blocks
                n = len(shared)
                st.rows[:n] = np.asarray(shared, np.int32)
                st.shared = n
                st.hi = n
                seed_len = plan.shared
                if plan.exact and plan.tail_src >= 0:
                    dst = self.pool.alloc(1, reserved=True)[0]
                    st.reserved -= 1
                    st.rows[n] = dst
                    st.hi = n + 1
                    copies.append((plan.tail_src, dst))
                    seed_len = plan.shared + plan.tail_len
                    # the tail-source pin served its purpose: the copy
                    # the engine dispatches next is device-ordered
                    # before any future re-user's write to this block
                    self.pool.decref([plan.tail_src])
            self.tables_dirty = True
            self._update_gauges()
            return {"copies": copies, "seed_len": seed_len}

    def ensure(self, slot: int, upto_tokens: int) -> bool:
        """Materialize table entries so rows [0, upto_tokens) are
        writable-or-shared — the "allocate blocks as the cursor advances"
        contract. Draws the slot's admission reservation first; anything
        beyond it (shouldn't happen — reserve_tokens is the worst case)
        competes for free headroom, evicting retained prefixes if it
        must. Returns True when the table changed (engine re-ships the
        device mirror)."""
        upto = min(int(upto_tokens), self.capacity)
        need = self.blocks_for(upto)
        with self._plock:
            grew = self._grow_locked(slot, need)
            if self.window_tables is not None:
                # the window layers' blocks follow the same cursor, and
                # those behind every coming query's window go back
                moved, freed = self.window_tables.advance(slot, upto)
                if moved:
                    self.tables_dirty = grew = True
                if freed and self.metrics is not None:
                    self.metrics.increment_counter(
                        "app_llm_kv_blocks_reclaimed_total", float(freed), model=self.model
                    )
            return grew

    def _grow_locked(self, slot: int, need: int) -> bool:
        """ensure's growth of the slot's table to `need` entries; the lock is held."""
        st = self._slot_tables[slot]
        if need <= st.hi:
            return False
        n = need - st.hi
        take_r = min(n, st.reserved)
        fresh: list[int] = []
        if take_r:
            fresh += self.pool.alloc(take_r, reserved=True)
            st.reserved -= take_r
        extra = n - take_r
        if extra:
            if self.pool.available() < extra and self.radix is not None:
                self.radix.evict_for(extra - self.pool.available())
            fresh += self.pool.alloc(extra)
        st.rows[st.hi : need] = np.asarray(fresh, np.int32)
        st.hi = need
        self.tables_dirty = True
        self._update_gauges()
        return True

    def _release_slot_locked(self, slot: int) -> None:
        st = self._slot_tables[slot]
        if st.hi:
            self.pool.decref(st.blocks())
        if st.reserved:
            self.pool.unreserve(st.reserved)
        st.hi = 0
        st.shared = 0
        st.reserved = 0
        st.owner = None
        if self.window_tables is not None:
            self.window_tables.release(slot)

    def release_slot(self, slot: int, owner=None) -> None:
        """Drop a slot's block references (retire/preempt/reassign).
        owner-checked when provided so a late release can never free a
        successor's blocks."""
        with self._plock:
            st = self._slot_tables[slot]
            if owner is not None and st.owner is not owner:
                return
            self._release_slot_locked(slot)
            self.tables_dirty = True
            self._update_gauges()

    def slot_owner(self, slot: int):
        return self._slot_tables[slot].owner

    def take_tables(self) -> np.ndarray | None:
        """The [slots, table_width] np mirror when dirty, else None."""
        with self._plock:
            if not self.tables_dirty:
                return None
            for s, st in enumerate(self._slot_tables):
                self._tables_np[s, : self.table_width] = st.rows
            if self.window_tables is not None:
                self._tables_np[:, self.table_width :] = self.window_tables.rows
            self.tables_dirty = False
            return self._tables_np.copy()

    # -- paged layout: publishing (radix + sessions) ----------------------
    def publish_plan(self, slot: int, tokens, *, want_tail: bool) -> dict | None:
        """Plan publishing a slot's first `len(tokens)` rows into the
        radix: the full blocks are shared in place; the sub-block tail
        (when wanted — exact-hit entries and session ends) is COPIED
        into a fresh radix-owned block. Returns None when sharing is off
        or the tail block cannot be allocated even after eviction."""
        if self.radix is None:
            return None
        n = len(tokens)
        full = n - n % self.block
        with self._plock:
            st = self._slot_tables[slot]
            if self.blocks_for(n) > st.hi:
                return None  # rows not resident (shouldn't happen)
            blocks = [int(b) for b in st.rows[: full // self.block]]
            tail_src = tail_dst = -1
            tail_len = n - full
            if want_tail and tail_len > 0:
                if self.pool.available() < 1:
                    self.radix.evict_for(1)
                if self.pool.available() < 1:
                    return None
                tail_src = int(st.rows[full // self.block])
                tail_dst = self.pool.alloc(1)[0]
            return {
                "slot": slot, "blocks": blocks, "tail_src": tail_src,
                "tail_dst": tail_dst, "tail_len": tail_len if want_tail else 0,
            }

    def publish_commit(self, plan: dict, tokens, logits=None, logits_nbytes: int = 0,
                       session_id: str | None = None) -> None:
        """Insert the published sequence into the radix (dedup against
        existing paths) and, for sessions, pin the leaf to the
        conversation."""
        with self._plock:
            node, key = self.radix.insert(
                list(tokens), plan["blocks"],
                tail_block=(plan["tail_dst"] if plan["tail_dst"] >= 0 else None),
                tail_len=plan["tail_len"],
                logits=logits, logits_nbytes=logits_nbytes,
            )
            self._count("store")
            if session_id and self.sessions is not None:
                self.radix.pin(node)
                nblocks = len(plan["blocks"]) + (1 if plan["tail_dst"] >= 0 else 0)
                self.sessions.publish(
                    session_id, tokens, node, key,
                    nblocks * self.block_bytes, self.radix,
                )
                self._count_session("publish")
            self._update_gauges()

    # -- paged layout: session spill/restore ------------------------------
    def session_path(self, sid: str) -> dict | None:
        """The device blocks a resident session's pinned leaf covers
        (root -> leaf order) + its end-record tail — what the engine
        fetches to host on spill."""
        if self.sessions is None:
            return None
        with self._plock:
            s = self.sessions.get(sid)
            if s is None or s.state != "resident" or s.node is None:
                return None
            blocks: list[int] = []
            node = s.node
            chain = []
            while node is not None and node.parent is not None:
                chain.append(node)
                node = node.parent
            for n in reversed(chain):
                blocks.extend(n.blocks)
            end = s.node.ends.get(s.end_key)
            tail = end.tail_block if end is not None and end.tail_block is not None else -1
            tail_len = end.tail_len if end is not None else 0
            return {
                "tokens": list(s.tokens), "blocks": blocks,
                "tail": tail, "tail_len": tail_len,
            }

    def spill_commit(self, sid: str, payload: dict, nbytes: int) -> None:
        """Bookkeeping after the engine fetched a session's blocks to
        host: unpin, store in the offload tier (LRU under its budget),
        and evict the session's now-exclusive leaf chain so the device
        blocks actually free (budget pressure is WHY it spilled). Nodes
        still pinned or shared by other sessions/prompts stay — their
        blocks were never this session's exclusive cost."""
        with self._plock:
            s = self.sessions.get(sid)
            node = s.node if s is not None else None
            self.sessions.mark_spilled(sid, self.radix)
            for dropped in self.sessions.offload.store(sid, payload, nbytes):
                # includes sid itself when the payload exceeds the whole
                # host budget — a "spilled" session with no stored
                # payload would otherwise leak in the registry forever
                self.sessions.forget(dropped, self.radix)
                self._count_session("expire")
            while (
                node is not None and node.parent is not None
                and not node.children and node.refs == 0
            ):
                parent = node.parent
                self.radix._evict_node(node)
                node = parent
            self._count_session("spill")
            self._update_gauges()

    def release_blocks(self, blocks: list[int]) -> None:
        """Drop one reference per block (a failed handoff import's
        allocation, before any table/radix adopted it)."""
        if not blocks:
            return
        with self._plock:
            self.pool.decref(blocks)
            self._update_gauges()

    def handoff_commit(
        self, tokens, blocks: list[int], tail_block: int, tail_len: int,
        *, logits=None, logits_nbytes: int = 0,
    ) -> None:
        """Adopt KV blocks a peer engine transferred in
        (docs/advanced-guide/sharded-serving.md#disaggregation): insert
        the prompt into the radix WITH its stored last-token logits, so
        the next admission of this exact prompt skips prefill — the
        disaggregated decode contract. Same reference discipline as
        restore_commit: insert() dedups against prefixes that grew here
        while the transfer flew; our allocation refs on deduplicated
        blocks release right below, and the tail block is adopted by the
        end record without an extra ref."""
        with self._plock:
            self.radix.insert(
                list(tokens), blocks,
                tail_block=(tail_block if tail_block >= 0 else None),
                tail_len=tail_len,
                logits=logits, logits_nbytes=logits_nbytes,
            )
            self.pool.decref(blocks)
            self._count("store")
            self._update_gauges()

    def restore_fetch(self, sid: str) -> dict | None:
        """Pop a spilled session's host payload (engine rebuilds blocks).
        A spilled session whose payload is gone (host-budget expiry
        races, refused oversized stores) is forgotten — the next turn is
        a clean miss, not a permanently dead registry entry."""
        if self.sessions is None:
            return None
        with self._plock:
            s = self.sessions.get(sid)
            if s is None or s.state != "spilled":
                return None
            payload = self.sessions.offload.fetch(sid)
            if payload is None:
                self.sessions.forget(sid, self.radix)
                self._count_session("expire")
            return payload

    def session_forget(self, sid: str) -> None:
        """Drop a session entirely (restore failed mid-flight: its
        payload is consumed and its blocks cannot be allocated)."""
        if self.sessions is None:
            return
        with self._plock:
            self.sessions.forget(sid, self.radix)
            self._count_session("expire")
            self._update_gauges()

    def alloc_restore(self, n: int) -> list[int] | None:
        with self._plock:
            if self.pool.available() < n and self.radix is not None:
                self.radix.evict_for(n - self.pool.available())
            if self.pool.available() < n:
                return None
            return self.pool.alloc(n)

    def restore_commit(self, sid: str, tokens, blocks: list[int],
                       tail_block: int, tail_len: int) -> None:
        """Re-insert a restored session into the radix and re-pin it.
        insert() dedups against any prefix that re-grew while the
        session was spilled; the duplicate blocks stay slot-free and the
        decref below releases our extra references."""
        with self._plock:
            node, key = self.radix.insert(
                list(tokens), blocks,
                tail_block=(tail_block if tail_block >= 0 else None),
                tail_len=tail_len,
            )
            # drop the allocation references — the radix now holds its
            # own (insert increfed exactly the blocks it adopted; blocks
            # it deduplicated away free right here). The tail block is
            # adopted by the end record without an extra ref.
            self.pool.decref(blocks)
            self.radix.pin(node)
            self.sessions.publish(
                sid, tokens, node, key,
                (len(blocks) + (1 if tail_block >= 0 else 0)) * self.block_bytes,
                self.radix,
            )
            self._count_session("restore")
            self._update_gauges()

    def spill_candidates(self, exclude=None):
        if self.sessions is None:
            return []
        with self._plock:
            return self.sessions.spill_candidates(exclude)

    def session_touch(self, sid: str) -> str:
        """Record a turn arriving for `sid`; returns the session state
        ("new" | "resident" | "spilled") so the engine knows whether a
        restore is needed."""
        if self.sessions is None:
            return "off"
        with self._plock:
            s = self.sessions.get(sid)
            if s is None:
                return "new"
            s.last_use = time.monotonic()
            if s.state == "resident":
                self.sessions.resumes += 1
                self._count_session("resume")
            return s.state

    # -- observability ----------------------------------------------------
    def _count(self, event: str) -> None:
        if self.metrics is not None:
            self.metrics.increment_counter(
                "app_kvcache_events", 1.0, model=self.model, event=event
            )

    def _count_session(self, event: str) -> None:
        if self.metrics is not None:
            self.metrics.increment_counter(
                "app_kvcache_session_events", 1.0, model=self.model, event=event
            )

    def _update_gauges(self) -> None:
        if self.metrics is None or not self.paged:
            return
        self.metrics.set_gauge(
            "app_kvcache_resident_bytes", float(self.pool.bytes_in_use()),
            model=self.model, kind="slots",
        )
        if self.radix is not None:
            self.metrics.set_gauge(
                "app_kvcache_resident_bytes", float(self.radix.owned_bytes),
                model=self.model, kind="prefix",
            )
        self.metrics.set_gauge(
            "app_kvcache_blocks_in_use", float(self.pool.blocks_in_use()),
            model=self.model,
        )
        self.metrics.set_gauge(
            "app_kvcache_blocks_shared", float(self.pool.blocks_shared()),
            model=self.model,
        )
        if self.sessions is not None:
            st = self.sessions.stats()
            self.metrics.set_gauge(
                "app_kvcache_spilled_bytes",
                float(st["offload"]["spilled_bytes"]), model=self.model,
            )
            self.metrics.set_gauge(
                "app_kvcache_session_count", float(st["resident"]),
                model=self.model, state="resident",
            )
            self.metrics.set_gauge(
                "app_kvcache_session_count", float(st["spilled"]),
                model=self.model, state="spilled",
            )

    def stats(self) -> dict[str, Any]:
        if not self.paged:
            return {
                "layout": "rolling" if self.rolling else "dense",
                "capacity": self.capacity,
                "window": self.window,
                "slot_bytes": self.slot_bytes,
                "row_bytes": self.row_bytes,
                "prefix": self.prefix.stats() if self.prefix is not None else None,
            }
        with self._plock:
            return {
                "layout": "paged",
                "capacity": self.capacity,
                "window": self.window,
                "block": self.block,
                "int8": self.int8,
                "pool_blocks": self.pool.n_blocks,
                "blocks_in_use": self.pool.blocks_in_use(),
                "blocks_shared": self.pool.blocks_shared(),
                "blocks_reserved": self.pool.reserved,
                "cow_copies": self.pool.cow_copies,
                "block_bytes": self.block_bytes,
                # one token of one layer, as stored (row_shapes; scales included)
                "row_bytes": self.row_bytes,
                # single source of truth for resident KV bytes: the pool
                "slot_bytes": self.pool.bytes_in_use(),
                "prefix": self.radix.stats() if self.radix is not None else None,
                "sessions": (
                    self.sessions.stats() if self.sessions is not None else None
                ),
                # A mixed stack keeps two kinds of state. The keys above
                # (pool_blocks, blocks_in_use, block_bytes, slot_bytes) are
                # then the FULL layers' pool, the one that grows with the
                # context; each kind's own numbers are here.
                **({"kinds": {
                    "full": {
                        "layers": self.pool_layers,
                        "pool_blocks": self.pool.n_blocks,
                        "blocks_in_use": self.pool.blocks_in_use(),
                        "block_bytes": self.block_bytes,
                    },
                    "window": {
                        "layers": len(self.kind_layers[1]),
                        **self.window_tables.stats(),
                    },
                }} if self.mixed else {}),
            }

    def close(self) -> None:
        if self.prefix is not None:
            self.prefix.clear()
        if self.paged:
            with self._plock:
                if self.sessions is not None:
                    self.sessions.clear(self.radix)
                if self.radix is not None:
                    self.radix.clear()
                for s in range(self.slots):
                    self._release_slot_locked(s)
        if self.metrics is not None:
            # freed with the engine: a stale gauge would keep reporting a
            # closed engine's KV bytes as resident forever
            for kind in ("slots", "prefix"):
                self.metrics.set_gauge(
                    "app_kvcache_resident_bytes", 0.0,
                    model=self.model, kind=kind,
                )
            if self.paged:
                self.metrics.set_gauge(
                    "app_kvcache_blocks_in_use", 0.0, model=self.model
                )
                self.metrics.set_gauge(
                    "app_kvcache_blocks_shared", 0.0, model=self.model
                )
                self.metrics.set_gauge(
                    "app_kvcache_spilled_bytes", 0.0, model=self.model
                )
                for state in ("resident", "spilled"):
                    self.metrics.set_gauge(
                        "app_kvcache_session_count", 0.0,
                        model=self.model, state=state,
                    )
