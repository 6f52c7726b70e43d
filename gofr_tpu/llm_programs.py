"""The serving engine's device programs, each written once.

`LLMEngine` (gofr_tpu.llm) schedules; this module builds what it dispatches
and is the one place that knows a program's argument list. The arrows run
llm.py -> here -> models/, kvcache/, ops/, profiling/: nothing is imported
from the engine.

Three program kinds carry decode, and each has ONE body:

- **chunk**: advance every active slot K decode steps (models.transformer
  decode_chunk / decode_chunk_paged), sampling on the device.
- **step**: the token-budget scheduler's unified step. Append one prompt
  chunk per packed row to its slot's KV (models.transformer.prefill_append:
  over the pool's gathered view, whose new rows go back through the tables,
  or straight into the contiguous stack, in place), activate rows
  whose prompt just completed (their first token sampled from the chunk's
  last-token logits and merged into the on-device tail, no host round trip),
  then, in the SAME program, the decode chunk: rows that finish this step
  decode immediately. The body has one static fact, whether that chunk
  follows the rows. The scheduler dispatches the **rows** alone
  (`llm.step_p{n}_d0`: the same body, stopped before the chunk, with no
  `live` mask to take and no tokens to return) for a step in which no lane
  decodes and no row finishes its prompt: every result of the chunk would
  have been masked, and it streams every weight K times.
- **verify**: speculative decoding's fused verify (gofr_tpu.spec). Score all
  W = draft+1 positions of every selected slot in one write-then-attend pass
  (models.transformer.verify_chunk), sample each position, accept the longest
  agreeing prefix ON DEVICE and advance tail/length to the accepted state, so
  the device batch state stays chained exactly as decode chunks leave it.
  Rejected rows stay above the rolled-back cursor, masked until overwritten
  (ops.chunk_prefill_attention's rollback contract).

What differs between the engine's configurations is supplied by two things
chosen when the engine is built, at trace time, never inside a traced branch:

- a **layout** (`_Slab`: the contiguous slab or rolling ring | `_Pool`: the
  paged block pool): how a step reads the rows it appends to and where it
  writes the new ones, how the decode chunk runs, which operands the cache is
  (`cache` | `cache, scales, tables` and a host `live` mask) and which of
  them are donated;
- a **sampler** (`_Sampler`: plain | `_GrammarSampler`: masked by a resident
  grammar's DFA, gofr_tpu.structured): the first-token sample with its start
  state, the per-iteration sampler of the decode chunk, the per-position
  sampling of verify, and the operands `gstate` (donated), `gids`, `gtab`.

A program's positional signature and result tuple are the layout's and the
sampler's operands spliced into the kind's own (`Programs._signature`); the
engine hands `Programs.call` its device state by those names and gets the
results back by name. The wave scheduler's programs (prefill, insert, admit
update, first token of a prefix hit) and the pool's seed / restore have one
body each and live here as they were.
"""

from __future__ import annotations

import functools
import inspect
from typing import Any

import jax
import jax.numpy as jnp

from .models.transformer import decode_chunk as chunk_fn
from .models.transformer import decode_chunk_paged, prefill, prefill_append, verify_chunk
from .profiling import instrument_jit

__all__ = ["Programs", "finite_guard"]

def finite_guard(logits, toks):
    """Numerical-watchdog sentinel: replace each sampled token whose
    logits row contains NaN/Inf with ``-1`` — an id no sampler can
    produce (argmax and top-k indices are >= 0), so the sentinel rides
    the existing token fetch at zero extra transfer cost and the
    collector converts it into a replica death instead of streaming
    garbage with status 200. One cheap on-device reduction per sampled
    row, trivially amortized against the matmuls that produced the
    logits. Traced into the engine's jitted programs when
    ``TPU_LLM_NUMERIC_CHECK`` is on; module-level so tests drive it with
    hand-built NaN logits."""
    ok = jnp.isfinite(logits).all(axis=-1)
    return jnp.where(ok, toks, jnp.int32(-1))


# -- samplers -------------------------------------------------------------------


class _Sampler:
    """Greedy / top-k temperature sampling with the numerical watchdog: the
    sampler of every unconstrained program."""

    suffix = ""  # of a program's registry name (llm.decode_chunk8 | llm.decode_chunk8g)
    names: dict = {}  # the jitted function's __name__ per kind; the layout's where absent
    state: tuple = ()  # device-persistent operand, donated, handed back
    ids: tuple = ()  # shipped with every dispatch
    table: tuple = ()  # read-only

    def __init__(self, vocab_size: int, numeric_check: bool):
        self.topk = min(64, vocab_size)
        self.numeric_check = numeric_check

    def raw(self, logits, temps, key):
        """Greedy for temp==0; temperature sampling restricted to the
        top-k logits otherwise. Full-vocab categorical would generate
        batch x vocab Gumbel draws per step (millions of threefry
        rounds for a 256k vocab) and dominates decode time; top-k keeps
        the RNG work at batch x 64."""
        greedy = jnp.argmax(logits, axis=-1)
        topv, topi = jax.lax.approx_max_k(logits, self.topk)
        local = jax.random.categorical(
            key, topv / jnp.maximum(temps, 1e-4)[:, None], axis=-1
        )
        sampled = jnp.take_along_axis(topi, local[:, None], axis=1)[:, 0]
        return jnp.where(temps > 0.0, sampled, greedy).astype(jnp.int32)

    def __call__(self, logits, temps, key):
        """raw() plus the numerical watchdog: a row whose logits went
        NaN/Inf samples the -1 sentinel instead (finite_guard) — the
        collector converts it to a replica death before anything is
        emitted."""
        out = self.raw(logits, temps, key)
        return finite_guard(logits, out) if self.numeric_check else out

    # the seams of the three bodies; `a` holds the program's operands by name
    def start(self, meta):
        """What a step's meta carries for the sampler beside slot | finish."""
        return None

    def first(self, a, start, logits, temps, key, finish):
        """A step's first tokens, and what seeds the finished rows' sampler
        state once the slots are activated (`seed(a, fin_slot)`)."""
        return self(logits, temps, key), lambda a, fin_slot: None

    def chunk(self, a):
        """(sample_fn, keywords) for decode_chunk / decode_chunk_paged."""
        return self, {}

    def positions(self, a, logits, temps, keys, drafts):
        """Verify's per-position samples [S, W], and what advances the
        sampler state past the accepted prefix (`accept(a, acc, bonus, sel)`)."""
        ys = jnp.stack(
            [self(logits[:, j], temps, keys[j]) for j in range(len(keys))],
            axis=1,
        )
        return ys, lambda a, acc, bonus, sel: None


class _GrammarSampler(_Sampler):
    """Grammar-constrained sampling (gofr_tpu.structured;
    docs/advanced-guide/structured-decoding.md). gtab [G, Smax, V] int32 is
    the resident-grammar transition table (entry < 0 = token not admitted in
    that state; read-only, retraced when its padded shape grows); gids [B]
    selects each lane's grammar (-1 = unconstrained; shipped per dispatch,
    it only changes at admission) and gstate [B] its current DFA state
    (device-persistent and donated exactly like the chain tail, so pipelined
    dispatches chain states without a host round trip). The watchdog guard
    runs on the RAW logits — a grammar mask is not a numerical fault.
    Unconstrained lanes take their logits UNTOUCHED (a jnp.where select, not
    a +0 bias), which is what pins mixed-batch token-identity with the
    unconstrained programs."""

    suffix = "g"
    names = {"chunk": "_chunk_c", "step": "_step_c", "rows": "_step_rows_c", "verify": "_verify_c"}
    state, ids, table = ("gstate",), ("gids",), ("gtab",)

    def __init__(self, vocab_size: int, numeric_check: bool):
        super().__init__(vocab_size, numeric_check)
        # a large-negative bias, not -inf: an all-masked padding row must
        # stay NaN-free
        self.neg = jnp.float32(-1e30)

    @staticmethod
    def rows(gtab, gid, gstate):
        G, Smax = gtab.shape[0], gtab.shape[1]
        rows = gtab[
            jnp.clip(gid, 0, G - 1), jnp.clip(gstate, 0, Smax - 1)
        ]  # [B, V] next state per token, or < 0
        on = (gid >= 0) & (gstate >= 0) & (gstate < Smax)
        return rows, on

    def mask(self, logits, rows, on):
        return jnp.where(on[:, None] & (rows < 0), self.neg, logits)

    def masked(self, logits, rows, on, temps, key):
        out = self.raw(self.mask(logits, rows, on), temps, key)
        return finite_guard(logits, out) if self.numeric_check else out

    @staticmethod
    def advance(rows, toks):
        return jnp.take_along_axis(rows, jnp.clip(toks, 0)[:, None], axis=1)[:, 0]

    def sample(self, logits, temps, key, gtab, gid, gstate):
        """One masked sample + DFA advance for per-lane grammar states:
        the stateful sampler threaded through decode chunks
        (models.transformer sample_state seam)."""
        rows, on = self.rows(gtab, gid, gstate)
        out = self.masked(logits, rows, on, temps, key)
        return out, jnp.where(on, self.advance(rows, out), gstate)

    def start(self, meta):
        # meta [4, nb] int32: slot | finish | grammar id | start DFA state
        return meta[2], meta[3]

    def first(self, a, start, logits, temps, key, finish):
        """A row whose prompt completes this step samples its FIRST token
        masked by its start state (0 fresh; the host mirror's state for a
        preemption/failover continuation) and seeds the slot's device
        state; the fused decode chunk then advances every lane's state
        token-by-token."""
        rows, on = self.rows(a["gtab"], *start)
        on = on & (finish == 1)
        first = self.masked(logits, rows, on, temps, key)
        st1 = self.advance(rows, first)

        def seed(a, fin_slot):
            a["gstate"] = a["gstate"].at[fin_slot].set(
                jnp.where(on, st1, 0), mode="drop"
            )

        return first, seed

    def chunk(self, a):
        gtab, gids = a["gtab"], a["gids"]
        return (
            lambda lg, tp, k, st: self.sample(lg, tp, k, gtab, gids, st)
        ), {"sample_state": a["gstate"]}

    def positions(self, a, logits, temps, keys, drafts):
        """Per-position grammar masks: position j's context is tail +
        draft[:j], so its mask derives from the state reached by advancing
        the slot state through the DRAFT tokens (known at trace time — a
        tiny unrolled chain). An inadmissible draft token sends the chain
        state dead, but the masked sample at its own position is
        guaranteed to disagree with it, so acceptance always stops before
        a dead state can matter; the post-accept state advances from the
        accepted prefix's state by the bonus token."""
        gtab, gids = a["gtab"], a["gids"]
        Kd = drafts.shape[1]
        s = a["gstate"]
        states, ys = [s], []
        for j in range(Kd + 1):
            rows, on = self.rows(gtab, gids, s)
            y = self.raw(self.mask(logits[:, j], rows, on), temps, keys[j])
            ys.append(finite_guard(logits[:, j], y) if self.numeric_check else y)
            if j < Kd:
                s = jnp.where(on, self.advance(rows, drafts[:, j]), s)
                states.append(s)

        def accept(a, acc, bonus, sel):
            st_acc = jnp.take_along_axis(
                jnp.stack(states, axis=1), acc[:, None], axis=1
            )[:, 0]
            rows_a, on_a = self.rows(gtab, gids, st_acc)
            nxt = self.advance(rows_a, bonus)
            a["gstate"] = jnp.where(sel & on_a, nxt, a["gstate"])

        return jnp.stack(ys, axis=1), accept


# -- layouts --------------------------------------------------------------------


class _Slab:
    """The contiguous layouts (kv_paged=False): a dense
    [n_layers, S, rows, hkv, hd] slab for global attention, or the
    window-bounded ROLLING ring of a sliding-window model (kv.ring > 0).
    Every program that adds rows writes THOSE rows into the engine's own
    donated stack, where it lies, by one scatter at (layer, slot, row), and
    reads the resident rows from it as stored: a step's append and verify
    through models.transformer._append_forward's `slots` form, the ring's
    decode chunk at its end-of-chunk merge (PERF.md §3)."""

    operands = results = ("cache",)  # what the cache is among a program's operands | results
    live: tuple = ()
    moe = False
    names = {"chunk": "_chunk_op", "step": "_step", "rows": "_step_rows", "verify": "_verify"}
    # the slab's chunk has always called the tail it decodes from `tokens`:
    # a jitted function's parameter names are the compiled program's
    renamed = {("chunk", "tail"): "tokens"}

    def __init__(self, p: "Programs"):
        self.p = p

    def donated(self, kind: str) -> tuple:
        return ("tail",) if kind in ("step", "rows") else ()

    def append(self, a, tokens, slot_idx, cursors, n_new, aids_row):
        # the engine's own donated stack and the packed rows' slots: the
        # chunk's rows are written into it where it lies (padding lanes
        # read a real slot, clipped, and write nothing)
        p = self.p
        logits, a["cache"] = prefill_append(
            a["params"], p.cfg, tokens, a["cache"], cursors, n_new,
            ring=p.kv.ring, aids=aids_row, mesh=p.mesh, slots=slot_idx,
        )
        return logits

    def decode(self, a, K, sample_fn, state):
        p = self.p
        toks, a["tail"], a["cache"], a["rng"], *st = chunk_fn(
            a["params"], p.cfg, a["tail"], a["cache"], a["active"], a["temps"],
            a["rng"], n_steps=K, sample_fn=sample_fn, ring=p.kv.ring,
            overlap=p.tp_gather, **state,
        )
        return toks, st

    def verify(self, a, toks, n_in, W):
        p, cache = self.p, a["cache"]
        logits, a["cache"] = verify_chunk(
            a["params"], p.cfg, toks, cache, cache.length, n_in,
            ring=p.kv.ring, aids=a["params"].get("aids"), mesh=p.mesh,
            slots=jnp.arange(p.slots, dtype=jnp.int32),
        )
        return logits


class _Pool:
    """The paged block pool (kvcache.paged; docs/advanced-guide/kv-cache.md).
    Same scheduler contracts as the contiguous layouts, but the slot KV
    lives in ONE block pool read/written through per-slot block tables:
    decode attention goes through ops.paged_chunk_decode_attention (Pallas
    paged kernel on TPU, dense-gather fallback elsewhere), appends/verifies
    gather the dense per-slot view at the program boundary and scatter
    exactly the rows they wrote back through the table (write indices from
    DEVICE lengths — rollback/pipeline safe). A host `live` mask rides
    every decode-bearing program: the contiguous path could afford clamped
    garbage writes for stale-active lanes, but a paged stale lane's table
    may point at blocks that now belong to someone else.

    The facts of this layout, stated once: `int8` (the pool stores int8
    rows and `scales` is real, donated and handed back; otherwise `scales`
    is an empty stand-in that passes through), `kernel` (the decode
    attention is a Pallas paged kernel), `paged_fn` (decode runs through
    decode_chunk_paged: with a kernel, and always for a latent cache, which
    has no contiguous decode chunk to fall back to — decode_chunk_paged
    gathers through the table itself off the TPU) and `moe` (a routed
    model's chunk and step return what their experts did)."""

    operands = ("cache", "scales", "tables")
    results = ("cache", "scales")
    live = ("live",)
    names = {"chunk": "_chunk", "step": "_step", "rows": "_step_rows", "verify": "_verify_paged"}
    renamed: dict = {}

    def __init__(self, p: "Programs", kernel: bool):
        self.p = p
        self.int8 = p.kv.int8
        self.kernel = kernel
        # (window and full layers in one stack: two pools, which only
        # decode_chunk_paged reads)
        self.mixed = bool(getattr(p.cfg, "mixed", False))
        self.paged_fn = kernel or bool(getattr(p.cfg, "latent", False)) or self.mixed
        self.moe = int(getattr(p.cfg, "n_experts", 0) or 0) > 0

    def donated(self, kind: str) -> tuple:
        # (the pool's step hands `tail` back without taking its buffer)
        return ("scales",) if self.int8 else ()

    def sc(self, scales):
        return scales if self.int8 else None

    def gather_view(self, cache, scales, tables, lengths):
        from .kvcache.paged import gather_slots

        sc = self.sc(scales)
        return gather_slots(
            cache.k, cache.v, tables, lengths, rows=self.p.kv.row_shapes,
            scales=(None if sc is None else (sc[0], sc[1])),
            dtype=self.p.cfg.dtype,
        )

    def scatter(self, cache, scales, tables, rows_k, rows_v, pos, valid):
        from .kvcache.paged import scatter_rows

        k2, v2, sc2 = scatter_rows(
            cache.k, cache.v, tables, rows_k, rows_v, pos, valid,
            scales=self.sc(scales),
        )
        return cache._replace(k=k2, v=v2), (sc2 if self.int8 else scales)

    @staticmethod
    def rows_at(stack, pos):
        """[L, S, C, hkv, hd] rows at per-slot positions [S, W]."""
        idx = jnp.clip(pos, 0, stack.shape[2] - 1)
        return jnp.take_along_axis(stack, idx[None, :, :, None, None], axis=2)

    def _write_back(self, a, tables, dense, pos, valid):
        a["cache"], a["scales"] = self.scatter(
            a["cache"], a["scales"], tables,
            self.rows_at(dense.k, pos), self.rows_at(dense.v, pos), pos, valid,
        )

    def _appended(self, tokens, cursors, n_new):
        """A prompt chunk's positions [nb, c] and which of them are written."""
        c = tokens.shape[1]
        pos = cursors[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
        valid = (
            jnp.arange(c, dtype=jnp.int32)[None, :] < n_new[:, None]
        ) & (pos < self.p.kv.capacity)
        return pos, valid

    def _append_mixed(self, a, tokens, tsub, slot_idx, cursors, n_new, aids_row):
        """A mixed stack's append: the full layers' view through their
        table, the window layers' last `window_ring` rows as a ring through
        theirs; the chunk's rows of every layer come back and go through
        each kind's table (models.transformer._append_forward_mixed)."""
        from .kvcache.paged import gather_ring, gather_slots, scatter_rows_by_kind, split_tables
        from .models.transformer import layer_kinds

        p, cache = self.p, a["cache"]
        kind_tables = split_tables(tsub)
        rows = p.kv.row_shapes
        full = gather_slots(cache.k[0], cache.v[0], kind_tables[0], cursors, rows=rows)
        ring = gather_ring(
            cache.k[1], cache.v[1], kind_tables[1], cursors, p.kv.window_ring, rows=rows
        )
        logits, new = prefill_append(
            a["params"], p.cfg, tokens,
            cache._replace(k=(full.k, ring.k), v=(full.v, ring.v), length=cursors),
            cursors, n_new, aids=aids_row, moe_out=a["moe_out"],
        )
        pos, valid = self._appended(tokens, cursors, n_new)
        k2, v2 = scatter_rows_by_kind(
            cache.k, cache.v, kind_tables, layer_kinds(p.cfg), new.k, new.v, pos, valid
        )
        length = cache.length.at[slot_idx].set(cursors + n_new, mode="drop")
        a["cache"] = cache._replace(k=k2, v=v2, length=length)
        return logits

    def append(self, a, tokens, slot_idx, cursors, n_new, aids_row):
        p = self.p
        tsub = jnp.take(a["tables"], jnp.clip(slot_idx, 0, p.slots - 1), axis=0)
        if self.mixed:
            return self._append_mixed(a, tokens, tsub, slot_idx, cursors, n_new, aids_row)
        sub = self.gather_view(a["cache"], a["scales"], tsub, cursors)
        logits, sub2 = prefill_append(
            a["params"], p.cfg, tokens, sub, cursors, n_new, ring=0,
            aids=aids_row, mesh=p.mesh, moe_out=a["moe_out"],
        )
        self._write_back(a, tsub, sub2, *self._appended(tokens, cursors, n_new))
        length = a["cache"].length.at[slot_idx].set(cursors + n_new, mode="drop")
        a["cache"] = a["cache"]._replace(length=length)
        return logits

    def decode(self, a, K, sample_fn, state):
        p, cache, scales, tables = self.p, a["cache"], a["scales"], a["tables"]
        eff = jnp.logical_and(a["active"], a["live"])
        if self.paged_fn:
            toks, a["tail"], a["cache"], sc, a["rng"], *st = decode_chunk_paged(
                a["params"], p.cfg, a["tail"], cache, self.sc(scales),
                tables, eff, a["temps"], a["rng"],
                n_steps=K, sample_fn=sample_fn, block=p.kv.block,
                use_kernel=self.kernel, overlap=p.tp_gather, mesh=p.mesh,
                moe_out=a["moe_out"], **state,
            )
            a["scales"] = sc if self.int8 else scales
            return toks, st
        dense = self.gather_view(cache, scales, tables, cache.length)
        toks, a["tail"], nd, a["rng"], *st = chunk_fn(
            a["params"], p.cfg, a["tail"], dense, eff, a["temps"], a["rng"],
            n_steps=K, sample_fn=sample_fn, ring=0, overlap=p.tp_gather,
            moe_out=a["moe_out"], **state,
        )
        pos = cache.length[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :]
        valid = eff[:, None] & (pos < p.kv.capacity)
        self._write_back(a, tables, nd, pos, valid)
        a["cache"] = a["cache"]._replace(length=nd.length)
        return toks, st

    def verify(self, a, toks, n_in, W):
        p, cache, tables = self.p, a["cache"], a["tables"]
        dense = self.gather_view(cache, a["scales"], tables, cache.length)
        logits, nd = verify_chunk(
            a["params"], p.cfg, toks, dense, cache.length, n_in, ring=0,
            aids=a["params"].get("aids"), mesh=p.mesh,
        )
        pos = cache.length[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
        valid = (
            jnp.arange(W, dtype=jnp.int32)[None, :] < n_in[:, None]
        ) & (pos < p.kv.capacity)
        self._write_back(a, tables, nd, pos, valid)
        return logits


# -- the programs ---------------------------------------------------------------


class Programs:
    """Every jitted program of one engine. Each goes through the compile
    observatory (profiling.instrument_jit): per-signature compile wall time
    + cost_analysis into the process registry (/.well-known/debug/compiles),
    app_jax_* metrics when a manager is wired; dispatch semantics (donation,
    shardings) are those of the bare jax.jit the wrapper replaces.

    Built from what the programs close over, not from the engine:
    `chunk_shapes` is empty under the wave scheduler (no step program),
    `spec_draft` 0 without speculation (no verify program: a spec-off
    engine compiles and registers nothing new), `kernel` says the decode
    attention is a Pallas paged kernel (the engine's attention_paths)."""

    def __init__(
        self, cfg, kv, *, slots: int, decode_chunk: int, chunk_shapes: tuple,
        spec_draft: int, mesh, tp_gather, kernel: bool, numeric_check: bool,
        label: str, metrics,
    ):
        self.cfg, self.kv, self.slots = cfg, kv, slots
        self.decode_chunk, self.chunk_shapes = decode_chunk, tuple(chunk_shapes)
        self.spec_draft = spec_draft
        self.mesh, self.tp_gather = mesh, tp_gather
        # last-token logits ride the prefill programs whenever ANY prefix
        # index can serve exact hits from them: the contiguous PrefixCache
        # or the paged radix tree (kvcache.paged)
        self.keep_logits = kv.prefix is not None or (kv.paged and kv.share)
        self.label, self.metrics = label, metrics
        # Two chunk lengths: the full chunk amortizes dispatch and is
        # chained eagerly to cover remaining demand (an 8-token completion
        # costs ~2 RTTs); the short variant (quarter length) only serves
        # tail ends where even one full chunk overshoots the whole batch's
        # remaining need (LLMEngine._dispatch).
        self.chunk_short = max(1, decode_chunk // 4)
        self.layout = _Pool(self, kernel) if kv.paged else _Slab(self)
        self._samplers = {
            False: _Sampler(cfg.vocab_size, numeric_check),
            True: _GrammarSampler(cfg.vocab_size, numeric_check),
        }
        self.sample = self._samplers[False]
        self._signatures = {
            (kind, g): self._signature(kind, g)
            for kind in ("chunk", "step", "rows", "verify") for g in (False, True)
        }
        self._families: dict = {}
        self._rows_ops: dict = {}
        self._restore_ops: dict[int, Any] = {}
        self.prefill_op = self._jit("llm.prefill", self._prefill_op)
        self.admit_update = self._jit(
            "llm.admit_update", _admit_update, donate_argnums=(0, 1, 2)
        )
        self.hit_first_op = (
            self._jit("llm.hit_first", self._hit_first) if self.keep_logits else None
        )
        pool_donated = (0, 1) if kv.int8 else (0,)
        if kv.paged:
            self.insert_many = self._jit(
                "llm.insert_many", self._insert_paged, donate_argnums=pool_donated
            )
            self.seed_op = self._jit(
                "llm.kv_seed", self._seed, donate_argnums=pool_donated
            )
        else:
            self.seed_op = None  # seeding a slot is the pool's
            self.insert_many = self._jit(
                "llm.insert_many", _insert_many, donate_argnums=(0,)
            )

    def _jit(self, name: str, fn, **kw):
        return instrument_jit(name, fn, model=self.label, metrics=self.metrics, **kw)

    # -- chunk | step | verify ----------------------------------------------
    def family(self, grammar: bool) -> tuple:
        """(chunk programs by K, step programs by chunk shape, the verify
        program or None) of one sampler. The grammar family is built at the
        first constrained dispatch and compiles at its first calls: a
        constrained-free engine builds zero new programs."""
        fam = self._families.get(grammar)
        if fam is None:
            s = self._samplers[grammar]
            fam = self._families[grammar] = (
                {
                    K: self._program("chunk", s, K)
                    for K in (self.decode_chunk, self.chunk_short)
                },
                # ONE jitted program per chunk shape. Executable count:
                # shapes x pow2-widths — it replaces the monolithic path's
                # buckets x widths prefill family plus its separate
                # insert/admit programs on the miss path.
                {shape: self._program("step", s, shape) for shape in self.chunk_shapes},
                self._program("verify", s, self.spec_draft + 1) if self.spec_draft else None,
            )
        return fam

    def rows(self, grammar: bool) -> dict:
        """The step programs without their decode chunk (`llm.step_p{n}_d0`)
        of one sampler, by chunk shape: what a step dispatches when no lane
        decodes and no row finishes. Built at first use, as `family` is."""
        ops = self._rows_ops.get(grammar)
        if ops is None:
            s = self._samplers[grammar]
            ops = self._rows_ops[grammar] = {
                shape: self._program("rows", s, shape) for shape in self.chunk_shapes
            }
        return ops

    def _signature(self, kind: str, grammar: bool) -> tuple[tuple, tuple]:
        """A program's positional operands and its results, by name."""
        lay, s = self.layout, self._samplers[grammar]
        if kind == "chunk":
            ins = ("params", "tail", *lay.operands, *lay.live, "active", "temps",
                   *s.state, *s.ids, "rng", *s.table)
            outs = ("toks", "tail", *lay.results, *s.state, "rng")
        elif kind in ("step", "rows"):
            # the rows alone run no decode chunk: nothing reads `live` or the
            # lanes' grammar ids, and no tokens come back (`first` is what
            # the collector fetches to know the program ended)
            live, ids, toks = (
                (lay.live, s.ids, ("toks",)) if kind == "step" else ((), (), ())
            )
            ins = ("params", *lay.operands, *live, "tail", "active", "temps",
                   *s.state, "pack", "meta", *ids, "rng", *s.table)
            outs = ("first", "kept", *toks, "tail", *lay.results, "active", "temps",
                    *s.state, "rng")
        else:
            ins = ("params", *lay.operands, "tail", "temps", *s.state, "pack",
                   *s.ids, "rng", *s.table)
            outs = ("ys", "acc", *lay.results, "tail", *s.state, "rng")
        # A routed model's chunk and step return what their experts did
        # beside their results: ONE int32 vector [pairs, touched, rows per
        # expert...] summed over the program's layer calls. A dense model's
        # programs return what they always did.
        if lay.moe and kind != "verify":
            outs += ("moe",)
        return ins, outs

    def call(self, kind: str, grammar: bool, op, env: dict) -> dict:
        """Run `op`, a program of `kind` (the engine looks it up at every
        dispatch, so a test may have wrapped it), on the operands `env`
        holds by name; the results by name."""
        ins, outs = self._signatures[kind, grammar]
        return dict(zip(outs, op(*(env[n] for n in ins))))

    _DONATED = {
        "chunk": ("cache",), "step": ("cache", "active", "temps"),
        "rows": ("cache", "active", "temps"), "verify": ("cache", "tail"),
    }

    def _program(self, kind: str, sampler: _Sampler, n: int):
        ins, outs = self._signatures[kind, sampler is not self.sample]
        body = {
            "chunk": self._chunk_body, "step": self._step_body,
            "rows": functools.partial(self._step_body, decode=False),
            "verify": self._verify_body,
        }[kind]

        def program(*args):
            a = dict(zip(ins, args), moe_out=[])
            body(a, sampler, n)
            if "moe" in outs:
                a["moe"] = sum(a["moe_out"])
            return tuple(a[name] for name in outs)

        # the device trace names a program's events after the jitted
        # function: benchmarks/metrics and hostspans match jit__step,
        # jit__chunk, jit__verify. Its parameters keep their names too.
        program.__name__ = program.__qualname__ = (
            sampler.names.get(kind) or self.layout.names[kind]
        )
        program.__signature__ = inspect.Signature([
            inspect.Parameter(
                self.layout.renamed.get((kind, x), x), inspect.Parameter.POSITIONAL_ONLY
            )
            for x in ins
        ])
        K = self.decode_chunk
        name = {
            "chunk": f"llm.decode_chunk{n}", "step": f"llm.step_p{n}_d{K}",
            "rows": f"llm.step_p{n}_d0", "verify": f"llm.step_v{n}",
        }[kind] + sampler.suffix
        donated = self._DONATED[kind] + self.layout.donated(kind) + sampler.state
        return self._jit(
            name, program,
            donate_argnums=tuple(i for i, x in enumerate(ins) if x in donated),
        )

    def _chunk_body(self, a, sampler, K):
        sample_fn, state = sampler.chunk(a)
        a["toks"], st = self.layout.decode(a, K, sample_fn, state)
        a.update(zip(sampler.state, st))

    def _step_body(self, a, sampler, shape, decode: bool = True):
        """pack [nb, shape+3] int32: tokens | cursor | n_new | temp-bits.
        meta [2, nb] int32 (4 with a grammar): slot (= `slots` for inert
        padding lanes) | finish flag. One packed h2d per step. `decode`:
        the decode chunk follows the rows (not in `llm.step_p{n}_d0`)."""
        params, pack, meta = a["params"], a["pack"], a["meta"]
        tokens = pack[:, :shape]
        cursors = pack[:, shape]
        n_new = pack[:, shape + 1]
        req_temps = jax.lax.bitcast_convert_type(pack[:, shape + 2], jnp.float32)
        slot_idx, finish = meta[0], meta[1]
        start = sampler.start(meta)
        # per-row adapter ids (LoRA engines only — static pytree check):
        # packed prefill lanes gather their slot's id; the fused decode
        # below reads the full per-slot vector itself
        aids_row = (
            jnp.take(params["aids"], slot_idx, mode="clip")
            if "aids" in params else None
        )
        logits = self.layout.append(a, tokens, slot_idx, cursors, n_new, aids_row)
        a["rng"], sub_rng = jax.random.split(a["rng"])
        a["first"], seed = sampler.first(a, start, logits, req_temps, sub_rng, finish)
        oob = self.slots  # out-of-range slot index: scatters are dropped
        fin_slot = jnp.where(finish == 1, slot_idx, oob)
        # Mid-prefill rows must deactivate their slot: the device
        # flag may still be True from the slot's PREVIOUS occupant
        # (nothing clears it at finish), and the decode merge
        # advances length for active slots — on a rolling ring the
        # stale advance between two appends can wrap past the
        # capacity slack and overwrite this prompt's in-window
        # rows. (Writes BEFORE the first chunk are harmless: the
        # first append resets length, and rows beyond it are
        # position-masked.) Disjoint from fin_slot — a pack row
        # either finishes or not.
        mid_slot = jnp.where(finish == 1, oob, slot_idx)
        a["active"] = a["active"].at[mid_slot].set(False, mode="drop")
        a["tail"] = a["tail"].at[fin_slot].set(a["first"], mode="drop")
        a["active"] = a["active"].at[fin_slot].set(True, mode="drop")
        a["temps"] = a["temps"].at[fin_slot].set(req_temps, mode="drop")
        seed(a, fin_slot)
        a["kept"] = logits if self.keep_logits else None
        if decode:
            self._chunk_body(a, sampler, self.decode_chunk)

    def _verify_body(self, a, sampler, W):
        """pack [S, Kd+2] int32: draft tokens | n_draft | selected.
        Unselected lanes write nothing (n_in 0 drops every scatter
        index) and keep their tail/length — the program is safe to run
        over the full slot batch."""
        Kd, pack, length = W - 1, a["pack"], a["cache"].length
        drafts = pack[:, :Kd]
        n_draft = pack[:, Kd]
        sel = pack[:, Kd + 1] == 1
        n_in = jnp.where(sel, n_draft + 1, 0)
        toks = jnp.concatenate([a["tail"][:, None], drafts], axis=1)
        logits = self.layout.verify(a, toks, n_in, W)
        a["rng"], sub = jax.random.split(a["rng"])
        keys = jax.random.split(sub, W)
        ys, accept = sampler.positions(a, logits, a["temps"], keys, drafts)
        # longest-agreeing-prefix acceptance (== Leviathan rejection
        # sampling for the deterministic drafter: ys[j] ~ p_j via the
        # sampler, so draft j is accepted with probability p_j(draft) and
        # a rejection emits the residual-distribution sample)
        agree = (ys[:, :Kd] == drafts) & (
            jnp.arange(Kd, dtype=jnp.int32)[None, :] < n_draft[:, None]
        )
        acc = jnp.cumprod(agree.astype(jnp.int32), axis=1).sum(axis=1)  # [S] accepted drafts
        bonus = jnp.take_along_axis(ys, acc[:, None], axis=1)[:, 0]
        accept(a, acc, bonus, sel)
        new_len = jnp.where(sel, length + acc + 1, length)
        a["cache"] = a["cache"]._replace(length=new_len)
        a["tail"] = jnp.where(sel, bonus, a["tail"])
        a["ys"], a["acc"] = ys, acc

    # -- the wave scheduler's programs, the pool's seed and restore ----------
    def _prefill_op(self, params, pack, rng):
        """pack [nb, bucket+2] int32: tokens | lengths | temps-as-bits.
        One packed host->device transfer per wave: every h2d array
        costs host-blocking latency regardless of its size, so the
        engine never ships loose vectors.
        For windowed configs the dense banded prefill is ring-packed to
        the rolling slot width; when a prefix index is on, the last-
        token logits ride along so hits can re-sample first tokens."""
        tokens = pack[:, :-2]
        lengths = pack[:, -2]
        temps = jax.lax.bitcast_convert_type(pack[:, -1], jnp.float32)
        last_logits, cache = prefill(
            params, self.cfg, tokens, lengths,
            self.kv.prefill_cache_len(tokens.shape[1]),
        )
        cache = self.kv.pack_prefill(cache)
        rng, sub = jax.random.split(rng)
        first = self.sample(last_logits, temps, sub)
        return first, cache, (last_logits if self.keep_logits else None), rng

    def _hit_first(self, logits, temps, rng):
        """First token for prefix-cache hits: the stored last-token
        logits sampled at each request's own temperature — greedy hits
        reproduce the uncached stream bit-for-bit."""
        rng, sub = jax.random.split(rng)
        return self.sample(logits, temps, sub), rng

    def _insert_paged(self, cache, scales, new_cache, meta, tables):
        """Wave-admission insert: scatter each prefilled row's
        valid prefix through its slot's block table and set the
        device lengths. meta [2, M]: slot | row (pads repeat
        entry 0 — duplicate writes carry identical values)."""
        slot_idx, rowsel = meta[0], meta[1]
        tsub = jnp.take(
            tables, jnp.clip(slot_idx, 0, self.slots - 1), axis=0
        )  # [M, MB]
        nk = jnp.take(new_cache.k, rowsel, axis=1)  # [L, M, W, ...]
        nv = jnp.take(new_cache.v, rowsel, axis=1)
        lens = jnp.take(new_cache.length, rowsel, axis=0)  # [M]
        W = nk.shape[2]
        pos = jnp.broadcast_to(
            jnp.arange(W, dtype=jnp.int32)[None, :], (slot_idx.shape[0], W)
        )
        valid = pos < jnp.minimum(lens, self.kv.capacity)[:, None]
        cache, scales = self.layout.scatter(cache, scales, tsub, nk, nv, pos, valid)
        length = cache.length.at[slot_idx].set(lens, mode="drop")
        return cache._replace(length=length), scales

    def _seed(self, cache, scales, srcs, dsts, slot_idx, seed_lens):
        """Exact-hit/session seeding: block-copy partial tails
        (srcs -> dsts; pad lanes dst >= NB are dropped) and set
        device lengths (pad lanes slot >= slots are dropped)."""
        from .kvcache.paged import copy_blocks

        lay = self.layout
        k2, v2, sc2 = copy_blocks(cache.k, cache.v, srcs, dsts, scales=lay.sc(scales))
        length = cache.length.at[slot_idx].set(seed_lens, mode="drop")
        return (
            cache._replace(k=k2, v=v2, length=length),
            (sc2 if lay.int8 else scales),
        )

    def _restore(self, cache, scales, hk, hv, hs, dsts):
        """Session restore: host-fetched blocks [L, n, B, h, d] land
        back in the pool at freshly-allocated ids (byte-identical
        h2d), their rows flattened to the stored width."""
        from .kvcache.paged import stored_rows

        k2 = cache.k.at[:, dsts].set(stored_rows(hk), mode="drop")
        v2 = cache.v.at[:, dsts].set(stored_rows(hv), mode="drop")
        if self.layout.int8:
            scales = scales.at[:, :, dsts].set(hs, mode="drop")
        return cache._replace(k=k2, v=v2), scales

    def restore_op(self, width: int):
        """The pool's restore program for `width` blocks (a power of two),
        built at first use."""
        op = self._restore_ops.get(width)
        if op is None:
            op = self._restore_ops[width] = self._jit(
                f"llm.kv_restore{width}", self._restore,
                donate_argnums=((0, 1) if self.kv.int8 else (0,)),
            )
        return op


def _insert_many(slot_cache, new_cache, meta):
    """Copy new_cache row meta[1][i] into slot meta[0][i] for i < M.
    Padding entries duplicate entry 0 (idempotent rewrite)."""

    def body(c, xs):
        si, row = xs
        k = jax.lax.dynamic_update_slice(
            c.k,
            jax.lax.dynamic_slice_in_dim(new_cache.k, row, 1, axis=1),
            (0, si, 0, 0, 0),
        )
        v = jax.lax.dynamic_update_slice(
            c.v,
            jax.lax.dynamic_slice_in_dim(new_cache.v, row, 1, axis=1),
            (0, si, 0, 0, 0),
        )
        length = jax.lax.dynamic_update_slice(
            c.length,
            jax.lax.dynamic_slice_in_dim(new_cache.length, row, 1, axis=0),
            (si,),
        )
        return c._replace(k=k, v=v, length=length), None

    cache, _ = jax.lax.scan(body, slot_cache, (meta[0], meta[1]))
    return cache


def _admit_update(tail, active, temps, first, meta):
    """Scatter freshly-prefilled first tokens into the on-device
    chain tail and mark the slots active with their temperatures —
    admission never forces a host round trip. meta [3, M] int32:
    slot_idx | rows | temps-as-bits; padding entries repeat index 0
    (idempotent)."""
    slot_idx, rows = meta[0], meta[1]
    req_temps = jax.lax.bitcast_convert_type(meta[2], jnp.float32)
    tail = tail.at[slot_idx].set(first[rows])
    active = active.at[slot_idx].set(True)
    temps = temps.at[slot_idx].set(req_temps)
    return tail, active, temps
