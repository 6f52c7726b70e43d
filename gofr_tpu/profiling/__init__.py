"""Compilation & device-program observability: the compile observatory.

Everything below the serving engine's request lifecycle was dark before
this subsystem: an XLA compilation triggered by a new shape bucket stalls
live traffic invisibly, and nothing in the process could say which
program compiled, when, for how long, or what it costs to run. GoFr
answers the equivalent question for Go services by exposing pprof next
to its metrics server; this package is the TPU-native analogue — a
**compile registry** fed by ``instrument_jit`` wrappers around every
jitted program the framework owns, plus ``jax.monitoring`` listeners for
the backend's own phase timings.

Three public surfaces:

- :func:`instrument_jit` — wrap a function the way ``jax.jit`` would,
  but with per-signature compile accounting: each distinct abstract
  argument signature is lowered + compiled exactly once through JAX's
  AOT API (so the compile wall time is measured directly, not inferred
  from a first-call envelope), its ``cost_analysis()`` FLOPs/bytes are
  recorded when the backend provides them, and every later call is a
  trace-cache hit counted per program. The registry entry carries the
  program name, abstract arg shapes, compile/trace seconds, and cost.
- :class:`CompileRegistry` / :func:`default_registry` — the process-wide
  store behind ``GET /.well-known/debug/compiles`` and
  ``engine.debug_state()["compiles"]``. Engines remove their entries on
  ``close()`` (a dead engine must not keep listing its programs, the
  same bug class as a dead engine exporting occupancy gauges).
- metrics: ``app_jax_compile_seconds{program,model}`` histograms plus
  compile / trace-cache-hit counters, registered idempotently via
  :func:`register_compile_metrics`.

MFU / roofline math lives in :mod:`gofr_tpu.profiling.mfu`; on-demand
``jax.profiler`` capture in :mod:`gofr_tpu.profiling.capture`.
:class:`engine_span` puts the engine threads' own spans into a captured
trace (docs/advanced-guide/profiling.md, "The step timeline").

This module imports no jax at import time — a pure-web app can serve the
(empty) compile registry without initializing a backend.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable

__all__ = [
    "CompileRegistry",
    "InstrumentedJit",
    "default_registry",
    "engine_span",
    "name_os_thread",
    "instrument_jit",
    "install_monitoring_listener",
    "register_compile_metrics",
]

# Compile times span four orders of magnitude: a tiny admission scatter
# compiles in ~10 ms on CPU while a sharded Gemma prefill takes tens of
# seconds on a real TPU — the serving TPU_BUCKETS ladder (100us..5s)
# would flatten every interesting compile into +Inf.
COMPILE_BUCKETS = (
    0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
)

# Serializes app_jax_* registration across engines/runtimes (same
# rationale as llm._OBS_REG_LOCK: replicas register on parallel threads).
_REG_LOCK = threading.Lock()


def register_compile_metrics(metrics) -> None:
    """Idempotently register the compile-observatory instruments."""
    with _REG_LOCK:
        if not metrics.has("app_jax_compile_seconds"):
            metrics.new_histogram(
                "app_jax_compile_seconds",
                "XLA compile wall seconds per program signature",
                COMPILE_BUCKETS,
            )
        for name, desc in (
            ("app_jax_compiles_total",
             "XLA compilations per program (new abstract signature)"),
            ("app_jax_trace_cache_hits_total",
             "dispatches served by an already-compiled executable"),
        ):
            if not metrics.has(name):
                metrics.new_counter(name, desc)


class CompileRegistry:
    """Process-wide store of compiled device programs.

    Entries are keyed by (program, model, arg-shape signature) so a
    program that recompiles under shape-bucket churn shows one row per
    bucket. The registry never touches jax: callers hand it plain
    numbers, so it is constructible (and serveable) in a jax-free
    process.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: dict[tuple, dict] = {}
        self._events: dict[str, list] = {}  # jax.monitoring: name -> [n, total_s]
        self._warmups: dict[str, dict] = {}
        self._degraded: dict[tuple, str] = {}  # (program, model) -> reason

    # -- writers ----------------------------------------------------------
    def record_compile(
        self,
        *,
        program: str,
        model: str = "",
        arg_shapes: tuple[str, ...] = (),
        trace_s: float = 0.0,
        compile_s: float = 0.0,
        flops: float | None = None,
        bytes_accessed: float | None = None,
        backend: str = "",
        measured: str = "aot",
    ) -> dict:
        key = (program, model, arg_shapes)
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                e = {
                    "program": program,
                    "model": model,
                    "arg_shapes": list(arg_shapes),
                    "compiles": 0,
                    "hits": 0,
                    "trace_s": 0.0,
                    "compile_s": 0.0,
                    "compile_s_total": 0.0,
                    "flops": None,
                    "bytes_accessed": None,
                    "backend": backend,
                    # "aot": lower().compile() timed directly;
                    # "first_call": first-dispatch envelope (compile+execute)
                    "measured": measured,
                    "first_compiled_at": time.time(),
                }
                self._entries[key] = e
            e["compiles"] += 1
            e["trace_s"] = round(trace_s, 6)
            e["compile_s"] = round(compile_s, 6)
            e["compile_s_total"] = round(e["compile_s_total"] + compile_s, 6)
            if flops is not None:
                e["flops"] = flops
            if bytes_accessed is not None:
                e["bytes_accessed"] = bytes_accessed
            return e

    def note_hit(self, program: str, model: str = "", arg_shapes: tuple[str, ...] = ()) -> None:
        key = (program, model, arg_shapes)
        with self._lock:
            e = self._entries.get(key)
            if e is not None:
                e["hits"] += 1

    def note_degraded(self, program: str, model: str, reason: str) -> None:
        """An InstrumentedJit left AOT dispatch for plain jit (its compiled
        executable rejected intact inputs). Listed in snapshot() so the
        fallback is never silent."""
        with self._lock:
            self._degraded.setdefault((program, model), reason)

    def note_backend_event(self, event: str, duration_s: float) -> None:
        """Aggregate a jax.monitoring duration event (bounded cardinality:
        jax emits a handful of /jax/core/compile/* phase names)."""
        with self._lock:
            agg = self._events.setdefault(event, [0, 0.0])
            agg[0] += 1
            agg[1] += duration_s

    def record_warmup(self, model: str, seconds: float, programs: int | None = None) -> None:
        """One engine warmup: total compile+execute wall time for the full
        program set (LLMEngine._warm overlaps compiles, so this is wall
        time, not the per-program sum)."""
        with self._lock:
            self._warmups[model] = {
                "seconds": round(seconds, 3),
                "programs": programs,
                "at": time.time(),
            }

    def remove_model(self, model: str) -> int:
        """Engine teardown: drop every entry (and warmup record) the label
        owns so a closed engine stops being listed. Returns entries removed."""
        with self._lock:
            gone = [k for k in self._entries if k[1] == model]
            for k in gone:
                del self._entries[k]
            for k in [k for k in self._degraded if k[1] == model]:
                del self._degraded[k]
            self._warmups.pop(model, None)
            return len(gone)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._events.clear()
            self._warmups.clear()
            self._degraded.clear()

    # -- readers ----------------------------------------------------------
    def snapshot(self, model: str | None = None) -> dict:
        """JSON-able view for /.well-known/debug/compiles. Bounded by the
        process's executable set (the engine's whole point is a bounded
        program count), so safe to serve under load."""
        now = time.time()
        with self._lock:
            entries = [
                dict(e, age_s=round(now - e["first_compiled_at"], 1))
                for k, e in self._entries.items()
                if model is None or k[1] == model
            ]
            events = {k: {"count": v[0], "total_s": round(v[1], 4)} for k, v in self._events.items()}
            warmups = {
                m: dict(w) for m, w in self._warmups.items()
                if model is None or m == model
            }
            degraded = [
                {"program": k[0], "model": k[1], "reason": why}
                for k, why in sorted(self._degraded.items())
                if model is None or k[1] == model
            ]
        entries.sort(key=lambda e: (e["model"], e["program"], e["arg_shapes"]))
        for e in entries:
            e.pop("first_compiled_at", None)
        return {
            "programs": entries,
            "totals": {
                "programs": len(entries),
                "compiles": sum(e["compiles"] for e in entries),
                "cache_hits": sum(e["hits"] for e in entries),
                "compile_s_total": round(sum(e["compile_s_total"] for e in entries), 3),
            },
            "backend_events": events,
            "warmup": warmups,
            # programs whose AOT executable rejected its inputs and that
            # dispatch through plain jit since (InstrumentedJit)
            "degraded": degraded,
        }


_default_registry = CompileRegistry()


def default_registry() -> CompileRegistry:
    """The process-wide registry every framework jit wrapper records into
    (one process = one XLA client = one program population; mirrors the
    process-wide persistent compilation cache)."""
    return _default_registry


# -- jax.monitoring bridge -------------------------------------------------

_monitoring_installed = False


def install_monitoring_listener() -> bool:
    """Register a jax.monitoring duration listener that aggregates the
    backend's own compile-phase timings (jaxpr trace, MLIR lowering,
    backend compile) into the DEFAULT registry — the events carry no
    program identity, so they always belong to the process-global view,
    never a wrapper-local registry. Idempotent; returns False where the
    API is unavailable. The listener survives engine teardown
    deliberately: it carries no per-engine labels to leak."""
    global _monitoring_installed
    with _REG_LOCK:  # replicas build engines on parallel threads
        if _monitoring_installed:
            return True
        try:
            import jax.monitoring as jm

            def _on_duration(event: str, duration: float, **_kw) -> None:
                if "compile" in event or "trace" in event:
                    default_registry().note_backend_event(event, duration)

            jm.register_event_duration_secs_listener(_on_duration)
        except Exception:  # noqa: BLE001 — monitoring is additive only
            return False
        _monitoring_installed = True
        return True


# -- host spans on the profiler's clock ------------------------------------


class engine_span:
    """One ``with`` for a stretch of an engine thread: a
    ``jax.profiler.TraceAnnotation`` (a host event in a captured trace, on
    the same clock as the device's lines; about a microsecond while no
    capture runs) and, where a heartbeat is given, its beat for the step
    watchdog, named ``name`` or ``name:kind``. It writes nowhere else:
    request spans with exporters are :mod:`gofr_tpu.tracing`'s.
    ``set()`` adds attributes learned inside the span (a program's ``seq``).
    A capture holds the spans that begin inside it: one that is open when it
    starts (an idle scheduler's sched.admit, waiting for a request) is not
    in it, whatever ``set()`` gives it later."""

    __slots__ = ("_ann", "_hb", "_beat")
    _annotation = None  # jax.profiler.TraceAnnotation, imported at the first span

    def __init__(self, name: str, hb=None, **attrs):
        if engine_span._annotation is None:
            from jax.profiler import TraceAnnotation

            engine_span._annotation = TraceAnnotation
        self._ann = engine_span._annotation(name, **attrs)
        self._hb = hb
        self._beat = f"{name}:{attrs['kind']}" if "kind" in attrs else name

    def set(self, **attrs) -> None:
        self._ann.set_metadata(**attrs)

    def __enter__(self):
        if self._hb is not None:
            self._hb.begin(self._beat)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        if self._hb is not None:
            self._hb.end()
        return False


def name_os_thread() -> None:
    """Give the calling thread its Python name at the OS too (Linux keeps
    15 bytes: ``llm-engine-sche``, ``llm-engine-coll``). The profiler names
    a host line after the OS thread, read at the thread's first event, so
    an engine thread calls this before its first span; Python sets the OS
    name itself only from 3.14 on."""
    try:
        import ctypes

        ctypes.CDLL(None).prctl(15, threading.current_thread().name.encode()[:15], 0, 0, 0)  # PR_SET_NAME
    except Exception:  # noqa: BLE001 — a platform without prctl keeps the process's name
        pass


# -- the jit wrapper -------------------------------------------------------


def _describe_args(args: tuple) -> tuple[str, ...]:
    """Human-readable per-argument shapes for registry rows: arrays as
    dtype[shape], pytrees collapsed to their leaf count (a 2B-param tree
    listed leaf-by-leaf would drown the row)."""
    out: list[str] = []
    for a in args:
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is not None and dtype is not None:
            out.append(f"{dtype}[{','.join(str(d) for d in shape)}]")
        elif isinstance(a, (dict, list, tuple)) or hasattr(a, "_fields"):
            import jax

            out.append(f"pytree[{len(jax.tree.leaves(a))} leaves]")
        else:
            out.append(type(a).__name__)
    return tuple(out)


class InstrumentedJit:
    """``jax.jit`` with compile accounting and an explicit executable cache.

    Dispatch path: the abstract signature of the arguments (leaf shapes,
    dtypes, weak types + treedef) keys a dict of AOT-compiled
    executables. A hit calls the executable directly (same cost class as
    jit's own cache lookup); a miss runs ``lower()`` / ``compile()``
    with the two phases timed separately, records the entry (with
    ``cost_analysis()`` FLOPs/bytes where the backend provides them),
    and installs the executable. Donation and input shardings flow
    through lowering unchanged, so engine semantics are identical.

    If an AOT call ever rejects intact inputs (a committed-device or
    layout drift the signature missed), the wrapper notes itself as
    degraded in the registry (snapshot()["degraded"], with the error) and
    permanently falls back to plain jit dispatch, where compiles are
    still counted per signature but timed as first-call envelopes. A
    compile error is never caught: plain jit would fail identically.
    """

    def __init__(
        self,
        program: str,
        fn: Callable,
        *,
        model: str = "",
        registry: CompileRegistry | None = None,
        metrics=None,
        donate_argnums: tuple[int, ...] = (),
        static_argnums: tuple[int, ...] = (),
        **jit_kw,
    ):
        import jax

        self.program = program
        self.model = model
        self.registry = registry if registry is not None else default_registry()
        self.metrics = metrics
        self._static = tuple(static_argnums)
        self._jitted = jax.jit(
            fn, donate_argnums=donate_argnums,
            static_argnums=static_argnums or None, **jit_kw,
        )
        self._lock = threading.Lock()
        self._compiled: dict[Any, Any] = {}
        self._shapes: dict[Any, tuple[str, ...]] = {}
        self._seen: set = set()
        self._aot = True
        self._arg0_memo: tuple | None = None
        self._memo_miss_streak = 0
        install_monitoring_listener()

    # jax.jit API passthroughs used by callers/tests
    def lower(self, *args, **kw):
        return self._jitted.lower(*args, **kw)

    def _dyn_args(self, args: tuple) -> tuple:
        """AOT Compiled.__call__ takes only the traced arguments — static
        values were baked in at lowering and must be dropped."""
        if not self._static:
            return args
        return tuple(a for i, a in enumerate(args) if i not in self._static)

    def _leaf_sigs(self, tree) -> tuple:
        import jax

        sig = []
        for x in jax.tree.leaves(tree):
            shape = getattr(x, "shape", None)
            if shape is not None:
                sig.append((
                    tuple(shape), str(getattr(x, "dtype", "")),
                    bool(getattr(x, "weak_type", False)),
                ))
            elif isinstance(x, (bool, int, float, complex)):
                # jit traces Python scalars as weak-typed values: ONE
                # executable per dtype, never one per value — keying by
                # repr would recompile on every distinct scalar
                sig.append(("py", type(x).__name__))
            else:
                sig.append(("pyval", repr(x)))
        return tuple(sig)

    def _signature(self, args: tuple):
        import jax

        # Identity memo for the leading argument: every framework op takes
        # the (immutable, engine-retained) params pytree first, and its
        # per-call structure+leaf walk is the only part of the signature
        # whose cost scales with model size. Same object -> same tree and
        # shapes; the memo holds a strong ref so the identity can never
        # be recycled. The varying tail (tokens, caches, rng) stays small.
        # static args are jit-compile-time CONSTANTS: key them by value,
        # or two calls differing only in a static argument would collide
        # on one executable and misread the mismatch as layout drift
        static = tuple(
            (i, repr(args[i])) for i in self._static if i < len(args)
        )
        if args and isinstance(args[0], (dict, list, tuple)):
            memo = self._arg0_memo
            if memo is not None and memo[0] is args[0]:
                head = memo[1]
                self._memo_miss_streak = 0
            else:
                head = (jax.tree.structure(args[0]), self._leaf_sigs(args[0]))
                # The memo holds a strong ref to arg0. Callers that REBIND
                # it every call (train steps: params = apply_updates(...))
                # would have the memo pin a whole dead parameter tree in
                # device memory between steps — after two consecutive
                # identity misses, stop memoizing for this wrapper.
                self._memo_miss_streak += 1
                self._arg0_memo = (
                    (args[0], head) if self._memo_miss_streak < 2 else None
                )
            tail = args[1:]
            return (static, head, jax.tree.structure(tail), self._leaf_sigs(tail))
        return (static, None, jax.tree.structure(args), self._leaf_sigs(args))

    def __call__(self, *args):
        sig = self._signature(args)
        exe = self._compiled.get(sig)
        if exe is not None:
            self.registry.note_hit(self.program, self.model, self._shapes[sig])
            if self.metrics is not None:
                self.metrics.increment_counter(
                    "app_jax_trace_cache_hits_total",
                    program=self.program, model=self.model,
                )
            try:
                with engine_span(self.program):
                    return exe(*self._dyn_args(args))
            except Exception as e:
                # Committed-device/layout drift the signature missed: fall
                # back to jit dispatch for good rather than failing serving.
                # But ONLY when the inputs are intact — a failure after the
                # executable consumed a donated buffer (engine chunk/insert
                # ops donate their caches) must propagate, or the retry
                # dies on 'array deleted' and masks the real error.
                import jax

                if any(
                    getattr(x, "is_deleted", lambda: False)()
                    for x in jax.tree.leaves(args)
                ):
                    raise
                with self._lock:
                    self._aot = False
                    self._compiled.clear()  # _seen still routes hits to jit
                self.registry.note_degraded(
                    self.program, self.model, f"{type(e).__name__}: {e}"[:300]
                )
                return self._jitted(*args)
        if sig in self._seen:  # degraded mode hit
            self.registry.note_hit(self.program, self.model, self._shapes[sig])
            if self.metrics is not None:
                self.metrics.increment_counter(
                    "app_jax_trace_cache_hits_total",
                    program=self.program, model=self.model,
                )
            return self._jitted(*args)
        return self._compile_and_call(sig, args)

    def _compile_and_call(self, sig, args: tuple):
        """Miss path. Not serialized across signatures on purpose: the
        engine's warmup pool compiles different widths concurrently and
        XLA releases the GIL while compiling."""
        shapes = _describe_args(args)
        with self._lock:
            self._shapes.setdefault(sig, shapes)
        if self._aot:
            # tracing and compile errors propagate — plain jit would raise
            # identically, and a bad input batch must not degrade the
            # wrapper for good
            t0 = time.perf_counter()
            lowered = self._jitted.lower(*args)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            # install + record BEFORE the first execution: a runtime
            # failure there must neither hide the (expensive) compile from
            # the registry nor discard the executable — the retry then
            # takes the hit path instead of re-paying lower()+compile()
            with self._lock:
                self._compiled[sig] = compiled
                self._seen.add(sig)
            self._record(shapes, {
                "trace_s": t1 - t0,
                "compile_s": time.perf_counter() - t1,
                "measured": "aot",
                **_cost_of(compiled),
            })
            return compiled(*self._dyn_args(args))
        t0 = time.perf_counter()
        out = self._jitted(*args)
        with self._lock:
            self._seen.add(sig)
        self._record(shapes, {
            "compile_s": time.perf_counter() - t0,
            "measured": "first_call",
        })
        return out

    def _record(self, shapes: tuple[str, ...], entry_kw: dict) -> None:
        import jax

        self.registry.record_compile(
            program=self.program, model=self.model, arg_shapes=shapes,
            backend=jax.default_backend(), **entry_kw,
        )
        if self.metrics is not None:
            register_compile_metrics(self.metrics)
            self.metrics.record_histogram(
                "app_jax_compile_seconds",
                entry_kw.get("compile_s", 0.0) + entry_kw.get("trace_s", 0.0),
                program=self.program, model=self.model,
            )
            self.metrics.increment_counter(
                "app_jax_compiles_total", program=self.program, model=self.model,
            )


def _cost_of(compiled) -> dict:
    """FLOPs / bytes-accessed from Compiled.cost_analysis() where the
    backend provides it (list-of-dicts on CPU/TPU; None/raises on some
    backends — the registry entry simply omits the numbers then)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — optional per backend
        return {}
    if ca is None:
        return {}
    if isinstance(ca, dict):
        ca = [ca]
    try:
        flops = sum(float(c.get("flops", 0.0)) for c in ca)
        bytes_accessed = sum(float(c.get("bytes accessed", 0.0)) for c in ca)
    except Exception:  # noqa: BLE001
        return {}
    out: dict[str, float] = {}
    if flops:
        out["flops"] = flops
    if bytes_accessed:
        out["bytes_accessed"] = bytes_accessed
    return out


def instrument_jit(
    program: str,
    fn: Callable,
    *,
    model: str = "",
    registry: CompileRegistry | None = None,
    metrics=None,
    donate_argnums: tuple[int, ...] = (),
    static_argnums: tuple[int, ...] = (),
    **jit_kw,
) -> InstrumentedJit:
    """Drop-in ``jax.jit`` replacement for framework-owned programs: same
    call surface, plus compile registry + app_jax_* metrics accounting.
    See :class:`InstrumentedJit`."""
    return InstrumentedJit(
        program, fn, model=model, registry=registry, metrics=metrics,
        donate_argnums=donate_argnums, static_argnums=static_argnums, **jit_kw,
    )
