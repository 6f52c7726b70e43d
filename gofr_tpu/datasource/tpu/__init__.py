"""The TPU datasource: model registry + executable cache + dynamic batching.

This is the build's `ctx.TPU()` (BASELINE.json north_star) — the TPU as a
datasource with the same shape the reference gives SQL/Redis (SURVEY.md
§2.4): constructor wired by the Container, per-call query-log + latency
histogram (analogue of reference db.go:47-58), health check with device
stats (analogue of sql/health.go:27-65), test seam via MockTPU.

Architecture:
- **Model registry.** `register_model(name, apply_fn, params)` device-puts
  params (optionally sharded over a mesh), jits apply_fn, and warms the
  executable cache per batch bucket so serving never eats a compile.
- **Dynamic batcher.** One per model. Handlers await `infer_async`; a
  collector thread coalesces up to TPU_BATCH_MAX_SIZE requests or
  TPU_BATCH_MAX_DELAY_MS (env knobs, precedent: reference KAFKA_BATCH_*
  container.go:107-109), pads the batch to a power-of-two bucket (one
  compiled executable per bucket), runs ONE device execution, and scatters
  per-request outputs back to the awaiting futures. This replaces the
  reference's goroutine-per-request-does-all hot loop (handler.go:58-63)
  with request-awaits-batch (SURVEY.md §7.5).
- **Cancellation.** A request whose future was cancelled (client timeout)
  is dropped at scatter time; the batch itself always completes — detaching
  one request never kills the batch (SURVEY.md §7 hard part 2).
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable

from .. import STATUS_DOWN, STATUS_UP, health

__all__ = ["TPURuntime", "Batcher", "MockTPU"]


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@dataclass
class _Pending:
    args: tuple  # single-example pytree args (no batch dim)
    future: Any  # concurrent.futures.Future
    enqueued: float = field(default_factory=time.perf_counter)


class Batcher:
    """Per-model dynamic batching queue, pipelined.

    Requests are single examples (leaves WITHOUT the batch axis); the
    collector stacks them, pads the batch dim to the next power of two
    (static shapes -> one XLA executable per bucket), and dispatches ONE
    device execution. Dispatch is asynchronous (XLA's launch model): the
    collector immediately returns to assembling the next wave while a pool
    of completion workers blocks on device->host readback and scatters rows
    to the per-request futures. Waves therefore overlap — device compute,
    host readback, and batch assembly pipeline instead of serializing,
    which is what sustains QPS when the host<->device link has latency.
    """

    def __init__(
        self,
        name: str,
        run_batch: Callable[[tuple, int], Any],  # (stacked_args, true_n) -> stacked_out (device, unfetched)
        *,
        max_batch: int = 64,
        max_delay_ms: float = 2.0,
        max_inflight: int = 8,
        metrics=None,
        logger=None,
    ):
        import concurrent.futures

        self.name = name
        self.run_batch = run_batch
        self.max_batch = max_batch
        self.max_delay = max_delay_ms / 1000.0
        self.metrics = metrics
        self.logger = logger
        self.q: queue.Queue[_Pending | None] = queue.Queue()
        self._inflight = threading.Semaphore(max_inflight)
        self._completion = concurrent.futures.ThreadPoolExecutor(
            max_workers=max_inflight, thread_name_prefix=f"tpu-complete-{name}"
        )
        self._thread = threading.Thread(
            target=self._loop, name=f"tpu-batcher-{name}", daemon=True
        )
        self._closed = False
        self._thread.start()

    def submit(self, args: tuple) -> Any:
        import concurrent.futures

        if self._closed:
            raise RuntimeError(f"batcher {self.name} is closed")
        fut = concurrent.futures.Future()
        self.q.put(_Pending(args=args, future=fut))
        return fut

    def _collect(self) -> list[_Pending]:
        """Block for the first request, then linger up to max_delay (or until
        max_batch) for co-travellers — the latency/throughput trade knob."""
        first = self.q.get()
        if first is None:
            return []
        batch = [first]
        deadline = time.perf_counter() + self.max_delay
        while len(batch) < self.max_batch:
            remaining = deadline - time.perf_counter()
            if remaining <= 0:
                break
            try:
                item = self.q.get(timeout=remaining)
            except queue.Empty:
                break
            if item is None:
                self._closed = True
                break
            batch.append(item)
        return batch

    def _loop(self) -> None:
        while True:
            batch = self._collect()
            if not batch:
                break
            self._dispatch(batch)
            if self._closed:
                break
        # Drain anything that raced past close(): a submit() that read
        # _closed as False but enqueued behind the shutdown sentinel must
        # get an error, not hang its caller forever.
        while True:
            try:
                item = self.q.get_nowait()
            except queue.Empty:
                break
            if item is not None:
                self._resolve(item, error=RuntimeError(f"batcher {self.name} is closed"))
        self._completion.shutdown(wait=True)

    def _dispatch(self, batch: list[_Pending]) -> None:
        """Collector side: stack, launch on device, hand off to completion.
        Bounded by max_inflight so waves can't pile up unboundedly."""
        import jax
        import numpy as np

        n = len(batch)
        t0 = time.perf_counter()
        if self.metrics is not None:
            self.metrics.record_histogram("app_tpu_batch_size", float(n), model=self.name)
            for p in batch:
                self.metrics.record_histogram(
                    "app_tpu_queue_wait", t0 - p.enqueued, model=self.name
                )
        self._inflight.acquire()
        try:
            bucket = _next_pow2(n)
            examples = [p.args for p in batch]
            # pad with copies of the last example up to the bucket size
            examples += [batch[-1].args] * (bucket - n)
            stacked = jax.tree.map(lambda *xs: np.stack(xs), *examples)
            out = self.run_batch(stacked, n)  # async dispatch, not fetched
        except Exception as e:  # noqa: BLE001 — launch failure fans out now
            self._inflight.release()
            for p in batch:
                self._resolve(p, error=e)
            return
        self._completion.submit(self._complete, batch, out, t0)

    @staticmethod
    def _resolve(pending: _Pending, result=None, error: Exception | None = None) -> None:
        """Set a future's outcome, tolerating concurrent client cancellation
        (cancelled() -> set_result races with the client's cancel; the
        InvalidStateError must not leak and poison the rest of the batch)."""
        try:
            if error is not None:
                pending.future.set_exception(error)
            else:
                pending.future.set_result(result)
        except Exception:  # noqa: BLE001 — already cancelled/resolved: detach
            pass

    def _complete(self, batch: list[_Pending], out: Any, t0: float) -> None:
        """Completion side: block on device->host readback, scatter rows."""
        import jax
        import numpy as np

        try:
            out = jax.tree.map(np.asarray, out)  # one readback per wave
            for i, p in enumerate(batch):
                self._resolve(p, result=jax.tree.map(lambda x: x[i], out))
        except Exception as e:  # noqa: BLE001 — batch failure fans out to callers
            for p in batch:
                self._resolve(p, error=e)
        finally:
            self._inflight.release()
        if self.metrics is not None:
            self.metrics.record_histogram(
                "app_tpu_stats", time.perf_counter() - t0, model=self.name, op="batch"
            )
        if self.logger is not None:
            self.logger.debug(
                f"TPU batch model={self.name} n={len(batch)} took "
                f"{(time.perf_counter() - t0) * 1e3:.2f}ms"
            )

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self.q.put(None)
            self._thread.join(timeout=10)


class _Model:
    def __init__(self, name: str, jitted, params, batcher: Batcher | None, meta: dict):
        self.name = name
        self.jitted = jitted
        self.params = params
        self.batcher = batcher
        self.meta = meta


class TPURuntime:
    """`ctx.tpu()` — constructed lazily by the Container (container seam:
    gofr_tpu/container/__init__.py Container.tpu)."""

    def __init__(self, config=None, logger=None, metrics=None, tracer=None):
        import jax

        self.logger = logger
        self.metrics = metrics
        self.tracer = tracer  # engine request-lifecycle spans (register_llm)
        self.config = config
        get = (lambda k, d: config.get_or_default(k, d)) if config is not None else (lambda k, d: d)
        # TPU_PLATFORM=cpu|tpu pins the jax backend before first device touch
        # (the dev/CI story: run the same app on the CPU backend). Normally
        # done by Container.create; repeated for standalone runtimes. A
        # platform that did not take raises.
        from ...utils import pin_jax_platform

        pin_jax_platform(get("TPU_PLATFORM", ""))
        self.default_max_batch = int(get("TPU_BATCH_MAX_SIZE", "64"))
        self.default_max_delay_ms = float(get("TPU_BATCH_MAX_DELAY_MS", "2"))
        self.default_max_inflight = int(get("TPU_BATCH_MAX_INFLIGHT", "8"))
        # LLM engine kv-cache defaults (gofr_tpu.kvcache), overridable per
        # register_llm call: prefix-cache byte budget in MB (0 disables).
        # Same env-knob precedent as the batcher's KAFKA_BATCH_* lineage.
        self.default_llm_prefix_cache_mb = float(
            get("TPU_LLM_PREFIX_CACHE_MB", "0")
        )
        # token-budget step scheduler knobs (gofr_tpu.llm; "" = engine
        # defaults, which also honor the same names as process env vars)
        self.default_llm_step_budget = get("TPU_LLM_STEP_TOKEN_BUDGET", "")
        self.default_llm_prefill_chunk = get("TPU_LLM_PREFILL_CHUNK", "")
        # speculative decoding knobs (gofr_tpu.spec; "" = engine
        # defaults, which read the same names as process env vars) —
        # docs/advanced-guide/speculative-decoding.md
        self.default_llm_spec = get("TPU_LLM_SPEC", "")
        self.default_llm_spec_draft = get("TPU_LLM_SPEC_DRAFT", "")
        # paged KV pool knobs (gofr_tpu.kvcache.paged; "" = engine
        # defaults, which read the same names as process env vars) —
        # docs/advanced-guide/kv-cache.md
        self.default_llm_kv_paged = get("TPU_LLM_KV_PAGED", "")
        self.default_llm_kv_block = get("TPU_LLM_KV_BLOCK", "")
        self.default_llm_kv_int8 = get("TPU_LLM_KV_INT8", "")
        self.default_llm_session_mb = get("TPU_LLM_SESSION_MB", "")
        self.default_llm_host_cache_mb = get("TPU_LLM_HOST_CACHE_MB", "")
        # resilience knobs (gofr_tpu.resilience): step-watchdog threshold
        # seconds ("" = engine default, which reads the same env var; 0
        # disables) and the numerical watchdog gate ("" = engine default,
        # on) — docs/advanced-guide/resilience.md
        self.default_llm_step_watchdog = get("TPU_LLM_STEP_WATCHDOG_S", "")
        self.default_llm_numeric_check = get("TPU_LLM_NUMERIC_CHECK", "")
        # grammar-constrained decoding knobs (gofr_tpu.structured; "" =
        # engine defaults, which read the same names as process env
        # vars) — docs/advanced-guide/structured-decoding.md
        self.default_llm_constrained = get("TPU_LLM_CONSTRAINED", "")
        self.default_llm_constrained_grammars = get(
            "TPU_LLM_CONSTRAINED_GRAMMARS", ""
        )
        # multi-tenant LoRA adapter serving knobs (gofr_tpu.lora; "" =
        # engine defaults, which read the same names as process env
        # vars) — docs/advanced-guide/multi-tenancy.md
        self.default_llm_lora_slots = get("TPU_LLM_LORA_SLOTS", "")
        self.default_llm_lora_rank = get("TPU_LLM_LORA_RANK_MAX", "")
        # sharded / disaggregated serving knobs (docs/advanced-guide/
        # sharded-serving.md): TPU_LLM_TP runs each replica
        # tensor-parallel over a submesh of that many chips;
        # TPU_LLM_DISAGG splits the fleet into prefill/decode role pools
        # with device-to-device KV handoff
        # incident flight recorder knobs (gofr_tpu.flightrec; "" =
        # engine defaults, which read the same names as process env
        # vars) — docs/advanced-guide/incident-debugging.md
        self.default_llm_flight_records = get("TPU_LLM_FLIGHT_RECORDS", "")
        self.default_llm_flight_redact = get("TPU_LLM_FLIGHT_REDACT", "")
        self.default_llm_blackbox_dir = get("GOFR_BLACKBOX_DIR", "")
        self.default_llm_blackbox_interval = get(
            "GOFR_BLACKBOX_INTERVAL_S", ""
        )
        self.default_llm_anomaly = get("TPU_LLM_ANOMALY", "")
        self.default_llm_wide_sample = get("TPU_LLM_WIDE_EVENT_SAMPLE", "")
        self.default_llm_tp = get("TPU_LLM_TP", "")
        self.default_llm_disagg = get("TPU_LLM_DISAGG", "")
        self.default_llm_disagg_prefill = get(
            "TPU_LLM_DISAGG_PREFILL_REPLICAS", ""
        )
        self.default_llm_handoff_timeout = get(
            "TPU_LLM_KV_HANDOFF_TIMEOUT_S", ""
        )
        self._models: dict[str, _Model] = {}
        self._lock = threading.Lock()
        if metrics is not None:
            # Normally done by the Container; repeated here so a standalone
            # runtime still records its stats. Silent existence guard: the
            # already-registered WARN is parity behavior for USER double
            # registration and must not fire on this intentional path.
            from ...metrics import TPU_BUCKETS

            for name, desc, buckets in (
                ("app_tpu_stats", "tpu execute time s", TPU_BUCKETS),
                ("app_tpu_batch_size", "dynamic batch sizes",
                 (1, 2, 4, 8, 16, 32, 64, 128, 256)),
                ("app_tpu_queue_wait", "batch queue wait s", TPU_BUCKETS),
            ):
                if not metrics.has(name):
                    metrics.new_histogram(name, desc, buckets)
            from ...profiling import register_compile_metrics

            register_compile_metrics(metrics)  # app_jax_* observatory
        self.devices = jax.devices()
        self.platform = self.devices[0].platform if self.devices else "none"
        # periodic HBM gauges (app_tpu_hbm_*); parks itself off-TPU.
        # TPU_TELEMETRY_INTERVAL_S=0 disables the sampler thread.
        self.telemetry = None
        if metrics is not None:
            from .telemetry import TPUTelemetry

            self.telemetry = TPUTelemetry(
                metrics, self.devices,
                interval_s=float(get("TPU_TELEMETRY_INTERVAL_S", "10")),
                logger=logger,
            )
        if logger is not None:
            logger.info(
                f"TPU runtime: {len(self.devices)} x {self.devices[0].device_kind}"
                if self.devices
                else "TPU runtime: no devices"
            )

    # -- registry ---------------------------------------------------------
    def register_model(
        self,
        name: str,
        apply_fn: Callable,  # (params, *batched_args) -> batched_out
        params: Any,
        *,
        example_args: tuple | None = None,  # single example (no batch dim)
        max_batch: int | None = None,
        max_delay_ms: float | None = None,
        max_inflight: int | None = None,
        mesh=None,
        param_specs: Any = None,
        donate_params: bool = False,
        warmup_buckets: tuple[int, ...] | None = None,
    ) -> None:
        """Move params to device (sharded if mesh+specs given), jit apply_fn,
        optionally pre-compile batch buckets, and start the batcher."""
        import jax

        if mesh is not None and param_specs is not None:
            from ...parallel.sharding import shard_params

            params = shard_params(params, mesh, param_specs)
        else:
            params = jax.device_put(params)

        # compile observatory: each batch bucket the batcher forms is a
        # distinct signature — the registry shows one row per bucket with
        # its compile time, so a mid-traffic compile stall is attributable
        from ...profiling import instrument_jit

        jitted = instrument_jit(
            f"model:{name}", apply_fn, model=name, metrics=self.metrics
        )
        max_batch = max_batch or self.default_max_batch
        max_delay_ms = (
            max_delay_ms if max_delay_ms is not None else self.default_max_delay_ms
        )

        def run_batch(stacked_args, true_n: int):
            # Launch only — XLA dispatch is async; the batcher's completion
            # workers block on readback so waves pipeline.
            return jitted(params, *stacked_args)

        batcher = Batcher(
            name,
            run_batch,
            max_batch=max_batch,
            max_delay_ms=max_delay_ms,
            max_inflight=max_inflight or self.default_max_inflight,
            metrics=self.metrics,
            logger=self.logger,
        )
        model = _Model(
            name,
            jitted,
            params,
            batcher,
            meta={
                "max_batch": max_batch,
                "max_delay_ms": max_delay_ms,
                "params_bytes": sum(
                    x.size * x.dtype.itemsize for x in jax.tree.leaves(params)
                ),
            },
        )
        with self._lock:
            if name in self._models:
                self._models[name].batcher.close()
            self._models[name] = model

        if example_args is not None:
            import numpy as np

            if warmup_buckets is None:
                # All power-of-two buckets the batcher can form, so serving
                # never eats an XLA compile mid-traffic.
                warmup_buckets = tuple(
                    1 << i for i in range((max_batch).bit_length())
                    if (1 << i) <= max_batch
                )
            for bucket in warmup_buckets:
                stacked = jax.tree.map(
                    lambda x: np.stack([np.asarray(x)] * bucket), example_args
                )
                jax.block_until_ready(jitted(params, *stacked))
            if self.logger is not None:
                self.logger.info(
                    f"model '{name}' registered & warmed (buckets {warmup_buckets})"
                )

    def model(self, name: str) -> _Model:
        try:
            return self._models[name]
        except KeyError:
            raise KeyError(
                f"model '{name}' not registered; known: {list(self._models)}"
            ) from None

    # -- inference --------------------------------------------------------
    def infer(self, name: str, *batched_args) -> Any:
        """Direct batched call (caller formed the batch). Sync, blocking."""
        m = self.model(name)
        t0 = time.perf_counter()
        import jax

        out = jax.block_until_ready(m.jitted(m.params, *batched_args))
        if self.metrics is not None:
            self.metrics.record_histogram(
                "app_tpu_stats", time.perf_counter() - t0, model=name, op="execute"
            )
        return out

    async def infer_async(self, name: str, *example_args) -> Any:
        """Single-example call through the dynamic batcher. Awaitable."""
        import asyncio

        m = self.model(name)
        fut = m.batcher.submit(example_args)
        return await asyncio.wrap_future(fut)

    def infer_one(self, name: str, *example_args, timeout: float | None = None) -> Any:
        """Single-example call through the batcher, blocking (CLI/cron use)."""
        m = self.model(name)
        return m.batcher.submit(example_args).result(timeout=timeout)

    # -- LLM engines (continuous batching; gofr_tpu.llm) -------------------
    def register_llm(self, name: str, cfg, params, **engine_kw):
        """Register a continuous-batching text-generation engine alongside
        the plain models; reachable as ctx.tpu().llm(name). Pass
        `replicas=N` (or `devices=[...]` / `meshes=[(mesh, specs), ...]`)
        for data-parallel replicated serving — N independent engines with
        a per-request router behind the same handle (SURVEY §2.8 row 1).
        TPU_LLM_TP=K runs each replica tensor-parallel over its own
        K-chip submesh (collective-compute overlap on the decode path via
        TPU_LLM_TP_OVERLAP, on by default), and TPU_LLM_DISAGG=1 splits
        the fleet into prefill-role and decode-role pools with
        device-to-device KV handoff
        (TPU_LLM_DISAGG_PREFILL_REPLICAS / TPU_LLM_KV_HANDOFF_TIMEOUT_S;
        docs/advanced-guide/sharded-serving.md).
        KV layout/residency policy comes from gofr_tpu.kvcache: the
        block-paged pool with radix prefix sharing by default
        (TPU_LLM_KV_PAGED/TPU_LLM_KV_BLOCK/TPU_LLM_KV_INT8), the
        X-GoFr-Session conversation tier with host offload
        (TPU_LLM_SESSION_MB/TPU_LLM_HOST_CACHE_MB), and `prefix_cache_mb`
        defaulting to the TPU_LLM_PREFIX_CACHE_MB config knob
        (docs/advanced-guide/kv-cache.md); the token-budget step
        scheduler honors TPU_LLM_STEP_TOKEN_BUDGET / TPU_LLM_PREFILL_CHUNK
        (docs/advanced-guide/scheduling.md). Speculative decoding — a
        host-side n-gram drafter with fused on-device verification,
        greedy-token-identical and distribution-preserving — is enabled
        per engine with TPU_LLM_SPEC=1 (draft length TPU_LLM_SPEC_DRAFT;
        docs/advanced-guide/speculative-decoding.md). Overload control — priority
        classes with batch preemption, per-client weighted fair queuing
        (`fair_weights`), predicted-wait shedding and brownout, the
        fleet admission cap and retry budget — is on by default and
        tuned via the TPU_LLM_FAIR / TPU_LLM_PREEMPT /
        TPU_LLM_SHED_WAIT_S / TPU_LLM_BROWNOUT_* knobs or the matching
        engine kwargs (docs/advanced-guide/overload.md). Replicated
        fleets also get device-health judgment by default: replica
        deaths are classified into a per-device ledger, a device
        crossing TPU_LLM_DEVICE_QUARANTINE_FAILURES is quarantined and
        its slot rebuilt elastically on an alternate healthy device (or
        parked, visibly), every rebuild passes a canary probe before
        routing, the numerical watchdog (TPU_LLM_NUMERIC_CHECK) turns
        NaN/Inf logits into a classified replica death, and a request in
        flight across TPU_LLM_POISON_DEATHS deaths is refused further
        failover (docs/advanced-guide/resilience.md). Multi-tenant LoRA
        adapter serving — N low-rank tenant deltas device-resident
        beside ONE base model, applied inside the same fused programs,
        hot-loaded/evicted via ModelHandle.register_adapter and selected
        per request with GenRequest.adapter / X-GoFr-Adapter /
        model=<adapter> on the OpenAI edge — is enabled with
        TPU_LLM_LORA_SLOTS=N (max rank TPU_LLM_LORA_RANK_MAX;
        docs/advanced-guide/multi-tenancy.md). A TransformerConfig with
        n_experts > 0 serves a mixture-of-experts FFN through the same
        engine; under TPU_LLM_TP the expert-batched weights shard on
        their expert axis over each replica's submesh (expert
        parallelism) when the degree divides the expert count."""
        from ...llm import LLMEngine, ReplicatedLLMEngine
        from ...resilience.rollout import ModelHandle

        engine_kw.setdefault("prefix_cache_mb", self.default_llm_prefix_cache_mb)
        if self.default_llm_step_budget != "":
            engine_kw.setdefault(
                "step_token_budget", int(self.default_llm_step_budget)
            )
        if self.default_llm_prefill_chunk != "":
            engine_kw.setdefault(
                "prefill_chunk", int(self.default_llm_prefill_chunk)
            )
        if self.default_llm_spec != "":
            engine_kw.setdefault(
                "speculative", self.default_llm_spec != "0"
            )
        if self.default_llm_spec_draft != "":
            engine_kw.setdefault(
                "spec_draft", int(self.default_llm_spec_draft)
            )
        if self.default_llm_step_watchdog != "":
            engine_kw.setdefault(
                "step_watchdog_s", float(self.default_llm_step_watchdog)
            )
        if self.default_llm_numeric_check != "":
            engine_kw.setdefault(
                "numeric_check", self.default_llm_numeric_check != "0"
            )
        if self.default_llm_constrained != "":
            engine_kw.setdefault(
                "constrained", self.default_llm_constrained != "0"
            )
        if self.default_llm_constrained_grammars != "":
            engine_kw.setdefault(
                "constrained_grammars",
                int(self.default_llm_constrained_grammars),
            )
        if self.default_llm_lora_slots != "":
            engine_kw.setdefault(
                "lora_slots", int(self.default_llm_lora_slots)
            )
        if self.default_llm_lora_rank != "":
            engine_kw.setdefault(
                "lora_rank", int(self.default_llm_lora_rank)
            )
        # paged KV pool / session-tier knobs (docs/advanced-guide/kv-cache.md)
        if self.default_llm_kv_paged != "":
            # "1" means AUTO exactly like the process-env knob (windowed
            # models keep the rolling ring unless sessions/kv_paged=True
            # opt in) — the two configuration surfaces must not resolve
            # the same value to different layouts
            engine_kw.setdefault(
                "kv_paged",
                False if self.default_llm_kv_paged == "0" else "auto",
            )
        if self.default_llm_kv_block != "":
            engine_kw.setdefault("kv_block", int(self.default_llm_kv_block))
        if self.default_llm_kv_int8 != "":
            engine_kw.setdefault("kv_int8", self.default_llm_kv_int8 != "0")
        if self.default_llm_session_mb != "":
            engine_kw.setdefault(
                "session_mb", float(self.default_llm_session_mb)
            )
        if self.default_llm_host_cache_mb != "":
            engine_kw.setdefault(
                "host_cache_mb", float(self.default_llm_host_cache_mb)
            )
        # incident flight recorder (docs/advanced-guide/
        # incident-debugging.md): record-ring size/redaction, black-box
        # bundle directory + per-trigger rate limit, perf-anomaly gate,
        # wide-event sampling factor
        if self.default_llm_flight_records != "":
            engine_kw.setdefault(
                "flight_records", int(self.default_llm_flight_records)
            )
        if self.default_llm_flight_redact != "":
            engine_kw.setdefault(
                "flight_redact", self.default_llm_flight_redact != "0"
            )
        if self.default_llm_blackbox_dir != "":
            engine_kw.setdefault(
                "blackbox_dir", self.default_llm_blackbox_dir
            )
        if self.default_llm_blackbox_interval != "":
            engine_kw.setdefault(
                "blackbox_interval_s",
                float(self.default_llm_blackbox_interval),
            )
        if self.default_llm_anomaly != "":
            engine_kw.setdefault(
                "anomaly", self.default_llm_anomaly != "0"
            )
        if self.default_llm_wide_sample != "":
            engine_kw.setdefault(
                "wide_event_sample", int(self.default_llm_wide_sample)
            )
        engine_kw.setdefault("kv_label", name)  # metric-series label
        engine_kw.setdefault("tracer", self.tracer)  # lifecycle spans
        # model-version label (docs/advanced-guide/rollouts.md): tagged
        # on metrics/wide events, pinned by mid-stream failover, and the
        # baseline a later ModelHandle.deploy() / POST
        # /.well-known/debug/rollout shifts away from
        engine_kw.setdefault("version", "v1")
        # per-tenant SLO targets (docs/advanced-guide/
        # observability-serving.md#slo-burn-rates): explicit slo= /
        # slo_tenants= kwargs win; otherwise the TPU_LLM_SLO_* config
        # knobs apply fleet-wide. No targets anywhere -> no SLO engine,
        # no gauges — the targets themselves are the opt-in.
        if "slo" not in engine_kw and self.config is not None:
            from ...metrics.slo import SLOPolicy

            _slo = SLOPolicy.from_config(self.config)
            if _slo.active():
                engine_kw["slo"] = _slo
        if not hasattr(self, "_llms"):
            self._llms: dict[str, Any] = {}
        if name in self._llms:
            self._llms[name].close()
        replicas = engine_kw.pop("replicas", None)
        # TPU_LLM_TP=N: each replica runs tensor-parallel over its own
        # N-chip submesh (docs/advanced-guide/sharded-serving.md) — the
        # device list is carved into replica submeshes and the standard
        # Megatron param_specs derived per mesh. Explicit meshes= wins.
        if (
            self.default_llm_tp not in ("", "0", "1")
            and "meshes" not in engine_kw
            and "devices" not in engine_kw
            and "mesh" not in engine_kw
        ):
            from ...parallel import tp_submeshes

            engine_kw["meshes"] = tp_submeshes(
                cfg, int(self.default_llm_tp), replicas=replicas,
            )
            replicas = None
        # explicit per-model override beats the process-wide config knob
        # (a smoke/test app can serve a disaggregated engine next to a
        # colocated control engine from one runtime)
        disagg = engine_kw.pop("disagg", None)
        if disagg is None:
            disagg = self.default_llm_disagg not in ("", "0")
        if disagg:
            from ...llm_disagg import DisaggregatedLLMEngine

            dkw = {}
            if (
                self.default_llm_disagg_prefill != ""
                and "prefill_replicas" not in engine_kw
            ):
                dkw["prefill_replicas"] = int(self.default_llm_disagg_prefill)
            if (
                self.default_llm_handoff_timeout != ""
                and "handoff_timeout_s" not in engine_kw
            ):
                dkw["handoff_timeout_s"] = float(
                    self.default_llm_handoff_timeout
                )
            engine = DisaggregatedLLMEngine(
                cfg, params, replicas=replicas,
                logger=self.logger, metrics=self.metrics, **dkw, **engine_kw,
            )
            build_kw = {}  # role pools retain their own rebuild inputs
        elif (replicas or 1) > 1 or "devices" in engine_kw or "meshes" in engine_kw:
            engine = ReplicatedLLMEngine(
                cfg, params, replicas=replicas,
                logger=self.logger, metrics=self.metrics, **engine_kw,
            )
            build_kw = {}  # the fleet retains its own rebuild inputs
        else:
            engine = LLMEngine(
                cfg, params, logger=self.logger, metrics=self.metrics, **engine_kw
            )
            # retained so a deploy() can build the staged engine with the
            # SAME serving shape (slots, buckets, scheduler, overload
            # knobs) — only the weights change
            build_kw = dict(
                engine_kw, logger=self.logger, metrics=self.metrics
            )
            build_kw.pop("version", None)
        handle = ModelHandle(
            name, engine, cfg=cfg, params=params, build_kw=build_kw,
            logger=self.logger, metrics=self.metrics,
        )
        self._llms[name] = handle
        return handle

    def llm(self, name: str):
        llms = getattr(self, "_llms", {})
        try:
            return llms[name]
        except KeyError:
            raise KeyError(
                f"LLM '{name}' not registered; known: {list(llms)}"
            ) from None

    # -- graceful drain (App.begin_drain calls these) ----------------------
    def drain(self) -> None:
        """Close admission on every registered LLM engine (submit ->
        EngineDraining/503) while their in-flight work runs to
        completion; batched models keep serving until close() — their
        executions are milliseconds, not multi-second decodes."""
        for eng in getattr(self, "_llms", {}).values():
            eng.drain()

    def drained(self) -> bool:
        """True once no LLM engine holds in-flight or queued work."""
        return all(
            eng.drained() for eng in getattr(self, "_llms", {}).values()
        )

    # -- lifecycle hooks (App.serve/_stop_servers call these) --------------
    async def start_batchers(self) -> None:
        """Batchers are thread-backed and start at register_model; this hook
        exists for the App lifecycle (and runtimes that defer startup)."""

    async def stop_batchers(self) -> None:
        for m in self._models.values():
            m.batcher.close()

    # -- health (analogue of reference sql/health.go:27-65) ---------------
    def health_check(self) -> dict:
        try:
            details: dict[str, Any] = {
                "platform": self.platform,
                "device_count": len(self.devices),
                "device_kind": self.devices[0].device_kind if self.devices else None,
                "models": {
                    n: dict(m.meta, queue_depth=m.batcher.q.qsize())
                    for n, m in self._models.items()
                },
                "llms": {
                    n: eng.stats() for n, eng in getattr(self, "_llms", {}).items()
                },
            }
            stats = {}
            try:
                ms = self.devices[0].memory_stats()
                if ms:
                    stats = {
                        "bytes_in_use": ms.get("bytes_in_use"),
                        "bytes_limit": ms.get("bytes_limit"),
                    }
            except Exception:  # noqa: BLE001 — memory_stats unsupported on CPU
                pass
            details["memory"] = stats
            return health(STATUS_UP, **details)
        except Exception as e:  # noqa: BLE001
            return health(STATUS_DOWN, error=str(e))

    def close(self) -> None:
        if self.telemetry is not None:
            self.telemetry.close()
        from ...profiling import default_registry

        for m in self._models.values():
            m.batcher.close()
            default_registry().remove_model(m.name)  # dead models unlisted
        self._models.clear()
        for eng in getattr(self, "_llms", {}).values():
            eng.close()
        if hasattr(self, "_llms"):
            self._llms.clear()


class MockTPU:
    """Test seam: the analogue of the reference's MockDB/MockRedis
    (container mock_container.go:19-32). Records calls, returns canned
    outputs, no jax involved."""

    def __init__(self, results: dict[str, Any] | None = None):
        self.results = results or {}
        self.calls: list[tuple[str, tuple]] = []

    def register_model(self, name: str, *a, **k) -> None:
        self.calls.append(("register_model", (name,)))
        self.results.setdefault(name, None)

    def infer(self, name: str, *args) -> Any:
        self.calls.append(("infer", (name, *args)))
        return self.results.get(name)

    async def infer_async(self, name: str, *args) -> Any:
        self.calls.append(("infer_async", (name, *args)))
        return self.results.get(name)

    def infer_one(self, name: str, *args, timeout=None) -> Any:
        self.calls.append(("infer_one", (name, *args)))
        return self.results.get(name)

    def health_check(self) -> dict:
        return health(STATUS_UP, platform="mock", device_count=0, models={})

    async def start_batchers(self) -> None:
        pass

    async def stop_batchers(self) -> None:
        pass

    def drain(self) -> None:
        pass

    def drained(self) -> bool:
        return True

    def close(self) -> None:
        pass
