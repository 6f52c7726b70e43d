"""LLM serving engine: slot-based continuous batching with token streaming.

The decode-serving core for BASELINE.json configs 3/5 (gRPC streaming
Gemma decode; multi-chip tensor-parallel serving). No counterpart in the
reference repo — this is the TPU-native replacement for its goroutine-per-
request model at the model-serving layer (SURVEY.md §7 hard part 5:
"continuous batching / slot-based scheduler is the real design problem").

Design (all shapes static; a bounded set of compiled executables):

- **Slots over a paged block pool (default).** A fixed decode batch of
  S slots whose KV lives in ONE device-resident pool of fixed-size
  blocks [n_layers, NB, block, hkv * hd] (a row flat, as its decode
  kernel reads a page: kvcache.row_shapes), read and written through
  per-slot block tables (gofr_tpu.kvcache.paged): blocks materialize as
  each cursor advances, sibling prompts share every common prefix block
  in place (refcounted, copy-on-write), and decode attention goes
  through ops.paged_chunk_decode_attention (Pallas paged kernel on TPU,
  dense-gather fallback elsewhere). TPU_LLM_KV_INT8 stores blocks int8.
  kv_paged=False restores the contiguous layouts — a dense
  [n_layers, S, max_seq_len, hkv, hd] slab for global attention, or a
  window-bounded ROLLING ring for sliding-window models — as the
  token-identical A/B lever. Inactive slots are masked (their tokens
  are discarded on host; their cursors never advance).
- **Prefix reuse.** With prefix_cache_mb > 0, admission consults the
  prefix index — the paged layout's RADIX TREE over token ids (every
  block-aligned shared prefix hits, exact published prompts skip
  prefill entirely via copied tails + stored logits), or the contiguous
  layout's refcounted LRU cache of whole retained rows
  (gofr_tpu.kvcache; hit/miss/partial_hit counters in
  stats()["kvcache"]). With session_mb > 0, X-GoFr-Session
  conversations keep their blocks resident between turns and spill to
  host RAM when cold (docs/advanced-guide/kv-cache.md#sessions).
- **Fused decode chunks.** Decode advances ALL slots K steps per dispatch
  (models.transformer.decode_chunk: a lax.scan over a chunk-ring-buffer
  layer body with on-device sampling — the main cache is read-only inside
  a chunk and merged once at chunk end, so no per-step scatter). One
  host→device dispatch per K tokens amortizes dispatch latency, and the
  engine keeps up to `lookahead` chunks in flight, chaining each chunk's
  input tokens from the previous chunk's on-device output so the device
  never waits for host readback.
- **Chunked prefill under a token budget (default).** Prompts are split
  into fixed-shape prefill chunks (TPU_LLM_PREFILL_CHUNK, default 64;
  the configured prefill_buckets survive only as the available chunk
  compile shapes) that append into the slot's KV cache incrementally via
  a per-request `prefill_pos` cursor — a partial-prefill slot is
  resident but not decoding. Each device step packs up to
  TPU_LLM_STEP_TOKEN_BUDGET (default 256) tokens of pending prefill
  chunks COALESCED with the active slots' decode chunk into one jitted
  unified-step program (the rows go out alone, llm.step_p{n}_d0, when no
  slot decodes and no row finishes: nobody would read the chunk), so no
  request ever waits behind more than one bounded step (Sarathi-style
  chunked prefill + piggybacked decode; the
  monolithic path held the chip for admit_cap x bucket tokens per wave
  and starved decode — BENCH_r05's 1.46 SLO p99/p50 was that
  head-of-line wait). A prompt whose PREFIX is already in the prefix
  cache seeds `prefill_pos` mid-prompt and only the unshared chunks run.
  step_token_budget=0 restores the monolithic wave path (the A/B lever
  the equality tests drive).
- **Speculative decoding (opt-in, TPU_LLM_SPEC=1).** A host-side
  n-gram/prompt-lookup drafter (gofr_tpu.spec) proposes up to
  TPU_LLM_SPEC_DRAFT tokens per decoding slot; ONE fused verify program
  (llm.step_v, models.transformer.verify_chunk) scores every draft
  position against the slot KV in a single write-then-attend pass,
  samples each with the regular top-k machinery, accepts the longest
  agreeing prefix ON DEVICE (tail/cursors stay chained; rejected rows
  roll back behind the cursor), and the host emits the accepted span as
  one multi-token push. Greedy spec-on is token-identical to spec-off;
  temperature is distribution-preserving. Verifies pipeline against
  their own optimistic draft stream; when nothing drafts the engine
  falls back to the plain chunk pipeline and periodically re-probes
  (docs/advanced-guide/speculative-decoding.md).
- **Admission without stalling decode.** Monolithic-path prefill waves
  dispatch asynchronously BETWEEN decode chunks; the first sampled token
  is merged into the on-device tail vector by a jitted scatter (no host
  round trip), and prefilled KV rows are copied into free slots via ONE
  jitted insert-many. Decode chunks already in flight keep streaming —
  their tokens for a reused slot are dropped on host via per-slot
  generation tags, never by draining the pipeline (the r2 engine's
  flush-before-admit barrier cost 72% of raw decode throughput).
- **On-device sampling.** Greedy or temperature sampling happens inside the
  chunk; the host syncs one [K, S] int32 array per chunk (started with
  copy_to_host_async at dispatch) instead of logits.
- **Streaming.** Each request owns a thread-safe queue; the engine thread
  pushes per-chunk token LISTS as fetches complete; consumers iterate
  stream() (sync) or astream() (async) and detach by cancelling — a
  detached request just frees its slot, never stalling the batch.
- **Observability.** With a tracer wired, submit() captures the caller's
  trace context (the scheduler/collector threads break contextvar flow)
  and the engine emits an llm.request span with queue_wait / prefill /
  per-chunk decode / emit children; with metrics wired it records the
  app_llm_* phase histograms and engine-state gauges; with a logger it
  emits one JSON wide-event line per completed request. stats()["phases"]
  and debug_state() expose recent-window p50/p99 and the live slot table
  (docs/advanced-guide/observability-serving.md). Every jitted program
  goes through profiling.instrument_jit — per-shape compile wall time,
  cost_analysis FLOPs, and cache-hit counts land in the process compile
  registry (/.well-known/debug/compiles) — and each prefill wave /
  decode chunk feeds analytic-FLOPs MFU, tokens/s/chip, and a roofline
  compute-vs-HBM classification (stats()["mfu"], app_llm_mfu gauges;
  docs/advanced-guide/profiling.md).
- **Programs.** What the scheduler dispatches is built in
  gofr_tpu.llm_programs: the decode chunk, the unified step and the
  speculative verify are each written once there, over this engine's KV
  layout and a plain or grammar sampler, and that module is the one place
  that knows a program's argument list (LLMEngine._run).

Tensor parallelism: pass mesh + param_specs (or TPU_LLM_TP via
register_llm) and the engine serves the model across an ICI submesh —
the KV pool/slab is COMMITTED to parallel.sharding.kv_specs (heads
sharded when the TP degree divides n_kv_heads, replicated under MQA),
and the sharded decode path double-buffers the next layer's weight
all-gather behind the current layer's matmul (TPU_LLM_TP_OVERLAP;
docs/advanced-guide/sharded-serving.md) — identical tokens single-chip
and multi-chip. Disaggregated prefill/decode role pools with
device-to-device KV handoff live in gofr_tpu.llm_disagg.
Quantization: quantize=True serves int8 weights (models.quant), halving
the HBM stream that bounds decode.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Any, Iterator

import numpy as np

from .profiling import engine_span, name_os_thread

__all__ = [
    "LLMEngine",
    "ReplicatedLLMEngine",
    "GenRequest",
    "EngineOverloaded",
    "EngineStoppedError",
    "EngineDraining",
    "PoisonedRequestError",
]

_EOS_DEFAULT = -1  # no EOS cut by default (random-weight models)

# One record per dispatched program (docs/advanced-guide/profiling.md, "The
# step timeline"): opened at dispatch in the in-flight entry's info, finished
# by the collector, kept as a tuple in this order. Times are perf_counter().
STEP_FIELDS = (
    "seq", "kind", "program", "k", "depth", "lanes", "decode_ctx", "rows",
    "t_dispatch", "t_dispatched", "t_fetch", "t_fetched", "t_emitted", "emitted",
    # routed experts (0 for a dense model): token-expert pairs the program
    # computed and experts that were given a row, summed over its layer calls
    "moe_pairs", "moe_touched",
    # every pair the program's routers chose: more than moe_pairs where this
    # program holds a share of the experts (cfg.moe_held_experts)
    "moe_pairs_routed",
)
# the ring holds two benchmark windows of programs (50 s each) down to 25 ms a program
STEP_LOG_LEN = 4096
_DECODE_KINDS = ("chunk", "step", "verify")

def latent_refusal(
    *, mesh, chunked: bool, kv_paged, speculative: bool, constrained, lora_slots,
) -> str | None:
    """What a latent-attention model (cfg.latent) is refused at engine build,
    as a sentence, or None: every path that was not written for a latent
    cache row says so here instead of falling back silently."""
    if mesh is not None:
        return (
            "latent-attention models serve on one chip: tensor or expert "
            "parallelism (a mesh, tp > 1) is not written for them, one chip "
            "holds each layer whole"
        )
    if kv_paged is False:
        return (
            "latent-attention models serve from the paged KV pool only: the "
            "contiguous layout (kv_paged=False) is not written for a latent row"
        )
    if not chunked:
        return (
            "latent-attention models need the token-budget step scheduler: "
            "the wave scheduler (step_token_budget=0) prefills through a "
            "contiguous cache"
        )
    if speculative:
        return (
            "speculative decoding (verify_chunk) is not written for "
            "latent-attention models: serve with speculative=False"
        )
    if constrained:
        return (
            "constrained decoding is not written for latent-attention models: "
            "serve with constrained=False"
        )
    if lora_slots:
        return (
            "LoRA adapters on the latent projections are not supported: serve "
            "with lora_slots=0"
        )
    return None


def mixed_refusal(*, mesh, chunked: bool, speculative: bool, lora_slots) -> str | None:
    """What a mixed stack (cfg.mixed: window and full layers, two kinds of
    paged state) is refused at engine build, as a sentence, or None. The
    cache's own refusals (the contiguous layout, an int8 pool, prefix
    sharing, sessions and with them spill and hand-off) are
    kvcache.mixed_kv_refusal's. Preemption follows both kinds: it releases
    the slot's blocks of each and re-queues the request."""
    if mesh is not None:
        return (
            "a mixed stack (window and full layers) serves on one chip: the "
            "sharding specs name one pool of KV blocks"
        )
    if not chunked:
        return (
            "a mixed stack needs the token-budget step scheduler: the wave "
            "scheduler (step_token_budget=0) prefills through ONE contiguous "
            "cache and inserts it into one pool"
        )
    if speculative:
        return (
            "speculative decoding (verify_chunk) is not written for a mixed "
            "stack: a rolled-back draft's rows would sit in blocks the window "
            "layers already gave back; serve with speculative=False"
        )
    if lora_slots:
        return "LoRA slots are not written for a mixed stack: serve with lora_slots=0"
    return None


# Serializes app_llm_* registration across engines (ReplicatedLLMEngine
# builds N engines on parallel threads; same rationale as the kvcache
# module's registration lock).
_OBS_REG_LOCK = threading.Lock()


def _register_phase_metrics(metrics) -> None:
    """Engine phase-latency instruments, shared across engines/replicas
    (series are separated by the model label). Histograms reuse
    TPU_BUCKETS (100us..5s) — queue wait, TTFT, and per-token latencies
    all live inside that envelope on every supported config."""
    from .metrics import TPU_BUCKETS

    with _OBS_REG_LOCK:
        for name, desc in (
            ("app_llm_queue_wait_seconds", "llm submit->slot admission wait s"),
            ("app_llm_ttft_seconds", "llm submit->first emitted token s"),
            ("app_llm_time_per_output_token_seconds",
             "llm steady-state decode s/token (requests with >1 token)"),
            ("app_llm_decode_step_seconds",
             "llm decode dispatch->fetch s/step (chunk=len, wave=pow2 active)"),
        ):
            if not metrics.has(name):
                metrics.new_histogram(name, desc, TPU_BUCKETS)
        if not metrics.has("app_llm_step_seconds"):
            # unified-step dispatch->fetch wall time (chunked scheduler)
            metrics.new_histogram(
                "app_llm_step_seconds",
                "llm unified step dispatch->fetch s (prefill chunks + "
                "piggybacked decode)", TPU_BUCKETS,
            )
        # sharded / disaggregated serving (docs/advanced-guide/
        # sharded-serving.md)
        if not metrics.has("app_llm_kv_handoff_seconds"):
            metrics.new_histogram(
                "app_llm_kv_handoff_seconds",
                "llm disaggregated prefill->decode KV handoff wall s "
                "(export + transfer + import)", TPU_BUCKETS,
            )
        if not metrics.has("app_llm_collective_seconds"):
            metrics.new_histogram(
                "app_llm_collective_seconds",
                "llm sharded-serving collective/transfer wall s "
                "(phase=weight_shard|kv_handoff_gather|"
                "kv_handoff_transfer|kv_handoff_scatter)", TPU_BUCKETS,
            )
        if not metrics.has("app_llm_tp_degree"):
            metrics.new_gauge(
                "app_llm_tp_degree",
                "tensor-parallel degree of each engine's submesh "
                "(1 = single-chip)",
            )
        if not metrics.has("app_llm_kv_handoffs_total"):
            metrics.new_counter(
                "app_llm_kv_handoffs_total",
                "llm disaggregated KV handoffs "
                "(outcome=ok|miss|fallback)",
            )
        if not metrics.has("app_llm_steps_without_decode_total"):
            metrics.new_counter(
                "app_llm_steps_without_decode_total",
                "llm unified steps dispatched without their decode chunk "
                "(no lane decoding, no prompt row finishing)",
            )
        if not metrics.has("app_llm_kv_blocks_reclaimed_total"):
            metrics.new_counter(
                "app_llm_kv_blocks_reclaimed_total",
                "llm KV blocks a mixed stack's window layers gave back behind "
                "the attention window",
            )
        if not metrics.has("app_llm_moe_pairs_total"):
            metrics.new_counter(
                "app_llm_moe_pairs_total",
                "llm (token, expert) pairs of the routed experts "
                "(kind=routed: chosen by the router | computed: by experts held here)",
            )
        if not metrics.has("app_llm_step_tokens"):
            metrics.new_histogram(
                "app_llm_step_tokens",
                "llm tokens packed per unified step (prefill chunk tokens "
                "+ decode steps x active slots)",
                (8.0, 16.0, 32.0, 64.0, 128.0, 256.0, 512.0, 1024.0,
                 2048.0, 4096.0, 8192.0),
            )
        # speculative decoding (gofr_tpu.spec;
        # docs/advanced-guide/speculative-decoding.md)
        for name, desc in (
            ("app_llm_spec_proposed_total",
             "llm speculative draft tokens proposed (n-gram drafter; "
             "constrained=0|1 splits grammar-masked lanes)"),
            ("app_llm_spec_accepted_total",
             "llm speculative draft tokens accepted by verification "
             "(constrained=0|1 splits grammar-masked lanes)"),
            ("app_llm_constrained_requests_total",
             "llm grammar-constrained generation requests accepted "
             "(gofr_tpu.structured)"),
        ):
            if not metrics.has(name):
                metrics.new_counter(name, desc)
        if not metrics.has("app_llm_constrained_mask_seconds"):
            metrics.new_histogram(
                "app_llm_constrained_mask_seconds",
                "llm grammar mask preparation wall s per constrained "
                "submit (dedup hit or table pad + device ship)",
                TPU_BUCKETS,
            )
        if not metrics.has("app_llm_constrained_grammars"):
            metrics.new_gauge(
                "app_llm_constrained_grammars",
                "llm resident compiled grammars in the engine's device "
                "transition table (zeroed at engine close)",
            )
        # multi-tenant LoRA adapter serving (gofr_tpu.lora;
        # docs/advanced-guide/multi-tenancy.md)
        for name, desc in (
            ("app_llm_adapter_requests_total",
             "llm requests attributed to a LoRA adapter (adapter label "
             "names the tenant)"),
            ("app_llm_adapter_swaps_total",
             "llm adapter hot-load publishes (staged gid repointed at a "
             "serving name; old gid drains as a zombie)"),
            ("app_llm_adapter_evictions_total",
             "llm idle resident adapters LRU-evicted to make room for a "
             "load (pool full)"),
        ):
            if not metrics.has(name):
                metrics.new_counter(name, desc)
        if not metrics.has("app_llm_adapters_resident"):
            metrics.new_gauge(
                "app_llm_adapters_resident",
                "llm named LoRA adapters resident in the engine's device "
                "tables (zeroed at engine close)",
            )
        if not metrics.has("app_llm_moe_experts"):
            metrics.new_gauge(
                "app_llm_moe_experts",
                "llm experts per MoE layer of the served model (0 = dense)",
            )
        if not metrics.has("app_llm_spec_tokens_per_step"):
            metrics.new_histogram(
                "app_llm_spec_tokens_per_step",
                "llm tokens emitted per slot per speculative verify step "
                "(accepted draft + 1 bonus; 1 = nothing accepted)",
                (1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 8.0, 12.0, 16.0),
            )
        for name, desc in (
            ("app_llm_slots_in_use", "llm decode slots holding a live request"),
            ("app_llm_queue_depth", "llm requests waiting for a slot"),
            ("app_llm_admission_backlog",
             "llm requests mid-admission (pulled from queue, not yet slotted)"),
            ("app_llm_step_budget_utilization",
             "tokens packed into the last unified step / step token "
             "budget (can exceed 1: decode always rides and a step "
             "always carries at least one chunk)"),
            ("app_llm_mfu",
             "model FLOPs utilization 0..1 per phase (analytic FLOPs / "
             "measured wall / device peak)"),
            ("app_llm_tokens_per_second_per_chip",
             "llm decoded tokens per second per chip (last chunk)"),
            ("app_llm_roofline_ratio",
             "compute_time/memory_time per phase (>1 compute-bound, "
             "<1 HBM-bandwidth-bound)"),
            ("app_llm_spec_accept_rate",
             "llm cumulative speculative draft acceptance rate 0..1 "
             "(accepted/proposed; zeroed at engine close)"),
        ):
            if not metrics.has(name):
                metrics.new_gauge(name, desc)
    from .profiling import register_compile_metrics

    register_compile_metrics(metrics)  # app_jax_* (own registration lock)
    from .resilience import register_resilience_metrics

    register_resilience_metrics(metrics)  # app_llm_*_total + drain gauge
    from .goodput import register_goodput_metrics

    register_goodput_metrics(metrics)  # app_llm_goodput_* + tenant meters


class EngineOverloaded(RuntimeError):
    """Raised by submit() when the admission queue cap is hit OR when the
    predicted queue wait crosses the shed threshold — the SLO-preserving
    alternative to unbounded queueing (map to HTTP 429). Carries
    `status_code` so the responder's statusCodeResponder seam translates
    it without a handler-side catch, and `retry_after` (seconds) so both
    edges tell the client WHEN capacity is predicted back (HTTP
    Retry-After header; gRPC retry-after trailer) instead of inviting an
    immediate blind retry. NON-RETRYABLE inside the fleet: the router
    picked the least-loaded replica, so every other replica is at least
    as overloaded — retrying the rest would amplify the overload
    (docs/advanced-guide/overload.md)."""

    status_code = 429
    retry_after: float | None = None

    def __init__(self, message: str = "", retry_after: float | None = None):
        super().__init__(message)
        if retry_after is not None:
            self.retry_after = max(0.1, float(retry_after))


class EngineStoppedError(RuntimeError):
    """Raised by submit() on a dead or closed engine. A TYPE, not a
    string: the replica router's retry loop used to match
    "engine stopped" in str(e) and silently swallowed any RuntimeError
    that happened to contain it. Subclasses RuntimeError so callers that
    caught the old error keep working."""


class EngineDraining(RuntimeError):
    """Raised by submit() while the engine drains (rolling deploy):
    admission is closed but in-flight work runs to completion. 503 via
    the statusCodeResponder seam — the load balancer should retry the
    next pod, not this one. `retry_after` rides the response (HTTP
    Retry-After / gRPC trailer) so a client talking straight to the pod
    backs off for roughly a readiness-probe window instead of spinning.
    RETRYABLE inside the fleet: another replica may still be accepting
    (the router excludes draining replicas, but a drain can begin
    between pick and submit)."""

    status_code = 503
    retry_after: float | None = 5.0


class UnknownAdapterError(KeyError):
    """Raised by submit() when ``req.adapter`` names no resident adapter
    (gofr_tpu.lora). 404 via the statusCodeResponder seam — the OpenAI
    edge turns it into the model-not-found error envelope. A KeyError
    subclass so registry-shaped callers that probe with ``except
    KeyError`` keep working."""

    status_code = 404

    def __init__(self, name: str, resident=()):
        super().__init__(name)
        self.adapter = name
        self.resident = sorted(resident)

    def __str__(self) -> str:
        return (
            f"unknown adapter {self.adapter!r}; resident: "
            f"{self.resident or 'none'}"
        )


class PoisonedRequestError(RuntimeError):
    """Raised by GenRequest.stream()/tokens() when the fleet refused a
    request further failover: it was in flight across
    ``TPU_LLM_POISON_DEATHS`` replica deaths, which makes its payload the
    prime suspect for those crashes — retrying it again would let one
    request kill every replica in turn. 500 via the statusCodeResponder
    seam (gRPC surfaces INTERNAL): the caller must NOT retry the same
    payload (docs/advanced-guide/resilience.md)."""

    status_code = 500


@dataclass(eq=False)  # identity semantics: requests are handles, and the
# engine's error path collects them in sets (dataclass __eq__ would make
# them unhashable and value-compared)
class GenRequest:
    prompt_tokens: list[int]
    max_new_tokens: int = 32
    temperature: float = 0.0
    eos_token: int = _EOS_DEFAULT
    # Overload-control identity (docs/advanced-guide/overload.md):
    # priority class "interactive" (latency-sensitive; may preempt batch
    # work under queue pressure) or "batch" (throughput work; absorbs
    # pressure via preemption and brownout clamping). Anything except
    # the literal "batch" is treated as interactive — the edge forwards
    # the X-GoFr-Priority header verbatim and a typo must degrade to the
    # latency-safe class, not an error.
    priority: str = "interactive"
    # Fair-queuing client id (X-GoFr-Client header / API key / caller's
    # choice). "" pools unattributed traffic into one anonymous client.
    client: str = ""
    # Explicit W3C trace context for callers whose submitting thread the
    # tracing contextvar does not reach (executor pools, user threads);
    # submit() prefers the live contextvar span when one is active.
    traceparent: str | None = None
    # Absolute wall deadline (time.perf_counter timebase). Past it the
    # engine cancels the request EVEN WHILE SLOTTED (finish_reason
    # "deadline") — a decode past its HTTP timeout burns chip time for a
    # client that already gave up. Handlers pass ctx.deadline here.
    deadline: float | None = None
    # Chaos-only payload marker: a fault spec armed with the same tag
    # fires exactly when THIS request's step dispatches (the
    # deterministic stand-in for a payload that crashes the step
    # program; gofr_tpu.resilience.faults). Empty for real traffic.
    tag: str = ""
    # Conversation id (X-GoFr-Session header; docs/advanced-guide/
    # kv-cache.md#sessions). On finish the full sequence's KV blocks
    # stay resident in the paged pool keyed by this id (spilled to host
    # RAM when cold), so the NEXT turn's prompt — which extends this
    # conversation — block-shares the whole history instead of
    # re-prefilling it. Empty = sessionless (blocks free at retire).
    session_id: str = ""
    # Grammar-constrained decoding (gofr_tpu.structured;
    # docs/advanced-guide/structured-decoding.md): a compiled
    # TokenGrammar. Every sampled token is masked to what the grammar's
    # current DFA state admits — the output is valid by construction —
    # and the per-slot state advances INSIDE the fused device programs,
    # so constrained and unconstrained requests share one program.
    # Requires the chunked scheduler; eos_token is taken from the
    # grammar when unset. None = unconstrained (zero new device work).
    grammar: Any = None
    # Multi-tenant LoRA adapter name (gofr_tpu.lora; docs/advanced-guide/
    # multi-tenancy.md): the resident adapter whose low-rank delta this
    # request decodes under. The OpenAI edge maps model=<adapter> / the
    # X-GoFr-Adapter header here. "" = the base model (gid 0 identity —
    # token-identical to an engine with no adapter support). Requires the
    # chunked scheduler and a LoRA-enabled engine (lora_slots > 0).
    adapter: str = ""
    # Synthetic-traffic marker (gofr_tpu.goodput): canary checks, shadow
    # probes, rollout bakes, and flight-record replays set probe=True so
    # the goodput ledger classes their chip time as `probe` waste rather
    # than tenant demand — and the quota gate waves them through (an
    # over-quota tenant must not block the canary that protects it).
    probe: bool = False
    id: int = field(default_factory=itertools.count().__next__)

    def __post_init__(self):
        self.out: queue.Queue = queue.Queue()
        self.cancelled = False
        # why the cancel happened — becomes the finish_reason when the
        # engine retires the request ("cancelled" for an explicit caller
        # cancel, "disconnect" when the serving edge detected a dead
        # peer; docs/advanced-guide/rollouts.md#client-disconnects)
        self.cancel_reason = "cancelled"
        # model version of the engine that last accepted this request —
        # stamped by LLMEngine.submit. Once the stream has emitted a
        # token, failover PINS to this version: a stream must never be
        # served tokens from two model versions (rollouts).
        self.engine_version: str | None = None
        self.emitted = 0
        self.capped = False  # engine reduced max_new_tokens to fit the cache
        self.browned = False  # brownout clamped max_new_tokens (batch class)
        self.preempted = 0  # times a slot was taken back for interactive work
        self._prompt_billed = False  # fairness ledger saw the prompt tokens
        self.finish_reason: str | None = None  # "eos" | "length" | "cancelled"
        #   | "shed" | "deadline" | "error" | "poison" ("failover"
        #   transiently marks a request rescued off a dying replica so
        #   drain paths skip it)
        self.submitted_at: float | None = None
        # -- failover state (gofr_tpu.resilience) --
        # tokens emitted since the last (re)submit: on replica death the
        # router re-seeds prompt_tokens + history as the continuation
        # prompt, so the failed-over stream resumes exactly where the
        # consumer left off (greedy streams are token-identical).
        self.history: list[int] = []
        self.retries = 0  # failover re-dispatches consumed
        # replica deaths this request was IN FLIGHT for (slotted,
        # prefilling, or riding a device snapshot at _die — queued-only
        # bystanders are not implicated). At TPU_LLM_POISON_DEATHS the
        # router refuses further failover (finish_reason "poison").
        self.deaths = 0
        # -- chunked-prefill scheduler state (engine-maintained) --
        self.prefill_pos = 0  # prompt tokens already appended to slot KV
        self.prefill_done = False  # all prompt tokens resident; decoding
        self.slot: int | None = None  # slot index while resident
        self._rows_hi = 0  # highest slot row ever written (prefix trim)
        # -- paged KV state (engine-maintained; kvcache.paged) --
        self._kv_limit = 0  # worst-case rows (CacheManager.reserve_tokens)
        self._kv_resv = 0  # admission block promise not yet bound to a slot
        self._kv_plan = None  # pinned seed plan not yet attached to a slot
        self._session_published = False  # end-of-turn radix publish done
        self._prefill_t0: float | None = None  # first chunk dispatch time
        self._load_acct = 0  # outstanding token estimate (router weighting)
        # -- grammar-constrained decoding (engine-maintained) --
        # _g_id: this engine's resident-grammar table slot (set at
        # submit; -1 while unconstrained). _g_state: HOST mirror of the
        # DFA state after every emitted token — feeds the drafter's
        # grammar filter and re-seeds the device state when a
        # continuation (preemption/failover) re-admits mid-output.
        self._g_id = -1
        self._g_state = 0
        # -- multi-tenant LoRA (engine-maintained; gofr_tpu.lora) --
        # _aid: the adapter pool gid this request's in-flight reference
        # pins (0 = base/identity, never refcounted). Re-resolved from
        # `adapter` on every submit — a failover continuation lands on a
        # replica whose pool may bind the name to a different gid.
        self._aid = 0
        # -- speculative decoding (gofr_tpu.spec; engine-maintained) --
        # acceptance-rate EMA driving the adaptive draft length, and the
        # plain-pass streak that paces the backed-off re-probe. Starts
        # optimistic: the first verify measures the request's real rate.
        self._spec_ema = 1.0
        self._spec_plain = 0
        # optimistic pipelining state: predicted-but-unconfirmed tokens
        # (one span per in-flight verify) the drafter extends so the
        # next verify can DISPATCH before the previous one is fetched —
        # the verify program chains tail/cursor from device state, so a
        # stale draft costs acceptance, never correctness
        self._spec_pending: list[int] = []
        self._spec_inflight = 0
        # -- observability (engine-maintained; read by debug/stats/traces) --
        self.phase = "new"  # new -> queued -> prefill -> decode -> done
        self.prefix_hit = False
        self.admitted_at: float | None = None
        self.first_token_at: float | None = None
        self.span = None  # detached llm.request span (engine has a tracer)
        self._observed = False  # terminal observability emitted (idempotence)
        # journey accounting: hop counts every re-admission after the
        # first (failover re-submit, preemption continuation) so the
        # wide event reads "hop 2 of journey J"; journey_id pins the
        # trace id of the FIRST submit and survives kills — the handle a
        # cross-process stitch is queried by.
        self.hop = 0
        self.journey_id: str | None = None
        # -- goodput attribution (gofr_tpu.goodput; engine-maintained) --
        # _chip: chip-seconds attributed to this request by waste class
        # (useful/padding/spec_reject/replay/probe) — rolled into the
        # wide event, flight record, and OpenAI usage block at finish.
        # _replay_pos: prompt positions below this index were already
        # computed once (preemption/failover continuation re-prefill) —
        # the ledger classes their re-prefill as `replay`, not `useful`.
        self._chip: dict[str, float] = {}
        self._replay_pos = 0

    # -- consumption ------------------------------------------------------
    def _raise_terminal(self) -> None:
        """End-of-stream classification: a poison refusal is an ERROR the
        caller must see (500/INTERNAL — the payload is implicated in
        replica deaths and will not be retried), not a quietly short
        stream. Every other finish reason keeps the legacy
        truncate-and-return contract."""
        if self.finish_reason == "poison":
            raise PoisonedRequestError(
                f"request {self.id} implicated in {self.deaths} replica "
                "deaths; failover refused (do not retry this payload)"
            )

    def _consumer_gone(self) -> None:
        """The consuming generator was CLOSED before the stream finished —
        the serving edge detected a dead peer (HTTP broken pipe, gRPC
        context done) or the caller abandoned the iterator. Either way
        nobody will read another token: cancel so the engine frees the
        slot and credits load_tokens instead of decoding to completion
        for a connection that no longer exists."""
        if self.finish_reason is None and not self.cancelled:
            self.cancel(reason="disconnect")

    def stream(self, timeout: float = 60.0) -> Iterator[int]:
        """Yield token ids until the engine signals completion."""
        try:
            while True:
                item = self.out.get(timeout=timeout)
                if item is None:
                    self._raise_terminal()
                    return
                if isinstance(item, list):
                    yield from item
                else:
                    yield item
        except GeneratorExit:
            self._consumer_gone()
            raise

    async def astream(self, timeout: float = 60.0):
        import asyncio

        loop = asyncio.get_running_loop()
        try:
            while True:
                item = await loop.run_in_executor(None, lambda: self.out.get(timeout=timeout))
                if item is None:
                    self._raise_terminal()
                    return
                if isinstance(item, list):
                    for t in item:
                        yield t
                else:
                    yield item
        except GeneratorExit:
            self._consumer_gone()
            raise

    def cancel(self, reason: str = "cancelled") -> None:
        self.cancel_reason = reason
        self.cancelled = True

    def tokens(self, timeout: float = 60.0) -> list[int]:
        return list(self.stream(timeout=timeout))


class LLMEngine:
    _FETCH_FAIL_LIMIT = 3  # consecutive fetch failures before full reset
    _PREEMPT_CAP = 2  # max evictions per batch request (then it keeps its slot)
    # plain decode chunks bought by one failed clean-pipe drafting probe
    # (speculative mode): the chunk pipeline then drains and speculation
    # re-probes — ~one exposed fetch RTT per this many chunks of overhead
    _SPEC_REPROBE_CHUNKS = 16

    def __init__(
        self,
        cfg,
        params,
        *,
        slots: int = 32,
        max_seq_len: int = 512,
        prefill_buckets: tuple[int, ...] = (16, 64, 128),
        decode_chunk: int = 8,
        prefill_chunk: int | None = None,
        step_token_budget: int | None = None,
        speculative: bool | None = None,
        spec_draft: int | None = None,
        lookahead: int = 3,
        admit_cap: int = 8,
        admit_delay_ms: float = 40.0,
        mesh=None,
        param_specs: Any = None,
        tp_overlap: bool | None = None,
        role: str = "",
        device=None,
        max_queue: int | None = None,
        ttft_deadline_ms: float | None = None,
        fair_queuing: bool | None = None,
        fair_weights: dict | None = None,
        fair_ledger=None,
        preemption: bool | None = None,
        shed_predicted_wait_s: float | None = None,
        brownout_wait_s: float | None = None,
        brownout_max_new: int | None = None,
        brownout_hold_s: float | None = None,
        step_watchdog_s: float | None = None,
        numeric_check: bool | None = None,
        constrained: bool | None = None,
        constrained_grammars: int | None = None,
        lora_slots: int | None = None,
        lora_rank: int | None = None,
        fault_injector=None,
        logger=None,
        metrics=None,
        tracer=None,
        warmup: bool = True,
        quantize: bool = False,
        kv_window: int | None = None,
        prefix_cache_mb: float = 0.0,
        kv_paged: bool | None = None,
        kv_block: int | None = None,
        kv_pool_blocks: int | None = None,
        kv_int8: bool | None = None,
        session_mb: float | None = None,
        host_cache_mb: float | None = None,
        kv_label: str = "llm",
        version: str = "v1",
        slo=None,
        slo_tenants: dict | None = None,
        flight_records: int | None = None,
        flight_redact: bool | None = None,
        blackbox_dir: str | None = None,
        blackbox_interval_s: float | None = None,
        anomaly: bool | None = None,
        wide_event_sample: int | None = None,
        goodput: bool | None = None,
        quotas: dict | None = None,
        usage_meter=None,
        usage_window_s: float | None = None,
    ):
        import jax
        import jax.numpy as jnp

        from .kvcache import CacheManager
        from .profiling import default_registry, instrument_jit
        from .profiling import mfu as mfu_mod
        from .utils import enable_compilation_cache

        enable_compilation_cache(logger=logger)

        if param_specs is not None and "unembed" in params and "unembed" not in param_specs:
            # untied-head (Llama) params: untied-ness lives in the pytree,
            # not the config, and callers routinely build specs with
            # sharding.param_specs(cfg, mesh) defaults — patch in embed's
            # spec (same [vocab, d] layout) instead of crashing shard_params
            param_specs = {**param_specs, "unembed": param_specs["embed"]}
        self.quantized = quantize

        self.cfg = cfg
        self.slots = slots
        self.max_seq_len = max_seq_len
        self.prefill_buckets = tuple(sorted(b for b in prefill_buckets if b <= max_seq_len))
        self.decode_chunk = decode_chunk
        self.lookahead = max(1, lookahead)
        self.admit_cap = min(admit_cap, slots)
        self.admit_delay = admit_delay_ms / 1000.0
        # -- token-budget step scheduler (chunked prefill) ----------------
        # step_token_budget bounds the TOTAL tokens packed into one device
        # step: the active slots' decode chunk is charged first (decode
        # always rides — it is the latency-critical work the budget
        # exists to protect) and prefill chunks coalesce into whatever
        # remains, floored at one chunk so a step always makes progress;
        # 0 restores the monolithic wave scheduler. prefill_chunk caps
        # the chunk compile shape; the configured buckets survive only as
        # the available chunk shapes, so short prompts keep their tight
        # compile shapes.
        import os as _os

        if step_token_budget is None:
            step_token_budget = int(
                _os.environ.get("TPU_LLM_STEP_TOKEN_BUDGET", "256")
            )
        if prefill_chunk is None:
            prefill_chunk = int(_os.environ.get("TPU_LLM_PREFILL_CHUNK", "64"))
        self.prefill_chunk = max(1, int(prefill_chunk))
        self.step_token_budget = max(0, int(step_token_budget))
        self.chunked = self.step_token_budget > 0
        shapes = {min(b, self.prefill_chunk) for b in self.prefill_buckets}
        shapes.discard(0)
        self.chunk_shapes = tuple(sorted(shapes)) or (
            min(self.prefill_chunk, max_seq_len),
        )
        # -- speculative decoding (gofr_tpu.spec;
        # docs/advanced-guide/speculative-decoding.md) --------------------
        # A host-side n-gram/prompt-lookup drafter proposes up to
        # spec_draft tokens per decoding slot; ONE fused verify program
        # scores all draft+1 positions against the slot KV, samples each
        # with the regular top-k machinery, accepts the longest agreeing
        # prefix ON DEVICE (tail/cursors stay device-resident), and rolls
        # the KV cursor back past rejected rows. Greedy spec-on is
        # token-identical to spec-off; temperature>0 is
        # distribution-preserving (Leviathan rejection sampling for a
        # deterministic drafter). OFF by default: disabled, no verify
        # program exists and no scheduler path changes — a true no-op.
        if speculative is None:
            speculative = _os.environ.get("TPU_LLM_SPEC", "0") not in ("", "0")
        self.speculative = bool(speculative)
        if spec_draft is None:
            spec_draft = int(_os.environ.get("TPU_LLM_SPEC_DRAFT", "") or 0)
        if not spec_draft:
            from .spec import SPEC_DRAFT_DEFAULT

            spec_draft = SPEC_DRAFT_DEFAULT
        # verify transiently writes draft+1 rows past a slot's length;
        # every slot is built with 2*decode_chunk rows of slack beyond
        # max_seq_len, so the draft must fit it (dense scatters drop overflow,
        # but a silent clamp beats silent garbage)
        self.spec_draft = (
            max(1, min(int(spec_draft), 2 * decode_chunk))
            if self.speculative else 0
        )
        if lora_slots is None:
            lora_slots = int(_os.environ.get("TPU_LLM_LORA_SLOTS", "0") or 0)
        if getattr(cfg, "latent", False):
            why = latent_refusal(
                mesh=mesh, chunked=self.chunked, kv_paged=kv_paged,
                speculative=self.speculative, constrained=constrained,
                lora_slots=lora_slots,
            )
            if why:
                raise ValueError(why)
            constrained, kv_paged = False, True
        elif getattr(cfg, "mixed", False):
            why = mixed_refusal(
                mesh=mesh, chunked=self.chunked, speculative=self.speculative,
                lora_slots=lora_slots,
            )
            if why:
                raise ValueError(why)
        elif len(getattr(cfg, "group_sizes", ())) > 1 and (mesh is not None or lora_slots):
            raise ValueError(
                "a model of two layer groups (leading dense layers, then routed "
                "ones) serves on one chip and without LoRA slots: the sharding "
                "specs and the adapter tables are written for one stacked group"
            )
        # SLO-aware overload control (both optional, both mutable at
        # runtime): max_queue bounds requests waiting for a slot — beyond
        # it submit() raises EngineOverloaded (-> 429) instead of letting
        # p99 grow with an unbounded closed-loop queue; ttft_deadline_ms
        # sheds a request still queued when its first token could no
        # longer arrive in time (finish_reason "shed").
        self.max_queue = max_queue
        self.ttft_deadline = (
            ttft_deadline_ms / 1000.0 if ttft_deadline_ms else None
        )
        self.rejected = 0  # submit-time cap rejections
        self.shed = 0  # deadline sheds at admission
        self.deadline_cancels = 0  # mid-flight deadline cancellations
        # -- overload control (gofr_tpu.resilience.overload;
        # docs/advanced-guide/overload.md) --------------------------------
        # Per-client weighted fair queuing: _waiting is ordered
        # (priority class, ledger counter, submit order) instead of FIFO,
        # so a flood from one client cannot starve another's weighted
        # share. ReplicatedLLMEngine passes ONE shared ledger to every
        # replica (fleet-wide fairness); a bare engine builds its own.
        from .resilience import FairLedger, OverloadController

        if fair_queuing is None:
            fair_queuing = _os.environ.get("TPU_LLM_FAIR", "1") != "0"
        self.ledger = None
        if fair_queuing:
            self.ledger = (
                fair_ledger if fair_ledger is not None
                else FairLedger(fair_weights)
            )
        # Priority preemption: under interactive queue pressure a slotted
        # batch request is preempted — its slot freed NOW, its emitted
        # tokens folded into a continuation prompt and requeued (the PR 5
        # failover re-seed, so greedy streams resume token-identically).
        if preemption is None:
            preemption = _os.environ.get("TPU_LLM_PREEMPT", "1") != "0"
        self.preemption = bool(preemption)
        self.preemptions = 0  # batch slots taken back for interactive work
        # Adaptive shedding + brownout: predicted queue wait (queued
        # tokens / measured step throughput) drives early 429s with a
        # computed Retry-After, and sustained pressure clamps batch-class
        # max_new_tokens BEFORE anything is shed (degrade, then shed).
        if shed_predicted_wait_s is None:
            shed_predicted_wait_s = float(
                _os.environ.get("TPU_LLM_SHED_WAIT_S", "0") or 0.0
            )
        if brownout_wait_s is None:
            brownout_wait_s = float(
                _os.environ.get("TPU_LLM_BROWNOUT_WAIT_S", "0") or 0.0
            )
        if brownout_max_new is None:
            brownout_max_new = int(
                _os.environ.get("TPU_LLM_BROWNOUT_MAX_NEW", "0") or 0
            )
        if brownout_hold_s is None:
            brownout_hold_s = float(
                _os.environ.get("TPU_LLM_BROWNOUT_HOLD_S", "2.0") or 0.0
            )
        self.overload = OverloadController(
            shed_wait_s=shed_predicted_wait_s,
            brownout_wait_s=brownout_wait_s,
            brownout_max_new=brownout_max_new,
            brownout_hold_s=brownout_hold_s,
        )
        self.sheds_predicted = 0  # predicted-wait 429s
        self.brownout_clamped = 0  # batch requests clamped while browned out
        self._tput_ema: float | None = None  # measured tokens/s (EMA)
        # -- resilience (gofr_tpu.resilience; docs/advanced-guide/resilience.md)
        from .resilience import Heartbeat, default_injector

        # fault-injection seams: disarmed cost is one dict lookup per
        # check; tests/chaos pass their own injector, production uses the
        # process default (armable via TPU_LLM_FAULTS)
        self.faults = fault_injector if fault_injector is not None else default_injector()
        # heartbeats the step watchdog monitors: the scheduler's blocking
        # dispatch section and the collector's device fetch
        self._hb_dispatch = Heartbeat()
        self._hb_fetch = Heartbeat()
        if step_watchdog_s is None:
            step_watchdog_s = float(
                _os.environ.get("TPU_LLM_STEP_WATCHDOG_S", "0") or 0.0
            )
        self.step_watchdog_s = max(0.0, float(step_watchdog_s))
        self.watchdog = None  # started after the engine threads
        # Numerical watchdog (docs/advanced-guide/resilience.md): trace
        # the finite_guard sentinel into every sampling program so
        # NaN/Inf logits become a replica death with reason "numerical"
        # instead of a garbage stream with status 200. On by default —
        # the on-device cost is one isfinite reduction per sampled row
        # and the sentinel rides fetches that happen anyway.
        if numeric_check is None:
            numeric_check = _os.environ.get("TPU_LLM_NUMERIC_CHECK", "1") != "0"
        self.numeric_check = bool(numeric_check)
        self.numerical_trips = 0  # non-finite logits -> replica death
        self.errored = 0  # requests finished "error"/"poison" (bake signal)
        self._draining = False  # drain(): admission closed, work finishes
        self._died = False  # _die ran (idempotence + stale-emission guard)
        self._die_guard = threading.Lock()
        self.died_reason: str | None = None
        # replica-failover seam: ReplicatedLLMEngine sets this; _die hands
        # it every recoverable in-flight/queued request instead of
        # error-draining them
        self.failover_hook = None
        self.logger = logger
        self.metrics = metrics
        self.tracer = tracer
        # kv_label doubles as the engine's metric/trace label (register_llm
        # passes the registered model name; replicas get a /rN suffix)
        self.label = kv_label
        # disaggregated serving role ("prefill" | "decode" | "" for a
        # colocated engine): rides the phase histograms as a `role` label
        # so TTFT/TPOT split per pool (docs/advanced-guide/
        # sharded-serving.md). Empty = no label, series unchanged.
        self.role = str(role)
        self._role_labels = {"role": self.role} if self.role else {}
        # model-version label (docs/advanced-guide/rollouts.md): which
        # weight set this engine serves. Streams pin to it across
        # failover; the wide-event line and the per-version request
        # counter carry it.
        self.version = str(version)
        self.disconnect_cancels = 0  # dead-peer cancellations (edges)
        if metrics is not None:
            _register_phase_metrics(metrics)
            metrics.set_gauge(
                "app_llm_model_version_info", 1.0,
                model=self.label, version=self.version,
            )
        # -- per-tenant SLO engine (docs/advanced-guide/
        # observability-serving.md#slo) ----------------------------------
        # Declared targets -> goodput counters + 5m/1h burn-rate gauges.
        # `slo` is an SLOPolicy/dict from register_llm (which merges the
        # TPU_LLM_SLO_* config knobs with per-model overrides); a bare
        # engine falls back to the process env so tests and scripts can
        # arm it without an app. None/inactive -> zero per-request cost.
        from .metrics.slo import SLOPolicy, SLOTracker

        policy = SLOPolicy.coerce(slo)
        if policy is None:
            policy = SLOPolicy(
                ttft_ms=float(_os.environ.get("TPU_LLM_SLO_TTFT_MS", "") or 0) or None,
                tpot_ms=float(_os.environ.get("TPU_LLM_SLO_TPOT_MS", "") or 0) or None,
                availability=float(
                    _os.environ.get("TPU_LLM_SLO_AVAILABILITY", "") or 0
                ) or None,
            )
        self.slo = None
        if policy.active():
            self.slo = SLOTracker(
                policy, metrics, self.label,
                tenant_overrides={
                    str(t): SLOPolicy.coerce(p)
                    for t, p in (slo_tenants or {}).items()
                },
            )
        # recent-window phase samples (seconds) for stats()/debug — exact
        # p50/p99 over the last ~512 observations, deque-append cheap
        from .metrics import RollingWindow

        self._phases = {
            "queue_wait": RollingWindow(),
            "ttft": RollingWindow(),
            "time_per_output_token": RollingWindow(),
            "decode_step": RollingWindow(),
            # unified-step dispatch->fetch wall (chunked scheduler only)
            "step": RollingWindow(),
        }
        # MFU/roofline accounting: analytic model FLOPs computed ONCE from
        # the architecture (gofr_tpu.profiling.mfu), combined per prefill
        # wave / decode chunk with measured dispatch->fetch wall time and
        # the device peak. Windows exist even without a metrics manager so
        # stats()["mfu"] and bench.py work on bare engines.
        self._mfu_mod = mfu_mod
        self._costs = mfu_mod.model_costs(cfg, quantized=quantize)
        # peaks of the device this engine runs on (its pinned replica
        # device or its mesh's chips), not of whichever device is first
        if mesh is not None:
            _dev = mesh.devices.flat[0]
        else:
            _dev = device if device is not None else jax.devices()[0]
        self._peak_flops = mfu_mod.device_peak_flops(_dev.platform, _dev.device_kind)
        self._hbm_bw = mfu_mod.device_hbm_bandwidth(_dev.platform, _dev.device_kind)
        self._n_chips = int(mesh.size) if mesh is not None else 1
        self._mfu_windows = {"prefill": RollingWindow(), "decode": RollingWindow()}
        self._roofline_windows = {"prefill": RollingWindow(), "decode": RollingWindow()}
        self._tok_chip_window = RollingWindow()
        self._registry = default_registry()
        self.warmup_s: float | None = None
        self._wide_events: list[dict] = []  # appended under _lock, drained outside
        # -- incident flight recorder (gofr_tpu.flightrec; docs/advanced-
        # guide/incident-debugging.md) -----------------------------------
        # Per-request black-box ring (started at submit, finalized on
        # every terminal path incl. _die), an incident bundle dumper
        # (inert unless GOFR_BLACKBOX_DIR / blackbox_dir is set), and
        # rolling-baseline perf-anomaly detectors whose flag transitions
        # are themselves bundle triggers.
        from .flightrec import (
            WIDE_EVENTS_KEEP,
            AnomalyDetector,
            BlackboxDumper,
            FlightRecorder,
        )

        self.flightrec = FlightRecorder(flight_records, redact=flight_redact)
        self.blackbox = BlackboxDumper(
            blackbox_dir, min_interval_s=blackbox_interval_s,
            logger=logger, metrics=metrics, label=self.label,
        )
        if self.slo is not None:
            # the fast-burn 0 -> 1 flip is a bundle trigger: capture the
            # engine while the budget-burning requests are still visible
            self.slo.on_fast_burn = lambda: self._incident(
                "slo_fast_burn",
                reason=f"error-budget fast burn tripped on {self.label}",
            )
        if anomaly is None:
            anomaly = _os.environ.get("TPU_LLM_ANOMALY", "1") not in ("", "0")
        self.anomaly = None
        if anomaly:
            self.anomaly = AnomalyDetector(
                metrics, self.label,
                on_flag=lambda sig, val, mean: self._incident(
                    "anomaly",
                    reason=(
                        f"{sig} sustained deviant: {val:.3f} vs baseline "
                        f"mean {mean:.3f}"
                    ),
                ),
            )
        # wide-event sampling (satellite of the flight recorder): 1-in-N
        # request lines under load — incident/error/failover lines always
        # emit. The FULL stream lands in _wide_retained either way, so a
        # bundle's wide-event section never has sampling holes.
        if wide_event_sample is None:
            wide_event_sample = int(
                _os.environ.get("TPU_LLM_WIDE_EVENT_SAMPLE", "") or 1
            )
        self._wide_sample = max(1, int(wide_event_sample))
        self._wide_seq = 0
        self._wide_retained: deque = deque(maxlen=WIDE_EVENTS_KEEP)
        # -- goodput ledger + per-tenant usage metering (gofr_tpu.goodput;
        # docs/advanced-guide/cost-accounting.md) -------------------------
        # Chip-time attribution at the fetch seam (every device window
        # split across its lanes into the waste taxonomy), rolling
        # per-tenant usage windows (shared fleet-wide when replicated
        # serving passes usage_meter=), and hard token-rate quotas
        # enforced at admission with a Retry-After priced from the
        # tenant's measured window.
        from .goodput import GoodputLedger, QuotaGate, UsageMeter
        from .goodput import parse_quota_spec as _parse_quota

        if goodput is None:
            goodput = _os.environ.get("TPU_LLM_GOODPUT", "1") not in ("", "0")
        self.goodput = None
        self.usage = None
        self.quota = None
        self.quota_sheds = 0
        if goodput:
            if usage_window_s is None:
                usage_window_s = float(
                    _os.environ.get("TPU_LLM_USAGE_WINDOW_S", "") or 60.0
                )
            self.usage = (
                usage_meter if usage_meter is not None
                else UsageMeter(window_s=usage_window_s)
            )
            self.goodput = GoodputLedger(
                metrics=metrics, label=self.label,
                version_fn=lambda: self.version, usage=self.usage,
            )
            q = _parse_quota(_os.environ.get("TPU_LLM_TENANT_QUOTA_TOK_S"))
            for tenant, rate in (quotas or {}).items():
                try:
                    q[str(tenant)] = float(rate)
                except (TypeError, ValueError):
                    continue
            self.quota = QuotaGate(q, self.usage)
        # KV layout/residency/reuse policy lives in the kvcache subsystem:
        # rolling ring for sliding-window models (slot memory O(window)),
        # dense slab otherwise; optional prompt-prefix reuse at admission.
        # kv_label distinguishes metric series: register_llm passes the
        # registered model name, and replicated serving suffixes a replica
        # index — otherwise N replicas' resident-bytes gauges share one
        # label set and clobber each other on /metrics.
        # UNIFIED capacity accounting: every append width one device
        # program can dispatch — the decode chunk, the chunked-prefill
        # chunk shapes, the speculative verify width — goes to the
        # CacheManager ONCE as append_widths; the rolling-ring capacity
        # and the paged block reservation both derive from the same
        # max() there, replacing the per-feature slack arithmetic the
        # chunked-prefill and speculative-verify paths each used to
        # layer onto the ring bound.
        append_widths = [decode_chunk]
        if self.chunked:
            append_widths.extend(self.chunk_shapes)
        if self.speculative:
            append_widths.append(self.spec_draft + 1)
        if kv_paged is None:
            from .kvcache import paged_default

            kv_paged = paged_default()
        self.kv = CacheManager(
            cfg, slots, max_seq_len, decode_chunk,
            window=kv_window, prefix_cache_mb=prefix_cache_mb,
            append_widths=tuple(append_widths),
            paged=kv_paged, block=kv_block, pool_blocks=kv_pool_blocks,
            kv_int8=kv_int8, session_mb=session_mb,
            host_cache_mb=host_cache_mb,
            metrics=metrics, model=kv_label,
        )
        self._sharded = mesh is not None and param_specs is not None
        self.mesh = mesh if self._sharded else None
        self.attention_paths = self._attention_paths()
        # Under a TP mesh every program hands its small state (chain tail,
        # masks, first tokens) back REPLICATED over the mesh, and an AOT
        # executable accepts only the input shardings it was compiled
        # for. _rep gives warm-up stand-ins, and stored logits whose
        # sharding GSPMD chose, that same placement; identity on one chip.
        if self._sharded:
            from jax.sharding import NamedSharding, PartitionSpec as _P0

            _replicated = NamedSharding(mesh, _P0())
            self._rep = lambda x: jax.device_put(x, _replicated)
        else:
            self._rep = lambda x: x
        # tensor-parallel degree (docs/advanced-guide/sharded-serving.md):
        # the "model" axis of the replica's submesh; 1 for single-chip.
        # Exported as app_llm_tp_degree so dashboards see the fleet shape.
        self.tp_degree = (
            int(dict(mesh.shape).get("model", 1)) if self._sharded else 1
        )
        # Collective-compute overlap (ROADMAP raw-speed side quest; ISSUE
        # 12): the sharded DECODE path stores weights sharded and
        # all-gathers the NEXT layer's shard while the current layer's
        # matmul runs (parallel.sharding.replicate_gather through
        # models.transformer._layer_scan). Also the numerics lever that
        # pins TP==TP1 greedy token equality: gathered-weight compute has
        # no partial-product psum, hence no reduction-order drift.
        if tp_overlap is None:
            tp_overlap = _os.environ.get("TPU_LLM_TP_OVERLAP", "1") != "0"
        self.tp_overlap = bool(tp_overlap) and self.tp_degree > 1
        if metrics is not None:
            metrics.set_gauge(
                "app_llm_tp_degree", float(self.tp_degree), model=kv_label,
            )
            metrics.set_gauge(
                "app_llm_moe_experts",
                float(getattr(cfg, "n_experts", 0) or 0), model=kv_label,
            )
        self._tp_gather = None
        if self.tp_overlap:
            from .parallel.sharding import replicate_gather

            self._tp_gather = replicate_gather(mesh)
        # Place first, quantize second: the quantize program then runs on
        # this engine's own device or mesh (not on device 0 whichever
        # replica it is for) and a sharded tree is quantized shard-wise.
        # An already-int8 tree (models.quant.init_params_quantized — the
        # only way a 7B model is built on one 16 GB chip) skips the
        # program: a jitted identity could still copy the tree in HBM.
        from .models.quant import (
            is_quantized, quantize_param_specs, quantize_params,
        )

        quantize_now = quantize and not is_quantized(params)
        if mesh is not None and param_specs is not None:
            from .parallel.sharding import shard_params

            t0_gather = time.perf_counter()
            if quantize and not quantize_now:
                param_specs = quantize_param_specs(param_specs)
            params = shard_params(params, mesh, param_specs)
            # initial shard placement: the weight-scatter wall a replica
            # pays once at build (phase label mirrors the per-layer
            # gathers the decode path then overlaps)
            if metrics is not None:
                metrics.record_histogram(
                    "app_llm_collective_seconds",
                    time.perf_counter() - t0_gather,
                    model=kv_label, phase="weight_shard",
                )
        elif device is not None:
            # replica pinning (data-parallel serving): committing params to
            # a device makes every jitted call and its donated state follow
            params = jax.device_put(params, device)
        else:
            params = jax.device_put(params)
        if quantize_now:
            # int8 weights halve the HBM stream decode is bound by
            quant_kw = {}
            if self._sharded:
                quant_kw["out_shardings"] = jax.tree.map(
                    lambda spec: jax.sharding.NamedSharding(mesh, spec),
                    quantize_param_specs(param_specs),
                    is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
                )
            params = instrument_jit(
                "llm.quantize_params",
                lambda p: quantize_params(p, cfg.dtype),
                model=kv_label, metrics=metrics, **quant_kw,
            )(params)

        # -- multi-tenant LoRA adapter pool (gofr_tpu.lora;
        # docs/advanced-guide/multi-tenancy.md) ---------------------------
        # lora_slots > 0 merges stacked zero-initialized (A, B) tables and
        # a per-slot adapter-id vector INTO the params pytree, so the same
        # fused programs serve every tenant via a batched gather — no
        # per-tenant compile, and a hot-load is one table-slice rewrite.
        # Chunked-scheduler only, like constrained decoding: the wave path
        # packs prefill rows != slots, so adapter ids cannot ride it.
        if lora_rank is None:
            lora_rank = int(_os.environ.get("TPU_LLM_LORA_RANK_MAX", "8") or 8)
        self.lora_slots = max(0, int(lora_slots)) if self.chunked else 0
        self.lora_rank = max(1, int(lora_rank))
        if self.lora_slots:
            from . import lora as lora_mod
            from .lora import AdapterPool

            self._lora_mod = lora_mod
            tables = lora_mod.zero_tables(cfg, self.lora_slots, self.lora_rank)
            aids0 = jnp.zeros((slots,), jnp.int32)
            if self._sharded:
                from jax.sharding import NamedSharding, PartitionSpec as _P

                from .parallel.sharding import shard_params as _shard

                tables = _shard(tables, mesh, lora_mod.table_specs(tables))
                aids0 = jax.device_put(aids0, NamedSharding(mesh, _P(None)))
            elif device is not None:
                tables = jax.device_put(tables, device)
                aids0 = jax.device_put(aids0, device)
            else:
                tables = jax.device_put(tables)
                aids0 = jax.device_put(aids0)
            # merged AFTER the quantize block on purpose: the tables stay
            # f32 (lora.zero_tables) and quantize_params only touches
            # _QUANT_KEYS, but this ordering makes it structural
            params = {
                **params,
                "layers": {**params["layers"], **tables},
                "aids": aids0,
            }
            self._lora_pool = AdapterPool(self.lora_slots)
            self._aids_host = [0] * slots
            self._aids_dirty = False
            # staging programs compile lazily per table shape (6 targets x
            # (a, b)); the gid is traced so every load reuses them
            self._lora_set_ops: dict = {}
        self.params = params
        self.device = device

        # -- jitted programs (gofr_tpu.llm_programs: one dispatch each) ----
        # chunk, step and verify are each written once there, over this
        # engine's cache layout and, per dispatch, the plain or the grammar
        # sampler. The plain family and the wave path's programs exist from
        # here on under the names tests and tools reach them by (raw call
        # signatures: llm_programs.Programs._signature); the grammar family
        # is built at the first constrained dispatch (_ops).
        from .llm_programs import Programs

        self._programs = Programs(
            cfg, self.kv, slots=slots, decode_chunk=decode_chunk,
            chunk_shapes=(self.chunk_shapes if self.chunked else ()),
            spec_draft=self.spec_draft, mesh=self.mesh,
            tp_gather=self._tp_gather,
            kernel=self.attention_paths["decode"].split(" | ")[0] in (
                "pallas_paged", "pallas_mla_paged",
            ),
            numeric_check=self.numeric_check, label=self.label,
            metrics=metrics,
        )
        self._prefill_op = self._programs.prefill_op
        self._insert_many = self._programs.insert_many
        self._admit_update = self._programs.admit_update
        self._hit_first_op = self._programs.hit_first_op
        self._seed_op = self._programs.seed_op
        self._chunk_short = self._programs.chunk_short
        self._chunk_ops, self._step_ops, self._verify_op = (
            self._programs.family(grammar=False)
        )
        self.drafter = None
        if self.speculative:
            from .spec import NGramDrafter

            self.drafter = NGramDrafter()
        self._rng = jax.random.PRNGKey(0)

        if self.kv.paged:
            # ONE block pool backs every slot; per-slot block tables map
            # logical rows to pool rows. self.cache keeps the KVCache
            # shape contract (k/v/length) so the donation chains and
            # state threading below are identical to the contiguous
            # layout — only the k/v geometry differs.
            self.cache, self._kv_scales = self.kv.pool_arrays(jnp)
            if self._kv_scales is None:
                self._kv_scales = jnp.zeros((0,), jnp.float32)
            self._tables_dev = jnp.zeros(
                (slots, self.kv.table_cols), jnp.int32
            )
            if device is not None:
                self.cache = jax.device_put(self.cache, device)
                self._kv_scales = jax.device_put(self._kv_scales, device)
                self._tables_dev = jax.device_put(self._tables_dev, device)
        else:
            self._kv_scales = None
            self._tables_dev = None
            self.cache = self.kv.init_cache(slots)
            if device is not None:
                self.cache = jax.device_put(self.cache, device)
        self._kv_sharding = None
        if self._sharded:
            # KV sharded along heads where the model allows, replicated
            # under MQA (parallel.sharding.kv_specs) — committed once
            # here; donation keeps the layout through every step/chunk/
            # verify program, so the pool never silently migrates to one
            # chip of the submesh.
            from jax.sharding import NamedSharding

            from .parallel.sharding import kv_specs

            self._kv_sharding = NamedSharding(
                mesh, kv_specs(cfg, mesh, paged=self.kv.paged)
            )
            self.cache = self.cache._replace(
                k=jax.device_put(self.cache.k, self._kv_sharding),
                v=jax.device_put(self.cache.v, self._kv_sharding),
            )
        # host-side upper bound on each slot's device length (paged block
        # allocation watermark; conservative under speculative pipelining)
        self._kv_hi = [0] * slots
        # end-of-turn session publishes deferred from the collector to the
        # scheduler thread (the only thread allowed to dispatch device
        # work against the donated pool): (slot, request) pairs
        self._session_pub: deque = deque()
        # host-work closures other threads queue for the SCHEDULER thread
        # (KV handoff export/import dispatch against the donated pool):
        # (fn, box) pairs — box carries done-event/result/error back
        self._sched_work: deque = deque()
        self._slot_req: list[GenRequest | None] = [None] * slots
        # device-resident batch state: chain tail, active mask, temps.
        # active is never cleared on retire (a stale True only advances a
        # garbage cursor in an unowned slot, clamped in-bounds) — clearing
        # would cost a host->device transfer per completion.
        self._tail = jnp.zeros((slots,), jnp.int32)
        self._active = jnp.zeros((slots,), bool)
        self._temps = jnp.zeros((slots,), jnp.float32)
        # -- grammar-constrained decoding (gofr_tpu.structured;
        # docs/advanced-guide/structured-decoding.md) ---------------------
        # Per-slot DFA state lives on device like the chain tail (the
        # fused chunk advances it token-by-token, and pipelined
        # dispatches must chain it without a host fetch); the resident
        # grammar table and the per-slot grammar ids are host-owned.
        # Chunked scheduler only: the wave path samples first tokens in
        # programs the mask does not ride.
        if constrained is None:
            constrained = _os.environ.get("TPU_LLM_CONSTRAINED", "1") != "0"
        self.constrained = bool(constrained) and self.chunked
        if constrained_grammars is None:
            constrained_grammars = int(
                _os.environ.get("TPU_LLM_CONSTRAINED_GRAMMARS", "8") or 8
            )
        self._g_cap = max(1, int(constrained_grammars))
        self._grammars: list[Any] = []  # resident TokenGrammars (index=gid)
        self._g_refs: list[int] = []  # live requests holding each gid
        self._gr_dev = None  # padded [G, Smax, V] device transition table
        self._gstate = jnp.zeros((slots,), jnp.int32)
        self.constrained_requests = 0  # lifetime constrained submissions
        self.spec_proposed_c = 0  # spec drafts proposed for constrained lanes
        self.spec_accepted_c = 0  # spec drafts accepted for constrained lanes
        self.adapter_requests = 0  # lifetime adapter-attributed submissions
        if device is not None:
            (
                self._tail, self._active, self._temps, self._gstate,
                self._rng,
            ) = jax.device_put(
                (
                    self._tail, self._active, self._temps, self._gstate,
                    self._rng,
                ),
                device,
            )
        self._admit_q: queue.Queue[GenRequest | None] = queue.Queue()
        self._waiting: list[GenRequest] = []  # drained queue, scheduler-only
        self.submitted = 0  # total requests ever submitted (router telemetry)
        self._admitting = 0  # sliced out of _waiting, not yet slotted
        # dispatch telemetry (cheap counters; exposed via stats() so a
        # saturation run reveals occupancy and wave-size efficiency)
        self._stat_chunks = 0  # decode chunks dispatched
        self._stat_chunk_steps = 0  # decode steps dispatched
        self._stat_active_sum = 0  # sum of active slots at chunk dispatch
        self._stat_waves: dict[int, int] = {}  # prefill wave width -> count
        self._stat_wave_reqs = 0  # requests admitted via waves
        self._stat_steps = 0  # unified steps dispatched (chunked scheduler)
        self._stat_step_tokens = 0  # tokens packed into unified steps
        self._stat_steps_d0 = 0  # steps that went out without their decode chunk
        # speculative-decoding telemetry (gofr_tpu.spec)
        self.spec_steps = 0  # verify dispatches
        self.spec_proposed = 0  # draft tokens proposed
        self.spec_accepted = 0  # draft tokens accepted
        self.spec_plain = 0  # verify lanes run with zero draft (plain decode)
        self._spec_hold = 0  # plain-chunk burst left before the next probe
        self._spec_rr = 0  # budget-cut rotation cursor (verify slot fairness)
        self._prefilling: deque[GenRequest] = deque()  # resident, not decoding
        self._load_tokens = 0  # outstanding token estimate (router weighting)
        self._last_submit_t: float | None = None
        self._ema_gap: float | None = None  # EMA inter-arrival (rate estimate)
        self._stop = False
        # in-flight device work, oldest first. Entries snapshot the REQUEST
        # objects they serve, so a slot can be reassigned while older
        # chunks still carry its previous request's tokens:
        #   ("chunk", toks_dev [K,S], [req-or-None per slot], k, info)
        #   ("prefill", first_dev [nb], [(slot, req), ...], info)
        # every kind's last element is its info dict, which holds the
        # program's step record (STEP_FIELDS) from dispatch to emit
        self._inflight: deque = deque()
        self._step_seq = 0
        self._stat_admitted = 0  # slots assigned: sched.admit's count per pass
        self._step_log: deque = deque(maxlen=STEP_LOG_LEN)
        # what the routed experts did, summed over the paged programs' records
        # (stats()["moe"]): [pairs, experts touched, rows per expert...]
        _E = int(getattr(cfg, "n_experts", 0) or 0)
        # (a program that holds a share of the experts counts its own, and
        # every pair its routers chose as one entry more: moe_stats_width)
        self._moe_held = int(getattr(cfg, "held_experts", _E) or 0)
        share = bool(getattr(cfg, "moe_held_experts", 0))
        self._moe_routed_at = -1 if share else 0  # where the vector holds the routed pairs
        self._moe_totals = np.zeros((2 + self._moe_held + share,), np.int64)
        self._moe_layer_calls = 0
        self._moe_layers = cfg.group_sizes[-1] if _E else 0  # the routed group is the last
        self.moe_path = self._moe_path() if _E else None
        # Two engine threads: the SCHEDULER owns every device dispatch
        # (admission prefills, inserts, decode chunks); the COLLECTOR owns
        # the blocking device->host fetches and token emission. One
        # thread doing both stalls dispatch behind every fetch and leaves
        # the device idle.
        self._lock = threading.RLock()
        self._work_cv = threading.Condition(self._lock)  # inflight appended
        self._kick = threading.Event()  # scheduler wake: submit/slots freed
        self._processing: tuple | None = None  # entry popped, not yet emitted
        self._jumped = False  # prefill-priority ration (one per chunk)
        self._fetch_fail_streak = 0  # consecutive collector fetch failures
        self._jnp = jnp
        self._jax = jax

        if warmup:
            self._warm()
        self._thread = threading.Thread(
            target=self._schedule_loop, name="llm-engine-sched", daemon=True
        )
        self._collector = threading.Thread(
            target=self._collect_loop, name="llm-engine-collect", daemon=True
        )
        self._thread.start()
        self._collector.start()
        if self.step_watchdog_s > 0:
            from .resilience import StepWatchdog

            # started AFTER _warm: beats wrap serving dispatch/fetch only,
            # so cold compiles can never trip a seconds-scale threshold
            self.watchdog = StepWatchdog(self, self.step_watchdog_s)

    def _moe_path(self) -> str:
        """The grouped matmul the routed FFN traces (stats()["moe"]["experts"]),
        decided by the predicate ops.grouped consults at trace time: the
        Pallas kernel over the int8 stacks, or `jax.lax.ragged_dot` with the
        reason. ragged_dot widens a layer's whole int8 stack before every
        call, so on a TPU that fallback is listed among the registry's
        degraded programs (snapshot()["degraded"]) and not only named."""
        import jax

        from .ops.grouped import grouped_kernel_why_not

        d, fe = self.cfg.d_model, self.cfg.moe_d_ff or self.cfg.d_ff
        # 16: the narrowest row tile the routed FFN lays out on the TPU
        why = grouped_kernel_why_not(d, fe, 16) or grouped_kernel_why_not(fe, d, 16)
        if not why:
            return "pallas_grouped"
        if jax.default_backend() == "tpu":
            self._registry.note_degraded("moe_grouped_matmul", self.label, why)
        return f"ragged_dot ({why})"

    def _attention_paths(self) -> dict:
        """The attention implementation each program family traces —
        decode, and prefill per chunk shape — decided by the predicates
        the ops themselves consult at trace time (ops.attention). A shape
        that cannot take its Pallas kernel carries the reason, so a
        fallback to XLA attention is never silent (stats()["attention"]).
        Beside a paged-decode kernel, the tile it derived from the pool's
        shape (pages and tokens of every local kv head per step) and what
        its pool operand is (ops.attention.paged_pool_operand: the stack
        as this engine stores it, or the call is refused)."""
        from .ops.attention import (
            chunk_prefill_why_not_flash, flash_why_not, paged_decode_pages,
            paged_kernel_why_not, paged_pool_operand,
        )

        hd = self.cfg.head_dim

        def flash_or(why: str) -> str:
            return f"xla ({why})" if why else "pallas_flash"

        if getattr(self.cfg, "latent", False):
            # one latent row for every head: its own paged-decode kernel, and
            # absorbed XLA attention over the gathered rows for prompt chunks
            from .ops.attention import mla_kernel_why_not

            (_, C), (_, R) = self.kv.row_shapes
            why = mla_kernel_why_not(C, R, self.kv.block)
            decode = f"xla_gather ({why})" if why else "pallas_mla_paged"
            paths = {
                "decode": decode,
                "prefill": {
                    c: "xla_latent_absorbed (no latent flash kernel)"
                    for c in self.chunk_shapes
                },
            }
            if not why:
                pages = paged_decode_pages(
                    self.kv.block, 1, C, self.cfg.dtype, self.kv.table_width
                )
                paths["decode_tile"] = {"pages": pages, "tokens": pages * self.kv.block}
                paths["pool_operand"] = paged_pool_operand(self.kv.pool_shapes()[0], C)
            return paths
        if self.kv.paged:
            why = paged_kernel_why_not(hd, self.kv.block)
            decode = f"xla_gather ({why})" if why else "pallas_paged"
        else:
            decode = "xla_ring" if self.kv.ring else "xla_dense"
        if self.kv.mixed:
            # Two kinds of layer, each named: the full layers' path first (as
            # a stack of one kind names its own), then the window layers'.
            # Decode is ONE kernel for both (the window is its band, under
            # the name paged_decode_window); a prompt chunk meets the full
            # layers' gathered view (flash where the shapes allow) and the
            # window layers' last rows as a ring, by XLA.
            w = self.cfg.window
            paths = {
                "decode": f"{decode} | window: {decode.split(' ')[0]} band {w}",
                "prefill": {
                    c: flash_or(chunk_prefill_why_not_flash(c, self.kv.capacity, hd))
                    + f" | window: xla ring of {self.kv.window_ring}"
                    for c in self.chunk_shapes
                },
            }
            if not why:
                pages = paged_decode_pages(
                    self.kv.block, self.cfg.n_kv_heads, hd, self.cfg.dtype, self.kv.table_width
                )
                paths["decode_tile"] = {"pages": pages, "tokens": pages * self.kv.block}
                paths["pool_operand"] = " | window: ".join(
                    paged_pool_operand(shape, hd) for shape in self.kv.pool_shapes()[0]
                )
            return paths
        if not self.chunked:
            prefill = {
                b: flash_or(flash_why_not(b, b, hd, 128, 128))
                for b in self.prefill_buckets
            }
        elif self.kv.ring:
            prefill = {c: "xla (rolling ring cache)" for c in self.chunk_shapes}
        else:
            prefill = {
                c: flash_or(chunk_prefill_why_not_flash(c, self.kv.capacity, hd))
                for c in self.chunk_shapes
            }
        paths = {"decode": decode, "prefill": prefill}
        if decode == "pallas_paged":
            pages = paged_decode_pages(
                self.kv.block, self.cfg.n_kv_heads, hd,
                "int8" if self.kv.int8 else self.cfg.dtype,
                self.kv.table_width, hq=self.cfg.n_heads, mesh=self.mesh,
            )
            paths["decode_tile"] = {"pages": pages, "tokens": pages * self.kv.block}
            paths["pool_operand"] = paged_pool_operand(self.kv.pool_shapes()[0], hd)
        return paths

    # -- public API -------------------------------------------------------
    def submit(self, req: GenRequest) -> GenRequest:
        if self._stop:
            raise EngineStoppedError("engine stopped")
        if self._draining:
            raise EngineDraining("engine draining (rolling deploy)")
        plen = len(req.prompt_tokens)
        if plen >= self.max_seq_len:
            raise ValueError(
                f"prompt of {plen} tokens exceeds max_seq_len {self.max_seq_len}"
            )
        # max_seq_len is what a request may hold: prompt + output. The
        # cursor's chunk-granular overshoot and the end-of-chunk merge
        # write into the two chunks of slack every slot is built with on
        # top of it (CacheManager.slot_rows), never over live rows.
        room = self.max_seq_len - plen
        # emitted discounts work already done — a failover continuation
        # re-submits with its history folded into the prompt, and only
        # the REMAINING tokens need decode room (emitted == 0 for fresh
        # requests, so this is the original cap there)
        if req.max_new_tokens - req.emitted > room:
            req.max_new_tokens = room + req.emitted
            req.capped = True
        if self.kv.paged:
            # a request whose worst case exceeds the WHOLE pool could
            # never be admitted — reject now instead of queueing forever
            # (pool-pressure queueing is for requests that fit eventually)
            need = self.kv.blocks_for(
                self.kv.reserve_tokens(plen, req.max_new_tokens)
            )
            if need > self.kv.pool.n_blocks:
                raise ValueError(
                    f"request needs {need} KV blocks, pool holds "
                    f"{self.kv.pool.n_blocks} (raise kv_pool_blocks / "
                    "TPU_LLM_KV_POOL_BLOCKS)"
                )
        # -- grammar-constrained decoding (gofr_tpu.structured;
        # docs/advanced-guide/structured-decoding.md) ---------------------
        if req.grammar is not None:
            if not self.constrained:
                raise ValueError(
                    "grammar-constrained decoding requires the chunked "
                    "scheduler (step_token_budget > 0) and "
                    "TPU_LLM_CONSTRAINED=1"
                )
            g = req.grammar
            if req.eos_token < 0:
                # the grammar's completion transition IS the eos: without
                # it the stream would run past the closed value into
                # dead-state garbage
                req.eos_token = g.eos_id
            elif req.eos_token != g.eos_id:
                raise ValueError(
                    f"request eos_token {req.eos_token} != grammar eos "
                    f"{g.eos_id} (the grammar closes the stream)"
                )
        # -- overload control (docs/advanced-guide/overload.md) -----------
        # Anything except the literal "batch" is interactive: the edges
        # forward the X-GoFr-Priority header verbatim, and a typo must
        # degrade to the latency-safe class, not an error.
        req.priority = "batch" if req.priority == "batch" else "interactive"
        # -- per-tenant token-rate quota (gofr_tpu.goodput) ---------------
        # Hard admission ceiling on the MEASURED usage window (chargeback
        # truth, not fair-share weights): tenants without an explicit
        # quota fall through to fair-share only. Probes are exempt — an
        # over-quota tenant must not block the canary that protects it.
        # Checked before any reference is taken (grammar/adapter) so a
        # quota shed never leaks engine state.
        if self.quota is not None and self.quota.active() and not req.probe:
            tenant = req.client or (
                f"adapter:{req.adapter}" if req.adapter else "-"
            )
            quota_retry = self.quota.check(tenant)
            if quota_retry is not None:
                self.quota_sheds += 1
                if self.metrics is not None:
                    self.metrics.increment_counter(
                        "app_llm_quota_sheds_total",
                        model=self.label, tenant=tenant,
                    )
                raise EngineOverloaded(
                    f"tenant {tenant!r} over token-rate quota "
                    f"{self.quota.quota_for(tenant):.0f} tok/s "
                    "(TPU_LLM_TENANT_QUOTA_TOK_S)",
                    retry_after=quota_retry,
                )
        wait_s = self.predicted_wait_s()
        spec = self.faults.take("overload_pressure", self.label)
        if spec is not None:
            # chaos seam: this submit sees `delay` seconds of predicted
            # wait regardless of the real backlog (deterministic
            # brownout/shed in tier-1 and the CI overload smoke)
            self._count_fault("overload_pressure")
            wait_s = spec.delay if spec.delay > 0 else 3600.0
        self.overload.observe(wait_s)
        shed_after = self.overload.should_shed(wait_s)
        if shed_after is not None:
            # predicted-wait shed: reject EARLY, before max_queue, with
            # the time the backlog needs to drain — a client told WHEN to
            # come back offers its load where capacity will exist
            self.sheds_predicted += 1
            if self.metrics is not None:
                self.metrics.increment_counter(
                    "app_llm_sheds_predicted_total", model=self.label
                )
            raise EngineOverloaded(
                f"predicted queue wait {wait_s:.1f}s exceeds shed "
                f"threshold {self.overload.shed_wait_s:.1f}s",
                retry_after=shed_after,
            )
        # brownout degrade: clamp bounds the REMAINING tokens — a
        # failover/preemption continuation re-submits with emitted > 0
        # and must not land below what it already streamed
        clamp = self.overload.clamp(
            req.max_new_tokens - req.emitted, req.priority
        ) + req.emitted
        if clamp < req.max_new_tokens:
            req.max_new_tokens = clamp
            req.browned = True
            self.brownout_clamped += 1
        if self.max_queue is not None:
            depth = self._admit_q.qsize() + len(self._waiting) + self._admitting
            if depth >= self.max_queue:
                self.rejected += 1
                raise EngineOverloaded(
                    f"admission queue full ({depth} >= {self.max_queue})",
                    retry_after=wait_s if wait_s else 1.0,
                )
        if req.grammar is not None:
            # register AFTER every shed/reject path: a rejected submit
            # must not leak a resident-grammar reference. Registration
            # wall (dedup hit or table compile+ship) is the mask-prep
            # cost the app_llm_constrained_mask_seconds series tracks.
            t0g = time.perf_counter()
            with self._lock:
                req._g_id = self._register_grammar(req.grammar)
                self._g_refs[req._g_id] += 1
            self.constrained_requests += 1
            if self.metrics is not None:
                self.metrics.increment_counter(
                    "app_llm_constrained_requests_total", model=self.label
                )
                self.metrics.record_histogram(
                    "app_llm_constrained_mask_seconds",
                    time.perf_counter() - t0g, model=self.label,
                )
        if req.adapter:
            # acquire AFTER every shed/reject path (same discipline as
            # grammar registration above): a rejected submit must not
            # leak a pool reference. Re-resolve unconditionally — a
            # failover continuation arrives with a stale _aid from a
            # replica whose pool bound the name to a different gid.
            if not self.lora_slots:
                raise ValueError(
                    f"request names adapter {req.adapter!r} but this "
                    "engine has no adapter pool (lora_slots=0; set "
                    "TPU_LLM_LORA_SLOTS)"
                )
            with self._lock:
                try:
                    req._aid = self._lora_pool.acquire(req.adapter)
                except KeyError:
                    raise UnknownAdapterError(
                        req.adapter, self._lora_pool.resident()
                    ) from None
            # default billing identity: un-attributed tenant traffic
            # bills to the adapter's pseudo-client so per-adapter quotas
            # (ledger.set_weight at register time) take effect without
            # every caller threading a client id
            if not req.client:
                req.client = f"adapter:{req.adapter}"
            self.adapter_requests += 1
            if self.metrics is not None:
                self.metrics.increment_counter(
                    "app_llm_adapter_requests_total", model=self.label,
                    adapter=req.adapter,
                )
        else:
            req._aid = 0
        now = time.perf_counter()
        req.submitted_at = now
        req.phase = "queued"
        # version stamp: once this request has emitted a token, failover
        # re-dispatch pins to this model version (no mixed-version stream)
        req.engine_version = self.version
        # continuations (failover re-submits) carry engine-side spec
        # state from their previous replica; it is meaningless here
        req._spec_pending = []
        req._spec_inflight = 0
        if self.tracer is not None and req.span is None:
            # span is None except for failover continuations, whose
            # llm.request span from the original submit stays open across
            # replicas — a second start would orphan the first
            # Contextvar capture happens HERE, on the submitting thread —
            # the scheduler/collector threads that serve the request never
            # see the caller's context, so every later phase span is
            # parented through the ids captured now. An EXPLICIT
            # traceparent on the request outranks the contextvar: it is a
            # deliberate re-parent by infrastructure code (the disagg
            # journey span, batch workers, failover seams) that may run
            # on a thread where someone else's span is still live.
            from .tracing import current_span, parse_traceparent

            link = parse_traceparent(req.traceparent)
            if link is None:
                parent = current_span()
                if parent is not None and parent.end_ns == 0:
                    link = (parent.trace_id, parent.span_id)
            req.span = self.tracer.start_detached_span(
                "llm.request", parent=link,
                attributes={
                    "llm.model": self.label,
                    "llm.request_id": req.id,
                    "llm.prompt_tokens": plen,
                    "llm.max_new_tokens": req.max_new_tokens,
                },
            )
            if req.journey_id is None:
                req.journey_id = req.span.trace_id
        elif self.tracer is not None and (req.deaths or req.retries or req.preempted):
            # failover continuation landing on a new replica: the original
            # llm.request span stays open (same trace — the journey_id is
            # stable across kills), and this hop gets its own continuation
            # span LINKED to the original so a 3-hop failover reads as one
            # journey even in link-aware external backends.
            req.hop += 1
            t_ns = time.time_ns()
            self.tracer.record_span(
                "llm.continuation",
                trace_id=req.span.trace_id,
                parent_id=req.span.span_id,
                start_ns=t_ns, end_ns=t_ns,
                attributes={
                    "llm.model": self.label,
                    "llm.request_id": req.id,
                    "llm.hop": req.hop,
                    "llm.kind": "failover",
                    "llm.deaths": req.deaths,
                    "llm.preempted": req.preempted,
                    "llm.emitted": req.emitted,
                },
                links=[(req.span.trace_id, req.span.span_id)],
            )
        if req.journey_id is None and req.span is not None:
            req.journey_id = req.span.trace_id
        self.submitted += 1  # routing/diagnostic counter (GIL-atomic enough)
        with self._lock:
            # outstanding-token estimate for the replica router: prompt
            # remainder + expected REMAINING decode, credited back as
            # chunks append and tokens emit (load_tokens()). max_new
            # minus emitted, not max_new: a failover continuation
            # re-submits with emitted > 0, and billing the already-
            # emitted tokens again would overweight the replica for work
            # nobody will do — multi-token speculative spans make that
            # drift material (docs/advanced-guide/speculative-decoding.md)
            req._load_acct = plen + max(0, req.max_new_tokens - req.emitted)
            self._load_tokens += req._load_acct
            # EMA update under the lock: concurrent submitters racing the
            # read-modify-write could blend NEGATIVE gaps into the estimate
            # and spuriously hold low-rate traffic for admit_delay
            last, self._last_submit_t = self._last_submit_t, now
            if last is not None:
                gap = min(max(now - last, 0.0), 1.0)
                self._ema_gap = (
                    gap if self._ema_gap is None else 0.8 * self._ema_gap + 0.2 * gap
                )
        if self.ledger is not None:
            # new-arrival lift BEFORE the request becomes orderable: a
            # client returning from idle starts at the active floor, not
            # at whatever stale credit its old counter banked
            self.ledger.touch(req.client)
        # flight record: capture the re-execution inputs NOW, so an
        # in-flight request is already replayable when the engine dies
        # (a failover continuation re-records its continuation prompt)
        self.flightrec.start(req, self)
        self._admit_q.put(req)
        # TOCTOU with _die()/close(): if the engine stopped between the
        # _stop check above and this put, its one-shot drain may already
        # have run and nothing will ever read the queue again — drain it
        # ourselves so the request cannot hang until stream timeout
        if self._stop:
            self._drain_pending()
        self._kick.set()
        return req

    def generate(self, prompt_tokens: list[int], **kw) -> list[int]:
        return self.submit(GenRequest(prompt_tokens, **kw)).tokens()

    def stats(self) -> dict:
        with self._lock:
            return {
                "version": self.version,
                "tp_degree": self.tp_degree,
                "tp_overlap": self.tp_overlap,
                "role": self.role,
                "disconnect_cancels": self.disconnect_cancels,
                "errored": self.errored,
                "slots": self.slots,
                "active": sum(r is not None for r in self._slot_req),
                "waiting": self._admit_q.qsize() + len(self._waiting),
                "max_seq_len": self.max_seq_len,
                "decode_chunk": self.decode_chunk,
                "inflight_chunks": sum(1 for e in self._inflight if e[0] == "chunk"),
                "submitted": self.submitted,
                "chunks": self._stat_chunks,
                "chunk_steps": self._stat_chunk_steps,
                "active_sum": self._stat_active_sum,  # raw: callers can delta
                "avg_active_at_dispatch": (
                    round(self._stat_active_sum / self._stat_chunks, 2)
                    if self._stat_chunks
                    else 0.0
                ),
                "prefill_waves": dict(sorted(self._stat_waves.items())),
                "wave_reqs": self._stat_wave_reqs,
                # token-budget step scheduler telemetry
                "scheduler": "chunked" if self.chunked else "wave",
                "steps": self._stat_steps,
                "step_tokens": self._stat_step_tokens,
                "steps_without_decode": self._stat_steps_d0,
                "step_token_budget": self.step_token_budget,
                "chunk_shapes": list(self.chunk_shapes),
                "prefilling": len(self._prefilling),
                # speculative decoding (gofr_tpu.spec)
                "spec": self._spec_summary(),
                # grammar-constrained decoding (gofr_tpu.structured)
                "constrained": self._constrained_summary(),
                # multi-tenant LoRA adapters (gofr_tpu.lora)
                "adapters": {
                    **(
                        self._lora_pool.snapshot() if self.lora_slots
                        else {"slots": 0, "resident": {}, "zombies": [],
                              "evictions": 0, "swaps": 0}
                    ),
                    "requests": self.adapter_requests,
                    "rank_max": self.lora_rank if self.lora_slots else 0,
                },
                "moe_experts": int(getattr(self.cfg, "n_experts", 0) or 0),
                # what the routed experts did (the paged programs' step records,
                # summed): pairs computed, experts given a row and layer calls
                # (touched / (experts x calls) is the share of the expert stream
                # a step reads), rows per expert over all layers (load balance)
                "moe": {
                    # the grouped matmul the experts traced (Pallas kernel,
                    # or ragged_dot and why); None for a dense model
                    "experts": self.moe_path,
                    "pairs": int(self._moe_totals[0]),
                    "touched": int(self._moe_totals[1]),
                    "layer_calls": int(self._moe_layer_calls),
                    "tokens_per_expert": [
                        int(x) for x in self._moe_totals[2 : 2 + self._moe_held]
                    ],
                    # the experts this program holds of the model's, and every
                    # pair its routers chose (= pairs when it holds them all)
                    "held": {
                        "first": int(getattr(self.cfg, "moe_first_expert", 0) or 0),
                        "count": self._moe_held,
                        "of": int(getattr(self.cfg, "n_experts", 0) or 0),
                    },
                    "pairs_routed": int(self._moe_totals[self._moe_routed_at]),
                },
                "load_tokens": self.load_tokens(),
                "rejected": self.rejected,
                "shed": self.shed,
                "deadline_cancels": self.deadline_cancels,
                # overload-control telemetry (docs/advanced-guide/overload.md)
                "preemptions": self.preemptions,
                "sheds_predicted": self.sheds_predicted,
                "brownout_clamped": self.brownout_clamped,
                "predicted_wait_s": self.predicted_wait_s(),
                "overload": self.overload.snapshot(),
                "fairness": (
                    self.ledger.snapshot() if self.ledger is not None else None
                ),
                "draining": self._draining,
                "watchdog_trips": self.watchdog.trips if self.watchdog else 0,
                "numerical_trips": self.numerical_trips,
                "kvcache": self.kv.stats(),
                # which attention implementation decode / each prefill
                # chunk shape traced (Pallas kernel, or XLA and why)
                "attention": self.attention_paths,
                # recent-window phase latencies (seconds): exact p50/p99
                # over the last ~512 observations per phase
                "phases": {k: w.summary() for k, w in self._phases.items()},
                # the step timeline: one finished record per dispatched
                # program, oldest first; a copy of the ring's references,
                # no work per record
                "step_log": {"fields": STEP_FIELDS, "records": tuple(self._step_log)},
                # utilization: analytic-FLOPs MFU + tokens/s/chip windows
                # and the roofline verdict (profiling.mfu)
                "mfu": self._mfu_summary(),
                # chip-time attribution + quota state (gofr_tpu.goodput)
                "goodput": (
                    self.goodput.snapshot()
                    if self.goodput is not None else None
                ),
                "quota": (
                    {**self.quota.snapshot(), "sheds": self.quota_sheds}
                    if self.quota is not None else None
                ),
                "warmup_s": self.warmup_s,
            }

    def usage_state(self) -> dict:
        """Windowed per-tenant usage + cumulative goodput attribution
        for the /.well-known/debug/usage endpoint (chargeback export).
        Same shape as ReplicatedLLMEngine.usage_state so the handler
        never branches on the engine kind."""
        usage = (
            self.usage.snapshot() if self.usage is not None
            else {"window_s": None, "tenants": {}}
        )
        return {
            "replicas": 1,
            "goodput": (
                self.goodput.snapshot() if self.goodput is not None else None
            ),
            "quota": (
                self.quota.snapshot() if self.quota is not None else None
            ),
            "quota_sheds": self.quota_sheds,
            **usage,
        }

    def set_tenant_quota(self, tenant: str, tok_s: float | None) -> None:
        """Set (or clear, with None) a tenant's hard token-rate quota at
        runtime — register_adapter's quota= knob lands here with the
        adapter's pseudo-client id."""
        if self.quota is not None:
            self.quota.set(tenant, tok_s)

    def debug_state(self) -> dict:
        """Live introspection for /.well-known/debug/engine: the slot
        table, in-flight device work, waiting requests, recent phase
        percentiles, and kv-cache residency. One lock acquisition; output
        is bounded (slots + at most 32 waiting entries) so the endpoint is
        safe to hit on a saturated engine."""
        now = time.perf_counter()

        def req_row(r: GenRequest, slot: int | None = None) -> dict:
            row = {
                "id": r.id,
                "phase": r.phase,
                "prompt_tokens": len(r.prompt_tokens),
                "prefill_pos": r.prefill_pos,
                "emitted": r.emitted,
                "max_new_tokens": r.max_new_tokens,
                "age_ms": (
                    round((now - r.submitted_at) * 1e3, 1)
                    if r.submitted_at is not None else None
                ),
                "prefix_hit": r.prefix_hit,
                "trace_id": r.span.trace_id if r.span is not None else "",
            }
            if slot is not None:
                row["slot"] = slot
            return row

        with self._lock:
            slot_table = [
                req_row(r, slot) if r is not None else None
                for slot, r in enumerate(self._slot_req)
            ]
            inflight = []
            entries = list(self._inflight)
            if self._processing is not None:
                entries.append(self._processing)
            for e in entries:
                if e[0] == "prefill":
                    inflight.append({
                        "kind": "prefill",
                        "requests": [r.id for _, r in e[2] if r is not None],
                        "wave": e[3]["nb"] or len(e[2]),
                        "bucket": e[3]["bucket"],
                        "age_ms": round((now - e[3]["t_dispatch"]) * 1e3, 1),
                    })
                elif e[0] == "step":
                    inflight.append({
                        "kind": "step",
                        "chunk_shape": e[6]["shape"],
                        "prefill_tokens": e[6]["prefill_tokens"],
                        "finishing": [r.id for _j, _s, r in e[2]],
                        "decode_steps": e[5],
                        "active": e[6]["active"],
                        "age_ms": round((now - e[6]["t_dispatch"]) * 1e3, 1),
                    })
                elif e[0] == "verify":
                    inflight.append({
                        "kind": "verify",
                        "requests": [r.id for _s, r in e[3]],
                        "draft": e[4]["W"] - 1,
                        "proposed": e[4]["proposed"],
                        "age_ms": round((now - e[4]["t_dispatch"]) * 1e3, 1),
                    })
                else:
                    inflight.append({
                        "kind": "chunk",
                        "steps": e[3],
                        "active": sum(r is not None for r in e[2]),
                        "age_ms": round((now - e[4]["t_dispatch"]) * 1e3, 1),
                    })
            waiting_total = self._admit_q.qsize() + len(self._waiting)
            waiting = [req_row(r) for r in self._waiting[:32]]
            phases = {k: w.summary() for k, w in self._phases.items()}
            steps = list(self._step_log)[-32:]
        return {
            "label": self.label,
            "version": self.version,
            "tp_degree": self.tp_degree,
            "tp_overlap": self.tp_overlap,
            "role": self.role,
            "alive": self.alive(),
            "draining": self._draining,
            "died_reason": self.died_reason,
            "disconnect_cancels": self.disconnect_cancels,
            "watchdog": (
                {"threshold_s": self.step_watchdog_s,
                 "trips": self.watchdog.trips}
                if self.watchdog is not None else None
            ),
            "faults": self.faults.snapshot(),
            "deadline_cancels": self.deadline_cancels,
            "preemptions": self.preemptions,
            "sheds_predicted": self.sheds_predicted,
            "predicted_wait_s": self.predicted_wait_s(),
            "overload": self.overload.snapshot(),
            "fairness": (
                self.ledger.snapshot() if self.ledger is not None else None
            ),
            "slots": self.slots,
            "active": sum(row is not None for row in slot_table),
            "max_seq_len": self.max_seq_len,
            "decode_chunk": self.decode_chunk,
            "scheduler": "chunked" if self.chunked else "wave",
            "step_token_budget": self.step_token_budget,
            "chunk_shapes": list(self.chunk_shapes),
            "prefilling": len(self._prefilling),
            "spec": self._spec_summary(),
            "constrained": self._constrained_summary(),
            "adapters": {
                **self.adapters(),
                "requests": self.adapter_requests,
                "rank_max": self.lora_rank if self.lora_slots else 0,
            },
            "moe_experts": int(getattr(self.cfg, "n_experts", 0) or 0),
            "slot_table": slot_table,
            "inflight": inflight,
            # the step timeline's newest records (STEP_FIELDS; the whole
            # ring is stats()["step_log"])
            "steps": [dict(zip(STEP_FIELDS, rec)) for rec in steps],
            "waiting_total": waiting_total,
            "waiting": waiting,
            "admitting": self._admitting,
            "phases": phases,
            "slo": self.slo.snapshot() if self.slo is not None else None,
            "mfu": self._mfu_summary(),
            "goodput": (
                self.goodput.snapshot() if self.goodput is not None else None
            ),
            "usage": (
                self.usage.snapshot() if self.usage is not None else None
            ),
            "quota": (
                {**self.quota.snapshot(), "sheds": self.quota_sheds}
                if self.quota is not None else None
            ),
            "warmup_s": self.warmup_s,
            # this engine's rows from the process compile registry (the
            # full cross-engine view lives at /.well-known/debug/compiles)
            "compiles": self._registry.snapshot(model=self.label)["programs"],
            "submitted": self.submitted,
            "rejected": self.rejected,
            "shed": self.shed,
            "kvcache": self.kv.stats(),
            "attention": self.attention_paths,
        }

    # -- incident flight recorder (gofr_tpu.flightrec; docs/advanced-
    # guide/incident-debugging.md) ----------------------------------------

    def _inflight_requests(self) -> list[GenRequest]:
        """Racy, lock-free sweep of every live request — slotted, riding
        a device snapshot, prefilling, or waiting. Runs on the incident
        path where the engine lock may be wedged under a hung device
        call: a torn read (one request too many) beats a bundle dump
        that blocks behind the very hang it is documenting."""
        out: list[GenRequest] = []
        seen: set[int] = set()

        def take(r: GenRequest | None) -> None:
            if r is not None and r.id not in seen:
                seen.add(r.id)
                out.append(r)

        for r in list(self._slot_req):
            take(r)
        entries = list(self._inflight)
        proc = self._processing
        if proc is not None:
            entries.append(proc)
        for e in entries:
            try:
                for r in self._entry_requests(e):
                    take(r)
            except Exception:  # noqa: BLE001 — racy sweep, entries may be torn
                continue
        for r in list(self._prefilling):
            take(r)
        for r in list(self._waiting):
            take(r)
        return out

    def _hbm_samples(self) -> list[dict]:
        """Per-device HBM occupancy for the bundle (the telemetry
        poller's sample shape, taken inline — the poller may be off)."""
        import jax

        out = []
        for d in jax.devices():
            try:
                stats = d.memory_stats() or {}
            except Exception:  # noqa: BLE001 — backends without memory_stats
                stats = {}
            out.append({
                "device": d.id,
                "platform": getattr(d, "platform", ""),
                "kind": getattr(d, "device_kind", ""),
                "bytes_in_use": stats.get("bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            })
        return out

    def _config_fingerprint(self) -> dict:
        """The engine's serving shape plus a content hash: 'is the
        replay host configured like the incident host' is the first
        question a post-mortem asks, and diffing two fingerprints
        answers it without eyeballing forty knobs."""
        import hashlib as _hashlib
        import json as _json

        shape = {
            "model": self.label,
            "version": self.version,
            "role": self.role,
            "slots": self.slots,
            "max_seq_len": self.max_seq_len,
            "decode_chunk": self.decode_chunk,
            "chunked": self.chunked,
            "speculative": self.speculative,
            "spec_draft": self.spec_draft,
            "constrained": self.constrained,
            "lora_slots": self.lora_slots,
            "quantized": self.quantized,
            "kv_paged": self.kv.paged,
            "kv_window": self.kv.window,
            "tp_degree": self.tp_degree,
            "flight_records": self.flightrec.capacity,
            "flight_redact": self.flightrec.redact,
            "wide_event_sample": self._wide_sample,
        }
        shape["sha256"] = _hashlib.sha256(
            _json.dumps(shape, sort_keys=True, default=repr).encode()
        ).hexdigest()
        return shape

    def _incident(
        self, trigger: str, *, reason: str = "", lock_timeout: float = 2.0
    ) -> str | None:
        """Dump one black-box bundle (gofr_tpu.flightrec.BlackboxDumper):
        engine debug state, the trace ring, the retained wide events,
        the compile registry, HBM occupancy, the config fingerprint, and
        the flight records of everything in flight. Returns the bundle
        path, or None when the dumper is unarmed or the trigger class is
        inside its rate-limit window. Never raises — the incident path
        must not add a second failure to the first."""
        if not self.blackbox.enabled():
            return None
        try:
            sections: dict[str, Any] = {}
            # engine state under a BOUNDED acquire: the incident may BE a
            # wedged device call that still holds the lock (RLock, so an
            # under-lock caller like the SLO flip re-enters instantly)
            if self._lock.acquire(timeout=lock_timeout):
                try:
                    sections["debug_state"] = self.debug_state()
                finally:
                    self._lock.release()
            else:
                sections["debug_state"] = {
                    "lock_wedged": True,
                    "died": self._died,
                    "died_reason": self.died_reason,
                }
            ring = getattr(self.tracer, "ring", None) if self.tracer else None
            if ring is not None:
                sections["traces"] = {
                    "stats": ring.stats(),
                    "trace_ids": ring.trace_ids(64),
                    "spans": ring.dump(512),
                }
            sections["wide_events"] = list(self._wide_retained)
            sections["compiles"] = self._registry.snapshot(model=self.label)
            sections["hbm"] = self._hbm_samples()
            sections["config"] = self._config_fingerprint()
            if self.anomaly is not None:
                sections["anomaly"] = self.anomaly.snapshot()
            records = self.flightrec.snapshot_inflight(self._inflight_requests())
            records.extend(self.flightrec.records(limit=64, final=True))
            return self.blackbox.dump(
                trigger, reason=reason, sections=sections, records=records
            )
        except Exception as e:  # noqa: BLE001 — see docstring
            if self.logger is not None:
                self.logger.error(f"black-box bundle capture failed: {e!r}")
            return None

    def replay(self, record_or_id, *, timeout: float = 120.0) -> dict:
        """Deterministically re-execute a recorded request with pinned
        version/adapter/grammar/seed and report the first-divergence
        token index vs the recorded emission (gofr_tpu.flightrec;
        `replay` CLI subcommand / POST /.well-known/debug/replay)."""
        from .flightrec import replay_record

        rec = record_or_id
        if not isinstance(rec, dict):
            rec = self.flightrec.get(int(record_or_id))
            if rec is None:
                return {
                    "id": record_or_id,
                    "error": "no flight record with that id (ring holds "
                             f"{len(self.flightrec)} of "
                             f"{self.flightrec.capacity})",
                }
        return replay_record(self, rec, timeout=timeout)

    def _spec_summary(self) -> dict:
        """Speculative-decoding telemetry block for stats()/debug_state:
        cheap counter reads, no lock requirements (GIL-atomic ints).
        The constrained split is what the structured-decoding bench
        point reads — acceptance on grammar-masked text should meet or
        beat the unconstrained rate (the drafter's proposals are
        pre-filtered by the same DFA)."""
        prop_u = self.spec_proposed - self.spec_proposed_c
        acc_u = self.spec_accepted - self.spec_accepted_c
        return {
            "enabled": self.speculative,
            "draft": self.spec_draft,
            "steps": self.spec_steps,
            "proposed": self.spec_proposed,
            "accepted": self.spec_accepted,
            "plain_lanes": self.spec_plain,
            "accept_rate": (
                round(self.spec_accepted / self.spec_proposed, 3)
                if self.spec_proposed else None
            ),
            "constrained": {
                "proposed": self.spec_proposed_c,
                "accepted": self.spec_accepted_c,
                "accept_rate": (
                    round(self.spec_accepted_c / self.spec_proposed_c, 3)
                    if self.spec_proposed_c else None
                ),
            },
            "unconstrained": {
                "proposed": prop_u,
                "accepted": acc_u,
                "accept_rate": (
                    round(acc_u / prop_u, 3) if prop_u else None
                ),
            },
        }

    # -- grammar-constrained decoding (gofr_tpu.structured) ---------------

    def _constrained_summary(self) -> dict:
        """Telemetry block for stats()/debug_state (lock held by caller
        or freshness unimportant — counter reads are GIL-atomic)."""
        return {
            "enabled": self.constrained,
            "requests": self.constrained_requests,
            "grammars_resident": sum(
                1 for g in self._grammars if g is not None
            ),
            "grammar_cap": self._g_cap,
            "states": [
                g.n_states if g is not None else 0 for g in self._grammars
            ],
        }

    def _register_grammar(self, g) -> int:
        """Resident-grammar table slot for one TokenGrammar (call with
        the engine lock held). Repeat schemas dedup by grammar key; a
        full table evicts a zero-ref entry, and a table whose every slot
        holds live requests sheds the submit (429 — capacity, not a
        client bug)."""
        vocab = getattr(g, "vocab_size", None)
        if vocab != self.cfg.vocab_size:
            raise ValueError(
                f"grammar compiled for vocab {vocab}, model vocab is "
                f"{self.cfg.vocab_size} — compile against this model's "
                "tokenizer"
            )
        for i, og in enumerate(self._grammars):
            if og is not None and og.key == g.key:
                return i
        gid = None
        if len(self._grammars) < self._g_cap:
            self._grammars.append(None)
            self._g_refs.append(0)
            gid = len(self._grammars) - 1
        else:
            for i, og in enumerate(self._grammars):
                if self._g_refs[i] == 0:
                    gid = i
                    break
        if gid is None:
            raise EngineOverloaded(
                f"all {self._g_cap} resident grammar slots hold live "
                "requests (raise TPU_LLM_CONSTRAINED_GRAMMARS)",
                retry_after=1.0,
            )
        self._grammars[gid] = g
        self._g_refs[gid] = 0
        self._rebuild_grammar_table()
        return gid

    def _rebuild_grammar_table(self) -> None:
        """Re-pad + re-ship the resident grammar table. Padded to
        power-of-two grammar count and state count so the constrained
        program family retraces O(log) times over an engine's life, not
        per registration; padding rows/states admit nothing (-1), which
        reads as 'dead' and is never reachable for a live lane."""
        jnp = self._jnp
        live = [g for g in self._grammars if g is not None]
        if not live:
            self._gr_dev = None
            if self.metrics is not None:
                self.metrics.set_gauge(
                    "app_llm_constrained_grammars", 0.0, model=self.label
                )
            return
        G = len(self._grammars)
        gp = 1 << max(0, G - 1).bit_length()
        smax = max(g.n_states for g in live)
        sp = max(32, 1 << max(0, smax - 1).bit_length())
        tab = np.full((gp, sp, self.cfg.vocab_size), -1, np.int32)
        for i, g in enumerate(self._grammars):
            if g is not None:
                tab[i, : g.n_states, :] = g.table
        arr = jnp.asarray(tab)
        if self.device is not None:
            arr = self._jax.device_put(arr, self.device)
        self._gr_dev = arr
        if self.metrics is not None:
            self.metrics.set_gauge(
                "app_llm_constrained_grammars", float(len(live)),
                model=self.label,
            )

    def _grammar_live(self) -> bool:
        """Any resident request constrained? (lock held). True routes
        EVERY device dispatch through the constrained program family —
        the per-slot gid mask keeps unconstrained lanes token-identical,
        and one family per dispatch keeps the DFA state chain coherent."""
        return any(
            r is not None and r.grammar is not None for r in self._slot_req
        ) or any(r.grammar is not None for r in self._prefilling)

    def _gids_np(self) -> np.ndarray:
        """Per-slot grammar selector for one dispatch (lock held):
        -1 = unconstrained lane (logits untouched)."""
        gids = np.full((self.slots,), -1, np.int32)
        for i, r in enumerate(self._slot_req):
            if r is not None and r.grammar is not None and r._g_id >= 0:
                gids[i] = r._g_id
        return gids

    # -- multi-tenant LoRA adapter lifecycle (gofr_tpu.lora;
    # docs/advanced-guide/multi-tenancy.md) ------------------------------
    def _require_lora(self) -> None:
        if not self.lora_slots:
            raise ValueError(
                "engine has no adapter pool (lora_slots=0; set "
                "TPU_LLM_LORA_SLOTS or pass lora_slots=)"
            )

    def _lora_stage(self, gid: int, canon: dict) -> None:
        """Write one adapter's padded (A, B) pairs into table row ``gid``
        (every target; absent targets write zeros so residue from the
        row's previous tenant can never leak into this one). The gid is
        TRACED, so every load on an engine's life reuses the same
        compiled set programs; params is never donated, so the rebuild
        is a dict swap around fresh table buffers and the serving jit
        caches stay warm."""
        jnp = self._jnp
        op = self._lora_set_ops.get("set")
        if op is None:
            def _set(tab, g, sl):
                return tab.at[:, g].set(sl)

            op = self._jax.jit(_set)
            self._lora_set_ops["set"] = op
        L, rmax = self.cfg.n_layers, self.lora_rank
        layers = dict(self.params["layers"])
        gid_dev = jnp.asarray(gid, jnp.int32)
        for name, (d_in, d_out) in self._lora_mod.target_dims(
            self.cfg
        ).items():
            ka, kb = f"lora_{name}_a", f"lora_{name}_b"
            if ka not in layers:
                continue
            a_pad = np.zeros((L, d_in, rmax), np.float32)
            b_pad = np.zeros((L, rmax, d_out), np.float32)
            if name in canon:
                a, b = canon[name]
                r = a.shape[2]
                a_pad[:, :, :r] = a
                b_pad[:, :r, :] = b
            layers[ka] = op(layers[ka], gid_dev, jnp.asarray(a_pad))
            layers[kb] = op(layers[kb], gid_dev, jnp.asarray(b_pad))
        # atomic publish of the new tables: dispatches read self.params
        # once per call, and the staged gid has no live lane (refs == 0
        # by allocate's contract), so a dispatch racing this swap serves
        # every resident tenant identically from either dict
        self.params = {**self.params, "layers": layers}

    def load_adapter(
        self, name: str, adapter: dict, *, version: str = "v1",
        alpha: float | None = None, fair_weight: float | None = None,
    ) -> int:
        """Validate ``adapter`` against the base config, bind ``name`` to
        a pool gid (LRU-evicting an idle resident when full), and stage
        its delta into the device tables. Callable while serving: the
        staged gid has no in-flight lane until a submit names it. Returns
        the gid. ``fair_weight`` sets the per-tenant FairLedger share of
        the adapter's pseudo-client (``adapter:<name>``)."""
        self._require_lora()
        canon = self._lora_mod.validate_adapter(
            self.cfg, adapter, rank_max=self.lora_rank, alpha=alpha
        )
        rank = max((a.shape[2] for a, _ in canon.values()), default=0)
        with self._lock:
            ev0 = self._lora_pool.evictions
            gid = self._lora_pool.allocate(name, version=version, rank=rank)
            evicted = self._lora_pool.evictions - ev0
        try:
            self._lora_stage(gid, canon)
        except BaseException:
            with self._lock:
                self._lora_pool.remove(name)
            raise
        if fair_weight is not None and self.ledger is not None:
            self.ledger.set_weight(f"adapter:{name}", fair_weight)
        if self.metrics is not None:
            if evicted:
                self.metrics.increment_counter(
                    "app_llm_adapter_evictions_total", float(evicted),
                    model=self.label,
                )
            self.metrics.set_gauge(
                "app_llm_adapters_resident", float(len(self._lora_pool)),
                model=self.label,
            )
        return gid

    def publish_adapter(self, staging: str, name: str) -> int | None:
        """Atomically repoint ``name`` at the gid staged under
        ``staging`` (hot-load commit after a canary gate). In-flight
        requests keep decoding on the OLD gid until they drain (zombie);
        new submits resolve to the new one. Returns the previous gid or
        None for a first load."""
        self._require_lora()
        with self._lock:
            old = self._lora_pool.publish(staging, name)
        if self.metrics is not None:
            self.metrics.increment_counter(
                "app_llm_adapter_swaps_total", model=self.label,
            )
            self.metrics.set_gauge(
                "app_llm_adapters_resident", float(len(self._lora_pool)),
                model=self.label,
            )
        return old

    def evict_adapter(self, name: str) -> int:
        """Unbind ``name`` (retire / canary reject). Its gid frees
        immediately when idle, else drains as a zombie while in-flight
        requests finish — the table row is not zeroed (no lane points at
        it; the next allocate overwrites it wholesale)."""
        self._require_lora()
        with self._lock:
            gid = self._lora_pool.remove(name)
        if self.metrics is not None:
            self.metrics.set_gauge(
                "app_llm_adapters_resident", float(len(self._lora_pool)),
                model=self.label,
            )
        return gid

    def adapters(self) -> dict:
        """Pool snapshot: resident adapters (gid/version/rank/refs),
        zombie gids, lifetime eviction/swap counts. Empty-shaped on
        engines without a pool so registry listings need no feature
        probe."""
        if not self.lora_slots:
            return {
                "slots": 0, "resident": {}, "zombies": [],
                "evictions": 0, "swaps": 0,
            }
        with self._lock:
            return self._lora_pool.snapshot()

    def _ops(self, use_g: bool) -> tuple:
        """(chunk programs by K, step programs by shape, the verify program)
        a dispatch draws from, looked up at every dispatch. The grammar
        family is lazy by design: engines that never see a grammar build
        nothing, and the first constrained request pays the compile the
        way the monolithic prefill family already does in chunked mode."""
        if use_g:
            return self._programs.family(grammar=True)
        return self._chunk_ops, self._step_ops, self._verify_op

    # a program's operand (llm_programs.Programs._signature) -> the attribute
    # that holds it between dispatches
    _DEVICE_STATE = (
        ("params", "params"), ("cache", "cache"), ("scales", "_kv_scales"),
        ("tail", "_tail"), ("active", "_active"), ("temps", "_temps"),
        ("gstate", "_gstate"), ("gtab", "_gr_dev"), ("rng", "_rng"),
    )

    def _run(self, kind: str, use_g: bool, op, **inputs) -> dict:
        """One program call: the engine's device state and this dispatch's
        `inputs` in, the state the program hands back adopted, what is left
        (tokens, first tokens, kept logits, a routed model's expert vector)
        returned by name. Call with the lock held."""
        env = {name: getattr(self, attr) for name, attr in self._DEVICE_STATE}
        out = self._programs.call(kind, use_g, op, {**env, **inputs})
        for name, attr in self._DEVICE_STATE:
            if name in out:
                setattr(self, attr, out.pop(name))
        return out

    def load(self) -> int:
        """Cheap routing signal for the replica router: occupants plus
        queue depth plus requests mid-admission (sliced out of _waiting,
        not yet slotted). Lock-free — _slot_req is only ever mutated in
        place (no resize), so a torn read costs at most a stale unit."""
        return (
            sum(r is not None for r in self._slot_req)
            + self._admit_q.qsize()
            + len(self._waiting)
            + self._admitting
        )

    def resident_slots(self) -> int:
        """Occupied decode slots RIGHT NOW — the decode-role routing
        signal (disaggregated serving admits decode work by slot
        residency, where the prefill role routes by queued prompt
        tokens). Lock-free like load(): _slot_req is mutated in place,
        a torn read costs at most one stale unit."""
        return sum(r is not None for r in self._slot_req)

    def load_tokens(self) -> int:
        """Token-weighted routing signal: the estimated device work still
        owed to every live request — prompt remainder plus expected decode
        — maintained as a counter (submit adds prompt + max_new; prefill
        chunks and emitted tokens credit it back; terminal paths flush the
        residue). A 128-token prompt weighs 16x an 8-token prompt here
        where load() weighs them identically, which is what the replica
        router actually needs to balance. Lock-free read of a single int
        (torn reads cost at most one stale request)."""
        return max(0, self._load_tokens)

    def predicted_wait_s(self) -> float | None:
        """Predicted queue wait for a NEW request: the outstanding token
        estimate (load_tokens) over the measured serving throughput (EMA
        over recent device windows). None until the first window lands —
        the overload controller treats that as no pressure, so a cold
        engine never sheds. An estimate, not a promise: pipelined
        windows overlap, so the EMA reads slightly low and the
        prediction slightly high (conservative for shedding)."""
        tput = self._tput_ema
        if not tput or tput <= 1e-9:
            return None
        return self.load_tokens() / tput

    def throughput_tok_s(self) -> float | None:
        """Measured serving throughput (EMA over recent device windows;
        None until the first window). The front router pools this across
        engine PROCESSES to price fleet admission the same way one
        engine prices its own (docs/advanced-guide/scale-out.md)."""
        return self._tput_ema

    def _observe_tput(self, tokens: int, dt: float) -> None:
        """Fold one finished device window (tokens served / wall) into
        the throughput EMA that prices predicted queue wait. Lock-free
        float write (a torn read costs one stale estimate)."""
        if tokens <= 0 or dt <= 0:
            return
        rate = tokens / dt
        ema = self._tput_ema
        self._tput_ema = rate if ema is None else 0.8 * ema + 0.2 * rate

    def _load_credit(self, r: GenRequest, n: int) -> None:
        """Retire `n` tokens of r's outstanding-work estimate (bounded by
        what it still owes). Call with the lock held."""
        n = min(n, r._load_acct)
        if n > 0:
            r._load_acct -= n
            self._load_tokens -= n

    def alive(self) -> bool:
        """Health signal for the replica router: the engine accepts work
        only while both its threads run and neither close() nor a terminal
        thread failure (_die) has begun."""
        return (
            not self._stop
            and self._thread.is_alive()
            and self._collector.is_alive()
        )

    def accepting(self) -> bool:
        """Routing signal: alive AND taking new work (a draining replica
        finishes its in-flight requests but must not be fed more)."""
        return self.alive() and not self._draining

    def drain(self) -> None:
        """Graceful-drain entry (rolling deploy): close admission —
        submit() raises EngineDraining (503) — while every slotted and
        queued request runs to completion. The app lifecycle polls
        drained() under GOFR_DRAIN_DEADLINE_S and then close()s."""
        self._draining = True
        if self.metrics is not None:
            self.metrics.set_gauge(
                "app_llm_drain_state", 1.0, model=self.label
            )
        self._kick.set()

    def undrain(self) -> None:
        """Reopen admission after a drain that was ROLLED BACK rather
        than completed — the rollout controller's single-engine rollback
        path (docs/advanced-guide/rollouts.md). A no-op on a dead engine
        (alive() is still False; the router will not route here)."""
        self._draining = False
        if self.metrics is not None:
            self.metrics.set_gauge(
                "app_llm_drain_state", 0.0, model=self.label
            )
        self._kick.set()

    def drained(self) -> bool:
        """True once no request holds a slot, waits, or is in flight.
        A DEAD engine is vacuously drained — its requests were rescued
        or closed by _die, and in the wedged-lock watchdog case the lock
        below is held forever by the hung device call (the drain poll
        must not block on a corpse)."""
        if self._died:
            return True
        with self._lock:
            return (
                self.load() == 0
                and not self._inflight
                and self._processing is None
            )

    # -- fault-injection seams (gofr_tpu.resilience.faults) ---------------
    def _fault(self, point: str) -> None:
        """Raise-kind seam: InjectedFault when `point` is armed for this
        engine label. Disarmed cost: one dict lookup."""
        spec = self.faults.take(point, self.label)
        if spec is None:
            return
        self._count_fault(point)
        from .resilience import InjectedFault

        raise InjectedFault(spec.message)

    def _fault_latency(self) -> None:
        """Sleep-kind seam: a wedged device transfer, as the host sees
        one — the blocking happens outside the engine lock, exactly where
        a real fetch blocks, so the step watchdog can convert it."""
        spec = self.faults.take("step_latency", self.label)
        if spec is None:
            return
        self._count_fault("step_latency")
        from .resilience.faults import sleep_for

        sleep_for(spec)

    def _count_fault(self, point: str) -> None:
        if self.logger is not None:
            self.logger.warn(f"fault injection: {point} fired on {self.label}")
        if self.metrics is not None:
            self.metrics.increment_counter(
                "app_llm_faults_injected_total", point=point, model=self.label
            )

    def _poison_fault(self) -> bool:
        """Poison-payload seam (scheduler pass): a ``device_step`` spec
        armed WITH A TAG fires exactly when a resident request carries
        the same tag — the deterministic stand-in for a payload whose
        content reliably crashes the step program. Terminal like
        replica_kill (the poison scenario is a replica-killing payload,
        not a transient step error); the router's poison quarantine then
        bounds the payload's blast radius. Disarmed cost: one dict
        lookup."""
        if not self.faults.has_tagged("device_step"):
            return False
        with self._lock:
            resident = [r for r in self._slot_req if r is not None]
            resident.extend(self._prefilling)
        for r in resident:
            tag = getattr(r, "tag", "")
            if tag and self.faults.take("device_step", self.label, tag=tag):
                self._count_fault("device_step")
                self._die(
                    f"poison payload: device_step fired for tagged request "
                    f"(tag={tag!r})"
                )
                return True
        return False

    def _numeric_trip(self, where: str) -> None:
        """Non-finite logits reached a fetched token array: convert the
        garbage stream into a replica death with a distinct,
        classifiable reason — the failover path re-seeds the in-flight
        requests on a replica whose compute is not poisoned, and the
        device ledger bills the trip as "numerical"."""
        self.numerical_trips += 1
        if self.metrics is not None:
            self.metrics.increment_counter(
                "app_llm_numerical_trips_total", model=self.label
            )
        self._die(f"numerical watchdog: non-finite logits ({where})")

    def _numeric_check_fetch(self, arr, cols: list[int], where: str):
        """Collector-side sentinel scan over one fetched token array
        (``cols`` are the last-axis lanes owned by live requests —
        inactive lanes legitimately carry garbage). Also hosts the
        ``nan_logits`` chaos seam: an armed spec corrupts one live lane
        with the sentinel, exactly what NaN logits produce on device —
        with the watchdog disabled the corruption streams through to the
        caller, which is the silent failure the watchdog exists to stop.
        Returns ``(arr, tripped)``; on a trip the engine is already
        dying and the caller must not emit."""
        if not cols:
            return arr, False
        if self.faults.take("nan_logits", self.label) is not None:
            self._count_fault("nan_logits")
            arr = np.array(arr)  # device fetches can be read-only views
            arr[..., cols[0]] = -1
        if self.numeric_check and bool((arr[..., cols] == -1).any()):
            self._numeric_trip(where)
            return arr, True
        return arr, False

    def _zero_state_gauges(self) -> None:
        """A stopped engine must not keep exporting its last live
        occupancy/backlog — dashboards and autoscaling would read load
        from an engine that no longer exists (same rationale as
        CacheManager.close() zeroing its resident-bytes gauge)."""
        if self.metrics is None:
            return
        for name in (
            "app_llm_slots_in_use",
            "app_llm_queue_depth",
            "app_llm_admission_backlog",
            "app_llm_step_budget_utilization",
            "app_llm_drain_state",
            "app_llm_brownout_state",
            "app_llm_fairness_debt",
            "app_llm_spec_accept_rate",
            "app_llm_constrained_grammars",
            "app_llm_adapters_resident",
            "app_llm_moe_experts",
        ):
            self.metrics.set_gauge(name, 0.0, model=self.label)
        # goodput ratio is load state too: a dead engine must not freeze
        # its last useful-fraction on the exposition (close() AND _die()
        # both funnel here — the PR 3/PR 18 regression class)
        if self.goodput is not None:
            self.goodput.zero_gauges()
        # a closed engine must not keep exporting its version row (the
        # dead-engine gauge bug class): the series would read as "this
        # label still serves version X" forever
        self.metrics.set_gauge(
            "app_llm_model_version_info", 0.0,
            model=self.label, version=self.version,
        )
        # SLO burn state is load state: a dead engine must not hold
        # "fast burn" (health would stay degraded forever) nor keep its
        # last burn rate on the dashboard; windows clear so a restarted
        # engine starts on a clean error budget
        if self.slo is not None:
            self.slo.zero_gauges()
        # same class: a dead engine must not hold an anomaly flag — the
        # degraded-backend signal would outlive the backend
        if self.anomaly is not None:
            self.anomaly.zero_gauges()

    def _teardown_profiling(self) -> None:
        """Compile-observatory teardown (close() and _die()): drop this
        engine's registry rows and zero its utilization gauges — a dead
        engine must neither list its programs at /debug/compiles nor keep
        exporting its last MFU (the slot-gauge bug class all over again)."""
        self._registry.remove_model(self.label)
        if self.metrics is None:
            return
        for phase in ("prefill", "decode"):
            self.metrics.set_gauge(
                "app_llm_mfu", 0.0, model=self.label, phase=phase
            )
            self.metrics.set_gauge(
                "app_llm_roofline_ratio", 0.0, model=self.label, phase=phase
            )
        self.metrics.set_gauge(
            "app_llm_tokens_per_second_per_chip", 0.0, model=self.label
        )

    def close(self) -> None:
        self._stop = True
        self._fail_sched_work()  # handoff waiters fail fast, not by timeout
        self._admit_q.put(None)
        self._kick.set()
        with self._work_cv:
            self._work_cv.notify_all()
        self._thread.join(timeout=10)
        with self._work_cv:
            self._work_cv.notify_all()
        self._collector.join(timeout=15)
        self._abort_all()
        self._drain_pending()
        self._zero_state_gauges()
        self._teardown_profiling()
        if self.ledger is not None:
            # a closed replica must not pin the fleet ledger's
            # new-arrival floor with a stale waiting-client set
            self.ledger.set_active(self.label, set())
        # flight-recorder teardown: no further bundles (the close()/_die()
        # contract), and the record ring clears WITH the engine — unlike
        # _die, where the ring outlives the death for post-mortems (the
        # bundle was already dumped by then)
        self.blackbox.close()
        self.flightrec.clear()
        self.kv.close()  # drop retained prefix rows (device buffers)

    def _drain_pending(self) -> None:
        """End-of-stream every request still in the waiting list or the
        admit queue (shared by close() and _die()): consumers see a
        'cancelled' finish instead of blocking until stream timeout."""
        with self._lock:
            waiting, self._waiting = self._waiting, []
        now = time.perf_counter()
        for r in waiting:
            if r.finish_reason is None:
                r.finish_reason = "cancelled"
                self._observe_finish(r, now)
                r.out.put(None)
        while True:
            try:
                r = self._admit_q.get_nowait()
            except queue.Empty:
                break
            if r is not None and r.finish_reason is None:
                r.finish_reason = "cancelled"
                self._observe_finish(r, now)
                r.out.put(None)
        if self.logger is not None:
            self._flush_wide_events()

    # -- engine internals -------------------------------------------------
    def _warm(self) -> None:
        """Compile every serving executable before traffic arrives. The
        compiles run CONCURRENTLY on a small thread pool: XLA releases the
        GIL while compiling and each jitted function owns its own cache
        entry, so the prefill variants, the decode chunk, and the admission
        ops overlap instead of serializing (r2's sequential warm took ~21 s;
        overlapped it is bounded by the slowest single program)."""
        from concurrent.futures import ThreadPoolExecutor

        jnp = self._jnp
        t0 = time.perf_counter()
        zero_rng = self._rng
        meta = jnp.zeros((3, self.admit_cap), jnp.int32)

        def warm_prefill(nb: int, b: int):
            pack = jnp.zeros((nb, b + 2), jnp.int32).at[:, -2].set(1)
            first, c, _logits, _ = self._prefill_op(self.params, pack, zero_rng)
            return first, c

        def warm_hit_first(nb: int):
            self._hit_first_op(
                self._rep(jnp.zeros((nb, self.cfg.vocab_size), jnp.float32)),
                jnp.zeros((nb,), jnp.float32), zero_rng,
            )

        def warm_admit_update(nb: int):
            """tail | active | temps | first, placed like the serving
            state the programs hand back (self._rep)."""
            self._admit_update(
                self._rep(jnp.zeros((self.slots,), jnp.int32)),
                self._rep(jnp.zeros((self.slots,), bool)),
                self._rep(jnp.zeros((self.slots,), jnp.float32)),
                self._rep(jnp.zeros((nb,), jnp.int32)), meta,
            )

        rows_ops = self._programs.rows(grammar=False)  # the steps without their decode chunk
        # every power-of-two admission width (wave sizing in _admit)
        nbs: list[int] = []
        nb = 1
        while nb < self.admit_cap:
            nbs.append(nb)
            nb <<= 1
        nbs.append(self.admit_cap)

        def warm_cache_ops():
            """insert + admit_update at every admission width, the
            unified-step programs at every (chunk shape, width) pair, with
            and without their decode chunk, then the decode chunk — CHAINED
            through the real slot cache by donation, exactly like live
            serving, so warm's peak memory never holds a second full-size
            cache and no two ops donate the same buffer. (The chain also
            serializes the step-program compiles; the wave path's
            prefill-family overlap does not apply here and the cost lands
            in warmup_s.)"""
            env = {
                "params": self.params, "cache": self.cache, "rng": zero_rng,
                "tail": jnp.zeros((self.slots,), jnp.int32),
                "active": jnp.zeros((self.slots,), bool),
                "temps": jnp.zeros((self.slots,), jnp.float32),
            }
            if self.kv.paged:
                # pool-layout operands. Zero tables/live/packs make every
                # write a dropped scatter — block 0 is never touched, state
                # stays zeros.
                env["scales"] = self._kv_scales
                env["tables"] = jnp.zeros(
                    (self.slots, self.kv.table_cols), jnp.int32
                )
                env["live"] = jnp.zeros((self.slots,), bool)

            def run(kind: str, op, **inputs) -> None:
                """One program of the chain: what it hands back of the
                cache and the batch state feeds the next (the rng does
                not chain: every program warms on zero_rng)."""
                out = self._programs.call(kind, False, op, {**env, **inputs})
                env.update((k, v) for k, v in out.items() if k in env and k != "rng")

            M = self.admit_cap
            for nb in nbs:
                if self.kv.mixed:
                    # (insert and seed are the wave scheduler's and the
                    # radix tree's: written for one pool, refused here)
                    warm_admit_update(nb)
                    continue
                scratch = self.kv.init_cache(nb)
                if self.kv.paged:
                    oob_b = self.kv.pool.n_blocks
                    env["cache"], env["scales"] = self._insert_many(
                        env["cache"], env["scales"], scratch, meta[:2], env["tables"]
                    )
                    env["cache"], env["scales"] = self._seed_op(
                        env["cache"], env["scales"],
                        jnp.full((M,), oob_b, jnp.int32),
                        jnp.full((M,), oob_b, jnp.int32),
                        jnp.full((M,), self.slots, jnp.int32),
                        jnp.zeros((M,), jnp.int32),
                    )
                else:
                    env["cache"] = self._insert_many(env["cache"], scratch, meta)
                warm_admit_update(nb)
            for kind, ops in (("step", self._step_ops), ("rows", rows_ops)):
                for shape, op in sorted(ops.items()):
                    for nb in nbs:
                        run(
                            kind, op, pack=jnp.zeros((nb, shape + 3), jnp.int32),
                            meta=jnp.full((2, nb), self.slots, jnp.int32).at[1].set(0),
                        )
            if self._verify_op is not None:
                # speculative verify program: one full-batch executable,
                # chained through the donated cache/tail like the rest.
                # All-unselected pack: no lane writes, state unchanged.
                run(
                    "verify", self._verify_op,
                    pack=jnp.zeros((self.slots, self.spec_draft + 2), jnp.int32),
                )
            for op in self._chunk_ops.values():
                run("chunk", op)
            if self.kv.paged:
                self._kv_scales = env["scales"]
            return env["tail"], env["cache"]

        n_step_tasks = (len(self._step_ops) + len(rows_ops)) * len(nbs)
        if self.chunked:
            # chunked mode: the monolithic prefill family exists (bench
            # probes and the A/B lever call it) but is compiled lazily —
            # warming it would double the cold-start bill for programs
            # live traffic never dispatches
            n_tasks = 1 + n_step_tasks
        else:
            n_tasks = len(self.prefill_buckets) * len(nbs) + 1
        if self._verify_op is not None:
            n_tasks += 1  # the speculative verify program (either scheduler)
        if self._hit_first_op is not None:
            n_tasks += len(nbs)
        # Sharded programs on the CPU backend (8-virtual-device test mesh)
        # must warm SEQUENTIALLY: concurrent sharded executions deadlock on
        # the per-device thread pool there (each execution parks waiting
        # for device workers another execution holds). Live serving is
        # unaffected — the scheduler is the only thread that executes
        # programs. Real-TPU warms keep the full overlap.
        sequential = self._sharded and self._jax.default_backend() == "cpu"
        with ThreadPoolExecutor(max_workers=1 if sequential else n_tasks) as pool:
            # (a sequential warm queues the chain on the one worker, first)
            futs = [pool.submit(warm_cache_ops)] if sequential else []
            if not self.chunked:
                for b in self.prefill_buckets:
                    for nb in nbs:
                        futs.append(pool.submit(warm_prefill, nb, b))
            if self._hit_first_op is not None:
                for nb in nbs:
                    futs.append(pool.submit(warm_hit_first, nb))
            # The chain runs HERE, on the calling thread, beside the pool's
            # tasks: on the chip's host a pool worker traces a third slower
            # and reads the compile cache three times slower than the
            # thread that built the engine (PERF.md §6, PR 31).
            last, cache = futs.pop(0).result() if sequential else warm_cache_ops()
            for f in futs:
                f.result()
        _ = np.asarray(last)  # sync: the warm chain ran to its end
        # the chain donated self.cache; adopt the output (zeros in, zeros
        # out — only length needs resetting)
        self.cache = cache._replace(length=jnp.zeros((self.slots,), jnp.int32))
        # Warmup cost into the compile registry: this is the bill a cold
        # restart pays before the first request, invisible in benches until
        # BENCH_r07 (wall time — the pool overlaps compiles, so it is NOT
        # the per-program sum the registry rows add up to).
        self.warmup_s = time.perf_counter() - t0
        self._registry.record_warmup(self.label, self.warmup_s, programs=n_tasks)
        if self.logger is not None:
            sched = (
                f"chunk shapes {self.chunk_shapes}, "
                f"step budget {self.step_token_budget}"
                if self.chunked else f"buckets {self.prefill_buckets}"
            )
            self.logger.info(
                f"LLM engine warmed in {self.warmup_s:.1f}s "
                f"({sched}, slots {self.slots}, "
                f"decode chunk {self.decode_chunk})"
            )

    def _bucket_for(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return self.max_seq_len

    def _wave_width(self, n: int) -> int:
        """Admission-wave batch dim: next power of two, capped at
        admit_cap — a wave of 2 must not pay the admit_cap-padded prefill
        (measured nb=1: 4.3 ms, nb=16: 30.5 ms; mid-load throughput
        collapsed when every trickle wave compiled/ran at full width).
        Bounded executable count: log2(admit_cap)+1 variants per bucket
        (and per hit-sample op), all pre-warmed — _warm enumerates the
        SAME widths, so any change here must change there too."""
        return min(self.admit_cap, 1 << max(0, n - 1).bit_length())

    def _inflight_steps(self) -> dict[int, int]:
        """Per-slot decode steps already dispatched for the CURRENT owner.
        Includes the entry the collector popped but has not emitted yet
        (its tokens are still coming). Call with the lock held."""
        steps: dict[int, int] = {}
        entries = list(self._inflight)
        if self._processing is not None:
            entries.append(self._processing)
        for e in entries:
            if e[0] == "prefill":
                # an un-fetched prefill entry carries each request's first
                # token — without counting it, demand is overestimated by 1
                # per fresh request and an extra decode chunk occasionally
                # dispatched
                for slot, r in e[2]:
                    if r is not None and r is self._slot_req[slot]:
                        steps[slot] = steps.get(slot, 0) + 1
                continue
            if e[0] == "verify":
                # a verify's yield is data-dependent (1..draft+1 tokens);
                # count the GUARANTEED minimum of one — overcounting
                # could virtually free a slot on tokens that never
                # arrive, stranding the request without an end-of-stream.
                # The 1-token floor also keeps the slot ineligible for
                # another verify until this one is fetched.
                for slot, r in e[3]:
                    if r is self._slot_req[slot]:
                        steps[slot] = steps.get(slot, 0) + 1
                continue
            if e[0] == "step":
                # unified step: each finishing row carries its first token,
                # and the piggybacked decode part carries k per snapshot slot
                _, _first, finishes, _toks, snapshot, k, _info = e
                for _j, slot, r in finishes:
                    if r is self._slot_req[slot]:
                        steps[slot] = steps.get(slot, 0) + 1
                if k and snapshot is not None:
                    for slot, r in enumerate(snapshot):
                        if r is not None and r is self._slot_req[slot]:
                            steps[slot] = steps.get(slot, 0) + k
                continue
            snapshot, k = e[2], e[3]
            for slot, r in enumerate(snapshot):
                if r is not None and r is self._slot_req[slot]:
                    steps[slot] = steps.get(slot, 0) + k
        return steps

    def _free_slots(self) -> list[int]:
        """Free or VIRTUALLY free slots. A slot whose in-flight chunks
        already cover its request's remaining tokens can be reassigned
        immediately: the old request keeps receiving from the chunk
        snapshots, the new request's prefill+insert are device-ordered
        after those chunks, and the next dispatched chunk serves the new
        occupant — admission overlaps the tail of the previous request
        instead of waiting out a fetch round trip."""
        steps = self._inflight_steps()
        out = []
        for i, r in enumerate(self._slot_req):
            if r is None:
                out.append(i)
            elif r.emitted + steps.get(i, 0) >= r.max_new_tokens or r.cancelled:
                out.append(i)
        return out

    def _any_active(self) -> bool:
        return any(r is not None for r in self._slot_req)

    def _needed_steps(self) -> int:
        """Decode steps still required to finish every current occupant,
        beyond what is already in flight — the dispatch gate. Bounds
        speculation by real demand (an upper bound under eos/cancel, which
        the host cannot project). A fresh occupant's un-fetched prefill
        entry is NOT extra demand: _inflight_steps counts the first token
        that entry carries, so `remaining` already discounts it."""
        steps = self._inflight_steps()
        worst = 0
        for i, r in enumerate(self._slot_req):
            if r is None or r.cancelled or not r.prefill_done:
                # a partial-prefill slot is resident but not decoding: its
                # demand starts when its last chunk activates it (counting
                # it here would dispatch decode chunks that only advance
                # garbage for it)
                continue
            remaining = r.max_new_tokens - r.emitted - steps.get(i, 0)
            if remaining > worst:
                worst = remaining
        return worst

    def _drain_and_observe(self, busy: bool) -> None:
        """Shared admission head (wave and chunked schedulers): drain the
        submit queue into the waiting list, shed requests past their TTFT
        deadline, flush queue-side terminations, refresh the state gauges."""
        while True:
            try:
                block = not busy and not self._waiting
                req = self._admit_q.get(timeout=0.05) if block else self._admit_q.get_nowait()
            except queue.Empty:
                break
            if req is None:
                self._stop = True
                break
            if req.cancelled:
                req.finish_reason = req.cancel_reason
                self._observe_finish(req, time.perf_counter())
                req.out.put(None)
                continue
            self._waiting.append(req)
        if self.ttft_deadline is not None and self._waiting:
            # shed-on-deadline: a request whose first token can no longer
            # arrive inside its TTFT budget gets a fast end-of-stream now
            # instead of consuming a prefill slot it can't benefit from
            now_t = time.perf_counter()
            kept = []
            for r in self._waiting:
                if (
                    r.submitted_at is not None
                    and now_t - r.submitted_at > self.ttft_deadline
                ):
                    self.shed += 1
                    r.finish_reason = "shed"
                    self._observe_finish(r, now_t)
                    r.out.put(None)
                else:
                    kept.append(r)
            self._waiting = kept
        self._expire_deadlines(time.perf_counter())
        self._order_waiting()
        # fresh pressure sample once per scheduler pass: brownout must be
        # able to DISENGAGE while no submits arrive (submit() feeds the
        # controller too, but an empty ingress would freeze the state)
        self.overload.observe(self.predicted_wait_s())
        if self.logger is not None:
            # queue-side terminations (cancelled in the drain, shed above)
            # have no collector iteration to flush them — do it here, on
            # the scheduler thread, with no lock held
            self._flush_wide_events()
        if self.metrics is not None:
            # engine-state gauges, refreshed once per scheduler pass —
            # lock-light sets, no device interaction
            active_n = sum(r is not None for r in self._slot_req)
            self.metrics.set_gauge(
                "app_llm_slots_in_use", float(active_n), model=self.label
            )
            self.metrics.set_gauge(
                "app_llm_queue_depth",
                float(self._admit_q.qsize() + len(self._waiting)),
                model=self.label,
            )
            self.metrics.set_gauge(
                "app_llm_admission_backlog", float(self._admitting),
                model=self.label,
            )
            self.metrics.set_gauge(
                "app_llm_brownout_state",
                1.0 if self.overload.brownout else 0.0, model=self.label,
            )
            if self.ledger is not None:
                self.metrics.set_gauge(
                    "app_llm_fairness_debt", self.ledger.debt_spread(),
                    model=self.label,
                )

    def _order_waiting(self) -> None:
        """Overload-aware queue order (replaces FIFO): interactive class
        first, then least weighted-served client (the fairness ledger's
        virtual token counter — "Fairness in Serving Large Language
        Models", OSDI'24), submit order last for determinism. Also
        refreshes the ledger's waiting-client set, which anchors the
        new-arrival floor. Sorting every pass is O(n log n) on a queue
        already bounded by max_queue; stable sort keeps equal keys FIFO."""
        led = self.ledger
        with self._lock:
            clients = {r.client for r in self._waiting}
            if led is not None:
                led.set_active(self.label, clients)
            if len(self._waiting) < 2:
                return
            # one bulk ledger snapshot for the whole sort: per-request
            # counter() calls would contend the fleet-shared lock
            # len(_waiting) times per scheduler pass per replica
            counters = led.counters_for(clients) if led is not None else {}
            self._waiting.sort(
                key=lambda r: (
                    1 if r.priority == "batch" else 0,
                    counters.get(r.client, 0.0),
                    r.id,
                )
            )

    def _preempt_for_waiting(self, free: list[int]) -> list[int]:
        """Priority preemption: when waiting interactive requests
        outnumber the free slots, take slots back from batch-class
        occupants — preferring the most recently admitted victim (least
        sunk progress to redo) — and return the refreshed free list.
        Nothing interactive waiting, or nothing batch slotted, is the
        common case and costs two scans of bounded lists."""
        if not self.preemption:
            return free
        with self._lock:
            want = sum(
                1 for r in self._waiting
                if r.priority != "batch" and r.finish_reason is None
            ) - len(free)
            if want <= 0:
                return free
            victims = [
                r for r in self._slot_req
                if r is not None and r.priority == "batch"
                and not r.cancelled and r.finish_reason is None
                # per-request preemption cap: a request evicted this many
                # times keeps its slot — without the bound, interactive
                # arrivals oscillating around capacity could thrash the
                # same batch request forever, re-running an ever-growing
                # continuation prefill at exactly the moment the engine
                # is pressured
                and r.preempted < self._PREEMPT_CAP
            ]
            if not victims:
                return free
            victims.sort(key=lambda r: (r.admitted_at or 0.0), reverse=True)
            for r in victims[:want]:
                self._preempt(r)
            self._kick.set()
            return self._free_slots()

    def _preempt(self, r: GenRequest) -> None:
        """Take r's slot back NOW: scrub every in-flight reference (no
        stale emission can reach it — the entry lists are shared with the
        collector, which only emits under this same lock), then fold the
        emitted tokens into the prompt and requeue as a continuation —
        the PR 5 failover re-seed, so a preempted greedy stream resumes
        token-identically; tokens computed-but-unfetched at preemption
        are recomputed by the continuation rather than emitted stale.
        Call with the lock held, scheduler thread only."""
        slot = r.slot
        if slot is not None and self._slot_req[slot] is r:
            self._slot_req[slot] = None
            if self.kv.paged:
                # the preempting request is about to seed this slot —
                # return the blocks now (in-flight programs targeting
                # them were dispatched earlier and execute before any
                # re-user's writes; single-device program order)
                self.kv.release_slot(slot, r)
                self._kv_hi[slot] = 0
        r.slot = None
        entries = list(self._inflight)
        if self._processing is not None:
            entries.append(self._processing)
        for e in entries:
            if e[0] == "prefill":
                # keep j-alignment with the first-token array: blank the
                # request, never remove the row
                e[2][:] = [
                    (s, rr if rr is not r else None) for s, rr in e[2]
                ]
            elif e[0] == "step":
                e[2][:] = [t for t in e[2] if t[2] is not r]
                if e[4] is not None:
                    for i, rr in enumerate(e[4]):
                        if rr is r:
                            e[4][i] = None
            elif e[0] == "verify":
                e[3][:] = [t for t in e[3] if t[1] is not r]
            else:
                for i, rr in enumerate(e[2]):
                    if rr is r:
                        e[2][i] = None
        try:
            self._prefilling.remove(r)
        except ValueError:
            pass
        # continuation re-seed (ReplicatedLLMEngine._failover semantics):
        # prompt grows by what was already streamed, scheduling state
        # resets, consumer-facing state (out queue, emitted) carries over
        # goodput replay marker: everything the continuation re-prefills
        # below this position was computed once already — the chunk
        # progress if nothing streamed yet, the whole grown prompt after
        # the history fold (the served tokens re-enter as prompt rows)
        replay_to = r.prefill_pos
        if r.history:
            r.prompt_tokens = list(r.prompt_tokens) + r.history
            r.history = []
            replay_to = len(r.prompt_tokens)
        r._replay_pos = max(r._replay_pos, replay_to)
        r.prefill_pos = 0
        r.prefill_done = False
        r._rows_hi = 0
        r._prefill_t0 = None
        r._spec_pending = []
        r._spec_inflight = 0
        r.phase = "queued"
        r.preempted += 1
        if self.tracer is not None and r.span is not None:
            # journey hop: the preemption continuation re-admits inside
            # this engine (it never passes through submit()), so the
            # continuation span is recorded here — linked to the original
            # request span, same trace, hop bumped (wide event reads
            # "hop N of journey J" across preemptions AND failovers)
            r.hop += 1
            t_ns = time.time_ns()
            self.tracer.record_span(
                "llm.continuation",
                trace_id=r.span.trace_id,
                parent_id=r.span.span_id,
                start_ns=t_ns, end_ns=t_ns,
                attributes={
                    "llm.model": self.label,
                    "llm.request_id": r.id,
                    "llm.hop": r.hop,
                    "llm.kind": "preemption",
                    "llm.preempted": r.preempted,
                    "llm.emitted": r.emitted,
                },
                links=[(r.span.trace_id, r.span.span_id)],
            )
        # fresh wait epoch, mirroring failover's path through submit():
        # without this, re-admission would observe queue_wait from the
        # ORIGINAL submit — service time + both waits in one inflated
        # sample, and the request counted twice in the histogram
        r.submitted_at = time.perf_counter()
        # outstanding-work estimate: the re-run prefill plus what decode
        # still owes (the residue of the old estimate is flushed)
        self._load_tokens -= r._load_acct
        r._load_acct = len(r.prompt_tokens) + max(
            0, r.max_new_tokens - r.emitted
        )
        self._load_tokens += r._load_acct
        self._waiting.append(r)
        if self.ledger is not None:
            self.ledger.touch(r.client)
        self.preemptions += 1
        if self.logger is not None:
            self.logger.info(
                f"preempted batch request {r.id} (emitted {r.emitted}); "
                "requeued as continuation"
            )
        if self.metrics is not None:
            self.metrics.increment_counter(
                "app_llm_preemptions_total", model=self.label
            )

    def _expire_deadlines(self, now: float) -> None:
        """Retire every request whose wall deadline passed OR that was
        cancelled by its consumer — INCLUDING slotted ones.
        ttft_deadline_ms only sheds at admission; before this sweep a
        decode past its HTTP timeout kept burning chip time for a client
        that already hung up. The cancel half closes the same gap for
        disconnect-cancels: a cancelled occupant with an IDLE pipeline
        (nothing in flight to carry the finish through _emit_to) used to
        hold its slot and its consumer's end-of-stream until the next
        admission reassigned it. Retired occupants free their slot
        through the virtual-free path (in-flight snapshots drop their
        tokens), so the next admission reuses the slot immediately. Runs
        once per scheduler pass: O(slots + waiting), no device work."""
        deadline_hit = 0
        expired: list[tuple[GenRequest, str]] = []
        with self._lock:
            for slot, r in enumerate(self._slot_req):
                if r is None or r.finish_reason is not None:
                    continue
                if r.cancelled:
                    expired.append((r, r.cancel_reason))
                    self._slot_req[slot] = None
                elif r.deadline is not None and now > r.deadline:
                    expired.append((r, "deadline"))
                    self._slot_req[slot] = None
            if self._waiting:
                kept = []
                for r in self._waiting:
                    if r.finish_reason is not None:
                        continue  # closed elsewhere; drop from the queue
                    if r.cancelled:
                        expired.append((r, r.cancel_reason))
                    elif r.deadline is not None and now > r.deadline:
                        expired.append((r, "deadline"))
                    else:
                        kept.append(r)
                self._waiting = kept
            for r, reason in expired:
                r.cancelled = True  # in-flight snapshots drop its tokens
                r.finish_reason = reason
                if reason == "deadline":
                    self.deadline_cancels += 1
                    deadline_hit += 1
                self._observe_finish(r, now)
                r.out.put(None)
        if expired:
            self._kick.set()
            if deadline_hit and self.metrics is not None:
                self.metrics.increment_counter(
                    "app_llm_deadline_cancels_total",
                    by=float(deadline_hit), model=self.label,
                )

    def _admit(self) -> bool:
        """Admission entry, called once per scheduler pass (THE seam:
        tests wedge it to freeze admission). Dispatches to the
        token-budget scheduler's immediate slot assignment or the
        monolithic path's wave batching."""
        return self._admit_chunked() if self.chunked else self._admit_wave()

    def _admit_wave(self) -> bool:
        """Pull waiting requests into (virtually) free slots, prefilling
        per bucket. Purely dispatch-side: decode chunks in flight are
        untouched, and the first sampled tokens merge into the device tail
        without a host round trip.

        Admission BATCHING: a prefill wave costs roughly the same device
        time at nb=1 as at nb=admit_cap, so firing a wave per trickle
        arrival melts throughput at mid load (measured open-loop: 200 QPS
        offered -> 138 achieved). While decode is active and a partial
        wave's oldest request is younger than admit_delay, hold admission
        to let the wave fill; an idle device admits immediately."""
        jnp = self._jnp
        with self._lock:
            free = self._free_slots()
            busy = self._any_active() or self._inflight or self._processing is not None
        self._drain_and_observe(busy)
        if self._waiting:
            free = self._preempt_for_waiting(free)
        if not self._waiting or not free:
            return False
        # Rate-gated wave-fill hold: a prefill wave costs device time that
        # barely depends on occupancy within a power-of-two width, so at
        # HIGH arrival rates it pays to wait (bounded by admit_delay) until
        # a meaningful wave accumulates. The gate (expected arrivals in the
        # window >= 4) keeps low-rate traffic on the admit-immediately
        # path: holding there adds chunk-pipeline slide (~2 chunks of
        # latency) and the wave never fills anyway.
        gap = self._ema_gap
        expected = self.admit_delay / gap if gap and gap > 0 else 0.0
        goal = min(self.admit_cap, int(expected))
        if (
            self.admit_delay > 0
            and busy
            and goal >= 4
            and len(self._waiting) < min(goal, len(free))
            and self._waiting[0].submitted_at is not None
            and time.perf_counter() - self._waiting[0].submitted_at < self.admit_delay
        ):
            return False
        pulled = self._waiting[: len(free)]
        self._waiting = self._waiting[len(free):]
        # visible to load() while in flight between _waiting and _slot_req —
        # without this the router undercounts a replica mid-admission and
        # least-loaded piles every request onto it
        self._admitting += len(pulled)
        # prefix consult: a hit skips its prefill wave entirely — the
        # retained KV rows and stored last-token logits go through the SAME
        # insert path as a prefilled wave (one _insert_many scatter + one
        # tail merge), so shared-prefix traffic costs no device prefill.
        # Contiguous layout: PrefixCache.lookup pins each entry until its
        # rows are inserted. Paged layout: the radix tree serves exact
        # hits (partials need the chunked scheduler's append path) and a
        # block RESERVATION gates admission — a pool that cannot host the
        # request's worst case keeps it queued instead of overcommitting.
        hits: list[tuple[GenRequest, Any]] = []
        misses: list[GenRequest] = pulled
        if self.kv.paged:
            # NOTE: no session restore here — the wave scheduler has no
            # mid-prompt append path, so a restored session could only
            # serve exact end records (which session publishes don't
            # store logits for); restoring would be pure wasted DMA +
            # pool churn. Sessions want the chunked scheduler.
            hits, misses, blocked = [], [], []
            for r in pulled:
                plan = self.kv.lookup_seed(r.prompt_tokens, allow_partial=False)
                r._kv_plan = plan
                if not self.kv.admit_reserve(
                    len(r.prompt_tokens), r.max_new_tokens, plan
                ):
                    self._kv_release_plan(r)
                    blocked.append(r)
                    continue
                r._kv_resv = self.kv.reserve_need(
                    len(r.prompt_tokens), r.max_new_tokens, plan
                )
                (hits.append((r, plan)) if plan is not None else misses.append(r))
            if blocked:
                with self._lock:
                    self._waiting = blocked + self._waiting
                    self._admitting -= len(blocked)
                pulled = [r for r in pulled if r not in blocked]
            if not pulled:
                return False
        elif self.kv.prefix is not None:
            hits, misses = [], []
            for r in pulled:
                e = self.kv.prefix.lookup(self.kv.prefix.key_for(r.prompt_tokens))
                (misses.append(r) if e is None else hits.append((r, e)))
        try:
            return self._admit_waves(hits, misses, free)
        except BaseException:
            self._requeue_stranded(pulled)
            raise
        finally:
            if self.kv.paged:
                # plans never attached (escaping device errors, groups
                # not reached) must drop their pins
                for r, _plan in hits:
                    self._kv_release_plan(r)

    def _admit_waves(
        self,
        hits: list[tuple[GenRequest, Any]],
        misses: list[GenRequest],
        free: list[int],
    ) -> bool:
        jnp = self._jnp
        self._fault("admission_oom")  # chaos seam: callers requeue stranded
        try:
            self._admit_exact_hits(hits, free)
        finally:
            # unpin EVERY looked-up entry in all paths — including the
            # groups never reached when an earlier group's device call
            # escapes to the scheduler's recovery. A pin that never drops
            # makes its entry uneviction-able forever. (Paged hits carry
            # SeedPlans, not pinned entries — radix mutation is
            # scheduler-thread-only, so nothing to release.)
            if self.kv.prefix is not None:
                for _, e in hits:
                    self.kv.prefix.release(e)
        # group by bucket to share prefill executions; chunks of admit_cap
        by_bucket: dict[int, list[GenRequest]] = {}
        for r in misses:
            by_bucket.setdefault(self._bucket_for(len(r.prompt_tokens)), []).append(r)
        by_wave: list[tuple[int, list[GenRequest]]] = []
        for bucket, reqs in by_bucket.items():
            for i in range(0, len(reqs), self.admit_cap):
                by_wave.append((bucket, reqs[i : i + self.admit_cap]))
        for bucket, reqs in by_wave:
            nb = self._wave_width(len(reqs))
            pack = np.zeros((nb, bucket + 2), np.int32)
            pack[:, -2] = 1  # pad rows: 1 token, discarded
            for j, r in enumerate(reqs):
                n = len(r.prompt_tokens)
                pack[j, :n] = r.prompt_tokens
                pack[j, -2] = n
                pack[j, -1] = np.float32(r.temperature).view(np.int32)
            t0 = time.perf_counter()
            with engine_span("dispatch.call", self._hb_dispatch, kind="prefill"):
                first_dev, new_cache, logits_dev, self._rng = self._prefill_op(
                    self.params, jnp.asarray(pack), self._rng,
                )
            if self.metrics is not None:
                self.metrics.record_histogram(
                    "app_tpu_stats", time.perf_counter() - t0,
                    model="llm", op=f"prefill_dispatch_{bucket}",
                )
            if self.kv.prefix is not None:
                # retain each fresh row + its last-token logits for future
                # hits; device-side slices, refcount/LRU inside the cache.
                # Rows are TRIMMED to the wave's bucket (valid rows never
                # exceed it — dense slabs are capacity-wide and mostly pad
                # at short buckets, so storing them whole would spend the
                # byte budget capacity/bucket-fold on padding); assemble()
                # pads back to capacity at hit time.
                keep = min(bucket, self.kv.capacity)
                for j, r in enumerate(reqs):
                    self.kv.prefix.put(
                        self.kv.prefix.key_for(r.prompt_tokens),
                        new_cache.k[:, j : j + 1, :keep],
                        new_cache.v[:, j : j + 1, :keep],
                        len(r.prompt_tokens), logits_dev[j : j + 1],
                    )
            self._slot_in(
                reqs, first_dev, new_cache, free,
                wave_nb=nb, wave_t0=t0, bucket=bucket,
            )
            if self.kv.paged and self.kv.share:
                # publish AFTER the insert (paged publishing shares the
                # SLOT's resident blocks in place — they must hold the
                # rows first); the contiguous path published the wave's
                # own rows pre-insert above
                for j, r in enumerate(reqs):
                    if r.slot is not None and self._slot_req[r.slot] is r:
                        self._kv_publish(
                            r.slot, r,
                            None if logits_dev is None else logits_dev[j : j + 1],
                        )
        return True

    def _admit_exact_hits(
        self, hits: list[tuple[GenRequest, Any]], free: list[int]
    ) -> None:
        """Dispatch exact prefix-cache hits (both schedulers share this):
        per admit_cap group, assemble the pinned entries' rows into one
        insert wave, re-sample each request's first token from the stored
        last-token logits at its own temperature, and slot the group in.
        Callers own the pins — their finally releases EVERY looked-up
        entry, including groups never reached when a device call escapes
        to the scheduler's recovery."""
        if self.kv.paged:
            return self._admit_exact_hits_paged(hits, free)
        jnp = self._jnp
        for i in range(0, len(hits), self.admit_cap):
            group = hits[i : i + self.admit_cap]
            reqs = [r for r, _ in group]
            nb = self._wave_width(len(reqs))
            t0 = time.perf_counter()
            new_cache, logits = self.kv.prefix.assemble(
                [e for _, e in group], nb, self.kv.capacity
            )
            temps = np.zeros((nb,), np.float32)
            temps[: len(reqs)] = [r.temperature for r in reqs]
            first_dev, self._rng = self._hit_first_op(
                self._rep(logits), jnp.asarray(temps), self._rng
            )
            for r in reqs:
                r.prefix_hit = True
            self._slot_in(reqs, first_dev, new_cache, free, wave_t0=t0)

    def _admit_exact_hits_paged(
        self, hits: list[tuple[GenRequest, Any]], free: list[int]
    ) -> None:
        """Paged exact hits: NO KV rows move for the shared prefix — the
        slot's block table points at the radix blocks in place
        (refcount++); only the sub-block tail is block-copied (COW by
        construction) and the first token re-samples from the stored
        last-token logits, exactly the PrefixCache exact-hit contract."""
        jnp = self._jnp
        M = self.admit_cap
        for i in range(0, len(hits), M):
            group = hits[i : i + M]
            reqs = [r for r, _ in group]
            nb = self._wave_width(len(reqs))
            t0 = time.perf_counter()
            rows = [p.logits for _, p in group]
            rows += [rows[0]] * (nb - len(group))
            logits = jnp.concatenate(rows, axis=0)
            temps = np.zeros((nb,), np.float32)
            temps[: len(reqs)] = [r.temperature for r in reqs]
            first_dev, self._rng = self._hit_first_op(
                self._rep(logits), jnp.asarray(temps), self._rng
            )
            now = time.perf_counter()
            for r in reqs:
                self._observe_admission(r, now)
            oob_b = self.kv.pool.n_blocks
            with self._work_cv:
                srcs = np.full((M,), oob_b, np.int32)
                dsts = np.full((M,), oob_b, np.int32)
                slot_idx = np.full((M,), self.slots, np.int32)
                lens = np.zeros((M,), np.int32)
                meta = np.zeros((3, M), np.int32)
                taken: list[tuple[int, GenRequest]] = []
                for j, (r, plan) in enumerate(group):
                    slot = free.pop(0)
                    self._assign_slot(r, slot, now)
                    info = self._kv_attach(r, slot, plan)
                    taken.append((slot, r))
                    r.prefix_hit = True
                    r.prefill_pos = len(r.prompt_tokens)
                    r.prefill_done = True
                    self._load_credit(r, len(r.prompt_tokens))
                    for s_, d_ in info["copies"]:
                        srcs[j], dsts[j] = s_, d_
                    slot_idx[j] = slot
                    lens[j] = info["seed_len"]
                    meta[0, j], meta[1, j] = slot, j
                    meta[2, j] = np.float32(r.temperature).view(np.int32)
                for j in range(len(group), M):
                    meta[:, j] = meta[:, 0]
                self.cache, self._kv_scales = self._seed_op(
                    self.cache, self._kv_scales,
                    jnp.asarray(srcs), jnp.asarray(dsts),
                    jnp.asarray(slot_idx), jnp.asarray(lens),
                )
                md = jnp.asarray(meta)
                self._tail, self._active, self._temps = self._admit_update(
                    self._tail, self._active, self._temps, first_dev, md
                )
                self._start_fetch(first_dev)
                self._inflight.append((
                    "prefill", first_dev, taken,
                    {**self._step_open(None, "prefill", self._hit_first_op, t0,
                                       time.perf_counter()),
                     "nb": 0, "bucket": None},
                ))
                self._admitting -= len(reqs)
                self._work_cv.notify()

    def _requeue_stranded(self, pulled: list[GenRequest]) -> None:
        """An escaping admission error strands requests already sliced out
        of _waiting but never slotted: they appear in no in-flight entry
        and own no slot, so _recover_all/_close_unreachable walk right
        past them and their consumers would hang until the stream timeout.
        Put exactly those back at the head of _waiting — recovery leaves
        the queue intact, so the next scheduler pass retries them (and
        _die's drain closes them if the engine is lost). Slotted members
        of a failed group stay out: _abort_all reaches them via the slot
        table."""
        with self._lock:
            stranded = [
                r for r in pulled
                if r.finish_reason is None
                and (r.slot is None or self._slot_req[r.slot] is not r)
            ]
            self._waiting = stranded + self._waiting
            self._admitting -= len(stranded)
        if self.kv.paged:
            # hand unconsumed block promises and plan pins back: a
            # reservation/pin whose request re-queued would otherwise
            # shrink the pool forever
            for r in stranded:
                if r._kv_resv:
                    self.kv.unreserve(r._kv_resv)
                    r._kv_resv = 0
                self._kv_release_plan(r)

    def _observe_admission(self, r: GenRequest, now: float) -> None:
        """queue_wait closes at admission (slot assigned, KV en route)."""
        r.admitted_at = now
        r.phase = "prefill"
        if self.ledger is not None and not r._prompt_billed:
            # prompt tokens bill once per request lifetime: a preempted
            # or failed-over continuation re-prefills its (grown) prompt,
            # but double-billing it would punish the client for the
            # engine's own scheduling decision
            r._prompt_billed = True
            self.ledger.charge(r.client, len(r.prompt_tokens))
        if r.submitted_at is not None:
            wait = now - r.submitted_at
            self._phases["queue_wait"].observe(wait)
            if self.metrics is not None:
                self.metrics.record_histogram(
                    "app_llm_queue_wait_seconds", wait, model=self.label,
                    exemplar=(
                        {"trace_id": r.span.trace_id}
                        if r.span is not None else None
                    ),
                    **self._role_labels,
                )
            self._phase_span(r, "llm.queue_wait", r.submitted_at, now)

    def _assign_slot(self, r: GenRequest, slot: int, now: float) -> None:
        """Make r the slot's occupant (call with the lock held). A
        cancelled previous occupant may have no in-flight snapshot left
        to deliver its end-of-stream — close it here (same contract as
        the wave path's _slot_in)."""
        old = self._slot_req[slot]
        if old is not None and old.cancelled and old.finish_reason is None:
            old.finish_reason = old.cancel_reason
            self._observe_finish(old, now)
            old.out.put(None)
        self._slot_req[slot] = r
        r.slot = slot
        self._stat_admitted += 1
        if self.lora_slots and self._aids_host[slot] != r._aid:
            # the slot's lane now computes under r's adapter; the device
            # mirror re-ships lazily at the next dispatch (_ship_aids)
            self._aids_host[slot] = r._aid
            self._aids_dirty = True

    def _ship_aids(self) -> None:
        """Re-ship the per-slot adapter-id vector into the params pytree
        when slot assignments changed (SCHEDULER THREAD ONLY — dispatches
        follow immediately). One tiny [slots] int32 h2d per assignment
        batch, not per dispatch: the tables inside params are untouched
        and params is never donated, so this is a dict rebuild around the
        same device buffers and every jit cache stays warm."""
        if not self.lora_slots or not self._aids_dirty:
            return
        with self._lock:
            host = np.asarray(self._aids_host, np.int32)
            self._aids_dirty = False
        if self._sharded:
            from jax.sharding import NamedSharding, PartitionSpec as _P

            aids = self._jax.device_put(
                host, NamedSharding(self.mesh, _P(None))
            )
        elif self.device is not None:
            aids = self._jax.device_put(host, self.device)
        else:
            aids = self._jax.device_put(host)
        self.params = {**self.params, "aids": aids}

    # -- paged-pool plumbing (kvcache.paged; SCHEDULER THREAD ONLY — the
    # helpers below dispatch device work against the donated pool) -------
    def _tables_device(self):
        """Device mirror of the block tables, re-shipped only when the
        host bookkeeping changed (one small h2d per table mutation, not
        per dispatch)."""
        t = self.kv.take_tables()
        if t is not None:
            self._tables_dev = self._jnp.asarray(t)
        return self._tables_dev

    def _kv_attach(self, r: GenRequest, slot: int, plan) -> dict:
        """Bind a slot's block table to its (possibly shared) seed plan;
        releases the previous occupant's blocks in the same move. The
        plan's lookup-time pins transfer to the slot (attach_seed)."""
        plen = len(r.prompt_tokens)
        info = self.kv.attach_seed(slot, plan, r, plen, r.max_new_tokens)
        r._kv_limit = self.kv.reserve_tokens(plen, r.max_new_tokens)
        r._kv_resv = 0  # admission promise consumed (now on the slot)
        r._kv_plan = None  # pins adopted by the slot table
        self._kv_hi[slot] = info["seed_len"]
        return info

    def _kv_release_plan(self, r: GenRequest) -> None:
        """Drop an unconsumed seed plan's pins (blocked requeues,
        stranded admissions, groups never reached after an escaping
        device error). Idempotent — attach clears the plan."""
        plan = r._kv_plan
        if plan is not None:
            r._kv_plan = None
            self.kv.release_plan(plan)

    def _kv_publish(self, slot: int, r: GenRequest, logits_dev=None, *,
                    session: bool = False) -> None:
        """Publish a slot's resident prefix into the radix tree: full
        blocks shared in place (refcount++), the sub-block tail COPIED
        into a radix-owned block (one tiny device dispatch), last-token
        logits retained for exact hits. session=True publishes the whole
        conversation (prompt + emitted) and pins it to the session id."""
        if not self.kv.paged or self.kv.radix is None:
            return
        if r._aid != 0:
            # adapted lanes never publish: their K/V rows were computed
            # under THIS tenant's wq/wkv deltas, so sharing them through
            # the radix tree would seed other tenants (or the base) with
            # prefix state from the wrong weights
            return
        # session publishes drop the LAST emitted token: a sampled token's
        # K/V row is only written when it re-enters as the next step's
        # input, so the final token of a finished stream has no resident
        # row — the next turn re-prefills it along with the new text
        tokens = r.prompt_tokens + (r.history[:-1] if session else [])
        if not tokens:
            return
        plan = self.kv.publish_plan(slot, tokens, want_tail=True)
        if plan is None:
            return
        jnp = self._jnp
        if plan["tail_dst"] >= 0:
            # padded to the SAME (admit_cap,) shape the exact-hit seeds
            # and warmup use — a (1,)-shaped variant would compile a
            # fresh executable on the scheduler thread at the first
            # publish, mid-serving (pad lanes: src clipped, dst/slot
            # out of bounds -> dropped)
            M = self.admit_cap
            oob_b = self.kv.pool.n_blocks
            srcs = np.full((M,), oob_b, np.int32)
            dsts = np.full((M,), oob_b, np.int32)
            srcs[0], dsts[0] = plan["tail_src"], plan["tail_dst"]
            self.cache, self._kv_scales = self._seed_op(
                self.cache, self._kv_scales,
                jnp.asarray(srcs), jnp.asarray(dsts),
                jnp.full((M,), self.slots, jnp.int32),  # no length change
                jnp.zeros((M,), jnp.int32),
            )
        self.kv.publish_commit(
            plan, tokens, logits=logits_dev,
            logits_nbytes=(0 if logits_dev is None else int(logits_dev.nbytes)),
            session_id=(r.session_id if session else None),
        )

    def _kv_session_flush(self) -> None:
        """Process end-of-turn session publishes the collector deferred
        (only the scheduler may dispatch against the donated pool). Slot
        ownership is re-checked: under slot pressure a reassigned slot's
        publish is skipped — the session goes cold, never corrupt."""
        while self._session_pub:
            slot, r = self._session_pub.popleft()
            if self.kv.slot_owner(slot) is r and not r._session_published:
                self._kv_publish(slot, r, None, session=True)
            r._session_published = True

    def _kv_sweep(self) -> None:
        """Return retired occupants' blocks to the pool. Runs after the
        session flush so an end-of-turn publish still sees its blocks;
        finished session turns awaiting their publish keep them one more
        pass."""
        for i in range(self.slots):
            r = self.kv.slot_owner(i)
            if not isinstance(r, GenRequest):
                continue
            if r.finish_reason is None or r.finish_reason == "failover":
                continue
            if (
                r.session_id and not r._session_published
                and r.finish_reason in ("eos", "length")
            ):
                continue
            cur = self._slot_req[i]
            if cur is None or cur is r:
                self.kv.release_slot(i, r)
                self._kv_hi[i] = 0

    def _kv_session_spill(self) -> None:
        """LRU-spill cold sessions' blocks to the host tier when their
        device budget is exceeded: fetch the blocks (d2h), hand them to
        the offload store, release the device copies."""
        if not self.kv.paged or self.kv.sessions is None:
            return
        cands = self.kv.spill_candidates()
        if not cands:
            return
        from .kvcache.paged import gather_blocks_host

        for s in cands:
            path = self.kv.session_path(s.id)
            if path is None:
                continue
            blocks = list(path["blocks"])
            if path["tail"] >= 0:
                blocks.append(path["tail"])
            if not blocks:
                continue
            sc = self._kv_scales if self.kv.int8 else None
            k, v, scales = gather_blocks_host(
                self.cache.k, self.cache.v, blocks, rows=self.kv.row_shapes,
                scales=sc,
            )
            payload = {
                "tokens": path["tokens"], "k": k, "v": v, "sc": scales,
                "n_full": len(path["blocks"]), "tail_len": path["tail_len"],
            }
            nbytes = k.nbytes + v.nbytes + (
                scales.nbytes if scales is not None else 0
            )
            self.kv.spill_commit(s.id, payload, nbytes)

    def _session_prepare(self, sid: str) -> None:
        """Admission-side session touch: a spilled conversation is
        restored block-wise (h2d into fresh pool blocks, re-inserted
        into the radix) BEFORE the radix consult, so the next turn's
        prompt block-shares the whole history. A pool too tight to
        restore leaves the session cold — full re-prefill, never an
        error."""
        if not self.kv.paged or self.kv.sessions is None or not sid:
            return
        if self.kv.session_touch(sid) != "spilled":
            return
        payload = self.kv.restore_fetch(sid)
        if payload is None or payload.get("k") is None:
            return
        n = int(payload["k"].shape[1])
        ids = self.kv.alloc_restore(n)
        if ids is None:
            # the payload is consumed and the pool cannot host it: drop
            # the session cleanly (a "spilled" entry with no payload
            # would leak in the registry and dead-end every later turn)
            self.kv.session_forget(sid)
            return
        self._kv_restore_blocks(
            payload["k"], payload["v"], payload.get("sc"), ids
        )
        n_full = int(payload["n_full"])
        tail_block = ids[n_full] if n > n_full else -1
        self.kv.restore_commit(
            sid, payload["tokens"], ids[:n_full], tail_block,
            int(payload["tail_len"]),
        )

    def _kv_restore_blocks(self, k, v, sc, ids: list[int]) -> None:
        """Scatter block payloads (host numpy from a session spill, or
        arrays a KV handoff placed on this engine's device) into freshly
        allocated pool blocks through the padded restore-op family.
        SCHEDULER THREAD ONLY — the restore op donates the pool."""
        jnp = self._jnp
        n = len(ids)
        width = 1 << max(0, n - 1).bit_length()  # pow-2 compile shapes
        op = self._programs.restore_op(width)
        pad = width - n

        def padd(a, axis):
            a = jnp.asarray(a)
            if pad == 0:
                return a
            pw = [(0, 0)] * a.ndim
            pw[axis] = (0, pad)
            return jnp.pad(a, pw)

        hk = padd(k, 1)
        hv = padd(v, 1)
        hs = (
            padd(sc, 2) if self.kv.int8
            else jnp.zeros((0,), jnp.float32)
        )
        dsts = jnp.asarray(
            np.asarray(ids + [self.kv.pool.n_blocks] * pad, np.int32)
        )
        with self._work_cv:
            self.cache, self._kv_scales = op(
                self.cache, self._kv_scales, hk, hv, hs, dsts
            )

    # -- scheduler-thread host work (KV handoff; disaggregated serving) --
    def _run_sched_work(self) -> None:
        """Run host-work closures other threads queued for the scheduler
        (the only thread allowed to dispatch against the donated pool
        arrays). A closure's error lands in its caller's box — it must
        never kill the engine loop."""
        while self._sched_work:
            try:
                fn, box = self._sched_work.popleft()
            except IndexError:  # racing _die's drain
                break
            try:
                box["result"] = fn()
            except Exception as e:  # noqa: BLE001 — caller's error, not ours
                box["error"] = e
            finally:
                box["done"].set()

    def _fail_sched_work(self) -> None:
        """End every queued scheduler-work box (engine dying/closing) so
        handoff callers fail fast instead of riding out their timeout."""
        while self._sched_work:
            try:
                _fn, box = self._sched_work.popleft()
            except IndexError:
                break
            box["error"] = EngineStoppedError("engine stopped")
            box["done"].set()

    def _run_on_scheduler(self, fn, timeout: float | None = None):
        if not self.alive():
            raise EngineStoppedError("engine stopped")
        box: dict = {"done": threading.Event(), "result": None, "error": None}
        self._sched_work.append((fn, box))
        self._kick.set()
        if not self.alive():
            # raced _die/close past the check above: their one-shot
            # _fail_sched_work may already have drained the deque before
            # our append, so nothing would ever pop this box — drain it
            # ourselves and fail fast instead of riding out the timeout
            self._fail_sched_work()
        wait_s = timeout if timeout is not None else 30.0
        if not box["done"].wait(wait_s):
            raise TimeoutError(
                f"scheduler work timed out after {wait_s}s (engine "
                f"{'alive' if self.alive() else 'dead'})"
            )
        if box["error"] is not None:
            raise box["error"]
        return box["result"]

    def kv_placement(self):
        """Where this engine's pool arrays live — the ``jax.device_put``
        target for a direct device-to-device KV handoff (the committed
        replica device, or the submesh NamedSharding of a TP engine).
        None = unpinned default placement; handoff callers host-stage."""
        if self._kv_sharding is not None:
            return self._kv_sharding
        return self.device

    def kv_handoff_export(
        self, prompt_tokens: list[int], *, timeout: float | None = None,
    ) -> dict | None:
        """Gather one exact published prompt's KV blocks plus its stored
        last-token logits for a prefill->decode handoff
        (docs/advanced-guide/sharded-serving.md#disaggregation). Returns
        the payload the peer's :meth:`kv_handoff_import` consumes —
        device arrays, so the caller chooses d2d ``jax.device_put`` or
        byte-identical host staging — or None when the prompt is not an
        exact published record (dropped publish, evicted, sharing off).
        Runs on the scheduler thread (the pool arrays are donated)."""
        if not self.kv.paged or self.kv.radix is None:
            return None
        from .kvcache.paged import viewed_rows

        jnp = self._jnp

        def work():
            t0 = time.perf_counter()
            plan = self.kv.lookup_seed(
                list(prompt_tokens), allow_partial=False, count=False
            )
            if plan is None or not plan.exact or plan.logits is None:
                if plan is not None:
                    self.kv.release_plan(plan)
                return None
            try:
                blocks = list(plan.blocks)
                tail = int(plan.tail_src)
                all_blocks = blocks + ([tail] if tail >= 0 else [])
                if not all_blocks:
                    return None
                idx = jnp.asarray(np.asarray(all_blocks, np.int32))
                # a block leaves the process as [L, n, B, h, d], whatever
                # the pool stores
                k_row, v_row = self.kv.row_shapes
                k = viewed_rows(jnp.take(self.cache.k, idx, axis=1), k_row)
                v = viewed_rows(jnp.take(self.cache.v, idx, axis=1), v_row)
                sc = (
                    jnp.take(self._kv_scales, idx, axis=2)
                    if self.kv.int8 else None
                )
                if self.metrics is not None:
                    self.metrics.record_histogram(
                        "app_llm_collective_seconds",
                        time.perf_counter() - t0,
                        model=self.label, phase="kv_handoff_gather",
                    )
                return {
                    "tokens": list(prompt_tokens),
                    "k": k, "v": v, "sc": sc,
                    "n_full": len(blocks),
                    "tail_len": int(plan.tail_len) if tail >= 0 else 0,
                    "logits": plan.logits,
                }
            finally:
                self.kv.release_plan(plan)

        return self._run_on_scheduler(work, timeout)

    def kv_handoff_import(
        self, payload: dict, *, timeout: float | None = None,
    ) -> bool:
        """Adopt a peer's exported prompt KV: allocate pool blocks,
        scatter the payload in (byte-identical — the restore-op family),
        and publish the prompt into the radix WITH its last-token
        logits, so this engine's next admission of that prompt is an
        exact hit that skips prefill entirely (the disaggregated decode
        contract). False = the pool cannot host it right now — the
        caller submits anyway and the engine re-prefills (slower, never
        wrong). Runs on the scheduler thread."""
        if not self.kv.paged or self.kv.radix is None:
            return False

        def work():
            t0 = time.perf_counter()
            k = payload["k"]
            n = int(k.shape[1])
            ids = self.kv.alloc_restore(n)
            if ids is None:
                return False
            try:
                self._kv_restore_blocks(k, payload["v"], payload.get("sc"), ids)
            except BaseException:
                self.kv.release_blocks(ids)
                raise
            n_full = int(payload["n_full"])
            tail_block = ids[n_full] if n > n_full else -1
            logits = payload.get("logits")
            logits_dev = None if logits is None else self._jnp.asarray(logits)
            self.kv.handoff_commit(
                payload["tokens"], ids[:n_full], tail_block,
                int(payload["tail_len"]),
                logits=logits_dev,
                logits_nbytes=(
                    0 if logits_dev is None else int(logits_dev.nbytes)
                ),
            )
            if self.metrics is not None:
                self.metrics.record_histogram(
                    "app_llm_collective_seconds",
                    time.perf_counter() - t0,
                    model=self.label, phase="kv_handoff_scatter",
                )
            return True

        return self._run_on_scheduler(work, timeout)

    def _admit_chunked(self) -> bool:
        """Chunked-scheduler admission: assign waiting requests to
        (virtually) free slots IMMEDIATELY — no wave-fill hold, because
        per-step packing replaces wave batching — and classify each
        against the prefix cache: an exact hit skips prefill entirely
        (stored last-token logits, the wave path's machinery); a partial
        hit seeds the slot's KV with the shared prefix and starts the
        prefill cursor mid-prompt; a miss starts at 0. Misses and
        partials do no prefill compute here — their chunks are packed
        into unified steps by _dispatch_step."""
        jnp = self._jnp
        with self._lock:
            free = self._free_slots()
            busy = (
                self._any_active() or bool(self._prefilling)
                or bool(self._inflight) or self._processing is not None
            )
        self._drain_and_observe(busy)
        if self._waiting:
            free = self._preempt_for_waiting(free)
        if not self._waiting or not free:
            return False
        self._fault("admission_oom")  # chaos seam: nothing pulled yet
        pulled = self._waiting[: len(free)]
        self._waiting = self._waiting[len(free):]
        self._admitting += len(pulled)
        hits: list[tuple[GenRequest, Any]] = []
        partials: list[tuple[GenRequest, Any]] = []
        rest: list[GenRequest] = pulled
        if self.kv.paged:
            # radix consult at BLOCK granularity: exact end records skip
            # prefill entirely; any block-aligned shared prefix seeds the
            # slot mid-prompt (the generalization of lookup_longest —
            # sibling prompts share every common block, not just stored
            # whole rows). The block reservation gates admission: a pool
            # that cannot host a request keeps it queued.
            rest, blocked = [], []
            for r in pulled:
                if r.session_id:
                    self._session_prepare(r.session_id)
                # constrained requests force a radix MISS: an exact hit
                # admits through _hit_first, a program the grammar mask
                # does not ride — re-prefilling trades latency for the
                # validity guarantee (partial seeds would be fine, but
                # one rule is auditable). Adapted requests (gofr_tpu.lora)
                # also force a miss: shared radix blocks hold K/V computed
                # under the BASE wq/wkv, not this tenant's deltas.
                plan = (
                    self.kv.lookup_seed(r.prompt_tokens)
                    if self.kv.share and r.grammar is None and r._aid == 0
                    else None
                )
                r._kv_plan = plan
                if not self.kv.admit_reserve(
                    len(r.prompt_tokens), r.max_new_tokens, plan
                ):
                    self._kv_release_plan(r)
                    blocked.append(r)
                    continue
                r._kv_resv = self.kv.reserve_need(
                    len(r.prompt_tokens), r.max_new_tokens, plan
                )
                if plan is None:
                    rest.append(r)
                elif plan.exact:
                    hits.append((r, plan))
                else:
                    partials.append((r, plan))
            if blocked:
                with self._lock:
                    self._waiting = blocked + self._waiting
                    self._admitting -= len(blocked)
                pulled = [r for r in pulled if r not in blocked]
            if not pulled:
                return False
        elif self.kv.prefix is not None:
            rest = []
            for r in pulled:
                if r.grammar is not None or r._aid != 0:
                    rest.append(r)  # constrained/adapted: full prefill
                    continue
                # mid-prompt seeding is a dense-layout move: a rolling
                # entry's ring rows are laid out for ITS final length and
                # cannot serve a shorter prefix — the cache skips the
                # partial probe entirely (no pin/LRU-bump/counter for
                # hits we would discard)
                e, exact = self.kv.prefix.lookup_longest(
                    r.prompt_tokens, allow_partial=not self.kv.rolling
                )
                if e is None:
                    rest.append(r)
                elif exact:
                    hits.append((r, e))
                else:
                    partials.append((r, e))
        try:
            # exact hits ride the wave path's machinery unchanged: stored
            # logits -> first token, rows -> insert_many (contiguous) or
            # table seeding (paged), slot activated
            self._admit_exact_hits(hits, free)
            # partial hits: seed the shared prefix, start the prefill
            # cursor mid-prompt, remaining chunks run through unified steps
            now = time.perf_counter()
            if self.kv.paged:
                # block-granular seeding is pure table bookkeeping: the
                # slot's table points at the shared radix blocks in
                # place — ZERO device work; the first append's pack
                # carries the cursor, so even lengths need no scatter
                with self._work_cv:
                    for r, plan in partials:
                        slot = free.pop(0)
                        self._assign_slot(r, slot, now)
                        self._kv_attach(r, slot, plan)
                        r.prefix_hit = True
                        r.prefill_pos = plan.shared
                        r._rows_hi = plan.shared
                        self._load_credit(r, plan.shared)
                        self._observe_admission(r, now)
                        self._prefilling.append(r)
                    self._admitting -= len(partials)
            else:
                for i in range(0, len(partials), self.admit_cap):
                    group = partials[i : i + self.admit_cap]
                    nb = self._wave_width(len(group))
                    new_cache, _logits = self.kv.prefix.assemble(
                        [e for _, e in group], nb, self.kv.capacity
                    )
                    with self._work_cv:
                        meta = np.zeros((3, self.admit_cap), np.int32)
                        for j, (r, e) in enumerate(group):
                            slot = free.pop(0)
                            self._assign_slot(r, slot, now)
                            r.prefix_hit = True
                            r.prefill_pos = e.length
                            r._rows_hi = e.length
                            self._load_credit(r, e.length)
                            meta[0, j], meta[1, j] = slot, j
                        for j in range(len(group), self.admit_cap):
                            meta[:, j] = meta[:, 0]
                        self.cache = self._insert_many(
                            self.cache, new_cache, jnp.asarray(meta)
                        )
                        for r, _e in group:
                            self._observe_admission(r, now)
                            self._prefilling.append(r)
                        self._admitting -= len(group)
        except BaseException:
            # pulled-but-unslotted requests (later groups, the whole miss
            # list) are otherwise unreachable from recovery — see
            # _requeue_stranded
            self._requeue_stranded(pulled)
            raise
        finally:
            # unpin EVERY looked-up entry/plan in all paths — including
            # groups never reached when an earlier group's device call
            # escapes to the scheduler's recovery. A pin that never
            # drops makes its entry uneviction-able (contiguous) or
            # leaks pool refs (paged).
            if self.kv.prefix is not None:
                for _r, e in hits:
                    self.kv.prefix.release(e)
                for _r, e in partials:
                    self.kv.prefix.release(e)
            elif self.kv.paged:
                for r, _plan in hits:
                    self._kv_release_plan(r)
                for r, _plan in partials:
                    self._kv_release_plan(r)
        # misses: slot residency only; chunks flow through unified steps
        if rest:
            now = time.perf_counter()
            with self._work_cv:
                for r in rest:
                    slot = free.pop(0)
                    self._assign_slot(r, slot, now)
                    if self.kv.paged:
                        self._kv_attach(r, slot, None)
                    self._observe_admission(r, now)
                    self._prefilling.append(r)
                self._admitting -= len(rest)
        self._kick.set()
        return True

    def _slot_in(
        self,
        reqs: list[GenRequest],
        first_dev,
        new_cache,
        free: list[int],
        wave_nb: int | None = None,
        wave_t0: float | None = None,
        bucket: int | None = None,
    ) -> None:
        """Shared admission tail for prefilled waves and prefix-cache hit
        waves: copy KV rows into (virtually) free slots via ONE jitted
        insert-many, scatter first tokens into the on-device chain tail,
        and queue the entry for the collector. wave_nb records prefill wave
        width telemetry (hit waves dispatched no prefill, so they don't);
        wave_t0/bucket feed the prefill phase span recorded at fetch."""
        jnp = self._jnp
        now = time.perf_counter()
        for r in reqs:
            self._observe_admission(r, now)
        with self._work_cv:
            meta = np.zeros((3, self.admit_cap), np.int32)
            taken: list[tuple[int, GenRequest]] = []
            for j, r in enumerate(reqs):
                slot = free.pop(0)
                self._assign_slot(r, slot, now)
                taken.append((slot, r))
                # wave admission covers the whole prompt in one dispatch
                r.prefill_pos = len(r.prompt_tokens)
                r.prefill_done = True
                self._load_credit(r, len(r.prompt_tokens))
                if self.kv.paged:
                    # bind the table + materialize blocks for the prompt
                    # rows the insert scatter is about to write
                    self._kv_attach(r, slot, None)
                    self.kv.ensure(slot, len(r.prompt_tokens))
                    self._kv_hi[slot] = len(r.prompt_tokens)
                meta[0, j], meta[1, j] = slot, j
                meta[2, j] = np.float32(r.temperature).view(np.int32)
            # pad entries duplicate entry 0 (idempotent)
            for j in range(len(reqs), self.admit_cap):
                meta[:, j] = meta[:, 0]
            md = jnp.asarray(meta)  # ONE packed h2d per wave
            if self.kv.paged:
                self.cache, self._kv_scales = self._insert_many(
                    self.cache, self._kv_scales, new_cache, md[:2],
                    self._tables_device(),
                )
            else:
                self.cache = self._insert_many(self.cache, new_cache, md)
            self._tail, self._active, self._temps = self._admit_update(
                self._tail, self._active, self._temps, first_dev, md
            )
            self._start_fetch(first_dev)
            # a miss wave ran the prefill program over whole prompts; a hit
            # wave only sampled first tokens from stored logits
            info = {
                **self._step_open(
                    None, "prefill",
                    self._prefill_op if bucket is not None else self._hit_first_op,
                    wave_t0 if wave_t0 is not None else now, time.perf_counter(),
                    rows=tuple((0, len(r.prompt_tokens), bucket) for r in reqs)
                    if bucket is not None else (),
                ),
                "nb": wave_nb or 0, "bucket": bucket,
            }
            self._inflight.append(("prefill", first_dev, taken, info))
            self._admitting -= len(reqs)
            if wave_nb is not None:
                # under the lock: stats() iterates _stat_waves concurrently
                self._stat_waves[wave_nb] = self._stat_waves.get(wave_nb, 0) + 1
                self._stat_wave_reqs += len(reqs)
            self._work_cv.notify()

    @staticmethod
    def _start_fetch(arr) -> None:
        copy = getattr(arr, "copy_to_host_async", None)
        if copy is not None:
            try:
                copy()
            except Exception:  # pragma: no cover — backend-dependent
                pass

    # -- the step timeline --------------------------------------------------
    def _decode_depth(self) -> int:
        """Decode-class programs in flight, the one the collector holds
        included. Call with the lock held."""
        depth = sum(1 for e in self._inflight if e[0] in _DECODE_KINDS)
        if self._processing is not None and self._processing[0] in _DECODE_KINDS:
            depth += 1
        return depth

    def _step_open(
        self, span, kind: str, op, t_dispatch: float, t_dispatched: float,
        *, k: int = 0, lanes: int = 0, rows: tuple = (), moe=None,
    ) -> dict:
        """The record of the program just dispatched (STEP_FIELDS): it rides
        the in-flight entry's info and the collector finishes it. `span` is
        the open sched.dispatch span, which learns which program it was
        (None for an admission wave: its programs go out inside sched.admit).
        Call with the lock held, before the entry is appended. `moe` is
        the device vector a routed model's program returned beside its
        tokens (llm_programs.Programs._signature), or None."""
        self._step_seq += 1
        program = getattr(op, "program", "")
        if span is not None:
            span.set(seq=self._step_seq, kind=kind, program=program)
        if moe is not None:
            self._start_fetch(moe)
        return {
            "moe_dev": moe, "moe_pairs": 0, "moe_touched": 0, "moe_pairs_routed": 0,
            "seq": self._step_seq, "kind": kind, "program": program, "k": k,
            "depth": self._decode_depth(), "lanes": lanes, "decode_ctx": (),
            "rows": rows, "t_dispatch": t_dispatch, "t_dispatched": t_dispatched,
            "t_fetch": 0.0, "t_fetched": 0.0, "t_emitted": 0.0, "emitted": 0,
        }

    def _step_close(self, info: dict, decode_ctx=(), first: int = 0) -> None:
        """The collector emitted the program's tokens: the record is whole
        and joins the ring. Call with the lock held (stats() copies the ring
        under it). `decode_ctx` pairs each decoding lane's context at the
        program's start (window-capped) with the tokens it emitted; `first`
        counts the first tokens that came out of prompt rows."""
        info["decode_ctx"] = tuple(decode_ctx)
        info["emitted"] = first + sum(n for _ctx, n in info["decode_ctx"])
        info["t_emitted"] = time.perf_counter()
        moe_dev = info.pop("moe_dev", None)
        if moe_dev is not None:
            # the program's tokens are here already, so this small vector is too
            vec = np.asarray(moe_dev).astype(np.int64)
            info["moe_pairs"], info["moe_touched"] = int(vec[0]), int(vec[1])
            info["moe_pairs_routed"] = int(vec[self._moe_routed_at])
            if self.metrics is not None:
                for kind, n in (("routed", info["moe_pairs_routed"]), ("computed", info["moe_pairs"])):
                    self.metrics.increment_counter(
                        "app_llm_moe_pairs_total", float(n), model=self.label, kind=kind
                    )
            self._moe_totals += vec
            self._moe_layer_calls += self._moe_layers * (
                info["k"] + (1 if info["kind"] == "step" else 0)
            )
        self._step_log.append(tuple(info[f] for f in STEP_FIELDS))

    def _ctx_of(self, r: GenRequest) -> int:
        """A decoding lane's context (prompt + emitted so far), capped at
        the sliding window since the rolling ring never reads past it. Read
        by the collector, where every earlier program's tokens are counted;
        at dispatch `emitted` lags by the programs in flight."""
        c = len(r.prompt_tokens) + r.emitted
        w = self._costs.sliding_window  # (0 where any layer reads everything)
        return min(c, w) if w else c

    def _ctx_read(self, r: GenRequest) -> float:
        """The positions a decoding lane's token attends, a layer on average
        (profiling.mfu.read_ctx): what the MFU and roofline observations sum.
        _ctx_of's number where every layer is alike; in a mixed stack the
        window layers' capped share and the full layers' whole context."""
        return self._mfu_mod.read_ctx(self._costs, len(r.prompt_tokens) + r.emitted)

    # -- observability ----------------------------------------------------
    def _observe_mfu(
        self, phase: str, tokens: int, flops: float, bytes_moved: float, dt: float,
    ) -> None:
        """One MFU/roofline observation for a finished device window.
        dt is the dispatch->fetch wall interval; decode chunks PIPELINE
        (up to `lookahead` in flight), so overlapping windows make this
        an apparent utilization — read the window percentiles, never sum
        them. Gauges carry the latest value; the rolling windows feed
        stats()/debug/bench."""
        if dt <= 0 or flops <= 0:
            return
        mfu = flops / dt / (self._peak_flops * self._n_chips)
        ratio = self._mfu_mod.roofline_ratio(
            flops, bytes_moved, self._peak_flops * self._n_chips,
            self._hbm_bw * self._n_chips,
        )
        self._mfu_windows[phase].observe(mfu)
        self._roofline_windows[phase].observe(ratio)
        if phase == "decode":
            self._tok_chip_window.observe(tokens / dt / self._n_chips)
        if self.metrics is not None:
            self.metrics.set_gauge(
                "app_llm_mfu", mfu, model=self.label, phase=phase
            )
            self.metrics.set_gauge(
                "app_llm_roofline_ratio", ratio, model=self.label, phase=phase
            )
            if phase == "decode":
                self.metrics.set_gauge(
                    "app_llm_tokens_per_second_per_chip",
                    tokens / dt / self._n_chips, model=self.label,
                )

    def _mfu_summary(self) -> dict:
        """The stats()/debug block: analytic constants + recent-window
        utilization percentiles + the roofline verdict (median decode
        ratio). Cheap: three window snapshots, no device interaction."""
        decode_ratio = self._roofline_windows["decode"].summary()
        return {
            "peak_flops_per_chip": self._peak_flops,
            "hbm_bw_per_chip": self._hbm_bw,
            "chips": self._n_chips,
            "params": self._costs.params,
            "flops_per_token": self._costs.matmul_flops_per_token,
            "prefill": self._mfu_windows["prefill"].summary(),
            "decode": self._mfu_windows["decode"].summary(),
            "tokens_per_second_per_chip": self._tok_chip_window.summary(),
            "roofline": {
                "prefill": self._roofline_windows["prefill"].summary(),
                "decode": decode_ratio,
                "bound": self._mfu_mod.classify_bound(decode_ratio["p50"]),
            },
        }

    def _phase_span(
        self, r: GenRequest, name: str, t0: float, t1: float,
        attrs: dict | None = None,
    ) -> None:
        """Retrospective phase span under the request's llm.request span.
        No-op for untraced requests, so the hot loop pays one None check.
        Timestamps anchor the monotonic interval [t0, t1] to a LIVE wall
        clock read (end = now, start = now - elapsed): a fixed anchor pair
        captured at engine construction would drift out of the parent
        span's live-clock window after any NTP step."""
        if r.span is None:
            return
        end_ns = time.time_ns() - int((time.perf_counter() - t1) * 1e9)
        self.tracer.record_span(
            name, trace_id=r.span.trace_id, parent_id=r.span.span_id,
            start_ns=end_ns - int((t1 - t0) * 1e9), end_ns=end_ns,
            attributes=attrs,
        )

    def _observe_finish(self, r: GenRequest, now: float, fetch_t: float | None = None) -> None:
        """Terminal observability for one request: per-token histogram,
        emit span, llm.request span closure, and the wide-event payload.
        Idempotent (error paths and stale chunk overlap may race the
        regular completion). Queues the wide event for logging OUTSIDE the
        engine lock — the collector calls this under _lock, and a stdout
        write there would serialize emission behind the logger. The whole
        body runs under _lock (re-entrant for the already-locked callers):
        the _observed check-then-set must be atomic against a concurrent
        finisher — close() on a user thread races the scheduler's drain —
        and the _wide_events append must not race _flush_wide_events'
        swap, which would silently drop the line."""
        with self._lock:
            if r._observed:
                return
            r._observed = True
            self._observe_finish_locked(r, now, fetch_t)

    def _observe_finish_locked(self, r: GenRequest, now: float, fetch_t: float | None) -> None:
        r.phase = "done"
        # flush the outstanding-work residue (cancel/shed/eos leave some)
        self._load_tokens -= r._load_acct
        r._load_acct = 0
        if 0 <= r._g_id < len(self._g_refs):
            # release the resident-grammar reference (the table slot
            # becomes evictable once no live request holds it)
            self._g_refs[r._g_id] = max(0, self._g_refs[r._g_id] - 1)
            r._g_id = -1
        if r._aid > 0 and self.lora_slots:
            # release the adapter-pool reference (mirrors the grammar
            # release above; the gid becomes evictable/reclaimable once
            # no in-flight request pins it)
            self._lora_pool.release(r._aid)
            r._aid = 0
        total = None if r.submitted_at is None else now - r.submitted_at
        queue_wait = (
            None if r.admitted_at is None or r.submitted_at is None
            else r.admitted_at - r.submitted_at
        )
        ttft = (
            None if r.first_token_at is None or r.submitted_at is None
            else r.first_token_at - r.submitted_at
        )
        tpot = None
        if r.first_token_at is not None and r.emitted > 1:
            tpot = (now - r.first_token_at) / (r.emitted - 1)
            self._phases["time_per_output_token"].observe(tpot)
            if self.metrics is not None:
                self.metrics.record_histogram(
                    "app_llm_time_per_output_token_seconds", tpot,
                    exemplar=(
                        {"trace_id": r.span.trace_id}
                        if r.span is not None else None
                    ),
                    **self._role_labels,
                    model=self.label,
                )
        if self.slo is not None and r.finish_reason not in ("cancelled", "disconnect"):
            # SLO verdict: availability counts service failures only — a
            # client that hung up is not our error budget. TTFT/TPOT
            # targets judge in ms; a request that never reached first
            # token but finished "eos"/"length" cannot happen, so None
            # latencies only ride the availability term.
            self.slo.observe(
                tenant=r.adapter or "-",
                priority=r.priority if r.priority == "batch" else "interactive",
                ok=r.finish_reason in ("eos", "length"),
                ttft_ms=None if ttft is None else ttft * 1e3,
                tpot_ms=None if tpot is None else tpot * 1e3,
            )
        # flight record: stamp the terminal outcome (timings, finish
        # reason, emitted token ids) — every terminal path funnels here,
        # so the ring never holds a dangling non-final record for a
        # finished request
        chip = dict(r._chip) if r._chip else {}
        self.flightrec.finalize(
            r,
            queue_wait_ms=None if queue_wait is None else queue_wait * 1e3,
            ttft_ms=None if ttft is None else ttft * 1e3,
            per_token_ms=None if tpot is None else tpot * 1e3,
            total_ms=None if total is None else total * 1e3,
            chip={c: round(v * 1e3, 3) for c, v in chip.items()} or None,
        )
        # perf-anomaly baselines (flightrec): sustained deviation flags
        # app_llm_anomaly and triggers a perf-incident bundle. The step
        # and spec-acceptance signals feed from the scheduler loop.
        if self.anomaly is not None:
            if queue_wait is not None:
                self.anomaly.observe("queue_wait", queue_wait * 1e3)
            if ttft is not None:
                self.anomaly.observe("ttft", ttft * 1e3)
            if tpot is not None:
                self.anomaly.observe("tpot", tpot * 1e3)
        if r.finish_reason == "disconnect":
            # dead-peer cancellation (edge detected a closed connection):
            # the slot is free and the remaining decode was never done —
            # count it so operators see abandoned-stream volume
            self.disconnect_cancels += 1
            if self.metrics is not None:
                self.metrics.increment_counter(
                    "app_llm_disconnect_cancels_total", model=self.label
                )
        if self.metrics is not None:
            # per-version request accounting (rollouts): which weight set
            # served this request — the canary dashboard's error-rate
            # denominator during a traffic shift
            self.metrics.increment_counter(
                "app_llm_requests_by_version_total",
                model=self.label, version=self.version,
                finish=r.finish_reason or "unknown",
            )
        if r.span is not None:
            if fetch_t is not None:
                # host-side tail: final tokens fetched -> emitted to the
                # consumer queue (detokenization happens at the consumer)
                self._phase_span(r, "llm.emit", fetch_t, now)
            r.span.set_attribute("llm.output_tokens", r.emitted)
            r.span.set_attribute("llm.finish_reason", r.finish_reason)
            if r.prefix_hit:
                r.span.set_attribute("llm.prefix_hit", True)
            if r.finish_reason in ("cancelled", "disconnect", "shed"):
                r.span.set_status("ERROR")
            r.span.end()
        if r.finish_reason in ("error", "poison"):
            self.errored += 1  # bake-window regression signal (rollouts)
        ms = lambda v: None if v is None else round(v * 1e3, 3)  # noqa: E731
        ev = {
            "event": "llm_request",
            "model": self.label,
            "model_version": self.version,
            "id": r.id,
            "trace_id": r.span.trace_id if r.span is not None else "",
            # journey identity: stable across failover/preemption
            # hops (the trace id of the FIRST submit), plus which hop
            # finished the work — `grep journey_id` over the fleet's
            # logs reconstructs the same object the stitcher serves
            "journey_id": r.journey_id or "",
            "hop": r.hop,
            "prompt_tokens": len(r.prompt_tokens),
            "output_tokens": r.emitted,
            "finish_reason": r.finish_reason,
            "queue_wait_ms": ms(queue_wait),
            "ttft_ms": ms(ttft),
            "per_token_ms": ms(tpot),
            "total_ms": ms(total),
            "prefix_hit": r.prefix_hit,
            "capped": r.capped,
            # chip-time attribution (gofr_tpu.goodput): device seconds
            # this request owned, by waste class — the per-request cost
            # line chargeback joins against the tenant usage windows
            "chip_ms": round(sum(chip.values()) * 1e3, 3),
            "chip_breakdown_ms": {
                c: round(v * 1e3, 3) for c, v in chip.items()
            },
        }
        # the FULL stream is retained for incident bundles regardless of
        # sampling or logger presence — a bundle's last-N wide events
        # must not have sampling holes
        self._wide_retained.append(ev)
        if self.logger is not None:
            # 1-in-N sampling (TPU_LLM_WIDE_EVENT_SAMPLE): one JSON line
            # per request is a real cost at the 1k QPS/chip target.
            # Incident lines — anything that didn't finish eos/length,
            # or that survived a death/hop — ALWAYS emit; sampled lines
            # carry the factor so log-derived rates can re-scale.
            self._wide_seq += 1
            forced = (
                r.finish_reason not in ("eos", "length")
                or r.deaths > 0
                or r.hop > 0
            )
            if self._wide_sample <= 1:
                self._wide_events.append(ev)
            elif forced:
                self._wide_events.append({**ev, "sample": 1})
            elif self._wide_seq % self._wide_sample == 0:
                self._wide_events.append({**ev, "sample": self._wide_sample})

    def _flush_wide_events(self) -> None:
        """Emit queued wide-event lines. Called with the lock NOT held."""
        if not self._wide_events:
            return
        with self._lock:
            events, self._wide_events = self._wide_events, []
        for ev in events:
            self.logger.info(ev)

    def _emit_to(self, r: GenRequest, slot: int, toks: list[int], now: float | None = None) -> int:
        """Append a request's next tokens, honoring max_new/eos/cancel, and
        return how many the consumer was handed.
        Frees the slot only if `r` still owns it (virtual-free admission
        may already have handed the slot to a successor). `now` is the
        fetch-completion time (phase attribution measures device+fetch,
        not the emit loop's position within the batch)."""
        if r.finish_reason is not None:
            return 0  # already finished; stale chunk overlap
        if self._died:
            # a dying engine must NEVER emit: its recoverable requests are
            # (or are about to be) rescued by the failover hook, and a
            # late emission here would race the continuation's stream on
            # the replacement replica (duplicate tokens). The check runs
            # under _lock — the same lock _die holds while rescuing — so
            # an emission is either fully before the rescue (counted in
            # history) or fully dropped.
            return 0
        if now is None:
            now = time.perf_counter()
        finish = None
        if r.cancelled:
            toks, finish = [], r.cancel_reason
        take = min(len(toks), r.max_new_tokens - r.emitted)
        toks = toks[:take]
        if r.eos_token >= 0 and r.eos_token in toks:
            toks = toks[: toks.index(r.eos_token) + 1]
            finish = "eos"
        if toks:
            if r.emitted == 0:
                r.first_token_at = now
                r.phase = "decode"
                if r.submitted_at is not None:
                    ttft = now - r.submitted_at
                    self._phases["ttft"].observe(ttft)
                    if self.metrics is not None:
                        # exemplar: the p99 TTFT bucket on /metrics links
                        # the trace id of the request that landed there —
                        # feed it to the journey aggregator for the full
                        # cross-process timeline
                        self.metrics.record_histogram(
                            "app_llm_ttft_seconds", ttft, model=self.label,
                            exemplar=(
                                {"trace_id": r.span.trace_id}
                                if r.span is not None else None
                            ),
                            **self._role_labels,
                        )
                        self.metrics.record_histogram(
                            "app_tpu_queue_wait", ttft, model="llm", op="ttft",
                        )
            r.out.put(toks)
            r.emitted += len(toks)
            r.history.extend(toks)  # failover continuation seed
            if r.grammar is not None:
                # host DFA mirror (drafter filter + continuation re-seed)
                st = r._g_state
                for t in toks:
                    if st < 0:
                        break
                    st = r.grammar.advance(st, t)
                r._g_state = st
            self._load_credit(r, len(toks))
            if self.ledger is not None:
                self.ledger.charge(r.client, len(toks))
        if finish is None and r.emitted >= r.max_new_tokens:
            finish = "length"
        if finish is not None:
            r.finish_reason = finish
            if (
                self.kv.paged and r.session_id
                and finish in ("eos", "length")
                and self.kv.slot_owner(slot) is r
            ):
                # defer the end-of-turn session publish to the scheduler
                # (only it may dispatch against the donated pool); the
                # block sweep keeps this slot's blocks until then
                self._session_pub.append((slot, r))
            self._observe_finish(r, time.perf_counter(), fetch_t=now)
            r.out.put(None)
            if self._slot_req[slot] is r:
                self._slot_req[slot] = None
        return len(toks)

    def _dispatch(self, needed_steps: int, span) -> int:
        """Launch one decode chunk chained from the on-device tail and
        return the dispatched chunk length (the scheduler debits it from
        its step budget). All inputs are device-resident — zero h2d
        transfers per chunk. Chunk length adapts to DEMAND, not occupancy:
        the short variant runs only for tail ends (fewer steps needed than
        a short chunk); otherwise the full chunk is dispatched and chained
        eagerly. The r5 engine instead forced short chunks whenever the
        batch was quiet, optimizing speculative TTFT for requests that had
        not arrived at the cost of 3-4x the fetch round trips for the
        requests actually in flight (BENCH_r05: 507 ms completion p50 at
        25 QPS against a ~100 ms TTFT floor). Demand-sized chunks finish
        an 8-token completion in ~2 RTTs (prefill + one covering chunk);
        a fresh arrival waits at most one chunk, and the collector's
        prefill-priority jump still fetches its first token ahead of
        queued chunk fetches. The saturated path is unchanged (full chunks
        either way). `span` is the scheduler's open sched.dispatch span."""
        self._ship_aids()
        with self._work_cv:
            # partial-prefill occupants are resident but NOT decoding:
            # the chunk's tokens for their slots are garbage (device
            # active mask is off), so they are snapshot-excluded exactly
            # like free slots
            snapshot = [
                r if (r is not None and r.prefill_done) else None
                for r in self._slot_req
            ]
            active_n = sum(r is not None for r in snapshot)
            k = (
                self._chunk_short
                if needed_steps <= self._chunk_short
                else self.decode_chunk
            )
            self._fault("device_step")
            t0 = time.perf_counter()
            # constrained family when ANY resident request carries a
            # grammar: per-slot gids mask only their own lanes, so
            # unconstrained neighbors stay token-identical, and the
            # device DFA state chain stays coherent across dispatches
            use_g = self.constrained and self._grammar_live()
            inputs = {}
            if use_g:
                inputs["gids"] = self._jnp.asarray(self._gids_np())
            chunk_ops, _step_ops, _verify_op = self._ops(use_g)
            op = chunk_ops[k]
            if self.kv.paged:
                # allocate blocks ahead of the chunk's cursor advance and
                # build the host liveness mask. Two exclusions: stale
                # lanes (their tables may name reassigned blocks) and
                # SATISFIED lanes — a request whose in-flight coverage
                # already reaches max_new must stop advancing, or chunks
                # driven by OTHER slots' demand would walk its device
                # length past the materialized watermark and scatter
                # through stale table entries (cross-slot corruption;
                # the contiguous path could afford the clamped garbage)
                steps = self._inflight_steps()
                live = np.zeros((self.slots,), bool)
                for i, r in enumerate(snapshot):
                    if r is None:
                        continue
                    if r.emitted + steps.get(i, 0) >= r.max_new_tokens:
                        continue
                    live[i] = True
                    self._kv_hi[i] = min(
                        self._kv_hi[i] + k, r._kv_limit or self.kv.capacity
                    )
                    self.kv.ensure(i, self._kv_hi[i])
                with engine_span("dispatch.inputs"):
                    inputs["tables"] = self._tables_device()
                    inputs["live"] = self._jnp.asarray(live)
            with engine_span("dispatch.call", self._hb_dispatch, kind="chunk"):
                out = self._run("chunk", use_g, op, **inputs)
            toks = out["toks"]
            info = self._step_open(
                span, "chunk", op, t0, time.perf_counter(), k=k, lanes=active_n,
                moe=out.get("moe"),
            )
            self._start_fetch(toks)
            self._inflight.append(("chunk", toks, snapshot, k, info))
            self._stat_chunks += 1
            self._stat_chunk_steps += k
            self._stat_active_sum += active_n
            self._work_cv.notify()
            return k

    def _chunk_shape_for(self, n: int) -> int:
        """Compile shape for a chunk covering n pending tokens: the
        smallest available shape that fits, else the largest (the prompt
        then takes multiple chunks). The configured prefill buckets
        survive exactly here — as chunk shapes — so short prompts keep
        their tight compile shapes instead of padding to prefill_chunk."""
        for s in self.chunk_shapes:
            if n <= s:
                return s
        return self.chunk_shapes[-1]

    def _dispatch_step(self, span) -> bool:
        """Pack one unified device step: one decode chunk for the active
        slots fused with up to admit_cap pending prefill chunks. The
        decode tokens are charged against step_token_budget first and
        prefill coalescing fills what remains, floored at one chunk — the
        budget bounds the step, it is never a stall gate. Decode rides
        every step beside which a lane decodes, whatever the budget: it is
        exactly the work whose starvation the budget exists to prevent, its
        per-step cost is one bounded chunk, and rows whose prompt completes
        this step decode immediately in the same program. A step in which
        NO lane decodes and NO row finishes goes out without the chunk
        (llm.step_p{n}_d0, k = 0): every result of it would be masked, and
        it streams every weight K times — the steady state of long prompts
        on few lanes, and of a request that arrives at an idle engine.
        Returns False when every queued prefill row turned out stale
        (reassigned/cancelled). `span` is the scheduler's open
        sched.dispatch span."""
        jnp = self._jnp
        self._ship_aids()
        self._fault("device_step")  # before any cursor mutation
        with self._work_cv:
            # purge stale prefill rows (cancelled, or slot reassigned)
            rows: list[tuple[GenRequest, int]] = []  # (request, n_new)
            K = self.decode_chunk
            active_n = sum(
                1 for r in self._slot_req if r is not None and r.prefill_done
            )
            shape = 0
            budget_left = 0
            keep: deque[GenRequest] = deque()
            while self._prefilling:
                r = self._prefilling.popleft()
                if (
                    r.slot is None
                    or self._slot_req[r.slot] is not r
                    or r.prefill_done
                ):
                    continue  # slot lost (recovery) or already finished
                if r.cancelled:
                    if r.finish_reason is None:
                        r.finish_reason = r.cancel_reason
                        self._observe_finish(r, time.perf_counter())
                        r.out.put(None)
                    self._slot_req[r.slot] = None
                    continue
                rem = len(r.prompt_tokens) - r.prefill_pos
                if not rows:
                    # first row fixes the step's compile shape and the
                    # prefill allowance: total budget minus the decode
                    # tokens riding this step, floored at one chunk
                    shape = self._chunk_shape_for(rem)
                    budget_left = max(
                        min(rem, shape), self.step_token_budget - K * active_n
                    )
                n = min(shape, rem)
                if len(rows) == self.admit_cap or n > budget_left:
                    keep.append(r)  # head-of-line stays FIFO for next step
                    break
                rows.append((r, n))
                budget_left -= n
                if r.prefill_pos + n < len(r.prompt_tokens):
                    keep.append(r)  # more chunks to come
            keep.extend(self._prefilling)
            self._prefilling = keep
            if not rows:
                return False
            now = time.perf_counter()
            nb = self._wave_width(len(rows))
            pack = np.zeros((nb, shape + 3), np.int32)
            # meta rows 2/3 (grammar id, start DFA state) ride only the
            # constrained program family; the plain op takes meta[:2]
            meta = np.zeros((4, nb), np.int32)
            meta[0, :] = self.slots  # pad lanes: inert (scatters dropped)
            meta[2, :] = -1  # pad/unconstrained lanes: no grammar
            finishes: list[tuple[int, int, GenRequest]] = []
            prefill_tokens = 0
            spans: list[tuple[int, int, int]] = []  # (cursor, n, shape): the record's rows
            for j, (r, n) in enumerate(rows):
                pos = r.prefill_pos
                pack[j, :n] = r.prompt_tokens[pos : pos + n]
                pack[j, shape] = pos
                pack[j, shape + 1] = n
                pack[j, shape + 2] = np.float32(r.temperature).view(np.int32)
                meta[0, j] = r.slot
                done = pos + n >= len(r.prompt_tokens)
                meta[1, j] = 1 if done else 0
                if r.grammar is not None and r._g_id >= 0 and r._g_state >= 0:
                    # first-token mask + device-state seed for the row's
                    # slot: fresh requests start at the DFA start state,
                    # continuations at the host mirror's state (a dead
                    # mirror — cannot happen while masking holds — keeps
                    # the lane unconstrained rather than wrong-state)
                    meta[2, j] = r._g_id
                    meta[3, j] = r._g_state
                if r._prefill_t0 is None:
                    r._prefill_t0 = now
                r.prefill_pos = pos + n
                # rows actually written: the append scatter drops indices
                # at i >= n, so padding past the valid count never lands —
                # retaining pos + shape would store garbage rows in the
                # prefix cache and bill them against its byte budget
                r._rows_hi = max(r._rows_hi, pos + n)
                if self.kv.paged:
                    # blocks for the appended rows (+ the fused decode
                    # chunk when this row activates)
                    hi = pos + n + (K if done else 0)
                    self._kv_hi[r.slot] = min(
                        max(self._kv_hi[r.slot], hi),
                        r._kv_limit or self.kv.capacity,
                    )
                    self.kv.ensure(r.slot, self._kv_hi[r.slot])
                self._load_credit(r, n)
                prefill_tokens += n
                spans.append((pos, n, shape))
                if done:
                    r.prefill_done = True
                    finishes.append((j, r.slot, r))
            use_g = self.constrained and (
                self._grammar_live()
                or any(m >= 0 for m in meta[2, : len(rows)])
            )
            # the decode chunk follows the rows unless nobody would read it
            fused = bool(active_n or finishes)
            if fused:
                kind, op = "step", self._ops(use_g)[1][shape]
            else:
                kind, op = "rows", self._programs.rows(use_g)[shape]
            inputs = {}
            if use_g and fused:
                inputs["gids"] = self._jnp.asarray(self._gids_np())
            t0 = time.perf_counter()
            if self.kv.paged and fused:
                steps_cov = self._inflight_steps()
                live = np.zeros((self.slots,), bool)
                for i, r in enumerate(self._slot_req):
                    if r is None or not r.prefill_done:
                        continue
                    if (
                        r.emitted + steps_cov.get(i, 0) >= r.max_new_tokens
                        and not any(s == i for _j, s, _r in finishes)
                    ):
                        # satisfied lane: must not advance past its
                        # materialized blocks (see _dispatch)
                        continue
                    live[i] = True
                    if not any(s == i for _j, s, _r in finishes):
                        # already-decoding slots advance K this step
                        self._kv_hi[i] = min(
                            self._kv_hi[i] + K,
                            r._kv_limit or self.kv.capacity,
                        )
                        self.kv.ensure(i, self._kv_hi[i])
            with engine_span("dispatch.inputs"):
                inputs["pack"] = jnp.asarray(pack)
                inputs["meta"] = jnp.asarray(meta if use_g else meta[:2])
                if self.kv.paged:
                    inputs["tables"] = self._tables_device()
                    if fused:
                        inputs["live"] = jnp.asarray(live)
            with engine_span("dispatch.call", self._hb_dispatch, kind="step"):
                out = self._run(kind, use_g, op, **inputs)
            t_dispatched = time.perf_counter()
            # (the rows alone return no tokens: their `first` tells the
            # collector that the program ended)
            first_dev, logits_dev, toks_dev = out["first"], out["kept"], out.get("toks")
            if finishes or not fused:
                self._start_fetch(first_dev)
            if fused:
                self._start_fetch(toks_dev)
            # retain finished prompts for prefix reuse: contiguous rows
            # sliced from the slot cache AFTER the append (device-ordered
            # before any later mutation) / paged blocks shared in place
            if self.kv.paged and self.kv.share:
                for j, slot, r in finishes:
                    self._kv_publish(
                        slot, r,
                        None if logits_dev is None else logits_dev[j : j + 1],
                    )
            elif self.kv.prefix is not None and logits_dev is not None:
                for j, slot, r in finishes:
                    if r._aid != 0:
                        # adapted rows hold tenant-delta K/V — never
                        # shareable through the base prefix cache
                        continue
                    keep_rows = (
                        self.kv.capacity if self.kv.rolling
                        else min(r._rows_hi, self.kv.capacity)
                    )
                    self.kv.prefix.put(
                        self.kv.prefix.key_for(r.prompt_tokens),
                        self.cache.k[:, slot : slot + 1, :keep_rows],
                        self.cache.v[:, slot : slot + 1, :keep_rows],
                        len(r.prompt_tokens), logits_dev[j : j + 1],
                    )
            # snapshot AFTER the rows loop: rows finishing this step have
            # prefill_done set and their decode runs in this program
            snapshot = [
                r if (r is not None and r.prefill_done) else None
                for r in self._slot_req
            ]
            decode_n = active_n + len(finishes)  # 0 exactly when not fused
            k = K if fused else 0
            step_tokens = prefill_tokens + k * decode_n
            info = {
                **self._step_open(
                    span, "step", op, t0, t_dispatched,
                    k=k, lanes=decode_n, rows=tuple(spans), moe=out.get("moe"),
                ),
                "shape": shape, "nb": nb,
                "prefill_tokens": prefill_tokens, "active": active_n,
                # row requests aligned with the record's rows — the goodput
                # ledger attributes each prefill span to its owner at the fetch
                "row_reqs": [r for r, _n in rows],
            }
            self._inflight.append(
                ("step", first_dev, finishes, toks_dev, snapshot, k, info)
            )
            self._stat_steps += 1
            self._stat_step_tokens += step_tokens
            if not fused:
                self._stat_steps_d0 += 1
                if self.metrics is not None:
                    self.metrics.increment_counter(
                        "app_llm_steps_without_decode_total", model=self.label
                    )
            if decode_n:
                self._stat_chunks += 1
                self._stat_chunk_steps += K
                self._stat_active_sum += decode_n
            if self.metrics is not None:
                self.metrics.record_histogram(
                    "app_llm_step_tokens", float(step_tokens), model=self.label
                )
                self.metrics.set_gauge(
                    "app_llm_step_budget_utilization",
                    step_tokens / self.step_token_budget, model=self.label,
                )
            self._work_cv.notify()
            return True

    def _spec_drafts(self, r: GenRequest) -> tuple[list[int], list[int]]:
        """(draft, predicted emitted span) for one decoding slot: draft
        length adapts to the request's acceptance EMA
        (gofr_tpu.spec.draft_len — backed-off requests run plain decode
        with a periodic 1-token probe), capped at the tokens the request
        can still emit; proposals come from the n-gram drafter over the
        OPTIMISTIC stream — prompt + emitted history + the predicted
        spans of verifies still in flight — which is what lets verify
        steps pipeline to `lookahead` depth instead of exposing a full
        dispatch->fetch round trip per step. The predicted span
        (draft + one predicted bonus token) is what the verify will emit
        if everything is accepted; a misprediction only mis-aims LATER
        drafts (they get rejected), never the emitted stream. Call with
        the lock held."""
        from .spec import draft_len

        emitted_opt = r.emitted + len(r._spec_pending)
        kmax = min(self.spec_draft, r.max_new_tokens - emitted_opt - 1)
        k = draft_len(r._spec_ema, kmax, r._spec_plain)
        if k <= 0:
            r._spec_plain += 1
            last = (
                r._spec_pending[-1] if r._spec_pending
                else r.history[-1] if r.history
                else r.prompt_tokens[-1] if r.prompt_tokens else 0
            )
            return [], [last]
        # ONE drafter call for k+1 tokens: the first k are the draft,
        # the overhang predicts the bonus token for the optimistic
        # pending stream — a second full-stream scan just to aim one
        # token would double the per-slot host cost on the scheduler
        # thread (the drafter's byte-scan design exists to keep this
        # cheap)
        stream = r.prompt_tokens + r.history + r._spec_pending
        d_full = self.drafter.draft(stream, k + 1)
        d = d_full[:k]
        if r.grammar is not None:
            # grammar-aware drafting (docs/advanced-guide/
            # structured-decoding.md), two moves on the host DFA mirror
            # advanced over the optimistic pending spans:
            # 1. FILTER — an inadmissible proposal is GUARANTEED
            #    rejection (the verify's masked sample cannot equal it),
            #    so cut the draft at the first token the DFA refuses;
            # 2. FAST-FORWARD — wherever the grammar admits EXACTLY ONE
            #    token (fixed property names, structural punctuation,
            #    literal tails), that token is a guaranteed-accept draft
            #    position: extend the draft through forced runs even
            #    when the n-gram drafter proposed nothing. This is what
            #    lifts constrained acceptance above the unconstrained
            #    baseline on schema-shaped output.
            st = r._g_state
            for t in r._spec_pending:
                if st < 0:
                    break
                st = r.grammar.advance(st, t)
            g_bonus: list[int] = []
            if st < 0:
                d = []
            else:
                d = r.grammar.filter_draft(st, d)
                s = st
                for t in d:
                    s = r.grammar.advance(s, t)
                while len(d) < k and s >= 0:
                    forced = np.flatnonzero(r.grammar.allowed(s))
                    if len(forced) != 1:
                        break
                    t = int(forced[0])
                    d.append(t)
                    s = r.grammar.advance(s, t)
                if d and s >= 0:
                    # grammar-forced BONUS aim: when the state after the
                    # draft admits exactly one token, the verify's bonus
                    # sample IS that token — a certain prediction keeps
                    # the optimistic pending stream (hence the next
                    # pipelined verify's drafts) on target
                    forced = np.flatnonzero(r.grammar.allowed(s))
                    if len(forced) == 1:
                        g_bonus = [int(forced[0])]
            if not d:
                r._spec_plain += 1
                return [], [stream[-1] if stream else 0]
            bonus = g_bonus or (
                (d_full[k : k + 1] if len(d) == k else d[-1:]) or d[-1:]
            )
            return d, d + bonus
        if not d:
            r._spec_plain += 1
            return [], [stream[-1] if stream else 0]
        bonus = d_full[k : k + 1] or d[-1:]
        return d, d + bonus

    def _dispatch_verify(self, span) -> bool:
        """Dispatch one fused speculative verify step (gofr_tpu.spec):
        every decoding slot whose in-flight coverage is verify-only gets
        its draft packed into one full-batch llm.step_v program; lanes
        whose drafter proposed nothing ride as draft-0 plain decode, so
        speculation never splits the batch. Verifies PIPELINE to
        `lookahead` depth: the program chains tail/cursor from device
        state, so a verify dispatched before its predecessor's fetch is
        still an exact continuation — only its drafts (aimed by the
        optimistic pending stream) can go stale, costing acceptance,
        never correctness. Selected lanes charge W = draft+1 tokens each
        against the step token budget (floored at one lane — the budget
        bounds the step, it is not a stall gate). Returns False when no
        slot was eligible OR nothing was drafted anywhere — the caller
        then runs the plain chunk pipeline, which is the adaptive
        backoff's no-regression guarantee at engine scope. `span` is the
        scheduler's open sched.dispatch span."""
        jnp = self._jnp
        self._ship_aids()
        self._fault("device_step")
        with self._work_cv:
            steps = self._inflight_steps()
            # verify-only coverage per slot: a slot whose ENTIRE in-flight
            # coverage is verify entries may pipeline another verify (its
            # optimistic pending stream tracks those); any chunk/step
            # coverage means un-predicted tokens are coming — wait for
            # the fetch
            ver_cover: dict[int, int] = {}
            entries = list(self._inflight)
            if self._processing is not None:
                entries.append(self._processing)
            for e in entries:
                if e[0] == "verify":
                    for slot, r in e[3]:
                        if r is self._slot_req[slot]:
                            ver_cover[slot] = ver_cover.get(slot, 0) + 1
            Kd = self.spec_draft
            W = Kd + 1
            budget = self.step_token_budget or self.slots * W
            pack = np.zeros((self.slots, Kd + 2), np.int32)
            sel: list[tuple[int, GenRequest]] = []
            proposed = 0
            cursors: dict[int, int] = {}
            n_draft: dict[int, int] = {}
            pred: dict[int, list[int]] = {}
            # Rotated scan: when the step budget cuts the selection short,
            # the next dispatch starts where this one stopped — without
            # the rotation, slots past floor(budget/W) would NEVER be
            # selected (and the chunk pipeline is blocked while verifies
            # fly), starving their requests under sustained admissions
            # into the low slots.
            start = self._spec_rr % self.slots
            cut: int | None = None
            for slot in (
                list(range(start, self.slots)) + list(range(0, start))
            ):
                r = self._slot_req[slot]
                if (
                    r is None
                    or not r.prefill_done
                    or r.cancelled
                    or r.finish_reason is not None
                    or steps.get(slot, 0) != ver_cover.get(slot, 0)
                    or r.emitted + len(r._spec_pending) >= r.max_new_tokens
                ):
                    continue
                if sel and (len(sel) + 1) * W > budget:
                    cut = slot
                    break
                d, p = self._spec_drafts(r)
                pack[slot, : len(d)] = d
                pack[slot, Kd] = len(d)
                pack[slot, Kd + 1] = 1
                sel.append((slot, r))
                proposed += len(d)
                n_draft[slot] = len(d)
                pred[slot] = p
                cursors[slot] = (
                    len(r.prompt_tokens) + r.emitted + len(r._spec_pending)
                )
            if not sel or not proposed:
                # nothing drafted anywhere: plain decode through the
                # chunk pipeline is strictly better (chained dispatches
                # hide the fetch RTT a 1-wide verify would expose) — the
                # scheduler falls back to _dispatch for this pass
                return False
            if cut is not None:
                self._spec_rr = cut  # resume the budget-cut scan here
            for slot, r in sel:
                r._spec_pending = r._spec_pending + pred[slot]
                r._spec_inflight += 1
                if not n_draft[slot]:
                    self.spec_plain += 1
            # constrained split: acceptance on grammar-masked text is the
            # structured-decoding bench signal (drafts were pre-filtered
            # by the DFA in _spec_drafts, so acceptance should not drop)
            gset = {slot for slot, r in sel if r.grammar is not None}
            proposed_c = sum(n_draft[s] for s in gset)
            use_g = self.constrained and self._grammar_live()
            inputs = {}
            if use_g:
                inputs["gids"] = jnp.asarray(self._gids_np())
            t0 = time.perf_counter()
            if self.kv.paged:
                # blocks for the verify's transient rows: [length,
                # length + W) per selected lane — the rollback leaves
                # rejected rows in PRIVATE blocks above the cursor,
                # rewritten by the next append (the contiguous path's
                # stale-row contract, at block granularity)
                for slot, r in sel:
                    self._kv_hi[slot] = min(
                        self._kv_hi[slot] + W,
                        r._kv_limit or self.kv.capacity,
                    )
                    self.kv.ensure(slot, self._kv_hi[slot])
            _chunk_ops, _step_ops, op = self._ops(use_g)
            with engine_span("dispatch.inputs"):
                inputs["pack"] = jnp.asarray(pack)
                if self.kv.paged:
                    inputs["tables"] = self._tables_device()
            with engine_span("dispatch.call", self._hb_dispatch, kind="verify"):
                out = self._run("verify", use_g, op, **inputs)
            t_dispatched = time.perf_counter()
            ys, acc = out["ys"], out["acc"]
            self._start_fetch(ys)
            self._start_fetch(acc)
            step_tokens = W * len(sel)
            info = {
                **self._step_open(
                    span, "verify", op, t0, t_dispatched, k=W, lanes=len(sel),
                ),
                "W": W, "proposed": proposed,
                "n_draft": n_draft, "cursors": cursors, "pred": pred,
                "gset": gset,
            }
            self._inflight.append(("verify", ys, acc, sel, info))
            self.spec_steps += 1
            self.spec_proposed += proposed
            self.spec_proposed_c += proposed_c
            self._stat_steps += 1
            self._stat_step_tokens += step_tokens
            if self.metrics is not None:
                if proposed - proposed_c:
                    self.metrics.increment_counter(
                        "app_llm_spec_proposed_total",
                        by=float(proposed - proposed_c),
                        model=self.label, constrained="0",
                    )
                if proposed_c:
                    self.metrics.increment_counter(
                        "app_llm_spec_proposed_total", by=float(proposed_c),
                        model=self.label, constrained="1",
                    )
                self.metrics.record_histogram(
                    "app_llm_step_tokens", float(step_tokens),
                    model=self.label,
                )
                if self.step_token_budget:
                    self.metrics.set_gauge(
                        "app_llm_step_budget_utilization",
                        step_tokens / self.step_token_budget,
                        model=self.label,
                    )
            self._work_cv.notify()
            return True

    def _process_entry(self, entry: tuple) -> None:
        """Fetch one device result (outside the lock — the blocking RTT
        must not stall the scheduler) and emit tokens (under the lock).
        The two halves are the collector's collect.fetch and collect.emit
        spans; both beat for the step watchdog."""
        kind, info = entry[0], entry[-1]
        if kind == "step":
            # first tokens where a row finished; a step without its decode
            # chunk (k = 0) has no tokens, and `first` says that it ended
            arrays = (entry[1] if entry[2] or not entry[5] else None, entry[3])
        elif kind == "verify":
            arrays = entry[1:3]
        else:
            arrays = entry[1:2]
        with engine_span("collect.fetch", self._hb_fetch, kind=kind, seq=info["seq"]):
            self._fault_latency()  # chaos: a wedged transfer
            info["t_fetch"] = time.perf_counter()
            # blocks; the device runs the next program meanwhile
            fetched = [None if a is None else np.asarray(a) for a in arrays]
            info["t_fetched"] = time.perf_counter()
        with engine_span("collect.emit", self._hb_fetch, kind=kind, seq=info["seq"]):
            self._emit_entry(entry, *fetched)

    def _emit_entry(self, entry: tuple, *fetched) -> None:
        if entry[0] == "verify":
            self._process_verify_entry(entry, *fetched)
            return
        if entry[0] == "step":
            self._process_step_entry(entry, *fetched)
            return
        if entry[0] == "prefill":
            _, _first_dev, taken, info = entry
            (first,) = fetched
            # numerical watchdog: scan BEFORE any emission, outside the
            # lock (_die must not run under our own lock — the failover
            # hook submits into other engines)
            first, tripped = self._numeric_check_fetch(
                first,
                [j for j, (_s, r) in enumerate(taken) if r is not None],
                "prefill first token",
            )
            if tripped:
                return
            now, t_dispatch = info["t_fetched"], info["t_dispatch"]
            if info["bucket"] is not None:  # miss wave: a device prefill ran
                # (prefix-hit waves dispatch no prefill — no MFU to claim)
                seq_lens = [
                    len(r.prompt_tokens) for _, r in taken if r is not None
                ]
                self._observe_tput(sum(seq_lens), now - t_dispatch)
                self._observe_mfu(
                    "prefill",
                    tokens=sum(seq_lens),
                    flops=self._mfu_mod.prefill_flops(self._costs, seq_lens),
                    bytes_moved=(
                        self._costs.params_bytes
                        + sum(seq_lens) * self._costs.kv_bytes_per_ctx_token
                    ),
                    dt=now - t_dispatch,
                )
            if self.goodput is not None:
                from .goodput import prefill_classes

                # miss wave: the device ran [nb, bucket] prompt rows —
                # live lanes own their prompt length (replay-split for
                # continuations), everything else in the rectangle is
                # padding (scrubbed lanes included). A prefix-hit wave
                # dispatched no prefill; its cost is ~the one seeded
                # first-token sample per lane.
                lanes: list = []
                plen_sum = 0
                for _s, r in taken:
                    if r is None:
                        continue
                    if info["bucket"] is not None:
                        plen = len(r.prompt_tokens)
                        lanes.append(
                            (r, prefill_classes(r._replay_pos, 0, plen))
                        )
                        plen_sum += plen
                    else:
                        lanes.append((r, {"useful": 1}))
                if info["bucket"] is not None:
                    pad = (
                        info["bucket"] * max(info["nb"], len(taken))
                        - plen_sum
                    )
                    if pad > 0:
                        lanes.append((None, {"padding": pad}))
                self.goodput.observe("prefill", t_dispatch, now, lanes)
            with self._lock:
                emitted = 0
                for j, (slot, r) in enumerate(taken):
                    if r is None:  # scrubbed by preemption: tokens dropped
                        continue
                    if r.span is not None and r.finish_reason is None:
                        self._phase_span(
                            r, "llm.prefill", t_dispatch, now,
                            attrs={
                                "llm.wave": info["nb"] or len(taken),
                                "llm.bucket": info["bucket"] or 0,
                                "llm.prefix_hit": r.prefix_hit,
                            },
                        )
                    emitted += self._emit_to(r, slot, [int(first[j])], now)
                self._step_close(info, first=emitted)
                self._processing = None  # same acquisition as the emits —
                # a separate clear would let the scheduler double-count
                # this entry in _inflight_steps after emitted already grew
            if self.logger is not None:
                self._flush_wide_events()
            return
        _, _toks_dev, snapshot, k, info = entry
        (toks,) = fetched  # [K, S]
        toks, tripped = self._numeric_check_fetch(
            toks, [s for s, r in enumerate(snapshot) if r is not None],
            "decode chunk",
        )
        if tripped:
            return
        now, t_dispatch = info["t_fetched"], info["t_dispatch"]
        if self.metrics is not None:
            self.metrics.record_histogram(
                "app_tpu_stats", now - info["t_fetch"],
                model="llm", op="decode_chunk",
            )
        # dispatch->fetch cost per decode step, attributed once per chunk
        # (wave = active slots at dispatch, bucketed to a power of two so
        # the label set stays bounded at log2(slots) values)
        ctxs = [self._ctx_of(r) for r in snapshot if r is not None]  # the record's
        active_n = len(ctxs)
        ctx_sum = sum(self._ctx_read(r) for r in snapshot if r is not None)
        self._observe_tput(k * active_n, now - t_dispatch)
        step_s = (now - t_dispatch) / k
        self._phases["decode_step"].observe(step_s)
        if active_n:
            # each of the k steps decodes one token per active slot and
            # re-streams the weights + the live KV prefix
            self._observe_mfu(
                "decode",
                tokens=k * active_n,
                flops=self._mfu_mod.decode_flops(
                    self._costs, k * active_n, k * ctx_sum
                ),
                bytes_moved=k * (
                    self._costs.params_bytes
                    + ctx_sum * self._costs.kv_bytes_per_ctx_token
                ),
                dt=now - t_dispatch,
            )
        if self.metrics is not None:
            wave = 1 << max(0, active_n - 1).bit_length() if active_n else 0
            self.metrics.record_histogram(
                "app_llm_decode_step_seconds", step_s,
                model=self.label, chunk=str(k), wave=str(wave), fused="0",
                **self._role_labels,
            )
        if self.goodput is not None:
            # dense decode pass: every slot lane ran k serial steps —
            # live lanes decoded useful tokens (capped at the request's
            # remaining budget: positions computed past max_new are
            # truncated at emit, i.e. slack, not demand), empty lanes
            # are padding
            lanes = []
            for r in snapshot:
                if r is None:
                    continue
                use = min(k, max(0, r.max_new_tokens - r.emitted))
                cl = {"useful": use}
                if k - use > 0:
                    cl["padding"] = k - use
                lanes.append((r, cl))
            dead = k * (len(snapshot) - active_n)
            if dead > 0:
                lanes.append((None, {"padding": dead}))
            self.goodput.observe("chunk", t_dispatch, now, lanes)
        cols = toks.T  # [S, K]
        with self._lock:
            ns = []
            for slot, r in enumerate(snapshot):
                if r is not None:
                    if r.span is not None and r.finish_reason is None:
                        self._phase_span(
                            r, "llm.decode", t_dispatch, now,
                            attrs={"llm.chunk": k, "llm.active": active_n,
                                   "llm.slot": slot},
                        )
                    ns.append(self._emit_to(r, slot, cols[slot].tolist(), now))
            self._step_close(info, zip(ctxs, ns))
            self._processing = None
        if self.logger is not None:
            self._flush_wide_events()

    def _process_step_entry(self, entry: tuple, first, toks) -> None:
        """Emit one fetched unified step: first tokens for rows whose
        prompt completed this step (their llm.prefill span closes here),
        then the piggybacked decode chunk's columns. MFU accounting is
        per-step — one prefill observation over the chunk spans and one
        decode observation over the chunk, both against the step's
        dispatch->fetch wall (they share the device window; read the
        window percentiles, never sum them)."""
        _, _first_dev, finishes, _toks_dev, snapshot, k, info = entry
        # numerical watchdog: both fetched arrays, before any emission
        if first is not None:
            first, tripped = self._numeric_check_fetch(
                first, [j for j, _s, _r in finishes], "step first token",
            )
            if tripped:
                return
        toks, tripped = self._numeric_check_fetch(
            toks, [s for s, r in enumerate(snapshot) if r is not None],
            "step decode",
        )
        if tripped:
            return
        decoded = any(r is not None for r in snapshot)
        now, t_dispatch = info["t_fetched"], info["t_dispatch"]
        step_s = now - t_dispatch
        spans = [row[:2] for row in info["rows"]]  # (cursor, n) per prompt row
        self._observe_tput(
            info["prefill_tokens"]
            + k * sum(1 for r in snapshot if r is not None),
            step_s,
        )
        self._phases["step"].observe(step_s)
        if self.anomaly is not None:
            self.anomaly.observe("step", step_s * 1e3)
        if self.metrics is not None:
            self.metrics.record_histogram(
                "app_llm_step_seconds", step_s, model=self.label,
                **self._role_labels,
            )
            if decoded:
                self.metrics.record_histogram(
                    "app_tpu_stats", now - info["t_fetch"], model="llm", op="decode_chunk",
                )
        if info["prefill_tokens"]:
            ctx_read = sum(self._mfu_mod.read_ctx(self._costs, pos) for pos, _n in spans)
            self._observe_mfu(
                "prefill",
                tokens=info["prefill_tokens"],
                flops=self._mfu_mod.chunk_prefill_flops(self._costs, spans),
                bytes_moved=(
                    self._costs.params_bytes
                    + (info["prefill_tokens"] + ctx_read)
                    * self._costs.kv_bytes_per_ctx_token
                ),
                dt=step_s,
            )
        if decoded:
            active_n = sum(r is not None for r in snapshot)
            ctx_sum = sum(self._ctx_read(r) for r in snapshot if r is not None)
            # per-token cadence requests actually experience: a fused
            # step's wall includes its prefill-append compute (a short
            # request may complete entirely inside its own step, so
            # skipping fused steps would leave the series empty for it)
            self._phases["decode_step"].observe(step_s / k)
            if active_n:
                self._observe_mfu(
                    "decode",
                    tokens=k * active_n,
                    flops=self._mfu_mod.decode_flops(
                        self._costs, k * active_n, k * ctx_sum
                    ),
                    bytes_moved=k * (
                        self._costs.params_bytes
                        + ctx_sum * self._costs.kv_bytes_per_ctx_token
                    ),
                    dt=step_s,
                )
            if self.metrics is not None:
                # fused="1" marks walls that include prefill-append compute
                # — filter to fused="0" for decode cost comparable 1:1 with
                # the wave scheduler's pure-decode dispatches
                wave = 1 << max(0, active_n - 1).bit_length() if active_n else 0
                self.metrics.record_histogram(
                    "app_llm_decode_step_seconds", step_s / k,
                    model=self.label, chunk=str(k), wave=str(wave),
                    fused="1" if info["prefill_tokens"] else "0",
                    **self._role_labels,
                )
        if self.goodput is not None:
            from .goodput import prefill_classes

            # fused step: each packed prefill span belongs to its row's
            # request (replay-split for continuations); the piggybacked
            # decode ran k steps over ALL slot lanes. Padding = unpacked
            # prefill rectangle + empty decode lanes.
            lanes = []
            for r, (pos, n) in zip(info["row_reqs"], spans):
                lanes.append((r, prefill_classes(r._replay_pos, pos, n)))
            decode_n = 0
            for r in snapshot:
                if r is not None:
                    decode_n += 1
                    use = min(k, max(0, r.max_new_tokens - r.emitted))
                    cl = {"useful": use}
                    if k - use > 0:
                        cl["padding"] = k - use
                    lanes.append((r, cl))
            pad = (
                info["shape"] * info["nb"] - info["prefill_tokens"]
                + k * (len(snapshot) - decode_n)
            )
            if pad > 0:
                lanes.append((None, {"padding": pad}))
            self.goodput.observe("step", t_dispatch, now, lanes)
        with self._lock:
            emitted = 0
            dctx = []
            for j, slot, r in finishes:
                if r.span is not None and r.finish_reason is None:
                    self._phase_span(
                        r, "llm.prefill", r._prefill_t0 or t_dispatch, now,
                        attrs={
                            "llm.wave": info["nb"],
                            "llm.bucket": info["shape"],
                            "llm.prefix_hit": r.prefix_hit,
                        },
                    )
                emitted += self._emit_to(r, slot, [int(first[j])], now)
            if decoded:
                cols = toks.T  # [S, K]
                for slot, r in enumerate(snapshot):
                    if r is not None:
                        if r.span is not None and r.finish_reason is None:
                            self._phase_span(
                                r, "llm.decode", t_dispatch, now,
                                attrs={"llm.chunk": k, "llm.active":
                                       info["active"], "llm.slot": slot},
                            )
                        # after the first tokens: a lane that starts in this
                        # step decodes from its prompt + 1
                        ctx = self._ctx_of(r)
                        dctx.append((ctx, self._emit_to(r, slot, cols[slot].tolist(), now)))
            self._step_close(info, dctx, first=emitted)
            self._processing = None  # same acquisition as the emits
        if self.logger is not None:
            self._flush_wide_events()

    def _process_verify_entry(self, entry: tuple, ys, acc) -> None:
        """Emit one fetched speculative verify step: per selected slot,
        the accepted draft tokens plus the bonus token (``ys[:acc+1]``)
        feed the existing emit path as ONE multi-token push — max_new /
        eos truncation, load_tokens credit, and the fairness ledger all
        see exactly the emitted count. Acceptance telemetry updates the
        per-request EMA that sizes the next draft, and MFU bills only
        the accepted tokens (verified-but-rejected positions are
        non-useful work — profiling.mfu.spec_verify_flops)."""
        _, _ys_dev, _acc_dev, sel, info = entry  # ys [S, W], acc [S]
        # numerical watchdog: live lanes scanned BEFORE any emission
        # (lanes are rows here; the helper scans last-axis columns)
        ys_t, tripped = self._numeric_check_fetch(
            ys.T, [slot for slot, _r in sel], "spec verify",
        )
        if tripped:
            return
        ys = ys_t.T
        now, t_dispatch = info["t_fetched"], info["t_dispatch"]
        dt = now - t_dispatch
        emitted_total = 0
        accepted_total = 0
        spans: list[tuple[int, int]] = []
        ctx_sum = 0
        gset = info.get("gset") or set()
        accepted_c = 0
        for slot, _r in sel:
            n = int(acc[slot]) + 1
            emitted_total += n
            accepted_total += int(acc[slot])
            if slot in gset:
                accepted_c += int(acc[slot])
            cur = info["cursors"].get(slot, 0)
            spans.append((cur, n))
            ctx_sum += self._mfu_mod.read_ctx(self._costs, cur)
        self.spec_accepted += accepted_total
        self.spec_accepted_c += accepted_c
        self._observe_tput(emitted_total, dt)
        self._phases["step"].observe(dt)
        if self.anomaly is not None:
            self.anomaly.observe("step", dt * 1e3)
            # per-STEP acceptance (not the cumulative gauge — a drift
            # detector needs the instantaneous rate): accepted over the
            # positions this verify actually proposed (ys is [S, W],
            # W-1 drafts + 1 bonus per selected lane)
            self.anomaly.observe(
                "spec_accept",
                accepted_total / max(1, len(sel) * (ys.shape[1] - 1)),
            )
        # per-token cadence the accepted spans actually delivered
        per_tok = dt / max(1.0, emitted_total / max(1, len(sel)))
        self._phases["decode_step"].observe(per_tok)
        self._observe_mfu(
            "decode",
            tokens=emitted_total,
            flops=self._mfu_mod.spec_verify_flops(self._costs, spans),
            bytes_moved=(
                self._costs.params_bytes
                + ctx_sum * self._costs.kv_bytes_per_ctx_token
            ),
            dt=dt,
        )
        if self.metrics is not None:
            if accepted_total - accepted_c:
                self.metrics.increment_counter(
                    "app_llm_spec_accepted_total",
                    by=float(accepted_total - accepted_c),
                    model=self.label, constrained="0",
                )
            if accepted_c:
                self.metrics.increment_counter(
                    "app_llm_spec_accepted_total",
                    by=float(accepted_c), model=self.label, constrained="1",
                )
            self.metrics.set_gauge(
                "app_llm_spec_accept_rate",
                self.spec_accepted / max(1, self.spec_proposed),
                model=self.label,
            )
            self.metrics.record_histogram(
                "app_llm_step_seconds", dt, model=self.label,
                **self._role_labels,
            )
            wave = 1 << max(0, len(sel) - 1).bit_length() if sel else 0
            # chunk label "v{W}" marks verify walls: per-token cost here
            # includes the whole W-wide pass, not a chunk's K serial steps
            self.metrics.record_histogram(
                "app_llm_decode_step_seconds", per_tok,
                model=self.label, chunk=f"v{info['W']}", wave=str(wave),
                fused="0", **self._role_labels,
            )
        if self.goodput is not None:
            # verify is a dense [S, W] device pass: selected lanes own
            # their accepted span (+1 bonus) as useful and the rejected
            # draft positions as spec_reject; unselected rows are padding
            lanes = []
            for slot, r in sel:
                a = int(acc[slot])
                use = min(a + 1, max(0, r.max_new_tokens - r.emitted))
                cl = {"useful": use}
                if a + 1 - use > 0:
                    cl["padding"] = a + 1 - use
                rej = info["n_draft"].get(slot, 0) - a
                if rej > 0:
                    cl["spec_reject"] = rej
                lanes.append((r, cl))
            pad = ys.shape[1] * (ys.shape[0] - len(sel))
            if pad > 0:
                lanes.append((None, {"padding": pad}))
            self.goodput.observe("verify", t_dispatch, now, lanes)
        from .spec import SPEC_EMA_ALPHA

        with self._lock:
            dctx = []
            for slot, r in sel:
                a = int(acc[slot])
                toks = [int(t) for t in ys[slot, : a + 1]]
                if self.metrics is not None:
                    self.metrics.record_histogram(
                        "app_llm_spec_tokens_per_step", float(len(toks)),
                        model=self.label,
                    )
                if r.span is not None and r.finish_reason is None:
                    self._phase_span(
                        r, "llm.decode", t_dispatch, now,
                        attrs={
                            "llm.spec_draft": info["n_draft"].get(slot, 0),
                            "llm.spec_accepted": a,
                            "llm.slot": slot,
                        },
                    )
                nd = info["n_draft"].get(slot, 0)
                if nd:
                    r._spec_ema = (
                        (1 - SPEC_EMA_ALPHA) * r._spec_ema
                        + SPEC_EMA_ALPHA * (a / nd)
                    )
                    r._spec_plain = 0
                # optimistic-pipeline reconciliation: a fully-correct
                # prediction pops its span off the pending stream; any
                # misprediction invalidates the whole remainder (later
                # in-flight verifies still emit VALID tokens — their
                # drafts were simply mis-aimed and will be rejected)
                p = info["pred"].get(slot, [])
                if toks == p and r._spec_pending[: len(p)] == p:
                    r._spec_pending = r._spec_pending[len(p):]
                else:
                    r._spec_pending = []
                r._spec_inflight = max(0, r._spec_inflight - 1)
                ctx = self._ctx_of(r)
                dctx.append((ctx, self._emit_to(r, slot, toks, now)))
            self._step_close(info, dctx)
            self._processing = None  # same acquisition as the emits
        if self.logger is not None:
            self._flush_wide_events()

    def _abort_all(self) -> None:
        jnp = self._jnp
        with self._lock:
            now = time.perf_counter()
            for slot, r in enumerate(self._slot_req):
                if r is not None and r.finish_reason is None:
                    r.finish_reason = "cancelled"
                    self._observe_finish(r, now)
                    r.out.put(None)
                self._slot_req[slot] = None
            self._active = jnp.zeros((self.slots,), bool)
            self._temps = jnp.zeros((self.slots,), jnp.float32)

    def _schedule_loop(self) -> None:
        jnp = self._jnp
        name_os_thread()  # the profiler names this thread's line by it
        try:
            while not self._stop:
                if self.faults.take("replica_kill", self.label) is not None:
                    # terminal chaos: the whole-replica death the failover
                    # and supervisor paths exist for (NOT routed through
                    # the per-iteration recovery below — a kill is final)
                    self._count_fault("replica_kill")
                    self._die("fault injection: replica_kill")
                    break
                if self._poison_fault():
                    break  # tagged payload killed this replica (terminal)
                try:
                    with engine_span("sched.housekeep"):
                        self._run_sched_work()
                        if self.kv.paged:
                            # paged-pool housekeeping, in dependency order:
                            # publish finished session turns (needs the
                            # blocks), return retired slots' blocks, spill
                            # cold sessions past their device budget
                            self._kv_session_flush()
                            self._kv_sweep()
                            self._kv_session_spill()
                    with engine_span("sched.admit") as span:
                        admitted0 = self._stat_admitted
                        did = self._admit()
                        span.set(admitted=self._stat_admitted - admitted0)
                    if self._stop:
                        break
                    with engine_span("sched.plan"), self._lock:
                        depth = self._decode_depth()
                        needed = self._needed_steps()
                        prefilling = bool(self._prefilling)
                    stepped = False
                    if prefilling and depth < self.lookahead:
                        # one unified step per pass: prefill chunks packed
                        # to the token budget, decode riding along — the
                        # loop comes straight back for the next step
                        with engine_span("sched.dispatch") as span:
                            stepped = self._dispatch_step(span)
                        if stepped:
                            depth += 1
                            needed = max(0, needed - self.decode_chunk)
                    did_v = False
                    chunk_ok = True
                    if self.speculative:
                        # Speculative regime policy: decode advances
                        # through fused verify steps whenever anything
                        # drafts (verifies pipeline to lookahead depth —
                        # see _dispatch_verify). When a CLEAN-pipe
                        # drafting attempt yields nothing — cold slots,
                        # or every request backed off — the engine buys a
                        # bounded burst of plain chunks (_spec_hold), the
                        # chunk pipeline hiding the fetch RTT a 1-wide
                        # verify would expose; at the end of the burst
                        # the pipe drains and speculation re-probes, so a
                        # stream whose tail turns repetitive recovers.
                        # Chunks and verifies never interleave: a chunk
                        # advances EVERY device-active slot from the
                        # on-device tail and would double-advance a
                        # verify's slots.
                        with engine_span("sched.plan"), self._lock:
                            inflight_kinds = {
                                e[0] for e in self._inflight
                            }
                            if self._processing is not None:
                                inflight_kinds.add(self._processing[0])
                            ver_fly = "verify" in inflight_kinds
                            dec_fly = bool(
                                inflight_kinds & {"chunk", "step", "verify"}
                            )
                        if (
                            not stepped and depth < self.lookahead
                            and self._spec_hold <= 0
                        ):
                            with engine_span("sched.dispatch") as span:
                                did_v = self._dispatch_verify(span)
                            if not did_v and not dec_fly:
                                # clean attempt, nothing drafted: plain
                                # decode burst before the next probe
                                self._spec_hold = self._SPEC_REPROBE_CHUNKS
                        chunk_ok = (
                            not ver_fly and not did_v and self._spec_hold > 0
                        )
                    want = 0
                    if chunk_ok:
                        want = min(
                            -(-needed // self.decode_chunk),
                            self.lookahead - depth,
                        )
                        for _ in range(max(0, want)):
                            with engine_span("sched.dispatch") as span:
                                needed = max(0, needed - self._dispatch(needed, span))
                            if self.speculative:
                                self._spec_hold -= 1
                    if not did and not stepped and not did_v and want <= 0:
                        with engine_span("sched.wait"):
                            self._kick.wait(timeout=0.005)
                        self._kick.clear()
                except Exception as e:  # noqa: BLE001 — engine must not die silently
                    if self.logger is not None:
                        self.logger.error(f"LLM engine step failed: {e!r}")
                    self._recover_all()
                    if self.logger is not None:
                        self._flush_wide_events()
                    time.sleep(0.1)
        finally:
            # Anything that escapes the per-iteration handler (BaseException,
            # a failure inside recovery itself) would otherwise leave a
            # zombie engine: queued requests hang until stream timeout and
            # the replica router keeps feeding it. Die loudly instead.
            if not self._stop:
                self._die("scheduler thread exited unexpectedly")

    def _die(self, why: str, lock_timeout: float | None = None) -> None:
        """Terminal failure: mark the engine dead (alive() -> False,
        submit() refuses), hand every RECOVERABLE request to the failover
        hook when one is wired (ReplicatedLLMEngine re-dispatches them to
        a live replica), then end-of-stream everything else — occupants,
        in-flight snapshots, the waiting list, and the admit queue — so
        no consumer blocks until its stream timeout.

        Idempotent (the watchdog, the scheduler's finally, and the
        collector's finally can race). `lock_timeout` bounds the lock
        acquisition for callers that suspect the lock is WEDGED under a
        hung device call (the watchdog): on timeout the engine is still
        marked dead — the router stops feeding it and the supervisor
        replaces it — but the drain is skipped and the hung entries'
        consumers hit their stream timeout (nothing else is safe to do
        from outside the critical section)."""
        with self._die_guard:
            if self._died:
                return
            self._died = True
        self._stop = True
        self.died_reason = why
        self._fail_sched_work()  # pending handoff work cannot run now
        if self.logger is not None:
            self.logger.error(f"LLM engine died: {why}")
        # black-box bundle FIRST, while the corpse is still warm — the
        # rescue/drain below mutates the very state the bundle captures
        # (slots empty, gauges zero, requests re-homed). The reason
        # prefix classifies the trigger: watchdog/numerical/poison trips
        # each rate-limit independently of generic engine deaths.
        from .flightrec import classify_die_reason

        self._incident(
            classify_die_reason(why), reason=why,
            lock_timeout=2.0 if lock_timeout is None
            else min(2.0, lock_timeout),
        )
        if lock_timeout is None:
            acquired = self._lock.acquire()
        else:
            acquired = self._lock.acquire(timeout=lock_timeout)
        rescued: list[GenRequest] = []
        if acquired:
            try:
                if self.failover_hook is not None:
                    rescued = self._extract_recoverable()
                try:
                    self._recover_all()
                except Exception:  # noqa: BLE001 — draining must not re-raise
                    pass
                self._drain_pending()
            finally:
                self._lock.release()
        elif self.logger is not None:
            self.logger.error(
                "LLM engine lock wedged while dying; marked dead without "
                "drain (in-flight consumers will hit their stream timeout)"
            )
        self._zero_state_gauges()
        self._teardown_profiling()
        # the bundle above was this engine's LAST dump: a dead engine
        # must not write further bundles. The record ring deliberately
        # survives (unlike close()) — it is the post-mortem's evidence.
        self.blackbox.close()
        try:
            # a dead engine's pool/radix/session bookkeeping (and its
            # resident-bytes gauges) must not survive it — same contract
            # as close(); device buffers free with the engine object
            self.kv.close()
        except Exception:  # noqa: BLE001 — dying must not re-raise
            pass
        if self.ledger is not None:
            self.ledger.set_active(self.label, set())  # see close()
        self._kick.set()
        if acquired:
            with self._work_cv:
                self._work_cv.notify_all()
        if rescued:
            # OUTSIDE the lock: the hook submits into OTHER engines and
            # must not nest their locks under ours
            try:
                self.failover_hook(rescued)
            except Exception as e:  # noqa: BLE001 — rescue must terminate
                if self.logger is not None:
                    self.logger.error(f"failover hook failed: {e!r}")
                for r in rescued:
                    if r.finish_reason == "failover":
                        r.finish_reason = "error"
                        r.out.put(None)

    def _extract_recoverable(self) -> list[GenRequest]:
        """Collect every request a replacement replica could finish —
        slotted, mid-prefill, riding an in-flight snapshot, waiting, or
        still in the admit queue — and mark each finish_reason="failover"
        so the regular die-drain paths (which close only requests with
        finish_reason None) walk straight past them. The failover hook
        clears the marker on re-dispatch or replaces it with "error".
        Call with the lock held. Returned in submit order (ids are a
        process-global monotone counter)."""
        rescued: dict[int, GenRequest] = {}
        # Requests IN FLIGHT at death (slotted, mid-prefill, or riding a
        # device snapshot) are implicated in it for the router's
        # poison-request quarantine; queued-only bystanders are not — a
        # request that merely waited behind a poison payload twice must
        # not be refused service for it.
        inflight_ids: set[int] = set()

        def take(r: GenRequest | None, inflight: bool = False) -> None:
            if r is not None and r.finish_reason is None and not r.cancelled:
                rescued[r.id] = r
                if inflight:
                    inflight_ids.add(r.id)

        for r in self._slot_req:
            take(r, inflight=True)
        entries = list(self._inflight)
        if self._processing is not None:
            entries.append(self._processing)
        for e in entries:
            for r in self._entry_requests(e):
                take(r, inflight=True)
        for r in self._prefilling:
            take(r, inflight=True)
        for r in self._waiting:
            take(r)
        # the admit queue must be drained here (not left to
        # _drain_pending, which would close rescued members): pulled
        # non-recoverable entries get their end-of-stream immediately
        now = time.perf_counter()
        while True:
            try:
                r = self._admit_q.get_nowait()
            except queue.Empty:
                break
            if r is None:
                continue
            if r.finish_reason is None and not r.cancelled:
                take(r)
            elif r.finish_reason is None:
                r.finish_reason = "cancelled"
                self._observe_finish(r, now)
                r.out.put(None)
        out = [rescued[i] for i in sorted(rescued)]
        for r in out:
            r.finish_reason = "failover"
            if r.id in inflight_ids:
                r.deaths += 1
        return out

    def _recover_all(self) -> None:
        """Full-stop recovery: close every request reachable from in-flight
        snapshots or slots, discard queued work, and reset device state.
        ONE critical section (callable from either thread): releasing the
        lock mid-way would let the other thread admit fresh requests into
        slots/tail that the remainder of the reset then clobbers."""
        with self._lock:
            # virtually-freed requests live ONLY in the snapshots
            # being discarded — close them before clearing, or
            # their consumers never see an end-of-stream
            orphans: set = set()
            entries = list(self._inflight)
            if self._processing is not None:
                entries.append(self._processing)
            for entry in entries:
                orphans.update(self._entry_requests(entry))
            now = time.perf_counter()
            for r in orphans:
                if r.finish_reason is None:
                    r.finish_reason = "cancelled"
                    self._observe_finish(r, now)
                    r.out.put(None)
            self._inflight.clear()
            self._processing = None
            self._prefilling.clear()  # occupants are closed by _abort_all
            self._fetch_fail_streak = 0  # fresh state deserves a fresh count
            self._admitting = 0  # an aborted wave never reaches its slots
            self._tail = self._jnp.zeros((self.slots,), self._jnp.int32)
            self._abort_all()

    def _collect_loop(self) -> None:
        try:
            self._collect_loop_inner()
        finally:
            if not self._stop:  # see _schedule_loop's finally
                self._die("collector thread exited unexpectedly")

    def _collect_loop_inner(self) -> None:
        name_os_thread()  # the profiler names this thread's line by it
        while True:
            with engine_span("collect.wait"), self._work_cv:
                while not self._inflight and not self._stop:
                    self._work_cv.wait(timeout=0.1)
                if not self._inflight:
                    if self._stop:
                        return
                    continue
                # TTFT: serve prefill entries (first tokens of fresh
                # requests) before queued chunk fetches. Only ordering
                # WITHIN a request matters, and a request's prefill always
                # precedes its chunks in the deque — jumping a prefill
                # ahead of other requests' chunk tokens is safe. The jump
                # is rationed to one per processed chunk: unbounded
                # priority starves chunk emission whenever fresh arrivals
                # keep the prefill queue non-empty (measured: p50 3x worse
                # at 50 QPS).
                idx = 0
                if not self._jumped:
                    idx = next(
                        (
                            i for i, e in enumerate(self._inflight)
                            if self._jump_safe(e)
                        ),
                        0,
                    )
                if idx:
                    entry = self._inflight[idx]
                    del self._inflight[idx]
                    self._jumped = True
                else:
                    entry = self._inflight.popleft()
                    if entry[0] in ("chunk", "verify") or (
                        entry[0] == "step" and entry[5]
                    ):
                        self._jumped = False
                self._processing = entry
            try:
                self._process_entry(entry)
                self._fetch_fail_streak = 0
            except Exception as e:  # noqa: BLE001
                if self.logger is not None:
                    self.logger.error(f"LLM engine fetch failed: {e!r}")
                self._fetch_fail_streak += 1
                if self._fetch_fail_streak >= self._FETCH_FAIL_LIMIT:
                    # persistent device-side failure: make-up chunks would
                    # fail too, so sparing slot occupants just busy-loops
                    # dispatch/fail forever — full reset like the
                    # scheduler's error path
                    self._fetch_fail_streak = 0
                    self._recover_all()
                else:
                    self._close_unreachable(entry)
            finally:
                with self._lock:
                    self._processing = None
            self._kick.set()
            if self.logger is not None:
                self._flush_wide_events()

    @staticmethod
    def _jump_safe(entry: tuple) -> bool:
        """May the collector serve this entry ahead of older in-flight
        entries? Prefill waves always: they carry ONLY fresh requests'
        first tokens, and a request's prefill precedes its chunks in the
        deque. A step entry with finishing rows carries first tokens too
        — but ALSO the piggybacked decode chunk for every already-active
        slot, and those slots' earlier tokens may sit in the bypassed
        entries; jumping it would permute an active request's stream. So
        a step jumps only when its decode part serves no one beyond its
        own finishing rows (cold prefill ramp — exactly when TTFT-jumping
        pays; finishing rows can't appear in older entries because they
        were not prefill_done at those dispatches)."""
        if entry[0] == "prefill":
            return True
        if entry[0] != "step" or not entry[2]:
            return False
        fin = {r for _j, _s, r in entry[2]}
        return all(r is None or r in fin for r in entry[4])

    @staticmethod
    def _entry_requests(entry: tuple):
        """Requests carried by an in-flight entry (all entry kinds)."""
        if entry[0] == "prefill":
            return [r for _, r in entry[2] if r is not None]
        if entry[0] == "verify":
            return [r for _s, r in entry[3]]
        if entry[0] == "step":
            out = [r for _j, _s, r in entry[2]]
            if entry[4] is not None:
                out.extend(r for r in entry[4] if r is not None)
            return out
        return [r for r in entry[2] if r is not None]

    def _close_unreachable(self, failed: tuple) -> None:
        """A failed fetch permanently loses its entry's tokens. A request
        in its snapshot can still reach max_new_tokens only if it owns a
        slot (the scheduler sees its stalled emitted count and dispatches
        make-up chunks) or if SURVIVING queued entries carry enough tokens
        to finish it. A virtually-freed predecessor with neither would
        never see end-of-stream and block its consumer until the stream
        timeout — close exactly those. (Survivors' streams carry a token
        gap where the lost entry's tokens were; loss is inherent to a
        failed fetch, and termination is the contract being kept.)"""
        with self._lock:
            # clear under the SAME acquisition as the closes: the failed
            # entry's tokens are lost, and leaving it visible lets the
            # scheduler count them in _inflight_steps and virtually free a
            # slot on the strength of tokens that will never arrive
            self._processing = None
            lost = set(self._entry_requests(failed))
            lost.difference_update(self._slot_req)
            if not lost:
                return
            cover: dict = {}
            for e in self._inflight:
                if e[0] == "verify":
                    # mirror _inflight_steps' guaranteed-minimum: a verify
                    # covers at least the bonus token per selected slot
                    for r in self._entry_requests(e):
                        if r in lost:
                            cover[r] = cover.get(r, 0) + 1
                    continue
                if e[0] == "step":
                    # mirror _inflight_steps (finishes and snapshot
                    # iterated SEPARATELY — a finishing row appears in
                    # both, and visiting it twice would credit 2K+2
                    # instead of K+1, spuriously skipping the close and
                    # hanging the consumer): a finishing row carries its
                    # first token plus the piggybacked decode; a
                    # snapshot-only rider carries the decode steps alone
                    fin = {r for _j, _s, r in e[2]}
                    for r in fin:
                        if r in lost:
                            cover[r] = cover.get(r, 0) + e[5] + 1
                    if e[4] is not None:
                        for r in e[4]:
                            if r is not None and r in lost and r not in fin:
                                cover[r] = cover.get(r, 0) + e[5]
                    continue
                n = 1 if e[0] == "prefill" else e[3]
                for r in self._entry_requests(e):
                    if r in lost:
                        cover[r] = cover.get(r, 0) + n
            now = time.perf_counter()
            for r in lost:
                if (
                    r.finish_reason is None
                    and r.emitted + cover.get(r, 0) < r.max_new_tokens
                ):
                    r.finish_reason = "cancelled"
                    self._observe_finish(r, now)
                    r.out.put(None)


class ReplicatedLLMEngine:
    """Data-parallel replicated serving: N independent LLMEngine replicas —
    one per chip (or per tensor-parallel submesh) — behind a per-request
    router (SURVEY §2.8 row 1: "Replicated serving across chips;
    per-replica dispatch of batched requests").

    Each replica owns its full weight copy, KV cache, and scheduler, so
    replicas never synchronize: DP serving scales throughput linearly the
    way the reference scales by stateless pod replication (README.md:25),
    but within one process over the local device set. Composition with TP:
    pass `meshes=[(mesh, param_specs), ...]` and each replica runs
    tensor-parallel over its own submesh — dp x tp serving from one API.

    Routing: "least_loaded" (default) weighs each replica by its QUEUED
    TOKENS (prompt remainder + expected decode, LLMEngine.load_tokens) —
    a 128-token prompt is 16x the device work of an 8-token prompt, and
    counting requests instead piles long-prompt traffic onto one replica;
    occupant/queue count breaks ties. "round_robin" is stateless and
    optimal for uniform work.

    The public surface mirrors LLMEngine (submit/generate/stats/close), so
    ctx.tpu().llm(name) callers cannot tell one replica from many.
    """

    def __init__(
        self,
        cfg,
        params,
        *,
        replicas: int | None = None,
        devices: list | None = None,
        meshes: list | None = None,
        router: str = "least_loaded",
        logger=None,
        supervise: bool = True,
        version: str = "v1",
        failover_retries: int | None = None,
        fleet_max_queue_tokens: int | None = None,
        retry_budget_per_s: float | None = None,
        retry_budget_burst: float | None = None,
        poison_deaths: int | None = None,
        canary: bool | None = None,
        health_ledger=None,
        **engine_kw,
    ):
        import jax
        import os as _os

        if router not in ("least_loaded", "round_robin"):
            raise ValueError(f"unknown router {router!r}")
        self.router = router
        self._rr = itertools.count()
        specs: list[dict]
        if meshes is not None:
            specs = [{"mesh": m, "param_specs": s} for m, s in meshes]
        else:
            if devices is None:
                devices = jax.devices()[: replicas or 1]
            if replicas is not None and len(devices) < replicas:
                raise ValueError(
                    f"need {replicas} devices for {replicas} replicas, "
                    f"have {len(devices)}"
                )
            specs = [{"device": d} for d in devices]
        if not specs:
            raise ValueError("no replicas configured")
        if logger is not None:
            logger.info(
                f"replicated LLM serving: {len(specs)} replicas, "
                f"router={router}, supervise={supervise}"
            )
        # Rebuild inputs retained for the supervisor: a dead replica is
        # reconstructed from the SAME cfg/params/spec on the same
        # device/submesh. Holding `params` keeps the host copy alive for
        # the process lifetime — the price of restartability (pass
        # supervise=False to opt out and drop nothing extra: the engines
        # hold their device copies either way).
        self.logger = logger
        self.metrics = engine_kw.get("metrics")
        self.label = engine_kw.pop("kv_label", "llm")
        engine_kw.pop("version", None)  # fleet-owned; per-slot below
        # -- versioned weight registry (docs/advanced-guide/rollouts.md) --
        # The fleet retains (cfg, params) PER VERSION: the active version
        # serves, a staged version is shifted in replica-by-replica by
        # the rollout controller, and a rollback rebuilds from whichever
        # retained version the slot should run. _slot_versions tracks
        # what each replica slot serves RIGHT NOW (mixed mid-rollout).
        self.version = str(version)
        self._versions: dict[str, tuple] = {self.version: (cfg, params)}
        self._slot_versions = [self.version] * len(specs)
        # slots the rollout controller owns right now: the supervisor
        # must not race it by rebuilding a replica the controller just
        # drained/closed on purpose
        self._rollout_hold: set[int] = set()
        self._rollout = None  # active/last RolloutController
        self._rollout_lock = threading.Lock()
        self._versions_seen: set[str] = set()  # every gauge row ever written
        # shadow-probe source: the last few REAL prompts, mirrored onto a
        # rollout candidate before it is admitted to routing (sanity, not
        # token equality — versions legitimately differ)
        self._shadow_ring: deque = deque(maxlen=8)
        # Session affinity (docs/advanced-guide/kv-cache.md#sessions):
        # the paged session tier is PER-REPLICA state, so a conversation
        # routed to a different replica pays a full re-prefill. Remember
        # which replica holds each session and prefer it while it
        # accepts; bounded LRU so abandoned conversations cannot grow
        # the map forever.
        self._session_affinity: OrderedDict[str, int] = OrderedDict()
        self._session_affinity_cap = 65536
        self._specs = specs
        self._engine_kw = engine_kw
        if failover_retries is None:
            failover_retries = int(
                _os.environ.get("TPU_LLM_FAILOVER_RETRIES", "2")
            )
        self.failover_retries = max(0, failover_retries)
        self.failovers = 0  # requests re-dispatched off a dead replica
        self.failover_errors = 0  # rescues that found no live replica
        self._draining = False
        # -- fleet overload control (docs/advanced-guide/overload.md) -----
        # ONE fairness ledger shared by every replica: the virtual token
        # counters pool across the fleet, so least-served ordering holds
        # no matter which replica a client's requests land on. Retained
        # in _engine_kw, so supervised rebuilds rejoin the same ledger.
        from .resilience import FairLedger, RetryBudget

        fq = engine_kw.get("fair_queuing")
        if fq is None:
            # same precedence as LLMEngine: an explicit kwarg beats the
            # env (otherwise TPU_LLM_FAIR=0 would silently skip the
            # SHARED ledger while each replica still built its own —
            # fleet fairness degraded to per-replica with no signal)
            fq = _os.environ.get("TPU_LLM_FAIR", "1") != "0"
        if fq:
            # NOT setdefault(key, FairLedger(pop(...))): the value
            # expression would evaluate eagerly, discarding fair_weights
            # (and a throwaway ledger) whenever a fair_ledger was also
            # passed — weights must land on whichever ledger is used
            weights = engine_kw.pop("fair_weights", None)
            if engine_kw.get("fair_ledger") is None:
                engine_kw["fair_ledger"] = FairLedger(weights)
            elif weights:
                for c, w in weights.items():
                    engine_kw["fair_ledger"].set_weight(c, w)
        self.ledger = engine_kw.get("fair_ledger")
        # ONE usage meter shared by every replica (the fair-ledger
        # pattern): per-tenant chip-second/token windows pool across the
        # fleet, so quota enforcement and the usage endpoint see the
        # tenant's total rate no matter which replica admitted the
        # request. Retained in _engine_kw for supervised rebuilds.
        gp_on = engine_kw.get("goodput")
        if gp_on is None:
            gp_on = _os.environ.get("TPU_LLM_GOODPUT", "1") not in ("", "0")
        if gp_on and engine_kw.get("usage_meter") is None:
            from .goodput import UsageMeter

            win = engine_kw.get("usage_window_s")
            if win is None:
                win = float(
                    _os.environ.get("TPU_LLM_USAGE_WINDOW_S", "") or 60.0
                )
            engine_kw["usage_meter"] = UsageMeter(window_s=float(win))
        self.usage = engine_kw.get("usage_meter")
        # Fleet admission cap: reject at the summed queued-token estimate
        # across accepting replicas instead of piling onto the last
        # healthy engine (0 disables; per-engine max_queue still applies)
        if fleet_max_queue_tokens is None:
            fleet_max_queue_tokens = int(
                _os.environ.get("TPU_LLM_FLEET_MAX_QUEUE_TOKENS", "0") or 0
            )
        self.fleet_max_queue_tokens = max(0, int(fleet_max_queue_tokens))
        # batch-class headroom factor: batch work sheds at this fraction
        # of the fleet cap, so the LAST slice of fleet queue capacity is
        # reserved for interactive traffic — shed the reservoir before
        # the latency-sensitive class ever sees a 429
        # (docs/advanced-guide/overload.md + batch-inference.md)
        self.fleet_batch_factor = min(1.0, max(0.0, float(
            _os.environ.get("TPU_LLM_FLEET_BATCH_FACTOR", "0.8") or 0.8
        )))
        self.fleet_rejected = 0
        # Retry budget: router-side retries (failover re-dispatch,
        # replica death between pick and submit) draw from a token
        # bucket, so overload can never amplify into a retry storm — the
        # same pathology the inter-service circuit breaker guards
        # (gofr_tpu.service).
        if retry_budget_per_s is None:
            retry_budget_per_s = float(
                _os.environ.get("TPU_LLM_RETRY_BUDGET_PER_S", "1.0") or 0.0
            )
        if retry_budget_burst is None:
            retry_budget_burst = float(
                _os.environ.get("TPU_LLM_RETRY_BUDGET_BURST", "10") or 0.0
            )
        self.retry_budget = RetryBudget(retry_budget_per_s, retry_budget_burst)
        self.retry_budget_exhausted = 0
        # -- device health + poison quarantine (resilience.health;
        # docs/advanced-guide/resilience.md) ------------------------------
        # One ledger for the fleet: replica deaths and rebuild failures
        # are classified and billed to the device the engine ran on, and
        # a device that accumulates TPU_LLM_DEVICE_QUARANTINE_FAILURES
        # inside the window is quarantined — the supervisor then rebuilds
        # the slot elastically on an alternate healthy device (or parks
        # it, visibly, when none exists).
        from .resilience import DeviceHealthLedger, spec_device_key

        self.health = (
            health_ledger if health_ledger is not None
            else DeviceHealthLedger(
                metrics=self.metrics, model=self.label, logger=logger,
            )
        )
        self._device_keys = [spec_device_key(s) for s in specs]  # home devices
        self._current_keys = list(self._device_keys)  # where each slot runs NOW
        # Poison-request quarantine: a request in flight across this many
        # replica deaths is refused further failover (finish_reason
        # "poison" -> 500/INTERNAL) — one payload's blast radius is
        # bounded to poison_deaths replicas, never the fleet. 0 disables.
        if poison_deaths is None:
            poison_deaths = int(_os.environ.get("TPU_LLM_POISON_DEATHS", "2") or 0)
        self.poison_deaths = max(0, int(poison_deaths))
        self.poisoned = 0  # requests refused failover as poison
        # Canary gate: a rebuilt/reintegrated replica must reproduce the
        # fixed greedy probe (token-compared against a healthy replica's
        # cached output when one exists) before it re-enters routing.
        if canary is None:
            canary = _os.environ.get("TPU_LLM_CANARY", "1") != "0"
        self._canary_enabled = bool(canary)
        # healthy replicas' probe tokens, PER MODEL VERSION — different
        # weights legitimately produce different canary streams, so a v2
        # candidate must never be token-compared against the v1 reference
        self._canary_ref: dict[str, list[int]] = {}
        # Fleet adapter registry (gofr_tpu.lora): host copies of every
        # registered adapter checkpoint, so a rebuilt/shifted replica
        # re-stages the SAME tenant set its peers serve (_build_replica).
        # Insertion-ordered: re-staging replays loads oldest-first, which
        # reproduces the pool's LRU layout closely enough for tests.
        self._adapters_host: dict[str, dict] = {}
        # build replicas concurrently: XLA releases the GIL while compiling,
        # so N warmups overlap instead of serializing construction N-fold.
        # On any failure, close the replicas that DID come up — each holds
        # scheduler threads plus device-resident weights and KV cache that
        # would otherwise leak with no handle to free them.
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(max_workers=len(specs)) as pool:
            futures = [
                pool.submit(self._build_replica, i)
                for i in range(len(specs))
            ]
            engines, first_err = [], None
            for f in futures:
                try:
                    engines.append(f.result())
                except Exception as e:  # noqa: BLE001
                    first_err = first_err or e
        if first_err is not None:
            for e in engines:
                e.close()
            raise first_err
        self.engines = engines
        self._observe_versions()
        # incident seam (gofr_tpu.flightrec): a quarantine trip dumps a
        # black-box bundle from a live replica — the dying replica's own
        # _die bundle captures ITS corpse, this one captures the fleet
        # context (ledger state, which device, surviving capacity)
        self.health.on_quarantine = lambda device, why: self.incident(
            "quarantine", reason=f"device {device} quarantined ({why})"
        )
        self.supervisor = None
        if supervise:
            from .resilience import ReplicaSupervisor

            self.supervisor = ReplicaSupervisor(
                self,
                interval_s=float(
                    _os.environ.get("TPU_LLM_SUPERVISOR_INTERVAL_S", "0.5")
                ),
                backoff_s=float(
                    _os.environ.get("TPU_LLM_RESTART_BACKOFF_S", "1.0")
                ),
                backoff_max_s=float(
                    _os.environ.get("TPU_LLM_RESTART_BACKOFF_MAX_S", "30")
                ),
            )

    def _build_replica(
        self, i: int, spec: dict | None = None, version: str | None = None,
    ) -> "LLMEngine":
        """Construct (and warm) replica slot i from its retained spec —
        the same path at first build, at supervised restart, and at a
        rollout shift. ``spec`` overrides the home placement for elastic
        rebuilds (the supervisor passes an alternate healthy device when
        the home device is quarantined); ``version`` overrides the
        slot's current version (the rollout controller passes the target
        version on a shift and the retained old version on a rollback).
        Wires the failover hook so the new replica's deaths rescue
        in-flight work too. Per-replica kv label: N replicas sharing one
        label set would clobber each other's resident-bytes gauges."""
        from .resilience import InjectedFault, default_injector, spec_device_key

        spec = self._specs[i] if spec is None else spec
        version = self._slot_versions[i] if version is None else version
        cfg, params = self._versions[version]
        inj = self._engine_kw.get("fault_injector") or default_injector()
        key = spec_device_key(spec)
        if inj.take("device_sick", key) is not None:
            # chaos: a persistently sick chip — construction (param
            # placement / warmup) fails on this device, as an HBM or ICI
            # fault would, until the spec is disarmed or exhausted
            if self.logger is not None:
                self.logger.warn(f"fault injection: device_sick fired on {key}")
            if self.metrics is not None:
                self.metrics.increment_counter(
                    "app_llm_faults_injected_total",
                    point="device_sick", model=self.label,
                )
            raise InjectedFault(f"device_sick: build refused on {key}")
        eng = LLMEngine(
            cfg, params, logger=self.logger,
            kv_label=f"{self.label}/r{i}", version=version, **spec,
            **self._engine_kw,
        )
        eng.failover_hook = self._failover
        # re-stage the fleet's registered adapters (gofr_tpu.lora): a
        # supervised restart or rollout shift must come back serving the
        # same tenant set as its peers — a replica with an empty pool
        # would 404 every adapter-routed request the router lands on it
        if getattr(eng, "lora_slots", 0):
            for name, rec in list(self._adapters_host.items()):
                try:
                    eng.load_adapter(
                        name, rec["adapter"], version=rec["version"],
                        alpha=rec["alpha"], fair_weight=rec["fair_weight"],
                    )
                except Exception as ex:  # noqa: BLE001
                    if self.logger is not None:
                        self.logger.warn(
                            f"adapter {name!r} re-stage failed on rebuilt "
                            f"replica: {ex}"
                        )
        return eng

    def _spec_for_rebuild(self, i: int) -> tuple[dict, str] | None:
        """Placement policy for rebuilding slot i, consulting the device
        ledger: the home device/submesh when it is usable (healthy, or
        in probation — the canary gate guards the probe) and not
        occupied by another live replica; otherwise an alternate
        same-platform device that is usable and unoccupied, or — for
        tensor-parallel submeshes — an alternate SAME-SIZE submesh of
        usable, unoccupied chips (elastic submesh placement;
        docs/advanced-guide/sharded-serving.md). None = park: only when
        no placement exists anywhere."""
        home = self._specs[i]
        hkey = self._device_keys[i]
        used = {
            self._current_keys[j]
            for j, e in enumerate(self.engines)
            if j != i and e.alive()
        }
        if self.health.usable(hkey) and hkey not in used:
            return home, hkey
        dev = home.get("device")
        if dev is None:
            return self._alternate_submesh_spec(i, home)
        import jax

        from .resilience import device_key

        platform = getattr(dev, "platform", None)
        for d in jax.devices():
            if getattr(d, "platform", None) != platform:
                continue
            k = device_key(d)
            if k == hkey or k in used or not self.health.usable(k):
                continue
            return {"device": d}, k
        return None

    def _alternate_submesh_spec(self, i: int, home: dict) -> tuple[dict, str] | None:
        """Elastic SUBMESH placement: rebuild slot i's tensor-parallel
        replica on an alternate same-size, same-shape submesh of usable,
        unoccupied chips. The quarantined home submesh used to park its
        slot unconditionally (PR 7); now it parks only when no such
        submesh exists — the chips of every other live replica and the
        members of every quarantined submesh are excluded, the alternate
        mesh reuses the home mesh's axis names/shape, and the home's
        param_specs carry over unchanged (PartitionSpecs are
        mesh-independent)."""
        mesh = home.get("mesh")
        if mesh is None:
            return None
        try:
            homedevs = list(mesh.devices.flat)
        except AttributeError:  # duck-typed test meshes: nothing to re-place
            return None
        if not homedevs:
            return None
        import jax
        import numpy as np

        from .resilience import device_key, spec_device_key, split_device_key

        n = len(homedevs)
        platform = getattr(homedevs[0], "platform", None)
        # chips occupied by OTHER live replicas, wherever elastic
        # rebuilds currently place them
        used: set[str] = set()
        for j, e in enumerate(self.engines):
            if j != i and e.alive():
                used.update(split_device_key(self._current_keys[j]))
        # members of every quarantined ledger unit: a submesh trips as a
        # unit, so its chips are individually suspect until it
        # reintegrates (probation members stay eligible — the canary
        # gate judges the rebuild, exactly like single-device probation)
        sick: set[str] = set()
        for key, row in self.health.snapshot()["devices"].items():
            if row["state"] == "quarantined":
                sick.update(split_device_key(key))
        cands = [
            d for d in jax.devices()
            if getattr(d, "platform", None) == platform
            and device_key(d) not in used
            and device_key(d) not in sick
        ]
        if len(cands) < n:
            return None  # park: no same-size submesh of usable chips
        new_mesh = jax.sharding.Mesh(
            np.asarray(cands[:n]).reshape(mesh.devices.shape),
            mesh.axis_names,
        )
        spec = dict(home, mesh=new_mesh)
        return spec, spec_device_key(spec)

    def _canary_check(self, replacement: "LLMEngine") -> tuple[bool, str]:
        """Gate a rebuilt replica before it enters routing: the fixed
        greedy probe, token-compared against a healthy SAME-VERSION
        replica's cached output when the fleet has (ever had) one, else
        against completeness/vocabulary checks
        (resilience.health.canary_check). References are cached per
        model version — greedy decode is deterministic per
        params+config, so a version's reference never goes stale, and a
        rollout candidate on new weights is never compared against the
        old version's tokens."""
        if not self._canary_enabled:
            return True, "disabled"
        from .resilience.health import CANARY_MAX_NEW, CANARY_PROMPT, canary_check

        v = replacement.version
        ref = self._canary_ref.get(v)
        has_peer = False
        if ref is None:
            for e in self.engines:
                if e is replacement or not e.accepting() or e.version != v:
                    continue
                has_peer = True
                try:
                    ref = e.generate(
                        list(CANARY_PROMPT), max_new_tokens=CANARY_MAX_NEW,
                        temperature=0.0, eos_token=-1, probe=True,
                    )
                    if len(ref) == CANARY_MAX_NEW:
                        self._canary_ref[v] = ref
                        break
                    ref = None
                except Exception:  # noqa: BLE001 — a sick reference is no reference
                    ref = None
        ok, detail, toks = canary_check(replacement, ref)
        if ok and ref is None and not has_peer:
            # TRULY no healthy same-version replica existed (the first
            # replica of a staged version, or a fleet-wide outage): the
            # gated candidate's own passing output seeds the reference
            # for future canaries of this version. When a peer exists
            # but its reference fetch failed transiently (saturated,
            # draining race), do NOT self-seed — caching an unverified
            # candidate's tokens would poison the permanent reference
            # and canary-reject every honest rebuild after it; the next
            # canary simply retries the peer.
            self._canary_ref[v] = toks
        return ok, detail

    # -- model lifecycle (resilience.rollout;
    # docs/advanced-guide/rollouts.md) --------------------------------------
    def deploy(
        self,
        cfg=None,
        params=None,
        *,
        version: str | None = None,
        bake_s: float | None = None,
        shadow_probes: int | None = None,
        drain_timeout_s: float | None = None,
    ) -> dict:
        """Stage a new model version and shift the running fleet onto it
        with zero downtime: the rollout controller drains one replica at
        a time, rebuilds it on the new weights through the supervisor's
        ``_build_replica`` seam, gates it with the canary probe plus a
        shadow-traffic replay, admits it to routing, and watches a bake
        window afterwards — any regression (replica death, numerical
        trip, canary/shadow failure, request-error delta) rolls every
        upgraded replica back to the retained old params. The fleet
        always ends fully on ONE version.

        ``params`` are validated against ``cfg`` (structure, shapes,
        dtypes — models.checkpoint.validate_params) BEFORE any device
        transfer: a bad checkpoint is a 4xx at the admin route, never a
        dead replica. Returns the rollout snapshot immediately; progress
        is visible in stats()/debug_state()["rollout"] and the
        app_llm_rollout_* metrics."""
        from .models.checkpoint import validate_params
        from .resilience.rollout import (
            RolloutController,
            RolloutError,
            RolloutInProgress,
        )

        if params is None:
            raise RolloutError("deploy() needs params (the new weights)")
        active_cfg, _ = self._versions[self.version]
        cfg = active_cfg if cfg is None else cfg
        validate_params(params, cfg)  # typed 4xx before anything moves
        with self._rollout_lock:
            if self._rollout is not None and self._rollout.active():
                raise RolloutInProgress(
                    f"rollout to {self._rollout.to_version!r} already in "
                    f"progress (state {self._rollout.state})"
                )
            if self._draining:
                raise EngineDraining("fleet draining; refusing rollout")
            if version is None:
                version = self._derive_version()
            if version in self._versions:
                raise RolloutError(
                    f"model version {version!r} already exists "
                    f"(known: {sorted(self._versions)})"
                )
            self._versions[version] = (cfg, params)
            ctl = RolloutController(
                self, version, bake_s=bake_s, shadow_probes=shadow_probes,
                drain_timeout_s=drain_timeout_s,
            )
            self._rollout = ctl
            ctl.start()
        return ctl.snapshot()

    def _derive_version(self) -> str:
        """Next free label in the conventional v<N> sequence (used when
        deploy() is not given an explicit version)."""
        import re

        nums = [
            int(m.group(1))
            for v in self._versions
            for m in [re.match(r"^v(\d+)$", v)] if m
        ]
        n = (max(nums) + 1) if nums else (len(self._versions) + 1)
        while f"v{n}" in self._versions:
            n += 1
        return f"v{n}"

    def version_counts(self) -> dict[str, int]:
        """Live replicas per model version (mixed only mid-rollout)."""
        counts: dict[str, int] = {}
        for e in self.engines:
            if e.alive():
                counts[e.version] = counts.get(e.version, 0) + 1
        return counts

    def _observe_versions(self) -> None:
        """Keep ``app_llm_model_version_info`` truthful at fleet level:
        value = live replicas serving that version, and every version
        label the fleet has ever exported is re-written (stale rows from
        a completed or rolled-back version must read 0, not their last
        live value — the dead-engine gauge bug class)."""
        if self.metrics is None:
            return
        counts = self.version_counts()
        for v in set(self._versions) | set(counts) | self._versions_seen:
            self._versions_seen.add(v)
            self.metrics.set_gauge(
                "app_llm_model_version_info", float(counts.get(v, 0)),
                model=self.label, version=v,
            )

    def rollout_state(self) -> dict | None:
        """Snapshot of the active (or most recent) rollout, None if a
        deploy was never staged."""
        ctl = self._rollout
        return None if ctl is None else ctl.snapshot()

    # -- multi-tenant adapters (gofr_tpu.lora;
    # docs/advanced-guide/multi-tenancy.md) --------------------------------
    def load_adapter(
        self, name: str, adapter: dict, *, version: str = "v1",
        alpha: float | None = None, fair_weight: float | None = None,
    ) -> int:
        """Stage ``adapter`` on every live replica and retain a host copy
        so rebuilt/shifted replicas re-stage it (_build_replica). Returns
        the number of replicas staged; raises when none took it (a
        partial fleet serves — the router only lands adapter traffic on
        replicas that resolved the name, via submit failover)."""
        errs: list[Exception] = []
        done = 0
        for e in self.engines:
            if not e.alive():
                continue
            try:
                e.load_adapter(
                    name, adapter, version=version, alpha=alpha,
                    fair_weight=fair_weight,
                )
                done += 1
            except Exception as ex:  # noqa: BLE001
                errs.append(ex)
        if not done:
            raise errs[0] if errs else EngineStoppedError("all replicas dead")
        self._adapters_host[name] = {
            "adapter": adapter, "version": str(version), "alpha": alpha,
            "fair_weight": fair_weight,
        }
        return done

    def publish_adapter(self, staging: str, name: str) -> int:
        """Commit a staged hot-load on every live replica (atomic
        per-replica; in-flight requests drain on their old gid). Returns
        replicas switched."""
        done = 0
        for e in self.engines:
            if not e.alive():
                continue
            try:
                e.publish_adapter(staging, name)
                done += 1
            except Exception:  # noqa: BLE001
                pass  # replica without the staging name: nothing to commit
        rec = self._adapters_host.pop(staging, None)
        if rec is not None:
            self._adapters_host[name] = rec
        return done

    def evict_adapter(self, name: str) -> int:
        """Retire ``name`` fleet-wide (idle gids free now, busy ones
        drain as zombies). Returns replicas that held it."""
        self._adapters_host.pop(name, None)
        done = 0
        for e in self.engines:
            if not e.alive():
                continue
            try:
                e.evict_adapter(name)
                done += 1
            except KeyError:
                pass
        return done

    def adapters(self) -> dict:
        """Fleet adapter view: the registry's names plus the first live
        replica's pool snapshot (replicas converge on the same resident
        set; gids may differ per replica and are reported per-pool)."""
        lead = next((e for e in self.engines if e.alive()), None)
        snap = lead.adapters() if lead is not None else {
            "slots": 0, "resident": {}, "zombies": [],
            "evictions": 0, "swaps": 0,
        }
        return {**snap, "registered": sorted(self._adapters_host)}

    # -- routing -----------------------------------------------------------
    def _pick(
        self,
        exclude: set | frozenset = frozenset(),
        version: str | None = None,
    ) -> "LLMEngine":
        """Route among replicas that ACCEPT work — alive and not
        draining. A replica whose scheduler or collector thread died
        (LLMEngine._die) hands its queued requests to the failover hook;
        the router's job is to stop feeding it new ones. ``version``
        restricts the candidate set to replicas serving that model
        version — the failover path's mid-stream pin (a stream must
        never carry tokens from two versions)."""
        live = [
            e for e in self.engines
            if e.accepting() and id(e) not in exclude
            and (version is None or e.version == version)
        ]
        if not live:
            if any(
                e.alive() for e in self.engines
                if version is None or e.version == version
            ):
                raise EngineDraining("all replicas draining")
            raise EngineStoppedError(
                "all replicas dead" if version is None
                else f"no live replica serves model version {version!r}"
            )
        if self.router == "round_robin" or len(live) == 1:
            return live[next(self._rr) % len(live)]
        # token-weighted least-loaded: queued device work, not request
        # count — load() breaks ties so an idle replica still wins when
        # token estimates momentarily agree
        return min(live, key=lambda e: (e.load_tokens(), e.load()))

    # -- LLMEngine surface -------------------------------------------------
    def submit(self, req: GenRequest) -> GenRequest:
        # keep the budget gauge live: written only on retry events it
        # would stick at its post-burst low forever while the bucket
        # quietly refilled — a permanent false alarm for operators
        # alerting on "0 = retries disabled"
        self._observe_retry_budget()
        # Fleet-level admission: reject at the SUMMED queued-token
        # estimate across accepting replicas. Without this, per-replica
        # caps let a dying fleet funnel the whole offered load onto the
        # last healthy engine — the cap the fleet was sized for, not the
        # cap one replica was.
        if self.fleet_max_queue_tokens > 0:
            queued = sum(
                e.load_tokens() for e in self.engines if e.accepting()
            )
            # batch sheds FIRST: the throughput class hits a lowered cap
            # (fleet_batch_factor) so the top slice of queue capacity
            # stays reserved for interactive traffic under pressure
            cap = self.fleet_max_queue_tokens
            if req.priority == "batch":
                cap = int(cap * self.fleet_batch_factor)
            if queued >= cap:
                self.fleet_rejected += 1
                if self.metrics is not None:
                    # its own series, NOT app_llm_sheds_predicted_total:
                    # a queue-cap rejection and a predicted-wait shed are
                    # different causes and operators alert on them
                    # differently
                    self.metrics.increment_counter(
                        "app_llm_fleet_rejected_total", model=self.label
                    )
                raise EngineOverloaded(
                    f"fleet queue full ({queued} >= {cap} queued tokens"
                    + (" at batch-class headroom)" if cap
                       < self.fleet_max_queue_tokens else ")"),
                    retry_after=self._fleet_retry_after(queued),
                )
        # Error classification (docs/advanced-guide/overload.md):
        # - EngineStoppedError / EngineDraining are RETRYABLE — the
        #   replica died or began draining between pick and submit, and
        #   another replica can serve the request. Retries past the first
        #   attempt draw from the retry budget (no retry storms).
        # - EngineOverloaded is NON-RETRYABLE: the router already picked
        #   the least-loaded replica, so every other replica is at least
        #   as loaded — walking the fleet would turn one client's 429
        #   into fleet-wide overload amplification.
        # Bounded: the supervisor may swap replacements in mid-loop, so
        # the exclusion set alone is not a terminator.
        tried: set[int] = set()
        first_err: Exception | None = None
        if req.adapter and req.adapter not in self._adapters_host:
            # fast 404 for a name NO replica can serve (fleet registry
            # miss + no direct per-engine load): walking the fleet would
            # burn retry budget on an error every replica repeats
            if not any(
                req.adapter in e.adapters()["resident"]
                for e in self.engines if e.alive()
            ):
                raise UnknownAdapterError(
                    req.adapter, self._adapters_host
                )
        # session affinity: the replica holding this conversation's KV
        # (resident or host-spilled) serves the next turn as a prefix
        # hit; any other replica re-prefills the whole history. Falls
        # back to normal routing when the remembered replica is gone or
        # not accepting — sessions degrade, never error.
        prefer = None
        sid = req.session_id
        if sid:
            eid = self._session_affinity.get(sid)
            if eid is not None:
                prefer = next(
                    (e for e in self.engines if id(e) == eid), None
                )
                if prefer is not None and not prefer.accepting():
                    prefer = None
        for attempt in range(2 * len(self.engines) + 2):
            if attempt > 0 and not self.retry_budget.take():
                self.retry_budget_exhausted += 1
                self._observe_retry_budget()
                raise first_err  # budget spent: surface the original error
            if attempt > 0:
                self._observe_retry_budget()
            if prefer is not None and id(prefer) not in tried:
                eng = prefer
            else:
                eng = self._pick(exclude=tried)
            try:
                out = eng.submit(req)
            except (
                EngineStoppedError, EngineDraining, UnknownAdapterError,
            ) as e:
                # UnknownAdapterError is retryable HERE only: a replica
                # mid-rebuild may not have re-staged the adapter yet,
                # while its peers serve it (the registry fast-path above
                # already 404'd names nobody holds)
                first_err = first_err or e
                tried.add(id(eng))
                continue
            if sid:
                self._session_affinity.pop(sid, None)
                self._session_affinity[sid] = id(eng)
                while len(self._session_affinity) > self._session_affinity_cap:
                    self._session_affinity.popitem(last=False)
            # shadow-probe source (rollouts): remember a bounded prefix
            # of real accepted prompts; a rollout candidate replays a few
            # before admission (deque append is thread-safe, O(1))
            self._shadow_ring.append(tuple(req.prompt_tokens[:32]))
            return out
        raise first_err or EngineStoppedError("all replicas dead")

    def _fleet_retry_after(self, queued_tokens: int) -> float:
        """Retry-After for a fleet-level rejection: excess backlog over
        the cap, priced at the fleet's pooled measured throughput (1 s
        floor when no replica has an estimate yet)."""
        tput = self.throughput_tok_s()
        if tput is None:
            return 1.0
        excess = max(0, queued_tokens - self.fleet_max_queue_tokens)
        return max(0.5, excess / tput) if excess else 1.0

    def _observe_retry_budget(self) -> None:
        if self.metrics is not None:
            self.metrics.set_gauge(
                "app_llm_retry_budget_remaining",
                self.retry_budget.remaining(), model=self.label,
            )

    # -- in-flight failover (gofr_tpu.resilience) --------------------------
    def _failover(self, reqs: list[GenRequest]) -> None:
        """A dying replica's rescued requests, re-dispatched to the live
        survivors. Each continuation re-seeds its prompt with everything
        already emitted (prompt + history), so the consumer's stream
        resumes exactly where it left off — no duplicate and no missing
        token, token-identical for greedy decodes (sampled decodes
        continue with fresh randomness). Errors surface only when the
        per-request retry budget is spent or no live replica remains."""
        # ONE overload-wait window shared by the whole batch: a saturated
        # survivor must cost the rescue ~5 s total, not 5 s per rescued
        # request serially on the dying engine's thread
        batch_deadline = time.perf_counter() + 5.0
        for r in reqs:
            if self.poison_deaths and r.deaths >= self.poison_deaths:
                # poison-request quarantine: this payload was in flight
                # for poison_deaths replica deaths — the router stops
                # treating it as an innocent bystander and errors it to
                # its caller (500/INTERNAL via PoisonedRequestError)
                # instead of letting it kill another replica
                self.poisoned += 1
                if self.metrics is not None:
                    self.metrics.increment_counter(
                        "app_llm_poison_requests_total", model=self.label
                    )
                if self.logger is not None:
                    self.logger.error(
                        f"poison quarantine: request {r.id} implicated in "
                        f"{r.deaths} replica deaths; failover refused"
                    )
                r.finish_reason = "poison"
                if r.span is not None and r.span.end_ns == 0:
                    r.span.set_attribute("llm.finish_reason", "poison")
                    r.span.set_status("ERROR")
                    r.span.end()
                r.out.put(None)
                continue
            r.retries += 1
            placed = False
            budget_ok = True
            if r.retries <= self.failover_retries:
                # failover re-dispatch is a router-side retry: it draws
                # from the same budget as submit-time retries, so a
                # crash-looping replica under overload cannot multiply
                # its queued work across the survivors forever
                budget_ok = self.retry_budget.take()
                if not budget_ok:
                    self.retry_budget_exhausted += 1
                self._observe_retry_budget()
            if budget_ok and r.retries <= self.failover_retries:
                # goodput replay marker: the survivor re-prefills work
                # the dead replica already did — its prefill progress,
                # or the whole grown prompt once history folds in
                replay_to = r.prefill_pos
                if r.history:
                    r.prompt_tokens = list(r.prompt_tokens) + r.history
                    r.history = []
                    replay_to = len(r.prompt_tokens)
                r._replay_pos = max(r._replay_pos, replay_to)
                # reset engine-owned scheduling state; consumer-facing
                # state (out queue, emitted, span) carries over
                r.finish_reason = None
                r.phase = "queued"
                r.prefill_pos = 0
                r.prefill_done = False
                r.slot = None
                r._rows_hi = 0
                r._prefill_t0 = None
                r._load_acct = 0
                tried: set[int] = set()
                # Mid-stream version pin (docs/advanced-guide/rollouts.md):
                # a request that already emitted tokens continues ONLY on
                # a replica serving the same model version — resuming the
                # continuation prompt on different weights would splice
                # two models' tokens into one stream (silent corruption:
                # the bytes look plausible and the status is 200). A
                # request with nothing emitted may restart anywhere; its
                # stream is still single-version by construction.
                pin = r.engine_version if r.emitted > 0 else None
                # A momentarily FULL live replica is not a dead one:
                # excluding it would error rescued work while capacity
                # exists seconds later (the overload+death case failover
                # exists for). Overloads wait-and-retry inside the shared
                # window; only stopped/draining replicas are excluded.
                first_try = True
                while first_try or time.perf_counter() < batch_deadline:
                    first_try = False
                    try:
                        eng = self._pick(exclude=tried, version=pin)
                    except (EngineStoppedError, EngineDraining):
                        if (
                            pin is not None
                            and self.logger is not None
                            and any(e.accepting() for e in self.engines)
                        ):
                            self.logger.error(
                                f"failover: request {r.id} pinned to model "
                                f"version {pin} mid-stream and no live "
                                f"replica serves it; erroring instead of "
                                f"mixing versions"
                            )
                        break
                    try:
                        eng.submit(r)
                        placed = True
                        break
                    except (EngineStoppedError, EngineDraining):
                        tried.add(id(eng))
                    except EngineOverloaded:
                        time.sleep(0.05)
                    except ValueError:
                        break  # continuation no longer fits the cache
            if placed:
                self.failovers += 1
                if self.metrics is not None:
                    self.metrics.increment_counter(
                        "app_llm_failovers_total", model=self.label
                    )
                if self.logger is not None:
                    self.logger.warn(
                        f"failover: request {r.id} re-dispatched "
                        f"(retry {r.retries}/{self.failover_retries})"
                    )
            else:
                self.failover_errors += 1
                if self.metrics is not None:
                    self.metrics.increment_counter(
                        "app_llm_failover_errors_total", model=self.label
                    )
                r.finish_reason = "error"
                if r.span is not None and r.span.end_ns == 0:
                    r.span.set_attribute("llm.finish_reason", "error")
                    r.span.set_status("ERROR")
                    r.span.end()
                r.out.put(None)

    def generate(self, prompt_tokens: list[int], **kw) -> list[int]:
        return self.submit(GenRequest(prompt_tokens, **kw)).tokens()

    def load(self) -> int:
        return sum(e.load() for e in self.engines)

    def load_tokens(self) -> int:
        return sum(e.load_tokens() for e in self.engines)

    def throughput_tok_s(self) -> float | None:
        """Pooled measured throughput across live replicas (None until
        any replica has a window) — the fleet's share of the scale-out
        admission signal (docs/advanced-guide/scale-out.md)."""
        tput = sum(e._tput_ema or 0.0 for e in self.engines if e.alive())
        return tput if tput > 1e-9 else None

    def predicted_wait_s(self) -> float | None:
        """Fleet predicted queue wait: summed queued tokens over pooled
        measured throughput (the per-engine estimate, lifted across
        replicas)."""
        tput = self.throughput_tok_s()
        if tput is None:
            return None
        return self.load_tokens() / tput

    def stats(self) -> dict:
        per = [e.stats() for e in self.engines]
        out = {
            "replicas": len(per),
            "replicas_alive": sum(e.alive() for e in self.engines),
            "router": self.router,
            "draining": self._draining,
            # model lifecycle (docs/advanced-guide/rollouts.md)
            "version": self.version,
            "versions": self.version_counts(),
            "rollout": self.rollout_state(),
            "disconnect_cancels": sum(
                s.get("disconnect_cancels", 0) for s in per
            ),
            "failovers": self.failovers,
            "failover_errors": self.failover_errors,
            "restarts": self.supervisor.restarts if self.supervisor else 0,
            # device health + poison quarantine (resilience.health)
            "poisoned": self.poisoned,
            "devices_quarantined": self.health.quarantined_count(),
            "replicas_parked": (
                self.supervisor.parked_count() if self.supervisor else 0
            ),
            "replicas_failed": (
                self.supervisor.failed_count() if self.supervisor else 0
            ),
            # fleet overload control (docs/advanced-guide/overload.md)
            "preemptions": sum(s.get("preemptions", 0) for s in per),
            "sheds_predicted": sum(s.get("sheds_predicted", 0) for s in per),
            "fleet_rejected": self.fleet_rejected,
            "fleet_max_queue_tokens": self.fleet_max_queue_tokens,
            "retry_budget_remaining": round(self.retry_budget.remaining(), 2),
            "retry_budget_exhausted": self.retry_budget_exhausted,
            "fairness": (
                self.ledger.snapshot() if self.ledger is not None else None
            ),
            # multi-tenant adapters (gofr_tpu.lora)
            "adapters": self.adapters(),
            # fleet speculative-decoding totals (per-replica in per_replica)
            "spec": {
                "enabled": any(
                    (s.get("spec") or {}).get("enabled") for s in per
                ),
                "proposed": sum(
                    (s.get("spec") or {}).get("proposed", 0) for s in per
                ),
                "accepted": sum(
                    (s.get("spec") or {}).get("accepted", 0) for s in per
                ),
            },
            "slots": sum(s["slots"] for s in per),
            "active": sum(s["active"] for s in per),
            "waiting": sum(s["waiting"] for s in per),
            "max_seq_len": per[0]["max_seq_len"],
            "decode_chunk": per[0]["decode_chunk"],
            "per_replica": per,
            # fleet-wide phase percentiles: pooled raw windows, not an
            # average of per-replica percentiles (which has no meaning)
            "phases": self._merged_phases(),
            "mfu": self._merged_mfu(),
            # fleet chip-time attribution (gofr_tpu.goodput): summed
            # per-replica ledgers; ratio recomputed from the pooled sums
            "goodput": self._merged_goodput(),
        }
        prefixes = [
            s["kvcache"]["prefix"] for s in per if s["kvcache"].get("prefix")
        ]
        if prefixes:  # fleet-wide prefix-cache totals (per-replica in per_replica)
            out["kvcache_prefix"] = {
                key: sum(p.get(key, 0) for p in prefixes)
                for key in ("hits", "misses", "partial_hits", "evictions",
                            "resident_bytes")
            }
        return out

    def _merged_phases(self) -> dict:
        from .metrics import summarize_window

        merged: dict[str, list[float]] = {}
        for e in self.engines:
            for name, w in e._phases.items():
                merged.setdefault(name, []).extend(w.values())
        return {name: summarize_window(vs) for name, vs in merged.items()}

    def _merged_mfu(self) -> dict:
        """Fleet utilization, same shape as LLMEngine.stats()['mfu'] so
        consumers (bench's _mfu_block, dashboards) never branch on the
        engine kind: pooled raw MFU/roofline/token-rate windows (the
        no-averaging-percentiles rule of _merged_phases)."""
        from .metrics import summarize_window

        lead = self.engines[0]
        out: dict = {
            "chips": sum(e._n_chips for e in self.engines),
            "peak_flops_per_chip": lead._peak_flops,
            "hbm_bw_per_chip": lead._hbm_bw,
            "params": lead._costs.params,
            "flops_per_token": lead._costs.matmul_flops_per_token,
        }
        for key in ("prefill", "decode"):
            out[key] = summarize_window(
                [v for e in self.engines for v in e._mfu_windows[key].values()]
            )
        out["tokens_per_second_per_chip"] = summarize_window(
            [v for e in self.engines for v in e._tok_chip_window.values()]
        )
        roofline = {
            key: summarize_window([
                v for e in self.engines
                for v in e._roofline_windows[key].values()
            ])
            for key in ("prefill", "decode")
        }
        roofline["bound"] = lead._mfu_mod.classify_bound(roofline["decode"]["p50"])
        out["roofline"] = roofline
        return out

    def debug_state(self) -> dict:
        return {
            "router": self.router,
            "replicas": len(self.engines),
            "replicas_alive": sum(e.alive() for e in self.engines),
            "draining": self._draining,
            # model lifecycle (docs/advanced-guide/rollouts.md)
            "version": self.version,
            "versions_retained": sorted(self._versions),
            "slot_versions": list(self._slot_versions),
            "rollout": self.rollout_state(),
            "failovers": self.failovers,
            "failover_errors": self.failover_errors,
            "failover_retries": self.failover_retries,
            "fleet_rejected": self.fleet_rejected,
            "fleet_max_queue_tokens": self.fleet_max_queue_tokens,
            "retry_budget": {
                "remaining": round(self.retry_budget.remaining(), 2),
                "rate_per_s": self.retry_budget.rate,
                "burst": self.retry_budget.burst,
                "exhausted": self.retry_budget_exhausted,
            },
            "fairness": (
                self.ledger.snapshot() if self.ledger is not None else None
            ),
            "supervisor": (
                self.supervisor.snapshot()
                if self.supervisor is not None else None
            ),
            "health": self.health.snapshot(),
            "devices": {
                "home": list(self._device_keys),
                "current": list(self._current_keys),
            },
            "poison_deaths": self.poison_deaths,
            "poisoned": self.poisoned,
            "canary": self._canary_enabled,
            "phases": self._merged_phases(),
            "slo": self._merged_slo(),
            "goodput": self._merged_goodput(),
            "usage": (
                self.usage.snapshot() if self.usage is not None else None
            ),
            "per_replica": [e.debug_state() for e in self.engines],
        }

    def _merged_goodput(self) -> dict | None:
        """Fleet goodput pooling: chip-second sums are additive across
        replicas; the useful fraction recomputes from the pooled sums
        (never average per-replica ratios)."""
        from .goodput import pool_goodput

        snaps = [
            e.goodput.snapshot() for e in self.engines
            if e.goodput is not None
        ]
        return pool_goodput(snaps) if snaps else None

    def usage_state(self) -> dict:
        """Windowed per-tenant usage + pooled goodput for the
        /.well-known/debug/usage endpoint (chargeback export). The meter
        is SHARED across replicas, so tenant windows are fleet-local
        totals already — no per-replica summing needed."""
        usage = (
            self.usage.snapshot() if self.usage is not None
            else {"window_s": None, "tenants": {}}
        )
        return {
            "replicas": len(self.engines),
            "goodput": self._merged_goodput(),
            "quota": (
                self.engines[0].quota.snapshot()
                if self.engines and self.engines[0].quota is not None
                else None
            ),
            "quota_sheds": sum(e.quota_sheds for e in self.engines),
            **usage,
        }

    def set_tenant_quota(self, tenant: str, tok_s: float | None) -> None:
        """Fleet quota update: every replica's gate enforces against the
        SHARED usage meter, so the ceiling is a fleet-total rate.
        Retained in _engine_kw so supervised rebuilds rejoin with the
        same quota table (the shared-ledger discipline)."""
        q = self._engine_kw.setdefault("quotas", {})
        if tok_s is None or tok_s <= 0:
            q.pop(tenant, None)
        else:
            q[tenant] = float(tok_s)
        for e in self.engines:
            e.set_tenant_quota(tenant, tok_s)

    def _merged_slo(self) -> dict | None:
        """Fleet SLO pooling: summed goodput, max-burn-across-replicas
        (the hottest replica gates health — same semantics as
        gauge_total over the per-replica fast-burn gauge)."""
        from .metrics.slo import pool_snapshots

        snaps = [
            e.slo.snapshot() for e in self.engines if e.slo is not None
        ]
        return pool_snapshots(snaps) or None

    # -- incident flight recorder (gofr_tpu.flightrec; docs/advanced-
    # guide/incident-debugging.md) ----------------------------------------
    def incident(self, trigger: str, *, reason: str = "") -> str | None:
        """Dump one black-box bundle from the first live replica —
        fleet-level triggers (quarantine, rollout rollback) need a
        witness that still has state; a dying replica dumps its own
        bundle from _die before this could reach it."""
        for e in self.engines:
            if e.alive():
                return e._incident(trigger, reason=reason)
        return None

    def replay(self, record_or_id, *, timeout: float = 120.0) -> dict:
        """Deterministic replay across the fleet: locate the flight
        record on any replica (dead ones keep their rings for exactly
        this), then re-execute on a live replica pinned to the record's
        model version — cross-version replays compare nothing."""
        from .flightrec import find_record, replay_record

        rec = record_or_id
        if not isinstance(rec, dict):
            rec, _owner = find_record(self, int(record_or_id))
            if rec is None:
                return {
                    "id": record_or_id,
                    "error": "no flight record with that id on any replica",
                }
        want = rec.get("model_version")
        for e in self.engines:
            if e.alive() and (not want or e.version == want):
                return replay_record(e, rec, timeout=timeout)
        return {
            "id": rec.get("id"),
            "error": f"no live replica serves version {want!r} for replay",
        }

    def drain(self) -> None:
        """Fleet drain: stop the supervisor from rebuilding (the process
        is going down), close admission on every live replica, let
        in-flight work finish. The app lifecycle polls drained()."""
        self._draining = True
        for e in self.engines:
            if e.alive():
                e.drain()

    def drained(self) -> bool:
        # aliveness FIRST: e.drained() on a watchdog-killed replica whose
        # lock is wedged under a hung device call would block the drain
        # poll forever (the deadline could never fire)
        return all(not e.alive() or e.drained() for e in self.engines)

    def close(self) -> None:
        self._draining = True  # a rebuild racing close must not be routed
        if self._rollout is not None:
            # a mid-shift controller must stop BEFORE the engines close:
            # it would otherwise race the teardown rebuilding replicas
            # into a fleet that no longer exists
            self._rollout.close()
        if self.supervisor is not None:
            self.supervisor.close()
        for e in self.engines:
            e.close()
        if self.metrics is not None:
            # a closed fleet must not keep exporting its last budget
            # level, capacity-degradation state, or model-version rows
            # (the dead-engine gauge bug class)
            for name in (
                "app_llm_retry_budget_remaining",
                "app_llm_devices_quarantined",
                "app_llm_replicas_parked",
                "app_llm_replicas_failed",
                "app_llm_rollout_state",
            ):
                self.metrics.set_gauge(name, 0.0, model=self.label)
            for v in set(self._versions) | self._versions_seen:
                self.metrics.set_gauge(
                    "app_llm_model_version_info", 0.0,
                    model=self.label, version=v,
                )
