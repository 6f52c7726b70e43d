"""examples/grpc-gemma: token-streaming LLM decode over gRPC —
BASELINE.json config 3 ("grpc-server unary + server-streaming Gemma-2B
decode") through the continuous-batching engine.

Weights: set GEMMA_CKPT to an HF safetensors checkpoint (file or sharded
dir) or an orbax directory of the native pytree — loaded via
gofr_tpu.models.checkpoint. Set GEMMA_TOKENIZER (or ship tokenizer.json in
the checkpoint dir) for text in/out. Without GEMMA_CKPT the model is
randomly initialized (this environment has no weight downloads) and the API
still works on raw token ids — the serving path is identical.

GEMMA_PRESET=tiny (default, CI/dev) | 2b | 7b | llama3-8b | tiny-llama |
mistral-7b | tiny-mistral | qwen2-7b chooses the architecture; llama,
mistral and qwen2 presets load via the Llama checkpoint mapping (untied
lm_head, silu, plain RMSNorm absorbed at load). With GEMMA_INT8=1 and no
checkpoint the random tree is built int8 directly on the device: a 7B
bf16 tree plus its int8 copy does not fit one 16 GB chip.

Drive it:
  unary:  json_unary(target, "Gemma", "Generate", {"prompt": "...", "max_new_tokens": 8})
  stream: json_server_stream(target, "Gemma", "Stream", {...}) -> one token per chunk
"""

import os
import sys

sys.path.insert(0, "../..")

import gofr_tpu

TOKENIZER = None  # set at build time when configured


def _spec_kw() -> dict:
    """Speculative-decoding kwargs from LLM_SPEC / LLM_SPEC_DRAFT —
    only the keys the operator actually set, so register_llm's
    app-config defaulting (TPU_LLM_SPEC*) still applies when unset."""
    kw: dict = {}
    v = os.environ.get("LLM_SPEC", "").lower()
    if v in ("1", "true"):
        kw["speculative"] = True
    elif v in ("0", "false"):
        kw["speculative"] = False
    d = int(os.environ.get("LLM_SPEC_DRAFT", "0") or 0)
    if d:
        kw["spec_draft"] = d
    return kw


def _session_kw() -> dict:
    """Session-tier kwargs from LLM_SESSION_MB / LLM_KV_PAGED — only
    the keys the operator actually set, so register_llm's app-config
    defaulting (TPU_LLM_SESSION_MB / TPU_LLM_KV_PAGED) still applies
    when unset. With a session budget, X-GoFr-Session conversations
    keep their KV blocks warm between turns
    (docs/advanced-guide/kv-cache.md#sessions)."""
    kw: dict = {}
    mb = float(os.environ.get("LLM_SESSION_MB", "0") or 0.0)
    if mb > 0:
        kw["session_mb"] = mb
    v = os.environ.get("LLM_KV_PAGED", "").lower()
    if v in ("1", "true"):
        kw["kv_paged"] = True
    elif v in ("0", "false"):
        kw["kv_paged"] = False
    return kw


def _topology_kw(cfg) -> dict:
    """Multi-chip topology from LLM_TP / LLM_DISAGG
    (docs/advanced-guide/sharded-serving.md):

    - ``LLM_TP=K`` carves the device slice into K-chip tensor-parallel
      submeshes — one replica per submesh (dp x tp serving; K=1 is one
      single-chip replica per device). Unset with >1 devices keeps the
      legacy default: ONE engine tensor-parallel over the whole slice.
    - ``LLM_DISAGG=1`` splits the replicas into prefill/decode role
      pools with device-to-device KV handoff
      (``LLM_DISAGG_PREFILL_REPLICAS`` sizes the prefill pool; the
      TPU_LLM_DISAGG_PREFILL_REPLICAS app-config knob still applies
      when unset).
    """
    import jax

    kw: dict = {}
    n_dev = len(jax.devices())
    tp_env = os.environ.get("LLM_TP", "")
    tp = int(tp_env or 0)
    if os.environ.get("LLM_DISAGG", "").lower() in ("1", "true"):
        kw["disagg"] = True
        pr = int(os.environ.get("LLM_DISAGG_PREFILL_REPLICAS", "0") or 0)
        if pr:
            kw["prefill_replicas"] = pr
        if tp > 1:
            from gofr_tpu.parallel import tp_submeshes

            kw["meshes"] = tp_submeshes(cfg, tp)
        else:
            kw["replicas"] = max(2, n_dev)
        return kw
    if tp > 1:
        from gofr_tpu.parallel import tp_submeshes

        meshes = tp_submeshes(cfg, tp)
        if len(meshes) == 1:
            kw["mesh"], kw["param_specs"] = meshes[0]
        else:
            kw["meshes"] = meshes
    elif tp == 1 and n_dev > 1:
        kw["replicas"] = n_dev
    elif n_dev > 1 and tp_env == "":
        from gofr_tpu.parallel import make_mesh, param_specs

        mesh = make_mesh({"data": 1, "model": n_dev})
        kw = {"mesh": mesh, "param_specs": param_specs(cfg, mesh)}
    return kw


def build_engine(app):
    global TOKENIZER
    import jax

    from gofr_tpu.models import TransformerConfig, init_params

    preset = os.environ.get("GEMMA_PRESET", "tiny")
    cfg = {
        "tiny": TransformerConfig.tiny,
        "2b": TransformerConfig.gemma_2b,
        "7b": TransformerConfig.gemma_7b,
        "llama3-8b": TransformerConfig.llama3_8b,
        "tiny-llama": TransformerConfig.tiny_llama,
        # sliding-window presets: the engine automatically serves these
        # from a window-bounded rolling KV cache (gofr_tpu.kvcache) —
        # slot memory O(window) instead of O(LLM_MAX_SEQ)
        "mistral-7b": TransformerConfig.mistral_7b,
        "tiny-mistral": TransformerConfig.tiny_mistral,
        "qwen2-7b": TransformerConfig.qwen2_7b,
    }[preset]()
    is_llama = any(f in preset for f in ("llama", "mistral", "qwen2"))
    int8 = os.environ.get("GEMMA_INT8", "").lower() in ("1", "true")

    ckpt = os.environ.get("GEMMA_CKPT", "")
    if ckpt:
        from gofr_tpu.models.checkpoint import (
            load_gemma_checkpoint,
            load_llama_checkpoint,
        )

        app.logger.info(f"loading weights from {ckpt}")
        loader = load_llama_checkpoint if is_llama else load_gemma_checkpoint
        params = loader(ckpt, cfg)
    else:
        app.logger.warn("GEMMA_CKPT not set: serving randomly initialized weights")
        if int8:
            from gofr_tpu.models.quant import init_params_quantized

            params = jax.jit(
                lambda k: init_params_quantized(k, cfg, cfg.dtype, untied=is_llama)
            )(jax.random.PRNGKey(0))
        else:
            params = init_params(jax.random.PRNGKey(0), cfg)

    tok_path = os.environ.get("GEMMA_TOKENIZER", "") or (ckpt if os.path.isdir(ckpt) else "")
    if tok_path:
        from gofr_tpu.models.tokenizer import load_tokenizer

        try:
            TOKENIZER = load_tokenizer(tok_path)
            app.logger.info(f"tokenizer loaded ({TOKENIZER.vocab_size} pieces)")
        except FileNotFoundError:
            app.logger.warn(f"no tokenizer.json under {tok_path}; id-only API")

    # LLM_TP=K: K-chip tensor-parallel submesh replicas; LLM_DISAGG=1:
    # disaggregated prefill/decode pools with KV handoff (see
    # _topology_kw; docs/advanced-guide/sharded-serving.md). Unset with
    # >1 devices keeps one engine TP across the whole slice.
    kw = _topology_kw(cfg)
    build_engine.cfg = cfg  # build_app reads vocab for the byte fallback
    app.container.tpu().register_llm(
        "gemma", cfg, params,
        slots=int(os.environ.get("LLM_SLOTS", "4")),
        max_seq_len=int(os.environ.get("LLM_MAX_SEQ", "256")),
        prefill_buckets=(16, 64, 128),
        # GEMMA_INT8=1: serve int8 weights (W8A8 prefill, weight-only
        # decode) — halves the HBM stream decode is bound by, and the only
        # way 7B fits one v5e chip
        quantize=int8,
        # LLM_SPEC=1: speculative decoding — the host-side n-gram
        # drafter with fused on-device verification. Greedy outputs are
        # token-identical to spec-off and temperature outputs keep their
        # distribution; repetitive/structured output (code, JSON,
        # extraction) decodes multiple tokens per forward pass
        # (docs/advanced-guide/speculative-decoding.md). Draft length
        # via LLM_SPEC_DRAFT (default 4). The kwargs ride **_spec_kw and
        # are OMITTED when the env vars are unset — passing None would
        # defeat register_llm's setdefault of the documented
        # TPU_LLM_SPEC / TPU_LLM_SPEC_DRAFT app-config knobs (the
        # prefix_cache_mb precedent below); an explicit LLM_SPEC=0 still
        # forces OFF even when the fleet-wide config knob is on.
        **_spec_kw(),
        # LLM_SESSION_MB>0: the paged session tier — X-GoFr-Session
        # conversations keep their KV blocks resident between turns
        # (spilled to host RAM when cold), so every follow-up turn
        # block-shares the whole history instead of re-prefilling it
        **_session_kw(),
        # prefix_cache_mb is NOT passed here: register_llm defaults it
        # from the documented TPU_LLM_PREFIX_CACHE_MB config knob
        # (docs/references/configs.md). Set it >0 to retain prefill KV
        # rows keyed by prompt so repeated/shared-prefix prompts skip
        # prefill (gofr_tpu.kvcache); hit/miss/eviction counters appear
        # on /metrics and in stats().
        **kw,
    )


def _request_tokens(body) -> tuple[list[int], int]:
    """Resolve prompt text or raw ids -> (tokens, eos)."""
    if "prompt" in body and TOKENIZER is not None:
        toks = TOKENIZER.encode(body["prompt"])
        eos = TOKENIZER.eos_id if TOKENIZER.eos_id is not None else -1
        return toks, eos
    if "prompt" in body:
        raise gofr_tpu.HTTPError(400, "no tokenizer configured; send 'tokens'")
    return list(body["tokens"]), int(body.get("eos_token", -1))


def generate(ctx):
    from gofr_tpu.handler import llm_request_kwargs

    body = ctx.bind()
    toks, eos = _request_tokens(body)
    out = ctx.tpu().llm("gemma").generate(
        toks, max_new_tokens=int(body.get("max_new_tokens", 16)),
        temperature=float(body.get("temperature", 0.0)), eos_token=eos,
        # end-to-end deadline: if this handler's timeout fires, the engine
        # cancels the slotted decode instead of finishing it for no one
        deadline=ctx.deadline,
        # overload-control identity from the edge (HTTP headers and gRPC
        # metadata both surface through ctx.header): X-GoFr-Priority
        # ("batch" absorbs pressure via preemption/brownout) and
        # X-GoFr-Client (per-client weighted fair queuing) — see
        # docs/advanced-guide/overload.md
        **llm_request_kwargs(ctx),
    )
    resp = {"tokens": out}
    if TOKENIZER is not None:
        resp["text"] = TOKENIZER.decode(out)
    return resp


async def stream(ctx):
    from gofr_tpu.handler import llm_request_kwargs
    from gofr_tpu.llm import GenRequest

    body = ctx.bind()
    toks, eos = _request_tokens(body)
    req = ctx.tpu().llm("gemma").submit(
        GenRequest(
            toks,
            max_new_tokens=int(body.get("max_new_tokens", 16)),
            temperature=float(body.get("temperature", 0.0)),
            eos_token=eos,
            # NO deadline here, unlike generate(): REQUEST_TIMEOUT only
            # bounds OBTAINING this generator, never the streaming phase,
            # so a connected client legitimately streams past it — a
            # deadline would silently truncate the live stream mid-flight
            **llm_request_kwargs(ctx),
        )
    )
    emitted: list[int] = []
    async for tok in req.astream():
        chunk = {"token": tok}
        if TOKENIZER is not None:
            # decode incrementally: text of all tokens so far minus prefix
            prev = TOKENIZER.decode(emitted)
            emitted.append(tok)
            chunk["text"] = TOKENIZER.decode(emitted)[len(prev):]
        yield chunk


def engine_stats(ctx):
    return ctx.tpu().llm("gemma").stats()


def _serving_tokenizer():
    """The configured tokenizer, else the dependency-free byte-level
    fallback when the model vocabulary admits it (>= 258 ids) — what
    lets the OpenAI edge and the batch tier serve TEXT against the
    randomly-initialized dev/CI presets with zero assets."""
    if TOKENIZER is not None:
        return TOKENIZER
    cfg = getattr(build_engine, "cfg", None)
    if cfg is not None and cfg.vocab_size >= 258:
        from gofr_tpu.models.tokenizer import ByteTokenizer

        return ByteTokenizer(cfg.vocab_size)
    return None


def build_app():
    app = gofr_tpu.new()
    build_engine(app)
    app.grpc_unary("Gemma", "Generate", generate)
    app.grpc_server_stream("Gemma", "Stream", stream)
    # the same handler over HTTP: one POST /generate produces one trace
    # (handler -> llm.request -> queue_wait/prefill/decode spans), one
    # wide-event log line, and app_llm_* series on /metrics — see
    # docs/advanced-guide/observability-serving.md. Live engine state:
    # GET /.well-known/debug/engine.
    app.post("/generate", generate)
    app.get("/stats", engine_stats)
    # OpenAI-compatible edge (docs/advanced-guide/batch-inference.md +
    # structured-decoding.md): stock OpenAI clients/load tools speak to
    # /v1/chat/completions (SSE streaming, json_schema response_format),
    # /v1/embeddings and /v1/models unmodified — directly or through the
    # front-router tier.
    from gofr_tpu.openai_compat import register_openai_routes

    register_openai_routes(app, model="gemma", tokenizer=_serving_tokenizer())
    # Offline batch tier (opt-in): LLM_BATCH_TOPIC + PUBSUB_BACKEND
    # drain JSON generation jobs from pub/sub into the engine's batch
    # priority class, results to <topic>.results or per-job webhooks,
    # POST /v1/batches to submit over HTTP.
    topic = os.environ.get("LLM_BATCH_TOPIC", "")
    if topic and app.container.pubsub is not None:
        from gofr_tpu.batch import attach_batch_worker

        attach_batch_worker(
            app, topic, model="gemma",
            tokenizer=_serving_tokenizer(),
            concurrency=int(os.environ.get("LLM_BATCH_CONCURRENCY", "4")),
        )
    return app


def main():
    build_app().run()


if __name__ == "__main__":
    main()
