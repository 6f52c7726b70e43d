#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

With no arguments, in ONE process (a chip belongs to one process at a time):

1. device: leaves JAX_PLATFORMS alone, requires ``jax.devices()[0].platform
   == "tpu"`` and a ``device_kind`` that is in the peaks table
   (gofr_tpu.profiling.mfu) — no accelerator, no result, non-zero exit;
2. kernel phase: COMPILES (never interprets) the two Pallas kernels on the
   serving path — flash attention with and without ``q_offsets`` (chunked
   prefill) and the paged-decode kernel over a bf16 and an int8 pool of
   16-, 64- and 128-token blocks — at
   the smoke model's head shapes and at 8 kv heads, and compares each with
   its XLA reference on seeded inputs;
3. server phase: boots ``examples/grpc-gemma``'s ``build_app()`` in-process
   at the published Qwen2-7B widths and full depth (int8 weights from a
   seed), and over the real socket sends one ``POST /generate`` and its
   exact repeat (radix hit, identical greedy tokens), a handful of
   concurrent ones (prompts of a few hundred tokens, >= 3 prefill chunks;
   5 decode chunks), one streamed ``POST /v1/chat/completions``, then
   reads ``/.well-known/health``, ``/.well-known/debug/compiles`` and
   ``/stats``.

Any failed check raises: no phase is caught and carried past. The last line
of stdout is one JSON object, ``{"ok": true, "device": {...}}``.

``--rehearse`` runs the same phases on the CPU with a tiny preset and
interpret-mode kernels (every line says so); it proves the script, not the
chip. ``--preset mistral-7b`` serves the rolling-ring layout (neither
kernel); ``--chips 4 --layout replicas|tp`` are builder-run on a four-chip
host and print where params and KV live. Measures nothing: see PERF.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

# Kernel vs reference on unit-variance inputs: |kernel - reference| must stay
# under KERNEL_ATOL + KERNEL_RTOL * |reference|. Both sides read the same
# bf16 (or the same int8) rows and accumulate in f32; the reference runs its
# matmuls at "highest" precision. What separates them is the bf16 rounding of
# the output — one ulp, at most 2^-7 of the value, which is KERNEL_RTOL — and
# under it the MXU's bf16 passes over the f32 probabilities and the scaled
# query inside the kernel and the reference's bf16 rounding of dequantized
# int8 rows, which is KERNEL_ATOL (measured on the v5e: <= 1.6e-2 in all at
# |out| <= 4). An 8-bit float or a dropped scale errs by >= 6e-2 at |out| ~ 1
# and fails.
KERNEL_ATOL = 1e-2
KERNEL_RTOL = 2.0**-7

# preset -> (its CPU rehearsal twin, slots, max_seq_len, prefix-cache MB).
# The windowed preset needs max_seq_len past its window or the ring never
# engages, and a prefix budget of two whole ring rows: the contiguous
# PrefixCache retains a slot's full row (545 MB at these widths) whatever
# the prompt's length, where the radix tree retains 16-token blocks.
PRESETS = {
    "qwen2-7b": ("tiny", 8, 1024, 64),
    "mistral-7b": ("tiny-mistral", 4, 8192, 1200),
}
PROMPT_TOKENS = (200, 230, 260, 290)  # >= 3 prefill chunks of 64 each
NEW_TOKENS = 40  # 5 decode chunks of 8
TAG = "[chip_smoke] "


def say(msg: str) -> None:
    print(TAG + msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


# -- kernel phase ----------------------------------------------------------


def kernel_phase(head_shapes, *, capacity: int, interpret: bool) -> None:
    """Each Pallas kernel, compiled for this device (interpreted only in a
    rehearsal), against its XLA reference on seeded inputs."""
    import jax
    import jax.numpy as jnp

    from gofr_tpu.kvcache.paged import quantize_rows, stored_rows
    from gofr_tpu.ops.attention import (
        flash_attention,
        mha_reference,
        paged_chunk_decode_attention,
    )

    b, d, chunk, steps = 2, 128, 64, 8
    how = "interpreted" if interpret else "compiled"

    def compare(name, got, want):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        diff = jnp.abs(got - want)
        # share of the tolerance used; nan if either side is non-finite
        used = float(jnp.max(diff / (KERNEL_ATOL + KERNEL_RTOL * jnp.abs(want))))
        check(used <= 1.0, f"{name}: |kernel - reference| is {used:.2f} of the tolerance "
                           f"(max |diff| {float(jnp.max(diff)):.2e})")
        say(f"kernel {name} ({how}): max |diff| {float(jnp.max(diff)):.2e}, "
            f"{used:.2f} of the tolerance")

    for hq, hkv in head_shapes:
        tag = f"hq={hq} hkv={hkv} d={d}"
        keys = iter(jax.random.split(jax.random.PRNGKey(hq * 100 + hkv), 24))

        def rand(shape):
            return jax.random.normal(next(keys), shape, jnp.bfloat16)

        # flash, monolithic prefill: sq == sk, causal
        q, k, v = rand((b, 256, hq, d)), rand((b, 256, hkv, d)), rand((b, 256, hkv, d))
        got = jax.jit(lambda q, k, v: flash_attention(q, k, v, interpret=interpret))(q, k, v)
        with jax.default_matmul_precision("highest"):
            want = mha_reference(q, k, v, causal=True)
        compare(f"flash {tag}", got, want)

        # flash with q_offsets, chunked prefill: one chunk of queries at a
        # per-sequence cursor against a whole slot cache (rows above the
        # chunk hold garbage the causal mask must hide)
        q = rand((b, chunk, hq, d))
        k, v = rand((b, capacity, hkv, d)), rand((b, capacity, hkv, d))
        cursors = jnp.asarray([3 * chunk, capacity - chunk], jnp.int32)
        got = jax.jit(
            lambda q, k, v, o: flash_attention(
                q, k, v, q_offsets=o, block_q=chunk, interpret=interpret
            )
        )(q, k, v, cursors)
        with jax.default_matmul_precision("highest"):
            want = mha_reference(
                q, k, v, causal=True,
                q_positions=cursors[:, None] + jnp.arange(chunk)[None, :],
            )
        compare(f"flash+q_offsets {tag} chunk={chunk} capacity={capacity}", got, want)

        # paged decode through a scrambled block table, bf16 then int8 pool,
        # at the three block sizes the kernel sizes its tile for; the pool a
        # stack of two layers as the engine stores it, read at the second
        q1 = rand((b, 1, hq, d))
        kb, vb = rand((b, steps, hkv, d)), rand((b, steps, hkv, d))
        lengths = jnp.asarray([37, capacity - steps], jnp.int32)
        step = jnp.asarray(3, jnp.int32)
        for block in (16, 64, 128):
            n_tbl = capacity // block
            n_blocks = b * n_tbl + 3
            pk, pv = rand((2, n_blocks, block, hkv, d)), rand((2, n_blocks, block, hkv, d))
            tables = (
                jax.random.permutation(next(keys), n_blocks)[: b * n_tbl]
                .reshape(b, n_tbl).astype(jnp.int32)
            )
            (qk, sk), (qv, sv) = quantize_rows(pk), quantize_rows(pv)
            for pool, (k_pool, v_pool, k_sc, v_sc) in {
                "bf16": (pk, pv, None, None), "int8": (qk, qv, sk, sv),
            }.items():
                k_pool, v_pool = stored_rows(k_pool), stored_rows(v_pool)

                def attend(use_kernel):
                    return jax.jit(
                        lambda q, kp, vp, ks, vs: paged_chunk_decode_attention(
                            q, kp, vp, tables, kb, vb, lengths, step,
                            layer=1, k_scales=ks, v_scales=vs,
                            use_kernel=use_kernel, interpret=interpret,
                        )
                    )(q1, k_pool, v_pool, k_sc, v_sc)

                got = attend(True)
                with jax.default_matmul_precision("highest"):
                    want = attend(False)  # paged_gather + chunk_decode_attention
                compare(f"paged-decode {pool} pool {tag} block={block}", got, want)


# -- server phase ----------------------------------------------------------


def http_json(method: str, url: str, body: dict | None = None):
    req = urllib.request.Request(
        url, data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method=method,
    )
    with urllib.request.urlopen(req, timeout=600) as resp:
        return resp.status, json.loads(resp.read())


def generate_concurrently(base: str, prompts: list[list[int]]) -> list[list[int]]:
    """One POST /generate per prompt, all in flight together."""
    out: list = [None] * len(prompts)
    errors: list = []

    def one(i: int) -> None:
        try:
            status, body = http_json("POST", f"{base}/generate", {
                "tokens": prompts[i], "max_new_tokens": NEW_TOKENS, "temperature": 0.0,
            })
            # the framework answers a POST route with 201 (the reference's rule)
            check(status == 201, f"/generate #{i}: HTTP {status}")
            out[i] = body["data"]["tokens"]
        except Exception as e:  # noqa: BLE001 — re-raised on the main thread below
            errors.append(e)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
        check(not t.is_alive(), "/generate did not answer in 900 s")
    if errors:
        raise errors[0]
    return out


def stream_chat(base: str) -> int:
    """POST /v1/chat/completions stream=true; returns the chunk count."""
    req = urllib.request.Request(
        f"{base}/v1/chat/completions",
        data=json.dumps({
            "messages": [{"role": "user", "content": "hello, chip"}],
            "max_tokens": 24, "stream": True,
        }).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=600) as resp:
        check(resp.status == 200, f"chat stream: HTTP {resp.status}")
        ctype = resp.headers.get("Content-Type", "")
        check(ctype.startswith("text/event-stream"), f"chat stream: Content-Type {ctype!r}")
        raw = resp.read().decode()
    events = [ln[len("data: "):] for ln in raw.split("\n") if ln.startswith("data: ")]
    check(bool(events) and events[-1] == "[DONE]", f"chat stream: no [DONE]: {events[-2:]}")
    chunks = [json.loads(e) for e in events[:-1]]
    finish = chunks[-1]["choices"][0]["finish_reason"]
    check(finish in ("stop", "length"), f"chat stream: finish_reason {finish!r}")
    return len(chunks)


def placement(engines) -> tuple[list[str], dict[int, int]]:
    """Which devices hold each engine's params and KV (printed for the
    four-chip layouts) and the weight bytes each device holds."""
    import jax

    lines, weight_bytes = [], {}
    for i, e in enumerate(engines):
        leaves = jax.tree.leaves(e.params)
        for x in leaves:
            for s in x.addressable_shards:
                weight_bytes[s.device.id] = weight_bytes.get(s.device.id, 0) + s.data.nbytes
        kv = e.cache.k
        lines.append(
            f"engine {i}: params on devices {sorted({d.id for x in leaves for d in x.devices()})}, "
            f"KV on devices {sorted(d.id for d in kv.devices())} "
            f"(pool {tuple(kv.shape)}, per-device shard {tuple(kv.sharding.shard_shape(kv.shape))})"
        )
    return lines, weight_bytes


def server_phase(args, preset: str, on_chip: bool) -> None:
    import jax
    import numpy as np

    _twin, slots, max_seq, prefix_mb = PRESETS[args.preset]
    os.environ.update({
        "GEMMA_PRESET": preset, "GEMMA_INT8": "1",
        "LLM_SLOTS": str(slots), "LLM_MAX_SEQ": str(max_seq),
        "TPU_LLM_PREFIX_CACHE_MB": str(prefix_mb),  # the exact repeat must hit
        "REQUEST_TIMEOUT": "600",
        "HTTP_PORT": "0", "METRICS_PORT": "0", "GRPC_PORT": "0",
        "LOG_LEVEL": "ERROR", "TRACE_EXPORTER": "none",
        "TPU_TELEMETRY_INTERVAL_S": "0",
    })
    os.environ.pop("LLM_TP", None)
    if args.chips > 1 and args.layout == "replicas":
        os.environ["LLM_TP"] = "1"  # one single-chip replica per device

    from main import build_app, build_engine  # examples/grpc-gemma

    from gofr_tpu.profiling import default_registry

    t0 = time.perf_counter()
    app = build_app()
    thread = app.run_in_background()
    setup_s = time.perf_counter() - t0
    try:
        cfg = build_engine.cfg
        base = f"http://127.0.0.1:{app.http_server.port}"
        engine = app.container.tpu().llm("gemma").engine
        engines = getattr(engine, "engines", None) or [engine]
        snap = default_registry().snapshot()
        warm = snap["totals"]
        warm_programs = {(e["program"], e["model"], *e["arg_shapes"]) for e in snap["programs"]}
        say(
            f"server up: preset {preset}, depth {cfg.n_layers} layers (full), "
            f"d_model {cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads} x {cfg.head_dim}, "
            f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, window {cfg.sliding_window}, "
            f"int8 weights from seed 0, {slots} slots x {max_seq} tokens, "
            f"{len(engines)} engine(s)"
        )
        say(f"set-up {setup_s:.1f} s (weights + engine build + warm-up), "
            f"{warm['compiles']} compiles, {warm['compile_s_total']} s compiling")
        lines, weight_bytes = placement(engines)
        for line in lines if args.chips > 1 else ():
            say(line)

        # One prompt alone, cold and then again (the radix hit): both runs
        # decode alone through the same programs from the same KV values,
        # so greedy tokens must be identical. The concurrent batch after it
        # is checked for shape only — which steps its decodes share with
        # which prefill chunks is a matter of timing, and two XLA programs
        # need not round alike.
        rng = np.random.default_rng(0)
        lengths = list(PROMPT_TOKENS)
        if cfg.sliding_window:  # one prompt past the window: the ring rolls
            lengths.append(cfg.sliding_window + 200)
        prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in lengths]
        solo = generate_concurrently(base, prompts[:1])[0]
        repeat = generate_concurrently(base, prompts[:1])[0]
        outs = [solo, repeat] + generate_concurrently(base, prompts[1:])
        for i, toks in enumerate(outs):
            check(len(toks) == NEW_TOKENS, f"/generate #{i}: {len(toks)} tokens, asked {NEW_TOKENS}")
            check(all(0 <= t < cfg.vocab_size for t in toks), f"/generate #{i}: token out of vocabulary")
        check(repeat == solo, f"greedy repeat differs: {repeat[:8]} vs {solo[:8]}")
        say(f"/generate alone, then its exact repeat: 201, identical {NEW_TOKENS} tokens")
        say("greedy tokens: " + ",".join(map(str, solo)))
        # each replica on its own, called directly: the router sends
        # simultaneous copies wherever load is least, two may share a replica
        for i, e in enumerate(engines if len(engines) > 1 else ()):
            toks = e.generate(prompts[0], max_new_tokens=NEW_TOKENS)
            check(toks == solo, f"replica {i}'s greedy tokens differ: {toks[:8]} vs {solo[:8]}")
            say(f"replica {i}, asked directly: the same {NEW_TOKENS} tokens")
        if args.expect_tokens:
            want = [int(t) for t in args.expect_tokens.split(",")]
            same = next((i for i, (a, b) in enumerate(zip(solo, want)) if a != b), len(want))
            say(f"against the one-chip run: the first {same} of {len(want)} greedy tokens agree")
            # a TP engine sums sharded partial products in another order than
            # one chip, and random weights leave near-tie logits: reported,
            # not required, there
            check(same == len(want) or engines[0].tp_degree > 1,
                  f"greedy tokens differ from the one-chip run at index {same}")
        say(f"{len(prompts) - 1} concurrent /generate: 201, {NEW_TOKENS} tokens each "
            f"(prompts of {', '.join(map(str, lengths[1:]))} tokens)")
        say(f"streamed /v1/chat/completions: {stream_chat(base)} chunks, [DONE]")

        _, health = http_json("GET", f"{base}/.well-known/health")
        _, compiles = http_json("GET", f"{base}/.well-known/debug/compiles")
        _, stats = http_json("GET", f"{base}/stats")
        stats, compiles = stats["data"], compiles["data"]
        tpu = health["data"]["tpu"]["details"]
        check(tpu["platform"] == jax.devices()[0].platform, f"health platform {tpu['platform']!r}")

        rows = stats.get("per_replica") or [stats]
        check(stats.get("replicas_alive", 1) == stats.get("replicas", 1), "a replica died")
        check(not stats.get("restarts") and not stats.get("failovers"),
              f"restarts {stats.get('restarts')} failovers {stats.get('failovers')}")
        for r in rows:
            check(r["numerical_trips"] == 0, f"numerical_trips {r['numerical_trips']}")
            check(r["errored"] == 0, f"errored {r['errored']}")
        hits = sum((r["kvcache"].get("prefix") or {}).get("hits", 0) for r in rows)
        check(hits >= 1, f"the exact repeat hit no prefix index: {rows[0]['kvcache'].get('prefix')}")

        paths = rows[0]["attention"]
        check(all(r["attention"] == paths for r in rows), "replicas traced different attention paths")
        if cfg.sliding_window:  # the rolling ring: XLA attention by design
            want_decode, want_prefill = "xla_ring", "xla (rolling ring cache)"
        elif on_chip:
            want_decode, want_prefill = "pallas_paged", "pallas_flash"
        else:  # rehearsal: the CPU backend takes no kernel, and says why
            want_decode, want_prefill = "xla_gather (backend cpu", "xla (backend cpu"
        check(paths["decode"].startswith(want_decode), f"decode traced {paths['decode']!r}")
        for shape, path in paths["prefill"].items():
            check(path.startswith(want_prefill), f"prefill chunk {shape} traced {path!r}")
        tile = paths.get("decode_tile")  # what the paged kernel holds per step
        say(f"attention traced: decode {paths['decode']}{f' {tile}' if tile else ''}; "
            f"prefill {paths['prefill']}")

        check(compiles["degraded"] == [], f"InstrumentedJit left AOT dispatch: {compiles['degraded']}")
        after = compiles["totals"]["compiles"]
        late = sorted(
            e["program"] for e in compiles["programs"]
            if (e["program"], e["model"], *e["arg_shapes"]) not in warm_programs
        )
        check(after == warm["compiles"], f"{after - warm['compiles']} compile(s) after warm-up: {late}")
        say(f"zero compiles after warm-up ({after} in all), no program left AOT dispatch")

        if on_chip:
            in_use = tpu["memory"]["bytes_in_use"]
            check(in_use >= weight_bytes[jax.devices()[0].id],
                  f"health bytes_in_use {in_use} < weight bytes {weight_bytes}")
            for d in jax.devices():
                say(f"device {d.id}: weights {weight_bytes.get(d.id, 0) / 1e9:.2f} GB, "
                    f"bytes_in_use {d.memory_stats()['bytes_in_use'] / 1e9:.2f} GB")
        else:
            say("health memory: the CPU backend reports none, not checked")
    finally:
        app.shutdown()
        thread.join(timeout=60)
    check(not thread.is_alive(), "the app did not shut down")


def main(argv: list[str] | None = None) -> int:
    global TAG
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny preset, interpret-mode kernels: proves the script, not the chip")
    ap.add_argument("--preset", default="qwen2-7b", choices=sorted(PRESETS))
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--layout", default="replicas", choices=("replicas", "tp"),
                    help="with --chips 4: four one-chip replicas, or one TP=4 engine")
    ap.add_argument("--expect-tokens", default="",
                    help="the greedy tokens a one-chip run printed, to compare with")
    args = ap.parse_args(argv)
    if args.rehearse:
        TAG = "[chip_smoke REHEARSAL cpu, tiny preset, interpret-mode kernels] "
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "--rehearse runs the phases on the CPU", file=sys.stderr)
        return 2
    check(args.rehearse or device["count"] == args.chips,
          f"--chips {args.chips} but JAX reports {device['count']} device(s)")

    sys.path[:0] = [REPO, os.path.join(REPO, "examples", "grpc-gemma")]
    from gofr_tpu.models import TransformerConfig
    from gofr_tpu.profiling import mfu
    from gofr_tpu.utils import enable_compilation_cache

    # a TPU kind that is not in the peaks table raises here
    peak = mfu.device_peak_flops(dev.platform, dev.device_kind)
    say(f"device: platform {dev.platform}, kind {dev.device_kind!r}, count {device['count']}, "
        f"table peak {peak / 1e12:.0f} TFLOP/s" + ("" if on_chip else " (CPU placeholder)"))
    cache_dir = enable_compilation_cache()

    def entries() -> int:  # jax creates the directory on its first write
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    before = entries()
    say(f"compile cache {cache_dir}: {before} entries before")
    cache_events = {"/jax/compilation_cache/cache_hits": 0, "/jax/compilation_cache/cache_misses": 0}

    def count_cache_event(event: str, **_kw) -> None:
        if event in cache_events:
            cache_events[event] += 1

    jax.monitoring.register_event_listener(count_cache_event)

    t0 = time.perf_counter()
    if args.rehearse:
        kernel_phase([(4, 2)], capacity=256, interpret=True)
        preset = PRESETS[args.preset][0]
    else:
        cfg = {"qwen2-7b": TransformerConfig.qwen2_7b, "mistral-7b": TransformerConfig.mistral_7b}[args.preset]()
        # the smoke model's heads, and the 8-kv-head shape of the other 7B families
        shapes = dict.fromkeys([(cfg.n_heads, cfg.n_kv_heads), (32, 8)])
        kernel_phase(list(shapes), capacity=1024, interpret=False)
        preset = args.preset
    say(f"kernel phase {time.perf_counter() - t0:.1f} s")
    server_phase(args, preset, on_chip)
    hits, misses = cache_events.values()
    say(f"compile cache {cache_dir}: {before} entries before, {entries()} after; "
        f"{hits} hits, {misses} misses")
    if args.rehearse:
        say("rehearsal passed: the script runs; nothing here is a chip result")
    print(json.dumps({"ok": True, "device": device, **({"rehearsal": True} if args.rehearse else {})}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
