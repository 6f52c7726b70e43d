"""Benchmark harness. Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Default (--model serving, on TPU): end-to-end Gemma-2B decode serving
through the LLMEngine (slot continuous batching + fused decode chunks) —
the BASELINE.json metric ("QPS/chip + p50/p99 latency serving Gemma-2B").
vs_baseline normalizes against the north-star floor of >=1,000 QPS/chip
(BASELINE.md): vs_baseline = measured QPS-equivalent / 1000, where a
"query" is a 16-token completion. detail reports prefill %-of-bf16-nominal
(int8 path: a utilization index, not MFU) and decode HBM-bandwidth
utilization so perf regressions are visible.

--model mlp: end-to-end serving QPS of the MNIST MLP through the TPU
datasource's dynamic batcher (BASELINE.json config 2 minus the socket);
vs_baseline = QPS / 1000 (same north-star floor).

--model greet: BASELINE config 1 — boots the stock New() app and hammers
GET /greet over real sockets; reports QPS (no reference number exists:
the Go toolchain is absent, so parity is recorded as absolute QPS).

Run on the real chip: python bench.py          (driver does this)
CPU smoke:            JAX_PLATFORMS=cpu python bench.py --model mlp --requests 200

NOTE on timing: every measurement below syncs via a real device->host fetch.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import threading
import time

import numpy as np

# Single source for device peaks: the profiling.mfu table the engine's
# MFU gauges use — bench's raw-probe math must never desynchronize from
# stats()["mfu"] for the same run. (mfu.py is jax-free, so this import
# cannot disturb the pre-jax greet-subprocess ordering below.)
from gofr_tpu.profiling import mfu as _mfu  # noqa: E402

V5E_PEAK_BF16 = _mfu.device_peak_flops("tpu", "tpu v5 lite")  # FLOP/s
V5E_HBM_BW = _mfu.device_hbm_bandwidth("tpu", "tpu v5 lite")  # B/s

# config-1 subrun workload — shared by the pre-jax subprocess argv and the
# in-process fallback so both paths always measure the same storm
GREET_SUB_REQUESTS = 1000
GREET_SUB_CLIENTS = 64


def _greet_subprocess() -> dict | None:
    """Run the greet bench (pure CPU) in a fresh subprocess. Must be called
    BEFORE jax initializes in this process: on the 1-core host the jax
    runtime's threads + multi-GB heap depress a later CPU-plane storm by
    2x+ (r4: 4.2k isolated vs 1.9k contaminated)."""
    import subprocess
    import sys

    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--model", "greet",
             "--requests", str(GREET_SUB_REQUESTS),
             "--clients", str(GREET_SUB_CLIENTS)],
            capture_output=True, text=True, timeout=300,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        # the subprocess prints the full result JSON and then the compact
        # summary line LAST — walk backwards to the full object
        for line in reversed(proc.stdout.strip().splitlines()):
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(obj, dict) and "detail" in obj:
                return obj
        return None
    except subprocess.TimeoutExpired:
        return None


def _percentile(xs: list[float], p: float) -> float:
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(p * len(xs)))]


def _spread(xs: list[float], nd: int = 3) -> dict:
    """{median, min, max} of a few repeated measurements — the
    variance-robust evidence format for adjudicated numbers (VERDICT r5
    weak #1: single-shot probes conflated chip-window luck with code)."""
    xs = sorted(xs)
    return {
        "median": round(xs[len(xs) // 2], nd),
        "min": round(xs[0], nd),
        "max": round(xs[-1], nd),
    }


def _raw_probes(eng, cfg, args, S: int, B: int) -> dict:
    """Device-true decode/prefill cost via the DELTA method: every
    synchronous measurement carries a fixed dispatch+fetch round trip, so
    absolute small-N timings measure the round trip, not the chip.
    marginal = (T(n2) - T(n1)) / (n2 - n1) cancels it."""
    import jax
    import jax.numpy as jnp

    K = args.decode_chunk
    rng = jax.random.PRNGKey(7)
    cache = eng.cache._replace(length=jnp.full((B,), S, jnp.int32))
    toks, last, cache, rng = eng._chunk_ops[K](
        eng.params, jnp.zeros((B,), jnp.int32), cache, eng._active, eng._temps, rng
    )
    _ = np.asarray(last)  # compile + sync
    # 3 trials per run length, delta of the MIN-ENVELOPES: each min
    # approximates a stall-free run, so a transient slowdown in either
    # window is discarded instead of biasing the delta (min over paired
    # deltas would preferentially select trials whose SHORT window caught
    # a stall, inflating the ceiling; observed engine_vs_ceiling 1.17 the
    # other way from a single-shot probe)
    times = {}
    for n in (2, 8):
        ts = []
        for _t in range(3):
            t0 = time.perf_counter()
            for _i in range(n):
                toks, last, cache, rng = eng._chunk_ops[K](
                    eng.params, last, cache, eng._active, eng._temps, rng
                )
            _ = np.asarray(last)
            ts.append(time.perf_counter() - t0)
        times[n] = ts
    # a stall can still make an envelope delta non-positive; clamp to a
    # floor of 10% of the per-chunk short-window cost so downstream
    # ratios stay finite and visibly wrong rather than negative
    floor = min(times[2]) / 2 / K * 0.1
    # PEAK capability: min-envelope delta (stall windows discarded) —
    # matches the chip's fast windows and is stable across sessions.
    raw_step_s = max((min(times[8]) - min(times[2])) / 6 / K, floor)
    # SUSTAINED estimate: mean-envelope delta over the spaced trials —
    # includes the throttled/stalled windows a long-running engine
    # actually lives through, so it is the fair ceiling denominator.
    raw_step_sust_s = max(
        (sum(times[8]) - sum(times[2])) / 3 / 6 / K, raw_step_s)
    # per-trial PAIRED deltas: the median is the variance-robust single
    # number, the spread shows how much the chip's windows wandered
    step_trials = [
        max((times[8][t] - times[2][t]) / 6 / K, floor) for t in range(3)
    ]
    raw_step_med_s = sorted(step_trials)[1]
    raw_tok_s = B / raw_step_s
    params_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(eng.params))
    # decode streams all weights + the live KV prefix + chunk buffers
    kv_bytes = cfg.n_layers * B * S * cfg.n_kv_heads * cfg.head_dim * 2 * 2
    bw_util = (params_bytes + kv_bytes) / raw_step_s / V5E_HBM_BW
    eng.cache = cache._replace(length=jnp.zeros((B,), jnp.int32))

    # prefill marginal at the admission-wave batch
    nb = eng.admit_cap
    pack = jnp.zeros((nb, S + 2), jnp.int32).at[:, -2].set(S)
    first, pc, _lg, _ = eng._prefill_op(eng.params, pack, rng)
    _ = np.asarray(first)
    ptimes = {}
    for n in (1, 5):
        ts = []
        for _t in range(3):
            t0 = time.perf_counter()
            for _i in range(n):
                first, pc, _lg, _ = eng._prefill_op(eng.params, pack, rng)
            _ = np.asarray(first)
            ts.append(time.perf_counter() - t0)
        ptimes[n] = ts
    pfloor = min(ptimes[1]) * 0.1
    prefill_s = max((min(ptimes[5]) - min(ptimes[1])) / 4, pfloor)
    prefill_sust_s = max(
        (sum(ptimes[5]) - sum(ptimes[1])) / 3 / 4, prefill_s)
    prefill_trials = [
        max((ptimes[5][t] - ptimes[1][t]) / 4, pfloor) for t in range(3)
    ]
    prefill_med_s = sorted(prefill_trials)[1]
    # FLOP count from the architecture (weights may be int8 QTensors)
    embed_params = cfg.vocab_size * cfg.d_model
    layer_params = (
        cfg.d_model * (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim
        + cfg.n_heads * cfg.head_dim * cfg.d_model
        + 3 * cfg.d_model * cfg.d_ff
    ) * cfg.n_layers
    prefill_flops = 2 * nb * S * layer_params + 2 * nb * embed_params
    mfu = prefill_flops / prefill_s / V5E_PEAK_BF16
    return {
        "decode_step_ms": round(raw_step_s * 1e3, 3),
        "decode_step_sustained_ms": round(raw_step_sust_s * 1e3, 3),
        "decode_step_median_ms": round(raw_step_med_s * 1e3, 3),
        "decode_step_ms_spread": _spread([t * 1e3 for t in step_trials]),
        "raw_decode_tok_s": round(raw_tok_s, 0),
        "decode_hbm_bw_pct": round(bw_util * 100, 1),
        f"prefill_ms_b{nb}": round(prefill_s * 1e3, 1),
        f"prefill_sustained_ms_b{nb}": round(prefill_sust_s * 1e3, 1),
        f"prefill_median_ms_b{nb}": round(prefill_med_s * 1e3, 1),
        "prefill_ms_spread": _spread([t * 1e3 for t in prefill_trials], 1),
        # % of the 197 TF/s bf16 NOMINAL figure; the prefill path runs
        # int8 (W8A8) where the MXU's nominal is 2x, so >100 is expected —
        # this is a utilization index, not an MFU claim (VERDICT r3 weak #6)
        "prefill_pct_of_bf16_nominal": round(mfu * 100, 1),
    }


def _mfu_block(eng) -> dict:
    """Compact utilization block from the engine's rolling MFU windows
    (gofr_tpu.profiling.mfu): analytic model FLOPs over measured phase
    wall time against the device peak, plus the roofline verdict."""
    m = eng.stats()["mfu"]
    return {
        "decode_p50": round(m["decode"]["p50"], 4),
        "prefill_p50": round(m["prefill"]["p50"], 4),
        "tokens_per_s_per_chip_p50": round(
            m["tokens_per_second_per_chip"]["p50"], 1
        ),
        "bound": m["roofline"]["bound"],
        "roofline_decode_p50": round(m["roofline"]["decode"]["p50"], 3),
        "peak_flops_per_chip": m["peak_flops_per_chip"],
    }


def _warmup_block(eng, engine_init_s: float) -> dict:
    """Cold-start bill (BENCH_r07+): engine _warm wall time plus the
    compile registry's per-program totals — wall < sum because warmup
    overlaps compiles on a pool."""
    from gofr_tpu.profiling import default_registry

    totals = default_registry().snapshot(model=eng.label)["totals"]
    return {
        "warmup_s": round(eng.warmup_s, 2) if eng.warmup_s else None,
        "engine_init_s": round(engine_init_s, 1),
        "programs": totals["programs"],
        "compile_s_total": totals["compile_s_total"],
    }


_PHASE_HISTS = {
    # summary key -> app_llm_* histogram feeding it (bench.py satellite:
    # BENCH_r06+ SLO points carry their own phase attribution)
    "queue_wait_ms": "app_llm_queue_wait_seconds",
    "ttft_ms": "app_llm_ttft_seconds",
    "per_token_ms": "app_llm_time_per_output_token_seconds",
    "decode_step_ms": "app_llm_decode_step_seconds",
}


def _phase_hist_counts(metrics) -> dict:
    """Snapshot of per-bucket counts for every phase histogram, merged
    across label sets (the bench engine emits one model label anyway)."""
    out = {}
    for key, name in _PHASE_HISTS.items():
        h = metrics.histogram(name)
        merged = None
        for _lbl, (counts, _s, _n) in h.collect_histogram():
            merged = counts if merged is None else [
                a + b for a, b in zip(merged, counts)
            ]
        out[key] = (tuple(h.buckets), merged or [0] * (len(h.buckets) + 1))
    return out


def _phase_breakdown(before: dict, after: dict) -> dict:
    """p50/p99 (ms) per phase from the histogram-count DELTAS between two
    snapshots — attributes exactly the requests of the window in between
    (the cumulative histograms also contain the warmup/probe traffic)."""

    def pct(buckets, deltas, q):
        total = sum(deltas)
        if total == 0:
            return 0.0
        target, acc = q * total, 0
        for i, c in enumerate(deltas):
            acc += c
            if acc >= target:
                return buckets[min(i, len(buckets) - 1)] * 1e3
        return buckets[-1] * 1e3

    out = {}
    for key in _PHASE_HISTS:
        buckets, b0 = before[key]
        _, b1 = after[key]
        deltas = [max(0, a - b) for a, b in zip(b1, b0)]
        out[key] = {
            "p50": round(pct(buckets, deltas, 0.50), 2),
            "p99": round(pct(buckets, deltas, 0.99), 2),
            "n": sum(deltas),
        }
    return out


def _closed_loop(eng, cfg, prompt_len, new_tokens: int, requests: int,
                 clients: int, seed: int = 0, shared_frac: float = 0.0) -> dict:
    """Closed-loop saturation: `clients` threads, each submit->drain.
    prompt_len: int for fixed-length prompts, or (lo, hi) for uniform
    mixed lengths (exercises the bucketed admission path under load).
    shared_frac > 0: that fraction of requests reuse ONE fixed prompt —
    the shared-prefix workload the prefix cache serves without prefill."""
    from gofr_tpu.llm import GenRequest

    rng_np = np.random.default_rng(seed)
    if isinstance(prompt_len, tuple):
        lo, hi = prompt_len
        draw_len = lambda: int(rng_np.integers(lo, hi + 1))  # noqa: E731
    else:
        draw_len = lambda: prompt_len  # noqa: E731
    shared = (
        rng_np.integers(1, cfg.vocab_size, size=draw_len()).tolist()
        if shared_frac > 0
        else None
    )

    def draw_prompt():
        if shared is not None and rng_np.random() < shared_frac:
            return shared
        return rng_np.integers(1, cfg.vocab_size, size=draw_len()).tolist()
    lat: list[float] = []
    ttft: list[float] = []
    errors: list[BaseException] = []
    lock = threading.Lock()

    def client(prompts: list[list[int]]):
        try:
            for prompt in prompts:
                t0 = time.perf_counter()
                req = eng.submit(GenRequest(prompt, max_new_tokens=new_tokens))
                toks: list[int] = []
                first_t = None
                for t in req.stream(timeout=600):
                    if first_t is None:
                        first_t = time.perf_counter() - t0
                    toks.append(t)
                dt = time.perf_counter() - t0
                assert len(toks) == new_tokens, f"short completion {len(toks)}"
                with lock:
                    lat.append(dt)
                    ttft.append(first_t)
        except BaseException as e:  # noqa: BLE001 — surface after join
            with lock:
                errors.append(e)

    st0 = eng.stats()  # snapshot: report THIS run's telemetry, not lifetime
    nthreads = min(clients, requests)
    per = max(1, requests // nthreads)
    done = per * nthreads
    work = [
        [draw_prompt() for _ in range(per)]
        for _ in range(nthreads)
    ]
    ts = [threading.Thread(target=client, args=(w,)) for w in work]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise RuntimeError(f"{len(errors)} bench clients failed: {errors[0]!r}")
    st1 = eng.stats()
    chunks = st1["chunks"] - st0["chunks"]
    active_sum = st1["active_sum"] - st0["active_sum"]
    waves = {
        nb: st1["prefill_waves"].get(nb, 0) - st0["prefill_waves"].get(nb, 0)
        for nb in st1["prefill_waves"]
    }
    return {
        "qps": round(done / wall, 1),
        "p50_ms": round(_percentile(lat, 0.50) * 1e3, 1),
        "p99_ms": round(_percentile(lat, 0.99) * 1e3, 1),
        "ttft_p50_ms": round(_percentile(ttft, 0.50) * 1e3, 1),
        "requests": done,
        "clients": nthreads,
        "avg_active_at_dispatch": round(active_sum / chunks, 2) if chunks else 0.0,
        "prefill_waves": {k: v for k, v in sorted(waves.items()) if v},
        "chunks": chunks,
    }


def _open_loop(eng, cfg, prompt_len, new_tokens: int, rate: float,
               duration_s: float, seed: int = 1) -> dict:
    """Open-loop Poisson arrivals at `rate` req/s: latency measured from
    the SCHEDULED arrival time, so queueing delay under overload is
    visible instead of being absorbed by client backpressure (the r2
    bench's closed-loop p50 was a queueing artifact — VERDICT weak #5).
    prompt_len: int for fixed lengths, or a (choices...) tuple drawn
    uniformly per request (the interactive-SLO mixed workload)."""
    from concurrent.futures import ThreadPoolExecutor

    from gofr_tpu.llm import EngineOverloaded, GenRequest

    rng_np = np.random.default_rng(seed)
    rejected = 0
    n = max(1, int(rate * duration_s))
    gaps = rng_np.exponential(1.0 / rate, size=n)
    arrivals = np.cumsum(gaps)
    if isinstance(prompt_len, tuple):
        lens = rng_np.choice(list(prompt_len), size=n)
    else:
        lens = [prompt_len] * n
    prompts = [rng_np.integers(1, cfg.vocab_size, size=int(pl)).tolist() for pl in lens]
    lat: list[float] = []
    ttft: list[float] = []
    lock = threading.Lock()
    pool = ThreadPoolExecutor(max_workers=min(1024, n))

    done_at: list[float] = []

    def consume(req, t_arrival):
        first_t = None
        count = 0
        for _t in req.stream(timeout=600):
            if first_t is None:
                first_t = time.perf_counter() - t_arrival
            count += 1
        now = time.perf_counter()
        dt = now - t_arrival
        with lock:
            lat.append(dt)
            ttft.append(first_t if first_t is not None else dt)
            done_at.append(now - t0)

    t0 = time.perf_counter()
    futs = []
    for i in range(n):
        # hybrid sleep+spin pacing: bare time.sleep overshoots by 1-5 ms
        # under GIL contention with the consumer pool, silently lowering
        # the offered rate ~10-20% at 200 QPS
        while True:
            wait = arrivals[i] - (time.perf_counter() - t0)
            if wait <= 0:
                break
            if wait > 0.002:
                time.sleep(wait - 0.002)
        t_arrival = t0 + arrivals[i]
        try:
            req = eng.submit(GenRequest(prompts[i], max_new_tokens=new_tokens))
        except EngineOverloaded:
            rejected += 1  # shed load: excluded from latency percentiles
            continue
        futs.append(pool.submit(consume, req, t_arrival))
    submit_end = time.perf_counter() - t0
    for f in futs:
        f.result(timeout=600)
    wall = time.perf_counter() - t0
    pool.shutdown(wait=False)
    # steady-state rate: completions over the window INTERIOR (after the
    # pipeline fills, before the arrival tail). n/wall undercounts
    # structurally — wall includes the tail drain, so 2000 reqs in a 10 s
    # window with 0.6 s of residency can never read above 2000/10.6 = 189
    # even with zero queue growth; r3's "200-QPS shed" was mostly this
    # artifact, not lost throughput.
    w0 = 0.2 * submit_end
    interior = sum(1 for t in done_at if w0 < t <= submit_end)
    out = {
        "offered_qps": rate,
        "achieved_qps": round((n - rejected) / wall, 1),
        "steady_qps": round(interior / (submit_end - w0), 1),
        "drain_ms": round((wall - submit_end) * 1e3, 1),
        "p50_ms": round(_percentile(lat, 0.50) * 1e3, 1),
        "p99_ms": round(_percentile(lat, 0.99) * 1e3, 1),
        "ttft_p50_ms": round(_percentile(ttft, 0.50) * 1e3, 1),
        "ttft_p99_ms": round(_percentile(ttft, 0.99) * 1e3, 1),
    }
    if rejected:
        out["rejected"] = rejected
    return out


def bench_serving(args) -> dict:
    # main() ran the greet subprocess before importing jax; a direct
    # bench_serving(args) caller without the attribute still gets one
    # (jax may already be live then — main()'s ordering is the clean path)
    greet_sub = getattr(args, "_greet_sub", None)
    if greet_sub is None and not args.no_subruns:
        greet_sub = _greet_subprocess()

    import jax

    from gofr_tpu.llm import LLMEngine
    from gofr_tpu.models import TransformerConfig, init_params

    on_tpu = jax.default_backend() == "tpu"
    seven_b = on_tpu and args.model_size == "7b"
    t0 = time.time()
    if seven_b:
        # Gemma-7B does NOT fit a v5e chip in bf16 (16.4 GB > 16 GB HBM);
        # int8 (8.2 GB) does — init directly quantized on device.
        from gofr_tpu.models.quant import init_params_quantized

        cfg = TransformerConfig.gemma_7b()
        params = jax.jit(lambda k: init_params_quantized(k, cfg))(jax.random.PRNGKey(0))
        # 7B-sized engine defaults unless the user overrode them
        if args.batch == 128:
            args.batch = 32
        if args.admit_cap == 16:
            args.admit_cap = 8
        args.no_short = True
    else:
        cfg = TransformerConfig.gemma_2b() if on_tpu else TransformerConfig.tiny()
        params = jax.jit(lambda k: init_params(k, cfg))(jax.random.PRNGKey(0))
    _ = float(np.asarray(params["final_norm"])[0])  # sync
    init_s = time.time() - t0

    S = args.prefill_len
    quantize = args.quantize and on_tpu
    t0 = time.time()
    # metrics manager on the headline engine only: the SLO point's
    # phase_breakdown is pulled from the app_llm_* histograms; the other
    # operating-point engines stay uninstrumented so the short-prompt
    # overhead-sensitive run measures the bare engine
    from gofr_tpu.metrics import new_metrics_manager

    metrics = new_metrics_manager()
    eng = LLMEngine(
        cfg, params, slots=args.batch,
        # prompts are S-8 long; leave new_tokens + 2 chunks of cap margin
        max_seq_len=S + args.new_tokens + 2 * args.decode_chunk,
        prefill_buckets=(S,), decode_chunk=args.decode_chunk,
        admit_cap=args.admit_cap, quantize=quantize, metrics=metrics,
    )
    engine_init_s = time.time() - t0
    n_params = sum(x.size for x in jax.tree.leaves(params))
    warmup = _warmup_block(eng, engine_init_s)
    raw = _raw_probes(eng, cfg, args, S, args.batch)

    # warm every serving path, then the headline closed-loop run
    _closed_loop(eng, cfg, S - 8, args.new_tokens, 2 * args.batch, args.clients)
    head = _closed_loop(eng, cfg, S - 8, args.new_tokens, args.requests, args.clients)
    qps = head["qps"]
    eng_tok_s = qps * args.new_tokens

    # latency vs offered load (open loop), uncongested -> near saturation
    lvl = []
    slo = None
    if not args.no_open_loop:
        for rate in (50, 100, 200, 0.8 * qps):
            rate = round(float(rate), 1)
            if rate <= 0:
                continue
            point = _open_loop(eng, cfg, S - 8, args.new_tokens, rate, args.open_loop_s)
            # transient-stall retry: a multi-second drain at an offered
            # rate the engine demonstrably sustains (observed twice: ~7.7 s
            # at 100 QPS, unreproducible in isolation) is a transient
            # stall, not engine behavior. Retry once and report both.
            # stall discriminator: p50 an order of magnitude above the
            # healthiest open-loop point so far (min-anchor scales to slow
            # configs where multi-second residency is legitimate). The
            # FIRST point uses the absolute 5 s rule alone — on configs
            # slow enough for that to be legitimate, 50 QPS is near
            # capacity and the rate < 0.7*qps guard already excludes it.
            prior = [p["p50_ms"] for p in lvl]
            threshold = max(5000, 10 * min(prior)) if prior else 5000.0
            if point["p50_ms"] > threshold and rate < 0.7 * qps:
                retry = _open_loop(eng, cfg, S - 8, args.new_tokens, rate, args.open_loop_s)
                retry["retried_after_stall"] = {
                    "drain_ms": point["drain_ms"], "p50_ms": point["p50_ms"],
                }
                point = retry
            lvl.append(point)
        # SLO point: 0.9x measured capacity WITH overload control on — a
        # bounded admission queue keeps p99 a small multiple of p50 where
        # the unbounded queue lets it grow with the backlog (VERDICT r3
        # weak #4). Cap sized to ~2 admission rounds of headroom.
        # MEDIAN-OF-3: the adjudicated numbers are the median run's (by
        # p50), with the {median,min,max} spread across runs reported so
        # a transient stall is visible instead of adjudicated
        # (VERDICT r5 weak #1).
        eng.max_queue = 2 * args.batch
        slo_rate = round(0.9 * qps, 1)
        slo_runs = []
        ph0 = _phase_hist_counts(metrics)
        for _ in range(3):
            st0 = eng.stats()
            point = _open_loop(
                eng, cfg, S - 8, args.new_tokens, slo_rate, args.open_loop_s
            )
            st1 = eng.stats()
            slo_runs.append((point, st1["rejected"] - st0["rejected"]))
        eng.max_queue = None
        point, slo_rejected = sorted(slo_runs, key=lambda pr: pr[0]["p50_ms"])[1]
        slo = {
            # utilization at the SLO operating point (BENCH_r07+): recent-
            # window MFU/token-rate over the three SLO runs' chunks/waves,
            # so the QPS/chip number carries its own roofline context
            "mfu": _mfu_block(eng),
            **point,
            "max_queue": 2 * args.batch,
            "rejected": slo_rejected,
            "p99_over_p50": round(point["p99_ms"] / max(point["p50_ms"], 1e-9), 2),
            "spread": {
                key: _spread([pr[0][key] for pr in slo_runs], 1)
                for key in ("p50_ms", "p99_ms", "steady_qps", "ttft_p50_ms")
            },
            # self-attributing SLO point: queue-wait / TTFT / per-token
            # p50+p99 from the engine's phase histograms, delta'd over the
            # three SLO runs (bucket-upper-bound estimates, ms)
            "phase_breakdown": _phase_breakdown(ph0, _phase_hist_counts(metrics)),
        }
    eng.close()

    # serial device roofline for THIS workload: every request costs one
    # share of an admission prefill wave plus new_tokens decode-step
    # shares; prefill and decode serialize on one chip. PEAK uses the
    # min-envelope probes (the chip's fast windows); the engine-vs-ceiling
    # ratio uses the SUSTAINED probes, because a long engine run lives
    # through the same throttled/stalled windows the sustained estimate
    # includes — dividing a sustained engine rate by a peak ceiling
    # conflates engine efficiency with chip-window luck (observed 0.70 and
    # 1.17 for the same code across sessions with single-shot probes).
    def _ceiling(prefill_ms, decode_ms):
        per_req_s = (
            prefill_ms / eng.admit_cap + decode_ms * args.new_tokens / args.batch
        ) / 1e3
        return 1.0 / per_req_s

    ceiling_qps = _ceiling(
        raw[f"prefill_ms_b{eng.admit_cap}"], raw["decode_step_ms"]
    )
    ceiling_sust_qps = _ceiling(
        raw[f"prefill_sustained_ms_b{eng.admit_cap}"],
        raw["decode_step_sustained_ms"],
    )
    # variance-robust alternative built from the median-of-3 probe trials
    ceiling_med_qps = _ceiling(
        raw[f"prefill_median_ms_b{eng.admit_cap}"],
        raw["decode_step_median_ms"],
    )

    detail = {
        **head,
        "engine_tok_s": round(eng_tok_s, 0),
        "device_ceiling_qps": round(ceiling_qps, 0),
        "device_ceiling_sustained_qps": round(ceiling_sust_qps, 0),
        "device_ceiling_median_qps": round(ceiling_med_qps, 0),
        "engine_vs_ceiling": round(qps / ceiling_sust_qps, 3),
        "engine_vs_peak_ceiling": round(qps / ceiling_qps, 3),
        # sustained/sustained, like engine_vs_ceiling: dividing the
        # engine's long-run token rate by the peak-window probe would
        # re-introduce the cross-session chip-luck noise
        "engine_vs_raw": round(
            eng_tok_s / (args.batch / (raw["decode_step_sustained_ms"] / 1e3)), 3
        ),
        **raw,
        "latency_vs_load": lvl,
        "slo_point": slo,
        "warmup": warmup,
        "batch_slots": args.batch,
        "admit_cap": eng.admit_cap,
        "decode_chunk": args.decode_chunk,
        "prefill_len": S,
        "new_tokens": args.new_tokens,
        "int8": quantize,
        "params_b": round(n_params / 1e9, 2),
        "init_s": round(init_s, 1),
        "engine_init_s": round(engine_init_s, 1),
        "device": jax.devices()[0].device_kind,
        "target_note": (
            "vs_baseline = QPS / 1000 (north-star floor: >=1k QPS/chip at "
            "16-tok completions; single-chip infeasible at 128-tok prompts "
            "— see BASELINE.md roofline)"
        ),
    }

    # north-star operating point: short prompts, wide batch (BASELINE.md
    # roofline — the 1k QPS/chip floor is only physical here)
    if on_tpu and not args.no_short:
        # reuse the first engine's (already-quantized) params — a second
        # quantize of the bf16 tree would hold a duplicate int8 copy in HBM.
        # chunk 8: at 8-token prompts decode granularity dominates the
        # admit/retire cadence (measured 1050 QPS at K=8 vs ~1010 at K=16)
        eng2 = LLMEngine(
            cfg, eng.params, slots=256,
            max_seq_len=16 + args.new_tokens + 2 * 8,
            prefill_buckets=(16,), decode_chunk=8,
            admit_cap=32, quantize=quantize,
        )
        _closed_loop(eng2, cfg, 8, args.new_tokens, 512, 1024)
        short = _closed_loop(eng2, cfg, 8, args.new_tokens, 4096, 1024)
        short["slots"], short["decode_chunk"] = 256, 8  # this engine's, not the CLI's
        # low-concurrency open-loop points: the closed-loop p50 above is
        # queueing-dominated (1,024 clients); these show the device-floor
        # latency a lightly-loaded deployment sees (VERDICT r4 weak #3)
        if not args.no_open_loop:
            short["latency_vs_load"] = [
                _open_loop(eng2, cfg, 8, args.new_tokens, rate, args.open_loop_s)
                for rate in (25.0, 50.0)
            ]
        eng2.close()
        detail["short_prompt_8tok"] = short

    # mixed-length prompts through bucketed admission (16..S-8 uniform,
    # buckets at S/4 and S) — the realistic-workload counterpart of the
    # fixed-length headline
    if on_tpu and not args.no_mixed:
        eng3 = LLMEngine(
            cfg, eng.params if quantize else params, slots=args.batch,
            max_seq_len=S + args.new_tokens + 2 * args.decode_chunk,
            prefill_buckets=(max(16, S // 4), S), decode_chunk=args.decode_chunk,
            admit_cap=args.admit_cap, quantize=quantize,
        )
        _closed_loop(eng3, cfg, (16, S - 8), args.new_tokens, 2 * args.batch, args.clients)
        mixed = _closed_loop(
            eng3, cfg, (16, S - 8), args.new_tokens, args.requests // 2, args.clients
        )
        eng3.close()
        detail["mixed_prompt_16_120"] = mixed

    # long-context operating point: 4k prompts through a sliding-window
    # config — the kvcache subsystem's rolling ring bounds slot KV memory
    # and decode bandwidth by O(window), and prefill runs the banded flash
    # kernel (dead k blocks never DMA'd)
    if on_tpu and not args.no_long_context:
        detail["long_context"] = _bench_long_context(
            args, cfg, eng.params if quantize else params, quantize
        )

    # interactive-SLO point (BENCH_r08+): mixed 16/120-token prompts at a
    # fixed offered load — the tail-latency view of the chunked-prefill
    # scheduler (TTFT p99, p99/p50, per-step wall-time jitter)
    if on_tpu and not args.no_interactive_slo and not args.no_open_loop:
        detail["interactive_slo"] = _bench_interactive_slo(
            args, cfg, eng.params if quantize else params, quantize
        )

    # degraded-operation point (BENCH_r09+): kill one of two replicas
    # mid-run via the fault injector — client-visible error rate,
    # failover count, and time-to-restored-capacity are the resilience
    # subsystem's numbers (gofr_tpu.resilience)
    if on_tpu and not args.no_degraded:
        detail["degraded"] = _bench_degraded(
            args, cfg, eng.params if quantize else params, quantize
        )

    # overload operating point (BENCH_r10+): ~2x offered load with a
    # 10:1 heavy:light batch client mix + interactive probes — goodput,
    # shed rate, interactive-vs-batch TTFT split, and the Jain fairness
    # index (gofr_tpu.resilience.overload)
    if on_tpu and not args.no_overload:
        detail["overload"] = _bench_overload(
            args, cfg, eng.params if quantize else params, quantize,
            ceiling_sust_qps,
        )

    # rollout operating point (BENCH_r13+): live weight reload on a
    # 2-replica fleet under steady load — p99 latency delta during the
    # shift vs steady state, time-to-fully-shifted, and the zero-error
    # contract (gofr_tpu.resilience.rollout)
    if on_tpu and not args.no_rollout:
        detail["rollout"] = _bench_rollout(args, cfg, params, quantize)

    # speculative-decoding operating point (BENCH_r12+): spec-on vs
    # spec-off decode tokens/s on a greedy repetitive-suffix mix (the
    # n-gram drafter's home turf) and a natural-text mix (the adaptive
    # backoff's no-regression check), acceptance rate alongside
    # (gofr_tpu.spec; docs/advanced-guide/speculative-decoding.md)
    if on_tpu and not args.no_spec:
        detail["speculative"] = _bench_speculative(
            args, cfg, eng.params if quantize else params, quantize
        )

    # structured-decoding operating point: grammar-constrained vs
    # unconstrained tok/s (mask overhead), schema-validity fraction, and
    # the speculative acceptance delta on grammar-masked JSON
    # (gofr_tpu.structured; docs/advanced-guide/structured-decoding.md)
    if on_tpu and not args.no_structured:
        detail["structured"] = _bench_structured(
            args, cfg, eng.params if quantize else params, quantize
        )

    # observability cost: flight recorder + anomaly baselines + wide
    # events + metrics all on vs all off, same decode-heavy closed run
    # (gofr_tpu.flightrec; docs/advanced-guide/incident-debugging.md) —
    # the <=3% claim that makes always-on flight recording defensible
    if on_tpu and not args.no_obs_overhead:
        detail["obs_overhead"] = _bench_obs_overhead(
            args, cfg, eng.params if quantize else params, quantize
        )

    # goodput ledger cost + yield: device-time attribution on vs off on
    # the same decode-heavy closed run, plus the measured goodput ratio
    # and per-class waste split (gofr_tpu.goodput;
    # docs/advanced-guide/cost-accounting.md) — the <=3% claim that
    # makes always-on chargeback metering defensible
    if on_tpu and not args.no_goodput:
        detail["goodput"] = _bench_goodput(
            args, cfg, eng.params if quantize else params, quantize
        )

    # multi-tenant operating point: 4 resident LoRA adapters decoded in
    # ONE mixed batch vs the single-tenant baseline (batched low-rank
    # deltas inside the same fused programs), adapter hot-load and
    # publish-swap latency (gofr_tpu.lora;
    # docs/advanced-guide/multi-tenancy.md)
    if on_tpu and not args.no_multitenant:
        detail["multitenant"] = _bench_multitenant(
            args, cfg, eng.params if quantize else params, quantize
        )

    # sessions operating point (BENCH_r14+): paged-vs-contiguous decode
    # tok/s (incl. the int8-KV variant), HBM bytes per idle multi-turn
    # session vs slot residency, and cold-resume-from-host latency vs
    # full re-prefill (gofr_tpu.kvcache.paged / sessions;
    # docs/advanced-guide/kv-cache.md)
    if on_tpu and not args.no_sessions:
        detail["sessions"] = _bench_sessions(
            args, cfg, eng.params if quantize else params, quantize
        )

    # sharded operating point (BENCH_r15+): TP=1/2/4 decode tok/s + QPS
    # scaling over ICI submeshes, disaggregated-vs-colocated TTFT under
    # the mixed 16/120 interactive load, KV-handoff latency percentiles
    # (gofr_tpu.llm_disagg; docs/advanced-guide/sharded-serving.md)
    if on_tpu and not args.no_sharded:
        detail["sharded"] = _bench_sharded(
            args, cfg, eng.params if quantize else params, quantize
        )

    # prefix-cache operating point: 50% shared-prefix traffic — hits skip
    # the prefill wave entirely, so the engine can exceed the NO-CACHE
    # device ceiling (per-request prefill is the larger serial share at
    # the headline shapes)
    if on_tpu and not args.no_prefix_cache:
        detail["prefix_cache"] = _bench_prefix_cache(
            args, cfg, eng.params if quantize else params, quantize,
            ceiling_sust_qps,
        )

    # BASELINE configs 1-2 recorded alongside the headline (VERDICT r2
    # missing #4: greet/mlp existed as modes but no number was on file)
    if not args.no_subruns:
        sub = argparse.Namespace(**vars(args))
        sub.requests, sub.clients = GREET_SUB_REQUESTS, GREET_SUB_CLIENTS
        if greet_sub is not None:
            g = greet_sub  # measured pre-jax at bench start (see top)
        else:
            g = bench_greet(sub)  # fallback: in-process (marked by key)
            detail["greet_in_process"] = True
        sub.requests = 2048
        m = bench_mlp(sub)
        detail["subruns"] = {
            "greet_qps_cpu": g["value"], "greet_p50_ms": g["detail"]["p50_ms"],
            "greet_uncongested_p50_ms": g["detail"]["uncongested_p50_ms"],
            "mlp_qps": m["value"], "mlp_p50_ms": m["detail"]["p50_ms"],
        }

    return {
        "metric": f"gemma{'7b' if seven_b else '2b'}_serving_qps_per_chip",
        "value": round(qps, 1),
        "unit": "req/s (16-tok completions)",
        "vs_baseline": round(qps / 1000.0, 3),
        "detail": detail,
    }


def _bench_long_context(args, cfg, params, quantize: bool) -> dict:
    """Long-context point: 4k-token prompts, sliding window 1024, int8.
    The rolling KV layout (gofr_tpu.kvcache) keeps each slot at
    window + chunk rows, so the engine's KV slab costs ~1/4 of the dense
    equivalent at these shapes and decode reads O(window) per step."""
    import dataclasses

    from gofr_tpu.llm import LLMEngine

    cfg_lc = dataclasses.replace(cfg, sliding_window=args.lc_window)
    S, K = args.lc_prompt, 16
    eng = LLMEngine(
        cfg_lc, params, slots=16,
        max_seq_len=S + args.new_tokens + 2 * K,
        prefill_buckets=(S,), decode_chunk=K, admit_cap=4, quantize=quantize,
    )
    try:
        _closed_loop(eng, cfg_lc, S - 8, args.new_tokens, 16, 16)  # warm
        point = _closed_loop(eng, cfg_lc, S - 8, args.new_tokens, 48, 16)
        kv = eng.kv.stats()
        point.update({
            "prompt_len": S - 8,
            "window": args.lc_window,
            "int8": quantize,
            "kv_layout": kv["layout"],
            "kv_capacity_rows": kv["capacity"],
            # whole-slab bytes (all slots), vs what a dense layout would
            # allocate for the same engine — the O(window) memory claim
            "kv_slab_mb": round(kv["slot_bytes"] / 2**20, 1),
            "dense_equiv_slab_mb": round(
                kv["slot_bytes"] / kv["capacity"] * eng.max_seq_len / 2**20, 1
            ),
        })
    finally:
        eng.close()
    return point


def _bench_degraded(args, cfg, params, quantize: bool) -> dict:
    """Degraded-operation point: a 2-replica fleet under steady
    closed-loop load loses replica 0 mid-run (fault injector) and the
    numbers that matter are the BLAST RADIUS — client-visible error
    rate, in-flight failovers, and time-to-restored-capacity (kill ->
    the supervisor's rebuilt replica back in the routing set). An
    unfailed run of the same shape would report error_rate 0 and no
    failovers; the point exists to keep those properties honest.

    BENCH_r11+ adds a device-health phase: the same replica dies again
    with its home device persistently sick (``device_sick``), and the
    point reports time-to-quarantine (kill -> the health ledger trips
    the device, ending the same-device restart loop) and
    time-to-reintegrated-capacity (quarantine -> 2 replicas alive
    again, via an elastic rebuild on an alternate device or a
    post-cooldown canary-gated reintegration)."""
    import jax

    from gofr_tpu.llm import GenRequest, ReplicatedLLMEngine
    from gofr_tpu.resilience import FaultInjector

    if len(jax.devices()) < 2:
        return {"skipped": "needs >=2 devices"}
    S = args.prefill_len
    inj = FaultInjector()
    # short quarantine window for the phase-2 measurement: the bench
    # must see reintegration inside its 120 s cap even on a 2-device
    # host where restored capacity waits out the cooldown
    _cooldown_prev = os.environ.get("TPU_LLM_DEVICE_COOLDOWN_S")
    os.environ["TPU_LLM_DEVICE_COOLDOWN_S"] = "5"
    try:
        rep = ReplicatedLLMEngine(
            cfg, params, replicas=2, fault_injector=inj,
            slots=args.batch,
            max_seq_len=S + args.new_tokens + 2 * args.decode_chunk,
            prefill_buckets=(S,), decode_chunk=args.decode_chunk,
            admit_cap=args.admit_cap, quantize=quantize,
        )
    finally:
        if _cooldown_prev is None:
            os.environ.pop("TPU_LLM_DEVICE_COOLDOWN_S", None)
        else:
            os.environ["TPU_LLM_DEVICE_COOLDOWN_S"] = _cooldown_prev
    ok = errors = 0
    lock = threading.Lock()
    stop = threading.Event()

    def client(cid: int):
        nonlocal ok, errors
        rng = np.random.default_rng(cid)
        while not stop.is_set():
            prompt = rng.integers(1, cfg.vocab_size, size=S - 8).tolist()
            try:
                req = rep.submit(GenRequest(prompt, max_new_tokens=args.new_tokens))
                toks = req.tokens(timeout=600)
                good = len(toks) == args.new_tokens
            except Exception:  # noqa: BLE001 — errors ARE the measurement
                good = False
            with lock:
                if good:
                    ok += 1
                else:
                    errors += 1

    n_clients = min(64, args.clients)
    ts = [threading.Thread(target=client, args=(c,)) for c in range(n_clients)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    t_quarantine = t_recapacity = None
    try:
        # steady state first, then the kill
        time.sleep(3.0)
        inj.arm("replica_kill", label="/r0")
        t_kill = time.perf_counter()
        # wait for the death, then for restored capacity (supervised
        # rebuild + warm on the same device; cap the wait at 120 s)
        t_restored = None
        deadline = t_kill + 120.0
        died = False
        while time.perf_counter() < deadline:
            alive = sum(e.alive() for e in rep.engines)
            if alive < 2:
                died = True
            elif died:
                t_restored = time.perf_counter()
                break
            time.sleep(0.05)
        time.sleep(2.0)  # post-restore steady state
        # phase 2 (BENCH_r11+): device-health blast radius — the home
        # device is now persistently sick, so the rebuild loop must END
        # in quarantine instead of repeating, and capacity must return
        # via an alternate device or a post-cooldown reintegration
        if t_restored is not None:
            home = rep._device_keys[0]
            inj.arm("device_sick", label=home, count=-1)
            inj.arm("replica_kill", label="/r0")
            t_kill2 = time.perf_counter()
            deadline = t_kill2 + 120.0
            while time.perf_counter() < deadline:
                if (
                    t_quarantine is None
                    and rep.health.state(home) != "healthy"
                ):
                    t_quarantine = time.perf_counter()
                    inj.disarm("device_sick")  # let a probe rebuild pass
                if (
                    t_quarantine is not None
                    and sum(e.alive() for e in rep.engines) == 2
                ):
                    t_recapacity = time.perf_counter()
                    break
                time.sleep(0.05)
            time.sleep(1.0)  # post-reintegration steady state
    finally:
        stop.set()
        for t in ts:
            t.join(timeout=60)
    wall = time.perf_counter() - t0
    st = rep.stats()
    landed = rep._current_keys[0]  # where slot 0 serves after phase 2
    rep.close()
    total = ok + errors
    return {
        "requests": total,
        "qps": round(total / wall, 1),
        "errors": errors,
        "error_rate": round(errors / max(1, total), 4),
        "failovers": st["failovers"],
        "failover_errors": st["failover_errors"],
        "restarts": st["restarts"],
        "time_to_restored_s": (
            round(t_restored - t_kill, 2) if t_restored is not None else None
        ),
        # device-health phase (BENCH_r11+)
        "quarantines": st["devices_quarantined"],
        "poisoned": st["poisoned"],
        "time_to_quarantine_s": (
            round(t_quarantine - t_kill2, 2)
            if t_quarantine is not None else None
        ),
        "time_to_reintegrated_capacity_s": (
            round(t_recapacity - t_quarantine, 2)
            if t_recapacity is not None else None
        ),
        "rebuilt_on": landed if t_recapacity is not None else None,
        "clients": n_clients,
        "replicas": 2,
    }


def _bench_rollout(args, cfg, params, quantize: bool) -> dict:
    """Rollout point: a 2-replica fleet serving steady closed-loop load
    performs a live weight rollout (deploy -> drain one replica at a
    time -> canary+shadow gate -> admit -> bake). The numbers that
    matter are the COST OF THE SHIFT: p99 request latency during the
    shift vs the pre-shift steady state (capacity runs one replica
    short while each slot rebuilds), time until the fleet is fully on
    the new version, and the zero-dropped-requests contract (error
    count must be 0 — an unshifted run of the same shape would report
    the same)."""
    import jax

    from gofr_tpu.llm import GenRequest, ReplicatedLLMEngine

    if len(jax.devices()) < 2:
        return {"skipped": "needs >=2 devices"}
    S = args.prefill_len
    rep = ReplicatedLLMEngine(
        cfg, params, replicas=2,
        slots=args.batch,
        max_seq_len=S + args.new_tokens + 2 * args.decode_chunk,
        prefill_buckets=(S,), decode_chunk=args.decode_chunk,
        admit_cap=args.admit_cap, quantize=quantize, supervise=False,
    )
    lat_lock = threading.Lock()
    lats: list[tuple[float, float]] = []  # (finish_t, seconds)
    errors = 0
    stop = threading.Event()

    def client(cid: int):
        nonlocal errors
        rng = np.random.default_rng(cid)
        while not stop.is_set():
            prompt = rng.integers(1, cfg.vocab_size, size=S - 8).tolist()
            t0 = time.perf_counter()
            try:
                req = rep.submit(
                    GenRequest(prompt, max_new_tokens=args.new_tokens)
                )
                ok = len(req.tokens(timeout=600)) == args.new_tokens
            except Exception:  # noqa: BLE001 — errors ARE the measurement
                ok = False
            t1 = time.perf_counter()
            with lat_lock:
                if ok:
                    lats.append((t1, t1 - t0))
                else:
                    errors += 1

    n_clients = min(64, args.clients)
    ts = [threading.Thread(target=client, args=(c,)) for c in range(n_clients)]
    for t in ts:
        t.start()
    t_deploy = t_shifted = None
    try:
        time.sleep(3.0)  # steady state on v1
        # new weights: same shapes, slightly perturbed — the rollout
        # machinery neither knows nor cares that the delta is tiny
        v2 = jax.tree.map(lambda x: x * (1.0 + 1e-3), params)
        t_deploy = time.perf_counter()
        rep.deploy(cfg, v2, version="v2", bake_s=2.0)
        deadline = t_deploy + 600.0
        while time.perf_counter() < deadline:
            if t_shifted is None and rep.version_counts() == {"v2": 2}:
                t_shifted = time.perf_counter()
            if not rep._rollout.active():
                break
            time.sleep(0.05)
        final_state = rep.rollout_state()["state"]
        time.sleep(2.0)  # post-shift steady state
    finally:
        stop.set()
        for t in ts:
            t.join(timeout=60)
    rep.close()

    def p(vals, q):
        if not vals:
            return None
        vals = sorted(vals)
        return round(
            vals[min(len(vals) - 1, int(q * len(vals)))] * 1e3, 1
        )

    with lat_lock:
        before = [s for t1, s in lats if t_deploy and t1 <= t_deploy]
        during = [
            s for t1, s in lats
            if t_deploy and t1 > t_deploy
            and (t_shifted is None or t1 <= t_shifted)
        ]
    p99_before = p(before, 0.99)
    p99_during = p(during, 0.99)
    return {
        "state": final_state,
        "requests": len(lats) + errors,
        "errors": errors,  # the zero-dropped-requests contract
        "time_to_fully_shifted_s": (
            round(t_shifted - t_deploy, 2)
            if t_shifted is not None and t_deploy is not None else None
        ),
        "p99_before_ms": p99_before,
        "p99_during_shift_ms": p99_during,
        "p99_shift_delta": (
            round(p99_during / p99_before, 2)
            if p99_before and p99_during else None
        ),
        "clients": n_clients,
        "replicas": 2,
    }


def _bench_prefix_cache(args, cfg, params, quantize: bool, ceiling_qps: float) -> dict:
    """Prefix-cache point: half the traffic reuses one shared prompt.
    Hits are admitted from retained KV rows (no prefill wave), so the
    achieved QPS is compared against the NO-CACHE device ceiling — the
    'perf beyond ceiling' lever (VERDICT r5 #9)."""
    from gofr_tpu.llm import LLMEngine

    S = args.prefill_len
    eng = LLMEngine(
        cfg, params, slots=args.batch,
        max_seq_len=S + args.new_tokens + 2 * args.decode_chunk,
        prefill_buckets=(S,), decode_chunk=args.decode_chunk,
        admit_cap=args.admit_cap, quantize=quantize, prefix_cache_mb=512.0,
    )
    try:
        _closed_loop(
            eng, cfg, S - 8, args.new_tokens, 2 * args.batch, args.clients,
            shared_frac=0.5,
        )  # warm the executables
        # DIFFERENT seed for the measured run: replaying the warm run's rng
        # stream would replay its exact prompts, and every "unique" prompt
        # would hit the entry its warm twin stored — a fake 100% hit rate
        kv0 = eng.stats()["kvcache"]["prefix"]  # exclude the warm run
        point = _closed_loop(
            eng, cfg, S - 8, args.new_tokens, args.requests, args.clients,
            seed=1, shared_frac=0.5,
        )
        kvp = eng.stats()["kvcache"]["prefix"]
        hits = kvp["hits"] - kv0["hits"]
        misses = kvp["misses"] - kv0["misses"]
        point.update({
            "shared_frac": 0.5,
            "prefix_hits": hits,
            "prefix_misses": misses,
            "hit_rate": round(hits / max(1, hits + misses), 3),
            "prefix_resident_mb": round(kvp["resident_bytes"] / 2**20, 1),
            "no_cache_ceiling_qps": round(ceiling_qps, 0),
            "qps_vs_no_cache_ceiling": round(point["qps"] / ceiling_qps, 3),
        })
    finally:
        eng.close()
    return point


def _bench_sessions(args, cfg, params, quantize: bool) -> dict:
    """Sessions point (BENCH_r14+): the paged KV pool's "millions of
    users" memory model (gofr_tpu.kvcache.paged/sessions).

    Three sub-measurements:

    - **paged vs contiguous decode tok/s** on a decode-heavy closed run
      (same shapes, kv_paged A/B), plus the int8-KV variant — the paged
      read path must hold the contiguous path's throughput while buying
      the sharing below.
    - **multi-turn residency**: N conversations (50% sharing one
      system-prefix, so sibling turns block-share it) each run a turn
      and go idle; the adjudicated number is HBM bytes per IDLE session
      (pool blocks, radix-deduplicated) vs what slot residency would
      cost — parking each conversation in a slot slab.
    - **cold resume**: sessions spilled to host, then one resumed —
      second-turn latency from the host tier vs the full re-prefill the
      same turn pays without a session. Restore is one DMA per block;
      re-prefill is a forward pass per token.
    """
    from gofr_tpu.llm import GenRequest, LLMEngine

    S = args.prefill_len
    K = args.decode_chunk
    new_tokens = args.new_tokens
    max_seq = 2 * S + 2 * new_tokens + 4 * K

    # -- paged vs contiguous decode tokens/s (+ int8 variant) -------------
    dec_tokens = max(4 * args.new_tokens, 64)

    def tok_s(paged: bool, int8: bool = False) -> float:
        eng = LLMEngine(
            cfg, params, slots=min(args.batch, 64),
            max_seq_len=S + dec_tokens + 2 * K,
            prefill_buckets=(S,), decode_chunk=K,
            admit_cap=args.admit_cap, quantize=quantize,
            kv_paged=paged, kv_int8=int8,
        )
        try:
            _closed_loop(eng, cfg, S - 8, 8, 16, 16)  # warm
            p = _closed_loop(
                eng, cfg, S - 8, dec_tokens, min(args.batch, 64) * 2, 64,
            )
            return p["qps"] * dec_tokens
        finally:
            eng.close()

    paged_tok_s = tok_s(True)
    contig_tok_s = tok_s(False)
    int8_tok_s = tok_s(True, int8=True)

    # -- multi-turn residency + cold resume -------------------------------
    n_sessions = 32
    eng = LLMEngine(
        cfg, params, slots=16, max_seq_len=max_seq,
        prefill_buckets=(S,), decode_chunk=K, admit_cap=args.admit_cap,
        quantize=quantize, session_mb=4096.0, prefix_cache_mb=64.0,
    )
    try:
        rng = np.random.default_rng(5)
        sys_prefix = rng.integers(1, cfg.vocab_size, S // 2).tolist()
        convs = []
        for i in range(n_sessions):
            own = rng.integers(
                1, cfg.vocab_size, S - 8 - (len(sys_prefix) if i % 2 else 0)
            ).tolist()
            convs.append((sys_prefix + own) if i % 2 else own)

        def turn(sid: str, prompt: list[int]) -> tuple[list[int], float, float]:
            t0 = time.perf_counter()
            req = eng.submit(GenRequest(
                prompt, max_new_tokens=new_tokens, session_id=sid,
            ))
            toks, first = [], None
            for t in req.stream(timeout=600):
                if first is None:
                    first = time.perf_counter() - t0
                toks.append(t)
            return toks, first, time.perf_counter() - t0

        outs = [turn(f"s{i}", convs[i]) for i in range(n_sessions)]
        deadline = time.time() + 30
        while time.time() < deadline:
            st = eng.kv.sessions.stats()
            if st["publishes"] >= n_sessions:
                break
            time.sleep(0.05)
        st = eng.kv.sessions.stats()
        kvs = eng.kv.stats()
        # idle-session residency: pool bytes pinned by sessions (radix
        # dedups the 50% shared prefix) vs parking each conversation in
        # a full slot slab (what pre-paging "keep it warm" would cost)
        per_session = st["resident_bytes"] / max(1, st["resident"])
        row_bytes = kvs["block_bytes"] / eng.kv.block
        slot_equiv = row_bytes * eng.max_seq_len
        # first-turn TTFT baseline, then the warm second turn (resident
        # blocks -> block-granular prefix hit on the whole history)
        first_ttfts = [o[1] for o in outs]
        warm2 = []
        for i in range(0, n_sessions, 8):
            t2 = convs[i] + outs[i][0] + [7, 8, 9]
            warm2.append(turn(f"s{i}", t2)[1])
        # cold resume: spill EVERYTHING, then resume one session — the
        # restore is h2d DMA + prefill of only the unshared tail, vs the
        # sessionless full re-prefill of the same prompt
        eng.kv.sessions.device_budget = 1
        eng._kick.set()
        deadline = time.time() + 30
        while time.time() < deadline:
            if eng.kv.sessions.stats()["resident"] == 0:
                break
            time.sleep(0.05)
        eng.kv.sessions.device_budget = 4096 * 2**20
        spilled = eng.kv.sessions.stats()
        # warm the restore executable (first call compiles the h2d
        # scatter for this session width) on a DIFFERENT session, then
        # time the adjudicated resume
        warm_t2 = convs[4] + outs[4][0] + [11, 12, 13]
        turn("s4", warm_t2)
        j = 2
        t2 = convs[j] + outs[j][0] + [11, 12, 13]
        _, resume_ttft, resume_total = turn(f"s{j}", t2)
        _, cold_ttft, cold_total = turn("", t2 + [14])  # sessionless: full prefill
        return {
            "paged_tok_s": round(paged_tok_s, 0),
            "contig_tok_s": round(contig_tok_s, 0),
            "paged_vs_contig": round(paged_tok_s / max(1e-9, contig_tok_s), 3),
            "int8_tok_s": round(int8_tok_s, 0),
            "int8_vs_contig": round(int8_tok_s / max(1e-9, contig_tok_s), 3),
            "sessions": n_sessions,
            "shared_frac": 0.5,
            "hbm_bytes_per_idle_session": int(per_session),
            "slot_equiv_bytes": int(slot_equiv),
            "idle_session_vs_slot": round(per_session / max(1, slot_equiv), 3),
            "blocks_shared": kvs["blocks_shared"],
            "first_turn_ttft_ms": round(
                1e3 * float(np.median(first_ttfts)), 1
            ),
            "second_turn_ttft_ms": round(1e3 * float(np.median(warm2)), 1),
            "spilled_sessions": spilled["spilled"],
            "spilled_mb": round(
                spilled["offload"]["spilled_bytes"] / 2**20, 1
            ),
            "cold_resume_ttft_ms": round(1e3 * resume_ttft, 1),
            "reprefill_ttft_ms": round(1e3 * cold_ttft, 1),
            "resume_vs_reprefill": round(
                resume_ttft / max(1e-9, cold_ttft), 3
            ),
        }
    finally:
        eng.close()


def _bench_sharded(args, cfg, params, quantize: bool) -> dict:
    """Sharded-serving point (BENCH_r15+): the multi-chip half of the
    serving story (docs/advanced-guide/sharded-serving.md).

    Three sub-measurements:

    - **TP scaling**: decode tok/s (decode-heavy closed run) and
      closed-loop QPS at the SLO shapes for TP=1/2/4 — one engine
      tensor-parallel over an ICI submesh, weight shards all-gathered
      with collective-compute overlap on the decode path. The
      adjudicated numbers are the scaling ratios vs TP=1.
    - **disaggregated vs colocated**: a 1-prefill + 1-decode role pair
      vs a colocated 2-replica fleet under the mixed 16/120-token
      open-loop interactive load — TTFT p99 and interactive p99/p50
      both ways (long prompts stop stealing decode steps from
      interactive streams on the disaggregated side).
    - **KV handoff latency percentiles**: submit -> decode-admit wall
      for the prefill->decode block transfers, from the engine's own
      window.
    """
    import jax

    from gofr_tpu.llm import LLMEngine, ReplicatedLLMEngine
    from gofr_tpu.llm_disagg import DisaggregatedLLMEngine
    from gofr_tpu.parallel import make_mesh, param_specs

    n_dev = len(jax.devices())
    S, K = args.prefill_len, args.decode_chunk
    dec_tokens = max(4 * args.new_tokens, 64)
    slots = min(args.batch, 64)
    out: dict = {"devices": n_dev}

    # -- TP scaling: decode tok/s + closed-loop QPS at TP=1/2/4 ----------
    tp_scaling: dict = {}
    base_tok_s = base_qps = None
    for tp in (1, 2, 4):
        if tp > n_dev:
            continue
        mesh = specs = None
        if tp > 1:
            mesh = make_mesh(
                {"data": 1, "model": tp}, devices=jax.devices()[:tp]
            )
            specs = param_specs(cfg, mesh)
        eng = LLMEngine(
            cfg, params, slots=slots, max_seq_len=S + dec_tokens + 2 * K,
            prefill_buckets=(max(16, S // 4), S), decode_chunk=K,
            admit_cap=args.admit_cap, quantize=quantize,
            mesh=mesh, param_specs=specs,
        )
        try:
            _closed_loop(eng, cfg, S - 8, 8, 16, 16)  # warm the shapes
            dec = _closed_loop(eng, cfg, S - 8, dec_tokens, slots * 2, 64)
            slo = _closed_loop(
                eng, cfg, S - 8, args.new_tokens,
                max(64, args.requests // 2), args.clients,
            )
            tok_s = dec["qps"] * dec_tokens
            row = {
                "decode_tok_s": round(tok_s, 0),
                "qps": slo["qps"],
                "p99_ms": slo["p99_ms"],
            }
            if tp == 1:
                base_tok_s, base_qps = tok_s, slo["qps"]
            else:
                row["decode_scaling_vs_tp1"] = round(
                    tok_s / max(1e-9, base_tok_s), 2
                )
                row["qps_scaling_vs_tp1"] = round(
                    slo["qps"] / max(1e-9, base_qps), 2
                )
            tp_scaling[f"tp{tp}"] = row
        finally:
            eng.close()
    out["tp"] = tp_scaling

    # -- disaggregated vs colocated under the mixed 16/120 load ----------
    if n_dev >= 2:
        rate = max(8.0, args.interactive_rate / 4)
        mix = (16, S - 8)
        fleet_kw = dict(
            slots=slots, max_seq_len=S + args.new_tokens + 2 * K,
            prefill_buckets=(max(16, S // 4), S), decode_chunk=K,
            admit_cap=args.admit_cap, quantize=quantize, supervise=False,
        )
        def warm_fleet(eng):
            # stats-free warm (fleet/disagg engines do not expose the
            # single-engine telemetry _closed_loop deltas): every prompt
            # length in the mix, both pools touched
            from gofr_tpu.llm import GenRequest

            rng_np = np.random.default_rng(7)
            reqs = [
                eng.submit(GenRequest(
                    rng_np.integers(1, cfg.vocab_size, size=pl).tolist(),
                    max_new_tokens=args.new_tokens,
                ))
                for pl in mix
                for _ in range(8)
            ]
            for r in reqs:
                r.tokens(timeout=600)

        co = ReplicatedLLMEngine(cfg, params, replicas=2, **fleet_kw)
        try:
            warm_fleet(co)
            co_res = _open_loop(
                co, cfg, mix, args.new_tokens, rate, args.open_loop_s
            )
        finally:
            co.close()
        dis = DisaggregatedLLMEngine(
            cfg, params, replicas=2, prefill_replicas=1, **fleet_kw
        )
        try:
            warm_fleet(dis)
            dis_res = _open_loop(
                dis, cfg, mix, args.new_tokens, rate, args.open_loop_s
            )
            hand = dis.stats()["handoff"]
        finally:
            dis.close()
        lat = hand.get("latency") or {}
        out["disagg"] = {
            "offered_qps": rate,
            "colocated_ttft_p99_ms": co_res["ttft_p99_ms"],
            "disagg_ttft_p99_ms": dis_res["ttft_p99_ms"],
            "ttft_p99_vs_colocated": round(
                dis_res["ttft_p99_ms"] / max(1e-9, co_res["ttft_p99_ms"]), 3
            ),
            "colocated_p99_over_p50": round(
                co_res["p99_ms"] / max(1e-9, co_res["p50_ms"]), 2
            ),
            "disagg_p99_over_p50": round(
                dis_res["p99_ms"] / max(1e-9, dis_res["p50_ms"]), 2
            ),
            "handoff_ok": hand.get("ok", 0),
            "handoff_miss": hand.get("miss", 0),
            "handoff_p50_ms": round(1e3 * (lat.get("p50") or 0.0), 1),
            "handoff_p99_ms": round(1e3 * (lat.get("p99") or 0.0), 1),
        }
    return out


def _bench_speculative(args, cfg, params, quantize: bool) -> dict:
    """Speculative-decoding point (BENCH_r12+): decode-heavy closed runs
    (short prompts, long completions — decode wall dominates) on two
    prompt mixes, spec-on vs spec-off, same engine shapes. The
    repetitive-suffix mix (prompt tail = a repeating 4-gram; greedy
    continuations extend the pattern) is where prompt-lookup drafting
    pays — the adjudicated number is its tokens/s speedup, with the
    measured acceptance rate alongside. The natural mix (uniform random
    tokens, ~0% self-similarity) checks the adaptive backoff's
    no-regression claim: spec-on must hold ~1x, not collapse."""
    from gofr_tpu.llm import GenRequest, LLMEngine

    S = args.prefill_len
    new_tokens = max(4 * args.new_tokens, 64)  # decode-dominated requests
    n_req = 2 * args.batch
    rng = np.random.default_rng(11)
    pattern = rng.integers(1, cfg.vocab_size, 4).tolist()
    rep_prompts = []
    nat_prompts = []
    for i in range(n_req):
        head = np.random.default_rng(1000 + i).integers(
            1, cfg.vocab_size, size=max(1, S - 8 - 24),
        ).tolist()
        rep_prompts.append((head + pattern * 6)[-(S - 8):])
        nat_prompts.append(np.random.default_rng(2000 + i).integers(
            1, cfg.vocab_size, size=S - 8,
        ).tolist())

    def run(spec_on: bool, prompts: list[list[int]]) -> tuple[float, dict]:
        eng = LLMEngine(
            cfg, params, slots=min(args.batch, 64),
            max_seq_len=S + new_tokens + 2 * args.decode_chunk,
            prefill_buckets=(S,), decode_chunk=args.decode_chunk,
            admit_cap=args.admit_cap, quantize=quantize,
            speculative=spec_on, spec_draft=4,
        )
        try:
            # warm every dispatch path on a short burst before timing
            warm = [eng.submit(GenRequest(list(p), max_new_tokens=8))
                    for p in prompts[:8]]
            for r in warm:
                r.tokens()
            st0 = eng.stats()["spec"]
            t0 = time.perf_counter()
            reqs = [eng.submit(GenRequest(list(p), max_new_tokens=new_tokens))
                    for p in prompts]
            total = sum(len(r.tokens(timeout=600)) for r in reqs)
            wall = time.perf_counter() - t0
            # diff over the timed window only: stats()["spec"] is
            # cumulative and the warm burst's drafting would otherwise
            # pollute the acceptance rate printed next to this speedup
            st1 = eng.stats()["spec"]
            st = {
                k: st1[k] - st0[k]
                for k in ("proposed", "accepted", "plain_lanes", "steps")
            }
            st["accept_rate"] = (
                round(st["accepted"] / st["proposed"], 3)
                if st["proposed"] else None
            )
        finally:
            eng.close()
        return total / wall, st

    out: dict = {"new_tokens": new_tokens, "requests": n_req, "draft": 4}
    for name, prompts in (("repetitive", rep_prompts), ("natural", nat_prompts)):
        base_tok_s, _ = run(False, prompts)
        spec_tok_s, st = run(True, prompts)
        out[name] = {
            "base_tok_s": round(base_tok_s, 0),
            "spec_tok_s": round(spec_tok_s, 0),
            "speedup": round(spec_tok_s / max(base_tok_s, 1e-9), 2),
            "accept_rate": st["accept_rate"],
            "proposed": st["proposed"],
            "accepted": st["accepted"],
            "plain_lanes": st["plain_lanes"],
        }
    return out


def _bench_obs_overhead(args, cfg, params, quantize: bool) -> dict:
    """Observability-overhead point (gofr_tpu.flightrec): the same
    decode-heavy closed run twice — once with every per-request
    observability sink armed (flight recorder at its default ring size,
    anomaly baselines, UNSAMPLED wide-event lines, Prometheus metrics),
    once with all of it off — and the tokens/s ratio between them. The
    adjudicated claim is <=3% decode-throughput overhead: the recorder
    is one dict write per request terminal and the detectors are O(1)
    ring arithmetic, so always-on flight recording must be affordable
    at the serving operating point."""
    import io as _io

    from gofr_tpu.llm import GenRequest, LLMEngine
    from gofr_tpu.logging import Logger
    from gofr_tpu.metrics import new_metrics_manager

    S = args.prefill_len
    new_tokens = max(4 * args.new_tokens, 64)  # decode-dominated requests
    n_req = 2 * args.batch
    prompts = [
        np.random.default_rng(3000 + i).integers(
            1, cfg.vocab_size, size=S - 8,
        ).tolist()
        for i in range(n_req)
    ]

    def run(observed: bool) -> float:
        kw: dict = {}
        if observed:
            kw.update(
                metrics=new_metrics_manager(),
                logger=Logger(out=_io.StringIO(), err=_io.StringIO(),
                              pretty=False),
                flight_records=512, anomaly=True, wide_event_sample=1,
            )
        else:
            kw.update(flight_records=0, anomaly=False)
        eng = LLMEngine(
            cfg, params, slots=min(args.batch, 64),
            max_seq_len=S + new_tokens + 2 * args.decode_chunk,
            prefill_buckets=(S,), decode_chunk=args.decode_chunk,
            admit_cap=args.admit_cap, quantize=quantize, **kw,
        )
        try:
            warm = [eng.submit(GenRequest(list(p), max_new_tokens=8))
                    for p in prompts[:8]]
            for r in warm:
                r.tokens()
            t0 = time.perf_counter()
            reqs = [eng.submit(GenRequest(list(p), max_new_tokens=new_tokens))
                    for p in prompts]
            total = sum(len(r.tokens(timeout=600)) for r in reqs)
            wall = time.perf_counter() - t0
        finally:
            eng.close()
        return total / wall

    base_tok_s = run(False)
    obs_tok_s = run(True)
    overhead = 1.0 - obs_tok_s / max(base_tok_s, 1e-9)
    return {
        "new_tokens": new_tokens,
        "requests": n_req,
        "base_tok_s": round(base_tok_s, 0),
        "obs_tok_s": round(obs_tok_s, 0),
        "overhead_frac": round(overhead, 4),
        "claim_frac": 0.03,
        "within_claim": overhead <= 0.03,
    }


def _bench_goodput(args, cfg, params, quantize: bool) -> dict:
    """Goodput-ledger point (gofr_tpu.goodput;
    docs/advanced-guide/cost-accounting.md): the same decode-heavy
    closed run twice — once with the device-time ledger metering every
    fused dispatch (per-lane attribution, waste taxonomy, per-tenant
    usage windows), once with the meter off — and the tokens/s ratio
    between them. Reports the measured goodput ratio and the per-class
    waste split of the metered run. The adjudicated claim is <=3%
    decode-throughput overhead: attribution is O(lanes) dict arithmetic
    per dispatch on the host collector thread, off the device path."""
    from gofr_tpu.llm import GenRequest, LLMEngine
    from gofr_tpu.metrics import new_metrics_manager

    S = args.prefill_len
    new_tokens = max(4 * args.new_tokens, 64)  # decode-dominated requests
    n_req = 2 * args.batch
    prompts = [
        np.random.default_rng(3100 + i).integers(
            1, cfg.vocab_size, size=S - 8,
        ).tolist()
        for i in range(n_req)
    ]

    def run(metered: bool) -> tuple[float, dict | None]:
        kw: dict = {"goodput": metered}
        if metered:
            kw["metrics"] = new_metrics_manager()
        eng = LLMEngine(
            cfg, params, slots=min(args.batch, 64),
            max_seq_len=S + new_tokens + 2 * args.decode_chunk,
            prefill_buckets=(S,), decode_chunk=args.decode_chunk,
            admit_cap=args.admit_cap, quantize=quantize, **kw,
        )
        try:
            warm = [eng.submit(GenRequest(list(p), max_new_tokens=8,
                                          client=f"t{i % 2}"))
                    for i, p in enumerate(prompts[:8])]
            for r in warm:
                r.tokens()
            t0 = time.perf_counter()
            reqs = [eng.submit(GenRequest(list(p), max_new_tokens=new_tokens,
                                          client=f"t{i % 2}"))
                    for i, p in enumerate(prompts)]
            total = sum(len(r.tokens(timeout=600)) for r in reqs)
            wall = time.perf_counter() - t0
            snap = eng.goodput.snapshot() if metered else None
        finally:
            eng.close()
        return total / wall, snap

    base_tok_s, _ = run(False)
    gp_tok_s, snap = run(True)
    overhead = 1.0 - gp_tok_s / max(base_tok_s, 1e-9)
    snap = snap or {}
    by = snap.get("by_class") or {}
    attributed = max(snap.get("attributed_s") or 0.0, 1e-9)
    return {
        "new_tokens": new_tokens,
        "requests": n_req,
        "base_tok_s": round(base_tok_s, 0),
        "metered_tok_s": round(gp_tok_s, 0),
        "overhead_frac": round(overhead, 4),
        "claim_frac": 0.03,
        "within_claim": overhead <= 0.03,
        "goodput_ratio": snap.get("goodput_ratio"),
        "idle_frac": round(
            (snap.get("idle_s") or 0.0) / max(snap.get("wall_s") or 0.0, 1e-9),
            4,
        ),
        "waste_frac": {
            c: round(by.get(c, 0.0) / attributed, 4)
            for c in ("padding", "spec_reject", "replay", "probe")
        },
    }


def _bench_structured(args, cfg, params, quantize: bool) -> dict:
    """Structured-decoding point (gofr_tpu.structured;
    docs/advanced-guide/structured-decoding.md): grammar-constrained vs
    unconstrained decode tokens/s at identical engine shapes (the mask's
    device cost: one table gather + select per sampled token), the
    schema-validity fraction of the constrained outputs (must be 1.0 —
    the by-construction guarantee measured on hardware), and the
    speculative acceptance DELTA: acceptance on grammar-masked JSON
    (drafts pre-filtered by the DFA) vs the same engine's acceptance on
    unconstrained output of the same prompts — constrained text is
    highly predictable, so the delta should be >= 0."""
    import json as _json

    from gofr_tpu.llm import GenRequest, LLMEngine
    from gofr_tpu.structured import compile_json_schema

    vocab = [bytes([i]) for i in range(min(256, cfg.vocab_size - 2))]
    vocab += [b""] * (cfg.vocab_size - len(vocab))
    eos = cfg.vocab_size - 1
    schema = {
        "type": "object",
        "properties": {
            "name": {"type": "string", "maxLength": 12},
            "count": {"type": "integer"},
            "ok": {"type": "boolean"},
        },
    }
    grammar = compile_json_schema(schema, vocab, eos)
    n_req = 2 * args.batch
    new_tokens = 120  # room for the grammar to close (worst-case value)
    prompts = [
        np.random.default_rng(3000 + i).integers(
            1, cfg.vocab_size - 2, size=max(8, args.prefill_len // 4),
        ).tolist()
        for i in range(n_req)
    ]

    def run(constrained: bool, spec_on: bool):
        # lookahead=1 for the acceptance COMPARISON: pipelined verifies
        # aim their drafts off predicted bonus tokens, and comparing
        # acceptance across content kinds should measure draft quality,
        # not pipeline-misaim noise (identical setting both sides)
        eng = LLMEngine(
            cfg, params, slots=min(args.batch, 32),
            max_seq_len=args.prefill_len + new_tokens + 32,
            decode_chunk=args.decode_chunk, admit_cap=args.admit_cap,
            quantize=quantize, speculative=spec_on, spec_draft=4,
            lookahead=1,
        )
        try:
            warm = [
                eng.submit(GenRequest(
                    list(p), max_new_tokens=8,
                    grammar=grammar if constrained else None,
                ))
                for p in prompts[:4]
            ]
            for r in warm:
                r.tokens()
            st0 = eng._spec_summary()
            t0 = time.perf_counter()
            reqs = [
                eng.submit(GenRequest(
                    list(p), max_new_tokens=new_tokens,
                    grammar=grammar if constrained else None,
                ))
                for p in prompts
            ]
            outs = [r.tokens(timeout=600) for r in reqs]
            wall = time.perf_counter() - t0
            total = sum(len(o) for o in outs)
            # per-step decode cadence p50: the mask's true device cost
            # (one table gather + select per sampled token), robust to
            # the early-eos batch drain that skews raw tok/s — a
            # completed grammar retires its request long before an
            # unconstrained neighbor's fixed budget
            step_p50 = (
                eng.stats()["phases"]["decode_step"].get("p50") or 0.0
            )
            st1 = eng._spec_summary()
            key = "constrained" if constrained else "unconstrained"
            prop = st1[key]["proposed"] - st0[key]["proposed"]
            acc = st1[key]["accepted"] - st0[key]["accepted"]
            valid = None
            if constrained:
                ok = 0
                for o in outs:
                    text = b"".join(
                        vocab[t] for t in o if 0 <= t < eos
                    ).decode("utf-8", "replace")
                    try:
                        obj = _json.loads(text)
                    except ValueError:
                        continue
                    try:
                        import jsonschema

                        jsonschema.validate(obj, schema)
                    except ImportError:
                        pass  # parse-only check without the library
                    except Exception:  # noqa: BLE001 — ValidationError etc.
                        continue  # counts against valid_frac, never crashes
                    ok += 1
                valid = ok / max(1, len(outs))
        finally:
            eng.close()
        return total / wall, step_p50, (acc / prop if prop else None), valid

    base_tok_s, base_step, _, _ = run(False, False)
    cons_tok_s, cons_step, _, valid_frac = run(True, False)
    _, _, acc_u, _ = run(False, True)
    spec_tok_s, _, acc_c, valid_spec = run(True, True)
    return {
        "requests": n_req, "new_tokens": new_tokens,
        "grammar_states": grammar.n_states,
        "unconstrained_tok_s": round(base_tok_s, 0),
        "constrained_tok_s": round(cons_tok_s, 0),
        "step_p50_unconstrained_ms": round(base_step * 1e3, 3),
        "step_p50_constrained_ms": round(cons_step * 1e3, 3),
        "mask_overhead": round(cons_step / max(base_step, 1e-9), 3),
        "valid_frac": valid_frac,
        "spec": {
            "constrained_tok_s": round(spec_tok_s, 0),
            "constrained_accept_rate": (
                round(acc_c, 3) if acc_c is not None else None
            ),
            "unconstrained_accept_rate": (
                round(acc_u, 3) if acc_u is not None else None
            ),
            "accept_delta": (
                round(acc_c - acc_u, 3)
                if acc_c is not None and acc_u is not None else None
            ),
            "valid_frac": valid_spec,
        },
    }


def _bench_multitenant(args, cfg, params, quantize: bool) -> dict:
    """Multi-tenant LoRA point (gofr_tpu.lora; docs/advanced-guide/
    multi-tenancy.md): decode tokens/s with 4 resident adapters decoded
    in ONE mixed batch (requests round-robin the tenants) vs the same
    engine's single-tenant baseline — the batched-delta claim is that N
    tenants ride the same fused programs for the cost of one rank-r
    einsum pair, so the ratio should hold >= ~0.9x. Alongside: adapter
    hot-load latency (host validate + device table stage, the time from
    "tenant uploaded a fine-tune" to "next submit can name it") and the
    publish-swap latency of repointing a live name at a staged v2."""
    import jax

    from gofr_tpu.llm import GenRequest, LLMEngine
    from gofr_tpu.lora import init_adapter

    n_adapters = 4
    new_tokens = 64
    n_req = 2 * args.batch
    prompts = [
        np.random.default_rng(4000 + i).integers(
            1, cfg.vocab_size - 2, size=max(8, args.prefill_len // 4),
        ).tolist()
        for i in range(n_req)
    ]
    names = [f"tenant{i}" for i in range(n_adapters)]
    adapters = [
        init_adapter(jax.random.PRNGKey(50 + i), cfg, rank=8, scale=0.05)
        for i in range(n_adapters + 1)  # +1: the v2 used by the swap
    ]
    eng = LLMEngine(
        cfg, params, slots=min(args.batch, 32),
        max_seq_len=args.prefill_len + new_tokens + 32,
        decode_chunk=args.decode_chunk, admit_cap=args.admit_cap,
        quantize=quantize, lora_slots=n_adapters + 2,
    )

    def run(tenants):
        warm = [
            eng.submit(GenRequest(
                list(p), max_new_tokens=8,
                adapter=tenants[i % len(tenants)] if tenants else "",
            ))
            for i, p in enumerate(prompts[:4])
        ]
        for r in warm:
            r.tokens()
        t0 = time.perf_counter()
        reqs = [
            eng.submit(GenRequest(
                list(p), max_new_tokens=new_tokens,
                adapter=tenants[i % len(tenants)] if tenants else "",
            ))
            for i, p in enumerate(prompts)
        ]
        total = sum(len(r.tokens(timeout=600)) for r in reqs)
        return total / (time.perf_counter() - t0)

    try:
        single_tok_s = run([])
        load_ms = []
        for name, ad in zip(names, adapters):
            t0 = time.perf_counter()
            eng.load_adapter(name, ad)
            load_ms.append((time.perf_counter() - t0) * 1e3)
        multi_tok_s = run(names)
        # hot swap while the pool is populated: stage tenant0's v2 under
        # a staging name, then atomically repoint the live name at it
        t0 = time.perf_counter()
        eng.load_adapter("tenant0@next", adapters[-1], version="v2")
        eng.publish_adapter("tenant0@next", "tenant0")
        swap_ms = (time.perf_counter() - t0) * 1e3
        snap = eng.adapters()
    finally:
        eng.close()
    return {
        "requests": n_req, "new_tokens": new_tokens,
        "adapters": n_adapters, "rank": 8,
        "single_tok_s": round(single_tok_s, 0),
        "multi_tok_s": round(multi_tok_s, 0),
        "ratio": round(multi_tok_s / max(single_tok_s, 1e-9), 3),
        "hot_load_ms": round(sum(load_ms) / len(load_ms), 1),
        "swap_ms": round(swap_ms, 1),
        "swaps": snap.get("swaps"), "evictions": snap.get("evictions"),
    }


def _bench_interactive_slo(args, cfg, params, quantize: bool) -> dict:
    """Interactive-SLO point (BENCH_r08+): mixed 16/120-token prompts at a
    FIXED offered load, reporting the tail metrics the chunked-prefill
    scheduler exists to move — TTFT p99, completion p99/p50, and
    per-step wall-time jitter. Fixed-rate (not capacity-relative) so
    rounds compare apples-to-apples: BENCH_r05's mixed point showed
    head-of-line TTFT (p50 804 ms) from bucket-padded monolithic waves;
    this point watches that tail directly."""
    from gofr_tpu.llm import LLMEngine

    S = args.prefill_len
    eng = LLMEngine(
        cfg, params, slots=args.batch,
        max_seq_len=S + args.new_tokens + 2 * args.decode_chunk,
        prefill_buckets=(max(16, S // 4), S), decode_chunk=args.decode_chunk,
        admit_cap=args.admit_cap, quantize=quantize,
        max_queue=4 * args.batch,
    )
    try:
        # floor the long length for tiny --prefill-len runs, but never
        # beyond S: max_seq_len is sized for S-token prompts, so anything
        # longer fails submit()'s decode-room check (ValueError, which
        # _open_loop does not shield) instead of serving
        long_len = min(max(24, S - 8), S)
        mixed = (min(16, long_len), long_len)
        # warm every step shape the mixed lengths touch
        _open_loop(eng, cfg, mixed, args.new_tokens, 50.0, 2.0)
        point = _open_loop(
            eng, cfg, mixed, args.new_tokens, args.interactive_rate,
            args.open_loop_s,
        )
        st = eng.stats()
        steps = st["phases"].get("step", {})
        decode = st["phases"].get("decode_step", {})
        point.update({
            "prompt_lens": list(mixed),
            "p99_over_p50": round(
                point["p99_ms"] / max(point["p50_ms"], 1e-9), 2
            ),
            "ttft_p99_over_p50": round(
                point["ttft_p99_ms"] / max(point["ttft_p50_ms"], 1e-9), 2
            ),
            "scheduler": st.get("scheduler"),
            "step_token_budget": st.get("step_token_budget"),
            # per-step wall-time jitter: the bounded-step claim in one
            # number — a monolithic wave path shows multi-ms spikes here
            "step_jitter": {
                "step_p50_ms": round(steps.get("p50", 0.0) * 1e3, 2),
                "step_p99_ms": round(steps.get("p99", 0.0) * 1e3, 2),
                "step_p99_over_p50": round(
                    steps.get("p99", 0.0) / max(steps.get("p50", 0.0), 1e-9), 2
                ) if steps.get("count") else 0.0,
                "decode_step_p50_ms": round(decode.get("p50", 0.0) * 1e3, 2),
                "decode_step_p99_ms": round(decode.get("p99", 0.0) * 1e3, 2),
            },
        })
    finally:
        eng.close()
    return point


def _bench_overload(args, cfg, params, quantize: bool,
                    ceiling_qps: float) -> dict:
    """Overload operating point (docs/advanced-guide/overload.md): open
    loop at ~2x the device ceiling with a 10:1 heavy:light batch client
    mix plus a low-rate interactive probe class. The numbers that matter
    under sustained excess demand: GOODPUT (completed req/s), shed rate
    (every shed carries a computed Retry-After), the interactive-vs-
    batch TTFT split (interactive stays flat while batch absorbs the
    pressure via fair queuing + preemption), and the Jain fairness index
    across the synthetic batch clients' completed tokens."""
    from concurrent.futures import ThreadPoolExecutor

    from gofr_tpu.llm import EngineOverloaded, GenRequest, LLMEngine

    S = args.prefill_len
    eng = LLMEngine(
        cfg, params, slots=args.batch,
        max_seq_len=S + args.new_tokens + 2 * args.decode_chunk,
        prefill_buckets=(max(16, S // 4), S), decode_chunk=args.decode_chunk,
        admit_cap=args.admit_cap, quantize=quantize,
        max_queue=8 * args.batch,
        # shed once the backlog prices a ~2 s first-token wait — at 2x
        # offered load the controller must shed roughly half the excess
        shed_predicted_wait_s=2.0,
    )
    duration = max(6.0, args.open_loop_s)
    offered = 2.0 * max(ceiling_qps, 1.0)
    # 10:1 heavy:light batch mix across 5 clients + interactive probes
    clients = [("heavy", offered * 10 / 14)] + [
        (f"light{i}", offered / 14) for i in range(4)
    ]
    probe_rate = max(2.0, offered * 0.05)
    rng = np.random.default_rng(7)
    lock = threading.Lock()
    stats = {
        "ok": 0, "shed": 0, "tokens": {},
        "ttft": {"interactive": [], "batch": []},
    }
    stop = threading.Event()
    pool = ThreadPoolExecutor(max_workers=1024)

    def consume(req, t_arrival, client, priority):
        first_t = None
        count = 0
        for _t in req.stream(timeout=600):
            if first_t is None:
                first_t = time.perf_counter() - t_arrival
            count += 1
        with lock:
            stats["ok"] += 1
            stats["tokens"][client] = stats["tokens"].get(client, 0) + count
            if first_t is not None:
                stats["ttft"][priority].append(first_t)

    def drive(client: str, rate: float, priority: str):
        t0 = time.perf_counter()
        n = max(1, int(rate * duration))
        arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))
        for i in range(n):
            if stop.is_set():
                return
            wait = arrivals[i] - (time.perf_counter() - t0)
            if wait > 0:
                time.sleep(wait)
            prompt = np.random.default_rng(i).integers(
                1, cfg.vocab_size, size=S - 8,
            ).tolist()
            try:
                req = eng.submit(GenRequest(
                    prompt, max_new_tokens=args.new_tokens,
                    priority=priority, client=client,
                ))
            except EngineOverloaded:
                with lock:
                    stats["shed"] += 1
                continue
            pool.submit(consume, req, t0 + arrivals[i], client, priority)

    threads = [
        threading.Thread(target=drive, args=(c, r, "batch"))
        for c, r in clients
    ]
    threads.append(
        threading.Thread(target=drive, args=("probe", probe_rate, "interactive"))
    )
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    # cancel any straggler BEFORE the engine closes (a driver still
    # pacing after its join timed out would hit a stopped engine and
    # skew the shed/ok counts with uncaught errors), then give it one
    # short join to observe the flag
    stop.set()
    for t in threads:
        t.join(timeout=5)
    pool.shutdown(wait=True)
    wall = time.perf_counter() - t_start
    st = eng.stats()
    eng.close()
    total = stats["ok"] + stats["shed"]
    # Jain index over the batch clients' WEIGHTED completed tokens (all
    # weight 1 here): (sum x)^2 / (n sum x^2); 1.0 is perfectly fair.
    # The heavy client's flood is 10x the offered rate of each light
    # client, so raw completions CANNOT be equal — fairness here means
    # each light client got its own demand served (no starvation), which
    # is what the per-client share vector feeds into the index.
    xs = [stats["tokens"].get(c, 0) for c, _ in clients]
    jain = (
        (sum(xs) ** 2) / (len(xs) * sum(x * x for x in xs))
        if any(xs) else 0.0
    )
    light_served = [stats["tokens"].get(f"light{i}", 0) for i in range(4)]
    it = stats["ttft"]["interactive"]
    bt = stats["ttft"]["batch"]
    return {
        "offered_qps": round(offered, 1),
        "duration_s": duration,
        "goodput_qps": round(stats["ok"] / wall, 1),
        "shed": stats["shed"],
        "shed_rate": round(stats["shed"] / max(1, total), 3),
        "sheds_predicted": st.get("sheds_predicted", 0),
        "preemptions": st.get("preemptions", 0),
        "ttft_interactive_p50_ms": round(_percentile(it, 0.5) * 1e3, 1) if it else None,
        "ttft_interactive_p99_ms": round(_percentile(it, 0.99) * 1e3, 1) if it else None,
        "ttft_batch_p50_ms": round(_percentile(bt, 0.5) * 1e3, 1) if bt else None,
        "ttft_batch_p99_ms": round(_percentile(bt, 0.99) * 1e3, 1) if bt else None,
        "jain_fairness": round(jain, 3),
        "client_tokens": {c: stats["tokens"].get(c, 0) for c, _ in clients},
        "light_client_spread": (
            round(min(light_served) / max(1, max(light_served)), 3)
        ),
        "clients": len(clients) + 1,
    }


def bench_mlp(args) -> dict:
    import jax

    from gofr_tpu.datasource.tpu import TPURuntime
    from gofr_tpu.logging import new_logger
    from gofr_tpu.metrics import new_metrics_manager
    from gofr_tpu.models import MLPConfig, mlp_forward, mlp_init

    metrics = new_metrics_manager()
    rt = TPURuntime(None, new_logger(level_name="ERROR"), metrics)
    cfg = MLPConfig()  # 784 -> 512 -> 256 -> 10, bf16
    params = mlp_init(jax.random.PRNGKey(0), cfg)
    rt.register_model(
        "mnist",
        lambda p, x: mlp_forward(p, x),
        params,
        example_args=(np.zeros(cfg.in_dim, np.float32),),
        max_batch=args.max_batch,
        max_delay_ms=args.max_delay_ms,
        max_inflight=args.max_inflight,
    )

    rng = np.random.default_rng(0)
    xs = rng.normal(size=(args.requests, cfg.in_dim)).astype(np.float32)
    latencies: list[float] = []

    async def one(sem, x):
        async with sem:
            t0 = time.perf_counter()
            out = await rt.infer_async("mnist", x)
            latencies.append(time.perf_counter() - t0)
            return out

    async def drive():
        sem = asyncio.Semaphore(args.concurrency)
        t0 = time.perf_counter()
        outs = await asyncio.gather(*[one(sem, x) for x in xs])
        return outs, time.perf_counter() - t0

    asyncio.run(drive())  # warm every bucket actually hit
    latencies.clear()
    outs, wall = asyncio.run(drive())
    assert len(outs) == args.requests and outs[0].shape == (cfg.out_dim,)

    qps = args.requests / wall
    out = {
        "metric": "mlp_serving_qps_per_chip",
        "value": round(qps, 1),
        "unit": "req/s",
        "vs_baseline": round(qps / 1000.0, 3),
        "detail": {
            # per-request p50 includes the device round trip (pipelined
            # batches keep QPS high regardless)
            "p50_ms": round(_percentile(latencies, 0.50) * 1e3, 3),
            "p99_ms": round(_percentile(latencies, 0.99) * 1e3, 3),
            "requests": args.requests,
            "platform": rt.platform,
            "device": rt.devices[0].device_kind if rt.devices else None,
        },
    }
    rt.close()
    return out


_GREET_CLIENT = r"""
import sys, time, threading, http.client, urllib.request
host, port, mode, nt, per = (
    sys.argv[1], int(sys.argv[2]), sys.argv[3], int(sys.argv[4]), int(sys.argv[5]),
)
lat, errs = [], []
lock = threading.Lock()
def ka_client(n):
    try:
        conn = http.client.HTTPConnection(host, port, timeout=10)
        local = []
        for _ in range(n):
            t0 = time.perf_counter()
            conn.request("GET", "/greet")
            r = conn.getresponse()
            assert r.status == 200
            r.read()
            local.append(time.perf_counter() - t0)
        conn.close()
        with lock:
            lat.extend(local)
    except BaseException as e:
        with lock:
            errs.append(repr(e))
def fresh_client(n):
    try:
        url = f"http://{host}:{port}/greet"
        local = []
        for _ in range(n):
            t0 = time.perf_counter()
            with urllib.request.urlopen(url, timeout=10) as r:
                assert r.status == 200
                r.read()
            local.append(time.perf_counter() - t0)
        with lock:
            lat.extend(local)
    except BaseException as e:
        with lock:
            errs.append(repr(e))
fn = ka_client if mode == "keepalive" else fresh_client
threads = [threading.Thread(target=fn, args=(per,)) for _ in range(nt)]
t0 = time.perf_counter()
[t.start() for t in threads]
[t.join() for t in threads]
wall = time.perf_counter() - t0
if errs:
    sys.exit("client errors: " + errs[0])
lat.sort()
import json
print(json.dumps({
    "qps": nt * per / wall,
    "p50": lat[len(lat) // 2],
    "p99": lat[min(len(lat) - 1, int(0.99 * len(lat)))],
}))
"""


def _greet_load(port: int, mode: str, nt: int, per: int) -> dict:
    """Run one load storm from a SEPARATE process. In-process clients
    share the GIL with the server's event loop and measure their own
    contention, not the server (r3 reported 703 QPS that way; the same
    server sustains ~4.4k from an external keep-alive client)."""
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "-c", _GREET_CLIENT, "127.0.0.1", str(port), mode,
         str(nt), str(per)],
        capture_output=True, text=True, timeout=300,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"greet load failed: {proc.stderr or proc.stdout}")
    return json.loads(proc.stdout)


def bench_greet(args) -> dict:
    """BASELINE config 1: stock app, GET /greet over real sockets.
    Load is generated out-of-process; keep-alive is the primary number
    (the reference league's benchmarks — wrk/hey against net/http — all
    use persistent connections), with a fresh-connection storm reported
    alongside. NOTE: this host has ONE core (os.cpu_count()==1), so
    client and server still share it; on multi-core hosts HTTP_WORKERS=N
    prefork raises this further (kernel-balanced SO_REUSEPORT accepts)."""
    import socket

    from gofr_tpu import App
    from gofr_tpu.config import new_mock_config

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        mport = s.getsockname()[1]
    app = App(config=new_mock_config({
        "APP_NAME": "bench", "HTTP_PORT": str(port), "METRICS_PORT": str(mport),
        "LOG_LEVEL": "ERROR",
    }))
    app.get("/greet", lambda ctx: "Hello World!")
    app.run_in_background()

    # modest client concurrency, like wrk/hey defaults: hundreds of client
    # THREADS on a small host measure client-side thrash (512 threads on
    # this 1-core box: p50 108 ms, QPS 1.3k vs 4.4k at 8 threads)
    nthreads = min(args.clients, 8)
    per = max(1, args.requests // nthreads)
    storm = _greet_load(port, "keepalive", nthreads, per)
    fresh = _greet_load(port, "fresh", nthreads, max(1, per // 2))
    lone = _greet_load(port, "keepalive", 1, 200)
    app.shutdown()
    return {
        "metric": "greet_qps_cpu",
        "value": round(storm["qps"], 1),
        "unit": "req/s",
        "vs_baseline": 1.0,  # no reference number exists (BASELINE.md: none published; Go toolchain absent)
        "detail": {
            "p50_ms": round(storm["p50"] * 1e3, 3),
            "p99_ms": round(storm["p99"] * 1e3, 3),
            "fresh_conn_qps": round(fresh["qps"], 1),
            "fresh_conn_p50_ms": round(fresh["p50"] * 1e3, 3),
            "uncongested_p50_ms": round(lone["p50"] * 1e3, 3),
            "uncongested_p99_ms": round(lone["p99"] * 1e3, 3),
            "requests": per * nthreads,
            "clients": nthreads,
            "host_cores": os.cpu_count(),
        },
    }


# ---------------------------------------------------------------------------
# scale-out: router tier over N engine PROCESSES (docs/advanced-guide/
# scale-out.md). Runs entirely via subprocesses — the bench process never
# initializes jax for this mode.
# ---------------------------------------------------------------------------

def _scaleout_spawn_engine(idx: int) -> dict:
    import subprocess
    import sys

    from gofr_tpu.router.autoscaler import free_port

    port, mport = free_port(), free_port()
    env = {
        **os.environ,
        "PYTHONPATH": os.path.dirname(os.path.abspath(__file__))
        + os.pathsep + os.environ.get("PYTHONPATH", ""),
        "ENGINE_SLOTS": os.environ.get("ENGINE_SLOTS", "8"),
        "ENGINE_MAX_QUEUE": "30000",
        "ENGINE_WARMUP": "0",
        "ENGINE_LOG_LEVEL": "ERROR",
        # no session/prefix retention: identical bench prompts would
        # otherwise flip the radix cache between hit/miss regimes under
        # pool pressure — bimodal throughput masquerading as (non-)
        # scaling. The QPS point measures honest prefill+decode.
        "ENGINE_SESSION_MB": "0",
        "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
        # tiny-model ops gain nothing from intra-op threading, and N
        # engine processes each spawning a whole-machine eigen pool
        # would thrash each other off the linearity the bench measures
        "XLA_FLAGS": (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_cpu_multi_thread_eigen=false"
        ).strip(),
    }
    proc = subprocess.Popen(
        [sys.executable, "-m", "gofr_tpu.router.engine_stub",
         "--port", str(port), "--metrics-port", str(mport),
         "--engine-id", f"e{idx}"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
    )
    return {"port": port, "metrics_port": mport, "proc": proc}


def _scaleout_spawn_router(engine_ports: list[int], max_inflight: int) -> dict:
    import subprocess
    import sys

    from gofr_tpu.router.autoscaler import free_port

    port, mport = free_port(), free_port()
    env = {
        **os.environ,
        "PYTHONPATH": os.path.dirname(os.path.abspath(__file__))
        + os.pathsep + os.environ.get("PYTHONPATH", ""),
        "HTTP_PORT": str(port), "METRICS_PORT": str(mport),
        "LOG_LEVEL": "ERROR", "REQUEST_TIMEOUT": "600",
        "TPU_ROUTER_BACKENDS": ",".join(
            f"http://127.0.0.1:{p}" for p in engine_ports
        ),
        "TPU_ROUTER_POLL_INTERVAL_S": "0.2",
        "TPU_ROUTER_PROXY_TIMEOUT_S": "600",
        "TPU_ROUTER_UPSTREAM_TIMEOUT_S": "600",
        "TPU_ROUTER_MAX_INFLIGHT": str(max_inflight),
    }
    proc = subprocess.Popen(
        [sys.executable, "-m", "gofr_tpu.router"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
    )
    return {"port": port, "metrics_port": mport, "proc": proc}


def _scaleout_wait_http(port: int, path: str, ok, timeout_s: float) -> None:
    import urllib.request

    deadline = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=3
            ) as r:
                if ok(r):
                    return
        except Exception as e:  # noqa: BLE001 — still booting
            last = e
        time.sleep(0.1)
    raise RuntimeError(f"http://127.0.0.1:{port}{path} not ready: {last!r}")


def _scaleout_post(port: int, path: str, payload: dict, timeout: float = 120):
    import urllib.request

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _scaleout_serial_p50(port: int, n: int, path: str = "/echo") -> float:
    """Serial request latencies over ONE keep-alive connection —
    identical request direct-vs-routed isolates the hop cost. The
    default /echo path carries no engine work, so scheduler
    quantization (admit delay, step cadence) can't pollute the delta."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    body = json.dumps(
        {"tokens": list(range(1, 9)), "max_new_tokens": 1}
    ).encode()
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        conn.request("POST", path, body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        resp.read()
        times.append(time.perf_counter() - t0)
    conn.close()
    return _percentile(times, 0.5)


def _scaleout_closed_loop(ports: list[int], clients: int, warm_s: float,
                          window_s: float, new_tokens: int) -> dict:
    """Closed-loop QPS through the router tier: `clients` concurrent
    asyncio clients (the framework's own pooled streaming client — one
    socket per in-flight request, keep-alive reuse between turns) split
    across the router replicas, counted over a steady window after a
    ramp."""
    from gofr_tpu.service import HTTPService

    done = {"n": 0, "errors": 0, "ramp_errors": 0, "counting": False}

    async def run():
        svcs = [HTTPService(f"http://127.0.0.1:{p}") for p in ports]
        for svc in svcs:
            svc._pool.max_idle = clients // len(svcs) + 16
        stop = asyncio.Event()

        async def client(i: int):
            svc = svcs[i % len(svcs)]
            # distinct prompts per client lane: identical prompts would
            # all share one radix prefix and measure the cache, not the
            # fleet
            base = (i % 64) + 1
            payload = json.dumps({
                "tokens": list(range(base, base + 8)),
                "max_new_tokens": new_tokens,
            }).encode()
            headers = {"Content-Type": "application/json",
                       "X-GoFr-Client": f"c{i % 64}"}
            while not stop.is_set():
                try:
                    st = await svc.astream(
                        "POST", "/generate", body=payload, headers=headers,
                        timeout=600,
                    )
                    await st.aread()
                    if st.status_code < 400:
                        if done["counting"]:
                            done["n"] += 1
                    elif done["counting"]:  # steady-window errors only:
                        done["errors"] += 1  # the ramp's dial storm is
                    else:  # not the steady-state contract under test
                        done["ramp_errors"] += 1
                except asyncio.CancelledError:
                    raise
                except Exception:  # noqa: BLE001 — errors ARE data
                    key = "errors" if done["counting"] else "ramp_errors"
                    done[key] += 1
                    await asyncio.sleep(0.05)

        tasks = []
        for i in range(clients):
            tasks.append(asyncio.ensure_future(client(i)))
            if i % 200 == 199:
                await asyncio.sleep(0.05)  # stagger the dial storm
        await asyncio.sleep(warm_s)
        done["counting"] = True
        t0 = time.monotonic()
        await asyncio.sleep(window_s)
        done["counting"] = False
        elapsed = time.monotonic() - t0
        stop.set()
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        for svc in svcs:
            svc.close()
        return elapsed

    elapsed = asyncio.run(run())
    return {
        "qps": done["n"] / elapsed,
        "completed": done["n"],
        "errors": done["errors"],
        "ramp_errors": done["ramp_errors"],
        "window_s": round(elapsed, 2),
    }


def _scaleout_warm_engine(port: int) -> None:
    """Warm one engine stub for the closed-loop phases: CONCURRENT
    rounds, not serial ones — full-width admission and full-slot decode
    programs only compile once multiple requests arrive together, and a
    compile inside the measurement window would masquerade as (negative)
    scaling noise."""
    for _ in range(2):
        threads = []
        for _i in range(24):
            t = threading.Thread(target=lambda: _scaleout_post(
                port, "/generate",
                {"tokens": list(range(1, 9)), "max_new_tokens": 8},
            ))
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=120)


def _scaleout_pool_hits(metrics_port: int) -> dict:
    import urllib.request

    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{metrics_port}/metrics", timeout=5
        ) as r:
            expo = r.read().decode()
    except Exception:  # noqa: BLE001
        return {}
    out = {"hit": 0.0, "dial": 0.0}
    for line in expo.splitlines():
        if line.startswith("app_http_service_conn_pool_total"):
            for key in out:
                if f'result="{key}"' in line:
                    out[key] += float(line.rsplit(" ", 1)[1])
    return out


def bench_scaleout(args) -> dict:
    """QPS linearity across engine PROCESSES: closed-loop QPS through
    the front router at 1/2/4 backend processes under `--scaleout-clients`
    concurrent clients, plus the router-added serial p50 overhead
    (direct-to-engine vs via-router, identical request). Fresh engines
    per point — a prior point's backlog must not pollute the next."""
    import resource

    procs_list = [int(x) for x in args.scaleout_procs.split(",") if x]
    clients = args.scaleout_clients
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    inf = resource.RLIM_INFINITY
    if soft != inf and (hard == inf or hard > soft):
        try:  # each concurrent client holds one socket in this process
            resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
            soft = hard
        except (ValueError, OSError):
            pass
    if soft != inf and soft >= 0:  # unlimited -> no clamp at all
        cap = max(64, soft - 2048)
        if clients > cap:
            print(f"scaleout: clamping clients {clients} -> {cap} "
                  f"(RLIMIT_NOFILE {soft})")
            clients = cap

    def kill(procs):
        for p in procs:
            try:
                p["proc"].kill()
            except Exception:  # noqa: BLE001
                pass
        for p in procs:
            try:
                p["proc"].wait(timeout=10)
            except Exception:  # noqa: BLE001
                pass

    # -- router hop overhead: one engine, serial, identical request -----
    engines = [_scaleout_spawn_engine(0)]
    router = None
    try:
        _scaleout_wait_http(
            engines[0]["port"], "/.well-known/alive",
            lambda r: r.status == 200, 120,
        )
        for _ in range(6):  # compile + warm the stub programs
            _scaleout_post(
                engines[0]["port"], "/generate",
                {"tokens": list(range(1, 9)), "max_new_tokens": 8},
            )
        n_serial = 400
        _scaleout_serial_p50(engines[0]["port"], 30)  # warm the edge
        direct_p50 = _scaleout_serial_p50(engines[0]["port"], n_serial)
        direct_gen_p50 = _scaleout_serial_p50(
            engines[0]["port"], 100, path="/generate"
        )
        router = _scaleout_spawn_router(
            [engines[0]["port"]], args.scaleout_max_inflight
        )
        _scaleout_wait_http(
            router["port"], "/.well-known/router",
            lambda r: all(
                b["accepting"]
                for b in json.loads(r.read())["data"]["fleet"]["backends"]
            ), 60,
        )
        _scaleout_serial_p50(router["port"], 30)  # warm the hop path
        routed_p50 = _scaleout_serial_p50(router["port"], n_serial)
        routed_gen_p50 = _scaleout_serial_p50(
            router["port"], 100, path="/generate"
        )
        overhead_ms = (routed_p50 - direct_p50) * 1e3
    finally:
        kill(engines + ([router] if router else []))

    # -- QPS vs process count -------------------------------------------
    # QPS vs process count. The router tier itself is stateless, so it
    # runs REPLICATED (like any production front tier) — a constant
    # count across phases, sized so one Python event loop's ~1 ms/req
    # ceiling never masquerades as an engine limit. Clients split
    # round-robin across router replicas; every router sees every
    # engine.
    points = []
    n_routers = args.scaleout_routers
    for n in procs_list:
        engines = [_scaleout_spawn_engine(i) for i in range(n)]
        routers = []
        try:
            for e in engines:
                _scaleout_wait_http(
                    e["port"], "/.well-known/alive",
                    lambda r: r.status == 200, 120,
                )
            for e in engines:  # compile/warm every backend directly
                _scaleout_warm_engine(e["port"])
            routers = [
                _scaleout_spawn_router(
                    [e["port"] for e in engines], args.scaleout_max_inflight
                )
                for _ in range(n_routers)
            ]
            for router in routers:
                _scaleout_wait_http(
                    router["port"], "/.well-known/router",
                    lambda r: sum(
                        b["accepting"] for b in
                        json.loads(r.read())["data"]["fleet"]["backends"]
                    ) == n, 60,
                )
            ramp = max(3.0, clients / 3000)
            res = _scaleout_closed_loop(
                [r["port"] for r in routers], clients, warm_s=ramp + 2.0,
                window_s=args.scaleout_window_s, new_tokens=8,
            )
            res["procs"] = n
            pool = {"hit": 0.0, "dial": 0.0}
            for router in routers:
                for k, v in _scaleout_pool_hits(
                    router["metrics_port"]
                ).items():
                    pool[k] += v
            res["pool"] = pool
            points.append(res)
            print(f"scaleout {n}p: {res['qps']:.1f} qps "
                  f"({res['completed']} done, {res['errors']} errors)")
        finally:
            kill(engines + routers)

    by_n = {p["procs"]: p for p in points}
    # scaling ratios only exist relative to a MEASURED 1-process point:
    # with `--scaleout-procs 2,4` (or a baseline that completed nothing)
    # a fabricated denominator would land absurd x-factors in the BENCH
    # summary line as if measured — report null instead
    qps1 = by_n.get(1, {}).get("qps") or None
    scaling = {
        f"x{n}": (round(by_n[n]["qps"] / qps1, 2) if qps1 else None)
        for n in by_n if n != 1
    }
    top = max(by_n)
    return {
        "metric": "scaleout_qps",
        "value": round(by_n[top]["qps"], 1),
        "unit": f"req/s ({top} engine processes, 8-tok completions)",
        "vs_baseline": (
            round(by_n[top]["qps"] / (qps1 * top), 3) if qps1 else None
        ),
        "detail": {
            "scaleout": {
                "clients": clients,
                "window_s": args.scaleout_window_s,
                "points": [
                    {k: (round(v, 2) if isinstance(v, float) else v)
                     for k, v in p.items()} for p in points
                ],
                "qps_scaling": scaling,
                "router_overhead_p50_ms": round(overhead_ms, 3),
                "direct_p50_ms": round(direct_p50 * 1e3, 2),
                "routed_p50_ms": round(routed_p50 * 1e3, 2),
                "direct_generate_p50_ms": round(direct_gen_p50 * 1e3, 2),
                "routed_generate_p50_ms": round(routed_gen_p50 * 1e3, 2),
                "host_cores": os.cpu_count(),
            },
        },
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    import sys

    # `bench.py scaleout` (ISSUE 13 spelling) == `--model scaleout`
    if len(sys.argv) > 1 and sys.argv[1] == "scaleout":
        sys.argv[1:2] = ["--model", "scaleout"]
    ap.add_argument(
        "--model", choices=("serving", "mlp", "greet", "scaleout"),
        default=None,
        help="default: serving on TPU, mlp on CPU (2B init on CPU is minutes)",
    )
    # gemma serving knobs (defaults = measured sweet spot on v5e:
    # 128 slots x 16-wave admission keeps the prefill/decode pipeline at
    # ~92% of the device-serial ceiling)
    ap.add_argument("--batch", type=int, default=128, help="engine slots")
    ap.add_argument("--prefill-len", type=int, default=128)
    ap.add_argument("--decode-chunk", type=int, default=16)
    ap.add_argument("--admit-cap", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--clients", type=int, default=512)
    ap.add_argument(
        "--no-quantize", dest="quantize", action="store_false", default=True,
        help="serve bf16 weights instead of int8 (int8 is the TPU default)",
    )
    ap.add_argument("--no-open-loop", action="store_true",
                    help="skip the open-loop latency-vs-load sweep")
    ap.add_argument("--open-loop-s", type=float, default=6.0,
                    help="duration of each open-loop rate point")
    ap.add_argument("--no-short", action="store_true",
                    help="skip the short-prompt north-star operating point")
    ap.add_argument("--no-mixed", action="store_true",
                    help="skip the mixed-length-prompt run")
    ap.add_argument("--no-long-context", action="store_true",
                    help="skip the 4k-prompt sliding-window operating point")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="skip the 50%%-shared-prefix prefix-cache point")
    ap.add_argument("--no-sharded", action="store_true",
                    help="skip the TP-scaling + disaggregated point")
    ap.add_argument("--no-sessions", action="store_true",
                    help="skip the sessions point (paged KV pool: "
                         "bytes/idle-session, cold resume, paged vs "
                         "contiguous tok/s)")
    ap.add_argument("--no-spec", action="store_true",
                    help="skip the speculative-decoding point (spec-on vs "
                         "spec-off tokens/s + acceptance rate)")
    ap.add_argument("--no-structured", action="store_true",
                    help="skip the structured-decoding point (constrained "
                         "vs unconstrained tokens/s + spec acceptance delta)")
    ap.add_argument("--no-obs-overhead", action="store_true",
                    help="skip the observability-overhead point (flight "
                         "recorder + anomaly + wide events + metrics on vs "
                         "all off; claim: <=3% decode overhead)")
    ap.add_argument("--no-goodput", action="store_true",
                    help="skip the goodput-ledger point (device-time "
                         "attribution on vs off; goodput ratio + waste "
                         "split; claim: <=3% decode overhead)")
    ap.add_argument("--no-multitenant", action="store_true",
                    help="skip the multi-tenant LoRA point (4-adapter "
                         "mixed decode vs single-tenant + swap latency)")
    ap.add_argument("--no-interactive-slo", action="store_true",
                    help="skip the mixed-prompt interactive-SLO point")
    ap.add_argument("--no-degraded", action="store_true",
                    help="skip the degraded-operation point (replica kill "
                         "mid-run; needs >=2 devices)")
    ap.add_argument("--no-rollout", action="store_true",
                    help="skip the live weight-rollout point (2-replica "
                         "shift under load; needs >=2 devices)")
    ap.add_argument("--no-overload", action="store_true",
                    help="skip the overload point (2x offered load, fair "
                         "queuing + shed telemetry)")
    ap.add_argument("--interactive-rate", type=float, default=250.0,
                    help="fixed offered load (req/s) for the interactive-"
                         "SLO point — fixed so rounds compare directly")
    ap.add_argument("--lc-prompt", type=int, default=4096,
                    help="long-context prompt bucket")
    ap.add_argument("--lc-window", type=int, default=1024,
                    help="long-context sliding window")
    ap.add_argument("--no-subruns", action="store_true",
                    help="skip the greet/mlp sub-benchmarks (configs 1-2)")
    ap.add_argument("--model-size", choices=("2b", "7b"), default="2b",
                    help="7b: Gemma-7B int8 single-chip (doesn't fit bf16)")
    # shared knobs
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--concurrency", type=int, default=512)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--max-inflight", type=int, default=32)
    ap.add_argument("--max-delay-ms", type=float, default=1.0)
    # scale-out (router tier over engine processes; CPU harness)
    ap.add_argument("--scaleout-procs", default="1,2,4",
                    help="engine process counts to measure, comma-separated")
    ap.add_argument("--scaleout-clients", type=int, default=10000,
                    help="concurrent closed-loop clients through the router "
                         "(clamped to the fd limit)")
    ap.add_argument("--scaleout-window-s", type=float, default=8.0,
                    help="steady measurement window per process count")
    ap.add_argument("--scaleout-max-inflight", type=int, default=512,
                    help="router upstream in-flight cap (queues the rest "
                         "at the router; bounds sockets and engine queues)")
    ap.add_argument("--scaleout-routers", type=int, default=2,
                    help="router replicas (stateless tier; constant across "
                         "phases so QPS ratios isolate ENGINE scaling)")
    args = ap.parse_args()

    if args.model == "scaleout":
        # subprocess-only mode: the bench process itself never touches jax
        result = bench_scaleout(args)
        print(json.dumps(result))
        print(json.dumps(_summary_line(result)))
        return

    # config-1 greet subprocess runs BEFORE jax touches this process (the
    # whole point of the isolation — see _greet_subprocess). --model greet
    # itself must not recurse; mlp-only (CPU) runs skip it too.
    args._greet_sub = None
    if args.model in (None, "serving") and not args.no_subruns:
        args._greet_sub = _greet_subprocess()

    import jax

    if args.model is None:
        args.model = "serving" if jax.default_backend() == "tpu" else "mlp"
    if args.requests is None:
        args.requests = {"serving": 2048, "mlp": 4096, "greet": 2000}[args.model]

    result = {
        "serving": bench_serving, "mlp": bench_mlp, "greet": bench_greet,
    }[args.model](args)
    print(json.dumps(result))
    # Compact summary as the FINAL line. The driver records only the tail
    # of this output; in round 4 that clipped the headline metric/value out
    # of the artifact (they print first in the full JSON above). This line
    # is small enough to always survive a 2000-byte tail and is itself a
    # complete {"metric": ...} JSON object.
    print(json.dumps(_summary_line(result)))


def _summary_line(result: dict) -> dict:
    d = result.get("detail") or {}
    s = {k: result[k] for k in ("metric", "value", "unit", "vs_baseline")}
    for key in ("engine_vs_ceiling", "device_ceiling_sustained_qps", "device"):
        if key in d:
            s[key] = d[key]
    if d.get("slo_point"):
        s["slo_steady_qps"] = d["slo_point"].get("steady_qps")
        s["slo_p99_over_p50"] = d["slo_point"].get("p99_over_p50")
        pb = d["slo_point"].get("phase_breakdown")
        if pb:  # compact: {phase: [p50_ms, p99_ms]}
            s["phase_breakdown"] = {
                k: [v["p50"], v["p99"]] for k, v in pb.items()
            }
        mfu = d["slo_point"].get("mfu")
        if mfu:  # utilization context for the QPS number (BENCH_r07+)
            s["mfu"] = {
                k: mfu[k] for k in
                ("decode_p50", "prefill_p50", "tokens_per_s_per_chip_p50",
                 "bound")
                if k in mfu
            }
    if d.get("warmup"):  # cold-start bill: warm wall + compile totals
        s["warmup"] = {
            k: d["warmup"][k] for k in
            ("warmup_s", "programs", "compile_s_total")
            if k in d["warmup"]
        }
    if d.get("short_prompt_8tok"):
        sp = d["short_prompt_8tok"]
        s["short_prompt_qps"] = sp.get("qps")
        lvl = sp.get("latency_vs_load") or []
        if lvl:
            s["short_prompt_lowload_p50_ms"] = lvl[0].get("p50_ms")
    if d.get("long_context"):
        lc = d["long_context"]
        s["long_context_qps"] = lc.get("qps")
        s["long_context_kv_slab_mb"] = lc.get("kv_slab_mb")
    if d.get("prefix_cache"):
        pc = d["prefix_cache"]
        s["prefix_cache_qps"] = pc.get("qps")
        s["prefix_vs_ceiling"] = pc.get("qps_vs_no_cache_ceiling")
    if d.get("sessions"):  # BENCH_r14+: paged KV pool + session tier
        se = d["sessions"]
        s["sessions"] = {
            "paged_vs_contig": se.get("paged_vs_contig"),
            "int8_vs_contig": se.get("int8_vs_contig"),
            "idle_session_vs_slot": se.get("idle_session_vs_slot"),
            "hbm_bytes_per_idle_session": se.get("hbm_bytes_per_idle_session"),
            "second_turn_ttft_ms": se.get("second_turn_ttft_ms"),
            "cold_resume_ttft_ms": se.get("cold_resume_ttft_ms"),
            "resume_vs_reprefill": se.get("resume_vs_reprefill"),
        }
    if d.get("sharded"):  # BENCH_r15+: TP submeshes + disaggregation
        sh = d["sharded"]
        row = {}
        for tp in ("tp2", "tp4"):
            if tp in (sh.get("tp") or {}):
                row[f"{tp}_decode_scaling"] = sh["tp"][tp].get(
                    "decode_scaling_vs_tp1"
                )
                row[f"{tp}_qps_scaling"] = sh["tp"][tp].get(
                    "qps_scaling_vs_tp1"
                )
        dg = sh.get("disagg") or {}
        row["disagg_ttft_p99_vs_colocated"] = dg.get("ttft_p99_vs_colocated")
        row["disagg_p99_over_p50"] = dg.get("disagg_p99_over_p50")
        row["handoff_p99_ms"] = dg.get("handoff_p99_ms")
        s["sharded"] = row
    if d.get("speculative"):  # BENCH_r12+: spec-on vs spec-off decode
        sp = d["speculative"]
        s["speculative"] = {
            "rep_speedup": (sp.get("repetitive") or {}).get("speedup"),
            "rep_accept_rate": (sp.get("repetitive") or {}).get("accept_rate"),
            "rep_spec_tok_s": (sp.get("repetitive") or {}).get("spec_tok_s"),
            "nat_speedup": (sp.get("natural") or {}).get("speedup"),
        }
    if d.get("structured"):  # grammar-constrained decoding point
        st = d["structured"]
        s["structured"] = {
            "mask_overhead": st.get("mask_overhead"),
            "constrained_tok_s": st.get("constrained_tok_s"),
            "valid_frac": st.get("valid_frac"),
            "spec_accept_delta": (st.get("spec") or {}).get("accept_delta"),
            "spec_accept_constrained": (st.get("spec") or {}).get(
                "constrained_accept_rate"
            ),
        }
    if d.get("obs_overhead"):  # flight recorder + anomaly + wide events
        ob = d["obs_overhead"]
        s["obs_overhead"] = {
            "base_tok_s": ob.get("base_tok_s"),
            "obs_tok_s": ob.get("obs_tok_s"),
            "overhead_frac": ob.get("overhead_frac"),
            "within_claim": ob.get("within_claim"),
        }
    if d.get("goodput"):  # device-time attribution + waste taxonomy
        gp = d["goodput"]
        s["goodput"] = {
            "goodput_ratio": gp.get("goodput_ratio"),
            "overhead_frac": gp.get("overhead_frac"),
            "within_claim": gp.get("within_claim"),
            "waste_frac": gp.get("waste_frac"),
        }
    if d.get("multitenant"):  # batched-LoRA multi-tenant point
        mt = d["multitenant"]
        s["multitenant"] = {
            "adapters": mt.get("adapters"),
            "single_tok_s": mt.get("single_tok_s"),
            "multi_tok_s": mt.get("multi_tok_s"),
            "ratio": mt.get("ratio"),
            "hot_load_ms": mt.get("hot_load_ms"),
            "swap_ms": mt.get("swap_ms"),
        }
    if d.get("interactive_slo"):  # BENCH_r08+: chunked-prefill tail view
        isl = d["interactive_slo"]
        s["interactive_slo"] = {
            "offered_qps": isl.get("offered_qps"),
            "steady_qps": isl.get("steady_qps"),
            "ttft_p99_ms": isl.get("ttft_p99_ms"),
            "p99_over_p50": isl.get("p99_over_p50"),
            "step_p99_over_p50": (isl.get("step_jitter") or {}).get(
                "step_p99_over_p50"
            ),
        }
    if d.get("degraded") and not d["degraded"].get("skipped"):
        dg = d["degraded"]  # BENCH_r09+: resilience blast radius
        s["degraded"] = {
            "error_rate": dg.get("error_rate"),
            "failovers": dg.get("failovers"),
            "time_to_restored_s": dg.get("time_to_restored_s"),
            # BENCH_r11+: device-health phase (sick device -> quarantine
            # -> elastic/reintegrated capacity)
            "time_to_quarantine_s": dg.get("time_to_quarantine_s"),
            "time_to_reintegrated_capacity_s": dg.get(
                "time_to_reintegrated_capacity_s"
            ),
        }
    if d.get("overload"):  # BENCH_r10+: demand-side robustness
        ov = d["overload"]
        s["overload"] = {
            "goodput_qps": ov.get("goodput_qps"),
            "shed_rate": ov.get("shed_rate"),
            "ttft_interactive_p99_ms": ov.get("ttft_interactive_p99_ms"),
            "ttft_batch_p99_ms": ov.get("ttft_batch_p99_ms"),
            "jain_fairness": ov.get("jain_fairness"),
            "preemptions": ov.get("preemptions"),
        }
    if d.get("rollout") and not d["rollout"].get("skipped"):
        ro = d["rollout"]  # BENCH_r13+: live weight reload under load
        s["rollout"] = {
            "state": ro.get("state"),
            "errors": ro.get("errors"),
            "time_to_fully_shifted_s": ro.get("time_to_fully_shifted_s"),
            "p99_shift_delta": ro.get("p99_shift_delta"),
        }
    if d.get("scaleout"):  # BENCH_r16+: router tier QPS linearity
        sc = d["scaleout"]
        row = {
            f"qps_{p['procs']}p": p.get("qps")
            for p in (sc.get("points") or [])
        }
        row.update(sc.get("qps_scaling") or {})
        row["router_overhead_p50_ms"] = sc.get("router_overhead_p50_ms")
        row["clients"] = sc.get("clients")
        errors = sum(p.get("errors", 0) for p in (sc.get("points") or []))
        row["errors"] = errors
        s["scaleout"] = row
    if d.get("subruns"):
        s["greet_qps"] = d["subruns"].get("greet_qps_cpu")
        s["mlp_qps"] = d["subruns"].get("mlp_qps")
    if "p50_ms" in d:
        s["p50_ms"] = d["p50_ms"]
    return s


if __name__ == "__main__":
    main()
