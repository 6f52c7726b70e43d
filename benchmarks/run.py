#!/usr/bin/env python3
"""The benchmark: one cell, one process, one last line.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds `workloads/<name>.json` -> `configs/<config>.json` ->
`families/<family>.py` (the one place that knows the configuration's
architecture: the program's config and weights, the plain reference, the
cost functions) -> every `metrics/*.py` whose META lists the cell, all by
name: a later PR adds a cell, a configuration, an architecture or a per-layer
metric by adding files and entries in BENCHMARK.json, and edits nothing here.
Of the configuration's `model` group the harness reads `vocab_size` (the
traffic's ids) and nothing else.

Set-up (weights from the seed on the device, the engine built through
`app.container.tpu().register_llm`, its warm-up, the ramp of the load) ends
when the window opens. The window drives the LLM handle for `--seconds` and
to the end of the step then in flight; then the load is closed, the device's peak is read, the program's state is
freed and the plain reference judges a sample of what the window served.
No TPU -> exit 2 and no result, unless `--rehearse` (CPU, the tiny twin of
the configuration, every line stamped as a rehearsal).
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path[:0] = [HERE, REPO]

import load as L  # noqa: E402
import stats as S  # noqa: E402
import traffic  # noqa: E402

TAG = "[bench] "
REQUEST_TIMEOUT_S = 120.0  # a request that yields nothing for this long has failed
RAMP_TIMEOUT_S = 300.0  # every client has its first token by then, or the run has no result


def say(msg: str) -> None:
    print(TAG + msg, file=sys.stderr, flush=True)


class RunFault(Exception):
    """The run cannot give a result (not: the result is incorrect)."""


# -- finding the cell's files ------------------------------------------------


def load_cell(name: str, rehearse: bool) -> tuple[dict, dict]:
    path = os.path.join(HERE, "workloads", name + ".json")
    if not os.path.isfile(path):
        raise RunFault(f"no workload file {path}")
    workload = traffic.load(path)
    config = traffic.load(os.path.join(HERE, "configs", workload["config"] + ".json"))
    if rehearse:  # the tiny twin: the same code at CPU sizes
        config = {**config, **config["rehearsal"]}
        workload = {**workload, **workload["rehearsal"]}
    return workload, config


def load_file(kind: str, path: str):
    """The module in one file of `metrics/` or `families/`."""
    stem = os.path.basename(path)[:-3]
    spec = importlib.util.spec_from_file_location(kind + "_" + re.sub(r"\W", "_", stem), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FAMILY_INTERFACE = ("program_config", "program_params", "gaps", "forward_logits",
                    "least_step_seconds", "decode_kv_read_bytes")


def load_family(config: dict):
    """`families/<family>.py`, by the name in the configuration's file. No
    default: a configuration says which architecture it is."""
    name = config.get("family")
    if not name:
        raise RunFault("the configuration's file names no `family` (benchmarks/families/<family>.py)")
    path = os.path.join(HERE, "families", name + ".py")
    if not os.path.isfile(path):
        raise RunFault(f"the configuration names the family {name!r}, and there is no {path}")
    mod = load_file("family", path)
    missing = [n for n in FAMILY_INTERFACE if not callable(getattr(mod, n, None))]
    if missing:
        raise RunFault(f"{path} lacks {missing} of the family's interface {list(FAMILY_INTERFACE)}")
    return mod


def load_metrics(cell: str) -> list:
    """Every metrics/<name>.py whose META['workloads'] names the cell (or
    has none: then every cell that reports what it moves)."""
    out = []
    folder = os.path.join(HERE, "metrics")
    for fn in sorted(os.listdir(folder)):
        if not fn.endswith(".py") or fn.startswith("_"):
            continue
        mod = load_file("metric", os.path.join(folder, fn))
        cells = mod.META.get("workloads")
        if cells is None or cell in cells:
            out.append(mod)
    return out


# -- the system under test ---------------------------------------------------


def expected_paths_ok(paths: dict, expect: dict) -> str:
    """'' when the engine traced the attention paths the configuration
    names, else what differs."""
    if not paths["decode"].startswith(expect["decode"]):
        return f"decode traced {paths['decode']!r}, the configuration names {expect['decode']!r}"
    for shape, path in paths["prefill"].items():
        if not path.startswith(expect["prefill"]):
            return f"prefill chunk {shape} traced {path!r}, the configuration names {expect['prefill']!r}"
    return ""


def compile_count(snapshot: dict) -> int:
    return int(snapshot["totals"]["compiles"])


# -- the run -------------------------------------------------------------------


def run(args) -> int:
    global TAG
    rehearse = args.rehearse
    if rehearse:
        TAG = "[bench REHEARSAL cpu, tiny twin: proves the script, not the chip] "
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.update({"LOG_LEVEL": "ERROR", "TRACE_EXPORTER": "none",
                       "TPU_TELEMETRY_INTERVAL_S": "0",
                       "HTTP_PORT": "0", "METRICS_PORT": "0", "GRPC_PORT": "0"})
    workload, config = load_cell(args.workload, rehearse)
    cell = args.workload
    chips = int(workload.get("chips", 1))
    model, engine_kw = config["model"], dict(config["engine"])
    family = load_family(config)
    plan = traffic.Plan(workload, args.seed, model["vocab_size"])
    longest = max(plan.distinct(), key=lambda r: r.prompt_len + r.output_len)
    held = longest.prompt_len + longest.output_len
    if held > int(engine_kw["max_seq_len"]):
        # the engine would cap it, and a request that returns another count than it asked has
        # failed: a faster engine then fails a request that a slower one never reached (PERF.md, PR 26)
        raise RunFault(f"the mix holds a request with a prompt of {longest.prompt_len} tokens asking "
                       f"{longest.output_len}, {held} together: over the engine's max_seq_len "
                       f"{engine_kw['max_seq_len']}")

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if not rehearse and (device["platform"] != "tpu" or device["count"] < chips):
        print(f"benchmarks/run.py: the cell needs {chips} TPU chip(s), JAX found "
              f"{device['count']} x {device['platform']!r}; --rehearse runs the tiny twin on the CPU",
              file=sys.stderr)
        return 2

    import costs
    import gofr_tpu
    from gofr_tpu.llm import GenRequest
    from gofr_tpu.profiling import default_registry
    from gofr_tpu.utils import enable_compilation_cache

    pk = costs.peaks(device["kind"]) if not rehearse else {
        "bf16_flops": 1e12, "int8_ops": 2e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
    cache_dir = enable_compilation_cache()
    say(f"device {device}; compile cache {cache_dir}")
    cache_events = {"/jax/compilation_cache/cache_hits": 0, "/jax/compilation_cache/cache_misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event in cache_events:
            cache_events[event] += 1

    jax.monitoring.register_event_listener(on_event)

    # -- set-up: weights, engine, warm-up ------------------------------------
    t = time.perf_counter()
    cfg = family.program_config(model)
    params = family.program_params(model, args.seed)
    jax.block_until_ready(params)
    t_weights = time.perf_counter() - t
    t = time.perf_counter()
    app = gofr_tpu.new()
    handle = app.container.tpu().register_llm("bench", cfg, params, **engine_kw)
    del params
    t_engine = time.perf_counter() - t
    why = expected_paths_ok(handle.stats()["attention"], config["expect"]["attention"])
    if why:
        raise RunFault(why)
    say("traffic " + json.dumps(plan.describe()))

    def make_request(tokens, max_new):
        return GenRequest(tokens, max_new_tokens=max_new, temperature=0.0, eos_token=-1)

    load = L.Load(handle, make_request, plan, timeout_s=REQUEST_TIMEOUT_S)
    t = time.perf_counter()
    load.start(horizon_s=args.seconds + 600.0)
    load.wait_ramp(timeout_s=RAMP_TIMEOUT_S)
    t_ramp = time.perf_counter() - t

    # -- the window ------------------------------------------------------------
    # Both edges are the end of a step's burst of tokens, so the window holds whole steps:
    # it opens at the first such end after the ramp and closes at the end of the step in
    # flight `--seconds` later (PERF.md section 2: an edge at a moment of the host's choosing
    # cut a step's tokens in two, and the count turned on where).
    registry = default_registry()
    t0 = load.burst_end(after=time.perf_counter(), wait_s=REQUEST_TIMEOUT_S)
    if t0 is None:
        raise RunFault(f"no token for {REQUEST_TIMEOUT_S} s after the ramp: the window has no edge")
    snap0, stats0 = registry.snapshot(), handle.stats()
    setup_s = t0 - T_PROCESS
    say(f"set-up {setup_s:.1f} s: weights {t_weights:.1f}, engine build + warm-up {t_engine:.1f} "
        f"({compile_count(snap0)} programs, {snap0['totals']['compile_s_total']} s compiling; "
        f"cache {cache_events['/jax/compilation_cache/cache_hits']} hits "
        f"{cache_events['/jax/compilation_cache/cache_misses']} misses), ramp {t_ramp:.1f}")
    kv_samples: list = []
    closing = threading.Event()

    def sample_kv() -> None:
        while not closing.wait(1.0):
            kv_samples.append(handle.stats()["kvcache"])

    sampler = threading.Thread(target=sample_kv, daemon=True)
    sampler.start()
    traced = None
    if args.trace:
        trace_dir = os.path.join(REPO, ".bench_trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        span = min(float(workload.get("trace_seconds", 2.0)), args.seconds / 2)
        time.sleep(max(0.0, (args.seconds - span) / 2))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        ta = time.perf_counter()
        time.sleep(span)
        tb = time.perf_counter()
        jax.profiler.stop_trace()
        traced = {"dir": trace_dir, "ta": ta, "tb": tb}
    time.sleep(max(0.0, t0 + args.seconds - time.perf_counter()))
    # no step in flight yields tokens (no burst within two steps: every lane is in prefill,
    # or idle): then no burst is cut, and the window closes on time
    t1 = load.burst_end(after=t0 + args.seconds, wait_s=2 * load.step_seconds(t0, t0 + args.seconds))
    t1 = t1 or t0 + args.seconds
    snap1, stats1 = registry.snapshot(), handle.stats()
    closing.set()
    load.stop()
    sampler.join(5)
    mem = devs[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs[:chips]))
    say(f"window {t1 - t0:.2f} s closed; device peak {device['memory_peak_bytes'] / 1e9:.2f} GB "
        f"of {mem.get('bytes_limit', 0) / 1e9:.2f}")
    paged = [s for s in kv_samples if s.get("layout") == "paged"]
    if paged:  # the peak counts the whole pool: say how much of it held live KV
        live = sum(s["blocks_in_use"] for s in paged) / len(paged) * paged[0]["block_bytes"]
        say(f"kv pool: {live / 1e9:.2f} GB live on average of "
            f"{paged[0]['pool_blocks'] * paged[0]['block_bytes'] / 1e9:.2f} GB pooled")

    summary = S.window_summary(load.records, t0, t1)
    say(f"requests: {summary['completed']} completed, {summary['failed']} failed in the window; "
        f"{summary['tokens']} tokens; ttft_ms {S.tails(summary['ttft_ms'])}; "
        f"tpot_ms {S.tails(summary['tpot_ms'])}")
    if load.lateness_s:
        say(f"generator lateness_s {S.tails(load.lateness_s)}")
    for e in summary["errors"]:
        say("failed request: " + e)

    # -- free the program, then judge ------------------------------------------
    engine_facts = {"step_token_budget": stats1["step_token_budget"], "slots": stats1["slots"],
                    "decode_chunk": stats1["decode_chunk"]}
    new_compiles = compile_count(snap1) - compile_count(snap0)
    degraded = snap1["degraded"]
    sampled = pick_samples(summary["done"], args.seed, int(workload["check"]["sample_requests"]))
    samples = [(plan.tokens(r.spec), r.tokens) for r in sampled]
    say(f"check samples {len(sampled)} of {summary['completed']} finished requests, clients "
        f"{sorted(r.spec.client for r in sampled)} of {plan.clients}")
    records = load.records
    app.container.tpu().close()
    del handle, load.handle, load, app
    gc.collect()
    jax.clear_caches()
    gc.collect()

    t = time.perf_counter()
    check = {"failed": [summary["failed"], 0],
             "compiles_in_window": [new_compiles, 0],
             "degraded_programs": [len(degraded), 0]}
    if samples:
        res = family.gaps(model, args.seed, samples, int(engine_kw["max_seq_len"]),
                          control=bool(args.control))
        say(f"reference: {len(samples)} requests, {len(res['gap'])} served tokens in "
            f"{time.perf_counter() - t:.1f} s; agree with the reference's first choice "
            f"{sum(res['agree'])}/{len(res['agree'])}; gap per request {res['per_request']}")
        judged = res["gap"]
        if args.control:
            # the control stands in the program's place: at the same positions, the token
            # that the int4 reference puts first is judged as if it had been served
            judged = res["control_gap"]
            say("CONTROL " + json.dumps({"workload": cell, "seed": args.seed,
                                         "served_gap_max": max(res["gap"]),
                                         "control_gap_max": max(res["control_gap"]),
                                         "tokens": len(res["gap"])}))
        check["served_gap_max"] = [float(max(judged)), float(workload["check"]["gap_limit"])]
    else:
        check["served_gap_max"] = [float("inf"), float(workload["check"]["gap_limit"])]
        say("no request finished in the window: nothing to compare, so not correct")
    correct = all(v <= lim for v, lim in check.values())

    # -- metrics ------------------------------------------------------------------
    ctx = {
        "cell": cell, "workload": workload, "config": config, "model": model, "family": family,
        "peaks": pk, "chips": chips, "records": records, "summary": summary,
        "stats0": stats0, "stats1": stats1, "kv_samples": kv_samples,
        "engine": engine_facts, "t0": t0, "t1": t1, "trace": None,
    }
    bench = traffic.load(os.path.join(REPO, "BENCHMARK.json"))
    metrics: dict = {}
    if not args.trace:
        values = {**S.end_to_end(summary), "setup_s": setup_s}
        for m in bench["end_to_end"]:
            if ("workloads" not in m or cell in m["workloads"]) and m["name"] in values:
                metrics[m["name"]] = {"value": float(values.pop(m["name"])), "unit": m["unit"]}
    else:
        import trace as T

        red = T.reduce_planes(T.load(traced["dir"]), rehearsal=rehearse, traced_s=traced["tb"] - traced["ta"])
        ctx["trace"] = {**traced, "reduced": red}
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        for mod in load_metrics(cell):
            v = mod.read(ctx)
            if v is not None:
                metrics[mod.META["name"]] = {"value": float(v), "unit": mod.META["unit"]}
        if not os.environ.get("BENCH_KEEP_TRACE"):
            shutil.rmtree(traced["dir"], ignore_errors=True)
    line = {"correct": bool(correct), "attempted": summary["attempted"], "failed": summary["failed"],
            "metrics": metrics, "device": device}
    if args.trace:
        line["breakdown"] = T.breakdown(red)
    if rehearse:
        line["rehearsal"] = True
    if not args.trace:
        line["extra"] = values  # the window's other statistics, judged by nothing
    line["check"] = {k: {"value": v, "limit": lim} for k, (v, lim) in check.items()}
    for k, (v, lim) in check.items():
        say(f"check {k}: {v} (limit {lim}) {'ok' if v <= lim else 'NOT CORRECT'}")
    print(json.dumps(line), flush=True)
    return 0


def pick_samples(done: list, seed: int, n: int) -> list:
    """The longest finished request and n-1 more in an order drawn from the
    seed, clients not yet sampled first: the sample spreads over as many
    lanes as it has requests, so a fault in one slot is not passed over
    run after run."""
    import numpy as np

    if not done:
        return []
    longest = max(done, key=lambda r: r.prompt_len + len(r.tokens))
    rest = [r for r in done if r is not longest]
    rest = [rest[i] for i in np.random.default_rng([int(seed), 9]).permutation(len(rest))]
    picks, seen = [longest], {longest.spec.client}
    for r in rest:
        if r.spec.client not in seen:
            picks.append(r)
            seen.add(r.spec.client)
    picks += [r for r in rest if not any(r is p for p in picks)]
    return picks[:n]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, the configuration's tiny twin: proves the script, not the chip")
    ap.add_argument("--control", type=int, default=0, choices=(0, 1),
                    help="judge the int4 control in the program's place: the run has to come out NOT "
                         "correct (prove.py and the tests; never a benchmark run)")
    args = ap.parse_args(argv)
    try:
        return run(args)
    except RunFault as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 3


if __name__ == "__main__":
    sys.exit(main())
