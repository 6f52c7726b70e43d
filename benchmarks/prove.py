#!/usr/bin/env python3
"""One cell's whole proof in ONE call of the chip tool.

    python3 benchmarks/prove.py --workload <name> --out <dir> [--runs 6] [--phases cold,sets,traced,check]

A parent that never imports JAX (a chip belongs to one process) and runs, one
after another as sub-processes sharing the compile cache:

- `cold`:   one run that compiles (its set-up is recorded apart);
- `sets`:   two sets of `--runs` runs with the same seeds in both sets;
- `traced`: three `--trace 1` runs on further seeds;
- `check`:  three further seeds with `--control 1`: the int4 control is judged
            in the program's place, and every such run has to come out NOT
            correct by the served gap alone.

Every run's last line goes to `<out>/<name>.jsonl` with its phase, seed, exit
code and seconds (and the end of its stderr where it failed); the `CONTROL`
lines go to `<out>/<name>.control.jsonl`. The summary printed at the end gives,
per end-to-end metric, each set's median and spread (interquartile distance
over the median, `statistics.quantiles(n=4)`), and the widest served gap beside
the smallest control gap. The exit code is 1 where a run that should be
correct is not, or a control run is.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
SEED0 = 2147483000  # large on purpose: seeds go past 2**31


def one_run(workload: str, seed: int, seconds: float, trace: int, control: int,
            phase: str, out_dir: str) -> dict:
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)]
    if control:
        cmd += ["--control", "1"]
    t = time.time()
    p = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True, timeout=1500)
    rec = {"phase": phase, "seed": seed, "trace": trace, "rc": p.returncode,
           "wall_s": round(time.time() - t, 1)}
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    try:
        rec["line"] = json.loads(lines[-1])
    except (IndexError, ValueError):
        rec["line"] = None
    if p.returncode != 0 or not rec["line"] or not rec["line"].get("correct"):
        rec["stderr_tail"] = p.stderr[-3000:]
    with open(os.path.join(out_dir, workload + ".jsonl"), "a") as f:
        f.write(json.dumps(rec) + "\n")
    with open(os.path.join(out_dir, workload + ".stderr.txt"), "a") as f:
        f.write(f"==== {phase} seed {seed} trace {trace} rc {p.returncode}\n")
        f.write("\n".join(ln for ln in p.stderr.splitlines() if "[bench" in ln) + "\n")
    for ln in p.stderr.splitlines():
        if "CONTROL " in ln:
            with open(os.path.join(out_dir, workload + ".control.jsonl"), "a") as f:
                f.write(ln.split("CONTROL ", 1)[1] + "\n")
    ok = bool(rec["line"] and rec["line"].get("correct"))
    rec["as_expected"] = as_expected(p.returncode, rec["line"], bool(control))
    print(f"[prove] {phase} seed {seed} trace {trace}: rc {p.returncode}, {rec['wall_s']} s, "
          f"correct {ok}{'' if rec['as_expected'] else '  <-- NOT as it should be'}", flush=True)
    return rec


def as_expected(rc: int, line: dict | None, control: bool) -> bool:
    """A run of the program has to be correct. A control run has to be NOT
    correct, by the served gap and by nothing else: a control that passes
    means the limit separates nothing."""
    if rc != 0 or not line:
        return False
    if not control:
        return line["correct"] is True
    within = {k: c["value"] <= c["limit"] for k, c in line["check"].items()}
    return line["correct"] is False and not within.pop("served_gap_max") and all(within.values())


def spread(values: list) -> float:
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def summarize(out_dir: str, workload: str) -> None:
    recs = [json.loads(ln) for ln in open(os.path.join(out_dir, workload + ".jsonl"))]
    sets: dict[str, dict[str, list]] = {}
    gaps = []
    for r in recs:
        line = r.get("line") or {}
        g = (line.get("check") or {}).get("served_gap_max")
        if g and r["phase"] != "check":
            gaps.append(g["value"])
        if r["phase"].startswith("set") and line:
            for name, m in line["metrics"].items():
                sets.setdefault(name, {}).setdefault(r["phase"], []).append(m["value"])
            for name, v in (line.get("extra") or {}).items():
                sets.setdefault("extra." + name, {}).setdefault(r["phase"], []).append(v)
    print(f"[prove] {workload}: {len(recs)} runs, "
          f"{sum(1 for r in recs if (r.get('line') or {}).get('correct'))} correct, "
          f"{sum(1 for r in recs if r['phase'] == 'check')} of them controls")
    for name, by_set in sorted(sets.items()):
        parts, widest = [], 0.0
        for phase, vals in sorted(by_set.items()):
            if len(vals) >= 2:
                sp = spread(vals)
                widest = max(widest, sp)
                parts.append(f"{phase}: median {statistics.median(vals):.6g} spread {sp:.4f} n {len(vals)}")
        print(f"[prove]   {name}: " + "; ".join(parts) + f"; widest {widest:.4f} -> bound ~{5 * widest:.3f}")
    if gaps:
        print(f"[prove]   served_gap_max over {len(gaps)} runs: largest {max(gaps):.5f}")
    cpath = os.path.join(out_dir, workload + ".control.jsonl")
    if os.path.isfile(cpath):
        ctl = [json.loads(ln) for ln in open(cpath)]
        print(f"[prove]   control (int4) gap over {len(ctl)} seeds: smallest "
              f"{min(c['control_gap_max'] for c in ctl):.5f}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--runs", type=int, default=6, help="runs in each of the two sets")
    ap.add_argument("--traced", type=int, default=3)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=0.0, help="default: BENCHMARK.json's run_seconds")
    ap.add_argument("--phases", default="cold,sets,traced,check")
    ap.add_argument("--seed0", type=int, default=SEED0)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    phases = args.phases.split(",")
    s0 = args.seed0
    recs = []
    if "cold" in phases:
        recs.append(one_run(args.workload, s0, seconds, 0, 0, "cold", args.out))
    if "sets" in phases:
        for which in ("set1", "set2"):
            for i in range(args.runs):
                recs.append(one_run(args.workload, s0 + 1 + i, seconds, 0, 0, which, args.out))
    if "traced" in phases:
        for i in range(args.traced):
            recs.append(one_run(args.workload, s0 + 101 + i, seconds, 1, 0, "traced", args.out))
    if "check" in phases:
        for i in range(args.controls):
            recs.append(one_run(args.workload, s0 + 201 + i, seconds, 0, 1, "check", args.out))
    summarize(args.out, args.workload)
    return 0 if all(r["as_expected"] for r in recs) else 1


if __name__ == "__main__":
    sys.exit(main())
