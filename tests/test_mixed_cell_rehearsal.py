"""`k-exaone-236b-a23b.mixed-closed` rehearsed on the CPU through the benchmark's
own harness (`benchmarks/run.py --rehearse`: the configuration's tiny twin, W8A8
prompt chunks as served): the cell runs, is `correct` by the family's gap
check, reads the metrics that have a CPU stand-in, and its int4 control is NOT
correct. A file of its own beside tests/test_mixed_stack.py so that the two
spread over the test workers."""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
CELL = "k-exaone-236b-a23b.mixed-closed"


def _rehearse(*args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seconds", "3",
                        "--rehearse", *args], capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p, (json.loads(lines[-1]) if lines else None)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_and_reads_its_metrics(trace):
    p, line = _rehearse("--seed", str(2**31 + 77), "--trace", str(trace))
    assert p.returncode == 0 and line["correct"] is True, p.stderr[-2000:]
    if trace:
        # (the three that read a Pallas kernel's events have no CPU stand-in)
        assert {"hybrid_step_mfu_pct", "window_kv_held_pct", "moe_pairs_here_pct"} <= set(line["metrics"])
        assert 0 < line["metrics"]["window_kv_held_pct"]["value"] < 100
        assert 10 < line["metrics"]["moe_pairs_here_pct"]["value"] < 45  # 4 of 16: 25 when even
    else:
        assert {"tokens_per_s", "ttft_ms_mean", "tpot_ms_mean", "setup_s"} == set(line["metrics"])


def test_the_control_run_of_the_cell_is_not_correct():
    p, line = _rehearse("--seed", "99", "--trace", "0", "--control", "1")
    assert p.returncode == 0 and line["correct"] is False, p.stderr[-2000:]
    assert line["check"]["served_gap_max"]["value"] > line["check"]["served_gap_max"]["limit"]
    # the second control rides the same run: a reference that leaves its held experts' part out would not pass either
    said = re.search(r"WITHOUT its routed part: widest mean ([0-9.]+)", p.stderr)
    assert said and float(said.group(1)) > line["check"]["served_gap_max"]["limit"], p.stderr[-2000:]
