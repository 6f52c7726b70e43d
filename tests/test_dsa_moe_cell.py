"""`deepseek-v3.2.docs-closed` rehearsed on the CPU through the benchmark's
own harness (`benchmarks/run.py --rehearse`: the configuration's tiny twin,
W8A8 prompt rows as served): the cell runs, is `correct` by the family's gap
check, serves its prompts from the radix tree, reads the metrics that have a
CPU stand-in, and its int4 control is NOT correct. A file of its own beside
tests/test_dsa_moe.py so that the two spread over the test workers.

The harness runs from a root of its own (links to the repo's `benchmarks/`,
`BENCHMARK.json` and `gofr_tpu/`): a traced run clears and writes
`<root>/.bench_trace`, and another cell's traced run on another worker at the
same time would clear this one's trace under it."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "deepseek-v3.2.docs-closed"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_root")
    for name in ("benchmarks", "BENCHMARK.json", "gofr_tpu"):
        os.symlink(os.path.join(REPO, name), root / name)
    return str(root)


def _rehearse(root, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, os.path.join(root, "benchmarks", "run.py"), "--workload", CELL,
                        "--seconds", "3", "--rehearse", *args],
                       capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p, (json.loads(lines[-1]) if lines else None)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_and_reads_its_metrics(root, trace):
    p, line = _rehearse(root, "--seed", str(2**31 + 77), "--trace", str(trace))
    assert p.returncode == 0 and line["correct"] is True, p.stderr[-2000:]
    if trace:
        # (the two kernel rooflines read Pallas calls, which have no CPU stand-in)
        got = line["metrics"]
        assert {"dsa_step_mfu_pct", "dsa_rows_read_pct", "prompt_cached_pct"} <= set(got)
        assert got["prompt_cached_pct"]["value"] > 50.0 and got["dsa_rows_read_pct"]["value"] < 100.0
    else:
        assert {"tokens_per_s", "ttft_ms_mean", "tpot_ms_mean", "setup_s"} == set(line["metrics"])


def test_the_control_run_of_the_cell_is_not_correct(root):
    p, line = _rehearse(root, "--seed", "99", "--trace", "0", "--control", "1")
    assert p.returncode == 0 and line["correct"] is False, p.stderr[-2000:]
    assert line["check"]["served_gap_max"]["value"] > line["check"]["served_gap_max"]["limit"]
