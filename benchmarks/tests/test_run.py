"""The harness end to end on the CPU: `--rehearse` on both cells prints a
last line with exactly the contract's keys; the bare command exits non-zero
without a chip; a throw-away cell, configuration and per-layer metric are
found by name from new files alone; BENCHMARK.json's entries are the files';
and a run whose timed path is broken underneath (a token altered where it is
produced), or in which the int4 control stands in the program's place, comes
out NOT correct."""
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys

import prove
import pytest
import run as R

CELLS = ["qwen2-7b.reason-closed", "mistral-7b.long-closed"]
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def bench(*args, env=ENV, cwd=R.REPO):
    p = subprocess.run([sys.executable, os.path.join(R.HERE, "run.py"), *args],
                       capture_output=True, text=True, env=env, cwd=cwd, timeout=900)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p, (json.loads(lines[-1]) if lines else None)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contracts_last_line(cell, trace):
    p, line = bench("--workload", cell, "--seed", str(2**31 + 77), "--seconds", "3",
                    "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    assert line["rehearsal"] is True and line["correct"] is True, p.stderr[-2000:]
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert list(line)[-1] == "check"
    spec = json.load(open(os.path.join(R.REPO, "BENCHMARK.json")))
    group = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in spec[group] if "workloads" not in m or cell in m["workloads"]}
    assert set(line["metrics"]) <= want
    for name, m in line["metrics"].items():
        assert isinstance(m["value"], float) and m["unit"]
    if not trace:
        assert set(line["metrics"]) == want and line["metrics"]["setup_s"]["value"] > 0
    else:
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] >= line["device"]["busy_s"]
        # every metric the cell lists is read; only the Mosaic kernel has no CPU stand-in
        assert want - {"paged_decode_roofline_pct"} <= set(line["metrics"])
        assert len(line["breakdown"]["device_ops"]) <= 10
    # each number compared stands beside its limit, on stderr's last lines too
    assert "check served_gap_max" in p.stderr.splitlines()[-1] or "check" in p.stderr.splitlines()[-1]
    assert all(set(v) == {"value", "limit"} for v in line["check"].values())


def test_benchmark_json_says_what_the_files_say():
    """Every entry has its file, found by the entry's name, and the two
    agree; names, units and lines keep to the contract's characters."""
    spec = json.load(open(os.path.join(R.REPO, "BENCHMARK.json")))
    name, unit = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$"), re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    configs = {c["name"]: c for c in spec["configs"]}
    for c in spec["configs"]:
        assert c["file"] == f"benchmarks/configs/{c['name']}.json" and name.match(c["name"])
        assert json.load(open(os.path.join(R.REPO, c["file"])))["source"] == c["source"]
        assert 1 <= len(c["why"]) <= 200
    for w in spec["workloads"]:
        f = json.load(open(os.path.join(R.HERE, "workloads", w["name"] + ".json")))
        assert (f["config"], f["chips"]) == (w["config"], w["chips"]) and w["config"] in configs
        assert w["name"] == f"{w['config']}.{w['traffic']}" and name.match(w["name"]) and 1 <= len(w["why"]) <= 200
    cells = [w["name"] for w in spec["workloads"]]
    metas = {}
    for fn in sorted(os.listdir(os.path.join(R.HERE, "metrics"))):
        if fn.endswith(".py"):
            s = importlib.util.spec_from_file_location("m", os.path.join(R.HERE, "metrics", fn))
            mod = importlib.util.module_from_spec(s)
            s.loader.exec_module(mod)
            assert fn == mod.META["name"] + ".py"
            metas[mod.META["name"]] = mod.META
    assert {m["name"]: m for m in spec["per_layer"]} == metas
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and all(0.01 <= m["bound"] <= 0.1 for m in e2e.values())
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in spec["per_layer"]:  # each cell that reads it reports the end-to-end metric it moves
        assert set(m.get("workloads", cells)) <= set(e2e[m["moves"]].get("workloads", cells))
    for cell in cells:
        assert any(cell in m.get("workloads", cells) for m in spec["per_layer"])
        assert sum(cell in m.get("workloads", cells) for m in spec["end_to_end"]) >= 2


def test_no_chip_no_result():
    p, line = bench("--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and line is None
    assert "TPU" in p.stderr


def test_bare_directory_fails(tmp_path):
    """Only BENCHMARK.json and the files under `paths`: no program, no result."""
    shutil.copy(os.path.join(R.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(R.HERE, tmp_path / "benchmarks", ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = subprocess.run([sys.executable, "benchmarks/run.py", "--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0", "--rehearse"],
                       capture_output=True, text=True, env=ENV, cwd=tmp_path, timeout=300)
    assert p.returncode != 0 and not p.stdout.strip()


def test_a_cell_a_configuration_and_a_metric_are_added_as_files_alone():
    """A throw-away example of each, found by name, then removed."""
    cfg = json.load(open(os.path.join(R.HERE, "configs", "mistral-7b.json")))
    wl = json.load(open(os.path.join(R.HERE, "workloads", "mistral-7b.long-closed.json")))
    wl["config"] = "throwaway-model"
    wl["rehearsal"]["arrival"] = {"kind": "bursty", "rate": 30.0, "cv": 3.0}
    made = {
        os.path.join(R.HERE, "configs", "throwaway-model.json"): json.dumps(cfg),
        os.path.join(R.HERE, "workloads", "throwaway-model.burst-open.json"): json.dumps(wl),
        os.path.join(R.HERE, "metrics", "throwaway_count.py"): (
            'META = {"name": "throwaway_count", "unit": "requests", "better": "higher",\n'
            '        "source": "program_counter", "layer": "Scheduler (step packing)",\n'
            '        "moves": "tokens_per_s", "workloads": ["throwaway-model.burst-open"]}\n\n'
            'def read(run):\n    return float(len(run["records"]))\n'),
    }
    try:
        for path, text in made.items():
            with open(path, "w") as f:
                f.write(text)
        p, line = bench("--workload", "throwaway-model.burst-open", "--seed", "5", "--seconds", "3",
                        "--trace", "1", "--rehearse")
        assert p.returncode == 0 and line["correct"], p.stderr[-2000:]
        assert line["metrics"]["throwaway_count"]["value"] > 10
        assert "kv_blocks_used_pct" not in line["metrics"]  # other cells' metrics stay out
        assert "generator lateness_s" in p.stderr  # open loop: how late the sender ran is printed
        p2, line2 = bench("--workload", CELLS[0], "--seed", "5", "--seconds", "2", "--trace", "1", "--rehearse")
        assert "throwaway_count" not in line2["metrics"]
    finally:
        for path in made:
            os.remove(path)


@pytest.mark.parametrize("cell", CELLS)
def test_the_int4_control_is_not_correct(cell):
    """`--control 1` judges the token that the int4 reference puts first as
    if the program had served it: the same run, the same limit, and the last
    line reads `correct: false` by the served gap and by nothing else."""
    p, line = bench("--workload", cell, "--seed", str(2**31 + 5), "--seconds", "3", "--trace", "0",
                    "--control", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    assert line["correct"] is False
    gap = line["check"]["served_gap_max"]
    assert gap["value"] > 2 * gap["limit"]
    assert all(c["value"] <= c["limit"] for k, c in line["check"].items() if k != "served_gap_max")
    assert "NOT CORRECT" in p.stderr.splitlines()[-1]
    assert prove.as_expected(0, line, control=True) and not prove.as_expected(0, line, control=False)


def test_prove_fails_a_control_that_passes():
    ok = {"correct": True, "check": {"failed": {"value": 0, "limit": 0},
                                     "served_gap_max": {"value": 0.1, "limit": 0.4}}}
    bad = {"correct": False, "check": {"failed": {"value": 0, "limit": 0},
                                       "served_gap_max": {"value": 1.4, "limit": 0.4}}}
    other = {"correct": False, "check": {"failed": {"value": 2, "limit": 0},
                                         "served_gap_max": {"value": 1.4, "limit": 0.4}}}
    assert prove.as_expected(0, ok, control=False) and not prove.as_expected(0, ok, control=True)
    assert prove.as_expected(0, bad, control=True) and not prove.as_expected(0, bad, control=False)
    assert not prove.as_expected(0, other, control=True)  # it has to fail by the gap alone
    assert not prove.as_expected(1, ok, control=False) and not prove.as_expected(0, None, control=True)


FAULT = '''
import sys, runpy
sys.argv = ["run.py"] + sys.argv[1:]
sys.path.insert(0, {bench!r}); sys.path.insert(0, {repo!r})
import gofr_tpu.llm as L
_emit = L.LLMEngine._emit_to
def emit(self, r, slot, toks, now=None):
    # the fault: one token of every chunk altered where it is produced
    toks = list(toks)
    if toks:
        toks[-1] = (toks[-1] + 1) % self.cfg.vocab_size or 1
    return _emit(self, r, slot, toks, now)
L.LLMEngine._emit_to = emit
runpy.run_path({run!r}, run_name="__main__")
'''


@pytest.mark.parametrize("cell", CELLS)
def test_an_altered_token_is_not_correct(cell, tmp_path):
    """Skips nothing of a run but the look for a chip (`--rehearse`): the
    program under the handle is broken, and `correct` comes out false by the
    served gap alone."""
    script = tmp_path / "fault.py"
    script.write_text(FAULT.format(bench=R.HERE, repo=R.REPO, run=os.path.join(R.HERE, "run.py")))
    p = subprocess.run([sys.executable, str(script), "--workload", cell, "--seed", "9", "--seconds", "3",
                        "--trace", "0", "--rehearse"], capture_output=True, text=True, env=ENV,
                       cwd=R.REPO, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    gap = line["check"]["served_gap_max"]
    assert gap["value"] > 10 * gap["limit"]
    assert line["check"]["failed"]["value"] == 0  # nothing else gave it away
