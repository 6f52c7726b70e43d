"""The harness end to end on the CPU: `--rehearse` on both cells prints a
last line with exactly the contract's keys; the bare command exits non-zero
without a chip; a throw-away family, configuration, cell and per-layer metrics
are found by name from new files alone; BENCHMARK.json's entries are the
files'; every configuration names a family that has the whole interface; the
dense family draws what `run.py` and `reference.py` drew before it existed; a
cell that cannot run is refused at set-up with a sentence; and a run whose timed path is broken underneath (a token altered where it is
produced), or in which the int4 control stands in the program's place, comes
out NOT correct."""
import hashlib
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys

import jax
import numpy as np
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


THROWAWAY_FAMILY = '''"""The dense family under another spelling: `ffn_width` for `intermediate_size`,
a key that this family alone reads."""
from families import dense


def _dense(model):
    model = dict(model)
    model["intermediate_size"] = model.pop("ffn_width")
    return model


def _renamed(fn):
    return lambda model, *args, **kw: fn(_dense(model), *args, **kw)


program_config, program_params = _renamed(dense.program_config), _renamed(dense.program_params)
gaps, forward_logits = _renamed(dense.gaps), _renamed(dense.forward_logits)
least_step_seconds = _renamed(dense.least_step_seconds)
decode_kv_read_bytes = _renamed(dense.decode_kv_read_bytes)
'''

# a whole-step share of its own, as a later configuration brings one: the accepted reader's
# arithmetic under a new name, its counts taken from whatever family the cell's configuration names
THROWAWAY_MFU = '''import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "accepted_step_mfu_pct", os.path.join(os.path.dirname(__file__), "step_mfu_pct.py"))
_accepted = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_accepted)
META = {**_accepted.META, "name": "throwaway_step_mfu_pct", "workloads": ["throwaway-model.burst-open"]}
read = _accepted.read
'''


def files_under(folder):
    out = {}
    for root, _dirs, names in os.walk(folder):
        if "__pycache__" not in root:
            for n in names:
                with open(os.path.join(root, n), "rb") as f:
                    out[os.path.join(root, n)] = hashlib.sha256(f.read()).hexdigest()
    return out


def test_a_family_a_configuration_a_cell_and_a_metric_are_added_as_files_alone():
    """A throw-away example of each, found by name, then removed; no file
    that was there is touched. The configuration spells one `model` key as
    only its own family reads it, so a reader that went round the family
    (or a harness that read the key itself) would raise."""
    before = files_under(R.HERE)
    cfg = json.load(open(os.path.join(R.HERE, "configs", "mistral-7b.json")))
    cfg["family"] = "throwaway_family"
    for group in (cfg["model"], cfg["rehearsal"]["model"]):
        group["ffn_width"] = group.pop("intermediate_size")
    wl = json.load(open(os.path.join(R.HERE, "workloads", "mistral-7b.long-closed.json")))
    wl["config"] = "throwaway-model"
    wl["rehearsal"]["arrival"] = {"kind": "bursty", "rate": 30.0, "cv": 3.0}
    made = {
        os.path.join(R.HERE, "families", "throwaway_family.py"): THROWAWAY_FAMILY,
        os.path.join(R.HERE, "configs", "throwaway-model.json"): json.dumps(cfg),
        os.path.join(R.HERE, "workloads", "throwaway-model.burst-open.json"): json.dumps(wl),
        os.path.join(R.HERE, "metrics", "throwaway_step_mfu_pct.py"): THROWAWAY_MFU,
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
        assert line["metrics"]["throwaway_step_mfu_pct"]["value"] > 0  # counted through the new family
        assert line["check"]["served_gap_max"]["value"] < line["check"]["served_gap_max"]["limit"]
        assert "kv_blocks_used_pct" not in line["metrics"]  # other cells' metrics stay out
        assert "step_mfu_pct" not in line["metrics"]
        assert "generator lateness_s" in p.stderr  # open loop: how late the sender ran is printed
        p2, line2 = bench("--workload", CELLS[0], "--seed", "5", "--seconds", "2", "--trace", "1", "--rehearse")
        assert not {"throwaway_count", "throwaway_step_mfu_pct"} & set(line2["metrics"])
        during = files_under(R.HERE)
        assert {k: during[k] for k in before} == before and set(during) - set(before) == set(made)
    finally:
        for path in made:
            os.remove(path)
    assert files_under(R.HERE) == before


def config_files():
    return sorted(fn[:-5] for fn in os.listdir(os.path.join(R.HERE, "configs")) if fn.endswith(".json"))


@pytest.mark.parametrize("name", config_files())
def test_a_configuration_names_a_whole_family_and_its_entry_agrees(name):
    """One case per configuration file: it names a `family`, the family's
    file is there with the six names, and BENCHMARK.json's entry says what
    the file says (source, file, and the keys cut from the source, each with
    its published count beside it)."""
    cfg = json.load(open(os.path.join(R.HERE, "configs", name + ".json")))
    assert os.path.isfile(os.path.join(R.HERE, "families", cfg["family"] + ".py"))
    family = R.load_family(cfg)  # raises where one of the six names is missing
    entry = {c["name"]: c for c in json.load(open(os.path.join(R.REPO, "BENCHMARK.json")))["configs"]}[name]
    assert entry["file"] == f"benchmarks/configs/{name}.json" and entry["source"] == cfg["source"]
    assert sorted(entry["reduced"]) == sorted(cfg.get("reduced", []))
    assert set(cfg.get("published", {})) == set(entry["reduced"])
    assert cfg["deployment"] and isinstance(cfg["model"]["vocab_size"], int)
    for twin in (cfg["model"], cfg["rehearsal"]["model"]):  # the family reads every key it needs
        assert family.least_step_seconds(twin, {"bf16_flops": 1e12, "int8_ops": 2e12}, prefill_contexts=[1, 2],
                                         decode_contexts=[3], prefill_int8=True)["seconds"] > 0
        assert family.decode_kv_read_bytes(twin, [3, 4]) > 0


# what `run.program_params` and `reference.forward_logits` gave at the parent of the PR that
# moved them behind the family (PR 28), per twin and seed: sha256 of the tree's paths, shapes,
# dtypes and int8 bytes; the float leaves' sum; of the logits over TOKENS the sum, the sum of
# magnitudes and a cosine-weighted sum, with every int8 weight as drawn and re-rounded to int4
PINNED = {
    ("qwen2-7b", 2**31 + 11): ("df732991cd59f6de", -0.6997639983601402,
                               (-1391.4137642066125, 33016.859850837594, 65.49354564529378),
                               (-1253.8628084031652, 33309.599692040996, 51.73359766582719)),
    ("qwen2-7b", 7): ("a9fa42c04dbf4652", 0.32536593227632693,
                      (-102.03006182936497, 33538.49008909169, -31.028207615240785),
                      (-293.72243118169536, 33786.48343242245, -4.4112394135018)),
    ("mistral-7b", 2**31 + 11): ("d51cc0970fa2fb63", 1.0635577948996797,
                                 (-415.2049527172512, 33385.85855146039, 102.61453891009869),
                                 (-195.84894198869006, 33515.97270424466, 159.33293821213266)),
    ("mistral-7b", 7): ("0d48acee9e922ff4", -0.06621426391211571,
                        (608.4906010718423, 33354.8163275308, -43.693418911411726),
                        (589.6032177836896, 33515.7931109988, -17.61897300665342)),
}


def logits_pin(logits):
    a = np.asarray(logits, np.float64)
    w = np.cos(np.arange(a.size, dtype=np.float64)).reshape(a.shape)
    return [float(a.sum()), float(np.abs(a).sum()), float((a * w).sum())]


@pytest.mark.parametrize("name,seed", sorted(PINNED))
def test_the_dense_family_draws_what_the_harness_drew_before_the_move(name, seed):
    cfg = json.load(open(os.path.join(R.HERE, "configs", name + ".json")))
    model, family = cfg["rehearsal"]["model"], R.load_family(cfg)
    digest, float_sum, logits, logits_int4 = PINNED[name, seed]
    h, floats = hashlib.sha256(), 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(family.program_params(model, seed))[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.shape} {a.dtype}\n".encode())
        if a.dtype == np.int8:
            h.update(a.tobytes())
        else:
            floats += float(np.sum(a.astype(np.float64)))
    assert h.hexdigest()[:16] == digest and floats == pytest.approx(float_sum, abs=1e-6)
    tokens = np.random.default_rng(0).integers(1, model["vocab_size"], (2, 40)).astype(np.int32)
    assert logits_pin(family.forward_logits(model, seed, tokens)) == pytest.approx(logits, rel=1e-5, abs=1e-2)
    assert logits_pin(family.forward_logits(model, seed, tokens, int4=True)) == pytest.approx(
        logits_int4, rel=1e-5, abs=1e-2)


REFUSED = {
    "no_family": (lambda cfg, wl: cfg.pop("family"), ["names no `family`"]),
    "family_without_a_file": (lambda cfg, wl: cfg.update(family="nowhere"), ["'nowhere'", "families"]),
    # the twin's engine holds 128: a prompt of 100 asking 40 would be capped at 28 and count as failed
    "request_over_max_seq_len": (lambda cfg, wl: wl["rehearsal"].update(
        prompt_tokens={"dist": "fixed", "value": 100}, output_tokens={"dist": "fixed", "value": 40}),
        ["100", "40", "140", "max_seq_len 128"]),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_cell_that_cannot_run_is_refused_at_set_up_with_a_sentence(case):
    """Exit 3, no result, and a sentence with the names or the sizes; from
    throw-away files, before anything touches the device."""
    change, words = REFUSED[case]
    cfg = json.load(open(os.path.join(R.HERE, "configs", "qwen2-7b.json")))
    wl = json.load(open(os.path.join(R.HERE, "workloads", "qwen2-7b.reason-closed.json")))
    wl["config"] = "throwaway-refused"
    change(cfg, wl)
    made = {os.path.join(R.HERE, "configs", "throwaway-refused.json"): cfg,
            os.path.join(R.HERE, "workloads", "throwaway-refused.closed.json"): wl}
    try:
        for path, obj in made.items():
            with open(path, "w") as f:
                json.dump(obj, f)
        p, line = bench("--workload", "throwaway-refused.closed", "--seed", "3", "--seconds", "1",
                        "--trace", "0", "--rehearse")
    finally:
        for path in made:
            os.remove(path)
    assert p.returncode == 3 and line is None, p.stderr[-2000:]
    sentence = p.stderr.strip().splitlines()[-1]
    assert sentence.startswith("benchmarks/run.py: ") and all(w in sentence for w in words), sentence


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
