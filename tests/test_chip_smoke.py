"""chip_smoke.py on a machine with no chip: the rehearsal runs every phase on
the CPU and says so in every line; the bare command — the driver's call —
fails and prints no result; so does the script alone, without the repo."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args: list[str], cwd: str = REPO, script: str = SMOKE):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)  # one CPU device, as on a one-chip machine
    return subprocess.run(
        [sys.executable, script, *args], env=env, capture_output=True,
        text=True, timeout=600, cwd=cwd,
    )


def test_rehearsal_runs_every_phase_and_labels_every_line():
    out = _run(["--rehearse"])
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert all("REHEARSAL" in ln for ln in lines[:-1]), lines
    said = "\n".join(lines)
    for phase in ("kernel flash+q_offsets", "kernel paged-decode int8 pool",
                  "its exact repeat: 201, identical", "greedy tokens: ",
                  "concurrent /generate",
                  "streamed /v1/chat/completions", "attention traced",
                  "zero compiles after warm-up"):
        assert phase in said, f"phase missing: {phase}"


def test_bare_command_fails_where_jax_finds_no_accelerator():
    out = _run([])
    assert out.returncode not in (0, None)
    assert '"ok"' not in out.stdout


def test_script_alone_without_the_repo_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = _run(["--rehearse"], cwd=str(tmp_path), script=str(tmp_path / "chip_smoke.py"))
    assert out.returncode not in (0, None)
    assert '"ok"' not in out.stdout
