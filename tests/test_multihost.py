"""Multi-host distributed backend: a REAL 2-process CPU cluster.

Two subprocesses join one jax runtime via parallel.multihost
(coordinator on localhost), build a GLOBAL mesh spanning both
processes' devices, and run a cross-process collective — the same
initialize → mesh → GSPMD path a TPU pod uses, with DCN played by
localhost TCP. This is the multi-host story the reference covers with
NCCL/MPI-backed integration tests.
"""

import os
import socket
import subprocess
import sys

_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax  # noqa: E402

from gofr_tpu.parallel.multihost import init_distributed, is_primary, topology  # noqa: E402

topo = init_distributed()  # GOFR_* env set by the parent
assert topo["process_count"] == 2, topo
assert topo["global_devices"] == 4 and topo["local_devices"] == 2, topo
assert is_primary() == (topo["process_index"] == 0)

import jax.numpy as jnp  # noqa: E402
from jax.experimental import multihost_utils  # noqa: E402

# cross-process collective: allgather each process's contribution
mine = jnp.asarray([float(topo["process_index"] + 1)])
gathered = multihost_utils.process_allgather(mine)
assert gathered.tolist() == [[1.0], [2.0]], gathered

# global mesh spanning BOTH processes; a jit over it runs a psum-backed
# global mean through GSPMD — the collective rides the runtime transport
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from gofr_tpu.parallel import make_mesh  # noqa: E402

mesh = make_mesh({"data": 4})
global_shape = (8, 4)
sharding = NamedSharding(mesh, P("data", None))
# each process addresses 4 of the 8 global rows (2 local devices x 2 rows)
local = jnp.full((4, 4), float(topo["process_index"] + 1))
arr = jax.make_array_from_process_local_data(sharding, local, global_shape)
total = jax.jit(
    lambda x: jnp.sum(x), out_shardings=NamedSharding(mesh, P())
)(arr)
# 4x4 block of ones from p0 + 4x4 block of twos from p1; the P() result
# is replicated, so every process reads it from a local shard
got = float(total.addressable_data(0))
assert got == 16.0 * 1.0 + 16.0 * 2.0, got
print(f"MULTIHOST-OK p{topo['process_index']} sum={got}")
"""


def _spawn_cluster(script: str, env_base: dict, cwd: str) -> list:
    with socket.socket() as s:  # free-port pick (inherent close-then-bind
        s.bind(("127.0.0.1", 0))  # race; the caller retries on a collision)
        port = s.getsockname()[1]
    return [
        subprocess.Popen(
            [sys.executable, script],
            env={
                **env_base,
                "GOFR_COORDINATOR": f"127.0.0.1:{port}",
                "GOFR_NUM_PROCESSES": "2",
                "GOFR_PROCESS_ID": str(i),
            },
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=cwd,
        )
        for i in range(2)
    ]


def test_two_process_cluster_runs_global_collectives(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env_base = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }
    env_base["PYTHONPATH"] = (
        repo_root + os.pathsep + env_base.get("PYTHONPATH", "")
    ).rstrip(os.pathsep)
    for attempt in (1, 2):  # fresh port on retry (port-pick TOCTOU)
        procs = _spawn_cluster(str(script), env_base, repo_root)
        outs = []
        try:
            for p in procs:
                out, err = p.communicate(timeout=80)
                outs.append((p.returncode, out, err))
        except subprocess.TimeoutExpired:
            outs = None  # coordinator never formed (port stolen / hang)
        finally:
            for p in procs:  # never leak workers, even on failure paths
                if p.poll() is None:
                    p.kill()
                    p.communicate()
        if outs is not None:
            break
        assert attempt == 1, "cluster failed to form twice"
    for rc, out, err in outs:
        assert rc == 0, f"worker failed: {err[-2000:]}"
        assert "MULTIHOST-OK" in out, (out, err[-500:])


def test_single_process_noop_topology():
    """Without cluster config, init_distributed is a no-op that still
    reports the local topology."""
    from gofr_tpu.parallel.multihost import init_distributed, is_primary

    topo = init_distributed()
    assert topo["process_count"] >= 1
    assert topo["global_devices"] >= topo["local_devices"] >= 1
    assert isinstance(is_primary(), bool)
