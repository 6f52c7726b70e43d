"""Multi-host bootstrap: the distributed-communication backend's entry
point.

The reference scales across machines with NCCL/MPI-style app-level
planes; here the collective plane is XLA over ICI (intra-slice) and DCN
(inter-slice), and multi-host just means every process joins one jax
runtime before building its Mesh: `jax.devices()` then enumerates the
GLOBAL device set, the same `make_mesh`/`param_specs` annotations apply
unchanged, and GSPMD routes collectives over ICI within a slice and DCN
across slices. On Cloud TPU pods `jax.distributed.initialize()`
auto-discovers the topology; elsewhere (CPU fleets, tests) the
coordinator is configured explicitly — env convention:

    GOFR_COORDINATOR=host:port   # process 0's address
    GOFR_NUM_PROCESSES=N
    GOFR_PROCESS_ID=i

`tests/test_multihost.py` runs a REAL 2-process CPU cluster through
this path (initialize → global mesh → cross-process collective).
"""

from __future__ import annotations

import os

__all__ = ["init_distributed", "topology", "is_primary"]


def init_distributed(
    coordinator: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> dict:
    """Join (or form) the multi-process jax runtime, then report topology.

    No-op when neither arguments nor env configure a cluster AND the
    platform isn't a TPU pod (single-process mode). Safe to call twice
    (jax raises on re-initialize; already-initialized is not an error
    here — the topology is simply reported).
    """
    import jax

    coordinator = coordinator or os.environ.get("GOFR_COORDINATOR")
    if num_processes is None:
        n = os.environ.get("GOFR_NUM_PROCESSES")
        num_processes = int(n) if n else None
    if process_id is None:
        p = os.environ.get("GOFR_PROCESS_ID")
        process_id = int(p) if p else None

    if not jax.distributed.is_initialized():
        if coordinator is not None:
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=num_processes,
                process_id=process_id,
            )
        elif _tpu_plausible():
            # TPU pods self-discover coordinator/topology from metadata;
            # single-host TPU initializes to a 1-process "cluster". The
            # plausibility check must NOT touch jax.default_backend():
            # evaluating it initializes XLA, after which initialize()
            # always raises — so detect via libtpu/env, and treat a
            # too-late call as single-process rather than crashing.
            try:
                jax.distributed.initialize()
            except (RuntimeError, ValueError):
                pass  # backend already up, or not actually a pod
    return topology()


def _tpu_plausible() -> bool:
    """TPU presence WITHOUT initializing the XLA backend."""
    import importlib.util

    if "tpu" in os.environ.get("JAX_PLATFORMS", "").lower():
        return True
    return importlib.util.find_spec("libtpu") is not None


def topology() -> dict:
    """Global/local device facts for logs, health, and sanity checks."""
    import jax

    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
        "platform": jax.default_backend(),
    }


def is_primary() -> bool:
    """True on process 0 — gate checkpoint writes, topic creation, and
    singleton side effects the way rank-0 guards do under MPI."""
    import jax

    return jax.process_index() == 0
