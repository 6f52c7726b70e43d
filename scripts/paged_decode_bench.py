#!/usr/bin/env python
"""Microbenchmark of the paged-decode kernel alone, on the chip.

    chiprun -- python scripts/paged_decode_bench.py
    chiprun -- env PYTHONPATH=.scratch/parent python scripts/paged_decode_bench.py

Times one layer-call of ``ops.attention`` at the shapes of
``qwen2-7b.reason-closed`` (hq 28, hkv 4, d 128, 16-token blocks, a table of
120, a bf16 pool of 2,394 blocks) with every lane's context at 16, 700 and
1,792 tokens and at a spread like the cell's, for 16 and 48 lanes: the
kernel's partials alone, the whole paged_chunk_decode_attention around it,
and the dense-gather path (use_kernel=False). 28 calls (a model's layers)
run inside one jit, each through a block table of its own (so that the
gather cannot be hoisted out of the loop), and the host's dispatch is
outside the number. Each row also gives max |kernel - gather| on the same
inputs. ``PYTHONPATH`` picks the checkout whose kernel is timed (this one's
by default). One JSON line per row on stdout, and all of them in
chiprun_out/paged_decode_bench.<here | the other checkout's name>.jsonl.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.append(REPO)  # after PYTHONPATH: another checkout named there wins

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from gofr_tpu.ops import attention as A  # noqa: E402

HQ, HKV, D, BLOCK, N_TBL, N_POOL, STEPS, LAYERS = 28, 4, 128, 16, 120, 2394, 8, 28
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(A.__file__))))
REST = ("k_pool", "v_pool", "k_buf", "v_buf", "lengths", "step")  # after (q, tables)


def inputs(lanes: int, contexts: np.ndarray, seed: int = 0):
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 8))

    def rand(shape):
        return jax.random.normal(next(ks), shape, jnp.bfloat16)

    return dict(
        qs=rand((LAYERS, lanes, 1, HQ, D)),
        k_pool=rand((N_POOL, BLOCK, HKV, D)), v_pool=rand((N_POOL, BLOCK, HKV, D)),
        tables=jax.random.randint(next(ks), (LAYERS, lanes, N_TBL), 0, N_POOL, jnp.int32),
        k_buf=rand((lanes, STEPS, HKV, D)), v_buf=rand((lanes, STEPS, HKV, D)),
        lengths=jnp.asarray(contexts, jnp.int32), step=jnp.asarray(3, jnp.int32),
    )


def per_call_us(fn, x) -> tuple[float, float]:
    """(least, median) microseconds per layer-call: 28 calls inside one jit,
    7 timed runs after one that compiles."""

    def layers(qs, tables, *rest):
        return jax.lax.map(lambda qt: fn(*qt, *rest), (qs, tables))

    run = jax.jit(layers)
    args = (x["qs"], x["tables"], *(x[k] for k in REST))
    jax.block_until_ready(run(*args))
    times = []
    for _ in range(7):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        times.append((time.perf_counter() - t0) / LAYERS * 1e6)
    return min(times), statistics.median(times)


def attend(use_kernel):
    def fn(q, tables, k_pool, v_pool, k_buf, v_buf, lengths, step):
        return A.paged_chunk_decode_attention(
            q, k_pool, v_pool, tables, k_buf, v_buf, lengths, step, use_kernel=use_kernel
        )
    return fn


def partials(q, tables, k_pool, v_pool, k_buf, v_buf, lengths, step):
    return A._paged_decode_partials(
        q[:, 0], k_pool, v_pool, tables, jnp.zeros_like(lengths), lengths, scale=D ** -0.5
    )


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU here ({dev.platform}): a time from this device is not a result", file=sys.stderr)
        return 2
    with open(os.path.join(REPO, "benchmarks", "peaks.json")) as f:
        hbm_bytes_per_s = json.load(f)[dev.device_kind]["hbm_bytes_per_s"]  # no default
    rows = []
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    rng = np.random.default_rng(0)
    for lanes in (16, 48):
        cases = {
            "16": np.full(lanes, 16), "700": np.full(lanes, 700), "1792": np.full(lanes, 1792 - STEPS),
            "spread 300-1100": rng.integers(300, 1100, lanes),
        }
        for name, ctx in cases.items():
            x = inputs(lanes, ctx)
            kern_min, kern_med = per_call_us(partials, x)
            att_min, att_med = per_call_us(attend(True), x)
            row = {
                "checkout": CHECKOUT,
                "device": dev.device_kind, "lanes": lanes, "contexts": name,
                "kernel_us_min": round(kern_min, 1), "kernel_us_median": round(kern_med, 1),
                "attend_us_min": round(att_min, 1), "attend_us_median": round(att_med, 1),
            }
            kv_bytes = int(ctx.sum()) * HKV * D * 2 * 2
            row["kv_mb"] = round(kv_bytes / 1e6, 2)
            row["kernel_roofline_pct"] = round(100 * kv_bytes / hbm_bytes_per_s / (kern_min * 1e-6), 2)
            if name in ("700", "spread 300-1100"):
                g_min, g_med = per_call_us(attend(False), x)
                row["gather_us_min"], row["gather_us_median"] = round(g_min, 1), round(g_med, 1)
            one = (x["qs"][0], x["tables"][0], *(x[k] for k in REST))
            got = jax.jit(attend(True))(*one)
            with jax.default_matmul_precision("highest"):
                want = jax.jit(attend(False))(*one)
            row["max_abs_diff"] = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))))
            rows.append(row)
            print(json.dumps(row), flush=True)
    tag = "here" if CHECKOUT == REPO else os.path.basename(CHECKOUT)
    with open(os.path.join(REPO, "chiprun_out", f"paged_decode_bench.{tag}.jsonl"), "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
