"""Disaggregated prefill/decode serving (docs/advanced-guide/sharded-serving.md).

One colocated engine interleaves prefill chunks and decode chunks on the
same chips, so a burst of long prompts steals decode steps from every
interactive stream (BENCH_r05's target_note: "single-chip infeasible at
128-tok prompts"). :class:`DisaggregatedLLMEngine` splits a replicated
fleet into two role pools instead — the DistServe/Splitwise serving
shape:

- **prefill pool** — replicas that run chunked prefill only: every
  request enters as an internal ``max_new_tokens=1`` probe whose prompt
  KV the engine publishes into its radix tree (gofr_tpu.kvcache.paged)
  with the last-token logits at prefill completion.
- **KV handoff** — the published blocks are gathered
  (``LLMEngine.kv_handoff_export``) and moved to a decode replica:
  direct ``jax.device_put`` onto the decode engine's committed
  device/submesh placement when possible, byte-identical host staging
  as the fallback and the A/B test oracle
  (``TPU_LLM_KV_HANDOFF_D2D=0``). The decode engine adopts them
  (``kv_handoff_import``) as an exact radix record WITH logits.
- **decode pool** — the caller's real request then admits on a decode
  replica as an exact prefix hit: prefill is skipped entirely, the
  first token re-samples from the transferred logits, and decode runs
  against the transferred blocks. Greedy outputs are token-identical to
  the colocated engine by construction — the exact-hit path is already
  pinned token-equal to the uncached path, and the handoff moves bytes.

Routing is by ROLE-SPECIFIC load: prefill replicas by queued prompt
tokens (their ``load_tokens`` is prompt-dominated — the internal probes
decode exactly one token), decode replicas by resident slots. Every
failure path degrades to a colocated submit — a dead decode pool
re-prefills on a live prefill replica, a dropped/evicted publish or an
exhausted pool simply costs a re-prefill on the decode side — so
disaggregation is an optimization with a correctness floor, never a new
failure mode. ``TPU_LLM_DISAGG=0`` (or just not building this class)
restores the colocated engine exactly.

Observability: ``app_llm_kv_handoff_seconds`` (submit -> decode-admit
handoff wall), ``app_llm_kv_handoffs_total{outcome=ok|miss|fallback}``,
``app_llm_collective_seconds{phase=kv_handoff_*}``, and per-role
``role="prefill"|"decode"`` labels on the engine phase histograms
(TTFT/TPOT/step walls split per pool).
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any

__all__ = ["DisaggregatedLLMEngine"]


class DisaggregatedLLMEngine:
    """Prefill-role and decode-role replica pools behind one
    LLMEngine-shaped surface (submit/generate/stats/drain/close).

    Construction mirrors :class:`~gofr_tpu.llm.ReplicatedLLMEngine` —
    ``replicas``/``devices`` for single-chip replicas, ``meshes`` for
    tensor-parallel submesh replicas — plus ``prefill_replicas``: the
    first P placements become the prefill pool, the rest decode. Each
    pool is a full ReplicatedLLMEngine (supervision, elastic rebuild,
    canary gates, in-pool failover), sharing ONE fairness ledger so
    per-client weighted ordering holds across roles.
    """

    def __init__(
        self,
        cfg,
        params,
        *,
        prefill_replicas: int | None = None,
        replicas: int | None = None,
        devices: list | None = None,
        meshes: list | None = None,
        handoff_timeout_s: float | None = None,
        handoff_d2d: bool | None = None,
        handoff_workers: int = 32,
        logger=None,
        supervise: bool = True,
        version: str = "v1",
        **engine_kw,
    ):
        import jax

        from .llm import EngineStoppedError  # noqa: F401 (re-raise type)
        from .llm import ReplicatedLLMEngine
        from .metrics import RollingWindow

        if engine_kw.get("kv_paged") is False:
            raise ValueError(
                "disaggregated serving requires the paged KV pool "
                "(kv_paged=False / TPU_LLM_KV_PAGED=0 cannot hand off "
                "blocks)"
            )
        if "mesh" in engine_kw or "param_specs" in engine_kw:
            # a single whole-slice mesh forwarded to every replica would
            # put both role pools on the SAME chips (the pool split a
            # no-op, the "handoff" a self-transfer, weights resident
            # once per replica) — TP disaggregation takes meshes=[...],
            # one disjoint submesh per replica (parallel.tp_submeshes)
            raise ValueError(
                "disaggregated serving takes meshes=[(mesh, specs), ...] "
                "(one disjoint submesh per replica), not a single "
                "mesh/param_specs pair shared by every replica"
            )
        # the handoff rides the radix tree: force a retention budget when
        # neither the prefix cache nor the session tier asked for one
        if (
            float(engine_kw.get("prefix_cache_mb") or 0.0) <= 0
            and float(engine_kw.get("session_mb") or 0.0) <= 0
        ):
            engine_kw["prefix_cache_mb"] = 64.0
        if prefill_replicas is None:
            prefill_replicas = int(
                os.environ.get("TPU_LLM_DISAGG_PREFILL_REPLICAS", "1") or 1
            )
        if handoff_timeout_s is None:
            handoff_timeout_s = float(
                os.environ.get("TPU_LLM_KV_HANDOFF_TIMEOUT_S", "10") or 10.0
            )
        if handoff_d2d is None:
            handoff_d2d = os.environ.get("TPU_LLM_KV_HANDOFF_D2D", "1") != "0"
        self.handoff_timeout_s = max(0.1, float(handoff_timeout_s))
        self.handoff_d2d = bool(handoff_d2d)
        self.logger = logger
        self.metrics = engine_kw.get("metrics")
        # trace continuity across the disagg seam: the probe, the KV
        # handoff, and the decode admit are phases of ONE caller journey —
        # submit() captures the caller's context (the handoff executor
        # threads never see the contextvar) and every phase span parents
        # under it (docs/advanced-guide/observability-serving.md#journeys)
        self.tracer = engine_kw.get("tracer")
        self.label = engine_kw.pop("kv_label", "llm")
        self.version = str(version)

        # -- split the placements into the two role pools -----------------
        pre_spec: dict[str, Any] = {}
        dec_spec: dict[str, Any] = {}
        if meshes is not None:
            P = int(prefill_replicas)
            if not (0 < P < len(meshes)):
                raise ValueError(
                    f"prefill_replicas={P} must leave both pools non-empty "
                    f"over {len(meshes)} meshes"
                )
            pre_spec["meshes"] = meshes[:P]
            dec_spec["meshes"] = meshes[P:]
        else:
            if devices is None:
                devs = jax.devices()
                n = max(2, int(replicas or 2))
                # round-robin when the host has fewer chips than replica
                # slots (the 1-device CPU case): the two pools then share
                # chips — correctness-identical, the role split still
                # isolates scheduling
                devices = [devs[i % len(devs)] for i in range(n)]
            P = int(prefill_replicas)
            if not (0 < P < len(devices)):
                raise ValueError(
                    f"prefill_replicas={P} must leave both pools non-empty "
                    f"over {len(devices)} devices"
                )
            pre_spec["devices"] = devices[:P]
            dec_spec["devices"] = devices[P:]
        self.prefill_replicas = P

        # ONE fairness ledger across both pools: least-served ordering
        # must hold no matter which role a request's work lands on
        from .resilience import FairLedger

        fq = engine_kw.get("fair_queuing")
        if fq is None:
            fq = os.environ.get("TPU_LLM_FAIR", "1") != "0"
        if fq and engine_kw.get("fair_ledger") is None:
            engine_kw["fair_ledger"] = FairLedger(
                engine_kw.pop("fair_weights", None)
            )

        self._stop = False
        self._draining = False
        self.submitted = 0
        self.handoffs_ok = 0  # decode admitted on transferred blocks
        self.handoffs_miss = 0  # handoff unavailable -> decode re-prefilled
        self.fallbacks = 0  # whole requests served colocated (pool down)
        self._handoff_window = RollingWindow()
        n_dec = (len(meshes) - P) if meshes is not None else (len(devices) - P)
        if logger is not None:
            logger.info(
                f"disaggregated LLM serving: {P} prefill + {n_dec} decode "
                f"replicas, handoff "
                f"{'d2d' if self.handoff_d2d else 'host-staged'}, "
                f"timeout {self.handoff_timeout_s:.1f}s"
            )
        self.prefill = ReplicatedLLMEngine(
            cfg, params, logger=logger, supervise=supervise,
            version=version, kv_label=f"{self.label}/prefill",
            role="prefill", **pre_spec, **engine_kw,
        )
        try:
            self.decode = ReplicatedLLMEngine(
                cfg, params, logger=logger, supervise=supervise,
                version=version, kv_label=f"{self.label}/decode",
                role="decode", **dec_spec, **engine_kw,
            )
        except BaseException:
            self.prefill.close()
            raise
        self._pool = ThreadPoolExecutor(
            max_workers=max(1, int(handoff_workers)),
            thread_name_prefix="llm-disagg-handoff",
        )
        self._lock = threading.Lock()

    # -- role-specific routing --------------------------------------------
    def _pick_prefill(self, exclude: set | frozenset = frozenset()):
        """Least queued PROMPT tokens. The prefill pool only ever runs
        the internal max_new=1 probes, so each engine's load_tokens IS
        its queued prompt tokens (plus one decode step per probe)."""
        live = [
            e for e in self.prefill.engines
            if e.accepting() and id(e) not in exclude
        ]
        if not live:
            return None
        return min(live, key=lambda e: (e.load_tokens(), e.load()))

    def _pick_decode(self, exclude: set | frozenset = frozenset()):
        """Fewest RESIDENT decode slots (streams being served right
        now); queue depth and queued tokens break ties."""
        live = [
            e for e in self.decode.engines
            if e.accepting() and id(e) not in exclude
        ]
        if not live:
            return None
        return min(
            live,
            key=lambda e: (e.resident_slots(), e.load(), e.load_tokens()),
        )

    # -- LLMEngine surface --------------------------------------------------
    def submit(self, req):
        from .llm import (
            EngineDraining,
            EngineStoppedError,
            GenRequest,
        )

        if self._stop:
            raise EngineStoppedError("engine stopped")
        if self._draining:
            raise EngineDraining("engine draining (rolling deploy)")
        with self._lock:
            self.submitted += 1
        # capture the caller's trace context HERE, on the submitting
        # thread — _serve runs on the handoff executor where the tracing
        # contextvar is empty, and without this stamp the probe and the
        # decode-side request would each start a FRESH trace (the
        # shattered-journey bug this threading exists to fix)
        if self.tracer is not None and req.traceparent is None:
            from .tracing import current_span

            cs = current_span()
            if cs is not None and cs.end_ns == 0:
                req.traceparent = cs.traceparent
        if req.session_id:
            # conversation KV lives with the decode pool (the publishing
            # side); routing turns through the prefill pool would
            # re-prefill the whole history every time. The decode fleet's
            # session affinity serves these colocated.
            return self.decode.submit(req)
        peng = self._pick_prefill()
        if peng is None:
            # prefill pool down: degrade to colocated on the decode pool
            # (it re-prefills) — capacity shrinks, requests never bounce
            with self._lock:
                self.fallbacks += 1
            self._count_handoff("fallback")
            return self.decode.submit(req)
        dspan = None
        if self.tracer is not None:
            from .tracing import parse_traceparent

            # one detached journey span for the whole disagg decision:
            # the prefill probe's llm.request, the handoff phases, and
            # the decode-side llm.request all parent under it, so the
            # stitcher renders probe -> handoff -> decode as ONE subtree
            dspan = self.tracer.start_detached_span(
                "llm.disagg",
                parent=parse_traceparent(req.traceparent),
                attributes={
                    "llm.model": self.label,
                    "llm.request_id": req.id,
                    "llm.prompt_tokens": len(req.prompt_tokens),
                },
            )
            req.traceparent = dspan.traceparent
            if req.journey_id is None:
                req.journey_id = dspan.trace_id
        preq = GenRequest(
            list(req.prompt_tokens), max_new_tokens=1, temperature=0.0,
            eos_token=-1, priority=req.priority, client=req.client,
            deadline=req.deadline, traceparent=req.traceparent,
        )
        # synchronous prefill-pool admission: overload/validation errors
        # (429 + Retry-After, prompt-too-long) surface to the CALLER,
        # exactly like a colocated submit — backpressure must not vanish
        # into the handoff executor
        tried: set[int] = set()
        while True:
            try:
                peng.submit(preq)
                break
            except (EngineStoppedError, EngineDraining):
                tried.add(id(peng))
                peng = self._pick_prefill(exclude=tried)
                if peng is None:
                    # raced the whole pool away: colocated fallback
                    with self._lock:
                        self.fallbacks += 1
                    self._count_handoff("fallback")
                    if dspan is not None:
                        dspan.set_attribute("llm.disagg.outcome", "fallback")
                        dspan.end()
                    return self.decode.submit(req)
        t0 = time.perf_counter()
        self._pool.submit(self._serve, req, peng, preq, t0, dspan)
        return req

    def _rec_phase(self, dspan, name: str, t0_ns: int, attrs: dict) -> None:
        """Retrospective child span for one handoff phase (worker thread,
        wall-clock anchored — same pattern as LLMEngine._phase_span)."""
        if dspan is None or self.tracer is None:
            return
        self.tracer.record_span(
            name, trace_id=dspan.trace_id, parent_id=dspan.span_id,
            start_ns=t0_ns, end_ns=time.time_ns(), attributes=attrs,
        )

    def _serve(self, req, peng, preq, t0: float, dspan=None) -> None:
        """Handoff worker: wait out the prefill probe, move the prompt's
        KV blocks to a decode replica, then hand the caller's request to
        it (an exact radix hit — prefill skipped). Every failure mode
        falls back to a colocated submit; the stream only errors when NO
        live replica exists anywhere."""
        try:
            probe_t0 = time.time_ns()
            try:
                preq.tokens(timeout=max(60.0, self.handoff_timeout_s))
                prefilled = preq.finish_reason in ("eos", "length")
            except Exception:  # noqa: BLE001 — probe died with its replica
                prefilled = False
            self._rec_phase(dspan, "disagg.prefill_probe", probe_t0, {
                "llm.request_id": req.id,
                "disagg.prefilled": prefilled,
            })
            handoff_t0 = time.time_ns()
            payload = None
            if prefilled and peng.alive():
                try:
                    payload = peng.kv_handoff_export(
                        req.prompt_tokens, timeout=self.handoff_timeout_s
                    )
                except Exception as e:  # noqa: BLE001 — export is best-effort
                    if self.logger is not None:
                        self.logger.warn(f"kv handoff export failed: {e!r}")
                    payload = None
            handoff_bytes = sum(
                int(getattr(payload.get(k), "nbytes", 0) or 0)
                for k in ("k", "v")
            ) if payload is not None else 0
            deng = self._pick_decode()
            imported = False
            if deng is not None and payload is not None:
                try:
                    payload = self._transfer(payload, deng)
                    imported = deng.kv_handoff_import(
                        payload, timeout=self.handoff_timeout_s
                    )
                except Exception as e:  # noqa: BLE001 — import is best-effort
                    if self.logger is not None:
                        self.logger.warn(f"kv handoff import failed: {e!r}")
                    imported = False
            admit_t0 = time.time_ns()
            placed_on = self._submit_decode(req, deng)
            # outcome AFTER placement: "ok" means the request was
            # actually accepted by the replica holding the transferred
            # blocks — an import whose target died/drained before the
            # submit re-prefilled elsewhere and is a miss, not a win
            if imported and placed_on is deng:
                outcome = "ok"
                dt = time.perf_counter() - t0
                with self._lock:
                    self.handoffs_ok += 1
                self._handoff_window.observe(dt)
                self._count_handoff("ok")
                if self.metrics is not None:
                    self.metrics.record_histogram(
                        "app_llm_kv_handoff_seconds", dt, model=self.label,
                        exemplar=(
                            {"trace_id": dspan.trace_id}
                            if dspan is not None else None
                        ),
                    )
            else:
                outcome = "miss"
                with self._lock:
                    self.handoffs_miss += 1
                self._count_handoff("miss")
            self._rec_phase(dspan, "disagg.kv_handoff", handoff_t0, {
                "llm.request_id": req.id,
                "disagg.outcome": outcome,
                "disagg.bytes": handoff_bytes,
                "disagg.imported": imported,
            })
            self._rec_phase(dspan, "disagg.decode_admit", admit_t0, {
                "llm.request_id": req.id,
                "disagg.placed": placed_on is not None,
                "disagg.on_transfer_target": placed_on is deng,
            })
            if dspan is not None:
                dspan.set_attribute("llm.disagg.outcome", outcome)
                dspan.set_attribute("llm.disagg.bytes", handoff_bytes)
                if placed_on is None:
                    dspan.set_status("ERROR")
                dspan.end()
        except BaseException as e:  # noqa: BLE001 — the stream must terminate
            if self.logger is not None:
                self.logger.error(f"disaggregated serve failed: {e!r}")
            if dspan is not None and dspan.end_ns == 0:
                dspan.set_attribute("error", repr(e))
                dspan.set_status("ERROR")
                dspan.end()
            if req.finish_reason is None:
                req.finish_reason = "error"
                req.out.put(None)

    def _submit_decode(self, req, deng):
        """Place the caller's request: the import target first, then the
        rest of the decode pool, then the prefill pool (colocated
        re-prefill — the handoff-failure failover the tests pin).
        Overloaded replicas are waited out inside a bounded window.
        Returns the engine the request landed on (None = stream
        errored: no live replica anywhere / deadline spent)."""
        from .llm import EngineDraining, EngineOverloaded, EngineStoppedError

        deadline = time.perf_counter() + max(5.0, self.handoff_timeout_s)
        tried: set[int] = set()
        fell_back = False
        while True:
            eng = deng if (deng is not None and id(deng) not in tried) else None
            if eng is None:
                eng = self._pick_decode(exclude=tried)
            if eng is None:
                # decode pool gone: re-prefill colocated on the prefill
                # pool — token-identical, counted as a fallback
                eng = self._pick_prefill(exclude=tried)
                if eng is None:
                    if req.finish_reason is None:
                        req.finish_reason = "error"
                        req.out.put(None)
                    return None
                if not fell_back:
                    fell_back = True
                    with self._lock:
                        self.fallbacks += 1
                    self._count_handoff("fallback")
            try:
                eng.submit(req)
                return eng
            except (EngineStoppedError, EngineDraining):
                tried.add(id(eng))
            except EngineOverloaded:
                if time.perf_counter() >= deadline:
                    if req.finish_reason is None:
                        req.finish_reason = "error"
                        req.out.put(None)
                    return None
                time.sleep(0.05)

    def _transfer(self, payload: dict, deng) -> dict:
        """Move an export payload onto the decode engine's placement:
        direct device-to-device ``jax.device_put`` against the
        committed device/submesh when enabled and available, else
        byte-identical host staging (numpy) — the fallback and the
        equality tests' oracle."""
        import jax
        import numpy as np

        t0 = time.perf_counter()
        target = deng.kv_placement() if self.handoff_d2d else None
        # a NamedSharding target describes the 5-D pool layout: only the
        # K/V stacks match its rank — scales/logits host-stage alongside
        pool_only = target is not None and hasattr(target, "spec")

        def move(a, pool_shaped: bool):
            if a is None:
                return None
            if target is None or (pool_only and not pool_shaped):
                return np.asarray(a)
            return jax.device_put(a, target)

        out = dict(
            payload,
            k=move(payload["k"], True),
            v=move(payload["v"], True),
            sc=move(payload.get("sc"), False),
            logits=move(payload.get("logits"), False),
        )
        for key in ("k", "v"):
            if hasattr(out[key], "block_until_ready"):
                out[key].block_until_ready()
        if self.metrics is not None:
            self.metrics.record_histogram(
                "app_llm_collective_seconds", time.perf_counter() - t0,
                model=self.label, phase="kv_handoff_transfer",
            )
        return out

    def _count_handoff(self, outcome: str) -> None:
        if self.metrics is not None:
            self.metrics.increment_counter(
                "app_llm_kv_handoffs_total", model=self.label,
                outcome=outcome,
            )

    def generate(self, prompt_tokens: list[int], **kw) -> list[int]:
        from .llm import GenRequest

        return self.submit(GenRequest(prompt_tokens, **kw)).tokens()

    def deploy(self, *a, **kw):
        """Weight rollouts are not yet wired for disaggregated fleets —
        raise loudly. Without this, ModelHandle.deploy's hasattr
        dispatch would fall through to the bare-engine swap rollout and
        silently replace the whole prefill/decode topology with one
        default single-chip engine. Roll the pools by process
        replacement behind the drain lifecycle instead
        (docs/advanced-guide/sharded-serving.md)."""
        from .resilience.rollout import RolloutError

        raise RolloutError(
            "weight rollouts are not supported for disaggregated "
            "prefill/decode fleets yet; drain and replace the process "
            "instead"
        )

    # -- aggregate views ----------------------------------------------------
    @property
    def engines(self):
        return list(self.prefill.engines) + list(self.decode.engines)

    def load(self) -> int:
        return self.prefill.load() + self.decode.load()

    def load_tokens(self) -> int:
        return self.prefill.load_tokens() + self.decode.load_tokens()

    def throughput_tok_s(self) -> float | None:
        """Pooled measured throughput across BOTH role pools — the
        scale-out fleet view reads one number per process
        (docs/advanced-guide/scale-out.md)."""
        vals = [
            p.throughput_tok_s() for p in (self.prefill, self.decode)
        ]
        tput = sum(v for v in vals if v)
        return tput if tput > 1e-9 else None

    def predicted_wait_s(self) -> float | None:
        tput = self.throughput_tok_s()
        if tput is None:
            return None
        return self.load_tokens() / tput

    def stats(self) -> dict:
        pre = self.prefill.stats()
        dec = self.decode.stats()
        return {
            "disaggregated": True,
            "version": self.version,
            "draining": self._draining,
            "submitted": self.submitted,
            "prefill_replicas": pre["replicas"],
            "decode_replicas": dec["replicas"],
            "replicas": pre["replicas"] + dec["replicas"],
            "replicas_alive": pre["replicas_alive"] + dec["replicas_alive"],
            "slots": pre["slots"] + dec["slots"],
            "active": pre["active"] + dec["active"],
            "waiting": pre["waiting"] + dec["waiting"],
            "handoff": {
                "ok": self.handoffs_ok,
                "miss": self.handoffs_miss,
                "fallbacks": self.fallbacks,
                "d2d": self.handoff_d2d,
                "timeout_s": self.handoff_timeout_s,
                "latency": self._handoff_window.summary(),
            },
            # per-pool phase percentiles: the per-role TTFT/TPOT split
            # (prefill pool TTFT ~= prefill wall; decode pool TTFT ~=
            # handoff-hit admission + first sample)
            "prefill": pre,
            "decode": dec,
        }

    def debug_state(self) -> dict:
        from .metrics.slo import pool_snapshots

        pre = self.prefill.debug_state()
        dec = self.decode.debug_state()
        return {
            "disaggregated": True,
            "draining": self._draining,
            "handoff": {
                "ok": self.handoffs_ok,
                "miss": self.handoffs_miss,
                "fallbacks": self.fallbacks,
                "d2d": self.handoff_d2d,
                "timeout_s": self.handoff_timeout_s,
                "latency": self._handoff_window.summary(),
            },
            # pooled across BOTH role pools (the caller's SLO does not
            # care which pool burned the budget)
            "slo": pool_snapshots(
                [s for s in (pre.get("slo"), dec.get("slo")) if s]
            ) or None,
            "prefill": pre,
            "decode": dec,
        }

    # -- lifecycle ----------------------------------------------------------
    def drain(self) -> None:
        self._draining = True
        self.prefill.drain()
        self.decode.drain()

    def drained(self) -> bool:
        return self.prefill.drained() and self.decode.drained()

    def close(self) -> None:
        self._stop = True
        self._draining = True
        # stop accepting handoff work, let in-flight workers finish their
        # (now fast-failing) submits, then tear the pools down
        self._pool.shutdown(wait=False)
        self.prefill.close()
        self.decode.close()
