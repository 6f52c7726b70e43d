"""The load generator: threads that drive the LLM handle and keep records.

Closed loop: one thread per client, each sends its next request when the
last has ended. Open loop: one sender thread submits on the plan's schedule
whether or not earlier requests have ended, and a small pool of reader
threads drains the streams; a request is timed from when it was DUE, and how
late the sender ran is kept.

The only things taken from the program are `handle.submit(req)`,
`req.stream(timeout)` and the request's own `admitted_at`/`prefix_hit`.
"""

from __future__ import annotations

import threading
import time

from stats import Record

BURST_GAP_S = 0.05  # tokens closer together than this came out of one step of the engine


class Load:
    def __init__(self, handle, make_request, plan, *, timeout_s: float = 120.0):
        self.handle = handle
        self.make_request = make_request  # (tokens, max_new) -> GenRequest
        self.plan = plan
        self.timeout_s = timeout_s
        self.records: list[Record] = []
        self.lateness_s: list[float] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._live: dict[int, object] = {}  # id(record) -> request in flight
        self._threads: list[threading.Thread] = []
        self._first_token = [threading.Event() for _ in range(plan.clients)]

    # -- one request ------------------------------------------------------
    def _run_one(self, spec, due: float | None = None) -> Record:
        tokens = self.plan.tokens(spec)
        req = self.make_request(tokens, spec.output_len)
        now = time.perf_counter()
        rec = Record(spec=spec, prompt_len=len(tokens), asked=spec.output_len,
                     due=now if due is None else due, sent=now)
        with self._lock:
            self.records.append(rec)
            self._live[id(rec)] = req
        try:
            self.handle.submit(req)
            for tok in req.stream(timeout=self.timeout_s):
                rec.stamps.append(time.perf_counter())
                rec.tokens.append(int(tok))
                if len(rec.stamps) == 1 and spec.client < len(self._first_token):
                    self._first_token[spec.client].set()
            rec.ended = time.perf_counter()
            if req.cancelled:
                rec.cut = True
            elif len(rec.tokens) != spec.output_len:
                rec.error = (f"asked {spec.output_len} tokens, got {len(rec.tokens)} "
                             f"(finish_reason {req.finish_reason!r})")
        except Exception as e:  # noqa: BLE001 — a failed request is a record, not a crash
            rec.ended = time.perf_counter()
            rec.error = f"{type(e).__name__}: {e}"[:200]
        finally:
            rec.admitted_at = getattr(req, "admitted_at", None)
            rec.prefix_hit = bool(getattr(req, "prefix_hit", False))
            with self._lock:
                self._live.pop(id(rec), None)
        return rec

    # -- closed loop ------------------------------------------------------
    def _client(self, c: int) -> None:
        k = 0
        while not self._stop.is_set():
            self._run_one(self.plan.client_request(c, k))
            k += 1

    # -- open loop --------------------------------------------------------
    def _sender(self, specs: list, start: float) -> None:
        for spec in specs:
            due = start + spec.due_s
            wait = due - time.perf_counter()
            if wait > 0 and self._stop.wait(wait):
                return
            if self._stop.is_set():
                return
            self.lateness_s.append(max(0.0, time.perf_counter() - due))
            t = threading.Thread(target=self._run_one, args=(spec, due), daemon=True)
            t.start()
            self._threads.append(t)

    # -- driving ----------------------------------------------------------
    def start(self, horizon_s: float = 0.0) -> None:
        if self.plan.kind == "closed":
            for c in range(self.plan.clients):
                t = threading.Thread(target=self._client, args=(c,), daemon=True)
                t.start()
                self._threads.append(t)
        else:
            specs = self.plan.arrivals(horizon_s)
            t = threading.Thread(target=self._sender, args=(specs, time.perf_counter()), daemon=True)
            t.start()
            self._threads.append(t)

    def wait_ramp(self, timeout_s: float) -> None:
        """Closed loop: until every client has had a first token. Open
        loop: until the first request has."""
        deadline = time.perf_counter() + timeout_s
        events = self._first_token if self.plan.kind == "closed" else self._first_token[:1]
        for e in events:
            if not e.wait(max(0.0, deadline - time.perf_counter())):
                raise TimeoutError("the ramp did not finish: a client never had a first token")

    def burst_end(self, after: float, wait_s: float, gap_s: float = BURST_GAP_S) -> float | None:
        """The stamp of the last token of the first burst that has a token at
        or after `after`; None where no token came within `wait_s` of it. An
        engine yields the tokens of one step together (a burst), and steps lie
        far apart beside `gap_s`. A window whose edges are such stamps holds
        whole steps, so its token count does not turn on which lanes' tokens
        of the step at the edge were stamped before a moment the host picked.
        Where tokens stream with no gap that wide (steps far shorter than
        `gap_s`), any stamp will do."""
        streaming_since = None
        while True:
            time.sleep(gap_s / 5)
            last = max((r.stamps[-1] for r in self._records() if r.stamps), default=0.0)
            now = time.perf_counter()
            if last >= after:
                if now - last >= gap_s:
                    return last
                streaming_since = streaming_since or now
                if now - streaming_since >= 10 * gap_s:
                    return last
            elif now - after > wait_s:
                return None

    def step_seconds(self, t0: float, t1: float, gap_s: float = BURST_GAP_S) -> float:
        """The median distance between the bursts of (t0, t1]: what a step
        that yields tokens takes. 0.0 where there were fewer than three."""
        stamps = sorted(s for r in self._records() for s in list(r.stamps) if t0 < s <= t1)
        starts = [b for a, b in zip(stamps, stamps[1:]) if b - a > gap_s]
        gaps = sorted(b - a for a, b in zip(starts, starts[1:]))
        return gaps[len(gaps) // 2] if gaps else 0.0

    def _records(self) -> list:
        with self._lock:
            return list(self.records)

    def stop(self, grace_s: float = 60.0) -> None:
        """Close: no new requests, cancel what is in flight (they were not
        due inside the window), and wait for every thread to end."""
        self._stop.set()
        with self._lock:
            live = list(self._live.values())
        for req in live:
            req.cancel()
        deadline = time.perf_counter() + grace_s
        for t in list(self._threads):
            t.join(max(0.0, deadline - time.perf_counter()))
        alive = [t for t in self._threads if t.is_alive()]
        if alive:
            raise TimeoutError(f"{len(alive)} load thread(s) did not end {grace_s} s after the close")
