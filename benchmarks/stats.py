"""The arithmetic from request records to end-to-end metrics.

A record is what the load generator kept of one request: when it was due and
sent, the host-clock stamp of every token it yielded, how many it asked for,
and whether it failed. All times are `time.perf_counter()` seconds of the
benchmark's own process.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Record:
    spec: object  # traffic.Spec
    prompt_len: int
    asked: int
    due: float  # closed loop: the moment of submit; open loop: the schedule's
    sent: float
    stamps: list = dataclasses.field(default_factory=list)  # one per token
    tokens: list = dataclasses.field(default_factory=list)
    ended: float | None = None
    error: str = ""  # non-empty: the request failed
    cut: bool = False  # cancelled by the harness when the window closed
    admitted_at: float | None = None  # the engine's own clock (same timebase)
    prefix_hit: bool = False

    @property
    def failed(self) -> bool:
        return bool(self.error)

    @property
    def finished(self) -> bool:
        return self.ended is not None and not self.error and not self.cut


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default), so a
    hand-made list can be checked by hand."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ttft_ms(r: Record) -> float:
    """Due (not sent: a late generator's wait counts) to the first token."""
    return (r.stamps[0] - r.due) * 1e3


def tpot_ms(r: Record) -> float | None:
    """(last token - first token) / (tokens - 1); None for one token."""
    if len(r.stamps) < 2:
        return None
    return (r.stamps[-1] - r.stamps[0]) / (len(r.stamps) - 1) * 1e3


def in_window(t: float | None, t0: float, t1: float) -> bool:
    """(t0, t1]: both edges are the stamp that ends a step's burst of tokens
    (`Load.burst_end`); the burst at t0 lies before the window, the one at t1 in it."""
    return t is not None and t0 < t <= t1


def window_summary(records: list, t0: float, t1: float) -> dict:
    """Everything the end-to-end metrics and the earlier lines need, over
    the window (t0, t1]: all the work and all the time."""
    tokens = sum(1 for r in records for s in r.stamps if in_window(s, t0, t1))
    first = [r for r in records if r.stamps and in_window(r.stamps[0], t0, t1) and not r.failed]
    done = [r for r in records if r.finished and in_window(r.ended, t0, t1)]
    failed = [r for r in records if r.failed and in_window(r.ended, t0, t1)]
    ttfts = [ttft_ms(r) for r in first]
    tpots = [x for x in (tpot_ms(r) for r in done) if x is not None]
    prompt = len(prefill_contexts(records, t0, t1))
    return {
        "seconds": t1 - t0,
        "prompt_tokens": prompt,
        "first": first,
        "tokens": tokens,
        "tokens_per_s": tokens / (t1 - t0),
        "ttft_ms": ttfts,
        "tpot_ms": tpots,
        "completed": len(done),
        "failed": len(failed),
        "attempted": len(done) + len(failed),
        "errors": sorted({r.error for r in failed})[:5],
        "done": done,
    }


def tails(values, qs=(50, 90, 95, 99)) -> dict:
    return {**{f"p{q}": percentile(values, q) for q in qs if values}, "n": len(values)}


def decode_contexts(records: list, ta: float, tb: float) -> list:
    """Context length (prompt + tokens so far, itself included) of every
    token after a request's first that was yielded in [ta, tb): those are the
    tokens a decode iteration produced (the first comes out of prefill)."""
    return [r.prompt_len + j + 1 for r in records
            for j, s in enumerate(r.stamps) if j > 0 and ta <= s < tb]


def prefill_contexts(records: list, ta: float, tb: float) -> list:
    """Context lengths of the prompt tokens processed in [ta, tb). The
    engine does not say when each chunk ran, so a prompt is taken to be
    processed evenly between its admission (else its submit) and its first
    token; the part of that stretch inside [ta, tb) gives the positions."""
    out: list = []
    for r in records:
        if not r.stamps:
            end = r.ended if r.ended is not None else tb
            if r.failed:
                continue
        else:
            end = r.stamps[0]
        start = r.admitted_at if r.admitted_at is not None and r.admitted_at <= end else r.sent
        if end <= ta or start >= tb or end <= start:
            continue
        a = (max(start, ta) - start) / (end - start)
        b = (min(end, tb) - start) / (end - start)
        out.extend(range(int(a * r.prompt_len) + 1, int(b * r.prompt_len) + 1))
    return out


def end_to_end(summary: dict) -> dict:
    """Every statistic of the window that BENCHMARK.json may judge, by name.
    The harness reports the ones the cell's `end_to_end` entries list and
    prints the rest under `extra`, so a later benchmark PR can weigh them
    and add an entry without touching this file."""
    out = {"tokens_per_s": summary["tokens_per_s"],
           "prompt_tokens_per_s": summary["prompt_tokens"] / summary["seconds"]}
    for name, xs in (("ttft_ms", summary["ttft_ms"]), ("tpot_ms", summary["tpot_ms"]),
                     ("request_ms", [(r.ended - r.due) * 1e3 for r in summary["done"]])):
        if xs:
            out[name + "_mean"] = sum(xs) / len(xs)
            out[name + "_p50"] = percentile(xs, 50)
            out[name + "_p90"] = percentile(xs, 90)
            out[name + "_n"] = len(xs)
    return out
