"""The generator is a pure function of its JSON and the seed; the percentile
and TPOT arithmetic on hand-made records."""
import dataclasses

import threading
import time
import types

import load as L
import pytest
import stats as S
import traffic

BASE = {"pool": 12, "pool_seed": 3,
        "prompt_tokens": {"median": 20, "sigma": 0.5, "min": 5, "max": 40},
        "output_tokens": {"median": 10, "sigma": 0.3, "min": 4, "max": 20}}
ARRIVALS = {
    "closed": {"kind": "closed", "clients": 4},
    "poisson": {"kind": "poisson", "rate": 20.0},
    "bursty": {"kind": "bursty", "rate": 20.0, "cv": 3.0},
}


def stream(plan):
    if plan.kind == "closed":
        return [plan.client_request(c, k) for c in range(plan.clients) for k in range(4)]
    return plan.arrivals(2.0)


@pytest.mark.parametrize("kind", sorted(ARRIVALS))
@pytest.mark.parametrize("prefix", [None, {"groups": 2, "prefix_tokens": 16, "turns": 2}])
def test_plan_is_a_pure_function_of_file_and_seed(kind, prefix):
    w = {**BASE, "arrival": ARRIVALS[kind], "prefix": prefix}
    a, b = traffic.Plan(w, 2**31 + 5, 512), traffic.Plan(w, 2**31 + 5, 512)
    sa, sb = stream(a), stream(b)
    assert sa and sa == sb
    assert [a.tokens(s) for s in sa] == [b.tokens(s) for s in sb]
    other = traffic.Plan(w, 7, 512)
    assert [a.tokens(s) for s in sa] != [other.tokens(s) for s in stream(other)]
    for s in sa:
        toks = a.tokens(s)
        assert len(toks) == s.prompt_len and all(1 <= t < 512 for t in toks)


@pytest.mark.parametrize("kind", sorted(ARRIVALS))
def test_every_seed_sends_the_same_schedule(kind):
    """Lengths, pairing, gaps and fractions are the file's; the seed draws
    the ids and nothing else."""
    w = {**BASE, "arrival": ARRIVALS[kind]}
    a, b = traffic.Plan(w, 1, 512), traffic.Plan(w, 2**31 + 9, 512)
    assert list(a.prompt_lens) == list(b.prompt_lens) and list(a.output_lens) == list(b.output_lens)
    shape = lambda p: [(s.client, s.prompt_len, s.output_len) for s in stream(p)]  # noqa: E731
    assert shape(a) == shape(b)
    if kind != "closed":
        assert list(a.gaps) == list(b.gaps)
        assert [s.due_s for s in stream(a)] == [s.due_s for s in stream(b)]
    other = traffic.Plan({**w, "pool_seed": 4}, 1, 512)
    assert list(other.prompt_lens) != list(a.prompt_lens)


def test_closed_first_request_is_staggered():
    p = traffic.Plan({**BASE, "arrival": ARRIVALS["closed"]}, 3, 512)
    for c in range(4):
        first, again = p.client_request(c, 0), p.client_request(c, 3)  # pool 12 = 3 rounds of 4
        assert first.prompt_len == again.prompt_len and 2 <= first.output_len <= again.output_len
    assert sorted(p.first_fraction) == [0.125, 0.375, 0.625, 0.875]


def test_bursty_is_burstier_than_poisson():
    import numpy as np

    w = {**BASE, "pool": 4000}
    po = traffic.Plan({**w, "arrival": ARRIVALS["poisson"]}, 1, 512).gaps
    bu = traffic.Plan({**w, "arrival": ARRIVALS["bursty"]}, 1, 512).gaps
    assert np.mean(po) == pytest.approx(0.05, rel=0.1) and np.mean(bu) == pytest.approx(0.05, rel=0.25)
    assert np.std(bu) / np.mean(bu) > 2.0 > 1.2 > np.std(po) / np.mean(po)


def test_sessions_extend_each_others_prompts():
    w = {**BASE, "arrival": ARRIVALS["closed"], "prefix": {"groups": 2, "prefix_tokens": 16, "turns": 3}}
    p = traffic.Plan(w, 11, 512)
    t0, t1, t2 = (p.tokens(p.client_request(1, k)) for k in range(3))
    assert t1[: len(t0)] == t0 and t2[: len(t1)] == t1 and len(t2) > len(t1) > len(t0)
    other = p.tokens(p.client_request(1, 3))  # next session
    assert other[16:32] != t0[16:32]


def rec(due, stamps, asked=None, error="", ended=None):
    r = S.Record(spec=None, prompt_len=10, asked=asked or len(stamps), due=due, sent=due)
    r.stamps = list(stamps)
    r.tokens = [1] * len(stamps)
    r.ended = ended if ended is not None else (stamps[-1] if stamps else due + 1)
    r.error = error
    return r


def test_percentile_by_hand():
    assert S.percentile([10, 20, 30, 40], 50) == 25
    assert S.percentile([10, 20, 30, 40, 50], 90) == pytest.approx(46)
    assert S.percentile([7], 90) == 7
    with pytest.raises(ValueError):
        S.percentile([], 50)


def test_ttft_tpot_and_window_by_hand():
    a = rec(10.0, [10.5, 10.6, 10.7, 10.8, 10.9])  # ttft 500 ms, tpot 100 ms
    b = rec(9.0, [9.9, 10.2, 10.5])  # first token before the window
    c = rec(10.2, [], error="TimeoutError", ended=10.9)
    d = rec(10.4, [10.6])  # one token: no tpot
    assert S.ttft_ms(a) == pytest.approx(500) and S.tpot_ms(a) == pytest.approx(100)
    assert S.tpot_ms(d) is None and S.tpot_ms(b) == pytest.approx(300)
    w = S.window_summary([a, b, c, d], 10.0, 11.0)
    assert w["tokens"] == 5 + 2 + 1 and w["tokens_per_s"] == pytest.approx(8.0)
    assert sorted(round(x) for x in w["ttft_ms"]) == [200, 500]
    assert sorted(round(x) for x in w["tpot_ms"]) == [100, 300]
    assert (w["completed"], w["failed"], w["attempted"]) == (3, 1, 4)
    e = S.end_to_end(w)
    assert e["tokens_per_s"] == pytest.approx(8.0) and e["ttft_ms_mean"] == pytest.approx(350)
    assert e["tpot_ms_mean"] == pytest.approx(200) and e["tpot_ms_p50"] == pytest.approx(200)
    assert (e["ttft_ms_n"], e["tpot_ms_n"], e["request_ms_n"]) == (2, 2, 3)
    assert "ttft_ms_mean" not in S.end_to_end(S.window_summary([], 10.0, 11.0))  # nothing to read: no number
    # the edges are stamps that end a burst: the one at t0 is before the window, the one at t1 in it
    assert S.window_summary([rec(9.0, [10.0, 10.5, 11.0])], 10.0, 11.0)["tokens"] == 2


@pytest.mark.parametrize("step_s, want", [(0.2, "the burst's last stamp"), (0.002, "any stamp")])
def test_a_window_edge_is_the_end_of_a_burst(step_s, want):
    """Bursts of 3 tokens a step: with steps far apart the edge is the last
    stamp of the first burst at or after the asked moment, not a stamp inside
    it; with steps shorter than the gap tokens stream and any stamp ends it."""
    load = L.Load(None, None, types.SimpleNamespace(clients=1, kind="closed"))
    r = rec(0.0, [])
    load.records.append(r)
    stop = threading.Event()

    def engine():
        while not stop.is_set():
            for _ in range(3):
                r.stamps.append(time.perf_counter())
                time.sleep(0.001)
            time.sleep(step_s)

    th = threading.Thread(target=engine, daemon=True)
    th.start()
    try:
        after = time.perf_counter() + 0.05
        edge = load.burst_end(after, wait_s=5.0, gap_s=0.05)
        step_s_read = load.step_seconds(0.0, edge, gap_s=0.05)
    finally:
        stop.set()
        th.join()
    assert load.burst_end(time.perf_counter(), wait_s=0.1, gap_s=0.05) is None  # no token any more: no edge
    assert edge in r.stamps and edge >= after
    i = r.stamps.index(edge)
    if want == "any stamp":
        assert edge - after < 1.0
    else:
        assert i % 3 == 2 and (i + 1 == len(r.stamps) or r.stamps[i + 1] - edge > 0.05)
        assert step_s_read == 0.0 or 0.2 <= step_s_read < 0.3  # 0.0: the edge came before a third burst
        assert i < 3 or r.stamps[i - 3] < after  # the FIRST such burst: the one before ended too early


def test_contexts_in_a_sub_window():
    a = rec(10.0, [10.5, 10.6, 10.7, 10.8])
    a.admitted_at = 10.1
    assert S.decode_contexts([a], 10.55, 10.75) == [12, 13]  # tokens 2 and 3 of a 10-token prompt
    # the prompt is taken to be processed evenly from 10.1 to 10.5: [10.2, 10.4) holds half
    assert S.prefill_contexts([a], 10.2, 10.4) == [3, 4, 5, 6, 7]
    assert S.prefill_contexts([a], 11.0, 12.0) == []
    assert dataclasses.is_dataclass(a)
