"""The request timeline's four readers (`first_row_wait_ms_mean`,
`prompt_rows_ms_mean`, `prompt_row_skip_pct`, `first_token_tail_ms_mean`) on
hand-built rings: the three means add up to the engine's TTFT request by
request, a request is read only where its rows' programs join the step records
by seq (and nothing is read below 90%), the window's edges are those of the
end-to-end metrics, and a program without request records reads nothing."""
import os

import pytest
import run as R

NAMES = ("first_row_wait_ms_mean", "prompt_rows_ms_mean", "prompt_row_skip_pct", "first_token_tail_ms_mean")
MODS = {n: R.load_file("metric", os.path.join(R.HERE, "metrics", n + ".py")) for n in NAMES}
REQ_FIELDS = ("id", "prompt", "cached", "t_submit", "t_admit", "seq_first_row", "seq_last_row",
              "rows", "row_steps", "t_first_token")
STEP_FIELDS = ("seq", "kind", "rows", "t_dispatch", "t_dispatched")


def a_run(requests, steps, t0=0.0, t1=100.0):
    """The harness's context as the readers see it, no trace."""
    return {"t0": t0, "t1": t1, "trace": None, "summary": {"first": requests},
            "stats1": {"request_log": {"fields": REQ_FIELDS, "records": tuple(requests)},
                       "step_log": {"fields": STEP_FIELDS, "records": tuple(steps)}}}


def step(seq, kind="step", rows=((0, 64, 64),)):
    return (seq, kind, rows, float(seq), float(seq) + 0.001)


def request(rid, first, last, n_rows, t_submit, t_first, prompt=256, cached=0):
    return (rid, prompt, cached, t_submit, t_submit + 0.5, first, last, n_rows, n_rows, t_first)


def read_all(run):
    return {n: MODS[n].read(run) for n in NAMES}


# programs 1..12 dispatched at t = seq: rows of A at 1-3, a decode-only chunk at
# 4, rows of B at 5 and 6, chunks 7-8, one row of C at 9
STEPS = [step(1), step(2), step(3), step(4, "chunk", ()), step(5), step(6),
         step(7, "chunk", ()), step(8, "chunk", ()), step(9), step(10, "chunk", ())]
A = request(1, 1, 3, 3, t_submit=0.5, t_first=4.25)
B = request(2, 3, 6, 3, t_submit=2.0, t_first=7.5)  # its rows at 3, 5, 6: program 4 skipped it
C = request(3, 9, 9, 1, t_submit=8.0, t_first=9.75)


def test_the_three_means_are_ttft_request_by_request():
    run = a_run([A, B, C], STEPS)
    got = read_all(run)
    assert got["first_row_wait_ms_mean"] == pytest.approx(1e3 * ((1 - 0.5) + (3 - 2.0) + (9 - 8.0)) / 3)
    assert got["prompt_rows_ms_mean"] == pytest.approx(1e3 * ((3 - 1) + (6 - 3) + 0) / 3)
    assert got["first_token_tail_ms_mean"] == pytest.approx(1e3 * ((4.25 - 3) + (7.5 - 6) + (9.75 - 9)) / 3)
    ttft = 1e3 * sum(r[9] - r[3] for r in (A, B, C)) / 3
    assert sum(got[n] for n in NAMES if n.endswith("_ms_mean")) == pytest.approx(ttft)


def test_the_skip_share_counts_the_decode_class_programs_of_each_stretch():
    # A: programs 1-3, all its own; B: 3-6 of which 4 carried none of its rows; C: 9 alone
    got = read_all(a_run([A, B, C], STEPS))["prompt_row_skip_pct"]
    assert got == pytest.approx(100.0 * 1 / (3 + 4 + 1))
    # a wave's prefill is a program of its own kind: the one program of its stretch
    wave = request(4, 11, 11, 1, t_submit=10.0, t_first=11.5)
    got = read_all(a_run([wave], STEPS + [step(11, "prefill")]))
    assert got["prompt_row_skip_pct"] == 0.0 and got["prompt_rows_ms_mean"] == 0.0


def test_a_cached_prompt_is_left_out():
    cached = (5, 256, 256, 1.0, 1.1, None, None, 0, 0, 1.3)
    assert read_all(a_run([A, B, C, cached], STEPS)) == read_all(a_run([A, B, C], STEPS))


def test_nothing_is_read_where_fewer_than_nine_in_ten_join(capsys):
    many = [request(10 + i, 9, 9, 1, t_submit=8.0, t_first=9.5) for i in range(8)]
    lost = request(30, 50, 52, 3, t_submit=8.0, t_first=9.5)  # its programs left the ring
    assert all(v is not None for v in read_all(a_run([A, *many, lost], STEPS)).values())  # 9 of 10
    capsys.readouterr()
    got = read_all(a_run([*many, lost, lost], STEPS))  # 8 of 10
    assert all(v is None for v in got.values())
    assert "fewer than 90% joined" in capsys.readouterr().err


def test_the_window_is_the_end_to_end_metrics_own():
    """(t0, t1]: a first token at t0 belongs to the window before, one at t1 to this one."""
    at_t0 = request(7, 1, 3, 3, t_submit=0.5, t_first=4.0)
    at_t1 = request(8, 5, 6, 2, t_submit=2.0, t_first=7.0)
    after = request(9, 9, 9, 1, t_submit=8.0, t_first=9.75)
    got = read_all(a_run([at_t0, at_t1, after], STEPS, t0=4.0, t1=7.0))
    assert got["first_row_wait_ms_mean"] == pytest.approx(1e3 * (5 - 2.0))
    assert got["first_token_tail_ms_mean"] == pytest.approx(1e3 * (7.0 - 6))
    assert all(v is None for v in read_all(a_run([at_t0, at_t1], STEPS, t0=7.0, t1=9.0)).values())


def test_a_program_without_request_records_reads_nothing(capsys):
    run = {"t0": 0.0, "t1": 1.0, "trace": None, "summary": {"first": []},
           "stats1": {"step_log": {"fields": STEP_FIELDS, "records": tuple(STEPS)}}}
    assert all(v is None for v in read_all(run).values())
    assert "no request records" in capsys.readouterr().err


def test_the_tail_splits_on_the_traces_clock():
    """The trace's clock stands 1000 s after the step records': dispatch -> start,
    execution, end -> first token, from the join of `sched.dispatch` to executions."""
    run = a_run([A, B, C], STEPS)
    got = MODS["first_row_wait_ms_mean"].joined(run)
    off = 1000.0
    join = {seq: ((seq + off + 0.2) * 1e9, (seq + off + 0.3) * 1e9, (seq + off) * 1e9, (seq + off + 0.001) * 1e9)
            for seq in (3, 6, 7)}
    steps = {s[0]: dict(zip(STEP_FIELDS, s)) for s in STEPS}
    n, depth, execution, fetch = MODS["first_token_tail_ms_mean"].tail_split(got, join, steps)
    assert n == 2  # A's last row at 3 and B's at 6; C's program 9 is not in the trace
    assert depth == pytest.approx(200.0) and execution == pytest.approx(100.0)
    assert fetch == pytest.approx(1e3 * ((4.25 - 3.3) + (7.5 - 6.3)) / 2)
