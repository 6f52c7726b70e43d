"""Submit -> the dispatch of the program that carried the request's first prompt
row, mean over the requests whose first token fell in the window: admission,
`admit_delay_ms`, and the programs that went out before its first row.

It and the three other readers of the request timeline (`prompt_rows_ms_mean`,
`prompt_row_skip_pct`, `first_token_tail_ms_mean`, which call `joined` below
as a module) read the engine's request records (`stats()["request_log"]`,
taken when the window closed), each joined by `seq` to the step records
(`stats()["step_log"]`) of the programs that carried its first and last prompt
rows. A request whose whole prompt was cached dispatched no row and is left
out. Where fewer than 90% of the rest join (the step ring dropped their
programs), or the program keeps no request records (one from before them),
nothing is read, and standard error says so."""
import sys

import hostspans
import stats as S

META = {"name": "first_row_wait_ms_mean", "unit": "ms", "better": "lower", "source": "program_span",
        "layer": "Scheduler (LLMEngine._schedule_loop, admission)", "moves": "ttft_ms_mean",
        "workloads": ["mistral-7b.long-closed", "qwen2-7b.reason-closed", "glm-4.7-flash.think-closed",
                      "k-exaone-236b-a23b.mixed-closed", "olmo-hybrid-7b.chat-closed"]}

JOINED_SHARE = 0.9


def say(msg: str) -> None:
    print("[request timeline] " + msg, file=sys.stderr, flush=True)


def joined(run) -> list | None:
    """Once per run: [(request, first row's step record, last row's, the
    decode-class programs dispatched from its first row to its last)] for the
    requests whose first token fell in the window and that dispatched rows,
    records as {field: value}; None where fewer than 90% of those join."""
    if "request_timeline" in run:
        return run["request_timeline"]
    run["request_timeline"] = None
    rlog, steps = (run.get("stats1") or {}).get("request_log"), hostspans.records_by_seq(run)
    if not rlog or steps is None:
        say("the program keeps no request records: nothing read")
        return None
    reqs = [dict(zip(rlog["fields"], rec)) for rec in rlog["records"]]
    reqs = [r for r in reqs if S.in_window(r["t_first_token"], run["t0"], run["t1"])]
    rowed = [r for r in reqs if r["rows"]]
    out = []
    for r in rowed:
        a, b = steps.get(r["seq_first_row"]), steps.get(r["seq_last_row"])
        if a is not None and b is not None:
            programs = sum(1 for s in range(a["seq"], b["seq"] + 1)
                           if s in steps and steps[s]["kind"] in hostspans.MODULE_OF_KIND)
            out.append((r, a, b, programs))
    seen = len((run.get("summary") or {}).get("first", ()))  # what the load saw
    say(f"{len(reqs)} first tokens in the window (the load saw {seen}): {len(rowed)} after prompt rows, "
        f"{len(reqs) - len(rowed)} with the whole prompt cached (left out); "
        f"{len(out)} of {len(rowed)} joined to the step records by seq")
    if not rowed or len(out) < JOINED_SHARE * len(rowed):
        say(f"fewer than {JOINED_SHARE:.0%} joined: the request timeline's metrics are left out")
        return None
    ttfts = (run.get("summary") or {}).get("ttft_ms") or []
    say(f"the three means sum to the engine's TTFT over these requests, "
        f"{1e3 * sum(r['t_first_token'] - r['t_submit'] for r, *_ in out) / len(out):.1f} ms; the window's "
        f"ttft_ms_mean (due -> first token yielded) {sum(ttfts) / max(1, len(ttfts)):.1f} ms over {len(ttfts)}")
    run["request_timeline"] = out
    return out


def read(run):
    got = joined(run)
    return None if got is None else 1e3 * sum(a["t_dispatch"] - r["t_submit"] for r, a, _b, _n in got) / len(got)
