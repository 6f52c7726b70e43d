"""Reduction of a profiler trace (`.xplane.pb`) to what the metrics read.

`reduce_planes` works on anything shaped like `jax.profiler.ProfileData`:
`.planes`, each with `.name` and `.lines`; each line with `.name` and
`.events`; each event with `.name`, `.start_ns`, `.duration_ns`. Device
planes are the ones named `/device:TPU:<n>`; their line "XLA Ops" holds one
event per operation run (its union is the busy time) and "XLA Modules" one
per program execution. The program gives its programs and kernels no stable
names yet, so each metric's file declares the pattern it looks for.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTAINERS = ("while", "conditional", "call")


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(log_dir: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(find_xplane(log_dir))


def union_ns(intervals: list) -> tuple[int, list]:
    """Total covered length of [start, end) intervals and the gaps between
    the merged runs, as (start, length)."""
    busy, gaps = 0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, s - cur_e))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def short_op(name: str) -> str:
    """The trace names an op by its whole HLO line; `%fusion.4 = bf16[..]
    fusion(...)` -> `fusion.4 fusion`."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    kind = re.search(r"[}\])] ([a-z][a-z0-9\-]*)\(", rhs) or re.search(r"\b([a-z][a-z0-9\-]*)\(", rhs)
    return f"{lhs.lstrip('%')} {kind.group(1)}" if kind else lhs.lstrip("%")


def event_text(ev) -> str:
    """What a metric's pattern is matched against: the op's whole name and
    the values of its stats (source op, kernel name, category)."""
    try:
        extra = " ".join(f"{k}={v}" for k, v in ev.stats)
    except Exception:  # noqa: BLE001 — a hand-built event has no stats
        extra = ""
    return f"{ev.name} {extra}"


def module_base(name: str) -> str:
    """`jit__step(1234567)` -> `jit__step`."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def _cpu_stand_in(data):
    """A rehearsal has no device plane. The host plane stands in so that the
    readers run: the XLA CPU executor's threads as "XLA Ops" and the
    `PjitFunction(jit(f))` host events as "XLA Modules" named `jit_f`."""
    from types import SimpleNamespace as NS

    planes = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name.startswith("tf_XLA"):
                ops += [e for e in line.events if e.name != "ThunkExecutor::Execute"]
                continue
            for e in line.events:
                m = re.match(r"PjitFunction\(jit\((.*)\)\)$", e.name)
                if m:
                    modules.append(NS(name="jit_" + m.group(1), start_ns=e.start_ns, duration_ns=e.duration_ns))
        planes.append(NS(name="/device:TPU:0", lines=[NS(name=OPS_LINE, events=ops),
                                                      NS(name=MODULES_LINE, events=modules)]))
    return NS(planes=planes)


def is_container(name: str) -> bool:
    """A loop's, a branch's or a call's own event spans the operations of its
    body, which the line holds too: it is no operation of its own, neither for
    the busy union (no gap inside a loop would ever show) nor for a listing
    (its time would count twice)."""
    short = short_op(name)  # `while.7 while` on the TPU; a bare `while.7` where the trace holds no HLO line
    return (short.rsplit(" ", 1)[-1] if " " in short else short.split(".")[0]) in CONTAINERS


def reduce_planes(data, device_plane=DEVICE_PLANE, rehearsal: bool = False, traced_s: float = 0.0) -> dict:
    """Per device plane: span, busy union, gaps, per-op and per-module
    times; then the mean over planes of busy and span. `traced_s` is the
    host's start_trace -> stop_trace stretch: the window is that, or the
    ops' own span where the trace holds more, so that a device idle at the
    edges of the traced stretch reads idle."""
    if rehearsal:
        data = _cpu_stand_in(data)
    devices = []
    for plane in data.planes:
        if not device_plane.match(plane.name):
            continue
        ops: dict[str, list] = {}  # whole name -> [count, total_ns, text to match]
        modules: dict[str, list] = {}  # base name -> list of (start, dur)
        intervals = []
        container: dict[str, bool] = {}
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    s, d = int(ev.start_ns), int(ev.duration_ns)
                    slot = ops.get(ev.name)
                    if slot is None:
                        slot = ops[ev.name] = [0, 0, event_text(ev)]
                        container[ev.name] = is_container(ev.name)
                    if not container[ev.name]:
                        intervals.append((s, s + d))
                    slot[0] += 1
                    slot[1] += d
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    modules.setdefault(module_base(ev.name), []).append(
                        (int(ev.start_ns), int(ev.duration_ns)))
        if not intervals:
            continue
        busy, gaps = union_ns(intervals)
        start = min(s for s, _ in intervals)
        end = max(e for _, e in intervals)
        devices.append({
            "plane": plane.name, "start_ns": start, "span_ns": end - start,
            "busy_ns": busy, "gaps": gaps, "ops": ops, "modules": modules,
        })
    if not devices:
        raise ValueError("the trace holds no device plane with XLA Ops: nothing ran on the device")
    n = len(devices)
    span_s = sum(d["span_ns"] for d in devices) / n / 1e9
    return {
        "devices": devices,
        "busy_s": sum(d["busy_ns"] for d in devices) / n / 1e9,
        "span_s": span_s,
        "window_s": max(span_s, float(traced_s)),
    }


def op_seconds(reduced: dict, pattern: str) -> tuple[float, int]:
    """Summed device time and count of the ops whose name matches, mean
    over devices."""
    rx = re.compile(pattern)
    total = count = 0
    for d in reduced["devices"]:
        for _name, (c, ns, text) in d["ops"].items():
            if rx.search(text):
                total += ns
                count += c
    n = len(reduced["devices"])
    return total / n / 1e9, count // n


def module_runs(reduced: dict, pattern: str) -> list:
    """Durations in seconds of every execution of the programs whose name
    matches, over all devices."""
    rx = re.compile(pattern)
    return [dur / 1e9 for d in reduced["devices"] for name, runs in d["modules"].items()
            if rx.search(name) for _s, dur in runs]


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The operations that took most device time and the longest idle gaps.
    The program has no host spans yet, so a gap is labelled by the programs
    on either side of it and `unattributed` for what the host did."""
    d0 = reduced["devices"][0]
    by_short: dict[str, float] = {}  # two programs may both hold a `fusion.4`: their times add
    for name, (_c, ns, _t) in d0["ops"].items():
        if is_container(name):
            continue
        short = short_op(name)
        by_short[short] = by_short.get(short, 0.0) + ns / 1e9
    ops = sorted(by_short.items(), key=lambda x: -x[1])[:top]
    runs = sorted((s, s + dur, name) for name, rs in d0["modules"].items() for s, dur in rs)

    def around(t: int) -> str:
        before = next((n for s, e, n in reversed(runs) if e <= t + 1000), "?")
        after = next((n for s, e, n in runs if s >= t - 1000), "?")
        return f"unattributed (after {before}, before {after})"

    by_label: dict[str, float] = {}
    for s, length in sorted(d0["gaps"], key=lambda g: -g[1])[:200]:
        label = around(s)
        by_label[label] = by_label.get(label, 0.0) + length / 1e9
    gaps = sorted(by_label.items(), key=lambda x: -x[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": [[n, s] for n, s in gaps]}


def outline(data, top: int = 25) -> str:
    """A trace by hand: every plane and line with its event count, and the
    names that took most time on each line."""
    out = []
    for plane in data.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            names: dict[str, list] = {}
            n = 0
            for ev in line.events:
                n += 1
                slot = names.get(key := short_op(ev.name))
                if slot is None:
                    slot = names[key] = [0, 0, event_text(ev)]
                slot[0] += 1
                slot[1] += int(ev.duration_ns)
            out.append(f"  line {line.name!r}: {n} events, {len(names)} names")
            for name, (c, ns, text) in sorted(names.items(), key=lambda x: -x[1][1])[:top]:
                out.append(f"    {ns / 1e6:10.3f} ms {c:7d} x  {name[:100]}")
                if "custom-call" in text or line.name == MODULES_LINE:
                    out.append(f"        text: {text[:300]} ... {text[-1500:]}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(outline(load(sys.argv[1]), int(sys.argv[2]) if len(sys.argv) > 2 else 25))
