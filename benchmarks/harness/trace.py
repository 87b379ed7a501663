"""From the profiler's `.xplane.pb` to numbers.

`load` turns the file into plain tuples (`jax.profiler.ProfileData`, so
nothing but JAX is needed); everything below it works on those tuples
and is tested on a small synthetic trace. Times are seconds from the
start of the profile, on one clock for host and devices.

What the trace of this runtime looks like (looked at by hand, PR 26, TPU
v5 lite, jax 0.9.0): one plane per chip named "/device:TPU:<n>", with a
line "XLA Modules" (one event per run of a compiled program, named
"<module>(<fingerprint>)"; `build_train_step`'s step is a jitted
`functools.partial` and so "jit__unknown") and a line "XLA Ops" (one
event per HLO instruction run, NAMED BY THE INSTRUCTION'S WHOLE TEXT,
nested where an instruction has a body, as a `while` has; asynchronous
copies lie on a line of their own, "Async XLA Ops", and are not counted
as busy); the host's threads are lines of the plane "/host:CPU", and the
loop's `TraceAnnotation` spans are events of the thread that made them.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {found}")
    return found[0]


def load(path: str, span_names=()) -> dict:
    """{"devices": {n: {"ops": [(start, end, name)], "modules": [...]}},
    "spans": [(start, end, name)]} with times in seconds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    want = set(span_names)
    out = {"devices": {}, "spans": []}

    def events(line, keep=None):
        return sorted(
            (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
            for e in line.events if keep is None or e.name in keep)

    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = events(line)
                elif line.name == MODULES_LINE:
                    dev["modules"] = events(line)
            out["devices"][int(m.group(1))] = dev
        elif plane.name == HOST_PLANE and want:
            for line in plane.lines:
                out["spans"].extend(events(line, want))
    out["spans"].sort()
    return out


# ---------------------------------------------------------------- reduction

def clip(intervals, t0: float, t1: float) -> list:
    return [(max(s, t0), min(e, t1)) for s, e, *_ in intervals
            if e > t0 and s < t1]


def clip_events(events, t0: float, t1: float) -> list:
    """The events that overlap [t0, t1], whole, with their names."""
    return [ev for ev in events if ev[1] > t0 and ev[0] < t1]


def union(intervals) -> list:
    """Merged, sorted, non-overlapping (start, end)."""
    out = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_seconds(ops, t0: float, t1: float) -> float:
    return sum(e - s for s, e in union(clip(ops, t0, t1)))


def idle_gaps(ops, t0: float, t1: float) -> list:
    """(start, end) of every stretch of [t0, t1] in which no operation
    ran, longest first."""
    gaps, at = [], t0
    for s, e in union(clip(ops, t0, t1)):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if t1 > at:
        gaps.append((at, t1))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def self_seconds(ops) -> dict:
    """{name: seconds} with the time of nested events taken out of the
    event that holds them (a `while` holds its body's instructions)."""
    total: dict = {}
    stack = []   # (end, name, start, covered by children)

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, start, covered = stack.pop()
            total[name] = total.get(name, 0.0) + (end - start) - covered
            if stack:
                top = stack[-1]
                stack[-1] = (top[0], top[1], top[2], top[3] + end - start)

    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        close(s)
        stack.append((e, name, s, 0.0))
    close(float("inf"))
    return total


def matching(events, pattern: str) -> list:
    rx = re.compile(pattern)
    return [ev for ev in events if rx.search(ev[2])]


def covering_span(gap, spans, default: str = "none") -> str:
    """The host span that covers most of the gap."""
    best, name = 0.0, default
    for s, e, n in spans:
        part = min(e, gap[1]) - max(s, gap[0])
        if part > best:
            best, name = part, n
    return name


def kind(name: str) -> str:
    """What an `XLA Ops` event is, short enough for a breakdown: on this
    runtime the event is named by the instruction's whole text
    ("%checkpoint.19 = (bf16[...]{...}, ...) custom-call(...), ..."). Kept:
    the name without its number, the opcode and the result's shapes, so
    the 24 copies of a layer's kernel in an unrolled program fall
    together and two fusions of different shapes do not."""
    head, _, rest = name.partition(" = ")
    stem = re.sub(r"[.\d]+$", "", head.lstrip("%")) or head
    op = re.search(r"\s([a-z][a-z\-]*)\(", " " + rest)
    if not op:
        return stem
    result = re.sub(r"\{[^}]*\}", "", rest[:max(op.start() - 1, 0)])
    return f"{stem} {op.group(1)} {result.strip()[:80]}".strip()


def step_modules(modules) -> list:
    """The runs of the step program: the modules of the name that took
    most time (a training window runs one heavy program over and over,
    and `build_train_step`'s has no name of its own to match)."""
    total: dict = {}
    for s, e, name in modules:
        total[name] = total.get(name, 0.0) + e - s
    if not total:
        return []
    heaviest = max(total, key=total.get)
    return [m for m in modules if m[2] == heaviest]


def summary(trace: dict) -> dict:
    """What every traced run reports: the traced stretch (from the first
    to the last run of the step program on the device), the seconds an
    operation ran in it averaged over the devices, and the breakdown."""
    per_dev, t_lo, t_hi = [], None, None
    for n, dev in sorted(trace["devices"].items()):
        steps = step_modules(dev["modules"])
        if not steps:
            continue
        t0, t1 = steps[0][0], max(e for _, e, _ in steps)
        per_dev.append((n, dev, t0, t1, steps))
        t_lo = t0 if t_lo is None else min(t_lo, t0)
        t_hi = t1 if t_hi is None else max(t_hi, t1)
    if not per_dev:
        return {}
    busy = [busy_seconds(dev["ops"], t_lo, t_hi)
            for _, dev, *_ in per_dev]
    fullest = max(range(len(busy)), key=busy.__getitem__)
    _, dev, _, _, steps = per_dev[fullest]
    ops = [o for o in dev["ops"] if o[1] > t_lo and o[0] < t_hi]
    by_kind: dict = {}
    for name, sec in self_seconds(ops).items():
        by_kind[kind(name)] = by_kind.get(kind(name), 0.0) + sec
    top = sorted(by_kind.items(), key=lambda kv: -kv[1])[:10]
    gaps = [[covering_span(g, trace["spans"]), g[1] - g[0]]
            for g in idle_gaps(dev["ops"], t_lo, t_hi)[:5]]
    return {"t0": t_lo, "t1": t_hi, "window_s": t_hi - t_lo,
            "busy_s": sum(busy) / len(busy), "busy_by_device": busy,
            "steps": len(steps), "fullest": per_dev[fullest][0],
            "step_modules": steps,
            "device_ops": [[k, v] for k, v in top],
            "idle_gaps": gaps}
