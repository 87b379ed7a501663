"""The share of the traced stretch, in percent, that the fullest device
spent in operations of one kind: `pattern` is searched in what
`trace.kind` keeps of each `XLA Ops` event (the instruction's name
without its number, and its opcode), and the time of operations nested
in an event counts for them, not for it. Nothing matched: nothing
returned."""
import re

from benchmarks.harness import trace as tr


def read(ctx: dict, params: dict):
    trace, s = ctx.get("trace"), ctx.get("summary")
    if not trace or not s:
        return None
    ops = tr.clip_events(trace["devices"][s["fullest"]]["ops"],
                         s["t0"], s["t1"])
    rx = re.compile(params["pattern"])
    hit = {name: sec for name, sec in tr.self_seconds(ops).items()
           if rx.search(tr.kind(name))}
    if not hit:
        return None
    ctx.setdefault("notes", []).append(
        f"{params['pattern']}: {len(hit)} instructions, "
        f"{1e3 * sum(hit.values()) / s['steps']:.3f} ms a step")
    return 100.0 * sum(hit.values()) / s["window_s"]
