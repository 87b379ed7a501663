"""Milliseconds per traced step that the fullest device spent in the
`XLA Ops` events whose whole text matches `pattern`: self time, so what
is nested in an event (a `while` holds its body's instructions) counts
for the nested events and not for it. The pattern names what the
PROGRAM named (a Pallas kernel's `name=` is its instruction's name,
"%flash_fwd.16 = ..."), never a shape or a name the compiler made up.

Two optional parameters tell apart runs of one kernel by where they
lie, still without shapes: `beside` keeps only the events whose holder
(the `while` they are nested in) also holds an event matching that
pattern, `not_beside` only those whose holder holds none. A forward
kernel replayed under rematerialisation lies in the backward loop
beside the backward kernels; the forward pass's own lies in a loop
without them. An event that no other holds (a program without a layer
loop) is kept by neither, so there both read nothing rather than the
wrong thing.

The runs per step go to the trace notes: that count is the counter.
Nothing matched (the path is not taken, or the program gives no such
name, as before PR 27): nothing returned."""
import re

from benchmarks.harness import trace as tr


def nested(ops) -> list:
    """[start, end, name, index of the event that holds it or None, self
    seconds] of every event, by start."""
    out, open_ = [], []
    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while open_ and out[open_[-1]][1] <= s:
            open_.pop()
        holder = open_[-1] if open_ else None
        if holder is not None:
            out[holder][4] -= e - s
        out.append([s, e, name, holder, e - s])
        open_.append(len(out) - 1)
    return out


def read(ctx: dict, params: dict):
    trace, s = ctx.get("trace"), ctx.get("summary")
    if not trace or not s:
        return None
    events = nested(tr.clip_events(trace["devices"][s["fullest"]]["ops"],
                                   s["t0"], s["t1"]))
    rx = re.compile(params["pattern"])
    hits = [ev for ev in events if rx.search(ev[2])]
    side = params.get("beside") or params.get("not_beside")
    if side:
        side_rx = re.compile(side)
        holders = {ev[3] for ev in events if side_rx.search(ev[2])}
        hits = [ev for ev in hits if ev[3] is not None
                and (ev[3] in holders) == ("beside" in params)]
    if not hits:
        return None
    ms = 1e3 * sum(ev[4] for ev in hits) / s["steps"]
    where = "".join(f", {k} {params[k]}" for k in ("beside", "not_beside")
                    if k in params)
    ctx.setdefault("notes", []).append(
        f"{params['pattern']}{where}: {len(hits) / s['steps']:g} runs, "
        f"{ms:.3f} ms a step")
    return ms
