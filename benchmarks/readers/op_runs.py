"""Runs per traced step, on the fullest device, of the `XLA Ops` events
whose whole text matches `pattern`: a count, where `op_ms` gives the
time. For an instruction inside a loop whose trip count is the data's
(the expert layer's rounds over its row buffer) the count is what the
data made the program do, and tells a step that moved because the
routing moved from one that moved because the code did. Nothing matched
(the path is not taken, or the program gives no such name): nothing
returned."""
from benchmarks.harness import trace as tr


def read(ctx: dict, params: dict):
    trace, s = ctx.get("trace"), ctx.get("summary")
    if not trace or not s:
        return None
    ops = trace["devices"][s["fullest"]]["ops"]
    hits = tr.matching(tr.clip_events(ops, s["t0"], s["t1"]),
                       params["pattern"])
    if not hits:
        return None
    return len(hits) / s["steps"]
