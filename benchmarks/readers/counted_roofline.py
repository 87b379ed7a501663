"""A layer's share of its roofline where the work is the DATA's: the
least time of the family's `counts[cost]` (flops and bytes from shapes)
for the rows the program counted it really took, over the device time,
a traced step, of the `XLA Ops` events whose text matches `pattern`
(self time, as `op_ms` gives it).

`counts[cost]` takes `counts["rows_held"]` rows a layer, what a balanced
router sends; the record (`step_counters`) has the rows routed, summed
over the blocks that count `rows`, a traced step. The family's `counts`
is taken again with the tokens scaled by (rows a layer) / `rows_held`:
its flops and its rows' bytes follow the rows, the weights' bytes do
not. Nothing matched, or no record: nothing returned."""
import re

from benchmarks.harness import cells, flops
from benchmarks.harness import trace as tr
from benchmarks.readers import step_counters
from benchmarks.readers.op_ms import nested


def read(ctx: dict, params: dict):
    trace, s = ctx.get("trace"), ctx.get("summary")
    if not trace or not s:
        return None
    name = params["rows"]
    steps = step_counters.traced(ctx, [name])
    if not steps:
        return None
    events = nested(tr.clip_events(trace["devices"][s["fullest"]]["ops"],
                                   s["t0"], s["t1"]))
    rx = re.compile(params["pattern"])
    seconds = sum(ev[4] for ev in events if rx.search(ev[2])) / s["steps"]
    if not seconds:
        return None
    rows = sum(step_counters.total(r, [name]) for r in steps) \
        / len(steps) / step_counters.layers(steps[0], name)
    scale = rows / ctx["counts"]["rows_held"]
    _, reference = cells.family(ctx["config"])
    stats = dict(ctx["stats"], tokens=ctx["stats"]["tokens"] * scale)
    cost = {k: v / ctx["chips"] for k, v in
            reference.counts(ctx["config"], stats)[params["cost"]].items()}
    least, bound = flops.least_seconds(cost, ctx["peak"])
    ctx.setdefault("notes", []).append(
        f"{params['cost']} at {rows:g} rows a layer ({scale:.3f} x "
        f"balanced): {seconds * 1e3:.3f} ms a step, least "
        f"{least * 1e3:.3f} ms, bound by {bound}")
    return 100.0 * least / seconds
