"""Whether the window's slow steps are the data's: over the untraced
window, the mean of a counter (`step_counters`: summed over `counters`
and the blocks) over the steps of the intervals at or above the
window's `q` quantile (`loop.step_intervals` with the mix's
`interval_steps`, as `step_ms_p90` takes them), over the median of that
counter over all the window's steps. About 1 where the tail is not the
counter's doing, above 1 where the slow steps carried more.

The window's steps are the records dispatched inside [t0, t1] of the
window's record, by the shared clock; where they are not as many as the
steps the window completed (a record that dropped some), nothing is
returned, as where there is no record."""
import statistics

from benchmarks.harness import loop
from benchmarks.readers import step_counters


def read(ctx: dict, params: dict):
    rec, got = ctx.get("window"), step_counters.records()
    if not rec or not got or not rec.get("done"):
        return None
    names = params["counters"]
    inside = [r for r in got if rec["t0"] <= r.begin_ns * 1e-9 <= rec["t1"]]
    values = [step_counters.total(r, names) for r in inside]
    if len(values) != len(rec["done"]) or None in values:
        return None
    k = ctx["mix"].get("interval_steps", 1)
    intervals = loop.step_intervals(rec["done"], rec["t0"], k)
    k = len(values) + 1 - len(intervals)
    cut = loop.percentile(intervals, params.get("q", 0.9))
    slow = [statistics.fmean(values[i:i + k])
            for i, t in enumerate(intervals) if t >= cut]
    median = statistics.median(values)
    if not median:
        return None
    ctx.setdefault("notes", []).append(
        f"{'+'.join(names)} of the {len(slow)} slowest of "
        f"{len(intervals)} intervals: {statistics.fmean(slow):g} against "
        f"a median of {median:g} over the window's {len(values)} steps")
    return statistics.fmean(slow) / median
