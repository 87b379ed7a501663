"""What the program's blocks counted, a traced step: the record the step
keeps of every call (`paddle_tpu.profiler.step_records()`: the call's
host dispatch on `time.perf_counter_ns`, the clock of the window's
record, and the counters its blocks handed out, `{name: [blocks]}`).

The traced steps are the records dispatched after the untraced window
closed (the window's `t1`), told by the clock and not by counting. A
counter's value a step is its sum over `counters` (names, matched with or
without a group's prefix, `g1.moe.routed` for `moe.routed`) and over the
blocks; the metric is its mean over the traced steps, or, with `over`,
the sum of `counters` over the sum of `over` across those steps, in
`percent` where that is set.

This reader imports `paddle_tpu.profiler` and nothing else of the
program. A program that keeps no such record (the parent of the PR that
added it), or whose blocks count nothing: nothing returned."""


def records():
    """The program's step records, or None where it keeps none."""
    try:
        from paddle_tpu import profiler
        return profiler.step_records()
    except (ImportError, AttributeError):
        return None


def total(record, names) -> int | None:
    """The sum over the blocks of the counters `names` of one record;
    None where it has none of them."""
    got = [v for k, v in record.counters.items()
           if any(k == n or k.endswith("." + n) for n in names)]
    return int(sum(int(v.sum()) for v in got)) if got else None


def layers(record, name: str) -> int:
    """How many blocks counted `name` in one record."""
    return sum(v.size for k, v in record.counters.items()
               if k == name or k.endswith("." + name))


def traced(ctx: dict, names) -> list:
    """The records dispatched after the untraced window that count
    `names`, oldest first."""
    rec, got = ctx.get("window"), records()
    if not rec or not got:
        return []
    return [r for r in got if r.begin_ns * 1e-9 > rec["t1"]
            and total(r, names) is not None]


def read(ctx: dict, params: dict):
    names = params["counters"]
    steps = traced(ctx, names)
    if not steps:
        return None
    num = sum(total(r, names) for r in steps)
    if "over" not in params:
        value = num / len(steps)
    else:
        den = sum(total(r, params["over"]) or 0 for r in steps)
        if not den:
            return None
        value = num / den * (100.0 if params.get("percent") else 1.0)
    ctx.setdefault("notes", []).append(
        f"{'+'.join(names)}"
        + (f" over {'+'.join(params['over'])}" if "over" in params else "")
        + f": {value:g} over {len(steps)} traced steps, by step "
        f"{[total(r, names) for r in steps]}")
    return value
