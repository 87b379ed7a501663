"""Seconds of set-up that the program's own spans of the names `spans`
cover: `paddle_tpu.profiler.spans()` (host spans on `time.perf_counter`,
the clock of `run.py`'s T_START and of the window's record), those that
ended before the untraced window started. Seconds by the wall: the
length of the union of the spans' intervals, so a span nested in
another of the set (a child, or a program traced while another is) is
not counted twice. `minus` names spans whose stretch is taken out,
because another metric counts it: a compile inside `model.build` is
`setup_model_s`'s and not `setup_compile_s`'s too, and the set-up
metrics add up.

This reader imports `paddle_tpu.profiler` and nothing else of the
program; until PR 27 only the family adapters imported the program at
all. A program without that recorder (the parent of the PR that added
it), or with no such span: nothing returned."""
from benchmarks.harness import trace as tr


def overlap(a, b) -> float:
    """Seconds that two sorted lists of disjoint intervals share."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(ctx: dict, params: dict):
    rec = ctx.get("window")
    if not rec:
        return None
    try:
        from paddle_tpu import profiler
        spans = profiler.spans()
    except (ImportError, AttributeError):
        return None
    before = rec["t0"]

    def stretch(names):
        return tr.union((sp.start_ns * 1e-9, sp.end_ns * 1e-9)
                        for sp in spans
                        if sp.name in names and sp.end_ns * 1e-9 <= before)

    mine = stretch(params["spans"])
    if not mine:
        return None
    whole = sum(e - s for s, e in mine)
    inside = overlap(mine, stretch(params.get("minus", ())))
    ctx.setdefault("notes", []).append(
        f"{'|'.join(params['spans'])}: {whole:.3f} s before the window"
        + (f", of which {inside:.3f} s inside "
           f"{'|'.join(params['minus'])} and counted there"
           if params.get("minus") else ""))
    return whole - inside
