"""A kernel family's share of its roofline over the traced stretch: the
least time the chip could take for what the kernels NEED per step (the
family's `counts[<cost>]`: flops and bytes from shapes; the larger of
flops / peak and bytes / bandwidth) over the summed device time of the
events whose name matches `pattern`, per traced step. A forward replayed
under rematerialisation is time, not work. Nothing matched (the cell
takes another path): nothing returned."""
from benchmarks.harness import flops
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
    kernel_s = sum(e - b for b, e, _ in hits) / s["steps"]
    cost = {k: v / ctx["chips"]
            for k, v in ctx["counts"][params["cost"]].items()}
    least, bound = flops.least_seconds(cost, ctx["peak"])
    ctx.setdefault("notes", []).append(
        f"{params['cost']}: {len(hits)} kernel runs, {kernel_s * 1e3:.3f} "
        f"ms a step, least {least * 1e3:.3f} ms, bound by {bound}")
    return 100.0 * least / kernel_s
