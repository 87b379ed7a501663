"""The longest stretch, in milliseconds, between the end of one run of
the step program on the device and the start of the next (the `XLA
Modules` events `trace.step_modules` picks)."""


def read(ctx: dict, params: dict):
    trace, s = ctx.get("trace"), ctx.get("summary")
    if not trace or not s:
        return None
    steps = s["step_modules"]
    gaps = [b[0] - a[1] for a, b in zip(steps, steps[1:])]
    return 1e3 * max(gaps) if gaps else None
