"""1 - (union of the intervals in which an operation ran) / (the traced
stretch), on the fullest device, in percent."""


def read(ctx: dict, params: dict):
    s = ctx.get("summary")
    if not s:
        return None
    return 100.0 * (1.0 - max(s["busy_by_device"]) / s["window_s"])
