"""Median host-clock seconds of one of the loop's own spans per step of
the untraced window, in milliseconds. `span`: which record of the window
("feed_s": taking the next host batch and `jax.device_put`)."""
import statistics


def read(ctx: dict, params: dict):
    values = (ctx.get("window") or {}).get(params["span"])
    if not values:
        return None
    return 1e3 * statistics.median(values)
