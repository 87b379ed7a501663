"""The whole step's share of the chip's peak: the operations the forward
and backward need for the real tokens (the family's `counts`: no
recomputation, no padding, causal attention at half, the vocabulary head
on gathered positions only) x steps completed in the untraced window,
over the window's seconds, over chips x the bf16 peak of the table."""


def read(ctx: dict, params: dict):
    rec = ctx.get("window")
    if not rec or not rec.get("done"):
        return None
    rate = ctx["counts"]["step_flops"] * len(rec["done"]) \
        / (rec["t1"] - rec["t0"])
    return 100.0 * rate / (ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
