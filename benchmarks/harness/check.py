"""The comparison that decides `correct` for a training cell.

Both sides are a record of the first steps: each step's loss, the norm
of the first gradient as the optimizer gets it, by leaf, and the norm of
the parameters' change after the last step, by leaf. The program's
record is worked out from its own state (Adam's first moment after one
step is (1 - beta1) g); the reference's comes from
`reference_train.run`. A control or a planted fault is the same record,
made by the reference in the program's place.

The numbers, each with a limit of its own (benchmarks/limits/<cell>.json):

  loss_gap      worst step: |loss - reference| / |reference|
  loss1_gap     the first step alone, before any update
  grad_gap      worst leaf: |norm - reference's| / max(reference's norm
                of that leaf, reference's median leaf)
  grad_scale_gap  |s - 1| with s the median leaf's ratio of the two
                norms: the part of the gap that every leaf shares
  grad_gap_p75  grad_gap with s taken out (|norm - s x reference's|), at
                the leaf at the 75th percentile: steady where the worst
                swings with one small leaf and the shared scale with the
                seed
  change_gap    as grad_gap on the parameters' change, over the leaves
                whose reference gradient is at least a thousandth of the
                median leaf's (a leaf with a gradient that is nought to
                rounding moves under Adam by round-off alone)

The gap is between the two norms, not the norm of a difference. A number
that a cell's file gives no limit, or null, is printed and not compared:
PERF.md section 4 says for each cell which those are and why.
"""
from __future__ import annotations

import statistics

DEAD_GRADIENT = 1e-3  # of the median leaf's gradient norm
NOT_A_NUMBER = 1e30   # what a NaN or an infinite gap reads: over any
#                       limit, and a number that JSON can carry


def _ranked(got: dict, ref: dict, leaves, scale: float = 1.0) -> list:
    """(gap, leaf), smallest gap first; a NaN is the worst there is."""
    floor = statistics.median(ref[k] for k in leaves)
    out = []
    for k in leaves:
        gap = abs(got[k] - scale * ref[k]) / max(ref[k], floor, 1e-30)
        out.append((gap if gap == gap else NOT_A_NUMBER, k))
    return sorted(out)


def gaps(got: dict, ref: dict) -> dict:
    """{number: (value, leaf or step where it is worst)}"""
    if got["grad"].keys() != ref["grad"].keys() or \
            got["change"].keys() != ref["change"].keys():
        raise ValueError("the two records do not name the same leaves")
    loss = [abs(a - b) / abs(b) for a, b in zip(got["losses"],
                                                ref["losses"])]
    loss = [x if x < NOT_A_NUMBER else NOT_A_NUMBER for x in loss]
    worst = max(range(len(loss)), key=loss.__getitem__)
    leaves = sorted(ref["grad"])
    med = statistics.median(ref["grad"].values())
    moved = [k for k in leaves if ref["grad"][k] >= DEAD_GRADIENT * med]
    grad = _ranked(got["grad"], ref["grad"], leaves)
    scale = statistics.median(
        got["grad"][k] / ref["grad"][k] if ref["grad"][k] > 0 else 1.0
        for k in leaves)
    if not scale < NOT_A_NUMBER:        # a NaN or an infinite ratio
        scale = NOT_A_NUMBER
    shape = _ranked(got["grad"], ref["grad"], leaves, scale)
    return {"loss_gap": (loss[worst], f"step {worst + 1}"),
            "loss1_gap": (loss[0], "step 1"),
            "grad_gap": grad[-1],
            "grad_scale_gap": (abs(scale - 1.0), "median leaf"),
            "grad_gap_p75": shape[int(0.75 * len(shape))],
            "change_gap": _ranked(got["change"], ref["change"], moved)[-1]}


def compare(got: dict, ref: dict, limits: dict) -> tuple:
    """(all within their limits, {number: {"value", "limit", "at"}})"""
    compared, ok = {}, True
    for name, (value, where) in gaps(got, ref).items():
        limit = (limits.get(name) or {}).get("limit")
        compared[name] = {"value": value, "limit": limit, "at": where}
        if limit is not None and not value <= limit:
            ok = False
    return ok, compared
