#!/usr/bin/env python3
"""Step 0 of the expert layer's rounds: what one layer's grouped experts
cost, forward and backward, by the LOAD the router sends to the held
experts and by the rows a round behind the first takes.

    python3 tools/moe_rounds_step0.py [--out chiprun_out/moe_rounds_step0.json]

One `MoEMLP`'s worth of work at the Keye cell's widths (16384 tokens of
2048, top 8 of 128, 16 held experts of 768, bf16 products): the plan's
sort, `grouped_experts` and the gradient of every operand, ms a call,
the median of `--calls` timed on the host around `block_until_ready`.
The load is data: `--loads` are multiples of what a balanced router
sends (16384 rows), spread evenly over the held experts; the first
round's buffer holds 1.5 of it, so 1.52 leaves the second round nearly
empty. `--shares`: a short round takes 1 / share of the buffer (1: every
round a whole buffer, the program before PR 35's second fix round; the
layer's own is `moe.TAIL_SHARE`). A time comes from a chip; on the CPU
use `--tokens 256` to see that it runs.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def assignments(rs, tokens, top_k, experts, held, load):
    """[tokens, top_k] expert ids: `load` x the balanced share of the
    assignments on the held experts (the first `held` ids), evenly."""
    import numpy as np
    want = load * tokens * top_k * held / experts / tokens   # held picks a token
    picks = np.floor(want).astype(int) + (rs.rand(tokens) < want % 1)
    picks = np.minimum(picks, min(top_k, held))
    base = rs.randint(0, held, tokens)[:, None]
    far = rs.randint(0, experts - held, tokens)[:, None]
    j = np.arange(top_k)[None, :]
    near = (base + j) % held
    away = held + (far + j) % (experts - held)
    return np.where(j < picks[:, None], near, away).astype(np.int32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--width", type=int, default=2048)
    ap.add_argument("--ffn", type=int, default=768)
    ap.add_argument("--loads", type=float, nargs="+",
                    default=[1.0, 1.52, 1.9, 2.6, 3.2, 4.6])
    ap.add_argument("--shares", type=int, nargs="+", default=[1, 2, 4, 8])
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.distributed.meta_parallel.moe import (
        dispatch_plan, grouped_experts, plan_rows)
    top_k, experts, held = 8, 128, 16
    t, d, f = args.tokens, args.width, args.ffn
    rs = np.random.RandomState(args.seed)
    x = jnp.asarray(rs.randn(t, d), jnp.bfloat16)
    ct = jnp.asarray(rs.randn(t, d), jnp.float32)
    weights = jnp.asarray(rs.rand(t, top_k), jnp.float32)
    ws = [jnp.asarray(rs.randn(*s) * 0.02, jnp.float32)
          for s in ((held, d, f), (held, d, f), (held, f, d))]
    worst = t * min(top_k, held)
    tile = 512 if t >= 4096 else 8
    rows = -(-3 * t * top_k * held // (2 * experts * tile)) * tile
    ids = {load: jnp.asarray(assignments(rs, t, top_k, experts, held, load))
           for load in args.loads}

    table = []
    for share in args.shares:
        tail = -(-rows // (share * tile)) * tile
        total = plan_rows(worst, rows, tail)

        def loss(x, weights, wg, wu, wd, experts_, ct):
            plan = dispatch_plan(experts_, 0, held, total)
            y = grouped_experts(x, plan, weights, wg, wu, wd, jnp.bfloat16,
                                rows, tail)
            return jnp.sum(y * ct)
        step = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
        for load in args.loads:
            jax.block_until_ready(step(x, weights, *ws, ids[load], ct))
            ms = []
            for _ in range(args.calls):
                t0 = time.perf_counter()
                jax.block_until_ready(step(x, weights, *ws, ids[load], ct))
                ms.append(1e3 * (time.perf_counter() - t0))
            row = {"share": share, "rows": rows, "tail": tail, "load": load,
                   "held_rows": int((np.asarray(ids[load]) < held).sum()),
                   "ms": statistics.median(ms), "ms_min": min(ms)}
            table.append(row)
            print(json.dumps(row), flush=True)
    out = {"device": jax.devices()[0].device_kind, "tokens": t, "width": d,
           "ffn": f, "table": table}
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
