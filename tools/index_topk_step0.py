#!/usr/bin/env python3
"""Step 0 of the indexer's top-k kernel (ISSUE 32), to be run on the chip:

    python tools/index_topk_step0.py [--seed N] [--out FILE] [--cell 0|1]

1. The exact top-`topk` of float32 [2, seq, seq] index scores (the score
   kernel's output on seeded bf16 inputs), as the layer scan of a step
   runs it: a `scan` of `--layers` calls, each over scores of its own.
   XLA's digit search (`_select_blocks`, what the program ran before the
   kernel and still runs off the TPU) against `index_topk` at each
   variant of VARIANTS: ms a call by the device's clock (a trace: the
   kernel's own events, and the whole program, which holds the harness's
   copies too), and whether the selection is XLA's bit for bit, on the
   seeded scores AND on a tie-heavy input (scores rounded to halves, rows
   of signed zeros).
2. With `--cell 1`, ROADMAP L1's step 0: the Keye cell's model on the
   benchmark's seeded weights and first batch, layer by layer; of the
   (q, k) blocks below the diagonal, the share that holds no selected
   pair, at 128 x 128 and at the flash kernels' own (group x chunk).

One JSON line a reading on standard output, all of them in `--out`.
Off the TPU (`--rehearse 1`, a small `--seq`) the kernels are interpreted:
a rehearsal of the control flow, whose times mean nothing.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle_tpu.ops import flash_attention as fa  # noqa: E402
from paddle_tpu.ops import index_select as ix  # noqa: E402

CELL = "keye-vl-2.0-30b-a3b.pretrain-s8192"
# keyword arguments of `ops.index_select.index_topk`
VARIANTS = [{"block": r, "digit": d}
            for d in (1, 2, 4) for r in (32, 64, 128, 256)]


def log(row: dict, rows: list) -> None:
    rows.append(row)
    print(json.dumps(row), flush=True)


def scores_of(seed: int, layers: int, seq: int):
    """[layers, 2, seq, seq] float32: the score kernel on seeded bf16
    queries, keys and head weights at the cell's widths."""
    def one(key):
        kq, kk, kw = jax.random.split(key, 3)
        q = jax.random.normal(kq, (2, seq, 16, 64), jnp.bfloat16)
        k = jax.random.normal(kk, (2, seq, 64), jnp.bfloat16)
        w = jax.random.normal(kw, (2, seq, 16), jnp.float32)
        return ix.index_scores(q, k, w)
    return jax.jit(lambda keys: jax.lax.map(one, keys))(
        jax.random.split(jax.random.key(seed), layers))


def tie_heavy(scores):
    """Rounded to halves, and in every 64th row signed zeros."""
    x = jnp.round(scores * 0.25) * 0.5
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 2)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.where(row % 64 == 3, jnp.where(col % 2 == 0, -0.0, 0.0), x)


def timed(fn, arg, args):
    """(result, ms a call of the whole program by the device's clock, ms
    a call of the kernel alone or None): `reps` traced runs of a program
    of `layers` calls. The program's time holds the harness too (a
    custom call's operand is sliced out of the stack by a copy)."""
    import shutil
    import tempfile
    from benchmarks.harness import trace
    from paddle_tpu.profiler import INDEX_TOPK
    out = jax.block_until_ready(fn(arg))        # compiles
    where = tempfile.mkdtemp(prefix="index_topk_step0_")
    try:
        with jax.profiler.trace(where):
            for _ in range(args.reps):
                out = jax.block_until_ready(fn(arg))
        # off the chip (a rehearsal) the trace has no device plane
        dev = trace.load(trace.find_xplane(where))["devices"].get(
            0, {"ops": [], "modules": []})
    finally:
        shutil.rmtree(where, ignore_errors=True)
    calls = args.reps * args.layers
    kernel = [e - s for s, e, name in dev["ops"]
              if re.match(rf"%{INDEX_TOPK}(\.[.\w]*)? = ", name)]
    return (out, 1e3 * sum(e - s for s, e, _ in dev["modules"]) / calls,
            1e3 * sum(kernel) / calls if kernel else None)


def topk_table(args, rows: list) -> None:
    def layers_of(select):
        return jax.jit(lambda xs: jax.lax.map(select, xs))

    scores = scores_of(args.seed, args.layers, args.seq)
    ties = jax.jit(tie_heavy)(scores[:1])
    xla = layers_of(lambda x: ix._select_blocks(x, args.topk))
    want, program, _ = timed(xla, scores, args)
    want_ties = jax.block_until_ready(xla(ties))
    log({"what": "xla", "program_ms_a_call": program,
         "kept_a_row": float(jnp.sum(want[0, 0, -1]))}, rows)
    same = jax.jit(lambda a, b: jnp.all(a == b))
    for kw in VARIANTS:
        if args.seq % kw["block"]:
            continue
        fn = layers_of(lambda x: ix.index_topk(x, args.topk, **kw))
        t = time.perf_counter()
        try:
            got, program, kernel = timed(fn, scores, args)
        except Exception as e:  # a variant the compiler refuses is a row
            log({"what": "index_topk", **kw, "error": str(e)[:300]}, rows)
            continue
        log({"what": "index_topk", **kw, "kernel_ms_a_call": kernel,
             "program_ms_a_call": program,
             "equal": bool(same(got, want)),
             "equal_ties": bool(same(fn(ties), want_ties)),
             "compile_and_runs_s": time.perf_counter() - t}, rows)
        del got


def empty_share(sel, rows: int, cols: int):
    """(blocks below or on the diagonal, those with no selected pair) of
    an int8 [b, s, s] selection cut into rows x cols blocks."""
    b, s, _ = sel.shape
    some = jnp.any(sel.reshape(b, s // rows, rows, s // cols, cols) > 0,
                   (2, 4))
    r = jnp.arange(s // rows)[:, None] * rows
    c = jnp.arange(s // cols)[None, :] * cols
    below = c <= r + rows - 1           # holds a pair with key <= query
    return int(b * jnp.sum(below)), int(jnp.sum(below & ~some))


def cell_selections(args, rows: list) -> None:
    from benchmarks.harness import cells, data
    from benchmarks.harness import weights as wt
    from paddle_tpu.models import KeyeForCausalLM
    from paddle_tpu.nn.layer import functional_call, trainable_state

    spec = cells.resolve(CELL)
    config, mix = spec["config"], spec["mix"]
    adapter, reference = cells.family(config)
    if args.rehearse:
        config.update(num_hidden_layers=2, hidden_size=256,
                      num_attention_heads=4, num_key_value_heads=2,
                      num_local_experts=4, vocab_size=512)
        config["published"]["vocab_size"] = 1024
        config["sa_config"]["topk"] = args.topk
        mix = dict(mix, seq=args.seq)
    cfg = adapter.program_config(config)
    model = KeyeForCausalLM(cfg)
    wt.load(model, jax.jit(functools.partial(
        reference.init_weights, config))(jax.random.key(args.seed)),
        wt.layer_names(adapter.OUTER, adapter.BLOCK, cfg.num_layers,
                       "model.layers"))
    ids = data.make_pool(dict(mix, pool_batches=1), config["vocab_size"],
                         args.seed)[0]["ids"]
    x = model.embed(jnp.asarray(ids))
    plan = fa._plan(mix["seq"], cfg.head_dim, cfg.dtype, True)
    shapes = {"128x128": (128, 128),
              "fwd_dq_group_x_chunk": (plan.sub, plan.chunk),
              "dkv_chunk_x_group": (plan.chunk, plan.sub)}
    for i, blk in enumerate(model.model.layers):
        params = trainable_state(blk)
        sel = jax.jit(functools.partial(selection, blk))(params, x)
        row = {"what": "selection", "layer": i,
               "kept_share_of_causal": float(
                   jnp.sum(sel.astype(jnp.float32))
                   / (sel.shape[0] * mix["seq"] * (mix["seq"] + 1) / 2))}
        for name, (r, c) in shapes.items():
            blocks, empty = empty_share(sel, r, c)
            row[name] = {"rows": r, "cols": c, "blocks": blocks,
                         "empty": empty}
        log(row, rows)
        del sel
        x = jax.jit(lambda p, x: functional_call(blk, p, x)[0])(params, x)


def selection(blk, params, x):
    """A decoder layer's selection for its own input, on `params`."""
    from paddle_tpu.nn.layer import swap_state
    with swap_state(blk, params):
        return blk.attn.selection(blk.ln1(x))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=2147491012)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--table", type=int, choices=(0, 1), default=1)
    ap.add_argument("--cell", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default="chiprun_out/index_topk_step0.json")
    args = ap.parse_args(argv)
    if args.rehearse:
        fa._interpret = lambda: True
    elif jax.default_backend() != "tpu":
        raise SystemExit("index_topk_step0: no TPU; nothing was run "
                         "(--rehearse 1 walks the script off the chip)")
    rows = []
    log({"what": "device", "kind": jax.devices()[0].device_kind,
         "seq": args.seq, "topk": args.topk, "layers": args.layers,
         "seed": args.seed, "rehearsal": bool(args.rehearse)}, rows)
    try:
        if args.table:
            topk_table(args, rows)
        if args.cell:
            cell_selections(args, rows)
    finally:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
