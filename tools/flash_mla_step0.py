#!/usr/bin/env python3
"""Step 0 of latent attention and of the kanana cell (ISSUE 33), of the
fused backward kernel that the latent and the selected family share
(ISSUES 34, 35) and of the latent forward (ISSUE 36), to be run on the
chip:

    python tools/flash_mla_step0.py [--kernels latent,selected]
        [--checkout DIR] [--step POLICY,POLICY] [--balance SEED,SEED]
        [--out FILE]

1. `--kernels latent` (or `1`): the latent flash kernels
   (`profiler.MLA_KERNELS`: since ISSUE 34 the forward and ONE backward,
   dq out of the dk/dv walk) at the kanana cell's widths, on the arrays
   the projections make (bf16 q [2, 8192, 32 x 192], kv [2, 8192, 32 x
   (128 + 128)], ONE rotary key head of 64), handed to the kernels as
   the tree's own `DeepseekV3Attention.forward` hands them: the rotary
   turn, the slices and the layout of each operand (q without position,
   kv, o and dO stay `[b, s, h w]` and the rotary query part goes
   head-major; a tree whose entry takes q whole lays every operand out
   `[b h, s, w]` inside it), so the program column holds what XLA does
   around the kernels in that tree; `--kernels selected`: the selected
   ones (`profiler.SEL_KERNELS`: likewise two) at the Keye
   cell's (32 query heads over `--kv-heads` 4 of 128, an int8 selection a
   row that keeps 2048 keys of a query's causal ones, 43.75 % of the
   causal pairs at 8192). Forward and backward, as the layer scan of a
   step runs them: a `scan` of `--layers` calls of value-and-gradient.
   Ms a call by the device's clock: each kernel's own events and the
   whole program, which holds what XLA does around them (the layouts, the
   sum over heads, the selection's transpose), and the loss's and the
   gradients' L1 norms.
   (How the kernels are handed the rotary key, through the index map or
   concatenated in HBM, the resident block by the values' width or the
   scores', where the fused backward holds the head's dq and where the
   forward keeps its running statistics were ranked before, PERF.md
   section 6. Each is settled and is what the kernels do.)
   `--checkout DIR` reads the same table from another tree's kernels
   (the parent's, unpacked by `git archive`), under the kernel names it
   has (an unfused tree's dq and dk/dv kernels each).
2. `--step full,dots`: the cell's whole step as the benchmark builds it,
   once for each remat policy named: compiled (or refused: the
   compiler's message is the row), `--steps` steps by the host's clock
   after the first, the allocator's peak.
3. `--balance 1,2,3`: for each seed the cell's run as the benchmark makes
   it (its weights, its pool, its step) for `--steps` steps, and at every
   `--every`-th the rows a layer's router sends to the 16 held experts
   (balanced: tokens x 6 x 16 / 128 = 12288), by the reference's routing
   on the program's own state.

One JSON line a reading on standard output, all of them in `--out`.
Off the TPU (`--rehearse 1`) everything is cut to a toy size and the
kernels are interpreted: a rehearsal of the control flow, whose times
mean nothing.
"""
from __future__ import annotations

import argparse
import functools
import gc
import inspect
import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONFIG = "kanana-2-30b-a3b-instruct-2601.json"
MIX = "pretrain-s8192-fresh.json"
fa = None       # `paddle_tpu.ops.flash_attention` of the tree under test


def log(row: dict, rows: list) -> None:
    rows.append(row)
    print(json.dumps(row), flush=True)


def device_trace(fn, reps: int) -> dict:
    """The device's `ops` and `modules` events of `reps` runs of `fn()`
    (compiled beforehand); nothing off the chip."""
    import shutil
    import tempfile
    from benchmarks.harness import trace
    where = tempfile.mkdtemp(prefix="flash_mla_step0_")
    try:
        with jax.profiler.trace(where):
            for _ in range(reps):
                jax.block_until_ready(fn())
        return trace.load(trace.find_xplane(where))["devices"].get(
            0, {"ops": [], "modules": []})
    finally:
        shutil.rmtree(where, ignore_errors=True)


def latent_family(args):
    """(kernel names, differentiable operands' shapes, the output's,
    further operands of a layer from a key, the call)."""
    from paddle_tpu.nn import functional as F
    from paddle_tpu.profiler import MLA_KERNELS
    b, s, h = 2, args.seq, args.heads
    dn, dr, dv = args.widths
    # an older tree's entry takes q whole and k_nope, v apart
    whole_q = "query" in inspect.signature(
        fa.flash_attention_latent).parameters

    def rotary(x):
        return F.rotary_embedding(x, 1e6, interleaved=True)

    def call(q, kv, k_rope):
        # what `DeepseekV3Attention.forward` of that tree does between the
        # projections and `o_proj`
        q, kv = q.reshape(b, s, h, dn + dr), kv.reshape(b, s, h, dn + dv)
        if whole_q:
            o = fa.flash_attention_latent(
                jnp.concatenate([q[..., :dn], rotary(q[..., dn:])], -1),
                kv[..., :dn], rotary(k_rope), kv[..., dn:])
        else:
            o = fa.flash_attention_latent(
                q[..., :dn], jnp.swapaxes(rotary(q[..., dn:]), 1, 2), kv,
                rotary(k_rope))
        return o.reshape(b, s, h * dv)
    return (MLA_KERNELS, [(b, s, h * (dn + dr)), (b, s, h * (dn + dv)),
                          (b, s, 1, dr)], (b, s, h * dv), None, call)


def selected_family(args):
    from paddle_tpu.profiler import SEL_KERNELS
    b, s, h, d = 2, args.seq, args.heads, args.widths[0]
    topk = s // 4

    def selection(key):
        # about `topk` of a query's causal keys, its own among them
        qi, ki = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
        u = jax.random.uniform(key, (b, s, s))
        return ((ki == qi) | ((ki < qi) & (u * (qi + 1) < topk))).astype(
            jnp.int8)

    def call(q, k, v, sel):
        return fa.flash_attention(q, k, v, causal=True, selection=sel)
    return (SEL_KERNELS, [(b, s, h, d), (b, s, args.kv_heads, d),
                          (b, s, args.kv_heads, d)], (b, s, h, d),
            selection, call)


FAMILIES = {"latent": latent_family, "selected": selected_family}


def kernel_table(args, rows: list, family: str) -> None:
    kernels, shapes, out_shape, further, call = FAMILIES[family](args)
    shapes = shapes + [out_shape]

    def operands(key):
        keys = jax.random.split(key, len(shapes) + 1)
        return ([jax.random.normal(k, sh, jnp.bfloat16)
                 for k, sh in zip(keys, shapes)],
                further(keys[-1]) if further else ())
    (*stacks, dos), more = jax.jit(lambda keys: jax.lax.map(operands, keys))(
        jax.random.split(jax.random.key(args.seed), args.layers))
    width = args.widths[2] if family == "latent" else args.widths[0]

    def layers(*diff):
        # the gradient is taken outside the scan, as a step takes it: a
        # backward loop of its own, and the kernels under the names a step
        # gives them
        def one(xs):
            *a, do, extra = xs
            return jnp.sum(call(*a, *((extra,) if further else ()))
                           .astype(jnp.float32) * do.astype(jnp.float32))
        return jnp.sum(jax.lax.map(one, (*diff, dos, more)))
    fn = jax.jit(lambda xs: jax.value_and_grad(
        layers, argnums=tuple(range(len(xs))))(*xs))
    t = time.perf_counter()
    out = jax.block_until_ready(fn(stacks))
    dev = device_trace(lambda: fn(stacks), args.reps)
    calls = args.reps * args.layers
    row = {"what": "kernels", "family": family,
           "plan": list(fa._values_plan(args.seq, width, jnp.bfloat16)),
           "program_ms_a_call":
               1e3 * sum(e - s0 for s0, e, _ in dev["modules"]) / calls}
    if further:
        row["selection_keeps_of_causal"] = float(
            jnp.sum(more[0], dtype=jnp.float32)
            / (more[0].shape[0] * args.seq * (args.seq + 1) / 2))
    for kernel in kernels:
        hits = [e - s0 for s0, e, n in dev["ops"]
                if re.match(rf"%{kernel}(\.[.\w]*)? = ", n)]
        row[kernel + "_ms_a_call"] = 1e3 * sum(hits) / calls
    # the loss and the L1 norm of each gradient: what two trees' rows are
    # held to each other by (the arrays themselves do not fit twice)
    row["l1_norms"] = [float(jnp.sum(jnp.abs(x.astype(jnp.float32))))
                       for x in jax.tree.leaves(out)]
    row["compile_and_runs_s"] = time.perf_counter() - t
    log(row, rows)


def cell_spec(args):
    from benchmarks.harness import cells
    config = cells.load_json("configs", CONFIG)
    mix = cells.load_json("traffic", MIX)
    config["step"].update(config["layouts"]["1"])
    if args.rehearse:
        config.update(hidden_size=64, intermediate_size=96,
                      moe_intermediate_size=32, num_hidden_layers=3,
                      num_attention_heads=4, kv_lora_rank=32,
                      qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                      n_routed_experts=4, num_experts_per_tok=2,
                      vocab_size=256)
        config["published"].update(n_routed_experts=8, vocab_size=512)
        mix = dict(mix, seq=args.seq, pool_batches=8)
    return config, mix, cells.family(config)


def build(config, mix, family, seed, devices):
    from benchmarks.harness import data
    adapter, reference = family
    pool = data.make_pool(mix, config["vocab_size"], seed)
    init = jax.jit(functools.partial(reference.init_weights, config))
    return adapter.build(config, mix, init(jax.random.key(seed)),
                         devices), pool


def step_table(args, rows: list) -> None:
    config, mix, family = cell_spec(args)
    devices = jax.devices()[:1]
    for policy in args.step.split(","):
        config["step"]["remat_policy"] = policy
        t = time.perf_counter()
        try:
            prog, pool = build(config, mix, family, args.seed, devices)
            state, prog.state = prog.state, None
            state, loss = prog.step(state, prog.put(pool[0]))
            first = float(loss)
            compiled = time.perf_counter() - t
            t = time.perf_counter()
            for i in range(1, args.steps + 1):
                state, loss = prog.step(state, prog.put(pool[i % len(pool)]))
            last = float(loss)
            ms = 1e3 * (time.perf_counter() - t) / args.steps
        except Exception as e:
            log({"what": "step", "remat_policy": policy,
                 "error": str(e)[:400]}, rows)
            continue
        peak = (devices[0].memory_stats() or {}).get("peak_bytes_in_use")
        log({"what": "step", "remat_policy": policy, "ms_a_step": ms,
             "steps": args.steps, "first_loss": first, "last_loss": last,
             "build_and_first_step_s": compiled,
             "memory_peak_bytes_so_far": peak}, rows)
        del state, prog
        gc.collect()


def held_rows(reference, config, w, ids):
    """Rows each expert layer's router sends to the held experts, by the
    reference's own attention, routing and expert layer on `w` (the
    program's state under the reference's names); default matmul
    precision: a census, not a comparison."""
    z = reference.sizes(config)
    mm = jnp.matmul

    def under(prefix):
        return {k[len(prefix):]: a.astype(jnp.float32)
                for k, a in w.items() if k.startswith(prefix)}

    def expert_layer(x, p):
        h = x + reference.attention(
            z, p, reference.rms_norm(x, p["ln1.w"], z["eps"]), mm)
        u = reference.rms_norm(h, p["ln2.w"], z["eps"])
        g = reference.routing(z, mm(u, p["router.w"]), p["router.bias"])
        here = jax.lax.dynamic_slice_in_dim(g, z["off"], z["held"], -1)
        return h + reference.moe(z, p, u, mm), jnp.sum(here > 0)
    x = reference.layer(z, under("dense."),
                        w["embed"][ids].astype(jnp.float32), mm, True)
    return jax.lax.scan(expert_layer, x, under("blocks."))[1]


def balance(args, rows: list) -> None:
    config, mix, family = cell_spec(args)
    census = jax.jit(functools.partial(held_rows, family[1], config))
    tokens = mix["batch"] * mix["seq"]
    even = tokens * config["num_experts_per_tok"] \
        * config["n_routed_experts"] / config["published"]["n_routed_experts"]
    for seed in (int(x) for x in args.balance.split(",")):
        prog, pool = build(config, mix, family, seed, jax.devices()[:1])
        state, prog.state = prog.state, None
        for i in range(args.steps + 1):
            if i % args.every == 0 or i == args.steps:
                got = [int(n) for n in census(prog.params(state),
                                              pool[i % len(pool)]["ids"])]
                log({"what": "balance", "seed": seed, "before_step": i + 1,
                     "rows_on_held_experts": got, "balanced": even,
                     "worst_share_of_balanced":
                         max(abs(n / even - 1.0) for n in got)}, rows)
            state, loss = prog.step(state, prog.put(pool[i % len(pool)]))
        log({"what": "balance", "seed": seed, "last_loss": float(loss)},
            rows)
        del state, prog
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=2147491012)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--widths", type=lambda t: tuple(map(int, t.split(","))),
                    default=(128, 64, 128), help="nope,rope,value")
    ap.add_argument("--layers", type=int, default=3,
                    help="calls a program: operands, gradients and the "
                         "scan's residuals of 6 do not fit the chip")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--kernels", default="",
                    help="families, of " + ",".join(FAMILIES)
                         + " (1: latent)")
    ap.add_argument("--kv-heads", type=int, default=4,
                    help="key/value heads of the selected family")
    ap.add_argument("--checkout", default=ROOT,
                    help="the tree whose kernels are read")
    ap.add_argument("--step", default="")
    ap.add_argument("--balance", default="")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--every", type=int, default=8)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default="chiprun_out/flash_mla_step0.json")
    args = ap.parse_args(argv)
    families = [f for f in args.kernels.replace("1", "latent").split(",")
                if f and f != "0"]
    if set(families) - set(FAMILIES):
        ap.error(f"--kernels: of {sorted(FAMILIES)}, got {args.kernels}")
    global fa
    root = os.path.abspath(args.checkout)
    sys.path.insert(0, root)
    from paddle_tpu.ops import flash_attention as fa
    if args.rehearse:
        from paddle_tpu.nn.functional import attention
        fa._interpret = lambda: True
        attention._pallas_ok = lambda q, k, causal: True
    elif jax.default_backend() != "tpu":
        raise SystemExit("flash_mla_step0: no TPU; nothing was run "
                         "(--rehearse 1 walks the script off the chip)")
    rows = []
    log({"what": "device", "kind": jax.devices()[0].device_kind,
         "seq": args.seq, "layers": args.layers, "seed": args.seed,
         "checkout": root, "rehearsal": bool(args.rehearse)}, rows)
    try:
        for family in families:
            kernel_table(args, rows, family)
        if args.step:
            step_table(args, rows)
        if args.balance:
            balance(args, rows)
    finally:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
