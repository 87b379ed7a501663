#!/usr/bin/env python3
"""Is the compiled step still the same program? Hashes of the StableHLO
(location metadata off) of every program `build_train_step` makes.

A change to `paddle_tpu/trainer/` that claims to move nothing shows it by
running this on the parent's checkout and on its own and comparing the
lines: equal hashes are equal programs, line for line.

    python tools/step_hlo.py forks <checkout> [--only a,b] [--dump DIR]
        every fork of the builder at toy size on the CPU's virtual
        devices: one device, ZeRO x tensor parallel (two streams), both
        pipeline schedules, ring and ulysses sequence parallelism,
        dropout, bf16 residency, the offloaded step's three programs,
        the flash kernels interpreted, the Keye decoder
    python tools/step_hlo.py cell <checkout> <workload> [--dump DIR]
        a benchmark cell's step as `benchmarks/families/` build it, on
        the devices this process has (on the chip: the real program;
        the kernels' serialized bodies are hashed apart)

One JSON line a program on standard output. Nothing is timed.
"""
from __future__ import annotations

import argparse
import functools
import hashlib
import json
import os
import re
import sys

PROGRAMS = ("gpt_train_step", "keye_train_step", "gpt_offload_grad",
            "gpt_offload_chunk", "gpt_offload_outer")
SEQ = 128


def sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:8]


def record_lowerings(jax, seen: dict):
    """`jax.jit` wrapped so that the first call of each program named in
    PROGRAMS leaves its StableHLO in `seen`."""
    jit = jax.jit

    def recording(fn, *a, **kw):
        jitted = jit(fn, *a, **kw)
        name = getattr(fn, "__name__", "")
        if name not in PROGRAMS:
            return jitted

        def call(*args):
            if name not in seen:
                seen[name] = jitted.lower(*args).as_text()
            return jitted(*args)
        call.lower = jitted.lower
        return call
    jax.jit = recording


def forks(only, dump):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    import jax
    import jax.numpy as jnp
    seen = {}
    record_lowerings(jax, seen)
    import paddle_tpu as pt
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models import (GPTForPretraining, KeyeForCausalLM,
                                   keye_tiny)
    try:
        from paddle_tpu.trainer import build_train_step, offload
    except ImportError:       # a checkout from before PR 31
        from paddle_tpu.models import build_train_step, gpt as offload
    from paddle_tpu.models.gpt import gpt_tiny
    from paddle_tpu.nn.functional import attention
    from paddle_tpu.ops import flash_attention as fa

    def adamw(**kw):
        return pt.optimizer.AdamW(
            learning_rate=1e-4, grad_clip=pt.nn.ClipGradByGlobalNorm(1.0),
            **kw)

    def run(tag, mesh_axes, devices=1, cfg=None, opt=None, model=None,
            rows=4, **build):
        if only and not any(o in tag for o in only):
            return
        seen.clear()
        if model is None:
            model = GPTForPretraining(gpt_tiny(
                dtype=jnp.float32, max_position_embeddings=SEQ,
                **(cfg or {})))
        mesh = build_mesh(devices=jax.devices()[:devices], **mesh_axes)
        step, state = build_train_step(model, opt or adamw(), mesh, **build)
        ids = jnp.arange(rows * SEQ, dtype=jnp.int32).reshape(rows, SEQ)
        batch = (ids * 7 % 250, (ids * 7 + 1) % 250)
        key = (jax.random.key(0),) if model.config.dropout > 0 else ()
        _, loss = step(state, batch, *key)
        emit(tag, dict(seen), dump, loss=float(loss))

    one, drop, host = {"dp": 1}, {"dropout": 0.1}, "unpinned_host"
    o2 = dict(param_dtype=jnp.bfloat16)
    run("one", one, loss_chunks=2)
    run("one_noremat_whole_loss", one, remat=False)
    run("one_full", one, remat_policy="full")
    run("one_dots_attn", one, remat_policy="dots_attn", donate=False)
    run("zero3_mp2", {"sharding": 2, "mp": 2}, 4, zero_stage=3,
        loss_chunks=2)
    run("zero2_dp2", {"sharding": 2, "dp": 2}, 4)
    run("mp2_odd_rows", {"mp": 2}, 2, rows=3)
    run("pp2_gpipe", {"pp": 2}, 2, num_microbatches=2)
    run("pp2_1f1b", {"pp": 2}, 2, num_microbatches=2,
        pipeline_schedule="1f1b")
    run("pp2_mp2_1f1b_chunks", {"pp": 2, "mp": 2}, 4, num_microbatches=4,
        pipeline_schedule="1f1b", loss_chunks=2)
    run("sp2_ring", {"sp": 2}, 2)
    run("sp2_ring_nozigzag", {"sp": 2}, 2, sequence_zigzag=False)
    run("sp2_ulysses", {"sp": 2}, 2, sequence_mode="ulysses")
    run("sp2_pp2_1f1b", {"sp": 2, "pp": 2}, 4, num_microbatches=2,
        pipeline_schedule="1f1b")
    run("dropout", one, cfg=drop)
    run("dropout_noremat", one, cfg=drop, remat=False)
    run("dropout_mp2", {"mp": 2}, 2, cfg=drop)
    run("dropout_pp2_gpipe", {"pp": 2}, 2, cfg=drop, num_microbatches=2)
    run("dropout_pp2_1f1b", {"pp": 2}, 2, cfg=drop, num_microbatches=2,
        pipeline_schedule="1f1b")
    run("dropout_sp2", {"sp": 2}, 2, cfg=drop)
    run("bf16_params", one, opt=adamw(multi_precision=True), **o2)
    run("bf16_params_zero3", {"sharding": 2}, 2,
        opt=adamw(multi_precision=True), zero_stage=3, **o2)
    run("offload", one, offload=True, offload_memory_kind=host)
    run("offload_sharding2", {"sharding": 2}, 2, offload=True,
        offload_memory_kind=host)
    run("offload_dropout", one, cfg=drop, offload=True,
        offload_memory_kind=host)
    run("offload_bf16", one, opt=adamw(multi_precision=True), offload=True,
        offload_memory_kind=host, **o2)
    offload._OFFLOAD_CHUNK_BYTES = 1     # the update in L chunks of one
    run("offload_chunks", one, offload=True, offload_memory_kind=host)
    # the kernels, interpreted
    fa._interpret = lambda: True
    attention._pallas_ok = lambda q, k, causal: True
    run("one_kernels", one, loss_chunks=2)
    run("zero3_mp2_kernels", {"sharding": 2, "mp": 2}, 4, zero_stage=3,
        loss_chunks=2)
    run("keye", one, model=KeyeForCausalLM(keye_tiny(dtype=jnp.float32)),
        remat_policy="dots_sel", loss_chunks=4)


def cell(workload, dump):
    import jax
    from benchmarks.harness import cells, data
    spec = cells.resolve(workload)
    config, mix = spec["config"], spec["mix"]
    devices = jax.devices()[:spec["cell"]["chips"]]
    adapter, reference = cells.family(config)
    pool = data.make_pool(mix, config["vocab_size"], 1)
    init = functools.partial(reference.init_weights, config)
    prog = adapter.build(config, mix, jax.jit(init)(jax.random.key(1)),
                         devices)
    text = prog.step.lower(prog.state, prog.put(pool[0])).as_text()
    name = re.search(r"module @jit_(\w+)", text).group(1)
    emit(workload, {name: text}, dump, device=devices[0].device_kind,
         chips=len(devices), kernels=len(re.findall("tpu_custom_call", text)))


def emit(tag, texts, dump, **more):
    for name, text in texts.items():
        # a Pallas kernel's body is serialized bytes in its custom call
        bare = re.sub(r'backend_config = "(?:[^"\\]|\\.)*"',
                      'backend_config = "..."', text)
        print(json.dumps({"fork": tag, "program": name, "sha": sha(text),
                          "sha_without_kernel_bodies": sha(bare),
                          "lines": text.count("\n"), **more}), flush=True)
        if dump:
            os.makedirs(dump, exist_ok=True)
            with open(os.path.join(dump, f"{tag}.{name}.txt"), "w") as f:
                f.write(bare)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("forks", "cell"))
    ap.add_argument("checkout")
    ap.add_argument("workload", nargs="?")
    ap.add_argument("--only", default="")
    ap.add_argument("--dump")
    args = ap.parse_args(argv)
    root = os.path.abspath(args.checkout)
    dump = os.path.abspath(args.dump) if args.dump else None
    os.chdir(root)
    sys.path.insert(0, root)
    if args.what == "forks":
        forks([o for o in args.only.split(",") if o], dump)
    elif not args.workload:
        ap.error("cell needs a workload")
    else:
        cell(args.workload, dump)
    return 0


if __name__ == "__main__":
    sys.exit(main())
