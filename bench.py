"""Training benchmarks for the BASELINE.json configs, on the TPU.

    python bench.py [spec ...]        # default: gpt

A spec is `name` or `name:arg`: `gpt` (`gpt:b16`, `gpt:nr`, `gpt:da`
variant arms), `bert`, `resnet:256`, `yolo:48`, `ernie:0p76b`. The whole
run is ONE process, the only one that touches JAX: a chip belongs to one
process at a time, so a parent that had looked at the backend would
starve every child it started. Each spec prints one JSON line as it
completes, naming the device it ran on (platform, kind, count).

There is no CPU arm. Without a TPU the run exits non-zero before it
builds a model; a spec that raises prints its traceback, the remaining
specs still run, and the exit code is non-zero. A device kind that is
not in `PEAK_FLOPS` is an error, not a default.

vs_baseline is MFU / 0.35 — the north-star target from BASELINE.json
("BERT-base pretraining >=35% MFU"); the reference publishes no absolute
numbers (BASELINE.md), so the MFU ratio is the comparable metric.

Every timed region ends in `jax.block_until_ready`; the compile and
warm-up steps run before the clock starts.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

import numpy as np


# bf16 peak FLOP/s of one chip, keyed by `jax.Device.device_kind` as the
# installed runtime reports it. Source: Google Cloud documentation,
# "TPU v5e" (197 TFLOP/s bf16; libtpu 0.0.34 names the chip
# "TPU v5 lite"). Add a row, with its source, before running elsewhere.
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,
}


def peak_flops(kind: str) -> float:
    try:
        return PEAK_FLOPS[kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s on record for device kind {kind!r}; known: "
            f"{sorted(PEAK_FLOPS)}. Add it to bench.PEAK_FLOPS with its "
            "source — an assumed peak makes every MFU a guess") from None


def model_flops_per_token(cfg, seq_len: int) -> float:
    """6*P matmul flops/token (fwd+bwd) + attention term 12*L*d*s."""
    d, L, V = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    ffn = cfg.ffn_hidden
    p_block = L * (4 * d * d + 2 * d * ffn)        # qkv+out + 2 mlp mats
    p_emb = V * d                                   # tied head matmul
    return 6.0 * (p_block + p_emb) + 12.0 * L * d * seq_len


def _timed_steps(step, state, steps, warmup):
    """Shared timing protocol: step(state) -> (state, loss). Compile and
    warm-up come first; the timed region ends when the last step's
    outputs are ready on the device."""
    import jax
    for _ in range(warmup):
        state, loss = step(state)
    jax.block_until_ready((state, loss))
    t0 = time.perf_counter()
    for _ in range(steps):
        state, loss = step(state)
    jax.block_until_ready((state, loss))
    return state, time.perf_counter() - t0


# ---------------------------------------------------------------- configs

def bench_gpt(variant: str = "") -> dict:
    """BASELINE config 3 (headline): GPT-345M, hybrid-capable train step:
    batch 8 per chip, selective remat (dots policy), chunked fused
    logits+CE (8 chunks), Pallas flash attention at seq 1024.

    `variant` arms: 'b16' doubles the batch, 'nr' drops remat (345M
    activations fit HBM — recompute is pure overhead if so), 'b16nr'
    both, 'da' switches to the dots_attn remat policy (keeps the named
    attention output so the backward skips the flash-forward replay —
    ~16MB/layer of residency for one less kernel pass)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models import gpt_345m, GPTForPretraining, \
        build_train_step

    n_dev = len(jax.devices())
    seq = 1024
    cfg = gpt_345m()
    batch = (16 if "b16" in variant else 8) * n_dev
    steps, warmup, chunks = 20, 3, 8

    mesh = build_mesh(dp=n_dev)
    model = GPTForPretraining(cfg)
    opt = pt.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                             grad_clip=pt.nn.ClipGradByGlobalNorm(1.0))
    step, state = build_train_step(model, opt, mesh, num_microbatches=1,
                                   remat="nr" not in variant,
                                   remat_policy="dots_attn"
                                   if "da" in variant else "dots",
                                   loss_chunks=chunks)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                      jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    _, dt = _timed_steps(lambda s: step(s, (ids, labels)), state, steps,
                         warmup)

    tokens_per_sec_chip = batch * seq * steps / dt / n_dev
    flops = model_flops_per_token(cfg, seq) * tokens_per_sec_chip
    mfu = flops / peak_flops(jax.devices()[0].device_kind)
    return {
        "metric": "gpt345m_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec_chip, 1),
        "unit": "tokens/s/chip",
        "config": variant or "base",
        "vs_baseline": round(mfu / 0.35, 4),
    }


def bench_bert() -> dict:
    """BASELINE config 2: BERT-base MLM+NSP pretraining, data parallel —
    the metric the north star is literally defined on."""
    import functools

    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models.bert import bert_base, BertForPretraining
    from paddle_tpu.nn.layer import functional_call, trainable_state

    seq, batch = 512, 32
    steps, warmup = 20, 3
    cfg = bert_base()
    model = BertForPretraining(cfg)
    opt = pt.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01)
    params = trainable_state(model)
    opt_state = opt.init_state(params)

    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    # realistic padded batch (VERDICT r3 item 6): ragged lengths; the
    # [b,1,1,s] padding mask reduces to the flash kernel's k-side mask
    lengths = rs.randint(int(seq * 0.7), seq + 1, (batch,))
    pad_valid = np.arange(seq)[None, :] < lengths[:, None]
    attention_mask = jnp.asarray(pad_valid)
    # reference-style MLM: up to max_predictions_per_seq=80 masked slots
    # per sequence, gathered BEFORE the vocab head (masked_positions);
    # ragged prediction counts pad with ignore_index -1
    max_preds = 80
    positions = np.zeros((batch, max_preds), np.int32)
    labels_np = np.full((batch, max_preds), -1, np.int32)
    for b in range(batch):
        n_pred = min(max_preds, max(1, int(lengths[b] * 0.15)))
        pos = rs.choice(lengths[b], size=n_pred, replace=False)
        positions[b, :n_pred] = np.sort(pos)
        labels_np[b, :n_pred] = rs.randint(0, cfg.vocab_size, n_pred)
    masked_positions = jnp.asarray(positions)
    mlm_labels = jnp.asarray(labels_np)
    nsp = jnp.asarray(rs.randint(0, 2, (batch,)), jnp.int32)

    def loss_fn(params, ids, mlm_labels, nsp):
        out, _ = functional_call(model, params, ids, None, attention_mask,
                                 mlm_labels, nsp,
                                 masked_positions=masked_positions)
        return out

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, ids, mlm_labels, nsp):
        params, opt_state = state
        loss, g = jax.value_and_grad(loss_fn)(params, ids, mlm_labels, nsp)
        new_p, new_s = opt.apply(params, g, opt_state)
        return (new_p, new_s), loss

    _, dt = _timed_steps(lambda s: step(s, ids, mlm_labels, nsp),
                         (params, opt_state), steps, warmup)

    n_dev = len(jax.devices())
    tok_s_chip = batch * seq * steps / dt / n_dev
    # executed flops: trunk on all `seq` tokens, tied vocab head only on
    # the `max_preds` GATHERED positions — counting the dense head here
    # would overstate MFU ~20% (the gather is the whole point)
    d, L, V = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    p_block = L * (4 * d * d + 2 * d * cfg.ffn_hidden)
    trunk_per_tok = 6.0 * p_block + 12.0 * L * d * seq
    head_per_pred = 6.0 * (V * d + d * d)  # vocab decode + transform
    step_flops = batch * (seq * trunk_per_tok + max_preds * head_per_pred)
    mfu = step_flops / dt * steps / n_dev / \
        peak_flops(jax.devices()[0].device_kind)
    return {"metric": "bert_base_pretrain_tokens_per_sec_per_chip",
            "value": round(tok_s_chip, 1), "unit": "tokens/s/chip",
            "vs_baseline": round(mfu / 0.35, 4)}


def _resnet_bench_config():
    """ONE source of truth for the bench's conv format + stem (the
    reported 'stem' field keys A/B dedup — a drifted duplicate of this
    logic would mislabel arms). space_to_depth is an EXACT
    reformulation of the 7x7/s2 stem
    (tests/test_vision_additions.py::TestSpaceToDepthStem)."""
    fmt = os.environ.get("PTPU_BENCH_CONV_FORMAT", "NHWC")
    stem = os.environ.get("PTPU_BENCH_RESNET_STEM",
                          "space_to_depth" if fmt == "NHWC" else "conv")
    return fmt, stem


def _bench_resnet_at(batch: int) -> float:
    import functools

    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.vision.models import resnet50
    from paddle_tpu.nn.layer import (buffer_state, functional_call,
                                     trainable_state)

    steps, warmup = 10, 2
    # channels-last end-to-end: the TPU-native conv layout — no
    # layout-assignment transposes around each conv+BN (VERDICT r3
    # item 2); weights stay OIHW so state dicts are unchanged
    fmt, stem = _resnet_bench_config()
    model = resnet50(data_format=fmt, stem=stem)
    opt = pt.optimizer.Momentum(learning_rate=0.1, momentum=0.9)
    params = trainable_state(model)
    buffers = buffer_state(model)
    opt_state = opt.init_state(params)
    rs = np.random.RandomState(0)
    shape = (batch, 224, 224, 3) if fmt == "NHWC" else (batch, 3, 224, 224)
    x = jnp.asarray(rs.randn(*shape), jnp.float32)
    y = jnp.asarray(rs.randint(0, 1000, (batch,)), jnp.int32)
    ce = pt.nn.CrossEntropyLoss()

    def loss_fn(params, buffers, x, y):
        with pt.amp.auto_cast(level="O1"):
            out, new_buf = functional_call(model, params, x,
                                           buffers=buffers)
        return ce(out, y), new_buf

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, x, y):
        params, buffers, opt_state = state
        (loss, new_buf), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, buffers, x, y)
        new_p, new_s = opt.apply(params, g, opt_state)
        return (new_p, new_buf, new_s), loss

    _, dt = _timed_steps(lambda s: step(s, x, y),
                         (params, buffers, opt_state), steps, warmup)
    return batch * steps / dt / len(jax.devices())


def bench_resnet(batch: int = 64) -> dict:
    """BASELINE config 1: ResNet-50 training throughput (imgs/sec),
    bf16 compute via amp auto_cast O1, at ONE batch size."""
    import jax

    imgs = _bench_resnet_at(batch)
    # ResNet-50 fwd ~4.1 GFLOPs/img at 224^2; x3 for fwd+bwd
    mfu = imgs * 3 * 4.1e9 / peak_flops(jax.devices()[0].device_kind)
    return {"metric": "resnet50_train_imgs_per_sec_per_chip",
            "value": round(imgs, 1), "unit": "imgs/s/chip",
            "batch": batch,
            "stem": _resnet_bench_config()[1],
            "vs_baseline": round(mfu / 0.35, 4)}


def bench_yolo(batch: int = 8) -> dict:
    """BASELINE config 4: PP-YOLO-class (YOLOv3-DarkNet53) training
    throughput at ONE batch size."""
    import jax

    imgs = _bench_yolo_at(batch)
    # YOLOv3-DarkNet53 fwd ~39 GFLOPs/img at 320^2; x3 for fwd+bwd
    mfu = imgs * 3 * 39e9 / peak_flops(jax.devices()[0].device_kind)
    return {"metric": "yolov3_darknet53_train_imgs_per_sec_per_chip",
            "value": round(imgs, 1), "unit": "imgs/s/chip",
            "batch": batch,
            "vs_baseline": round(mfu / 0.35, 4)}


def _bench_yolo_at(batch: int) -> float:
    import functools

    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.vision.models import yolov3_darknet53, yolo_loss
    from paddle_tpu.nn.layer import (buffer_state, functional_call,
                                     trainable_state)

    size, steps, warmup = 320, 8, 2
    fmt = os.environ.get("PTPU_BENCH_CONV_FORMAT", "NHWC")
    model = yolov3_darknet53(num_classes=80, data_format=fmt)
    model.train()
    opt = pt.optimizer.Momentum(learning_rate=0.01, momentum=0.9)
    params = trainable_state(model)
    buffers = buffer_state(model)
    opt_state = opt.init_state(params)
    rs = np.random.RandomState(0)
    shape = (batch, size, size, 3) if fmt == "NHWC" \
        else (batch, 3, size, size)
    x = jnp.asarray(rs.randn(*shape), jnp.float32)
    gt_box = jnp.asarray(rs.uniform(0.2, 0.8, (batch, 16, 4)), jnp.float32)
    gt_cls = jnp.asarray(rs.randint(0, 80, (batch, 16)), jnp.int32)

    def loss_fn(params, buffers, x):
        with pt.amp.auto_cast(level="O1"):
            outs, new_buf = functional_call(model, params, x,
                                            buffers=buffers)
        return yolo_loss(outs, gt_box, gt_cls, num_classes=80), new_buf

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, x):
        params, buffers, opt_state = state
        (loss, new_buf), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, buffers, x)
        new_p, new_s = opt.apply(params, g, opt_state)
        return (new_p, new_buf, new_s), loss

    _, dt = _timed_steps(lambda s: step(s, x),
                         (params, buffers, opt_state), steps, warmup)
    return batch * steps / dt / len(jax.devices())


def bench_ernie(size: str = "2p6b") -> dict:
    """BASELINE config 5: ERNIE-10B-class sharded/offloaded pretraining.

    On the one available chip this is the offload story: Adam m/v (fp32,
    2x params) rest in HOST memory (`build_train_step(offload=True)` —
    reference: sharding offload_helper.py), so the largest trainable
    size is bounded by params+grads+activations, not optimizer state."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models import (GPTForPretraining, build_train_step,
                                   ernie_10b, gpt_760m, gpt_1p3b,
                                   gpt_2p6b, gpt_6p7b)

    cfgs = {"10b": ernie_10b, "6p7b": gpt_6p7b, "2p6b": gpt_2p6b,
            "1p3b": gpt_1p3b, "0p76b": gpt_760m}
    cfg = cfgs[size]()
    n_dev = len(jax.devices())
    seq, batch, steps, warmup = 1024, 1 * n_dev, 8, 2
    mesh = build_mesh(dp=n_dev)
    # construct the eager model on the CLIENT CPU: its fp32 params are
    # only the source material (masters / bf16 cast) — at 2.6B they
    # must never occupy HBM alongside the resident state
    cpu0 = jax.local_devices(backend="cpu")[0]
    with jax.default_device(cpu0):
        model = GPTForPretraining(cfg)
    # >=2.6B: params must rest bf16 (fp32 params+grads alone exceed
    # HBM); fp32 master weights join the host-offloaded slots
    # (reference pure-fp16 + multi-precision adam)
    o2 = size in ("10b", "6p7b", "2p6b")
    opt = pt.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                             grad_clip=pt.nn.ClipGradByGlobalNorm(1.0),
                             multi_precision=o2)
    # slots rest in ordinary host RAM by default; pinned_host (DMA-able)
    # is the alternative, bounded by the host's pinned pool
    step, state = build_train_step(
        model, opt, mesh, remat=True, remat_policy="full", loss_chunks=8,
        offload=True,
        offload_memory_kind=os.environ.get("PTPU_OFFLOAD_MEMKIND",
                                           "unpinned_host"),
        param_dtype=jnp.bfloat16 if o2 else None)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                      jnp.int32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    _, dt = _timed_steps(lambda s: step(s, (ids, labels)), state, steps,
                         warmup)
    tok_s_chip = batch * seq * steps / dt / n_dev
    mfu = model_flops_per_token(cfg, seq) * tok_s_chip / \
        peak_flops(jax.devices()[0].device_kind)
    return {"metric": f"ernie_class_{size}_offload_tokens_per_sec_per_chip",
            "value": round(tok_s_chip, 1), "unit": "tokens/s/chip",
            "size": size, "vs_baseline": round(mfu / 0.35, 4)}


# name -> (function, type of its one optional argument)
_BENCHES = {"gpt": (bench_gpt, str),            # variant
            "bert": (bench_bert, None),
            "resnet": (bench_resnet, int),      # batch
            "yolo": (bench_yolo, int),          # batch
            "ernie": (bench_ernie, str)}        # size


def run_spec(spec: str) -> dict:
    """One config, 'name' or 'name:arg'."""
    name, _, arg = spec.partition(":")
    if name not in _BENCHES:
        raise ValueError(f"unknown bench {name!r}; known: "
                         f"{sorted(_BENCHES)}")
    fn, arg_type = _BENCHES[name]
    if not arg:
        return fn()
    if arg_type is None:
        raise ValueError(f"{name} takes no argument")
    return fn(arg_type(arg))


def main(argv=None) -> int:
    specs = list(sys.argv[1:] if argv is None else argv) or ["gpt"]
    from paddle_tpu.core import compile_cache
    cache_dir = compile_cache.enable()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench: needs a TPU, found platform {dev.platform!r} "
              f"({dev.device_kind}); nothing measured", file=sys.stderr)
        return 1
    peak_flops(dev.device_kind)     # unknown kind: fail before any work
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"bench: device {device}, compile cache {cache_dir}",
          file=sys.stderr)
    failed = []
    for spec in specs:
        try:
            res = run_spec(spec)
        except Exception:  # noqa: BLE001 — reported, and the run fails
            traceback.print_exc()
            failed.append(spec)
            continue
        print(json.dumps(dict(res, device=device)), flush=True)
    if failed:
        print(f"bench: FAILED {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
