"""Book example: long-context training with sequence parallelism
COMPOSED with pipeline parallelism (SP x PP, round 5).

BEYOND-REFERENCE capability (SURVEY.md §5 long-context mandate): the
reference has no sequence/context parallelism; here zigzag-balanced
causal ring attention (`distributed/meta_parallel/sequence_parallel.py`)
rides INSIDE the stacked-stage 1F1B pipeline schedule
(`distributed/meta_parallel/stacked_pipeline.py`) in one compiled step.

The axes are orthogonal by construction:
  * 'pipe'     — stacks decoder blocks; microbatches stream through the
                 collective-permute schedule (splits the BATCH dim)
  * 'sequence' — shards every activation on the SEQUENCE dim; each
                 layer's attention runs blockwise ring attention with
                 K/V rotating over the axis via ppermute
  * 'data'     — plain data parallelism over what remains

Run (any machine — forces an 8-virtual-device CPU mesh):
    python examples/long_context_pipeline.py [--steps N]
"""
import argparse
import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
          if "host_platform_device_count" not in f]
os.environ["XLA_FLAGS"] = " ".join(
    _flags + ["--xla_force_host_platform_device_count=8"])

import jax                                                   # noqa: E402
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402

import paddle_tpu as pt                                      # noqa: E402
from paddle_tpu.distributed import build_mesh                # noqa: E402
from paddle_tpu.models import GPTConfig, GPTForPretraining   # noqa: E402
from paddle_tpu.trainer import build_train_step              # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()

    # seq 256 sharded 2-way: each chip holds 128 tokens of activations;
    # scale `sp` (and seq) up on a real slice — the step is identical
    mesh = build_mesh(dp=2, pp=2, sp=2)
    cfg = GPTConfig(vocab_size=2048, hidden_size=128, num_layers=4,
                    num_heads=8, max_position_embeddings=256,
                    dtype=jnp.bfloat16)
    model = GPTForPretraining(cfg)
    opt = pt.optimizer.AdamW(learning_rate=3e-4, weight_decay=0.01,
                             grad_clip=pt.nn.ClipGradByGlobalNorm(1.0))
    step, state = build_train_step(model, opt, mesh,
                                   pipeline_schedule="1f1b",
                                   num_microbatches=2)

    rs = np.random.RandomState(0)
    B, S = 8, 256
    for i in range(args.steps):
        ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (B, S)), jnp.int32)
        labels = jnp.asarray(np.roll(np.asarray(ids), -1, 1), jnp.int32)
        t0 = time.perf_counter()
        state, loss = step(state, (ids, labels))
        loss = float(loss)
        print(f"step {i}: loss {loss:.4f}  "
              f"({time.perf_counter() - t0:.2f}s"
              f"{' incl. compile' if i == 0 else ''})")


if __name__ == "__main__":
    main()
