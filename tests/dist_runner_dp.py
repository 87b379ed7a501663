"""Per-rank runner for the multi-process DP loss-equivalence test.

The child-script half of the reference's `TestDistBase` pattern
(`test_dist_base.py:743` + `dist_mnist.py`): launched by
`paddle_tpu.distributed.launch`, reads the trainer env contract, brings up
the jax coordination service, trains a tiny GPT data-parallel over the
global (multi-process) mesh, and rank 0 writes the loss trajectory to the
JSON path in argv[1]. The parent test asserts equality with a
single-process run.
"""
import json
import os
import sys

import jax
import numpy as np

import paddle_tpu as pt  # noqa: E402
from paddle_tpu.distributed import env as denv  # noqa: E402

denv.init_parallel_env()

import jax.numpy as jnp  # noqa: E402
from jax.experimental import multihost_utils  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from paddle_tpu.distributed import build_mesh  # noqa: E402
from paddle_tpu.models import GPTConfig, GPTForPretraining  # noqa: E402
from paddle_tpu.trainer import build_train_step  # noqa: E402


def main():
    out_path = sys.argv[1]
    world = denv.get_world_size()
    rank = denv.get_rank()
    pt.seed(0)  # identical init on every rank
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=4, max_position_embeddings=64,
                    dtype=jnp.float32)
    model = GPTForPretraining(cfg)
    opt = pt.optimizer.AdamW(learning_rate=1e-3)
    mesh = build_mesh(dp=len(jax.devices()))
    step, state = build_train_step(model, opt, mesh, remat=False)

    rs = np.random.RandomState(0)
    B, S = 8, 16
    ids = rs.randint(0, 128, (B, S)).astype(np.int32)
    labels = rs.randint(0, 128, (B, S)).astype(np.int32)
    per = B // world
    lo = rank * per

    def to_global(a):
        if world == 1:
            return jnp.asarray(a)
        return multihost_utils.host_local_array_to_global_array(
            a[lo:lo + per], mesh, P(("data", "sharding"), None))

    losses = []
    for _ in range(3):
        state, loss = step(state, (to_global(ids), to_global(labels)))
        losses.append(float(loss))
    if rank == 0:
        with open(out_path, "w") as f:
            json.dump(losses, f)
    print(f"RUNNER_OK rank={rank} losses={losses}", flush=True)


if __name__ == "__main__":
    main()
