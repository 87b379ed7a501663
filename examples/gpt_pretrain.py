"""Book example 2: GPT causal-LM pretraining with the hybrid-parallel
compiled step (the BASELINE config-3 flow at toy scale).

Run: python examples/gpt_pretrain.py [--steps N]
Scale up: pass a bigger GPTConfig and a multi-axis mesh — the same
build_train_step compiles dp x tp x pp x zero from mesh axes alone.
"""
import argparse

import numpy as np


def main(steps=10):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models import GPTConfig, GPTForPretraining
    from paddle_tpu.trainer import build_train_step

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=128,
                    dtype=jnp.float32)
    model = GPTForPretraining(cfg)
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-3, weight_decay=0.01,
        grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
    mesh = build_mesh(dp=1)
    step, state = build_train_step(model, opt, mesh)

    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 512, (4, 64)), jnp.int32)
    losses = []
    for _ in range(steps):
        state, loss = step(state, (ids, ids))
        losses.append(float(loss))
    print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    assert losses[-1] < losses[0]
    return losses


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    main(steps=ap.parse_args().steps)
