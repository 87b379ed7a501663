"""Book example: billion-class pretraining on ONE chip via host offload
(the BASELINE config-5 flow at toy scale).

Reference bar: static ShardingOptimizer ZeRO-2 + offload
(`fleet/meta_optimizers/sharding/offload_helper.py`) — Adam moments and
fp32 master weights rest in HOST memory and stream through device
memory per parameter group during the update. Here the same design is
three compiled XLA programs (grad phase / chunked slot-streaming
update / outer update) built by `build_train_step(offload=True)`.

Two knobs matter at scale:
  * `offload=True`            — slots rest on host, streamed per chunk
  * `param_dtype=bf16` (+ `multi_precision=True` on the optimizer) —
    params+grads rest bf16, EXACT fp32 masters live with the slots
    (2.6B fits one v5e chip this way)

Run: python examples/ernie_offload_pretrain.py [--steps N]
"""
import argparse

import numpy as np


def main(steps=8, o2=True):
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models import GPTConfig, GPTForPretraining
    from paddle_tpu.trainer import build_train_step

    paddle.seed(0)
    # toy stand-in for ernie_10b()/gpt_2p6b(); the flags are the point
    cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=4,
                    num_heads=4, max_position_embeddings=128,
                    dtype=jnp.float32)
    model = GPTForPretraining(cfg)
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-3, weight_decay=0.01,
        grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0),
        multi_precision=o2)
    mesh = build_mesh(dp=1)
    step, state = build_train_step(
        model, opt, mesh, remat=True, remat_policy="full", loss_chunks=2,
        offload=True, param_dtype=jnp.bfloat16 if o2 else None)

    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (4, 64)), jnp.int32)
    labels = jnp.asarray(rs.randint(0, cfg.vocab_size, (4, 64)),
                         jnp.int32)
    losses = []
    for i in range(steps):
        state, loss = step(state, (ids, labels))
        losses.append(float(loss))
        print(f"step {i}: loss {losses[-1]:.4f}")
    # where the state actually lives
    _, _, opt_state = state
    some = next(n for n in opt_state["slots"])
    kinds = {s: opt_state["slots"][some][s].sharding.memory_kind
             if not isinstance(opt_state["slots"][some][s], tuple)
             else opt_state["slots"][some][s][0].sharding.memory_kind
             for s in opt_state["slots"][some]}
    print("slot residence:", kinds)
    return losses, kinds


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--no-o2", action="store_true")
    args = ap.parse_args()
    main(steps=args.steps, o2=not args.no_o2)
