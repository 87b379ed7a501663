"""The GPT family through the program's own entry: `GPTForPretraining` +
`build_train_step`, exactly as `chip_smoke._build_gpt_step` and the
README call it. The benchmark's weights take the place of the program's
own draw through `set_state_dict`, as a checkpoint would, so the
reference can start from the same numbers without taking anything the
program made.
"""
from __future__ import annotations

import types

# reference leaf -> the program's parameter name (outer) / block leaf
OUTER = {
    "wte": "gpt.embeddings.word_embeddings.weight",
    "wpe": "gpt.embeddings.position_embeddings.weight",
    "ln_f.w": "gpt.ln_f.weight", "ln_f.b": "gpt.ln_f.bias",
}
BLOCK = {
    "ln1.w": "ln1.weight", "ln1.b": "ln1.bias",
    "qkv.w": "qkv.weight", "qkv.b": "qkv.bias",
    "proj.w": "out_proj.weight", "proj.b": "out_proj.bias",
    "ln2.w": "ln2.weight", "ln2.b": "ln2.bias",
    "fc1.w": "fc1.weight", "fc1.b": "fc1.bias",
    "fc2.w": "fc2.weight", "fc2.b": "fc2.bias",
}


def build(config: dict, mix: dict, weights: dict, devices: list):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    import paddle_tpu as pt
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models import GPTForPretraining, build_train_step
    from paddle_tpu.models.gpt import GPTConfig
    from benchmarks.harness import weights as wt

    run, o = config["step"], config["optimizer"]
    cfg = GPTConfig(
        vocab_size=config["padded_vocab_size"],
        hidden_size=config["n_embd"], num_layers=config["n_layer"],
        num_heads=config["n_head"], ffn_hidden=config.get("n_inner"),
        max_position_embeddings=config["n_positions"],
        dtype=jnp.dtype(run["compute_dtype"]),
        initializer_range=config["initializer_range"])
    mesh = build_mesh(devices=devices, **run["mesh"])
    model = GPTForPretraining(cfg)
    wt.load(model, weights, wt.layer_names(OUTER, BLOCK, cfg.num_layers,
                                           "gpt.layers"))
    del weights
    clip = o.get("clip_global_norm")
    opt = pt.optimizer.AdamW(
        learning_rate=o["lr"], weight_decay=o["weight_decay"],
        beta1=o["beta1"], beta2=o["beta2"], epsilon=o["epsilon"],
        grad_clip=pt.nn.ClipGradByGlobalNorm(clip) if clip else None)
    step, state = build_train_step(
        model, opt, mesh, remat=run["remat"],
        remat_policy=run["remat_policy"], loss_chunks=run["loss_chunks"],
        **run.get("build_train_step", {}))
    rows = NamedSharding(mesh, P(("data", "sharding"), None))

    def put(batch: dict):
        return jax.device_put((batch["ids"], batch["labels"]), rows)

    def params(state) -> dict:
        out = {c: state[0][n] for c, n in OUTER.items()}
        out.update({"blocks." + c: state[1][n] for c, n in BLOCK.items()})
        return out

    def moment1(state) -> dict:
        slots = state[2]["slots"]
        out = {c: slots[n]["moment1"] for c, n in OUTER.items()}
        out.update({"blocks." + c: slots["blocks." + n]["moment1"]
                    for c, n in BLOCK.items()})
        return out

    return types.SimpleNamespace(step=step, state=state, put=put,
                                 params=params, moment1=moment1)
