"""The Keye family through the program's own entry: `KeyeForCausalLM` +
`build_train_step`, the builder GPT goes through. The benchmark's weights
take the place of the program's own draw through `set_state_dict`, as a
checkpoint would, so the reference can start from the same numbers
without taking anything the program made.
"""
from __future__ import annotations

import types

# reference leaf -> the program's parameter name (outer) / block leaf
OUTER = {
    "embed": "model.embed_tokens.weight",
    "head": "lm_head",
    "norm_f.w": "model.norm.weight",
}
BLOCK = {
    "ln1.w": "ln1.weight",
    "q.w": "attn.q_proj.weight", "k.w": "attn.k_proj.weight",
    "v.w": "attn.v_proj.weight",
    "q_norm.w": "attn.q_norm.weight", "k_norm.w": "attn.k_norm.weight",
    "o.w": "attn.o_proj.weight",
    "idx_q.w": "attn.index_q.weight", "idx_k.w": "attn.index_k.weight",
    "idx_k_norm.w": "attn.index_k_norm.weight",
    "idx_k_norm.b": "attn.index_k_norm.bias",
    "idx_w.w": "attn.index_w.weight",
    "ln2.w": "ln2.weight",
    "router.w": "moe.gate_weight",
    "experts.gate": "moe.w_gate", "experts.up": "moe.w_up",
    "experts.down": "moe.w_down",
}


def program_config(config: dict):
    """The published keys and the chip's share as the program's config."""
    import jax.numpy as jnp
    from paddle_tpu.models.keye import KeyeConfig
    sa = config["sa_config"]
    return KeyeConfig(
        vocab_size=config["published"]["vocab_size"],
        vocab_held=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"],
        experts_held=config["num_local_experts"],
        expert_offset=config.get("expert_offset", 0),
        indexer_num_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"], indexer_topk=sa["topk"],
        dtype=jnp.dtype(config["step"]["compute_dtype"]),
        initializer_range=config["initializer_range"])


def build(config: dict, mix: dict, weights: dict, devices: list):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    import paddle_tpu as pt
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models import KeyeForCausalLM, build_train_step
    from benchmarks.harness import weights as wt

    run, o = config["step"], config["optimizer"]
    cfg = program_config(config)
    mesh = build_mesh(devices=devices, **run["mesh"])
    model = KeyeForCausalLM(cfg)
    wt.load(model, weights, wt.layer_names(OUTER, BLOCK, cfg.num_layers,
                                           "model.layers"))
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
