"""The afmoe family through the program's own entry: `AfmoeForCausalLM` +
`build_train_step`, the step builder every family goes through. The
benchmark's weights take the place of the program's own draw through
`set_state_dict`, as a checkpoint would, so the reference can start from
the same numbers without taking anything the program made.

The reference keeps layer 0 (the dense one) as outer leaves "dense.<kind>"
and the expert layers stacked under "blocks."; the program's state keeps
its groups of blocks, runs of one kind (`AfmoeForCausalLM.block_groups`):
"g0.<name>" [1, ...] the dense layer, then one group a run of expert
layers, sliding or full. `params` and `moment1` map one onto the other.
"""
from __future__ import annotations

import types

# the program is imported here, at the top, so that a checkout without it
# fails at once on this family's cells
from paddle_tpu.models.afmoe import AfmoeConfig

from benchmarks.families import afmoe_reference as ref

# reference leaf -> the program's parameter name (outer) / block leaf
OUTER = {
    "embed": "model.embed_tokens.weight",
    "head": "lm_head",
    "norm_f.w": "model.norm.weight",
}
ATTENTION = {
    "ln_in.w": "input_layernorm.weight",
    "q.w": "self_attn.q_proj.weight",
    "k.w": "self_attn.k_proj.weight",
    "v.w": "self_attn.v_proj.weight",
    "gate.w": "self_attn.gate_proj.weight",
    "q_norm.w": "self_attn.q_norm.weight",
    "k_norm.w": "self_attn.k_norm.weight",
    "o.w": "self_attn.o_proj.weight",
    "ln_attn_out.w": "post_attention_layernorm.weight",
    "ln_mlp_in.w": "pre_mlp_layernorm.weight",
    "ln_mlp_out.w": "post_mlp_layernorm.weight",
}
DENSE = {
    **ATTENTION,
    "mlp.gate": "mlp.gate_proj.weight", "mlp.up": "mlp.up_proj.weight",
    "mlp.down": "mlp.down_proj.weight",
}
BLOCK = {
    **ATTENTION,
    "router.w": "mlp.gate_weight", "router.bias": "mlp.choice_bias",
    "experts.gate": "mlp.w_gate", "experts.up": "mlp.w_up",
    "experts.down": "mlp.w_down",
    "shared.gate": "mlp.shared.gate_proj.weight",
    "shared.up": "mlp.shared.up_proj.weight",
    "shared.down": "mlp.shared.down_proj.weight",
}


def program_config(config: dict) -> AfmoeConfig:
    """The published keys and the chip's share as the program's config."""
    import jax.numpy as jnp
    same = ("hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_hidden_layers", "num_dense_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "rms_norm_eps",
            "sliding_window", "global_attn_every_n_layers",
            "num_experts_per_tok", "num_shared_experts", "score_func",
            "route_norm", "route_scale", "n_group", "topk_group",
            "mup_enabled", "initializer_range")
    return AfmoeConfig(
        vocab_size=config["published"]["vocab_size"],
        vocab_held=config["vocab_size"],
        num_experts=config["published"]["num_experts"],
        experts_held=config["num_experts"],
        expert_offset=config.get("expert_offset", 0),
        rope_theta=float(config["rope_theta"]),
        layer_types=tuple(ref.layer_types(config)),
        dtype=jnp.dtype(config["step"]["compute_dtype"]),
        **{k: config[k] for k in same})


def names(n_layer: int) -> dict:
    """Every reference leaf id -> the program's parameter name."""
    prefix = "model.layers"
    out = dict(OUTER)
    out.update({"dense." + c: f"{prefix}.0.{n}" for c, n in DENSE.items()})
    for i in range(n_layer - 1):
        out.update({f"blocks.{i}.{c}": f"{prefix}.{i + 1}.{n}"
                    for c, n in BLOCK.items()})
    return out


def build(config: dict, mix: dict, weights: dict, devices: list):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    import paddle_tpu as pt
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models import AfmoeForCausalLM, build_train_step
    from benchmarks.harness import weights as wt

    run, o = config["step"], config["optimizer"]
    cfg = program_config(config)
    mesh = build_mesh(devices=devices, **run["mesh"])
    model = AfmoeForCausalLM(cfg)
    # the groups after the dense one: (first expert layer, layers) each
    runs = [n for _, n in model.block_groups()][1:]
    wt.load(model, weights, names(cfg.num_hidden_layers))
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

    def as_reference(outer: dict, stacked: dict) -> dict:
        """The program's groups under the reference's names: group 0's
        one block without its leading dim, and each expert layer's leaves
        by layer, "blocks.<i>.<kind>" (a slice of its group's, where a
        concatenation of the groups would copy them)."""
        out = {c: outer[n] for c, n in OUTER.items()}
        out.update({"dense." + c: stacked["g0." + n][0]
                    for c, n in DENSE.items()})
        layer = 0
        for g, count in enumerate(runs, start=1):
            for j in range(count):
                out.update({f"blocks.{layer}.{c}": stacked[f"g{g}.{n}"][j]
                            for c, n in BLOCK.items()})
                layer += 1
        return out

    def params(state) -> dict:
        return as_reference(state[0], state[1])

    def moment1(state) -> dict:
        slots = {n: s["moment1"] for n, s in state[2]["slots"].items()}
        return as_reference(
            slots, {n[len("blocks."):]: v for n, v in slots.items()
                    if n.startswith("blocks.")})

    return types.SimpleNamespace(step=step, state=state, put=put,
                                 params=params, moment1=moment1)
