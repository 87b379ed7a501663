"""The BERT family through the path `bench.bench_bert` takes:
`BertForPretraining` under `functional_call`, `jax.value_and_grad`,
`AdamW.apply`, one `jax.jit` with the state donated. Every array of the
batch is an argument of the step (a closed-over array would be baked
into the program as a constant). The benchmark's weights take the place
of the program's own draw through `set_state_dict`, as a checkpoint
would.
"""
from __future__ import annotations

import functools
import types

OUTER = {
    "wte": "bert.embeddings.word_embeddings.weight",
    "wpe": "bert.embeddings.position_embeddings.weight",
    "wtt": "bert.embeddings.token_type_embeddings.weight",
    "emb_ln.w": "bert.embeddings.layer_norm.weight",
    "emb_ln.b": "bert.embeddings.layer_norm.bias",
    "pooler.w": "bert.pooler.dense.weight",
    "pooler.b": "bert.pooler.dense.bias",
    "mlm.transform.w": "cls.transform.weight",
    "mlm.transform.b": "cls.transform.bias",
    "mlm.ln.w": "cls.layer_norm.weight", "mlm.ln.b": "cls.layer_norm.bias",
    "mlm.bias": "cls.decoder_bias",
    "nsp.w": "cls.seq_relationship.weight",
    "nsp.b": "cls.seq_relationship.bias",
}
BLOCK = {
    "qkv.w": "qkv.weight", "qkv.b": "qkv.bias",
    "proj.w": "out_proj.weight", "proj.b": "out_proj.bias",
    "ln1.w": "ln1.weight", "ln1.b": "ln1.bias",
    "fc1.w": "fc1.weight", "fc1.b": "fc1.bias",
    "fc2.w": "fc2.weight", "fc2.b": "fc2.bias",
    "ln2.w": "ln2.weight", "ln2.b": "ln2.bias",
}


def build(config: dict, mix: dict, weights: dict, devices: list):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    from paddle_tpu.nn.layer import functional_call, trainable_state
    from paddle_tpu.nn.layer_conv_norm import LayerNorm
    from benchmarks.harness import weights as wt

    run, o = config["step"], config["optimizer"]
    if len(devices) != 1:
        raise ValueError("the BERT step is one jitted program on one chip")
    cfg = BertConfig(
        vocab_size=config["padded_vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        ffn_hidden=config["intermediate_size"],
        max_position_embeddings=config["max_position_embeddings"],
        type_vocab_size=config["type_vocab_size"],
        dtype=jnp.dtype(run["compute_dtype"]),
        initializer_range=config["initializer_range"])
    model = BertForPretraining(cfg)
    # BertConfig carries no epsilon; the layers do
    for layer in model.sublayers():
        if isinstance(layer, LayerNorm):
            layer.epsilon = config["layer_norm_eps"]
    clip = o.get("clip_global_norm")
    opt = pt.optimizer.AdamW(
        learning_rate=o["lr"], weight_decay=o["weight_decay"],
        beta1=o["beta1"], beta2=o["beta2"], epsilon=o["epsilon"],
        grad_clip=pt.nn.ClipGradByGlobalNorm(clip) if clip else None)
    names = wt.layer_names(OUTER, BLOCK, cfg.num_layers, "bert.encoder")
    wt.load(model, weights, names)
    del weights
    device = devices[0]
    params = trainable_state(model)
    # committed to the device from the start, as every later state is:
    # an uncommitted first state makes the second call another program
    state = jax.device_put((params, opt.init_state(params)), device)
    del params

    def loss_fn(params, b):
        out, _ = functional_call(
            model, params, b["ids"], b["types"], b["valid"],
            b["mlm_labels"], b["nsp"], masked_positions=b["positions"])
        return out

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, batch):
        params, opt_state = state
        loss, g = jax.value_and_grad(loss_fn)(params, batch)
        new_p, new_s = opt.apply(params, g, opt_state)
        return (new_p, new_s), loss

    def put(batch: dict):
        return jax.device_put(batch, device)

    def leaves(state) -> dict:
        return {c: state[0][n] for c, n in names.items()}

    def moment1(state) -> dict:
        slots = state[1]["slots"]
        return {c: slots[n]["moment1"] for c, n in names.items()}

    return types.SimpleNamespace(step=step, state=state, put=put,
                                 params=leaves, moment1=moment1)
