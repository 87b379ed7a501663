"""BERT pretraining, plain: float32 `jax.numpy`, no kernel, no sharding.

Follows "BERT: Pre-training of Deep Bidirectional Transformers" (Devlin
et al., 2019) and the published `config.json`: token + position +
segment embeddings under a LayerNorm, post-LN encoder blocks (fused qkv
-> softmax attention over the real keys -> projection, residual, LN; fc
-> gelu -> projection, residual, LN), a tanh pooler on the first
position; the masked-LM head (dense -> gelu -> LN -> decoder tied to the
token embedding, plus a bias) on the masked positions only, and the
next-sentence classifier on the pooled output. Loss: mean masked-LM
cross-entropy over the real predictions + mean NSP cross-entropy.
`hidden_act` is the published erf gelu throughout.

Weights are this file's own draw from the seed: N(0, initializer_range)
matrices and embeddings, zero biases, unit LN scales. Leaves of the
blocks are stacked over layers. Imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.families.gpt_reference import attention, layer_norm
from benchmarks.harness import flops


def sizes(config: dict) -> tuple:
    return (config["num_hidden_layers"], config["hidden_size"],
            config["num_attention_heads"], config["intermediate_size"],
            config["padded_vocab_size"],
            config["max_position_embeddings"], config["type_vocab_size"])


def init_weights(config: dict, key) -> dict:
    L, d, _, ffn, V, S, T = sizes(config)
    std = config["initializer_range"]
    ks = jax.random.split(key, 10)

    def n(k, *shape):
        return std * jax.random.normal(k, shape, jnp.float32)
    z, o = jnp.zeros, jnp.ones
    return {
        "wte": n(ks[0], V, d), "wpe": n(ks[1], S, d), "wtt": n(ks[2], T, d),
        "emb_ln.w": o((d,)), "emb_ln.b": z((d,)),
        "blocks.qkv.w": n(ks[3], L, d, 3 * d),
        "blocks.qkv.b": z((L, 3 * d)),
        "blocks.proj.w": n(ks[4], L, d, d), "blocks.proj.b": z((L, d)),
        "blocks.ln1.w": o((L, d)), "blocks.ln1.b": z((L, d)),
        "blocks.fc1.w": n(ks[5], L, d, ffn), "blocks.fc1.b": z((L, ffn)),
        "blocks.fc2.w": n(ks[6], L, ffn, d), "blocks.fc2.b": z((L, d)),
        "blocks.ln2.w": o((L, d)), "blocks.ln2.b": z((L, d)),
        "pooler.w": n(ks[7], d, d), "pooler.b": z((d,)),
        "mlm.transform.w": n(ks[8], d, d), "mlm.transform.b": z((d,)),
        "mlm.ln.w": o((d,)), "mlm.ln.b": z((d,)), "mlm.bias": z((V,)),
        "nsp.w": n(ks[9], d, 2), "nsp.b": z((2,)),
    }


def gelu(x):
    return 0.5 * x * (1 + jax.lax.erf(x / jnp.sqrt(2.0)))


def cross_entropy(logits, labels):
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jax.nn.logsumexp(logits, -1) - picked


def loss(config: dict, w: dict, batch: dict, mm) -> jax.Array:
    with jax.default_matmul_precision("highest"):
        return _loss(config, w, batch, mm)


def _loss(config, w, batch, mm):
    L, d, h, _, _, _, _ = sizes(config)
    eps = config["layer_norm_eps"]
    ids = batch["ids"]
    b, s = ids.shape
    x = (w["wte"][ids] + w["wpe"][jnp.arange(s)][None]
         + w["wtt"][batch["types"]])
    x = layer_norm(x, w["emb_ln.w"], w["emb_ln.b"], eps)
    keep = batch["valid"][:, None, None, :]
    blocks = {k[7:]: a for k, a in w.items() if k.startswith("blocks.")}

    def block(x, p):
        y = mm(x, p["qkv.w"]) + p["qkv.b"]
        q, k, v = (y[..., i * d:(i + 1) * d].reshape(b, s, h, d // h)
                   for i in range(3))
        a = attention(q, k, v, keep).reshape(b, s, d)
        x = layer_norm(x + mm(a, p["proj.w"]) + p["proj.b"],
                       p["ln1.w"], p["ln1.b"], eps)
        y = mm(gelu(mm(x, p["fc1.w"]) + p["fc1.b"]), p["fc2.w"])
        return layer_norm(x + y + p["fc2.b"], p["ln2.w"], p["ln2.b"],
                          eps), None

    x, _ = jax.lax.scan(jax.checkpoint(block), x, blocks)
    pooled = jnp.tanh(mm(x[:, 0], w["pooler.w"]) + w["pooler.b"])

    picked = jnp.take_along_axis(x, batch["positions"][..., None], 1)
    t = gelu(mm(picked, w["mlm.transform.w"]) + w["mlm.transform.b"])
    t = layer_norm(t, w["mlm.ln.w"], w["mlm.ln.b"], eps)
    logits = mm(t, w["wte"].T) + w["mlm.bias"]
    labels = batch["mlm_labels"]
    real = labels >= 0
    nll = cross_entropy(logits, jnp.maximum(labels, 0))
    mlm = jnp.sum(jnp.where(real, nll, 0.0)) / jnp.maximum(jnp.sum(real), 1)
    nsp = cross_entropy(mm(pooled, w["nsp.w"]) + w["nsp.b"], batch["nsp"])
    return mlm + jnp.mean(nsp)


def counts(config: dict, stats: dict) -> dict:
    """Operations a step needs for the REAL tokens (attention over
    sum len^2, the vocabulary head on the real predictions only) and
    what its attention kernels need."""
    L, d, _, ffn, V, _, _ = sizes(config)
    pairs = flops.attention_pairs(stats["lengths"], causal=False)
    n_seq = len(stats["lengths"])
    return {
        "step_flops": flops.transformer_train_flops(
            n_layer=L, d=d, ffn=ffn, tokens=stats["tokens"], pairs=pairs,
            head_rows=stats["predictions"], head_params=V * d + d * d,
            extra_params_rows=(d * d + 2 * d) * n_seq),
        "attention": flops.flash_attention_cost(
            n_layer=L, d=d, rows=stats["rows"], pairs=pairs),
    }
