"""GPT-2, plain: float32 `jax.numpy`, no kernel, no cache, no sharding.

Follows "Language Models are Unsupervised Multitask Learners" (Radford
et al., 2019) and the published `config.json`: learned token and position
embeddings, pre-LN blocks (LN -> fused qkv -> causal softmax attention
-> projection, LN -> fc -> gelu_new -> projection, both with residuals),
a final LN, the output head tied to the token embedding, mean
cross-entropy over the real positions. The embedding table holds
`padded_vocab_size` rows (the config's `assumed`); ids and labels come
from the first `vocab_size`.

Weights are this file's own draw from the seed: N(0, initializer_range)
matrices and embeddings, zero biases, unit LN scales (GPT-2's init
without the residual-depth scaling, which the config states under
`assumed`). Leaves of the blocks are stacked over layers: "blocks.<kind>"
is [n_layer, ...]. Imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.harness import flops

LOSS_CHUNK = 128  # positions whose logits exist at once


def sizes(config: dict) -> tuple:
    d = config["n_embd"]
    return (config["n_layer"], d, config["n_head"],
            config.get("n_inner") or 4 * d, config["padded_vocab_size"],
            config["n_positions"])


def init_weights(config: dict, key) -> dict:
    L, d, _, ffn, V, S = sizes(config)
    std = config["initializer_range"]
    ks = jax.random.split(key, 6)

    def n(k, *shape):
        return std * jax.random.normal(k, shape, jnp.float32)
    z, o = jnp.zeros, jnp.ones
    return {
        "wte": n(ks[0], V, d), "wpe": n(ks[1], S, d),
        "blocks.ln1.w": o((L, d)), "blocks.ln1.b": z((L, d)),
        "blocks.qkv.w": n(ks[2], L, d, 3 * d),
        "blocks.qkv.b": z((L, 3 * d)),
        "blocks.proj.w": n(ks[3], L, d, d), "blocks.proj.b": z((L, d)),
        "blocks.ln2.w": o((L, d)), "blocks.ln2.b": z((L, d)),
        "blocks.fc1.w": n(ks[4], L, d, ffn), "blocks.fc1.b": z((L, ffn)),
        "blocks.fc2.w": n(ks[5], L, ffn, d), "blocks.fc2.b": z((L, d)),
        "ln_f.w": o((d,)), "ln_f.b": z((d,)),
    }


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def gelu_new(x):
    return 0.5 * x * (1 + jnp.tanh(
        jnp.sqrt(2 / jnp.pi) * (x + 0.044715 * x ** 3)))


def attention(q, k, v, keep):
    """softmax(q k^T / sqrt(hd)) v over [b, s, h, hd]; `keep` [.., q, k]
    broadcasts against the scores and is False where a key is hidden."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(q.shape[-1])
    scores = jnp.where(keep, scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def loss(config: dict, w: dict, batch: dict, mm) -> jax.Array:
    with jax.default_matmul_precision("highest"):
        return _loss(config, w, batch, mm)


def _loss(config, w, batch, mm):
    L, d, h, _, _, _ = sizes(config)
    eps = config["layer_norm_epsilon"]
    ids, labels = batch["ids"], batch["labels"]
    b, s = ids.shape
    x = w["wte"][ids] + w["wpe"][jnp.arange(s)][None]
    causal = jnp.tril(jnp.ones((s, s), bool))[None, None]
    blocks = {k[7:]: a for k, a in w.items() if k.startswith("blocks.")}

    def block(x, p):
        y = mm(layer_norm(x, p["ln1.w"], p["ln1.b"], eps),
               p["qkv.w"]) + p["qkv.b"]
        q, k, v = (y[..., i * d:(i + 1) * d].reshape(b, s, h, d // h)
                   for i in range(3))
        a = attention(q, k, v, causal).reshape(b, s, d)
        x = x + mm(a, p["proj.w"]) + p["proj.b"]
        y = mm(layer_norm(x, p["ln2.w"], p["ln2.b"], eps),
               p["fc1.w"]) + p["fc1.b"]
        return x + mm(gelu_new(y), p["fc2.w"]) + p["fc2.b"], None

    x, _ = jax.lax.scan(jax.checkpoint(block), x, blocks)
    x = layer_norm(x, w["ln_f.w"], w["ln_f.b"], eps)

    # the [b, s, V] logits never exist whole: positions in chunks
    c = min(LOSS_CHUNK, s)
    xs = jnp.moveaxis(x.reshape(b, s // c, c, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(b, s // c, c), 1, 0)
    head = w["wte"].T

    def chunk(tot, xl):
        xc, lab = xl
        logits = mm(xc, head)
        real = lab >= 0
        picked = jnp.take_along_axis(
            logits, jnp.maximum(lab, 0)[..., None], -1)[..., 0]
        nll = jax.nn.logsumexp(logits, -1) - picked
        return tot + jnp.sum(jnp.where(real, nll, 0.0)), None

    tot, _ = jax.lax.scan(jax.checkpoint(chunk), jnp.zeros(()), (xs, ls))
    return tot / jnp.maximum(jnp.sum(labels >= 0), 1)


def counts(config: dict, stats: dict) -> dict:
    """Operations a step needs (causal attention at half) and what its
    attention kernels need, from the shapes."""
    L, d, _, ffn, V, _ = sizes(config)
    pairs = flops.attention_pairs(stats["lengths"], causal=True)
    return {
        "step_flops": flops.transformer_train_flops(
            n_layer=L, d=d, ffn=ffn, tokens=stats["tokens"], pairs=pairs,
            head_rows=stats["tokens"], head_params=V * d),
        "attention": flops.flash_attention_cost(
            n_layer=L, d=d, rows=stats["rows"], pairs=pairs),
    }
