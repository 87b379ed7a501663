"""Trinity-Mini (`model_type: afmoe`), plain: float32 `jax.numpy`, no
kernel, no cache, no sharding. One chip's share of an eight-chip layer.

Follows the published `config.json` and the AFMoE modeling code published
with the model (d = `hidden_size`, h query heads over g key/value heads of
`head_dim`, no biases, eps `rms_norm_eps`): token embedding times
sqrt(d) (`mup_enabled`), the layers the configuration holds, a final
RMSNorm, an untied head, mean cross-entropy. Each layer norms both
branches on the way in and on the way out:

  h = x + RMSNorm(Attn(RMSNorm(x)));  y = h + RMSNorm(F_l(RMSNorm(h)))

F_l is a SiLU-gated MLP of `intermediate_size` in the first
`num_dense_layers` layers held and the expert layer in the others.

Attention: q = u Wq as [s, h, hd], k = u Wk and v = u Wv as [s, g, hd];
RMSNorm over each head of q and of k; on a `sliding_attention` layer
rotary positions (half-split pairs, theta `rope_theta`) on q and k, on a
`full_attention` layer none; query head i reads key/value head i // (h /
g); softmax in float32 of q kT / sqrt(hd) under the causal mask and, on a
sliding layer, the band: query p reads keys p - `sliding_window` + 1 ..
p, an explicit mask; o = P v, times sigmoid(u Wgate), then o Wo.

Expert layer: s = sigmoid(u Wr) in float32 over all
`published.num_experts`; the choice is top_k(s + expert_bias) (`n_group`
= `topk_group` = 1); the weights are s gathered at the choice, WITHOUT
the bias, over their sum + 1e-20 (`route_norm`), times `route_scale`;
y = sum_k w_k E_k(u) + Shared(u), E a SiLU-gated MLP of
`moe_intermediate_size`, Shared one of `num_shared_experts` x that width.
This chip holds experts `expert_offset` .. `expert_offset + num_experts`;
an assignment to an absent expert keeps its share of the renormalisation
and adds nothing here (its chip would add it); the shared expert is
whole. The embedding and the head hold the first `vocab_size` rows of the
published vocabulary.

Departures, each stated in the configuration's `assumed`: the choice bias
is drawn from the seed (NOT at zero), takes no gradient and is not moved
by the load (`load_balance_coeff` is not applied); no balancing loss;
positions 0 .. s-1.

The layers held are `layer_types[first_layer : first_layer +
num_hidden_layers]` of the published list. Layer 0 held (the dense one)
keeps outer leaves ("dense.<kind>"); the expert layers' are stacked:
"blocks.<kind>" is [L - 1, ...], the experts' [L - 1, held, ...], and
which of them slide is a static mask beside them. Imports nothing of the
program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LOSS_CHUNK = 512    # positions whose logits exist at once
QUERY_BLOCK = 64    # query rows whose scores exist at once (at 128 the
#                     step is refused by 198 MB at the cell's size)
SLIDING = "sliding_attention"


def layer_types(config: dict) -> list:
    """The kinds of the layers held, in order: the published list's slice
    from `first_layer`."""
    first = config.get("first_layer", 0)
    return config["layer_types"][first:first + config["num_hidden_layers"]]


def sizes(config: dict) -> dict:
    types = layer_types(config)
    return dict(
        L=config["num_hidden_layers"], dense=config["num_dense_layers"],
        d=config["hidden_size"], h=config["num_attention_heads"],
        g=config["num_key_value_heads"], hd=config["head_dim"],
        ffn=config["intermediate_size"], V=config["vocab_size"],
        E=config["published"]["num_experts"], held=config["num_experts"],
        off=config.get("expert_offset", 0),
        top=config["num_experts_per_tok"],
        ff=config["moe_intermediate_size"],
        shared=config["num_shared_experts"] * config["moe_intermediate_size"],
        scaling=config["route_scale"], norm_topk=config["route_norm"],
        theta=float(config["rope_theta"]), eps=config["rms_norm_eps"],
        window=config["sliding_window"], mup=config["mup_enabled"],
        sliding=[t == SLIDING for t in types])


def init_weights(config: dict, key) -> dict:
    z = sizes(config)
    if z["dense"] != 1:
        raise ValueError("this file lays out ONE leading dense layer")
    n_moe, d, h, g, hd = z["L"] - 1, z["d"], z["h"], z["g"], z["hd"]
    std = config["initializer_range"]
    ks = iter(jax.random.split(key, 32))

    def n(*shape, std=std):
        return std * jax.random.normal(next(ks), shape, jnp.float32)

    def attention(pre, lead):
        ones = {pre + c: jnp.ones(lead + (w,)) for c, w in (
            ("ln_in.w", d), ("q_norm.w", hd), ("k_norm.w", hd),
            ("ln_attn_out.w", d), ("ln_mlp_in.w", d), ("ln_mlp_out.w", d))}
        return {**ones,
                pre + "q.w": n(*lead, d, h * hd),
                pre + "k.w": n(*lead, d, g * hd),
                pre + "v.w": n(*lead, d, g * hd),
                pre + "gate.w": n(*lead, d, h * hd),
                pre + "o.w": n(*lead, h * hd, d)}
    w = {"embed": n(z["V"], d, std=config["embedding_initializer_range"]),
         "head": n(d, z["V"]),
         "norm_f.w": jnp.ones((d,))}
    w.update(attention("dense.", ()))
    w.update({"dense.mlp.gate": n(d, z["ffn"]), "dense.mlp.up": n(d, z["ffn"]),
              "dense.mlp.down": n(z["ffn"], d)})
    w.update(attention("blocks.", (n_moe,)))
    w.update({
        "blocks.router.w": n(n_moe, d, z["E"]),
        "blocks.router.bias": n(n_moe, z["E"],
                                std=config["choice_bias_range"]),
        "blocks.experts.gate": n(n_moe, z["held"], d, z["ff"]),
        "blocks.experts.up": n(n_moe, z["held"], d, z["ff"]),
        "blocks.experts.down": n(n_moe, z["held"], z["ff"], d),
        "blocks.shared.gate": n(n_moe, d, z["shared"]),
        "blocks.shared.up": n(n_moe, d, z["shared"]),
        "blocks.shared.down": n(n_moe, z["shared"], d),
    })
    return w


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def rotary(x, theta):
    """Half-split rotary positions 0 .. s-1 on x [s, heads, n]."""
    s, _, n = x.shape
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], -1)[:, None, :]
    half = jnp.concatenate([-x[..., n // 2:], x[..., :n // 2]], -1)
    return x * jnp.cos(emb) + half * jnp.sin(emb)


def attention_row(z, q, k, v, reach):
    """One row: q [s, h, hd], k and v [s, g, hd] -> [s, h, hd], a block
    of queries at a time; the softmax in float32. `reach`: the keys a
    query reads, its own and those before it (the window on a sliding
    layer, the row's length on a full one)."""
    s, h, hd = q.shape
    g = z["g"]
    c = min(QUERY_BLOCK, s)
    # query head i as (i // (h / g), i % (h / g)): the key/value heads are
    # read by their groups, not repeated to every query head
    q = q.reshape(s, g, h // g, hd)

    def block(i):
        t0 = i * c
        qb = jax.lax.dynamic_slice_in_dim(q, t0, c, 0)
        sc = jnp.einsum("tgjd,sgd->gjts", qb, k) / hd ** 0.5
        # the causal band as a [c, s] term added to every head's scores
        back = t0 + jnp.arange(c)[:, None] - jnp.arange(s)[None, :]
        seen = (back >= 0) & (back < reach)
        p = jax.nn.softmax(sc + jnp.where(seen, 0.0, -jnp.inf), -1)
        return jnp.einsum("gjts,sgd->tgjd", p, v)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(s // c))
    return out.reshape(s, h, hd)


def attention(z, p, u, mm, sliding):
    """`sliding`: a traced bool, this layer's kind."""
    b, s, _ = u.shape
    h, g, hd = z["h"], z["g"], z["hd"]
    q = rms_norm(mm(u, p["q.w"]).reshape(b, s, h, hd), p["q_norm.w"],
                 z["eps"])
    k = rms_norm(mm(u, p["k.w"]).reshape(b, s, g, hd), p["k_norm.w"],
                 z["eps"])
    v = mm(u, p["v.w"]).reshape(b, s, g, hd)
    reach = jnp.where(sliding, z["window"], s)

    def row(xs):
        q, k, v = xs
        # a branch, not a select: the backward of a replayed layer then
        # holds one q and one k of the row, not both kinds of each
        q, k = jax.lax.cond(
            sliding, lambda q, k: (rotary(q, z["theta"]),
                                   rotary(k, z["theta"])),
            lambda q, k: (q, k), q, k)
        return attention_row(z, q, k, v, reach)
    o = jax.lax.map(row, (q, k, v)).reshape(b, s, h * hd)
    return mm(o * jax.nn.sigmoid(mm(u, p["gate.w"])), p["o.w"])


def routing(z, logits, bias):
    """[.., E] router logits -> [.., E] weights (nought at the experts
    not chosen): sigmoid scores, the choice by score + bias, the weights
    the unbiased scores over their sum + 1e-20, times the route scale."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), z["top"])
    w = jnp.take_along_axis(scores, idx, -1)
    if z["norm_topk"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * z["scaling"]
    return jnp.sum(jax.nn.one_hot(idx, z["E"], dtype=w.dtype)
                   * w[..., None], -2)


def gated_mlp(u, gate, up, down, mm):
    return mm(jax.nn.silu(mm(u, gate)) * mm(u, up), down)


def moe(z, p, u, mm):
    """The held experts' share of the layer as a dense masked sum, and
    the shared expert."""
    g = routing(z, mm(u, p["router.w"]), p["router.bias"])
    g = jax.lax.dynamic_slice_in_dim(g, z["off"], z["held"], -1)

    def one(xs):
        gate, up, down, ge = xs
        return ge[..., None] * gated_mlp(u, gate, up, down, mm)
    # the sum is taken outside the checkpoint: inside it, every expert's
    # running sum would be kept for the backward
    y, _ = jax.lax.scan(
        lambda acc, xs: (acc + jax.checkpoint(one)(xs), None),
        jnp.zeros_like(u),
        (p["experts.gate"], p["experts.up"], p["experts.down"],
         jnp.moveaxis(g, -1, 0)))
    return y + gated_mlp(u, p["shared.gate"], p["shared.up"],
                         p["shared.down"], mm)


def layer(z, p, x, mm, dense: bool, sliding):
    """Each half under a checkpoint of its own: the backward of one half
    then holds that half's activations and not the other's as well."""
    def attn_half(p, x):
        a = attention(z, p, rms_norm(x, p["ln_in.w"], z["eps"]), mm, sliding)
        return x + rms_norm(a, p["ln_attn_out.w"], z["eps"])

    def mlp_half(p, h):
        u = rms_norm(h, p["ln_mlp_in.w"], z["eps"])
        y = gated_mlp(u, p["mlp.gate"], p["mlp.up"], p["mlp.down"], mm) \
            if dense else moe(z, p, u, mm)
        return h + rms_norm(y, p["ln_mlp_out.w"], z["eps"])
    return jax.checkpoint(mlp_half)(p, jax.checkpoint(attn_half)(p, x))


def _under(w: dict, prefix: str) -> dict:
    return {k[len(prefix):]: a for k, a in w.items() if k.startswith(prefix)}


def hidden(config: dict, w: dict, ids, mm):
    z = sizes(config)
    x = w["embed"][ids]
    if z["mup"]:
        x = x * z["d"] ** 0.5
    x = jax.checkpoint(lambda x, p: layer(z, p, x, mm, True,
                                          z["sliding"][0]))(
        x, _under(w, "dense."))
    x, _ = jax.lax.scan(
        jax.checkpoint(lambda x, ps: (layer(z, ps[0], x, mm, False, ps[1]),
                                      None)),
        x, (_under(w, "blocks."), jnp.asarray(z["sliding"][1:])))
    return rms_norm(x, w["norm_f.w"], z["eps"])


def logits(config: dict, w: dict, ids, mm):
    with jax.default_matmul_precision("highest"):
        return mm(hidden(config, w, ids, mm), w["head"])


def loss(config: dict, w: dict, batch: dict, mm) -> jax.Array:
    with jax.default_matmul_precision("highest"):
        return _loss(config, w, batch, mm)


def _loss(config, w, batch, mm):
    ids, labels = batch["ids"], batch["labels"]
    b, s = ids.shape
    x = hidden(config, w, ids, mm)
    c = min(LOSS_CHUNK, s)
    xs = jnp.moveaxis(x.reshape(b, s // c, c, -1), 1, 0)
    ls = jnp.moveaxis(labels.reshape(b, s // c, c), 1, 0)

    def chunk(tot, xl):
        xc, lab = xl
        lg = mm(xc, w["head"])
        picked = jnp.take_along_axis(
            lg, jnp.maximum(lab, 0)[..., None], -1)[..., 0]
        nll = jax.nn.logsumexp(lg, -1) - picked
        return tot + jnp.sum(jnp.where(lab >= 0, nll, 0.0)), None

    tot, _ = jax.lax.scan(jax.checkpoint(chunk), jnp.zeros(()), (xs, ls))
    return tot / jnp.maximum(jnp.sum(labels >= 0), 1)


def band_pairs(lengths, window: int) -> float:
    """(query, key) pairs a causal band of `window` keys needs over rows
    of these lengths: min(p + 1, window) keys at position p."""
    def one(n):
        w = min(int(n), window)
        return w * (w + 1) / 2 + (int(n) - w) * w
    return float(sum(one(n) for n in lengths))


def counts(config: dict, stats: dict) -> dict:
    """Operations and bytes a step NEEDS, from the shapes: weight products
    at 6 x parameters x rows (2 forward, 4 backward), the held experts on
    `rows_held`, the rows a BALANCED router sends here (this chip's share
    of the tokens' `top` assignments), attention 3.5 x 4 x h x hd flops a
    (query, key) pair a layer (forward 2 x hd for the scores and 2 x hd
    for the values a head; backward 2.5 x that) over the pairs the layer
    needs: the band's on a sliding layer (`window_attention`), the causal
    triangle's on a full one (`full_attention`), the head on the slice.
    No recomputation counts, nor a pair outside the band. The bytes of
    each: q, o, dO, dq at h heads and k, v, dk, dv at g, each once
    forward and once as the backward reads or writes it (bf16)."""
    z = sizes(config)
    L, d, h, g, hd = z["L"], z["d"], z["h"], z["g"], z["hd"]
    tokens, rows = stats["tokens"], stats["rows"]
    n_win = sum(z["sliding"])
    n_full = L - n_win
    causal = sum(int(n) * (int(n) + 1) / 2 for n in stats["lengths"])
    band = band_pairs(stats["lengths"], z["window"])
    attn = 2 * d * h * hd + 2 * d * g * hd + h * hd * d
    n_moe = L - z["dense"]
    expert = 3 * d * z["ff"]
    rows_held = tokens * z["top"] * z["held"] / z["E"]
    dense = (L * attn + z["dense"] * 3 * d * z["ffn"]
             + n_moe * (d * z["E"] + 3 * d * z["shared"]))
    per_pair = 3.5 * 4 * h * hd
    # forward reads q, k, v and writes o; backward reads q, k, v, o, dO
    # and writes dq, dk, dv
    per_row = 2 * (h * hd * 4 + g * hd * 4)
    experts_flops = n_moe * 6.0 * expert * rows_held
    experts_bytes = n_moe * (3 * z["held"] * expert * 2
                             + rows_held * (4 * d + 6 * z["ff"]) * 2)
    window_flops = n_win * per_pair * band
    full_flops = n_full * per_pair * causal
    return {
        "step_flops": (6.0 * dense * tokens + experts_flops
                       + 6.0 * d * z["V"] * tokens + window_flops
                       + full_flops),
        "window_attention": {"flops": window_flops,
                             "bytes": float(n_win * rows * per_row)},
        "full_attention": {"flops": full_flops,
                           "bytes": float(n_full * rows * per_row)},
        "experts": {"flops": experts_flops, "bytes": float(experts_bytes)},
        "rows_held": rows_held,
    }
