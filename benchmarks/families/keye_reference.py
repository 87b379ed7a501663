"""Keye-VL-2.0-30B-A3B's text decoder, plain: float32 `jax.numpy`, no
kernel, no cache, no sharding. One chip's share of an eight-chip layer.

Follows the published `config.json` (Kwai-Keye/Keye-VL-2.0-30B-A3B; key
names of Qwen3-MoE plus `sa_config`): token embedding, `num_hidden_layers`
identical pre-RMSNorm layers, a final RMSNorm, an untied output head,
mean cross-entropy over the real positions.

  h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h))

Attention: 32 query heads and 4 key/value heads of 128, RMSNorm over each
head's 128 on q and k, rotary positions (theta 1e7, half-split pairs
(i, i + 64)), each query head h reads key/value head h // 8. A learned
indexer (DeepSeek-V3.2-Exp's lightning indexer) picks the keys: 16 index
query heads of 64, one index key head of 64 under a LayerNorm, 16 head
weights, rotary on both, I[t, s] = sum_j wI[t, j] relu(qI[t, j] . kI[s]);
query t attends the `topk` keys s <= t with the largest I[t, s] (ties to
the smaller s; every s <= t where t < topk), one selection for all heads,
and the softmax runs over the selected keys alone.

MoE: r = softmax(u Wr) over all `num_experts` in float32, the
`num_experts_per_tok` largest renormalised over themselves, SiLU-gated
experts of width `moe_intermediate_size`, no shared expert. This chip
holds experts `expert_offset` .. `expert_offset + num_local_experts`; an
assignment to an absent expert keeps its share of the renormalisation and
adds nothing here (its chip would add it). The embedding and the head
hold the first `vocab_size` rows of the published vocabulary; ids, logits
and loss live on that slice.

Departures from the published description, each stated in the
configuration's `assumed`:
  * text only: the three M-RoPE position ids coincide, so `mrope_section`
    changes nothing and positions are 0 .. s-1;
  * q/k RMSNorm per head (the Qwen3-MoE convention);
  * what `sa_config` does not give is DeepSeek-V3.2-Exp's: LayerNorm on
    the index key, rotary over the whole index head dim, positive scale
    factors left out (they move no top-k), `q_chunk_size`/`kv_chunk_size`
    read as tiling, not as part of the result;
  * the selection carries no gradient and there is no indexer loss
    (DeepSeek's KL objective is a training recipe, not in `config`): the
    indexer's leaves move by weight decay alone;
  * no auxiliary routing loss, no capacity: nothing is dropped.

Weights are this file's own draw from the seed: N(0, initializer_range)
for every matrix, the head among them (ISSUE 29's), unit norm scales,
zero LayerNorm bias, and two departures made so that an untrained router
with no balancing loss stays balanced, as a trained one does (sources and
readings in the configuration's `assumed.init`): the embedding at
N(0, `embedding_initializer_range`), and the two matrices that write to
the residual stream, attention output and expert down, at
initializer_range / sqrt(2 x `residual_init_layers`), the published
depth. With 0.02 everywhere every token ranks the experts alike and the
rows that reach the held experts follow the seed and the step. Leaves of
the layers are stacked: "blocks.<kind>" is [L, ...], the experts'
[L, held, ...].
Imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LOSS_CHUNK = 512    # positions whose logits exist at once
QUERY_BLOCK = 512   # query rows whose scores exist at once


def sizes(config: dict) -> dict:
    sa = config["sa_config"]
    return dict(
        L=config["num_hidden_layers"], d=config["hidden_size"],
        h=config["num_attention_heads"], kv=config["num_key_value_heads"],
        hd=config["head_dim"], V=config["vocab_size"],
        E=config["num_experts"], held=config["num_local_experts"],
        off=config.get("expert_offset", 0),
        top=config["num_experts_per_tok"],
        ff=config["moe_intermediate_size"],
        ih=sa["indexer_num_heads"], ihd=sa["indexer_head_dim"],
        topk=sa["topk"], theta=float(config["rope_theta"]),
        eps=config["rms_norm_eps"])


def init_weights(config: dict, key) -> dict:
    z = sizes(config)
    L, d, hd = z["L"], z["d"], z["hd"]
    std = config["initializer_range"]
    emb = config["embedding_initializer_range"]
    out = std / (2.0 * config["residual_init_layers"]) ** 0.5
    ks = iter(jax.random.split(key, 16))

    def n(*shape, std=std):
        return std * jax.random.normal(next(ks), shape, jnp.float32)
    one, zero = jnp.ones, jnp.zeros
    return {
        "embed": n(z["V"], d, std=emb), "head": n(d, z["V"]),
        "norm_f.w": one((d,)),
        "blocks.ln1.w": one((L, d)),
        "blocks.q.w": n(L, d, z["h"] * hd),
        "blocks.k.w": n(L, d, z["kv"] * hd),
        "blocks.v.w": n(L, d, z["kv"] * hd),
        "blocks.q_norm.w": one((L, hd)), "blocks.k_norm.w": one((L, hd)),
        "blocks.o.w": n(L, z["h"] * hd, d, std=out),
        "blocks.idx_q.w": n(L, d, z["ih"] * z["ihd"]),
        "blocks.idx_k.w": n(L, d, z["ihd"]),
        "blocks.idx_k_norm.w": one((L, z["ihd"])),
        "blocks.idx_k_norm.b": zero((L, z["ihd"])),
        "blocks.idx_w.w": n(L, d, z["ih"]),
        "blocks.ln2.w": one((L, d)),
        "blocks.router.w": n(L, d, z["E"]),
        "blocks.experts.gate": n(L, z["held"], d, z["ff"]),
        "blocks.experts.up": n(L, z["held"], d, z["ff"]),
        "blocks.experts.down": n(L, z["held"], z["ff"], d, std=out),
    }


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def rotary(x, theta):
    """x [s, heads, n]: pairs (i, i + n/2) turned by position x
    theta^(-2i/n)."""
    s, _, n = x.shape
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :n // 2], x[..., n // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def select(scores, t0, topk):
    """scores [rows, s] of the queries t0 .. t0 + rows: True at the `topk`
    keys s <= t with the largest score, ties to the smaller s; at every
    s <= t where there are no more than `topk`."""
    rows, s = scores.shape
    t = t0 + jnp.arange(rows)[:, None]
    seen = jnp.arange(s)[None, :] <= t
    if topk >= s:
        return seen
    x = jnp.where(seen, scores, -jnp.inf)
    kth = jnp.sort(x, axis=-1)[:, s - topk][:, None]
    above = x > kth
    ties = (x == kth) & seen
    room = topk - jnp.sum(above, -1, keepdims=True)
    return above | (ties & (jnp.cumsum(ties, -1) <= room))


def attention_row(z, q, k, v, qi, ki, wi):
    """One row: q [s, h, hd], k, v [s, kv, hd], qi [s, ih, ihd], ki [s, ihd],
    wi [s, ih] -> [s, h, hd], a block of queries at a time."""
    s, h, hd = q.shape
    group = h // z["kv"]
    c = min(QUERY_BLOCK, s)

    def block(i):
        t0 = i * c
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, t0, c, 0)  # noqa
        index = jnp.einsum("tj,tjs->ts", take(wi), jax.nn.relu(
            jnp.einsum("tjd,sd->tjs", take(qi), ki)))
        keep = select(jax.lax.stop_gradient(index), t0, z["topk"])
        qb = take(q).reshape(c, z["kv"], group, hd)
        sc = jnp.einsum("tcgd,scd->cgts", qb, k) / jnp.sqrt(float(hd))
        p = jax.nn.softmax(jnp.where(keep[None, None], sc, -jnp.inf), -1)
        return jnp.einsum("cgts,scd->tcgd", p, v).reshape(c, h, hd)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(s // c))
    return out.reshape(s, h, hd)


def attention(z, p, u, mm):
    b, s, _ = u.shape
    eps, th = z["eps"], z["theta"]

    def heads(x, n):
        return x.reshape(b, s, -1, n)
    q = rms_norm(heads(mm(u, p["q.w"]), z["hd"]), p["q_norm.w"], eps)
    k = rms_norm(heads(mm(u, p["k.w"]), z["hd"]), p["k_norm.w"], eps)
    v = heads(mm(u, p["v.w"]), z["hd"])
    qi = heads(mm(u, p["idx_q.w"]), z["ihd"])
    ki = layer_norm(mm(u, p["idx_k.w"]), p["idx_k_norm.w"],
                    p["idx_k_norm.b"], 1e-6)
    wi = mm(u, p["idx_w.w"])

    def row(xs):
        q, k, v, qi, ki, wi = xs
        return attention_row(z, rotary(q, th), rotary(k, th), v,
                             rotary(qi, th), rotary(ki[:, None], th)[:, 0],
                             wi)
    o = jax.lax.map(row, (q, k, v, qi, ki, wi))
    return mm(o.reshape(b, s, -1), p["o.w"])


def routing(z, logits):
    """[.., E] router logits -> [.., E] weights: the softmax over all,
    the `top` largest renormalised over themselves, nought elsewhere
    (ties to the smaller expert id)."""
    r = jax.nn.softmax(logits.astype(jnp.float32), -1)
    _, idx = jax.lax.top_k(r, z["top"])
    chosen = jnp.sum(jax.nn.one_hot(idx, z["E"], dtype=r.dtype), -2)
    g = r * chosen
    return g / jnp.sum(g, -1, keepdims=True)


def moe(z, p, u, mm):
    """The held experts' share of the layer: a dense masked sum."""
    g = routing(z, mm(u, p["router.w"]))
    g = jax.lax.dynamic_slice_in_dim(g, z["off"], z["held"], -1)

    def one(acc, xs):
        gate, up, down, ge = xs
        y = mm(jax.nn.silu(mm(u, gate)) * mm(u, up), down)
        return acc + ge[..., None] * y, None
    y, _ = jax.lax.scan(
        jax.checkpoint(one), jnp.zeros_like(u),
        (p["experts.gate"], p["experts.up"], p["experts.down"],
         jnp.moveaxis(g, -1, 0)))
    return y


def layer(z, p, x, mm):
    h = x + attention(z, p, rms_norm(x, p["ln1.w"], z["eps"]), mm)
    return h + moe(z, p, rms_norm(h, p["ln2.w"], z["eps"]), mm)


def hidden(config: dict, w: dict, ids, mm):
    z = sizes(config)
    blocks = {k[7:]: a for k, a in w.items() if k.startswith("blocks.")}
    x, _ = jax.lax.scan(
        jax.checkpoint(lambda x, p: (layer(z, p, x, mm), None)),
        w["embed"][ids], blocks)
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


def selected_pairs(lengths, topk: int) -> float:
    """(query, key) pairs the selected attention needs: query t of a
    sequence reads min(t + 1, topk) keys."""
    total = 0.0
    for n in lengths:
        n, k = int(n), min(int(n), topk)
        total += k * (k + 1) / 2 + (n - k) * topk
    return total


def counts(config: dict, stats: dict) -> dict:
    """Operations and bytes a step NEEDS, from the shapes: weight products
    at 6 x parameters x rows (2 forward, 4 backward), the held experts on
    `rows_held`, the rows a BALANCED router sends here (this chip's share
    of the tokens' `top` assignments), attention on the selected pairs
    only, the indexer's scores on the causal pairs. No recomputation
    counts. The rows really routed are the data's and no count from
    shapes has them: where the router is off balance, `experts` and its
    tenth of `step_flops` are off with it, so no share of a roofline is
    taken from `experts` in a cell whose router is not kept balanced."""
    z = sizes(config)
    L, d, h, kv, hd = z["L"], z["d"], z["h"], z["kv"], z["hd"]
    tokens, rows = stats["tokens"], stats["rows"]
    pairs = selected_pairs(stats["lengths"], z["topk"])
    causal = sum(int(n) * (int(n) + 1) / 2 for n in stats["lengths"])
    # q, o, k, v and the router carry a gradient (6 x parameters x rows);
    # the indexer's projections run forward only (2 x): no gradient
    # reaches them
    dense = 2 * d * h * hd + 2 * d * kv * hd + d * z["E"]
    index_proj = d * z["ih"] * z["ihd"] + d * z["ihd"] + d * z["ih"]
    expert = 3 * d * z["ff"]
    rows_held = tokens * z["top"] * z["held"] / z["E"]
    sel_flops = L * 3.5 * 4 * h * hd * pairs
    # q, o forward; q, o, do, dq backward at h heads; k, v forward; k, v,
    # dk, dv backward at kv heads (bf16); the int8 selection once forward
    # and twice backward (dq, and dk/dv's transposed copy)
    sel_bytes = L * ((6 * h + 6 * kv) * rows * hd * 2
                     + 3 * sum(int(n) ** 2 for n in stats["lengths"]))
    # index scores: forward only (no gradient), 2 flops a multiply-add
    index_flops = L * 2 * z["ih"] * z["ihd"] * causal
    experts_flops = L * 6.0 * expert * rows_held
    # expert weights read forward and backward and their gradient
    # written (bf16 operands), rows in and out at d and ff
    experts_bytes = L * (3 * z["held"] * expert * 2
                         + rows_held * (4 * d + 6 * z["ff"]) * 2)
    return {
        "step_flops": (6.0 * L * dense * tokens + experts_flops
                       + 6.0 * d * z["V"] * tokens + sel_flops
                       + 2.0 * L * index_proj * tokens + index_flops),
        "selected_attention": {"flops": sel_flops,
                               "bytes": float(sel_bytes)},
        "experts": {"flops": experts_flops, "bytes": float(experts_bytes)},
        "index_scores": {"flops": float(index_flops),
                         "bytes": float(L * causal * 2 * 4)},
        "rows_held": rows_held,
    }
