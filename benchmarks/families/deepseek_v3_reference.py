"""kanana-2-30b-a3b-instruct-2601 (`model_type: deepseek_v3`), plain:
float32 `jax.numpy`, no kernel, no cache, no sharding. One chip's share of
an eight-chip layer.

Follows the published `config.json` and Hugging Face's
`modeling_deepseek_v3.py` step by step (d = `hidden_size`, h heads, no
biases, eps `rms_norm_eps`): token embedding, `num_hidden_layers`
pre-RMSNorm layers, a final RMSNorm, an untied head, mean cross-entropy.

  h = x + Attn(RMSNorm(x));  y = h + F_l(RMSNorm(h))

F_l is a SiLU-gated MLP of `intermediate_size` in the first
`first_k_dense_replace` layers and the expert layer in the others.

Attention (`q_lora_rank` null): q = u Wq as [s, h, nope + rope];
u Wkva as [s, kv_lora_rank + rope] = (c, k_rope), k_rope ONE head;
RMSNorm(c) Wkvb as [s, h, nope + v] = (k_nope, v). Rotary
(`apply_rotary_pos_emb_interleave`): the rope lanes are de-interleaved
([x0, x2, .., x1, x3, ..]), then turned by halves (`rotate_half`) with
cos / sin of position x theta^(-2i/rope); q = cat(q_nope, q_rope),
k = cat(k_nope, k_rope expanded to every head); softmax in float32 of
q kT / sqrt(nope + rope) under the causal mask; o = P v; o Wo.
`rope_scaling` is null: no mscale.

Expert layer (`DeepseekV3TopkRouter` + `DeepseekV3MoE`): s = sigmoid(u Wr)
in float32 over all `published.n_routed_experts`; the choice is
top_k(s + e_score_correction_bias) (`n_group` = `topk_group` = 1: the
group step keeps everything); the weights are s gathered at the choice,
WITHOUT the bias, over their sum + 1e-20 (`norm_topk_prob`), times
`routed_scaling_factor`; y = sum_k w_k E_k(u) + Shared(u), E a SiLU-gated
MLP of `moe_intermediate_size`, Shared one SiLU-gated MLP of
`n_shared_experts` x that width. This chip holds experts `expert_offset`
.. `expert_offset + n_routed_experts`; an assignment to an absent expert
keeps its share of the renormalisation and adds nothing here (its chip
would add it); the shared expert is whole. The embedding and the head
hold the first `vocab_size` rows of the published vocabulary.

Departures, each stated in the configuration's `assumed`: the choice bias
is drawn from the seed (NOT at zero), takes no gradient (the source keeps
it in a buffer and moves it by the load, outside the loss) and is not
moved by the load; no balancing loss; positions 0 .. s-1.

Weights are this file's own draw from the seed (`assumed.init`). Layer
0's leaves are outer leaves ("dense.<kind>"); the expert layers' are
stacked: "blocks.<kind>" is [L - 1, ...], the experts' [L - 1, held, ...].
Imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LOSS_CHUNK = 512    # positions whose logits exist at once
QUERY_BLOCK = 128   # query rows whose scores exist at once (at 256 the
#                     step is refused by 9 MB at the cell's size: PERF.md)


def sizes(config: dict) -> dict:
    return dict(
        L=config["num_hidden_layers"], dense=config["first_k_dense_replace"],
        d=config["hidden_size"], h=config["num_attention_heads"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        vd=config["v_head_dim"], lat=config["kv_lora_rank"],
        ffn=config["intermediate_size"], V=config["vocab_size"],
        E=config["published"]["n_routed_experts"],
        held=config["n_routed_experts"], off=config.get("expert_offset", 0),
        top=config["num_experts_per_tok"],
        ff=config["moe_intermediate_size"],
        shared=config["n_shared_experts"] * config["moe_intermediate_size"],
        scaling=config["routed_scaling_factor"],
        norm_topk=config["norm_topk_prob"],
        theta=float(config["rope_theta"]), eps=config["rms_norm_eps"])


def init_weights(config: dict, key) -> dict:
    z = sizes(config)
    if z["dense"] != 1:
        raise ValueError("this file lays out ONE leading dense layer")
    n_moe, d, h = z["L"] - 1, z["d"], z["h"]
    std = config["initializer_range"]
    out = std / (2.0 * config["residual_init_layers"]) ** 0.5
    ks = iter(jax.random.split(key, 32))

    def n(*shape, std=std):
        return std * jax.random.normal(next(ks), shape, jnp.float32)

    def attention(pre, lead):
        return {
            pre + "ln1.w": jnp.ones(lead + (d,)),
            pre + "q.w": n(*lead, d, h * (z["nope"] + z["rope"])),
            pre + "kv_a.w": n(*lead, d, z["lat"] + z["rope"]),
            pre + "kv_norm.w": jnp.ones(lead + (z["lat"],)),
            pre + "kv_b.w": n(*lead, z["lat"], h * (z["nope"] + z["vd"])),
            pre + "o.w": n(*lead, h * z["vd"], d, std=out),
            pre + "ln2.w": jnp.ones(lead + (d,)),
        }
    w = {"embed": n(z["V"], d, std=config["embedding_initializer_range"]),
         "head": n(d, z["V"]), "norm_f.w": jnp.ones((d,))}
    w.update(attention("dense.", ()))
    w.update({"dense.mlp.gate": n(d, z["ffn"]), "dense.mlp.up": n(d, z["ffn"]),
              "dense.mlp.down": n(z["ffn"], d, std=out)})
    w.update(attention("blocks.", (n_moe,)))
    w.update({
        "blocks.router.w": n(n_moe, d, z["E"]),
        "blocks.router.bias": n(n_moe, z["E"],
                                std=config["choice_bias_range"]),
        "blocks.experts.gate": n(n_moe, z["held"], d, z["ff"]),
        "blocks.experts.up": n(n_moe, z["held"], d, z["ff"]),
        "blocks.experts.down": n(n_moe, z["held"], z["ff"], d, std=out),
        "blocks.shared.gate": n(n_moe, d, z["shared"]),
        "blocks.shared.up": n(n_moe, d, z["shared"]),
        "blocks.shared.down": n(n_moe, z["shared"], d, std=out),
    })
    return w


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def rotate_half(x):
    n = x.shape[-1]
    return jnp.concatenate([-x[..., n // 2:], x[..., :n // 2]], -1)


def rotary_interleave(x, theta):
    """`apply_rotary_pos_emb_interleave` on x [s, heads, n], positions
    0 .. s-1: de-interleave, then rotate by halves."""
    s, heads, n = x.shape
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], -1)[:, None, :]
    x = jnp.swapaxes(x.reshape(s, heads, n // 2, 2), -1, -2).reshape(
        s, heads, n)
    return x * jnp.cos(emb) + rotate_half(x) * jnp.sin(emb)


def attention_row(z, q, k, v):
    """One row: q, k [s, h, nope + rope], v [s, h, vd] -> [s, h, vd], a
    block of queries at a time; the softmax in float32."""
    s = q.shape[0]
    c = min(QUERY_BLOCK, s)
    scale = 1.0 / (z["nope"] + z["rope"]) ** 0.5

    def block(i):
        t0 = i * c
        qb = jax.lax.dynamic_slice_in_dim(q, t0, c, 0)
        sc = jnp.einsum("thd,shd->hts", qb, k) * scale
        # the causal mask as a [c, s] term added to every head's scores:
        # a `where` keeps its predicate, at every head's shape, for the
        # backward of all s / c blocks at once (2 GB of bytes a row at
        # 32 x 8192 x 8192)
        seen = jnp.arange(s)[None, :] <= t0 + jnp.arange(c)[:, None]
        p = jax.nn.softmax(sc + jnp.where(seen, 0.0, -jnp.inf)[None], -1)
        return jnp.einsum("hts,shd->thd", p, v)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(s // c))
    return out.reshape(s, z["h"], z["vd"])


def attention(z, p, u, mm):
    b, s, _ = u.shape
    h, nope, rope = z["h"], z["nope"], z["rope"]
    q = mm(u, p["q.w"]).reshape(b, s, h, nope + rope)
    kv_a = mm(u, p["kv_a.w"])
    c, k_rope = kv_a[..., :z["lat"]], kv_a[..., z["lat"]:]
    kv = mm(rms_norm(c, p["kv_norm.w"], z["eps"]), p["kv_b.w"])
    kv = kv.reshape(b, s, h, nope + z["vd"])

    def row(xs):
        q, kv, k_rope = xs
        q_rope = rotary_interleave(q[..., nope:], z["theta"])
        k_rope = rotary_interleave(k_rope[:, None, :], z["theta"])
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (s, h, rope))], -1)
        return attention_row(z, jnp.concatenate([q[..., :nope], q_rope], -1),
                             k, kv[..., nope:])
    o = jax.lax.map(row, (q, kv, k_rope))
    return mm(o.reshape(b, s, -1), p["o.w"])


def routing(z, logits, bias):
    """[.., E] router logits -> [.., E] weights (nought at the experts
    not chosen): sigmoid scores, the choice by score + bias, the weights
    the unbiased scores over their sum + 1e-20, times the scaling
    factor."""
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
    # running sum would be kept for the backward (1 GiB a layer)
    y, _ = jax.lax.scan(
        lambda acc, xs: (acc + jax.checkpoint(one)(xs), None),
        jnp.zeros_like(u),
        (p["experts.gate"], p["experts.up"], p["experts.down"],
         jnp.moveaxis(g, -1, 0)))
    return y + gated_mlp(u, p["shared.gate"], p["shared.up"],
                         p["shared.down"], mm)


def layer(z, p, x, mm, dense: bool):
    """Each half under a checkpoint of its own: the backward of one half
    then holds that half's activations and not the other's as well (a
    float32 layer at 8192 positions does not fit beside the weights, two
    moments and two gradients otherwise)."""
    def attn_half(p, x):
        return x + attention(z, p, rms_norm(x, p["ln1.w"], z["eps"]), mm)

    def mlp_half(p, h):
        u = rms_norm(h, p["ln2.w"], z["eps"])
        if dense:
            return h + gated_mlp(u, p["mlp.gate"], p["mlp.up"],
                                 p["mlp.down"], mm)
        return h + moe(z, p, u, mm)
    return jax.checkpoint(mlp_half)(p, jax.checkpoint(attn_half)(p, x))


def _under(w: dict, prefix: str) -> dict:
    return {k[len(prefix):]: a for k, a in w.items() if k.startswith(prefix)}


def hidden(config: dict, w: dict, ids, mm):
    z = sizes(config)
    x = jax.checkpoint(lambda x, p: layer(z, p, x, mm, True))(
        w["embed"][ids], _under(w, "dense."))
    x, _ = jax.lax.scan(
        jax.checkpoint(lambda x, p: (layer(z, p, x, mm, False), None)),
        x, _under(w, "blocks."))
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


def counts(config: dict, stats: dict) -> dict:
    """Operations and bytes a step NEEDS, from the shapes: weight products
    at 6 x parameters x rows (2 forward, 4 backward), the held experts on
    `rows_held`, the rows a BALANCED router sends here (this chip's share
    of the tokens' `top` assignments), attention 3.5 x 2 x (qk + v) flops
    a causal pair a head a layer (forward 2 x qk for the scores and 2 x v
    for the values; backward 2.5 x that), the head on the slice. No
    recomputation counts. `latent_attention` is what the attention
    kernels need, whatever implements them: q and dq at h x qk, k_nope,
    v, o, do, dk_nope, dv at h x their widths, the rotary key and its
    gradient at ONE head, each once forward and once as the backward
    reads or writes it (bf16)."""
    z = sizes(config)
    L, d, h = z["L"], z["d"], z["h"]
    qk = z["nope"] + z["rope"]
    tokens, rows = stats["tokens"], stats["rows"]
    pairs = sum(int(n) * (int(n) + 1) / 2 for n in stats["lengths"])
    attn = (d * h * qk + d * (z["lat"] + z["rope"])
            + z["lat"] * h * (z["nope"] + z["vd"]) + h * z["vd"] * d)
    n_moe = L - z["dense"]
    expert = 3 * d * z["ff"]
    rows_held = tokens * z["top"] * z["held"] / z["E"]
    dense = (L * attn + z["dense"] * 3 * d * z["ffn"]
             + n_moe * (d * z["E"] + 3 * d * z["shared"]))
    attention_flops = L * 3.5 * 2 * (qk + z["vd"]) * h * pairs
    experts_flops = n_moe * 6.0 * expert * rows_held
    # forward reads q, k_nope, k_rope, v and writes o; backward reads q,
    # k_nope, k_rope, v, o, do and writes dq, dk_nope, dk_rope, dv
    per_row = (h * qk * 3 + h * z["nope"] * 3 + z["rope"] * 3
               + h * z["vd"] * 6)
    experts_bytes = n_moe * (3 * z["held"] * expert * 2
                             + rows_held * (4 * d + 6 * z["ff"]) * 2)
    return {
        "step_flops": (6.0 * dense * tokens + experts_flops
                       + 6.0 * d * z["V"] * tokens + attention_flops),
        "latent_attention": {"flops": attention_flops,
                             "bytes": float(L * rows * per_row * 2)},
        "experts": {"flops": experts_flops, "bytes": float(experts_bytes)},
        "rows_held": rows_held,
    }
