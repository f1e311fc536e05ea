"""Plain reference of olmo-1b-v8 as configured in ``olmo-1b-v8.json``.

OLMo-1B (arXiv:2402.00838): pre-norm decoder with non-parametric
LayerNorm, rotary position embedding on q and k (NeoX half split, theta
10000), causal multi-head attention, SwiGLU MLP ``W_down(silu(W_gate x)
* W_up x)``, and output logits tied to the embedding. LoRA
``(x A) B * alpha/r`` sits on q, k, v and o. The vocabulary is this
chip's eighth of it: tokens and logits cover 6288 rows, padded to 6400
with the padding masked out of the loss. The loss is mean next-token
cross-entropy over the sequence, and the probe representation is the
mean final hidden state.
"""
import jax
import jax.numpy as jnp

from refkit import attention, embed_init, layer_norm, lora_out, normal, \
    padded_vocab, zeros


def param_tree(cfg, num_classes):
    del num_classes
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, f = d // h, cfg["intermediate_size"]
    r = cfg["lora"]["rank"]
    out_heads = {"q": h, "k": kv, "v": kv}
    adapters = {}
    for t in cfg["lora"]["targets"]:
        if t == "o":
            adapters["o_a"] = normal((L, h, e, r), h * e)
            adapters["o_b"] = zeros((L, r, d))
        else:
            adapters[t + "_a"] = normal((L, d, r), d)
            adapters[t + "_b"] = zeros((L, r, out_heads[t], e))
    return {
        "frozen": {
            "embed": embed_init((padded_vocab(cfg["vocab_size"]), d)),
            "blocks": {
                "attn": {"wq": normal((L, d, h, e), d),
                         "wk": normal((L, d, kv, e), d),
                         "wv": normal((L, d, kv, e), d),
                         "wo": normal((L, h, e, d), h * e)},
                "mlp": {"w_gate": normal((L, d, f), d),
                        "w_up": normal((L, d, f), d),
                        "w_down": normal((L, f, d), f)},
            },
        },
        "lora": {"blocks": {"attn": adapters}},
    }


def embed(num, cfg, frozen, tokens):
    return jnp.take(frozen["embed"], tokens, axis=0)


def rope(x, theta):
    n, e = x.shape[1], x.shape[-1]
    half = e // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def block(num, cfg, p, lp, x):
    a, la = p["attn"], lp["attn"]
    eps = cfg["layer_norm_eps"]
    scale = cfg["lora"]["alpha"] / cfg["lora"]["rank"]
    xn = layer_norm(x, eps=eps)
    proj = {}
    for t in ("q", "k", "v"):
        y = num.mm("bsd,dhe->bshe", xn, a["w" + t])
        if t + "_a" in la:
            y = y + lora_out(num, xn, la[t + "_a"], la[t + "_b"], scale)
        proj[t] = y
    q = rope(proj["q"], cfg["rope_theta"])
    k = rope(proj["k"], cfg["rope_theta"])
    o = attention(num, q, k, proj["v"], causal=True)
    out = num.mm("bshe,hed->bsd", o, a["wo"])
    if "o_a" in la:
        flat = o.reshape(o.shape[:2] + (-1,))
        out = out + lora_out(num, flat, la["o_a"], la["o_b"], scale)
    x = x + out
    xn = layer_norm(x, eps=eps)
    m = p["mlp"]
    g = jax.nn.silu(num.mm("bsd,df->bsf", xn, m["w_gate"]))
    u = num.mm("bsd,df->bsf", xn, m["w_up"])
    return x + num.mm("bsf,fd->bsd", g * u, m["w_down"])


def head(num, cfg, frozen, lora, x):
    x = layer_norm(x, eps=cfg["layer_norm_eps"])
    logits = num.mm("bsd,vd->bsv", x, frozen["embed"])
    return x.mean(axis=1), logits


def per_example_loss(cfg, logits, tokens, labels):
    lg = logits[:, :-1, :].astype(jnp.float32)
    v = cfg["vocab_size"]
    lg = lg + jnp.where(jnp.arange(lg.shape[-1]) < v, 0.0, -1e30)
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return (jax.nn.logsumexp(lg, axis=-1) - gold).mean(axis=-1)
