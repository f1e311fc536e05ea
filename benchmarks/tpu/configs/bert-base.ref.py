"""Plain reference of bert-base as configured in ``bert-base.json``.

Post-LN encoder: token + position embeddings and LayerNorm, then per
block ``x = LN(x + Attn(x))``, ``x = LN(x + W_out gelu(W_in x))``, with
LoRA ``(x A) B * alpha/r`` on the q and v projections. The readout is
the [CLS] position through a tanh pooler and a linear classifier, and
the loss is cross-entropy over the class labels. Departures from
BERT-base-uncased follow ``bert-base.json``: the tanh form of GELU and
LayerNorm epsilon 1e-5. The segment table exists, is drawn from the
seed, and is never read, since every token has segment 0 and the
program adds no segment embedding.
"""
import jax
import jax.numpy as jnp

from refkit import embed_init, layer_norm, lora_out, normal, ones, \
    padded_vocab, zeros, attention


def param_tree(cfg, num_classes):
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    e, f = d // h, cfg["intermediate_size"]
    r = cfg["lora"]["rank"]
    ln = {"bias": zeros((L, d)), "scale": ones((L, d))}
    attn = {"wq": normal((L, d, h, e), d), "wk": normal((L, d, h, e), d),
            "wv": normal((L, d, h, e), d), "wo": normal((L, h, e, d), h * e)}
    adapters = {}
    for t in cfg["lora"]["targets"]:
        adapters[t + "_a"] = normal((L, d, r), d)
        adapters[t + "_b"] = zeros((L, r, h, e))
    return {
        "frozen": {
            "embed": embed_init((padded_vocab(cfg["vocab_size"]), d)),
            "pos": embed_init((cfg["max_position_embeddings"], d)),
            "seg": embed_init((cfg["type_vocab_size"], d)),
            "ln_embed": {"bias": zeros((d,)), "scale": ones((d,))},
            "blocks": {"attn": attn, "ln1": dict(ln), "ln2": dict(ln),
                       "mlp": {"w_in": normal((L, d, f), d),
                               "b_in": zeros((L, f)),
                               "w_out": normal((L, f, d), f),
                               "b_out": zeros((L, d))}},
        },
        "lora": {
            "blocks": {"attn": adapters},
            "pooler": {"w": normal((d, d), d), "b": zeros((d,))},
            "head": {"w": normal((d, num_classes), d),
                     "b": zeros((num_classes,))},
        },
    }


def embed(num, cfg, frozen, tokens):
    x = jnp.take(frozen["embed"], tokens, axis=0)
    x = x + frozen["pos"][:tokens.shape[1]][None]
    ln = frozen["ln_embed"]
    return layer_norm(x, ln["scale"], ln["bias"], cfg["layer_norm_eps"])


def block(num, cfg, p, lp, x):
    a, la = p["attn"], lp["attn"]
    scale = cfg["lora"]["alpha"] / cfg["lora"]["rank"]
    proj = {}
    for t in ("q", "k", "v"):
        y = num.mm("bsd,dhe->bshe", x, a["w" + t])
        if t + "_a" in la:
            y = y + lora_out(num, x, la[t + "_a"], la[t + "_b"], scale)
        proj[t] = y
    o = attention(num, proj["q"], proj["k"], proj["v"], causal=False)
    eps = cfg["layer_norm_eps"]
    x = layer_norm(x + num.mm("bshe,hed->bsd", o, a["wo"]),
                   p["ln1"]["scale"], p["ln1"]["bias"], eps)
    m = p["mlp"]
    u = jax.nn.gelu(num.mm("bsd,df->bsf", x, m["w_in"]) + m["b_in"],
                    approximate=True)
    f = num.mm("bsf,fd->bsd", u, m["w_out"]) + m["b_out"]
    return layer_norm(x + f, p["ln2"]["scale"], p["ln2"]["bias"], eps)


def head(num, cfg, frozen, lora, x):
    cls = x[:, 0, :]
    pooled = jnp.tanh(num.mm("bd,dk->bk", cls, lora["pooler"]["w"])
                      + lora["pooler"]["b"])
    logits = num.mm("bd,dk->bk", pooled, lora["head"]["w"]) \
        + lora["head"]["b"]
    return cls, logits


def per_example_loss(cfg, logits, tokens, labels):
    lg = logits.astype(jnp.float32)
    gold = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jax.nn.logsumexp(lg, axis=-1) - gold
