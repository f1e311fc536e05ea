"""Plain reference of a toy stack that is not uniform: layer 0 is a
dense pre-norm decoder block with a gated MLP of ``intermediate_size``,
and every later layer routes each token to ``num_experts_per_tok`` of
``n_routed_experts`` gated MLPs of ``moe_intermediate_size``, weighted
by the router's softmax (no renormalisation). The dense layer's weights
are a ``prefix`` list, the expert layers' a stacked ``blocks`` tree, and
``run_layers`` runs both. Attention has no position encoding; the head
is tied to the embedding and the loss is next-token cross-entropy.
"""
import jax
import jax.numpy as jnp

from refkit import attention, embed_init, layer_norm, lora_out, normal, \
    padded_vocab, zeros


def _attn_tree(lead, cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    e, r = d // h, cfg["lora"]["rank"]
    frozen = {"wq": normal(lead + (d, h, e), d),
              "wk": normal(lead + (d, h, e), d),
              "wv": normal(lead + (d, h, e), d),
              "wo": normal(lead + (h, e, d), d)}
    lora = {}
    for t in cfg["lora"]["targets"]:
        lora[t + "_a"] = normal(lead + (d, r), d)
        lora[t + "_b"] = zeros(lead + (r, h, e))
    return frozen, lora


def param_tree(cfg, num_classes):
    del num_classes
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    n_dense = cfg["first_k_dense_replace"]
    lead = (cfg["num_hidden_layers"] - n_dense,)
    n_exp, fe = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    prefix, prefix_lora = [], []
    for _ in range(n_dense):
        attn, lora = _attn_tree((), cfg)
        prefix.append({"attn": attn,
                       "mlp": {"w_gate": normal((d, f), d),
                               "w_up": normal((d, f), d),
                               "w_down": normal((f, d), f)}})
        prefix_lora.append({"attn": lora})
    attn, lora = _attn_tree(lead, cfg)
    experts = {"router": normal(lead + (d, n_exp), d),
               "w_gate": normal(lead + (n_exp, d, fe), d),
               "w_up": normal(lead + (n_exp, d, fe), d),
               "w_down": normal(lead + (n_exp, fe, d), fe)}
    return {"frozen": {"embed": embed_init((padded_vocab(cfg["vocab_size"]),
                                            d)),
                       "prefix": prefix,
                       "blocks": {"attn": attn, "moe": experts}},
            "lora": {"prefix": prefix_lora, "blocks": {"attn": lora}}}


def embed(num, cfg, frozen, tokens):
    return jnp.take(frozen["embed"], tokens, axis=0)


def _attention(num, cfg, a, la, x):
    scale = cfg["lora"]["alpha"] / cfg["lora"]["rank"]
    xn = layer_norm(x, eps=cfg["layer_norm_eps"])
    proj = {}
    for t in ("q", "k", "v"):
        y = num.mm("bsd,dhe->bshe", xn, a["w" + t])
        if t + "_a" in la:
            y = y + lora_out(num, xn, la[t + "_a"], la[t + "_b"], scale)
        proj[t] = y
    o = attention(num, proj["q"], proj["k"], proj["v"], causal=True)
    return x + num.mm("bshe,hed->bsd", o, a["wo"])


def _gated(num, x, w_gate, w_up, w_down):
    g = jax.nn.silu(num.mm("...d,df->...f", x, w_gate))
    return num.mm("...f,fd->...d", g * num.mm("...d,df->...f", x, w_up),
                  w_down)


def dense_block(num, cfg, p, lp, x):
    x = _attention(num, cfg, p["attn"], lp["attn"], x)
    xn = layer_norm(x, eps=cfg["layer_norm_eps"])
    m = p["mlp"]
    return x + _gated(num, xn, m["w_gate"], m["w_up"], m["w_down"])


def expert_block(num, cfg, p, lp, x):
    x = _attention(num, cfg, p["attn"], lp["attn"], x)
    xn = layer_norm(x, eps=cfg["layer_norm_eps"])
    m = p["moe"]
    probs = jax.nn.softmax(
        num.mm("bsd,de->bse", xn, m["router"]).astype(jnp.float32), -1)
    _, top = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    gates = probs * jax.nn.one_hot(top, probs.shape[-1]).sum(-2)
    outs = jax.vmap(lambda g, u, d: _gated(num, xn, g, u, d))(
        m["w_gate"], m["w_up"], m["w_down"])
    return x + num.mm("bse,ebsd->bsd", gates.astype(x.dtype), outs)


def run_layers(num, cfg, frozen, lora, x, lo, hi, cut):
    n_dense = len(frozen["prefix"])
    for i in range(lo, min(hi, n_dense)):
        x = cut(x, i)
        x = dense_block(num, cfg, frozen["prefix"][i], lora["prefix"][i], x)

    def body(x, i):
        x = cut(x, i)
        p = jax.tree_util.tree_map(lambda a: a[i - n_dense], frozen["blocks"])
        lp = jax.tree_util.tree_map(lambda a: a[i - n_dense], lora["blocks"])
        return expert_block(num, cfg, p, lp, x), None
    return jax.lax.scan(body, x, jnp.arange(max(lo, n_dense), hi))[0]


def head(num, cfg, frozen, lora, x):
    x = layer_norm(x, eps=cfg["layer_norm_eps"])
    return x.mean(axis=1), num.mm("bsd,vd->bsv", x, frozen["embed"])


def per_example_loss(cfg, logits, tokens, labels):
    lg = logits[:, :-1, :].astype(jnp.float32)
    lg = lg + jnp.where(jnp.arange(lg.shape[-1]) < cfg["vocab_size"], 0.0,
                        -1e30)
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return (jax.nn.logsumexp(lg, axis=-1) - gold).mean(axis=-1)
