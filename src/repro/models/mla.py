"""DeepSeek-V2 Multi-head Latent Attention (MLA) [arXiv:2405.04434].

Prefill/train use the naive (expanded) form; decode uses the *absorbed* form:
queries are projected into the compressed latent space so the KV cache holds
only (c_kv, k_rope) — (kv_lora_rank + rope_dim) per token, shared across all
128 heads — and attention runs MQA-style over the latent cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.params import Spec
from repro.models.common import rope, gqa_attention, apply_norm, NEG_INF


def mla_specs(cfg):
    a = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk = a.nope_head_dim + a.rope_head_dim
    p = {
        "w_dq": Spec((d, a.q_lora_rank), ("embed", "lora_r")),
        "q_norm": {"scale": Spec((a.q_lora_rank,), (None,), "ones")},
        "w_uq": Spec((a.q_lora_rank, h, qk), ("lora_r", "heads", None),
                     fan_in_axes=(-3,)),
        "w_dkv": Spec((d, a.kv_lora_rank + a.rope_head_dim), ("embed", None)),
        "kv_norm": {"scale": Spec((a.kv_lora_rank,), (None,), "ones")},
        "w_uk": Spec((a.kv_lora_rank, h, a.nope_head_dim), (None, "heads", None),
                     fan_in_axes=(-3,)),
        "w_uv": Spec((a.kv_lora_rank, h, a.v_head_dim), (None, "heads", None),
                     fan_in_axes=(-3,)),
        "wo": Spec((h, a.v_head_dim, d), ("heads", None, "embed"),
                   fan_in_axes=(-3, -2)),
    }
    return p


def mla_lora_specs(cfg):
    """LoRA adapters on the MLA query/output paths."""
    a, r = cfg.mla, cfg.lora.rank
    d, h = cfg.d_model, cfg.num_heads
    qk = a.nope_head_dim + a.rope_head_dim
    out = {}
    if "q" in cfg.lora.targets:
        out["q_a"] = Spec((d, r), ("embed", "lora_r"))
        out["q_b"] = Spec((r, h, qk), ("lora_r", "heads", None), "zeros")
    if "o" in cfg.lora.targets:
        out["o_a"] = Spec((h, a.v_head_dim, r), ("heads", None, "lora_r"),
                          fan_in_axes=(-3, -2))
        out["o_b"] = Spec((r, d), ("lora_r", "embed"), "zeros")
    return out


def _queries(cfg, p, lp, x, positions):
    a = cfg.mla
    ls = cfg.lora.alpha / cfg.lora.rank
    cq = apply_norm("rmsnorm", p["q_norm"], x @ p["w_dq"].astype(x.dtype))
    q = jnp.einsum("bsr,rhe->bshe", cq, p["w_uq"].astype(x.dtype))
    if lp is not None and "q_a" in lp:
        t = x @ lp["q_a"].astype(x.dtype)
        q = q + jnp.einsum("bsr,rhe->bshe", t, lp["q_b"].astype(x.dtype)) * ls
    q_nope = q[..., : a.nope_head_dim]
    q_rope = rope(q[..., a.nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def _out(cfg, p, lp, o, x):
    ls = cfg.lora.alpha / cfg.lora.rank
    y = jnp.einsum("bshe,hed->bsd", o, p["wo"].astype(o.dtype))
    if lp is not None and "o_a" in lp:
        t = jnp.einsum("bshe,her->bsr", o, lp["o_a"].astype(o.dtype))
        y = y + (t @ lp["o_b"].astype(o.dtype)) * jnp.asarray(ls, o.dtype)
    return y


def mla_full(cfg, p, lp, x, *, positions, chunk=2048):
    """Train/prefill path (expanded keys/values, causal)."""
    a = cfg.mla
    B, S, D = x.shape
    q_nope, q_rope = _queries(cfg, p, lp, x, positions)

    dkv = x @ p["w_dkv"].astype(x.dtype)
    c_kv = apply_norm("rmsnorm", p["kv_norm"], dkv[..., : a.kv_lora_rank])
    k_rope = rope(dkv[..., None, a.kv_lora_rank:], positions, cfg.rope_theta)

    k_nope = jnp.einsum("bsr,rhe->bshe", c_kv, p["w_uk"].astype(x.dtype))
    v = jnp.einsum("bsr,rhe->bshe", c_kv, p["w_uv"].astype(x.dtype))

    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:-1] + (a.rope_head_dim,))], -1)
    o = gqa_attention(q, k, v, causal=True, q_offset=0, chunk=chunk)
    return _out(cfg, p, lp, o, x)


def mla_decode(cfg, p, lp, x, cache, *, chunk=4096):
    """Absorbed decode: cache holds (c_kv, k_rope); MQA over the latent."""
    a = cfg.mla
    B, S1, D = x.shape  # S1 == 1
    cur = cache["len"]
    positions = cur + jnp.arange(S1)
    q_nope, q_rope = _queries(cfg, p, lp, x, positions)

    dkv = x @ p["w_dkv"].astype(x.dtype)
    c_kv_new = apply_norm("rmsnorm", p["kv_norm"], dkv[..., : a.kv_lora_rank])
    k_rope_new = rope(dkv[..., None, a.kv_lora_rank:], positions,
                      cfg.rope_theta)[:, :, 0, :]

    ck = jax.lax.dynamic_update_slice_in_dim(
        cache["c_kv"], c_kv_new.astype(cache["c_kv"].dtype), cur, 1)
    cr = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], k_rope_new.astype(cache["k_rope"].dtype), cur, 1)

    # absorb W_uk into q:  score = <W_uk^T q_nope, c_kv> + <q_rope, k_rope>
    q_lat = jnp.einsum("bshe,rhe->bshr", q_nope, p["w_uk"].astype(x.dtype))
    q_eff = jnp.concatenate([q_lat, q_rope], -1)             # (B,1,H,R+rope)
    k_eff = jnp.concatenate([ck, cr], -1)[:, :, None, :]     # (B,S,1,R+rope)

    o_lat = gqa_attention(q_eff, k_eff, ck[:, :, None, :], causal=True,
                          q_offset=cur, kv_valid=cur + S1, chunk=chunk,
                          scale=(a.nope_head_dim + a.rope_head_dim) ** -0.5)
    # project latent attention output through W_uv per head
    o = jnp.einsum("bshr,rhe->bshe", o_lat, p["w_uv"].astype(x.dtype))
    new_cache = {"c_kv": ck, "k_rope": cr, "len": cur + S1}
    return _out(cfg, p, lp, o, x), new_cache
