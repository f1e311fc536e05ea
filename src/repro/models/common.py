"""Shared model primitives: norms, rotary, GQA attention (direct + chunked
online-softmax + decode), MLPs, LoRA application, spec builders.

All functions are pure; parameters are plain pytrees built from
``repro.models.params.Spec`` trees.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.models.params import Spec

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# spec helpers
# ---------------------------------------------------------------------------

def stack_specs(n: int, tree):
    """Prepend a ('layers', n) dim to every Spec in the tree (for lax.scan)."""
    return jax.tree_util.tree_map(
        lambda s: s._replace(shape=(n,) + s.shape, axes=("layers",) + s.axes),
        tree, is_leaf=lambda x: isinstance(x, Spec))


def norm_specs(kind: str, d: int):
    if kind == "rmsnorm":
        return {"scale": Spec((d,), ("embed",), "ones")}
    if kind == "layernorm":
        return {"scale": Spec((d,), ("embed",), "ones"),
                "bias": Spec((d,), ("embed",), "zeros")}
    if kind == "nonparametric":
        return {}
    raise ValueError(kind)


def attn_specs(cfg, *, cross: bool = False):
    """q/k/v/o projection specs (+ optional bias, + LoRA adapters)."""
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    p = {
        "wq": Spec((d, h, hd), ("embed", "heads", None), fan_in_axes=(-3,)),
        "wk": Spec((d, kv, hd), ("embed", "kv_heads", None),
                   fan_in_axes=(-3,)),
        "wv": Spec((d, kv, hd), ("embed", "kv_heads", None),
                   fan_in_axes=(-3,)),
        "wo": Spec((h, hd, d), ("heads", None, "embed"),
                   fan_in_axes=(-3, -2)),
    }
    if cfg.qkv_bias:
        p["bq"] = Spec((h, hd), ("heads", None), "zeros")
        p["bk"] = Spec((kv, hd), ("kv_heads", None), "zeros")
        p["bv"] = Spec((kv, hd), ("kv_heads", None), "zeros")
    return p


def attn_lora_specs(cfg):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    r = cfg.lora.rank
    out = {}
    dims = {"q": (h, hd), "k": (kv, hd), "v": (kv, hd), "o": (d,)}
    for t in cfg.lora.targets:
        if t not in dims:
            continue
        if t == "o":
            out[f"{t}_a"] = Spec((h, hd, r), ("heads", None, "lora_r"),
                                 fan_in_axes=(-3, -2))
            out[f"{t}_b"] = Spec((r, d), ("lora_r", "embed"), "zeros")
        else:
            n, e = dims[t]
            out[f"{t}_a"] = Spec((d, r), ("embed", "lora_r"))
            out[f"{t}_b"] = Spec((r, n, e), ("lora_r", "kv_heads" if t in ("k", "v") else "heads", None), "zeros")
    return out


def mlp_specs(cfg, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.norm == "layernorm":   # classic (whisper/bert/xlstm): 2-matrix MLP
        return {"w_in": Spec((d, f), ("embed", "mlp")),
                "b_in": Spec((f,), ("mlp",), "zeros"),
                "w_out": Spec((f, d), ("mlp", "embed")),
                "b_out": Spec((d,), ("embed",), "zeros")}
    return {"w_gate": Spec((d, f), ("embed", "mlp")),
            "w_up": Spec((d, f), ("embed", "mlp")),
            "w_down": Spec((f, d), ("mlp", "embed"))}


# ---------------------------------------------------------------------------
# norms / activations / rotary
# ---------------------------------------------------------------------------

def _acc_dtype(x):
    """Accumulation dtype: at least f32, but keep f64 inputs in f64 so
    x64-mode parity runs are not silently re-quantized to f32."""
    return jnp.promote_types(x.dtype, jnp.float32)


def apply_norm(kind: str, p, x, eps: float = 1e-5):
    xf = x.astype(_acc_dtype(x))
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return (y * p["scale"].astype(xf.dtype)).astype(x.dtype)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), -1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    if kind == "layernorm":
        y = y * p["scale"].astype(xf.dtype) + p["bias"].astype(xf.dtype)
    return y.astype(x.dtype)


def activation(kind: str, x):
    return jax.nn.silu(x) if kind == "silu" else jax.nn.gelu(x)


def rope(x, positions, theta: float):
    """x: (..., S, n, head_dim); positions: (..., S) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs      # (...,S,half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# LoRA
# ---------------------------------------------------------------------------

def lora_delta(lp, target: str, x, scale: float):
    """x (..., D) -> adapter output reshaped like the target projection."""
    a, b = lp.get(f"{target}_a"), lp.get(f"{target}_b")
    if a is None:
        return None
    t = x @ a.reshape(-1, a.shape[-1]).astype(x.dtype) if a.ndim > 2 else x @ a.astype(x.dtype)
    out = jnp.tensordot(t, b.astype(x.dtype), axes=1)
    return out * jnp.asarray(scale, x.dtype)


def project(p, lp, x, target: str, lora_scale: float):
    """Fused frozen projection + LoRA adapter for q/k/v."""
    w = p[f"w{target}"]
    y = jnp.einsum("...d,dne->...ne", x, w.astype(x.dtype))
    if f"b{target}" in p:
        y = y + p[f"b{target}"].astype(x.dtype)
    if lp is not None:
        d = lora_delta(lp, target, x, lora_scale)
        if d is not None:
            y = y + d
    return y


def out_project(p, lp, att, x_shape_dtype, lora_scale: float):
    y = jnp.einsum("...ne,ned->...d", att, p["wo"].astype(att.dtype))
    if lp is not None and "o_a" in lp:
        t = jnp.einsum("...ne,ner->...r", att, lp["o_a"].astype(att.dtype))
        y = y + (t @ lp["o_b"].astype(att.dtype)) * jnp.asarray(lora_scale, att.dtype)
    return y


# ---------------------------------------------------------------------------
# attention core
# ---------------------------------------------------------------------------

def _mask(q_pos, k_pos, *, causal: bool, window: int, kv_valid: Optional[jnp.ndarray]):
    """q_pos (Sq,), k_pos (Sk,) -> bool (Sq, Sk), True = attend."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= k_pos[None, :] <= q_pos[:, None]
    if window:
        m &= k_pos[None, :] > (q_pos[:, None] - window)
    if kv_valid is not None:
        m &= k_pos[None, :] < kv_valid
    return m


def _chunked_attn_fwd_core(qr, ks, vs, kpos_chunks, q_pos, *, causal,
                           window, kv_valid, scale):
    """Online-softmax forward over kv chunks.

    qr: (B,Sq,KV,G,Dh); ks/vs: (nc, B, C, KV, Dh); returns (o, m, l) with
    o (B,KV,G,Sq,Dv) fp32, m/l (B,KV,G,Sq) fp32.
    """
    B, Sq, KV, G, Dh = qr.shape
    Dv = vs.shape[-1]
    acc_dt = _acc_dtype(qr)

    def body(carry, inp):
        m_run, l_run, acc = carry
        kc, vc, k_pos = inp
        s = jnp.einsum("bqkgd,bskd->bkgqs", qr, kc,
                       preferred_element_type=acc_dt) * scale
        msk = _mask(q_pos, k_pos, causal=causal, window=window,
                    kv_valid=kv_valid)
        s = jnp.where(msk[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m_run, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(vc.dtype), vc).astype(acc_dt)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, acc_dt)
    l0 = jnp.zeros((B, KV, G, Sq), acc_dt)
    a0 = jnp.zeros((B, KV, G, Sq, Dv), acc_dt)
    (m_f, l_f, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                      (ks, vs, kpos_chunks))
    o = acc / jnp.maximum(l_f, 1e-30)[..., None]
    return o, m_f, l_f


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _chunked_attn(qr, ks, vs, causal, window, scale, chunk):
    """Flash-style chunked attention with memory-lean backward.

    The naive differentiation of the online-softmax scan saves the fp32
    (Sq x chunk) probability block for EVERY chunk step — the full S×S
    attention matrix.  This custom VJP saves only (q, k, v, o, m, l) and
    re-materializes probability blocks one chunk at a time in backward
    (the standard flash-attention backward).
    """
    nc = ks.shape[0]
    q_pos = jnp.arange(qr.shape[1])
    kpos = jnp.arange(nc * chunk).reshape(nc, chunk)
    o, _, _ = _chunked_attn_fwd_core(qr, ks, vs, kpos, q_pos, causal=causal,
                                     window=window, kv_valid=None,
                                     scale=scale)
    return o


def _chunked_attn_fwd(qr, ks, vs, causal, window, scale, chunk):
    nc = ks.shape[0]
    q_pos = jnp.arange(qr.shape[1])
    kpos = jnp.arange(nc * chunk).reshape(nc, chunk)
    o, m, l = _chunked_attn_fwd_core(qr, ks, vs, kpos, q_pos, causal=causal,
                                     window=window, kv_valid=None,
                                     scale=scale)
    return o, (qr, ks, vs, o, m, l)


def _chunked_attn_bwd(causal, window, scale, chunk, res, do):
    qr, ks, vs, o, m, l = res
    B, Sq, KV, G, Dh = qr.shape
    nc = ks.shape[0]
    acc_dt = _acc_dtype(qr)
    q_pos = jnp.arange(Sq)
    l_safe = jnp.maximum(l, 1e-30)
    # D_i = sum_d do_i * o_i  (B,KV,G,Sq)
    dsum = jnp.einsum("bkgqd,bkgqd->bkgq", do.astype(acc_dt), o)

    def body(dq_acc, inp):
        kc, vc, k_pos = inp
        s = jnp.einsum("bqkgd,bskd->bkgqs", qr, kc,
                       preferred_element_type=acc_dt) * scale
        msk = _mask(q_pos, k_pos, causal=causal, window=window,
                    kv_valid=None)
        s = jnp.where(msk[None, None, None], s, NEG_INF)
        p = jnp.exp(s - m[..., None]) / l_safe[..., None]      # normalized
        dp = jnp.einsum("bkgqd,bskd->bkgqs", do.astype(acc_dt),
                        vc.astype(acc_dt))
        ds = p * (dp - dsum[..., None]) * scale
        dv_c = jnp.einsum("bkgqs,bkgqd->bskd", p,
                          do.astype(acc_dt)).astype(vs.dtype)
        dk_c = jnp.einsum("bkgqs,bqkgd->bskd", ds.astype(qr.dtype),
                          qr).astype(ks.dtype)
        dq_acc = dq_acc + jnp.einsum("bkgqs,bskd->bqkgd",
                                     ds.astype(kc.dtype), kc)
        return dq_acc, (dk_c, dv_c)

    kpos = jnp.arange(nc * chunk).reshape(nc, chunk)
    dq0 = jnp.zeros(qr.shape, qr.dtype)
    dq, (dk, dv) = jax.lax.scan(body, dq0, (ks, vs, kpos))
    return dq, dk, dv


_chunked_attn.defvjp(_chunked_attn_fwd, _chunked_attn_bwd)


def gqa_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                  kv_offset=0, kv_valid=None, chunk=2048, use_flash=False,
                  scale=None, k_positions=None):
    """Grouped-query attention with online-softmax kv chunking.

    q: (B, Sq, H, Dh); k, v: (B, Sk, KV, Dh).  ``q_offset`` is the absolute
    position of q[:,0]; ``kv_valid`` masks cache slots >= current length.
    Never materializes an (Sq, Sk) tensor when Sk > chunk.
    """
    if use_flash:
        from repro.kernels.flash_attention import ops as fa_ops
        return fa_ops.flash_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            kv_valid=kv_valid)
    B, Sq, H, Dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = Dh ** -0.5 if scale is None else scale
    qr = q.reshape(B, Sq, KV, G, Dh)
    q_pos = q_offset + jnp.arange(Sq)

    if Sk <= chunk:
        k_pos = k_positions if k_positions is not None else kv_offset + jnp.arange(Sk)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qr, k,
                       preferred_element_type=_acc_dtype(q)) * scale
        m = _mask(q_pos, k_pos, causal=causal, window=window, kv_valid=kv_valid)
        s = jnp.where(m[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bkgqs,bskd->bqkgd", p, v)
        return o.reshape(B, Sq, H, v.shape[-1])

    # chunked online softmax over kv
    n_chunks = Sk // chunk
    assert Sk % chunk == 0, f"Sk={Sk} not divisible by chunk={chunk}"
    ks = k.reshape(B, n_chunks, chunk, KV, k.shape[-1]).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, n_chunks, chunk, KV, v.shape[-1]).transpose(1, 0, 2, 3, 4)
    Dv = v.shape[-1]

    standard = (kv_valid is None and k_positions is None
                and isinstance(q_offset, int) and q_offset == 0
                and isinstance(kv_offset, int) and kv_offset == 0)
    if standard:
        # train/prefill: flash-style custom VJP (memory-lean backward)
        o = _chunked_attn(qr, ks, vs, causal, window, scale, chunk)
        return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, Dv).astype(q.dtype)

    # decode path (dynamic offsets / ring positions); no grad flows here
    if k_positions is not None:
        kpos_chunks = k_positions.reshape(n_chunks, chunk)
    else:
        kpos_chunks = (kv_offset + jnp.arange(Sk)).reshape(n_chunks, chunk)
    o, _, _ = _chunked_attn_fwd_core(
        qr, ks, vs, kpos_chunks, q_pos, causal=causal, window=window,
        kv_valid=kv_valid, scale=scale)
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, Dv).astype(q.dtype)


def apply_mlp(cfg, p, x, d_ff: Optional[int] = None):
    if "w_in" in p:
        h = activation(cfg.act, x @ p["w_in"].astype(x.dtype) + p["b_in"].astype(x.dtype))
        return h @ p["w_out"].astype(x.dtype) + p["b_out"].astype(x.dtype)
    g = activation(cfg.act, x @ p["w_gate"].astype(x.dtype))
    return (g * (x @ p["w_up"].astype(x.dtype))) @ p["w_down"].astype(x.dtype)


# ---------------------------------------------------------------------------
# standard decoder block (dense archs; also used by vlm/hybrid attn layers)
# ---------------------------------------------------------------------------

def block_specs(cfg, d_ff: Optional[int] = None):
    return {
        "ln1": norm_specs(cfg.norm, cfg.d_model),
        "attn": attn_specs(cfg),
        "ln2": norm_specs(cfg.norm, cfg.d_model),
        "mlp": mlp_specs(cfg, d_ff),
    }


def block_lora_specs(cfg):
    return {"attn": attn_lora_specs(cfg)}


def attn_apply(cfg, p, lp, x, *, positions, cache=None, window=0,
               causal=True, chunk=2048):
    """Self-attention sublayer.  With ``cache`` (decode): k/v appended at
    ``positions`` and attention runs over the cache."""
    ls = cfg.lora.alpha / cfg.lora.rank
    q = project(p, lp, x, "q", ls)
    k = project(p, lp, x, "k", ls)
    v = project(p, lp, x, "v", ls)
    if cfg.max_position_embeddings == 0:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    if cache is not None:
        ck, cv, cur = cache["k"], cache["v"], cache["len"]
        ring = "pos" in cache          # windowed ring-buffer cache
        idx = cur % ck.shape[1] if ring else cur
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), idx, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), idx, 1)
        if ring:
            pos = jax.lax.dynamic_update_slice_in_dim(
                cache["pos"], cur + jnp.arange(q.shape[1], dtype=jnp.int32),
                idx, 0)
            o = gqa_attention(q, ck, cv, causal=True, window=window,
                              q_offset=cur, k_positions=pos, chunk=chunk)
            new_cache = {"k": ck, "v": cv, "pos": pos, "len": cur + q.shape[1]}
        else:
            o = gqa_attention(q, ck, cv, causal=True, window=window,
                              q_offset=cur, kv_valid=cur + q.shape[1],
                              chunk=chunk)
            new_cache = {"k": ck, "v": cv, "len": cur + q.shape[1]}
        return out_project(p, lp, o, x, ls), new_cache
    # train/prefill: positions start at 0 (static), keeping the
    # flash-style custom-VJP path eligible
    o = gqa_attention(q, k, v, causal=causal, window=window, q_offset=0,
                      chunk=chunk)
    return out_project(p, lp, o, x, ls), None


def decoder_block(cfg, p, lp, x, *, positions, cache=None, window=0,
                  chunk=2048):
    h, new_cache = attn_apply(cfg, p["attn"],
                              lp["attn"] if lp else None, apply_norm(cfg.norm, p["ln1"], x),
                              positions=positions, cache=cache, window=window,
                              chunk=chunk)
    x = x + h
    x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg.norm, p["ln2"], x))
    return x, new_cache
