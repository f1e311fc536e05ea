"""xLSTM blocks [arXiv:2405.04517]: mLSTM (matrix memory, 7 of 8 blocks) and
sLSTM (scalar memory with recurrent weights, 1 of 8).

mLSTM train/prefill uses a chunkwise form: ``lax.scan`` over chunks with the
stabilized intra-chunk interaction computed attention-style
((B,H,L,L) decay-masked score matrices).  Decode is the exact stabilized
recurrence on (C, n, m).  sLSTM is inherently sequential -> ``lax.scan``
over time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.params import Spec
from repro.models.common import apply_norm, NEG_INF


def _inner(cfg):
    return int(cfg.ssm.proj_factor * cfg.d_model)


def _heads(cfg):
    return cfg.num_heads


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------

def mlstm_specs(cfg):
    d, di, h = cfg.d_model, _inner(cfg), _heads(cfg)
    k = cfg.ssm.conv_kernel
    return {
        "ln": {"scale": Spec((d,), ("embed",), "ones"),
               "bias": Spec((d,), ("embed",), "zeros")},
        "w_x": Spec((d, di), ("embed", "mlp")),
        "w_z": Spec((d, di), ("embed", "mlp")),
        "conv_w": Spec((k, di), (None, "mlp")),
        "conv_b": Spec((di,), ("mlp",), "zeros"),
        "wq": Spec((di, di), ("mlp", None)),
        "wk": Spec((di, di), ("mlp", None)),
        "wv": Spec((di, di), ("mlp", None)),
        "w_i": Spec((di, h), ("mlp", "heads")),
        "b_i": Spec((h,), ("heads",), "zeros"),
        "w_f": Spec((di, h), ("mlp", "heads")),
        "b_f": Spec((h,), ("heads",), "const", 3.0),  # forget-gate bias high
        "gn": {"scale": Spec((di,), ("mlp",), "ones")},
        "w_down": Spec((di, d), ("mlp", "embed")),
    }


def mlstm_lora_specs(cfg):
    di, r = _inner(cfg), cfg.lora.rank
    out = {}
    for t in cfg.lora.targets:
        if t in ("q", "k", "v"):
            out[f"{t}_a"] = Spec((di, r), ("mlp", "lora_r"))
            out[f"{t}_b"] = Spec((r, di), ("lora_r", None), "zeros")
    return out


def slstm_specs(cfg):
    d, h = cfg.d_model, _heads(cfg)
    hd = d // h
    gates = {}
    for g in ("i", "f", "z", "o"):
        gates[f"w_{g}"] = Spec((d, h, hd), ("embed", "heads", None),
                               fan_in_axes=(-3,))
        gates[f"r_{g}"] = Spec((h, hd, hd), ("heads", None, None), "normal", 0.5)
        gates[f"b_{g}"] = Spec((h, hd), ("heads", None),
                               "const" if g == "f" else "zeros",
                               3.0 if g == "f" else 1.0)
    return {
        "ln": {"scale": Spec((d,), ("embed",), "ones"),
               "bias": Spec((d,), ("embed",), "zeros")},
        **gates,
        "gn": {"scale": Spec((d,), ("embed",), "ones")},
        "w_up1": Spec((d, int(d * 4 / 3)), ("embed", "mlp")),
        "w_up2": Spec((d, int(d * 4 / 3)), ("embed", "mlp")),
        "w_down": Spec((int(d * 4 / 3), d), ("mlp", "embed")),
    }


# ---------------------------------------------------------------------------
# mLSTM cell
# ---------------------------------------------------------------------------

def _mlstm_chunk(q, k, v, log_f, log_i, C0, n0, m0):
    """Stabilized chunkwise mLSTM.

    q,k,v: (B,H,L,Dh) fp32; log_f, log_i: (B,H,L);
    carry C0 (B,H,Dh,Dh), n0 (B,H,Dh), m0 (B,H).  Returns h (B,H,L,Dh) + carry.
    """
    B, H, L, Dh = q.shape
    F = jnp.cumsum(log_f, -1)                            # (B,H,L)
    # intra-chunk log weights: D[t,τ] = F[t]-F[τ] + log_i[τ]  (τ<=t)
    Dmat = F[..., :, None] - F[..., None, :] + log_i[..., None, :]
    tri = jnp.tril(jnp.ones((L, L), bool))
    Dmat = jnp.where(tri, Dmat, NEG_INF)
    # inter-chunk log weight for state from previous chunks: F[t] + m0
    inter = F + m0[..., None]                            # (B,H,L)
    m_t = jnp.maximum(Dmat.max(-1), inter)               # stabilizer per step
    # intra attention-style weights: C[t,τ] = (q_t·k_τ/√d)·exp(D[t,τ]-m_t)
    s = (q @ k.transpose(0, 1, 3, 2)) * (Dh ** -0.5)     # (B,H,L,L)
    w = s * jnp.exp(Dmat - m_t[..., None])
    h_intra = w @ v                                      # (B,H,L,Dh)
    # inter: q · C0 scaled by exp(F[t]+m0-m_t)
    scale_inter = jnp.exp(inter - m_t)[..., None]        # (B,H,L,1)
    h_inter = (q @ C0) * (Dh ** -0.5) * scale_inter
    h_num = h_intra + h_inter
    # normalizer: row-sums of w plus inter normalizer q·n0/√d
    qn0 = jnp.einsum("bhtd,bhd->bht", q, n0) * (Dh ** -0.5)
    row = w.sum(-1) + qn0 * scale_inter[..., 0]          # (B,H,L)
    denom = jnp.maximum(jnp.abs(row), jnp.exp(-m_t))[..., None]
    h = h_num / denom

    # carry update to end of chunk
    m_end = jnp.maximum(F[..., -1] + m0, (F[..., -1:] - F + log_i).max(-1))
    wk = jnp.exp(F[..., -1:] - F + log_i - m_end[..., None])  # (B,H,L)
    C_new = jnp.exp(F[..., -1] + m0 - m_end)[..., None, None] * C0 + \
        jnp.einsum("bhs,bhsd,bhse->bhde", wk, k, v)
    n_new = jnp.exp(F[..., -1] + m0 - m_end)[..., None] * n0 + \
        jnp.einsum("bhs,bhsd->bhd", wk, k)
    return h, C_new, n_new, m_end


def mlstm_cell_step(q, k, v, log_f, log_i, C, n, m):
    """Exact single-step stabilized recurrence.  q,k,v: (B,H,Dh) fp32."""
    Dh = q.shape[-1]
    m_new = jnp.maximum(log_f + m, log_i)                # (B,H)
    fs = jnp.exp(log_f + m - m_new)[..., None, None]
    is_ = jnp.exp(log_i - m_new)[..., None]
    C_new = fs * C + (is_[..., None] * k[..., :, None]) * v[..., None, :]
    n_new = fs[..., 0] * n + is_ * k
    qn = jnp.einsum("bhd,bhd->bh", q, n_new) * (Dh ** -0.5)
    denom = jnp.maximum(jnp.abs(qn), jnp.exp(-m_new))[..., None]
    h = jnp.einsum("bhd,bhde->bhe", q, C_new) * (Dh ** -0.5) / denom
    return h, C_new, n_new, m_new


def _split_heads(x, h):
    B, S, di = x.shape
    return x.reshape(B, S, h, di // h).transpose(0, 2, 1, 3)  # (B,H,S,Dh)


def mlstm_apply(cfg, p, lp, x, *, cache=None):
    """mLSTM block.  x: (B,S,D).  cache: {'conv','C','n','m'} or None."""
    B, S, D = x.shape
    di, H = _inner(cfg), _heads(cfg)
    K = cfg.ssm.conv_kernel
    ls = cfg.lora.alpha / cfg.lora.rank

    xn = apply_norm("layernorm", p["ln"], x)
    xi = xn @ p["w_x"].astype(x.dtype)
    z = xn @ p["w_z"].astype(x.dtype)

    # causal conv on the qk path
    if cache is not None:
        xp = jnp.concatenate([cache["conv"].astype(x.dtype), xi], 1)
    else:
        xp = jnp.pad(xi, ((0, 0), (K - 1, 0), (0, 0)))
    xc = sum(xp[:, i:i + S, :] * p["conv_w"][i].astype(x.dtype) for i in range(K))
    xc = jax.nn.silu(xc + p["conv_b"].astype(x.dtype))
    new_conv = xp[:, -(K - 1):, :]

    def proj(t, src):
        y = src @ p[f"w{t}"].astype(x.dtype)
        if lp is not None and f"{t}_a" in lp:
            y = y + ((src @ lp[f"{t}_a"].astype(x.dtype))
                     @ lp[f"{t}_b"].astype(x.dtype)) * jnp.asarray(ls, x.dtype)
        return y

    q = _split_heads(proj("q", xc), H).astype(jnp.float32)
    k = _split_heads(proj("k", xc), H).astype(jnp.float32)
    v = _split_heads(proj("v", xi), H).astype(jnp.float32)
    log_i = (xc @ p["w_i"].astype(x.dtype) + p["b_i"].astype(x.dtype)
             ).astype(jnp.float32).transpose(0, 2, 1)   # (B,H,S)
    log_f = jax.nn.log_sigmoid(
        (xc @ p["w_f"].astype(x.dtype) + p["b_f"].astype(x.dtype)
         ).astype(jnp.float32)).transpose(0, 2, 1)

    Dh = di // H
    if cache is not None:
        C0 = cache["C"].astype(jnp.float32)
        n0 = cache["n"].astype(jnp.float32)
        m0 = cache["m"].astype(jnp.float32)
    else:
        C0 = jnp.zeros((B, H, Dh, Dh), jnp.float32)
        n0 = jnp.zeros((B, H, Dh), jnp.float32)
        m0 = jnp.zeros((B, H), jnp.float32)

    if S == 1:
        h, C_new, n_new, m_new = mlstm_cell_step(
            q[:, :, 0], k[:, :, 0], v[:, :, 0], log_f[:, :, 0], log_i[:, :, 0],
            C0, n0, m0)
        h = h[:, :, None, :]
    else:
        L = min(cfg.ssm.chunk, S)
        assert S % L == 0
        nc = S // L

        def body(carry, inp):
            C, n, m = carry
            qc, kc, vc, fc, ic = inp
            hh, C2, n2, m2 = _mlstm_chunk(qc, kc, vc, fc, ic, C, n, m)
            return (C2, n2, m2), hh

        xs = (q.reshape(B, H, nc, L, Dh).transpose(2, 0, 1, 3, 4),
              k.reshape(B, H, nc, L, Dh).transpose(2, 0, 1, 3, 4),
              v.reshape(B, H, nc, L, Dh).transpose(2, 0, 1, 3, 4),
              log_f.reshape(B, H, nc, L).transpose(2, 0, 1, 3),
              log_i.reshape(B, H, nc, L).transpose(2, 0, 1, 3))
        (C_new, n_new, m_new), hs = jax.lax.scan(body, (C0, n0, m0), xs)
        h = hs.transpose(1, 2, 0, 3, 4).reshape(B, H, S, Dh)

    h = h.transpose(0, 2, 1, 3).reshape(B, S, di).astype(x.dtype)
    h = apply_norm("rmsnorm", p["gn"], h)                # group-norm stand-in
    out = (h * jax.nn.silu(z)) @ p["w_down"].astype(x.dtype)

    new_cache = None
    if cache is not None:
        new_cache = {"conv": new_conv.astype(cache["conv"].dtype),
                     "C": C_new.astype(cache["C"].dtype),
                     "n": n_new.astype(cache["n"].dtype),
                     "m": m_new.astype(cache["m"].dtype)}
    return x + out, new_cache


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------

def slstm_apply(cfg, p, lp, x, *, cache=None):
    """sLSTM block.  x: (B,S,D).  cache: {'c','n','m','h'} or None.

    Scalar-memory LSTM with exponential gates, per-head recurrent weights,
    stabilizer state m.  Sequential lax.scan over time.
    """
    B, S, D = x.shape
    H = _heads(cfg)
    hd = D // H
    xn = apply_norm("layernorm", p["ln"], x)

    # pre-compute input contributions for all gates: (B,S,H,hd)
    pre = {g: jnp.einsum("bsd,dhe->bshe", xn, p[f"w_{g}"].astype(x.dtype))
           + p[f"b_{g}"].astype(x.dtype) for g in ("i", "f", "z", "o")}

    if cache is not None:
        c0 = cache["c"].astype(jnp.float32)
        n0 = cache["n"].astype(jnp.float32)
        m0 = cache["m"].astype(jnp.float32)
        h0 = cache["h"].astype(jnp.float32)
    else:
        c0 = jnp.zeros((B, H, hd), jnp.float32)
        n0 = jnp.ones((B, H, hd), jnp.float32)
        m0 = jnp.zeros((B, H, hd), jnp.float32)
        h0 = jnp.zeros((B, H, hd), jnp.float32)

    # recurrent weights stay bf16 (fp32 accumulation): halves the per-step
    # HBM traffic of the sequential scan, which dominates xLSTM's memory
    # roofline term (EXPERIMENTS.md §Perf)
    r = {g: p[f"r_{g}"] for g in ("i", "f", "z", "o")}

    def step(carry, inp):
        c, n, m, h = carry
        pi, pf, pz, po = inp                              # (B,H,hd) each
        rec = {g: jnp.einsum("bhd,hde->bhe", h.astype(r[g].dtype), r[g],
                             preferred_element_type=jnp.float32)
               for g in r}
        log_i = pi.astype(jnp.float32) + rec["i"]
        log_f = jax.nn.log_sigmoid(pf.astype(jnp.float32) + rec["f"])
        zt = jnp.tanh(pz.astype(jnp.float32) + rec["z"])
        ot = jax.nn.sigmoid(po.astype(jnp.float32) + rec["o"])
        m_new = jnp.maximum(log_f + m, log_i)
        ft = jnp.exp(log_f + m - m_new)
        it = jnp.exp(log_i - m_new)
        c_new = ft * c + it * zt
        n_new = ft * n + it
        h_new = ot * c_new / jnp.maximum(n_new, 1e-6)
        return (c_new, n_new, m_new, h_new), h_new

    xs = tuple(pre[g].transpose(1, 0, 2, 3) for g in ("i", "f", "z", "o"))
    (c_f, n_f, m_f, h_f), hs = jax.lax.scan(step, (c0, n0, m0, h0), xs)
    h = hs.transpose(1, 0, 2, 3).reshape(B, S, D).astype(x.dtype)
    h = apply_norm("rmsnorm", p["gn"], h)
    x = x + h
    # post-block gated FFN (4/3 factor per xLSTM)
    u = jax.nn.gelu(x @ p["w_up1"].astype(x.dtype)) * (x @ p["w_up2"].astype(x.dtype))
    x = x + u @ p["w_down"].astype(x.dtype)

    new_cache = None
    if cache is not None:
        new_cache = {"c": c_f.astype(cache["c"].dtype),
                     "n": n_f.astype(cache["n"].dtype),
                     "m": m_f.astype(cache["m"].dtype),
                     "h": h_f.astype(cache["h"].dtype)}
    return x, new_cache


def mlstm_cache_specs(cfg, batch: int):
    di, H = _inner(cfg), _heads(cfg)
    Dh = di // H
    K = cfg.ssm.conv_kernel
    return {"conv": Spec((batch, K - 1, di), ("batch", None, "mlp"), "zeros"),
            "C": Spec((batch, H, Dh, Dh), ("batch", "heads", None, None), "zeros"),
            "n": Spec((batch, H, Dh), ("batch", "heads", None), "zeros"),
            "m": Spec((batch, H), ("batch", "heads"), "zeros")}


def slstm_cache_specs(cfg, batch: int):
    D, H = cfg.d_model, _heads(cfg)
    hd = D // H
    sp = {}
    for k_, init in (("c", "zeros"), ("n", "ones"), ("m", "zeros"), ("h", "zeros")):
        sp[k_] = Spec((batch, H, hd), ("batch", "heads", None), init)
    return sp
