"""Fused LoRA matmul Pallas TPU kernel: y = x W + (x A) B · s.

Grid: (nt, no, nk) with the contraction (k) innermost; two fp32 VMEM
accumulators — the main (bt, bo) tile and the low-rank (bt, r) projection —
advance together, so the xA intermediate never round-trips through HBM.
B (r, bo-tile) is applied on the final k step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import block_size, resolve_interpret


def _lora_kernel(x_ref, w_ref, a_ref, b_ref, o_ref, acc_ref, accp_ref, *,
                 nk: int, scale: float):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        accp_ref[...] = jnp.zeros_like(accp_ref)

    x = x_ref[...].astype(jnp.float32)            # (bt, bk)
    w = w_ref[...].astype(jnp.float32)            # (bk, bo)
    a = a_ref[...].astype(jnp.float32)            # (bk, r)
    acc_ref[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)
    accp_ref[...] += jax.lax.dot(x, a, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        b = b_ref[...].astype(jnp.float32)        # (r, bo)
        y = acc_ref[...] + scale * jax.lax.dot(
            accp_ref[...], b, preferred_element_type=jnp.float32)
        o_ref[...] = y.astype(o_ref.dtype)


def lora_matmul_td(x, w, a, b, scale: float, *, bt: int = 256,
                   bo: int = 512, bk: int = 512,
                   interpret: bool | None = None):
    """x: (T, K); w: (K, O); a: (K, r); b: (r, O) -> (T, O).

    ``bo``/``bk`` are caps: each tile is the largest multiple of 128
    below the cap that divides its axis (:func:`repro.kernels.block_size`).
    ``interpret=None`` -> backend-aware default (compiled on TPU).
    """
    interpret = resolve_interpret(interpret)
    T, K = x.shape
    _, O = w.shape
    r = a.shape[1]
    bt, bo, bk = min(bt, T), block_size(O, bo), block_size(K, bk)
    assert T % bt == 0, (T, bt)
    nt, no, nk = T // bt, O // bo, K // bk
    kernel = functools.partial(_lora_kernel, nk=nk, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(nt, no, nk),
        in_specs=[
            pl.BlockSpec((bt, bk), lambda t, o, k: (t, k)),
            pl.BlockSpec((bk, bo), lambda t, o, k: (k, o)),
            pl.BlockSpec((bk, r), lambda t, o, k: (k, 0)),
            pl.BlockSpec((r, bo), lambda t, o, k: (0, o)),
        ],
        out_specs=pl.BlockSpec((bt, bo), lambda t, o, k: (t, o)),
        out_shape=jax.ShapeDtypeStruct((T, O), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, bo), jnp.float32),
                        pltpu.VMEM((bt, r), jnp.float32)],
        interpret=interpret,
    )(x, w, a, b)
