"""Count-sketch compress/decompress Pallas TPU kernels (Eqs. 20–21).

TPU adaptation (DESIGN.md §3): the hash scatter/gather is re-expressed as
matmuls against a dense signed-selection tensor S (Y, D, Z), S[y,d,z] =
sign[y,d]·1[bucket[y,d]=z], so both directions run on the MXU:

  compress:   out[t,y,:]  = Σ_d H[t,d]·S[y,d,:]      (T,D)x(D,Z) per y
  decompress: est[t,y,d]  = Σ_z U[t,y,z]·S[y,d,z]    (T,Z)x(Z,D) per y
              out[t,d]    = median_y est[t,y,d]       (compare-exchange net)

Blocks are (bt, bd) tiles with fp32 accumulation in VMEM scratch; the
(y, z) extent is small (Y≈3, Z≈D/(ρY)) and stays resident, so each
compress output block spans the full (Y, Z) of its row tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import block_size, resolve_interpret


def _compress_kernel(h_ref, s_ref, o_ref, acc_ref, *, nd: int, y: int):
    di = pl.program_id(1)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    h = h_ref[...].astype(jnp.float32)            # (bt, bd)
    for yy in range(y):
        s = s_ref[yy].astype(jnp.float32)         # (bd, Z)
        acc_ref[yy] += jax.lax.dot(h, s, preferred_element_type=jnp.float32)

    @pl.when(di == nd - 1)
    def _finish():
        for yy in range(y):
            o_ref[:, yy, :] = acc_ref[yy].astype(o_ref.dtype)


def sketch_compress_tz(h, s, *, bt: int = 256, bd: int = 512,
                       interpret: bool | None = None):
    """h: (T, D); s: (Y, D, Z) -> (T, Y, Z).

    Each row tile reads H once and fills the full (Y, Z) extent of its
    output block.  ``bd`` is a cap: the D tile is the largest multiple
    of 128 below it that divides D (:func:`repro.kernels.block_size`).
    ``interpret=None`` -> backend-aware default (compiled on TPU).
    """
    interpret = resolve_interpret(interpret)
    T, D = h.shape
    Y, _, Z = s.shape
    bt = min(bt, T)
    bd = block_size(D, bd)
    assert T % bt == 0, (T, bt)
    nt, nd = T // bt, D // bd
    kernel = functools.partial(_compress_kernel, nd=nd, y=Y)
    return pl.pallas_call(
        kernel,
        grid=(nt, nd),
        in_specs=[
            pl.BlockSpec((bt, bd), lambda t, d: (t, d)),
            pl.BlockSpec((Y, bd, Z), lambda t, d: (0, d, 0)),
        ],
        out_specs=pl.BlockSpec((bt, Y, Z), lambda t, d: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((T, Y, Z), h.dtype),
        scratch_shapes=[pltpu.VMEM((Y, bt, Z), jnp.float32)],
        interpret=interpret,
    )(h, s)


def _median_rows(rows):
    n = len(rows)
    rows = list(rows)
    for i in range(n):
        for j in range(n - 1 - i):
            lo = jnp.minimum(rows[j], rows[j + 1])
            hi = jnp.maximum(rows[j], rows[j + 1])
            rows[j], rows[j + 1] = lo, hi
    if n % 2:
        return rows[(n - 1) // 2]
    return 0.5 * (rows[n // 2 - 1] + rows[n // 2])


def _decompress_kernel(u_ref, s_ref, o_ref, *, y: int):
    u = u_ref[...].astype(jnp.float32)            # (bt, Y, Z)
    ests = []
    for yy in range(y):
        s_y = s_ref[...][yy].astype(jnp.float32)  # (bd, Z)
        ests.append(jax.lax.dot_general(
            u[:, yy, :], s_y, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32))  # (bt, bd)
    o_ref[...] = _median_rows(ests).astype(o_ref.dtype)


def sketch_decompress_tz(u, s, *, bt: int = 256, bd: int = 512,
                         interpret: bool | None = None):
    """u: (T, Y, Z); s: (Y, D, Z) -> (T, D) median estimates.

    ``bd`` is a cap, as in :func:`sketch_compress_tz`.
    ``interpret=None`` -> backend-aware default (compiled on TPU).
    """
    interpret = resolve_interpret(interpret)
    T, Y, Z = u.shape
    _, D, _ = s.shape
    bt = min(bt, T)
    bd = block_size(D, bd)
    assert T % bt == 0, (T, bt)
    kernel = functools.partial(_decompress_kernel, y=Y)
    return pl.pallas_call(
        kernel,
        grid=(T // bt, D // bd),
        in_specs=[
            pl.BlockSpec((bt, Y, Z), lambda t, d: (t, 0, 0)),
            pl.BlockSpec((Y, bd, Z), lambda t, d: (0, d, 0)),
        ],
        out_specs=pl.BlockSpec((bt, bd), lambda t, d: (t, d)),
        out_shape=jax.ShapeDtypeStruct((T, D), u.dtype),
        interpret=interpret,
    )(u, s)
