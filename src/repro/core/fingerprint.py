"""Behavioral fingerprints (ELSA §III.B.1, Eqs. 4–6).

Each client's behavior on the public probe set is summarized as a
multivariate Gaussian over its pooled hidden representations
(``[CLS]`` for encoders; pooled final hidden state for decoder-only /
SSM architectures — see DESIGN.md §8).  Pairwise behavioral discrepancy
is the symmetrized KL divergence between those Gaussians.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np


class Fingerprint(NamedTuple):
    mu: jnp.ndarray      # (D,)
    sigma: jnp.ndarray   # (D, D)


def fingerprint(embeddings: jnp.ndarray, ridge: float = 1e-3) -> Fingerprint:
    """Eq. 4: R_n = N(mu_n, Sigma_n) from probe embeddings (Q, D).

    A ridge term keeps Sigma positive-definite when Q < D (the paper's
    Q=100 << D=768 regime necessarily yields a rank-deficient MLE).
    """
    acc = jnp.promote_types(embeddings.dtype, jnp.float32)
    embeddings = embeddings.astype(acc)
    q, d = embeddings.shape
    mu = embeddings.mean(0)
    centered = embeddings - mu
    sigma = (centered.T @ centered) / q + ridge * jnp.eye(d, dtype=acc)
    return Fingerprint(mu, sigma)


def kl_gaussian(a: Fingerprint, b: Fingerprint) -> jnp.ndarray:
    """Eq. 6: closed-form KL(N_a || N_b), via Cholesky for stability.

    The per-pair definition; ``divergence_matrix`` evaluates the same
    terms from factors it computes once per client.
    """
    d = a.mu.shape[0]
    lb = jnp.linalg.cholesky(b.sigma)
    la = jnp.linalg.cholesky(a.sigma)
    # tr(Sigma_b^-1 Sigma_a) = ||Lb^-1 La||_F^2
    m = jax.scipy.linalg.solve_triangular(lb, la, lower=True)
    tr = jnp.sum(m * m)
    diff = b.mu - a.mu
    y = jax.scipy.linalg.solve_triangular(lb, diff, lower=True)
    maha = jnp.sum(y * y)
    logdet = 2.0 * (jnp.sum(jnp.log(jnp.diagonal(lb)))
                    - jnp.sum(jnp.log(jnp.diagonal(la))))
    return 0.5 * (tr - d + logdet + maha)


def _kl_factored(mu_a, la, hld_a, mu_b, lb, hld_b) -> jnp.ndarray:
    """Eq. 6 as ``kl_gaussian`` computes it, from the Cholesky factors
    and half log-determinants of both covariances."""
    d = mu_a.shape[0]
    m = jax.scipy.linalg.solve_triangular(lb, la, lower=True)
    tr = jnp.sum(m * m)
    diff = mu_b - mu_a
    y = jax.scipy.linalg.solve_triangular(lb, diff, lower=True)
    maha = jnp.sum(y * y)
    logdet = 2.0 * (hld_b - hld_a)
    return 0.5 * (tr - d + logdet + maha)


def sym_kl(a: Fingerprint, b: Fingerprint) -> jnp.ndarray:
    """Eq. 5: R(n, n') = KL(a||b) + KL(b||a)."""
    return kl_gaussian(a, b) + kl_gaussian(b, a)


# bytes of per-pair temporaries one chunk of the pair map may hold: the
# two gathered factors, the solve's output and its workspace, ~4 D x D
_CHUNK_BYTES = 100 * 2**20


def _pair_chunk(d: int, itemsize: int) -> int:
    """Pairs per vmapped step of the pair map, from the shapes alone."""
    return max(1, _CHUNK_BYTES // (4 * d * d * itemsize))


@functools.partial(jax.jit, static_argnames="chunk")
def _divergence(fps: tuple[Fingerprint, ...], chunk: int) -> jnp.ndarray:
    """(N, N) Eq. 5 matrix of N fingerprints.

    Each sigma is factored once; Eq. 6 then runs for every ordered pair
    (a, b), a != b, in chunks of ``chunk`` pairs that gather their two
    factors inside the mapped function, so no per-pair copy of the
    factors outlives its chunk.
    """
    n = len(fps)
    mu = jnp.stack([f.mu for f in fps])
    chol = jnp.linalg.cholesky(jnp.stack([f.sigma for f in fps]))
    # sum(log(diag(L))) = log|Sigma| / 2
    hld = jnp.sum(jnp.log(jnp.diagonal(chol, axis1=1, axis2=2)), 1)
    ia, ib = np.nonzero(~np.eye(n, dtype=bool))

    def kl(pair):
        a, b = pair
        return _kl_factored(mu[a], chol[a], hld[a], mu[b], chol[b], hld[b])

    kls = jax.lax.map(kl, (jnp.asarray(ia), jnp.asarray(ib)),
                      batch_size=chunk)
    k = jnp.zeros((n, n), kls.dtype).at[ia, ib].set(kls)
    return k + k.T


def divergence_matrix(fps: Sequence[Fingerprint]) -> np.ndarray:
    """Dense (N, N) symmetric Eq. 5 matrix with a zero diagonal, as
    float64 numpy.

    One jitted program over all N clients (N Cholesky factorisations,
    Eq. 6 for the N(N-1) ordered pairs) and one host read of the result.
    """
    chunk = _pair_chunk(fps[0].mu.shape[0], fps[0].sigma.dtype.itemsize)
    return np.asarray(_divergence(tuple(fps), chunk), np.float64)


def pooled_embedding(hidden: jnp.ndarray, family: str) -> jnp.ndarray:
    """Task-agnostic per-input profile: [CLS] for encoders, mean-pool
    otherwise (DESIGN.md §8)."""
    if family == "encoder":
        return hidden[:, 0, :]
    return hidden.mean(axis=1)
