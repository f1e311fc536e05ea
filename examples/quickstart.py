"""Quickstart: the three ELSA mechanisms in ~60 lines.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.clustering import cluster_clients
from repro.core.sketch import compress, decompress, make_plan
from repro.core.splitting import SplitPolicy, splits_for_population
from repro.core.ssop import apply_ssop, apply_ssop_inverse, make_ssop
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

# 1. behavior-aware clustering (Eqs. 4-6 + Stages 1-4) ----------------------
rng = np.random.default_rng(0)
n_clients, n_edges = 12, 3
div = np.abs(rng.normal(5.0, 0.5, (n_clients, n_clients)))
div = (div + div.T) / 2
np.fill_diagonal(div, 0)
for g in range(3):                       # three behaviorally-tight groups
    idx = np.arange(4 * g, 4 * g + 4)
    div[np.ix_(idx, idx)] *= 0.02
trust = np.ones(n_clients)
trust[7] = 0.05                          # a poisoned client
latency = np.full((n_clients, n_edges), 500.0)
for g in range(3):
    latency[4 * g:4 * g + 4, g] = 30.0
result = cluster_clients(div, trust, latency, tau_max=200.0, w_min=0.3)
print("clusters:", {k: v for k, v in result.groups.items()})
print("excluded (low trust / out of range):",
      result.excluded + result.escalated)

# 2. resource-aware dynamic splitting (Eqs. 7-9) ----------------------------
policy = SplitPolicy(num_blocks=12, o_fix=2, p_min=1, p_max=6)
splits = splits_for_population(
    capacities=[1e9, 5e10, 1e12], bandwidths=[1e8, 5e6, 1e6], policy=policy)
print("splits (p, q, o) for weak/mid/strong clients:", splits)

# 3. SS-OP + count-sketch channel (Eqs. 17-21) ------------------------------
d = 256
h = jax.random.normal(jax.random.PRNGKey(0), (32, d))     # hidden states
ssop = make_ssop(h, r=8, salt="secret", client_id=3)
plan = make_plan(d, y=3, z=40, seed=1)                    # rho ~ 2.1
wire = compress(apply_ssop(h, ssop), plan)                # what is sent
print(f"wire payload: {wire.shape} ({h.size / wire.size:.2f}x smaller)")
h_rec = apply_ssop_inverse(decompress(wire, plan), ssop)  # receiver side
rel = float(jnp.linalg.norm(h_rec - h) / jnp.linalg.norm(h))
print(f"round-trip relative error (sketch noise only): {rel:.3f}")
# an eavesdropper without V_n cannot undo the rotation:
leak = decompress(wire, plan)
cos = float(jnp.mean(jnp.sum(leak * h, -1) /
                     (jnp.linalg.norm(leak, axis=-1)
                      * jnp.linalg.norm(h, axis=-1))))
print(f"eavesdropper cosine similarity: {cos:.3f}")
