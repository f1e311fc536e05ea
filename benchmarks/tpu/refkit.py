"""Plain reference of ELSA's split-training step, written apart from the
program under test.

It imports nothing of the program. Weights come from the run's seed by
the same recipe the configuration states (one ``jax.random.split`` of
``PRNGKey(seed)`` over the parameter leaves in sorted-key order, fan-in
scaled normals). Each rotation comes from ``sha256(salt || client)``.
The count-sketch hash comes from the deployment seed. Handed in are the
inputs (token batches, probe and test tokens, example counts, which
clients trained at which split) and two things the program made: the
global adapters its eval was given, and its SS-OP basis U, which the
harness first holds to this module's own singular values
(``harness.basis_check``; why, in ``harness.reference_readings``).

Everything is straightforward ``jax.numpy``. The reference runs in
float32 at ``Precision.HIGHEST``. The control runs the same code in
bfloat16 at default precision, the next precision below what the
configurations state.

A model file (``configs/<name>.ref.py``) supplies ``param_tree(cfg)``,
``embed``, ``head`` and ``per_example_loss``, and runs its layers one
of two ways. A uniform stack gives ``block``: this module scans it over
``frozen["blocks"]`` and ``lora["blocks"]``, each leaf stacked by
layer. Any other stack (a dense first layer before expert layers, say)
gives ``run_layers(num, cfg, frozen, lora, x, lo, hi, cut)``, which runs
layers ``[lo, hi)`` and calls ``cut(x, i)`` before layer ``i``: ``cut``
applies the channel where ``i`` is a split point. This module holds
what every model shares: parameter init, the channel, the split
forward, local SGD and product-space aggregation.
"""
from __future__ import annotations

import hashlib
import importlib.util
import math
from dataclasses import dataclass
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

SSOP_SALT = "elsa-salt"


@dataclass(frozen=True)
class Numerics:
    """dtype and matmul precision of one reference evaluation."""
    dtype: object
    precision: object

    def mm(self, eq, a, b):
        return jnp.einsum(eq, a, b, precision=self.precision)


REFERENCE = Numerics(jnp.float32, jax.lax.Precision.HIGHEST)
CONTROL = Numerics(jnp.bfloat16, jax.lax.Precision.DEFAULT)


def load_model(path):
    """Import a ``configs/<name>.ref.py`` model file by path."""
    path = Path(path)
    spec = importlib.util.spec_from_file_location(
        "ref_" + path.name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def normal(shape, fan_in):
    return ("normal", tuple(shape), 1.0 / math.sqrt(max(fan_in, 1)))


def embed_init(shape):
    return ("normal", tuple(shape), 0.02)


def zeros(shape):
    return ("zeros", tuple(shape), 0.0)


def ones(shape):
    return ("ones", tuple(shape), 0.0)


def _is_init(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], str)


def init_params(table, seed: int):
    """Materialise ``table`` (a tree of dicts and lists of init tuples)
    in float32: one key per leaf from ``jax.random.split(PRNGKey(seed),
    n)``, the leaves in the tree's flattening order (dict keys sorted,
    lists in order)."""
    leaves, tree = jax.tree_util.tree_flatten(table, is_leaf=_is_init)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = []
    for (kind, shape, std), key in zip(leaves, keys):
        if kind == "zeros":
            out.append(jnp.zeros(shape, jnp.float32))
        elif kind == "ones":
            out.append(jnp.ones(shape, jnp.float32))
        else:
            out.append(jax.random.normal(key, shape, jnp.float32) * std)
    return jax.tree_util.tree_unflatten(tree, out)


def cast(tree, dtype):
    return jax.tree_util.tree_map(lambda a: a.astype(dtype), tree)


def padded_vocab(v: int) -> int:
    return ((v + 255) // 256) * 256


# ---------------------------------------------------------------------------
# shared layers
# ---------------------------------------------------------------------------

def layer_norm(x, scale=None, bias=None, eps=1e-5):
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(-1, keepdims=True)
    y = (xf - mean) / jnp.sqrt(var + eps)
    if scale is not None:
        y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def attention(num, q, k, v, causal: bool):
    """q (B,S,H,E), k/v (B,S,H,E): softmax(q k^T / sqrt(E)) v."""
    s = num.mm("bqhe,bkhe->bhqk", q, k).astype(jnp.float32)
    s = s / math.sqrt(q.shape[-1])
    if causal:
        n = q.shape[1]
        keep = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
        s = jnp.where(keep, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return num.mm("bhqk,bkhe->bqhe", p, v)


def lora_out(num, x, a, b, scale):
    """Adapter ``(x A) B * scale``; ``b`` is (r, ...) and the output
    takes its trailing shape."""
    t = num.mm("...d,dr->...r", x, a.reshape(-1, a.shape[-1]))
    y = num.mm("...r,rk->...k", t, b.reshape(b.shape[0], -1))
    return (y * scale).reshape(x.shape[:-1] + b.shape[1:]).astype(x.dtype)


# ---------------------------------------------------------------------------
# channel: SS-OP rotation, count sketch, median decode, inverse rotation
# ---------------------------------------------------------------------------

def rotation(client: int, r: int) -> np.ndarray:
    """V_n = QR(N(0,1) seeded by sha256(salt || n)), sign-fixed."""
    h = hashlib.sha256(f"{SSOP_SALT}||{client}".encode()).digest()
    rng = np.random.default_rng(int.from_bytes(h[:8], "little"))
    q, rr = np.linalg.qr(rng.standard_normal((r, r)))
    return (q * np.sign(np.diagonal(rr))[None, :]).astype(np.float32)


def sketch_hash(d: int, y: int, z: int, seed: int):
    """Bucket (Y, D) and sign (Y, D) rows of the count sketch."""
    rng = np.random.default_rng(seed)
    bucket = rng.integers(0, z, size=(y, d), dtype=np.int32)
    sign = rng.choice(np.array([-1.0, 1.0], np.float32), size=(y, d))
    return bucket, sign


def sketch_matrix(bucket, sign, z: int) -> np.ndarray:
    """(Y, D, Z) signed one-hot: sketch[y, z] = sum_d h[d] S[y, d, z]."""
    y, d = bucket.shape
    s = np.zeros((y, d, z), np.float32)
    s[np.arange(y)[:, None], np.arange(d)[None, :], bucket] = sign
    return s


def median_rows(rows):
    """Median over a list of arrays by compare-exchange."""
    rows = list(rows)
    n = len(rows)
    for i in range(n):
        for j in range(n - 1 - i):
            rows[j], rows[j + 1] = (jnp.minimum(rows[j], rows[j + 1]),
                                    jnp.maximum(rows[j], rows[j + 1]))
    if n % 2:
        return rows[n // 2]
    return 0.5 * (rows[n // 2 - 1] + rows[n // 2])


def channel(num, h, u, v, sel):
    """h Q^T -> sketch -> median decode -> (.) Q, Q = U V U^T + I - U U^T."""
    u = u.astype(h.dtype)
    r = u.shape[1]
    eye = jnp.eye(r, dtype=h.dtype)
    vt_i = (v.T - eye).astype(h.dtype)
    v_i = (v - eye).astype(h.dtype)
    sel = sel.astype(h.dtype)
    x = h + num.mm("...r,dr->...d", num.mm("...r,rk->...k",
                                           num.mm("...d,dr->...r", h, u),
                                           vt_i), u)
    sk = num.mm("...d,ydz->...yz", x, sel)
    est = num.mm("...yz,ydz->...yd", sk, sel)
    x = median_rows([est[..., i, :] for i in range(est.shape[-2])])
    return x + num.mm("...r,dr->...d", num.mm("...r,rk->...k",
                                              num.mm("...d,dr->...r", x, u),
                                              v_i), u)


# ---------------------------------------------------------------------------
# the reference federation pieces
# ---------------------------------------------------------------------------

class Reference:
    """One model configuration's reference at one precision.

    ``cfg`` is the configuration file's dict, ``model`` its ``.ref.py``
    module, ``fed`` the federation's channel settings (ssop_r, sketch_y,
    sketch_z, rho, seed (the weights), deployment_seed (the sketch hash),
    lr, num_classes).
    """

    def __init__(self, cfg, model, fed, num: Numerics):
        self.cfg, self.model, self.fed, self.num = cfg, model, fed, num
        seed = fed["seed"]
        params = init_params(model.param_tree(cfg, fed["num_classes"]),
                             seed)
        self.frozen = cast(params["frozen"], num.dtype)
        self.lora0 = cast(params["lora"], num.dtype)
        d = cfg["hidden_size"]
        y = fed["sketch_y"]
        z = fed["sketch_z"] or max(4, int(d / (fed["rho"] * y)))
        bucket, sign = sketch_hash(d, y, z, fed["deployment_seed"] + 11)
        self.sel = jnp.asarray(sketch_matrix(bucket, sign, z))
        self.num_layers = cfg["num_hidden_layers"]
        self._round = jax.jit(self._client_round)
        self._repr = jax.jit(self._probe_repr, static_argnums=3)
        self._logits = jax.jit(self._eval_logits)

    # -- forward ------------------------------------------------------------
    def _blocks(self, num, frozen, lora, x, lo, hi, chan_at, chan):
        """Layers [lo, hi); the channel runs before every layer listed
        in ``chan_at`` (traced ints). A uniform stack runs in a scan
        over layers, any other through the model's ``run_layers``."""
        def cut(x, i):
            for at in chan_at:
                x = jax.lax.cond(i == at, chan, lambda t: t, x)
            return x

        run_layers = getattr(self.model, "run_layers", None)
        if run_layers is not None:
            return run_layers(num, self.cfg, frozen, lora, x, lo, hi, cut)

        def body(x, i):
            x = cut(x, i)
            p = jax.tree_util.tree_map(lambda a: a[i], frozen["blocks"])
            lp = jax.tree_util.tree_map(lambda a: a[i], lora["blocks"])
            return self.model.block(num, self.cfg, p, lp, x), None
        return jax.lax.scan(body, x, jnp.arange(lo, hi))[0]

    def _split_forward(self, frozen, lora, tokens, p, q, u, v):
        num, cfg = self.num, self.cfg
        chan = (lambda h: channel(num, h, u, v, self.sel))
        x = self.model.embed(num, cfg, frozen, tokens).astype(num.dtype)
        x = self._blocks(num, frozen, lora, x, 0, self.num_layers,
                         (p, p + q), chan)
        x = jax.lax.cond(p + q == self.num_layers, chan, lambda t: t, x)
        return self.model.head(num, cfg, frozen, lora, x)

    def _probe_repr(self, frozen, lora, tokens, num):
        x = self.model.embed(num, self.cfg, frozen, tokens)
        x = self._blocks(num, frozen, lora, x.astype(num.dtype), 0,
                         self.num_layers, (), None)
        return self.model.head(num, self.cfg, frozen, lora, x)[0]

    def _eval_logits(self, frozen, lora, tokens):
        num = self.num
        x = self.model.embed(num, self.cfg, frozen, tokens)
        x = self._blocks(num, frozen, lora, x.astype(num.dtype), 0,
                         self.num_layers, (), None)
        return self.model.head(num, self.cfg, frozen, lora, x)[1]

    def _loss(self, lora, frozen, tok, lab, wt, p, q, u, v):
        _, logits = self._split_forward(frozen, lora, tok, p, q, u, v)
        per = self.model.per_example_loss(self.cfg, logits, tok, lab)
        per = per.astype(jnp.float32)
        s = wt.sum()
        return (per * wt).sum() / jnp.where(s > 0, s, 1.0)

    def _client_round(self, frozen, lora, toks, labs, wts, p, q, u, v):
        lr = self.fed["lr"]

        def step(lp, xs):
            tok, lab, wt = xs
            loss, g = jax.value_and_grad(self._loss)(lp, frozen, tok, lab,
                                                     wt, p, q, u, v)
            lp = jax.tree_util.tree_map(
                lambda a, b: (a.astype(jnp.float32) - lr * b.astype(
                    jnp.float32)).astype(a.dtype), lp, g)
            return lp, loss
        return jax.lax.scan(step, lora, (toks, labs, wts))

    # -- public -------------------------------------------------------------
    def probe_embeddings(self, probe_tokens):
        """Pooled representations J (Q, D) of the probes under the
        initial adapters."""
        return self._repr(self.frozen, self.lora0,
                          jnp.asarray(probe_tokens), self.num)

    def semantic_basis(self, probe_tokens):
        """Eq. 17: top-r right singular vectors of J."""
        j = self.probe_embeddings(probe_tokens)
        _, _, vt = jnp.linalg.svd(j.astype(jnp.float32),
                                  full_matrices=False)
        return vt[:self.fed["ssop_r"]].T

    def eval_logits(self, lora, tokens, rows: int = 64):
        """The eval forward (no split, no channel) of ``tokens`` under
        ``lora``, ``rows`` test rows at a time; (N, classes) float64."""
        lora = cast(jax.tree_util.tree_map(jnp.asarray, lora),
                    self.num.dtype)
        out = [np.asarray(self._logits(self.frozen, lora,
                                       jnp.asarray(tokens[i:i + rows])),
                          np.float64)
               for i in range(0, len(tokens), rows)]
        return np.concatenate(out)

    def client_round(self, client, split, u, toks, labs, wts):
        """K local SGD steps from ``lora0`` for one client; returns
        (per-step losses (K,), final lora tree)."""
        v = jnp.asarray(rotation(client, self.fed["ssop_r"]))
        p, q = int(split[0]), int(split[1])
        final, losses = self._round(self.frozen, self.lora0,
                                    jnp.asarray(toks), jnp.asarray(labs),
                                    jnp.asarray(wts, jnp.float32),
                                    p, q, u, v)
        return np.asarray(losses, np.float64), final


# ---------------------------------------------------------------------------
# product-space aggregation (weight-delta mean, anchored pinv re-fit)
# ---------------------------------------------------------------------------

def weighted_mean(trees, w):
    return jax.tree_util.tree_map(
        lambda *ls: sum(wi * l.astype(jnp.float32)
                        for wi, l in zip(w, ls)), *trees)


def product_mean(trees, weights, precision=jax.lax.Precision.HIGHEST):
    """Weighted mean of LoRA trees in weight-delta space: per layer
    ``A <- mean A``, ``B <- mean B + A^+ (mean(A_i B_i) - A mean B)``
    with ``A^+ = (A^T A + 1e-8 I)^-1 A^T``; other leaves averaged.
    Adapters under a ``blocks`` key are stacked by layer; elsewhere (a
    list of leading layers, say) each pair is one layer's."""
    w = np.asarray(weights, np.float64)
    w = [float(x) for x in w / w.sum()]
    if len(trees) == 1:
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                      trees[0])
    mean = weighted_mean(trees, w)

    def refit(node, nodes, stacked):
        if isinstance(node, (list, tuple)):
            return type(node)(refit(v, [n[i] for n in nodes], stacked)
                              for i, v in enumerate(node))
        if not isinstance(node, dict):
            return node
        out = {k: refit(v, [n[k] for n in nodes], stacked or k == "blocks")
               for k, v in node.items()}
        lead = (lambda a: a) if stacked else (lambda a: a[None])
        for key in node:
            if not key.endswith("_a") or key[:-2] + "_b" not in node:
                continue
            t = key[:-2]
            a_m, b_m = lead(out[t + "_a"]), lead(out[t + "_b"])
            layers, r = a_m.shape[0], a_m.shape[-1]
            am = a_m.reshape(layers, -1, r)
            bm = b_m.reshape(layers, r, -1)
            dw = sum(wi * jnp.einsum(
                "lmr,lrk->lmk",
                lead(n[t + "_a"]).astype(jnp.float32).reshape(layers, -1, r),
                lead(n[t + "_b"]).astype(jnp.float32).reshape(layers, r, -1),
                precision=precision) for wi, n in zip(w, nodes))
            res = dw - jnp.einsum("lmr,lrk->lmk", am, bm,
                                  precision=precision)
            gram = jnp.einsum("lmr,lms->lrs", am, am, precision=precision) \
                + 1e-8 * jnp.eye(r, dtype=jnp.float32)
            rhs = jnp.einsum("lmr,lmk->lrk", am, res, precision=precision)
            out[t + "_b"] = (bm + jnp.linalg.solve(gram, rhs)
                             ).reshape(out[t + "_b"].shape)
        return out

    return refit(mean, list(trees), False)
