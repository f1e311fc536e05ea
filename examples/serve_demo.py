"""Serving-path demo: batched greedy decoding with a KV cache on a
reduced assigned architecture (the serve_step lowered by the decode
dry-run shapes).

  PYTHONPATH=src python examples/serve_demo.py [--arch llama3-8b] [--steps 12]
"""
import argparse

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import make_serve_step
from repro.models import zoo
from repro.models.params import init_tree


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b", choices=ASSIGNED)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=12)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch).reduced()
    model = zoo.get_model(cfg)
    if model.decode_step is None:
        raise SystemExit(f"{args.arch} has no decode path")
    params = init_tree(model.specs(cfg), jax.random.PRNGKey(0), jnp.float32)
    cache = init_tree(model.cache_specs(cfg, args.batch, 64),
                      jax.random.PRNGKey(1), jnp.float32)
    serve = jax.jit(make_serve_step(cfg))

    tok = jax.random.randint(jax.random.PRNGKey(2), (args.batch, 1), 0,
                             cfg.vocab_size)
    print(f"{args.arch} (reduced: {cfg.num_layers}L d={cfg.d_model}) "
          f"decoding {args.steps} tokens for batch={args.batch}")
    seqs = [tok[:, 0]]
    for t in range(args.steps):
        nxt, cache = serve(params["frozen"], params["lora"], cache,
                           {"tokens": tok})
        tok = nxt[:, None]
        seqs.append(nxt)
    out = jnp.stack(seqs, 1)
    for b in range(args.batch):
        print(f"  seq[{b}]: {out[b].tolist()}")


if __name__ == "__main__":
    main()
