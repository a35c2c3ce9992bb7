"""Serving launcher: batched generation for any --arch, optional kNN-LM
retrieval backed by the PGBJ join.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --reduced \
      --requests 8 --new-tokens 16 [--retrieval]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_arch, get_reduced
from repro.models import ModelOptions, init_params
from repro.serve import (
    BatchedServer, Datastore, KnnLMConfig, ServeConfig, interpolate,
    knn_logits)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--retrieval", action="store_true")
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_arch(args.arch)
    opts = ModelOptions(dtype=jnp.float32 if args.reduced else jnp.bfloat16,
                        remat=False, max_abs_pos=4096)
    params = init_params(cfg, jax.random.PRNGKey(0), opts)
    rng = np.random.default_rng(0)

    hook = None
    if args.retrieval:
        keys = rng.normal(size=(2048, 32)).astype(np.float32)
        vals = rng.integers(0, cfg.vocab, 2048).astype(np.int32)
        store = Datastore.build(keys, vals, k=8, n_pivots=128, n_groups=8)
        kcfg = KnnLMConfig(lam=0.2, tau=50.0, k=8)

        def hook(logits, cache):
            q = np.asarray(logits)[:, :32]
            return interpolate(logits, knn_logits(q, store, kcfg, cfg.vocab),
                               kcfg.lam)

    srv = BatchedServer(
        cfg, ServeConfig(batch=args.batch, temperature=args.temperature),
        params, opts, logits_hook=hook)
    prompts = [rng.integers(0, cfg.vocab, int(rng.integers(4, 16)))
               for _ in range(args.requests)]
    t0 = time.time()
    outs = srv.generate(prompts, max_new_tokens=args.new_tokens)
    dt = time.time() - t0
    total = sum(len(o) for o in outs)
    print(f"{args.requests} requests × {args.new_tokens} tokens in {dt:.2f}s"
          f" ({total/dt:.1f} tok/s){' with kNN-LM retrieval' if hook else ''}")
    for i, o in enumerate(outs[:4]):
        print(f"  req {i}: {list(o)[:10]}{'…' if len(o) > 10 else ''}")


if __name__ == "__main__":
    main()
