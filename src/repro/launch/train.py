"""Production training launcher: ``--arch <id>`` on the current device
topology (or the production mesh under the dry-run device forcing).

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
      --steps 100 --seq 512 --batch 16 [--ckpt-dir …] [--restart]

On a real pod each host runs this same script (jax.distributed handles
process groups); here it drives the host mesh end-to-end: sharded params,
gradient accumulation, checkpoint/restart, stateless data replay.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_arch, get_reduced
from repro.data import DataConfig, synthetic_lm_batch
from repro.distributed.sharding import axis_rules, param_shardings
from repro.launch.mesh import make_host_mesh
from repro.models import ModelOptions, count_params, init_params
from repro.train import OptConfig, TrainConfig, checkpoint, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--restart", action="store_true")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_arch(args.arch)
    opts = ModelOptions(dtype=jnp.float32 if args.reduced else jnp.bfloat16,
                        remat=not args.reduced,
                        max_abs_pos=max(4096, args.seq))
    mesh = make_host_mesh()
    tcfg = TrainConfig(opt=OptConfig(lr=args.lr, warmup_steps=10,
                                     decay_steps=args.steps),
                       accum=args.accum)
    opt_init, step_fn = make_train_step(cfg, tcfg, opts)

    with mesh, axis_rules(mesh):
        params = init_params(cfg, jax.random.PRNGKey(0), opts)
        params = jax.device_put(params, param_shardings(params, mesh))
        opt = opt_init(params)
        print(f"{cfg.name}: {count_params(params)/1e6:.1f}M params on "
              f"{len(jax.devices())} devices")
        start = 0
        if args.restart and args.ckpt_dir and \
                checkpoint.latest_step(args.ckpt_dir) is not None:
            avals = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                {"params": params, "opt": opt})
            shardings = jax.tree_util.tree_map(
                lambda x: x.sharding, {"params": params, "opt": opt})
            restored, start = checkpoint.restore(
                args.ckpt_dir, avals, shardings=shardings)
            params, opt = restored["params"], restored["opt"]
            print(f"restored step {start}")

        dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                          global_batch=args.batch * max(1, args.accum))
        jstep = jax.jit(step_fn, donate_argnums=(0, 1))
        t0 = time.time()
        for i in range(start, args.steps):
            raw = synthetic_lm_batch(dcfg, i)
            if args.accum > 1:
                raw = {k: v.reshape(args.accum, args.batch, -1)
                       for k, v in raw.items()}
            batch = {k: jnp.asarray(v) for k, v in raw.items()}
            params, opt, m = jstep(params, opt, batch)
            if (i + 1) % 10 == 0:
                print(f"step {i+1:5d} loss {float(m['loss']):.4f} "
                      f"({(time.time()-t0)/10:.2f}s/step)")
                t0 = time.time()
            if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
                checkpoint.save(args.ckpt_dir, i + 1,
                                {"params": params, "opt": opt})
    print("done")


if __name__ == "__main__":
    main()
