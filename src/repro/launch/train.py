"""Training launcher.

On a pod: builds the production mesh, shards params/opt-state with the rule
set, runs the jitted train step over the data pipeline, checkpoints.
On this CPU container: ``--host-mesh`` runs a reduced config end-to-end
(the quickstart/train example uses it).

Usage:
    python -m repro.launch.train --arch minicpm_2b --steps 100 --reduced \
        --host-mesh --batch 8 --seq 256
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import save_checkpoint
from ..configs import get_config
from ..data import SyntheticTextDataset
from ..models import Model
from ..models.transformer import RuntimeFlags
from ..optim import make_schedule
from ..runtime.steps import TrainState, make_train_step
from ..sharding.rules import batch_specs, param_specs, train_state_specs
from .compile_cache import enable_compile_cache
from .mesh import make_host_mesh, make_production_mesh


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm_2b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--host-mesh", action="store_true",
                    help="small mesh over local devices (CPU)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    print(f"arch={cfg.name} params={model.param_count():,d} "
          f"optimizer={cfg.optimizer} schedule={cfg.lr_schedule}")

    schedule = make_schedule(cfg.lr_schedule, peak_lr=args.lr,
                             warmup=max(args.steps // 20, 5),
                             total=args.steps)
    flags = RuntimeFlags()
    if args.host_mesh:
        mesh = make_host_mesh()
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        flags = dataclasses.replace(
            flags,
            batch_axes=("pod", "data") if args.multi_pod else ("data",),
            batch_divisor=int(np.prod(
                [mesh.shape[a] for a in
                 (("pod", "data") if args.multi_pod else ("data",))])),
            moe_impl="ep", model_size=mesh.shape["model"])

    train_step, init_state = make_train_step(model, schedule=schedule,
                                             flags=flags)
    params = model.init(jax.random.PRNGKey(args.seed))
    state = init_state(params)

    state_sh = train_state_specs(model.template, mesh, cfg.optimizer)
    with jax.set_mesh(mesh):
        state = jax.device_put(state, state_sh)
        step_fn = jax.jit(train_step, in_shardings=(state_sh, None),
                          out_shardings=(state_sh, None),
                          donate_argnums=(0,))

        ds = SyntheticTextDataset(cfg.vocab_size, args.seq, args.seed)
        losses = []
        t0 = time.time()
        for step in range(args.steps):
            batch = {k: jnp.asarray(v)
                     for k, v in ds.batch(step, args.batch).items()}
            if cfg.is_encoder_decoder:
                batch["enc_embeds"] = jnp.asarray(
                    np.random.RandomState(step).randn(
                        args.batch, args.seq, cfg.d_model), jnp.float32)
            if cfg.frontend:
                P = cfg.num_prefix_embeddings
                batch["prefix_embeds"] = jnp.asarray(
                    np.random.RandomState(step).randn(
                        args.batch, P, cfg.d_model) * 0.02, jnp.float32)
                batch["labels"] = jnp.concatenate(
                    [jnp.zeros((args.batch, P), jnp.int32),
                     batch["labels"]], axis=1)
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            if step % args.log_every == 0 or step == args.steps - 1:
                dt = time.time() - t0
                print(f"step {step:5d} loss={loss:.4f} "
                      f"lr={float(metrics['lr']):.2e} "
                      f"gnorm={float(metrics['grad_norm']):.2f} "
                      f"({dt:.1f}s)")
    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    print(f"loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NO IMPROVEMENT'})")
    if args.checkpoint_dir:
        path = save_checkpoint(args.checkpoint_dir, args.steps, state.params)
        print("checkpoint:", path)
    return 0 if last < first else 1


if __name__ == "__main__":
    sys.exit(main())
