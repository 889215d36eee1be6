"""Production-mesh dry run: lower and compile every (arch, input shape,
mesh) combination against 512 placeholder host devices and record
memory, cost and roofline estimates.

    python -m repro.launch.dryrun --arch minicpm_2b
"""
import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ALL_ARCHS, get_config
from ..models import INPUT_SHAPES, Model
from ..models.transformer import RuntimeFlags
from ..optim import make_optimizer, make_schedule
from ..runtime.steps import TrainState, make_decode_step, \
    make_prefill_step, make_train_step
from ..sharding.rules import (batch_specs, cache_specs, param_specs,
                              train_state_specs)
from .analysis import (collective_bytes, cost_stats, memory_stats,
                       model_flops, roofline)
from .hlo_cost import hlo_cost
from .mesh import make_production_mesh

LONG_WINDOW = 8192


def adjusted_config(cfg, shape_name: str):
    """long_500k policy: pure-attention archs run the
    sliding-window variant; SSM/hybrid run natively."""
    if shape_name == "long_500k" and not cfg.sub_quadratic \
            and cfg.family != "hybrid":
        cfg = dataclasses.replace(cfg, sliding_window=LONG_WINDOW)
    return cfg


def build_lowering(arch: str, shape_name: str, mesh,
                   flags: RuntimeFlags = RuntimeFlags()):
    """Returns (jitted_fn, example_args) ready for .lower()."""
    import dataclasses as _dc
    batch_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    divisor = 1
    for a in batch_axes:
        divisor *= mesh.shape[a]
    flags = _dc.replace(flags, batch_axes=batch_axes, batch_divisor=divisor,
                        moe_impl="ep", model_axis="model",
                        model_size=mesh.shape["model"])
    cfg = adjusted_config(get_config(arch), shape_name)
    shape = INPUT_SHAPES[shape_name]
    model = Model(cfg)
    abstract_p = model.abstract()
    p_sh = param_specs(model.template, mesh)
    batch_sds = model.input_shapes_for(shape)
    b_sh = batch_specs(batch_sds, mesh)

    if shape.kind == "train":
        schedule = make_schedule(cfg.lr_schedule, peak_lr=3e-4,
                                 warmup=100, total=10_000)
        train_step, _ = make_train_step(model, schedule=schedule,
                                        flags=flags)
        opt_init, _ = make_optimizer(cfg.optimizer)
        abstract_opt = jax.eval_shape(opt_init, abstract_p)
        state_sds = TrainState(abstract_p, abstract_opt)
        state_sh = train_state_specs(model.template, mesh, cfg.optimizer)
        fn = jax.jit(train_step,
                     in_shardings=(state_sh, b_sh),
                     out_shardings=(state_sh, None),
                     donate_argnums=(0,))
        return fn, (state_sds, batch_sds)

    if shape.kind == "prefill":
        enc_len = shape.seq_len if cfg.is_encoder_decoder else 0
        prefill_step = make_prefill_step(model, max_cache_len=shape.seq_len,
                                         flags=flags)
        cache_sds = model.abstract_cache(shape.global_batch, shape.seq_len,
                                         enc_len)
        c_sh = cache_specs(cache_sds, mesh)
        fn = jax.jit(prefill_step,
                     in_shardings=(p_sh, b_sh),
                     out_shardings=(NamedSharding(mesh, P()), c_sh))
        return fn, (abstract_p, batch_sds)

    # decode
    enc_len = shape.seq_len if cfg.is_encoder_decoder else 0
    if cfg.is_encoder_decoder and cfg.sliding_window:
        enc_len = min(enc_len, cfg.sliding_window)
    decode_step = make_decode_step(model, flags=flags)
    cache_sds = model.abstract_cache(shape.global_batch, shape.seq_len,
                                     enc_len)
    c_sh = cache_specs(cache_sds, mesh)
    tok_sds = batch_sds["tokens"]
    tok_sh = b_sh["tokens"]
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
    fn = jax.jit(decode_step,
                 in_shardings=(p_sh, tok_sh, c_sh, NamedSharding(mesh, P())),
                 out_shardings=(tok_sh, c_sh),
                 donate_argnums=(2,))
    return fn, (abstract_p, tok_sds, cache_sds, pos_sds)


def dryrun_one(arch: str, shape_name: str, multi_pod: bool,
               flags: RuntimeFlags = RuntimeFlags(),
               verbose: bool = True) -> Dict[str, Any]:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    with jax.set_mesh(mesh):
        fn, args = build_lowering(arch, shape_name, mesh, flags)
        lowered = fn.lower(*args)
        compiled = lowered.compile()
    mem = memory_stats(compiled)
    hlo_text = compiled.as_text()
    try:
        import zstandard as zstd
        os.makedirs("hlo", exist_ok=True)
        tag = f"{arch}_{shape_name}_{'multi' if multi_pod else 'single'}"
        with open(os.path.join("hlo", tag + ".hlo.zst"), "wb") as f:
            f.write(zstd.ZstdCompressor(level=6).compress(
                hlo_text.encode()))
    except Exception:
        pass
    xla_cost = cost_stats(compiled)          # undercounts while loops
    hc = hlo_cost(hlo_text)                  # loop-aware cost model
    cost = {"flops": hc["flops"], "bytes": hc["bytes"],
            "xla_flops": xla_cost["flops"], "xla_bytes": xla_cost["bytes"]}
    coll = {"total": hc["coll"],
            **{k: hc[k] for k in ("all-gather", "all-reduce",
                                  "reduce-scatter", "all-to-all",
                                  "collective-permute")}}
    rl = roofline(cost["flops"], cost["bytes"], coll["total"], chips)
    cfg = adjusted_config(get_config(arch), shape_name)
    mf = model_flops(cfg, INPUT_SHAPES[shape_name])
    mf_per_chip = mf / chips
    useful = mf_per_chip / cost["flops"] if cost["flops"] else 0.0
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "hbm_per_device_gb": mem["total_per_device"] / 2**30,
        "flops_per_device": cost["flops"],
        "bytes_per_device": cost["bytes"],
        "collective_bytes": coll["total"],
        "collective_counts": {k: v for k, v in coll.items()
                              if k not in ("total",) and v},
        "compute_s": rl["compute_s"], "memory_s": rl["memory_s"],
        "collective_s": rl["collective_s"], "dominant": rl["dominant"],
        "model_flops_per_chip": mf_per_chip,
        "useful_flops_ratio": useful,
        "compile_time_s": time.time() - t0,
    }
    if verbose:
        print(f"[{arch} x {shape_name} x {result['mesh']}] "
              f"hbm/dev={result['hbm_per_device_gb']:.2f}GiB "
              f"compute={rl['compute_s']*1e3:.2f}ms "
              f"memory={rl['memory_s']*1e3:.2f}ms "
              f"collective={rl['collective_s']*1e3:.2f}ms "
              f"dominant={rl['dominant']} useful={useful:.2f} "
              f"compile={result['compile_time_s']:.0f}s")
        print("  memory_analysis:", {k: f"{v/2**30:.2f}GiB"
                                     for k, v in mem.items()
                                     if "size" in k})
        print("  cost_analysis:", cost)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    choices=list(INPUT_SHAPES) + ["all"])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="",
                    help="append JSON results to this file")
    ap.add_argument("--flash", action="store_true")
    args = ap.parse_args(argv)

    archs = ALL_ARCHS if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    flags = RuntimeFlags(use_flash=args.flash)

    results, failures = [], []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    results.append(dryrun_one(arch, shape, mp, flags))
                except Exception as e:  # noqa: BLE001
                    import traceback
                    traceback.print_exc()
                    failures.append((arch, shape, mp, repr(e)))
    if args.out:
        with open(args.out, "a") as f:
            for r in results:
                f.write(json.dumps(r) + "\n")
    print(f"\n{len(results)} lowered+compiled OK, {len(failures)} failed")
    for f_ in failures:
        print("  FAIL:", f_)
    return 1 if failures else 0


if __name__ == "__main__":
    # before the backend initializes: the production meshes (16x16 and
    # 2x16x16) need 512 placeholder host devices
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    sys.exit(main())
