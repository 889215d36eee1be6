"""Production mesh definitions (TPU v5e pods).

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).

Hardware constants for the roofline (per chip): 197 TFLOP/s bf16,
819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import os

import jax
from jax.sharding import AxisType

# TPU v5e roofline constants (per chip)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW = 50e9                     # bytes/s per link


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the sharding rules place params
    and activations by annotation and let GSPMD propagate the rest,
    which Explicit axes (``make_mesh``'s default) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (CPU smoke tests)."""
    n = len(jax.devices())
    mp = model_parallel if n % model_parallel == 0 else 1
    return _auto_mesh((n // mp, mp), ("data", "model"))


def make_serving_mesh(model_parallel: int = 0, *, devices=None):
    """Tensor-parallel SERVING mesh: a (1, tp) ("data", "model") mesh
    over the first ``model_parallel`` local devices (0 = all of them).

    Built from an explicit device list rather than ``jax.make_mesh`` so
    one process can hold meshes of different sizes over device SUBSETS —
    which is how the sharded-equivalence tests compare mesh=1/2/4 runs
    inside a single ``--xla_force_host_platform_device_count=4``
    process (docs/SHARDING.md).  ``devices`` overrides the pool."""
    import numpy as np
    from jax.sharding import Mesh

    devs = list(jax.devices()) if devices is None else list(devices)
    tp = len(devs) if not model_parallel else int(model_parallel)
    if tp < 1 or tp > len(devs):
        raise ValueError(f"model_parallel={tp} needs {tp} devices, "
                         f"have {len(devs)} (set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count=N "
                         f"to simulate more on CPU)")
    return Mesh(np.asarray(devs[:tp]).reshape(1, tp), ("data", "model"))


def mesh_desc(mesh) -> dict:
    """JSON-able description of a mesh for observability tags (metric
    labels, flight-recorder incidents, bench provenance).  ``None``
    (unsharded) reports the single-device shape."""
    if mesh is None:
        return {"devices": 1, "axes": {}}
    axes = {str(k): int(v) for k, v in mesh.shape.items()}
    n = 1
    for v in axes.values():
        n *= v
    plats = sorted({d.platform for d in mesh.devices.flat})
    return {"devices": n, "axes": axes, "platform": ",".join(plats)}


def simulated_devices_env(n: int) -> dict:
    """Copy of the environment that gives a child process ``n`` simulated
    host devices (any earlier forced count replaced) — how a CPU run
    re-execs itself to get a multi-device 'pod' (docs/SHARDING.md).

    Only a CPU run may do this.  On an accelerator the parent already
    holds the chips, and a child that needs them would fail or hang, so
    this raises instead: a mesh there is built from the real devices in
    one process."""
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"{n} devices wanted, {jax.device_count()} {backend} devices "
            f"present; only a CPU run can simulate more")
    env = dict(os.environ)
    flags = [t for t in env.get("XLA_FLAGS", "").split()
             if not t.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env
