"""Tensor-parallel serving equivalence (docs/SHARDING.md).

The actual measurements run in ONE subprocess
(``tests/_sharded_battery.py``) launched with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``: the forced
host-device count must be set before the jax backend initializes, so an
in-process pytest cannot hold a multi-device mesh itself.  Inside that
process, meshes of size 1/2/4 are built over device SUBSETS and every
scenario's streamed tokens are compared against an unsharded run.

The tests here are thin, parametrized assertions over the battery's
JSON verdicts — one test per (scenario, backend, mesh size) so a single
regression names exactly what broke.
"""
import json
import os
import subprocess
import sys

import pytest

_BATTERY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "_sharded_battery.py")


@pytest.fixture(scope="module")
def battery():
    env = dict(os.environ)
    flags = [t for t in env.get("XLA_FLAGS", "").split()
             if not t.startswith("--xla_force_host_platform_device_count")]
    flags.append("--xla_force_host_platform_device_count=4")
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, _BATTERY],
                          capture_output=True, text=True, env=env,
                          timeout=1200)
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("BATTERY ")]
    assert lines, (f"battery produced no verdict "
                   f"(rc={proc.returncode}):\n{proc.stdout[-4000:]}\n"
                   f"{proc.stderr[-4000:]}")
    return json.loads(lines[-1][len("BATTERY "):])


def _check(battery, key):
    assert key in battery, f"battery never ran {key}: {sorted(battery)}"
    verdict = battery[key]
    assert verdict["ok"], f"{key}: {verdict['detail']}"


@pytest.mark.parametrize("tp", [1, 2, 4])
@pytest.mark.parametrize("backend", ["slot", "paged", "state", "hybrid"])
def test_decode_bit_identical(battery, backend, tp):
    """Greedy decode on an N-way mesh streams the exact tokens of the
    unsharded run, for every cache layout."""
    _check(battery, f"decode/{backend}/unfused/tp{tp}")


@pytest.mark.parametrize("tp", [1, 2, 4])
@pytest.mark.parametrize("backend", ["slot", "paged"])
def test_decode_bit_identical_fused(battery, backend, tp):
    """The fused flash-decode dispatch (shard_map, per-rank K/V head
    slices) is bit-identical too."""
    _check(battery, f"decode/{backend}/fused/tp{tp}")


@pytest.mark.parametrize("tp", [1, 2, 4])
def test_decode_bit_identical_paged_kernel(battery, tp):
    """The paged-attention kernel's dispatch (interpreted; shard_map
    over each rank's kv heads) streams the unsharded kernel run's exact
    tokens."""
    _check(battery, f"decode/paged/kernel/tp{tp}")


@pytest.mark.parametrize("tp", [2, 4])
@pytest.mark.parametrize("backend,tag", [("slot", "unfused"),
                                         ("paged", "unfused"),
                                         ("paged", "fused")])
def test_verify_window_bit_identical(battery, backend, tag, tp):
    """Speculative verify windows (draft + verify + truncate rollback)
    accept and emit the same tokens on a mesh."""
    _check(battery, f"verify/{backend}/{tag}/tp{tp}")


@pytest.mark.parametrize("tp", [2, 4])
@pytest.mark.parametrize("backend", ["slot", "paged"])
def test_chunked_extend_bit_identical(battery, backend, tp):
    """Chunked prefill (extend steps over a long prompt) lands the same
    K/V and tokens on a mesh."""
    _check(battery, f"extend/{backend}/tp{tp}")


@pytest.mark.parametrize("tp", [2, 4])
def test_preemption_replay_bit_identical(battery, tp):
    """Under block pressure the sharded scheduler preempts and the
    replayed victims still reproduce their tokens exactly."""
    _check(battery, f"preempt/paged/tp{tp}")


def test_default_arena_scales_with_mesh(battery):
    """GraphServer's default paged arena grows by cache_shards(): each
    rank holds 1/tp of every block's bytes, so fixed per-rank memory
    admits tp x blocks."""
    _check(battery, "capacity/paged")
