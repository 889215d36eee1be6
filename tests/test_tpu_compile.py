"""Compile the main-path Pallas kernels for a TPU v5e chip, at MiniCPM-2B
widths (36 heads x 64, d_model 2304; the paged-attention kernel also
at DeepSeek-7B's 32 x 128), with no chip attached.

Every other kernel test runs the kernels in interpret mode.  These
compile them through Mosaic for a described v5e topology, the way the
public ``repro.kernels.ops`` wrappers lower them on a TPU, so a block
shape, scratch buffer or VMEM budget Mosaic would refuse fails here
instead of on the chip.  Nothing runs: the tests say nothing about
results or speed.

The topology is described inside a module-scoped fixture, never at
import: the TPU compiler library may be loaded by one process at a
time, and the test run's other workers import this module too.  The
persistent compilation cache is off around these compiles, since an
entry compiled for an absent chip cannot be read back.
"""
from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

HEADS = KV_HEADS = 36
HEAD_DIM = 64
D_MODEL = 2304
#: v5e high-bandwidth memory per chip
HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2 topology.  Skips only where the TPU compiler
    library is not installed (a ``jax[cpu]`` install); any other failure
    to describe the topology fails the tests."""
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed: no TPU compiler to call")
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    """Compile ``fn`` for the described chip; return the executable
    after checking that a Mosaic kernel is in it and that the program
    fits the chip's memory."""
    compiled = fn.lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES, mem
    return compiled


def _sds(sharding, *shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_attention_prefill(one_chip):
    qkv = _sds(one_chip, 1, 1024, HEADS, HEAD_DIM)
    _compile(ops.flash_attention, qkv, qkv, qkv)


@pytest.mark.parametrize("split_k", [False, True],
                         ids=["gathered", "split_k"])
def test_fused_flash_decode(one_chip, split_k):
    B, T, bs = 4, 2048, 16
    num_blocks = 1 + B * T // bs
    fn = jax.jit(lambda *a: ops.fused_flash_decode(*a, split_k=split_k))
    tok = _sds(one_chip, B, 1, HEADS, HEAD_DIM)
    pages = _sds(one_chip, num_blocks, bs, KV_HEADS, HEAD_DIM)
    compiled = _compile(
        fn, tok, tok, tok, pages, pages,
        _sds(one_chip, B, T // bs, dtype=jnp.int32),
        _sds(one_chip, B, dtype=jnp.int32))
    # the arenas come back through the kernel's aliased outputs
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= 2 * num_blocks * bs * KV_HEADS \
        * HEAD_DIM * 2


@pytest.mark.parametrize("rows,pages,kv_heads,head_dim", [
    (11, 128, 36, 64), (2, 256, 32, 128)],
    ids=["minicpm-2b.chat", "deepseek-7b-pp2.longprompt"])
def test_paged_attention(one_chip, rows, pages, kv_heads, head_dim):
    """The paged-attention kernel at the widths of the two benchmark
    cells: 16-token pages, every row's table full width.  It asks for
    no VMEM beyond Mosaic's default scoped limit, so compiling shows
    its buffers and temporaries fit that limit; its own estimate of the
    page buffers agrees."""
    from repro.kernels.paged_attention import (DEFAULT_VMEM_LIMIT,
                                               paged_vmem_bytes)
    bs = 16
    assert paged_vmem_bytes(bs, kv_heads, head_dim, 2, pages) \
        <= DEFAULT_VMEM_LIMIT // 2
    num_blocks = 1 + rows * pages
    arena = _sds(one_chip, num_blocks, bs, kv_heads, head_dim)
    _compile(ops.paged_attention,
             _sds(one_chip, rows, kv_heads, head_dim), arena, arena,
             _sds(one_chip, rows, pages, dtype=jnp.int32),
             _sds(one_chip, rows, dtype=jnp.int32))


def test_rmsnorm(one_chip):
    _compile(ops.rmsnorm, _sds(one_chip, 1024, D_MODEL),
             _sds(one_chip, D_MODEL))


@pytest.mark.parametrize("rows,fits", [(2048, True), (4096, False)])
def test_gathered_vmem_estimate(rows, fits):
    """The gathered variant's VMEM estimate agrees with what Mosaic
    reported for a v5e at these widths: a 2048-token row fits under the
    cap (107 MiB), a 4096-token row does not (213 MiB)."""
    from repro.kernels.flash_decode import (GATHER_VMEM_LIMIT,
                                            gathered_vmem_bytes)
    need = gathered_vmem_bytes(rows, KV_HEADS, HEAD_DIM)
    assert (need <= GATHER_VMEM_LIMIT) == fits, need >> 20


def test_engine_refuses_gathered_row_over_vmem(monkeypatch):
    """Built for a TPU, an engine whose gathered fused-decode row would
    not fit the VMEM cap is refused at construction; split-K is not."""
    import dataclasses
    from repro.configs import get_config
    from repro.models.transformer import RuntimeFlags
    from repro.serving import LLMEngine
    cfg = dataclasses.replace(get_config("minicpm_2b").reduced(),
                              num_kv_heads=36, num_heads=36,
                              dtype="bfloat16")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="fused_split_k"):
        LLMEngine(cfg, max_len=4096,
                  flags=RuntimeFlags(use_fused_decode=True))
    LLMEngine(cfg, max_len=4096,
              flags=RuntimeFlags(use_fused_decode=True, fused_split_k=True))
    LLMEngine(cfg, max_len=2048, flags=RuntimeFlags(use_fused_decode=True))
