"""Multi-device equivalence battery for tensor-parallel serving.

NOT a test module (the leading underscore keeps pytest away):
``tests/test_sharded_serving.py`` runs this file in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` — the forced
host-device count must be set before the jax backend initializes, which
is too late for an already-running pytest process.  One process holds
meshes of SIZES 1, 2 and 4 over device subsets
(``make_serving_mesh(tp, devices=jax.devices()[:tp])``), so every
comparison below is sharded-vs-unsharded within a single jax runtime.

Every scenario serves a fixed greedy workload through a GraphServer on
an N-way mesh and requires the streamed tokens to be BIT-IDENTICAL to
the unsharded run — sharding is a memory layout, never a semantic
(docs/SHARDING.md).  Covered: plain decode, speculative verify windows,
chunked prefill, preemption + replay, and the capacity scaling of the
default paged arena — across slot | paged | state | hybrid backends and
the fused | unfused decode dispatch, and the paged-attention kernel's
head-parallel dispatch.

Prints one ``BATTERY {json}`` line: {scenario: {ok, detail}}.
"""
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402

import repro.calculators  # noqa: F401,E402
from repro.configs import get_config  # noqa: E402
from repro.launch.mesh import make_serving_mesh  # noqa: E402
from repro.models.transformer import DEFAULT_FLAGS  # noqa: E402
from repro.serving import GraphServer, LLMEngine  # noqa: E402

MESH_SIZES = (1, 2, 4)
MAX_LEN = 64
RESULTS = {}

# attention stack with head counts divisible by every mesh size, so the
# KV arena shards on kv_heads and the fused kernel's GQA groups stay
# rank-local at tp in {1, 2, 4}
ATTN = dataclasses.replace(
    get_config("minicpm_2b").reduced(), num_layers=1, d_model=64,
    num_heads=4, num_kv_heads=4, head_dim=16, vocab_size=256)
# tiny vocab: greedy decode settles into repetition loops, the regime
# where prompt-lookup drafting actually proposes windows to verify
SPEC = dataclasses.replace(ATTN, vocab_size=4)
STATE = dataclasses.replace(
    get_config("xlstm_1_3b").reduced(), num_layers=2, d_model=64,
    vocab_size=256, block_pattern=("mlstm", "slstm"))
HYBRID = dataclasses.replace(
    get_config("jamba_1_5_large_398b").reduced(), d_model=64,
    vocab_size=256)

_ENGINES = {}


def engine_for(cfg, fused, tp, tag=""):
    """One engine per (config, fused, mesh-size, tag); tp=0 is
    unsharded.  A tag keeps apart engines whose steps are traced under
    a different dispatch."""
    key = (cfg.name, cfg.vocab_size, fused, tp, tag)
    if key not in _ENGINES:
        flags = dataclasses.replace(DEFAULT_FLAGS, use_fused_decode=True) \
            if fused else None
        mesh = make_serving_mesh(tp, devices=jax.devices()[:tp]) \
            if tp else None
        kw = {"flags": flags} if flags is not None else {}
        _ENGINES[key] = LLMEngine(cfg, max_len=MAX_LEN, seed=0,
                                  mesh=mesh, **kw)
    return _ENGINES[key]


def serve(engine, prompts, **srv_kw):
    kw = dict(num_slots=2, max_new_tokens=6)
    kw.update(srv_kw)
    with GraphServer(engine, **kw) as srv:
        handles = [srv.submit(p) for p in prompts]
        outs = [[int(t) for t in h.result(timeout=600)] for h in handles]
        stats = srv.stats()
    return outs, stats


def record(key, ok, detail=""):
    RESULTS[key] = {"ok": bool(ok), "detail": str(detail)}
    print(f"{'ok ' if ok else 'FAIL'} {key} {detail}", flush=True)


def prompts_for(cfg, n=4, seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size,
                        size=int(rng.choice([5, 9, 12]))).astype(np.int32)
            for _ in range(n)]


def main():
    assert jax.device_count() >= 4, \
        f"battery needs 4 forced devices, got {jax.device_count()}"

    # ---- decode: every backend x fused x mesh size -------------------
    decode_plan = [("slot", ATTN, False), ("slot", ATTN, True),
                   ("paged", ATTN, False), ("paged", ATTN, True),
                   ("state", STATE, False), ("hybrid", HYBRID, False)]
    for backend, cfg, fused in decode_plan:
        prompts = prompts_for(cfg)
        srv_kw = {"backend": backend}
        if backend in ("paged", "hybrid"):
            srv_kw["block_size"] = 8
        base, _ = serve(engine_for(cfg, fused, 0), prompts, **srv_kw)
        for tp in MESH_SIZES:
            outs, _ = serve(engine_for(cfg, fused, tp), prompts, **srv_kw)
            tag = "fused" if fused else "unfused"
            record(f"decode/{backend}/{tag}/tp{tp}", outs == base,
                   "" if outs == base else f"{outs} != {base}")

    # ---- the paged-attention kernel's own dispatch ------------------
    # Lowered for the CPU, paged decode attention is the page gather;
    # here it goes through the kernel's dispatch (interpreted; shard_map
    # over each rank's kv heads on a mesh), unsharded run included.
    from repro.models import attention
    gather_dispatch = attention._paged_attention
    attention._paged_attention = attention._paged_kernel_call
    try:
        prompts = prompts_for(ATTN)
        srv_kw = {"backend": "paged", "block_size": 8}
        base, _ = serve(engine_for(ATTN, False, 0, "kernel"), prompts,
                        **srv_kw)
        for tp in MESH_SIZES:
            outs, _ = serve(engine_for(ATTN, False, tp, "kernel"), prompts,
                            **srv_kw)
            record(f"decode/paged/kernel/tp{tp}", outs == base,
                   "" if outs == base else f"{outs} != {base}")
    finally:
        attention._paged_attention = gather_dispatch

    # ---- verify windows: speculative decode on the loop workload -----
    for backend in ("slot", "paged"):
        for fused in (False, True) if backend == "paged" else (False,):
            prompts = prompts_for(SPEC, seed=5)
            srv_kw = {"backend": backend, "speculate_k": 3,
                      "max_new_tokens": 24}
            if backend == "paged":
                srv_kw["block_size"] = 8
            base, bstats = serve(engine_for(SPEC, fused, 0), prompts,
                                 **srv_kw)
            drafted = bstats["scheduler"].get("spec_drafted", 0)
            for tp in (2, 4):
                outs, _ = serve(engine_for(SPEC, fused, tp), prompts,
                                **srv_kw)
                tag = "fused" if fused else "unfused"
                ok = outs == base and drafted > 0
                record(f"verify/{backend}/{tag}/tp{tp}", ok,
                       f"drafted={drafted}" if ok else
                       f"drafted={drafted} {outs} != {base}")

    # ---- chunked extend: long prompts ingested in fixed chunks -------
    rng = np.random.RandomState(7)
    long_prompts = [rng.randint(0, 256, size=40).astype(np.int32)
                    for _ in range(3)]
    for backend in ("slot", "paged"):
        srv_kw = {"backend": backend, "chunk_size": 8,
                  "max_new_tokens": 6}
        if backend == "paged":
            srv_kw["block_size"] = 8
        base, _ = serve(engine_for(ATTN, False, 0), long_prompts,
                        **srv_kw)
        for tp in (2, 4):
            outs, _ = serve(engine_for(ATTN, False, tp), long_prompts,
                            **srv_kw)
            record(f"extend/{backend}/tp{tp}", outs == base,
                   "" if outs == base else f"{outs} != {base}")

    # ---- preemption + replay under block pressure --------------------
    # 1 page at admission, 2+ worst-case, 5 usable blocks: optimistic
    # admission must preempt and the evicted request's replay must
    # reproduce its tokens exactly — on every mesh size
    short = [rng.randint(0, 256, size=6).astype(np.int32)
             for _ in range(5)]
    srv_kw = {"backend": "paged", "block_size": 8, "num_blocks": 6,
              "num_slots": 5, "admission": "preempt",
              "max_new_tokens": 6}
    base, bstats = serve(engine_for(ATTN, False, 0), short, **srv_kw)
    for tp in (2, 4):
        outs, stats = serve(engine_for(ATTN, False, tp), short, **srv_kw)
        pre = stats["scheduler"]["preemptions"]
        ok = outs == base and pre > 0
        record(f"preempt/paged/tp{tp}", ok,
               f"preemptions={pre}" if ok else
               f"preemptions={pre} {outs} != {base}")

    # ---- capacity: the default paged arena scales with rank count ----
    blocks = {}
    for tp in MESH_SIZES:
        eng = engine_for(ATTN, False, tp)
        with GraphServer(eng, num_slots=2, max_new_tokens=4,
                         backend="paged", block_size=8) as srv:
            blocks[tp] = srv._num_blocks
    ok = blocks[1] < blocks[2] < blocks[4]
    record("capacity/paged", ok, f"blocks={blocks}")

    print("BATTERY " + json.dumps(RESULTS, sort_keys=True))
    return 0 if all(r["ok"] for r in RESULTS.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
