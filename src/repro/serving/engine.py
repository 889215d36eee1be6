"""LLM inference engine: jitted prefill + decode with KV/SSM cache.

The engine is the "model inference" component consumed by the MediaPipe
graph's InferenceCalculator (paper §6.1 'performs ML inference ... using an
inference engine').  On a pod it holds pjit-sharded params; in the examples
and tests it runs a reduced config on CPU.

Two decode modes:

* :meth:`generate` — classic static batch: prefill a [B, S] batch, then
  greedy-decode all rows in lockstep (scalar ``cache_pos``).
* the serving API — continuous batching over a
  :class:`~repro.serving.kvcache.CacheBackend`:
  :meth:`new_cache` / :meth:`insert` / :meth:`decode` / :meth:`extend`
  / :meth:`verify` dispatched on the backend's cache layout (contiguous
  slot rows or a paged block-pool arena).  Jitted steps are cached per
  layout, so one engine can serve slot and paged backends at the same
  time.  Used by :class:`repro.serving.batching.Scheduler` and the
  GraphServer.  ``verify`` is the speculative-decoding scoring pass
  (docs/SPECULATIVE.md).
"""
from __future__ import annotations

import threading
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import tracer as trace_mod
from ..core.metrics import (Counter, Histogram, MetricsRegistry,
                            NullRegistry)
from ..models.config import ArchConfig
from ..models.model import Model
from ..models.transformer import (DEFAULT_FLAGS, RuntimeFlags,
                                  check_hybrid_support,
                                  check_mixed_extend_support,
                                  check_paged_support)
from ..runtime.steps import (kernel_path, make_decode_step, make_extend_step,
                             make_hybrid_insert, make_paged_insert,
                             make_prefill_step, make_serve_decode_step,
                             make_slot_insert, make_state_extend_step,
                             make_state_rewind, make_state_verify_step,
                             make_verify_step)
from .observe import decode_span_args

#: cache layouts whose recurrent layers live in O(1) state slabs — decode
#: masks state commits per row, and verify returns per-position state
#: stacks for rewind (docs/STATE_CACHE.md)
STATE_KINDS = ("state", "hybrid")

#: JAX's monitoring event for one XLA backend compile (or load from the
#: persistent cache), with the jitted function's name as ``fun_name``
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_compile_lock = threading.Lock()
_compile_metrics: Optional[Tuple[Any, Any]] = None


def compile_metrics():
    """The process-wide ``(engine.compiles, engine.compile_ms)`` pair,
    fed by one listener on :data:`COMPILE_EVENT`, registered on first
    use.  A retrace compiles again and counts again.  JAX's event does
    not say which engine compiled, so every engine's registry shares
    these two instruments and counts the whole process's compiles."""
    global _compile_metrics
    with _compile_lock:
        if _compile_metrics is None:
            compiles = Counter(
                "engine.compiles", "XLA backend compiles in this "
                "process, retraces included, by jitted function")
            compile_ms = Histogram(
                "engine.compile_ms", "wall time of one XLA backend "
                "compile in this process, by jitted function (ms)")

            def on_duration(event, secs, fun_name="?", **_):
                if event == COMPILE_EVENT:
                    compiles.inc(fun=fun_name)
                    compile_ms.observe(secs * 1e3, fun=fun_name)

            jax.monitoring.register_event_duration_secs_listener(
                on_duration)
            _compile_metrics = (compiles, compile_ms)
        return _compile_metrics


class LLMEngine:
    def __init__(self, cfg: ArchConfig, params=None, *,
                 max_len: int = 512, seed: int = 0,
                 flags: RuntimeFlags = DEFAULT_FLAGS,
                 mesh=None):
        self.cfg = cfg
        self.model = Model(cfg)
        self.max_len = max_len
        # Tensor-parallel serving (docs/SHARDING.md): with a device mesh
        # the params are placed per sharding/rules.py::param_specs and
        # every backend's cache arena is allocated with
        # sharding/rules.py::cache_specs shardings (new_cache); jitted
        # serving steps then run SPMD-partitioned — GSPMD for the gather
        # paths, shard_map for the fused flash-decode kernel
        # (flags.decode_mesh).  Greedy tokens stay bit-identical to the
        # unsharded engine: head/expert parallelism never reorders any
        # per-token reduction.
        self.mesh = mesh
        self.tp = int(mesh.shape.get("model", 1)) if mesh is not None else 1
        if self.tp > 1:
            import dataclasses
            flags = dataclasses.replace(flags, decode_shards=self.tp,
                                        decode_mesh=mesh)
        self.flags = flags
        self._check_fused_vmem()
        if params is None:
            params = self.model.init(jax.random.PRNGKey(seed))
        if mesh is not None:
            from ..sharding.rules import param_specs
            params = jax.device_put(
                params, param_specs(self.model.template, mesh))
        self.params = params
        # Engine-side profiling registry (docs/OBSERVABILITY.md): the
        # process-wide compile counter and the kernel-path counter.
        # GraphServer.metrics() merges it with the scheduler's registry.
        # Under tracer.COMPILED_OUT the registry is a no-op sink and the
        # engine opens no profiler spans.
        if trace_mod.COMPILED_OUT:
            self.metrics: MetricsRegistry = NullRegistry()
        else:
            self.metrics = MetricsRegistry()
            self.metrics.share(*compile_metrics())
        self._span = trace_mod.span_factory(not trace_mod.COMPILED_OUT)
        self._prefill = jax.jit(make_prefill_step(self.model, max_len, flags))
        self._decode = jax.jit(make_decode_step(self.model, flags))
        # serving jits, built lazily per cache layout: key is
        # (backend.kind, block_size); extend steps add prefix_len,
        # verify steps add the window width 1+k
        self._serve: Dict[Tuple, Dict[str, Any]] = {}
        self._extend_steps: Dict[Tuple, Any] = {}
        self._verify_steps: Dict[Tuple, Any] = {}
        self._state_rewind = None       # built on first verify/truncate
        # per-(step, layout) bound kernel-path and page counters
        # (_observe_kernel runs on every decode tick; keep it off the
        # registry lookup path)
        self._kernel_obs: Dict[Tuple, Any] = {}

    @staticmethod
    def _layout(backend) -> str:
        return f"{backend.kind}/{getattr(backend, 'block_size', 0)}"

    def _observe_kernel(self, step: str, backend, positions=None,
                        active=None, block_tables=None) -> None:
        """Count which attention implementation served a decode/verify
        step (``fused`` flash-decode, ``paged`` paged-attention kernel,
        or the gather ``fallback``), so a silent fall-off the kernels
        shows up in ``metrics_text()``, not just as degraded
        throughput.  A ``paged`` decode step also adds to
        ``engine.decode_pages``: ``kind="read"``, the live pages of its
        active rows (what the kernel reads, besides one trash page per
        inactive row), and ``kind="table"``, rows x table width (what a
        page gather reads).  Runs on every decode tick: the dispatch
        decision, label sets and counter handles are resolved once per
        (step, layout) and cached."""
        if not self.metrics.enabled:
            return
        key = (step, backend.kind, getattr(backend, "block_size", 0))
        obs = self._kernel_obs.get(key)
        if obs is None:
            path = kernel_path(self.cfg, self.flags, backend.kind, step)
            ctr = self.metrics.counter(
                "engine.kernel_path",
                "decode/verify steps by attention implementation "
                "(fused flash-decode, paged-attention kernel, gather "
                "fallback)"
            ).bind(path=path, step=step, layout=self._layout(backend))
            pages = None
            if path == "paged" and step == "decode":
                c = self.metrics.counter(
                    "engine.decode_pages",
                    "paged decode steps' pages: live pages of the active "
                    "rows (read) and rows x table width (table)")
                pages = (c.bind(kind="read"), c.bind(kind="table"))
            obs = self._kernel_obs[key] = (ctr, pages)
        ctr, pages = obs
        ctr.inc()
        if pages is not None:
            rows, width = np.shape(block_tables)
            live = (np.asarray(positions)[np.asarray(active, bool)]
                    // backend.block_size + 1)
            pages[0].inc(int(np.minimum(live, width).sum()))
            pages[1].inc(rows * width)

    # ------------------------------------------------------------------
    # static-batch generation
    # ------------------------------------------------------------------
    def generate(self, tokens: np.ndarray, max_new_tokens: int = 16,
                 eos_id: Optional[int] = None) -> np.ndarray:
        """Greedy-decode a batch. tokens: [B, S] int32 -> [B, max_new]."""
        tokens = jnp.asarray(tokens, jnp.int32)
        B, S = tokens.shape
        batch = {"tokens": tokens}
        next_tok, cache = self._prefill(self.params, batch)
        out = [np.asarray(next_tok)]
        cur = next_tok[:, None]
        pos = S
        for _ in range(max_new_tokens - 1):
            cur, cache = self._decode(self.params, cur, cache,
                                      jnp.asarray(pos, jnp.int32))
            out.append(np.asarray(cur[:, 0]))
            pos += 1
            if eos_id is not None and bool((cur == eos_id).all()):
                break
        return np.stack(out, axis=1)

    def __call__(self, payload):
        """Engine interface for InferenceCalculator: payload is a dict
        {'tokens': [B,S] int32, 'max_new_tokens': int}."""
        return self.generate(payload["tokens"],
                             payload.get("max_new_tokens", 16))

    # ------------------------------------------------------------------
    # serving API (continuous batching over a CacheBackend)
    # ------------------------------------------------------------------
    def prefill(self, tokens: np.ndarray) -> Tuple[np.ndarray, Dict]:
        """Prefill [B, S] prompts; returns (first tokens [B], cache rows).
        All rows must share one length — the scheduler groups by length
        so padding never perturbs positions (exactness over utilisation).
        The ``engine.prefill`` span ends at the host sync on the first
        tokens."""
        rows, length = np.shape(tokens)
        with self._span("engine.prefill", tokens=int(length),
                        rows=int(rows)):
            next_tok, cache = self._prefill(
                self.params, {"tokens": jnp.asarray(tokens, jnp.int32)})
            return np.asarray(next_tok), cache

    def _check_fused_vmem(self) -> None:
        """On a TPU the gathered fused-decode variant stages a whole
        ``max_len`` K/V row in VMEM: refuse here a row Mosaic would
        refuse at compile time (the split-K variant has no such bound)."""
        from ..kernels.flash_decode import (GATHER_VMEM_LIMIT,
                                            gathered_vmem_bytes)
        from ..models.paging import use_fused_decode
        cfg, flags = self.cfg, self.flags
        if (flags.fused_split_k or cfg.use_mla
                or "attn" not in cfg.layer_kinds()
                or not use_fused_decode(cfg, flags)
                or jax.default_backend() != "tpu"):
            return
        need = gathered_vmem_bytes(
            self.max_len, cfg.num_kv_heads // flags.decode_shards,
            cfg.head_dim, jnp.dtype(cfg.dtype).itemsize)
        if need > GATHER_VMEM_LIMIT:
            raise ValueError(
                f"use_fused_decode: a {self.max_len}-token row needs about "
                f"{need >> 20} MiB of VMEM in the gathered variant, over "
                f"its {GATHER_VMEM_LIMIT >> 20} MiB cap; set "
                f"fused_split_k=True or lower max_len")

    def _check_paged(self, block_size: int) -> None:
        check_paged_support(self.cfg)
        if self.max_len % block_size != 0:
            raise ValueError(f"engine max_len {self.max_len} must be a "
                             f"multiple of block_size {block_size}")
        self.check_extend_support("paged")

    def _check_hybrid(self, block_size: int) -> None:
        check_hybrid_support(self.cfg)
        if self.max_len % block_size != 0:
            raise ValueError(f"engine max_len {self.max_len} must be a "
                             f"multiple of block_size {block_size}")
        self.check_extend_support("hybrid")

    def check_extend_support(self, backend_kind: str = "slot") -> None:
        """Prefix/chunked-extend prefill has no sequence-parallel path
        yet.  On the slot/paged layouts it additionally needs a
        pure-attention decoder stack; the state/hybrid layouts instead
        *continue the sequential state scan* for recurrent layers
        (docs/STATE_CACHE.md), so only per-layer attention limits remain.
        Paged/hybrid backends always need it; slot/state backends only
        with chunked prefill enabled.  ``use_flash`` routes the suffix
        attention through the Pallas flash kernel with a static
        ``q_offset`` — chunk-invariant bitwise because k-block partition
        boundaries are fixed at ``block_k`` multiples of absolute
        position (docs/KERNELS.md)."""
        if backend_kind in STATE_KINDS:
            check_mixed_extend_support(self.cfg)
        else:
            check_paged_support(self.cfg)
        if getattr(self.flags, "model_size", 1) > 1:
            raise ValueError("extend prefill is single-host for now "
                             "(prefix-extend attention is not "
                             "sequence-parallel)")

    def check_spec_support(self, backend_kind: str = "slot") -> None:
        """Speculative decoding verifies a multi-token window through the
        decode path.  Slot/paged layouts need a pure-attention decoder
        stack (their recurrent state has no rollback); the state/hybrid
        layouts verify recurrent layers through the sequential window
        pass with state stacks + rewind (docs/STATE_CACHE.md).  Neither
        has a sliding-window mask.  Verify windows run in-kernel under
        ``use_fused_decode`` (the fused flash-decode kernel masks each
        query at ``idx <= pos + s``) and through the page gather
        otherwise: the single-query paged-attention kernel serves plain
        decode steps only."""
        if backend_kind in STATE_KINDS:
            if self.cfg.sliding_window and "attn" in self.cfg.layer_kinds():
                raise ValueError("speculative decode has no "
                                 "sliding-window mask")
        else:
            check_paged_support(self.cfg)
        if getattr(self.flags, "model_size", 1) > 1:
            raise ValueError("speculative decode is single-host for now")

    def _serve_steps(self, backend) -> Dict[str, Any]:
        key = (backend.kind, getattr(backend, "block_size", 0))
        steps = self._serve.get(key)
        if steps is None:
            paged = backend.kind in ("paged", "hybrid")
            masked = backend.kind in STATE_KINDS
            if backend.kind == "hybrid":
                insert = make_hybrid_insert(self.model, backend.block_size)
            elif backend.kind == "paged":
                insert = make_paged_insert(backend.block_size)
            else:
                insert = make_slot_insert()
            steps = {
                "decode": jax.jit(make_serve_decode_step(
                    self.model, self.flags, paged=paged,
                    masked_state=masked)),
                "insert": jax.jit(insert),
            }
            self._serve[key] = steps
        return steps

    def new_cache(self, backend):
        """Zeroed decode cache in the backend's layout: ``num_slots``
        contiguous max_len rows (slot — the state layout shares it:
        recurrent slot caches already ARE O(1) state slabs), a
        ``num_blocks`` x ``block_size`` block-pool arena with trash
        block 0 (paged), or the per-layer mix of both (hybrid)."""
        if backend.kind == "paged":
            self._check_paged(backend.block_size)
            abstract = self.model.abstract_paged_cache(backend.num_blocks,
                                                       backend.block_size)
        elif backend.kind == "hybrid":
            self._check_hybrid(backend.block_size)
            abstract = self.model.abstract_hybrid_cache(
                backend.num_slots, backend.num_blocks, backend.block_size)
        else:
            abstract = self.model.abstract_cache(backend.num_slots,
                                                 self.max_len)
        if self.mesh is None:
            return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                abstract)
        # mesh-sharded arena: every leaf is allocated WITH its sharding
        # (sharding/rules.py::cache_specs — kv_heads across the model
        # axis for attention K/V, the recurrent-slab axes for state
        # leaves), so per-rank HBM holds 1/tp of each block from the
        # first byte.  Jitted steps preserve these shardings (GSPMD
        # propagates them through the scatter/gather; the leak fixture
        # in tests/conftest.py asserts no silent replication drift).
        # Zeros are made under jit with the output sharding, so each
        # rank allocates only its shard: no whole arena on one device.
        from ..sharding.rules import cache_specs
        specs = cache_specs(abstract, self.mesh)
        return jax.jit(lambda: jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), abstract),
            out_shardings=specs)()

    @property
    def mesh_desc(self) -> Dict[str, Any]:
        """JSON-able mesh shape for observability tags (metrics,
        flight-recorder incidents, scheduler debug_state)."""
        from ..launch.mesh import mesh_desc
        return mesh_desc(self.mesh)

    def cache_shards(self) -> int:
        """Factor by which ONE cache block's per-rank bytes shrink under
        the serving mesh — i.e. how many times more blocks the same
        per-rank HBM holds.  GraphServer scales its default paged-arena
        size by this (capacity reflects per-rank HBM × ranks, not a
        single chip — docs/SHARDING.md).  Attention K/V shards on
        kv_heads (or head_dim when kv heads don't divide); MLA's latent
        cache on its lora rank; a stack with no attention arena (pure
        recurrent) reports 1 — its O(1) slabs are not the capacity
        bound."""
        if self.mesh is None or self.tp <= 1:
            return 1
        cfg = self.cfg
        if "attn" not in cfg.layer_kinds():
            return 1
        if getattr(cfg, "use_mla", False):
            rank = getattr(cfg, "kv_lora_rank", 0) or 0
            return self.tp if rank % self.tp == 0 else 1
        if (cfg.num_kv_heads % self.tp == 0
                or cfg.head_dim % self.tp == 0):
            return self.tp
        return 1

    def insert(self, backend, cache, rows, row: int, dst):
        """Land prefilled cache row ``row`` of ``rows`` in the cache.
        ``dst`` is the backend's write ref: a slot index (slot/state
        layouts), a [max_len // block_size] int32 page-id vector (paged
        layout, 0 = skip page), or a ``(page_ids, slot)`` pair
        (hybrid)."""
        step = self._serve_steps(backend)["insert"]
        with self._span("engine.insert"):
            if backend.kind == "hybrid":
                page_ids, slot = dst
                return step(cache, rows, jnp.asarray(row, jnp.int32),
                            jnp.asarray(page_ids, jnp.int32),
                            jnp.asarray(slot, jnp.int32))
            return step(cache, rows, jnp.asarray(row, jnp.int32),
                        jnp.asarray(dst, jnp.int32))

    def decode(self, backend, cache, last_tokens: np.ndarray,
               positions: np.ndarray, active: np.ndarray,
               block_tables: Optional[np.ndarray] = None
               ) -> Tuple[np.ndarray, Dict]:
        """One greedy decode step across all slots.

        last_tokens/positions/active: [N] — each slot's most recent token,
        cache offset, and occupancy.  Paged backends pass their
        ``block_tables`` ([N, P] int32; inactive rows all-zero).  Returns
        ([N] next tokens, cache); inactive slots yield the pad token."""
        step = self._serve_steps(backend)["decode"]
        span = self._span
        with span("engine.decode", **(decode_span_args(positions, active)
                                      if trace_mod.profiling() else {})):
            with span("engine.decode.inputs"):
                args = (self.params,
                        jnp.asarray(last_tokens, jnp.int32)[:, None],
                        cache,
                        jnp.asarray(positions, jnp.int32),
                        jnp.asarray(active, bool))
                if backend.kind in ("paged", "hybrid"):
                    args += (jnp.asarray(block_tables, jnp.int32),)
            next_tok, cache = step(*args)
            with span("engine.decode.sync"):
                out = np.asarray(next_tok[:, 0])
        self._observe_kernel("decode", backend, positions, active,
                             block_tables)
        return out, cache

    def verify(self, backend, cache, tokens: np.ndarray,
               positions: np.ndarray, active: np.ndarray,
               block_tables: Optional[np.ndarray] = None
               ) -> Tuple[np.ndarray, Dict]:
        """Speculative verification: score a [N, 1+k] token window per
        slot (each row: last emitted token ++ k drafted tokens, padded
        with the pad id) in one forward pass.

        Returns ([N, 1+k] greedy argmax at every window position, cache).
        Row ``b``'s window occupies cache positions
        ``positions[b]..positions[b]+k`` — the caller must guarantee
        ``positions[b] + k < max_len`` for every slot (free slots
        included: their stray writes must stay in bounds) and, on paged
        backends, must have backed every position it intends to keep
        (unbacked pages trash-route their writes).  Compiled once per
        (layout, window width)."""
        width = int(np.asarray(tokens).shape[1])
        key = (backend.kind, getattr(backend, "block_size", 0), width)
        step = self._verify_steps.get(key)
        if step is None:
            step = jax.jit(make_verify_step(
                self.model, self.flags, paged=backend.kind == "paged"))
            self._verify_steps[key] = step
        args = (self.params, jnp.asarray(tokens, jnp.int32), cache,
                jnp.asarray(positions, jnp.int32),
                jnp.asarray(active, bool))
        if backend.kind == "paged":
            guess, cache = step(*args, jnp.asarray(block_tables, jnp.int32))
        else:
            guess, cache = step(*args)
        out = np.asarray(guess)
        self._observe_kernel("verify", backend)
        return out, cache

    def verify_window(self, backend, cache, tokens: np.ndarray,
                      positions: np.ndarray, active: np.ndarray,
                      block_tables: Optional[np.ndarray] = None):
        """:meth:`verify` for the state/hybrid layouts: same window
        contract, but recurrent state slabs are left *uncommitted* and
        per-position state stacks come back alongside — the backend's
        ``truncate`` commits the accepted prefix via
        :meth:`state_rewind` (docs/STATE_CACHE.md).  Returns
        ([N, 1+k] guesses, cache, stacks)."""
        width = int(np.asarray(tokens).shape[1])
        key = (backend.kind, getattr(backend, "block_size", 0), width,
               "stacks")
        step = self._verify_steps.get(key)
        if step is None:
            step = jax.jit(make_state_verify_step(
                self.model, self.flags, paged=backend.kind == "hybrid"))
            self._verify_steps[key] = step
        args = (self.params, jnp.asarray(tokens, jnp.int32), cache,
                jnp.asarray(positions, jnp.int32),
                jnp.asarray(active, bool))
        if backend.kind == "hybrid":
            guess, cache, stacks = step(
                *args, jnp.asarray(block_tables, jnp.int32))
        else:
            guess, cache, stacks = step(*args)
        out = np.asarray(guess)
        self._observe_kernel("verify", backend)
        return out, cache, stacks

    def state_rewind(self, cache, stacks, slot: int, idx: int):
        """Commit the state after window position ``idx`` (0-based) of
        row ``slot`` from ``stacks`` (returned by :meth:`verify_window`)
        into the live state slabs; attention leaves pass through.  One
        jitted function retraces per (layout, window width)."""
        if self._state_rewind is None:
            self._state_rewind = jax.jit(make_state_rewind(self.model))
        return self._state_rewind(cache, stacks,
                                  jnp.asarray(slot, jnp.int32),
                                  jnp.asarray(idx, jnp.int32))

    def extend(self, backend, cache, suffix_tokens: np.ndarray,
               prefix_len: int, ref) -> Tuple[np.ndarray, Dict]:
        """Chunked/prefix prefill: compute ``suffix_tokens`` (positions
        ``prefix_len`` on) against the request's cached prefix and write
        the new K/V back.  ``ref`` is the backend's write ref — a slot
        index (slot/state), a ``(table_row, page_ids)`` pair (paged), or
        a ``(table_row, page_ids, slot)`` triple (hybrid).  Returns
        ([1] next token after the suffix, cache).  Compiled per
        (layout, prefix_len, suffix shape)."""
        kind = backend.kind
        key = (kind, getattr(backend, "block_size", 0), int(prefix_len))
        step = self._extend_steps.get(key)
        if step is None:
            if kind in STATE_KINDS:
                step = jax.jit(make_state_extend_step(
                    self.model, int(prefix_len), self.flags,
                    block_size=backend.block_size if kind == "hybrid"
                    else 0,
                    max_cache_len=self.max_len))
            else:
                step = jax.jit(make_extend_step(
                    self.model, int(prefix_len), self.flags,
                    block_size=backend.block_size if kind == "paged"
                    else 0,
                    max_cache_len=self.max_len))
            self._extend_steps[key] = step
        suffix = jnp.asarray(suffix_tokens, jnp.int32)[None]
        if kind == "paged":
            table_row, page_ids = ref
            next_tok, cache = step(self.params, suffix, cache,
                                   jnp.asarray(table_row, jnp.int32),
                                   jnp.asarray(page_ids, jnp.int32))
        elif kind == "hybrid":
            table_row, page_ids, slot = ref
            next_tok, cache = step(self.params, suffix, cache,
                                   jnp.asarray(table_row, jnp.int32),
                                   jnp.asarray(page_ids, jnp.int32),
                                   jnp.asarray(slot, jnp.int32))
        else:
            next_tok, cache = step(self.params, suffix, cache,
                                   jnp.asarray(ref, jnp.int32))
        return np.asarray(next_tok), cache
