#!/usr/bin/env python3
"""Smoke test of the serving main path on a TPU, at full MiniCPM-2B width.

    python chip_smoke.py            # one chip: device, serve, kernels
    python chip_smoke.py --tp 4     # four chips: tensor-parallel serving only

One process drives every phase and starts no other.  Weights are random,
drawn from ``--seed``: the repository ships no checkpoint.

* **device** — prints the platform, device kind and count, and the JAX
  version; anything but a TPU fails here, with no CPU fallback.
* **serve** — ``LLMEngine(minicpm_2b, max_len=2048)`` in bf16 behind a
  paged ``GraphServer`` with 4 slots serves 8 requests (prompt lengths
  drawn from {256, 1024}, 32 new tokens each), compared per request with
  ``engine.generate``, the repository's exactness oracle.  Its decode
  attention is the default paged-attention kernel: the lowered decode
  step must hold it as a Mosaic ``tpu_custom_call``, and it must agree
  with its float32 oracle on random inputs at the serving shapes.
* **kernels** — the same requests through engines that share those
  params with the Pallas kernels on (flash prefill attention, fused
  RMSNorm, fused flash decode in its gathered and split-K variants),
  compared with the same oracle.  The lowered steps must hold each
  kernel as a Mosaic ``tpu_custom_call``, and each fused-decode variant
  must agree with its float32 oracle (``kernels/ref.py``) on random
  inputs at the serving shapes to within bf16 output rounding.
* **tp** (``--tp N`` only, and then nothing else) — the same requests
  through ``LLMEngine(mesh=make_serving_mesh(N))`` against the oracle of
  a one-chip engine on the first device, with the same params.

Comparison rule.  bf16 serving on the chip need not be bit-identical
across batch shapes or kernels, so a served token may differ from the
oracle's where the model's best two logits are closer than bf16 can
resolve.  The oracle's token streams are scored by one teacher-forced
forward each, in bf16 (the served dtype) and with float32 activations
and ``highest`` matmul precision on the same weights.  ``E`` is the
largest |bf16 - f32| logit difference over every request, step and
vocabulary entry.  A bf16 path whose logits stay within ``E`` of the
f32 ones picks, at every step, a token whose f32 logit is within
``tol = 2E`` of the f32 best.  Each compared path is held to that, twice:

* **free-running**: its own greedy stream; at the first step where it
  parts from the oracle (the causal prefix is the same up to there) its
  token must be within ``tol`` of the f32 best;
* **teacher-forced**: the path serves the requests again through the
  same ``GraphServer``, scheduler, paged arena and jitted steps, but the
  token fed back at every step is the oracle's (``ForcedEngine``); the
  token the path's own step picked must, at every one of the 8 x 32
  steps, equal the oracle's or be within ``tol`` of the f32 best.

Two guards keep the rule able to fail: every pick of the oracle itself
must be within ``tol`` (a tolerance that does not cover the oracle is
too tight), and on average no more than ``MAX_CANDIDATES`` tokens per
step may lie within ``tol`` of the best (a wider tolerance would let a
wrong token through).  Every logit of the scored streams must be finite
and every token in the vocabulary.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed
only when every phase passed.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "minicpm_2b"
MAX_LEN = 2048
PROMPT_LENS = (256, 1024)
NUM_REQUESTS = 8
NEW_TOKENS = 32
SLOTS = 4
BLOCK = 16
#: every slot can hold its longest request at once; the default arena
#: (num_slots full max_len rows) would not leave room on a 16 GB chip
#: for the un-donated arena copy an insert makes
NUM_BLOCKS = 1 + SLOTS * -(-(max(PROMPT_LENS) + NEW_TOKENS) // BLOCK)
#: seconds one GraphServer run may take to serve every request
SERVE_TIMEOUT = 300
#: the comparison rule's tolerance may admit at most this many tokens
#: per step on average (a uniformly random token: 1 in ~12,000)
MAX_CANDIDATES = 10
#: a kernel's bf16 output against its f32 oracle: half a bf16 ulp is at
#: most 2^-8 of a value; allow one ulp of the largest output
KERNEL_REL_TOL = 2.0 ** -7
KERNELS = {"flash": "_flash_kernel", "rmsnorm": "_rmsnorm_kernel",
           "fused": "_fused_gather_kernel",
           "fused_split_k": "_fused_splitk_kernel",
           "paged": "_paged_kernel"}


class CompileClock:
    """Seconds JAX spent compiling (or loading from the persistent
    cache) since start, from its own monitoring events."""

    def __init__(self, jax):
        from jax._src import dispatch
        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.secs = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.event:
            self.secs += secs
            self.count += 1


class ForcedEngine:
    """An ``LLMEngine`` as the serving stack sees it, with teacher
    forcing at its boundary: ``prefill`` and ``decode`` run the engine's
    own steps, record the token each request's step picked, and hand the
    scheduler the request's ``forced`` token for that step instead.
    Everything else is the wrapped engine's.

    Requests are told apart by prompt at prefill, and by the first arena
    page of their block table at decode (the paged backend only)."""

    def __init__(self, engine, prompts, forced):
        import numpy as np
        self._engine, self._np = engine, np
        self._prompts = prompts
        self._forced = forced
        self._by_prompt = {np.asarray(p, np.int32).tobytes(): i
                           for i, p in enumerate(prompts)}
        assert len(self._by_prompt) == len(prompts), "prompts must differ"
        self._by_page = {}
        self._pending = None
        self.picks = [np.full(len(f), -1, np.int64) for f in forced]

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def prefill(self, tokens):
        np = self._np
        first, rows = self._engine.prefill(tokens)
        i = self._by_prompt[np.asarray(tokens[0], np.int32).tobytes()]
        self.picks[i][0] = first[0]
        self._pending = i
        return np.asarray([self._forced[i][0]], first.dtype), rows

    def insert(self, backend, cache, rows, row, dst):
        self._by_page[int(self._np.asarray(dst)[0])] = self._pending
        return self._engine.insert(backend, cache, rows, row, dst)

    def decode(self, backend, cache, last_tokens, positions, active,
               block_tables=None):
        out, cache = self._engine.decode(backend, cache, last_tokens,
                                         positions, active, block_tables)
        out = out.copy()
        for b in self._np.nonzero(active)[0]:
            i = self._by_page[int(block_tables[b, 0])]
            t = int(positions[b]) - len(self._prompts[i]) + 1
            if last_tokens[b] != self._forced[i][t - 1]:
                raise RuntimeError(f"request {i} step {t}: fed "
                                   f"{last_tokens[b]}, forced "
                                   f"{self._forced[i][t - 1]}")
            self.picks[i][t] = out[b]
            out[b] = self._forced[i][t]
        return out, cache


class Smoke:
    def __init__(self, args):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.configs import get_config
        self.jax, self.jnp, self.np = jax, jnp, np
        self.args = args
        self.cfg = get_config(ARCH)
        self.clock = CompileClock(jax)
        rng = np.random.RandomState(args.seed)
        lens = rng.choice(PROMPT_LENS, size=NUM_REQUESTS)
        self.prompts = [rng.randint(0, self.cfg.vocab_size, size=int(n))
                        .astype(np.int32) for n in lens]
        self.failures = []

    # -- helpers -------------------------------------------------------
    def init_params(self):
        """Random weights from ``--seed`` at the published widths, drawn
        by the model's own initializer with every matrix at std
        1/sqrt(fan-in) within one layer: a projection's input rows, and
        heads x head_dim for the attention output.  (``Model.init``
        divides a layer-stacked matrix by sqrt(num_layers) instead.)"""
        import dataclasses
        from repro.models.model import Model
        from repro.models.params import ParamSpec, init_params
        jax, np = self.jax, self.np

        def per_layer(path, spec):
            if spec.init != "normal":
                return spec
            shape = spec.shape[1:] if spec.axes[0] == "layers" \
                else spec.shape
            fan_in = np.prod(shape[:-1]) if path[-1].key == "wo" \
                else shape[0]
            # init_params draws std scale / sqrt(spec.shape[0])
            return dataclasses.replace(
                spec, scale=spec.scale * float(np.sqrt(spec.shape[0]
                                                       / fan_in)))

        template = jax.tree_util.tree_map_with_path(
            per_layer, Model(self.cfg).template,
            is_leaf=lambda x: isinstance(x, ParamSpec))
        return init_params(template, jax.random.PRNGKey(self.args.seed),
                           self.cfg.dtype)

    def fail(self, msg):
        self.failures.append(msg)
        print(f"FAIL {msg}", flush=True)

    def peak_bytes(self):
        return (self.jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use")

    def serve(self, engine):
        """Serve every prompt through a paged GraphServer; returns the
        token arrays in request order."""
        from repro.serving import GraphServer
        with GraphServer(engine, num_slots=SLOTS, max_new_tokens=NEW_TOKENS,
                         backend="paged", block_size=BLOCK,
                         num_blocks=NUM_BLOCKS) as srv:
            handles = [srv.submit(p, request_id=f"r{i}")
                       for i, p in enumerate(self.prompts)]
            deadline = time.monotonic() + SERVE_TIMEOUT
            outs = [self.np.asarray(h.result(
                timeout=max(1.0, deadline - time.monotonic())))
                for h in handles]
        del srv
        gc.collect()
        return outs

    def check_outputs(self, name, outs):
        vocab = self.cfg.vocab_size
        for i, o in enumerate(outs):
            if len(o) != NEW_TOKENS or o.min() < 0 or o.max() >= vocab:
                self.fail(f"{name} request {i}: {len(o)} tokens, range "
                          f"[{o.min()}, {o.max()}] (want {NEW_TOKENS} "
                          f"in [0, {vocab}))")

    def scorer(self):
        """Jitted teacher-forced forward of a [1, L] sequence whose
        prompt ends at ``start``: for each of the NEW_TOKENS steps, the
        float32 logits (f32 activations, highest matmul precision), the
        max |bf16 - f32| logit difference and whether both are finite."""
        import dataclasses
        from repro.models.model import Model
        jax, jnp = self.jax, self.jnp
        vocab = self.cfg.vocab_size
        model = Model(self.cfg)
        ref = Model(dataclasses.replace(self.cfg, dtype="float32"))

        def window(m, params, tokens, start):
            # the padded vocabulary tail holds -1e30 masks, not logits
            logits = m.forward(params, tokens)[0][0, :, :vocab]
            return jax.lax.dynamic_slice_in_dim(
                logits, start, NEW_TOKENS, axis=0).astype(jnp.float32)

        @jax.jit
        def score(params, tokens, start):
            low = window(model, params, tokens, start)
            with jax.default_matmul_precision("highest"):
                high = window(ref, params, tokens, start)
            finite = jnp.isfinite(low).all(-1) & jnp.isfinite(high).all(-1)
            return high, jnp.abs(low - high).max(-1), finite

        return score

    def reference(self, engine):
        """The oracle: ``engine.generate`` per request, scored.  Sets the
        oracle's streams, their f32 logits and the tolerance."""
        np = self.np
        t0, c0 = time.perf_counter(), self.clock.secs
        self.ref = [np.asarray(engine.generate(p[None], NEW_TOKENS)[0])
                    for p in self.prompts]
        self.check_outputs("generate", self.ref)
        score = self.scorer()
        width = max(PROMPT_LENS) + NEW_TOKENS
        self.logits, errs = [], []
        for i, (p, o) in enumerate(zip(self.prompts, self.ref)):
            seq = np.zeros((1, width), np.int32)
            seq[0, :len(p)] = p
            seq[0, len(p):len(p) + len(o)] = o
            high, err, finite = (np.asarray(a) for a in score(
                engine.params, seq, np.int32(len(p) - 1)))
            if not finite.all():
                self.fail(f"generate request {i}: non-finite logits at "
                          f"steps {np.nonzero(~finite)[0].tolist()}")
            self.logits.append(high)
            errs.append(float(err.max()))
        self.top = [lg.max(-1) for lg in self.logits]
        err = max(errs)
        self.tol = 2 * err
        own = max(float(m.max()) for m in self.margins(self.ref))
        cands = np.mean([(lg >= top[:, None] - self.tol).sum(-1)
                         for lg, top in zip(self.logits, self.top)])
        top2 = np.concatenate([np.partition(lg, -2, -1)[:, -2:]
                               for lg in self.logits])
        print(f"oracle: E = max |bf16 - f32| logit {err:.5f} (per request "
              f"{[round(e, 4) for e in errs]}); tol = 2E = {self.tol:.5f}; "
              f"f32 top logit {top2[:, 1].min():.3f}-{top2[:, 1].max():.3f}"
              f", median top-2 gap "
              f"{float(np.median(top2[:, 1] - top2[:, 0])):.5f}; "
              f"{cands:.2f} tokens "
              f"per step within tol; the oracle's own worst f32 margin "
              f"{own:.5f}", flush=True)
        if own > self.tol:
            self.fail(f"oracle: its own pick is {own:.5f} under the f32 "
                      f"best, over tol = {self.tol:.5f}")
        if cands > MAX_CANDIDATES:
            self.fail(f"oracle: {cands:.2f} tokens per step within tol, "
                      f"over {MAX_CANDIDATES}: the rule cannot tell a "
                      f"wrong token from a near-tie")
        self.phase_end("oracle", t0, c0)

    def margins(self, picks):
        """Per request and step: f32 best logit minus the f32 logit of
        the picked token, on the oracle's causal prefix."""
        np = self.np
        return [top - lg[np.arange(len(p)), p]
                for lg, top, p in zip(self.logits, self.top, picks)]

    def check_path(self, name, engine):
        """Serve free-running and teacher-forced through ``engine`` and
        hold both to the comparison rule (module docstring)."""
        np = self.np
        got = self.serve(engine)
        self.check_outputs(name, got)
        exact, firsts, bad = 0, [], 0
        for i, (g, r) in enumerate(zip(got, self.ref)):
            diff = np.nonzero(g != r)[0]
            if diff.size == 0:
                exact += 1
                continue
            t = int(diff[0])
            m = float(self.top[i][t] - self.logits[i][t, g[t]])
            firsts.append((i, t, round(m, 5)))
            if m > self.tol:
                bad += 1
                self.fail(f"{name} request {i} step {t}: token {g[t]} is "
                          f"{m:.5f} under the f32 best, over tol "
                          f"{self.tol:.5f}")
        print(f"{name} free-running vs generate: {exact}/{len(got)} "
              f"identical; first differences (request, step, f32 margin) "
              f"{firsts}; {bad} over tol", flush=True)

        forced = ForcedEngine(engine, self.prompts, self.ref)
        self.serve(forced)
        if any((p < 0).any() for p in forced.picks):
            self.fail(f"{name} teacher-forced: some steps were not served")
        marg = self.margins([np.maximum(p, 0) for p in forced.picks])
        steps = NEW_TOKENS * len(self.ref)
        agree = sum(int((p == r).sum())
                    for p, r in zip(forced.picks, self.ref))
        off = np.concatenate([m[p != r] for m, p, r in
                              zip(marg, forced.picks, self.ref)])
        over = int((off > self.tol).sum())
        print(f"{name} teacher-forced on generate's tokens: {agree}/{steps}"
              f" steps agree; {len(off)} differ, worst f32 margin "
              f"{float(off.max()) if len(off) else 0.0:.5f}; {over} over "
              f"tol {self.tol:.5f}", flush=True)
        if over:
            self.fail(f"{name} teacher-forced: {over} of {steps} steps "
                      f"picked a token over tol under the f32 best")

    def kernels_compiled(self, name, engine, want):
        """Lower the engine's own prefill and decode steps and require
        each wanted kernel in them as a Mosaic custom call."""
        jax, jnp = self.jax, self.jnp
        from repro.runtime.steps import (make_prefill_step,
                                         make_serve_decode_step)
        model, flags = engine.model, engine.flags
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
        decode = jax.jit(make_serve_decode_step(model, flags, paged=True))
        prefill = jax.jit(make_prefill_step(model, MAX_LEN, flags))
        text = decode.lower(
            engine.params, i32(SLOTS, 1),
            model.abstract_paged_cache(NUM_BLOCKS, BLOCK), i32(SLOTS),
            jax.ShapeDtypeStruct((SLOTS,), jnp.bool_),
            i32(SLOTS, MAX_LEN // BLOCK)).as_text()
        text += prefill.lower(engine.params,
                              {"tokens": i32(1, PROMPT_LENS[0])}).as_text()
        found = {k: f'kernel_name = "{KERNELS[k]}"' in text for k in want}
        print(f"{name}: tpu_custom_call x{text.count('tpu_custom_call')}, "
              f"kernels {found}", flush=True)
        for k, ok in found.items():
            if not ok:
                self.fail(f"{name}: kernel {k} is not a tpu_custom_call")

    def decode_inputs(self):
        """Random bf16 decode inputs at the serving shapes: q, new K/V,
        the two arenas, block tables of distinct pages, and positions
        spread over the tables' live length."""
        jax, jnp, np = self.jax, self.jnp, self.np
        cfg = self.cfg
        H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        pages = MAX_LEN // BLOCK
        keys = jax.random.split(jax.random.PRNGKey(self.args.seed), 5)
        bf = jnp.bfloat16
        q = jax.random.normal(keys[0], (SLOTS, 1, H, hd), bf)
        k_new = jax.random.normal(keys[1], (SLOTS, 1, KV, hd), bf)
        v_new = jax.random.normal(keys[2], (SLOTS, 1, KV, hd), bf)
        arena = (NUM_BLOCKS, BLOCK, KV, hd)
        kp = jax.random.normal(keys[3], arena, bf)
        vp = jax.random.normal(keys[4], arena, bf)
        rows = np.random.RandomState(self.args.seed).permutation(
            np.arange(1, NUM_BLOCKS))[:SLOTS * (NUM_BLOCKS // SLOTS - 1)]
        tables = np.zeros((SLOTS, pages), np.int32)
        used = NUM_BLOCKS // SLOTS - 1
        tables[:, :used] = rows.reshape(SLOTS, used)
        positions = np.linspace(0, used * BLOCK - 1, SLOTS).astype(np.int32)
        return q, k_new, v_new, kp, vp, tables, positions

    def check_vs_oracle(self, name, parts, out, want):
        np = self.np
        for part, got, exp in zip(parts, out, want):
            got, exp = np.asarray(got, np.float32), np.asarray(exp)
            if part != "out":       # block 0 is the trash block
                got, exp = got[1:], exp[1:]
            err, bound = np.abs(got - exp).max(), \
                KERNEL_REL_TOL * np.abs(exp).max()
            print(f"{name}: {part} max |kernel - f32 oracle| {err:.6f} "
                  f"(bound {bound:.6f})", flush=True)
            if not err <= bound:
                self.fail(f"{name}: {part} off its f32 oracle by {err}")

    def paged_kernel_vs_oracle(self, name):
        """The paged-attention kernel against its f32 oracle on random
        bf16 inputs at the serving shapes."""
        jax, jnp = self.jax, self.jnp
        from repro.kernels import ops, ref
        q, _, _, kp, vp, tables, positions = self.decode_inputs()
        out = ops.paged_attention(q[:, 0], kp, vp, tables, positions)
        f32 = [a.astype(jnp.float32) for a in (q[:, 0], kp, vp)]
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref.paged_attention_ref)(*f32, tables, positions)
        self.check_vs_oracle(name, ("out",), (out,), (want,))

    def decode_kernel_vs_oracle(self, name, split_k):
        """The fused decode kernel against its f32 oracle on random bf16
        inputs at the serving shapes: attention output and the arena
        pages it scatters into."""
        jax, jnp = self.jax, self.jnp
        from repro.kernels import ops, ref
        cfg = self.cfg
        q, k_new, v_new, kp, vp, tables, positions = self.decode_inputs()
        out = ops.fused_flash_decode(q, k_new, v_new, kp, vp, tables,
                                     positions, rope_theta=cfg.rope_theta,
                                     split_k=split_k)
        f32 = [a.astype(jnp.float32) for a in (q, k_new, v_new, kp, vp)]
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref.fused_flash_decode_ref,
                           static_argnames=("rope_theta",))(
                *f32, tables, positions, rope_theta=cfg.rope_theta)
        self.check_vs_oracle(name, ("out", "k_pages", "v_pages"), out, want)

    def phase_end(self, name, t0, c0):
        print(f"{name}: {time.perf_counter() - t0:.1f}s wall, "
              f"{self.clock.secs - c0:.1f}s compiling "
              f"({self.clock.count} compiles so far), peak_bytes_in_use "
              f"{self.peak_bytes()}", flush=True)

    # -- phases --------------------------------------------------------
    def run_one_chip(self):
        from repro.models.transformer import RuntimeFlags
        from repro.serving import LLMEngine
        engine = LLMEngine(self.cfg, self.init_params(), max_len=MAX_LEN)
        self.reference(engine)
        t0, c0 = time.perf_counter(), self.clock.secs
        self.check_path("serve", engine)
        self.kernels_compiled("serve", engine, ["paged"])
        self.paged_kernel_vs_oracle("serve")
        self.phase_end("serve", t0, c0)

        for split_k in (False, True):
            name = "kernels/" + ("fused_split_k" if split_k else "fused")
            t0, c0 = time.perf_counter(), self.clock.secs
            flags = RuntimeFlags(use_flash=True, fused_rmsnorm=True,
                                 use_fused_decode=True, fused_split_k=split_k)
            keng = LLMEngine(self.cfg, params=engine.params,
                             max_len=MAX_LEN, flags=flags)
            self.check_path(name, keng)
            self.kernels_compiled(name, keng, [
                "flash", "rmsnorm",
                "fused_split_k" if split_k else "fused"])
            self.decode_kernel_vs_oracle(name, split_k)
            del keng
            gc.collect()
            self.phase_end(name, t0, c0)

    def run_tp(self, n):
        from repro.launch.mesh import make_serving_mesh
        from repro.serving import LLMEngine
        jax = self.jax
        devs = jax.devices()
        if len(devs) < n:
            self.fail(f"--tp {n} needs {n} devices, have {len(devs)}")
            return
        one = LLMEngine(self.cfg, self.init_params(), max_len=MAX_LEN)
        self.reference(one)
        t0, c0 = time.perf_counter(), self.clock.secs
        tp = LLMEngine(self.cfg, params=one.params, max_len=MAX_LEN,
                       mesh=make_serving_mesh(n, devices=devs[:n]))
        del one
        gc.collect()
        self.check_path(f"tp/{n}", tp)
        per_dev = {d.id: 0 for d in devs[:n]}
        for leaf in jax.tree.leaves(tp.params):
            for s in leaf.addressable_shards:
                per_dev[s.device.id] += s.data.nbytes
        for d in devs[:n]:
            stats = d.memory_stats() or {}
            print(f"  device {d.id}: params {per_dev[d.id]} bytes, "
                  f"bytes_in_use {stats.get('bytes_in_use')}, "
                  f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
        if len(set(per_dev.values())) != 1 or not all(per_dev.values()):
            self.fail(f"tp/{n}: params are not spread evenly: {per_dev}")
        self.phase_end(f"tp/{n}", t0, c0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp", type=int, default=0,
                    help="run only the tensor-parallel phase over N chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    devs = jax.devices()
    d0 = devs[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)} jax={jax.__version__}", flush=True)
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {d0.platform}",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    t0 = time.perf_counter()
    smoke = Smoke(args)
    try:
        if args.tp:
            smoke.run_tp(args.tp)
        else:
            smoke.run_one_chip()
    except Exception as e:  # noqa: BLE001 - reported, then exit non-zero
        import traceback
        traceback.print_exc()
        smoke.fail(f"{type(e).__name__}: {e}")
    print(f"total: {time.perf_counter() - t0:.1f}s wall, "
          f"{smoke.clock.secs:.1f}s compiling in {smoke.clock.count} "
          f"compiles", flush=True)
    if smoke.failures:
        print(f"chip_smoke: {len(smoke.failures)} failure(s)",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
