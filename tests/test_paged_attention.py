"""Pallas paged-attention kernel vs its pure-JAX oracle (float32
tolerance), the pages it never reads, and the model-level paged decode
path vs the contiguous ``cache_pos`` path.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.kernels.ops import paged_attention
from repro.kernels.ref import paged_attention_ref
from repro.models import attention
from repro.serving import LLMEngine, PagedBackend, Scheduler, SlotBackend


def make_paged_inputs(rng, B, H, KV, hd, NB, bs, P, dtype=np.float32):
    q = jnp.asarray(rng.randn(B, H, hd), dtype)
    k = jnp.asarray(rng.randn(NB, bs, KV, hd), dtype)
    v = jnp.asarray(rng.randn(NB, bs, KV, hd), dtype)
    tbl = jnp.asarray(rng.randint(0, NB, size=(B, P)), jnp.int32)
    pos = jnp.asarray(rng.randint(0, P * bs, size=B), jnp.int32)
    return q, k, v, tbl, pos


def with_edge_rows(rng, tbl, pos, NB, bs):
    """Append three rows: one whose position ends a page, one whose
    position starts a page, and an inactive row (position 0, all-zero
    table: the trash block)."""
    P = tbl.shape[1]
    end = min(2, P) * bs - 1
    start = min(2, P - 1) * bs
    extra = np.zeros((3, P), np.int32)
    extra[:2] = rng.randint(1, NB, size=(2, P))
    tbl = jnp.concatenate([tbl, jnp.asarray(extra)])
    pos = jnp.concatenate([pos, jnp.asarray([end, start, 0], jnp.int32)])
    return tbl, pos


class TestPagedKernel:
    @pytest.mark.parametrize("B,H,KV,hd,NB,bs,P", [
        (3, 8, 2, 16, 10, 4, 5),     # GQA group 4
        (2, 4, 4, 32, 6, 8, 3),      # MHA (KV == H)
        (1, 6, 1, 64, 12, 16, 4),    # MQA, MXU-width head_dim
        (2, 4, 2, 96, 8, 4, 3),      # non-power-of-two head_dim
        (2, 36, 36, 64, 24, 16, 10),   # minicpm-2b's heads (MHA)
        (2, 32, 32, 128, 24, 16, 10),  # deepseek-7b's heads (MHA)
    ])
    def test_bit_exact_vs_ref(self, B, H, KV, hd, NB, bs, P):
        """Equal to the fully-gathered oracle up to float32 reduction
        order (the kernel's softmax is the online recurrence), for
        random rows plus a row ending a page, one starting a page and
        an inactive all-trash row."""
        rng = np.random.RandomState(B + H)
        q, k, v, tbl, pos = make_paged_inputs(rng, B, H, KV, hd, NB, bs, P)
        tbl, pos = with_edge_rows(rng, tbl, pos, NB, bs)
        q = jnp.concatenate([q, jnp.asarray(rng.randn(3, H, hd),
                                            jnp.float32)])
        ref = np.asarray(paged_attention_ref(q, k, v, tbl, pos))
        got = np.asarray(paged_attention(q, k, v, tbl, pos))
        assert got.shape == (B + 3, H, hd)
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-6)

    def test_trash_block_padding_is_masked(self):
        """Entries past ``positions`` — including block-table padding that
        points at the trash block 0 — must not affect the output."""
        rng = np.random.RandomState(0)
        q, k, v, tbl, _ = make_paged_inputs(rng, 2, 4, 2, 16, 8, 4, 4)
        # valid pages never name block 0 (the allocator reserves it)
        tbl = jnp.asarray(rng.randint(1, 8, size=(2, 4)), jnp.int32)
        pos = jnp.asarray([5, 9], jnp.int32)
        base = np.asarray(paged_attention(q, k, v, tbl, pos))
        # trash everything the mask should hide: rewrite trailing pages
        tbl2 = np.asarray(tbl).copy()
        tbl2[0, 2:] = 0
        tbl2[1, 3:] = 0
        k2 = k.at[0].set(777.0)      # block 0 content is arbitrary garbage
        v2 = v.at[0].set(-777.0)
        got = np.asarray(paged_attention(q, k2, v2,
                                         jnp.asarray(tbl2), pos))
        np.testing.assert_array_equal(got, base)

    def test_pages_past_the_bound_are_never_read(self):
        """NaN in the trash block, in every page past each row's live
        bound and in the last live page past the row's position: the
        output is finite and equal to the run without them, so none of
        it is read (a NaN read into a score or a value would spread)."""
        rng = np.random.RandomState(7)
        # nine pages a row: two page groups of the kernel's grid
        B, H, KV, hd, bs, P = 4, 4, 2, 16, 4, 9
        NB = 1 + B * P
        q, k, v, _, _ = make_paged_inputs(rng, B, H, KV, hd, NB, bs, P)
        tbl = np.arange(1, NB, dtype=np.int32).reshape(B, P)
        pos = np.asarray([0, bs - 1, 2 * bs + 1, P * bs - 1], np.int32)
        clean = np.asarray(paged_attention(q, k, v, jnp.asarray(tbl),
                                           jnp.asarray(pos)))
        k_nan, v_nan = np.asarray(k).copy(), np.asarray(v).copy()
        k_nan[0] = v_nan[0] = np.nan
        for b in range(B):
            live = pos[b] // bs + 1
            k_nan[tbl[b, live:]] = v_nan[tbl[b, live:]] = np.nan
            tail = tbl[b, live - 1]
            k_nan[tail, pos[b] % bs + 1:] = np.nan
            v_nan[tail, pos[b] % bs + 1:] = np.nan
        got = np.asarray(paged_attention(q, jnp.asarray(k_nan),
                                         jnp.asarray(v_nan),
                                         jnp.asarray(tbl), jnp.asarray(pos)))
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, clean)

    def test_schedule_bounds_the_grid(self):
        """The grid has one step per group of live pages, however wide
        the tables: the bound engages in the schedule, not only in the
        math."""
        from repro.kernels.paged_attention import _schedule
        tbl = jnp.arange(1, 1 + 3 * 64, dtype=jnp.int32).reshape(3, 64)
        pos = jnp.asarray([0, 16 * 9 + 3, 16 * 20], jnp.int32)
        steps, row, grp, pages = _schedule(tbl, pos, 16, 4)
        # live pages 1, 10, 21 -> groups 1, 3, 6
        assert int(steps) == 10
        np.testing.assert_array_equal(np.asarray(row)[:10],
                                      [0, 1, 1, 1, 2, 2, 2, 2, 2, 2])
        np.testing.assert_array_equal(np.asarray(grp)[:10],
                                      [0, 0, 1, 2, 0, 1, 2, 3, 4, 5])
        pages = np.asarray(pages).reshape(-1, 4)
        # row 0 holds one page; its other slots repeat it (no copy)
        np.testing.assert_array_equal(pages[0], [1, 1, 1, 1])
        # row 1's last group holds its 9th and 10th pages; the slots
        # past them keep the pages they held at the step before
        np.testing.assert_array_equal(pages[3], [65 + 8, 65 + 9, 65 + 6,
                                                 65 + 7])
        live = {int(p) for p in pages[:10].ravel()}
        assert live <= set(range(1, 2)) | set(range(65, 75)) \
            | set(range(129, 150))


class TestPagedDecodeModel:
    """Engine-level: paged decode (gather path and Pallas-kernel path)
    produces the same greedy tokens as ``generate``."""

    def _engine(self, **flag_kw):
        from repro.models.transformer import DEFAULT_FLAGS
        cfg = dataclasses.replace(get_config("minicpm_2b").reduced(),
                                  num_layers=2, d_model=128,
                                  vocab_size=512)
        flags = dataclasses.replace(DEFAULT_FLAGS, **flag_kw)
        return LLMEngine(cfg, max_len=32, seed=11, flags=flags)

    def _paged_generate(self, eng, prompt, n, bs=8):
        backend = PagedBackend(eng, 1, num_blocks=12, block_size=bs)
        cache = eng.new_cache(backend)
        P = eng.max_len // bs
        n_pages = -(-len(prompt) // bs)
        first, rows = eng.prefill(prompt[None])
        ids = np.zeros(P, np.int32)
        ids[:n_pages] = np.arange(1, n_pages + 1)
        cache = eng.insert(backend, cache, rows, 0, ids)
        table = np.zeros((1, P), np.int32)
        table[0, :n_pages] = np.arange(1, n_pages + 1)
        nxt_free = n_pages + 1
        toks = [int(first[0])]
        pos = np.array([len(prompt)], np.int32)
        last = np.array(toks, np.int32)
        for _ in range(n - 1):
            page = int(pos[0]) // bs
            if table[0, page] == 0:
                table[0, page] = nxt_free
                nxt_free += 1
            nt, cache = eng.decode(backend, cache, last, pos,
                                   np.array([True]), block_tables=table)
            pos += 1
            toks.append(int(nt[0]))
            last = nt
        return np.asarray(toks, np.int32)

    def test_gather_path_bit_identical(self):
        eng = self._engine()
        rng = np.random.RandomState(1)
        for L in (5, 9, 16):
            prompt = rng.randint(0, 512, size=L).astype(np.int32)
            ref = eng.generate(prompt[None], max_new_tokens=6)[0]
            got = self._paged_generate(eng, prompt, 6)
            np.testing.assert_array_equal(got, ref)

    def test_pallas_kernel_path_matches(self, monkeypatch):
        """The default engine's paged decode runs the Pallas kernel; on
        the CPU it lowers to the page gather, so this test swaps the
        interpreted kernel in.  Greedy tokens must match ``generate``."""
        monkeypatch.setattr(attention, "_paged_attention_cpu",
                            paged_attention)
        eng = self._engine()
        rng = np.random.RandomState(2)
        prompt = rng.randint(0, 512, size=7).astype(np.int32)
        ref = eng.generate(prompt[None], max_new_tokens=4)[0]
        got = self._paged_generate(eng, prompt, 4)
        np.testing.assert_array_equal(got, ref)
        assert 'path="paged"' in eng.metrics.to_prometheus()

    def test_kernel_serves_a_scheduler_batch(self, monkeypatch):
        """Served through the scheduler with the interpreted kernel in
        place of the CPU lowering: rows of different lengths, slots
        freed and refilled, every stream equal to ``generate``."""
        monkeypatch.setattr(attention, "_paged_attention_cpu",
                            paged_attention)
        eng = self._engine()
        rng = np.random.RandomState(6)
        prompts = [rng.randint(0, 512, size=L).astype(np.int32)
                   for L in (3, 8, 13)]
        sched = Scheduler(PagedBackend(eng, 2, num_blocks=12, block_size=8),
                          max_new_tokens=6)
        for i, p in enumerate(prompts):
            sched.submit({"tokens": p, "id": i})
        got = {}
        while sched.has_work():
            for ev in sched.admit() + sched.step():
                if ev.finished:
                    got[ev.request.id] = np.asarray(ev.request.tokens,
                                                    np.int32)
        for i, p in enumerate(prompts):
            ref = eng.generate(p[None], max_new_tokens=6)[0]
            np.testing.assert_array_equal(got[i], ref, err_msg=f"req {i}")

    def test_paged_cache_rejects_bad_shapes(self):
        eng = self._engine()
        with pytest.raises(ValueError, match="multiple"):
            # 32 % 5 != 0
            eng.new_cache(PagedBackend(eng, 1, num_blocks=8, block_size=5))


class TestFusedFlashDecodeModel:
    """Engine-level: ``use_fused_decode`` routes serving decode AND
    speculative verify through the fused flash-decode Pallas kernel on
    both cache layouts; greedy streams must stay bit-identical to the
    sequential ``generate`` reference, and the path taken must show up
    in the ``engine.kernel_path`` metric."""

    def _engine(self, **flag_kw):
        from repro.models.transformer import DEFAULT_FLAGS
        cfg = dataclasses.replace(get_config("minicpm_2b").reduced(),
                                  num_layers=2, d_model=128,
                                  vocab_size=512)
        flags = dataclasses.replace(DEFAULT_FLAGS, **flag_kw)
        return LLMEngine(cfg, max_len=64, seed=11, flags=flags)

    @staticmethod
    def _backend(eng, kind):
        if kind == "paged":
            return PagedBackend(eng, 2, num_blocks=65, block_size=8)
        return SlotBackend(eng, 2)

    @staticmethod
    def _run(eng, kind, prompts, max_new, **sched_kw):
        sched = Scheduler(TestFusedFlashDecodeModel._backend(eng, kind),
                          max_new_tokens=max_new, **sched_kw)
        for i, p in enumerate(prompts):
            sched.submit({"tokens": p, "id": i})
        got = {}
        while sched.has_work():
            for ev in sched.admit() + sched.step():
                if ev.finished:
                    got[ev.request.id] = np.asarray(ev.request.tokens,
                                                    np.int32)
        return got

    @pytest.mark.parametrize("kind", ["slot", "paged"])
    @pytest.mark.parametrize("split_k", [False, True])
    def test_decode_bit_identical(self, kind, split_k):
        eng = self._engine(use_fused_decode=True, fused_split_k=split_k)
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, 512, size=L).astype(np.int32)
                   for L in (5, 13, 17)]
        got = self._run(eng, kind, prompts, 8)
        for i, p in enumerate(prompts):
            ref = eng.generate(p[None], max_new_tokens=8)[0]
            np.testing.assert_array_equal(got[i], ref, err_msg=f"req {i}")

    @pytest.mark.parametrize("kind", ["slot", "paged"])
    def test_verify_window_bit_identical(self, kind):
        """Speculative verify windows run in-kernel: any draft — here a
        repeat-last-token guesser with mixed accept/reject — must leave
        the stream identical to sequential greedy."""
        eng = self._engine(use_fused_decode=True)

        def draft(ctx, k):
            return np.full(k, int(ctx[-1]), np.int32)

        rng = np.random.RandomState(4)
        prompts = [rng.randint(0, 512, size=L).astype(np.int32)
                   for L in (6, 11)]
        got = self._run(eng, kind, prompts, 8, speculate_k=3,
                        draft_fn=draft)
        for i, p in enumerate(prompts):
            ref = eng.generate(p[None], max_new_tokens=8)[0]
            np.testing.assert_array_equal(got[i], ref, err_msg=f"req {i}")

    def test_kernel_path_metric(self):
        """The fused engine reports path="fused" steps; the default
        engine reports path="paged" on a paged arena and
        path="fallback" on slot rows — the observability face of the
        dispatch seam."""
        rng = np.random.RandomState(5)
        prompt = [rng.randint(0, 512, size=7).astype(np.int32)]
        fused = self._engine(use_fused_decode=True)
        self._run(fused, "paged", prompt, 4)
        text = fused.metrics.to_prometheus()
        assert 'path="fused"' in text and 'path="fallback"' not in text
        plain = self._engine()
        self._run(plain, "paged", prompt, 4)
        text = plain.metrics.to_prometheus()
        assert 'path="paged"' in text and 'path="fallback"' not in text
        slot = self._engine()
        self._run(slot, "slot", prompt, 4)
        assert 'path="fallback"' in slot.metrics.to_prometheus()

    def test_mla_stack_falls_back(self):
        """MLA configs decode through the latent cache (mla.py); the
        dispatch predicate must refuse to fuse them even with the flag
        set."""
        from repro.models.transformer import DEFAULT_FLAGS
        from repro.runtime.steps import kernel_path
        flags = dataclasses.replace(DEFAULT_FLAGS, use_fused_decode=True)
        mla_cfg = get_config("deepseek_v3_671b").reduced()
        assert mla_cfg.use_mla
        assert kernel_path(mla_cfg, flags, "paged") == "fallback"
        attn_cfg = dataclasses.replace(get_config("minicpm_2b").reduced(),
                                       num_layers=2, d_model=128)
        assert kernel_path(attn_cfg, flags, "paged") == "fused"
