"""Serving tests: continuous batching on the ring engine (moved from
test_substrate), the paged block-table subsystem (allocator invariants,
paged flash-decode bit-identity, chunked prefill, preemption), the
continuously-batched :class:`~repro.serving.PagedServingEngine`, and the
synthetic traffic harness.

The allocator property tests use hypothesis when installed and the
deterministic conftest fallback otherwise (same API surface:
``given``/``settings`` + ``sampled_from``/``integers``/``floats``/
``booleans``).
"""
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from conftest import run_forced_devices_subprocess as _run_subprocess
from repro.configs import get_config, reduced_config
from repro.kernels import ops as kops
from repro.kernels.decode_attention import paged_block_pages
from repro.kernels.ref import decode_attention_paged_ref, decode_attention_ref
from repro.models import build_model
from repro.models.model import Model, block_apply
from repro.quant import QuantPlan, kernel_mode
from repro.serving import (BlockAllocator, PagedKVCache, PagedServingEngine,
                           PoolExhausted, Request, RequestStatus,
                           ServingEngine)

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced_config(get_config("gemma-2b"))
    m = build_model(cfg)
    params = m.init(KEY)
    return cfg, m, params


# ---------------------------------------------------------------------------
# ring-cache serving engine (moved from test_substrate.py)
# ---------------------------------------------------------------------------
class TestServingEngine:
    def test_continuous_batching_generates(self, small_model):
        cfg, m, params = small_model
        eng = ServingEngine(m, params, n_slots=3, max_len=64,
                            prefill_bucket=8)
        rng = np.random.default_rng(0)
        reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, 5 + i),
                        max_new_tokens=6 + i) for i in range(5)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_done(max_iters=200)
        assert all(r.done for r in reqs)
        for i, r in enumerate(reqs):
            assert len(r.generated) == 6 + i
        # more requests than slots -> continuous batching actually batched
        assert eng.stats.prefills == 5
        assert max(eng.stats.batch_occupancy) > 1 / 3

    def test_greedy_matches_stepwise_forward(self, small_model):
        """Engine greedy decode == naive full-forward argmax decode."""
        cfg, m, params = small_model
        prompt = np.array([5, 9, 2, 7], np.int32)
        eng = ServingEngine(m, params, n_slots=2, max_len=32,
                            prefill_bucket=4)
        req = Request(uid=0, prompt=prompt, max_new_tokens=5)
        eng.submit(req)
        eng.run_until_done(max_iters=50)

        toks = list(prompt)
        for _ in range(5):
            logits, _, _ = m.forward(params,
                                     {"inputs": jnp.asarray([toks])})
            toks.append(int(jnp.argmax(logits[0, -1])))
        assert req.generated == toks[len(prompt):]

    def test_bucket_padded_prefill_matches_exact(self, small_model):
        """Regression for pad-token leakage: bucket padding repeats the
        last prompt token, but those positions now carry the
        empty-slot sentinel (2**30) — the model must produce the exact
        logits and greedy continuation of an unpadded prefill."""
        cfg, m, params = small_model
        prompt = np.array([5, 9, 2, 7, 11], np.int32)          # len 5
        e_pad = ServingEngine(m, params, n_slots=1, max_len=32,
                              prefill_bucket=8)                # 3 pads
        e_exact = ServingEngine(m, params, n_slots=1, max_len=32,
                                prefill_bucket=5)              # no pad
        toks_pad = np.concatenate(
            [prompt, np.full(3, prompt[-1])]).astype(np.int32)
        lp, _ = e_pad._prefill_one(e_pad.params, e_pad.cache,
                                   jnp.asarray(toks_pad), 0, 5)
        le, _ = e_exact._prefill_one(e_exact.params, e_exact.cache,
                                     jnp.asarray(prompt), 0, 5)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(le),
                                   rtol=1e-5, atol=1e-5)

        r_pad = Request(uid=0, prompt=prompt, max_new_tokens=6)
        e_pad.submit(r_pad)
        e_pad.run_until_done(max_iters=50)
        r_exact = Request(uid=0, prompt=prompt, max_new_tokens=6)
        e2 = ServingEngine(m, params, n_slots=1, max_len=32,
                           prefill_bucket=5)
        e2.submit(r_exact)
        e2.run_until_done(max_iters=50)
        assert r_pad.generated == r_exact.generated

    def test_bucket_padded_prefill_sliding_window(self):
        """Pad entries must not consume sliding-window ring capacity:
        with prompt_len + pad > window, a naive ring write would evict
        real in-window tokens with masked pads (regression: the ring
        update now keeps the last `cap` VALID entries)."""
        cfg = reduced_config(get_config("gemma3-4b"))   # window 8
        assert cfg.sliding_window
        m = build_model(cfg)
        params = m.init(KEY)
        prompt = np.arange(1, 13, dtype=np.int32) % cfg.vocab  # len 12
        gens = []
        for bucket in (16, 12):                        # padded vs exact
            eng = ServingEngine(m, params, n_slots=1, max_len=32,
                                prefill_bucket=bucket)
            req = Request(uid=0, prompt=prompt, max_new_tokens=5)
            eng.submit(req)
            eng.run_until_done(max_iters=50)
            gens.append(req.generated)
        assert gens[0] == gens[1]

    def test_freed_slot_reuse_int8_cache_matches_fresh_engine(self):
        """Continuous-batching slot reuse with the int8 KV cache: a slot
        freed by a finished request and re-admitted must generate the
        same tokens as a fresh engine — pins the _set_pos_empty +
        quantized-cache (k/v + scales) reset interaction."""
        import dataclasses

        cfg = dataclasses.replace(reduced_config(get_config("gemma-2b")),
                                  kv_cache_dtype="int8")
        m = build_model(cfg)
        params = m.init(KEY)
        rng = np.random.default_rng(3)
        prompt_a = rng.integers(0, cfg.vocab, 6).astype(np.int32)
        prompt_b = rng.integers(0, cfg.vocab, 5).astype(np.int32)

        def generate(engine, prompt, uid):
            req = Request(uid=uid, prompt=prompt, max_new_tokens=6)
            engine.submit(req)
            engine.run_until_done(max_iters=50)
            return req.generated

        eng = ServingEngine(m, params, n_slots=1, max_len=64,
                            prefill_bucket=8)
        generate(eng, prompt_a, 0)          # occupies then frees slot 0
        reused = generate(eng, prompt_b, 1)  # re-admitted into slot 0
        fresh = ServingEngine(m, params, n_slots=1, max_len=64,
                              prefill_bucket=8)
        assert reused == generate(fresh, prompt_b, 1)

    def test_quant_plan_engine_generates(self, small_model):
        """Full-plan INT8 engine: whole decode path on QuantizedLinear
        leaves (oracle numerics on CPU) still serves correctly."""
        from repro.quant import plan_is_applied
        cfg, m, params = small_model
        eng = ServingEngine(m, params, n_slots=2, max_len=32,
                            prefill_bucket=4, quant_plan=QuantPlan.full())
        assert plan_is_applied(m.groups, eng.params, QuantPlan.full())
        req = Request(uid=0, prompt=np.array([5, 9, 2, 7], np.int32),
                      max_new_tokens=5)
        eng.submit(req)
        eng.run_until_done(max_iters=50)
        assert len(req.generated) == 5

    def test_submit_rejects_empty_prompt(self, small_model):
        """Regression: an empty prompt used to IndexError deep inside
        ``_admit`` (``req.prompt[-1]`` for bucket padding) mid-serve;
        submit now rejects it up front with a clear error."""
        cfg, m, params = small_model
        eng = ServingEngine(m, params, n_slots=1, max_len=32,
                            prefill_bucket=4)
        with pytest.raises(ValueError, match="empty prompt"):
            eng.submit(Request(uid=0, prompt=np.array([], np.int32)))
        assert not eng.queue

    def test_submit_rejects_prompt_that_would_wrap_cache(self, small_model):
        """Regression: a prompt whose bucket-padded length reaches
        max_len used to wrap the ring cache silently (the prefill write
        evicted the oldest prompt tokens, corrupting generations);
        submit now rejects it with a clear error."""
        cfg, m, params = small_model
        eng = ServingEngine(m, params, n_slots=1, max_len=16,
                            prefill_bucket=8)
        # len 12 pads to 16 == max_len -> wrap
        with pytest.raises(ValueError, match="ring cache would wrap"):
            eng.submit(Request(uid=0,
                               prompt=np.arange(12, dtype=np.int32) % 7))
        # len 9 pads to 16 too, even though 9 < max_len
        with pytest.raises(ValueError, match="ring cache would wrap"):
            eng.submit(Request(uid=1,
                               prompt=np.arange(9, dtype=np.int32) % 7))
        # len 7 pads to 8 < 16: admitted and served normally
        ok = Request(uid=2, prompt=np.arange(7, dtype=np.int32) % 7,
                     max_new_tokens=3)
        eng.submit(ok)
        eng.run_until_done(max_iters=20)
        assert len(ok.generated) == 3


# ---------------------------------------------------------------------------
# block allocator: property-style invariants
# ---------------------------------------------------------------------------
class TestBlockAllocator:
    @given(num_blocks=st.sampled_from([2, 5, 17, 64]),
           seed=st.integers(0, 7))
    @settings(deadline=None, max_examples=32)
    def test_random_alloc_free_conserves_pool(self, num_blocks, seed):
        """Random alloc/free interleavings: no double allocation, the
        free list + live blocks always partition the pool, the null
        block never leaks, and a full drain restores every block."""
        rng = np.random.default_rng((num_blocks, seed))
        alloc = BlockAllocator(num_blocks, block_size=4)
        held = []
        for _ in range(200):
            if held and rng.random() < 0.45:
                b = held.pop(int(rng.integers(len(held))))
                alloc.free(b)
            else:
                try:
                    b = alloc.alloc()
                except PoolExhausted:
                    assert alloc.n_free == 0
                    continue
                assert b not in held, "double allocation"
                assert b != 0, "null block handed out"
                held.append(b)
            alloc.check()
            assert alloc.n_used == len(held)
        for b in held:
            alloc.free(b)
        alloc.check()
        assert alloc.n_free == num_blocks - 1
        assert all(alloc.refcount(b) == 0 for b in range(num_blocks))

    @given(n_slots=st.sampled_from([1, 3, 4]), seed=st.integers(0, 7),
           tight=st.booleans())
    @settings(deadline=None, max_examples=32)
    def test_random_admit_evict_rollback_interleavings(self, n_slots, seed,
                                                      tight):
        """PagedKVCache under random ensure/release/failed-ensure
        sequences: ensure is atomic (a PoolExhausted grow changes
        nothing), tables and the allocator never disagree, and draining
        every slot returns the pool to fully free with zero refcounts.

        Host-only: model/device pools are not needed to exercise the
        bookkeeping, so the device tree is stubbed out.
        """
        class _NoCacheModel:
            def init_paged_cache(self, *a, **kw):
                return {}

        pc = PagedKVCache(_NoCacheModel(), n_slots, max_len=32,
                          block_size=4,
                          num_blocks=(1 + n_slots * 3 if tight else None))
        rng = np.random.default_rng((n_slots, seed, tight))
        tokens_of = np.zeros(n_slots, int)
        for _ in range(150):
            slot = int(rng.integers(n_slots))
            op = rng.random()
            if op < 0.5:                     # grow (admit / decode step)
                want = tokens_of[slot] + int(rng.integers(1, 9))
                before_free = pc.allocator.n_free
                before_have = int(pc.n_blocks_of[slot])
                before_row = pc.tables[slot].copy()
                try:
                    pc.ensure(slot, want)
                    tokens_of[slot] = want
                except PoolExhausted:        # rollback: nothing changed
                    assert pc.allocator.n_free == before_free
                    assert int(pc.n_blocks_of[slot]) == before_have
                    np.testing.assert_array_equal(pc.tables[slot],
                                                  before_row)
            else:                            # evict / finish
                freed = pc.release(slot)
                assert len(set(freed)) == len(freed)
                tokens_of[slot] = 0
            pc.allocator.check()
            # tables and allocator agree: every nonzero table entry is
            # a live block, counted exactly once
            live = [b for row in pc.tables for b in row if b != 0]
            assert len(set(live)) == len(live)
            assert len(live) == pc.allocator.n_used
        for slot in range(n_slots):
            pc.release(slot)
        pc.allocator.check()
        assert pc.allocator.n_used == 0
        assert pc.allocator.n_free == pc.allocator.num_blocks - 1
        assert (pc.tables == 0).all()

    def test_free_errors(self):
        alloc = BlockAllocator(4, block_size=2)
        b = alloc.alloc()
        alloc.free(b)
        with pytest.raises(ValueError, match="double free"):
            alloc.free(b)
        with pytest.raises(ValueError, match="invalid block"):
            alloc.free(0)
        with pytest.raises(ValueError, match="invalid block"):
            alloc.free(99)

    def test_refcounts_support_sharing(self):
        alloc = BlockAllocator(4, block_size=2)
        b = alloc.alloc()
        alloc.retain(b)
        alloc.free(b)                        # one ref left
        assert alloc.refcount(b) == 1
        assert alloc.n_free == 2             # not recycled yet
        alloc.free(b)
        assert alloc.n_free == 3
        alloc.check()

    def test_ensure_rejects_over_table_width(self):
        class _NoCacheModel:
            def init_paged_cache(self, *a, **kw):
                return {}

        pc = PagedKVCache(_NoCacheModel(), 2, max_len=16, block_size=4)
        with pytest.raises(PoolExhausted, match="table"):
            pc.ensure(0, 17)                 # 5 blocks > max_blocks=4
        assert pc.allocator.n_used == 0


# ---------------------------------------------------------------------------
# paged flash-decode kernel: bit-identity pins
# ---------------------------------------------------------------------------
def _ring_and_pages(B, S, KH, G, D, bs, seed, int8=False, n_empty=0,
                    lengths=None):
    """Build equivalent ring-layout and paged-layout KV caches.

    The paged pools use a seeded *permutation* of physical blocks (so
    the test actually exercises the block-table indirection, not an
    identity mapping) with block 0 reserved as the null block; rows can
    have fewer valid tokens (``lengths``) — their tail blocks stay
    mapped to the null block, exercising the unallocated-entry masking.
    """
    rng = np.random.default_rng(seed)
    assert S % bs == 0
    nb = S // bs
    q = jnp.asarray(rng.normal(size=(B, KH, G, D)), jnp.float32)
    k = rng.normal(size=(B, S, KH, D)).astype(np.float32)
    v = rng.normal(size=(B, S, KH, D)).astype(np.float32)
    pos = np.broadcast_to(np.arange(S, dtype=np.int32)[None], (B, S)).copy()
    if lengths is None:
        lengths = [S - n_empty * bs] * B
    for b, L in enumerate(lengths):
        pos[b, L:] = 2 ** 30                 # empty-slot sentinel
        k[b, L:] = 0.0
        v[b, L:] = 0.0
    q_pos = jnp.asarray([max(L - 1, 0) for L in lengths], jnp.int32)

    NB = 1 + B * nb
    perm = rng.permutation(np.arange(1, NB))
    k_pages = np.zeros((NB, bs, KH, D), np.float32)
    v_pages = np.zeros((NB, bs, KH, D), np.float32)
    pos_pages = np.full((NB, bs), 2 ** 30, np.int32)
    tables = np.zeros((B, nb), np.int32)
    i = 0
    for b, L in enumerate(lengths):
        for lb in range(-(-L // bs)):        # only blocks holding tokens
            p = int(perm[i]); i += 1
            tables[b, lb] = p
            k_pages[p] = k[b, lb * bs:(lb + 1) * bs]
            v_pages[p] = v[b, lb * bs:(lb + 1) * bs]
            pos_pages[p] = pos[b, lb * bs:(lb + 1) * bs]
    ring = dict(k=jnp.asarray(k), v=jnp.asarray(v), pos=jnp.asarray(pos))
    paged = dict(k_pages=jnp.asarray(k_pages), v_pages=jnp.asarray(v_pages),
                 pos_pages=jnp.asarray(pos_pages),
                 block_tables=jnp.asarray(tables))
    if int8:
        from repro.models.attention import _quantize_kv
        kq, ks = _quantize_kv(ring["k"])
        vq, vs = _quantize_kv(ring["v"])
        ring.update(k=kq, v=vq, k_scale=ks, v_scale=vs)
        kqp = np.zeros((NB, bs, KH, D), np.int8)
        vqp = np.zeros((NB, bs, KH, D), np.int8)
        ksp = np.zeros((NB, bs, KH), np.float32)
        vsp = np.zeros((NB, bs, KH), np.float32)
        for b in range(B):
            for lb in range(nb):
                p = int(tables[b, lb])
                if p == 0:
                    continue
                kqp[p] = np.asarray(kq)[b, lb * bs:(lb + 1) * bs]
                vqp[p] = np.asarray(vq)[b, lb * bs:(lb + 1) * bs]
                ksp[p] = np.asarray(ks)[b, lb * bs:(lb + 1) * bs]
                vsp[p] = np.asarray(vs)[b, lb * bs:(lb + 1) * bs]
        paged.update(k_pages=jnp.asarray(kqp), v_pages=jnp.asarray(vqp),
                     k_scale_pages=jnp.asarray(ksp),
                     v_scale_pages=jnp.asarray(vsp))
    # pools as the paged kernel reads them: a one-layer stack [1, NB, ...]
    paged = {k: a if k == "block_tables" else a[None]
             for k, a in paged.items()}
    return q, q_pos, ring, paged


def _compute_block(paged, bs):
    """The paged kernel's compute block in tokens for these tables."""
    nb = paged["block_tables"].shape[1]
    return paged_block_pages(bs, nb) * bs


class TestPagedDecodeKernel:
    """The paged kernel shares the online-softmax step and keep mask
    with the ring kernel, so at ``block_k`` = the paged kernel's compute
    block on equivalent layouts the two are *bit-identical* — and both match the dense oracle."""

    def _run_both(self, q, q_pos, ring, paged, bs, window=None):
        ring_out = kops.decode_attention(
            q, ring["k"], ring["v"], ring["pos"], q_pos,
            k_scale=ring.get("k_scale"), v_scale=ring.get("v_scale"),
            window=window, block_k=_compute_block(paged, bs), n_splits=1)
        paged_out = kops.decode_attention_paged(
            q, paged["k_pages"], paged["v_pages"], paged["pos_pages"],
            paged["block_tables"], q_pos, 0,
            k_scale_pages=paged.get("k_scale_pages"),
            v_scale_pages=paged.get("v_scale_pages"), window=window)
        return np.asarray(ring_out), np.asarray(paged_out)

    @pytest.mark.parametrize("G", [1, 4])    # MQA-per-kv-head vs GQA
    def test_fp_paged_equals_ring_equals_oracle(self, G):
        q, q_pos, ring, paged = _ring_and_pages(
            B=3, S=32, KH=2, G=G, D=8, bs=8, seed=0,
            lengths=[32, 17, 9])
        r, p = self._run_both(q, q_pos, ring, paged, bs=8)
        assert (r == p).all()
        oracle = np.asarray(decode_attention_ref(
            q, ring["k"], ring["v"], ring["pos"], q_pos))
        np.testing.assert_allclose(p, oracle, rtol=2e-5, atol=2e-5)
        paged_oracle = np.asarray(decode_attention_paged_ref(
            q, paged["k_pages"], paged["v_pages"], paged["pos_pages"],
            paged["block_tables"], q_pos, 0))
        np.testing.assert_allclose(p, paged_oracle, rtol=2e-5, atol=2e-5)

    def test_sliding_window_paged_equals_ring(self):
        q, q_pos, ring, paged = _ring_and_pages(
            B=2, S=32, KH=2, G=2, D=8, bs=8, seed=1, lengths=[32, 21])
        r, p = self._run_both(q, q_pos, ring, paged, bs=8, window=7)
        assert (r == p).all()
        oracle = np.asarray(decode_attention_ref(
            q, ring["k"], ring["v"], ring["pos"], q_pos, window=7))
        np.testing.assert_allclose(p, oracle, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("window", [15, 17, 24])
    def test_window_straddling_blocks_paged_equals_ring(self, window):
        """Windows that straddle 2–3 physical blocks (bs=8): the block
        skip condition must admit every partially-covered block on both
        layouts, and the in-block mask must then agree bit-for-bit."""
        q, q_pos, ring, paged = _ring_and_pages(
            B=3, S=64, KH=2, G=2, D=8, bs=8, seed=5,
            lengths=[64, 41, 26])
        r, p = self._run_both(q, q_pos, ring, paged, bs=8, window=window)
        assert (r == p).all()
        oracle = np.asarray(decode_attention_ref(
            q, ring["k"], ring["v"], ring["pos"], q_pos, window=window))
        np.testing.assert_allclose(p, oracle, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("window", [15, 17])
    def test_window_with_null_block_tail(self, window):
        """Sliding window interacting with the unallocated-entry mask:
        short rows leave their tail blocks mapped to the null block, so
        a window reaching back from q_pos must mask *both* out-of-window
        and never-written entries — and a row whose whole window fits in
        its last partial block must ignore the null block entirely."""
        q, q_pos, ring, paged = _ring_and_pages(
            B=3, S=48, KH=2, G=2, D=8, bs=8, seed=6,
            lengths=[48, 19, 9])
        tables = np.asarray(paged["block_tables"])
        assert (tables[1, 3:] == 0).all() and (tables[2, 2:] == 0).all()
        r, p = self._run_both(q, q_pos, ring, paged, bs=8, window=window)
        assert (r == p).all()
        oracle = np.asarray(decode_attention_ref(
            q, ring["k"], ring["v"], ring["pos"], q_pos, window=window))
        np.testing.assert_allclose(p, oracle, rtol=2e-5, atol=2e-5)

    def test_int8_kv_window_straddles_blocks(self):
        """int8-KV path with a 3-block-straddling window: per-block
        dequant scales must line up with the same mask on both layouts."""
        q, q_pos, ring, paged = _ring_and_pages(
            B=2, S=32, KH=2, G=4, D=8, bs=8, seed=7, int8=True,
            lengths=[32, 21])
        r, p = self._run_both(q, q_pos, ring, paged, bs=8, window=17)
        assert (r == p).all()

    def test_int8_kv_paged_equals_ring(self):
        q, q_pos, ring, paged = _ring_and_pages(
            B=3, S=32, KH=2, G=4, D=8, bs=8, seed=2, int8=True,
            lengths=[32, 13, 24])
        r, p = self._run_both(q, q_pos, ring, paged, bs=8)
        assert (r == p).all()

    def test_all_empty_rows_finite_and_match(self):
        """A row with no valid tokens (all-null block table) must stay
        finite and equal the ring kernel's all-empty behavior exactly."""
        q, q_pos, ring, paged = _ring_and_pages(
            B=2, S=16, KH=2, G=2, D=8, bs=8, seed=3, lengths=[16, 0])
        assert (np.asarray(paged["block_tables"])[1] == 0).all()
        r, p = self._run_both(q, q_pos, ring, paged, bs=8)
        assert np.isfinite(p).all()
        assert (r == p).all()

    def test_single_token_row(self):
        q, q_pos, ring, paged = _ring_and_pages(
            B=2, S=16, KH=2, G=2, D=8, bs=8, seed=4, lengths=[1, 16])
        r, p = self._run_both(q, q_pos, ring, paged, bs=8)
        assert (r == p).all()
        oracle = np.asarray(decode_attention_ref(
            q, ring["k"], ring["v"], ring["pos"], q_pos))
        np.testing.assert_allclose(p, oracle, rtol=2e-5, atol=2e-5)

    # Many-page compute blocks: 64-token pages, so a compute block holds
    # 8 pages (512 tokens) and these tables hold 2-3 blocks.
    def test_final_block_mixes_live_and_null_pages(self):
        """Rows end inside their last compute block, whose remaining
        table entries are the null block: the walk copies and masks
        them, bit for bit as the ring kernel's last block."""
        q, q_pos, ring, paged = _ring_and_pages(
            B=3, S=1024, KH=2, G=2, D=8, bs=64, seed=30, int8=True,
            lengths=[700, 1024, 513])
        tables = np.asarray(paged["block_tables"])
        assert _compute_block(paged, 64) == 512
        assert tables[0, 8] > 0 and (tables[0, 11:] == 0).all()
        r, p = self._run_both(q, q_pos, ring, paged, bs=64)
        assert (r == p).all()
        oracle = np.asarray(decode_attention_ref(
            q, ring["k"], ring["v"], ring["pos"], q_pos, k_scale=ring[
                "k_scale"], v_scale=ring["v_scale"]))
        np.testing.assert_allclose(p, oracle, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("window", [700, 1000])
    def test_window_starts_mid_compute_block(self, window):
        """A sliding window whose first visible position lies inside a
        compute block (the second for 700, so the walk skips the first;
        the first for 1000): the walk agrees with the ring kernel bit
        for bit."""
        q, q_pos, ring, paged = _ring_and_pages(
            B=2, S=1536, KH=2, G=2, D=8, bs=64, seed=31,
            lengths=[1400, 1536])
        r, p = self._run_both(q, q_pos, ring, paged, bs=64, window=window)
        assert (r == p).all()
        oracle = np.asarray(decode_attention_ref(
            q, ring["k"], ring["v"], ring["pos"], q_pos, window=window))
        np.testing.assert_allclose(p, oracle, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("kv", ["f32", "int8"])
    def test_one_token_full_and_empty_rows(self, kv):
        """One batch walks one block (a 1-token row), every block (a
        full table) and every block over the null block (a row that sees
        nothing keeps every block): all equal the ring kernel."""
        q, q_pos, ring, paged = _ring_and_pages(
            B=3, S=1024, KH=2, G=4, D=8, bs=64, seed=32,
            int8=kv == "int8", lengths=[1, 1024, 0])
        assert (np.asarray(paged["block_tables"])[2] == 0).all()
        r, p = self._run_both(q, q_pos, ring, paged, bs=64)
        assert np.isfinite(p).all()
        assert (r == p).all()

    @pytest.mark.parametrize("kv,window", [("f32", None), ("int8", None),
                                           ("f32", 300)])
    def test_table_not_a_multiple_of_compute_block(self, kv, window):
        """20 pages of 64 tokens against 8-page compute blocks: the last
        block pads the table with null entries.  The ring kernel needs
        whole blocks, so the oracle judges."""
        q, q_pos, ring, paged = _ring_and_pages(
            B=3, S=1280, KH=2, G=2, D=8, bs=64, seed=33,
            int8=kv == "int8", lengths=[1280, 1100, 40])
        nb = paged["block_tables"].shape[1]
        assert nb % paged_block_pages(64, nb) != 0
        p = np.asarray(kops.decode_attention_paged(
            q, paged["k_pages"], paged["v_pages"], paged["pos_pages"],
            paged["block_tables"], q_pos, 0,
            k_scale_pages=paged.get("k_scale_pages"),
            v_scale_pages=paged.get("v_scale_pages"), window=window))
        oracle = np.asarray(decode_attention_paged_ref(
            q, paged["k_pages"], paged["v_pages"], paged["pos_pages"],
            paged["block_tables"], q_pos, 0, window=window,
            k_scale_pages=paged.get("k_scale_pages"),
            v_scale_pages=paged.get("v_scale_pages")))
        np.testing.assert_allclose(p, oracle, rtol=2e-5, atol=2e-5)

    def _stacked_case(self, kv, window, S, bs, lengths):
        int8 = kv == "int8"
        layers = [_ring_and_pages(B=3, S=S, KH=2, G=2, D=8, bs=bs,
                                  seed=20 + l, int8=int8, lengths=lengths)
                  for l in range(3)]
        names = ["k_pages", "v_pages", "pos_pages"] + (
            ["k_scale_pages", "v_scale_pages"] if int8 else [])

        def cast(a):
            return a if int8 or a.dtype != jnp.float32 \
                else a.astype(jnp.bfloat16)

        stack = {n: jnp.concatenate([cast(pg[n]) for _, _, _, pg in layers])
                 for n in names}
        for l, (q, q_pos, ring, paged) in enumerate(layers):
            q = cast(q)
            one = {n: cast(paged[n]) for n in names}
            bt = paged["block_tables"]

            def call(fn, pools, layer):
                return np.asarray(fn(
                    q, pools["k_pages"], pools["v_pages"],
                    pools["pos_pages"], bt, q_pos, layer, window=window,
                    k_scale_pages=pools.get("k_scale_pages"),
                    v_scale_pages=pools.get("v_scale_pages")))

            out = call(kops.decode_attention_paged, stack, l)
            assert (out == call(kops.decode_attention_paged, one, 0)).all()
            ring_out = kops.decode_attention(
                q, cast(ring["k"]), cast(ring["v"]), ring["pos"], q_pos,
                k_scale=ring.get("k_scale"), v_scale=ring.get("v_scale"),
                window=window, block_k=_compute_block(paged, bs), n_splits=1)
            assert (out == np.asarray(ring_out)).all()
            oracle = call(decode_attention_paged_ref, stack, l)
            assert (oracle == call(decode_attention_paged_ref, one, 0)).all()
            np.testing.assert_allclose(
                out.astype(np.float32), oracle.astype(np.float32),
                rtol=2e-2 if kv == "bf16" else 2e-5,
                atol=2e-2 if kv == "bf16" else 2e-5)

    @pytest.mark.parametrize("kv,window", [("bf16", None), ("bf16", 7),
                                           ("int8", None), ("int8", 7)])
    def test_stacked_pools_at_layer_equal_one_layer(self, kv, window):
        """The kernel and its oracle read layer ``l`` of a 3-layer pool
        stack exactly as they read that layer's pools alone, and the
        kernel equals the ring kernel on the equivalent layout, bit for
        bit.  The other layers hold other sequences' pools under other
        tables, so a read of the wrong layer shows."""
        self._stacked_case(kv, window, S=32, bs=8, lengths=[32, 19, 9])

    @pytest.mark.parametrize("kv,window", [("bf16", None), ("int8", 600)])
    def test_stacked_pools_at_layer_many_blocks(self, kv, window):
        """The same with two 8-page compute blocks per table."""
        self._stacked_case(kv, window, S=1024, bs=64,
                           lengths=[1024, 700, 60])

    def test_tp_paged_decode_parity(self):
        """Head-parallel paged flash-decode (quant/tp.py) == unsharded
        kernel bit-for-bit at 1/2-way model meshes (forced host
        devices, so it runs in a subprocess like test_tp)."""
        out = _run_subprocess(textwrap.dedent("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.kernels import ops as kops
            from repro.quant import tp as _tp

            rng = np.random.default_rng(5)
            B, S, KH, G, D, bs = 2, 32, 4, 2, 8, 8
            nb, NB = S // bs, 1 + 2 * (S // bs)
            q = jnp.asarray(rng.normal(size=(B, KH, G, D)), jnp.float32)
            kp = rng.normal(size=(NB, bs, KH, D)).astype(np.float32)
            vp = rng.normal(size=(NB, bs, KH, D)).astype(np.float32)
            pp = np.full((NB, bs), 2 ** 30, np.int32)
            bt = np.zeros((B, nb), np.int32)
            lengths = [32, 19]
            perm = rng.permutation(np.arange(1, NB))
            i = 0
            for b, L in enumerate(lengths):
                for lb in range(-(-L // bs)):
                    p = int(perm[i]); i += 1
                    bt[b, lb] = p
                    valid = min(bs, L - lb * bs)
                    pp[p, :valid] = np.arange(lb * bs, lb * bs + valid)
            q_pos = jnp.asarray([L - 1 for L in lengths], jnp.int32)
            kp, vp = jnp.asarray(kp), jnp.asarray(vp)
            pp, bt = jnp.asarray(pp), jnp.asarray(bt)
            kp, vp, pp = kp[None], vp[None], pp[None]   # one-layer stack
            ref = np.asarray(kops.decode_attention_paged(
                q, kp, vp, pp, bt, q_pos, 0))
            for p in (1, 2):
                mesh = make_mesh((p,), ("model",))
                out = np.asarray(_tp.decode_attn_paged(
                    mesh, q, kp, vp, pp, bt, q_pos, 0))
                assert (out == ref).all(), p
            print("tp_paged OK")
        """), devices=2)
        assert "tp_paged OK" in out


# ---------------------------------------------------------------------------
# paged serving engine
# ---------------------------------------------------------------------------
def _requests(cfg, n, seed=0, out=4, max_prompt=20, temperature=0.0):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(1, cfg.vocab, int(
                        rng.integers(1, max_prompt))).astype(np.int32),
                    max_new_tokens=out, temperature=temperature, seed=7)
            for i in range(n)]


class TestPagedServingEngine:
    def _engine(self, m, params, **kw):
        kw.setdefault("n_slots", 4)
        kw.setdefault("max_len", 64)
        kw.setdefault("prefill_bucket", 16)
        kw.setdefault("block_size", 8)
        return PagedServingEngine(m, params, **kw)

    def test_continuous_batching_generates_and_drains_pool(self,
                                                           small_model):
        cfg, m, params = small_model
        eng = self._engine(m, params)
        reqs = _requests(cfg, 6, out=5)
        for r in reqs:
            eng.submit(r)
        eng.run_until_done(max_iters=300)
        assert all(r.status is RequestStatus.OK for r in reqs)
        assert all(len(r.generated) == 5 for r in reqs)
        # every block returned, refcounts zero at drain
        eng.paged.allocator.check()
        assert eng.paged.allocator.n_used == 0
        assert (eng.paged.tables == 0).all()
        assert eng.stats.prefill_chunks >= eng.stats.prefills

    def test_kv_walk_counter_sums_live_pages(self, small_model):
        """The walked-pages counter is, over every decode row of a short
        serve, the pages of the row's live compute blocks (64-token
        pages, 8 to a block, 16-entry tables), and the table counter is
        the decoding rows' table width; obs carries the same sums."""
        from repro.obs import Observability
        cfg, m, params = small_model
        obs = Observability()
        eng = self._engine(m, params, max_len=1024, block_size=64,
                           prefill_chunk=256, obs=obs)
        rng = np.random.default_rng(1)
        reqs = [Request(uid=i, prompt=rng.integers(1, cfg.vocab, n).astype(
                    np.int32), max_new_tokens=20)
                for i, n in enumerate((500, 30, 1000))]
        for r in reqs:
            eng.submit(r)
        eng.run_until_done(max_iters=200)
        assert all(r.status is RequestStatus.OK for r in reqs)
        nb, block = 16, 8 * 64
        assert paged_block_pages(64, nb) == 8
        # decode step j of a request attends its prompt and j tokens
        lens = [len(r.prompt) + j for r in reqs
                for j in range(1, len(r.generated))]
        walked = sum(min(nb, -(-n // block) * 8) for n in lens)
        assert walked < nb * len(lens)          # row 1 walks one block
        assert eng.stats.kv_pages_walked == walked
        assert eng.stats.kv_pages_table == nb * len(lens)
        assert obs.kv_pages_walked_total.value() == walked
        assert obs.kv_pages_table_total.value() == nb * len(lens)

    def test_greedy_matches_stepwise_forward(self, small_model):
        """Paged-engine greedy decode == naive full-forward argmax."""
        cfg, m, params = small_model
        prompt = np.array([5, 9, 2, 7], np.int32)
        eng = self._engine(m, params, prefill_chunk=4)
        req = Request(uid=0, prompt=prompt, max_new_tokens=5)
        eng.submit(req)
        eng.run_until_done(max_iters=50)
        toks = list(prompt)
        for _ in range(5):
            logits, _, _ = m.forward(params,
                                     {"inputs": jnp.asarray([toks])})
            toks.append(int(jnp.argmax(logits[0, -1])))
        assert req.generated == toks[len(prompt):]

    def test_chunked_prefill_matches_single_chunk(self, small_model):
        """A prompt prefilled in 4-token chunks generates exactly what a
        single-chunk prefill generates (the chunked path writes the
        same logical KV state)."""
        cfg, m, params = small_model
        prompt = np.arange(1, 14, dtype=np.int32) % cfg.vocab   # len 13
        gens = []
        for chunk in (16, 4):
            eng = self._engine(m, params, prefill_chunk=chunk)
            req = Request(uid=0, prompt=prompt, max_new_tokens=6)
            eng.submit(req)
            eng.run_until_done(max_iters=60)
            gens.append(req.generated)
        assert gens[0] == gens[1]

    def test_chunked_prefill_interleaves_with_decode(self, small_model):
        """While a long prompt prefills chunk-by-chunk, an already-
        running sequence keeps decoding — chunked prefill must not
        stall the decode batch (the ring engine's full-prompt prefill
        did)."""
        cfg, m, params = small_model
        eng = self._engine(m, params, prefill_chunk=4)
        a = Request(uid=0, prompt=np.array([3, 1, 4], np.int32),
                    max_new_tokens=12)
        eng.submit(a)
        eng.step()                           # a prefills and decodes
        b = Request(uid=1,
                    prompt=(np.arange(16, dtype=np.int32) % cfg.vocab) + 1,
                    max_new_tokens=2)
        eng.submit(b)
        done_before = len(a.generated)
        eng.step()                           # b chunk 1/4 + a decodes
        assert len(a.generated) == done_before + 1
        assert not b.generated               # still prefilling
        eng.run_until_done(max_iters=60)
        assert a.status is RequestStatus.OK and len(a.generated) == 12
        assert b.status is RequestStatus.OK and len(b.generated) == 2

    def test_block_granular_submit_bounds(self, small_model):
        """Satellite regression: admission is block-granular, not
        ring-bucket-granular.  With one block of headroom the boundary
        sits at capacity_tokens - 1 prompt tokens (one position must
        remain for the first decode write): 63 admits, 64 rejects on an
        8x8 table — and a 56-token prompt the ring engine rejects
        (pads to 64 == max_len) is admissible here."""
        cfg, m, params = small_model
        eng = self._engine(m, params)        # 8 blocks x 8 = 64 positions
        cap = eng.paged.capacity_tokens
        assert cap == 64
        with pytest.raises(ValueError, match="block table"):
            eng.submit(Request(uid=0, prompt=np.ones(cap, np.int32)))
        ok = Request(uid=1, prompt=np.ones(cap - 1, np.int32),
                     max_new_tokens=1)
        assert eng.submit(ok) is RequestStatus.QUEUED
        eng.run_until_done(max_iters=80)
        assert ok.status is RequestStatus.OK

        ring = ServingEngine(m, params, n_slots=1, max_len=64,
                             prefill_bucket=16)
        with pytest.raises(ValueError, match="ring cache would wrap"):
            ring.submit(Request(uid=2, prompt=np.ones(56, np.int32)))
        paged_ok = Request(uid=3, prompt=np.ones(56, np.int32),
                           max_new_tokens=2)
        eng2 = self._engine(m, params)
        assert eng2.submit(paged_ok) is RequestStatus.QUEUED
        eng2.run_until_done(max_iters=80)
        assert paged_ok.status is RequestStatus.OK

    def test_preemption_resumes_bitwise_greedy(self, small_model):
        """Under a tight pool the youngest sequence is evicted and later
        resumed by recompute; greedy generations match an engine with a
        roomy pool exactly, every request completes, and the pool
        drains clean."""
        cfg, m, params = small_model
        runs = []
        for num_blocks in (9, None):         # 8 allocatable vs roomy
            eng = self._engine(m, params, num_blocks=num_blocks,
                               prefill_chunk=8)
            reqs = _requests(cfg, 6, seed=1, out=6)
            for r in reqs:
                eng.submit(r)
            eng.run_until_done(max_iters=2000)
            assert all(r.status is RequestStatus.OK for r in reqs)
            eng.paged.allocator.check()
            assert eng.paged.allocator.n_used == 0
            runs.append((eng, [r.generated for r in reqs]))
        tight, roomy = runs
        assert tight[0].stats.preemptions >= 1
        assert roomy[0].stats.preemptions == 0
        assert tight[1] == roomy[1]

    def test_sole_sequence_pool_exhaustion_fails_not_stalls(self,
                                                            small_model):
        """A sequence that outgrows the whole pool with no victim to
        preempt fails typed (FAILED, not an engine stall/hang)."""
        cfg, m, params = small_model
        eng = self._engine(m, params, n_slots=1, num_blocks=3,
                           prefill_chunk=8)  # 2 allocatable = 16 positions
        req = Request(uid=0, prompt=np.ones(12, np.int32),
                      max_new_tokens=32)
        eng.submit(req)
        eng.run_until_done(max_iters=100)
        assert req.status is RequestStatus.FAILED
        assert "pool exhausted" in req.error
        eng.paged.allocator.check()
        assert eng.paged.allocator.n_used == 0

    def test_int8_kv_paged_engine_serves(self, small_model):
        """Full-plan INT8 engine on the paged cache: int8 block pools +
        scale side-tensors, flash-decode dequantizes in-kernel."""
        cfg, m, params = small_model
        eng = self._engine(m, params, n_slots=2,
                           quant_plan=QuantPlan.full())
        assert eng.kv_dtype == "int8"
        assert any("k_scale_pages" in g for g in eng.cache.values())
        reqs = _requests(cfg, 3, seed=5, out=4)
        for r in reqs:
            eng.submit(r)
        eng.run_until_done(max_iters=200)
        assert all(r.status is RequestStatus.OK for r in reqs)
        assert all(len(r.generated) == 4 for r in reqs)
        eng.paged.allocator.check()
        assert eng.paged.allocator.n_used == 0

    def test_freed_blocks_reused_clean(self, small_model):
        """Slot + block reuse: generations after a full drain/refill
        cycle equal a fresh engine's (pins the release-time position
        scrub — a reallocated block must never expose stale
        positions)."""
        cfg, m, params = small_model
        eng = self._engine(m, params, n_slots=1, prefill_chunk=8)

        def generate(engine, prompt, uid):
            req = Request(uid=uid, prompt=prompt, max_new_tokens=6)
            engine.submit(req)
            engine.run_until_done(max_iters=60)
            return req.generated

        rng = np.random.default_rng(3)
        prompt_a = rng.integers(1, cfg.vocab, 11).astype(np.int32)
        prompt_b = rng.integers(1, cfg.vocab, 9).astype(np.int32)
        generate(eng, prompt_a, 0)           # dirties + frees the blocks
        reused = generate(eng, prompt_b, 1)
        fresh = self._engine(m, params, n_slots=1, prefill_chunk=8)
        assert reused == generate(fresh, prompt_b, 1)

    def test_expiry_and_shutdown_release_blocks(self, small_model):
        cfg, m, params = small_model
        t = [0.0]
        eng = self._engine(m, params, clock=lambda: t[0])
        live = Request(uid=0, prompt=np.ones(9, np.int32),
                       max_new_tokens=64, deadline_s=5.0)
        eng.submit(live)
        eng.step()
        assert eng.paged.allocator.n_used > 0
        t[0] = 10.0                          # expire mid-decode
        eng.step()
        assert live.status is RequestStatus.TIMED_OUT
        assert eng.paged.allocator.n_used == 0
        eng.submit(Request(uid=1, prompt=np.ones(4, np.int32),
                           max_new_tokens=64))
        eng.step()
        assert eng.paged.allocator.n_used > 0
        eng.shutdown(drain=False)
        assert eng.paged.allocator.n_used == 0
        eng.paged.allocator.check()


class _SlicedPoolsModel(Model):
    """The layer scan before the pools rode its carry: every cache leaf,
    pools included, is scanned as ``xs``/``ys``, so each layer's pools
    are sliced out of the stack and written back whole (as one-layer
    stacks read at layer 0)."""

    def _stack(self, params, x, positions, caches, prefix_len,
               decode=False, train=False):
        new_caches = {}
        for gi, (spec, _) in enumerate(self.groups):
            def body(x, layer_in, spec=spec):
                lparams, lcache = layer_in
                pools = [k for k in lcache if k.endswith("_pages")]
                lcache = {**lcache, **{k: lcache[k][None] for k in pools},
                          "layer": jnp.zeros((), jnp.int32)}
                x, ncache, _, _ = block_apply(lparams, spec, self.cfg, x,
                                              positions, lcache, prefix_len)
                return x, {k: a[0] if k in pools else a
                           for k, a in ncache.items()}

            x, new_caches[f"group_{gi}"] = jax.lax.scan(
                body, x, (params[f"group_{gi}"], caches[f"group_{gi}"]))
        return (x, new_caches, jnp.zeros((), jnp.float32),
                jnp.zeros((0, 2), jnp.int32))


class TestCarriedPools:
    @pytest.mark.parametrize("kv,kernels", [("bf16", False),
                                            ("int8", False),
                                            ("int8", True)])
    def test_engine_bitwise_equals_sliced_pools_scan(self, small_model, kv,
                                                     kernels):
        """A tight-pool engine run (chunked prefill, decode, a preemption
        and its resume) hands out the same greedy tokens and fetches
        the same logits, bit for bit, whether the layer scan carries
        the stacked pools or slices each layer's pools as ``xs``/``ys``;
        on the jnp oracles and on the Pallas kernels (interpreted)."""
        cfg, m, params = small_model
        runs = []
        for model in (m, _SlicedPoolsModel(cfg)):
            fetched = []

            def record(phase, logits):
                fetched.append((phase, np.array(logits)))

            eng = PagedServingEngine(
                model, params, n_slots=4, max_len=64, prefill_bucket=16,
                block_size=8, num_blocks=9, prefill_chunk=8,
                quant_plan=QuantPlan.full() if kv == "int8" else None,
                fault_hook=record)
            reqs = _requests(cfg, 6, seed=1, out=6)
            for r in reqs:
                eng.submit(r)
            with kernel_mode(kernels):
                eng.run_until_done(max_iters=2000)
            assert all(r.status is RequestStatus.OK for r in reqs)
            assert eng.stats.preemptions >= 1
            runs.append(([r.generated for r in reqs], fetched))
        (carried, c_logits), (sliced, s_logits) = runs
        assert carried == sliced
        assert [p for p, _ in c_logits] == [p for p, _ in s_logits]
        for (_, a), (_, b) in zip(c_logits, s_logits):
            assert a.tobytes() == b.tobytes()


# ---------------------------------------------------------------------------
# traffic harness
# ---------------------------------------------------------------------------
def make_workload(n, load, seed, vocab, max_prompt=24, max_out=8):
    """Seeded Poisson request stream: exponential inter-arrival gaps of
    mean ``1/load`` engine steps, uniform prompt/output lengths.
    Returns ``[(arrival_step, Request)]``."""
    rng = np.random.default_rng(seed)
    t = 0.0
    out = []
    for i in range(n):
        t += rng.exponential(1.0 / load)
        L = int(rng.integers(1, max_prompt + 1))
        out.append((int(t), Request(
            uid=i, prompt=rng.integers(1, vocab, L).astype(np.int32),
            max_new_tokens=int(rng.integers(2, max_out + 1)), seed=7)))
    return out


def run_traffic(engine, workload, tick):
    """Submit ``workload`` on its arrival schedule, stepping the engine
    once per simulated step until everything drains.  ``tick`` is the
    mutable step counter behind the engine's injected clock."""
    pending = list(workload)
    while pending or engine.pending():
        while pending and pending[0][0] <= tick[0]:
            engine.submit(pending.pop(0)[1])
        engine.step()
        tick[0] += 1
        assert tick[0] <= 200_000, "traffic run did not drain"
    ok = [r for _, r in workload if r.status is RequestStatus.OK]
    return {"steps": tick[0], "completed": len(ok),
            "goodput": sum(len(r.generated) for r in ok) / max(1, tick[0]),
            "generations": {r.uid: r.generated for _, r in workload}}


class StaticBatchEngine(PagedServingEngine):
    """Head-of-line static batching over the same paged cache: admission
    only when every slot is free, so the batch advances in lockstep and
    drains fully before the next batch starts."""

    def _admit(self, now):
        if any(r is not None for r in self.slot_req):
            return
        super()._admit(now)


class TestTrafficHarness:
    def test_deterministic_and_conserves_tokens(self, small_model):
        """Fixed seed => identical metrics and generations across runs,
        and completed-token conservation: every OK request carries
        exactly max_new_tokens tokens, goodput * steps sums them."""
        cfg, m, params = small_model
        results = []
        for _ in range(2):
            with kernel_mode(False):
                tick = [0]
                eng = PagedServingEngine(
                    m, params, n_slots=4, max_len=64, prefill_bucket=16,
                    block_size=8, prefill_chunk=16,
                    clock=lambda: float(tick[0]))
                wl = make_workload(10, load=1.0, seed=17, vocab=cfg.vocab)
                results.append(run_traffic(eng, wl, tick))
        assert results[0] == results[1]
        metrics = results[0]
        wl_reqs = [r for _, r in make_workload(10, load=1.0, seed=17,
                                               vocab=cfg.vocab)]
        assert metrics["completed"] == 10
        expect = sum(r.max_new_tokens for r in wl_reqs)
        assert round(metrics["goodput"] * metrics["steps"]) == expect

    def test_continuous_equals_static_bitwise(self, small_model):
        """Scheduling must never change tokens: for a workload that fits
        both, continuously-batched serving and head-of-line static
        batching produce bitwise-identical generations per request —
        there is no cross-row pollution through the shared pools."""
        cfg, m, params = small_model
        gens = []
        for build in (PagedServingEngine, StaticBatchEngine):
            with kernel_mode(False):
                tick = [0]
                eng = build(m, params, n_slots=4, max_len=64,
                            prefill_bucket=16, block_size=8,
                            prefill_chunk=16, clock=lambda: float(tick[0]))
                wl = make_workload(8, load=2.0, seed=23, vocab=cfg.vocab)
                metrics = run_traffic(eng, wl, tick)
            assert metrics["completed"] == 8
            assert eng.stats.preemptions == 0
            gens.append(metrics["generations"])
        assert gens[0] == gens[1]


# ---------------------------------------------------------------------------
# serving launcher (repro.launch.serve) and its set-up
# ---------------------------------------------------------------------------
@pytest.fixture
def restore_cache_dir():
    """Put the compilation-cache setting back after a test that turns
    the cache on."""
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


class TestServeLauncher:
    ARGS = ["--arch", "deepseek-67b", "--int8", "--requests", "3",
            "--slots", "2", "--prompt-len", "6", "20", "--max-new", "4",
            "--prefill-chunk", "8"]

    @pytest.fixture(scope="class")
    def ds_model(self):
        return build_model(reduced_config(get_config("deepseek-67b")))

    def test_init_quantized_equals_quantize_of_init(self, ds_model):
        """Layer-by-layer init + quantize builds exactly the tree that
        quantizing the whole bf16 init builds."""
        plan = QuantPlan.full()
        want = ds_model.quantize(ds_model.init(KEY), plan)
        got = ds_model.init_quantized(KEY, plan)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert a.shape == b.shape and a.dtype == b.dtype
            assert (np.asarray(a) == np.asarray(b)).all()

    def test_memory_budget_counts_what_is_built(self, ds_model):
        from repro.launch import serve
        args = serve.parse_args(self.ARGS)
        plan = QuantPlan.full()
        b = serve.memory_budget(ds_model, plan, args.slots,
                                serve.max_len_for(args))
        params = ds_model.init_quantized(KEY, plan)
        eng = serve.build_engine(args, ds_model, params, plan)
        nbytes = serve._nbytes
        layers = [v for k, v in params.items() if k.startswith("group_")]
        assert b["layers_bytes"] == nbytes(layers)
        assert b["head_bytes"] + b["layers_bytes"] == nbytes(params)
        assert b["kv_bytes"] == nbytes(eng.cache)
        assert b["layers_fp_bytes"] > b["layers_bytes"]

    def test_main_serves_reduced_config_on_cpu(self, ds_model,
                                               restore_cache_dir):
        """On the CPU the launcher serves ``reduced_config`` on the
        paged engine; every greedy request gets its tokens."""
        from repro.launch import serve
        res = serve.main(self.ARGS)
        assert res["tokens_out"] == 3 * 4
        assert len(res["ttft_s"]) == 3
        assert res["decode_step_s"] and res["prefill_step_s"]

    def test_paged_steps_donate_the_pools(self, ds_model):
        """A step consumes the pool tree it was given: the engine holds
        one copy of the KV pools, not two."""
        from repro.launch import serve
        args = serve.parse_args(self.ARGS)
        plan = QuantPlan.full()
        eng = serve.build_engine(args, ds_model,
                                 ds_model.init_quantized(KEY, plan), plan)
        assert eng.paged.cache is None        # the engine owns the pools
        pools = [a for g in eng.cache.values() for k, a in g.items()
                 if "pages" in k]
        eng.submit(Request(uid=0, prompt=np.ones(5, np.int32),
                           max_new_tokens=2))
        eng.run_until_done(max_iters=20)
        assert len(pools) == 5 and all(a.is_deleted() for a in pools)


class TestCompileCache:
    def test_env_dir_wins_and_nothing_is_set(self, monkeypatch,
                                             restore_cache_dir):
        from repro.launch.compile_cache import enable_compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        jax.config.update("jax_compilation_cache_dir", None)
        assert enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir is None

    def test_default_is_fixed_repo_dir(self, monkeypatch, restore_cache_dir):
        from pathlib import Path

        from repro.launch.compile_cache import enable_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = Path(__file__).resolve().parents[1]
        want = str(repo / ".jax_cache")
        assert enable_compile_cache() == want
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want


# ---------------------------------------------------------------------------
# dispatch pins
# ---------------------------------------------------------------------------
class TestPagedDispatchPin:
    def test_full_plan_paged_decode_matches_manifest(self):
        """The paged decode step costs exactly the ring decode step's
        manifest schedule (6 fused Pallas dispatches per dense block at
        reduced dims) — the block-table indirection rides the existing
        flash-decode dispatch as scalar-prefetch operands, never as
        extra kernels — and dtype flow stays clean (no int32 to HBM, no
        XLA int8 dot, no XLA dequant).  Structural on the jaxpr — no
        kernel execution."""
        from repro.analysis import jaxpr_tools as jt
        from repro.analysis import manifest, passes

        cfg = reduced_config(get_config("gemma-2b"))
        m = build_model(cfg)
        assert m.groups == [(("attn", "dense"), 4)]
        qparams = m.quantize(m.init(KEY))
        cache = m.init_paged_cache(2, num_blocks=9, block_size=8,
                                   max_blocks=4)
        batch = {"inputs": jnp.ones((2, 1), jnp.int32)}
        with kernel_mode(True):
            jaxpr = jax.make_jaxpr(
                lambda p, b, c: m.decode_step(p, b, c))(qparams, batch,
                                                        cache)
        expected = manifest.model_sites(m, "decode", kv_len=32)
        assert sum(expected.values()) == 6               # the paper bar
        assert passes.dispatch_audit(jt.pallas_sites(jaxpr),
                                     expected) == []
        assert passes.dtype_flow_audit(jaxpr) == []
