"""Continuous-batching serve stack: paged KV allocator invariants, paged
flash-attention parity against a dense oracle, prefix-sharing reuse, and
end-to-end engine behavior (no head-of-line stall, paged < dense KV bytes,
preemption, deprecation shims)."""
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.serve.kv_cache import SCRATCH_PAGE, PagedKVCache, cdiv


# =============================== allocator ===================================

def test_alloc_free_roundtrip():
    kv = PagedKVCache(num_pages=9, page_size=4)
    assert kv.free_pages == 8 and kv.pages_in_use == 0
    kv.allocate("a")
    assert kv.ensure("a", 10)            # 3 pages
    assert kv.pages_in_use == 3 and kv.length("a") == 10
    assert all(p != SCRATCH_PAGE for p in kv.table("a"))
    kv.advance("a", 1)
    assert kv.length("a") == 11
    kv.check_invariants()
    kv.free_seq("a")
    assert kv.pages_in_use == 0 and kv.free_pages == 8
    kv.check_invariants()


def test_ensure_all_or_nothing_rollback():
    kv = PagedKVCache(num_pages=5, page_size=4)   # 4 allocatable
    kv.allocate("a")
    assert kv.ensure("a", 8)             # 2 pages
    kv.allocate("b")
    assert kv.ensure("b", 4)             # 1 page -> 1 left
    before = kv.table("b")
    assert not kv.ensure("b", 12)        # needs 2 more, only 1 free
    assert kv.table("b") == before       # rolled back, nothing leaked
    assert kv.free_pages == 1
    kv.check_invariants()
    # the remaining page is still allocatable after the failed grow
    assert kv.ensure("b", 8)
    assert kv.free_pages == 0
    kv.check_invariants()


def test_double_free_raises():
    kv = PagedKVCache(num_pages=4, page_size=2)
    kv.allocate("a")
    assert kv.ensure("a", 2)
    kv.free_seq("a")
    with pytest.raises(KeyError):
        kv.free_seq("a")                 # table already gone
    kv.check_invariants()


def test_block_table_row_scratch_padded():
    kv = PagedKVCache(num_pages=6, page_size=2)
    kv.allocate("a")
    assert kv.ensure("a", 3)             # 2 pages
    row = kv.block_table_row("a", width=5)
    assert row.dtype == np.int32 and row.shape == (5,)
    assert list(row[:2]) == kv.table("a")
    assert all(p == SCRATCH_PAGE for p in row[2:])
    with pytest.raises(ValueError, match="width"):
        kv.block_table_row("a", width=1)


def test_prefix_sharing_reuse_counts():
    ps = 4
    kv = PagedKVCache(num_pages=12, page_size=ps)
    prompt = list(range(100, 111))       # 11 tokens = 2 full pages + 3

    kv.allocate("donor")
    assert kv.ensure("donor", len(prompt))
    added = kv.register_prefix("donor", prompt)
    assert added == 2 and kv.prefix_entries == 2
    donor_pages = kv.table("donor")

    # A sharer with the same prompt reuses BOTH full pages...
    pages, shared = kv.match_prefix(prompt)
    assert pages == donor_pages[:2] and shared == 2 * ps
    # ...but never the partial tail, and never ALL pages of an exact
    # page-multiple prompt (>= 1 token must remain to prefill).
    exact = list(range(100, 108))        # 8 tokens = 2 exact pages
    pages_e, shared_e = kv.match_prefix(exact)
    assert shared_e == ps and len(pages_e) == 1

    kv.allocate("sharer", shared_pages=pages, shared_tokens=shared)
    assert kv.stats.prefix_hit_tokens == shared
    assert kv.ensure("sharer", len(prompt))
    assert kv.table("sharer")[:2] == donor_pages[:2]      # physically shared
    assert kv.table("sharer")[2] != donor_pages[2]
    kv.check_invariants()

    # Shared pages survive the donor's exit (index + sharer hold refs)...
    kv.free_seq("donor")
    kv.check_invariants()
    again, shared2 = kv.match_prefix(prompt)
    assert again == donor_pages[:2] and shared2 == 2 * ps
    # ...and return to the pool only after every holder drops them.
    kv.free_seq("sharer")
    kv.check_invariants()
    assert kv.pages_in_use == 2          # prefix index still pins them

    kv.allocate("other", shared_pages=again, shared_tokens=shared2)
    with pytest.raises(ValueError, match="full pages"):
        kv.allocate("bad", shared_pages=again, shared_tokens=3)


def test_prefix_eviction_under_pressure():
    ps = 2
    kv = PagedKVCache(num_pages=4, page_size=ps)      # 3 allocatable
    prompt = [1, 2, 3]
    kv.allocate("donor")
    assert kv.ensure("donor", 3)                      # 2 pages
    kv.register_prefix("donor", prompt)
    kv.free_seq("donor")
    assert kv.pages_in_use == 1 and kv.prefix_entries == 1

    # Demand exceeding the free list reclaims the unreferenced prefix page.
    kv.allocate("big")
    assert kv.ensure("big", 6)                        # needs all 3 pages
    assert kv.stats.evictions == 1 and kv.prefix_entries == 0
    assert kv.pages_in_use == 3
    kv.check_invariants()
    # Pool exhausted and nothing evictable -> ensure refuses.
    kv.allocate("late")
    assert not kv.ensure("late", 1)


# ======================= paged attention vs dense oracle =====================

def _dense_oracle(q, kd, vd, q_start, lengths, causal, window):
    """Masked grouped-GQA softmax over DENSE per-request K/V (numpy f32); a
    query row with no key to attend to reads zeros."""
    b, h, tq, d = q.shape
    _, hkv, t, _ = kd.shape
    g = h // hkv
    scale = 1.0 / d ** 0.5
    out = np.zeros_like(q, dtype=np.float32)
    for bi in range(b):
        for hi in range(h):
            kv_h = hi // g
            s = (q[bi, hi].astype(np.float32)
                 @ kd[bi, kv_h].astype(np.float32).T) * scale    # (tq, t)
            qi = q_start[bi] + np.arange(tq)[:, None]
            ki = np.arange(t)[None, :]
            mask = np.broadcast_to(ki < lengths[bi], (tq, t)).copy()
            if causal:
                mask &= ki <= qi
            if window is not None:
                mask &= ki > qi - window
            s = np.where(mask, s, -1e30)
            p = np.exp(s - s.max(axis=1, keepdims=True))
            p = p / np.maximum(p.sum(axis=1, keepdims=True), 1e-30)
            p = np.where(mask.any(axis=1, keepdims=True), p, 0.0)
            out[bi, hi] = p @ vd[bi, kv_h].astype(np.float32)
    return out


def _pack_pages(pages):
    """(P, Hkv, ps, D) pages -> the pool layout of ``kv_page_shape``."""
    from repro.kernels.flash_attention import kv_page_shape
    p, hkv, ps, d = pages.shape
    _, groups, _, lanes = kv_page_shape(p, hkv, ps, d)
    rows = (pages.reshape(p, groups, hkv // groups, ps, d)
            .transpose(0, 1, 3, 2, 4).reshape(p, groups, ps, -1))
    return np.pad(rows, ((0, 0),) * 3 + ((0, lanes - rows.shape[-1]),))


def _paged_setup(rng, b, hkv, g, tq, t_max, d, ps):
    """Random pool + block tables + the dense K/V each table represents."""
    w = cdiv(t_max, ps)
    n_pages = 1 + b * w
    k_pages = rng.standard_normal((n_pages, hkv, ps, d)).astype(np.float32)
    v_pages = rng.standard_normal((n_pages, hkv, ps, d)).astype(np.float32)
    perm = rng.permutation(np.arange(1, n_pages))     # scrambled physical ids
    bt = perm[: b * w].reshape(b, w).astype(np.int32)
    kd = k_pages[bt].transpose(0, 2, 1, 3, 4).reshape(b, hkv, w * ps, d)
    vd = v_pages[bt].transpose(0, 2, 1, 3, 4).reshape(b, hkv, w * ps, d)
    q = rng.standard_normal((b, hkv * g, tq, d)).astype(np.float32)
    return q, _pack_pages(k_pages), _pack_pages(v_pages), bt, kd, vd


def test_kv_page_shape_packs_heads_into_whole_lane_rows():
    from repro.kernels.flash_attention import kv_page_shape
    assert kv_page_shape(9, 8, 16, 64) == (9, 4, 16, 128)     # granite
    assert kv_page_shape(9, 32, 16, 96) == (9, 8, 16, 384)    # phi3-mini
    assert kv_page_shape(9, 8, 16, 128) == (9, 8, 16, 128)
    assert kv_page_shape(9, 8, 16, 120) == (9, 8, 16, 128)    # h2o-danube3
    assert kv_page_shape(9, 2, 8, 24) == (9, 1, 8, 48)        # no whole row


@pytest.mark.parametrize("g,tq,window,d", [
    pytest.param(2, 1, None, 64, id="1-None"),
    pytest.param(2, 6, None, 64, id="6-None"),
    pytest.param(2, 4, 7, 64, id="4-7"),
    pytest.param(1, 1, None, 64, id="g1-decode"),
    pytest.param(4, 1, None, 64, id="g4-decode"),
    pytest.param(1, 6, None, 64, id="g1-tq6"),
    pytest.param(4, 6, None, 64, id="g4-tq6"),
    pytest.param(1, 64, None, 64, id="g1-chunk"),
    pytest.param(2, 64, None, 64, id="g2-chunk"),
    pytest.param(4, 64, None, 64, id="g4-chunk"),
    pytest.param(2, 64, 7, 64, id="g2-chunk-window"),
    pytest.param(2, 6, None, 128, id="g2-tq6-d128"),
    pytest.param(2, 6, None, 120, id="g2-tq6-d120"),
    pytest.param(2, 6, None, 24, id="g2-tq6-d24"),
])
def test_paged_flash_matches_dense_oracle(rng, g, tq, window, d):
    """Rows end mid-page and mid-block (16-token pages, 8-page blocks); the
    third row is an idle slot of length 0.  Every page no live range names
    (the scratch page, dead table slots, the idle row's pages) holds NaN in
    the kernel's pool: nothing outside the live ranges reaches the output."""
    from repro.kernels.flash_attention import paged_flash_attention
    from repro.models.attention import paged_attention_ref

    b, hkv, ps, t_max = 3, 4, 16, 200
    q, kp, vp, bt, kd, vd = _paged_setup(rng, b, hkv, g, tq, t_max, d, ps)
    q_start = np.array([5, 131, 0], np.int32)
    lengths = (q_start + tq) * np.array([1, 1, 0], np.int32)
    live_pages = {int(bt[bi, j]) for bi in range(b)
                  for j in range(cdiv(int(lengths[bi]), ps))}
    dead = np.array([p not in live_pages for p in range(kp.shape[0])])
    kp_nan = np.where(dead[:, None, None, None], np.nan, kp)
    vp_nan = np.where(dead[:, None, None, None], np.nan, vp)

    want = _dense_oracle(q, kd, vd, q_start, lengths, True, window)
    got_k = paged_flash_attention(
        jnp.asarray(q), jnp.asarray(kp_nan), jnp.asarray(vp_nan),
        jnp.asarray(bt), jnp.asarray(q_start), jnp.asarray(lengths),
        causal=True, window=window, interpret=True)
    got_r = paged_attention_ref(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
        jnp.asarray(q_start), jnp.asarray(lengths), causal=True,
        window=window)
    np.testing.assert_allclose(np.asarray(got_k), want, atol=2e-5, rtol=2e-5)
    live = lengths > 0          # the XLA reference averages an idle row
    np.testing.assert_allclose(np.asarray(got_r)[live], want[live],
                               atol=2e-5, rtol=2e-5)


def test_paged_kv_write_scatter(rng):
    from repro.kernels.flash_attention import kv_page_shape
    from repro.models.attention import paged_kv_write

    b, hkv, c, d, ps, w = 2, 4, 4, 64, 4, 3
    n_pages = 1 + b * w
    shape = kv_page_shape(n_pages, hkv, ps, d)
    kp = jnp.zeros(shape, jnp.float32)
    vp = jnp.zeros(shape, jnp.float32)
    bt = jnp.asarray(1 + np.arange(b * w).reshape(b, w), jnp.int32)
    q_start = jnp.asarray([2, 5], jnp.int32)
    n_valid = jnp.asarray([4, 2], jnp.int32)          # row 1: 2 dead slots
    k_new = jnp.asarray(rng.standard_normal((b, hkv, c, d)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((b, hkv, c, d)), jnp.float32)

    kp2, vp2 = paged_kv_write(kp, vp, k_new, v_new, bt, q_start, n_valid)
    kp2, vp2 = np.asarray(kp2), np.asarray(vp2)
    for bi in range(b):
        for i in range(int(n_valid[bi])):
            pos = int(q_start[bi]) + i
            pg, off = int(bt[bi, pos // ps]), pos % ps
            np.testing.assert_array_equal(kp2[pg, :, off].reshape(hkv, d),
                                          np.asarray(k_new)[bi, :, i])
            np.testing.assert_array_equal(vp2[pg, :, off].reshape(hkv, d),
                                          np.asarray(v_new)[bi, :, i])
    # Dead rows landed ONLY in the scratch page; real pages untouched
    # beyond the valid writes (count the nonzero rows).
    real = kp2[1:]
    assert (np.abs(real) > 0).any(axis=-1).sum() == (
        int(n_valid.sum()) * shape[1])


# ============================== engine e2e ===================================

@pytest.fixture(scope="module")
def engine_setup():
    from repro.configs import base as cb
    from repro.models.transformer import build_model

    cfg = cb.get("phi3-mini-3.8b", smoke=True)
    model = build_model(cfg, policy="bf16", remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompt(cfg, n, seed=0):
    r = np.random.default_rng(seed)
    return r.integers(2, cfg.vocab, (n,)).astype(np.int32)


def test_no_head_of_line_stall_and_kv_bytes(engine_setup):
    from repro.serve.engine import ServeEngine

    cfg, model, params = engine_setup
    eng = ServeEngine(model, params, max_len=64, max_batch=3, page_size=8)
    long_uid = eng.add_request(_prompt(cfg, 12, seed=1), max_new_tokens=24)
    s1 = eng.add_request(_prompt(cfg, 6, seed=2), max_new_tokens=3)
    s2 = eng.add_request(_prompt(cfg, 6, seed=3), max_new_tokens=3)

    finish_step = {}
    step = 0
    while eng.pending:
        for req in eng.step():
            finish_step[req.uid] = step
        step += 1
        assert step < 200
    # Short requests retire strictly before the long one: continuous
    # batching backfills their slots instead of waiting for the wave.
    assert finish_step[s1] < finish_step[long_uid]
    assert finish_step[s2] < finish_step[long_uid]

    steps = eng.step_telemetry
    assert [s.step for s in steps] == list(range(len(steps)))
    assert {s.phase for s in steps} <= {"prefill", "mixed", "decode"}
    assert sum(s.tokens for s in steps) == 24 + 3 + 3
    # Paged footprint strictly below the dense wave allocation throughout.
    assert all(s.kv_bytes < s.kv_bytes_dense for s in steps)
    assert all(s.kv_bytes == s.pages_in_use * 8 * eng._token_bytes
               for s in steps)
    eng.kv.check_invariants()
    assert eng.kv.live_sequences == 0            # everything retired


def test_prefix_sharing_and_output_parity(engine_setup):
    from repro.serve.engine import ServeEngine

    cfg, model, params = engine_setup
    prompt = _prompt(cfg, 20, seed=7)

    # Donor prefills the full prompt; a later sharer with the same prompt
    # reuses the donor's full KV pages and must emit the same greedy tokens.
    eng = ServeEngine(model, params, max_len=64, max_batch=3, page_size=8)
    donor = eng.add_request(prompt, max_new_tokens=4)
    done = {}
    while eng.pending:
        for r in eng.step():
            done[r.uid] = r.out_tokens
    assert eng.kv.prefix_entries == 2            # 16 of 20 tokens indexed
    sharer = eng.add_request(prompt, max_new_tokens=4)
    while eng.pending:
        for r in eng.step():
            done[r.uid] = r.out_tokens
    assert eng.kv.stats.prefix_hit_tokens == 16
    # Sharing is transparent: identical prompt => identical greedy tokens.
    assert done[sharer] == done[donor]
    eng.kv.check_invariants()


def test_preemption_requeues_and_completes(engine_setup):
    from repro.serve.engine import ServeEngine

    cfg, model, params = engine_setup
    # A pool too small for both requests' full lengths forces preemption.
    eng = ServeEngine(model, params, max_len=64, max_batch=2, page_size=8,
                      max_pages=8)
    a = eng.add_request(_prompt(cfg, 10, seed=4), max_new_tokens=16)
    b = eng.add_request(_prompt(cfg, 10, seed=5), max_new_tokens=16)
    done = {}
    steps = 0
    while eng.pending:
        for r in eng.step():
            done[r.uid] = r.out_tokens
        steps += 1
        assert steps < 300
    assert len(done[a]) == 16 and len(done[b]) == 16
    assert sum(s.preemptions for s in eng.step_telemetry) > 0
    eng.kv.check_invariants()


def test_preemption_mid_reserve_skips_evicted_slots(engine_setup):
    """Regression: with 3 live slots and a dry pool, the oldest slot's
    reservation evicts the newest; the reserve loop must then SKIP the
    freed slot instead of calling ensure() on it (KeyError on its gone
    block table).  One page per 8-token prompt fills the pool exactly, so
    the first decode step needs a page for every slot at once."""
    from repro.serve.engine import ServeEngine

    cfg, model, params = engine_setup
    eng = ServeEngine(model, params, max_len=64, max_batch=3, page_size=8,
                      max_pages=4)                 # 3 allocatable pages
    uids = [eng.add_request(_prompt(cfg, 8, seed=10 + i), max_new_tokens=4)
            for i in range(3)]
    done = {}
    steps = 0
    while eng.pending:
        for r in eng.step():
            done[r.uid] = r.out_tokens
        steps += 1
        assert steps < 300
    assert sorted(done) == sorted(uids)
    # The first decode step preempts BOTH newer slots (the oldest evicts
    # the newest for its page; the middle one then self-preempts).
    assert sum(s.preemptions for s in eng.step_telemetry) >= 2
    eng.kv.check_invariants()
    assert eng.kv.live_sequences == 0


def test_failed_admission_rolls_back_prefix_stats(engine_setup):
    """Regression: a sharer stuck at the queue head (its prompt doesn't
    fit) must not re-inflate prefix_hit_tokens on every step's admission
    attempt — only the one successful admission counts."""
    from repro.serve.engine import ServeEngine

    cfg, model, params = engine_setup
    prompt = _prompt(cfg, 20, seed=8)              # 2 full pages + 4 tokens
    eng = ServeEngine(model, params, max_len=64, max_batch=2, page_size=8,
                      max_pages=4)                 # 3 allocatable pages
    donor = eng.add_request(prompt, max_new_tokens=2)
    done = {}
    while eng.pending:
        for r in eng.step():
            done[r.uid] = r.out_tokens
    assert eng.kv.prefix_entries == 2              # 2 pinned + 1 free page
    # The blocker takes the last free page; the sharer's admission then
    # fails (its 2 shared pages are unreclaimable) until the blocker ends.
    blocker = eng.add_request(_prompt(cfg, 6, seed=9), max_new_tokens=2)
    sharer = eng.add_request(prompt, max_new_tokens=2)
    steps = 0
    while eng.pending:
        for r in eng.step():
            done[r.uid] = r.out_tokens
        steps += 1
        assert steps < 300
    assert {donor, blocker, sharer} <= set(done)
    assert eng.kv.stats.prefix_hit_tokens == 16    # counted exactly once
    assert done[sharer] == done[donor]
    eng.kv.check_invariants()


def test_engine_admission_errors(engine_setup):
    from repro.serve.engine import ServeEngine

    cfg, model, params = engine_setup
    eng = ServeEngine(model, params, max_len=32, max_batch=2, page_size=8)
    with pytest.raises(ValueError, match="max_len"):
        eng.add_request(_prompt(cfg, 40), max_new_tokens=2)
    tiny = ServeEngine(model, params, max_len=64, max_batch=2, page_size=8,
                       max_pages=3)
    with pytest.raises(ValueError, match="pages"):
        tiny.add_request(_prompt(cfg, 30), max_new_tokens=30)


def test_wave_shim_deprecation_and_guards(engine_setup):
    from repro.serve.engine import ServeEngine

    cfg, model, params = engine_setup
    with pytest.warns(DeprecationWarning, match="batch_size"):
        eng = ServeEngine(model, params, batch_size=2, max_len=32)
    with pytest.raises(RuntimeError, match="continuous"):
        eng.add_request(_prompt(cfg, 4))
    with pytest.raises(RuntimeError, match="continuous"):
        eng.step()

    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        ServeEngine(model, params, max_len=32, max_batch=2)   # no warning


def test_warm_prefixes_populates_index(engine_setup):
    from repro.serve.engine import ServeEngine

    cfg, model, params = engine_setup
    eng = ServeEngine(model, params, max_len=64, max_batch=3, page_size=8)
    sys_prompt = _prompt(cfg, 20, seed=11)
    assert eng.warm_prefixes([sys_prompt]) == 2  # 16 of 20 tokens -> 2 pages
    assert eng.kv.prefix_entries == 2
    # Warm-up leaves no live work and no telemetry behind.
    assert eng.step_telemetry == [] and eng._step_counter == 0
    assert eng.kv.live_sequences == 0
    eng.kv.check_invariants()

    # The first real request sharing the warmed system prompt skips its
    # full warmed pages.
    eng.add_request(np.concatenate([sys_prompt, _prompt(cfg, 6, seed=12)]),
                    max_new_tokens=2)
    while eng.pending:
        eng.step()
    assert eng.kv.stats.prefix_hit_tokens == 16
    eng.kv.check_invariants()


def test_warm_prefixes_parity_skips_and_guards(engine_setup):
    from repro.serve.engine import ServeEngine

    cfg, model, params = engine_setup
    prompt = _prompt(cfg, 20, seed=13)

    def drain(eng):
        done = {}
        while eng.pending:
            for r in eng.step():
                done[r.uid] = r.out_tokens
        return done

    cold = ServeEngine(model, params, max_len=64, max_batch=3, page_size=8)
    cold_uid = cold.add_request(prompt, max_new_tokens=4)
    cold_out = drain(cold)[cold_uid]

    warm = ServeEngine(model, params, max_len=64, max_batch=3, page_size=8)
    # Sub-page prompts can never be indexed: skipped, not an error.
    assert warm.warm_prefixes([prompt[:4]]) == 0
    assert warm.warm_prefixes([prompt]) == 2
    warm_uid = warm.add_request(prompt, max_new_tokens=4)
    # Sharing warmed pages is transparent: identical greedy tokens.
    assert drain(warm)[warm_uid] == cold_out
    assert warm.kv.stats.prefix_hit_tokens == 16

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        wave = ServeEngine(model, params, batch_size=2, max_len=32)
    with pytest.raises(RuntimeError, match="continuous"):
        wave.warm_prefixes([prompt])
