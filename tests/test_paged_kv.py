"""Paged KV memory plane (horovod_tpu/serving/paged_kv.py): paged vs
slab bit-parity (incl. staggered multi-slot, RoPE/GQA, slot/page reuse
after eviction), prefix-cache hit parity + accounting, refcount /
copy-on-write correctness, zero-retrace with paging on, pool-exhaustion
admission control (pause/resume, watermark), and the page-aware
router/capacity surfaces."""

import json
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def _cfg(**kw):
    from horovod_tpu.models.transformer import TransformerConfig

    base = dict(
        vocab_size=61,
        num_layers=1,
        d_model=16,
        num_heads=2,
        d_ff=32,
        max_len=64,
        causal=True,
        dtype=jnp.float32,
    )
    base.update(kw)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def toy():
    from horovod_tpu.models.transformer import Transformer

    model = Transformer(_cfg())
    params = model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32), train=False
    )
    return model, params


def _engine(toy, **kw):
    from horovod_tpu.serving.engine import InferenceEngine

    model, params = toy
    kw.setdefault("slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("min_bucket", 4)
    kw.setdefault("page_tokens", 16)
    return InferenceEngine(model, params, **kw)


def _greedy_ref(model, params, prompt, n):
    seq = list(map(int, prompt))
    for _ in range(n):
        lg = model.apply(params, jnp.asarray([seq]), train=False)
        seq.append(int(np.asarray(lg)[0, -1].argmax()))
    return seq[len(prompt):]


def _generate(engine, slot, prompt, n):
    out = [engine.prefill(slot, prompt)]
    for _ in range(n - 1):
        toks = np.zeros(engine.slots, np.int32)
        toks[slot] = out[-1]
        nxt = engine.decode_step(toks)
        engine.manager.advance(slot)
        out.append(int(nxt[slot]))
    return out


def _pool_factory(heads=2, head_dim=4, layers=1):
    return lambda pages, pt: [
        {
            "k": jnp.zeros((pages, pt, heads, head_dim)),
            "v": jnp.zeros((pages, pt, heads, head_dim)),
        }
        for _ in range(layers)
    ]


def _manager(**kw):
    from horovod_tpu.serving.paged_kv import PagedKVCacheManager

    kw.setdefault("page_tokens", 4)
    kw.setdefault("prefix_cache", True)
    return PagedKVCacheManager(_pool_factory(), **kw)


# ---------------------------------------------------------------- parity


def test_paged_vs_slab_greedy_bit_parity(toy):
    """THE acceptance property: greedy decode through the page pool is
    token-identical to the contiguous slab at every position."""
    model, params = toy
    paged = _engine(toy, paged=True)
    slab = _engine(toy, paged=False)
    prompt = [5, 7, 11, 13, 17, 19, 23]
    out_p = _generate(paged, paged.manager.alloc("p"), prompt, 8)
    out_s = _generate(slab, slab.manager.alloc("s"), prompt, 8)
    assert out_p == out_s == _greedy_ref(model, params, prompt, 8)


def test_pool_bytes_follow_pages_not_max_len(toy):
    """The footprint claim: the KV carry of an undersubscribed pool is
    smaller than the slab's, and the same pool at twice the max_len is
    byte for byte as large (only the page tables grow)."""

    def carry_bytes(engine):
        return sum(
            leaf.nbytes
            for leaf in jax.tree_util.tree_leaves(engine.manager.cache)
        )

    slab = _engine(toy, paged=False)
    pool = _engine(toy, paged=True, pages=8)
    pool2 = _engine(toy, paged=True, pages=8, max_len=128)
    assert carry_bytes(pool) == carry_bytes(pool2)
    assert carry_bytes(pool) < carry_bytes(slab)


def test_paged_decode_logits_bitwise_equal_to_slab(toy):
    """Stronger than token parity: the decode-step logits of the active
    row are BITWISE equal between layouts (pages tile max_len exactly,
    so shapes — and therefore reductions — match)."""
    from horovod_tpu.models.transformer import init_cache

    model, params = toy
    cfg = model.cfg
    slots, pt = 2, 16
    W = cfg.max_len // pt
    prompt = jnp.asarray([[9, 8, 7, 6, 5]], jnp.int32)

    slab = init_cache(cfg, slots, cfg.max_len)
    row = [{k: v[0:1] for k, v in layer.items()} for layer in slab]
    _, newrow = model.apply(
        params, prompt, train=False, cache=row, cache_index=jnp.array([0])
    )
    for layer, nl in zip(slab, newrow):
        for k in layer:
            layer[k] = layer[k].at[0:1].set(nl[k])

    pool = init_cache(cfg, slots * W, pt)
    tables = np.full((slots, W), slots * W, np.int32)
    tables[0] = [5, 2, 7, 0]  # scrambled physical order on purpose
    _, pool = model.apply(
        params, prompt, train=False, cache=pool,
        cache_index=jnp.array([0]), pages=jnp.asarray(tables[0:1]),
    )

    toks = jnp.asarray([[3], [0]], jnp.int32)
    lengths = jnp.asarray([5, 0], jnp.int32)
    lg_s, _ = model.apply(
        params, toks, train=False, cache=slab, cache_index=lengths
    )
    lg_p, _ = model.apply(
        params, toks, train=False, cache=pool, cache_index=lengths,
        pages=jnp.asarray(tables),
    )
    assert bool(jnp.all(lg_s[0] == lg_p[0]))


def test_paged_parity_staggered_multislot(toy):
    """Two sequences admitted at different times, decoding together
    through the shared pool: both streams stay exact."""
    model, params = toy
    eng = _engine(toy, paged=True)
    p1, p2 = [3, 5, 7], [11, 13, 17, 19, 21]
    s1 = eng.manager.alloc("a")
    out1 = [eng.prefill(s1, p1)]
    for _ in range(3):  # r1 decodes alone first
        toks = np.zeros(eng.slots, np.int32)
        toks[s1] = out1[-1]
        out1.append(int(eng.decode_step(toks)[s1]))
        eng.manager.advance(s1)
    s2 = eng.manager.alloc("b")  # staggered admission mid-stream
    out2 = [eng.prefill(s2, p2)]
    for _ in range(4):
        toks = np.zeros(eng.slots, np.int32)
        toks[s1], toks[s2] = out1[-1], out2[-1]
        nxt = eng.decode_step(toks)
        eng.manager.advance(s1)
        eng.manager.advance(s2)
        out1.append(int(nxt[s1]))
        out2.append(int(nxt[s2]))
    assert out1 == _greedy_ref(model, params, p1, 8)
    assert out2 == _greedy_ref(model, params, p2, 5)


def test_paged_parity_rope_gqa_variant():
    """The paged read/write composes with per-slot RoPE offsets and
    grouped-query heads exactly like the slab does."""
    from horovod_tpu.models.transformer import Transformer

    cfg = _cfg(num_heads=4, num_kv_heads=2, rope=True)
    model = Transformer(cfg)
    params = model.init(
        jax.random.PRNGKey(1), jnp.ones((1, 4), jnp.int32), train=False
    )
    toy = (model, params)
    prompt = [31, 33, 35, 37, 39]
    paged = _engine(toy, paged=True)
    slab = _engine(toy, paged=False)
    out_p = _generate(paged, paged.manager.alloc(), prompt, 6)
    out_s = _generate(slab, slab.manager.alloc(), prompt, 6)
    assert out_p == out_s == _greedy_ref(model, params, prompt, 6)


def test_paged_slot_and_page_reuse_after_eviction(toy):
    """A freed slot's pages recycle WITHOUT zeroing; the next occupant
    (and the next owner of those physical pages) still decodes exactly."""
    model, params = toy
    eng = _engine(
        toy, slots=1, paged=True, pages=4, prefix_cache=False
    )  # 4-page pool over a 64-token slot: reuse is guaranteed
    slot = eng.manager.alloc("a")
    _generate(eng, slot, [41, 43, 45, 47, 49, 51, 53], 12)
    eng.manager.free(slot)
    assert eng.manager.stats()["pages_free"] == 4  # all recycled
    slot2 = eng.manager.alloc("b")
    assert slot2 == slot
    out = _generate(eng, slot2, [2, 4], 6)
    assert out == _greedy_ref(model, params, [2, 4], 6)


def test_chunked_prefill_parity_with_paging(toy):
    model, params = toy
    eng = _engine(toy, paged=True, prefill_ceiling=8)
    prompt = list(np.random.default_rng(3).integers(1, 60, size=21))
    slot = eng.manager.alloc()
    out = _generate(eng, slot, prompt, 4)
    assert out == _greedy_ref(model, params, prompt, 4)
    assert eng.stats()["chunked_prefill_chunks"] == 2


# ---------------------------------------------------------- prefix cache


def test_prefix_hit_bit_parity_and_chunk_skip(toy):
    """A request sharing a cached prefix attaches pages instead of
    prefilling them — and its greedy stream is bit-identical to a cold
    prefill of the same tokens."""
    model, params = toy
    eng = _engine(toy, paged=True, page_tokens=8)
    p1 = list(range(1, 21))                  # 2 full pages + tail
    p2 = list(range(1, 21)) + [55, 56, 57]   # shares both full pages
    s1 = eng.manager.alloc("a")
    eng.prefill(s1, p1)
    s2 = eng.manager.alloc("b")
    out = [eng.prefill(s2, p2)]
    st = eng.stats()
    assert st["prefill_chunks_skipped"] == 2
    assert st["prefill_tokens_skipped"] == 16
    m = eng.manager.stats()
    assert m["prefix_hits"] == 2 and m["prefix_hit_requests"] == 1
    for _ in range(5):
        toks = np.zeros(eng.slots, np.int32)
        toks[s2] = out[-1]
        out.append(int(eng.decode_step(toks)[s2]))
        eng.manager.advance(s2)
        eng.manager.advance(s1)
    assert out == _greedy_ref(model, params, p2, 6)


def test_full_prefix_hit_still_recomputes_last_token(toy):
    """A prompt that is ENTIRELY cached (exact page multiple) must
    still recompute its final token — the first output's logits come
    from it — and the output stays exact."""
    model, params = toy
    eng = _engine(toy, paged=True, page_tokens=8)
    prompt = list(range(2, 18))  # 16 tokens = exactly 2 pages
    s1 = eng.manager.alloc("a")
    eng.prefill(s1, prompt)
    eng.manager.free(s1)
    s2 = eng.manager.alloc("b")
    out = _generate(eng, s2, prompt, 5)
    # only the FIRST page may hit: the cap keeps the last token (and
    # its page) recomputed, so no write ever lands in a shared page
    assert eng.stats()["prefill_chunks_skipped"] == 1
    assert out == _greedy_ref(model, params, prompt, 5)


def test_prefix_cache_off_never_hits(toy):
    eng = _engine(toy, paged=True, page_tokens=8, prefix_cache=False)
    prompt = list(range(1, 20))
    eng.prefill(eng.manager.alloc(), prompt)
    eng.prefill(eng.manager.alloc(), prompt)
    assert eng.stats()["prefill_chunks_skipped"] == 0
    assert eng.manager.stats()["prefix_hits"] == 0


def test_page_hashes_chain_commits_to_full_prefix():
    from horovod_tpu.serving.paged_kv import page_hashes

    a = page_hashes(np.arange(16), 4)
    b = page_hashes(np.arange(16), 4)
    assert a == b and len(a) == 4
    # same page-2 CONTENT under a different page-1 history: different
    # hash (the chain commits to the whole prefix, not the chunk)
    c = page_hashes(
        np.concatenate([np.arange(4) + 99, np.arange(4, 16)]), 4
    )
    assert c[1] != b[1] and c[2] != b[2]
    # a partial trailing chunk is never hashed
    assert len(page_hashes(np.arange(15), 4)) == 3


# --------------------------------------------------- refcounts, COW, LRU


def test_refcounts_shared_pages_survive_publisher_eviction():
    mgr = _manager(slots=3, max_len=16, num_pages=12)
    from horovod_tpu.serving.paged_kv import page_hashes

    prompt = np.arange(1, 9)  # 2 full pages
    hashes = page_hashes(prompt, 4)
    a = mgr.alloc("a")
    assert mgr.ensure_pages(a, 8)
    mgr.set_length(a, 8)
    mgr.publish_prefix(a, hashes)
    page0 = int(mgr.table_row(a)[0])
    # a second slot attaches the shared prefix
    b = mgr.alloc("b")
    hits = mgr.lookup_prefix(hashes)
    assert len(hits) == 2
    mgr.attach_prefix(b, hits)
    # publisher retires: shared pages must NOT free (slot b + index)
    mgr.free(a)
    assert int(mgr._ref[page0]) == 2  # slot b + index hold
    mgr.free(b)
    assert int(mgr._ref[page0]) == 1  # index only — reclaimable now
    assert mgr.stats()["pages_cached"] == 2
    assert mgr.free_pages_available() == 12


def test_lru_eviction_only_at_refcount_zero():
    mgr = _manager(slots=2, max_len=16, num_pages=4)
    from horovod_tpu.serving.paged_kv import page_hashes

    h1 = page_hashes(np.arange(1, 9), 4)      # 2 pages
    a = mgr.alloc("a")
    assert mgr.ensure_pages(a, 8)
    mgr.publish_prefix(a, h1)
    # slot a still holds its pages: they are published but NOT
    # reclaimable, so a demand for 3 more pages must fail...
    b = mgr.alloc("b")
    assert not mgr.ensure_pages(b, 12)
    assert mgr.stats()["page_evictions"] == 0
    # ...until a retires: now the index-only pages LRU-evict to serve b
    mgr.free(a)
    assert mgr.ensure_pages(b, 12)
    assert mgr.stats()["page_evictions"] >= 1
    assert mgr.lookup_prefix(h1) == []  # evicted entries miss


def test_cow_guards_writes_into_shared_pages():
    """Defensive copy-on-write: a write landing in a page referenced
    elsewhere copies it first — the sharer's view never changes."""
    mgr = _manager(slots=2, max_len=16, num_pages=6, prefix_cache=False)
    a = mgr.alloc("a")
    assert mgr.ensure_pages(a, 4)
    page = int(mgr.table_row(a)[0])
    # poke a recognizable value into the shared page
    mgr.cache = jax.tree_util.tree_map(
        lambda leaf: leaf.at[page].set(7.0), mgr.cache
    )
    b = mgr.alloc("b")
    mgr.attach_prefix(b, [page])  # synthetic partial-page share
    assert int(mgr._ref[page]) == 2
    # slot b will WRITE inside the shared page -> COW must fire
    assert mgr.ensure_pages(b, 4, write_from=2)
    assert mgr.stats()["page_cow"] == 1
    new = int(mgr.table_row(b)[0])
    assert new != page and int(mgr._ref[page]) == 1
    # the copy carried the content; the original is untouched
    leaf = mgr.cache[0]["k"]
    assert bool(jnp.all(leaf[new] == 7.0)) and bool(
        jnp.all(leaf[page] == 7.0)
    )


def test_detach_keep_reattach_and_release():
    mgr = _manager(slots=2, max_len=16, num_pages=8, prefix_cache=False)
    a = mgr.alloc("a")
    assert mgr.ensure_pages(a, 7)
    mgr.set_length(a, 7)
    kept, length = mgr.detach_keep(a)
    assert length == 7 and len(kept) == 2
    assert mgr.stats()["slots_active"] == 0
    assert mgr.free_pages_available() == 6  # kept pages still held
    b = mgr.alloc("resume")
    mgr.reattach(b, kept, length)
    assert mgr.length(b) == 7
    assert [int(p) for _, p in kept] == [
        int(x) for x in mgr.table_row(b)[:2]
    ]
    kept2, _ = mgr.detach_keep(b)
    mgr.release_kept(kept2)
    assert mgr.free_pages_available() == 8


def test_page_tokens_must_divide_max_len():
    with pytest.raises(ValueError, match="divide"):
        _manager(slots=1, max_len=10, num_pages=4, page_tokens=4)


# ------------------------------------------------- zero-retrace invariant


def test_zero_retrace_with_paging_and_pauses(toy):
    """decode_compiles stays EXACTLY 1 across rolling admissions,
    evictions, prefix hits, pool-exhaustion pauses and resumes — page
    tables are data, never shapes."""
    from horovod_tpu.serving.batcher import ContinuousBatcher

    model, params = toy
    eng = _engine(
        toy, slots=3, paged=True, page_tokens=8, pages=12,
        page_watermark=1,
    )
    b = ContinuousBatcher(
        eng, max_admit_per_step=3, default_max_new_tokens=20
    )
    reqs = [
        b.submit(list(range(i * 4 + 1, i * 4 + 9)), max_new_tokens=20)
        for i in range(5)
    ]
    guard = 0
    while not all(r.finished() for r in reqs):
        b.step()
        guard += 1
        assert guard < 5000, [r.status for r in reqs]
    assert all(r.status == "done" for r in reqs)
    for i, r in enumerate(reqs):
        assert r.out_tokens == _greedy_ref(
            model, params, list(range(i * 4 + 1, i * 4 + 9)), 20
        ), f"request {i} diverged"
    assert eng.stats()["decode_compiles"] == 1


# -------------------------------------------- exhaustion admission control


def test_pool_exhaustion_pauses_youngest_and_resumes(toy):
    from horovod_tpu.common.metrics import registry
    from horovod_tpu.serving.batcher import ContinuousBatcher

    model, params = toy
    registry.reset()
    eng = _engine(
        toy, slots=3, paged=True, page_tokens=8, pages=9,
        page_watermark=1, prefix_cache=False,
    )
    b = ContinuousBatcher(
        eng, max_admit_per_step=3, default_max_new_tokens=24
    )
    reqs = [
        b.submit(list(range(i * 3 + 1, i * 3 + 11)), max_new_tokens=24)
        for i in range(3)
    ]
    guard = 0
    while not all(r.finished() for r in reqs):
        b.step()
        guard += 1
        assert guard < 5000
    snap = registry.snapshot()
    assert snap.get("serve.paused", 0) > 0, "pool never exhausted"
    assert snap.get("serve.resumed", 0) > 0
    for i, r in enumerate(reqs):
        assert r.status == "done"
        assert r.out_tokens == _greedy_ref(
            model, params, list(range(i * 3 + 1, i * 3 + 11)), 24
        ), f"request {i} diverged across pause/resume"


def test_admission_gated_on_page_watermark(toy):
    from horovod_tpu.serving.batcher import ContinuousBatcher

    eng = _engine(
        toy, slots=2, paged=True, page_tokens=16, pages=8,
        page_watermark=4, prefix_cache=False,
    )
    b = ContinuousBatcher(eng, default_max_new_tokens=16)
    r1 = b.submit(list(range(1, 33)))   # 2 prompt pages
    r2 = b.submit(list(range(1, 49)))   # 3 prompt pages
    b.step()
    # r1 admitted (headroom 8-4=4 >= 2); r2 blocked by the watermark
    # (headroom now <= 2 < 3) even though a SLOT is free
    assert b.active() == 1 and b.queue_depth() == 1
    assert eng.manager.stats()["slots_free"] == 1
    guard = 0
    while not (r1.finished() and r2.finished()):
        b.step()
        guard += 1
        assert guard < 1000
    assert r1.status == r2.status == "done"


def test_reject_request_that_can_never_fit_pool(toy):
    from horovod_tpu.serving.batcher import ContinuousBatcher, Rejected

    eng = _engine(
        toy, slots=2, paged=True, page_tokens=16, pages=2,
        prefix_cache=False,
    )
    b = ContinuousBatcher(eng, default_max_new_tokens=16)
    with pytest.raises(Rejected, match="pages"):
        b.submit(list(range(1, 40)))  # 39 + 16 tokens -> 4 pages > 2
    b.submit([1, 2, 3])  # 3 + 16 -> 2 pages: fits


def test_queued_paused_request_expiring_releases_pages(toy):
    from horovod_tpu.serving.batcher import ContinuousBatcher

    eng = _engine(
        toy, slots=2, paged=True, page_tokens=8, prefix_cache=False
    )
    b = ContinuousBatcher(eng, default_max_new_tokens=4)
    r = b.submit([1, 2, 3, 4, 5], deadline_ms=60_000.0)
    b.step()
    assert r.status == "running"
    # pause it by hand (the exhaustion path), then expire it in queue
    slot = next(iter(b._slot_req))
    held_before = eng.manager.free_pages_available()
    b._slot_req.pop(slot)
    r.kept_pages, r.resume_length = eng.manager.detach_keep(slot)
    r.paused = True
    r.status = "queued"
    b._queue.appendleft(r)
    r.deadline_ts = time.monotonic() - 0.001
    b.step()
    assert r.finished() and r.status == "deadline"
    assert r.kept_pages is None
    assert eng.manager.free_pages_available() > held_before


# ----------------------------------------------- capacity + router surface


def test_capacity_reports_pages_and_saturation_flips_slots(toy):
    import horovod_tpu as hvd

    model, params = toy
    handle = hvd.serve(
        model, params, port=0, slots=2, max_len=64,
        max_new_tokens=4, addr="127.0.0.1", handle_sigterm=False,
        page_tokens=16, pages=8, page_watermark=2,
    )
    try:
        cap = handle.frontend.capacity()
        assert cap["pages_total"] == 8
        assert cap["free_pages"] == 6  # 8 free - watermark 2
        assert "prefix_hit_rate" in cap
        assert cap["free_slots"] == 2
        # drain the pool: headroom 0 must flip announced slots to 0
        mgr = handle.engine.manager
        s = mgr.alloc("hog")
        assert mgr.ensure_pages(s, 64)  # all 8 pages... (4 pages/slot)
        s2 = mgr.alloc("hog2")
        assert mgr.ensure_pages(s2, 64)
        cap = handle.frontend.capacity()
        assert cap["free_pages"] == 0
        assert cap["free_slots"] == 0  # saturated pool -> no capacity
        mgr.free(s)
        mgr.free(s2)
    finally:
        handle.stop()


def test_router_prefers_page_headroom_with_legacy_blob_compat(toy):
    from horovod_tpu.runner.rendezvous import KVStore
    from horovod_tpu.serving.frontend import Router

    store = KVStore()

    def announce(rank, port, **fields):
        blob = dict(
            rank=rank, addr="127.0.0.1", port=port, ts=time.time(),
            draining=False, queue_depth=0,
        )
        blob.update(fields)
        store.put("serve", str(rank), json.dumps(blob).encode())

    # rank 0: MORE free slots but fewer free pages; rank 1 page-rich.
    announce(0, 9000, free_slots=8, free_pages=1, pages_total=16)
    announce(1, 9001, free_slots=2, free_pages=9, pages_total=16)
    router = Router(store)
    assert router.pick()["rank"] == 1  # pages outrank slots
    # legacy blob (no page fields) parses and routes on slots
    store2 = KVStore()
    blob = {
        "rank": 3, "addr": "127.0.0.1", "port": 9003,
        "free_slots": 4, "queue_depth": 0, "ts": time.time(),
    }
    store2.put("serve", "3", json.dumps(blob).encode())
    router2 = Router(store2)
    assert router2.pick()["rank"] == 3


def test_paged_counters_land_in_flight_recorder(toy, monkeypatch):
    from horovod_tpu.common import telemetry
    from horovod_tpu.serving.batcher import ContinuousBatcher

    monkeypatch.setenv("HOROVOD_TELEMETRY", "1")
    telemetry._reset_hub()
    try:
        eng = _engine(toy, paged=True, page_tokens=8)
        b = ContinuousBatcher(eng, default_max_new_tokens=10)
        r = b.submit([5, 6, 7, 8, 9, 10, 11, 12])
        while not r.finished():
            b.step()
        recs = telemetry.hub().records()
        assert recs
        assert any("serve.page_allocs" in rec for rec in recs)
        assert (
            sum(rec.get("serve.page_allocs", 0) for rec in recs) > 0
        ), "decode frontier crossings produced no page_allocs deltas"
    finally:
        telemetry._reset_hub()
