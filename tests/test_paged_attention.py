"""Paged flash-attention kernel (ops/paged_attention.py): op-level
≤1-ulp parity vs the gather-view dense oracle (scrambled page tables,
staggered multi-slot lengths, GQA, prefill chunks), engine-level greedy
token parity kernel-vs-gather (RoPE/GQA, post-eviction page reuse,
chunked long prompts), zero-retrace with the kernel on across rolling
admissions AND pool-exhaustion pauses, every rung of the fallback
ladder counted, sampled decode as pure DATA through the one decode
executable (temp-0 bitwise greedy, seeded reproducibility, top-k), and
the transfer-sender split regression (device_get off the scheduler
thread — decode-round latency independent of an in-flight transfer)."""

import json
import threading
import time
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.common.metrics import registry as _metrics

# ---------------------------------------------------------------- fixtures


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


def _toy(**cfg_kw):
    from horovod_tpu.models.transformer import Transformer

    model = Transformer(_cfg(**cfg_kw))
    params = model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32), train=False
    )
    return model, params


@pytest.fixture(scope="module")
def toy():
    return _toy()


def _engine(toy, **kw):
    from horovod_tpu.serving.engine import InferenceEngine

    model, params = toy
    kw.setdefault("slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("min_bucket", 4)
    kw.setdefault("paged", True)
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


# ------------------------------------------------------- op-level parity

_EPS = float(np.finfo(np.float32).eps)


def _assert_ulp_close(got, ref, ulps=4):
    """The documented numerics bound: the kernel's only structural
    difference from the dense path is the online softmax's reassociated
    denominator, ≤1–2 ulp at the output scale (measured); 4 is the
    assertion envelope."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    tol = ulps * _EPS * max(1.0, float(np.abs(ref).max()))
    assert float(np.abs(got - ref).max()) <= tol, (
        float(np.abs(got - ref).max()), tol
    )


def _gather_oracle(q, k_pool, v_pool, tables, lengths):
    """The pure-XLA baseline the kernel replaces: gather every slot's
    pages into a contiguous view (mode="clip", exactly like the model's
    jnp.take path), then causal dense softmax attention."""
    b, t, h, d = q.shape
    kvh = k_pool.shape[2]
    r = h // kvh
    tbl = jnp.asarray(tables, jnp.int32)
    k = jnp.take(k_pool, tbl, axis=0, mode="clip").reshape(b, -1, kvh, d)
    v = jnp.take(v_pool, tbl, axis=0, mode="clip").reshape(b, -1, kvh, d)
    kk, vv = jnp.repeat(k, r, axis=2), jnp.repeat(v, r, axis=2)
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), kk.astype(jnp.float32)
    ) / np.sqrt(d)
    q_pos = jnp.asarray(lengths)[:, None] + jnp.arange(t)[None]  # [b, t]
    key_pos = jnp.arange(k.shape[1])
    mask = key_pos[None, None, None, :] <= q_pos[:, None, :, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vv.astype(jnp.float32))


def _pools(num_pages, pt, kvh, d, seed):
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.normal(size=(num_pages, pt, kvh, d)), jnp.float32)
    v = jnp.asarray(
        rng.normal(size=(num_pages, pt, kvh, d)), jnp.float32
    )
    return k, v


def test_decode_parity_scrambled_pages_staggered_lengths():
    """t=1 decode over a shared pool: scrambled physical page order,
    ragged lengths (including a just-admitted length-0 slot and a full
    row), GQA r=2 — the fused read matches the gather oracle to ulps."""
    from horovod_tpu.ops.paged_attention import paged_attention

    b, pt, kvh, h, d = 4, 8, 2, 4, 8
    num_pages, n_logical = 20, 4  # 4 pages x 8 tokens = 32-token slots
    k_pool, v_pool = _pools(num_pages, pt, kvh, d, 0)
    rng = np.random.default_rng(1)
    tables = np.full((b, n_logical), num_pages, np.int32)  # sentinel
    phys = rng.permutation(num_pages)
    lengths = np.asarray([0, 5, 17, 31], np.int32)
    off = 0
    for i, n in enumerate(lengths):
        live = -(-(int(n) + 1) // pt)
        tables[i, :live] = phys[off:off + live]
        off += live
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    got = paged_attention(
        q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lengths)
    )
    ref = _gather_oracle(q, k_pool, v_pool, tables, lengths)
    assert got.shape == (b, 1, h, d)
    _assert_ulp_close(got, ref)


def test_prefill_chunk_parity_unaligned_starts():
    """t=8 chunk (the chunked-prefill shape): per-slot start offsets
    that do NOT sit on page boundaries still mask and accumulate to the
    oracle's values."""
    from horovod_tpu.ops.paged_attention import paged_attention

    b, t, pt, kvh, h, d = 3, 8, 8, 1, 2, 8
    num_pages, n_logical = 12, 4
    k_pool, v_pool = _pools(num_pages, pt, kvh, d, 2)
    rng = np.random.default_rng(3)
    tables = np.asarray(
        [[7, 2, 9, 0], [4, 11, 1, 3], [8, 5, 10, 6]], np.int32
    )
    lengths = np.asarray([0, 5, 16], np.int32)
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    got = paged_attention(
        q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lengths)
    )
    ref = _gather_oracle(q, k_pool, v_pool, tables, lengths)
    _assert_ulp_close(got, ref)


# --------------------------------------- engine-level kernel/gather parity


def _ab_engines(toy, **kw):
    on = _engine(toy, paged_attn="on", **kw)
    off = _engine(toy, paged_attn="off", **kw)
    assert on.paged_attn and not off.paged_attn
    return on, off


def test_kernel_greedy_parity_rope_gqa_staggered():
    """The acceptance gate: kernel-on greedy decode is token-identical
    to the gather read — on the variant most sensitive to KV placement
    (RoPE + grouped-query heads), with staggered admissions."""
    toy = _toy(num_heads=4, num_kv_heads=1, rope=True)
    model, params = toy
    on, off = _ab_engines(toy)
    p1, p2 = [3, 5, 7], [11, 13, 17, 19, 21]
    outs = {}
    for eng in (on, off):
        s1 = eng.manager.alloc("a")
        o1 = [eng.prefill(s1, p1)]
        for _ in range(3):
            toks = np.zeros(eng.slots, np.int32)
            toks[s1] = o1[-1]
            o1.append(int(eng.decode_step(toks)[s1]))
            eng.manager.advance(s1)
        s2 = eng.manager.alloc("b")  # staggered admission mid-stream
        o2 = [eng.prefill(s2, p2)]
        for _ in range(4):
            toks = np.zeros(eng.slots, np.int32)
            toks[s1], toks[s2] = o1[-1], o2[-1]
            nxt = eng.decode_step(toks)
            eng.manager.advance(s1)
            eng.manager.advance(s2)
            o1.append(int(nxt[s1]))
            o2.append(int(nxt[s2]))
        outs[eng is on] = (o1, o2)
    assert outs[True] == outs[False]
    assert outs[True][0] == _greedy_ref(model, params, p1, 8)
    assert outs[True][1] == _greedy_ref(model, params, p2, 5)
    assert on.stats()["paged_attn_calls"] > 0
    assert on.stats()["paged_attn_fallbacks"] == 0
    assert off.stats()["paged_attn_calls"] == 0


def test_kernel_parity_page_reuse_after_eviction(toy):
    """Recycled physical pages (no zeroing on free) decode exactly
    through the kernel read — stale pool contents past the frontier are
    invisible to the clamped page walk."""
    model, params = toy
    eng = _engine(
        toy, slots=1, pages=4, prefix_cache=False, paged_attn="on"
    )
    slot = eng.manager.alloc("a")
    _generate(eng, slot, [41, 43, 45, 47, 49, 51, 53], 12)
    eng.manager.free(slot)
    slot2 = eng.manager.alloc("b")
    out = _generate(eng, slot2, [2, 4], 6)
    assert out == _greedy_ref(model, params, [2, 4], 6)
    assert eng.stats()["paged_attn_fallbacks"] == 0


def test_kernel_parity_chunked_long_prompt(toy):
    """Chunked prefill rides the kernel too: every ceiling chunk and
    the tail each count one kernel call, and the long-prompt stream
    matches the dense reference."""
    model, params = toy
    eng = _engine(toy, prefill_ceiling=8, paged_attn="on")
    prompt = list(np.random.default_rng(3).integers(1, 60, size=21))
    slot = eng.manager.alloc()
    out = _generate(eng, slot, prompt, 4)
    assert out == _greedy_ref(model, params, prompt, 4)
    st = eng.stats()
    assert st["chunked_prefill_chunks"] == 2
    # 2 ceiling chunks + 1 tail prefill + 3 decode steps
    assert st["paged_attn_calls"] == 6
    assert st["paged_attn_fallbacks"] == 0


# ------------------------------------------------- zero-retrace invariant


def test_zero_retrace_kernel_on_admissions_and_exhaustion(toy):
    """decode_compiles stays EXACTLY 1 with the kernel on, across
    rolling admissions, pool-exhaustion pauses and resumes — page
    tables stay DATA through the scalar-prefetch grid, never shapes."""
    from horovod_tpu.serving.batcher import ContinuousBatcher

    model, params = toy
    _metrics.reset()
    eng = _engine(
        toy, slots=3, page_tokens=8, pages=9, page_watermark=1,
        prefix_cache=False, paged_attn="on",
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
        assert guard < 5000, [r.status for r in reqs]
    snap = _metrics.snapshot()
    assert snap.get("serve.paused", 0) > 0, "pool never exhausted"
    assert snap.get("serve.resumed", 0) > 0
    st = eng.stats()
    assert st["decode_compiles"] == 1
    assert st["paged_attn_fallbacks"] == 0
    assert st["paged_attn_calls"] > 0
    for i, r in enumerate(reqs):
        assert r.status == "done"
        assert r.out_tokens == _greedy_ref(
            model, params, list(range(i * 3 + 1, i * 3 + 11)), 24
        ), f"request {i} diverged across pause/resume"


# --------------------------------------------------------- fallback ladder


def test_fallback_alignment_rungs_are_tpu_only():
    """Rungs 2–3: Mosaic tile floors (128-lane head_dim, 8-sublane
    page_tokens) gate only on real TPU backends — interpret mode (CPU
    tests, dryrun benches) runs any geometry."""
    from horovod_tpu.ops import paged_attention as pa

    assert pa.unsupported_reason(8, 16) is None  # CPU: lenient
    r = pa.unsupported_reason(8, 16, backend="tpu")
    assert r and "lane" in r
    r = pa.unsupported_reason(128, 12, backend="tpu")
    assert r and "sublane" in r
    assert pa.unsupported_reason(128, 16, backend="tpu") is None


def test_fallback_vmem_budget_counted(toy, monkeypatch):
    """Rung 4: the VMEM estimate vs HOROVOD_FLASH_VMEM_BUDGET — an
    oversized page staging footprint rides the gather path, counted."""
    from horovod_tpu.ops import paged_attention as pa

    monkeypatch.setenv("HOROVOD_FLASH_VMEM_BUDGET", "1024")
    reason = pa.unsupported_reason(8, 16)
    assert reason and "VMEM" in reason
    eng = _engine(toy, paged_attn="on")
    assert eng.paged_attn is False
    assert eng.stats()["paged_attn_fallbacks"] == 1


def test_fallback_sliding_window_counted():
    """Rung 5: the kernel has no band mask — sliding-window models keep
    the gather read and the fallback is counted at engine build."""
    toy = _toy(sliding_window=8)
    model, params = toy
    eng = _engine(toy, paged_attn="on")
    assert eng.paged_attn is False
    assert eng.stats()["paged_attn_fallbacks"] == 1
    out = _generate(eng, eng.manager.alloc(), [5, 9, 2], 4)
    assert len(out) == 4


def test_model_level_fallback_wide_prefill_chunk(toy, monkeypatch):
    """The per-trace rung: a budget that admits the decode geometry
    (t=1) but not an 8-wide prefill chunk falls back ONLY for the wide
    trace — loud warning + serve.paged_attn_fallbacks — while decode
    keeps the kernel. The fallen-back chunk is bitwise the gather
    path."""
    from horovod_tpu.models.transformer import init_cache
    from horovod_tpu.ops import paged_attention as pa

    model, params = toy
    cfg = model.cfg
    d = cfg.d_model // cfg.num_heads
    lo = pa.fwd_vmem_bytes(1, d, 16)
    hi = pa.fwd_vmem_bytes(8, d, 16)
    assert lo < hi
    monkeypatch.setenv("HOROVOD_FLASH_VMEM_BUDGET", str((lo + hi) // 2))
    _metrics.reset()

    pt, slots = 16, 2
    W = cfg.max_len // pt
    tables = np.full((slots, W), slots * W, np.int32)
    tables[0] = [1, 3, 0, 2]
    prompt = jnp.asarray([[9, 8, 7, 6, 5, 4, 3, 2]], jnp.int32)

    def run(paged_attn):
        pool = init_cache(cfg, slots * W, pt)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            lg, pool = model.apply(
                params, prompt, train=False, cache=pool,
                cache_index=jnp.array([0]),
                pages=jnp.asarray(tables[0:1]),
                paged_attn=paged_attn,
            )
        return lg, pool, [str(x.message) for x in w]

    lg_k, pool, warns = run(True)
    assert any("unsupported" in m for m in warns)
    assert _metrics.snapshot().get("serve.paged_attn_fallbacks") == 1.0
    lg_g, _, warns_g = run(False)
    assert not any("paged_attn" in m for m in warns_g)
    assert bool(jnp.all(lg_k == lg_g))  # fell back -> same program

    # decode (t=1) stays inside the budget: kernel engages, no warning
    toks = jnp.asarray([[3], [0]], jnp.int32)
    lengths = jnp.asarray([8, 0], jnp.int32)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        lg_dk, _ = model.apply(
            params, toks, train=False, cache=pool, cache_index=lengths,
            pages=jnp.asarray(tables), paged_attn=True,
        )
    assert not any("paged_attn" in str(x.message) for x in w)
    lg_dg, _ = model.apply(
        params, toks, train=False, cache=pool, cache_index=lengths,
        pages=jnp.asarray(tables), paged_attn=False,
    )
    assert int(jnp.argmax(lg_dk[0, -1])) == int(jnp.argmax(lg_dg[0, -1]))
    _assert_ulp_close(lg_dk[0], lg_dg[0], ulps=16)  # logit scale


# ----------------------------------------------------------- sampled decode


def _sampled_stream(toy, prompt, n, temp, topk, seed, **engine_kw):
    eng = _engine(toy, **engine_kw)
    slot = eng.manager.alloc()
    eng.set_sampling(slot, temp, topk, seed=seed)
    return _generate(eng, slot, prompt, n), eng


def test_temperature_zero_is_bitwise_greedy(toy):
    """temperature 0 takes the jnp.where greedy branch — bit-identical
    to an engine that never heard of sampling, even with a seeded key
    riding the carry."""
    model, params = toy
    prompt = [7, 3, 9, 1]
    out, _ = _sampled_stream(toy, prompt, 10, 0.0, 0, seed=123)
    assert out == _greedy_ref(model, params, prompt, 10)


def test_seeded_sampling_reproducible_and_not_greedy(toy):
    """Same seed, fresh engines: identical streams (the key rides the
    donated carry deterministically). High temperature diverges from
    greedy; top_k=1 collapses back to greedy at ANY temperature."""
    model, params = toy
    prompt = [2, 4, 6, 8]
    a, _ = _sampled_stream(toy, prompt, 12, 5.0, 0, seed=7)
    b, _ = _sampled_stream(toy, prompt, 12, 5.0, 0, seed=7)
    assert a == b
    greedy = _greedy_ref(model, params, prompt, 12)
    assert a[0] == greedy[0]  # the prefill token is always greedy
    assert a != greedy
    c, _ = _sampled_stream(toy, prompt, 12, 5.0, 1, seed=7)
    assert c == greedy


def test_sampling_is_data_zero_retrace_and_slot_isolation(toy):
    """Sampling knobs through the batcher are DATA in the one decode
    executable: a sampled and a greedy request share a batch without
    retrace, the greedy stream stays exact, retirement clears the
    knobs for the slot's next occupant, and a replayed seed
    reproduces."""
    from horovod_tpu.serving.batcher import ContinuousBatcher

    model, params = toy
    eng = _engine(toy)
    bat = ContinuousBatcher(eng, default_max_new_tokens=8)
    g = bat.submit([1, 2, 3, 4], max_new_tokens=8)
    s = bat.submit([5, 6, 7, 8], max_new_tokens=8,
                   temperature=1.5, seed=11)
    while not (g.finished() and s.finished()):
        bat.step()
    assert g.result()["tokens"] == _greedy_ref(
        model, params, [1, 2, 3, 4], 8
    )
    assert eng.stats()["decode_compiles"] == 1
    # replayed seed reproduces the sampled stream bit for bit
    s2 = bat.submit([5, 6, 7, 8], max_new_tokens=8,
                    temperature=1.5, seed=11)
    # the sampled slot was cleared on retire: a greedy request landing
    # on any slot decodes greedy
    g2 = bat.submit([5, 6, 7, 8], max_new_tokens=8)
    while not (s2.finished() and g2.finished()):
        bat.step()
    assert s2.result()["tokens"] == s.result()["tokens"]
    assert g2.result()["tokens"] == _greedy_ref(
        model, params, [5, 6, 7, 8], 8
    )
    assert eng.stats()["decode_compiles"] == 1


def test_sampling_composes_with_kernel_read(toy):
    """Sampled decode and the paged-attention kernel share the decode
    executable: seeded reproducibility holds with the kernel on, and
    temp-0 matches the gather engine's greedy stream."""
    model, params = toy
    prompt = [9, 2, 5]
    a, ea = _sampled_stream(toy, prompt, 8, 3.0, 0, seed=4,
                            paged_attn="on")
    b, _ = _sampled_stream(toy, prompt, 8, 3.0, 0, seed=4,
                           paged_attn="on")
    assert a == b
    assert ea.stats()["paged_attn_calls"] > 0
    g, _ = _sampled_stream(toy, prompt, 8, 0.0, 0, seed=4,
                           paged_attn="on")
    assert g == _greedy_ref(model, params, prompt, 8)


# ------------------------------------- transfer-sender split (satellite 1)


def test_gather_pages_defers_device_get(toy, monkeypatch):
    """The sender split: gather_pages (scheduler-thread half) performs
    NO host transfer; pages_to_host does exactly ONE batched device_get
    for all pages of all leaves; the composition equals extract_pages
    bit for bit."""
    eng = _engine(toy, prefix_cache=False)
    slot = eng.manager.alloc("a")
    eng.prefill(slot, [1, 2, 3, 4, 5])
    eng.manager.set_length(slot, 5)
    kept, length = eng.manager.detach_keep(slot)
    calls = []
    real = jax.device_get

    def spy(x):
        calls.append(threading.current_thread().name)
        return real(x)

    monkeypatch.setattr(jax, "device_get", spy)
    raw = eng.gather_pages(kept)
    assert calls == [], "gather_pages touched the host on the hot path"
    out = eng.pages_to_host(raw, kept, length)
    assert len(calls) == 1, "pages_to_host must batch ONE device_get"
    monkeypatch.undo()
    ref = eng.extract_pages(kept, length)
    for a, b in zip(out, ref):
        np.testing.assert_array_equal(a, b)
    pt = eng.manager.page_tokens
    assert float(np.abs(out[0][-1, length % pt:]).max()) == 0.0
    eng.manager.release_kept(kept)


class _FakeAnnounceClient:
    def __init__(self, anns):
        self.anns = dict(anns)

    def keys(self, scope):
        return [str(r) for r in self.anns]

    def get(self, scope, key):
        return json.dumps(self.anns[int(key)]).encode()


def test_decode_round_latency_is_transfer_independent(toy):
    """The regression the split exists for: a SLOW host materialization
    (0.5 s injected into pages_to_host) must not stretch any scheduler
    step — the blocking half runs on the handoff thread, so in-flight
    transfers leave decode-round latency untouched."""
    from horovod_tpu.serving.batcher import ContinuousBatcher
    from horovod_tpu.serving.kv_transfer import (
        KVTransferServer,
        TransferCoordinator,
    )

    model, params = toy
    deng = _engine(toy, role="decode")
    dbat = ContinuousBatcher(deng, role="decode",
                             default_max_new_tokens=6)
    server = KVTransferServer(dbat, port=0, addr="127.0.0.1")
    server.start()
    peng = _engine(toy, role="prefill")
    pbat = ContinuousBatcher(peng, role="prefill",
                             default_max_new_tokens=6)
    pbat.transfer = TransferCoordinator(
        peng,
        client=_FakeAnnounceClient({0: {
            "port": 1, "addr": "127.0.0.1", "role": "decode",
            "transfer_port": server.port, "free_pages": 100,
            "free_slots": 4, "ts": time.time(),
        }}),
        wire="fp32",
    )
    dbat.start()
    try:
        def pump(req, measure=False):
            worst = 0.0
            deadline = time.monotonic() + 60.0
            while not req.finished() and time.monotonic() < deadline:
                t0 = time.perf_counter()
                pbat.step()
                worst = max(worst, time.perf_counter() - t0)
                time.sleep(0.002)
            assert req.finished(), "transfer never completed"
            return worst

        # warm-up ONCE: bucket→exact promotion now runs on a background
        # thread (disk tier first), so a repeat admission can no longer
        # inject a promotion compile into the measured hot path
        prompt = list(range(1, 9))
        pump(pbat.submit(prompt, max_new_tokens=6))

        seen = {}
        real = peng.pages_to_host

        def slow(raw, kept, length):
            seen["thread"] = threading.current_thread().name
            time.sleep(0.5)
            return real(raw, kept, length)

        peng.pages_to_host = slow
        try:
            req = pbat.submit(prompt, max_new_tokens=6)
            worst = pump(req, measure=True)
        finally:
            peng.pages_to_host = real
        assert req.status == "done"
        assert seen["thread"].startswith("hvd-kv-handoff"), seen
        # every scheduler round stayed far below the injected 0.5 s
        assert worst < 0.35, (
            f"a scheduler step blocked {worst:.3f}s on the transfer"
        )
        assert dbat.engine.stats()["transfer_ingests"] >= 2
    finally:
        dbat.stop()
        server.stop()
