"""The serving loop names its own work: a stamp per token in every reply,
one ``hvd.batcher.idle_wait`` span for each stretch the scheduler idles, and
``hvd.engine.decode_step`` under ``HOROVOD_TRACE`` at its sample rate, none
of them a second program (``decode_compiles == 1``)."""

import time

import pytest

from test_process_spans import ring  # noqa: F401 - fixture
from test_serving import _batcher, _post, toy  # noqa: F401 - fixture


def _names(rec):
    return [r["name"] for r in rec.spans()]


@pytest.mark.parametrize("prompts, new_tokens", [
    ([[3, 5, 7]], 6),
    ([[3, 5, 7], [11, 13], [2, 4, 6, 8]], 4),  # more requests than slots
])
def test_token_ms_has_one_increasing_stamp_per_output_token(
        toy, ring, prompts, new_tokens):  # noqa: F811
    ring(False)  # the stamps need no switch
    b = _batcher(toy, default_max_new_tokens=new_tokens)
    reqs = [b.submit(p, max_new_tokens=new_tokens) for p in prompts]
    while not all(r.finished() for r in reqs):
        b.step()
    for r in reqs:
        out = r.result()
        stamps = out["token_ms"]
        assert len(stamps) == len(out["tokens"]) == new_tokens
        assert all(b > a for a, b in zip(stamps, stamps[1:])), stamps
        # the first stamp is the first token's: the TTFT
        assert stamps[0] == pytest.approx(out["ttft_ms"], abs=1e-3)
        # gen_ms runs from the first token to the last
        assert stamps[-1] - stamps[0] == pytest.approx(
            out["gen_ms"], abs=1e-2)
    assert b.engine.stats()["decode_compiles"] == 1


def test_token_ms_is_in_the_http_reply(toy):  # noqa: F811
    import horovod_tpu as hvd

    model, params = toy
    handle = hvd.serve(model, params, port=0, slots=2, max_len=64,
                       max_new_tokens=4, addr="127.0.0.1",
                       handle_sigterm=False)
    try:
        status, out = _post(handle.port, {"tokens": [9, 10, 11]})
        assert status == 200 and len(out["token_ms"]) == len(out["tokens"])
        assert out["token_ms"] == sorted(out["token_ms"])
    finally:
        handle.stop()


def test_one_idle_wait_span_covers_each_idle_stretch(toy, ring):  # noqa: F811
    rec = ring(True)
    b = _batcher(toy, default_max_new_tokens=4)
    b.start()
    try:
        t0 = time.monotonic()
        time.sleep(0.3)
        r = b.submit([3, 5, 7], max_new_tokens=4)
        assert r.wait(60)
        busy_s = r.result()["token_ms"][-1] / 1e3
        time.sleep(0.3)
        idle_s = time.monotonic() - t0 - busy_s
    finally:
        b.stop()
    waits = [r for r in rec.spans() if r["name"] == "hvd.batcher.idle_wait"]
    # fifteen 20 ms waits on either side of the request, one span a
    # stretch: an idle plane does not fill the ring that requests share
    assert len(waits) == 2
    assert all(r["tags"] == {"active": 0} for r in waits)
    # the scheduler did nothing else, so the spans cover the time it idled
    # (and the request's round is no part of them; stopping takes a wait)
    assert 0.7 * idle_s <= sum(
        r["dur_ms"] for r in waits) / 1e3 <= idle_s + 0.1


@pytest.mark.parametrize("trace_on, sample", [
    (False, 1.0), (True, 1.0), (True, 0.0)])
def test_the_decode_step_span_follows_the_switches(
        toy, ring, trace_on, sample):  # noqa: F811
    rec = ring(trace_on, sample)
    b = _batcher(toy, default_max_new_tokens=4)
    r = b.submit([3, 5, 7], max_new_tokens=4)
    while not r.finished():
        b.step()
    steps = [s for s in rec.spans() if s["name"] == "hvd.engine.decode_step"]
    assert bool(steps) == (trace_on and sample > 0)
    for step in steps:
        assert step["tags"]["active"] >= 1
        if b.engine.paged:
            assert 0 < step["tags"]["live_pages"] <= step["tags"]["pages"]
    # nothing else of the program's fires per round (the model's span and
    # the compile ledger's fire while its programs are traced, lowered and
    # compiled): every hvd.* span has a reader
    assert {n for n in _names(rec) if n.startswith("hvd.")} <= {
        "hvd.engine.decode_step", "hvd.trainer.trace_model",
        "hvd.init.jit_trace", "hvd.init.jit_lower", "hvd.init.jit_compile"}
    assert b.engine.stats()["decode_compiles"] == 1
