"""The readers of the program's compile ledger and step plan
(``benchmark/lib/jit_spans.py`` and nine files of ``benchmark/metrics``): the
step found from inside a hand-made ring by containment, each reader's sum,
and None over a ring without the spans (the parent's)."""

import pytest

from benchmark.lib import jit_spans, manifest, program_spans

JIT_METRICS = (
    "init.jit_trace_s", "init.jit_lower_s", "init.jit_compile_s",
    "init.cache_miss_compile_s", "init.other_programs_s",
    "init.trace_kernels_s", "kernels.flash_dkv_empty_steps",
    "kernels.flash_staged_vmem_mib", "exchange.planned_bytes_per_step",
)
ALL_CELLS = ["gpt2m-train-1chip", "bertl-train-1chip", "gpt2m-train-dp4",
             "trinity-train-s8k-1chip", "kanana-train-s8k-1chip",
             "sdar-train-blockdiff-s8k-1chip"]


def _ring(world=4, empty_steps=0, step_cache="hit"):
    """One process's ring as the program writes it: seconds since the
    process began, a span a row ``(name, start, seconds, tags)``. The
    weights program and an eager operation, the state's placement, the step
    (``train_step``: model, two forward kernel calls, a backward with a dQ
    and a blocked dK/dV call, the exchange's plan, the update), then the
    reference's step, which compiles from cold."""
    rows = [
        ("hvd.init", 1.0, 0.02, {}),
        ("hvd.trainer.trace_model", 2.0, 1.5, {}),  # the shapes
        ("hvd.kernels.flash_call", 2.5, 0.1, _call("flash_fwd", 8)),
        ("hvd.init.jit_trace", 1.9, 1.7, {"fun": "<lambda>", "inner": 90}),
        ("hvd.init.jit_trace", 4.0, 0.5, {"fun": "build"}),
        ("hvd.init.jit_lower", 4.5, 0.25, {"fun": "jit(build)"}),
        ("hvd.init.jit_compile", 4.75, 8.0, {
            "fun": "jit(build)", "cache": "miss", "small": 12,
            "small_s": 0.125, "small_misses": 2, "small_miss_s": 0.0625}),
        # an inner event long enough for a span of its own: no second count
        ("hvd.init.jit_trace", 13.1, 0.2, {"fun": "_where", "depth": 1}),
        ("hvd.init.jit_trace", 13.0, 0.5, {"fun": "put", "inner": 3,
                                           "inner_s": 0.2}),
        ("hvd.init.jit_compile", 13.5, 0.5, {"fun": "jit(put)",
                                             "cache": "uncached"}),
        # the step
        ("hvd.trainer.trace_model", 20.5, 6.0, {}),
        ("hvd.kernels.flash_call", 21.0, 0.25, _call("flash_fwd", 8)),
        ("hvd.kernels.flash_call", 27.0, 0.5, _call("flash_dq", 16)),
        ("hvd.kernels.flash_call", 28.0, 0.75, dict(
            _call("flash_dkv", 0), staging="blocked", grid_steps=9216,
            kept_tiles=9216 - empty_steps)),
        ("hvd.exchange.plan", 30.0, 0.01, {"world": world, "bytes": 1624}),
        ("hvd.trainer.trace_update", 29.9, 0.3, {}),
        ("hvd.init.jit_trace", 20.0, 12.0, {
            "fun": "train_step", "inner": 400, "small": 30, "small_s": 0.25}),
        ("hvd.init.jit_lower", 32.0, 8.0, {"fun": "jit(train_step)"}),
        ("hvd.init.jit_compile", 40.0, 3.0, {
            "fun": "jit(train_step)", "cache": step_cache,
            "small": 4, "small_s": 0.5}),
        # after the step: the reference's, compiled from cold
        ("hvd.init.jit_trace", 50.0, 2.0, {"fun": "<lambda>"}),
        ("hvd.init.jit_lower", 52.0, 1.0, {"fun": "jit(<lambda>)"}),
        ("hvd.init.jit_compile", 53.0, 95.0, {"fun": "jit(<lambda>)",
                                              "cache": "miss"}),
    ]
    return [{"name": name, "seq": seq, "ts": 1.7e9 + start,
             "dur_ms": seconds * 1e3, "tags": tags}
            for seq, (name, start, seconds, tags) in enumerate(
                sorted(rows, key=lambda row: row[1]))]


def _call(kernel, staged_mib):
    return {"kernel": kernel, "staging": "whole", "grid_steps": 256,
            "staged_vmem_bytes": staged_mib * 2**20}


def _read(ring):
    return {name: manifest.load_module("metrics", name).read(
        {program_spans.KEY: ring}) for name in JIT_METRICS}


def test_the_step_is_found_from_inside_the_ring():
    step = jit_spans.step_events({program_spans.KEY: _ring()})
    assert {k: (v["tags"]["fun"], v["dur_ms"]) for k, v in step.items()} == {
        "trace": ("train_step", 12000.0),
        "lower": ("jit(train_step)", 8000.0),
        "compile": ("jit(train_step)", 3000.0)}


def test_each_reader_over_a_hand_made_ring(capsys):
    assert _read(_ring()) == {
        "init.jit_trace_s": pytest.approx(12.0),
        "init.jit_lower_s": pytest.approx(8.0),
        "init.jit_compile_s": pytest.approx(3.0),
        # the weights program's compile and its tally's two small misses;
        # not the reference's 95 s, which follow the step's compile
        "init.cache_miss_compile_s": pytest.approx(8.0625),
        # before the step's trace: 1.7 + 0.5 + 0.25 + 8 + 0.5 + 0.5 of
        # spans (the child span is inside its parent), 0.125 of their
        # tallies, and the tallies the step's own spans carry: 0.25 + 0.5
        "init.other_programs_s": pytest.approx(12.325),
        "init.trace_kernels_s": pytest.approx(1.6),
        "kernels.flash_dkv_empty_steps": 0,
        "kernels.flash_staged_vmem_mib": pytest.approx(16.0),
        "exchange.planned_bytes_per_step": 1624,
    }
    err = capsys.readouterr().err
    assert '"cache": "hit"' in err  # the step's compile span's tags
    # forward calls lie inside trace_model, backward calls outside it
    assert '"inside_trace_model": 0.35, "outside": 1.25' in err


def test_what_the_readers_tell_apart():
    # a step that compiled from cold is a miss of its own
    cold = _read(_ring(step_cache="miss"))
    assert cold["init.cache_miss_compile_s"] == pytest.approx(11.0625)
    # a mask that brought empty grid steps back shows
    assert _read(_ring(empty_steps=141312 - 132096))[
        "kernels.flash_dkv_empty_steps"] == 9216
    # one chip: the plan is written (world 1), and nothing is exchanged
    assert _read(_ring(world=1))["exchange.planned_bytes_per_step"] is None


def test_a_ring_without_the_spans_reads_nothing():
    """The parent's ring: PR 25's spans and no other."""
    ring = [r for r in _ring() if r["name"] in (
        "hvd.init", "hvd.trainer.trace_model", "hvd.trainer.trace_update")]
    assert set(_read(ring).values()) == {None}
    assert set(_read([]).values()) == {None}
    # the ledger's spans without a step among them (a serving process)
    ring = [r for r in _ring() if r["name"] != "hvd.trainer.trace_update"]
    got = _read(ring)
    assert got["init.jit_trace_s"] is None
    assert got["init.cache_miss_compile_s"] is None
    assert got["kernels.flash_staged_vmem_mib"] == pytest.approx(16.0)


@pytest.mark.parametrize("name", JIT_METRICS)
def test_each_new_metric_has_a_reader_and_an_entry(name):
    entries = manifest.load_manifest()["per_layer"]
    entry = {m["name"]: m for m in entries}[name]
    assert manifest.load_module("metrics", name) is not None
    # appended, the accepted entries before them as they were
    assert [m["name"] for m in entries[-9:]] == list(JIT_METRICS)
    assert entry["better"] == "lower"
    if name.startswith("init."):
        assert (entry["moves"], entry["unit"], entry["source"]) == (
            "setup_s", "s", "program_span")
    else:
        assert (entry["moves"], entry["source"]) == (
            "train_tokens_per_s", "program_counter")
    cells = {
        "kernels.flash_dkv_empty_steps": [
            "trinity-train-s8k-1chip", "sdar-train-blockdiff-s8k-1chip"],
        "exchange.planned_bytes_per_step": ["gpt2m-train-dp4"],
    }.get(name, ALL_CELLS)
    assert entry["workloads"] == cells
