"""The program's compile ledger and step plan, read from its span ring
(``lib/program_spans.py: snapshot``): ``hvd.init.jit_trace`` / ``jit_lower`` /
``jit_compile`` (one span an outermost trace, lowering or compile of a jit,
``horovod_tpu/common/compile_cache.py``), ``hvd.kernels.flash_call`` (one a
``pallas_call`` of the flash kernels while JAX traces it) and
``hvd.exchange.plan``. The step's events are found from inside the ring, by
no name the benchmark chooses: the outermost ``jit_trace`` span whose
interval holds a ``hvd.trainer.trace_update`` span is the step's trace, and
the ``jit_lower`` and ``jit_compile`` spans of that function that follow it
are its lowering and its compile. Every function here gives None (or an
empty list) over a ring without these spans, as the parent of the PR that
brought them has."""

from . import program_spans

TRACE = "hvd.init.jit_trace"
LOWER = "hvd.init.jit_lower"
COMPILE = "hvd.init.jit_compile"
JIT = (TRACE, LOWER, COMPILE)
FLASH_CALL = "hvd.kernels.flash_call"
PLAN = "hvd.exchange.plan"
SLACK_S = 1e-3  # a span's start is on the wall clock, its length is not


def seconds(record) -> float:
    return record["dur_ms"] / 1e3


def end(record) -> float:
    return record["ts"] + seconds(record)


def holds(outer, inner) -> bool:
    return (outer["ts"] - SLACK_S <= inner["ts"]
            and end(inner) <= end(outer) + SLACK_S)


def tag(record, key, default=0):
    return record.get("tags", {}).get(key, default)


def named(readings, *names) -> list:
    return [r for r in program_spans.snapshot(readings)
            if r.get("name") in names and "dur_ms" in r and "ts" in r]


def outermost(readings, *names) -> list:
    """The ledger's spans that are no child of another (``depth``)."""
    return [r for r in named(readings, *names) if not tag(r, "depth")]


def step_events(readings) -> dict:
    """``{"trace": .., "lower": .., "compile": ..}``: the step's own spans,
    each None where the ring has none (a lowering under the ledger's 20 ms
    floor is in a tally and no span)."""
    found = dict.fromkeys(("trace", "lower", "compile"))
    updates = named(readings, "hvd.trainer.trace_update")
    for trace in outermost(readings, TRACE):
        if any(holds(trace, u) for u in updates):
            found["trace"] = trace
            break
    else:
        return found
    fun = tag(trace, "fun", "")
    # JAX names a lowering and a compile by the module, jit(<function>)
    names = (fun, f"jit({fun})", f"jit_{fun}")
    for key, name in (("lower", LOWER), ("compile", COMPILE)):
        for r in outermost(readings, name):
            if r["seq"] > trace["seq"] and tag(r, "fun") in names:
                found[key] = r
                break
    return found


def step_seconds(readings, key):
    record = step_events(readings)[key]
    return None if record is None else seconds(record)


def in_step_trace(readings, name) -> list:
    """The spans called ``name`` inside one trace of the step."""
    trace = step_events(readings)["trace"]
    if trace is None:
        return []
    return [r for r in named(readings, name) if holds(trace, r)]
