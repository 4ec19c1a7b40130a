"""The step's device time by the scope of each operation. JAX writes the
path of ``jax.named_scope`` names (flax's module names, the program's
``hvd_exchange`` / ``hvd_update`` / ``hvd_accumulate``) into every
operation's ``op_name``; the compiler keeps it in the optimised HLO
(``metadata={op_name="..."}``) and the profiler in each operation's event
metadata (stat ``tf_op``). One rule table, first match wins, sorts every
operation of the traced window into a class, and the classes partition the
device's busy time. The table is the program's own scopes (here) and the
model family's (``benchmark/scopes/<family>.py``).

Two ways to the scope of an operation, both keyed by the instruction's name
(the ``%fusion.12`` that starts a device event's name):

- :func:`scopes_of_hlo`, from the compiled step's own text. This is what
  a run uses: ``lib/xtrace.py: record`` deletes the trace's file once it
  has read names and times from it, so the step is lowered and compiled
  once more after the run and its text is read (:func:`step_hlo`); the
  names join only if every event of the trace has its instruction there
  with the same result shape and opcode (:func:`foreign_seconds`).
- :func:`scopes_of_xplane`, from a recorded ``.xplane.pb`` (a small reader
  of the protobuf wire format: ``jax.profiler.ProfileData`` shows an
  event's own stats but not its metadata's). This is what the test on
  the recorded v5e trace uses, and what a run can use once the trace's
  path is handed over.
"""

import re
import sys

from . import xtrace

# The program's own scopes, searched first (they are never inside a model's
# module), and after the family's rules the step function's own operations,
# one level under jit(...): the user's apply_updates, which the compiler
# fuses with the update. The model's classes and rules are its family's
# (``benchmark/scopes/<family>.py``: CLASSES, RULES).
PROGRAM_CLASSES = ("exchange", "optimizer")
PROGRAM_RULES = (
    ("exchange", r"(^|/)hvd_exchange(/|$)"),
    ("optimizer", r"(^|/)hvd_(update|accumulate)(/|$)"),
)
STEP_RULES = (
    ("optimizer", r"^jit\([^/]*\)/(shard_map/)?[\w\-]+:?$"),
)
_COLLECTIVE_STAGE = re.compile(r"/collective(/|$)")
_PROGRAM_SCOPE = re.compile(r"(^|/)hvd_(exchange|update|accumulate)(/|$)")


class Rules:
    """One rule table: ``(class, pattern searched in the operation's
    op_name)``, first match wins; ``classes`` in the order they print."""

    def __init__(self, family: str = ""):
        from . import manifest

        module = manifest.load_module("scopes", family) if family else None
        self.classes = PROGRAM_CLASSES + tuple(
            getattr(module, "CLASSES", ())) + ("unscoped",)
        self.rules = tuple(
            (cls, re.compile(pattern)) for cls, pattern in
            PROGRAM_RULES + tuple(getattr(module, "RULES", ())) + STEP_RULES)

    def classify(self, op_name: str) -> str:
        if op_name:
            for cls, pattern in self.rules:
                if pattern.search(op_name):
                    return cls
        return "unscoped"


def instruction(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ")[0].strip().lstrip("%")


_HLO_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?"
    r'metadata=\{[^}]*?op_name="([^"]*)"', re.M)


_HLO_NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ", re.M)


def scopes_of_hlo(hlo_text: str) -> dict:
    """Instruction name -> ``op_name`` over every computation of an
    optimised module's text; ``""`` for an instruction without one."""
    out = dict.fromkeys(_HLO_NAME.findall(hlo_text), "")
    out.update(_HLO_LINE.findall(hlo_text))
    return out


# layouts ``{1,0:T(8,128)}`` and the printer's ``/*index=5*/`` marks in
# long tuples: how a shape is printed, not what it is
_NOISE = re.compile(r"\{[^{}]*\}|/\*.*?\*/")
_HEAD = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = (.*?) ([\w\-]+)\(")
_ARRAY = re.compile(r"[a-z]\w*\[[\d,]*\]")


def signature(text: str):
    """``(result arrays, opcode)`` of an instruction as the compiler
    prints it, in a module's text and in a device event's name alike:
    ``%fusion.12 = bf16[8,512]{1,0:T(8,128)} fusion(...)`` ->
    ``(("bf16[8,512]",), "fusion")``; a tuple gives its arrays in order.
    None for a bare name (and for a name cut short before its opcode)."""
    m = _HEAD.match(_NOISE.sub("", text))
    return (tuple(_ARRAY.findall(m.group(1))), m.group(2)) if m else None


def signatures_of_hlo(hlo_text: str) -> dict:
    """Instruction name -> :func:`signature` over a module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_NAME.match(line)
        if m:
            out[m.group(1)] = signature(line)
    return out


# ---------------------------------------------------------- .xplane.pb

def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one protobuf message; the value
    of a length-delimited field is a memoryview of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield field, wire, value


def _map_value(entry):
    for field, _, value in _fields(entry):
        if field == 2:
            return value
    return None


def scopes_of_xplane(path: str) -> dict:
    """Instruction name -> ``tf_op`` from the event metadata of the device
    planes of an ``.xplane.pb`` (XSpace.planes=1; XPlane.name=2,
    .event_metadata=4, .stat_metadata=5; XEventMetadata.name=2, .stats=5;
    XStat.metadata_id=1, .str_value=5, .ref_value=7)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, _, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f2, _, value in _fields(plane):
            if f2 == 2:
                name = bytes(value).decode()
            elif f2 == 4:
                events.append(_map_value(value))
            elif f2 == 5:
                sid, sname = 0, ""
                for f3, _, v in _fields(_map_value(value)):
                    if f3 == 1:
                        sid = v
                    elif f3 == 2:
                        sname = bytes(v).decode()
                stat_names[sid] = sname
        if not xtrace.DEVICE_PLANE.match(name):
            continue
        for meta in events:
            ev_name, tf_op = "", None
            for f3, _, v in _fields(meta):
                if f3 == 2:
                    ev_name = bytes(v).decode()
                elif f3 == 5:
                    stat = {f4: v4 for f4, _, v4 in _fields(v)}
                    if stat_names.get(stat.get(1)) != "tf_op":
                        continue
                    if 5 in stat:
                        tf_op = bytes(stat[5]).decode()
                    elif 7 in stat:
                        tf_op = stat_names.get(stat[7], "")
            if ev_name and tf_op is not None:
                out[instruction(ev_name)] = tf_op
    return out


# ------------------------------------------------------------ reduction

def class_table(trace, scope_of: dict, rules: Rules) -> dict:
    """``{class: {"s": seconds, "ops": {compiler's name: seconds}}}`` over
    the trace's window, averaged over its devices. Every instant in which
    a device runs an operation is counted once, for the operation that
    started first, so the classes sum to ``trace.busy_s``. ``"collective"``
    (no class: a part of ``exchange``) is the exchange's time in
    collectives, by the compiler's name or by the program's stage
    ``collective`` (the compiler calls an all-reduce of one array after
    its primitive, ``psum.12``)."""
    start, end = trace.window
    table = {c: {"s": 0.0, "ops": {}} for c in rules.classes + ("collective",)}
    n = max(len(trace.devices), 1)
    for dev in trace.devices.values():
        cursor = start
        for name, s, e in dev.ops:  # sorted by start
            s, e = max(s, cursor), min(e, end)
            if e <= s:
                continue
            cursor = e
            base = xtrace.base_name(name)
            op_name = scope_of.get(instruction(name), "")
            cls = rules.classify(op_name)
            targets = [cls]
            if cls == "exchange" and (
                    xtrace.COLLECTIVE.match(base)
                    or _COLLECTIVE_STAGE.search(op_name)):
                targets.append("collective")
            for t in targets:
                row = table[t]
                row["s"] += (e - s) / 1e9 / n
                row["ops"][base] = row["ops"].get(base, 0.0) + (e - s) / 1e9 / n
    return table


def foreign_seconds(trace, signatures: dict) -> float:
    """Seconds a device of the window spent, on average, in operations
    that are not the module's: an event whose instruction the module does
    not have, or has with another result shape or opcode (the same name
    for another operation: the module was numbered differently)."""
    start, end = trace.window
    total = 0
    for dev in trace.devices.values():
        for name, s, e in dev.ops:
            if e <= start or s >= end:
                continue
            known = instruction(name) in signatures
            mine, theirs = signature(name), signatures.get(instruction(name))
            if not known or (mine and theirs and mine != theirs):
                total += min(e, end) - max(s, start)
    return total / 1e9 / max(len(trace.devices), 1)


def has_program_scopes(scope_of: dict) -> bool:
    """Whether the program names its own work (the parent of the PR that
    brought the scopes does not: its exchange and update are anonymous)."""
    return any(_PROGRAM_SCOPE.search(v) for v in scope_of.values())


def print_table(table: dict, classes, steps: int, out=None) -> None:
    out = out or sys.stderr
    print("device time by scope (ms a step; the three largest operations "
          "of each class):", file=out)
    for cls in tuple(classes) + ("collective",):
        row = table[cls]
        top = sorted(row["ops"].items(), key=lambda kv: -kv[1])[:3]
        print("  %-11s %9.3f  %s" % (
            cls + ("*" if cls == "collective" else ""),
            row["s"] * 1e3 / max(steps, 1),
            ", ".join("%s %.3f" % (k, v * 1e3 / max(steps, 1))
                      for k, v in top)), file=out)
    print("  (* part of exchange)", file=out)


# ------------------------------------------- from a run's readings

def step_hlo(readings: dict) -> str:
    """The optimised HLO of the run's training step: the step is built,
    lowered and compiled again from the run's configuration and traffic
    with abstract arguments (no weights). The seconds of both go to
    standard error: a compile of seconds is the persistent cache's
    answer, one of half a minute is not."""
    import time

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from . import manifest, program

    cfg, t = readings["cfg"], readings["traffic"]
    family = manifest.load_module("models", cfg["model"])
    model = family.build_model(cfg, remat=t["remat"])
    hvd, mesh, opt = program.init_training(model, t)
    replicated = NamedSharding(mesh, P())

    def abstract(tree, sharding):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    shapes = family.param_shapes(model, t["seq"])
    params = abstract(shapes, replicated)
    state = abstract(jax.eval_shape(opt.init, shapes), replicated)
    batch = abstract(tuple(family.make_batch(cfg, t, hvd.size(), 0)),
                     hvd.rank_sharding(mesh))
    step = program.make_train_step(hvd, model, opt, mesh)
    t0 = time.perf_counter()
    lowered = step.lower(params, state, *batch)
    t1 = time.perf_counter()
    text = lowered.compile().as_text()
    print("scopes: the step once more, trace+lower %.1f s, compile and "
          "print %.1f s" % (t1 - t0, time.perf_counter() - t1),
          file=sys.stderr)
    return text


KEY = "scope_table"


def table_of(readings: dict):
    """``(class table, steps, program names its work)`` of a training run's
    traced window, computed and printed once a run and kept in the readings
    under ``KEY``; None where there is no device trace or the operations
    of the trace are not those of the rebuilt step."""
    trace = readings.get("trace")
    if readings.get("kind") != "train" or trace is None or not trace.devices:
        return None
    if KEY not in readings:
        readings[KEY] = _table_of(readings, trace)
    return readings[KEY]


def _table_of(readings, trace):
    from . import program_spans

    program_spans.snapshot(readings)  # before the step is traced again
    steps = xtrace.step_count(trace)
    if not steps:
        return None
    text = step_hlo(readings)
    scope_of = scopes_of_hlo(text)
    rules = Rules(readings["cfg"].get("model", ""))
    table = class_table(trace, scope_of, rules)
    # the names join the trace to the rebuilt module only if it is the
    # module that ran: every event's instruction must be there with the
    # event's own result shape and opcode, else nothing is said
    foreign = foreign_seconds(trace, signatures_of_hlo(text))
    busy = trace.busy_s
    total = sum(table[c]["s"] for c in rules.classes)
    print("scopes: classes sum to %.6f s of %.6f s busy; %.6f s in "
          "operations that the rebuilt step does not have as the trace "
          "has them" % (total, busy, foreign), file=sys.stderr)
    if foreign > 0.005 * busy:
        return None
    print_table(table, rules.classes, steps)
    return table, steps, has_program_scopes(scope_of)


def ms_per_step(readings: dict, *classes, program_scope: bool = False):
    """Milliseconds a step in ``classes``; None without a table, where the
    model's family has no such class, and, with ``program_scope``, where
    the program does not name its own work."""
    got = table_of(readings)
    if got is None:
        return None
    table, steps, named = got
    if (program_scope and not named) or not all(c in table for c in classes):
        return None
    return sum(table[c]["s"] for c in classes) * 1e3 / steps
