"""The step's device time by scope (``benchmark/lib/scopes.py``) and the
readers of the program's own spans (``lib/program_spans.py``): the rule
table, the two ways to an operation's scope, the partition of the busy time
on the trace recorded on a TPU v5e, and the eleven per-layer metrics that
read them."""

import json
import os

import pytest

from benchmark.lib import manifest, program_spans, scopes, xtrace
from benchmark.lib.xtrace import DeviceTrace, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "data", "train_w1.xplane.pb")
DATA = os.path.join(HERE, "data")

SCOPE_METRICS = (
    "exchange.device_ms_per_step",
    "trainer.optimizer_ms_per_step", "model.head_loss_ms_per_step",
    "model.attention_ms_per_step", "model.mlp_ms_per_step",
    "model.remat_ms_per_step", "trainer.unscoped_ms_per_step",
)
SPAN_METRICS = ("init.hvd_init_s", "init.place_state_s",
                "init.trace_optimizer_s", "init.trace_model_s")


RULES = scopes.Rules("transformer")


@pytest.mark.parametrize("op_name, cls", [
    ("jit(train_step)/shard_map/hvd_exchange/collective/psum", "exchange"),
    ("hvd_exchange/collective/psum", "exchange"),
    ("jit(train_step)/hvd_exchange/convert_element_type", "exchange"),
    ("jit(train_step)/hvd_update/mul", "optimizer"),
    ("jit(train_step)/hvd_accumulate/add", "optimizer"),
    ("jit(train_step)/add", "optimizer"),  # the user's apply_updates
    ("jit(train_step)/shard_map/add:", "optimizer"),
    ("jit(train_step)/transpose(jvp(Transformer))/checkpoint/"
     "rematted_computation/block_3/MultiHeadAttention_0/out/dot_general",
     "remat"),
    ("jit(train_step)/jvp(Transformer)/lm_head/dot_general", "head_loss"),
    ("jit(train_step)/jvp(Transformer)/LayerNorm_0/mul", "head_loss"),
    ("jit(train_step)/shard_map/jvp(jit(take_along_axis))/gather",
     "head_loss"),
    ("jit(train_step)/transpose(jvp(jit(take_along_axis)))/scatter-add",
     "head_loss"),
    ("jit(train_step)/shard_map/transpose(jvp())/mul", "head_loss"),
    ("jvp()/reduce_max", "head_loss"),
    ("jit(train_step)/jvp(Transformer)/block_0/MultiHeadAttention_0/out/"
     "convert_element_type:", "attention"),
    ("jit(train_step)/transpose(jvp(Transformer))/checkpoint/block_7/"
     "Dense_1/dot_general", "mlp"),
    ("jit(train_step)/jvp(Transformer)/Embed_0/jit(_take)/gather", "embed"),
    ("jit(train_step)/shard_map/transpose(jvp(jit(_take)))/scatter-add",
     "embed"),
    # no rule sweeps up what is differentiated: another model's backward
    # is not this family's head
    ("jit(train_step)/transpose(jvp(ResNet))/conv_3/conv_general_dilated",
     "unscoped"),
    ("jit(train_step)/jvp(jit(_roll_static))/slice", "unscoped"),
    ("", "unscoped"),
    ("reduce_sum", "unscoped"),
])
def test_rule_table(op_name, cls):
    assert RULES.classify(op_name) == cls


def test_a_family_without_rules_keeps_the_programs_own():
    rules = scopes.Rules("no-such-family")
    assert rules.classes == ("exchange", "optimizer", "unscoped")
    assert rules.classify("jit(s)/hvd_exchange/collective/psum") == "exchange"
    assert rules.classify(
        "jit(s)/jvp(Transformer)/block_0/MultiHeadAttention_0/out") == (
        "unscoped")


@pytest.mark.parametrize("text, sig", [
    ("%fusion.334 = s32[1,4,4,128]{3,2,1,0:T(4,128)S(1)} fusion(s32[1,4,512]"
     "{2,1,0:T(4,128)} %tokens.1), kind=kLoop, calls=%fused_computation.398",
     (("s32[1,4,4,128]",), "fusion")),
    ("  ROOT %mul.3 = f32[8]{0} multiply(%p, %p), metadata={op_name=\"a\"}",
     (("f32[8]",), "multiply")),
    ("%flash_fwd.4 = (bf16[16,512,64]{2,1,0:T(8,128)(2,1)S(1)}, f32[16,512,1]"
     "{2,1,0:T(8,128)}) custom-call(bf16[16,512,64]{2,1,0} %bitcast)",
     (("bf16[16,512,64]", "f32[16,512,1]"), "custom-call")),
    # a long tuple: the printer marks every fifth element, or does not
    ("%all-reduce.7 = (f32[4]{0}, f32[2,2]{1,0}, f32[]{:S(2)}, f32[1]{0}, "
     "f32[3]{0}, /*index=5*/f32[5]{0}) all-reduce(%a, %b, %c, %d, %e, "
     "/*index=5*/%f), replica_groups={{0,1},{2,3}}",
     (("f32[4]", "f32[2,2]", "f32[]", "f32[1]", "f32[3]", "f32[5]"),
      "all-reduce")),
    ("all-reduce.7 = (f32[4], f32[2,2], f32[], f32[1], f32[3], f32[5]) "
     "all-reduce(f32[4] a, f32[2,2] b), replica_groups={{0,1},{2,3}}",
     (("f32[4]", "f32[2,2]", "f32[]", "f32[1]", "f32[3]", "f32[5]"),
      "all-reduce")),
    ("flash_fwd", None),
    ("%all-reduce.7 = (f32[4]{0}, f32[2,2]{1,0}, f32[]{:S(2", None),  # cut
])
def test_signature_of_an_instruction_as_printed(text, sig):
    assert scopes.signature(text) == sig


HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %mul.3 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/hvd_update/mul" source_file="a.py" source_line=3}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %all-reduce.1 = f32[8]{0} all-reduce(%a), replica_groups={}, metadata={op_name="jit(step)/shard_map/hvd_exchange/collective/psum" stack_frame_id=4}
  %copy-done.2 = f32[8]{0} copy-done(%all-reduce.1)
  ROOT %fusion.7 = f32[8]{0} fusion(%copy-done.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/hvd_update/mul"}
}
"""


def test_scopes_of_hlo_reads_every_computation():
    got = scopes.scopes_of_hlo(HLO)
    assert got["all-reduce.1"].endswith("hvd_exchange/collective/psum")
    assert got["fusion.7"] == got["mul.3"] == "jit(step)/hvd_update/mul"
    assert got["copy-done.2"] == "" and got["p"] == ""
    sigs = scopes.signatures_of_hlo(HLO)
    assert sigs["fusion.7"] == (("f32[8]",), "fusion")
    assert sigs["copy-done.2"] == (("f32[8]",), "copy-done")
    assert set(sigs) == set(got)
    assert scopes.has_program_scopes(got)
    assert not scopes.has_program_scopes({"x": "jit(step)/add"})


def test_instruction_of_an_event_name():
    assert scopes.instruction(
        "%fusion.334 = s32[1,4]{1,0} fusion(s32[4] %x), kind=kLoop"
    ) == "fusion.334"
    assert scopes.instruction("flash_fwd") == "flash_fwd"


def _hand_made():
    # device 0: exchange 0-40 (a collective 10-30 inside a cast fusion
    # that started first, then an all-reduce that the compiler named after
    # its primitive 30-40), update 40-70, a copy-done without a scope
    # 70-80, idle 80-100; device 1: all attention
    d0 = DeviceTrace(
        [("%fusion.1 = f32[] fusion()", 0, 30),
         ("%all-reduce.1 = f32[] all-reduce()", 10, 30),
         ("%psum.7 = f32[] all-reduce()", 30, 40),
         ("%fusion.2 = f32[] fusion()", 40, 70),
         ("%copy-done.2 = f32[] copy-done()", 70, 80)],
        [("jit_step", 0, 100)])
    d1 = DeviceTrace([("%flash_fwd = f32[] custom-call()", 0, 100)],
                     [("jit_step", 0, 100)])
    scope_of = {
        "fusion.1": "jit(s)/hvd_exchange/convert_element_type",
        "all-reduce.1": "jit(s)/hvd_exchange/collective/psum",
        "psum.7": "jit(s)/hvd_exchange/collective/psum",
        "fusion.2": "jit(s)/hvd_update/mul",
        "flash_fwd": "jit(s)/jvp(Transformer)/block_0/MultiHeadAttention_0/f",
    }
    return Trace({0: d0, 1: d1}, [], (0, 100)), scope_of


def test_classes_partition_the_busy_time_and_average_over_devices():
    trace, scope_of = _hand_made()
    table = scopes.class_table(trace, scope_of, RULES)
    # an instant is counted once, for the operation that started first
    assert table["exchange"]["s"] == pytest.approx(40 / 2 / 1e9)
    assert table["collective"]["s"] == pytest.approx(10 / 2 / 1e9)
    assert table["optimizer"]["s"] == pytest.approx(30 / 2 / 1e9)
    assert table["unscoped"]["s"] == pytest.approx(10 / 2 / 1e9)
    assert table["unscoped"]["ops"] == {"copy-done": pytest.approx(5e-9)}
    assert table["attention"]["s"] == pytest.approx(100 / 2 / 1e9)
    total = sum(table[c]["s"] for c in RULES.classes)
    assert total == pytest.approx(trace.busy_s)


def test_an_event_that_is_another_operation_is_foreign():
    """The join rests on names: the same name for another operation (the
    rebuilt module numbered its instructions differently) must show."""
    trace, _ = _hand_made()
    mine = {"fusion.1": (("f32[]",), "fusion"),
            "all-reduce.1": (("f32[]",), "all-reduce"),
            "psum.7": (("f32[]",), "all-reduce"),
            "fusion.2": (("f32[]",), "fusion"),
            "copy-done.2": (("f32[]",), "copy-done"),
            "flash_fwd": (("f32[]",), "custom-call")}
    assert scopes.foreign_seconds(trace, mine) == 0
    # fusion.2 is a copy there, and copy-done.2 has another shape
    theirs = dict(mine, **{"fusion.2": (("f32[]",), "copy"),
                           "copy-done.2": (("f32[8]",), "copy-done")})
    assert scopes.foreign_seconds(trace, theirs) == pytest.approx(
        (30 + 10) / 2 / 1e9)
    # and an instruction the module does not have at all
    del theirs["flash_fwd"]
    assert scopes.foreign_seconds(trace, theirs) == pytest.approx(
        (30 + 10 + 100) / 2 / 1e9)


@pytest.fixture(scope="module")
def recorded():
    return (xtrace.steady_steps(xtrace.load(FIXTURE), 1),
            scopes.scopes_of_xplane(FIXTURE))


def test_recorded_trace_carries_scopes_in_its_event_metadata(recorded):
    _, scope_of = recorded
    assert len(scope_of) > 100
    assert any("MultiHeadAttention" in v for v in scope_of.values())
    assert any("rematted_computation" in v for v in scope_of.values())
    # recorded before the program named its own work
    assert not scopes.has_program_scopes(scope_of)


def test_recorded_trace_is_partitioned(recorded):
    trace, scope_of = recorded
    table = scopes.class_table(trace, scope_of, RULES)
    total = sum(table[c]["s"] for c in RULES.classes)
    assert total == pytest.approx(trace.busy_s, rel=5e-3)
    assert table["exchange"]["s"] == 0 and table["collective"]["s"] == 0
    for cls in ("remat", "head_loss", "attention", "mlp", "embed"):
        assert table[cls]["s"] > 0, cls
    # the flash kernels are inside the attention class or remat's forward
    flash = sum(table[c]["ops"].get(k, 0.0) for c in ("attention", "remat")
                for k in ("flash_fwd", "flash_dq", "flash_dkv"))
    seconds = trace.op_seconds()
    assert flash == pytest.approx(sum(
        seconds[k] for k in ("flash_fwd", "flash_dq", "flash_dkv")))
    # what no scope names in that trace is the compiler's own copies
    left = table["unscoped"]
    assert left["s"] < 0.08 * trace.busy_s
    assert max(left["ops"], key=left["ops"].get) == "copy-done"


def test_the_table_prints_each_class_with_its_largest_operations(recorded):
    import io

    trace, scope_of = recorded
    out = io.StringIO()
    scopes.print_table(scopes.class_table(trace, scope_of, RULES),
                       RULES.classes, xtrace.step_count(trace), out)
    text = out.getvalue()
    for cls in RULES.classes:
        assert cls in text
    assert "flash_dkv" in text


def _readings(world_traffic="test-train-tiny"):
    with open(os.path.join(DATA, "tiny-causal.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(DATA, "traffic", world_traffic + ".json")) as f:
        traffic = json.load(f)
    return {"kind": "train", "cfg": cfg, "traffic": traffic, "chips": 8}


def test_the_step_is_rebuilt_from_the_readings_and_names_its_work(hvd):
    """What a run does after its window: rebuild the step from the run's
    configuration, read its scopes, and sort a trace of it. The trace here
    is made of the rebuilt step's own instructions (a CPU run has no
    device plane)."""
    readings = _readings()
    text = scopes.step_hlo(readings)
    scope_of = scopes.scopes_of_hlo(text)
    assert scopes.has_program_scopes(scope_of)
    # an event's name is the instruction as the compiler prints it
    lines = [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()
             if " = " in ln and 'op_name="' in ln]
    ops = [(ln, 10 * i, 10 * i + 10) for i, ln in enumerate(lines)]
    t = 10 * len(ops)
    trace = Trace({0: DeviceTrace(ops, [("jit_train_step", 0, t)])}, [],
                  (0, t))
    readings["trace"] = trace
    table, steps, named_work = scopes.table_of(readings)
    assert steps == 1 and named_work
    assert sum(table[c]["s"] for c in RULES.classes) == pytest.approx(
        trace.busy_s)
    assert table["exchange"]["s"] > 0 and table["optimizer"]["s"] > 0
    for cls in ("head_loss", "attention", "mlp", "embed"):
        assert table[cls]["s"] > 0, cls
    # without a scope: what the compiler names itself ("reduce_sum")
    assert table["unscoped"]["s"] < 0.15 * trace.busy_s
    for name in SCOPE_METRICS:
        value = manifest.load_module("metrics", name).read(readings)
        assert value is not None and value >= 0, name
    readings["traffic"] = dict(readings["traffic"], remat=False)
    assert manifest.load_module(
        "metrics", "model.remat_ms_per_step").read(readings) is None
    # a trace of another module is not sorted by this module's names
    del readings[scopes.KEY]
    readings["trace"] = Trace(
        {0: DeviceTrace([("%other.1 = f32[] op()", 0, 10)],
                        [("jit_other", 0, 10)])}, [], (0, 10))
    assert scopes.table_of(readings) is None
    # nor is a trace whose names are this module's for other operations
    del readings[scopes.KEY]
    readings["trace"] = Trace({0: DeviceTrace(
        [(ln.replace("f32[", "s8["), s, e) for ln, s, e in ops],
        [("jit_train_step", 0, t)])}, [], (0, t))
    assert scopes.table_of(readings) is None
    assert manifest.load_module(
        "metrics", "model.mlp_ms_per_step").read(readings) is None


@pytest.mark.parametrize("name", SCOPE_METRICS + SPAN_METRICS)
def test_each_new_metric_has_a_reader_and_an_entry(name):
    entry = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}[name]
    reader = manifest.load_module("metrics", name)
    assert reader is not None and entry["workloads"]
    # no device trace (a serving run, a CPU run): nothing, and no error
    if name in SCOPE_METRICS:
        assert reader.read({"kind": "train", "trace": None}) is None
        assert reader.read({"kind": "serve"}) is None
        assert entry["source"] == "device_trace"
        assert entry["moves"] == "train_tokens_per_s"
    else:
        assert entry["moves"] == "setup_s" and entry["unit"] == "s"


def test_span_readers_sum_the_programs_own_spans():
    ring = [
        {"name": "hvd.init", "dur_ms": 1500.0},
        {"name": "hvd.engine.decode_step", "dur_ms": 700.0},
        {"name": "hvd.init.broadcast_parameters", "dur_ms": 2000.0},
        {"name": "hvd.init.optimizer_init", "dur_ms": 250.0},
        {"name": "hvd.init.broadcast_optimizer_state", "dur_ms": 750.0},
        {"name": "hvd.trainer.trace_model", "dur_ms": 100.0},
        {"name": "hvd.trainer.trace_model", "dur_ms": 900.0},
    ]
    read = {n: manifest.load_module("metrics", n).read(
        {program_spans.KEY: ring}) for n in SPAN_METRICS}
    assert read == {
        "init.hvd_init_s": pytest.approx(1.5),
        "init.place_state_s": pytest.approx(3.0),
        "init.trace_model_s": pytest.approx(1.0),
        # a program that recorded no such span (the parent): nothing
        "init.trace_optimizer_s": None,
    }
