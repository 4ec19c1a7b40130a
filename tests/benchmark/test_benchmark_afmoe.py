"""The ``afmoe`` family's files of the benchmark at a CPU test size
(``data/tiny-afmoe.json``, ``data/BENCHMARK.afmoe.json``): the loop end to
end through ``drive_afmoe.py`` with the timed path whole and with a planted
routing fault; the work file's counts against a hand reckoning; the
family's scope rules on a compiled step; the new readers where there is
nothing to read; the cell's entries in ``BENCHMARK.json``."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import manifest, scopes

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
CELL = "trinity-train-s8k-1chip"
NEW_METRICS = (
    "model.mfu.train.family", "flash_fwd_roofline.gqa_window",
    "flash_dq_roofline.gqa_window", "flash_dkv_roofline.gqa_window",
    "moe.experts_roofline", "model.moe_ms_per_step",
    "moe.dispatch_ms_per_step")


def drive(fault=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    cmd = [sys.executable, os.path.join(HERE, "drive_afmoe.py"),
           "test-train-afmoe", "0", "1.0"] + ([fault] if fault else [])
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stderr


def test_the_loop_runs_the_family_end_to_end_and_is_correct():
    line, stderr = drive()
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(line["compared"]) >= {"loss3_gap", "grad_gap", "change_gap",
                                     "compiles_in_window"}
    assert "correct = true" in stderr


def test_a_routing_fault_in_the_timed_path_is_not_correct():
    line, stderr = drive("top1_routing")
    assert line["correct"] is False
    for name in ("grad_gap", "change_gap"):
        c = line["compared"][name]
        assert c["value"] > 100 * c["limit"], (name, c)
    assert "correct = false" in stderr


# ------------------------------------------------------- the work file

def _published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-mini.json")) as f:
        return json.load(f)


def _work():
    return manifest.load_module("work", "afmoe")


def test_band_pairs_and_expert_evaluations_by_hand():
    w, cfg = _work(), _published()
    # full layer: row r sees r + 1 keys
    assert w.score_pairs(cfg, 8192, "full_attention") == sum(
        r + 1 for r in range(8192))
    # window layer: row r sees min(r + 1, 2048): S*W - W^2/2 + W/2
    band = sum(min(r + 1, 2048) for r in range(8192))
    assert w.score_pairs(cfg, 8192, "sliding_attention") == band
    assert band == 8192 * 2048 - 2048 * 2048 // 2 + 1024
    assert 0.43 < band / w.score_pairs(cfg, 8192, "full_attention") < 0.45
    # a sequence inside the window is a causal one
    assert w.score_pairs(cfg, 1024, "sliding_attention") == 1024 * 1025 / 2
    # top-8 of 128 with 16 held: one routed evaluation a token expected here
    assert w.expert_evaluations_per_token(cfg) == 1.0


def test_forward_flops_by_hand():
    w, cfg = _work(), _published()
    parts = w.forward_flops_per_token(cfg, 8192)
    d, heads = 2048, 32 * 128
    # q, gate and o at 4096, k and v at 2 x 512, in five layers
    assert parts["projections"] == 5 * 2 * d * (3 * heads + 2 * 4 * 128)
    band = (8192 * 2048 - 2048 * 2047 / 2) / 8192
    assert parts["scores"] == pytest.approx(
        4 * heads * (4 * band + 8193 / 2), rel=1e-12)
    assert parts["dense_mlp"] == 3 * 2 * d * 6144
    expert = 3 * 2 * d * 1024
    assert parts["experts"] == 4 * (2 * d * 128 + expert * (1 + 1.0))
    assert parts["head"] == 2 * d * 25024
    total = sum(parts.values())
    assert total == pytest.approx(737.95e6, rel=1e-4)
    assert w.train_flops_per_token(cfg, 8192) == 3 * total
    # the mechanisms the other cells lack (all but the head and the dense
    # layer's feed-forward) are over three quarters of the counted work
    assert (total - parts["head"] - parts["dense_mlp"]) / total > 0.75


def test_flash_and_expert_calls_by_hand():
    w, cfg = _work(), _published()
    pairs = w.score_pairs(cfg, 8192, "sliding_attention")
    flops, nbytes = w.flash_call_work(cfg, "flash_dkv", 2, 8192,
                                      "sliding_attention")
    assert flops == 4 * 2 * 2 * 32 * pairs * 128  # four products, 32 heads
    # q, o, dO at 32 heads; K, V, dK, dV at 4; bf16
    assert nbytes == (3 * 32 + 4 * 4) * 2 * 8192 * 128 * 2
    _, fwd_bytes = w.flash_call_work(cfg, "flash_fwd", 2, 8192,
                                     "full_attention")
    assert fwd_bytes == (2 * 32 + 2 * 4) * 2 * 8192 * 128 * 2
    flops, nbytes = w.expert_matmul_work(cfg, 16384)
    assert flops == 2 * 16384 * 2048 * 1024
    assert nbytes == (16384 * (2048 + 1024) + 16 * 2048 * 1024) * 2
    least = w.experts_least_seconds_per_step(cfg, 16384, 197e12, 819e9)
    assert least == pytest.approx(9 * 4 * flops / 197e12)  # compute bound


# ------------------------------------------- the readers with nothing to read

def test_new_readers_return_none_where_there_is_nothing_to_read():
    readings = {"kind": "train", "cfg": {"model": "transformer"},
                "traffic": {"seq": 512, "batch_per_chip": 8, "remat": True},
                "chips": 1, "tokens_per_s": 1.0, "device_kind": "cpu",
                "trace": None}
    for name in NEW_METRICS:
        reader = manifest.load_module("metrics", name)
        assert reader.read(dict(readings)) is None, name
    # the family's work file is there, the device is not a chip: no share
    readings["cfg"] = dict(_published())
    for name in NEW_METRICS:
        reader = manifest.load_module("metrics", name)
        assert reader.read(dict(readings)) is None, name


def test_mfu_of_the_family_from_a_rate():
    reader = manifest.load_module("metrics", "model.mfu.train.family")
    cfg = _published()
    value = reader.read({
        "cfg": cfg, "traffic": {"seq": 8192}, "chips": 1,
        "tokens_per_s": 30000.0, "device_kind": "TPU v5 lite"})
    assert value == pytest.approx(
        100 * 30000 * 3 * 737.951744e6 / 197e12, rel=1e-6)


# ------------------------------------------------- scopes on a compiled step

@pytest.fixture(scope="module")
def tiny_step_scopes():
    """``instruction -> op_name`` of the test cell's compiled step."""
    import jax

    from benchmark.lib import program

    cell = manifest.Cell(manifest.load_manifest(
        os.path.join(DATA, "BENCHMARK.afmoe.json")), "test-train-afmoe", DATA)
    family, t = cell.model(), cell.traffic
    model = family.build_model(cell.config, remat=t["remat"])
    hvd, mesh, opt = program.init_training(model, t)
    try:
        shapes = family.param_shapes(model, t["seq"])
        state = jax.eval_shape(opt.init, shapes)
        batch = family.make_batch(cell.config, t, hvd.size(), 0)
        step = program.make_train_step(hvd, model, opt, mesh)
        text = step.lower(shapes, state, *batch).compile().as_text()
    finally:
        hvd.shutdown()
    return scopes.scopes_of_hlo(text)


def test_every_class_of_the_family_is_in_a_compiled_step(tiny_step_scopes):
    rules = scopes.Rules("afmoe")
    assert rules.classes == (
        "exchange", "optimizer", "remat", "head_loss", "attention", "mlp",
        "moe", "moe_experts", "moe_shared", "embed", "unscoped")
    by_class = {}
    for name, op_name in tiny_step_scopes.items():
        by_class.setdefault(rules.classify(op_name), []).append(op_name)
    for cls in ("remat", "head_loss", "attention", "mlp", "moe",
                "moe_experts", "moe_shared", "embed", "optimizer"):
        assert by_class.get(cls), cls
    # the grouped matmuls, and only they, are the expert-matmul class
    assert all("moe_experts" in n for n in by_class["moe_experts"])
    assert any("gmm" in n for n in by_class["moe_experts"])
    # routing, sort, gather and combine are what is left of the module
    for scope in ("moe_route", "moe_dispatch", "moe_combine"):
        assert any(scope in n for n in by_class["moe"]), scope
    assert not any("moe_shared" in n or "moe_experts" in n
                   for n in by_class["moe"])


@pytest.mark.parametrize("op_name, cls", [
    ("jit(train_step)/jvp(Transformer)/block_2/moe/moe_experts/gmm", "moe_experts"),
    ("jit(train_step)/transpose(jvp(Transformer))/block_2/moe/moe_experts/tgmm",
     "moe_experts"),
    ("jit(train_step)/jvp(Transformer)/block_2/moe/moe_shared/shared/up/dot_general",
     "moe_shared"),
    ("jit(train_step)/jvp(Transformer)/block_2/moe/moe_route/router/dot_general",
     "moe"),
    ("jit(train_step)/jvp(Transformer)/block_2/moe/mul", "moe"),
    ("jit(train_step)/jvp(Transformer)/block_0/mlp/gate/dot_general", "mlp"),
    ("jit(train_step)/jvp(Transformer)/block_3/RMSNorm_2/mul", "mlp"),
    ("jit(train_step)/jvp(Transformer)/block_3/MultiHeadAttention_0/attn_full/"
     "flash_fwd", "attention"),
    ("jit(train_step)/transpose(jvp(Transformer))/block_1/rematted_computation/"
     "moe/moe_experts/gmm", "remat"),
    ("jit(train_step)/jvp(Transformer)/lm_head/dot_general", "head_loss"),
    ("jit(train_step)/jvp(Transformer)/RMSNorm_0/mul", "head_loss"),
    ("jit(train_step)/jvp(Transformer)/Embed_0/take", "embed"),
])
def test_scope_rules_of_the_family(op_name, cls):
    assert scopes.Rules("afmoe").classify(op_name) == cls


# -------------------------------------------------------- BENCHMARK.json

def test_the_cell_is_declared_as_the_issue_names_it():
    m = manifest.load_manifest()
    cell = manifest.Cell(m, CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "trinity-mini", "train-causal-b2s8192", 1)
    assert len(cell.entry["why"]) <= 200
    assert "8 x its share" in cell.entry["why"]
    t = cell.traffic
    assert (t["loop"], t["batch_per_chip"], t["seq"], t["remat"]) == (
        "train", 2, 8192, True)
    reported = {x["name"] for x in cell.per_layer}
    assert set(NEW_METRICS) <= reported
    assert {"kernels.flash_ms_per_step", "model.attention_ms_per_step",
            "model.mlp_ms_per_step", "model.head_loss_ms_per_step",
            "model.remat_ms_per_step", "trainer.unscoped_ms_per_step",
            "device.peak_hbm_gib.train", "init.trace_model_s"} <= reported
    # one chip: no exchange; GPT-2's shapes: not these readers
    assert not reported & {
        "exchange.exposed_collective_ms", "exchange.device_ms_per_step",
        "exchange.allreduce_bytes_per_step", "model.mfu.train",
        "flash_fwd_roofline", "flash_dq_roofline", "flash_dkv_roofline"}
    assert {x["name"] for x in cell.end_to_end} == {
        "train_tokens_per_s", "setup_s"}
    for name in NEW_METRICS:
        (entry,) = [x for x in m["per_layer"] if x["name"] == name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_tokens_per_s"
        assert manifest.load_module("metrics", name) is not None
    assert set(cell.limits()) >= {"loss3_gap", "grad_gap", "change_gap",
                                  "compiles_in_window", "nonfinite_losses"}


def test_the_configuration_keeps_every_published_width():
    cfg = _published()
    pub = cfg["published"]
    changed = {k for k, v in pub.items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types", "num_experts",
        "vocab_size"}
    (entry,) = [c for c in manifest.load_manifest()["configs"]
                if c["name"] == "trinity-mini"]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert cfg["layer_types"] == pub["layer_types"][:5] == [
        "sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
    assert (cfg["num_experts"], cfg["num_experts_total"],
            cfg["experts_held"]) == (16, pub["num_experts"], [0, 16])
    assert cfg["vocab_size"] * 8 == pub["vocab_size"]
    assert "eight chips share each layer" in cfg["deployment"]
    assert "1/8" in cfg["expert_load"]
    assert len(cfg["assumed"]) >= 8
