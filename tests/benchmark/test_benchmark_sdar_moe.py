"""The ``sdar_moe`` family's files of the benchmark at a CPU test size
(``data/tiny-sdar-moe.json``, ``data/BENCHMARK.sdar_moe.json``): the new
loop kind end to end through ``drive_sdar_moe.py`` with the timed path whole
and with planted faults; the work file's counts against a hand reckoning and
a brute-force count of the mask; the family's scope rules on the compiled
step; the new readers where there is nothing to read; the cell's entries in
``BENCHMARK.json`` and the shape of its limits file."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.lib import manifest, scopes

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
CELL = "sdar-train-blockdiff-s8k-1chip"
CONFIG = "sdar-30b-a3b-chat"
NEW_METRICS = (
    "flash_fwd_roofline.block_diffusion", "flash_dq_roofline.block_diffusion",
    "flash_dkv_roofline.block_diffusion", "model.mfu.train.sdar_moe")


def drive(fault=None, trace=0):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    cmd = [sys.executable, os.path.join(HERE, "drive_sdar_moe.py"),
           "test-train-sdar-moe", str(trace), "0.5"] + (
               [fault] if fault else [])
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stderr


def test_the_new_loop_runs_the_family_end_to_end_and_is_correct():
    line, stderr = drive()
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) >= {"train_tokens_per_s", "setup_s"}
    assert set(line["compared"]) >= {"loss3_gap", "grad_gap", "change_gap",
                                     "compiles_in_window"}
    assert "correct = true" in stderr


@pytest.mark.parametrize("fault", [
    "leak_own_clean", "causal_in_block", "positions_2l", "no_weight",
    "half_blocks", "no_gate_norm", "top_k_less_1"])
def test_a_planted_fault_in_the_timed_path_is_not_correct(fault):
    line, stderr = drive(fault)
    assert line["correct"] is False
    for name in ("grad_gap", "change_gap"):
        c = line["compared"][name]
        assert c["value"] > 100 * c["limit"], (name, c)
    assert "correct = false" in stderr


def test_a_traced_run_makes_its_scope_table_from_the_step_that_ran():
    """``lib/scopes.py`` would build loop kind ``train``'s step again; this
    loop hands over the table of its own (CPU: the trace holds no device
    operation, so there is none, and no reader fails)."""
    line, stderr = drive(trace=1)
    assert line["correct"] is True
    assert "Traceback" not in stderr
    assert line["metrics"] == {}  # the test manifest lists no reader


# ------------------------------------------------------- the work file

def _published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def _work():
    return manifest.load_module("work", "sdar_moe")


@pytest.mark.parametrize("length, block", [
    (8, 4), (16, 4), (32, 4), (32, 8), (24, 2), (16, 16)])
def test_score_pairs_is_a_brute_force_count_of_the_mask(length, block):
    kept = 0
    for r in range(2 * length):
        for c in range(2 * length):
            bi, bj = r % length // block, c % length // block
            if r < length and c < length:
                kept += bi == bj
            elif r < length:
                kept += bj < bi
            elif c >= length:
                kept += bj <= bi
    assert _work().score_pairs(length, block) == kept
    ref = manifest.load_module("references", "sdar_moe")
    mask = ref.keep_mask({"block_length": block},
                         np.arange(2 * length), length)
    assert int(np.asarray(mask).sum()) == kept


def test_forward_flops_by_hand():
    w, cfg = _work(), _published()
    layers = cfg["num_hidden_layers"]
    parts = w.forward_flops_per_token(cfg, 8192)
    d = 2048
    # q and the output projection at 32 x 128, k and v at 4 x 128, and both
    # copies of a data token pass them: 18.87M parameters a layer (ISSUE 33)
    assert parts["projections"] == layers * 2 * 2 * d * (
        2 * 4096 + 2 * 512)
    assert parts["projections"] / (layers * 2 * 2) == 18_874_368
    # the mask keeps L^2 + L B pairs a head: 8196 keys a data token on
    # average, QK^T and PV over 128 at 32 heads
    assert w.score_pairs(8192, 4) == 67_141_632
    assert parts["scores"] == layers * 4 * 4096 * 8196
    # top-8 of 128 with 16 held: one routed evaluation a position expected
    # here, no shared expert; the router's 128 outputs; both copies
    assert w.expert_evaluations_per_position(cfg) == 1.0
    assert parts["experts"] == layers * 2 * (
        2 * d * 128 + 3 * 2 * d * 768)
    assert parts["head"] == 2 * d * 18992  # over the noised half alone
    total = sum(parts.values())
    assert w.train_flops_per_token(cfg, 8192) == 3 * total
    # the masked score products are ~55% of a data token's counted work,
    # the matrices 2 x 143 MFLOP a layer, the head 0.23 GFLOP (ISSUE 33)
    assert 0.54 < parts["scores"] / total < 0.57
    matrices = 3 * (parts["projections"] + parts["experts"]) / layers
    assert matrices == pytest.approx(2 * 143e6, rel=0.01)
    assert 3 * parts["head"] == pytest.approx(0.233e9, rel=0.01)


def test_flash_calls_by_hand():
    w, cfg = _work(), _published()
    pairs = 8192 * 8192 + 8192 * 4
    positions = 2 * 8192
    # forward: QK^T and PV at 32 heads; q, o at 32 heads and k, v at 4
    assert w.flash_call_work(cfg, "flash_fwd", 1, 8192) == (
        2 * 2 * 32 * pairs * 128, (2 * 32 + 2 * 4) * positions * 128 * 2)
    # dQ: the scores, dO V^T and dS K; q, o, dO, dQ and k, v
    assert w.flash_call_work(cfg, "flash_dq", 1, 8192) == (
        3 * 2 * 32 * pairs * 128, (4 * 32 + 2 * 4) * positions * 128 * 2)
    # dK/dV: the scores, P^T dO, dO V^T and dS^T Q; q, o, dO and k, v, dK, dV
    assert w.flash_call_work(cfg, "flash_dkv", 1, 8192) == (
        4 * 2 * 32 * pairs * 128, (3 * 32 + 4 * 4) * positions * 128 * 2)
    # the three together: 9 products, 25.1 ms a layer at the chip's peak
    least = sum(w.flash_call_work(cfg, k, 1, 8192)[0]
                for k in w.FLASH_KERNELS) / 197e12
    assert least == pytest.approx(25.1e-3, rel=0.01)
    # a quarter of the 2L x 2L square, twice a causal row of L
    assert pairs / positions ** 2 == pytest.approx(0.25, rel=1e-3)
    assert pairs / (8192 * 8193 / 2) == pytest.approx(2.0, rel=1e-3)


class _Trace:
    devices = {0: None}

    def __init__(self, calls):
        self.calls = calls

    def op_calls(self, kernel):
        return self.calls.get(kernel, [])


def test_a_roofline_share_from_a_trace():
    w, cfg = _work(), _published()
    layers = cfg["num_hidden_layers"]
    flops, _ = w.flash_call_work(cfg, "flash_dkv", 1, 8192)
    least = flops / 197e12  # compute bound
    readings = {"cfg": cfg, "device_kind": "TPU v5 lite",
                "traffic": {"batch_per_chip": 1, "seq": 8192},
                "trace": _Trace({"flash_dkv": [2 * least] * layers})}
    reader = manifest.load_module(
        "metrics", "flash_dkv_roofline.block_diffusion")
    assert reader.read(readings) == pytest.approx(50.0)
    assert manifest.load_module(
        "metrics", "flash_fwd_roofline.block_diffusion").read(
        readings) is None  # no such call in the trace: None, never 0


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


def test_mfu_of_the_family_counts_data_tokens():
    reader = manifest.load_module("metrics", "model.mfu.train.sdar_moe")
    cfg = _published()
    value = reader.read({
        "cfg": cfg, "traffic": {"seq": 8192}, "chips": 1,
        "tokens_per_s": 13600.0, "device_kind": "TPU v5 lite"})
    flops = _work().train_flops_per_token(cfg, 8192)
    assert value == pytest.approx(100 * 13600 * flops / 197e12, rel=1e-9)
    assert 25 < value < 35


# ------------------------------------------------- scopes on a compiled step

@pytest.fixture(scope="module")
def tiny_step_scopes():
    """``instruction -> op_name`` of the test cell's compiled step, the new
    loop's."""
    import jax

    from benchmark.lib import program
    from benchmark.loops import train_block_diffusion as loop

    cell = manifest.Cell(manifest.load_manifest(os.path.join(
        DATA, "BENCHMARK.sdar_moe.json")), "test-train-sdar-moe", DATA)
    assert cell.loop() is loop
    family, t = cell.model(), cell.traffic
    model = family.build_model(cell.config, remat=t["remat"])
    hvd, mesh, opt = program.init_training(model, t)
    try:
        shapes = family.param_shapes(model, t["seq"])
        state = jax.eval_shape(opt.init, shapes)
        batch = family.make_batch(cell.config, t, hvd.size(), 0)
        step = loop.make_train_step(hvd, model, opt, mesh,
                                    family.per_chip_loss)
        text = step.lower(shapes, state, *batch).compile().as_text()
    finally:
        hvd.shutdown()
    return scopes.scopes_of_hlo(text)


def test_every_class_of_the_family_is_in_a_compiled_step(tiny_step_scopes):
    rules = scopes.Rules("sdar_moe")
    assert rules.classes == (
        "exchange", "optimizer", "remat", "head_loss", "attention", "mlp",
        "moe", "moe_experts", "embed", "unscoped")
    by_class = {}
    for name, op_name in tiny_step_scopes.items():
        by_class.setdefault(rules.classify(op_name), []).append(op_name)
    for cls in ("remat", "head_loss", "attention", "mlp", "moe",
                "moe_experts", "embed", "optimizer"):
        assert by_class.get(cls), cls
    # (a parameter's name is its path in the tree: no operation)
    assert all("attn_blockdiff" in n for n in by_class["attention"]
               if n.startswith("jit("))
    assert any("gmm" in n for n in by_class["moe_experts"])
    assert not any("moe_shared" in n for n in tiny_step_scopes.values())
    # the weighted loss and the cut to the noised half go with the head
    unscoped = [n for n in by_class.get("unscoped", []) if n]
    assert not any("jvp(" in n for n in unscoped), unscoped[:5]


@pytest.mark.parametrize("op_name, cls", [
    ("jit(train_step)/jvp(Transformer)/block_2/MultiHeadAttention_0/"
     "attn_blockdiff/flash_fwd", "attention"),
    ("jit(train_step)/transpose(jvp(Transformer))/block_2/"
     "MultiHeadAttention_0/attn_blockdiff/flash_dkv", "attention"),
    ("jit(train_step)/jvp(Transformer)/block_2/MultiHeadAttention_0/"
     "attn_blockdiff/q_norm/mul", "attention"),
    ("jit(train_step)/transpose(jvp(Transformer))/block_1/"
     "rematted_computation/MultiHeadAttention_0/attn_blockdiff/kv/"
     "dot_general", "remat"),
    ("jit(train_step)/jvp(Transformer)/block_2/moe/moe_experts/gmm",
     "moe_experts"),
    ("jit(train_step)/jvp(Transformer)/block_2/moe/moe_route/router/"
     "dot_general", "moe"),
    ("jit(train_step)/jvp(Transformer)/block_2/moe/moe_combine/scatter-add",
     "moe"),
    ("jit(train_step)/jvp(Transformer)/block_3/RMSNorm_1/mul", "mlp"),
    ("jit(train_step)/jvp(Transformer)/lm_head/dot_general", "head_loss"),
    ("jit(train_step)/jvp(Transformer)/slice", "head_loss"),
    ("jit(train_step)/jvp(Transformer)/Embed_0/take", "embed"),
])
def test_scope_rules_of_the_family(op_name, cls):
    assert scopes.Rules("sdar_moe").classify(op_name) == cls


# -------------------------------------------------------- BENCHMARK.json

def test_the_cell_is_declared_as_the_issue_names_it():
    m = manifest.load_manifest()
    cell = manifest.Cell(m, CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        CONFIG, "train-blockdiff-b1s8192", 1)
    assert len(cell.entry["why"]) <= 200
    assert "8 x its share" in cell.entry["why"]
    assert cell.traffic["loop"] == "train-block-diffusion"
    assert (cell.traffic["batch_per_chip"], cell.traffic["seq"],
            cell.traffic["remat"]) == (1, 8192, True)
    assert cell.traffic["noise"] == {
        "per": "block", "t": "eps + (1 - eps) * U[0,1)", "eps": 0.001,
        "weight": "1/t on masked positions"}
    assert cell.traffic["labels"] == "clean token at masked positions"
    assert cell.traffic["optimizer"] == {
        "kind": "sgd", "lr": 0.01, "momentum": 0.9, "op": "Average"}
    reported = {x["name"] for x in cell.per_layer}
    # by membership, never by position or by equality: a later PR appends
    # cells, configurations and metrics, and may list this cell on more
    assert set(NEW_METRICS) <= reported
    assert {"kernels.flash_ms_per_step", "model.attention_ms_per_step",
            "model.mlp_ms_per_step", "model.head_loss_ms_per_step",
            "model.remat_ms_per_step", "trainer.unscoped_ms_per_step",
            "trainer.step_ms_p50", "trainer.optimizer_ms_per_step",
            "device.peak_hbm_gib.train", "device.idle_share.train",
            "init.trace_model_s", "init.compile_s"} <= reported
    assert {"train_tokens_per_s", "setup_s"} <= {
        x["name"] for x in cell.end_to_end}
    for name in NEW_METRICS:
        (entry,) = [x for x in m["per_layer"] if x["name"] == name]
        assert entry["workloads"] == [CELL]
        assert (entry["moves"], entry["unit"]) == ("train_tokens_per_s", "%")
        assert manifest.load_module("metrics", name) is not None
    (config,) = [c for c in m["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert set(cell.limits()) >= {"loss3_gap", "grad_gap", "change_gap",
                                  "compiles_in_window", "nonfinite_losses"}
    # one four-chip cell among the benchmark's cells, as before
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    assert len(m["workloads"]) >= 6


def test_the_limits_file_states_its_readings():
    with open(os.path.join(ROOT, "benchmark", "limits", CELL + ".json")) as f:
        limits = json.load(f)
    assert set(limits["limits"]) == {
        "loss3_gap", "grad_gap", "change_gap", "compiles_in_window",
        "nonfinite_losses"}
    assert limits["limits"]["compiles_in_window"] == 0
    assert limits["limits"]["nonfinite_losses"] == 0
    ref = manifest.load_module("references", "sdar_moe")
    caught = set()
    for name in ("loss3_gap", "grad_gap", "change_gap"):
        r = limits["readings"][name]
        # the limit lies above the program's worst reading
        assert len(r["lower_all"]) >= 10
        assert r["lower"] == max(r["lower_all"]) < limits["limits"][name]
        uppers = {k: v for k, v in r.items()
                  if k.startswith(("fault_", "control_"))}
        assert {"fault_" + f + "_min" for f in ref.FAULTS} <= set(uppers)
        assert "control_fp8_min" in uppers
        caught |= {k for k, v in uppers.items()
                   if v > limits["limits"][name]}
        assert limits["reasons"][name]
    # the control in fp8 and the planted faults read over at least one
    # limit (one of the cell's limits, not each), but for the faults that
    # read inside the program's own heavy-tailed readings (the weight 1/t)
    # or under the room their tail needs: the file names each of those
    # with its readings, and the CPU tests hold them
    assert "control_fp8_min" in caught
    not_caught = limits.get("not_caught", {})
    assert caught | set(not_caught) >= {
        "fault_" + f + "_min" for f in ref.FAULTS}
    assert not caught & set(not_caught)
    assert set(not_caught) <= {
        "fault_leak_own_clean_min", "fault_no_noised_part_min",
        "fault_causal_in_block_min", "fault_top_k_less_1_min",
        "fault_positions_2l_min"}
    assert all(len(reason) > 40 for reason in not_caught.values())


def test_the_configuration_keeps_every_published_width():
    cfg = _published()
    pub = cfg["published"]
    changed = {k for k, v in pub.items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    (entry,) = [c for c in manifest.load_manifest()["configs"]
                if c["name"] == CONFIG]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["intermediate_size"],
            cfg["num_experts_per_tok"], cfg["norm_topk_prob"],
            cfg["rope_theta"], cfg["rms_norm_eps"]) == (
        2048, 32, 4, 128, 768, 6144, 8, True, 1000000, 1e-06)
    assert (cfg["num_experts"], cfg["num_experts_total"],
            cfg["experts_held"]) == (16, pub["num_experts"], [0, 16])
    assert 4 <= cfg["num_hidden_layers"] <= 6  # the floor is four layers
    assert cfg["vocab_size"] * 8 == pub["vocab_size"]
    assert (cfg["block_length"], cfg["mask_token_id"], cfg["noise_eps"],
            cfg["embedding_std"]) == (4, cfg["vocab_size"] - 1, 0.001, 1.0)
    assert (cfg["model"], cfg["reference"]) == ("sdar_moe", "sdar_moe")
    assert "eight chips share each layer" in cfg["deployment"]
    assert "1/8" in cfg["expert_load"]
    assert "GiB" in cfg["reduced_how"]["num_hidden_layers"]
    assumed = " ".join(cfg["assumed"])
    for said in ("block_length 4", "eps 1e-3", "mask_token_id 18991",
                 "normal(0, 0.02)", "normal(0, 1)", "mean of the data tokens",
                 "SGD with momentum", "flash_block 512", "Qwen3-MoE",
                 "2503.09573"):
        assert said in assumed, said
