"""The ``deepseek_v3`` family's files of the benchmark at a CPU test size
(``data/tiny-deepseek-v3.json``, ``data/BENCHMARK.deepseek_v3.json``): the
loop end to end through ``drive_deepseek_v3.py`` with the timed path whole
and with a planted rotation fault; the work file's counts against a hand
reckoning; the family's scope rules on a compiled step; the new readers
where there is nothing to read; the cell's entries in ``BENCHMARK.json``
and the shape of its limits file."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import manifest, scopes

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
CELL = "kanana-train-s8k-1chip"
NEW_METRICS = (
    "flash_fwd_roofline.mla", "flash_dq_roofline.mla",
    "flash_dkv_roofline.mla", "model.latent_proj_ms_per_step")
FAMILY_METRICS = (
    "model.mfu.train.family", "moe.experts_roofline",
    "model.moe_ms_per_step", "moe.dispatch_ms_per_step")


def drive(fault=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    cmd = [sys.executable, os.path.join(HERE, "drive_deepseek_v3.py"),
           "test-train-deepseek-v3", "0", "1.0"] + ([fault] if fault else [])
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stderr


def test_the_loop_runs_the_family_end_to_end_and_is_correct():
    line, stderr = drive()
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) >= {"train_tokens_per_s", "setup_s"}
    assert set(line["compared"]) >= {"loss3_gap", "grad_gap", "change_gap",
                                     "compiles_in_window"}
    assert "correct = true" in stderr


def test_a_rotation_fault_in_the_timed_path_is_not_correct():
    line, stderr = drive("rope_half_split")
    assert line["correct"] is False
    for name in ("grad_gap", "change_gap"):
        c = line["compared"][name]
        assert c["value"] > 10 * c["limit"], (name, c)
    assert "correct = false" in stderr


# ------------------------------------------------------- the work file

def _published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kanana-2-30b-a3b.json")) as f:
        return json.load(f)


def _work():
    return manifest.load_module("work", "deepseek_v3")


def test_forward_flops_by_hand():
    w, cfg = _work(), _published()
    parts = w.forward_flops_per_token(cfg, 8192)
    d = 2048
    # q at 32 x 192 and the output projection from 32 x 128, in six layers
    assert parts["projections"] == 6 * 2 * d * 32 * (192 + 128)
    # the joint down-projection to 512 + 64, the up-projection from 512 to
    # 32 heads of 128 + 128
    assert parts["latent_projections"] == 6 * 2 * (
        d * 576 + 512 * 32 * 256)
    # a layer's attention matrices are 26.35M parameters (ISSUE 31)
    assert (parts["projections"] + parts["latent_projections"]) / 12 == (
        12_582_912 + 1_179_648 + 4_194_304 + 8_388_608)
    # a row sees itself and what is before it: 8193 / 2 keys on average,
    # QK^T over 192 and PV over 128 at 32 heads
    assert w.score_pairs(8192) == sum(r + 1 for r in range(8192))
    assert parts["scores"] == 6 * 2 * 32 * (192 + 128) * 8193 / 2
    assert parts["dense_mlp"] == 3 * 2 * d * 6144
    # top-6 of 128 with 16 held: 0.75 routed evaluations a token expected
    # here; the two shared experts; the router's 128 outputs
    assert w.expert_evaluations_per_token(cfg) == 0.75
    expert = 3 * 2 * d * 768
    assert parts["experts"] == 5 * (2 * d * 128 + expert * (2 + 0.75))
    assert parts["head"] == 2 * d * 16032
    total = sum(parts.values())
    assert total == pytest.approx(1.0932e9, rel=1e-3)
    assert w.train_flops_per_token(cfg, 8192) == 3 * total
    # attention is three quarters of the counted work, the kernels alone
    # 46%, the expert layers 12% (ISSUE 31)
    attention = (parts["projections"] + parts["latent_projections"]
                 + parts["scores"])
    assert 0.74 < attention / total < 0.76
    assert 0.45 < parts["scores"] / total < 0.47
    assert 0.11 < parts["experts"] / total < 0.13


def test_flash_and_expert_calls_by_hand():
    w, cfg = _work(), _published()
    pairs = 8192 * 8193 / 2
    rows = 2 * 32  # every head of both sequences has its own key and value
    # forward: QK^T over 192, PV over 128; q, k at 192 and v, o at 128
    assert w.flash_call_work(cfg, "flash_fwd", 2, 8192) == (
        2 * rows * pairs * (192 + 128),
        rows * 8192 * (2 * 192 + 2 * 128) * 2)
    # dQ: the scores and dS K over 192, dO V^T over 128; q, k, dq and v,
    # o, do
    assert w.flash_call_work(cfg, "flash_dq", 2, 8192) == (
        2 * rows * pairs * (2 * 192 + 128),
        rows * 8192 * (3 * 192 + 3 * 128) * 2)
    # dK/dV: the scores and dS^T Q over 192, dO V^T and P^T dO over 128;
    # q, k, dk and v, o, do, dv
    assert w.flash_call_work(cfg, "flash_dkv", 2, 8192) == (
        2 * rows * pairs * (2 * 192 + 2 * 128),
        rows * 8192 * (3 * 192 + 4 * 128) * 2)
    # a value padded to the key's width would count 1.2 x the forward
    padded = 2 * rows * pairs * (192 + 192)
    assert padded / w.flash_call_work(cfg, "flash_fwd", 2, 8192)[0] == 1.2
    flops, nbytes = w.expert_matmul_work(cfg, 16384)
    assert flops == 2 * 12288 * 2048 * 768  # 12,288 rows expected here
    assert nbytes == (12288 * (2048 + 768) + 16 * 2048 * 768) * 2
    least = w.experts_least_seconds_per_step(cfg, 16384, 197e12, 819e9)
    assert least == pytest.approx(9 * 5 * flops / 197e12)  # compute bound


class _Trace:
    devices = {0: None}

    def __init__(self, calls):
        self.calls = calls

    def op_calls(self, kernel):
        return self.calls.get(kernel, [])


def test_a_roofline_share_from_a_trace_counts_remats_second_forward():
    w, cfg = _work(), _published()
    flops, _ = w.flash_call_work(cfg, "flash_fwd", 2, 8192)
    least = flops / 197e12  # compute bound
    # six layers, each called in the forward and again under remat
    readings = {"cfg": cfg, "device_kind": "TPU v5 lite",
                "traffic": {"batch_per_chip": 2, "seq": 8192},
                "trace": _Trace({"flash_fwd": [2 * least] * 12})}
    reader = manifest.load_module("metrics", "flash_fwd_roofline.mla")
    assert reader.read(readings) == pytest.approx(50.0)
    assert manifest.load_module("metrics", "flash_dq_roofline.mla").read(
        readings) is None  # no such call in the trace


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
    for name in NEW_METRICS + FAMILY_METRICS:
        reader = manifest.load_module("metrics", name)
        assert reader.read(dict(readings)) is None, name


def test_mfu_of_the_family_from_a_rate():
    reader = manifest.load_module("metrics", "model.mfu.train.family")
    value = reader.read({
        "cfg": _published(), "traffic": {"seq": 8192}, "chips": 1,
        "tokens_per_s": 23000.0, "device_kind": "TPU v5 lite"})
    flops = _work().train_flops_per_token(_published(), 8192)
    assert value == pytest.approx(100 * 23000 * flops / 197e12, rel=1e-9)
    assert 38 < value < 39  # the issue's reckoning: ~23k tokens/s at 38%


# ------------------------------------------------- scopes on a compiled step

@pytest.fixture(scope="module")
def tiny_step_scopes():
    """``instruction -> op_name`` of the test cell's compiled step."""
    import jax

    from benchmark.lib import program

    cell = manifest.Cell(manifest.load_manifest(os.path.join(
        DATA, "BENCHMARK.deepseek_v3.json")), "test-train-deepseek-v3", DATA)
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
    rules = scopes.Rules("deepseek_v3")
    assert rules.classes == (
        "exchange", "optimizer", "remat", "head_loss", "latent_proj",
        "attention", "mlp", "moe", "moe_experts", "moe_shared", "embed",
        "unscoped")
    by_class = {}
    for name, op_name in tiny_step_scopes.items():
        by_class.setdefault(rules.classify(op_name), []).append(op_name)
    for cls in ("remat", "head_loss", "latent_proj", "attention", "mlp",
                "moe", "moe_experts", "moe_shared", "embed", "optimizer"):
        assert by_class.get(cls), cls
    # what latent attention adds, and only that
    assert all("/attn_latent/" in n and "/latent_proj/" in n
               for n in by_class["latent_proj"])
    for part in ("kv_a", "kv_b"):
        assert any(f"latent_proj/{part}/" in n
                   for n in by_class["latent_proj"]), part
    assert not any("latent_proj" in n for n in by_class["attention"])
    # (a parameter's name is its path in the tree: no operation)
    assert all("attn_latent" in n for n in by_class["attention"]
               if n.startswith("jit("))
    assert any("gmm" in n for n in by_class["moe_experts"])


@pytest.mark.parametrize("op_name, cls", [
    ("jit(train_step)/jvp(Transformer)/block_2/MultiHeadAttention_0/"
     "attn_latent/latent_proj/kv_b/dot_general", "latent_proj"),
    ("jit(train_step)/transpose(jvp(Transformer))/block_2/"
     "MultiHeadAttention_0/attn_latent/latent_proj/concatenate",
     "latent_proj"),
    ("jit(train_step)/jvp(Transformer)/block_2/MultiHeadAttention_0/"
     "attn_latent/q/dot_general", "attention"),
    ("jit(train_step)/jvp(Transformer)/block_3/MultiHeadAttention_0/"
     "attn_latent/flash_fwd", "attention"),
    ("jit(train_step)/transpose(jvp(Transformer))/block_1/"
     "rematted_computation/MultiHeadAttention_0/attn_latent/latent_proj/"
     "kv_a/dot_general", "remat"),
    ("jit(train_step)/jvp(Transformer)/block_2/moe/moe_experts/gmm",
     "moe_experts"),
    ("jit(train_step)/jvp(Transformer)/block_2/moe/moe_shared/shared/up/"
     "dot_general", "moe_shared"),
    ("jit(train_step)/jvp(Transformer)/block_2/moe/moe_route/router/"
     "dot_general", "moe"),
    ("jit(train_step)/jvp(Transformer)/block_0/mlp/gate/dot_general", "mlp"),
    ("jit(train_step)/jvp(Transformer)/block_3/RMSNorm_1/mul", "mlp"),
    ("jit(train_step)/jvp(Transformer)/lm_head/dot_general", "head_loss"),
    ("jit(train_step)/jvp(Transformer)/Embed_0/take", "embed"),
])
def test_scope_rules_of_the_family(op_name, cls):
    assert scopes.Rules("deepseek_v3").classify(op_name) == cls


# -------------------------------------------------------- BENCHMARK.json

def test_the_cell_is_declared_as_the_issue_names_it():
    m = manifest.load_manifest()
    cell = manifest.Cell(m, CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "kanana-2-30b-a3b", "train-causal-b2s8192", 1)
    assert len(cell.entry["why"]) <= 200
    assert "8 x its share" in cell.entry["why"]
    reported = {x["name"] for x in cell.per_layer}
    # by membership, never by position or by equality: a later PR appends
    # cells, configurations and metrics, and may list this cell on more
    assert set(NEW_METRICS) <= reported
    assert {"kernels.flash_ms_per_step", "model.attention_ms_per_step",
            "model.mlp_ms_per_step", "model.head_loss_ms_per_step",
            "model.remat_ms_per_step", "trainer.unscoped_ms_per_step",
            "device.peak_hbm_gib.train", "init.trace_model_s"} <= reported
    assert {"train_tokens_per_s", "setup_s"} <= {
        x["name"] for x in cell.end_to_end}
    for name in NEW_METRICS:
        (entry,) = [x for x in m["per_layer"] if x["name"] == name]
        assert CELL in entry["workloads"]
        assert entry["moves"] == "train_tokens_per_s"
        assert manifest.load_module("metrics", name) is not None
    assert "kanana-2-30b-a3b" in [c["name"] for c in m["configs"]]
    assert set(cell.limits()) >= {"loss3_gap", "grad_gap", "change_gap",
                                  "compiles_in_window", "nonfinite_losses"}


def test_the_limits_file_states_its_readings():
    with open(os.path.join(ROOT, "benchmark", "limits", CELL + ".json")) as f:
        limits = json.load(f)
    assert set(limits["limits"]) == {
        "loss3_gap", "grad_gap", "change_gap", "compiles_in_window",
        "nonfinite_losses"}
    assert limits["limits"]["compiles_in_window"] == 0
    ref = manifest.load_module("references", "deepseek_v3")
    for name in ("grad_gap", "change_gap"):
        r = limits["readings"][name]
        # the limit lies between the program's worst reading and the least
        # planted fault, the lower-precision control among them
        assert len(r["lower_all"]) >= 9
        assert r["lower"] == max(r["lower_all"]) < limits["limits"][name]
        uppers = {k: v for k, v in r.items()
                  if k.startswith(("fault_", "control_"))}
        assert {"fault_" + f + "_min" for f in ref.FAULTS} <= set(uppers)
        assert "control_fp8_min" in uppers
        assert min(uppers.values()) > limits["limits"][name], uppers


def test_the_configuration_keeps_every_published_width():
    cfg = _published()
    pub = cfg["published"]
    changed = {k for k, v in pub.items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    (entry,) = [c for c in manifest.load_manifest()["configs"]
                if c["name"] == "kanana-2-30b-a3b"]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["qk_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["n_shared_experts"], cfg["num_experts_per_tok"],
            cfg["routed_scaling_factor"], cfg["rope_theta"]) == (
        2048, 32, 128, 64, 192, 128, 512, 6144, 768, 2, 6, 2.448, 1000000)
    assert (cfg["n_routed_experts"], cfg["n_routed_experts_total"],
            cfg["experts_held"]) == (16, pub["n_routed_experts"], [0, 16])
    assert cfg["num_hidden_layers"] == 6  # the dense layer and five
    assert cfg["vocab_size"] * 8 == pub["vocab_size"]
    assert "eight chips share each layer" in cfg["deployment"]
    assert "1/8" in cfg["expert_load"]
    assert len(cfg["assumed"]) >= 8
