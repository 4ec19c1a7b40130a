"""Language-model pretraining throughput — BERT-large / GPT-2-medium.

The two tracked LM configs from BASELINE.json [V]: BERT-large with
Adasum gradient combination (config #3) and GPT-2 medium with
hierarchical allreduce (config #4). Prints ONE JSON line:
  {"metric": "<model>_samples_per_sec", "value": N, "unit": "samples/s"}

Env: BENCH_MODEL=bert_large|gpt2_medium (default bert_large),
BENCH_BATCH (default 8), BENCH_SEQ (default: model max 512/1024 capped
at 512), BENCH_ITERS (default 10), BENCH_PLATFORM=cpu + tiny model for
the harness smoke test (BENCH_TINY=1).

``BENCH_AB=local_sgd`` runs the local-SGD A/B instead
(``ab_local_sgd`` legs, PR 14 / ROADMAP item 3): the SAME tiny-LM
training loop twice — ``k1`` (the existing path: hierarchical int8
allreduce every step, the PR 10 wire) vs ``k8``
(``DistributedOptimizer(local_sgd_steps=K)``: ICI-only local steps, a
hierarchical-Adasum int8 reconciliation round every K steps via
``hvd.local_sgd.maybe_sync``). Each leg appends one JSON artifact
(``lm_ab_local_sgd_<leg>.json`` under BENCH_ARTIFACT_DIR) with
ms/step, the full loss trajectory, the lowered step program's
collective counts, and the per-hop byte ledger from the shared
payload-width model (``FusionManager._hop_bytes`` for the every-step
wire, ``local_sgd.round_inter_bytes`` — the VHDD model — for the
rounds): ``inter_bytes_per_step`` and ``inter_ratio_vs_k1``.
BENCH_DRYRUN=1 is the CI smoke shape and gates the two pre-registered
predictions (docs/perf.md): inter bytes/step drop ≥ K/2× vs the k1
hier-int8 row, and the K-step leg keeps ≥ half of k1's loss
improvement. The k8 step program is additionally asserted to carry
ZERO inter-slice replica groups (the hloaudit rule, run inline).
Env: BENCH_LOCAL_K (default 8), BENCH_INTRA (default 4),
BENCH_AB_STEPS (default 2·K), BENCH_BATCH/BENCH_SEQ as above.
"""

import json
import os
import time

from _benchlib import stamp as _stamp
from functools import partial

import numpy as np

_SIM_NOTE = (
    "logic-validation only (CPU simulation); step-time is NOT a TPU "
    "wall-clock number — byte accounting, loss math and HLO shape are "
    "exact"
)


def run_ab_local_sgd():
    """The ``ab_local_sgd`` A/B legs (module docstring)."""
    import jax

    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])

    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from _benchlib import sync as _sync
    from horovod_tpu import analysis, local_sgd
    from horovod_tpu.analysis import rules
    from horovod_tpu.common.topology import hierarchical_stage_groups
    from horovod_tpu.models import Transformer, TransformerConfig
    from horovod_tpu.ops.fusion import FusionManager

    dryrun = os.environ.get("BENCH_DRYRUN", "").strip() in ("1", "true")
    k = int(os.environ.get("BENCH_LOCAL_K", "8"))
    intra = int(os.environ.get("BENCH_INTRA", "4"))
    batch = int(os.environ.get("BENCH_BATCH", "2" if dryrun else "8"))
    hvd.init()
    mesh = hvd.mesh()
    world = hvd.size()
    if world % intra:
        intra = 2 if world % 2 == 0 else 1
    stages = hierarchical_stage_groups(world, intra)
    if stages is None:
        raise SystemExit(
            f"no two-level split for world={world} intra={intra}"
        )
    L, H = intra, world // intra
    intra_groups = tuple(tuple(g) for g in stages[0])
    steps = int(os.environ.get("BENCH_AB_STEPS", str(2 * k)))
    steps = max(steps, k)  # at least one full round
    platform = jax.devices()[0].platform
    artifact_dir = os.environ.get(
        "BENCH_ARTIFACT_DIR", os.path.join("bench_results", "lm")
    )
    os.makedirs(artifact_dir, exist_ok=True)

    cfg = TransformerConfig.tiny(causal=True) if dryrun else (
        TransformerConfig.gpt2_medium()
    )
    seq = int(os.environ.get("BENCH_SEQ", str(min(cfg.max_len, 32 if dryrun else 512))))
    model = Transformer(cfg)
    tokens0 = jnp.zeros((batch, seq), jnp.int32)
    params0 = jax.jit(
        lambda: model.init(jax.random.PRNGKey(0), tokens0, train=False)
    )()
    grad_bytes = sum(
        int(np.prod(np.shape(l))) * 4
        for l in jax.tree_util.tree_leaves(params0)
    )
    rng = np.random.default_rng(0)
    # per-rank data: slices see DIFFERENT streams, so local phases
    # genuinely diverge before each round reconciles them
    toks = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(steps, world, batch, seq)),
        jnp.int32,
    )
    labels = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(steps, world, batch, seq)),
        jnp.int32,
    )

    def make_leg(leg_k):
        if leg_k > 1:
            opt = hvd.DistributedOptimizer(
                optax.sgd(0.05, momentum=0.9), op=hvd.Average,
                local_sgd_steps=leg_k, local_sgd_intra=intra,
            )
        else:
            # the existing path: the PR 10 two-level wire, int8 on the
            # DCN hop, EVERY step — the baseline the ÷K claim is
            # measured against
            opt = hvd.DistributedOptimizer(
                optax.sgd(0.05, momentum=0.9), op=hvd.Average,
                compression=hvd.Compression.hier_int8,
            )

        @partial(
            jax.shard_map, mesh=mesh,
            in_specs=(
                P(hvd.WORLD_AXIS), P(hvd.WORLD_AXIS),
                P(hvd.WORLD_AXIS), P(hvd.WORLD_AXIS),
            ),
            out_specs=(
                P(hvd.WORLD_AXIS), P(hvd.WORLD_AXIS), P(hvd.WORLD_AXIS),
            ),
            check_vma=False,
        )
        def step(pm, sm, tk, lb):
            p = jax.tree_util.tree_map(lambda x: x[0], pm)
            s = jax.tree_util.tree_map(lambda x: x[0], sm)
            tk, lb = tk[0], lb[0]

            def loss_fn(q):
                logits = model.apply(q, tk, train=True)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits.astype(jnp.float32), lb
                ).mean()

            loss, grads = jax.value_and_grad(loss_fn)(p)
            u, s = opt.update(grads, s, p)
            p = optax.apply_updates(p, u)
            add = jax.tree_util.tree_map(lambda x: x[None], (p, s))
            # per-rank loss rides home rank-major: a cross-slice mean
            # would put an inter-spanning collective INSIDE the
            # local-phase program — the host averages the rows instead
            return add[0], add[1], loss[None]

        sync_step = None
        if leg_k > 1:
            @partial(
                jax.shard_map, mesh=mesh,
                in_specs=(P(hvd.WORLD_AXIS), P(hvd.WORLD_AXIS)),
                out_specs=(P(hvd.WORLD_AXIS), P(hvd.WORLD_AXIS)),
                check_vma=False,
            )
            def sync_step(pm, sm):
                p = jax.tree_util.tree_map(lambda x: x[0], pm)
                s = jax.tree_util.tree_map(lambda x: x[0], sm)
                p, s = opt.sync(p, s)
                return jax.tree_util.tree_map(
                    lambda x: x[None], (p, s)
                )

            sync_step = jax.jit(sync_step)
        return opt, jax.jit(step), sync_step

    def rank_major(tree):
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                jnp.asarray(x)[None],
                (world,) + tuple(np.shape(x)),
            ),
            tree,
        )

    block = 512
    results = {}
    for leg_k, leg in ((1, "k1"), (k, "k8")):
        opt, step, sync_step = make_leg(leg_k)
        pm = rank_major(params0)
        sm = rank_major(opt.init(params0))
        g = analysis.parse_module(step.lower(pm, sm, toks[0], labels[0]))
        counts = g.counts()
        if leg_k > 1:
            # the lowered local-phase program must carry ZERO
            # inter-slice replica groups (the hloaudit invariant,
            # asserted inline on the real bench program)
            for kind in (
                "all_reduce", "reduce_scatter", "all_gather",
                "all_to_all", "collective_permute",
            ):
                analysis.expect(
                    g,
                    rules.ReplicaGroupStructure(
                        kind, groups_any_of=(intra_groups,),
                        forbid_world_spanning=True,
                    ),
                )
        losses = []
        rounds = 0
        # warm (compile) outside the timed loop
        pm_w, sm_w, l0 = step(pm, sm, toks[0], labels[0])
        _sync(l0)
        pm, sm = pm_w, sm_w
        losses.append(float(np.mean(np.asarray(l0))))
        t0 = time.perf_counter()
        for i in range(1, steps):
            pm, sm, loss = step(pm, sm, toks[i], labels[i])
            losses.append(float(np.mean(np.asarray(loss))))
            if leg_k > 1 and local_sgd.due(i, leg_k):
                out, synced = local_sgd.run_round(
                    sync_step, pm, sm,
                    payload_bytes=grad_bytes, stages=stages,
                )
                if synced:
                    pm, sm = out
                    rounds += 1
        _sync(pm)
        ms = (time.perf_counter() - t0) * 1e3 / max(steps - 1, 1)
        # per-hop byte ledger, shared payload-width models
        elems = grad_bytes // 4
        if leg_k == 1:
            # hier-int8 every step: bf16 intra legs + int8 inter on
            # the 1/L shard (bench_hier's accounting)
            ib, _ = FusionManager._hop_bytes(
                -(-elems // L), "int8", 4, H, block
            )
            inter_per_step = ib
        else:
            round_bytes = local_sgd.round_inter_bytes(
                grad_bytes, stages, "int8"
            )
            inter_per_step = round_bytes / leg_k
        line = {
            "metric": "lm_ab_local_sgd",
            "leg": leg,
            "k": leg_k,
            "world": world,
            "intra": L,
            "slices": H,
            "steps": steps,
            "rounds": rounds,
            "grad_bytes": grad_bytes,
            "value": round(ms, 3),
            "unit": "ms/step",
            "platform": platform,
            "collectives": counts,
            "inter_bytes_per_step": round(inter_per_step, 1),
            "loss_first": round(losses[0], 4),
            "loss_final": round(losses[-1], 4),
            "losses": [round(x, 4) for x in losses],
        }
        if platform != "tpu":
            line["note"] = _SIM_NOTE
        results[leg] = line

    r1, r8 = results["k1"], results["k8"]
    ratio = (
        r1["inter_bytes_per_step"] / r8["inter_bytes_per_step"]
        if r8["inter_bytes_per_step"]
        else float("inf")
    )
    r8["inter_ratio_vs_k1"] = round(ratio, 2)
    r1["inter_ratio_vs_k1"] = 1.0
    for leg, line in results.items():
        print(json.dumps(_stamp(line)), flush=True)
        with open(
            os.path.join(artifact_dir, f"lm_ab_local_sgd_{leg}.json"), "a"
        ) as f:
            f.write(json.dumps(_stamp(line)) + "\n")
    # pre-registered gates (docs/perf.md): the sync rounds moved the
    # expected ÷K of the every-step wire's DCN bytes, and the K-step
    # leg kept at least half of k1's loss improvement
    assert r8["rounds"] >= 1, "no sync round ran"
    assert ratio >= k / 2, (
        f"inter-byte drop {ratio:.2f}x < pre-registered K/2 = {k / 2}"
    )
    imp1 = r1["loss_first"] - r1["loss_final"]
    imp8 = r8["loss_first"] - r8["loss_final"]
    assert imp1 > 0, f"k1 leg did not learn: {imp1}"
    assert imp8 >= 0.5 * imp1, (
        f"k8 loss improvement {imp8:.4f} < half of k1's {imp1:.4f}"
    )


def main():
    if os.environ.get("BENCH_AB", "").strip() == "local_sgd":
        return run_ab_local_sgd()
    import jax

    from _benchlib import require_accelerator

    device = require_accelerator()

    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import Transformer, TransformerConfig

    model_name = os.environ.get("BENCH_MODEL", "bert_large")
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    iters = int(os.environ.get("BENCH_ITERS", "10"))

    hvd.init()
    mesh = hvd.mesh()

    if os.environ.get("BENCH_TINY"):
        cfg = TransformerConfig.tiny(causal=(model_name == "gpt2_medium"))
    elif model_name == "gpt2_medium":
        cfg = TransformerConfig.gpt2_medium()
    else:
        cfg = TransformerConfig.bert_large()
    # remat trades FLOPs for memory; at bench batch sizes the model may
    # fit without it, making it pure recompute overhead — BENCH_REMAT=0
    # measures that. Default stays on (the large-model-safe setting).
    remat = not os.environ.get("BENCH_TINY") and os.environ.get(
        "BENCH_REMAT", "1"
    ) not in ("0", "false", "off")
    cfg = dataclasses_replace(cfg, remat=remat)
    if os.environ.get("BENCH_FLASH", "auto") in ("0", "false", "off"):
        # escape hatch: dense attention (e.g. if the Pallas kernel
        # misbehaves on a new libtpu)
        cfg = dataclasses_replace(cfg, flash_attention=False)
    if os.environ.get("BENCH_HEAD") == "fp32":
        # A/B escape hatch for the mixed-precision LM head default
        cfg = dataclasses_replace(cfg, head_mixed_precision=False)
    if os.environ.get("BENCH_KV_HEADS"):
        # grouped-query attention A/B: fewer KV heads (must divide the
        # model's head count); the kernels read shared KV rows directly
        cfg = dataclasses_replace(
            cfg, num_kv_heads=int(os.environ["BENCH_KV_HEADS"])
        )
    if os.environ.get("BENCH_FLASH_BLOCK"):
        bq = int(os.environ["BENCH_FLASH_BLOCK"])
        if bq < 8 or (bq & (bq - 1)) != 0:
            raise SystemExit(
                f"BENCH_FLASH_BLOCK={bq}: must be a power of two >= 8 "
                "(Mosaic tiling; see ops/flash_attention.py)"
            )
        cfg = dataclasses_replace(cfg, flash_block_q=bq, flash_block_k=bq)
    seq = int(os.environ.get("BENCH_SEQ", str(min(cfg.max_len, 512))))

    # The BASELINE pairing: BERT-large exercises Adasum, GPT-2 medium the
    # hierarchical two-level reduction (BASELINE.json configs [V]).
    if model_name == "bert_large":
        reduce_op = hvd.Adasum
    else:
        reduce_op = hvd.Average
        os.environ.setdefault("HOROVOD_HIERARCHICAL_ALLREDUCE", "1")

    model = Transformer(cfg)
    tokens = jnp.zeros((batch, seq), jnp.int32)
    params = jax.jit(
        lambda: model.init(jax.random.PRNGKey(0), tokens, train=False)
    )()
    # mesh-placed before the first call, so step 2 runs the program
    # step 1 compiled (chip_smoke.py checks exactly this)
    params = hvd.broadcast_parameters(params)
    opt = hvd.DistributedOptimizer(
        optax.sgd(0.01, momentum=0.9), op=reduce_op
    )
    opt_state = hvd.broadcast_optimizer_state(opt.init(params))

    # Chunked fused linear-cross-entropy (ops/fused_xent.py): never
    # materializes the (batch·seq, vocab) logits — the step's largest
    # activation (~823 MB fp32 at GPT-2-medium b8/s512) — at the cost
    # of one logits recompute in backward. BENCH_FUSED_XENT=1 enables
    # it for the on-chip A/B; BENCH_XENT_CHUNK tunes the vocab chunk.
    fused_xent = os.environ.get("BENCH_FUSED_XENT", "0") not in (
        "0", "false", "off"
    )
    xent_chunk = int(os.environ.get("BENCH_XENT_CHUNK", "8192"))
    # BENCH_PADDED=1: right-padded batch (uniform lengths in
    # [seq*3/4, seq]) driven through the kernels' native lengths=
    # support — measures the padded-path overhead vs the dense-mask
    # alternative the reference-style stack would pay. Loss masks
    # padded positions.
    padded = os.environ.get("BENCH_PADDED", "0") not in (
        "0", "false", "off"
    )

    # Padded mode: fixed synthetic lengths (the bench reuses one batch,
    # so a closed-over constant is consistent with its style). Loss
    # averages over valid positions only — the fused loss composes
    # because it returns per-token losses (masking the reduction zeroes
    # the masked tokens' cotangents through the custom VJP).
    bench_lens = (
        jnp.asarray(
            np.random.default_rng(7).integers(
                3 * seq // 4, seq + 1, size=(batch,)
            ),
            jnp.int32,
        )
        if padded
        else None
    )

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(hvd.WORLD_AXIS), P(hvd.WORLD_AXIS)),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    def train_step(params, opt_state, tokens, labels):
        tokens, labels = tokens[0], labels[0]

        def loss_fn(p):
            if fused_xent:
                from horovod_tpu.ops.fused_xent import (
                    fused_linear_cross_entropy,
                )

                hidden = model.apply(
                    p, tokens, train=True, return_hidden=True,
                    lengths=bench_lens,
                )
                head = p["params"]["lm_head"]
                per_tok = fused_linear_cross_entropy(
                    hidden.reshape(-1, cfg.d_model),
                    head["kernel"],
                    head["bias"],
                    labels.reshape(-1),
                    chunk=xent_chunk,
                    compute_dtype=(
                        cfg.dtype if cfg.head_mixed_precision else None
                    ),
                )
                if padded:
                    valid = (
                        jnp.arange(tokens.shape[1])[None, :]
                        < bench_lens[:, None]
                    ).reshape(-1)
                    return jnp.sum(
                        jnp.where(valid, per_tok, 0.0)
                    ) / jnp.sum(valid)
                return per_tok.mean()
            if padded:
                logits = model.apply(
                    p, tokens, train=True, lengths=bench_lens
                )
                per_tok = optax.softmax_cross_entropy_with_integer_labels(
                    logits.astype(jnp.float32), labels
                )
                valid = (
                    jnp.arange(tokens.shape[1])[None, :]
                    < bench_lens[:, None]
                )
                return jnp.sum(
                    jnp.where(valid, per_tok, 0.0)
                ) / jnp.sum(valid)
            logits = model.apply(p, tokens, train=True)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), labels
            ).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, jax.lax.pmean(loss, hvd.WORLD_AXIS)

    # No donation here: fresh-initialized params contain aliased
    # (deduplicated) zero buffers, and donating the same buffer twice is
    # an XLA error.
    step = jax.jit(train_step)
    rng = np.random.default_rng(0)
    world = hvd.size()
    rank_major = hvd.rank_sharding(mesh)
    toks = jax.device_put(
        rng.integers(0, cfg.vocab_size, size=(world, batch, seq)).astype(
            np.int32
        ),
        rank_major,
    )
    labels = jax.device_put(
        rng.integers(0, cfg.vocab_size, size=(world, batch, seq)).astype(
            np.int32
        ),
        rank_major,
    )

    from _benchlib import aot_compile, bytes_accessed, mfu_fields

    step, flops = aot_compile(step, params, opt_state, toks, labels)
    step_bytes = bytes_accessed(step)
    flops_note = None
    if flops and cfg.uses_flash(seq=seq):
        # The Pallas flash-attention kernels are custom calls — invisible
        # to XLA cost analysis — so add their matmul FLOPs analytically:
        # fwd 2 matmuls (QKᵀ, PV) = 4·b·s²·d, bwd ≈ 2× fwd (dq/dk/dv +
        # blockwise recompute), halved for causal masking.
        attn = 12.0 * batch * world * (seq**2) * cfg.d_model * cfg.num_layers
        if cfg.causal:
            attn /= 2.0
        flops += attn
        flops_note = (
            "xla_cost_analysis + analytic flash-attention matmul flops"
        )
    from _benchlib import sync as _sync

    params, opt_state, loss = step(params, opt_state, toks, labels)
    _sync(loss)  # warm
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt_state, loss = step(params, opt_state, toks, labels)
    _sync(loss)  # loss chains through every step's params
    dt = time.perf_counter() - t0
    samples_per_sec = batch * world * iters / dt
    result = {
        "metric": f"{model_name}_samples_per_sec",
        "value": round(samples_per_sec, 2),
        "unit": "samples/s",
        "batch": batch,
        "seq": seq,
        "world": world,
        "remat": remat,
        "head": "mixed" if cfg.head_mixed_precision else "fp32",
        "xent": "fused" if fused_xent else "dense",
        # padded mode: samples/s counts whole padded rows; MFU uses the
        # full-seq analytic attention flops, so it UNDERSTATES true
        # utilization on the valid tokens (conservative)
        "padded": padded,
        "kv_heads": cfg.num_kv_heads or cfg.num_heads,
        # provenance: the kernel auto-shrinks to the sequence, so record
        # the EFFECTIVE block, not the config ask (r04 flipped the
        # default 128->512 mid-capture-chain; without this field those
        # artifacts would be indistinguishable)
        "flash_block": (
            _effective_block(seq, cfg) if cfg.uses_flash(seq=seq) else None
        ),
        "platform": device.platform,
        "device_kind": device.device_kind,
    }
    result.update(mfu_fields(flops, iters, dt, step_bytes=step_bytes))
    if flops_note:
        result["flops_note"] = flops_note
    print(json.dumps(_stamp(result)))


def _effective_block(seq, cfg):
    from horovod_tpu.ops.flash_attention import _pick_block

    return _pick_block(seq, cfg.flash_block_q)


def dataclasses_replace(cfg, **kw):
    import dataclasses

    return dataclasses.replace(cfg, **kw)


if __name__ == "__main__":
    main()
