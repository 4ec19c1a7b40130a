"""Local-SGD mode (PR 14, horovod_tpu/local_sgd.py + optimizer knobs):

* grouped hierarchical Adasum (the sync-round combiner) vs the host
  VHDD oracle, scale invariance, the non-power-of-two slice-count
  excess path, and int8-wire replica consistency;
* K=1 bit-parity with the existing path; K>1 within-slice replication,
  cross-slice divergence, and consensus reconciliation for BOTH
  optimizers;
* the tentpole structural invariant: lowered local-phase step programs
  carry ZERO inter-slice replica groups (the hloaudit
  ReplicaGroupStructure rule, asserted on real lowered modules);
* EF-residual chaining across rounds (bit-exact conservation at the
  pre-quantization point);
* the ``"local"`` layout family's 8→6 reshard migration;
* chaos: a DCN fault mid-sync-round defers the round (zero gang
  restarts — training continues on the ICI wire) and the counter
  ledger records it;
* elastic rejoin: a slice restored at the anchor re-syncs from the
  Adasum consensus, not from rank 0's parameters;
* the eager fused dispatcher's local-phase routing.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

WORLD = 8


def _stages(world=WORLD, intra=4):
    from horovod_tpu.common.topology import hierarchical_stage_groups

    return hierarchical_stage_groups(world, intra)


def _rank_major(tree, world=WORLD):
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(
            jnp.asarray(x)[None], (world,) + tuple(np.shape(jnp.asarray(x)))
        ),
        tree,
    )


def _strip(tree):
    return jax.tree_util.tree_map(lambda x: x[0], tree)


def _lift(tree):
    return jax.tree_util.tree_map(lambda x: x[None], tree)


INTRA_KINDS = (
    "all_reduce", "reduce_scatter", "all_gather", "all_to_all",
    "collective_permute",
)


def _assert_intra_only(graph, intra_groups):
    from horovod_tpu import analysis
    from horovod_tpu.analysis import rules

    intra = tuple(tuple(g) for g in intra_groups)
    for kind in INTRA_KINDS:
        analysis.expect(
            graph,
            rules.ReplicaGroupStructure(
                kind, groups_any_of=(intra,), forbid_world_spanning=True
            ),
        )


# ---------------------------------------------------------------------------
# grouped hierarchical Adasum (the sync-round combiner)
# ---------------------------------------------------------------------------


class TestGroupedAdasum:
    def _run(self, hvd, slice_vals, intra, wire="fp32", seed=0,
             world=None):
        """Each slice's ranks hold the slice value (replicated);
        returns the merged output rows."""
        from horovod_tpu.ops.adasum import adasum_allreduce_groups

        world = world or WORLD
        stages = _stages(world, intra)
        mesh = hvd.mesh() if world == WORLD else None
        if mesh is None:
            from jax.sharding import Mesh

            mesh = Mesh(
                np.asarray(jax.devices()[:world]), ("hvd",)
            )
        rows = np.stack(
            [slice_vals[r // intra] for r in range(world)]
        ).astype(np.float32)

        @partial(
            jax.shard_map, mesh=mesh, in_specs=(P("hvd"),),
            out_specs=P("hvd"), check_vma=False,
        )
        def run(x):
            return adasum_allreduce_groups(
                x[0], axis_name="hvd", stages=stages, inter_wire=wire,
                seed=seed,
            )[None]

        return np.asarray(jax.jit(run)(jnp.asarray(rows)))

    def test_matches_host_oracle_fp32(self, hvd, rng):
        from horovod_tpu.ops.adasum import adasum_vhdd_host

        vals = [rng.normal(size=(97,)).astype(np.float32) for _ in range(2)]
        out = self._run(hvd, vals, intra=4)
        want = adasum_vhdd_host(vals)
        np.testing.assert_allclose(out[0], want, rtol=1e-5, atol=1e-6)
        # replicated result across every rank
        for r in range(WORLD):
            np.testing.assert_array_equal(out[r], out[0])

    def test_four_slices(self, hvd, rng):
        from horovod_tpu.ops.adasum import adasum_vhdd_host

        vals = [rng.normal(size=(64,)).astype(np.float32) for _ in range(4)]
        out = self._run(hvd, vals, intra=2)
        want = adasum_vhdd_host(vals)
        np.testing.assert_allclose(out[0], want, rtol=1e-5, atol=1e-6)

    def test_non_pow2_slice_count_excess_path(self, hvd, rng):
        """world=6, L=2 → H=3: the VHDD pre-reduction (excess) path."""
        from horovod_tpu.ops.adasum import adasum_vhdd_host

        vals = [rng.normal(size=(40,)).astype(np.float32) for _ in range(3)]
        out = self._run(hvd, vals, intra=2, world=6)
        want = adasum_vhdd_host(vals)
        np.testing.assert_allclose(out[0], want, rtol=1e-5, atol=1e-6)
        for r in range(6):
            np.testing.assert_array_equal(out[r], out[0])

    def test_scale_invariance(self, hvd, rng):
        """Adasum is invariant to rescaling any input — the property
        that makes it the right merge operator for deltas whose local
        learning rates (or local step counts) differ."""
        vals = [rng.normal(size=(64,)).astype(np.float32) for _ in range(2)]
        base = self._run(hvd, vals, intra=4)
        scaled = self._run(
            hvd, [vals[0] * 7.5, vals[1]], intra=4
        )
        # adasum(c·a, b) has the same direction structure; for the
        # 2-slice case adasum(a,b) with a scaled keeps b's projection
        # removal exact: compare against the host oracle directly
        from horovod_tpu.ops.adasum import adasum_vhdd_host

        want = adasum_vhdd_host([vals[0] * 7.5, vals[1]])
        np.testing.assert_allclose(scaled[0], want, rtol=1e-4, atol=1e-5)
        assert not np.allclose(scaled[0], base[0])

    def test_int8_wire_close_and_replica_consistent(self, hvd, rng):
        from horovod_tpu.ops.adasum import adasum_vhdd_host

        vals = [rng.normal(size=(512,)).astype(np.float32) for _ in range(2)]
        out = self._run(hvd, vals, intra=4, wire="int8", seed=3)
        want = adasum_vhdd_host(vals)
        scale = np.abs(want).max()
        assert np.abs(out[0] - want).max() < 0.05 * max(scale, 1e-3)
        for r in range(WORLD):
            # bitwise identical replicas under the lossy wire (the
            # owner-consumes-self-wire discipline)
            np.testing.assert_array_equal(out[r], out[0])


class TestGroupedQuantizedEF:
    def test_average_ef_steady_state_unbiased(self, hvd, rng):
        """The grouped int8 wire's EF carry under op=Average: the
        time-averaged output must converge to the true group average
        within a fraction of one quantum. Regression for the stage-2
        e2 over-correction (×L) that made EF a persistent bias on
        this path — the grouped recipe quantizes the SUM shard (the
        ÷L happens after), so its e2 must stay UN-scaled."""
        from horovod_tpu.ops import traced

        stages = _stages()  # L=4, two groups
        intra = stages[0]
        mesh = hvd.mesh()
        vals = rng.normal(size=(WORLD, 257)).astype(np.float32)
        truth = np.stack(
            [vals[(r // 4) * 4 : (r // 4) * 4 + 4].mean(axis=0)
             for r in range(WORLD)]
        )

        @partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P("hvd"), P("hvd"), P()),
            out_specs=(P("hvd"), P("hvd")),
            check_vma=False,
        )
        def ar(xm, resm, seed):
            out, new_r = traced.quantized_allreduce(
                xm[0] + resm[0], op=hvd.Average, seed=seed,
                return_residual=True, groups=intra,
            )
            return out[None], new_r[None]

        run = jax.jit(ar)
        res = jnp.zeros_like(jnp.asarray(vals))
        errs = []
        for i in range(30):
            out, res = run(jnp.asarray(vals), res, jnp.int32(i))
            errs.append(np.asarray(out) - truth)
        per_round = np.abs(np.stack(errs)).max()
        bias = np.abs(np.mean(np.stack(errs[5:]), axis=0)).max()
        # EF keeps the walk unbiased: the time-mean error is far
        # below the per-round quantum (the ×L bug sat ~20x higher)
        assert bias < per_round / 4, (bias, per_round)
        assert bias < 3e-3, bias


# ---------------------------------------------------------------------------
# DistributedOptimizer local-SGD mode
# ---------------------------------------------------------------------------


def _make_opt_step(hvd, opt, mesh):
    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(hvd.WORLD_AXIS),) * 3,
        out_specs=(P(hvd.WORLD_AXIS), P(hvd.WORLD_AXIS)),
        check_vma=False,
    )
    def step(pm, sm, gm):
        p, s, g = _strip(pm), _strip(sm), _strip(gm)
        u, s = opt.update(g, s, p)
        p = optax.apply_updates(p, u)
        return _lift(p), _lift(s)

    return jax.jit(step)


def _make_sync_step(hvd, opt, mesh, method=None):
    sync = method if method is not None else opt.sync

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(hvd.WORLD_AXIS),) * 2,
        out_specs=(P(hvd.WORLD_AXIS), P(hvd.WORLD_AXIS)),
        check_vma=False,
    )
    def sync_step(pm, sm):
        p, s = _strip(pm), _strip(sm)
        p, s = sync(p, s)
        return _lift(p), _lift(s)

    return jax.jit(sync_step)


def _params(rng):
    return {
        "w": jnp.asarray(rng.normal(size=(24, 8)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(8,)), jnp.float32),
    }


def _grads(rng, world=WORLD):
    return {
        "w": jnp.asarray(rng.normal(size=(world, 24, 8)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(world, 8)), jnp.float32),
    }


class TestDistributedOptimizerLocalSGD:
    def test_k1_is_the_existing_path_bitwise(self, hvd, rng):
        """local_sgd_steps=1 IS the existing optimizer — identical
        transformation, bit-identical trajectory."""
        params = _params(rng)
        grads = _grads(rng)
        mesh = hvd.mesh()
        outs = []
        for kw in ({}, {"local_sgd_steps": 1}):
            opt = hvd.DistributedOptimizer(
                optax.adam(1e-2), op=hvd.Average, **kw
            )
            assert not isinstance(opt, hvd.LocalSGDGradientTransformation)
            step = _make_opt_step(hvd, opt, mesh)
            pm, sm = _rank_major(params), _rank_major(opt.init(params))
            for _ in range(3):
                pm, sm = step(pm, sm, grads)
            outs.append(np.asarray(pm["w"]))
        np.testing.assert_array_equal(outs[0], outs[1])

    def test_local_phase_diverges_and_sync_reconciles(self, hvd, rng):
        params = _params(rng)
        grads = _grads(rng)
        mesh = hvd.mesh()
        opt = hvd.DistributedOptimizer(
            optax.sgd(0.1), op=hvd.Average, local_sgd_steps=4,
            local_sgd_intra=4,
        )
        assert isinstance(opt, hvd.LocalSGDGradientTransformation)
        assert opt.local_sgd_steps == 4
        step = _make_opt_step(hvd, opt, mesh)
        pm, sm = _rank_major(params), _rank_major(opt.init(params))
        for _ in range(4):
            pm, sm = step(pm, sm, grads)
        w = np.asarray(pm["w"])
        np.testing.assert_array_equal(w[0], w[3])  # intra replicas
        assert not np.allclose(w[0], w[4])  # slices diverged
        sync = _make_sync_step(hvd, opt, mesh)
        pm2, sm2 = sync(pm, sm)
        w2 = np.asarray(pm2["w"])
        np.testing.assert_array_equal(w2[0], w2[7])  # world replicas
        # anchor re-based on the consensus
        anc = np.asarray(sm2.local_anchor["w"])
        np.testing.assert_array_equal(anc[0], w2[0])

    def test_sync_matches_host_adasum_of_deltas(self, hvd, rng):
        from horovod_tpu.ops.adasum import adasum_vhdd_host

        params = _params(rng)
        grads = _grads(rng)
        mesh = hvd.mesh()
        L = 4
        opt = hvd.DistributedOptimizer(
            optax.sgd(0.1), op=hvd.Average, local_sgd_steps=2,
            local_sgd_intra=L, local_sgd_inter_wire="fp32",
        )
        step = _make_opt_step(hvd, opt, mesh)
        pm0 = _rank_major(params)
        pm, sm = pm0, _rank_major(opt.init(params))
        for _ in range(2):
            pm, sm = step(pm, sm, grads)
        pm2, _ = _make_sync_step(hvd, opt, mesh)(pm, sm)
        deltas = []
        for h in range(WORLD // L):
            dw = np.asarray(pm["w"])[h * L] - np.asarray(pm0["w"])[0]
            db = np.asarray(pm["b"])[h * L] - np.asarray(pm0["b"])[0]
            deltas.append(
                np.concatenate([dw.reshape(-1), db.reshape(-1)])
            )
        merged = adasum_vhdd_host(deltas)
        want_w = (
            np.asarray(pm0["w"])[0].reshape(-1) + merged[: 24 * 8]
        )
        np.testing.assert_allclose(
            np.asarray(pm2["w"])[0].reshape(-1), want_w,
            rtol=1e-5, atol=1e-5,
        )

    def test_local_phase_program_has_zero_inter_groups(self, hvd, rng):
        """The tentpole structural invariant, on the real lowered
        module — bucketed AND monolithic paths."""
        from horovod_tpu import analysis

        params = _params(rng)
        grads = _grads(rng)
        mesh = hvd.mesh()
        stages = _stages()
        for buckets in (0, 3):
            opt = hvd.DistributedOptimizer(
                optax.sgd(0.1), op=hvd.Sum, local_sgd_steps=8,
                local_sgd_intra=4, overlap_buckets=buckets,
                overlap_min_bytes=0,
            )
            step = _make_opt_step(hvd, opt, mesh)
            pm, sm = _rank_major(params), _rank_major(opt.init(params))
            g = analysis.parse_module(step.lower(pm, sm, grads))
            _assert_intra_only(g, stages[0])
            assert g.count("all_reduce") >= 1

    def test_local_phase_program_int8_wire_intra_only(self, hvd, rng):
        """int8 local wire: the quantized exchange stays inside the
        slice too (every all_to_all / all_gather group-limited)."""
        from horovod_tpu import analysis

        params = _params(rng)
        grads = _grads(rng)
        mesh = hvd.mesh()
        opt = hvd.DistributedOptimizer(
            optax.sgd(0.1), op=hvd.Average, local_sgd_steps=8,
            local_sgd_intra=4, compression=hvd.Compression.int8_block,
            overlap_buckets=2, overlap_min_bytes=0,
        )
        step = _make_opt_step(hvd, opt, mesh)
        pm, sm = _rank_major(params), _rank_major(opt.init(params))
        g = analysis.parse_module(step.lower(pm, sm, grads))
        _assert_intra_only(g, _stages()[0])
        assert g.count("all_to_all") >= 1  # the quantized wire ran

    def test_ef_residual_chains_across_rounds(self, hvd, rng):
        """int8 inter wire EF: conservation at the pre-quantization
        point is bit-exact (quantized + residual' == delta + residual)
        and the carry actually lands in the next round's signal."""
        params = _params(rng)
        mesh = hvd.mesh()
        opt = hvd.DistributedOptimizer(
            optax.sgd(0.1), op=hvd.Average, local_sgd_steps=2,
            local_sgd_intra=4, local_sgd_inter_wire="int8",
        )
        step = _make_opt_step(hvd, opt, mesh)
        sync = _make_sync_step(hvd, opt, mesh)
        pm, sm = _rank_major(params), _rank_major(opt.init(params))
        res0 = np.asarray(sm.local_residual["w"])
        assert np.all(res0 == 0.0)
        grads = _grads(rng)
        for _ in range(2):
            pm, sm = step(pm, sm, grads)
        pm, sm = sync(pm, sm)
        res1 = np.asarray(sm.local_residual["w"])
        assert np.any(res1 != 0.0), "int8 wire must leave a carry"
        # replicated-consistent carry (gathered over intra)
        np.testing.assert_array_equal(res1[0], res1[3])
        # round 2 consumes the carry: running again from the same
        # params with a zeroed carry changes the merged result
        grads2 = _grads(rng)
        for _ in range(2):
            pm, sm = step(pm, sm, grads2)
        pm_a, sm_a = sync(pm, sm)
        sm_zero = sm._replace(
            local_residual=jax.tree_util.tree_map(
                jnp.zeros_like, sm.local_residual
            )
        )
        pm_b, _ = sync(pm, sm_zero)
        assert not np.array_equal(
            np.asarray(pm_a["w"]), np.asarray(pm_b["w"])
        ), "the EF carry must join the next round's wire signal"

    def test_rejects_bad_configs(self, hvd):
        with pytest.raises(ValueError, match="Sum/Average"):
            hvd.DistributedOptimizer(
                optax.sgd(0.1), op=hvd.Adasum, local_sgd_steps=4
            )
        with pytest.raises(ValueError, match="inter_wire"):
            hvd.DistributedOptimizer(
                optax.sgd(0.1), local_sgd_steps=4,
                local_sgd_inter_wire="fp8",
            )

    def test_env_default(self, hvd, monkeypatch):
        monkeypatch.setenv("HOROVOD_LOCAL_SGD_STEPS", "4")
        # the live config snapshots at init — re-init under the env
        hvd.shutdown()
        hvd.init()
        opt = hvd.DistributedOptimizer(optax.sgd(0.1))
        assert isinstance(opt, hvd.LocalSGDGradientTransformation)
        assert opt.local_sgd_steps == 4

    def test_unresolvable_split_raises(self, hvd, rng):
        """No intra override, single-slice CPU runtime: the local
        phase cannot exist and the trace says why."""
        opt = hvd.DistributedOptimizer(
            optax.sgd(0.1), local_sgd_steps=4
        )
        params = _params(rng)
        step = _make_opt_step(hvd, opt, hvd.mesh())
        with pytest.raises(ValueError, match="two-level topology"):
            step(
                _rank_major(params), _rank_major(opt.init(params)),
                _grads(rng),
            )


# ---------------------------------------------------------------------------
# ShardedDistributedOptimizer local-SGD mode
# ---------------------------------------------------------------------------


def _make_sharded_steps(hvd, opt, mesh):
    def loss(p, xb):
        return jnp.sum(jnp.tanh(xb @ p["w"]) * p["b"])

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(hvd.WORLD_AXIS), opt.state_spec(), P(hvd.WORLD_AXIS)),
        out_specs=(P(hvd.WORLD_AXIS), opt.state_spec()),
        check_vma=False,
    )
    def step(pm, s, xb):
        p = _strip(pm)
        _, g_sh = opt.value_and_grad(loss)(p, xb[0])
        u, s = opt.update(g_sh, s, p)
        return _lift(optax.apply_updates(p, u)), s

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(hvd.WORLD_AXIS), opt.state_spec()),
        out_specs=(P(hvd.WORLD_AXIS), opt.state_spec()),
        check_vma=False,
    )
    def sync_step(pm, s):
        p, s = opt.sync_round(_strip(pm), s)
        return _lift(p), s

    return jax.jit(step), jax.jit(sync_step)


def _sharded_params(rng):
    return {
        "w": jnp.asarray(rng.normal(size=(12, 6)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(6,)), jnp.float32),
    }


class TestShardedLocalSGD:
    def test_stage2_local_phase_and_sync(self, hvd, rng):
        params = _sharded_params(rng)
        xs = jnp.asarray(rng.normal(size=(WORLD, 4, 12)), jnp.float32)
        mesh = hvd.mesh()
        opt = hvd.ShardedDistributedOptimizer(
            optax.adam(1e-2), op=hvd.Sum, zero_stage=2,
            overlap_buckets=2, overlap_min_bytes=0,
            local_sgd_steps=4, local_sgd_intra=4,
        )
        state = opt.init(params)
        assert "local" in state
        step, sync = _make_sharded_steps(hvd, opt, mesh)
        pm = _rank_major(params)
        for _ in range(4):
            pm, state = step(pm, state, xs)
        w = np.asarray(pm["w"])
        np.testing.assert_array_equal(w[0], w[3])
        assert not np.allclose(w[0], w[4])
        pm2, state2 = sync(pm, state)
        w2 = np.asarray(pm2["w"])
        np.testing.assert_array_equal(w2[0], w2[7])
        assert int(np.asarray(state2["local"]["round"])[0]) == 1

    def test_stage2_local_program_zero_inter_groups(self, hvd, rng):
        from horovod_tpu import analysis

        params = _sharded_params(rng)
        xs = jnp.asarray(rng.normal(size=(WORLD, 4, 12)), jnp.float32)
        mesh = hvd.mesh()
        for stage in (1, 2):
            opt = hvd.ShardedDistributedOptimizer(
                optax.adam(1e-2), op=hvd.Sum, zero_stage=stage,
                overlap_buckets=2, overlap_min_bytes=0,
                local_sgd_steps=4, local_sgd_intra=4,
            )
            state = opt.init(params)
            step, _ = _make_sharded_steps(hvd, opt, mesh)
            g = analysis.parse_module(
                step.lower(_rank_major(params), state, xs)
            )
            _assert_intra_only(g, _stages()[0])

    def test_stage3_rejected(self, hvd):
        with pytest.raises(NotImplementedError, match="zero_stage<=2"):
            hvd.ShardedDistributedOptimizer(
                optax.adam(1e-2), zero_stage=3, local_sgd_steps=4
            )

    def test_guard_agreement_is_intra_only(self, hvd, rng):
        """A NaN in one slice skips THAT slice's step; the other slice
        applies its update — slices are independent during the local
        phase, and the guard flag never crosses DCN."""
        params = _sharded_params(rng)
        mesh = hvd.mesh()
        opt = hvd.ShardedDistributedOptimizer(
            optax.sgd(0.1), op=hvd.Sum, zero_stage=1,
            overlap_buckets=0, grad_guard=True,
            local_sgd_steps=4, local_sgd_intra=4,
        )
        state = opt.init(params)

        @partial(
            jax.shard_map, mesh=mesh,
            in_specs=(
                P(hvd.WORLD_AXIS), opt.state_spec(), P(hvd.WORLD_AXIS),
            ),
            out_specs=(P(hvd.WORLD_AXIS), opt.state_spec()),
            check_vma=False,
        )
        def step(pm, s, gm):
            p, g = _strip(pm), _strip(gm)
            u, s = opt.update(g, s, p)
            return _lift(optax.apply_updates(p, u)), s

        grads = _grads(rng)

        def poisoned(g):
            arr = np.asarray(g["w"])
            arr = arr.copy()
            arr[0, 0, 0] = np.nan  # rank 0 → slice 0 only
            return {"w": jnp.asarray(arr), "b": g["b"]}

        gw = {
            "w": jnp.asarray(
                rng.normal(size=(WORLD, 12, 6)), jnp.float32
            ),
            "b": jnp.asarray(rng.normal(size=(WORLD, 6)), jnp.float32),
        }
        pm = _rank_major(params)
        pm2, state2 = jax.jit(step)(pm, state, poisoned(gw))
        w0 = np.asarray(pm["w"])[0]
        w2 = np.asarray(pm2["w"])
        np.testing.assert_array_equal(w2[0], w0)  # slice 0 skipped
        assert not np.allclose(w2[4], w0)  # slice 1 applied
        skips = np.asarray(state2["guard"]["skips"])
        assert skips[0] == 1 and skips[4] == 0

    def test_reshard_local_family_8_to_6(self, hvd, rng):
        """The "local" layout family migrates across a world change:
        anchor values bit-exact, width re-resolved, round carried."""
        params = _sharded_params(rng)
        xs = jnp.asarray(rng.normal(size=(WORLD, 4, 12)), jnp.float32)
        mesh = hvd.mesh()
        opt = hvd.ShardedDistributedOptimizer(
            optax.adam(1e-2), op=hvd.Sum, zero_stage=2,
            overlap_buckets=2, overlap_min_bytes=0,
            local_sgd_steps=4, local_sgd_intra=4,
        )
        state = opt.init(params)
        step, sync = _make_sharded_steps(hvd, opt, mesh)
        pm = _rank_major(params)
        for _ in range(4):
            pm, state = step(pm, state, xs)
        pm, state = sync(pm, state)
        L_old = int(np.asarray(state["local"]["intra"])[0])
        size = int(np.asarray(params["w"]).size)
        anc_full_old = np.concatenate(
            [
                np.asarray(state["local"]["anchor"]["w"])[i]
                for i in range(L_old)
            ]
        )[:size]
        params_host = {k: np.asarray(v)[0] for k, v in pm.items()}
        st6 = opt.reshard_state(state, params_host, 6)
        L_new = int(np.asarray(st6["local"]["intra"])[0])
        assert L_new == 2  # gcd(4, 6)
        anc_full_new = np.concatenate(
            [
                np.asarray(st6["local"]["anchor"]["w"])[i]
                for i in range(L_new)
            ]
        )[:size]
        np.testing.assert_array_equal(anc_full_old, anc_full_new)
        assert int(np.asarray(st6["local"]["round"])[0]) == 1
        assert np.asarray(st6["local"]["anchor"]["w"]).shape[0] == 6
        # downgrade: local turned off strips the family and re-cuts
        # the moments to the flat world split
        opt_flat = hvd.ShardedDistributedOptimizer(
            optax.adam(1e-2), op=hvd.Sum, zero_stage=2,
            overlap_buckets=2, overlap_min_bytes=0,
        )
        opt_flat._world = WORLD
        st_flat = opt_flat.reshard_state(state, params_host, 6)
        assert "local" not in st_flat or not isinstance(
            st_flat, dict
        ) or set(st_flat) == {"state"}

    def test_layout_mismatch_errors(self, hvd, rng):
        params = _sharded_params(rng)
        opt_local = hvd.ShardedDistributedOptimizer(
            optax.sgd(0.1), zero_stage=1, local_sgd_steps=4,
            local_sgd_intra=4,
        )
        opt_flat = hvd.ShardedDistributedOptimizer(
            optax.sgd(0.1), zero_stage=1
        )
        st_local = opt_local.init(params)
        st_flat = opt_flat.init(params)
        mesh = hvd.mesh()

        def run(opt, st):
            @partial(
                jax.shard_map, mesh=mesh,
                in_specs=(
                    P(hvd.WORLD_AXIS), opt.state_spec(),
                    P(hvd.WORLD_AXIS),
                ),
                out_specs=(P(), opt.state_spec()),
                check_vma=False,
            )
            def step(pm, s, gm):
                p, g = _strip(pm), _strip(gm)
                u, s = opt.update(g, s, p)
                return u, s

            gm = {
                "w": jnp.ones((WORLD, 12, 6)), "b": jnp.ones((WORLD, 6)),
            }
            return step(_rank_major(params), st, gm)

        with pytest.raises(ValueError, match='no "local" layout'):
            run(opt_local, st_flat)
        with pytest.raises(ValueError, match="local_sgd_steps <= 1"):
            run(opt_flat, st_local)


# ---------------------------------------------------------------------------
# round driver: cadence, chaos-defer, counters, rejoin
# ---------------------------------------------------------------------------


class TestRoundDriver:
    def test_due_cadence(self, hvd):
        from horovod_tpu import local_sgd

        assert [local_sgd.due(i, 4) for i in range(8)] == [
            False, False, False, True, False, False, False, True,
        ]
        assert not any(local_sgd.due(i, 1) for i in range(8))

    def test_round_inter_bytes_model(self, hvd):
        from horovod_tpu import local_sgd
        from horovod_tpu.ops.adasum import vhdd_wire_bytes

        stages = _stages()
        got = local_sgd.round_inter_bytes(1 << 20, stages, "int8")
        # 2^18 fp32 elems / L=4 = 2^16 shard elems at 1 byte/elem,
        # VHDD over H=2
        want = vhdd_wire_bytes(2, (1 << 16))
        assert got == want
        assert local_sgd.round_inter_bytes(
            1 << 20, stages, "fp32"
        ) == 4 * want

    def test_inter_bytes_drop_against_the_every_step_wire(self, hvd):
        """K=8 rounds against hier-int8 on every step: the bytes over
        the slice boundary fall by at least K/2 a step (the ledger's own
        models on both sides)."""
        from horovod_tpu import local_sgd
        from horovod_tpu.ops.fusion import FusionManager

        K, L, H, grad_bytes = 8, 4, 2, 1 << 20
        every_step, _ = FusionManager._hop_bytes(
            -(-(grad_bytes // 4) // L), "int8", 4, H, 512
        )
        per_step = local_sgd.round_inter_bytes(
            grad_bytes, _stages(), "int8"
        ) / K
        assert every_step / per_step >= K / 2

    def test_k8_keeps_half_of_k1s_loss_improvement(self, hvd, rng):
        """16 steps of a small regression on per-rank data: local SGD
        with a round every 8 steps learns at least half of what the
        every-step exchange learns, and a round ran."""
        from horovod_tpu import local_sgd

        mesh = hvd.mesh()
        w_true = rng.normal(size=(24, 8)).astype(np.float32)
        xs = rng.normal(size=(16, WORLD, 6, 24)).astype(np.float32)
        ys = xs @ w_true
        params = {"w": jnp.zeros((24, 8), jnp.float32)}

        def leg(k):
            kw = dict(local_sgd_steps=k, local_sgd_intra=4) if k > 1 else {}
            opt = hvd.DistributedOptimizer(
                optax.sgd(0.05), op=hvd.Average, **kw
            )

            @partial(
                jax.shard_map, mesh=mesh,
                in_specs=(P(hvd.WORLD_AXIS),) * 4,
                out_specs=(P(hvd.WORLD_AXIS),) * 3,
                check_vma=False,
            )
            def step(pm, sm, xb, yb):
                p, s = _strip(pm), _strip(sm)
                loss, g = jax.value_and_grad(
                    lambda q: jnp.mean((xb[0] @ q["w"] - yb[0]) ** 2)
                )(p)
                u, s = opt.update(g, s, p)
                return _lift(optax.apply_updates(p, u)), _lift(s), loss[None]

            step = jax.jit(step)
            sync = _make_sync_step(hvd, opt, mesh) if k > 1 else None
            pm, sm = _rank_major(params), _rank_major(opt.init(params))
            losses, rounds = [], 0
            for i in range(16):
                pm, sm, loss = step(pm, sm, xs[i], ys[i])
                losses.append(float(np.mean(np.asarray(loss))))
                if k > 1 and local_sgd.due(i, k):
                    out, synced = local_sgd.run_round(
                        sync, pm, sm, payload_bytes=24 * 8 * 4,
                        stages=_stages(),
                    )
                    if synced:
                        (pm, sm), rounds = out, rounds + 1
            return losses[0] - losses[-1], rounds

        imp1, _ = leg(1)
        imp8, rounds = leg(8)
        assert rounds >= 1
        assert imp1 > 0
        assert imp8 >= 0.5 * imp1, (imp8, imp1)

    def test_chaos_fault_defers_round_zero_restarts(self, hvd, rng):
        """The acceptance drill, in-process: a DCN fault mid-sync-round
        exhausts the retry ladder, the round DEFERS (counted), training
        continues on the ICI wire, and the NEXT round completes — zero
        gang restarts, no exception reaches the training loop."""
        from horovod_tpu import local_sgd
        from horovod_tpu.common.metrics import registry
        from horovod_tpu.common.retry import RetryPolicy
        from horovod_tpu.testing import chaos

        params = _params(rng)
        mesh = hvd.mesh()
        opt = hvd.DistributedOptimizer(
            optax.sgd(0.1), op=hvd.Average, local_sgd_steps=2,
            local_sgd_intra=4,
        )
        step = _make_opt_step(hvd, opt, mesh)
        sync = _make_sync_step(hvd, opt, mesh)
        pm, sm = _rank_major(params), _rank_major(opt.init(params))
        base = registry.snapshot()
        # two resets in a row beats attempts=2 → the round defers once
        chaos.configure("seed=7;local_sgd.sync@1:reset;local_sgd.sync@2:reset")
        policy = RetryPolicy.from_env(
            "local_sgd.sync", attempts=2, backoff_ms=1.0,
            circuit_threshold=0,
        )
        try:
            grads = _grads(rng)
            histories = []
            for i in range(4):
                pm, sm = step(pm, sm, grads)
                out, synced = local_sgd.maybe_sync(
                    sync, pm, sm, step=i, k=2, policy=policy,
                    payload_bytes=1 << 10, stages=_stages(),
                )
                if synced:
                    pm, sm = out
                histories.append(synced)
        finally:
            chaos.reset()
        assert histories == [False, False, False, True]
        snap = registry.snapshot()
        assert (
            snap.get("local_sgd.rounds_deferred", 0)
            - base.get("local_sgd.rounds_deferred", 0)
        ) == 1
        assert (
            snap.get("local_sgd.sync_rounds", 0)
            - base.get("local_sgd.sync_rounds", 0)
        ) == 1
        assert (
            snap.get("local_sgd.local_steps", 0)
            - base.get("local_sgd.local_steps", 0)
        ) == 4
        assert (
            snap.get("local_sgd.inter_bytes", 0)
            - base.get("local_sgd.inter_bytes", 0)
        ) > 0
        assert (
            snap.get("faults_injected", 0)
            - base.get("faults_injected", 0)
        ) == 2
        # params ended reconciled: the deferred round extended the
        # local phase, the next one completed the reconciliation
        w = np.asarray(pm["w"])
        np.testing.assert_array_equal(w[0], w[7])

    def test_single_fault_retries_round_whole(self, hvd, rng):
        """One transient fault < attempts: the round RETRIES and
        completes — no deferral at all."""
        from horovod_tpu import local_sgd
        from horovod_tpu.common.metrics import registry
        from horovod_tpu.common.retry import RetryPolicy
        from horovod_tpu.testing import chaos

        params = _params(rng)
        mesh = hvd.mesh()
        opt = hvd.DistributedOptimizer(
            optax.sgd(0.1), op=hvd.Average, local_sgd_steps=2,
            local_sgd_intra=4,
        )
        step = _make_opt_step(hvd, opt, mesh)
        sync = _make_sync_step(hvd, opt, mesh)
        pm, sm = _rank_major(params), _rank_major(opt.init(params))
        base = registry.snapshot()
        chaos.configure("seed=7;local_sgd.sync@1:timeout")
        policy = RetryPolicy.from_env(
            "local_sgd.sync", attempts=3, backoff_ms=1.0,
            circuit_threshold=0,
        )
        try:
            grads = _grads(rng)
            for i in range(2):
                pm, sm = step(pm, sm, grads)
            out, synced = local_sgd.run_round(sync, pm, sm, policy=policy)
        finally:
            chaos.reset()
        assert synced
        snap = registry.snapshot()
        assert (
            snap.get("local_sgd.rounds_deferred", 0)
            - base.get("local_sgd.rounds_deferred", 0)
        ) == 0

    def test_flight_recorder_carries_round_deltas(self, hvd):
        """StepStats records carry the local_sgd.* per-step deltas, so
        a postmortem pins a deferred round to its exact step."""
        from horovod_tpu.common.metrics import registry
        from horovod_tpu.common.telemetry import TelemetryHub

        hub = TelemetryHub(capacity=4)
        hub.step_begin(0)
        registry.counter("local_sgd.local_steps")
        registry.counter("local_sgd.rounds_deferred")
        hub.step_end()
        hub.step_begin(1)
        registry.counter("local_sgd.local_steps")
        registry.counter("local_sgd.sync_rounds")
        registry.counter("local_sgd.inter_bytes", 4096)
        hub.step_end()
        recs = hub.records()
        assert recs[-2]["local_sgd.rounds_deferred"] == 1.0
        assert recs[-2]["local_sgd.sync_rounds"] == 0.0
        assert recs[-1]["local_sgd.sync_rounds"] == 1.0
        assert recs[-1]["local_sgd.inter_bytes"] == 4096.0
        assert recs[-1]["local_sgd.rounds_deferred"] == 0.0

    def test_rejoin_syncs_from_consensus_not_root(self, hvd, rng):
        """Elastic rejoin: slice 0 'restored at the anchor' (zero
        delta — the newcomer), slice 1 kept training. The rejoin round
        lands EVERY rank on the Adasum consensus — which, with one
        zero delta, is the SURVIVING slice's progress — and NOT on
        rank 0's (the root's) stale parameters."""
        from horovod_tpu import local_sgd
        from horovod_tpu.ops.adasum import adasum_vhdd_host

        params = _params(rng)
        mesh = hvd.mesh()
        opt = hvd.DistributedOptimizer(
            optax.sgd(0.1), op=hvd.Average, local_sgd_steps=4,
            local_sgd_intra=4, local_sgd_inter_wire="fp32",
        )
        step = _make_opt_step(hvd, opt, mesh)
        sync = _make_sync_step(hvd, opt, mesh)
        pm0 = _rank_major(params)
        pm, sm = pm0, _rank_major(opt.init(params))
        grads = _grads(rng)
        for _ in range(3):
            pm, sm = step(pm, sm, grads)
        # simulate the newcomer: slice 0 restored AT the anchor
        def stale_slice0(leaf, anchor_leaf):
            arr = np.asarray(leaf).copy()
            arr[0:4] = np.asarray(anchor_leaf)[0:4]
            return jnp.asarray(arr)

        pm_stale = jax.tree_util.tree_map(
            stale_slice0, pm, sm.local_anchor
        )
        out, synced = local_sgd.rejoin_sync(sync, pm_stale, sm)
        assert synced
        pm2, _ = out
        w2 = np.asarray(pm2["w"])
        np.testing.assert_array_equal(w2[0], w2[7])
        # consensus: anchor + adasum(0, delta_slice1) == slice 1's
        # progress folded in — NOT rank 0's stale params
        anchor_w = np.asarray(sm.local_anchor["w"])[0]
        d1 = np.asarray(pm["w"])[4] - anchor_w
        zero = np.zeros_like(d1).reshape(-1)
        merged = adasum_vhdd_host([zero, d1.reshape(-1)])
        want = anchor_w.reshape(-1) + merged
        np.testing.assert_allclose(
            w2[0].reshape(-1), want, rtol=1e-5, atol=1e-6
        )
        assert not np.allclose(w2[0], anchor_w), (
            "a root broadcast from the stale newcomer would have "
            "landed here"
        )


# ---------------------------------------------------------------------------
# eager fused dispatcher phase routing
# ---------------------------------------------------------------------------


class TestEagerLocalPhase:
    def test_fused_allreduce_routes_intra(self, hvd):
        from horovod_tpu.common import topology as topo

        mesh = hvd.mesh()
        stages = _stages()
        x = topo.shard_from_rank_fn(
            lambda r: np.full((8,), float(r)), mesh, dtype=np.float32
        )
        fusion = hvd.common.basics.state().fusion
        before = fusion.cache_stats()["local_dispatches"]
        with hvd.local_sgd.local_phase(stages):
            out = np.asarray(hvd.allreduce(x, op=hvd.Sum))
        assert np.all(out[0] == 6.0) and np.all(out[4] == 22.0)
        assert fusion.cache_stats()["local_dispatches"] == before + 1
        # phase cleared: the SAME composition now reduces world-wide
        # (cache keys split — a flat executable never serves a local
        # dispatch and vice versa)
        flat = np.asarray(hvd.allreduce(x, op=hvd.Sum))
        assert np.all(flat[0] == 28.0)

    def test_int8_fused_local_phase(self, hvd):
        from horovod_tpu.common import topology as topo

        mesh = hvd.mesh()
        stages = _stages()
        base = np.linspace(0.0, 1.0, 4096)
        x = topo.shard_from_rank_fn(
            lambda r: base + r, mesh, dtype=np.float32
        )
        with hvd.local_sgd.local_phase(stages):
            out = np.asarray(
                hvd.allreduce(
                    x, op=hvd.Average, compression=hvd.Compression.int8
                )
            )
        # per-chunk scales, two quantization stages: bound ~2 quanta
        # of the slice-1 range (|max| ≈ 6.5 → quantum ≈ 0.05)
        want0 = base + np.mean([0, 1, 2, 3])
        assert np.abs(out[0] - want0).max() < 0.11
        want1 = base + np.mean([4, 5, 6, 7])
        assert np.abs(out[4] - want1).max() < 0.11

    def test_phase_reset(self, hvd):
        from horovod_tpu import local_sgd

        local_sgd.set_local_phase(_stages())
        assert local_sgd.active_intra_groups() is not None
        local_sgd.reset()
        assert local_sgd.active_intra_groups() is None
