"""Two-level world (PR 10): topology detection, the hierarchical recipe
family on the flat axis, default routing through fusion / overlap /
ZeRO, hierarchical Adasum, and the straggler rebalance plane.

Bit-exactness methodology: flat psum on XLA:CPU is a left-fold while
the two-level decomposition sums intra-then-inter, so fp32 equality for
ARBITRARY data is a reassociation question, not a correctness one (see
docs/perf.md). The bit-exact assertions therefore use INTEGER-VALUED
fp32 payloads — every partial sum is exactly representable, so any
routing / permutation / scaling bug breaks equality bitwise while
legitimate reassociation cannot — plus ulp-bounded assertions on random
normal data.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from horovod_tpu.common import topology as topo_mod
from horovod_tpu import analysis
from horovod_tpu.ops import overlap, traced
from horovod_tpu.ops.reduction_ops import Average, Sum

STAGES_84 = topo_mod.hierarchical_stage_groups(8, 4)
STAGES_82 = topo_mod.hierarchical_stage_groups(8, 2)


def _mesh():
    return Mesh(np.asarray(jax.devices()[:8]), ("hvd",))


def _sm(fn, mesh=None, ins=P("hvd"), outs=P("hvd")):
    return jax.jit(
        shard_map(
            fn,
            mesh=mesh or _mesh(),
            in_specs=ins,
            out_specs=outs,
            check_vma=False,
        )
    )


def _ints(rng, shape, lo=-100, hi=100):
    return rng.integers(lo, hi, size=shape).astype(np.float32)


# ------------------------------------------------- topology detection


class TestTopologyDetection:
    def test_override_env_wins(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_INTRA_SIZE", "4")
        assert topo_mod.detect_intra_size((), 1, 1) == 1  # gcd(4, 1)
        assert topo_mod.detect_intra_size([None] * 8, 1, 1, override=4) == 4

    def test_slice_index_detection(self):
        class D:
            def __init__(self, si):
                self.slice_index = si

        devs = [D(0)] * 4 + [D(1)] * 4
        assert topo_mod.detect_intra_size(devs, 8, 1) == 4
        # uneven slices: no uniform split
        devs = [D(0)] * 5 + [D(1)] * 3
        assert topo_mod.detect_intra_size(devs, 8, 1) == 8

    def test_process_structure_detection(self):
        devs = [object()] * 8  # no slice_index attr
        assert topo_mod.detect_intra_size(devs, 2, 4) == 2
        # single process driving everything = one slice
        assert topo_mod.detect_intra_size(devs, 8, 1) == 8

    def test_gcd_degrade_survives_elastic_resize(self):
        # 8 -> 6 under HOROVOD_INTRA_SIZE=4: gcd keeps a valid split
        assert topo_mod._gcd_degrade(4, 6) == 2
        assert topo_mod._gcd_degrade(4, 8) == 4
        assert topo_mod._gcd_degrade(5, 6) == 1
        st = topo_mod.hierarchy_stages(world=6, mode="on", intra=4)
        assert st == ([[0, 1], [2, 3], [4, 5]], [[0, 2, 4], [1, 3, 5]])

    def test_mode_tri_state(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_INTRA_SIZE", "4")
        monkeypatch.setenv("HOROVOD_HIERARCHICAL", "off")
        assert topo_mod.hierarchy_stages(world=8) is None
        monkeypatch.setenv("HOROVOD_HIERARCHICAL", "on")
        assert topo_mod.hierarchy_stages(world=8) == STAGES_84
        monkeypatch.setenv("HOROVOD_HIERARCHICAL", "auto")
        # auto + explicit override = positive evidence
        assert topo_mod.hierarchy_stages(world=8) == STAGES_84
        monkeypatch.delenv("HOROVOD_INTRA_SIZE")
        # auto with no evidence (single-slice sim): flat
        assert topo_mod.hierarchy_stages(world=8) is None

    def test_legacy_flag_reads_as_on(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_HIERARCHICAL_ALLREDUCE", "1")
        monkeypatch.setenv("HOROVOD_INTRA_SIZE", "2")
        assert topo_mod.hierarchy_stages(world=8) == STAGES_82

    def test_two_level_mesh(self, hvd, monkeypatch):
        import horovod_tpu as hvd_mod

        monkeypatch.setenv("HOROVOD_INTER_AXIS", "dcn")
        from horovod_tpu.common import basics

        mesh = basics.topology().two_level_mesh(intra_size=4)
        assert mesh.axis_names == ("dcn", "intra")
        assert mesh.devices.shape == (2, 4)
        with pytest.raises(ValueError):
            basics.topology().two_level_mesh(intra_size=3)


# ------------------------------------- traced recipe family (groups)


class TestHierRecipes:
    @pytest.mark.parametrize("stages", [STAGES_84, STAGES_82])
    @pytest.mark.parametrize("op", [Sum, Average])
    def test_allreduce_groups_bitexact_integer(self, hvd, stages, op):
        rng = np.random.default_rng(0)
        x = _ints(rng, (8, 37))
        flat = _sm(lambda v: traced.allreduce(v, op=op))(x)
        hier = _sm(
            lambda v: traced.hierarchical_allreduce_groups(
                v[0], op=op, stages=stages
            )[None]
        )(x)
        np.testing.assert_array_equal(np.asarray(flat), np.asarray(hier))

    def test_allreduce_groups_ulp_bound_random(self, hvd):
        rng = np.random.default_rng(1)
        x = rng.normal(size=(8, 513)).astype(np.float32)
        hier = np.asarray(
            _sm(
                lambda v: traced.hierarchical_allreduce_groups(
                    v[0], op=Sum, stages=STAGES_84
                )[None]
            )(x)
        )
        want = x.astype(np.float64).sum(0)
        # reassociation-only error: a few ulp of the accumulated sum
        tol = 8 * np.finfo(np.float32).eps * np.abs(want).max()
        assert np.abs(hier[0] - want).max() <= tol
        # replicas agree bitwise — it is a well-formed allreduce
        for r in range(8):
            np.testing.assert_array_equal(hier[r], hier[0])

    def test_allreduce_groups_scales(self, hvd):
        rng = np.random.default_rng(2)
        x = _ints(rng, (8, 16))
        out = np.asarray(
            _sm(
                lambda v: traced.hierarchical_allreduce_groups(
                    v[0], op=Sum, stages=STAGES_84,
                    prescale_factor=0.5, postscale_factor=2.0,
                )[None]
            )(x)
        )
        np.testing.assert_array_equal(out[0], x.sum(0))

    def test_int8_inter_within_quanta_and_consistent(self, hvd):
        rng = np.random.default_rng(3)
        x = rng.normal(size=(8, 300)).astype(np.float32)
        out = np.asarray(
            _sm(
                lambda v: traced.hierarchical_allreduce_groups(
                    v[0], op=Sum, stages=STAGES_84, inter_wire="int8",
                    intra_wire="bf16", block_size=64, seed=7,
                )[None]
            )(x)
        )
        want = x.sum(0)
        scale = np.abs(want).max() / 127.0
        assert np.abs(out[0] - want).max() < 3.0 * scale
        for r in range(8):
            np.testing.assert_array_equal(out[r], out[0])

    def test_int8_inter_ef_residual_chains(self, hvd):
        """Two chained EF steps: the cumulative transmitted signal
        lands within one fresh step's error of 2x the target (the EF
        property, group edition of the two-axis test)."""
        rng = np.random.default_rng(4)
        x = rng.normal(size=(8, 128)).astype(np.float32)

        def step(v, c):
            o, r = traced.hierarchical_allreduce_groups(
                v[0] + c[0], op=Sum, stages=STAGES_84,
                inter_wire="int8", block_size=64, seed=11,
                return_residual=True,
            )
            return o[None], r[None]

        f = _sm(step, ins=(P("hvd"), P("hvd")), outs=(P("hvd"), P("hvd")))
        want = x.sum(0)
        scale = np.abs(want).max() / 127.0
        carry = jnp.zeros_like(jnp.asarray(x))
        outs = []
        for _ in range(2):
            o, carry = f(jnp.asarray(x), carry)
            outs.append(np.asarray(o))
        cum = np.abs(outs[0][0] + outs[1][0] - 2 * want).max()
        assert cum < 4.0 * scale
        # the carry really changed what step 2 transmitted
        assert np.abs(outs[1] - outs[0]).max() > 0.0

    @pytest.mark.parametrize("op", [Sum, Average])
    def test_reducescatter_bitexact_integer(self, hvd, op):
        rng = np.random.default_rng(5)
        panes = _ints(rng, (8, 8, 5))

        def flat(v):
            out = jax.lax.psum_scatter(
                v[0], "hvd", scatter_dimension=0, tiled=True
            )
            return out / 8 if op == Average else out

        ref = np.asarray(_sm(flat)(panes))
        got = np.asarray(
            _sm(
                lambda v: traced.hierarchical_reducescatter(
                    v[0], op=op, stages=STAGES_84
                )[None]
            )(panes)
        )
        np.testing.assert_array_equal(ref, got)

    def test_allgather_bitexact_and_int8(self, hvd):
        rng = np.random.default_rng(6)
        shards = _ints(rng, (8, 5))
        ref = np.asarray(
            _sm(lambda v: jax.lax.all_gather(v[0], "hvd")[None])(shards)
        )
        got = np.asarray(
            _sm(
                lambda v: traced.hierarchical_allgather(
                    v[0], stages=STAGES_84
                )[None]
            )(shards)
        )
        np.testing.assert_array_equal(ref, got)
        g8 = np.asarray(
            _sm(
                lambda v: traced.hierarchical_allgather(
                    v[0], stages=STAGES_84, inter_wire="int8",
                    block_size=4, seed=1,
                )[None]
            )(shards)
        )
        scale = np.abs(shards).max() / 127.0
        assert np.abs(g8 - ref).max() <= 1.5 * scale
        for r in range(8):
            np.testing.assert_array_equal(g8[r], g8[0])


class TestMaskedDegeneration:
    """psets and join masks have no uniform group shape under the
    two-level split — the routing must degenerate to the (bit-exact)
    flat masked wire, never half-apply the hierarchy."""

    def test_join_mask_bitexact_vs_flat(self, hvd):
        rng = np.random.default_rng(30)
        x = _ints(rng, (8, 48))
        mask = np.array([True] * 6 + [False] * 2)

        def body(v, stages):
            out = overlap.bucketed_allreduce(
                {"g": v[0]}, op=Average, n_buckets=2,
                min_bucket_bytes=0, mask=mask, hier_stages=stages,
            )
            return out["g"][None]

        flat = np.asarray(_sm(partial(body, stages=None))(x))
        hier = np.asarray(_sm(partial(body, stages=STAGES_84))(x))
        np.testing.assert_array_equal(flat, hier)
        np.testing.assert_array_equal(flat[0], x[:6].sum(0) / 6)

    def test_pset_bitexact_vs_flat(self, hvd):
        import horovod_tpu as hvd_mod
        from horovod_tpu.common.process_sets import ProcessSet

        ps = ProcessSet([0, 1, 2, 3])
        ps.process_set_id = 7  # proper subset (not the global set)
        rng = np.random.default_rng(31)
        x = _ints(rng, (8, 32))

        def body(v, stages):
            out = overlap.bucketed_allreduce(
                {"g": v[0]}, op=Sum, n_buckets=2, min_bucket_bytes=0,
                process_set=ps, hier_stages=stages,
            )
            return out["g"][None]

        flat = np.asarray(_sm(partial(body, stages=None))(x))
        hier = np.asarray(_sm(partial(body, stages=STAGES_84))(x))
        np.testing.assert_array_equal(flat, hier)
        np.testing.assert_array_equal(flat[0], x[:4].sum(0))
        np.testing.assert_array_equal(flat[5], x[5])  # outsider keeps input

    def test_eager_mask_keeps_flat_wire(self, monkeypatch):
        """The fused dispatcher: a join-masked batch under forced
        hierarchy still computes the exact masked result (the spec
        degenerates before the core compiles)."""
        import horovod_tpu as hvd_mod

        monkeypatch.setenv("HOROVOD_HIERARCHICAL", "on")
        monkeypatch.setenv("HOROVOD_INTRA_SIZE", "4")
        hvd_mod.shutdown()
        hvd_mod.init()
        try:
            rng = np.random.default_rng(32)
            per = _ints(rng, (8, 40))
            x = hvd_mod.shard_from_rank_fn(
                lambda r: per[r], hvd_mod.mesh()
            )
            mask = np.array([True] * 6 + [False] * 2)
            out = np.asarray(
                jax.device_get(
                    hvd_mod.allreduce(x, op=hvd_mod.Average, mask=mask)
                )
            )
            np.testing.assert_array_equal(out[0], per[:6].sum(0) / 6)
        finally:
            hvd_mod.shutdown()


# ---------------------------------- lowered-module stage structure


# structure gates ride the shared horovod_tpu.analysis parser — no
# per-file regex over as_text()

INTRA_84 = ((0, 1, 2, 3), (4, 5, 6, 7))
INTER_84 = ((0, 4), (1, 5), (2, 6), (3, 7))


def _tree(rng, shapes):
    return {
        f"p{i}": jnp.asarray(
            np.broadcast_to(
                _ints(rng, (8,) + s, -40, 40), (8,) + s
            ).copy()
        )
        for i, s in enumerate(shapes)
    }


class TestLoweredStructure:
    def test_per_bucket_intra_rs_inter_ar_intra_ag(self, hvd):
        """With N buckets on the hierarchical wire, the lowered module
        carries exactly N intra-group reduce-scatters + N inter-group
        all-reduces + N intra-group all-gathers, and no bucket's
        collective chain depends on another's (independence — the
        overlap contract survives the two-level decomposition)."""
        rng = np.random.default_rng(7)
        t = _tree(rng, [(64,), (33,), (7,)])

        def body(tr):
            local = jax.tree_util.tree_map(lambda x: x[0], tr)
            out = overlap.bucketed_allreduce(
                local, op=Sum, n_buckets=3, min_bucket_bytes=0,
                hier_stages=STAGES_84,
            )
            return jax.tree_util.tree_map(lambda x: x[None], out)

        fn = _sm(body)
        g = analysis.parse_module(fn.lower(t))
        counts = g.counts()
        assert counts["reduce_scatter"] == counts["all_reduce"]
        assert counts["all_reduce"] == counts["all_gather"]
        assert counts["reduce_scatter"] >= 2  # 3 leaves -> >= 2 buckets
        # intra groups on RS/AG, inter groups on the AR; no bucket's
        # inter stage depends on another's
        analysis.expect(
            g,
            analysis.ReplicaGroupStructure(
                "reduce_scatter", groups=INTRA_84, require_present=True
            ),
            analysis.ReplicaGroupStructure(
                "all_gather", groups=INTRA_84, require_present=True
            ),
            analysis.ReplicaGroupStructure(
                "all_reduce", groups=INTER_84, require_present=True,
                forbid_world_spanning=True,
            ),
            analysis.NoInterCollectiveDefUse("all_reduce"),
        )
        # and the result is bit-exact vs the flat wire
        flat = jax.device_get(
            _sm(
                lambda tr: jax.tree_util.tree_map(
                    lambda x: x[None],
                    overlap.bucketed_allreduce(
                        jax.tree_util.tree_map(lambda x: x[0], tr),
                        op=Sum, n_buckets=3, min_bucket_bytes=0,
                        hier_stages=None,
                    ),
                )
            )(t)
        )
        hier = jax.device_get(fn(t))
        for k in t:
            np.testing.assert_array_equal(flat[k], hier[k])

    def test_zero_legs_hier_structure_and_parity(self, hvd):
        """The ZeRO bucket legs: hierarchical RS/AG are bit-exact vs
        flat on integer payloads, and the lowered RS leg carries
        intra-group reduce-scatters (the DCN hop sees 1/L panes)."""
        rng = np.random.default_rng(8)
        t = _tree(rng, [(64,), (33,)])

        def rs(tr, stages):
            local = jax.tree_util.tree_map(lambda x: x[0], tr)
            out = overlap.bucketed_reduce_scatter(
                local, op=Sum, n_buckets=2, min_bucket_bytes=0,
                hier_stages=stages,
            )
            return jax.tree_util.tree_map(lambda x: x[None], out)

        f_flat = _sm(partial(rs, stages=None))
        f_hier = _sm(partial(rs, stages=STAGES_84))
        a = jax.device_get(f_flat(t))
        b = jax.device_get(f_hier(t))
        for k in t:
            np.testing.assert_array_equal(a[k], b[k])
        g_hier = analysis.parse_module(f_hier.lower(t))
        # the DCN hop sees 1/L panes: the RS leg carries intra-group
        # reduce-scatters (the inter exchange rides its own groups)
        assert INTRA_84 in g_hier.replica_groups("reduce_scatter")

        def ag(tr, stages):
            local = jax.tree_util.tree_map(lambda x: x[0], tr)
            sh = overlap.bucketed_reduce_scatter(
                local, op=Sum, n_buckets=2, min_bucket_bytes=0,
                hier_stages=None,
            )
            full = overlap.bucketed_shard_all_gather(
                sh, local, n_buckets=2, min_bucket_bytes=0,
                hier_stages=stages,
            )
            return jax.tree_util.tree_map(lambda x: x[None], full)

        a = jax.device_get(_sm(partial(ag, stages=None))(t))
        b = jax.device_get(_sm(partial(ag, stages=STAGES_84))(t))
        for k in t:
            np.testing.assert_array_equal(a[k], b[k])


# ----------------------------------- default routing: fused + ZeRO


class TestDefaultRouting:
    def _reinit(self, monkeypatch, **env):
        import horovod_tpu as hvd_mod

        for k, v in env.items():
            monkeypatch.setenv(k, v)
        hvd_mod.shutdown()
        hvd_mod.init()
        return hvd_mod

    def test_fused_eager_hier_default_bitexact(self, monkeypatch):
        hvd_mod = self._reinit(
            monkeypatch,
            HOROVOD_HIERARCHICAL="on",
            HOROVOD_INTRA_SIZE="4",
        )
        try:
            rng = np.random.default_rng(9)
            per = _ints(rng, (8, 513))
            x = hvd_mod.shard_from_rank_fn(
                lambda r: per[r], hvd_mod.mesh()
            )
            out = np.asarray(
                jax.device_get(hvd_mod.allreduce(x, op=hvd_mod.Sum))
            )
            np.testing.assert_array_equal(out[0], per.sum(0))
            from horovod_tpu.common import basics

            st = basics.state().fusion.cache_stats()
            assert st["hier_dispatches"] >= 1
            assert st["wire_bytes_saved_inter"] > 0
            assert st["wire_bytes_saved_intra"] == 0  # fp32 intra
            # still one dispatch for the batch
            assert basics.state().fusion.last_cycle_dispatches == 1
        finally:
            hvd_mod.shutdown()

    def test_fused_eager_hier_off_by_default_on_single_slice(
        self, monkeypatch
    ):
        hvd_mod = self._reinit(monkeypatch)  # auto, no evidence
        try:
            rng = np.random.default_rng(10)
            per = _ints(rng, (8, 64))
            x = hvd_mod.shard_from_rank_fn(
                lambda r: per[r], hvd_mod.mesh()
            )
            np.asarray(jax.device_get(hvd_mod.allreduce(x, op=hvd_mod.Sum)))
            from horovod_tpu.common import basics

            assert (
                basics.state().fusion.cache_stats()["hier_dispatches"] == 0
            )
        finally:
            hvd_mod.shutdown()

    def test_int8_wire_places_bf16_intra_int8_inter(self, monkeypatch):
        hvd_mod = self._reinit(
            monkeypatch,
            HOROVOD_HIERARCHICAL="on",
            HOROVOD_INTRA_SIZE="4",
        )
        try:
            from horovod_tpu.ops.compression import Compression

            rng = np.random.default_rng(11)
            per = rng.normal(size=(8, 600)).astype(np.float32)
            h = hvd_mod.allreduce_async(
                hvd_mod.shard_from_rank_fn(
                    lambda r: per[r], hvd_mod.mesh()
                ),
                op=hvd_mod.Sum,
                compression=Compression.int8_block,
            )
            out = np.asarray(h.wait())
            want = per.sum(0)
            scale = np.abs(want).max() / 127.0
            assert np.abs(out[0] - want).max() < 4.0 * scale
            from horovod_tpu.common import basics
            from horovod_tpu.common.metrics import WIRE_FORMAT_CODES

            st = basics.state().fusion.cache_stats()
            assert st["wire_format_inter"] == WIRE_FORMAT_CODES["int8"]
            assert st["wire_format_intra"] == WIRE_FORMAT_CODES["bf16"]
            assert st["wire_bytes_saved_inter"] > 0
            assert st["wire_bytes_saved_intra"] > 0  # bf16 intra
        finally:
            hvd_mod.shutdown()

    def test_sharded_optimizer_hier_trajectory(self, monkeypatch):
        import optax

        monkeypatch.setenv("HOROVOD_HIERARCHICAL", "on")
        monkeypatch.setenv("HOROVOD_INTRA_SIZE", "4")
        from horovod_tpu.sharded_optimizer import (
            ShardedDistributedOptimizer,
        )

        rng = np.random.default_rng(12)
        params = {
            "w": jnp.asarray(rng.normal(size=(33,)).astype(np.float32)),
            "v": jnp.asarray(rng.normal(size=(65,)).astype(np.float32)),
        }

        def run(hier):
            opt = ShardedDistributedOptimizer(
                optax.adam(1e-2), world=8, overlap_buckets=2,
                hierarchical=hier,
            )
            state = opt.init(params)
            p = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (8,) + x.shape), params
            )

            def step(p_, s_, g_):
                pl = jax.tree_util.tree_map(lambda x: x[0], p_)
                gl = jax.tree_util.tree_map(lambda x: x[0], g_)
                upd, s2 = opt.update(gl, s_, pl)
                p2 = optax.apply_updates(pl, upd)
                return (
                    jax.tree_util.tree_map(lambda x: x[None], p2),
                    s2,
                )

            f = _sm(
                step,
                ins=(P("hvd"), opt.state_spec(), P("hvd")),
                outs=(P("hvd"), opt.state_spec()),
            )
            for i in range(3):
                g = jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(
                        jnp.sin(x * (i + 1)), (8,) + x.shape
                    ),
                    params,
                )
                p, state = f(p, state, g)
            return jax.device_get(p)

        flat, hier = run(False), run(None)
        for k in flat:
            np.testing.assert_allclose(
                flat[k], hier[k], rtol=0, atol=1e-6
            )

    def test_elastic_8_to_6_reshard_on_two_level_mesh(self, monkeypatch):
        """The chaos geometry: a gang shrinks 8 -> 6 under
        HOROVOD_INTRA_SIZE=4. The split degrades to gcd=2 (stays
        two-level), the sharded state reshard carries moments, and the
        world-6 hierarchical update equals the world-6 flat one."""
        import optax

        monkeypatch.setenv("HOROVOD_HIERARCHICAL", "on")
        monkeypatch.setenv("HOROVOD_INTRA_SIZE", "4")
        from horovod_tpu.sharded_optimizer import (
            ShardedDistributedOptimizer,
        )

        assert topo_mod.hierarchy_stages(world=6) == (
            [[0, 1], [2, 3], [4, 5]],
            [[0, 2, 4], [1, 3, 5]],
        )
        rng = np.random.default_rng(13)
        params = {
            "w": jnp.asarray(rng.normal(size=(45,)).astype(np.float32))
        }
        mesh6 = Mesh(np.asarray(jax.devices()[:6]), ("hvd",))

        def run(hier):
            opt8 = ShardedDistributedOptimizer(
                optax.adam(1e-2), world=8, overlap_buckets=2,
                hierarchical=hier,
            )
            state = opt8.init(params)
            # the reshard is the elastic resume contract: moments carry
            opt6 = ShardedDistributedOptimizer(
                optax.adam(1e-2), world=6, overlap_buckets=2,
                hierarchical=hier,
            )
            state6 = opt6.reshard_state(state, params, 6)
            p = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (6,) + x.shape), params
            )

            def step(p_, s_, g_):
                pl = jax.tree_util.tree_map(lambda x: x[0], p_)
                gl = jax.tree_util.tree_map(lambda x: x[0], g_)
                upd, s2 = opt6.update(gl, s_, pl)
                return (
                    jax.tree_util.tree_map(
                        lambda x: x[None],
                        optax.apply_updates(pl, upd),
                    ),
                    s2,
                )

            f = _sm(
                step,
                mesh=mesh6,
                ins=(P("hvd"), opt6.state_spec(), P("hvd")),
                outs=(P("hvd"), opt6.state_spec()),
            )
            g = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(
                    jnp.cos(x), (6,) + x.shape
                ),
                params,
            )
            p, state6 = f(p, state6, g)
            return jax.device_get(p)

        flat, hier = run(False), run(None)
        np.testing.assert_allclose(
            flat["w"], hier["w"], rtol=0, atol=1e-6
        )


# ---------------------------------------- hier_int8 (satellite fix)


class TestHierInt8TracedPath:
    def test_optimizer_path_is_two_level_and_matches_eager(
        self, monkeypatch
    ):
        """Compression.hier_int8 on the traced/optimizer path no longer
        collapses to flat single-stage int8: the lowered module carries
        the intra RS/AG legs, and the result agrees with the eager
        fused placement within the shared quantum budget."""
        import horovod_tpu as hvd_mod

        monkeypatch.setenv("HOROVOD_INTRA_SIZE", "4")
        hvd_mod.shutdown()
        hvd_mod.init()
        try:
            from horovod_tpu.optimizer import _allreduce_grads
            from horovod_tpu.ops.compression import Compression

            rng = np.random.default_rng(14)
            g = rng.normal(size=(8, 600)).astype(np.float32)

            def body(t):
                out = _allreduce_grads(
                    {"g": t[0]}, Average, Compression.hier_int8,
                    1.0, 1.0, None, "hvd", seed=3,
                )
                return out["g"][None]

            f = _sm(body)
            # two-level signature: an intra reduce-scatter + the intra
            # all-gather around the inter int8 recipe
            analysis.expect(
                analysis.parse_module(f.lower(jnp.asarray(g))),
                analysis.CollectiveCount("reduce_scatter", 1),
                analysis.ReplicaGroupStructure(
                    "reduce_scatter", groups=INTRA_84
                ),
            )
            out = np.asarray(f(jnp.asarray(g)))
            want = g.mean(0)
            scale = np.abs(g.sum(0)).max() / 127.0 / 8
            assert np.abs(out[0] - want).max() < 4.0 * scale
            # eager placement on the same data agrees within budget
            h = hvd_mod.allreduce_async(
                hvd_mod.shard_from_rank_fn(
                    lambda r: g[r], hvd_mod.mesh()
                ),
                op=hvd_mod.Average,
                compression=Compression.hier_int8,
            )
            eager = np.asarray(h.wait())
            assert np.abs(eager[0] - out[0]).max() < 6.0 * scale
        finally:
            hvd_mod.shutdown()

    def test_bucketed_hier_int8_explicit_request(self, monkeypatch):
        """hier_int8 through the bucketed exchange resolves a split in
        auto mode from the explicit request alone."""
        monkeypatch.setenv("HOROVOD_INTRA_SIZE", "2")
        from horovod_tpu.ops.compression import Compression

        rng = np.random.default_rng(15)
        t = {"a": jnp.asarray(rng.normal(size=(8, 256)).astype(np.float32))}

        def body(tr):
            local = jax.tree_util.tree_map(lambda x: x[0], tr)
            out = overlap.bucketed_allreduce(
                local, op=Sum, n_buckets=1, min_bucket_bytes=0,
                compression=Compression.hier_int8,
            )
            return jax.tree_util.tree_map(lambda x: x[None], out)

        f = _sm(body)
        analysis.expect(
            analysis.parse_module(f.lower(t)),
            analysis.CollectiveCount("reduce_scatter", 1),
            analysis.ReplicaGroupStructure(
                "reduce_scatter",
                groups=((0, 1), (2, 3), (4, 5), (6, 7)),
            ),
        )
        out = jax.device_get(f(t))["a"]
        want = np.asarray(t["a"]).sum(0)
        scale = np.abs(want).max() / 127.0
        assert np.abs(out[0] - want).max() < 4.0 * scale


    @pytest.mark.parametrize(
        "wire,least", [("fp32", 4.0), ("int8", 3.0)]
    )
    def test_inter_hop_bytes_drop_per_bucket_schedule(self, wire, least):
        """The scarce hop's bytes over a bucket schedule, by the ledger's
        own model (``FusionManager._hop_bytes``): the two-level wire
        sends the 1/L shard across slices, so fp32 drops >= L x against
        the flat wire and int8 >= 3 x (4L x less the block scales)."""
        from horovod_tpu.ops.fusion import FusionManager

        L, H, block = 4, 2, 512
        leaves = [np.zeros((64, 64), np.float32) for _ in range(4)]
        sched = overlap.build_bucket_schedule(leaves, 4, 0)
        flat = inter = 0
        for nbytes in sched.bucket_bytes:
            elems = nbytes // 4
            flat += FusionManager._hop_bytes(elems, "fp32", 4, L * H, block)[0]
            inter += FusionManager._hop_bytes(
                -(-elems // L), wire, 4, H, block
            )[0]
        assert flat / inter >= least, (flat, inter)

    def test_bucketed_hier_int8_counts_per_bucket(self, hvd):
        """Per bucket the int8 leg lowers to one intra reduce-scatter
        and two inter all_to_alls (the int8 payload and its scales)."""
        from horovod_tpu.ops.compression import Compression

        rng = np.random.default_rng(16)
        t = _tree(rng, [(64, 64)] * 4)

        def body(tr):
            local = jax.tree_util.tree_map(lambda x: x[0], tr)
            out = overlap.bucketed_allreduce(
                local, op=Sum, n_buckets=4, min_bucket_bytes=0,
                compression=Compression.int8_block,
                hier_stages=STAGES_84,
            )
            return jax.tree_util.tree_map(lambda x: x[None], out)

        analysis.expect(
            analysis.parse_module(_sm(body).lower(t)),
            analysis.CollectiveCount("reduce_scatter", 4),
            analysis.CollectiveCount("all_to_all", 8),
        )


# ------------------------------------------------ hierarchical Adasum


class TestHierAdasum:
    def _mesh2(self, L):
        return Mesh(
            np.asarray(jax.devices()[:8]).reshape(8 // L, L),
            (topo_mod.INTER_AXIS, topo_mod.INTRA_AXIS),
        )

    def _run(self, per, L, **kw):
        from horovod_tpu.ops import adasum

        spec = P((topo_mod.INTER_AXIS, topo_mod.INTRA_AXIS))
        f = jax.jit(
            shard_map(
                lambda x: adasum.adasum_allreduce(
                    x[0], hierarchical=True, **kw
                )[None],
                mesh=self._mesh2(L),
                in_specs=spec,
                out_specs=spec,
                check_vma=False,
            )
        )
        return np.asarray(f(jnp.asarray(per)))

    @pytest.mark.parametrize("L", [2, 4])
    def test_matches_host_oracle(self, hvd, L):
        """intra Sum -> Adasum across slices == adasum_vhdd_host over
        the per-slice sums (the reference's hierarchical semantics,
        adasum_gpu_operations.cc [V])."""
        from horovod_tpu.ops import adasum

        H = 8 // L
        rng = np.random.default_rng(16)
        per = rng.normal(size=(8, 97)).astype(np.float32)
        want = adasum.adasum_vhdd_host(
            [per[e * L : (e + 1) * L].sum(0) for e in range(H)]
        )
        got = self._run(per, L)
        np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-5)
        for r in range(8):
            np.testing.assert_array_equal(got[r], got[0])

    def test_scale_invariance(self, hvd):
        rng = np.random.default_rng(17)
        per = rng.normal(size=(8, 64)).astype(np.float32)
        a = self._run(per, 4)
        b = self._run(per * 1000.0, 4)
        np.testing.assert_allclose(
            b[0] / 1000.0, a[0], rtol=1e-4, atol=1e-5
        )

    @pytest.mark.parametrize("L", [2, 4])
    def test_int8_inter_wire_consistent_within_quanta(self, hvd, L):
        """The quantized inter wire: all replicas agree BITWISE (the
        owner-consumes-wire-value rule + piece-class keys) and the
        result stays within a few quanta of the exact composition."""
        from horovod_tpu.ops import adasum

        H = 8 // L
        rng = np.random.default_rng(18)
        per = rng.normal(size=(8, 97)).astype(np.float32)
        want = adasum.adasum_vhdd_host(
            [per[e * L : (e + 1) * L].sum(0) for e in range(H)]
        )
        got = self._run(per, L, inter_wire="int8", seed=5)
        for r in range(8):
            np.testing.assert_array_equal(got[r], got[0])
        scale = np.abs(want).max() / 127.0
        assert np.abs(got[0] - want).max() < 6.0 * scale

    def test_rejects_process_sets(self, hvd):
        from horovod_tpu.ops import adasum
        from horovod_tpu.common.process_sets import ProcessSet

        with pytest.raises(NotImplementedError):
            adasum.adasum_allreduce(
                jnp.zeros(4), hierarchical=True,
                process_set=ProcessSet([0, 1]),
            )


# ---------------------------------------------- per-hop wire tuning


class TestPerHopWire:
    def test_intra_hop_never_int8(self):
        overlap.reset_wire_tuner()
        assert (
            overlap.resolve_wire("int8", 1 << 20, hop="intra") == "fp32"
        )
        assert (
            overlap.resolve_wire("int8", 1 << 20, hop="inter") == "int8"
        )

    def test_hop_keys_are_disjoint(self):
        overlap.reset_wire_tuner()
        t = overlap.wire_tuner()
        key = ("bucket", 1 << 20)
        # teach the inter hop that int8 is great; the intra hop must
        # not inherit that observation
        for _ in range(t.trials):
            t.record(key + ("inter",), "int8", 1 << 20, 1e-3)
            t.record(key + ("inter",), "fp32", 1 << 20, 1.0)
            t.record(key + ("inter",), "bf16", 1 << 20, 1.0)
        assert (
            overlap.resolve_wire("auto", 1 << 20, key=key, hop="inter")
            == "int8"
        )
        assert (
            overlap.resolve_wire("auto", 1 << 20, key=key, hop="intra")
            != "int8"
        )
        overlap.reset_wire_tuner()


# ------------------------------------------------ straggler rebalance


class TestRebalance:
    def _driver(self, monkeypatch, enabled=True):
        import types

        from horovod_tpu.elastic.driver import ElasticDriver
        from horovod_tpu.elastic.discovery import HostDiscovery
        from horovod_tpu.runner.hosts import HostInfo
        from horovod_tpu.runner.rendezvous import KVStore

        class Disc(HostDiscovery):
            def find_available_hosts_and_slots(self):
                return [HostInfo("a", 4), HostInfo("b", 4)]

        if enabled:
            monkeypatch.setenv("HOROVOD_REBALANCE", "1")
        d = ElasticDriver(Disc(), ["true"], min_np=1)
        d._server = types.SimpleNamespace(store=KVStore())
        return d

    def _beat(self, d, p50s, ts):
        for r, p in p50s.items():
            d.stall_inspector.record_heartbeat(
                r, ts=ts, step=100, step_ms_p50=p
            )
        d.stall_inspector.check()

    def test_down_weights_persistent_straggler(self, monkeypatch):
        import time

        from horovod_tpu.runner.rendezvous import (
            read_rebalance_weights,
        )

        d = self._driver(monkeypatch)
        p50s = {0: 100.0, 1: 100.0, 2: 100.0, 3: 800.0}
        now = time.time()
        # streak 1 (fresh stamp): no rebalance yet
        self._beat(d, p50s, now)
        d._maybe_rebalance()
        assert read_rebalance_weights(d._server.store) == {}
        # streak 2 (second FRESH stamp): rank 3 down-weighted
        self._beat(d, p50s, now + 10)
        d._maybe_rebalance()
        w = read_rebalance_weights(d._server.store)
        assert w[3] < 1.0
        assert w[0] == w[1] == w[2] == 1.0
        assert w[3] == max(0.25, min(1.0, round(100.0 / 800.0, 2)))
        # recovery publishes the reset map
        p50s[3] = 100.0
        self._beat(d, p50s, now + 20)
        d._maybe_rebalance()
        w = read_rebalance_weights(d._server.store)
        assert all(v == 1.0 for v in w.values())

    def test_stale_stamp_does_not_advance(self, monkeypatch):
        import time

        from horovod_tpu.runner.rendezvous import (
            read_rebalance_weights,
        )

        d = self._driver(monkeypatch)
        p50s = {0: 100.0, 1: 100.0, 2: 800.0}
        now = time.time()
        self._beat(d, p50s, now)
        # the driver polls faster than workers beat: same stamp again
        self._beat(d, p50s, now)
        d._maybe_rebalance()
        assert read_rebalance_weights(d._server.store) == {}

    def test_disabled_publishes_nothing(self, monkeypatch):
        import time

        from horovod_tpu.runner.rendezvous import (
            read_rebalance_weights,
        )

        d = self._driver(monkeypatch, enabled=False)
        now = time.time()
        self._beat(d, {0: 100.0, 1: 900.0}, now)
        self._beat(d, {0: 100.0, 1: 900.0}, now + 10)
        d._maybe_rebalance()
        assert read_rebalance_weights(d._server.store) == {}

    def test_worker_read_helpers(self, monkeypatch):
        from horovod_tpu.elastic import worker as worker_mod
        from horovod_tpu.runner.rendezvous import (
            KVStore,
            put_rebalance_weights,
            read_rebalance_weights,
        )

        store = KVStore()
        put_rebalance_weights(store, {0: 1.0, 3: 0.5}, epoch=2)
        assert read_rebalance_weights(store) == {0: 1.0, 3: 0.5}
        # no rendezvous configured: helpers degrade to defaults
        monkeypatch.delenv("HOROVOD_GLOO_RENDEZVOUS_ADDR", raising=False)
        assert worker_mod.rebalance_weights() == {}
        assert worker_mod.rebalance_weight(rank=3) == 1.0

    def test_malformed_blob_reads_empty(self):
        from horovod_tpu.runner.rendezvous import (
            KVStore,
            REBALANCE_SCOPE,
            read_rebalance_weights,
        )

        store = KVStore()
        store.put(REBALANCE_SCOPE, "weights", b"\xff not json")
        assert read_rebalance_weights(store) == {}
