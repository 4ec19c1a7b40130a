"""Backward-interleaved bucketed gradient exchange (ops/overlap.py).

The acceptance contract of the bucketed layer:

* numeric parity with the monolithic path — BIT-exact for op=Sum fp32
  (psum over a concat is elementwise identical to per-leaf psum),
  within the documented quantum/cast bounds for Average / compressed
  wires, including process-set and join cases;
* compiled-program evidence of independence — the lowered step for
  ``overlap_buckets=N`` carries N separate collective ops with no
  def-use path from one bucket's collective to another's operands;
* schedule/compile stability — one schedule build and one trace per
  bucket config across steps (cache stats + trace counter);
* per-bucket preservation of the PR-2 wire machinery — EF residuals,
  the prescale fold, and Compression.int8_block granularity.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd_mod
from horovod_tpu import analysis
from horovod_tpu.ops import overlap, traced
from horovod_tpu.ops.compression import Compression

WORLD = 8


def _shmap(mesh, fn, in_specs=(P(),), out_specs=P()):
    return jax.jit(
        partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )(fn)
    )


def _tree(rng, sizes, dtype=np.float32):
    return {
        f"p{i:02d}": jnp.asarray(rng.normal(size=s), dtype)
        for i, s in enumerate(sizes)
    }


# --------------------------------------------------------- the schedule


class TestBucketSchedule:
    def test_reverse_order_and_balance(self):
        leaves = [np.zeros((64,), np.float32) for _ in range(8)]
        s = overlap.build_bucket_schedule(leaves, 4)
        assert s.n_buckets == 4
        # reverse flatten order: the LAST leaves (produced first in
        # backprop) fill bucket 0
        assert s.buckets == ((7, 6), (5, 4), (3, 2), (1, 0))
        assert set(s.bucket_bytes) == {512}
        assert s.total_bytes == 8 * 64 * 4

    def test_dtype_boundary_forces_split(self):
        leaves = [
            np.zeros((16,), np.float32),
            np.zeros((16,), np.float16),
            np.zeros((16,), np.float16),
        ]
        s = overlap.build_bucket_schedule(leaves, 1)
        # one bucket requested, but fp16 and fp32 cannot share a concat
        assert s.n_buckets == 2
        assert s.buckets == ((2, 1), (0,))

    def test_min_bytes_merges_small_buckets(self):
        leaves = [np.zeros((64,), np.float32) for _ in range(8)]
        s = overlap.build_bucket_schedule(
            leaves, 8, min_bucket_bytes=512
        )
        assert s.n_buckets == 4
        assert all(b >= 512 for b in s.bucket_bytes)

    def test_float0_leaves_pass_through(self):
        leaves = [
            np.zeros((8,), np.float32),
            np.zeros((4,), jax.dtypes.float0),
        ]
        s = overlap.build_bucket_schedule(leaves, 2)
        assert s.passthrough == (1,)
        assert s.buckets == ((0,),)

    def test_schedule_cache_no_churn(self):
        overlap.reset_schedule_cache()
        rng = np.random.default_rng(0)
        t = _tree(rng, [(32,), (16,), (8, 4)])
        leaves, treedef = jax.tree_util.tree_flatten(t)
        for _ in range(5):
            overlap.schedule_for(leaves, treedef, 2)
        stats = overlap.schedule_cache_stats()
        assert stats["misses"] == 1
        assert stats["hits"] == 4


# --------------------------------------------- numeric parity (traced)


class TestParity:
    def test_sum_fp32_bitexact(self, hvd):
        mesh = hvd_mod.mesh()
        rng = np.random.default_rng(1)
        t = _tree(rng, [(33, 7), (129,), (64,), (5, 5, 5), (3,)])
        mono = _shmap(
            mesh,
            lambda p: jax.tree_util.tree_map(
                lambda g: traced.allreduce(g, op=hvd_mod.Sum), p
            ),
        )
        for n in (1, 2, 3, 5):
            buck = _shmap(
                mesh,
                lambda p, n=n: overlap.bucketed_allreduce(
                    p, op=hvd_mod.Sum, n_buckets=n,
                    min_bucket_bytes=0,
                ),
            )
            a, b = mono(t), buck(t)
            for k in t:
                assert (np.asarray(a[k]) == np.asarray(b[k])).all(), (
                    k,
                    n,
                )

    def test_average_parity(self, hvd):
        mesh = hvd_mod.mesh()
        rng = np.random.default_rng(2)
        t = _tree(rng, [(40,), (30,), (20,)])
        mono = _shmap(
            mesh,
            lambda p: jax.tree_util.tree_map(
                lambda g: traced.allreduce(g, op=hvd_mod.Average), p
            ),
        )
        buck = _shmap(
            mesh,
            lambda p: overlap.bucketed_allreduce(
                p, op=hvd_mod.Average, n_buckets=2, min_bucket_bytes=0
            ),
        )
        a, b = mono(t), buck(t)
        for k in t:
            np.testing.assert_allclose(
                np.asarray(a[k]), np.asarray(b[k]), rtol=1e-7
            )

    def test_bf16_wire_tolerance(self, hvd):
        mesh = hvd_mod.mesh()
        rng = np.random.default_rng(3)
        t = _tree(rng, [(50,), (60,)])
        buck = _shmap(
            mesh,
            lambda p: overlap.bucketed_allreduce(
                p,
                op=hvd_mod.Sum,
                n_buckets=2,
                compression=Compression.bf16,
                min_bucket_bytes=0,
            ),
        )
        out = buck(t)
        for k in t:
            exact = np.asarray(t[k]) * WORLD
            # one bf16 cast each way: ~2^-8 relative
            np.testing.assert_allclose(
                np.asarray(out[k]), exact, rtol=2e-2, atol=1e-2
            )

    def test_process_set_bitexact(self, hvd):
        ps = hvd.add_process_set([1, 3, 5])
        mesh = hvd_mod.mesh()
        t = {
            "a": jnp.arange(24.0, dtype=jnp.float32).reshape(4, 6),
            "b": jnp.arange(10.0, dtype=jnp.float32),
        }

        def body(p, x):
            # rank-dependent payload: rank r contributes p * (r + 1)
            r = (traced.rank() + 1).astype(jnp.float32)
            scaled = jax.tree_util.tree_map(lambda g: g * r, p)
            mono = jax.tree_util.tree_map(
                lambda g: traced.allreduce(
                    g, op=hvd_mod.Sum, process_set=ps
                ),
                scaled,
            )
            buck = overlap.bucketed_allreduce(
                scaled, op=hvd_mod.Sum, n_buckets=2, process_set=ps,
                min_bucket_bytes=0,
            )
            return mono, buck

        # out_specs with world axis needs a leading axis: wrap leaves
        run = _shmap(
            mesh,
            lambda p: jax.tree_util.tree_map(
                lambda x: x[None], body(p, None)
            ),
            in_specs=(P(),),
            out_specs=(
                P(hvd_mod.WORLD_AXIS),
                P(hvd_mod.WORLD_AXIS),
            ),
        )
        mono, buck = run(t)
        for k in t:
            assert (
                np.asarray(mono[k]) == np.asarray(buck[k])
            ).all(), k
        # members hold the member-sum, non-members their own input
        member_sum = {
            k: np.asarray(t[k]) * (2 + 4 + 6) for k in t
        }
        np.testing.assert_allclose(
            np.asarray(buck["a"])[3], member_sum["a"]
        )
        np.testing.assert_allclose(
            np.asarray(buck["a"])[0], np.asarray(t["a"]) * 1
        )

    def test_join_mask_parity(self, hvd):
        """The traced join mask: joined ranks drop out, Average divides
        by the live count — identical monolithic vs bucketed."""
        mesh = hvd_mod.mesh()
        mask = np.ones(WORLD, dtype=bool)
        mask[2] = False
        mask[5] = False
        t = {"a": jnp.ones((12,), jnp.float32), "b": jnp.ones((7,))}

        def body(p):
            r = (traced.rank() + 1).astype(jnp.float32)
            scaled = jax.tree_util.tree_map(lambda g: g * r, p)
            mono = jax.tree_util.tree_map(
                lambda g: traced.allreduce(
                    g, op=hvd_mod.Average, mask=mask
                ),
                scaled,
            )
            buck = overlap.bucketed_allreduce(
                scaled, op=hvd_mod.Average, n_buckets=2, mask=mask,
                min_bucket_bytes=0,
            )
            return jax.tree_util.tree_map(
                lambda x: x[None], (mono, buck)
            )

        mono, buck = _shmap(
            mesh,
            body,
            in_specs=(P(),),
            out_specs=(P(hvd_mod.WORLD_AXIS), P(hvd_mod.WORLD_AXIS)),
        )(t)
        live = [r + 1 for r in range(WORLD) if mask[r]]
        expected = np.mean(live)
        for k in t:
            assert (
                np.asarray(mono[k]) == np.asarray(buck[k])
            ).all(), k
            np.testing.assert_allclose(
                np.asarray(buck[k])[0],
                np.asarray(t[k]) * expected,
                rtol=1e-6,
            )


# ------------------------------------ compiled-program independence
# (shared parser: horovod_tpu.analysis — the per-file regex these
# tests used to carry lives there now, typed and rule-checked)


class TestCompiledIndependence:
    def test_n_buckets_n_collectives_no_serial_dep(self, hvd):
        """The lowered module for overlap_buckets=N holds exactly N
        all_reduce ops, and no all_reduce's operands transitively
        reach another all_reduce's result — there is no artificial
        serialization between buckets."""
        mesh = hvd_mod.mesh()
        rng = np.random.default_rng(4)
        t = _tree(rng, [(64,)] * 6)
        n = 3
        fn = _shmap(
            mesh,
            lambda p: overlap.bucketed_allreduce(
                p, op=hvd_mod.Sum, n_buckets=n, min_bucket_bytes=0
            ),
        )
        g = analysis.parse_module(fn.lower(t))
        analysis.expect(
            g,
            analysis.CollectiveCount("all_reduce", n),
            analysis.NoInterCollectiveDefUse("all_reduce"),
        )

    def test_in_backprop_boundary_emits_n_collectives(self, hvd):
        mesh = hvd_mod.mesh()
        rng = np.random.default_rng(5)
        params = _tree(rng, [(16, 16)] * 6)
        n = 3

        def loss(p, x):
            p = overlap.overlap_boundary(
                p, op=hvd_mod.Sum, n_buckets=n, min_bucket_bytes=0
            )
            h = x
            for k in sorted(p):
                h = jnp.tanh(h @ p[k])
            return jnp.sum(h * h)

        fn = _shmap(
            mesh,
            lambda p, x: jax.grad(loss)(p, x[0]),
            in_specs=(P(), P(hvd_mod.WORLD_AXIS)),
        )
        x = jnp.asarray(
            rng.normal(size=(WORLD, 4, 16)), jnp.float32
        )
        g = analysis.parse_module(fn.lower(params, x))
        analysis.expect(g, analysis.CollectiveCount("all_reduce", n))

    def test_no_retrace_and_one_schedule_across_steps(self, hvd):
        """Per-bucket-config compile happens once: 4 steps of the same
        jitted bucketed step trace once and build one schedule."""
        overlap.reset_schedule_cache()
        mesh = hvd_mod.mesh()
        rng = np.random.default_rng(6)
        t = _tree(rng, [(32,), (48,), (16,)])
        traces = {"n": 0}

        def body(p):
            traces["n"] += 1
            return overlap.bucketed_allreduce(
                p, op=hvd_mod.Sum, n_buckets=2, min_bucket_bytes=0
            )

        fn = _shmap(mesh, body)
        # placed as the step's in_specs say (chip_smoke.py does the
        # same): call 2 is fed call 1's mesh-sharded output, and an
        # unplaced first argument would be another signature
        out = jax.device_put(t, NamedSharding(mesh, P()))
        for _ in range(4):
            out = fn(out)
        assert traces["n"] == 1, "bucketed step retraced"
        stats = overlap.schedule_cache_stats()
        assert stats["misses"] == 1, stats


# ------------------------------------------------ quantized per bucket


def _quantum_bound_bucket(rows):
    """Two-stage quantum bound for one bucket buffer (the
    test_fusion_quantized bound, bucket edition)."""
    q1 = sum(np.abs(np.asarray(r)).max() for r in rows) / 127.0
    total = np.sum(np.stack(rows), axis=0)
    q2 = np.abs(total).max() / 127.0
    return q1 + q2


class TestQuantizedBuckets:
    def _run(self, hvd, fn, t, n_out=1):
        mesh = hvd_mod.mesh()
        out_specs = (
            P() if n_out == 1 else tuple(P() for _ in range(n_out))
        )
        return _shmap(mesh, fn, out_specs=out_specs)(t)

    def test_parity_vs_monolithic_quantized(self, hvd):
        """Bucketed int8_block lands within the summed quantum bounds
        of the PR-2 monolithic (per-leaf) quantized path."""
        rng = np.random.default_rng(7)
        sizes = [(700,), (260,), (300,)]
        t = _tree(rng, sizes)
        mono = self._run(
            hvd,
            lambda p: jax.tree_util.tree_map(
                lambda g: traced.quantized_allreduce(
                    g, op=hvd_mod.Sum, block_size=512
                ),
                p,
            ),
            t,
        )
        buck = self._run(
            hvd,
            lambda p: overlap.bucketed_allreduce(
                p,
                op=hvd_mod.Sum,
                n_buckets=2,
                compression=Compression.int8_block,
                seed=3,
                min_bucket_bytes=0,
            ),
            t,
        )
        # every rank contributes the same row here, so exact = 8x
        for k in t:
            exact = np.asarray(t[k]) * WORLD
            rows = [np.asarray(t[k]).ravel()] * WORLD
            bound = _quantum_bound_bucket(rows)
            # bucket buffers concat several leaves: the bucket bound is
            # conservative (absmax over the shared blocks); both paths
            # must sit within their bound, and within the sum of each
            # other's
            assert (
                np.abs(np.asarray(mono[k]).ravel() - exact.ravel()).max()
                <= bound * 3
            )
            assert (
                np.abs(np.asarray(buck[k]).ravel() - exact.ravel()).max()
                <= bound * 3
            )

    def test_ef_residual_sliced_per_bucket_bitexact(self, hvd):
        """EF residuals are SLICED from the bucket buffer, not
        recomputed per leaf: for each bucket, calling the monolithic
        `quantized_allreduce(return_residual=True)` on the hand-built
        concat of that bucket's members (same seed stride, same block
        size) reproduces the bucketed outputs AND residuals bit-for-bit
        after splitting."""
        rng = np.random.default_rng(8)
        t = _tree(rng, [(256,), (128,), (64,)])
        leaves, treedef = jax.tree_util.tree_flatten(t)
        sched = overlap.build_bucket_schedule(leaves, 2)
        seed = 11

        def bucketed(p):
            res0 = jax.tree_util.tree_map(jnp.zeros_like, p)
            return overlap.bucketed_allreduce(
                p,
                op=hvd_mod.Sum,
                n_buckets=2,
                compression=Compression.int8_block,
                residuals=res0,
                seed=seed,
                min_bucket_bytes=0,
            )

        out, res = self._run(hvd, bucketed, t, n_out=2)

        def oracle(p):
            lv = jax.tree_util.tree_flatten(p)[0]
            outs, ress = [], []
            for b, idxs in enumerate(sched.buckets):
                buf = jnp.concatenate(
                    [lv[i].reshape(-1) for i in idxs]
                )
                o, r = traced.quantized_allreduce(
                    buf,
                    op=hvd_mod.Sum,
                    seed=seed * sched.n_buckets + b,
                    return_residual=True,
                    block_size=Compression.int8_block.block_size,
                )
                outs.append(o)
                ress.append(r)
            return tuple(outs), tuple(ress)

        o_outs, o_ress = self._run(hvd, oracle, t, n_out=2)
        flat_keys = sorted(t)
        for b, idxs in enumerate(sched.buckets):
            off = 0
            for i in idxs:
                k = flat_keys[i]
                sz = np.asarray(t[k]).size
                np.testing.assert_array_equal(
                    np.asarray(out[k]),
                    np.asarray(o_outs[b])[off : off + sz],
                )
                np.testing.assert_array_equal(
                    np.asarray(res[k]),
                    np.asarray(o_ress[b])[off : off + sz],
                )
                off += sz

    def test_ef_converges_across_steps(self, hvd):
        """EF-SGD property through the BUCKETED wire: with a constant
        gradient, the running mean of reduced outputs converges to the
        exact sum (the carry keeps the quantizer honest)."""
        rng = np.random.default_rng(9)
        t = _tree(rng, [(200,), (100,)])
        mesh = hvd_mod.mesh()

        def step(p, r, s):
            return overlap.bucketed_allreduce(
                p,
                op=hvd_mod.Sum,
                n_buckets=2,
                compression=Compression.int8_block,
                residuals=r,
                seed=s,
                min_bucket_bytes=0,
            )

        fn = jax.jit(
            partial(
                jax.shard_map,
                mesh=mesh,
                in_specs=(P(), P(), P()),
                out_specs=(P(), P()),
                check_vma=False,
            )(step),
            static_argnums=(),
        )
        res = jax.tree_util.tree_map(jnp.zeros_like, t)
        acc = {k: 0.0 for k in t}
        steps = 12
        for s in range(steps):
            out, res = fn(t, res, jnp.asarray(s))
            for k in t:
                acc[k] = acc[k] + np.asarray(out[k])
        for k in t:
            exact = np.asarray(t[k]) * WORLD
            mean_err = np.abs(acc[k] / steps - exact).max()
            one_shot = np.abs(np.asarray(out[k]) - exact).max()
            assert mean_err <= max(one_shot, 1e-6) * 1.05, (
                k,
                mean_err,
                one_shot,
            )

    def test_prescale_fold_parity(self, hvd):
        """The prescale fold survives bucketing: folded prescale ==
        two-pass (pre-multiplied tensor) bit-exactly for positive
        factors, per bucket."""
        rng = np.random.default_rng(10)
        t = _tree(rng, [(300,), (212,)])
        f = 0.37
        folded = self._run(
            hvd,
            lambda p: overlap.bucketed_allreduce(
                p,
                op=hvd_mod.Sum,
                n_buckets=2,
                compression=Compression.int8_block,
                prescale_factor=f,
                seed=5,
                min_bucket_bytes=0,
            ),
            t,
        )
        twopass = self._run(
            hvd,
            lambda p: overlap.bucketed_allreduce(
                jax.tree_util.tree_map(lambda g: g * f, p),
                op=hvd_mod.Sum,
                n_buckets=2,
                compression=Compression.int8_block,
                seed=5,
                min_bucket_bytes=0,
            ),
            t,
        )
        for k in t:
            np.testing.assert_allclose(
                np.asarray(folded[k]),
                np.asarray(twopass[k]),
                rtol=1e-6,
                atol=1e-7,
            )

    def test_block_granularity_honored(self, hvd):
        """A custom block_size (Compression.int8_block.with_block_size)
        reaches the bucket wire: an outlier leaf sharing a bucket with
        a small-magnitude leaf must not destroy the latter's precision
        when blocks are fine enough to separate them."""
        fine = Compression.int8_block.with_block_size(128)
        small = np.full(512, 1e-3, np.float32)
        outlier = np.full(512, 1e3, np.float32)
        t = {
            "small": jnp.asarray(small),
            "outlier": jnp.asarray(outlier),
        }
        out = self._run(
            hvd,
            lambda p: overlap.bucketed_allreduce(
                p, op=hvd_mod.Sum, n_buckets=1, compression=fine,
                seed=2, min_bucket_bytes=0,
            ),
            t,
        )
        exact_small = small * WORLD
        # fine blocks: the small leaf's blocks own their scales, so its
        # relative error stays at the int8 quantum, not the outlier's
        err = np.abs(np.asarray(out["small"]) - exact_small).max()
        assert err <= (1e-3 * WORLD) / 127.0 * 3 + (1e-3 / 127.0) * 8


# --------------------------------------- end-to-end optimizer parity


class TestOptimizerIntegration:
    def _problem(self, rng):
        params = _tree(rng, [(24, 8), (8,), (8, 8), (8,)])
        x = jnp.asarray(
            rng.normal(size=(WORLD, 6, 24)), jnp.float32
        )
        y = jnp.asarray(rng.normal(size=(WORLD, 6, 8)), jnp.float32)
        return params, x, y

    @staticmethod
    def _loss(p, xb, yb):
        h = jnp.tanh(xb @ p["p00"] + p["p01"])
        h = h @ p["p02"] + p["p03"]
        return jnp.mean((h - yb) ** 2)

    def _make_step(self, opt, vg):
        mesh = hvd_mod.mesh()

        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(P(), P(), P(hvd_mod.WORLD_AXIS),
                      P(hvd_mod.WORLD_AXIS)),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
        def step(p, st, xb, yb):
            loss, g = vg(p, xb[0], yb[0])
            u, st = opt.update(g, st, p)
            return optax.apply_updates(p, u), st, jax.lax.pmean(
                loss, hvd_mod.WORLD_AXIS
            )

        return jax.jit(step)

    def test_distributed_optimizer_overlap_bitexact(self, hvd):
        """DistributedOptimizer(overlap_buckets=N) hands its inner
        optimizer the monolithic exchange's reduced gradients bit for
        bit (op=Sum, fp32): the docstring's claim. The Adam trajectory
        after them is held to a few ulp, not to the bit: the compiler
        fuses the update into two different graphs (where the reduced
        gradients are an output of the step, the parameters are equal
        too)."""
        rng = np.random.default_rng(11)
        params, x, y = self._problem(rng)
        vg = jax.value_and_grad(self._loss)

        def pair(inner):
            return (
                hvd_mod.DistributedOptimizer(inner, op=hvd_mod.Sum),
                hvd_mod.DistributedOptimizer(
                    inner, op=hvd_mod.Sum, overlap_buckets=2,
                    overlap_min_bytes=0,
                ),
            )

        # identity inner: the update IS the reduced gradient
        def reduced(opt):
            @partial(
                jax.shard_map,
                mesh=hvd_mod.mesh(),
                in_specs=(P(), P(), P(hvd_mod.WORLD_AXIS),
                          P(hvd_mod.WORLD_AXIS)),
                out_specs=P(),
                check_vma=False,
            )
            def exchange(p, st, xb, yb):
                _, g = vg(p, xb[0], yb[0])
                return opt.update(g, st, p)[0]

            return jax.jit(exchange)(params, opt.init(params), x, y)

        r1, r2 = (reduced(o) for o in pair(optax.identity()))
        for k in params:
            np.testing.assert_array_equal(
                np.asarray(r1[k]), np.asarray(r2[k]), err_msg=k
            )

        o1, o2 = pair(optax.adam(1e-2))
        s1, s2 = o1.init(params), o2.init(params)
        st1, st2 = self._make_step(o1, vg), self._make_step(o2, vg)
        p1 = p2 = params
        for _ in range(3):
            p1, s1, l1 = st1(p1, s1, x, y)
            p2, s2, l2 = st2(p2, s2, x, y)
        for k in params:
            np.testing.assert_allclose(
                np.asarray(p1[k]), np.asarray(p2[k]),
                rtol=1e-6, atol=1e-7, err_msg=k,
            )
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)

    def test_value_and_grad_in_backprop_parity(self, hvd):
        """hvd.value_and_grad(overlap_buckets=N) — the custom_vjp
        boundary — returns the same reduced gradients as the post-hoc
        exchange (within float tolerance; the exchange runs at a
        different point of the backward)."""
        rng = np.random.default_rng(12)
        params, x, y = self._problem(rng)
        vg_mono = hvd_mod.value_and_grad(self._loss, op=hvd_mod.Sum)
        vg_over = hvd_mod.value_and_grad(
            self._loss, op=hvd_mod.Sum, overlap_buckets=2,
            overlap_min_bytes=0,
        )
        mesh = hvd_mod.mesh()

        def run(vg):
            return _shmap(
                mesh,
                lambda p, xb, yb: vg(p, xb[0], yb[0]),
                in_specs=(P(), P(hvd_mod.WORLD_AXIS),
                          P(hvd_mod.WORLD_AXIS)),
                out_specs=(P(), P()),
            )(params, x, y)

        (l1, g1), (l2, g2) = run(vg_mono), run(vg_over)
        assert float(l1) == float(l2)
        for k in params:
            np.testing.assert_allclose(
                np.asarray(g1[k]), np.asarray(g2[k]),
                rtol=1e-6, atol=1e-7,
            )

    def test_value_and_grad_overlap_rejects_tuple_argnums(self, hvd):
        with pytest.raises(ValueError, match="argnums"):
            hvd_mod.value_and_grad(
                self._loss, argnums=(0, 1), overlap_buckets=2
            )

    def test_overlap_rejects_adasum(self, hvd):
        with pytest.raises(ValueError, match="Adasum"):
            hvd_mod.DistributedOptimizer(
                optax.sgd(1e-2), op=hvd_mod.Adasum, overlap_buckets=2
            )

    def test_env_default_falls_back_for_unsupported_ops(
        self, hvd, monkeypatch
    ):
        """HOROVOD_OVERLAP=1 is a fleet-wide default: a job whose op
        the bucketed layer can't carry (Min/Max/Adasum) silently keeps
        the monolithic path — only an EXPLICIT overlap_buckets= with a
        bad op is a construction error."""
        monkeypatch.setenv("HOROVOD_OVERLAP", "1")
        monkeypatch.setenv("HOROVOD_OVERLAP_BUCKETS", "4")
        # constructs fine (falls back), and the wrapped update traces
        # through the monolithic per-leaf path
        opt = hvd_mod.DistributedOptimizer(
            optax.sgd(1e-2), op=hvd_mod.Min
        )
        rng = np.random.default_rng(20)
        params = _tree(rng, [(8,), (4,)])
        mesh = hvd_mod.mesh()
        st = opt.init(params)
        upd = _shmap(
            mesh,
            lambda p: opt.update(p, st, p)[0],
        )(params)
        for k in params:
            assert np.isfinite(np.asarray(upd[k])).all()
        # the tape API falls back the same way
        hvd_mod.value_and_grad(self._loss, op=hvd_mod.Min)
        # explicit request still raises loudly
        with pytest.raises(ValueError, match="Sum/Average"):
            hvd_mod.DistributedOptimizer(
                optax.sgd(1e-2), op=hvd_mod.Min, overlap_buckets=4
            )
        with pytest.raises(ValueError, match="Sum/Average"):
            hvd_mod.value_and_grad(
                self._loss, op=hvd_mod.Min, overlap_buckets=4
            )

    def test_sharded_optimizer_bucketed_bitexact_and_hlo(self, hvd):
        """ZeRO-1 with overlap_buckets: bit-exact trajectory vs the
        per-leaf exchange, and the lowered step carries N independent
        reduce_scatter + N all_gather ops."""
        rng = np.random.default_rng(13)
        params, x, y = self._problem(rng)
        o1 = hvd_mod.ShardedDistributedOptimizer(optax.adam(1e-2))
        o2 = hvd_mod.ShardedDistributedOptimizer(
            optax.adam(1e-2), overlap_buckets=2, overlap_min_bytes=0
        )
        mesh = hvd_mod.mesh()

        def make(opt):
            @partial(
                jax.shard_map,
                mesh=mesh,
                in_specs=(P(), opt.state_spec(),
                          P(hvd_mod.WORLD_AXIS),
                          P(hvd_mod.WORLD_AXIS)),
                out_specs=(P(), opt.state_spec(), P()),
                check_vma=False,
            )
            def step(p, st, xb, yb):
                loss, g = jax.value_and_grad(self._loss)(
                    p, xb[0], yb[0]
                )
                u, st = opt.update(g, st, p)
                return optax.apply_updates(p, u), st, jax.lax.pmean(
                    loss, hvd_mod.WORLD_AXIS
                )

            return jax.jit(step)

        s1, s2 = o1.init(params), o2.init(params)
        st1, st2 = make(o1), make(o2)
        g = analysis.parse_module(st2.lower(params, s2, x, y))
        analysis.expect(
            g,
            analysis.CollectiveCount("reduce_scatter", 2),
            analysis.CollectiveCount("all_gather", 2),
            analysis.NoInterCollectiveDefUse("reduce_scatter"),
        )
        p1, p2 = params, params
        for _ in range(3):
            p1, s1, l1 = st1(p1, s1, x, y)
            p2, s2, l2 = st2(p2, s2, x, y)
        for k in params:
            assert (np.asarray(p1[k]) == np.asarray(p2[k])).all(), k


# ------------------------------------------------- tuner + config


class TestOverlapTuner:
    def test_explore_then_exploit(self):
        from horovod_tpu.common.autotune import OverlapTuner

        t = OverlapTuner(min_bucket_bytes=0, trials=2)
        key = "step"
        total = 1 << 22
        seen = []
        # feed synthetic observations: n=4 has the best goodput
        for _ in range(2 * len(t.candidates) + 4):
            n = t.choose(key, total)
            seen.append(n)
            secs = {1: 1.0, 2: 0.8, 4: 0.5, 8: 0.7, 16: 0.9}[n]
            t.record(key, n, total, secs)
        # exploration visited every candidate `trials` times...
        for c in t.candidates:
            assert seen.count(c) >= 2 or seen[-1] == 4
        # ...then settled on the argmax
        assert seen[-1] == 4
        assert t.choose(key, total) == 4

    def test_min_bytes_floor_prunes_candidates(self):
        from horovod_tpu.common.autotune import OverlapTuner

        t = OverlapTuner(min_bucket_bytes=1 << 20, trials=1)
        # 2 MiB total: 4/8/16 buckets would be under the 1 MiB floor
        assert t.viable(2 << 20) == (1, 2)
        # tiny totals leave only the monolithic schedule — chosen
        # without any trial bookkeeping
        assert t.choose("k", 1 << 10) == 1

    def test_config_env(self, monkeypatch):
        from horovod_tpu.common.config import Config

        monkeypatch.setenv("HOROVOD_OVERLAP", "1")
        monkeypatch.setenv("HOROVOD_OVERLAP_BUCKETS", "7")
        monkeypatch.setenv("HOROVOD_OVERLAP_MIN_BYTES", "4096")
        cfg = Config.from_env()
        assert cfg.overlap is True
        assert cfg.overlap_buckets == 7
        assert cfg.overlap_min_bytes == 4096
        assert overlap.default_buckets() in (7, 0)  # init state free

    def test_default_buckets_off_by_default(self, monkeypatch):
        monkeypatch.delenv("HOROVOD_OVERLAP", raising=False)
        assert overlap.default_buckets() == 0


# ----------------------------------------- metrics + timeline estimate


class TestObservability:
    def test_schedule_publishes_metrics(self, hvd):
        from horovod_tpu.common.metrics import registry

        registry.reset()
        mesh = hvd_mod.mesh()
        rng = np.random.default_rng(14)
        t = _tree(rng, [(64,), (32,), (16,)])
        _shmap(
            mesh,
            lambda p: overlap.bucketed_allreduce(
                p, op=hvd_mod.Sum, n_buckets=2, min_bucket_bytes=0
            ),
        )(t)
        snap = registry.snapshot()
        assert snap["overlap.buckets"] == 2
        assert snap["overlap.bucket_bytes_total"] == (64 + 32 + 16) * 4
        assert snap["overlap.bucket_bytes_max"] >= snap[
            "overlap.bucket_bytes_min"
        ]

    def test_collective_overlap_stats_synthetic(self):
        """Exposed vs hidden on a hand-built trace: a 100us collective
        with 60us of concurrent compute on the same device pid is 60
        hidden / 40 exposed; a second, fully-exposed collective adds
        its whole duration to exposed."""
        from horovod_tpu.common.traced_timeline import (
            collective_overlap_stats,
        )

        events = [
            # device pid 7: collective [0, 100)
            {"ph": "X", "pid": 7, "tid": 1, "ts": 0, "dur": 100,
             "name": "all-reduce.1"},
            # concurrent compute [20, 80) on another row of pid 7
            {"ph": "X", "pid": 7, "tid": 2, "ts": 20, "dur": 60,
             "name": "fusion.42"},
            # fully exposed collective [200, 250)
            {"ph": "X", "pid": 7, "tid": 1, "ts": 200, "dur": 50,
             "name": "all-gather.3"},
            # async start half must be ignored
            {"ph": "X", "pid": 7, "tid": 1, "ts": 300, "dur": 10,
             "name": "all-reduce-start.9"},
        ]
        s = collective_overlap_stats(events)
        assert s["spans"] == 2
        assert s["collective_us"] == 150
        assert s["hidden_us"] == 60
        assert s["exposed_us"] == 90

    def test_container_rows_do_not_count_as_hiding_compute(self):
        """Profiler annotation rows ('Steps', 'XLA Modules', name
        scopes) span the whole step on the device pid; counting them
        as compute would report every collective 100% hidden for any
        schedule. They are excluded via thread_name metadata; real op
        rows still hide."""
        from horovod_tpu.common.traced_timeline import (
            collective_overlap_stats,
        )

        events = [
            {"ph": "M", "pid": 7, "tid": 9, "name": "thread_name",
             "args": {"name": "Steps"}},
            {"ph": "M", "pid": 7, "tid": 8, "name": "thread_name",
             "args": {"name": "XLA Modules"}},
            {"ph": "M", "pid": 7, "tid": 2, "name": "thread_name",
             "args": {"name": "XLA Ops"}},
            # whole-step container spans blanket the timeline
            {"ph": "X", "pid": 7, "tid": 9, "ts": 0, "dur": 1000,
             "name": "train 0"},
            {"ph": "X", "pid": 7, "tid": 8, "ts": 0, "dur": 1000,
             "name": "jit_step"},
            # the collective, with 30us of REAL op compute concurrent
            {"ph": "X", "pid": 7, "tid": 2, "ts": 100, "dur": 100,
             "name": "all-reduce.5"},
            {"ph": "X", "pid": 7, "tid": 2, "ts": 150, "dur": 30,
             "name": "fusion.9"},
        ]
        s = collective_overlap_stats(events)
        assert s["spans"] == 1
        assert s["collective_us"] == 100
        assert s["hidden_us"] == 30  # only the real op row hides
        assert s["exposed_us"] == 70

    def test_traced_timeline_exports_overlap_counters(self, hvd,
                                                      tmp_path):
        """The chrome-trace export computes the exposed/hidden split,
        publishes overlap.* metrics, and appends counter events."""
        import gzip
        import json as _json
        import os

        from horovod_tpu.common.metrics import registry
        from horovod_tpu.common.traced_timeline import TracedTimeline

        registry.reset()
        tl = TracedTimeline(str(tmp_path / "tl.json"))
        # fabricate a profiler output instead of running one: the
        # export path only reads the trace.json.gz files
        d = os.path.join(
            tl.logdir, "plugins", "profile", "run1"
        )
        os.makedirs(d)
        trace = {
            "traceEvents": [
                {"ph": "X", "pid": 3, "tid": 1, "ts": 0, "dur": 100,
                 "name": "all-reduce.7"},
                {"ph": "X", "pid": 3, "tid": 2, "ts": 50, "dur": 100,
                 "name": "fusion.1"},
            ]
        }
        with gzip.open(
            os.path.join(d, "host.trace.json.gz"), "wt"
        ) as f:
            _json.dump(trace, f)
        tl._export_chrome_trace()
        snap = registry.snapshot()
        assert snap["overlap.collective_ms"] == pytest.approx(0.1)
        assert snap["overlap.hidden_collective_ms"] == pytest.approx(
            0.05
        )
        assert snap["overlap.exposed_collective_ms"] == pytest.approx(
            0.05
        )
        out = _json.load(open(tmp_path / "tl.json"))
        names = [e.get("name") for e in out["traceEvents"]]
        assert "hvd.exposed_collective_ms" in names
        assert "hvd.hidden_collective_ms" in names
