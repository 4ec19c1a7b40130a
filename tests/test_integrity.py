"""Training-state integrity plane (ISSUE 7): non-finite guards,
cross-rank parameter audit, exactly-once elastic resume.

Acceptance surface:

* GradGuard skip-step semantics in both optimizers — a NaN/Inf in the
  reduced gradients skips the update (zero updates, optimizer state
  and EF residuals untouched), counts ``guard.nonfinite_steps``, and
  after K consecutive skips latches an escalation that
  ``State.commit()`` raises as ``HorovodInternalError``.
* Guard overhead: the lowered guarded step carries the SAME collective
  count as the unguarded one (the flag is a scalar reduction over
  already-replicated values) and the no-skip path never reaches the
  host (zero callback fires across a finite run).
* ``hvd.audit`` digests + ``find_divergent`` majority logic + the
  driver's divergence quarantine/restart.
* Checkpoint content digests: corrupt-but-parseable checkpoints fall
  back; ``restore(like=)`` structure mismatches raise a clear
  ``CheckpointStructureError`` with the tree-path diff.
* Sampler/dataset cursors: reshard-deterministic global order,
  mid-epoch exactly-once resume across a save/SIGKILL/restore cycle
  including an 8→6 world change, bit-identical post-resume
  trajectories across repeated runs.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import horovod_tpu as hvd_mod  # noqa: E402
from horovod_tpu import analysis  # noqa: E402
from horovod_tpu.common import guard as guard_mod  # noqa: E402
from jax import shard_map  # noqa: E402
from horovod_tpu.common.metrics import registry  # noqa: E402


def _delta(name, before):
    return registry.snapshot().get(name, 0.0) - before.get(name, 0.0)


@pytest.fixture(autouse=True)
def _fresh_guard():
    guard_mod._reset_guard()
    yield
    guard_mod._reset_guard()


def _jit_step(hvd, opt, mesh, lr_step=True):
    """One jitted data-parallel step: rank-major grads in, updated
    params + state out (the repo's standard shard_map harness)."""

    @jax.jit
    def step(grads, state, params):
        def body(g, s, p):
            g = jax.tree_util.tree_map(lambda x: x[0], g)
            u, s2 = opt.update(g, s, p)
            if lr_step:
                p = jax.tree_util.tree_map(lambda a, b: a + b, p, u)
                return p, s2, u
            return p, s2, u

        return shard_map(
            body, mesh=mesh,
            in_specs=(P(hvd.WORLD_AXIS), P(), P()),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )(grads, state, params)

    return step


def _grads(world, n=16, bad=False, val=1.0):
    g = {"w": jnp.full((world, n), val, jnp.float32),
         "b": jnp.full((world, 4), val, jnp.float32)}
    if bad:
        g = {"w": g["w"].at[0, 0].set(jnp.nan), "b": g["b"]}
    return g


# ------------------------------------------------------------ grad guard


class TestGradGuard:
    @pytest.mark.parametrize("buckets", [0, 2])
    def test_skip_step_semantics(self, hvd, buckets):
        """A NaN step: zero updates, inner state untouched, step
        counter advanced, one skip counted (per-shard callbacks
        deduped), streak reset by the next good step."""
        world = hvd.size()
        opt = hvd_mod.DistributedOptimizer(
            optax.sgd(0.1), op=hvd_mod.Sum, grad_guard=True,
            overlap_buckets=buckets,
        )
        params = {"w": jnp.ones((16,)), "b": jnp.zeros((4,))}
        state = opt.init(params)
        step = _jit_step(hvd, opt, hvd.mesh())
        before = registry.snapshot()

        params, state, u = step(_grads(world), state, params)
        assert int(state.guard_skips) == 0
        p_good = jax.device_get(params)

        params, state, u = step(_grads(world, bad=True), state, params)
        jax.block_until_ready(u)
        assert int(state.guard_skips) == 1
        assert int(state.guard_streak) == 1
        assert int(state.step) == 2  # the step counter still advances
        assert float(jnp.abs(u["w"]).max()) == 0.0
        assert float(jnp.abs(u["b"]).max()) == 0.0
        # params unchanged by the skipped step
        np.testing.assert_array_equal(
            np.asarray(params["w"]), np.asarray(p_good["w"])
        )
        assert _delta("guard.nonfinite_steps", before) == 1  # deduped

        params, state, u = step(_grads(world), state, params)
        assert int(state.guard_streak) == 0  # good step resets
        assert int(state.guard_skips) == 1

    def test_trajectory_matches_unguarded_without_nan(self, hvd):
        """Finite gradients: the guarded optimizer matches the
        unguarded one to float tolerance. (Not bit-exact BY PROGRAM:
        the guard's lax.cond changes XLA's fusion choices, which can
        move a last-ulp rounding — the guard itself only ever reads.)"""
        world = hvd.size()
        mesh = hvd.mesh()
        params = {"w": jnp.linspace(0, 1, 16), "b": jnp.zeros((4,))}
        outs = []
        for g_on in (False, True):
            opt = hvd_mod.DistributedOptimizer(
                optax.adam(1e-2), op=hvd_mod.Sum, grad_guard=g_on,
                overlap_buckets=2,
            )
            p, state = dict(params), opt.init(params)
            step = _jit_step(hvd, opt, mesh)
            for i in range(3):
                p, state, _ = step(_grads(world, val=0.5 + i), state, p)
            outs.append(jax.device_get(p))
        np.testing.assert_allclose(
            outs[0]["w"], outs[1]["w"], rtol=1e-6, atol=1e-7
        )
        np.testing.assert_allclose(
            outs[0]["b"], outs[1]["b"], rtol=1e-6, atol=1e-7
        )

    def test_escalation_latches_and_commit_raises(self, hvd):
        from horovod_tpu.elastic.state import JaxState

        world = hvd.size()
        opt = hvd_mod.DistributedOptimizer(
            optax.sgd(0.1), op=hvd_mod.Sum, grad_guard=True,
            guard_max_skips=2,
        )
        params = {"w": jnp.ones((16,)), "b": jnp.zeros((4,))}
        state = opt.init(params)
        step = _jit_step(hvd, opt, hvd.mesh())
        for _ in range(2):
            params, state, u = step(
                _grads(world, bad=True), state, params
            )
            jax.block_until_ready(u)
        assert guard_mod.status()["escalated"]
        est = JaxState(params=params, batch=0)
        with pytest.raises(hvd_mod.HorovodInternalError):
            est.commit()
        # the raise cleared the latch; the next commit is clean
        est.commit()
        assert not guard_mod.status()["escalated"]

    def test_error_feedback_residual_kept_on_skip(self, hvd):
        """EF carry stays at the LAST APPLIED step's residual across a
        skipped step — the carry must describe what was actually
        transmitted."""
        world = hvd.size()
        opt = hvd_mod.DistributedOptimizer(
            optax.sgd(0.1), op=hvd_mod.Average,
            compression=hvd_mod.Compression.int8,
            error_feedback=True, grad_guard=True,
        )
        params = {"w": jnp.linspace(-1, 1, 64), "b": jnp.zeros((4,))}
        state = opt.init(params)
        step = _jit_step(hvd, opt, hvd.mesh())
        params, state, _ = step(_grads(world, n=64, val=0.37), state, params)
        res_good = jax.device_get(state.residual)
        assert float(np.abs(res_good["w"]).max()) > 0  # int8 did quantize
        params, state, u = step(
            _grads(world, n=64, bad=True), state, params
        )
        jax.block_until_ready(u)
        assert int(state.guard_skips) == 1
        res_after = jax.device_get(state.residual)
        np.testing.assert_array_equal(res_good["w"], res_after["w"])
        np.testing.assert_array_equal(res_good["b"], res_after["b"])

    def test_accumulation_boundary_skip_discards_window(self, hvd):
        """backward_passes_per_step=2: a NaN micro-batch poisons the
        boundary step — skipped, and the accumulator is cleared (the
        window is discarded, not replayed)."""
        world = hvd.size()
        opt = hvd_mod.DistributedOptimizer(
            optax.sgd(0.1), op=hvd_mod.Sum, grad_guard=True,
            backward_passes_per_step=2,
        )
        params = {"w": jnp.ones((16,)), "b": jnp.zeros((4,))}
        state = opt.init(params)
        step = _jit_step(hvd, opt, hvd.mesh())
        params, state, u = step(_grads(world, bad=True), state, params)
        assert int(state.guard_skips) == 0  # off-boundary: no event
        params, state, u = step(_grads(world), state, params)
        jax.block_until_ready(u)
        assert int(state.guard_skips) == 1  # boundary judged the window
        assert float(jnp.abs(u["w"]).max()) == 0.0
        acc = jax.device_get(state.accum)
        assert float(np.abs(acc["w"]).max()) == 0.0  # window discarded

    def test_guard_off_keeps_state_structure(self, hvd):
        opt = hvd_mod.DistributedOptimizer(
            optax.sgd(0.1), op=hvd_mod.Sum, grad_guard=False
        )
        state = opt.init({"w": jnp.ones((4,))})
        assert state.guard_skips is None
        assert state.guard_streak is None
        # None leaves are empty subtrees: unguarded checkpoints keep
        # their exact leaf list
        leaves = jax.tree_util.tree_leaves(state)
        opt0 = hvd_mod.DistributedOptimizer(optax.sgd(0.1), op=hvd_mod.Sum)
        assert len(leaves) == len(
            jax.tree_util.tree_leaves(opt0.init({"w": jnp.ones((4,))}))
        )


class TestGuardOverhead:
    """Acceptance: one fused scalar reduction per bucket — no extra
    collectives, no host sync on the no-skip path."""

    def _lowered_text(self, hvd, grad_guard):
        opt = hvd_mod.DistributedOptimizer(
            optax.sgd(0.1), op=hvd_mod.Sum, grad_guard=grad_guard,
            overlap_buckets=3, overlap_min_bytes=0,
        )
        # three SAME-SIZE leaves so the balanced partition closes one
        # bucket per leaf (a lopsided tree would merge the small ones)
        params = {
            "a": jnp.ones((32, 8)), "b": jnp.ones((32, 8)),
            "c": jnp.ones((32, 8)),
        }
        state = opt.init(params)
        world = hvd.size()
        grads = {
            k: jnp.ones((world,) + tuple(np.shape(v)))
            for k, v in params.items()
        }

        def step(g, s, p):
            def body(g, s, p):
                g = jax.tree_util.tree_map(lambda x: x[0], g)
                return opt.update(g, s, p)

            return shard_map(
                body, mesh=hvd.mesh(),
                in_specs=(P(hvd_mod.WORLD_AXIS), P(), P()),
                out_specs=(P(), P()),
                check_vma=False,
            )(g, s, p)

        return (
            analysis.parse_module(jax.jit(step).lower(grads, state, params)),
            opt, state, grads, params,
        )

    def test_no_additional_collectives(self, hvd):
        g_off, *_ = self._lowered_text(hvd, grad_guard=False)
        g_on, *_ = self._lowered_text(hvd, grad_guard=True)
        analysis.expect(
            g_off, analysis.CollectiveCount("all_reduce", 3)
        )  # one per bucket
        # the guard flag adds NO collective of ANY kind
        analysis.expect(
            g_on, analysis.GuardOverhead(g_off, extra_scalar_allreduces=0)
        )

    def test_no_host_sync_on_no_skip_path(self, hvd):
        """Run many finite steps under jit: the guard callback must
        never fire (it lives inside the skip branch only)."""
        _, opt, state, grads, params = self._lowered_text(
            hvd, grad_guard=True
        )

        @jax.jit
        def step(g, s, p):
            def body(g, s, p):
                g = jax.tree_util.tree_map(lambda x: x[0], g)
                return opt.update(g, s, p)

            return shard_map(
                body, mesh=hvd_mod.mesh(),
                in_specs=(P(hvd_mod.WORLD_AXIS), P(), P()),
                out_specs=(P(), P()),
                check_vma=False,
            )(g, s, p)

        before = registry.snapshot()
        for _ in range(10):
            u, state = step(grads, state, params)
        jax.block_until_ready(u)
        assert guard_mod.status()["nonfinite_steps"] == 0
        assert _delta("guard.nonfinite_steps", before) == 0


class TestShardedGuard:
    def test_skip_and_counters(self, hvd):
        world = hvd.size()
        opt = hvd_mod.ShardedDistributedOptimizer(
            optax.adam(1e-2), op=hvd_mod.Average, grad_guard=True,
            guard_max_skips=0,
        )
        params = {"w": jnp.linspace(0, 1, 32), "b": jnp.zeros((4,))}
        state = opt.init(params)
        assert set(state) == {"state", "guard"}

        @jax.jit
        def step(g, s, p):
            def body(g, s, p):
                g = jax.tree_util.tree_map(lambda x: x[0], g)
                return opt.update(g, s, p)

            return shard_map(
                body, mesh=hvd.mesh(),
                in_specs=(P(hvd.WORLD_AXIS), opt.state_spec(), P()),
                out_specs=(P(), opt.state_spec()),
                check_vma=False,
            )(g, s, p)

        u, state = step(_grads(world, n=32), state, params)
        mu_good = np.asarray(
            jax.tree_util.tree_leaves(state["state"])[1]
        ).copy()
        u, state = step(_grads(world, n=32, bad=True), state, params)
        jax.block_until_ready(u)
        assert np.asarray(state["guard"]["skips"]).max() == 1
        assert float(jnp.abs(u["w"]).max()) == 0.0
        # optimizer moments untouched by the skipped step
        mu_after = np.asarray(
            jax.tree_util.tree_leaves(state["state"])[1]
        )
        np.testing.assert_array_equal(mu_good, mu_after)
        assert guard_mod.status()["nonfinite_steps"] == 1

    def test_one_extra_scalar_collective_only(self, hvd):
        """The sharded flag costs exactly ONE extra all_reduce (the
        4-byte agreement psum) — shards diverge, so it cannot be
        free — and nothing else."""
        world = hvd.size()
        params = {"w": jnp.ones((32,)), "b": jnp.zeros((4,))}
        texts = {}
        for g_on in (False, True):
            opt = hvd_mod.ShardedDistributedOptimizer(
                optax.sgd(0.1), op=hvd_mod.Average, grad_guard=g_on
            )
            state = opt.init(params)
            grads = {
                k: jnp.ones((world,) + tuple(np.shape(v)))
                for k, v in params.items()
            }

            def step(g, s, p):
                def body(g, s, p):
                    g = jax.tree_util.tree_map(lambda x: x[0], g)
                    return opt.update(g, s, p)

                return shard_map(
                    body, mesh=hvd.mesh(),
                    in_specs=(P(hvd_mod.WORLD_AXIS), opt.state_spec(), P()),
                    out_specs=(P(), opt.state_spec()),
                    check_vma=False,
                )(g, s, p)

            texts[g_on] = analysis.parse_module(
                jax.jit(step).lower(grads, state, params)
            )
        # exactly one extra all_reduce, and it is SCALAR (the 4-byte
        # agreement flag) — GuardOverhead checks both
        analysis.expect(
            texts[True],
            analysis.GuardOverhead(texts[False], extra_scalar_allreduces=1),
        )

    def test_layout_migration_both_directions(self, hvd):
        """Flat state under a newly-enabled guard and guarded state
        under a disabled guard both get a clear error at update() and
        a working migration through reshard_state()."""
        params = {"w": jnp.linspace(0, 1, 32)}
        opt_off = hvd_mod.ShardedDistributedOptimizer(
            optax.adam(1e-2), op=hvd_mod.Average, grad_guard=False
        )
        opt_on = hvd_mod.ShardedDistributedOptimizer(
            optax.adam(1e-2), op=hvd_mod.Average, grad_guard=True
        )
        flat = opt_off.init(params)
        guarded = opt_on.init(params)
        with pytest.raises(ValueError, match="flat"):
            opt_on.update({"w": jnp.ones(32)}, flat, params)
        with pytest.raises(ValueError, match="guard counters"):
            opt_off.update({"w": jnp.ones(32)}, guarded, params)
        up = opt_on.reshard_state(flat, params, 8)
        assert set(up) == {"state", "guard"}
        assert np.asarray(up["guard"]["skips"]).shape == (8,)
        down = opt_off.reshard_state(guarded, params, 8)
        assert not isinstance(down, dict) or "guard" not in down

    def test_reshard_carries_guard_counters(self, hvd):
        world = hvd.size()
        opt = hvd_mod.ShardedDistributedOptimizer(
            optax.adam(1e-2), op=hvd_mod.Average, grad_guard=True
        )
        params = {"w": jnp.linspace(0, 1, 32)}
        state = opt.init(params)

        @jax.jit
        def step(g, s, p):
            def body(g, s, p):
                g = jax.tree_util.tree_map(lambda x: x[0], g)
                return opt.update(g, s, p)

            return shard_map(
                body, mesh=hvd.mesh(),
                in_specs=(P(hvd.WORLD_AXIS), opt.state_spec(), P()),
                out_specs=(P(), opt.state_spec()),
                check_vma=False,
            )(g, s, p)

        g = {"w": jnp.ones((world, 32))}
        _, state = step(g, state, params)
        _, state = step({"w": g["w"].at[0, 0].set(jnp.inf)}, state, params)
        state6 = opt.reshard_state(state, params, 6)
        assert np.asarray(state6["guard"]["skips"]).shape == (6,)
        assert np.asarray(state6["guard"]["skips"]).max() == 1
        assert np.asarray(state6["guard"]["step"]).max() == 2


# ----------------------------------------------------------------- audit


class TestAudit:
    def test_digest_canonical_and_sensitive(self, hvd):
        t = {"w": jnp.linspace(0, 1, 32), "n": 3}
        a = hvd_mod.tree_digest(t)
        b = hvd_mod.tree_digest(
            {"w": jnp.linspace(0, 1, 32), "n": 3}
        )
        assert a == b
        assert a != hvd_mod.tree_digest(
            {"w": jnp.linspace(0, 1, 32).at[7].add(1e-7), "n": 3}
        )
        assert a != hvd_mod.tree_digest({"w": jnp.linspace(0, 1, 32)})

    def test_audit_metrics_and_cadence(self, hvd):
        before = registry.snapshot()
        t = {"w": jnp.ones((4,))}
        assert hvd_mod.maybe_audit(t, step=3, every=5) is None
        assert hvd_mod.maybe_audit(t, step=5, every=5) is not None
        assert hvd_mod.maybe_audit(t, step=10, every=5) is not None
        assert hvd_mod.maybe_audit(t, step=10, every=0) is None
        assert _delta("audit.digests", before) == 2
        assert registry.snapshot()["audit.last_digest_step"] == 10

    @pytest.mark.parametrize(
        "digests,expect",
        [
            # majority wins
            (
                {0: ("aaa", 5), 1: ("aaa", 5), 2: ("bbb", 5)},
                (5, (2,)),
            ),
            # tie breaks toward rank 0's digest
            ({0: ("aaa", 5), 1: ("bbb", 5)}, (5, (1,))),
            # agreement -> healthy
            ({0: ("aaa", 5), 1: ("aaa", 5)}, None),
            # newest quorum step rules; stale odd rank ignored
            (
                {0: ("aaa", 6), 1: ("bbb", 5), 2: ("aaa", 6)},
                None,
            ),
            # single reporter: no quorum
            ({0: ("aaa", 5)}, None),
        ],
    )
    def test_find_divergent(self, digests, expect):
        from horovod_tpu.audit import find_divergent

        shaped = {
            r: {"digest": d, "step": s} for r, (d, s) in digests.items()
        }
        assert find_divergent(shaped) == expect

    def test_kv_roundtrip(self):
        from horovod_tpu.runner.rendezvous import (
            KVStore,
            put_audit,
            read_audit_digests,
        )

        class _C:
            def __init__(self, store):
                self._s = store

            def put(self, scope, key, value):
                self._s.put(scope, key, value)

        store = KVStore()
        put_audit(_C(store), 3, 17, "deadbeef")
        store.put("audit", "bogus", b"not json")
        out = read_audit_digests(store)
        assert out == {3: out[3]}
        assert out[3]["step"] == 17 and out[3]["digest"] == "deadbeef"

    def test_driver_divergence_quarantine(self, monkeypatch):
        from horovod_tpu.elastic.driver import ElasticDriver
        from horovod_tpu.runner.hosts import HostInfo
        from horovod_tpu.runner.rendezvous import KVStore, put_audit

        from tests.test_chaos import _StoreServer
        from tests.test_elastic import FakeDiscovery

        d = ElasticDriver(
            FakeDiscovery([HostInfo("a", 2), HostInfo("b", 6)]),
            ["true"], min_np=1,
        )
        d.host_manager.refresh()
        d._server = _StoreServer(KVStore())
        d._blocks = [
            {"HOROVOD_RANK": str(r), "HOROVOD_HOSTNAME": h}
            for r, h in enumerate(["a"] * 2 + ["b"] * 6)
        ]

        class _C:
            def __init__(self, store):
                self._s = store

            def put(self, scope, key, value):
                self._s.put(scope, key, value)

        c = _C(d._server.store)
        before = registry.snapshot()
        for r in range(8):
            put_audit(c, r, 40, "good" if r != 1 else "evil")
        d._last_audit_poll = -1e9
        reason = d._poll_audit(time.monotonic())
        assert reason is not None and "divergence" in reason
        assert "1" in reason
        assert d.host_manager.is_blacklisted("a")
        assert not d.host_manager.is_blacklisted("b")
        assert d.compute_assignment().world_size == 6
        assert _delta("driver.divergence_restarts", before) == 1
        # the same audit round is never judged twice
        d._last_audit_poll = -1e9
        assert d._poll_audit(time.monotonic()) is None

    def test_driver_divergence_capacity_guard_still_restarts(
        self, monkeypatch
    ):
        """A diverged replica is WRONG, not slow: when the capacity
        guard forbids blacklisting, the gang still restarts (the
        restore re-syncs the replicas — that is the repair)."""
        from horovod_tpu.elastic.driver import ElasticDriver
        from horovod_tpu.runner.hosts import HostInfo
        from horovod_tpu.runner.rendezvous import KVStore, put_audit

        from tests.test_chaos import _StoreServer
        from tests.test_elastic import FakeDiscovery

        d = ElasticDriver(
            FakeDiscovery([HostInfo("a", 4), HostInfo("b", 4)]),
            ["true"], min_np=8,
        )
        d.host_manager.refresh()
        d._server = _StoreServer(KVStore())
        d._blocks = [
            {"HOROVOD_RANK": str(r), "HOROVOD_HOSTNAME": h}
            for r, h in enumerate(["a"] * 4 + ["b"] * 4)
        ]

        class _C:
            def __init__(self, store):
                self._s = store

            def put(self, scope, key, value):
                self._s.put(scope, key, value)

        c = _C(d._server.store)
        for r in range(8):
            put_audit(c, r, 7, "good" if r != 6 else "evil")
        d._last_audit_poll = -1e9
        reason = d._poll_audit(time.monotonic())
        assert reason is not None and "divergence" in reason
        assert not d.host_manager.is_blacklisted("b")  # capacity guard


# ---------------------------------------------------- checkpoint digests


class TestCheckpointIntegrity:
    def _mgr(self, tmp_path, **kw):
        from horovod_tpu.checkpoint import CheckpointManager

        kw.setdefault("async_save", False)
        return CheckpointManager(str(tmp_path / "ckpt"), **kw)

    def test_digest_sidecar_written_and_pruned(self, hvd, tmp_path):
        mgr = self._mgr(tmp_path, max_to_keep=2)
        tree = {"w": jnp.linspace(0, 1, 256)}
        for s in (1, 2, 3):
            mgr.save(s, tree)
        mgr.wait_until_finished()
        root = str(tmp_path / "ckpt")
        names = sorted(
            n for n in os.listdir(root) if n.startswith("digest-")
        )
        assert names == ["digest-2.json", "digest-3.json"]

    def test_corrupt_but_parseable_falls_back(self, hvd, tmp_path):
        mgr = self._mgr(tmp_path)
        # non-constant payload: constant arrays compress away and the
        # flip would land in container slack
        tree = {"w": jnp.linspace(0, 1, 4096, dtype=jnp.float32)}
        mgr.save(1, tree)
        mgr.save(2, tree)
        mgr.wait_until_finished()
        before = registry.snapshot()
        mgr._bitflip_step(2)
        step, restored = mgr.restore_latest_good(like=tree)
        assert step == 1
        assert _delta("checkpoint.digest_mismatch", before) >= 1
        assert _delta("checkpoint.fallback", before) >= 1
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.asarray(tree["w"])
        )

    def test_chaos_bitflip_kind_at_save(self, hvd, tmp_path):
        from horovod_tpu.testing import chaos

        chaos.configure("checkpoint.save@2:bitflip")
        try:
            mgr = self._mgr(tmp_path)
            tree = {"w": jnp.linspace(0, 2, 4096, dtype=jnp.float32)}
            mgr.save(1, tree)
            mgr.save(2, tree)  # hit 2: flipped post-commit
            mgr.wait_until_finished()
            step, _ = mgr.restore_latest_good(like=tree)
            assert step == 1
        finally:
            chaos.reset()

    def test_structure_mismatch_raises_clear_error(self, hvd, tmp_path):
        from horovod_tpu.checkpoint import CheckpointStructureError

        mgr = self._mgr(tmp_path)
        tree = {"params": {"w": jnp.ones((8,))}, "step": 3}
        mgr.save(1, tree)
        mgr.wait_until_finished()
        bad_like = {"params": {"weights": jnp.ones((8,))}, "step": 0}
        with pytest.raises(CheckpointStructureError) as ei:
            mgr.restore(1, like=bad_like)
        msg = str(ei.value)
        assert "weights" in msg and "w" in msg
        assert "structure" in msg
        # restore_latest_good re-raises immediately — older steps
        # cannot repair a caller bug
        with pytest.raises(CheckpointStructureError):
            mgr.restore_latest_good(like=bad_like)

    def test_dtype_casting_restore_is_not_corruption(self, hvd, tmp_path):
        """restore_latest_good(like=<re-typed tree>) casts on restore;
        the META digest gate must skip byte verification instead of
        misreading every retained checkpoint as corrupt."""
        mgr = self._mgr(tmp_path)
        tree = {"w": jnp.linspace(0, 1, 256, dtype=jnp.float32)}
        mgr.save(1, tree)
        mgr.wait_until_finished()
        like_bf16 = {"w": jnp.zeros((256,), jnp.bfloat16)}
        step, restored = mgr.restore_latest_good(like=like_bf16)
        assert step == 1
        assert restored["w"].dtype == jnp.bfloat16

    def test_matching_like_still_restores(self, hvd, tmp_path):
        mgr = self._mgr(tmp_path)
        tree = {"params": {"w": jnp.ones((8,))}, "step": 3}
        mgr.save(1, tree)
        mgr.wait_until_finished()
        out = mgr.restore(1, like=tree)
        assert int(out["step"]) == 3


# --------------------------------------------------- chaos data kinds


class TestChaosDataKinds:
    def test_parse_and_return(self):
        from horovod_tpu.testing import chaos

        plan = chaos.FaultPlan.parse("x@1:nan;y@1:bitflip;z@1:reset")
        assert plan.fire("x") == "nan"
        assert plan.fire("x") is None  # one-shot
        assert plan.fire("y") == "bitflip"
        with pytest.raises(ConnectionResetError):
            plan.fire("z")
        assert [f["kind"] for f in plan.fired()] == [
            "nan", "bitflip", "reset",
        ]

    def test_fusion_dispatch_nan_detected_by_eager_guard(self, hvd):
        from horovod_tpu.testing import chaos

        fusion = hvd_mod.common.basics.state().fusion
        fusion.guard = True
        chaos.configure("fusion.dispatch@1:nan")
        try:
            out = hvd.allreduce(
                hvd.replicate(np.ones((64,), np.float32)), op=hvd_mod.Sum
            )
            assert not bool(np.isfinite(np.asarray(out)).all())
            before = registry.snapshot()
            assert fusion.guard_poll() == 1
            assert _delta("guard.nonfinite_batches", before) == 1
            # a clean dispatch polls clean
            out = hvd.allreduce(
                hvd.replicate(np.ones((64,), np.float32)), op=hvd_mod.Sum
            )
            assert fusion.guard_poll() == 0
        finally:
            chaos.reset()


# ------------------------------------------------- exactly-once resume


class TestSamplerResume:
    def test_reshard_determinism_same_global_order(self):
        """The epoch order is a function of (seed, epoch) only: every
        world size walks the same permutation."""
        from horovod_tpu.data import ShardedIndexSampler

        orders = []
        for world in (2, 6, 8):
            s = ShardedIndexSampler(
                48, num_replicas=world, rank=0, seed=9
            )
            orders.append(s._epoch_order().tolist())
        assert orders[0] == orders[1] == orders[2]
        # and the union of rank stripes IS that order, in global terms
        world = 6
        stripes = [
            list(ShardedIndexSampler(48, num_replicas=world, rank=r, seed=9))
            for r in range(world)
        ]
        flat = [
            stripes[i % world][i // world] for i in range(48)
        ]
        assert flat == orders[0]

    def test_mid_epoch_resume_exactly_once_8_to_6(self):
        """Consume 24 of 96 on 8 ranks, reshard to 6 (72 remaining
        divides 6): the epoch is partitioned exactly — every sample
        once, none dropped, none replayed."""
        from horovod_tpu.data import ShardedIndexSampler

        samps = [
            ShardedIndexSampler(96, num_replicas=8, rank=r, seed=3)
            for r in range(8)
        ]
        seen = []
        for s in samps:
            it = iter(s)
            for _ in range(3):
                seen.append(next(it))
        states = [s.state_dict() for s in samps]
        assert all(st == states[0] for st in states)  # SPMD agreement
        assert states[0]["cursor"] == 24
        s6 = [
            ShardedIndexSampler(96, num_replicas=6, rank=r, seed=3)
            for r in range(6)
        ]
        for s in s6:
            s.load_state_dict(states[0])
        assert all(len(s) == 12 for s in s6)
        rest = [i for s in s6 for i in s]
        assert sorted(seen + rest) == list(range(96))

    def test_seed_mismatch_rejected(self):
        from horovod_tpu.data import ShardedIndexSampler

        s = ShardedIndexSampler(10, num_replicas=2, rank=0, seed=1)
        with pytest.raises(ValueError):
            s.load_state_dict({"epoch": 0, "cursor": 4, "seed": 2})

    def test_epoch_end_cursor_yields_nothing(self):
        from horovod_tpu.data import ShardedIndexSampler

        s = ShardedIndexSampler(10, num_replicas=2, rank=0, seed=1)
        s.load_state_dict({"epoch": 0, "cursor": 10, "seed": 1})
        assert list(s) == []
        s.set_epoch(1)
        assert len(list(s)) == 5  # new epoch resets the cursor


class TestDatasetResume:
    def _write(self, tmp_path, n=96):
        from horovod_tpu.data import write_shards

        x = np.arange(n, dtype=np.int64).reshape(n, 1)
        write_shards(str(tmp_path / "shards"), x, rows_per_shard=20)
        return str(tmp_path / "shards")

    def test_state_roundtrip_same_world(self, hvd, tmp_path):
        from horovod_tpu.data import ShardedFileDataset

        path = self._write(tmp_path)
        consumed = []
        dss = [
            ShardedFileDataset(
                path, batch_size=2, num_replicas=8, rank=r, seed=4
            )
            for r in range(8)
        ]
        for ds in dss:
            it = iter(ds)
            for _ in range(2):  # 2 batches x 2 rows
                consumed.append(next(it))
        st = dss[0].state_dict()
        assert st["cursor"] == 2 * 2 * 8
        fresh = [
            ShardedFileDataset(
                path, batch_size=2, num_replicas=8, rank=r, seed=4
            )
            for r in range(8)
        ]
        rest = []
        for ds in fresh:
            ds.load_state_dict(st)
            rest.extend(list(ds))
        ids_first = sorted(
            int(v) for b in consumed for v in np.asarray(b).reshape(-1)
        )
        ids_rest = sorted(
            int(v) for b in rest for v in np.asarray(b).reshape(-1)
        )
        assert sorted(ids_first + ids_rest) == list(range(96))

    @pytest.mark.slow
    def test_sigkill_resume_world_change_no_replay_no_drop(
        self, hvd, tmp_path
    ):
        """The acceptance drill's data half: iterate 2 batches/rank on
        8 ranks, commit durable state, SIGKILL the process; a fresh
        process at world 6 resumes from disk and lands on the exact
        next global index — the epoch partitions exactly across the
        kill + world change, three runs bit-identical."""
        path = self._write(tmp_path, n=96)
        ckdir = str(tmp_path / "state")
        script = tmp_path / "phase1.py"
        script.write_text(
            f"""
import os, signal
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import horovod_tpu as hvd
from horovod_tpu.checkpoint import DurableJaxState
from horovod_tpu.data import ShardedFileDataset
import jax.numpy as jnp

dss = [
    ShardedFileDataset({path!r}, batch_size=2, num_replicas=8, rank=r,
                       seed=4)
    for r in range(8)
]
st = DurableJaxState({ckdir!r}, params={{"w": jnp.ones(4)}}, batch=0)
# ONE logical stream name (the world-size-independent contract): the
# cursor is global, so rank 0's sampler speaks for the gang
st.register_data("train", dss[0])
seen = []
its = [iter(ds) for ds in dss]
for _ in range(2):
    for it in its:
        seen.append(np.asarray(next(it)).reshape(-1).tolist())
st.batch = 2
st.commit()
st.wait_until_finished()
with open({str(tmp_path / 'seen.json')!r}, "w") as f:
    import json; json.dump(seen, f)
os.kill(os.getpid(), signal.SIGKILL)
"""
        )
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        repo = os.path.dirname(
            os.path.dirname(os.path.abspath(hvd_mod.__file__))
        )
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable, str(script)], env=env, timeout=180
        )
        assert proc.returncode == -signal.SIGKILL
        with open(tmp_path / "seen.json") as f:
            seen = json.load(f)
        seen_ids = sorted(int(v) for b in seen for v in b)
        assert len(seen_ids) == 32  # 2 batches x 2 rows x 8 ranks

        def resume_rest():
            from horovod_tpu.checkpoint import DurableJaxState
            from horovod_tpu.data import ShardedFileDataset

            dss = [
                ShardedFileDataset(
                    path, batch_size=2, num_replicas=6, rank=r, seed=4
                )
                for r in range(6)
            ]
            st2 = DurableJaxState(
                ckdir, params={"w": jnp.zeros(4)}, batch=0
            )
            # each (simulated) process registers ITS dataset under the
            # same stream name and loads the shared global cursor
            st2.register_data("train", dss[0])
            assert st2.resume_latest()
            assert st2.batch == 2
            cursor = dss[0].state_dict()
            for ds in dss[1:]:
                ds.load_state_dict(cursor)
            out = []
            for ds in dss:
                out.append(
                    [np.asarray(b).reshape(-1).tolist() for b in ds]
                )
            return out

        runs = [resume_rest() for _ in range(3)]
        assert runs[0] == runs[1] == runs[2]  # deterministic resume
        rest_ids = sorted(
            int(v) for rank in runs[0] for b in rank for v in b
        )
        # 64 remaining over 6 ranks: ceil(64/6)=11 -> 5 batches x 2
        # rows x 6 ranks = 60 delivered inside exact batches; nothing
        # REPLAYED, and the undelivered tail is only the SPMD ragged
        # tail, never an arbitrary sample
        assert not (set(rest_ids) & set(seen_ids)), "sample replayed"
        assert len(rest_ids) == len(set(rest_ids)), "sample duplicated"
        missing = set(range(96)) - set(seen_ids) - set(rest_ids)
        assert len(missing) <= 64 - 60


class TestElasticCursorRollback:
    def test_restore_rewinds_data_cursor(self, hvd):
        from horovod_tpu.data import ShardedIndexSampler
        from horovod_tpu.elastic.state import JaxState

        s = ShardedIndexSampler(64, num_replicas=8, rank=0, seed=5)
        st = JaxState(params={"w": jnp.ones(4)}, batch=0)
        st.register_data("train", s)
        it = iter(s)
        [next(it) for _ in range(3)]
        st.batch = 3
        st.commit()
        it = iter(s)
        [next(it) for _ in range(2)]
        assert s.state_dict()["cursor"] == 16
        st.restore()
        assert s.state_dict()["cursor"] == 24  # last commit's cursor
        assert st.batch == 3

    def test_register_data_rejects_cursorless(self, hvd):
        from horovod_tpu.elastic.state import JaxState

        st = JaxState(params={"w": jnp.ones(4)})
        with pytest.raises(TypeError):
            st.register_data("x", object())


# ------------------------------------------------- end-to-end drill


@pytest.mark.slow
class TestEndToEndDrill:
    """The acceptance drill, composed: a seeded guarded run eats one
    injected NaN step (skipped + counted), one injected checkpoint
    bitflip (newest commit corrupted POST-commit), and a SIGKILL;
    resume at world 6 falls back past the damaged checkpoint via
    digest verification, lands on the exact next global sample, and
    produces a BIT-IDENTICAL post-resume loss trajectory across 3
    repeated resumes."""

    N, BATCH = 96, 2

    def test_full_drill(self, hvd, tmp_path):
        from horovod_tpu.data import ShardedFileDataset, write_shards

        path = str(tmp_path / "shards")
        x = np.arange(self.N, dtype=np.int64).reshape(self.N, 1)
        write_shards(path, x, rows_per_shard=20)
        ckdir = str(tmp_path / "state")
        repo = os.path.dirname(
            os.path.dirname(os.path.abspath(hvd_mod.__file__))
        )
        script = tmp_path / "phase1.py"
        script.write_text(
            f"""
import os, signal, sys, json
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()
import numpy as np, jax, jax.numpy as jnp, optax
from jax.sharding import PartitionSpec as P
import horovod_tpu as hvd
from jax import shard_map
from horovod_tpu.checkpoint import DurableJaxState
from horovod_tpu.data import ShardedFileDataset
from horovod_tpu.testing import chaos

# the seeded plan: NaN at training step 3, bitflip on the 4th (last)
# checkpoint save — the NEWEST commit is the corrupted one
chaos.configure("seed=11;train.nan@3:nan;checkpoint.save@4:bitflip")
hvd.init()
world = 8
dss = [
    ShardedFileDataset({path!r}, batch_size={self.BATCH},
                       num_replicas=8, rank=r, seed=4)
    for r in range(8)
]
opt = hvd.DistributedOptimizer(
    optax.sgd(0.05), op=hvd.Average, grad_guard=True, guard_max_skips=0
)
params = {{"w": jnp.linspace(1.0, 2.0, 4096, dtype=jnp.float32)}}
ostate = opt.init(params)
st = DurableJaxState({ckdir!r}, params=params, opt_state=ostate, batch=0)
st.register_data("train", dss[0])
mesh = hvd.mesh()

@jax.jit
def step(g, s, p):
    def body(g, s, p):
        g = jax.tree_util.tree_map(lambda t: t[0], g)
        u, s2 = opt.update(g, s, p)
        return jax.tree_util.tree_map(lambda a, b: a + b, p, u), s2
    return shard_map(
        body, mesh=mesh, in_specs=(P(hvd.WORLD_AXIS), P(), P()),
        out_specs=(P(), P()), check_vma=False,
    )(g, s, p)

its = [iter(ds) for ds in dss]
losses = []
for i in range(1, 5):
    rows = [np.asarray(next(it)).reshape(-1) for it in its]
    g = {{"w": jnp.stack([
        jnp.full((4096,), float(r.sum()) / 100.0, jnp.float32)
        for r in rows
    ])}}
    if chaos.inject("train.nan") == "nan":
        g = {{"w": g["w"].at[0, 0].set(jnp.nan)}}
    newp, ostate = step(g, st.opt_state, st.params)
    jax.block_until_ready(newp["w"])
    st.params = newp
    st.opt_state = ostate
    st.batch = i
    losses.append(float(jnp.sum(newp["w"])))
    st.commit()
st.wait_until_finished()
assert int(st.opt_state.guard_skips) == 1, int(st.opt_state.guard_skips)
with open({str(tmp_path / "phase1.json")!r}, "w") as f:
    json.dump({{"losses": losses}}, f)
os.kill(os.getpid(), signal.SIGKILL)
"""
        )
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, str(script)], env=env, timeout=300
        )
        assert proc.returncode == -signal.SIGKILL
        assert (tmp_path / "phase1.json").exists()

        # ---- resume at world 6, three times, bit-identical ----
        from jax.sharding import Mesh

        from horovod_tpu.checkpoint import DurableJaxState

        mesh6 = Mesh(
            np.array(jax.devices()[:6]), (hvd_mod.WORLD_AXIS,)
        )
        opt6 = hvd_mod.DistributedOptimizer(
            optax.sgd(0.05), op=hvd_mod.Average, grad_guard=True,
            guard_max_skips=0,
        )

        def resume_and_train():
            dss = [
                ShardedFileDataset(
                    path, batch_size=self.BATCH, num_replicas=6,
                    rank=r, seed=4,
                )
                for r in range(6)
            ]
            params = {"w": jnp.zeros((4096,), jnp.float32)}
            st2 = DurableJaxState(
                ckdir, params=params, opt_state=opt6.init(params),
                batch=0,
            )
            st2.register_data("train", dss[0])
            before = registry.snapshot()
            assert st2.resume_latest()
            # the bitflipped NEWEST commit (4) was bypassed: digest
            # mismatch counted, batch rolled to commit 3
            assert _delta("checkpoint.digest_mismatch", before) >= 1
            assert _delta("checkpoint.fallback", before) >= 1
            assert st2.batch == 3
            # the skipped NaN step survived the durable boundary
            assert int(st2.opt_state.guard_skips) == 1
            # exact next sample: 3 batches x 2 rows x 8 ranks consumed
            cursor = dss[0].state_dict()
            assert cursor["cursor"] == 3 * self.BATCH * 8
            for ds in dss[1:]:
                ds.load_state_dict(cursor)

            @jax.jit
            def step6(g, s, p):
                def body(g, s, p):
                    g = jax.tree_util.tree_map(lambda t: t[0], g)
                    u, s2 = opt6.update(g, s, p)
                    return (
                        jax.tree_util.tree_map(
                            lambda a, b: a + b, p, u
                        ),
                        s2,
                    )

                return shard_map(
                    body, mesh=mesh6,
                    in_specs=(P(hvd_mod.WORLD_AXIS), P(), P()),
                    out_specs=(P(), P()),
                    check_vma=False,
                )(g, s, p)

            its = [iter(ds) for ds in dss]
            # the elastic reinit re-replicates state onto the NEW
            # gang's mesh; this drill does it explicitly for the
            # 6-device sub-mesh
            from jax.sharding import NamedSharding

            sh6 = NamedSharding(mesh6, P())
            ostate = jax.device_put(jax.device_get(st2.opt_state), sh6)
            params = jax.device_put(jax.device_get(st2.params), sh6)
            losses, batch_ids = [], []
            for _ in range(3):
                rows = [np.asarray(next(it)).reshape(-1) for it in its]
                batch_ids.extend(int(v) for r in rows for v in r)
                g = {"w": jnp.stack([
                    jnp.full(
                        (4096,), float(r.sum()) / 100.0, jnp.float32
                    )
                    for r in rows
                ])}
                params, ostate = step6(g, ostate, params)
                jax.block_until_ready(params["w"])
                losses.append(float(jnp.sum(params["w"])))
            return losses, batch_ids

        runs = [resume_and_train() for _ in range(3)]
        assert runs[0] == runs[1] == runs[2]  # BIT-identical trajectory
        # no sample of the committed prefix is replayed: the first 48
        # global samples were consumed before the commit the resume
        # landed on
        order = np.random.default_rng((4, 0)).permutation(self.N)
        consumed = set(order[: 3 * self.BATCH * 8].tolist())
        assert not (set(runs[0][1]) & consumed), "sample replayed"


# --------------------------------------------------- StepStats deltas


class TestStepStatsIntegrity:
    def test_guard_and_audit_deltas_in_records(self, hvd):
        from horovod_tpu.common import telemetry

        telemetry._reset_hub()
        try:
            hub = telemetry.TelemetryHub(capacity=8)
            hub.step_begin(0)
            registry.counter("guard.nonfinite_steps")
            hvd_mod.audit({"w": jnp.ones(4)}, step=7)
            rec = hub.step_end()
            assert rec["guard.nonfinite_steps"] == 1
            assert rec["audit_ran"] == 1.0
            assert rec["audit.last_digest_step"] == 7.0  # the gauge
            hub.step_begin(1)
            rec = hub.step_end()
            assert rec["guard.nonfinite_steps"] == 0
            assert rec["audit_ran"] == 0.0
            assert rec["audit.last_digest_step"] == 7.0
        finally:
            telemetry._reset_hub()
