"""Static-analysis subsystem tests (horovod_tpu/analysis/).

Three surfaces:

* the :mod:`hlo_parse` parser + :mod:`rules` engine over both
  hand-built module text (exact control of the shapes) and real
  lowered programs (the format contract against this JAX version);
* the :mod:`sched_audit` runtime recorder: deterministic folding,
  the FusionManager dispatch hook, KV round-trip, majority
  arbitration, first-divergent-index recovery;
* the driver's ``sched_divergence`` path — in-process, and the
  acceptance drill: a multi-process fleet where one rank's fusion
  composition is deliberately skewed and the driver must flag the
  divergence through the rendezvous KV BEFORE the stall inspector's
  shutdown window could fire.
"""

import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import horovod_tpu as hvd_mod  # noqa: E402
from horovod_tpu import analysis  # noqa: E402
from horovod_tpu.analysis import rules, sched_audit  # noqa: E402
from jax import shard_map  # noqa: E402


# A hand-built module: two independent world all_reduces, one scalar
# inter-group all_reduce, an int8 all_to_all on intra groups, a
# dependent chain, and a donated arg — every parser feature in ~30
# lines of exact text.
_MODULE = textwrap.dedent(
    """
    module @jit_step attributes {mhlo.num_partitions = 8 : i32, mhlo.num_replicas = 1 : i32} {
      func.func public @main(%arg0: tensor<8x16xf32> {jax.buffer_donor = true}, %arg1: tensor<8x16xf32>) -> (tensor<8x16xf32> {jax.result_info = ""}) {
        %0 = call @shmap_body(%arg0) : (tensor<8x16xf32>) -> tensor<8x16xf32>
        return %0 : tensor<8x16xf32>
      }
      func.func private @shmap_body(%arg0: tensor<1x16xf32>) -> (tensor<1x16xf32>) {
        %0 = "stablehlo.all_reduce"(%arg0) <{channel_handle = #stablehlo.channel_handle<handle = 1, type = 1>, replica_groups = dense<[[0, 1, 2, 3, 4, 5, 6, 7]]> : tensor<1x8xi64>, use_global_device_ids}> ({
        ^bb0(%a: tensor<f32>, %b: tensor<f32>):
          %s = stablehlo.add %a, %b : tensor<f32>
          stablehlo.return %s : tensor<f32>
        }) : (tensor<1x16xf32>) -> tensor<1x16xf32>
        %1 = "stablehlo.all_reduce"(%arg0) <{channel_handle = #stablehlo.channel_handle<handle = 2, type = 1>, replica_groups = dense<[[0, 1, 2, 3, 4, 5, 6, 7]]> : tensor<1x8xi64>, use_global_device_ids}> ({
        ^bb0(%a: tensor<f32>, %b: tensor<f32>):
          %s = stablehlo.add %a, %b : tensor<f32>
          stablehlo.return %s : tensor<f32>
        }) : (tensor<1x16xf32>) -> tensor<1x16xf32>
        %2 = "stablehlo.all_reduce"(%1) <{channel_handle = #stablehlo.channel_handle<handle = 3, type = 1>, replica_groups = dense<[[0, 4], [1, 5], [2, 6], [3, 7]]> : tensor<4x2xi64>, use_global_device_ids}> ({
        ^bb0(%a: tensor<f32>, %b: tensor<f32>):
          %s = stablehlo.add %a, %b : tensor<f32>
          stablehlo.return %s : tensor<f32>
        }) : (tensor<f32>) -> tensor<f32>
        %3 = stablehlo.convert %arg0 : (tensor<1x16xf32>) -> tensor<1x16xi8>
        %4 = "stablehlo.all_to_all"(%3) <{channel_handle = #stablehlo.channel_handle<handle = 4, type = 1>, replica_groups = dense<[[0, 1, 2, 3], [4, 5, 6, 7]]> : tensor<2x4xi64>, split_dimension = 0 : i64, concat_dimension = 0 : i64, split_count = 4 : i64}> : (tensor<1x16xi8>) -> tensor<1x16xi8>
        %5 = stablehlo.add %0, %1 : tensor<1x16xf32>
        return %5 : tensor<1x16xf32>
      }
    }
    """
)

WORLD_G = ((0, 1, 2, 3, 4, 5, 6, 7),)
INTRA_G = ((0, 1, 2, 3), (4, 5, 6, 7))
INTER_G = ((0, 4), (1, 5), (2, 6), (3, 7))


class TestParser:
    def test_collectives_groups_types(self):
        g = analysis.parse_module(_MODULE)
        assert g.num_partitions == 8
        assert g.counts() == {
            "all_reduce": 3, "reduce_scatter": 0, "all_gather": 0,
            "all_to_all": 1, "collective_permute": 0,
        }
        ars = g.collectives("all_reduce")
        assert ars[0].replica_groups == WORLD_G
        assert ars[2].replica_groups == INTER_G
        assert ars[0].operand_types[0].shape == (1, 16)
        assert ars[0].operand_types[0].dtype == "f32"
        assert ars[0].operand_types[0].nbytes == 64
        assert ars[2].is_scalar()
        assert not ars[0].is_scalar()
        assert ars[0].reduction_dtype == "f32"
        a2a = g.collectives("all_to_all")[0]
        assert a2a.dtypes == ("i8",)
        assert a2a.replica_groups == INTRA_G
        assert g.group_sizes("all_to_all") == [4]

    def test_def_use_edges(self):
        g = analysis.parse_module(_MODULE)
        # %2 consumes %1: exactly one dependent pair among all_reduces
        pairs = g.dependent_pairs("all_reduce")
        assert len(pairs) == 1
        dep, on = pairs[0]
        assert (dep.sid, on.sid) == ("%2", "%1")
        assert not g.independent("all_reduce")
        assert g.independent("all_to_all")

    def test_donation_args(self):
        g = analysis.parse_module(_MODULE)
        args = g.args()
        assert [a.donated for a in args] == [True, False]
        assert g.donated_args()[0].index == 0

    def test_world_spanning(self):
        g = analysis.parse_module(_MODULE)
        ars = g.collectives("all_reduce")
        assert ars[0].spans(8)
        assert not ars[2].spans(8)

    def test_snippet_and_line_anchor(self):
        g = analysis.parse_module(_MODULE)
        c = g.collectives("all_to_all")[0]
        assert '"stablehlo.all_to_all"' in c.snippet
        line = _MODULE.splitlines()[c.line_no].strip()
        # snippets are truncated for readability but stay anchored to
        # the exact source line
        assert line.startswith(c.snippet.rstrip("."))
        assert len(c.snippet) <= 240

    def test_real_lowered_program(self, hvd):
        """Format contract against THIS jax version: shard_map psum
        over 8 CPU devices parses with groups, dtype, donation."""
        mesh = hvd_mod.mesh()

        def body(x):
            return jax.lax.psum(x, hvd_mod.WORLD_AXIS)

        fn = jax.jit(
            shard_map(
                body, mesh=mesh, in_specs=P(hvd_mod.WORLD_AXIS),
                out_specs=P(hvd_mod.WORLD_AXIS), check_vma=False,
            ),
            donate_argnums=(0,),
        )
        g = analysis.parse_module(fn.lower(jnp.ones((8, 16))))
        assert g.count("all_reduce") == 1
        assert g.collectives("all_reduce")[0].replica_groups == WORLD_G
        assert g.donated_args()


class TestRules:
    def _g(self):
        return analysis.parse_module(_MODULE)

    def test_collective_count_int_and_range(self):
        g = self._g()
        assert not rules.CollectiveCount("all_reduce", 3).check(g)
        assert rules.CollectiveCount("all_reduce", 2).check(g)
        assert not rules.CollectiveCount("all_to_all", (1, 2)).check(g)
        assert rules.CollectiveCount("all_to_all", (2, 9)).check(g)

    def test_def_use_rule_names_the_pair(self):
        f = rules.NoInterCollectiveDefUse("all_reduce").check(self._g())
        assert len(f) == 1
        assert "%2" in f[0].message and "%1" in f[0].message
        assert "all_reduce" in f[0].snippet

    def test_replica_group_structure(self):
        g = self._g()
        assert not rules.ReplicaGroupStructure(
            "all_to_all", groups=INTRA_G
        ).check(g)
        assert rules.ReplicaGroupStructure(
            "all_to_all", groups=INTER_G
        ).check(g)
        assert not rules.ReplicaGroupStructure(
            "all_to_all", forbid_world_spanning=True
        ).check(g)
        assert rules.ReplicaGroupStructure(
            "all_reduce", forbid_world_spanning=True
        ).check(g)
        # vacuous pass is a violation under require_present
        assert rules.ReplicaGroupStructure(
            "reduce_scatter", require_present=True
        ).check(g)
        assert not rules.ReplicaGroupStructure(
            "all_to_all", groups_any_of=(INTRA_G, INTER_G)
        ).check(g)
        assert rules.ReplicaGroupStructure(
            "all_to_all", groups_any_of=(INTER_G,)
        ).check(g)

    def test_wire_dtype_placement(self):
        g = self._g()
        # the module's i8 all_to_all rides INTRA groups: a placement
        # violation under the two-level contract
        f = rules.WireDtype(
            inter_groups=INTER_G, intra_groups=INTRA_G
        ).check(g)
        assert len(f) == 1 and "INTRA hop" in f[0].message
        # and any i8 at all violates a full-width contract
        assert rules.WireDtype(int8_allowed=False).check(g)

    def test_donation_coverage(self):
        g = self._g()
        assert not rules.DonationCoverage(arg_indices=(0,)).check(g)
        assert rules.DonationCoverage(arg_indices=(1,)).check(g)
        assert not rules.DonationCoverage(min_donated=1).check(g)
        assert rules.DonationCoverage(min_donated=2).check(g)

    def test_guard_overhead(self):
        base = self._g()
        same = self._g()
        assert not rules.GuardOverhead(base).check(same)
        # a module with one extra SCALAR all_reduce passes +1, fails +0
        extra = analysis.parse_module(
            _MODULE.replace(
                "%5 = stablehlo.add %0, %1 : tensor<1x16xf32>",
                """%9 = "stablehlo.all_reduce"(%arg0) <{replica_groups = dense<[[0, 1, 2, 3, 4, 5, 6, 7]]> : tensor<1x8xi64>}> ({
    ^bb0(%a: tensor<f32>, %b: tensor<f32>):
      %s = stablehlo.add %a, %b : tensor<f32>
      stablehlo.return %s : tensor<f32>
    }) : (tensor<f32>) -> tensor<f32>
    %5 = stablehlo.add %0, %1 : tensor<1x16xf32>""",
            )
        )
        assert rules.GuardOverhead(base).check(extra)
        assert not rules.GuardOverhead(
            base, extra_scalar_allreduces=1
        ).check(extra)

    def test_compile_budget(self):
        r = rules.CompileBudget(decode_compiles=1, prefills=(2, 4))
        assert not r.check({"decode_compiles": 1, "prefills": 3})
        assert r.check({"decode_compiles": 2, "prefills": 3})
        assert r.check({"prefills": 3})  # absent counter is a finding

    def test_expect_raises_with_snippet(self):
        with pytest.raises(AssertionError, match="all_reduce"):
            analysis.expect(
                self._g(), rules.NoInterCollectiveDefUse("all_reduce")
            )

    def test_report_json_shape(self):
        rep = rules.check_program(
            self._g(),
            [rules.CollectiveCount("all_reduce", 2)],
        )
        d = rep.to_dict()
        assert d["ok"] is False
        assert d["rules_checked"] == ["CollectiveCount[all_reduce==2]"]
        assert d["violations"][0]["rule"].startswith("CollectiveCount")


# ------------------------------------------------ schedule recorder


class TestScheduleRecorder:
    def test_deterministic_and_composition_sensitive(self):
        r = sched_audit.ScheduleRecorder()
        r.record("allreduce:2", ("a", (32,), "float32"), wire="fp32")
        r.record("allreduce:2", ("b", (64,), "float32"), wire="int8")
        fp1 = r.fingerprint()
        r2 = sched_audit.ScheduleRecorder()
        r2.record("allreduce:2", ("a", (32,), "float32"), wire="fp32")
        r2.record("allreduce:2", ("b", (64,), "float32"), wire="int8")
        assert r2.fingerprint() == fp1  # identical schedule, identical fp
        r3 = sched_audit.ScheduleRecorder()
        r3.record("allreduce:2", ("a", (32,), "float32"), wire="fp32")
        r3.record("allreduce:2", ("b", (64,), "float32"), wire="fp32")
        assert r3.fingerprint() != fp1  # the WIRE is part of the schedule

    def test_ring_bounded_and_indexed(self):
        r = sched_audit.ScheduleRecorder()
        for i in range(300):
            r.record("allreduce:2", ("t", (i,), "float32"))
        snap = r.snapshot()
        assert snap["dispatches"] == 300
        assert len(snap["ring"]) == 128
        assert snap["ring"][0][0] == 300 - 128
        assert snap["ring"][-1][0] == 299

    def test_reset(self):
        r = sched_audit.ScheduleRecorder()
        r.record("allreduce:2", ("t", (4,), "float32"))
        fp = r.fingerprint()
        r.reset()
        assert r.dispatch_count == 0
        assert r.fingerprint() != fp

    def test_disabled_by_env(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_SCHED_AUDIT", "0")
        sched_audit.reset()
        sched_audit.record("allreduce:2", ("t", (4,), "float32"))
        assert sched_audit.recorder().dispatch_count == 0
        assert sched_audit.publish(step=1, rank=0) is False
        monkeypatch.setenv("HOROVOD_SCHED_AUDIT", "1")
        sched_audit.record("allreduce:2", ("t", (4,), "float32"))
        assert sched_audit.recorder().dispatch_count == 1
        sched_audit.reset()

    def test_fusion_dispatch_folds(self, hvd):
        """The real hook: identical eager dispatch sequences fold to
        identical fingerprints; a skewed composition diverges."""
        mesh = hvd_mod.mesh()

        def run(shapes):
            sched_audit.reset()
            for s in shapes:
                hvd_mod.allreduce(
                    hvd_mod.shard_from_rank_fn(
                        lambda r: np.ones(s, np.float32), mesh
                    )
                )
            return sched_audit.recorder().snapshot()

        a = run([(32,), (64,)])
        b = run([(32,), (64,)])
        c = run([(32,), (48,)])
        assert a["dispatches"] >= 2
        assert a["fingerprint"] == b["fingerprint"]
        assert a["fingerprint"] != c["fingerprint"]
        sched_audit.reset()

    def test_first_divergent_index_with_full_rings(self):
        """Trailing-extra-dispatch divergence stays locatable once both
        rings are full: the frontier comparison, not ring length, names
        the first divergent index."""
        good_r = sched_audit.ScheduleRecorder()
        for _ in range(299):
            good_r.record("allreduce:2", ("t", (32,), "float32"))
        bad = dict(good_r.snapshot())
        good = dict(good_r.snapshot())
        # bad rank ran ONE extra dispatch; both rings hold 128 entries
        bad_r = sched_audit.ScheduleRecorder()
        for _ in range(299):
            bad_r.record("allreduce:2", ("t", (32,), "float32"))
        bad_r.record("allreduce:2", ("EXTRA", (48,), "float32"))
        bad = bad_r.snapshot()
        assert len(bad["ring"]) == len(good["ring"]) == 128
        assert sched_audit.first_divergent_index(bad, good) == 299

    def test_grouped_auto_names_fold_without_counter(self):
        """grouped_allreduce auto-names carry the process counter AND a
        member index: the counter must not reach the fingerprint (a
        rejoined worker restarts it at 0), the member index must."""
        from horovod_tpu.ops.fusion import _sched_entry_name

        assert _sched_entry_name("allreduce.noname.7") == "allreduce"
        assert (
            _sched_entry_name("grouped_allreduce.noname.42.0")
            == "grouped_allreduce.0"
        )
        assert (
            _sched_entry_name("grouped_allreduce.noname.9000.0")
            == "grouped_allreduce.0"
        )
        assert _sched_entry_name("my_grad/layer0") == "my_grad/layer0"

    def test_find_divergent_majority_and_index(self):
        r = sched_audit.ScheduleRecorder()
        for i in range(3):
            r.record("allreduce:2", ("t", (32,), "float32"))
        good = dict(r.snapshot(), step=5)
        r.record("allreduce:2", ("EXTRA", (48,), "float32"))
        bad = dict(r.snapshot(), step=5)
        out = sched_audit.find_divergent({0: good, 1: dict(good), 2: bad})
        assert out == (5, (2,))
        assert sched_audit.first_divergent_index(bad, good) == 3
        # agreement -> None
        assert (
            sched_audit.find_divergent({0: good, 1: dict(good)}) is None
        )

    def test_kv_roundtrip(self):
        from horovod_tpu.runner.rendezvous import (
            KVStore,
            put_sched,
            read_sched_fingerprints,
        )

        class _C:
            def __init__(self, store):
                self._s = store

            def put(self, scope, key, value):
                self._s.put(scope, key, value)

        store = KVStore()
        put_sched(_C(store), 3, 17, "abcd", 42, [[41, "ffff"]])
        store.put("sched", "bogus", b"not json")
        out = read_sched_fingerprints(store)
        assert set(out) == {3}
        assert out[3]["fingerprint"] == "abcd"
        assert out[3]["dispatches"] == 42
        assert out[3]["ring"] == [[41, "ffff"]]


# ------------------------------------------------ driver integration


def _driver_with_store():
    from horovod_tpu.elastic.driver import ElasticDriver
    from horovod_tpu.runner.hosts import HostInfo
    from horovod_tpu.runner.rendezvous import KVStore

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

    return d, _C(d._server.store)


class TestDriverSchedDivergence:
    def test_quarantine_reason_and_dispatch_index(self):
        from horovod_tpu.common.metrics import registry
        from horovod_tpu.runner.rendezvous import put_sched

        d, c = _driver_with_store()
        r = sched_audit.ScheduleRecorder()
        for _ in range(3):
            r.record("allreduce:2", ("t", (32,), "float32"))
        good = r.snapshot()
        r.record("allreduce:2", ("EXTRA", (48,), "float32"))
        bad = r.snapshot()
        before = registry.snapshot()
        for rank in range(8):
            snap = bad if rank == 1 else good
            put_sched(
                c, rank, 9, snap["fingerprint"], snap["dispatches"],
                snap["ring"],
            )
        d._last_audit_poll = -1e9
        reason = d._poll_audit(time.monotonic())
        assert reason is not None and reason.startswith("sched_divergence")
        assert "1" in reason
        assert "first divergent dispatch #3" in reason
        assert d.host_manager.is_blacklisted("a")
        assert not d.host_manager.is_blacklisted("b")
        snap_m = registry.snapshot()
        assert (
            snap_m.get("driver.sched_divergence_restarts", 0)
            - before.get("driver.sched_divergence_restarts", 0)
            == 1
        )
        # the same round is never judged twice
        d._last_audit_poll = -1e9
        assert d._poll_audit(time.monotonic()) is None

    def test_sched_agreement_falls_through_to_param_audit(self):
        from horovod_tpu.runner.rendezvous import put_audit, put_sched

        d, c = _driver_with_store()
        r = sched_audit.ScheduleRecorder()
        r.record("allreduce:2", ("t", (32,), "float32"))
        snap = r.snapshot()
        for rank in range(8):
            put_sched(
                c, rank, 4, snap["fingerprint"], snap["dispatches"],
                snap["ring"],
            )
            put_audit(c, rank, 4, "good" if rank != 2 else "evil")
        d._last_audit_poll = -1e9
        reason = d._poll_audit(time.monotonic())
        # schedules agree; the PARAM divergence is still caught
        assert reason is not None and reason.startswith("divergence")
        assert "2" in reason


class TestMultiProcessSkewedSchedule:
    def test_driver_flags_sched_divergence_before_stall_window(
        self, tmp_path, monkeypatch
    ):
        """Acceptance drill: three REAL worker processes run eager
        fused dispatches — rank 1's fusion composition deliberately
        skewed — and publish schedule fingerprints + heartbeats over
        HTTP into a live rendezvous KV. The driver must quarantine
        rank 1 with reason ``sched_divergence`` while every rank's
        heartbeat is fresh and the stall inspector's shutdown window
        (set explicitly below) has not elapsed — divergence caught as
        a SCHEDULE mismatch, not minutes later as a hang."""
        import os
        import signal  # noqa: F401  (symmetry with sibling drills)

        from horovod_tpu.elastic.driver import ElasticDriver
        from horovod_tpu.runner.hosts import HostInfo
        from horovod_tpu.runner.rendezvous import RendezvousServer

        from tests.test_elastic import FakeDiscovery

        stall_window_s = 300.0
        monkeypatch.setenv(
            "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS", str(stall_window_s)
        )
        server = RendezvousServer(secret_key=None, backend="python")
        port = server.start()
        worker = tmp_path / "sched_worker.py"
        worker.write_text(
            textwrap.dedent(
                """
                import os, sys
                os.environ.setdefault("JAX_PLATFORMS", "cpu")
                rank, skew = int(sys.argv[1]), sys.argv[2] == "1"
                import numpy as np
                import horovod_tpu as hvd
                from horovod_tpu.analysis import sched_audit
                from horovod_tpu.common.config import Config
                from horovod_tpu.runner.rendezvous import (
                    _client_from_cfg, put_heartbeat,
                )

                hvd.init()
                mesh = hvd.mesh()

                def ar(n):
                    hvd.allreduce(
                        hvd.shard_from_rank_fn(
                            lambda r: np.ones((n,), np.float32), mesh
                        )
                    )

                for _ in range(3):
                    ar(32)
                if skew:
                    ar(48)  # the divergent dispatch (index 3)
                client = _client_from_cfg(Config.from_env())
                put_heartbeat(client, rank)
                ok = sched_audit.publish(step=1, rank=rank)
                print("PUBLISHED", ok, sched_audit.recorder().dispatch_count)
                hvd.shutdown()
                """
            )
        )
        t0 = time.monotonic()
        try:
            env = dict(os.environ)
            env["PYTHONPATH"] = os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))
            )
            env["HOROVOD_GLOO_RENDEZVOUS_ADDR"] = "127.0.0.1"
            env["HOROVOD_GLOO_RENDEZVOUS_PORT"] = str(port)
            env.pop("HOROVOD_SECRET_KEY", None)
            env.pop("XLA_FLAGS", None)  # 1-device worker: faster init
            procs = [
                subprocess.Popen(
                    [sys.executable, str(worker), str(rank),
                     "1" if rank == 1 else "0"],
                    env=env, stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE, text=True,
                )
                for rank in range(3)
            ]
            outs = [p.communicate(timeout=240) for p in procs]
            for p, (out, err) in zip(procs, outs):
                assert p.returncode == 0, err[-2000:]
                assert "PUBLISHED True" in out, (out, err[-2000:])

            d = ElasticDriver(
                FakeDiscovery([HostInfo("h0", 1), HostInfo("h1", 1),
                               HostInfo("h2", 1)]),
                ["true"], min_np=1,
            )
            d.host_manager.refresh()
            d._server = server
            d._blocks = [
                {"HOROVOD_RANK": str(r), "HOROVOD_HOSTNAME": f"h{r}"}
                for r in range(3)
            ]
            # heartbeats are FRESH (the divergent rank is alive and
            # beating — nothing for the stall path to see)
            d._last_hb_poll = -1e9
            assert d._poll_heartbeats(time.monotonic()) is None
            d._last_audit_poll = -1e9
            reason = d._poll_audit(time.monotonic())
            elapsed = time.monotonic() - t0
            assert reason is not None, "sched divergence not flagged"
            assert reason.startswith("sched_divergence"), reason
            assert "1" in reason
            assert "first divergent dispatch #3" in reason, reason
            assert d.host_manager.is_blacklisted("h1")
            assert not d.host_manager.is_blacklisted("h0")
            # ... and the whole detection ran inside the stall window:
            # the hang this prevents would not even have been NOTICED yet
            assert elapsed < stall_window_s, (
                f"detection took {elapsed:.1f}s, stall window "
                f"{stall_window_s}s"
            )
        finally:
            server.stop()
