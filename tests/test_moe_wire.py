"""Expert wire (PR 12): quantized + two-level MoE alltoall, moe_ffn
edge cases against a host oracle, the capacity-factor autotuner,
persistent tuner state, the eager-alltoall observability fix, the
expert-load KV plumbing, and MoE decode in the serving plane.

Bit-exactness methodology follows tests/test_hier_wire.py: the
hierarchical alltoall is a pure permutation for exact wires, so
fp32/int32 equality vs the flat ``lax.all_to_all`` is asserted
BITWISE on arbitrary data (no reassociation exists to excuse); the
int8 wire is bounded in quanta of the per-block absmax, with
self-slice blocks bit-exact (they never cross the lossy hop).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu import analysis
from jax import shard_map
from horovod_tpu.common import topology as topo_mod
from horovod_tpu.ops import traced
from horovod_tpu.parallel.moe import MoEParams, init_moe_params, moe_ffn

STAGES_84 = topo_mod.hierarchical_stage_groups(8, 4)
STAGES_82 = topo_mod.hierarchical_stage_groups(8, 2)


def _mesh(axis="ep"):
    return Mesh(np.asarray(jax.devices()[:8]), (axis,))


def _sm(fn, ins=P("ep"), outs=P("ep"), axis="ep"):
    return jax.jit(
        shard_map(
            fn,
            mesh=_mesh(axis),
            in_specs=ins,
            out_specs=outs,
            check_vma=False,
        )
    )


def _flat_a2a(axis="ep"):
    return _sm(
        lambda v: jax.lax.all_to_all(v[0], axis, 0, 0, tiled=True)[None],
        axis=axis,
    )


def _a2a_group_sizes(lowered):
    """Replica-group row lengths of every all_to_all in a lowered
    module (the monolithic-flat-alltoall detector) — via the shared
    ``horovod_tpu.analysis`` parser, not regex."""
    return analysis.parse_module(lowered).group_sizes("all_to_all")


# ---------------------------------------------------- wire primitives


class TestQuantizedAlltoall:
    def test_parity_and_pad_exclusion(self, hvd):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(8, 8, 4, 64)).astype(np.float32)
        x[:, :, 3, :] = 0.0  # an empty (dropped/pad) dispatch slot
        q = np.asarray(
            _sm(
                lambda v: traced.quantized_alltoall(
                    v[0], axis_name="ep", seed=1, block_size=32
                )[None]
            )(x)
        )
        f = np.asarray(_flat_a2a()(x))
        # pad slots arrive as exact zeros — excluded from every scale
        np.testing.assert_array_equal(q[:, :, 3, :], 0.0)
        bound = 2.5 * np.abs(f).max() / 127.0
        assert np.abs(q - f).max() <= bound
        # unbiased-ish: the mean error is far below one quantum
        assert abs((q - f).mean()) < bound / 20

    def test_groups_restrict_exchange(self, hvd):
        rng = np.random.default_rng(1)
        groups = STAGES_84[1]  # [[0,4],[1,5],[2,6],[3,7]]
        x = rng.normal(size=(8, 2, 3, 32)).astype(np.float32)
        q = np.asarray(
            _sm(
                lambda v: traced.quantized_alltoall(
                    v[0], axis_name="ep", seed=2, block_size=16,
                    groups=groups,
                )[None]
            )(x)
        )
        f = np.asarray(
            _sm(
                lambda v: jax.lax.all_to_all(
                    v[0], "ep", 0, 0, tiled=True,
                    axis_index_groups=groups,
                )[None]
            )(x)
        )
        assert np.abs(q - f).max() <= 2.5 * np.abs(f).max() / 127.0

    def test_block_wider_than_row_clamps(self, hvd):
        """block_size > d must clamp to the row width — otherwise the
        zero-pad up to the block would make the int8 wire move MORE
        bytes than fp32 (the review-caught default-block-512 trap)."""
        rng = np.random.default_rng(12)
        x = rng.normal(size=(8, 8, 2, 64)).astype(np.float32)

        def run(bs):
            return np.asarray(
                _sm(
                    lambda v: traced.quantized_alltoall(
                        v[0], axis_name="ep", seed=4, block_size=bs
                    )[None]
                )(x)
            )

        np.testing.assert_array_equal(run(512), run(64))

    def test_shape_validation(self, hvd):
        with pytest.raises(ValueError, match="slots"):
            _sm(
                lambda v: traced.quantized_alltoall(
                    v[0].reshape(4, -1)[None][0], axis_name="ep"
                )[None]
            )(np.zeros((8, 4, 2, 8), np.float32))


class TestHierarchicalAlltoall:
    @pytest.mark.parametrize("stages", [STAGES_84, STAGES_82])
    def test_fp32_bitexact_vs_flat(self, hvd, stages):
        rng = np.random.default_rng(2)
        x = rng.normal(size=(8, 8, 4, 16)).astype(np.float32)
        hier = np.asarray(
            _sm(
                lambda v: traced.hierarchical_alltoall(
                    v[0], axis_name="ep", stages=stages
                )[None]
            )(x)
        )
        np.testing.assert_array_equal(hier, np.asarray(_flat_a2a()(x)))

    def test_int32_map_bitexact(self, hvd):
        rng = np.random.default_rng(3)
        xi = rng.integers(-1, 7, size=(8, 8, 4, 1)).astype(np.int32)
        hier = np.asarray(
            _sm(
                lambda v: traced.hierarchical_alltoall(
                    v[0], axis_name="ep", stages=STAGES_84,
                    intra_wire="bf16", inter_wire="int8",  # ignored: int
                )[None]
            )(xi)
        )
        np.testing.assert_array_equal(hier, np.asarray(_flat_a2a()(xi)))

    @pytest.mark.parametrize("inter_wire", ["int8", "bf16"])
    def test_lossy_inter_spares_intra_blocks(self, hvd, inter_wire):
        rng = np.random.default_rng(4)
        x = rng.normal(size=(8, 8, 4, 64)).astype(np.float32)
        out = np.asarray(
            _sm(
                lambda v: traced.hierarchical_alltoall(
                    v[0], axis_name="ep", stages=STAGES_84,
                    inter_wire=inter_wire, seed=5, block_size=32,
                )[None]
            )(x)
        )
        f = np.asarray(_flat_a2a()(x))
        L = 4
        for r in range(8):
            h = r // L
            sl = slice(h * L, (h + 1) * L)
            # blocks from same-slice sources never crossed DCN: exact
            np.testing.assert_array_equal(out[r][sl], f[r][sl])
        bound = (
            2.5 * np.abs(f).max() / 127.0
            if inter_wire == "int8"
            else 0.01 * np.abs(f).max()
        )
        assert np.abs(out - f).max() <= bound

    def test_lowered_no_monolithic_alltoall(self, hvd):
        x = np.zeros((8, 8, 4, 64), np.float32)
        low = _sm(
            lambda v: traced.hierarchical_alltoall(
                v[0], axis_name="ep", stages=STAGES_84,
                inter_wire="int8", block_size=32,
            )[None]
        ).lower(jnp.asarray(x))
        sizes = _a2a_group_sizes(low)
        assert sizes, "expected group-limited all_to_all ops"
        assert all(s < 8 for s in sizes), sizes

    def test_validation(self, hvd):
        x = np.zeros((8, 8, 4, 8), np.float32)
        with pytest.raises(ValueError, match="stages"):
            _sm(
                lambda v: traced.hierarchical_alltoall(
                    v[0], axis_name="ep"
                )[None]
            )(x)


# ------------------------------------------------------- moe_ffn core


def _full_params(rng_key, d=16, f=32, e_total=16):
    return init_moe_params(rng_key, d, f, e_total, e_total)


_PARAM_SPEC = MoEParams(
    router=P(), w1=P("ep"), b1=P("ep"), w2=P("ep"), b2=P("ep")
)


def _run_moe(params, x, stats=False, **kw):
    def body(p, v):
        out = moe_ffn(p, v[0], return_stats=stats, **kw)
        if stats:
            o, s = out
            return o[None], s
        return out[None]

    outs = (P("ep"), P()) if stats else P("ep")
    return _sm(body, (_PARAM_SPEC, P("ep")), outs)(params, x)


def _oracle(params, x, capacity_factor, member_ranks=None, live=None):
    """Host top-1 switch router + per-token expert FFN: routing from
    fp32 logits (argmax of logits == argmax of softmax), gate from the
    fp32 softmax, capacity filled in token order per (source, dest)
    pair, dropped tokens output zero. Returns (out, hist, dropped)."""
    ep, t, d = x.shape
    e_total = params.router.shape[1]
    e_local = e_total // ep
    k = ep if member_ranks is None else len(member_ranks)
    members = (
        list(range(ep)) if member_ranks is None else list(member_ranks)
    )
    capacity = int(max(1, round(capacity_factor * t / k)))
    out = np.zeros_like(x)
    hist = np.zeros(e_total)
    dropped = 0
    router = np.asarray(params.router, np.float32)
    for r in range(ep):
        if live is not None and not live[r]:
            continue
        if member_ranks is not None and r not in members:
            continue
        logits = x[r].astype(np.float32) @ router
        if member_ranks is not None:
            allowed = np.isin(np.arange(e_total) // e_local, members)
            logits = np.where(allowed[None], logits, -np.inf)
        m = logits.max(axis=1, keepdims=True)
        pr = np.exp(logits - m)
        pr /= pr.sum(axis=1, keepdims=True)
        e = logits.argmax(axis=1)
        fills = {}
        for i in range(t):
            dest = e[i] // e_local
            pos = fills.get(dest, 0)
            fills[dest] = pos + 1
            if pos >= capacity:
                dropped += 1
                continue
            hist[e[i]] += 1
            xe = x[r, i].astype(np.float32)
            h = jax.nn.gelu(
                xe @ np.asarray(params.w1[e[i]], np.float32)
                + np.asarray(params.b1[e[i]], np.float32)
            )
            y = np.asarray(h, np.float32) @ np.asarray(
                params.w2[e[i]], np.float32
            ) + np.asarray(params.b2[e[i]], np.float32)
            out[r, i] = pr[i, e[i]] * y
    return out, hist, dropped


class TestMoEFFN:
    @pytest.mark.parametrize("t_local", [8, 10])  # 10: not % ep == 0
    def test_host_oracle_gate_and_output(self, hvd, t_local):
        rng = np.random.default_rng(5)
        params = _full_params(jax.random.PRNGKey(0))
        x = rng.normal(size=(8, t_local, 16)).astype(np.float32)
        out, st = _run_moe(
            params, x, stats=True, capacity_factor=2.0, wire="fp32"
        )
        want, hist, dropped = _oracle(params, x, 2.0)
        np.testing.assert_allclose(
            np.asarray(out), want, rtol=2e-4, atol=2e-5
        )
        np.testing.assert_array_equal(np.asarray(st.expert_tokens), hist)
        assert float(st.dropped) == dropped
        assert float(st.total) == 8 * t_local

    def test_capacity_overflow_drop_parity(self, hvd):
        """Dropped tokens output EXACT zeros (the residual connection
        carries them), and the drop counter matches the oracle."""
        rng = np.random.default_rng(6)
        params = _full_params(jax.random.PRNGKey(1))
        x = rng.normal(size=(8, 12, 16)).astype(np.float32)
        out, st = _run_moe(
            params, x, stats=True, capacity_factor=0.5, wire="fp32"
        )
        want, hist, dropped = _oracle(params, x, 0.5)
        assert dropped > 0  # the gate actually bites at cf=0.5
        out = np.asarray(out)
        drop_rows = np.all(want == 0.0, axis=2)
        np.testing.assert_array_equal(out[drop_rows], 0.0)
        assert float(st.dropped) == dropped
        np.testing.assert_array_equal(np.asarray(st.expert_tokens), hist)

    def test_routing_identical_across_wires(self, hvd):
        """The acceptance gate: flat-fp32 vs hier-int8 route the SAME
        tokens to the SAME experts (stats bitwise equal) and outputs
        agree within the documented quanta bound (docs/perf.md)."""
        rng = np.random.default_rng(7)
        params = _full_params(jax.random.PRNGKey(2))
        x = rng.normal(size=(8, 8, 16)).astype(np.float32)
        base, st0 = _run_moe(
            params, x, stats=True, capacity_factor=1.25, wire="fp32"
        )
        out8, st8 = _run_moe(
            params, x, stats=True, capacity_factor=1.25,
            wire="int8", hier=STAGES_84, seed=3,
        )
        np.testing.assert_array_equal(
            np.asarray(st0.expert_tokens), np.asarray(st8.expert_tokens)
        )
        assert float(st0.dropped) == float(st8.dropped)
        base, out8 = np.asarray(base), np.asarray(out8)
        # two lossy hops (dispatch + return) on inter-slice tokens:
        # a few quanta through a Lipschitz FFN — bounded loosely but
        # far below the signal scale
        scale = np.abs(base).max()
        assert np.abs(out8 - base).max() <= 0.15 * scale
        assert np.abs(out8 - base).mean() <= 0.01 * scale

    def test_hier_fp32_bitexact_vs_flat(self, hvd):
        rng = np.random.default_rng(8)
        params = _full_params(jax.random.PRNGKey(3))
        x = rng.normal(size=(8, 8, 16)).astype(np.float32)
        a = _run_moe(params, x, capacity_factor=1.25, wire="fp32")
        b = _run_moe(
            params, x, capacity_factor=1.25, wire="fp32",
            hier=STAGES_84,
        )
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_join_mask(self, hvd):
        """A masked-out rank contributes no tokens and outputs zeros;
        live ranks are bit-identical to the unmasked run (their
        routing and capacity fills are local)."""
        rng = np.random.default_rng(9)
        params = _full_params(jax.random.PRNGKey(4))
        x = rng.normal(size=(8, 6, 16)).astype(np.float32)
        mask = np.array([True] * 7 + [False])
        base = np.asarray(_run_moe(params, x, capacity_factor=2.0))
        out, st = _run_moe(
            params, x, stats=True, capacity_factor=2.0, mask=mask
        )
        out = np.asarray(out)
        np.testing.assert_array_equal(out[7], 0.0)
        np.testing.assert_array_equal(out[:7], base[:7])
        assert float(st.total) == 7 * 6

    def test_process_set(self, hvd):
        ps = hvd.add_process_set([0, 2, 4, 5])
        rng = np.random.default_rng(10)
        params = _full_params(jax.random.PRNGKey(5))
        x = rng.normal(size=(8, 8, 16)).astype(np.float32)
        out, st = _run_moe(
            params, x, stats=True, capacity_factor=2.0,
            process_set=ps,
        )
        out = np.asarray(out)
        for r in (1, 3, 6, 7):
            np.testing.assert_array_equal(out[r], 0.0)
        want, hist, dropped = _oracle(
            params, x, 2.0, member_ranks=[0, 2, 4, 5]
        )
        np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
        np.testing.assert_array_equal(np.asarray(st.expert_tokens), hist)
        # experts used all belong to member ranks
        used = np.nonzero(np.asarray(st.expert_tokens))[0]
        assert set(used // 2) <= {0, 2, 4, 5}
        hvd.remove_process_set(ps)

    def test_lowered_hier_int8_structure(self, hvd):
        """The compiled MoE step's dispatch is two-level: every
        all_to_all is group-limited (intra or inter), none spans the
        world — the acceptance criterion's structural gate."""
        params = _full_params(jax.random.PRNGKey(6))
        x = np.zeros((8, 8, 16), np.float32)

        def body(p, v):
            return moe_ffn(
                p, v[0], capacity_factor=1.25, wire="int8",
                hier=STAGES_84,
            )[None]

        low = _sm(body, (_PARAM_SPEC, P("ep")), P("ep")).lower(
            params, jnp.asarray(x)
        )
        sizes = _a2a_group_sizes(low)
        assert sizes, "expected group-limited all_to_all ops"
        assert all(s < 8 for s in sizes), sizes
        # intra legs of L=4 and inter legs of H=2, nothing else
        assert set(sizes) <= {4, 2}, sizes

        # the flat wire is the monolithic baseline: every all_to_all
        # spans the world
        def flat(p, v):
            return moe_ffn(p, v[0], capacity_factor=1.25, wire="fp32")[None]

        flat_sizes = _a2a_group_sizes(
            _sm(flat, (_PARAM_SPEC, P("ep")), P("ep")).lower(
                params, jnp.asarray(x)
            )
        )
        assert flat_sizes and all(s == 8 for s in flat_sizes), flat_sizes

    def test_int8_wire_differentiates_straight_through(self, hvd):
        """grad through the int8 wire: the custom_vjp routes the
        cotangent through the exact inverse exchange — gradients are
        finite, nonzero, and close to the fp32 wire's."""
        params = _full_params(jax.random.PRNGKey(7))
        rng = np.random.default_rng(11)
        x = rng.normal(size=(8, 8, 16)).astype(np.float32)

        def make(wire, hier):
            def body(p, v):
                def loss(vv):
                    o = moe_ffn(
                        p, vv, capacity_factor=2.0, wire=wire,
                        hier=hier, seed=2,
                    )
                    return jnp.sum(o * o)

                l, g = jax.value_and_grad(loss)(v[0])
                return jax.lax.psum(l, "ep")[None], g[None]

            return _sm(body, (_PARAM_SPEC, P("ep")), (P("ep"), P("ep")))

        _, g_fp = make("fp32", None)(params, x)
        _, g_q = make("int8", STAGES_84)(params, x)
        g_fp, g_q = np.asarray(g_fp), np.asarray(g_q)
        assert np.isfinite(g_q).all()
        assert np.abs(g_q).max() > 0
        scale = np.abs(g_fp).max()
        assert np.abs(g_q - g_fp).max() <= 0.25 * scale


# ------------------------------------------- capacity-factor autotune


class TestCapacityTuner:
    def _feed(self, tuner, key, cand, drop_frac, seconds):
        hist = [10.0, 10.0, 40.0, 10.0]
        total = 100.0
        tuner.observe_load(
            key, cand, hist, dropped=total * drop_frac, total=total,
            seconds=seconds,
        )

    def test_explore_then_exploit_by_goodput(self):
        from horovod_tpu.common.autotune import CapacityTuner

        t = CapacityTuner(trials=2, candidates=(1.0, 2.0))
        key = ("moe", 64)
        seen = [t.choose(key) for _ in range(1)]
        # explore: feed both candidates their trials; 1.0 keeps fewer
        # tokens but is MUCH faster -> higher kept-token goodput
        for _ in range(2):
            self._feed(t, key, 1.0, drop_frac=0.1, seconds=0.1)
            self._feed(t, key, 2.0, drop_frac=0.0, seconds=1.0)
        assert t.choose(key) == 1.0
        assert seen[0] in (1.0, 2.0)

    def test_drop_rate_prior_overrides_goodput(self):
        from horovod_tpu.common.autotune import CapacityTuner

        t = CapacityTuner(
            trials=1, candidates=(1.0, 2.0), max_drop_rate=0.2
        )
        key = ("moe", 64)
        # 1.0 is faster but drops 40% — past the bound, never exploited
        self._feed(t, key, 1.0, drop_frac=0.4, seconds=0.1)
        self._feed(t, key, 2.0, drop_frac=0.0, seconds=1.0)
        assert t.choose(key) == 2.0
        assert t.drop_rate(key, 1.0) == pytest.approx(0.4)

    def test_drop_curve_of_real_steps_is_monotone(self, hvd):
        """The tuner fed from ``moe_ffn``'s own stats: more capacity
        never drops more, and the factor it settles on is a candidate."""
        from horovod_tpu.common.autotune import CapacityTuner

        rng = np.random.default_rng(8)
        params = _full_params(jax.random.PRNGKey(3))
        x = rng.normal(size=(8, 12, 16)).astype(np.float32)
        t = CapacityTuner(trials=1, candidates=(0.5, 1.0, 2.0))
        key = ("moe", 8, 12, 16)
        while t.needs_trial(key, t.choose(key)):
            cf = t.choose(key)
            _, st = _run_moe(
                params, x, stats=True, capacity_factor=cf, wire="fp32"
            )
            t.observe_load(
                key, cf, np.asarray(st.expert_tokens),
                dropped=float(st.dropped), total=float(st.total),
                seconds=1.0,
            )
        drops = [t.drop_rate(key, c) for c in sorted(t.candidates)]
        assert drops[0] > 0  # cf=0.5 bites
        assert all(a >= b for a, b in zip(drops, drops[1:])), drops
        assert t.choose(key) in t.candidates

    def test_all_over_bound_takes_largest(self):
        from horovod_tpu.common.autotune import CapacityTuner

        t = CapacityTuner(
            trials=1, candidates=(1.0, 1.5), max_drop_rate=0.05
        )
        key = ("k",)
        self._feed(t, key, 1.0, drop_frac=0.5, seconds=0.1)
        self._feed(t, key, 1.5, drop_frac=0.3, seconds=0.1)
        assert t.choose(key) == 1.5

    def test_imbalance_meter(self):
        from horovod_tpu.common.autotune import CapacityTuner

        t = CapacityTuner(trials=1)
        key = ("k",)
        t.observe_load(key, 1.25, [10.0, 10.0, 40.0, 10.0], 30.0, 100.0)
        # hottest expert 40 vs mean kept 70/4
        assert t.imbalance(key, 1.25) == pytest.approx(40.0 / 17.5)

    def test_state_roundtrip(self, tmp_path, monkeypatch):
        from horovod_tpu.common.autotune import (
            CapacityTuner,
            persist,
            warm_start,
        )

        monkeypatch.setenv("HOROVOD_TUNER_CACHE", str(tmp_path))
        t = CapacityTuner(trials=1, candidates=(1.0, 2.0))
        key = ("moe", 64)
        self._feed(t, key, 1.0, drop_frac=0.1, seconds=0.1)
        self._feed(t, key, 2.0, drop_frac=0.0, seconds=1.0)
        path = persist(t, "capacity")
        assert path and os.path.exists(path)
        t2 = CapacityTuner(trials=1, candidates=(1.0, 2.0))
        assert warm_start(t2, "capacity") > 0
        # warm-started: no candidate needs a trial, drop ledger intact
        assert not t2.needs_trial(key, 1.0)
        assert not t2.needs_trial(key, 2.0)
        assert t2.drop_rate(key, 1.0) == pytest.approx(0.1)
        assert t2.choose(key) == t.choose(key)


class TestTunerPersistence:
    def test_wire_tuner_roundtrip_skips_trials(self, tmp_path, monkeypatch):
        from horovod_tpu.common.autotune import (
            WireTuner,
            persist,
            warm_start,
        )

        monkeypatch.setenv("HOROVOD_TUNER_CACHE", str(tmp_path))
        t = WireTuner(min_int8_bytes=0, trials=2)
        key = ("alltoall", 1 << 20, "float32", "inter")
        for cand, secs in (("fp32", 1.0), ("bf16", 0.6), ("int8", 0.3)):
            for _ in range(2):
                t.record(key, cand, 1 << 20, secs)
        assert persist(t, "wire") is not None
        t2 = WireTuner(min_int8_bytes=0, trials=2)
        assert warm_start(t2, "wire") == 3
        for cand in ("fp32", "bf16", "int8"):
            assert not t2.needs_trial(key, cand)
        assert t2.choose(key, payload_bytes=1 << 20) == "int8"

    def test_live_observations_beat_disk(self, tmp_path, monkeypatch):
        from horovod_tpu.common.autotune import (
            WireTuner,
            persist,
            warm_start,
        )

        monkeypatch.setenv("HOROVOD_TUNER_CACHE", str(tmp_path))
        t = WireTuner(min_int8_bytes=0, trials=1)
        t.record(("k",), "fp32", 100, 1.0)
        persist(t, "wire")
        t2 = WireTuner(min_int8_bytes=0, trials=1)
        t2.record(("k",), "fp32", 999, 1.0)  # live entry
        warm_start(t2, "wire")
        assert t2.goodput(("k",), "fp32") == pytest.approx(999.0)

    def test_persist_merges_with_disk(self, tmp_path, monkeypatch):
        """Two tuners legitimately share the ``wire`` file (fused
        allreduce keys + trace-time alltoall keys); the second atexit
        writer must MERGE, not clobber, the first's observations."""
        from horovod_tpu.common.autotune import (
            WireTuner,
            persist,
            warm_start,
        )

        monkeypatch.setenv("HOROVOD_TUNER_CACHE", str(tmp_path))
        a = WireTuner(min_int8_bytes=0, trials=1)
        a.record(("allreduce", 4096, "float32"), "bf16", 4096, 0.1)
        persist(a, "wire")
        b = WireTuner(min_int8_bytes=0, trials=1)
        b.record(("alltoall", 4096, "float32", "inter"), "int8", 4096, 0.1)
        persist(b, "wire")  # never saw a's entry
        c = WireTuner(min_int8_bytes=0, trials=1)
        assert warm_start(c, "wire") == 2
        assert not c.needs_trial(("allreduce", 4096, "float32"), "bf16")
        assert not c.needs_trial(
            ("alltoall", 4096, "float32", "inter"), "int8"
        )

    def test_overlap_tuner_persistence_parity(
        self, tmp_path, monkeypatch
    ):
        """PR 14 satellite (ROADMAP item 1a slice): the OverlapTuner
        rides the same warm_start/persist machinery as the WireTuner —
        roundtrip skips trials, and persist MERGES with disk (the
        WireTuner merge test, overlap edition)."""
        from horovod_tpu.common.autotune import (
            OverlapTuner,
            persist,
            warm_start,
        )

        monkeypatch.setenv("HOROVOD_TUNER_CACHE", str(tmp_path))
        a = OverlapTuner(min_bucket_bytes=0, trials=1, candidates=(1, 4))
        a.record(("step",), 1, 1 << 20, 2.0)
        a.record(("step",), 4, 1 << 20, 1.0)
        assert persist(a, "overlap") is not None
        b = OverlapTuner(min_bucket_bytes=0, trials=1, candidates=(1, 8))
        b.record(("step",), 8, 1 << 20, 0.5)
        persist(b, "overlap")  # never saw a's entries: must merge
        c = OverlapTuner(
            min_bucket_bytes=0, trials=1, candidates=(1, 4, 8)
        )
        assert warm_start(c, "overlap") == 3
        for cand in (1, 4, 8):
            assert not c.needs_trial(("step",), cand)
        assert c.choose(("step",), 1 << 20) == 8

    def test_capacity_tuner_merge_on_persist(
        self, tmp_path, monkeypatch
    ):
        """Capacity edition of the merge test — including the load
        ledger (drop-rate prior survives the merge)."""
        from horovod_tpu.common.autotune import (
            CapacityTuner,
            persist,
            warm_start,
        )

        monkeypatch.setenv("HOROVOD_TUNER_CACHE", str(tmp_path))
        a = CapacityTuner(trials=1, candidates=(1.0, 2.0))
        a.observe_load(("m",), 1.0, [50.0, 50.0], 30.0, 130.0, seconds=0.1)
        persist(a, "capacity")
        b = CapacityTuner(trials=1, candidates=(1.0, 2.0))
        b.observe_load(("m",), 2.0, [65.0, 65.0], 0.0, 130.0, seconds=0.2)
        persist(b, "capacity")
        c = CapacityTuner(trials=1, candidates=(1.0, 2.0))
        assert warm_start(c, "capacity") == 2
        assert not c.needs_trial(("m",), 1.0)
        assert not c.needs_trial(("m",), 2.0)
        assert c.drop_rate(("m",), 1.0) == pytest.approx(30.0 / 130.0)

    def test_shared_accessors_warm_start_and_register(
        self, tmp_path, monkeypatch
    ):
        """shared_overlap_tuner / shared_capacity_tuner warm-start
        from the fingerprinted cache on first use and are registered
        for persist-at-exit (the FusionManager's WireTuner contract,
        extended)."""
        from horovod_tpu.common import autotune
        from horovod_tpu.common.autotune import (
            CapacityTuner,
            OverlapTuner,
            persist,
        )

        monkeypatch.setenv("HOROVOD_TUNER_CACHE", str(tmp_path))
        seed_o = OverlapTuner(min_bucket_bytes=0, trials=1)
        seed_o.record(("k",), 4, 100, 1.0)
        persist(seed_o, "overlap")
        seed_c = CapacityTuner(trials=1)
        seed_c.record(("k",), 1.25, 100, 1.0)
        persist(seed_c, "capacity")
        autotune.reset_shared_tuners()
        try:
            ot = autotune.shared_overlap_tuner(
                min_bucket_bytes=0, trials=1
            )
            assert not ot.needs_trial(("k",), 4)
            assert autotune.shared_overlap_tuner() is ot
            ct = autotune.shared_capacity_tuner(trials=1)
            assert not ct.needs_trial(("k",), 1.25)
            registered = {
                name for _, (_, name) in autotune._persist_registry
            }
            assert {"overlap", "capacity"} <= registered
        finally:
            autotune.reset_shared_tuners()

    def test_corrupt_cache_reads_zero(self, tmp_path, monkeypatch):
        from horovod_tpu.common.autotune import (
            WireTuner,
            tuner_cache_path,
            warm_start,
        )

        monkeypatch.setenv("HOROVOD_TUNER_CACHE", str(tmp_path))
        path = tuner_cache_path("wire")
        with open(path, "w") as f:
            f.write("\xff not json {")
        assert warm_start(WireTuner(), "wire") == 0

    def test_no_cache_dir_is_noop(self, monkeypatch):
        from horovod_tpu.common.autotune import (
            WireTuner,
            persist,
            tuner_cache_path,
            warm_start,
        )

        monkeypatch.delenv("HOROVOD_TUNER_CACHE", raising=False)
        assert tuner_cache_path("wire") is None
        assert persist(WireTuner(), "wire") is None
        assert warm_start(WireTuner(), "wire") == 0

    def test_fingerprint_pins_topology(self, hvd):
        from horovod_tpu.common.autotune import topology_fingerprint

        fp = topology_fingerprint()
        assert fp.startswith("w8-") and fp.endswith("-cpu")

    def test_fusion_manager_warm_starts(self, tmp_path, monkeypatch, hvd):
        from horovod_tpu.common.autotune import WireTuner, persist
        from horovod_tpu.ops.fusion import FusionManager

        monkeypatch.setenv("HOROVOD_TUNER_CACHE", str(tmp_path))
        seed_tuner = WireTuner(trials=3)
        key = ("allreduce", 4096, "float32")
        for _ in range(3):
            seed_tuner.record(key, "bf16", 4096, 0.1)
            seed_tuner.record(key, "fp32", 4096, 0.5)
            seed_tuner.record(key, "int8", 4096, 0.9)
        persist(seed_tuner, "wire")
        mgr = FusionManager(
            hvd.mesh(), threshold_bytes=1 << 20, cycle_time_ms=1.0,
            wire="auto",
        )
        assert mgr.wire_tuner is not None
        assert not mgr.wire_tuner.needs_trial(key, "bf16")
        assert mgr.wire_tuner.choose(key, payload_bytes=4096) == "bf16"


# --------------------------------------------- alltoall observability


class TestAlltoallObservability:
    def test_eager_alltoall_reaches_registry(self, hvd):
        from horovod_tpu.common import basics
        from horovod_tpu.common.metrics import registry

        registry.reset()
        x = np.stack(
            [np.full((8, 4), r, np.float32) for r in range(8)]
        )
        hvd.alltoall(x)
        snap = registry.snapshot()
        assert snap.get("alltoall.dispatches", 0) >= 1
        assert snap.get("alltoall.wire_bytes", 0) > 0
        stats = basics.state().fusion.cache_stats()
        assert stats["alltoall_dispatches"] >= 1
        assert stats["alltoall_wire_bytes"] > 0

    def test_legend_and_counter_keys(self):
        from horovod_tpu.common.metrics import MOE_METRICS
        from horovod_tpu.common.telemetry import _COUNTER_KEYS

        assert "alltoall.dispatches" in MOE_METRICS
        assert "alltoall.wire_bytes" in MOE_METRICS
        assert "alltoall.dispatches" in _COUNTER_KEYS
        assert "alltoall.wire_bytes" in _COUNTER_KEYS
        assert "moe.dropped_tokens" in _COUNTER_KEYS

    def test_publish_moe(self):
        from horovod_tpu.common.metrics import publish_moe, registry

        registry.reset()
        publish_moe(
            [10.0, 30.0, 10.0, 10.0], dropped=5.0, total=65.0,
            capacity_factor=1.5,
        )
        snap = registry.snapshot()
        assert snap["moe.dropped_tokens"] == 5.0
        assert snap["moe.routed_tokens"] == 65.0
        assert snap["moe.expert_tokens_max"] == 30.0
        assert snap["moe.imbalance"] == pytest.approx(30.0 / 15.0)
        assert snap["moe.drop_rate"] == pytest.approx(5.0 / 65.0)
        assert snap["moe.capacity_factor"] == 1.5

    def test_step_record_carries_alltoall_delta(self, hvd):
        from horovod_tpu.common.telemetry import TelemetryHub

        hub = TelemetryHub(capacity=8)
        hub.step_begin(step=1)
        x = np.stack(
            [np.full((8, 4), r, np.float32) for r in range(8)]
        )
        hvd.alltoall(x)
        rec = hub.step_end()
        assert rec["alltoall.dispatches"] >= 1
        assert rec["alltoall.wire_bytes"] > 0


# ------------------------------------------------ expert-load KV feed


class TestExpertLoadKV:
    def test_roundtrip_and_malformed(self):
        from horovod_tpu.runner.rendezvous import (
            EXPERT_LOAD_SCOPE,
            KVStore,
            put_expert_load,
            read_expert_loads,
        )

        store = KVStore()
        put_expert_load(
            store, 3, [1.0, 2.0], dropped=1.0, total=4.0,
            capacity_factor=1.5,
        )
        store.put(EXPERT_LOAD_SCOPE, "9", b"\xff not json")
        store.put(
            EXPERT_LOAD_SCOPE, "bad", json.dumps({"x": 1}).encode()
        )
        loads = read_expert_loads(store)
        assert list(loads) == [3]
        assert loads[3]["expert_tokens"] == [1.0, 2.0]
        assert loads[3]["capacity_factor"] == 1.5

    def test_worker_helpers_degrade_outside_elastic(self, monkeypatch):
        from horovod_tpu.elastic import worker as worker_mod

        monkeypatch.delenv("HOROVOD_GLOO_RENDEZVOUS_ADDR", raising=False)
        worker_mod._reset_rebalance_cache()
        assert not worker_mod.publish_expert_load([1.0], 0.0, 1.0)
        assert worker_mod.expert_loads() == {}

    def test_driver_aggregates_gauges(self, monkeypatch):
        import types

        from horovod_tpu.common.metrics import registry
        from horovod_tpu.elastic.discovery import HostDiscovery
        from horovod_tpu.elastic.driver import ElasticDriver
        from horovod_tpu.runner.hosts import HostInfo
        from horovod_tpu.runner.rendezvous import (
            KVStore,
            put_expert_load,
        )

        class Disc(HostDiscovery):
            def find_available_hosts_and_slots(self):
                return [HostInfo("a", 4)]

        d = ElasticDriver(Disc(), ["true"], min_np=1)
        d._server = types.SimpleNamespace(store=KVStore())
        put_expert_load(
            d._server.store, 0, [10.0, 30.0], dropped=10.0, total=50.0
        )
        put_expert_load(
            d._server.store, 1, [0.0, 40.0], dropped=0.0, total=40.0
        )
        registry.reset()
        d._poll_expert_loads()
        snap = registry.snapshot()
        assert snap["driver.expert_load.ranks"] == 2
        # fleet hist [10, 70], kept 80, mean 40 -> imbalance 1.75
        assert snap["driver.expert_load.imbalance"] == pytest.approx(1.75)
        assert snap["driver.expert_load.drop_rate"] == pytest.approx(
            10.0 / 90.0
        )
        # staleness: a rank whose ts stops ADVANCING ages out of the
        # gauges (departed-rank blob must not skew the fleet forever)
        from horovod_tpu.elastic import driver as driver_mod

        monkeypatch.setattr(driver_mod, "_EXPERT_LOAD_STALE_S", 0.0)
        put_expert_load(
            d._server.store, 0, [20.0, 20.0], dropped=0.0, total=40.0
        )  # rank 0 advances; rank 1's blob is frozen
        d._poll_expert_loads()
        snap = registry.snapshot()
        assert snap["driver.expert_load.ranks"] == 1
        assert snap["driver.expert_load.drop_rate"] == 0.0


# --------------------------------------------------- serve MoE decode


def _moe_model(vocab=64):
    from horovod_tpu.models.transformer import (
        Transformer,
        TransformerConfig,
    )

    cfg = TransformerConfig(
        vocab_size=vocab, num_layers=2, d_model=32, num_heads=4,
        d_ff=64, max_len=64, causal=True, dtype=jnp.float32,
        flash_attention=False, moe_experts=4,
    )
    model = Transformer(cfg)
    params = model.init(
        jax.random.PRNGKey(0), np.zeros((1, 4), np.int32)
    )["params"]
    return model, params


class TestServeMoE:
    def test_zero_retrace_across_rolling_admissions(self, hvd):
        from horovod_tpu.serving.batcher import ContinuousBatcher
        from horovod_tpu.serving.engine import InferenceEngine

        model, params = _moe_model()
        eng = InferenceEngine(model, params, slots=4, max_len=64)
        b = ContinuousBatcher(eng)
        rng = np.random.default_rng(0)
        reqs = [
            b.submit(
                rng.integers(0, 64, size=n).tolist(), max_new_tokens=6
            )
            for n in (5, 9, 3)
        ]
        for _ in range(40):
            b.step()
        # rolling admissions into freed slots: still ONE decode program
        reqs += [
            b.submit(
                rng.integers(0, 64, size=n).tolist(), max_new_tokens=4
            )
            for n in (7, 2)
        ]
        for _ in range(40):
            b.step()
        s = eng.stats()
        assert s["decode_compiles"] == 1, s
        assert all(r.status == "done" for r in reqs)
        assert all(len(r.out_tokens) > 0 for r in reqs)

    def test_paged_slab_parity(self, hvd):
        """MoE decode is bit-identical between the paged pool and the
        slab — routing is a pure function of values the two layouts
        agree on. Slot 1 (11 prompt tokens) crosses into its second
        16-token page at the sixth step."""
        from horovod_tpu.serving.engine import InferenceEngine

        model, params = _moe_model()
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 64, size=n).tolist() for n in (6, 11)]

        def run(paged):
            eng = InferenceEngine(
                model, params, slots=2, max_len=64, paged=paged
            )
            toks = np.zeros(2, np.int32)
            # slots come from the manager, as the batcher takes them:
            # the pre-decode page sweep maps a frontier page for owned
            # slots only, and a slot that nobody owns loses its writes
            # past its first page
            slots = [eng.manager.alloc(f"r{i}") for i in range(2)]
            for slot, p in zip(slots, prompts):
                toks[slot] = eng.prefill(slot, p)
            outs = [list() for _ in prompts]
            for _ in range(8):
                for s in slots:
                    outs[s].append(int(toks[s]))
                toks = eng.decode_step(toks)
                for s in slots:
                    eng.manager.advance(s)
            return outs

        assert run(True) == run(False)

    def test_shard_moe_params(self, hvd):
        from horovod_tpu.models.transformer import shard_moe_params

        model, params = _moe_model()
        mesh = Mesh(np.asarray(jax.devices()[:4]), ("ep",))
        sharded = shard_moe_params(params, mesh, "ep")
        leaf = sharded["block_0"]["moe"]["w1"]
        assert leaf.sharding.spec == P("ep")
        # the router stays replicated
        router = sharded["block_0"]["moe"]["router"]["kernel"]
        assert getattr(router.sharding, "spec", P()) in (P(), P(None))
        # outputs match the replicated params bitwise on one forward
        toks = np.zeros((1, 4), np.int32)
        a = model.apply({"params": params}, toks, train=False)
        b_ = model.apply({"params": sharded}, toks, train=False)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
        # a mesh without the axis is a no-op; non-dividing is loud
        assert shard_moe_params(params, None, "ep") is params
        mesh3 = Mesh(np.asarray(jax.devices()[:3]), ("ep",))
        with pytest.raises(ValueError, match="divide"):
            shard_moe_params(params, mesh3, "ep")

    def test_moe_ffn_emits_cfg_dtype(self, hvd):
        """The MoE branch must honor the dense branch's activation
        contract: cfg.dtype out, not the fp32 LayerNorm input dtype."""
        from horovod_tpu.models.transformer import (
            MoEFFN,
            TransformerConfig,
        )

        cfg = TransformerConfig(
            vocab_size=32, num_layers=1, d_model=16, num_heads=2,
            d_ff=32, max_len=16, dtype=jnp.bfloat16,
            flash_attention=False, moe_experts=4,
        )
        m = MoEFFN(cfg)
        x = jnp.zeros((1, 4, 16), jnp.float32)  # the LN output dtype
        params = m.init(jax.random.PRNGKey(0), x)
        out = m.apply(params, x)
        assert out.dtype == jnp.bfloat16

    def test_moe_off_keeps_param_tree(self, hvd):
        """moe_experts=0 is the exact pre-PR model — checkpoints stay
        layout-compatible."""
        from horovod_tpu.models.transformer import (
            Transformer,
            TransformerConfig,
        )

        cfg = TransformerConfig(
            vocab_size=32, num_layers=1, d_model=16, num_heads=2,
            d_ff=32, max_len=16, dtype=jnp.float32,
            flash_attention=False,
        )
        params = Transformer(cfg).init(
            jax.random.PRNGKey(0), np.zeros((1, 4), np.int32)
        )["params"]
        assert "moe" not in params["block_0"]
        assert "Dense_0" in params["block_0"]


# ------------------------------------- parallel transformer threading


class TestParallelThreading:
    @pytest.mark.parametrize("wire", ["fp32", "int8"])
    def test_train_step_with_expert_wire(self, hvd, wire):
        from horovod_tpu.parallel import transformer as ptf

        stages = topo_mod.hierarchical_stage_groups(4, 2)
        cfg = ptf.ParallelTransformerConfig(
            vocab_size=64, num_layers=2, d_model=32, num_heads=2,
            d_ff=64, max_len=32, n_experts=4, n_microbatches=1,
            moe_wire=wire, moe_hier=stages if wire == "int8" else None,
        )
        mesh = Mesh(
            np.asarray(jax.devices()[:8]).reshape(2, 1, 4, 1, 1),
            ("dp", "pp", "ep", "sp", "tp"),
        )
        params = ptf.make_sharded_params(cfg, mesh, jax.random.PRNGKey(0))
        step = ptf.make_train_step(cfg, mesh)
        rng = np.random.default_rng(0)
        toks = rng.integers(0, 64, size=(8, 32)).astype(np.int32)
        labs = rng.integers(0, 64, size=(8, 32)).astype(np.int32)
        params, loss = step(params, toks, labs)
        l0 = float(loss)
        assert np.isfinite(l0)
        for _ in range(3):
            params, loss = step(params, toks, labs)
        assert float(loss) < l0
