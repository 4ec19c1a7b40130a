"""Eager-mode dispatch: tensor queue, fusion buffer, cycle batching, handles.

This module is the TPU re-design of the reference's core machine —
background loop + tensor queue + fusion buffer + response cache
(ref: horovod/common/operations.cc RunLoopOnce, tensor_queue.cc,
fusion_buffer_manager.cc, response_cache.cc [V]; SURVEY.md §2.1, §3.2) —
re-thought for a single controller:

* No negotiation: every process sees the same eager dispatch order, so
  tensor-readiness agreement is structural. What the reference's controller
  negotiates dynamically, the single controller knows trivially.
* Fusion survives: many small eager collectives are still slow if dispatched
  one XLA executable each. Entries accumulate in a queue; a *cycle* flush
  batches same-key collectives (`HOROVOD_FUSION_THRESHOLD` caps each
  fused batch, `HOROVOD_CYCLE_TIME` bounds queue latency — same env
  contract, same semantics). Fusion covers the whole collective family:
  allreduce AND same-key broadcast / allgather / reducescatter groups
  ride the same pack → collective → unpack machinery.
* One fused cycle is ONE compiled XLA executable (the in-JIT pack path,
  `HOROVOD_FUSION_INJIT`, default on): the cached executor takes the
  batch's raw per-entry tensors as arguments and performs the
  flatten/concat pack, the collective, and the per-entry split/reshape
  unpack entirely inside `jax.jit`. XLA fuses the pack into the
  collective's producer and the unpack into its consumers (the EQuARX
  observation, arXiv 2506.17615), eliminating the two extra full HBM
  passes and the ~2N Python dispatches the host-side pack paid. Inputs
  are donated (`HOROVOD_FUSION_DONATE`, default auto: on for TPU/GPU)
  so the fusion buffer aliases the argument storage instead of doubling
  peak HBM — eager collectives CONSUME their inputs on backends with
  donation support, matching the reference's in-place `allreduce_`
  semantics.
* The executor cache is stabilized under batch-composition churn by
  SHAPE BUCKETING (`HOROVOD_FUSION_BUCKETS`, default on): the fused
  buffer's per-rank row is rounded up to the next power-of-two element
  count (zero-pad tail, sliced off inside the program; zero is the
  identity of every supported reduction, Adasum's inner products
  included) and executors are cached in two tiers —

    exact tier  (op-key, bucket, per-entry shape tuple) → the fused
                in-JIT executable, one dispatch per batch, packed
                UNPADDED (its key pins the shapes, so padding would
                only put dead zeros on the wire of a stable job);
    bucket tier (op-key, bucket)                        → a padded
                buffer → buffer collective program, composition-
                independent.

  A batch whose exact composition is cached dispatches the single
  fused executable. A NEW composition inside an already-seen bucket
  falls back to the bucket-tier program (host-side pack into the
  padded buffer — the pre-rework dispatch path) instead of compiling,
  so a long eager job with a drifting tensor set stops recompiling
  every cycle; compositions seen `HOROVOD_FUSION_PROMOTE_AFTER` times
  (default 2) are promoted to their own exact executable. Padding cost
  is observable: `bucket_pad_bytes`, per-cycle pad, recompile and
  dispatch counts all land in cache_stats()/common.metrics, and the
  autotune parameter manager is fed useful-vs-wire bytes so the GP
  scores goodput, not padded throughput.
* The response cache's job (skip re-negotiation for repeating tensor
  sets) is played by this executor cache: repeated (op, dtype, shape)
  batches hit an already-compiled XLA executable
  (`HOROVOD_CACHE_CAPACITY` bounds both tiers via one LRU).
* The fused buffer can traverse the wire QUANTIZED
  (`HOROVOD_FUSION_WIRE={fp32,bf16,int8,auto}`): on the int8 wire the
  compiled program block-quantizes the packed buffer (one scale per
  `HOROVOD_FUSION_WIRE_BLOCK` elements, stochastic rounding seeded per
  rank and dispatch), runs the quantized reduce-scatter/all-gather
  recipe of `traced.quantized_allreduce`, and dequantizes before the
  unpack — quantize once per BATCH instead of once per tensor, still
  exactly one dispatch, ~4x fewer wire bytes for fp32 payloads
  (EQuARX, arXiv 2506.17615). `auto` picks the format per bucket tier
  online by goodput (common/autotune.py WireTuner); `bf16` moves the
  buffer as a half-width cast; `HOROVOD_FUSION_WIRE_HIER` places bf16
  on the intra-host stage and int8 on the cross-host stage only.
  Error-feedback residuals are sliced per entry from the fused
  residual buffer (`allreduce(..., return_residual=True)`), so EF
  composes with fusion.
* Flushing is cooperative (on enqueue-over-threshold, cycle expiry at next
  enqueue, or synchronize()) — there is no background thread to race with
  JAX dispatch.

Handles reproduce the async API: `allreduce_async_` returns a handle;
`synchronize(handle)` blocks (ref: horovod/torch/handle_manager.cc [V]).
"""

from __future__ import annotations

import dataclasses
import re
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from ..common.topology import WORLD_AXIS
from ..common.process_sets import ProcessSet
from ..common.logging import get_logger
from ..analysis import sched_audit as _sched_audit
from .reduction_ops import Average, Sum, Adasum, Min, Max, Product, ReduceOp

_log = get_logger("fusion")


@dataclasses.dataclass
class _Entry:
    """One pending collective (ref: TensorTableEntry in common.h [V])."""

    name: str
    kind: str  # 'allreduce' | 'allgather' | 'broadcast' | 'alltoall' | 'reducescatter'
    payload: Any  # rank-major jax.Array [world, ...]
    op: ReduceOp = Average
    prescale: float = 1.0
    postscale: float = 1.0
    root_rank: int = 0
    process_set: Optional[ProcessSet] = None
    mask: Optional[np.ndarray] = None  # [world] bool; False = rank joined
    extra: Any = None  # op-specific (e.g. uneven-length info)
    handle: "Handle" = None
    enqueue_t: float = 0.0
    group_id: Optional[int] = None  # grouped_allreduce membership
    wire: Optional[str] = None  # per-entry wire override (None = manager)
    wire_block: Optional[int] = None  # per-entry block size (compressor's)
    want_residual: bool = False  # error-feedback carry (int8 wire only)


class Handle:
    """Async completion handle (ref: handle_manager.cc [V])."""

    def __init__(self, fusion: "FusionManager", entry: _Entry):
        self._fusion = fusion
        self._entry = entry
        self._result = None
        self._done = False

    def _fulfill(self, result) -> None:
        self._result = result
        self._done = True

    def poll(self) -> bool:
        """Non-blocking done check; also drives a cooperative cycle tick."""
        if not self._done:
            self._fusion.maybe_cycle()
        return self._done

    def wait(self):
        if not self._done:
            self._fusion.flush()
        assert self._done, "flush did not fulfill handle"
        return self._result


_SCHED_NONAME = re.compile(r"^(\w+)\.noname\.\d+(\..+)?$")


def _sched_entry_name(name: str) -> str:
    """Schedule-fingerprint view of an entry name: auto-generated
    ``<op>.noname.<counter>`` labels collapse to the op prefix — the
    process-global counter only restates dispatch order (which the
    rolling fold already encodes) and would make two identical
    schedules diverge on counter offset alone (e.g. a rejoined worker
    restarting its counter at 0). Grouped entries
    (``<op>.noname.<counter>.<i>``) keep the member index ``<i>`` —
    that part IS schedule identity. User-supplied names fold as-is."""
    m = _SCHED_NONAME.match(name or "")
    if m is None:
        return name or ""
    return m.group(1) + (m.group(2) or "")


def _group_key(e: _Entry) -> Tuple:
    mask_key = None if e.mask is None else e.mask.tobytes()
    pset = 0 if e.process_set is None else e.process_set.process_set_id
    return (
        e.kind,
        int(e.op),
        e.payload.dtype.name,
        e.prescale,
        e.postscale,
        e.root_rank,
        pset,
        mask_key,
        e.extra is not None,  # v-variant allgather never fuses with even
        e.wire,  # entries on different wire formats never share a batch
        e.wire_block,
        e.want_residual,
    )


def _bucket_elems(elems: int, bucketing: bool) -> int:
    """Round a per-rank row length up to the next power of two."""
    if not bucketing or elems <= 1:
        return max(elems, 1)
    return 1 << (elems - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class _BatchPlan:
    """Static pack/unpack geometry of one fused batch."""

    family: str  # 'allreduce' | 'adasum_pset' | 'broadcast' | 'allgather' | 'reducescatter'
    shapes: Tuple[Tuple[int, ...], ...]  # per-entry payload shapes
    dtype: str
    sizes: Tuple[int, ...]  # per-entry packed columns (per-rank-chunk for rs)
    useful: int  # packed columns before padding
    bucket: int  # packed columns after bucketing
    world: int
    n_ranks: int  # participating ranks (world, or process-set size)
    itemsize: int

    @property
    def pad_elems(self) -> int:
        return self.bucket - self.useful

    @property
    def pad_bytes(self) -> int:
        # padding is carried on every rank's row (and every rank chunk
        # for reducescatter, whose pad rides inside each chunk)
        rows = self.world * (
            self.n_ranks if self.family == "reducescatter" else 1
        )
        return self.pad_elems * rows * self.itemsize


@dataclasses.dataclass(frozen=True)
class _ExecSpec:
    """One batch's resolved execution recipe: geometry, cache keys, the
    per-shard core builder, and the wire format the fused buffer will
    traverse the collective in."""

    plan: _BatchPlan
    core_key: Tuple
    builder: Callable
    needs_keep: bool = False  # adasum_pset: dynamic join-mask argument
    needs_seed: bool = False  # quantized wire: per-dispatch rounding seed
    want_res: bool = False  # error-feedback residual outputs
    wire: str = "fp32"  # INTER-hop (or flat) wire: 'fp32' | 'bf16' | 'int8'
    hier_n: Optional[int] = None  # two-level: inter-group (slice) count
    intra_n: Optional[int] = None  # two-level: chips per slice (L)
    intra_wire: str = "fp32"  # two-level: the intra-hop wire format
    tuned: bool = False  # wire chosen by the WireTuner (auto mode)
    block: Optional[int] = None  # int8: elements per block scale


def _make_plan(
    family: str, batch: List[_Entry], world: int, n_ranks: int, bucketing: bool
) -> _BatchPlan:
    shapes = tuple(tuple(e.payload.shape) for e in batch)
    itemsize = int(batch[0].payload.dtype.itemsize)
    if family == "reducescatter":
        sizes = tuple(
            int(np.prod(s[1:], dtype=np.int64)) // n_ranks for s in shapes
        )
    else:
        sizes = tuple(int(np.prod(s[1:], dtype=np.int64)) for s in shapes)
    useful = sum(sizes)
    return _BatchPlan(
        family=family,
        shapes=shapes,
        dtype=batch[0].payload.dtype.name,
        sizes=sizes,
        useful=useful,
        bucket=_bucket_elems(useful, bucketing),
        world=world,
        n_ranks=n_ranks,
        itemsize=itemsize,
    )


def _pack(tensors, plan: _BatchPlan):
    """Flatten + concat + zero-pad the batch into the fused buffer.

    Runs either under `jax.jit` tracing (the in-JIT path — XLA fuses it
    into the collective's producer) or eagerly (the bucket-tier / legacy
    host-pack path). Zero padding is safe for every reduction: zeros are
    the identity of sum/avg contributions and of Adasum's inner
    products, and min/max/product padding lanes are sliced off unread.
    """
    world = plan.world
    if plan.family == "reducescatter":
        # chunk-major layout: [world, n_ranks, chunk]; rank r's result is
        # the concatenation of every entry's r-th chunk
        mats = [t.reshape(world, plan.n_ranks, -1) for t in tensors]
        buf = mats[0] if len(mats) == 1 else jnp.concatenate(mats, axis=2)
        if plan.pad_elems:
            buf = jnp.pad(buf, ((0, 0), (0, 0), (0, plan.pad_elems)))
    else:
        mats = [t.reshape(world, -1) for t in tensors]
        buf = mats[0] if len(mats) == 1 else jnp.concatenate(mats, axis=1)
        if plan.pad_elems:
            buf = jnp.pad(buf, ((0, 0), (0, plan.pad_elems)))
    return buf


def _unpack(out, plan: _BatchPlan):
    """Split the collective's output back into per-entry results,
    slicing the bucket padding off. Inverse of `_pack` modulo each
    family's output geometry."""
    pieces = []
    off = 0
    if plan.family == "allgather":
        # out: [world, n_ranks, bucket] → per entry [world, n_ranks, n, ...]
        for shape, sz in zip(plan.shapes, plan.sizes):
            pieces.append(
                out[:, :, off : off + sz].reshape(
                    (plan.world, plan.n_ranks) + shape[1:]
                )
            )
            off += sz
    elif plan.family == "reducescatter":
        # out: [world, bucket] → per entry [world, n/n_ranks, ...]
        for shape, sz in zip(plan.shapes, plan.sizes):
            pieces.append(
                out[:, off : off + sz].reshape(
                    (plan.world, shape[1] // plan.n_ranks) + tuple(shape[2:])
                )
            )
            off += sz
    else:
        # out: [world, bucket] → per entry payload-shaped
        for shape, sz in zip(plan.shapes, plan.sizes):
            pieces.append(out[:, off : off + sz].reshape(shape))
            off += sz
    return pieces


class FusionManager:
    def __init__(
        self,
        mesh: Mesh,
        threshold_bytes: int,
        cycle_time_ms: float,
        cache_capacity: Optional[int] = None,
        injit_pack: Optional[bool] = None,
        bucketing: Optional[bool] = None,
        donate: Optional[bool] = None,
        promote_after: Optional[int] = None,
        wire: Optional[str] = None,
        wire_block: Optional[int] = None,
        wire_hier: Optional[bool] = None,
        wire_min_bytes: Optional[int] = None,
        guard: Optional[bool] = None,
    ):
        self.mesh = mesh
        self.threshold_bytes = threshold_bytes
        self.cycle_time_ms = cycle_time_ms
        self.world = int(mesh.devices.size)
        self.pending: List[_Entry] = []
        self.pending_bytes = 0
        self.cycle_start: Optional[float] = None
        # attached by basics.init:
        self.timeline = None
        self.stall_inspector = None
        self.parameter_manager = None
        if (
            cache_capacity is None
            or injit_pack is None
            or bucketing is None
            or donate is None
            or promote_after is None
            or wire is None
            or wire_block is None
            or wire_hier is None
            or wire_min_bytes is None
            or guard is None
        ):
            from ..common.config import Config

            cfg = Config.from_env()
            if guard is None:
                guard = cfg.guard
            if cache_capacity is None:
                cache_capacity = cfg.cache_capacity
            if injit_pack is None:
                injit_pack = cfg.fusion_injit
            if bucketing is None:
                bucketing = cfg.fusion_buckets
            if donate is None:
                donate = cfg.fusion_donate
            if promote_after is None:
                promote_after = cfg.fusion_promote_after
            if wire is None:
                wire = cfg.fusion_wire
            if wire_block is None:
                wire_block = cfg.fusion_wire_block
            if wire_hier is None:
                wire_hier = cfg.fusion_wire_hier
            if wire_min_bytes is None:
                wire_min_bytes = cfg.fusion_wire_min_bytes
        self.injit_pack = bool(injit_pack)
        # Non-finite sentinel on the eager data plane (HOROVOD_GUARD /
        # common/guard.py): float allreduce batches fold ONE
        # all(isfinite) scalar over the fused output buffer into the
        # SAME compiled executable. Flags are device scalars collected
        # without syncing; guard_poll() (called from hvd.guard_check /
        # State.commit) is the explicit sync point that counts
        # guard.nonfinite_batches. Detection-only here — eager handles
        # are already fulfilled by flush time, so skip-step semantics
        # belong to the optimizers, not the dispatcher.
        self.guard = bool(guard)
        self._guard_flags: List = []
        self.wire = str(wire)
        self.wire_block = max(int(wire_block), 1)
        self.wire_hier = bool(wire_hier)
        self.wire_min_bytes = int(wire_min_bytes)
        self.wire_tuner = None
        if self.wire == "auto":
            self.wire_tuner = self._make_wire_tuner()
        self.bucketing = bool(bucketing)
        if donate is None:
            # auto: donation is a no-op (plus a warning) on backends
            # without buffer aliasing — enable only where it bites
            platform = getattr(
                mesh.devices.reshape(-1)[0], "platform", "cpu"
            )
            donate = platform in ("tpu", "gpu", "cuda", "rocm")
        self.donate = bool(donate)
        self.promote_after = max(int(promote_after), 1)
        # Executor cache — the response-cache analog, with the
        # reference's HOROVOD_CACHE_CAPACITY semantics enforced (ref:
        # response_cache.cc [V]): ONE LRU bounds both tiers (exact fused
        # executables AND bucket-level core programs), so a long eager
        # job with varying shapes cannot leak compiled executables;
        # capacity 0 disables caching entirely.
        self.cache_capacity = max(int(cache_capacity), 0)
        self._executors: "OrderedDict[Tuple, Callable]" = OrderedDict()
        self._buckets_seen: "OrderedDict[Tuple, None]" = OrderedDict()
        self._comp_seen: "OrderedDict[Tuple, int]" = OrderedDict()
        self.cache_hits = 0  # dispatched a cached executor for the key
        self.cache_misses = 0  # executor builds (exact or bucket tier)
        self.cache_evictions = 0
        # persistent disk tier below exact/bucket (common/exe_cache.py,
        # HOROVOD_EXE_CACHE): a "miss" above may deserialize instead of
        # compile — disk_hits counts those. With no cache dir
        # configured both stay 0 and every build path is byte-identical
        # to the memory-only manager.
        from ..common import exe_cache as _exe_cache

        self._exe_base = _exe_cache.cache_dir()
        self._exe_fp = None  # resolved lazily: topology may not be up
        self.disk_hits = 0
        self.disk_misses = 0
        self.bucket_hits = 0  # exact miss served by the bucket tier
        self.promotions = 0  # compositions promoted to an exact executable
        self.dispatches = 0  # executor invocations, cumulative
        self.last_cycle_dispatches = 0
        self.pad_bytes_total = 0  # cumulative bucket padding on the wire
        self.last_cycle_pad_bytes = 0
        # cumulative payload bytes flushed — with pad/saved totals this
        # lets the telemetry hub reconstruct per-step wire bytes as a
        # snapshot delta (common/telemetry.py StepStats)
        self.flushed_bytes_total = 0
        self.donated_bytes_total = 0
        # quantized-wire observability (payload-width byte model: the
        # fused buffer's wire footprint at the chosen format vs fp32)
        self.wire_bytes_saved_total = 0
        self.last_cycle_wire_saved = 0
        self.quant_blocks_total = 0  # block-scale quantizations performed
        self.last_wire_format = "fp32"  # wire of the most recent dispatch
        # two-level (intra/inter) split of the same ledger — advanced
        # only by hierarchical dispatches, so the inter counter is a
        # pure DCN-byte meter (docs/observability.md)
        self.hier_dispatches = 0
        self.wire_bytes_saved_intra_total = 0
        self.wire_bytes_saved_inter_total = 0
        self.last_wire_format_intra = "fp32"
        self.last_wire_format_inter = "fp32"
        # eager alltoall observability (the gap PR 12 closed: these
        # dispatches were counted in `dispatches` but never reached a
        # metrics legend, so expert-dispatch bytes were invisible to
        # the flight recorder). Wire bytes use the (n-1)/n·payload
        # exchange model — the self block never leaves the chip.
        self.alltoall_dispatches = 0
        self.alltoall_wire_bytes_total = 0
        # local-SGD phase routing (horovod_tpu/local_sgd.py): fused
        # allreduce dispatches that ran group-limited to the intra
        # slice while a local phase was active
        self.local_dispatches = 0
        self.ef_residual_norm = 0.0  # L2 of the last EF residual batch
        self._seed_counter = 0  # decorrelates stochastic rounding per dispatch
        self._prev_outs = None  # queue-drain anchor for WireTuner trials
        self._anchor_ttl = 0  # dispatches the anchor stays alive for
        self.cycles = 0
        self._group_depth = 0
        self._next_group_id = 0

    def _make_wire_tuner(self):
        """WireTuner construction with durable state (HOROVOD_TUNER_CACHE):
        warm-started from the (topology-fingerprinted) cache so a
        restarted job skips straight to exploitation, and registered
        for persist-at-exit so this run's observations join the
        fleet's. No cache dir configured = exactly the old in-memory
        behavior."""
        from ..common.autotune import (
            WireTuner,
            register_persist_at_exit,
            warm_start,
        )

        tuner = WireTuner(min_int8_bytes=self.wire_min_bytes)
        warm_start(tuner, "wire")
        register_persist_at_exit(tuner, "wire")
        return tuner

    # ------------------------------------------------------------------ queue

    def begin_group(self) -> int:
        """Start an atomic enqueue group (ref: group_table.cc — a group
        is fused and reduced as one unit [V]): threshold/cycle flush
        triggers are deferred until the matching end_group(), so a group
        larger than the fusion threshold cannot be split mid-group."""
        self._group_depth += 1
        gid = self._next_group_id
        self._next_group_id += 1
        return gid

    def abort_group(self, gid: int) -> None:
        """Drop an incompletely-enqueued group (a member failed
        validation): its entries must not dispatch at end_group."""
        kept = [e for e in self.pending if e.group_id != gid]
        dropped = len(self.pending) - len(kept)
        if dropped:
            self.pending = kept
            self.pending_bytes = sum(
                int(e.payload.nbytes) for e in self.pending
            )

    def end_group(self) -> None:
        self._group_depth = max(self._group_depth - 1, 0)
        if self._group_depth == 0 and (
            self.pending_bytes >= self.threshold_bytes
            or self._cycle_expired()
        ):
            self.flush()

    def enqueue(self, entry: _Entry) -> Handle:
        entry.enqueue_t = time.monotonic()
        entry.handle = Handle(self, entry)
        if self.timeline is not None:
            self.timeline.begin(entry.name, "QUEUE")
        if self.stall_inspector is not None:
            self.stall_inspector.record_enqueue(entry.name)
        if self.cycle_start is None:
            self.cycle_start = entry.enqueue_t
        self.pending.append(entry)
        self.pending_bytes += int(entry.payload.nbytes)
        if self._group_depth == 0 and (
            self.pending_bytes >= self.threshold_bytes
            or self._cycle_expired()
        ):
            self.flush()
        return entry.handle

    def _cycle_expired(self) -> bool:
        return (
            self.cycle_start is not None
            and (time.monotonic() - self.cycle_start) * 1e3 >= self.cycle_time_ms
        )

    def maybe_cycle(self) -> None:
        if self.pending and self._cycle_expired():
            self.flush()

    # ------------------------------------------------------------------ flush

    def flush(self) -> None:
        if not self.pending:
            return
        # ``fusion.dispatch`` injection site: a transport-shaped fault
        # here models a peer dying under a collective. It surfaces as
        # HorovodInternalError — the exception the elastic contract
        # (hvd.elastic.run -> state.restore) is built to absorb — so
        # chaos tests can drive the rollback path deterministically.
        from ..testing import chaos as _chaos

        try:
            chaos_kind = _chaos.inject("fusion.dispatch")
        except (
            ConnectionResetError, TimeoutError, _chaos.InjectedServerError
        ) as e:
            from ..common.basics import HorovodInternalError

            raise HorovodInternalError(str(e)) from e
        t0 = time.monotonic()
        entries, self.pending = self.pending, []
        if chaos_kind == "nan":
            # data-plane corruption drill: poison ONE element of the
            # first float payload in the batch — exactly what a flipped
            # gradient bit looks like to the guard's isfinite sentinel
            for e in entries:
                if jnp.issubdtype(e.payload.dtype, jnp.floating):
                    e.payload = jnp.reshape(
                        jnp.reshape(e.payload, (-1,)).at[0].set(jnp.nan),
                        e.payload.shape,
                    )
                    break
        flushed_bytes, self.pending_bytes = self.pending_bytes, 0
        self.flushed_bytes_total += flushed_bytes
        self.cycle_start = None
        self.cycles += 1
        self.last_cycle_dispatches = 0
        self.last_cycle_pad_bytes = 0
        self.last_cycle_wire_saved = 0
        if self.timeline is not None:
            self.timeline.mark_cycle()
        if self.stall_inspector is not None:
            self.stall_inspector.check()

        # Group fusable entries; preserve dispatch order within groups.
        groups: Dict[Tuple, List[_Entry]] = {}
        for e in entries:
            groups.setdefault(_group_key(e), []).append(e)
        for key, group in groups.items():
            kind = key[0]
            if kind == "alltoall":
                for e in group:
                    self._execute_alltoall(e)
            elif kind == "allgather" and group[0].extra is not None:
                # v-variant: padded rows + per-rank valid-prefix slicing;
                # host-repack-bound like the reference's MPI_Allgatherv,
                # dispatched one entry at a time
                for e in group:
                    self._execute_batch([e])
            elif kind == "allreduce" and ReduceOp(key[1]) == Adasum:
                # Adasum's dot-product coefficients are per-tensor;
                # concatenating entries would compute joint projections
                # over the fused buffer. Execute one entry at a time
                # (still through the in-JIT pack machinery — bucketing
                # is sound because zero-padding adds nothing to Adasum's
                # inner products).
                for e in group:
                    self._execute_batch([e])
            else:
                for batch in self._batches_by_threshold(group):
                    self._execute_batch(batch)

        for e in entries:
            if self.timeline is not None:
                self.timeline.end(e.name, "QUEUE")
            if self.stall_inspector is not None:
                self.stall_inspector.record_complete(e.name)
        if _log.isEnabledFor(10):  # DEBUG — cycle + cache stats
            _log.debug(
                "cycle %d: %d entries, %dB (+%dB pad), %d dispatches, "
                "%.2fms; cache hits=%d bucket_hits=%d misses=%d "
                "evictions=%d size=%d",
                self.cycles,
                len(entries),
                flushed_bytes,
                self.last_cycle_pad_bytes,
                self.last_cycle_dispatches,
                (time.monotonic() - t0) * 1e3,
                self.cache_hits,
                self.bucket_hits,
                self.cache_misses,
                self.cache_evictions,
                len(self._executors),
            )
        from ..common.metrics import registry as _metrics

        _metrics.update("fusion", self.cache_stats())
        # expert-dispatch legend (MOE_METRICS): cumulative values under
        # their own prefix so StepStats _COUNTER_KEYS can delta them —
        # the eager alltoall family finally reaches the flight recorder
        _metrics.gauge("alltoall.dispatches", self.alltoall_dispatches)
        _metrics.gauge(
            "alltoall.wire_bytes", self.alltoall_wire_bytes_total
        )
        _metrics.gauge("fusion.cycles", self.cycles)
        _metrics.gauge("fusion.last_flush_bytes", flushed_bytes)
        _metrics.gauge(
            "fusion.last_cycle_pad_bytes", self.last_cycle_pad_bytes
        )
        _metrics.gauge(
            "fusion.last_cycle_dispatches", self.last_cycle_dispatches
        )
        _metrics.gauge(
            "fusion.last_cycle_wire_saved", self.last_cycle_wire_saved
        )
        _metrics.maybe_dump()
        if self.timeline is not None:
            self.timeline.counter(
                "fusion.pad_bytes", self.last_cycle_pad_bytes
            )
            self.timeline.counter(
                "fusion.dispatches", self.last_cycle_dispatches
            )
            self.timeline.counter(
                "fusion.wire_bytes_saved", self.last_cycle_wire_saved
            )
            from ..common.metrics import WIRE_FORMAT_CODES

            self.timeline.counter(
                "fusion.wire_format",
                WIRE_FORMAT_CODES.get(self.last_wire_format, 0),
            )
        if self.parameter_manager is not None:
            # useful vs wire bytes: the GP scores goodput (useful/sec),
            # so bucket padding — which costs time but moves no payload
            # — is penalized, not rewarded; a quantized wire that
            # removes payload bytes is credited the same way
            self.parameter_manager.record(
                bytes_=flushed_bytes,
                seconds=time.monotonic() - t0,
                wire_bytes=max(
                    flushed_bytes
                    + self.last_cycle_pad_bytes
                    - self.last_cycle_wire_saved,
                    0,
                ),
            )
            self.threshold_bytes, self.cycle_time_ms = (
                self.parameter_manager.current()
            )

    def _batches_by_threshold(self, group: List[_Entry]):
        """Split a fusable group into batches of <= threshold bytes,
        mirroring the fusion buffer's capacity (fusion_buffer_manager.cc
        [V]). A single over-threshold entry still goes alone, and a
        grouped_allreduce group is one indivisible unit — its members
        always share one fused collective (group_table.cc [V])."""
        units: List[List[_Entry]] = []
        for e in group:
            if (
                e.group_id is not None
                and units
                and units[-1][0].group_id == e.group_id
            ):
                units[-1].append(e)
            else:
                units.append([e])
        batch, batch_bytes = [], 0
        for unit in units:
            nbytes = sum(int(e.payload.nbytes) for e in unit)
            if batch and batch_bytes + nbytes > self.threshold_bytes:
                yield batch
                batch, batch_bytes = [], 0
            batch.extend(unit)
            batch_bytes += nbytes
        if batch:
            yield batch

    # ------------------------------------------------------------- executors

    def _pset_mask(self, e: _Entry):
        """Static [world] membership tuple for a proper-subset process
        set, else None. Masked full-axis collectives replace
        axis_index_groups here: XLA's TPU lowering requires equal-sized
        replica groups, which a set+singletons partition can never be
        (ref: per-set communicators in process_set.cc [V])."""
        if e.process_set is None or e.process_set.process_set_id == 0:
            return None
        if e.process_set.size == self.world:
            return None
        members = set(e.process_set.ranks)
        return tuple(r in members for r in range(self.world))

    def _pset_ranks(self, e: _Entry) -> Optional[Tuple[int, ...]]:
        if e.process_set is None or e.process_set.process_set_id == 0:
            return None
        return tuple(e.process_set.ranks)

    def _cache_get(self, key: Tuple) -> Optional[Callable]:
        if self.cache_capacity == 0:
            return None
        fn = self._executors.get(key)
        if fn is not None:
            self._executors.move_to_end(key)
        return fn

    def _cache_put(self, key: Tuple, fn: Callable) -> None:
        if self.cache_capacity == 0:
            return
        self._executors[key] = fn
        while len(self._executors) > self.cache_capacity:
            self._executors.popitem(last=False)
            self.cache_evictions += 1

    def _executor(self, key: Tuple, builder: Callable) -> Callable:
        """Single-tier lookup (alltoall and other non-fused paths)."""
        fn = self._cache_get(key)
        if fn is not None:
            self.cache_hits += 1
            return fn
        self.cache_misses += 1
        fn = builder()
        self._cache_put(key, fn)
        return fn

    def _note_composition(self, exact_key: Tuple) -> int:
        """Count sightings of an exact batch composition (bounded)."""
        n = self._comp_seen.pop(exact_key, 0) + 1
        self._comp_seen[exact_key] = n
        limit = max(self.cache_capacity * 4, 256)
        while len(self._comp_seen) > limit:
            self._comp_seen.popitem(last=False)
        return n

    def _note_bucket(self, core_key: Tuple) -> bool:
        """Record a bucket sighting; True when first seen. Bounded the
        same way as _comp_seen — core keys embed prescale/postscale
        floats, so a drifting scale (dynamic loss scaling) would
        otherwise grow this O(steps)."""
        fresh = self._buckets_seen.pop(core_key, "absent") == "absent"
        self._buckets_seen[core_key] = None
        limit = max(self.cache_capacity * 4, 256)
        while len(self._buckets_seen) > limit:
            self._buckets_seen.popitem(last=False)
        return fresh

    def cache_stats(self) -> Dict[str, int]:
        from ..common.metrics import WIRE_FORMAT_CODES

        return {
            "capacity": self.cache_capacity,
            "size": len(self._executors),
            "hits": self.cache_hits,
            "misses": self.cache_misses,
            "evictions": self.cache_evictions,
            "disk_hits": self.disk_hits,
            "disk_misses": self.disk_misses,
            "bucket_hits": self.bucket_hits,
            "promotions": self.promotions,
            "recompiles": self.cache_misses,
            "dispatches": self.dispatches,
            "bucket_pad_bytes": self.pad_bytes_total,
            "flushed_bytes": self.flushed_bytes_total,
            "donated_bytes": self.donated_bytes_total,
            "wire_bytes_saved": self.wire_bytes_saved_total,
            "quant_blocks": self.quant_blocks_total,
            "wire_format": WIRE_FORMAT_CODES.get(self.last_wire_format, 0),
            "hier_dispatches": self.hier_dispatches,
            "local_dispatches": self.local_dispatches,
            "wire_bytes_saved_intra": self.wire_bytes_saved_intra_total,
            "wire_bytes_saved_inter": self.wire_bytes_saved_inter_total,
            "wire_format_intra": WIRE_FORMAT_CODES.get(
                self.last_wire_format_intra, 0
            ),
            "wire_format_inter": WIRE_FORMAT_CODES.get(
                self.last_wire_format_inter, 0
            ),
            "alltoall_dispatches": self.alltoall_dispatches,
            "alltoall_wire_bytes": self.alltoall_wire_bytes_total,
        }

    def _shard_map(self, fn, in_specs=P(WORLD_AXIS), out_specs=P(WORLD_AXIS)):
        return shard_map(
            fn,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )

    # ---------------------------------------------------- fused dispatch

    def _hier_stages(self):
        """Two-level replica groups for an EXPLICIT per-call request
        (Compression.hier_int8 / HOROVOD_FUSION_WIRE_HIER): any
        resolvable split qualifies (mode "on"), or None when the
        hierarchy degenerates. Factored out so tests can inject a
        synthetic multi-slice split on a single-host mesh."""
        from ..common import topology as _topo

        return _topo.hierarchy_stages(world=self.world, mode="on")

    def _default_hier_stages(self):
        """The DEFAULT-routing decision — HOROVOD_HIERARCHICAL's
        tri-state (common/topology.py hierarchy_stages): every fused
        allreduce batch rides the two-level recipe when a real inter
        axis is present, flat otherwise."""
        from ..common import topology as _topo

        return _topo.hierarchy_stages(world=self.world)

    def _resolve_wire(self, e0: _Entry, plan: _BatchPlan):
        """Pick the wire plan for one allreduce batch: the entry's
        compression override beats the manager knob; ``auto`` asks the
        per-bucket WireTuner. Returns ``(wire, hier_stages, tuned,
        intra_wire)`` with both wires in {'fp32','bf16','int8'} —
        ineligible batches (non-float dtype, reductions that don't
        commute with quantization/cast) always ride fp32; ``tuned``
        marks choices that came from the tuner (only those dispatches
        ever pay trial synchronization).

        Hierarchy: an explicit request (``Compression.hier_int8`` /
        ``HOROVOD_FUSION_WIRE_HIER``) places bf16 intra + int8 inter
        whenever a split is resolvable; otherwise EVERY eligible batch
        consults the HOROVOD_HIERARCHICAL default decision — when an
        inter axis is present, the fused collective decomposes into
        intra RS -> inter collective on the 1/L shard -> intra AG,
        with each hop's format resolved independently (``wire`` names
        the INTER hop; the WireTuner keys are per (bucket-tier, hop))."""
        import jax.numpy as _jnp

        wire = e0.wire or self.wire
        eligible = e0.op in (Average, Sum) and _jnp.issubdtype(
            _jnp.dtype(plan.dtype), _jnp.floating
        )
        if e0.want_residual:
            if not eligible:
                raise ValueError(
                    "return_residual needs the int8 wire, which supports "
                    "float Sum/Average allreduce only"
                )
            # EF is defined by the quantization error — it forces the
            # flat int8 wire (the hierarchical split has no single
            # local residual to carry on this path).
            return "int8", None, False, "fp32"
        if not eligible:
            return "fp32", None, False, "fp32"
        explicit_hier = wire == "int8_hier" or (
            wire == "int8" and self.wire_hier
        )
        if wire == "int8_hier":
            wire = "int8"
        hier = (
            self._hier_stages()
            if explicit_hier
            else self._default_hier_stages()
        )
        tuned = False
        if wire in (None, "fp32"):
            return "fp32", hier, False, "fp32"
        if wire == "auto":
            if self.wire_tuner is None:  # knob flipped after init
                self.wire_tuner = self._make_wire_tuner()
            bucket_key = ("allreduce", plan.bucket, plan.dtype)
            if hier is not None:
                # per-hop choice: the inter hop sees 1/L of the bytes
                # (int8 competes there), the intra hop the full buffer
                # (fp32/bf16 only — ICI is fast, the quant tax never
                # pays for itself inside the slice)
                intra_n = len(hier[0][0])
                wire = self.wire_tuner.choose(
                    bucket_key + ("inter",),
                    payload_bytes=plan.bucket * plan.itemsize // intra_n,
                    itemsize=plan.itemsize,
                )
                intra_wire = self.wire_tuner.choose(
                    bucket_key + ("intra",),
                    payload_bytes=plan.bucket * plan.itemsize,
                    itemsize=plan.itemsize,
                    candidates=("fp32", "bf16"),
                )
                return wire, hier, True, intra_wire
            wire = self.wire_tuner.choose(
                bucket_key,
                payload_bytes=plan.bucket * plan.itemsize,
                itemsize=plan.itemsize,
            )
            tuned = True
            if wire == "int8" and self.wire_hier:
                hier = self._hier_stages()
        # static per-hop defaults: the EQuARX placement (bf16 intra
        # under an int8 inter); exact/bf16 wires apply hop-uniformly
        intra_wire = "bf16" if wire == "int8" and hier is not None else wire
        return wire, hier, tuned, intra_wire

    def _classify(self, batch: List[_Entry]) -> "_ExecSpec":
        """Resolve a batch to an _ExecSpec. `core_key` identifies the
        composition-independent padded-buffer program; the exact fused
        executable's key appends the per-entry shape tuple."""
        e0 = batch[0]
        kind = e0.kind
        if kind == "allreduce":
            pset_mask = self._pset_mask(e0)
            if e0.op == Adasum and pset_mask is not None:
                # Adasum over a process set rides adasum_allreduce's
                # masked full-axis formulation; a join mask composes by
                # zeroing the joined MEMBERS' rows (zero is Adasum's
                # identity) via the dynamic `keep` argument — NOT the
                # key — so one compiled program serves every join
                # pattern. Full-axis is the multi-process-safe shape
                # (tests/test_multiprocess_ops.py).
                ranks = self._pset_ranks(e0)
                plan = self._plan(batch, "adasum_pset", self.world)
                core_key = (
                    "adasum_pset", e0.prescale, e0.postscale, ranks,
                    plan.bucket, plan.dtype,
                )
                builder = lambda: self._core_adasum_pset(
                    e0.prescale, e0.postscale, ranks
                )
                return _ExecSpec(plan, core_key, builder, needs_keep=True)
            mask = None if e0.mask is None else tuple(bool(b) for b in e0.mask)
            plan = self._plan(batch, "allreduce", self.world)
            wire, hier, tuned, intra_wire = self._resolve_wire(e0, plan)
            # local-SGD local phase (horovod_tpu/local_sgd.py): an
            # active phase restricts every eligible fused allreduce to
            # its intra group — no inter hop exists, so the two-level
            # decomposition is moot. Masked/pset batches stay flat
            # (a masked subgroup has no uniform replica-group shape);
            # they are the caller's explicit cross-slice request.
            local_groups = None
            if (
                pset_mask is None
                and mask is None
                and e0.op in (Average, Sum)
            ):
                from .. import local_sgd as _local_sgd

                local_groups = _local_sgd.active_intra_groups()
            if local_groups is not None:
                hier = None
                if tuned:
                    # never feed an ICI-only dispatch's timing into
                    # the WireTuner's world/hier keys (they persist via
                    # HOROVOD_TUNER_CACHE and would poison the goodput
                    # real DCN-crossing dispatches choose from) — and
                    # auto never picks int8 inside a slice (the quant
                    # tax cannot pay for itself on ICI)
                    tuned = False
                    if wire == "int8":
                        wire = "fp32"
                if (e0.wire or self.wire) == "int8_hier":
                    # int8 was licensed for the inter hop only; with no
                    # inter hop the placement degenerates to its intra
                    # leg's wire
                    wire = "bf16"
                self.local_dispatches += 1
            if pset_mask is not None or mask is not None:
                # masked hierarchy degenerates to flat inside the core;
                # keep the spec (and so the wire-byte model + autotune
                # feed) consistent with what actually compiles
                hier = None
            # the canonical (world, L) layout pins the group structure,
            # so this pair is the cache-key-safe hier fingerprint (a
            # topology change mid-process re-keys the executors)
            hier_key = (
                None if hier is None else (len(hier[0]), len(hier[0][0]))
            )
            # the local phase re-keys the executors the same way: a
            # flat-wire executable must never serve a local dispatch
            local_key = (
                None
                if local_groups is None
                else (len(local_groups), len(local_groups[0]))
            )
            if wire == "int8":
                # a compressor's block_size (Compression.int8_block
                # subclasses) beats the manager knob, matching the
                # traced/optimizer path's granularity
                block = e0.wire_block or self.wire_block
                core_key = (
                    "allreduce_q", int(e0.op), e0.prescale, e0.postscale,
                    pset_mask, mask, plan.bucket, plan.dtype, block,
                    e0.want_residual, hier_key, intra_wire, local_key,
                )
                builder = lambda: self._core_allreduce_q(
                    e0.op, e0.prescale, e0.postscale, pset_mask, mask,
                    block, e0.want_residual, hier, intra_wire,
                    local_groups,
                )
                return _ExecSpec(
                    plan, core_key, builder, needs_seed=True,
                    want_res=e0.want_residual, wire="int8",
                    hier_n=None if hier is None else len(hier[1][0]),
                    intra_n=None if hier is None else len(hier[0][0]),
                    tuned=tuned, block=block, intra_wire=intra_wire,
                )
            core_key = (
                "allreduce", int(e0.op), e0.prescale, e0.postscale,
                pset_mask, mask, plan.bucket, plan.dtype, wire,
                hier_key, intra_wire, local_key,
            )
            builder = lambda: self._core_allreduce(
                e0.op, e0.prescale, e0.postscale, pset_mask, mask,
                wire=wire, hier_stages=hier, intra_wire=intra_wire,
                local_groups=local_groups,
            )
            return _ExecSpec(
                plan, core_key, builder, wire=wire, tuned=tuned,
                hier_n=None if hier is None else len(hier[1][0]),
                intra_n=None if hier is None else len(hier[0][0]),
                intra_wire=intra_wire,
            )
        if kind == "broadcast":
            pset_mask = self._pset_mask(e0)
            plan = self._plan(batch, "broadcast", self.world)
            core_key = (
                "broadcast", e0.root_rank, pset_mask, plan.bucket,
                plan.dtype,
            )
            builder = lambda: self._core_broadcast(e0.root_rank, pset_mask)
            return _ExecSpec(plan, core_key, builder)
        if kind == "allgather":
            ranks = self._pset_ranks(e0)
            n_ranks = self.world if ranks is None else len(ranks)
            plan = self._plan(batch, "allgather", n_ranks)
            core_key = ("allgather", ranks, plan.bucket, plan.dtype)
            builder = lambda: self._core_allgather(ranks)
            return _ExecSpec(plan, core_key, builder)
        if kind == "reducescatter":
            ranks = self._pset_ranks(e0)
            n_ranks = self.world if ranks is None else len(ranks)
            for e in batch:
                if e.payload.shape[1] % n_ranks != 0:
                    raise ValueError(
                        f"equal-split reducescatter needs dim1 divisible "
                        f"by the participating rank count {n_ranks}"
                    )
            plan = self._plan(batch, "reducescatter", n_ranks)
            core_key = (
                "reducescatter", int(e0.op), e0.prescale, e0.postscale,
                ranks, plan.bucket, plan.dtype,
            )
            builder = lambda: self._core_reducescatter(
                e0.op, e0.prescale, e0.postscale, ranks
            )
            return _ExecSpec(plan, core_key, builder)
        raise ValueError(f"unknown kind {kind}")

    def _plan(self, batch, family, n_ranks) -> _BatchPlan:
        return _make_plan(family, batch, self.world, n_ranks, self.bucketing)

    def _keep_arg(self, e: _Entry):
        """[world, 1] keep-row flags for the adasum_pset join mask:
        joined MEMBERS' contributions are zeroed (Adasum identity);
        joined NON-members keep their rows — their pass-through must
        return the original input."""
        if e.mask is None:
            return jnp.ones((self.world, 1), dtype=bool)
        member_set = set(self._pset_ranks(e) or range(self.world))
        return jnp.asarray(
            [
                [not (r in member_set and not bool(e.mask[r]))]
                for r in range(self.world)
            ]
        )

    def _execute_batch(self, batch: List[_Entry]) -> None:
        spec = self._classify(batch)
        plan, core_key = spec.plan, spec.core_key
        # collective-schedule audit (analysis/sched_audit.py): fold this
        # dispatch's rank-invariant identity — kind/op, fused-entry
        # composition, resolved wire, pset — into the rolling per-rank
        # fingerprint. A rank whose tuner, composition, or code path
        # diverges here is about to compile a DIFFERENT collective
        # sequence: the deadlock precursor the driver quarantines on.
        # (enabled() gates at the call site so a disabled audit skips
        # the composition-tuple construction too, not just the fold)
        if _sched_audit.enabled():
            _sched_audit.record(
                f"{batch[0].kind}:"
                f"{'' if batch[0].op is None else int(batch[0].op)}",
                (
                    plan.family,
                    tuple(_sched_entry_name(e.name) for e in batch),
                    plan.shapes,
                    plan.dtype,
                ),
                wire=(
                    f"{spec.intra_wire}/{spec.wire}"
                    if spec.hier_n
                    else spec.wire
                ),
                pset=(
                    0
                    if batch[0].process_set is None
                    else batch[0].process_set.process_set_id
                ),
            )
        # the non-finite sentinel rides only float batches (integer
        # payloads are finite by construction); the flag is an extra
        # executor output, so it is part of what the cache key already
        # pins (guard is fixed per manager, dtype is in every key)
        guarded = self.guard and jnp.issubdtype(
            jnp.dtype(plan.dtype), jnp.floating
        )
        exact_key = core_key + ("x", plan.shapes)
        # The exact tier is keyed on the full per-entry shape tuple, so
        # bucket padding buys it zero cache stability — it would only
        # put dead zeros on the wire every cycle of a stable job. Pad
        # only the bucket tier, whose executables must be
        # composition-independent.
        exact_plan = (
            plan
            if plan.bucket == plan.useful
            else dataclasses.replace(plan, bucket=plan.useful)
        )
        phase = batch[0].kind.upper()
        if self.timeline is not None:
            for e in batch:
                self.timeline.begin(e.name, phase)

        keep = self._keep_arg(batch[0]) if spec.needs_keep else None
        seed = self._next_seed() if spec.needs_seed else None
        outs = None
        used_plan = plan
        misses_before = self.cache_misses
        trial_pairs = []
        if spec.tuned:  # wire came from the tuner — no trials otherwise
            bucket_key = ("allreduce", plan.bucket, plan.dtype)
            if spec.hier_n:
                # per-hop keys: the inter and intra decisions explore
                # and converge independently (bf16-intra / int8-inter
                # is reachable without a combined menu)
                cand = [
                    (bucket_key + ("inter",), spec.wire),
                    (bucket_key + ("intra",), spec.intra_wire),
                ]
            else:
                cand = [(bucket_key, spec.wire)]
            trial_pairs = [
                (k, c)
                for k, c in cand
                if self.wire_tuner.needs_trial(k, c)
            ]
            if trial_pairs:
                self._anchor_ttl = 16  # exploration active: keep anchors
                # drain the dispatch queue up to the PREVIOUS batch so
                # the trial's clock measures this dispatch alone, not
                # whatever earlier async work was still in flight
                if self._prev_outs is not None:
                    try:
                        jax.block_until_ready(self._prev_outs)
                    except RuntimeError:
                        # the user may have DONATED the fulfilled
                        # outputs since (deleted buffers); the queue is
                        # then already drained past them
                        pass
        t_disp = time.monotonic()
        if not self.injit_pack or self.cache_capacity == 0:
            # host-pack mode (the A/B baseline leg), or caching disabled
            # — capacity 0 must not build a throwaway fused program per
            # cycle on top of an uncacheable core
            if self.injit_pack and self.cache_capacity == 0:
                self.cache_misses += 1
                fn = self._build_fused(
                    exact_plan, spec.builder(), spec, guarded
                )
                outs = self._dispatch_fused(
                    fn, batch, exact_plan, keep, seed, guarded
                )
                used_plan = exact_plan
            else:
                fn = self._executor(core_key, lambda: self._build_core(
                    plan, spec.builder(), spec, guarded))
                outs = self._dispatch_core(
                    fn, batch, plan, keep, seed, spec, guarded
                )
        else:
            fn = self._cache_get(exact_key)
            if fn is not None:
                self.cache_hits += 1
                outs = self._dispatch_fused(
                    fn, batch, exact_plan, keep, seed, guarded
                )
                used_plan = exact_plan
            else:
                seen = self._note_composition(exact_key)
                core = self._cache_get(core_key)
                fresh_bucket = self._note_bucket(core_key)
                if fresh_bucket or seen >= self.promote_after:
                    # first composition in this bucket, or a composition
                    # hot enough to deserve its own fused executable
                    self.cache_misses += 1
                    if not fresh_bucket:
                        self.promotions += 1
                    fn = self._build_fused(
                        exact_plan, spec.builder(), spec, guarded
                    )
                    fn = self._finalize_exe(
                        fn, "fusion.fused", spec,
                        lambda: [e.payload for e in batch]
                        + self._extra_args(keep, seed),
                        donate_n=len(exact_plan.shapes),
                    )
                    self._cache_put(exact_key, fn)
                    outs = self._dispatch_fused(
                        fn, batch, exact_plan, keep, seed, guarded
                    )
                    used_plan = exact_plan
                else:
                    # composition churn inside a known bucket: reuse (or
                    # build once) the bucket-tier program instead of
                    # compiling per composition
                    if core is None:
                        self.cache_misses += 1
                        core = self._build_core(
                            plan, spec.builder(), spec, guarded
                        )
                        core = self._finalize_exe(
                            core, "fusion.core", spec,
                            lambda: [
                                _pack([e.payload for e in batch], plan)
                            ] + self._extra_args(keep, seed),
                        )
                        self._cache_put(core_key, core)
                    self.bucket_hits += 1
                    outs = self._dispatch_core(
                        core, batch, plan, keep, seed, spec, guarded
                    )

        self.pad_bytes_total += used_plan.pad_bytes
        self.last_cycle_pad_bytes += used_plan.pad_bytes
        self._account_wire(spec, used_plan)
        if trial_pairs and self.cache_misses == misses_before:
            # exploration observation: pay one sync so the sample
            # measures execution (quant tax + wire), not the
            # format-independent async dispatch overhead; compile-time
            # dispatches are excluded — they would poison the goodput.
            # A hierarchical dispatch feeds BOTH hop keys the same
            # whole-dispatch sample — each hop's bandit ranks its own
            # candidates by it across dispatches.
            jax.block_until_ready(outs)
            seconds = time.monotonic() - t_disp
            for k, c in trial_pairs:
                self.wire_tuner.record(
                    k,
                    c,
                    useful_bytes=spec.plan.useful
                    * spec.plan.itemsize
                    * used_plan.world,
                    seconds=seconds,
                )
        # the anchor pins the previous batch's outputs in memory, so it
        # lives only while exploration is ACTIVE: each trial refreshes
        # a small TTL, and a half-explored bucket that stops recurring
        # stops pinning buffers after the TTL drains (it would
        # otherwise hold a threshold-sized batch for the process
        # lifetime)
        self._anchor_ttl = max(self._anchor_ttl - 1, 0)
        self._prev_outs = outs if self._anchor_ttl > 0 else None
        if self.timeline is not None and self.timeline.active:
            # device-completion stamp (SURVEY §7 checklist, eager half):
            # one block_until_ready per flush while someone is WATCHING
            # — the dispatch→completion delta is the device-side span
            # the dispatch-lifecycle begin/end pairs cannot see. The
            # sync is an observability cost the timeline explicitly
            # opts into (same gate as the EF-norm metrics); `active`
            # matters: after stop_timeline() the Timeline object stays
            # attached, and paying a sync per flush for spans the
            # writer would drop would serialize dispatch forever. The
            # span anchors at dispatch time ONLY when this flush
            # compiled nothing — on a cache-miss flush the executor
            # build/JIT ran after t_disp, and back-dating would report
            # host compile seconds as device collective time (the same
            # poisoning the WireTuner guards its goodput against), so
            # those spans anchor post-dispatch and measure the
            # remaining completion wait only.
            if self.cache_misses == misses_before:
                t0_us = self.timeline.now_us() - (
                    time.monotonic() - t_disp
                ) * 1e6
            else:
                t0_us = self.timeline.now_us()
            jax.block_until_ready(outs)
            dur_us = self.timeline.now_us() - t0_us
            for e in batch:
                self.timeline.span(
                    e.name, f"{phase}_DEVICE", t0_us, dur_us
                )
        resids = None
        if spec.want_res:
            outs, resids = outs
            self._note_residuals(resids)
        for i, (e, out) in enumerate(zip(batch, outs)):
            if e.kind == "allgather" and e.extra is not None:
                # Uneven dim0: rows were padded to max length; slice each
                # rank's valid prefix and concat (MPI_Allgatherv parity).
                lengths = e.extra
                ranks = self._pset_ranks(e)
                srcs = range(self.world) if ranks is None else ranks
                pieces = [
                    out[:, i, : lengths[s]] for i, s in enumerate(srcs)
                ]
                out = jnp.concatenate(pieces, axis=1)
            if self.timeline is not None:
                self.timeline.end(e.name, phase)
            e.handle._fulfill(
                (out, resids[i]) if resids is not None else out
            )

    def _next_seed(self) -> int:
        """Per-dispatch stochastic-rounding seed: monotone, so no two
        fused dispatches (within or across cycles) reuse a rounding
        pattern; the per-rank decorrelation is folded in inside the
        compiled program (rank index is not known on the host)."""
        s = self._seed_counter
        self._seed_counter += 1
        return s

    @staticmethod
    def _hop_bytes(elems: int, wire: str, itemsize: int, n: int, block):
        """Payload-width model of one hop's per-row wire bytes: the
        allreduce-equivalent traffic of ``elems`` elements at ``wire``
        over ``n`` participants (RS+AG of a ring allreduce jointly move
        ~one payload; ring/topology factors cancel in every ratio this
        model feeds). int8 adds both stages' block scales."""
        if wire == "bf16":
            return elems * 2, 0
        if wire == "int8":
            chunk = -(-elems // max(n, 1))
            nb = -(-chunk // block)
            return elems + nb * (n + 1) * 4, nb * (n + 1)
        return elems * itemsize, 0

    def _account_wire(
        self, spec: "_ExecSpec", used_plan: _BatchPlan
    ) -> None:
        """Wire-byte accounting for one dispatch, payload-width model
        (:meth:`_hop_bytes`), vs the flat-fp32 baseline of
        ``bucket·itemsize`` per rank row.

        Flat dispatches feed the aggregate ``wire_bytes_saved`` /
        ``wire_format`` exactly as before. A HIERARCHICAL dispatch
        splits the ledger per hop: the intra hop carries the full
        buffer at ``intra_wire``; the inter (DCN) hop carries the
        1/L shard at ``wire`` — so ``wire_bytes_saved_inter`` measures
        exactly the scarce-hop bytes the two-level recipe removed
        (≥3x for fp32 payloads under int8-inter: L·4x minus scale
        overhead), and ``wire_format_intra/inter`` let telemetry and
        the flight recorder attribute a regression to the right hop."""
        self.last_wire_format = spec.wire
        rows = used_plan.world
        elems = used_plan.bucket
        itemsize = used_plan.itemsize
        fp32_b = elems * itemsize
        block = spec.block or self.wire_block
        if spec.hier_n:
            L = spec.intra_n or 1
            shard = -(-elems // L)
            intra_b, _ = self._hop_bytes(
                elems, spec.intra_wire, itemsize, L, block
            )
            inter_b, qb = self._hop_bytes(
                shard, spec.wire, itemsize, spec.hier_n, block
            )
            self.quant_blocks_total += qb * rows
            saved_intra = max(fp32_b - intra_b, 0) * rows
            saved_inter = max(fp32_b - inter_b, 0) * rows
            self.wire_bytes_saved_intra_total += saved_intra
            self.wire_bytes_saved_inter_total += saved_inter
            self.last_wire_format_intra = spec.intra_wire
            self.last_wire_format_inter = spec.wire
            self.hier_dispatches += 1
            saved = max(fp32_b - intra_b - inter_b, 0) * rows
            self.wire_bytes_saved_total += saved
            self.last_cycle_wire_saved += saved
            return
        saved = 0
        if spec.wire == "bf16":
            saved = max(fp32_b - elems * 2, 0) * rows
        elif spec.wire == "int8":
            n = self.world
            wire_b, qb = self._hop_bytes(
                elems, "int8", itemsize, n, block
            )
            saved = max(fp32_b - wire_b, 0) * rows
            self.quant_blocks_total += qb * rows
        self.wire_bytes_saved_total += saved
        self.last_cycle_wire_saved += saved

    def _note_residuals(self, resids) -> None:
        """EF-residual observability: the L2 norm of the batch's carry.
        Computed only when someone is watching (timeline or metrics
        sink) — it forces a host sync on the eager path."""
        from ..common.metrics import registry as _metrics

        if self.timeline is None and not _metrics.exporting:
            return
        # one traced reduction over every entry, ONE host transfer —
        # per-entry float() would serialize a device sync per tensor
        # against the dispatch pipeline
        sq = sum(
            jnp.vdot(jnp.asarray(r, jnp.float32), jnp.asarray(r, jnp.float32))
            for r in resids
        )
        self.ef_residual_norm = float(jnp.sqrt(sq))
        _metrics.gauge("fusion.ef_residual_norm", self.ef_residual_norm)
        if self.timeline is not None:
            self.timeline.counter(
                "fusion.ef_residual_norm", self.ef_residual_norm
            )

    @staticmethod
    def _extra_args(keep, seed):
        extra = []
        if keep is not None:
            extra.append(keep)
        if seed is not None:
            # a committed scalar array, not a Python int: weak-typed
            # host scalars would re-trace the executable per value
            extra.append(jnp.int32(seed))
        return extra

    def _note_guard_flag(self, ok) -> None:
        """Collect a device-scalar finite flag WITHOUT syncing; the
        list is bounded so an unpolled guard cannot pin buffers
        forever (old flags drop oldest-first — the poll is a
        rate-limited health check, not an exact ledger)."""
        self._guard_flags.append(ok)
        if len(self._guard_flags) > 256:
            del self._guard_flags[: len(self._guard_flags) - 256]

    def guard_poll(self) -> int:
        """Sync point for the eager sentinel: resolve the collected
        flags (this is where the host pays the transfer — call it from
        commit-boundary code, not per dispatch), count non-finite
        batches into ``guard.nonfinite_batches``, return the count."""
        flags, self._guard_flags = self._guard_flags, []
        bad = 0
        for f in flags:
            try:
                if not bool(f):
                    bad += 1
            except Exception:  # deleted/donated buffer: unknowable
                continue
        if bad:
            from ..common.metrics import registry as _metrics

            _metrics.counter("guard.nonfinite_batches", bad)
            _log.warning(
                "non-finite values in %d fused batch(es) since the "
                "last guard poll", bad,
            )
        return bad

    def _dispatch_fused(self, fn, batch, plan, keep, seed=None, guarded=False):
        """One executor invocation covering pack + collective + unpack
        (and, on the quantized wire, quantize + dequantize)."""
        args = [e.payload for e in batch] + self._extra_args(keep, seed)
        self.dispatches += 1
        self.last_cycle_dispatches += 1
        if self.donate:
            self.donated_bytes_total += sum(
                int(e.payload.nbytes) for e in batch
            )
        out = fn(*args)
        if guarded:
            out, ok = out
            self._note_guard_flag(ok)
        return out

    def _dispatch_core(
        self, fn, batch, plan, keep, seed=None, spec=None, guarded=False
    ):
        """Bucket-tier dispatch: host-side pack into the padded buffer,
        one collective invocation, host-side unpack. This is the
        pre-rework dispatch path, kept as the composition-independent
        fallback (``HOROVOD_FUSION_INJIT=0``; tests/test_fusion_injit.py
        holds its parity with the in-JIT path)."""
        if self.timeline is not None and len(batch) > 1:
            for e in batch:
                self.timeline.begin(e.name, "MEMCPY_IN_FUSION_BUFFER")
        buf = _pack([e.payload for e in batch], plan)
        if self.timeline is not None and len(batch) > 1:
            for e in batch:
                self.timeline.end(e.name, "MEMCPY_IN_FUSION_BUFFER")
        self.dispatches += 1
        self.last_cycle_dispatches += 1
        out = fn(buf, *self._extra_args(keep, seed))
        if guarded:
            out, ok = out
            self._note_guard_flag(ok)
        if spec is not None and spec.want_res:
            out, res = out
            return _unpack(out, plan), _unpack(res, plan)
        return _unpack(out, plan)

    def _mapped_core(self, per_shard, spec: "_ExecSpec"):
        """shard_map the per-shard core with the argument/output specs
        its flags imply: buffer (+ keep) (+ replicated seed) in, buffer
        (+ residual buffer) out."""
        in_specs = [P(WORLD_AXIS)]
        if spec.needs_keep:
            in_specs.append(P(WORLD_AXIS))
        if spec.needs_seed:
            in_specs.append(P())
        out_specs = (
            (P(WORLD_AXIS), P(WORLD_AXIS)) if spec.want_res else P(WORLD_AXIS)
        )
        return self._shard_map(
            per_shard, in_specs=tuple(in_specs), out_specs=out_specs
        )

    def _finalize_exe(
        self, jitted, family: str, spec: "_ExecSpec", args_thunk,
        donate_n: int = 0,
    ):
        """Disk tier below the exact/bucket tiers (HOROVOD_EXE_CACHE,
        common/exe_cache.py): AOT-lower the freshly built program with
        its first dispatch's argument avals, then load a previously
        persisted executable by (topology, HLO, wire, donation) key —
        or compile and persist for the next process/standby. Includes
        bucket→exact promotions: a recurring composition promotes from
        disk instead of paying the promotion compile. No cache dir →
        the jitted callable is returned untouched (zero behavior
        change); any AOT/serialization failure falls back the same
        way — the disk tier is an accelerator, never a dependency."""
        if self._exe_base is None:
            return jitted
        from ..common import exe_cache as _exe_cache

        if self._exe_fp is None:
            self._exe_fp = _exe_cache.topology_fingerprint()
        wire = (
            f"{spec.intra_wire}/{spec.wire}" if spec.hier_n else spec.wire
        )
        donation = _exe_cache.donation_signature(
            tuple(range(donate_n)) if (self.donate and donate_n) else ()
        )
        try:
            lowered = jitted.lower(*args_thunk())
            exe, hit = _exe_cache.get_or_compile(
                lowered,
                family=family,
                wire=wire,
                donation=donation,
                fingerprint=self._exe_fp,
                base=self._exe_base,
            )
        except Exception as e:
            _log.warning(
                "exe disk tier unavailable for %s (%s); serving the "
                "jit path", family, e,
            )
            return jitted
        if hit:
            self.disk_hits += 1
        else:
            self.disk_misses += 1
        return exe

    def _build_core(
        self, plan: _BatchPlan, per_shard, spec: "_ExecSpec",
        guarded: bool = False,
    ) -> Callable:
        """Compile the composition-independent padded-buffer program.
        ``guarded`` appends the non-finite sentinel — one
        ``all(isfinite)`` scalar over the output buffer, inside the
        same executable."""
        mapped = self._mapped_core(per_shard, spec)
        if not guarded:
            return jax.jit(mapped)
        want_res = spec.want_res

        def core(*args):
            out = mapped(*args)
            buf = out[0] if want_res else out
            return out, jnp.all(jnp.isfinite(buf))

        return jax.jit(core)

    def _build_fused(
        self, plan: _BatchPlan, per_shard, spec: "_ExecSpec",
        guarded: bool = False,
    ) -> Callable:
        """Compile the whole batch — in-JIT pack, (quantize,)
        collective, (dequantize,) in-JIT unpack — as ONE donated
        executable. XLA sees the reshape/concat producers and the
        slice/reshape consumers next to the collective and fuses them;
        donation lets the fusion buffer alias the argument storage
        instead of doubling peak HBM. ``guarded`` folds the
        non-finite sentinel (one scalar reduction over the fused
        output buffer) into the same program."""
        mapped = self._mapped_core(per_shard, spec)
        n_tensors = len(plan.shapes)
        want_res = spec.want_res

        def fused(*args):
            tensors = args[:n_tensors]
            buf = _pack(tensors, plan)
            out = mapped(buf, *args[n_tensors:])
            if want_res:
                out, res = out
                pieces = (
                    tuple(_unpack(out, plan)), tuple(_unpack(res, plan))
                )
            else:
                pieces = tuple(_unpack(out, plan))
            if guarded:
                return pieces, jnp.all(jnp.isfinite(out))
            return pieces

        kwargs = {}
        if self.donate:
            kwargs["donate_argnums"] = tuple(range(n_tensors))
        return jax.jit(fused, **kwargs)

    # ----------------------------------------------------- per-shard cores
    #
    # Each core is a per-shard function over the fused buffer
    # ([1, bucket] rows; [1, n_ranks, bucket] for reducescatter). The
    # bucket tier caches it on the PADDED power-of-two geometry; the
    # exact tier wraps the same (shape-polymorphic) core with in-JIT
    # pack/unpack over the UNPADDED (bucket == useful) geometry — its
    # key already pins the exact shapes, so padding would buy nothing.

    def _core_allreduce(
        self, op, prescale, postscale, pset_mask, mask, wire="fp32",
        hier_stages=None, intra_wire=None, local_groups=None,
    ):
        world = self.world
        op = ReduceOp(op)
        bf16_wire = wire == "bf16"
        mask_arr = (
            None if mask is None else np.asarray(mask, dtype=bool)
        )
        pset_arr = (
            None if pset_mask is None else np.asarray(pset_mask, dtype=bool)
        )
        # Effective participation = joined AND in the process set; the
        # two masks share one identity-masked full-axis collective.
        if mask_arr is not None and pset_arr is not None:
            active_arr = mask_arr & pset_arr
        else:
            active_arr = mask_arr if mask_arr is not None else pset_arr

        # Two-level decomposition (ref: nccl_operations.cc
        # HOROVOD_HIERARCHICAL_ALLREDUCE [V], promoted to the
        # HOROVOD_HIERARCHICAL default): the caller (_classify /
        # _resolve_wire) already resolved the topology decision; masked
        # batches arrive with hier_stages=None (degenerate to flat).
        # Only the unrestricted Sum/Average path qualifies.
        if active_arr is not None or op not in (Average, Sum):
            hier_stages = None
            local_groups = None  # masked local phase degenerates flat
        if local_groups is not None:
            # local-SGD local phase (horovod_tpu/local_sgd.py): the
            # collective never leaves the slice — and a two-level
            # decomposition would reintroduce the inter hop
            hier_stages = None
        if intra_wire is None:
            intra_wire = wire if bf16_wire else "fp32"

        def per_shard(x):  # x: [1, N] — this rank's slice of the buffer
            idx = lax.axis_index(WORLD_AXIS)
            raw = x
            if prescale != 1.0:
                x = x * jnp.asarray(prescale, x.dtype)
            if active_arr is not None:
                active = jnp.asarray(active_arr)[idx]
                contrib = jnp.where(active, x, jnp.zeros_like(x))
            else:
                active = jnp.asarray(True)
                contrib = x
            if op in (Average, Sum) and hier_stages is not None:
                # intra RS -> inter psum on the 1/L shard -> intra AG
                # (ops/traced.py recipe family): the DCN hop carries
                # 1/L of the buffer; exact for fp32 hops.
                from .traced import hierarchical_allreduce_groups

                out = hierarchical_allreduce_groups(
                    contrib[0], op=ReduceOp(op), axis_name=WORLD_AXIS,
                    stages=hier_stages, intra_wire=intra_wire,
                    inter_wire=wire,
                )[None]
            elif op in (Average, Sum) and local_groups is not None:
                # local phase: one group-limited psum per slice; the
                # divisor is the slice width (masks/psets never reach
                # this branch — they degenerate to flat above)
                if bf16_wire:
                    contrib = contrib.astype(jnp.bfloat16)
                out = lax.psum(
                    contrib, WORLD_AXIS,
                    axis_index_groups=[list(g) for g in local_groups],
                )
                if bf16_wire:
                    out = out.astype(x.dtype)
                if op == Average:
                    out = out / jnp.asarray(
                        len(local_groups[0]), out.dtype
                    )
            elif op in (Average, Sum):
                # bf16 wire: the cast is the compression — XLA fuses it
                # into the collective's producer/consumer, so the wire
                # moves half-width bytes at zero extra HBM passes
                # (Compression.bf16's contract, applied buffer-wide)
                if bf16_wire:
                    contrib = contrib.astype(jnp.bfloat16)
                out = lax.psum(contrib, WORLD_AXIS)
                if bf16_wire:
                    out = out.astype(x.dtype)
                if op == Average:
                    count = lax.psum(active.astype(x.dtype), WORLD_AXIS)
                    out = out / jnp.maximum(count, 1)
            elif op == Min:
                big = jnp.full_like(x, _max_value(x.dtype))
                contrib = (
                    jnp.where(active, x, big)
                    if active_arr is not None
                    else x
                )
                out = lax.pmin(contrib, WORLD_AXIS)
            elif op == Max:
                small = jnp.full_like(x, _min_value(x.dtype))
                contrib = (
                    jnp.where(active, x, small)
                    if active_arr is not None
                    else x
                )
                out = lax.pmax(contrib, WORLD_AXIS)
            elif op == Product:
                contrib = (
                    jnp.where(active, x, jnp.ones_like(x))
                    if active_arr is not None
                    else x
                )
                gathered = lax.all_gather(contrib, WORLD_AXIS)
                out = jnp.prod(gathered, axis=0)
            elif op == Adasum:
                from .adasum import adasum_allreduce

                # Zero is Adasum's identity (a zero vector has no
                # projection to remove and adds nothing), so the same
                # contribution masking covers joined ranks here too —
                # and the bucket's zero tail pads harmlessly.
                out = adasum_allreduce(contrib, axis_name=WORLD_AXIS)
            else:
                raise ValueError(f"unsupported op {op}")
            if postscale != 1.0:
                out = out * jnp.asarray(postscale, out.dtype)
            # Ranks outside the process set keep their input untouched
            # (reference: non-members don't participate at all). Joined
            # ranks (join mask) DO take the result — that's the point
            # of join().
            if pset_arr is not None:
                out = jnp.where(jnp.asarray(pset_arr)[idx], out, raw)
            return out

        return per_shard

    def _core_allreduce_q(
        self, op, prescale, postscale, pset_mask, mask, block,
        want_res, hier_stages, intra_wire="bf16", local_groups=None,
    ):
        """The quantized fused wire: the whole fused buffer traverses
        the collective as block-scaled int8, entirely inside the
        compiled program — quantize ONCE over the batch instead of once
        per tensor (the quantize pass has a fixed cost per call: one
        call a batch, not one a tensor).

        Recipe = traced.quantized_allreduce's two-stage shape applied
        to this rank's [1, N] buffer row: block-quantize the row split
        into per-peer chunks → all_to_all of int8 + block scales (the
        scatter half of reduce-scatter) → dequant-sum the received
        chunks at f32 → block-quantize the reduced shard → all_gather →
        dequant. XLA fuses the quantize into the pack producer and the
        dequant into the unpack consumers, so the batch still costs
        exactly ONE dispatch; wire bytes drop ~4x for fp32 payloads
        (block scales cost 4·(n+1)/n/block of the payload — <1% at
        block=512).

        ``prescale`` folds into the stage-1 wire scales (quantization
        is scale-invariant — see traced.quantized_allreduce), so the
        quantized path never pays a pre-multiply HBM pass. Bucket-tier
        zero padding is excluded from the scales by construction (zeros
        never raise a block absmax, quantize to zero, and leave a zero
        residual). With ``hier_stages``, compression follows the
        topology: bf16 psum on the intra-host (ICI) stage, the int8
        recipe on the cross-host (DCN) stage only — EQuARX's placement.

        ``want_res=True`` returns ``(out, residual)`` — the
        error-feedback carry in INPUT units, per-entry slices of which
        `_unpack` hands back so DistributedOptimizer-style EF composes
        with fusion.

        NOTE this body intentionally mirrors the ``block_size`` branch
        of ``traced.quantized_allreduce`` (which lacks the mask/pset/
        hier machinery but shares every numeric contract: wire-scale
        prescale fold, Average×n and /prescale residual corrections,
        prescale==0 zero carry). A change to either residual contract
        must land in BOTH — tests/test_fusion_quantized.py's fused-vs-
        unfused parity tests are the tripwire.
        """
        world = self.world
        op = ReduceOp(op)
        mask_arr = None if mask is None else np.asarray(mask, dtype=bool)
        pset_arr = (
            None if pset_mask is None else np.asarray(pset_mask, dtype=bool)
        )
        if mask_arr is not None and pset_arr is not None:
            active_arr = mask_arr & pset_arr
        else:
            active_arr = mask_arr if mask_arr is not None else pset_arr
        if active_arr is not None:
            local_groups = None  # masked local phase degenerates flat
        if local_groups is not None:
            hier_stages = None  # the local phase has no inter hop
        # divisor is static: the single controller knows the join mask
        n_active = (
            (len(local_groups[0]) if local_groups is not None else world)
            if active_arr is None
            else max(int(active_arr.sum()), 1)
        )
        if hier_stages is not None and active_arr is not None:
            hier_stages = None  # masked hierarchy degenerates to flat

        from .traced import _block_dequant, _stochastic_round_blocks

        def per_shard(x, seed):  # x: [1, N]; seed: replicated scalar
            idx = lax.axis_index(WORLD_AXIS)
            raw = x
            row = x[0].astype(jnp.float32)
            if active_arr is not None:
                active = jnp.asarray(active_arr)[idx]
                row = jnp.where(active, row, jnp.zeros_like(row))
            if hier_stages is not None:
                intra_groups, inter_groups = hier_stages
                # intra reduce-scatter FIRST (bf16 by default — ICI is
                # fast, spend 2 bytes), so the int8 inter stage below
                # quantizes the 1/L shard: the DCN hop pays
                # payload/L/4, not payload/4 (the full hierarchical
                # recipe, ops/traced.py). The matching intra all-gather
                # runs after the inter stage.
                L = len(intra_groups[0])
                mfull = row.shape[0]
                pad_l = (-mfull) % L
                if pad_l:
                    row = jnp.pad(row, (0, pad_l))
                wire_row = (
                    row.astype(jnp.bfloat16)
                    if intra_wire == "bf16"
                    else row
                )
                row = lax.psum_scatter(
                    wire_row, WORLD_AXIS, scatter_dimension=0,
                    tiled=True, axis_index_groups=intra_groups,
                ).astype(jnp.float32)
                n = len(inter_groups[0])
                groups = inter_groups
            elif local_groups is not None:
                # local phase: the whole two-stage int8 recipe runs
                # inside the slice (chunk ownership by group position)
                n = len(local_groups[0])
                groups = [list(g) for g in local_groups]
            else:
                n = world
                groups = None
            m = row.shape[0]
            chunk = -(-m // n)
            flat = (
                jnp.pad(row, (0, chunk * n - m))
                if chunk * n != m
                else row
            )
            chunks = flat.reshape(n, chunk)
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(0), seed), idx
            )
            q, scales = _stochastic_round_blocks(chunks, block, key)
            wire_scales = (
                scales * jnp.asarray(prescale, scales.dtype)
                if prescale != 1.0
                else scales
            )
            recv = lax.all_to_all(
                q, WORLD_AXIS, split_axis=0, concat_axis=0, tiled=True,
                axis_index_groups=groups,
            )
            recv_s = lax.all_to_all(
                wire_scales, WORLD_AXIS, split_axis=0, concat_axis=0,
                tiled=True, axis_index_groups=groups,
            )
            shard = jnp.sum(_block_dequant(recv, recv_s), axis=0)  # [cpad]
            if op == Average:
                shard = shard / jnp.asarray(n_active, shard.dtype)
            q2, s2 = _stochastic_round_blocks(
                shard[None], block, jax.random.fold_in(key, 7919)
            )
            all_q = lax.all_gather(
                q2[0], WORLD_AXIS, axis_index_groups=groups
            )
            all_s = lax.all_gather(
                s2[0], WORLD_AXIS, axis_index_groups=groups
            )
            out = _block_dequant(all_q, all_s)[:, :chunk].reshape(-1)[:m]
            if hier_stages is not None:
                # the reduced 1/L shard rides the intra all-gather home
                # (same wire as the intra RS leg)
                ag = (
                    out.astype(jnp.bfloat16)
                    if intra_wire == "bf16"
                    else out
                )
                out = lax.all_gather(
                    ag, WORLD_AXIS, tiled=True,
                    axis_index_groups=hier_stages[0],
                ).astype(jnp.float32)[:mfull]
            if postscale != 1.0:
                out = out * jnp.asarray(postscale, out.dtype)
            out = out.astype(x.dtype)[None]
            if pset_arr is not None:
                out = jnp.where(jnp.asarray(pset_arr)[idx], out, raw)
            if not want_res:
                return out
            if prescale == 0.0:
                # nothing is transmitted: zero carry (see
                # traced.quantized_allreduce) rather than 0/0 NaNs
                return out, jnp.zeros_like(out)
            # EF carry, both stages, input units (traced.
            # quantized_allreduce's contract): stage-1 against the
            # UNSCALED block scales; stage-2 on the owned chunk,
            # un-Averaged and un-prescaled so a +res input correction
            # cancels it exactly.
            res1 = chunks - _block_dequant(q, scales)[:, :chunk]
            res_flat = res1.reshape(-1)
            e2 = (shard - _block_dequant(q2, s2)[0])[:chunk]
            if op == Average:
                e2 = e2 * jnp.asarray(n_active, e2.dtype)
            if prescale != 1.0:
                e2 = e2 / jnp.asarray(prescale, e2.dtype)
            if local_groups is not None:
                # chunk ownership = position within the intra group
                from .traced import _group_pos_table

                own = jnp.asarray(_group_pos_table(local_groups))[idx]
            else:
                own = idx
            res_flat = lax.dynamic_update_slice(
                res_flat,
                lax.dynamic_slice(res_flat, (own * chunk,), (chunk,)) + e2,
                (own * chunk,),
            )
            res = res_flat[:m].astype(x.dtype)[None]
            if pset_arr is not None:
                res = jnp.where(
                    jnp.asarray(pset_arr)[idx], res, jnp.zeros_like(res)
                )
            return out, res

        return per_shard

    def _core_broadcast(self, root_rank, pset_mask):
        pset_arr = (
            None if pset_mask is None else np.asarray(pset_mask, dtype=bool)
        )

        def per_shard(x):
            idx = lax.axis_index(WORLD_AXIS)
            contrib = jnp.where(idx == root_rank, x, jnp.zeros_like(x))
            out = lax.psum(contrib, WORLD_AXIS)
            # Non-members of the process set keep their input unchanged
            # (reference: they don't participate at all).
            if pset_arr is not None:
                out = jnp.where(jnp.asarray(pset_arr)[idx], out, x)
            return out

        return per_shard

    def _member_tables(self, ranks):
        from ..common.process_sets import member_tables

        return member_tables(self.world, ranks)

    def _core_allgather(self, ranks=None):
        ranks_t = None if ranks is None else tuple(ranks)
        member = None
        if ranks_t is not None:
            member, _ = self._member_tables(ranks_t)

        def per_shard(x):  # [1, N] → [1, n_ranks, N]
            g = lax.all_gather(x[0], WORLD_AXIS)  # [world, N]
            if ranks_t is None:
                return g[None]
            mg = g[jnp.asarray(ranks_t)]  # static member selection
            is_m = jnp.asarray(member)[lax.axis_index(WORLD_AXIS)]
            return jnp.where(is_m, mg, jnp.zeros_like(mg))[None]

        return per_shard

    def _core_reducescatter(self, op, prescale, postscale, ranks=None):
        op = ReduceOp(op)
        if ranks is None:
            n_ranks = self.world

            def per_shard(x):  # [1, n_ranks, K] → [1, K]
                if prescale != 1.0:
                    x = x * jnp.asarray(prescale, x.dtype)
                k = x.shape[2]
                out = lax.psum_scatter(
                    x.reshape(1, n_ranks * k),
                    WORLD_AXIS,
                    scatter_dimension=1,
                    tiled=True,
                )
                if op == Average:
                    out = out / jnp.asarray(n_ranks, out.dtype)
                if postscale != 1.0:
                    out = out * jnp.asarray(postscale, out.dtype)
                return out
        else:
            ranks_t = tuple(ranks)
            n_ranks = len(ranks_t)
            member, pos = self._member_tables(ranks_t)

            def per_shard(x):  # [1, n_ranks, K] → [1, K]
                if prescale != 1.0:
                    x = x * jnp.asarray(prescale, x.dtype)
                idx = lax.axis_index(WORLD_AXIS)
                is_m = jnp.asarray(member)[idx]
                contrib = jnp.where(is_m, x, jnp.zeros_like(x))
                total = lax.psum(contrib, WORLD_AXIS)  # member sum
                mine = lax.dynamic_index_in_dim(
                    total, jnp.asarray(pos)[idx], axis=1, keepdims=False
                )  # [1, K]
                if op == Average:
                    mine = mine / jnp.asarray(n_ranks, mine.dtype)
                if postscale != 1.0:
                    mine = mine * jnp.asarray(postscale, mine.dtype)
                return jnp.where(is_m, mine, jnp.zeros_like(mine))

            return per_shard

        return per_shard

    def _core_adasum_pset(self, prescale, postscale, ranks):
        """Adasum over a process set as a masked full-axis program
        (adasum_allreduce's gather+tree formulation); non-members keep
        their input. Join masking rides the dynamic `keep` argument so
        the compiled program is mask-independent."""
        from .adasum import adasum_allreduce

        ranks_l = list(ranks)
        member, _ = self._member_tables(ranks_l)

        def per_shard(x, keep):  # x: [1, N]; keep: [1, 1] bool
            idx = lax.axis_index(WORLD_AXIS)
            raw = x
            x = jnp.where(keep, x, jnp.zeros_like(x))
            if prescale != 1.0:
                x = x * jnp.asarray(prescale, x.dtype)
            out = adasum_allreduce(
                x[0], WORLD_AXIS, groups=[ranks_l]
            )[None]
            if postscale != 1.0:
                out = out * jnp.asarray(postscale, out.dtype)
            return jnp.where(jnp.asarray(member)[idx], out, raw)

        return per_shard

    # -------------------------------------------------------- alltoall

    def _execute_alltoall(self, e: _Entry) -> None:
        """Equal-split alltoall — the one family outside the fused
        machinery (its split/concat geometry is per-entry; the uneven
        v-variant repacks on host in eager.py)."""
        if self.timeline is not None:
            self.timeline.begin(e.name, "ALLTOALL")
        ranks = self._pset_ranks(e)
        n_ranks = self.world if ranks is None else len(ranks)
        payload = e.payload
        if payload.shape[1] % n_ranks != 0:
            raise ValueError(
                f"equal-split alltoall needs dim1 divisible by the "
                f"participating rank count {n_ranks}"
            )
        key = ("alltoall", ranks, payload.shape, payload.dtype.name)
        if _sched_audit.enabled():
            _sched_audit.record(
                "alltoall",
                (
                    _sched_entry_name(e.name),
                    tuple(payload.shape),
                    payload.dtype.name,
                ),
                wire=e.wire,
                pset=(
                    0
                    if e.process_set is None
                    else e.process_set.process_set_id
                ),
            )
        fn = self._executor(key, lambda: self._build_alltoall(ranks))
        self.dispatches += 1
        self.last_cycle_dispatches += 1
        self.alltoall_dispatches += 1
        self.alltoall_wire_bytes_total += (
            int(payload.nbytes) * max(n_ranks - 1, 0) // max(n_ranks, 1)
        )
        out = fn(payload)
        if self.timeline is not None:
            self.timeline.end(e.name, "ALLTOALL")
        e.handle._fulfill(out)

    def _build_alltoall(self, ranks=None):
        if ranks is None:
            def per_shard(x):  # [1, n, ...]; n % world == 0
                return lax.all_to_all(
                    x, WORLD_AXIS, split_axis=1, concat_axis=1, tiled=True
                )
        else:
            ranks_t = tuple(ranks)
            n_ranks = len(ranks_t)
            member, pos = self._member_tables(ranks_t)

            def per_shard(x):  # [1, n, ...]; n % n_ranks == 0
                # Masked full-axis formulation: gather every row, select
                # the member block addressed to this rank's member
                # position. More wire than a member-only exchange, but
                # expressible with equal replica groups AND launched
                # identically by every process.
                row = x[0]
                k = row.shape[0] // n_ranks
                g = lax.all_gather(row, WORLD_AXIS)  # [world, n, ...]
                mg = g[jnp.asarray(ranks_t)]         # [n_ranks, n, ...]
                blocks = mg.reshape(
                    (n_ranks, n_ranks, k) + row.shape[1:]
                )
                idx = lax.axis_index(WORLD_AXIS)
                mine = lax.dynamic_index_in_dim(
                    blocks, jnp.asarray(pos)[idx], axis=1, keepdims=False
                )  # [n_ranks, k, ...]
                mine = mine.reshape((n_ranks * k,) + row.shape[1:])
                is_m = jnp.asarray(member)[idx]
                return jnp.where(is_m, mine, jnp.zeros_like(mine))[None]

        return jax.jit(self._shard_map(per_shard))


# The group builder moved to common/topology.py (the one home of the
# two-level split); re-exported here for the existing import surface.
from ..common.topology import hierarchical_stage_groups  # noqa: E402,F401


def _max_value(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.finfo(dtype).max
    return jnp.iinfo(dtype).max


def _min_value(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.finfo(dtype).min
    return jnp.iinfo(dtype).min
