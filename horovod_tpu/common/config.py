"""Typed configuration backed by the ``HOROVOD_*`` environment-variable contract.

TPU-native re-design of the reference's two-tier config system
(ref: horovod/common/utils/env_parser.cc + horovod/runner/launch.py [V] —
see SURVEY.md §5.6; the reference mount was empty, citations are structural).

The reference parses ~30 HOROVOD_* env vars scattered across C++ and Python.
Here the full behavioral surface lives in one frozen dataclass, parsed once at
``hvd.init()`` time, while keeping the env-var names so existing launch scripts
keep working.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

# Default fusion threshold matches the reference: 64 MB
# (ref: horovod/common/fusion_buffer_manager.cc [V]).
DEFAULT_FUSION_THRESHOLD = 64 * 1024 * 1024
# Background-cycle batching window, milliseconds
# (ref: HOROVOD_CYCLE_TIME in horovod/common/operations.cc [V]).
DEFAULT_CYCLE_TIME_MS = 1.0
DEFAULT_CACHE_CAPACITY = 1024
DEFAULT_STALL_WARNING_SECONDS = 60.0
DEFAULT_STALL_SHUTDOWN_SECONDS = 0.0  # 0 = never shut down
DEFAULT_ELASTIC_DISCOVERY_INTERVAL = 1.0
# Control-plane retry/backoff defaults — the ONE home for these
# numbers: the Config fields below and RetryPolicy.from_env
# (common/retry.py) both read them, so the typed mirror and the
# pre-init env path cannot drift apart.
DEFAULT_RETRY_ATTEMPTS = 3
DEFAULT_RETRY_BACKOFF_MS = 100.0
DEFAULT_RETRY_BACKOFF_MAX_MS = 2000.0
DEFAULT_RETRY_DEADLINE_S = 60.0
DEFAULT_RETRY_ATTEMPT_TIMEOUT_S = 30.0
DEFAULT_RETRY_CIRCUIT_THRESHOLD = 3
DEFAULT_RETRY_CIRCUIT_COOLDOWN_S = 30.0
DEFAULT_STRAGGLER_QUARANTINE_POLLS = 3
# Training-state integrity plane (common/guard.py, audit.py): the
# non-finite skip-step guard escalates to HorovodInternalError after
# this many CONSECUTIVE skipped steps (the elastic restore contract),
# and the parameter audit runs every N optimizer steps (0 = off).
DEFAULT_GUARD_MAX_SKIPS = 3
DEFAULT_AUDIT_STEPS = 0
# Serving plane (horovod_tpu/serving/): decode-slot count (concurrent
# sequences), admissions per decode step, default per-request token
# budget/deadline, and the frontend port (0 = ephemeral).
DEFAULT_SERVE_PORT = 0
DEFAULT_SERVE_KV_SLOTS = 8
DEFAULT_SERVE_MAX_BATCH = 4
DEFAULT_SERVE_MAX_TOKENS = 64
DEFAULT_SERVE_DEADLINE_MS = 0.0  # 0 = no deadline
# Expert wire (parallel/moe.py, PR 12): dispatch/return wire format,
# ICI-leg format under a two-level split, block-scale granularity of
# the int8 alltoall, and the default capacity factor (the static
# per-destination buffer size; CapacityTuner can drive it per step
# harness instead of leaving it hand-set).
DEFAULT_MOE_WIRE = "fp32"
DEFAULT_MOE_INTRA_WIRE = "fp32"
DEFAULT_MOE_WIRE_BLOCK = 512
DEFAULT_MOE_CAPACITY_FACTOR = 1.25
# Serving memory plane (serving/paged_kv.py): tokens per KV page, pool
# size in pages (0 = auto: full backing, slots × max_len ÷ page_tokens
# — undersubscribe explicitly to make HBM scale with tokens in
# flight), prefix-cache toggle, and the admission reserve watermark
# (-1 = auto: 0 at full backing, one page per slot otherwise).
DEFAULT_SERVE_PAGE_TOKENS = 16
DEFAULT_SERVE_PAGES = 0
DEFAULT_SERVE_PREFIX_CACHE = True
DEFAULT_SERVE_PAGE_WATERMARK = -1
# Disaggregated prefill/decode fleet (serving/kv_transfer.py): the
# worker's role in the fleet (unified = classic single-engine worker,
# the default — single-worker deployments are untouched), the KV-page
# wire format for prefill→decode transfers (int8 = block-scaled
# quantized pages, the headline; fp32 = lossless pool-dtype
# passthrough, the bit-parity reference; bf16 = the middle ground),
# and the decode worker's transfer-ingest port (0 = ephemeral,
# announced through the capacity blobs either way).
DEFAULT_SERVE_ROLE = "unified"
DEFAULT_SERVE_KV_WIRE = "int8"
DEFAULT_SERVE_TRANSFER_PORT = 0
# Paged-attention kernel read (ops/paged_attention.py): auto = fuse the
# pool read on real TPU backends and keep the gather read (the numerics
# oracle) elsewhere; on = force the kernel (interpret-mode on CPU —
# what the parity tests and the A/B bench run); off = always gather.
DEFAULT_SERVE_PAGED_ATTN = "auto"
# Crash-safe serving (serving/frontend.py Router + drain path): hedge
# delay in ms before the Router fires a first-writer-wins backup
# request (0 = off), the SIGTERM drain deadline in seconds past which
# in-flight sequences are live-migrated to a peer instead of run to
# completion (0 = run to completion, the classic drain), and the TTL of
# the completed-result dedupe cache that makes client retries by
# request_id idempotent.
DEFAULT_SERVE_HEDGE_MS = 0.0
DEFAULT_SERVE_DRAIN_DEADLINE_S = 0.0
DEFAULT_SERVE_DEDUPE_TTL_S = 120.0


def _env_bool(name: str, default: bool = False) -> bool:
    val = os.environ.get(name)
    if val is None:
        return default
    return val.strip().lower() in ("1", "true", "yes", "on")


def _env_int(name: str, default: int) -> int:
    val = os.environ.get(name)
    if val is None or not val.strip():
        return default
    try:
        return int(val)
    except ValueError:
        raise ValueError(f"{name} must be an integer, got {val!r}")


def _env_choice(name: str, default: str, choices) -> str:
    val = os.environ.get(name)
    if val is None or not val.strip():
        return default
    val = val.strip().lower()
    if val not in choices:
        raise ValueError(
            f"{name} must be one of {'/'.join(choices)}, got {val!r}"
        )
    return val


def _env_float(name: str, default: float) -> float:
    val = os.environ.get(name)
    if val is None or not val.strip():
        return default
    try:
        return float(val)
    except ValueError:
        raise ValueError(f"{name} must be a float, got {val!r}")


@dataclasses.dataclass(frozen=True)
class Config:
    """Snapshot of every knob the framework honors.

    Field groups mirror the reference's env surface (SURVEY.md §5.6) plus
    TPU-specific additions prefixed ``mesh_*``.
    """

    # --- fusion / eager dispatch ---
    fusion_threshold_bytes: int = DEFAULT_FUSION_THRESHOLD
    cycle_time_ms: float = DEFAULT_CYCLE_TIME_MS
    cache_capacity: int = DEFAULT_CACHE_CAPACITY
    batch_d2d_memcopies: bool = True
    # in-JIT pack/unpack: one donated executable per fused batch
    # (ops/fusion.py; off = pre-rework host-side pack, the A/B baseline)
    fusion_injit: bool = True
    # power-of-two byte bucketing of the fused buffer (executor-cache
    # stability under batch-composition churn)
    fusion_buckets: bool = True
    # donate fused-batch inputs so the fusion buffer aliases them
    # (None = auto: on where the backend supports aliasing — TPU/GPU)
    fusion_donate: Optional[bool] = None
    # promote a batch composition to its own exact executable after
    # this many sightings (before that, churn rides the bucket tier)
    fusion_promote_after: int = 2
    # wire format of the fused buffer's collective: fp32 (payload
    # width), bf16 (half-width cast wire), int8 (block-scaled
    # quantized wire, EQuARX-style), or auto (per-bucket online choice
    # by goodput — common/autotune.py WireTuner)
    fusion_wire: str = "fp32"
    # elements per block scale on the int8 fused wire
    fusion_wire_block: int = 512
    # hierarchical wire: bf16 on the intra-host (ICI) stage, int8 on
    # the cross-host (DCN) stage (needs HOROVOD_HIERARCHICAL_ALLREDUCE
    # topology stages to be non-degenerate)
    fusion_wire_hier: bool = False
    # auto mode never tries int8 below this fused-buffer byte size
    # (the per-dispatch quant tax dominates tiny buffers)
    fusion_wire_min_bytes: int = 64 * 1024

    # --- reduction behavior ---
    hierarchical_allreduce: bool = False
    hierarchical_allgather: bool = False

    # --- two-level topology (common/topology.py hierarchy_stages) ---
    # HOROVOD_HIERARCHICAL: route the fused eager batch, the overlap
    # buckets and the ZeRO-2/3 exchange legs through the two-level
    # (intra-slice ICI / inter-slice DCN) recipe. "auto" (default)
    # engages it exactly when a real inter axis exists (multi-slice
    # detection, or an explicit HOROVOD_INTRA_SIZE); "on" forces it
    # wherever a non-degenerate split is resolvable; "off" keeps every
    # wire flat. The legacy HOROVOD_HIERARCHICAL_ALLREDUCE=1 is read
    # as "on".
    hierarchical: str = "auto"
    # explicit chips-per-slice override for the slice-boundary
    # detection (None = detect from JAX device slice_index / process
    # structure). Must divide the world; a non-dividing value degrades
    # to gcd(intra, world) so an elastic reshard (8 -> 6) keeps a
    # valid two-level split instead of crashing.
    intra_size: Optional[int] = None
    # axis NAME the two-level world mesh uses for the cross-slice
    # (DCN) dimension; the intra axis is always "intra"
    inter_axis: str = "inter"
    # straggler-aware scheduling (elastic/driver.py): publish per-rank
    # micro-batch weights into the rendezvous KV, down-weighting ranks
    # whose step p50 STAYS flagged by the straggler ledger, instead of
    # only logging them. Workers read the weights via
    # hvd.elastic.rebalance_weight().
    rebalance: bool = False
    # local-SGD mode (horovod_tpu/local_sgd.py): slices train
    # independently on their ICI-only wire for K micro-steps, then
    # reconcile parameter deltas across the inter (DCN) axis with
    # hierarchical Adasum on the int8 inter wire. 1 (default) = the
    # existing every-step sync path; the mode engages at K > 1.
    # Explicit local_sgd_steps= per optimizer always wins.
    local_sgd_steps: int = 1

    # --- ZeRO sharding stage (sharded_optimizer.py) ---
    # default zero_stage for ShardedDistributedOptimizer(zero_stage=None):
    # 1 = optimizer-state sharding only, 2 = + gradient shards (bucketed
    # reduce-scatter straight into shard storage), 3 = + parameter shards
    # (forward-interleaved per-bucket all-gather). Explicit zero_stage=
    # per optimizer always wins.
    zero_stage: int = 1
    # wire format of the SHARDED exchange legs (reduce-scatter /
    # all-gather) when the optimizer passes wire=None. Deliberately a
    # SEPARATE knob from fusion_wire: HOROVOD_FUSION_WIRE governs the
    # eager fused allreduce wire, and inheriting it here would silently
    # change sharded-optimizer numerics (and its state layout) for
    # deployments that set it long before ZeRO-2/3 existed.
    zero_wire: str = "fp32"

    # --- backward-interleaved gradient exchange (ops/overlap.py) ---
    # master switch: when on, DistributedOptimizer / value_and_grad /
    # ShardedDistributedOptimizer default to the bucketed exchange
    # (N independent per-bucket collectives XLA overlaps with backprop)
    # unless the caller passes overlap_buckets= explicitly
    overlap: bool = False
    # bucket count of the default schedule (explicit overlap_buckets=
    # always wins). For a measured choice, the step harness can sweep
    # candidates through common/autotune.py's OverlapTuner — a bucket
    # count is a compile-time property of the step, so tuning happens
    # across recompiles at the loop level (OverlapTuner's docstring
    # has the loop), never inside one compiled step
    overlap_buckets: int = 4
    # buckets below this byte size merge forward: per-collective launch
    # overhead outweighs any overlap win under the floor
    overlap_min_bytes: int = 1 << 20

    # --- expert wire (parallel/moe.py) ---
    # dispatch/return wire of the MoE alltoall: fp32 (payload width),
    # bf16, int8 (block-scaled quantized, ops/traced.py
    # quantized_alltoall), or auto (trace-time choice through the
    # shared WireTuner's (alltoall, hop) keys). Under a two-level
    # split (HOROVOD_HIERARCHICAL) this names the INTER (DCN) hop.
    moe_wire: str = DEFAULT_MOE_WIRE
    # ICI-leg format of the two-level expert dispatch (never int8 —
    # the quant tax cannot pay for itself inside a slice)
    moe_intra_wire: str = DEFAULT_MOE_INTRA_WIRE
    # elements per block scale on the int8 expert wire
    moe_wire_block: int = DEFAULT_MOE_WIRE_BLOCK
    # default capacity factor of the switch-MoE dispatch buffer
    # (explicit capacity_factor= per call wins)
    moe_capacity_factor: float = DEFAULT_MOE_CAPACITY_FACTOR

    # --- autotune ---
    autotune: bool = False
    autotune_log: Optional[str] = None
    # directory for persistent tuner state (common/autotune.py):
    # WireTuner / OverlapTuner / CapacityTuner observations serialize
    # here keyed by (tuner name, topology fingerprint) and warm-start
    # exploration across runs. None = in-memory only.
    tuner_cache: Optional[str] = None
    autotune_warmup_samples: int = 3
    autotune_steps_per_sample: int = 10
    autotune_bayes_opt_max_samples: int = 20
    autotune_gaussian_process_noise: float = 0.8

    # --- timeline ---
    timeline: Optional[str] = None
    timeline_mark_cycles: bool = False

    # --- telemetry (common/telemetry.py) ---
    # flight-recorder ring size: the last N closed StepStats records
    telemetry_steps: int = 256
    # JSON-lines path the ring is dumped to on exit/SIGTERM (None = off)
    flight_recorder: Optional[str] = None
    # per-worker /metrics + /telemetry scrape port (0 = no server)
    metrics_port: int = 0
    # straggler threshold: flag ranks whose heartbeat-reported step_ms
    # p50 exceeds this multiple of the gang median
    straggler_factor: float = 3.0

    # --- trace plane (common/tracing.py) ---
    # master switch for cross-host request/step spans; off by default so
    # the decode hot path carries zero tracing cost
    trace: bool = False
    # fraction of minted root contexts that are sampled (descendant
    # spans inherit the root's decision, so a trace is all-or-nothing)
    trace_sample: float = 1.0
    # per-worker span ring size; oldest spans are evicted first
    trace_spans: int = 2048

    # --- stall inspector ---
    stall_check_disable: bool = False
    stall_warning_seconds: float = DEFAULT_STALL_WARNING_SECONDS
    stall_shutdown_seconds: float = DEFAULT_STALL_SHUTDOWN_SECONDS

    # --- control-plane retry/backoff (common/retry.py) ---
    # Typed mirror of the HOROVOD_RETRY_* contract; the live consumer
    # is RetryPolicy.from_env, which shares these defaults and parsers
    # (policies are built before hvd.init(), so they cannot depend on
    # an initialized Config instance).
    # attempts per cross-host hop (rendezvous KV, signed RPC,
    # heartbeats, discovery); 1 = the old single-attempt behavior
    retry_attempts: int = DEFAULT_RETRY_ATTEMPTS
    # first backoff delay, doubled per retry with +/-25% jitter
    retry_backoff_ms: float = DEFAULT_RETRY_BACKOFF_MS
    retry_backoff_max_ms: float = DEFAULT_RETRY_BACKOFF_MAX_MS
    # overall deadline across one hop's attempts (0 = unbounded)
    retry_deadline_s: float = DEFAULT_RETRY_DEADLINE_S
    # per-attempt socket/urlopen timeout hint
    retry_attempt_timeout_s: float = DEFAULT_RETRY_ATTEMPT_TIMEOUT_S
    # consecutive exhausted rounds against one peer before its circuit
    # opens (fail-fast CircuitOpenError instead of a full backoff
    # ladder per touch); 0 disables the breaker
    retry_circuit_threshold: int = DEFAULT_RETRY_CIRCUIT_THRESHOLD
    retry_circuit_cooldown_s: float = DEFAULT_RETRY_CIRCUIT_COOLDOWN_S
    # deterministic fault-injection plan (testing/chaos.py syntax, or
    # @/path/to/file); None = chaos off
    fault_plan: Optional[str] = None
    # self-healing driver: quarantine a host after its rank is flagged
    # as a straggler for this many CONSECUTIVE fresh heartbeat
    # observations (proactive gang-restart excluding it); 0 disables
    straggler_quarantine_polls: int = DEFAULT_STRAGGLER_QUARANTINE_POLLS

    # --- training-state integrity (common/guard.py, audit.py) ---
    # non-finite sentinel: when on, DistributedOptimizer /
    # ShardedDistributedOptimizer fold a per-bucket finiteness
    # reduction into the compiled update and SKIP the step (zero
    # update, optimizer state and EF residuals untouched) when the
    # reduced gradients carry a NaN/Inf, instead of silently poisoning
    # every parameter. Explicit grad_guard= per optimizer always wins.
    guard: bool = False
    # consecutive skipped steps before the guard escalates to
    # HorovodInternalError (-> hvd.elastic.run restores the last
    # commit); 0 = skip forever, never escalate
    guard_max_skips: int = DEFAULT_GUARD_MAX_SKIPS
    # cross-rank parameter audit cadence: hvd.audit_maybe(tree, step)
    # digests every N steps (0 = off). Digest mismatches across ranks
    # surface through the rendezvous KV as a `divergence` restart.
    audit_steps: int = DEFAULT_AUDIT_STEPS
    # collective-schedule audit (analysis/sched_audit.py): every eager
    # fused dispatch folds (op kind, composition, wire, pset) into a
    # per-rank rolling fingerprint, published beside the parameter
    # digests on the HOROVOD_AUDIT_STEPS cadence; the driver flags a
    # rank whose compiled collective schedule diverges (reason
    # `sched_divergence`) before the mismatch becomes a hang. The fold
    # is a sub-microsecond hash per DISPATCH (not per step), so it is
    # on by default; 0 disables recording and publication.
    sched_audit: bool = True

    # --- serving plane (horovod_tpu/serving/) ---
    # hvd.serve frontend port (0 = ephemeral, announced over the
    # rendezvous KV either way)
    serve_port: int = DEFAULT_SERVE_PORT
    # decode slots = concurrent in-flight sequences per worker (the
    # fixed decode-batch shape; also the KV cache's batch dimension)
    serve_kv_slots: int = DEFAULT_SERVE_KV_SLOTS
    # prefill admissions between two decode steps — the TTFT-vs-TPOT
    # interleaving policy knob (serving/batcher.py)
    serve_max_batch: int = DEFAULT_SERVE_MAX_BATCH
    # default per-request new-token budget (per-request max_tokens wins)
    serve_max_tokens: int = DEFAULT_SERVE_MAX_TOKENS
    # default per-request deadline in ms (0 = none; per-request wins)
    serve_deadline_ms: float = DEFAULT_SERVE_DEADLINE_MS
    # paged KV memory plane: tokens per page, pool pages (0 = full
    # backing), prefix-cache toggle, admission watermark (-1 = auto)
    serve_page_tokens: int = DEFAULT_SERVE_PAGE_TOKENS
    serve_pages: int = DEFAULT_SERVE_PAGES
    serve_prefix_cache: bool = DEFAULT_SERVE_PREFIX_CACHE
    serve_page_watermark: int = DEFAULT_SERVE_PAGE_WATERMARK
    # disaggregated fleet: worker role, KV transfer wire format, and
    # the transfer-ingest port (serving/kv_transfer.py)
    serve_role: str = DEFAULT_SERVE_ROLE
    serve_kv_wire: str = DEFAULT_SERVE_KV_WIRE
    serve_transfer_port: int = DEFAULT_SERVE_TRANSFER_PORT
    # paged-attention kernel read: auto / on / off
    serve_paged_attn: str = DEFAULT_SERVE_PAGED_ATTN
    # crash-safe serving: Router hedge delay (ms, 0 = off), SIGTERM
    # drain deadline before live migration (s, 0 = run to completion),
    # completed-result dedupe cache TTL (s)
    serve_hedge_ms: float = DEFAULT_SERVE_HEDGE_MS
    serve_drain_deadline_s: float = DEFAULT_SERVE_DRAIN_DEADLINE_S
    serve_dedupe_ttl_s: float = DEFAULT_SERVE_DEDUPE_TTL_S

    # --- logging ---
    log_level: str = "warning"
    log_timestamp: bool = True

    # --- rank / rendezvous contract (set by the runner for each worker) ---
    rank: Optional[int] = None
    size: Optional[int] = None
    local_rank: Optional[int] = None
    local_size: Optional[int] = None
    cross_rank: Optional[int] = None
    cross_size: Optional[int] = None
    controller: str = "tpu"
    cpu_operations: str = "xla"
    rendezvous_addr: Optional[str] = None
    rendezvous_port: Optional[int] = None
    gloo_timeout_seconds: float = 30.0
    # jax.distributed coordination service (set by the runner; replaces
    # the reference's MPI_Init / Gloo rendezvous bootstrap — SURVEY §5.8)
    coordinator_addr: Optional[str] = None
    coordinator_port: Optional[int] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None
    secret_key_hex: Optional[str] = None

    # --- elastic ---
    elastic_discovery_interval: float = DEFAULT_ELASTIC_DISCOVERY_INTERVAL
    # persistent executable cache root (common/exe_cache.py): serialized
    # AOT executables keyed by (topology fp, HLO fp, wire, donation);
    # None = disk tier off everywhere
    exe_cache: Optional[str] = None
    # warm-standby hosts the elastic driver holds OUT of the gang,
    # pre-initialized (rendezvous-registered, executables deserialized,
    # params staged) so restarts/scale-ups swap one in instead of
    # cold-starting; 0 = off
    warm_standby: int = 0

    # --- TPU mesh ---
    mesh_shape: Optional[str] = None  # e.g. "dp=8" or "dp=4,tp=2"
    num_streams: int = 1

    @staticmethod
    def from_env() -> "Config":
        env = os.environ
        rendezvous_port = env.get("HOROVOD_GLOO_RENDEZVOUS_PORT")
        return Config(
            fusion_threshold_bytes=_env_int(
                "HOROVOD_FUSION_THRESHOLD", DEFAULT_FUSION_THRESHOLD
            ),
            cycle_time_ms=_env_float("HOROVOD_CYCLE_TIME", DEFAULT_CYCLE_TIME_MS),
            cache_capacity=_env_int("HOROVOD_CACHE_CAPACITY", DEFAULT_CACHE_CAPACITY),
            batch_d2d_memcopies=_env_bool("HOROVOD_BATCH_D2D_MEMCOPIES", True),
            fusion_injit=_env_bool("HOROVOD_FUSION_INJIT", True),
            fusion_buckets=_env_bool("HOROVOD_FUSION_BUCKETS", True),
            fusion_donate=(
                None
                if env.get("HOROVOD_FUSION_DONATE", "auto").strip().lower()
                in ("auto", "")
                else _env_bool("HOROVOD_FUSION_DONATE")
            ),
            fusion_promote_after=_env_int("HOROVOD_FUSION_PROMOTE_AFTER", 2),
            fusion_wire=_env_choice(
                "HOROVOD_FUSION_WIRE",
                "fp32",
                ("fp32", "bf16", "int8", "auto"),
            ),
            fusion_wire_block=_env_int("HOROVOD_FUSION_WIRE_BLOCK", 512),
            fusion_wire_hier=_env_bool("HOROVOD_FUSION_WIRE_HIER"),
            fusion_wire_min_bytes=_env_int(
                "HOROVOD_FUSION_WIRE_MIN_BYTES", 64 * 1024
            ),
            hierarchical_allreduce=_env_bool("HOROVOD_HIERARCHICAL_ALLREDUCE"),
            hierarchical_allgather=_env_bool("HOROVOD_HIERARCHICAL_ALLGATHER"),
            hierarchical=_env_choice(
                "HOROVOD_HIERARCHICAL", "auto", ("auto", "on", "off")
            ),
            intra_size=(
                _env_int("HOROVOD_INTRA_SIZE", 0)
                if env.get("HOROVOD_INTRA_SIZE", "").strip()
                else None
            ),
            inter_axis=env.get("HOROVOD_INTER_AXIS", "inter").strip()
            or "inter",
            rebalance=_env_bool("HOROVOD_REBALANCE"),
            local_sgd_steps=_env_int("HOROVOD_LOCAL_SGD_STEPS", 1),
            zero_stage=int(
                _env_choice("HOROVOD_ZERO_STAGE", "1", ("1", "2", "3"))
            ),
            zero_wire=_env_choice(
                "HOROVOD_ZERO_WIRE",
                "fp32",
                ("fp32", "bf16", "int8", "auto"),
            ),
            overlap=_env_bool("HOROVOD_OVERLAP"),
            overlap_buckets=_env_int("HOROVOD_OVERLAP_BUCKETS", 4),
            overlap_min_bytes=_env_int(
                "HOROVOD_OVERLAP_MIN_BYTES", 1 << 20
            ),
            moe_wire=_env_choice(
                "HOROVOD_MOE_WIRE",
                DEFAULT_MOE_WIRE,
                ("fp32", "bf16", "int8", "auto"),
            ),
            moe_intra_wire=_env_choice(
                "HOROVOD_MOE_INTRA_WIRE",
                DEFAULT_MOE_INTRA_WIRE,
                ("fp32", "bf16"),
            ),
            moe_wire_block=_env_int(
                "HOROVOD_MOE_WIRE_BLOCK", DEFAULT_MOE_WIRE_BLOCK
            ),
            moe_capacity_factor=_env_float(
                "HOROVOD_MOE_CAPACITY_FACTOR", DEFAULT_MOE_CAPACITY_FACTOR
            ),
            autotune=_env_bool("HOROVOD_AUTOTUNE"),
            autotune_log=env.get("HOROVOD_AUTOTUNE_LOG"),
            tuner_cache=env.get("HOROVOD_TUNER_CACHE") or None,
            autotune_warmup_samples=_env_int("HOROVOD_AUTOTUNE_WARMUP_SAMPLES", 3),
            autotune_steps_per_sample=_env_int(
                "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE", 10
            ),
            autotune_bayes_opt_max_samples=_env_int(
                "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES", 20
            ),
            autotune_gaussian_process_noise=_env_float(
                "HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE", 0.8
            ),
            timeline=env.get("HOROVOD_TIMELINE"),
            timeline_mark_cycles=_env_bool("HOROVOD_TIMELINE_MARK_CYCLES"),
            telemetry_steps=_env_int("HOROVOD_TELEMETRY_STEPS", 256),
            flight_recorder=env.get("HOROVOD_FLIGHT_RECORDER") or None,
            metrics_port=_env_int("HOROVOD_METRICS_PORT", 0),
            straggler_factor=_env_float("HOROVOD_STRAGGLER_FACTOR", 3.0),
            trace=_env_bool("HOROVOD_TRACE"),
            trace_sample=_env_float("HOROVOD_TRACE_SAMPLE", 1.0),
            trace_spans=_env_int("HOROVOD_TRACE_SPANS", 2048),
            stall_check_disable=_env_bool("HOROVOD_STALL_CHECK_DISABLE"),
            stall_warning_seconds=_env_float(
                "HOROVOD_STALL_CHECK_TIME_SECONDS", DEFAULT_STALL_WARNING_SECONDS
            ),
            stall_shutdown_seconds=_env_float(
                "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS", DEFAULT_STALL_SHUTDOWN_SECONDS
            ),
            retry_attempts=_env_int(
                "HOROVOD_RETRY_ATTEMPTS", DEFAULT_RETRY_ATTEMPTS
            ),
            retry_backoff_ms=_env_float(
                "HOROVOD_RETRY_BACKOFF_MS", DEFAULT_RETRY_BACKOFF_MS
            ),
            retry_backoff_max_ms=_env_float(
                "HOROVOD_RETRY_BACKOFF_MAX_MS", DEFAULT_RETRY_BACKOFF_MAX_MS
            ),
            retry_deadline_s=_env_float(
                "HOROVOD_RETRY_DEADLINE_S", DEFAULT_RETRY_DEADLINE_S
            ),
            retry_attempt_timeout_s=_env_float(
                "HOROVOD_RETRY_ATTEMPT_TIMEOUT_S",
                DEFAULT_RETRY_ATTEMPT_TIMEOUT_S,
            ),
            retry_circuit_threshold=_env_int(
                "HOROVOD_RETRY_CIRCUIT_THRESHOLD",
                DEFAULT_RETRY_CIRCUIT_THRESHOLD,
            ),
            retry_circuit_cooldown_s=_env_float(
                "HOROVOD_RETRY_CIRCUIT_COOLDOWN_S",
                DEFAULT_RETRY_CIRCUIT_COOLDOWN_S,
            ),
            fault_plan=env.get("HOROVOD_FAULT_PLAN") or None,
            straggler_quarantine_polls=_env_int(
                "HOROVOD_STRAGGLER_QUARANTINE_POLLS",
                DEFAULT_STRAGGLER_QUARANTINE_POLLS,
            ),
            guard=_env_bool("HOROVOD_GUARD"),
            guard_max_skips=_env_int(
                "HOROVOD_GUARD_MAX_SKIPS", DEFAULT_GUARD_MAX_SKIPS
            ),
            audit_steps=_env_int(
                "HOROVOD_AUDIT_STEPS", DEFAULT_AUDIT_STEPS
            ),
            sched_audit=_env_bool("HOROVOD_SCHED_AUDIT", True),
            serve_port=_env_int("HOROVOD_SERVE_PORT", DEFAULT_SERVE_PORT),
            serve_kv_slots=_env_int(
                "HOROVOD_SERVE_KV_SLOTS", DEFAULT_SERVE_KV_SLOTS
            ),
            serve_max_batch=_env_int(
                "HOROVOD_SERVE_MAX_BATCH", DEFAULT_SERVE_MAX_BATCH
            ),
            serve_max_tokens=_env_int(
                "HOROVOD_SERVE_MAX_TOKENS", DEFAULT_SERVE_MAX_TOKENS
            ),
            serve_deadline_ms=_env_float(
                "HOROVOD_SERVE_DEADLINE_MS", DEFAULT_SERVE_DEADLINE_MS
            ),
            serve_page_tokens=_env_int(
                "HOROVOD_SERVE_PAGE_TOKENS", DEFAULT_SERVE_PAGE_TOKENS
            ),
            serve_pages=_env_int(
                "HOROVOD_SERVE_PAGES", DEFAULT_SERVE_PAGES
            ),
            serve_prefix_cache=_env_bool(
                "HOROVOD_SERVE_PREFIX_CACHE", DEFAULT_SERVE_PREFIX_CACHE
            ),
            serve_page_watermark=_env_int(
                "HOROVOD_SERVE_PAGE_WATERMARK",
                DEFAULT_SERVE_PAGE_WATERMARK,
            ),
            serve_role=_env_choice(
                "HOROVOD_SERVE_ROLE", DEFAULT_SERVE_ROLE,
                ("unified", "prefill", "decode"),
            ),
            serve_kv_wire=_env_choice(
                "HOROVOD_SERVE_KV_WIRE", DEFAULT_SERVE_KV_WIRE,
                ("fp32", "bf16", "int8"),
            ),
            serve_transfer_port=_env_int(
                "HOROVOD_SERVE_TRANSFER_PORT",
                DEFAULT_SERVE_TRANSFER_PORT,
            ),
            serve_paged_attn=_env_choice(
                "HOROVOD_SERVE_PAGED_ATTN", DEFAULT_SERVE_PAGED_ATTN,
                ("auto", "on", "off"),
            ),
            serve_hedge_ms=_env_float(
                "HOROVOD_SERVE_HEDGE_MS", DEFAULT_SERVE_HEDGE_MS
            ),
            serve_drain_deadline_s=_env_float(
                "HOROVOD_SERVE_DRAIN_DEADLINE_S",
                DEFAULT_SERVE_DRAIN_DEADLINE_S,
            ),
            serve_dedupe_ttl_s=_env_float(
                "HOROVOD_SERVE_DEDUPE_TTL_S", DEFAULT_SERVE_DEDUPE_TTL_S
            ),
            log_level=env.get("HOROVOD_LOG_LEVEL", "warning").lower(),
            log_timestamp=_env_bool("HOROVOD_LOG_TIMESTAMP", True),
            rank=_env_int("HOROVOD_RANK", -1) if "HOROVOD_RANK" in env else None,
            size=_env_int("HOROVOD_SIZE", -1) if "HOROVOD_SIZE" in env else None,
            local_rank=(
                _env_int("HOROVOD_LOCAL_RANK", -1)
                if "HOROVOD_LOCAL_RANK" in env
                else None
            ),
            local_size=(
                _env_int("HOROVOD_LOCAL_SIZE", -1)
                if "HOROVOD_LOCAL_SIZE" in env
                else None
            ),
            cross_rank=(
                _env_int("HOROVOD_CROSS_RANK", -1)
                if "HOROVOD_CROSS_RANK" in env
                else None
            ),
            cross_size=(
                _env_int("HOROVOD_CROSS_SIZE", -1)
                if "HOROVOD_CROSS_SIZE" in env
                else None
            ),
            controller=env.get("HOROVOD_CONTROLLER", "tpu").lower(),
            cpu_operations=env.get("HOROVOD_CPU_OPERATIONS", "xla").lower(),
            rendezvous_addr=env.get("HOROVOD_GLOO_RENDEZVOUS_ADDR"),
            rendezvous_port=int(rendezvous_port) if rendezvous_port else None,
            gloo_timeout_seconds=_env_float("HOROVOD_GLOO_TIMEOUT_SECONDS", 30.0),
            coordinator_addr=env.get("HOROVOD_COORDINATOR_ADDR"),
            coordinator_port=(
                int(env["HOROVOD_COORDINATOR_PORT"])
                if env.get("HOROVOD_COORDINATOR_PORT")
                else None
            ),
            num_processes=(
                _env_int("HOROVOD_NUM_PROCESSES", -1)
                if "HOROVOD_NUM_PROCESSES" in env
                else None
            ),
            process_id=(
                _env_int("HOROVOD_PROCESS_ID", -1)
                if "HOROVOD_PROCESS_ID" in env
                else None
            ),
            secret_key_hex=env.get("HOROVOD_SECRET_KEY"),
            elastic_discovery_interval=_env_float(
                "HOROVOD_ELASTIC_DISCOVERY_INTERVAL",
                DEFAULT_ELASTIC_DISCOVERY_INTERVAL,
            ),
            exe_cache=env.get("HOROVOD_EXE_CACHE") or None,
            warm_standby=_env_int("HOROVOD_WARM_STANDBY", 0),
            mesh_shape=env.get("HOROVOD_TPU_MESH"),
            num_streams=_env_int("HOROVOD_NUM_STREAMS", 1),
        )
