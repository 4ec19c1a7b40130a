"""Global runtime state and the init/shutdown lifecycle.

TPU-native re-design of the reference's C-API bootstrap + global state
(ref: horovod/common/operations.cc `horovod_init`/`InitializeHorovodOnce` +
horovod/common/global_state.h `HorovodGlobalState` + horovod/common/basics.py
`HorovodBasics` [V], SURVEY.md §2.1/§3.1).

What is deliberately *absent* relative to the reference: the background
coordination thread and the Request/Response negotiation protocol. On TPU,
XLA's static schedule plays that role for traced code (SURVEY.md §5.8); the
eager path batches through a fusion manager (ops/fusion.py) driven from the
dispatching thread, so no dedicated coordinator thread is needed — dispatch
order is identical on every process because eager dispatch happens on the
single controller.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

from . import config as config_mod
from . import topology as topo_mod
from .process_sets import ProcessSet, ProcessSetTable


class HorovodInternalError(RuntimeError):
    """A collective failed (peer/slice died). Elastic catches this
    (ref: horovod/common/exceptions [V], surfaced to hvd.elastic.run)."""


class HostsUpdatedInterrupt(Exception):
    """Cluster membership changed; current state is still good
    (ref: horovod/common/elastic.py [V])."""


class NotInitializedError(RuntimeError):
    def __init__(self):
        super().__init__(
            "horovod_tpu has not been initialized; call hvd.init() first."
        )


class _GlobalState:
    """Singleton mirroring HorovodGlobalState (global_state.h [V])."""

    def __init__(self):
        self.lock = threading.Lock()
        self.initialized = False
        self.config: Optional[config_mod.Config] = None
        self.topology: Optional[topo_mod.Topology] = None
        self.mesh = None
        self.process_set_table: Optional[ProcessSetTable] = None
        self.fusion = None  # FusionManager, attached by ops.eager on init
        self.timeline = None  # Timeline, attached when HOROVOD_TIMELINE set
        self.traced_timeline = None  # TracedTimeline (jax.profiler wrapper)
        self.parameter_manager = None  # autotune, attached when enabled
        self.stall_inspector = None
        self.telemetry_server = None  # MetricsServer (HOROVOD_METRICS_PORT)


_state = _GlobalState()


def _maybe_init_jax_distributed(cfg: config_mod.Config) -> None:
    """Join the jax.distributed coordination service when the runner
    exported coordinator env (HOROVOD_COORDINATOR_ADDR/PORT +
    HOROVOD_NUM_PROCESSES/PROCESS_ID).

    This is the TPU-native replacement for the reference's MPI_Init /
    Gloo-rendezvous bootstrap inside BackgroundThreadLoop (ref:
    horovod/common/operations.cc §3.1 [V]): rank-0's host runs the
    coordination service; everyone else dials in. Must happen before the
    first jax.devices() call, which is why it lives at the top of init().
    """
    if not cfg.coordinator_addr or not cfg.num_processes:
        return
    if cfg.num_processes <= 1:
        return
    import jax

    if jax.distributed.is_initialized():
        return  # already joined (e.g. TPU-VM auto-bootstrap)
    jax.distributed.initialize(
        coordinator_address=f"{cfg.coordinator_addr}:{cfg.coordinator_port}",
        num_processes=cfg.num_processes,
        process_id=cfg.process_id,
    )


def _require_init() -> _GlobalState:
    if not _state.initialized:
        raise NotInitializedError()
    return _state


def state() -> _GlobalState:
    return _state


def init(process_sets: Optional[Sequence[ProcessSet]] = None) -> None:
    """Initialize the runtime: read config, discover topology, build the
    world mesh, register process sets, start aux subsystems.

    Idempotent like the reference's InitializeHorovodOnce
    (operations.cc [V]). Unlike the reference there is no thread to spawn:
    collective scheduling is XLA's job.
    """
    from . import tracing

    with _state.lock, contextlib.ExitStack() as stack:
        if _state.initialized:
            return
        # everything below is the span ``hvd.init``; the first process
        # span mints the root that the later ones hang from
        stack.enter_context(tracing.span("hvd.init"))
        cfg = config_mod.Config.from_env()
        # Logging first so every subsystem below starts up observable
        # (ref: logging.cc — level/timestamp read once at init [V]).
        from . import logging as hvd_logging

        log = hvd_logging.configure_from_init(
            cfg.log_level, cfg.log_timestamp
        )
        from .metrics import registry as _metrics

        _metrics.configure_export()  # HOROVOD_METRICS_FILE, if set
        from . import compile_cache

        compile_cache.ensure()  # before anything below can compile
        _maybe_init_jax_distributed(cfg)
        topology = topo_mod.discover(cfg)
        if cfg.rendezvous_addr:
            # Same-version gang guard (the launch driver's probe in the
            # reference, driver_service.py [V]); mismatch raises, any
            # rendezvous trouble only warns.
            from ..runner.rendezvous import check_version_consistency

            check_version_consistency(cfg, topology, log)
        _state.config = cfg
        _state.topology = topology
        _state.mesh = topology.world_mesh()
        _state.process_set_table = ProcessSetTable(topology.size)
        if process_sets:
            for ps in process_sets:
                _state.process_set_table.register(ps)

        # Aux subsystems — imported lazily to keep the init dependency graph
        # one-directional (they all depend on basics).
        from ..ops.fusion import FusionManager

        _state.fusion = FusionManager(
            mesh=_state.mesh,
            threshold_bytes=cfg.fusion_threshold_bytes,
            cycle_time_ms=cfg.cycle_time_ms,
            cache_capacity=cfg.cache_capacity,
            injit_pack=cfg.fusion_injit,
            bucketing=cfg.fusion_buckets,
            donate=cfg.fusion_donate,
            promote_after=cfg.fusion_promote_after,
            wire=cfg.fusion_wire,
            wire_block=cfg.fusion_wire_block,
            wire_hier=cfg.fusion_wire_hier,
            wire_min_bytes=cfg.fusion_wire_min_bytes,
            guard=cfg.guard,
        )
        if cfg.timeline:
            from .timeline import Timeline

            _state.timeline = Timeline(cfg.timeline, mark_cycles=cfg.timeline_mark_cycles)
            _state.fusion.timeline = _state.timeline
        if not cfg.stall_check_disable:
            from .stall_inspector import StallInspector

            _state.stall_inspector = StallInspector(
                warning_seconds=cfg.stall_warning_seconds,
                shutdown_seconds=cfg.stall_shutdown_seconds,
                straggler_factor=cfg.straggler_factor,
            )
            _state.fusion.stall_inspector = _state.stall_inspector
        # Telemetry hub (flight recorder) + optional live scrape
        # endpoint. The hub is process-wide and outlives init/shutdown
        # cycles (the flight recorder must survive a teardown to be a
        # post-mortem tool); init only refreshes its knobs and wires
        # the current timeline/inspector into it.
        from . import telemetry as telemetry_mod

        _hub = telemetry_mod.hub()
        _hub.configure(
            capacity=cfg.telemetry_steps,
            flight_path=cfg.flight_recorder,
        )
        _hub.timeline = _state.timeline
        _hub.stall_inspector = _state.stall_inspector
        if cfg.metrics_port:
            _state.telemetry_server = telemetry_mod.MetricsServer(
                port=cfg.metrics_port
            )
            _state.telemetry_server.start()
        if cfg.autotune:
            from .autotune import ParameterManager

            _state.parameter_manager = ParameterManager.from_config(cfg)
            _state.fusion.parameter_manager = _state.parameter_manager
        _state.initialized = True
        log.info(
            "initialized: world=%d local=%d platform=%s fusion=%dB "
            "cycle=%.1fms cache=%d",
            topology.size,
            topology.local_size,
            getattr(topology.devices[0], "platform", "?"),
            cfg.fusion_threshold_bytes,
            cfg.cycle_time_ms,
            cfg.cache_capacity,
        )


def shutdown() -> None:
    """Tear down (ref: horovod_shutdown in operations.cc [V])."""
    with _state.lock:
        if not _state.initialized:
            return
        if _state.fusion is not None:
            _state.fusion.flush()
        if _state.timeline is not None:
            _state.timeline.close()
        if _state.traced_timeline is not None:
            _state.traced_timeline.close()
        if _state.telemetry_server is not None:
            _state.telemetry_server.stop()
        from . import telemetry as telemetry_mod

        _hub = telemetry_mod.hub()
        _hub.timeline = None
        _hub.stall_inspector = None
        try:
            # the ring survives shutdown (post-mortem tool), but a
            # clean teardown is a natural dump point for the recorder
            _hub.dump()
        except OSError:
            pass
        _state.initialized = False
        _state.config = None
        _state.topology = None
        _state.mesh = None
        _state.process_set_table = None
        _state.fusion = None
        _state.timeline = None
        _state.traced_timeline = None
        _state.parameter_manager = None
        _state.stall_inspector = None
        _state.telemetry_server = None


def is_initialized() -> bool:
    return _state.initialized


# --- rank/size queries (ref: HorovodBasics in horovod/common/basics.py [V]) ---


def size() -> int:
    return _require_init().topology.size


def rank() -> int:
    return _require_init().topology.rank


def local_size() -> int:
    return _require_init().topology.local_size


def local_rank() -> int:
    return _require_init().topology.local_rank


def cross_size() -> int:
    return _require_init().topology.cross_size


def cross_rank() -> int:
    return _require_init().topology.cross_rank


def mesh():
    return _require_init().mesh


def topology() -> topo_mod.Topology:
    return _require_init().topology


def live_config() -> config_mod.Config:
    """The initialized runtime's config snapshot when there is one,
    else a fresh env parse — the resolution every config-deferring
    default (overlap buckets, guard, audit cadence) shares."""
    if _state.initialized and _state.config is not None:
        return _state.config
    return config_mod.Config.from_env()


def get_config() -> config_mod.Config:
    return _require_init().config


def is_homogeneous() -> bool:
    """True when every host drives the same number of chips
    (ref: horovod_is_homogeneous [V]; always true on a TPU slice)."""
    st = _require_init()
    return st.topology.size == st.topology.cross_size * st.topology.local_size


# --- build-capability predicates, API parity with basics.py [V] ---


def mpi_enabled() -> bool:
    return False


def mpi_built() -> bool:
    return False


def gloo_enabled() -> bool:
    return False


def gloo_built() -> bool:
    return False


def nccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def xla_built() -> bool:
    return True


def tpu_enabled() -> bool:
    return True


def mpi_threads_supported() -> bool:
    return False


# --- process-set API (ref: horovod/common/process_sets.py [V]) ---


def add_process_set(ranks: Sequence[int]) -> ProcessSet:
    st = _require_init()
    ps = ranks if isinstance(ranks, ProcessSet) else ProcessSet(ranks)
    return st.process_set_table.register(ps)


def remove_process_set(ps: ProcessSet) -> None:
    _require_init().process_set_table.remove(ps)


def get_process_set_ids() -> Sequence[int]:
    return _require_init().process_set_table.ids()


def get_process_set(process_set_id: int) -> ProcessSet:
    return _require_init().process_set_table.get(process_set_id)


def global_process_set() -> ProcessSet:
    return _require_init().process_set_table.global_set
