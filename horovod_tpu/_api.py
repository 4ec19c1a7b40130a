"""The ``hvd.*`` surface: everything ``import horovod_tpu as hvd``
exposes. ``horovod_tpu/__init__.py`` loads this module on the first
attribute access, so that importing the package alone (the launcher
parent, ``python -m horovod_tpu.runner``) does not import JAX.
"""

from .common.basics import (  # noqa: F401
    HorovodInternalError,
    HostsUpdatedInterrupt,
    add_process_set,
    ccl_built,
    cross_rank,
    cross_size,
    cuda_built,
    ddl_built,
    get_config,
    get_process_set,
    get_process_set_ids,
    gloo_built,
    gloo_enabled,
    global_process_set,
    init,
    is_homogeneous,
    is_initialized,
    local_rank,
    local_size,
    mesh,
    mpi_built,
    mpi_enabled,
    mpi_threads_supported,
    nccl_built,
    rank,
    remove_process_set,
    rocm_built,
    shutdown,
    size,
    topology,
    tpu_enabled,
    xla_built,
)
from .common.process_sets import ProcessSet  # noqa: F401
from .common.topology import (  # noqa: F401
    WORLD_AXIS,
    rank_sharding,
    replicated_sharding,
    shard_from_rank_fn,
)
from .ops.reduction_ops import (  # noqa: F401
    Adasum,
    Average,
    Max,
    Min,
    Product,
    ReduceOp,
    Sum,
)
from .ops.compression import Compression  # noqa: F401
from .ops.eager import (  # noqa: F401
    allgather,
    allgather_async,
    allreduce,
    allreduce_,
    allreduce_async,
    allreduce_async_,
    alltoall,
    alltoall_async,
    broadcast,
    broadcast_,
    broadcast_async,
    broadcast_async_,
    first,
    flush,
    grouped_allgather,
    grouped_allgather_async,
    grouped_allreduce,
    grouped_allreduce_async,
    grouped_reducescatter,
    grouped_reducescatter_async,
    barrier,
    join,
    join_ranks,
    my_row,
    poll,
    reducescatter,
    reducescatter_async,
    replicate,
    synchronize,
)
from .optimizer import (  # noqa: F401
    DistributedOptimizer,
    LocalSGDGradientTransformation,
    allgather_object,
    broadcast_object,
    broadcast_optimizer_state,
    broadcast_parameters,
    grad,
    value_and_grad,
)
from .sharded_optimizer import (  # noqa: F401
    ShardedDistributedOptimizer,
)
from . import ops  # noqa: F401
from .ops import traced  # noqa: F401
from .ops import overlap  # noqa: F401
from .ops.overlap import (  # noqa: F401
    bucketed_allreduce,
    build_bucket_schedule,
    overlap_boundary,
)
from .ops.fused_xent import fused_linear_cross_entropy  # noqa: F401
from . import local_sgd  # noqa: F401  (K-step ICI-local training regime)
from . import elastic  # noqa: F401  (hvd.elastic.run / State, ref [V])
from . import callbacks  # noqa: F401  (Keras-callback parity, ref [V])
from . import data  # noqa: F401  (DistributedSampler analog + prefetch)
from . import executor  # noqa: F401  (RayExecutor / spark.run parity, ref [V])
from . import checkpoint  # noqa: F401  (durable ckpt — fills ref gap, SURVEY §5.4)
from . import preemption  # noqa: F401  (TPU preemption → durable commit)
from .common import telemetry  # noqa: F401  (flight recorder + /metrics)
from .common.telemetry import (  # noqa: F401
    step_begin,
    step_end,
)
from .common.guard import (  # noqa: F401  (non-finite sentinel)
    check as guard_check,
    status as guard_status,
)
from .audit import (  # noqa: F401  (cross-rank parameter audit)
    audit,
    maybe_audit,
    tree_digest,
)


def serve(model, params, port=None, **kwargs):
    """``hvd.serve(model, params, port=...)`` — start the inference
    plane on this worker (horovod_tpu/serving/: continuous batching
    over a compiled prefill/decode split, slot KV cache, SLO-metered
    HTTP frontend, rendezvous-announced capacity, SIGTERM drain).
    Returns a ``ServeHandle``; see docs/serving.md."""
    from .serving import serve as _serve

    return _serve(model, params, port=port, **kwargs)


def __getattr__(name):
    # hvd.SyncBatchNorm parity (ref [V]) without making flax a hard
    # import-time dependency of the whole surface.
    if name == "SyncBatchNorm":
        from .models.resnet import SyncBatchNorm

        return SyncBatchNorm
    if name == "serving":
        # lazy: the serving plane is worker-role code, not launcher code
        # (import_module: ``from . import serving`` would ask the
        # package's __getattr__, which asks this one)
        import importlib

        return importlib.import_module(__package__ + ".serving")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def start_timeline(
    file_path: str, mark_cycles: bool = False, traced: bool = False
) -> None:
    """Runtime timeline activation (ref: hvd.start_timeline, v0.21+ [V]).

    ``traced=False`` (default): the eager per-collective lifecycle
    timeline (QUEUE/ALLREDUCE/... phases). ``traced=True``: an XLA
    profiler session for jit/shard_map runs — stop_timeline() writes a
    chrome://tracing JSON of every compiled op (collectives included,
    with device timestamps) and keeps the TensorBoard profile dir next
    to it. Use :func:`timeline_step` to mark step boundaries."""
    from .common import basics as _basics

    st = _basics._require_init()
    if traced:
        from .common.traced_timeline import TracedTimeline

        if st.traced_timeline is None:
            st.traced_timeline = TracedTimeline(file_path)
        st.traced_timeline.start()
        return
    from .common.timeline import Timeline

    if st.timeline is None:
        st.timeline = Timeline(file_path, mark_cycles=mark_cycles)
        st.fusion.timeline = st.timeline
        # keep the telemetry hub's step-boundary counter track on the
        # SAME timeline, whether it came from env at init or from this
        # runtime call (common/telemetry.py)
        from .common import telemetry as _telemetry

        _telemetry.hub().timeline = st.timeline
    st.timeline.start()


def stop_timeline() -> None:
    from .common import basics as _basics

    st = _basics._require_init()
    if st.traced_timeline is not None:
        st.traced_timeline.stop()
    if st.timeline is not None:
        st.timeline.stop()


def timeline_step(name: str = "step", step_num=None):
    """Context manager marking one traced training step in the profiler
    timeline (the NVTX-range analog, nvtx_op_range.h [V]). No-op when no
    traced timeline is active.

    When telemetry is enabled (flight recorder / /metrics scraper /
    HOROVOD_TELEMETRY=1) the same boundary also opens and closes a
    flight-recorder StepStats record, so profiler steps and telemetry
    steps share ids."""
    from .common import basics as _basics
    from .common import telemetry as _telemetry
    from .common.traced_timeline import TracedTimeline

    st = _basics._require_init()
    if st.traced_timeline is None:
        st.traced_timeline = TracedTimeline("horovod_timeline.json")
    ctx = st.traced_timeline.step(name, step_num)
    if not _telemetry.auto_enabled():
        return ctx
    import contextlib

    @contextlib.contextmanager
    def _with_telemetry():
        _telemetry.hub().step_begin(step_num)
        try:
            with ctx:
                yield
        finally:
            _telemetry.hub().step_end()

    return _with_telemetry()
