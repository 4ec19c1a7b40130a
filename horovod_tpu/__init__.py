"""horovod_tpu — a TPU-native distributed training framework with the
capability surface of Horovod (reference: jiaqianjing/horovod, a fork of
horovod/horovod; see SURVEY.md).

Import convention mirrors the reference's per-framework modules
(``import horovod.torch as hvd`` [V]):

    import horovod_tpu as hvd

    hvd.init()
    mesh = hvd.mesh()                       # the world: 1 chip = 1 rank
    out = hvd.allreduce(hvd.replicate(x))   # eager, fused + async-capable
    # ... or the TPU fast path: hvd.traced.allreduce inside jit/shard_map.

Architecture (SURVEY.md §7): traced collectives lower to XLA collectives
over ICI — the compiler statically schedules, fuses, and overlaps them,
replacing the reference's background negotiate-fuse-execute thread. The
eager API keeps Horovod's async-handle semantics on top of a fusion-cycle
dispatcher (ops/fusion.py). Everything honors the HOROVOD_* env contract.
"""

__version__ = "0.1.0"


def __getattr__(name):
    """Load the ``hvd.*`` surface (``_api.py``) on first use.

    Importing ``horovod_tpu`` - which ``python -m horovod_tpu.runner``
    does before anything else - must not import JAX: a launcher parent
    that initialises JAX takes the chips its workers need. The first
    attribute access imports ``_api`` and copies its names here, after
    which only ``_api``'s own lazy names (and misses) come this way.
    """
    if name.startswith("__") and name.endswith("__"):
        raise AttributeError(name)
    api = globals().get("_api")  # bound by the import system once loaded
    if api is None:
        import importlib

        # (not ``from . import _api``: that form asks this function first)
        api = importlib.import_module(__name__ + "._api")
        # a name of the surface wins over a same-named submodule
        # attribute (``hvd.audit`` is the function, as it always was)
        globals().update(
            (k, v) for k, v in vars(api).items()
            if not (k.startswith("__") and k.endswith("__"))
        )
        if name in globals():
            return globals()[name]
    return getattr(api, name)  # its own lazy names, or AttributeError
