"""The scopes that name the step's device work (``hvd_exchange`` with its
``collective``s, ``hvd_update``, ``hvd_accumulate``): every exchange path carries
them in the ``op_name`` of its operations, and they are metadata only: the
optimised HLO with ``metadata={...}`` stripped is the same text with the
scope call patched out."""

import contextlib
import re
from functools import partial

import jax
import jax.numpy as jnp
import optax
import pytest
from conftest import stripped_hlo
from jax.sharding import PartitionSpec as P


def _params():
    return {"w": jnp.ones((8, 16), jnp.float32),
            "b": jnp.zeros((16,), jnp.float32),
            "v": jnp.ones((16, 4), jnp.float32)}


def _loss(p, x):
    return jnp.mean((jnp.tanh(x @ p["w"] + p["b"]) @ p["v"]) ** 2)


def _dp_step(hvd, **opt_kwargs):
    """The quick start's step on a toy model: ``DistributedOptimizer``
    inside ``jit(shard_map)``."""
    opt = hvd.DistributedOptimizer(
        optax.sgd(0.1, momentum=0.9), op=hvd.Average, **opt_kwargs)
    params = _params()
    state = opt.init(params)

    @jax.jit
    @partial(jax.shard_map, mesh=hvd.mesh(),
             in_specs=(P(), P(), P(hvd.WORLD_AXIS)), out_specs=(P(), P()),
             check_vma=False)
    def step(params, state, x):
        grads = jax.grad(_loss)(params, x[0])
        updates, state = opt.update(grads, state, params)
        return optax.apply_updates(params, updates), state

    x = jnp.ones((hvd.size(), 4, 8), jnp.float32)
    return step, (params, state, x)


def _zero_step(hvd, **opt_kwargs):
    opt = hvd.ShardedDistributedOptimizer(
        optax.sgd(0.1, momentum=0.9), op=hvd.Average, **opt_kwargs)
    params = _params()
    state = opt.init(params)

    @jax.jit
    @partial(jax.shard_map, mesh=hvd.mesh(),
             in_specs=(P(), opt.state_spec(), P(hvd.WORLD_AXIS)),
             out_specs=(P(), opt.state_spec()), check_vma=False)
    def step(params, state, x):
        grads = jax.grad(_loss)(params, x[0])
        updates, state = opt.update(grads, state, params)
        return optax.apply_updates(params, updates), state

    x = jnp.ones((hvd.size(), 4, 8), jnp.float32)
    return step, (params, state, x)


PATHS = {
    "monolithic": (_dp_step, {}),
    "monolithic-guarded": (_dp_step, {"grad_guard": True}),
    "bucketed": (_dp_step, {"overlap_buckets": 2}),
    "accumulating": (_dp_step, {"backward_passes_per_step": 2}),
    "zero1": (_zero_step, {}),
    "zero1-bucketed": (_zero_step, {"overlap_buckets": 2}),
}


def _op_names(compiled_text: str):
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_exchange_path_carries_the_scopes(hvd, path):
    build, kwargs = PATHS[path]
    step, args = build(hvd, **kwargs)
    names = [n.split("/") for n in
             _op_names(step.lower(*args).compile().as_text())]
    assert any("hvd_exchange" in n for n in names), names
    assert any("hvd_update" in n for n in names)
    if "backward_passes_per_step" in kwargs:
        assert any("hvd_accumulate" in n for n in names)
    # a collective of the step is never outside the exchange's scope
    collectives = [n for n in names if n[-1] in (
        "psum", "psum_scatter", "all_gather", "all_to_all")]
    assert collectives
    for n in collectives:
        assert "collective" in n[n.index("hvd_exchange"):], n


class _NoScope(contextlib.ContextDecorator):
    """``jax.named_scope`` patched out: a context and a decorator."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("path", ["monolithic", "bucketed", "zero1"])
def test_scopes_are_metadata_only(hvd, monkeypatch, path):
    from horovod_tpu.ops import traced

    build, kwargs = PATHS[path]
    step, args = build(hvd, **kwargs)
    scoped = step.lower(*args).compile().as_text()
    assert "hvd_exchange" in scoped and "/collective/" in scoped
    monkeypatch.setattr(jax, "named_scope", lambda name: _NoScope())
    monkeypatch.setattr(traced, "clax", jax.lax)
    step, args = build(hvd, **kwargs)
    bare = step.lower(*args).compile().as_text()
    for scope in ("hvd_exchange", "hvd_update", "/collective/"):
        assert scope not in bare, scope
    assert stripped_hlo(scoped) == stripped_hlo(bare)
