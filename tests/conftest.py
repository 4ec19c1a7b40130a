"""Test harness: simulate an 8-chip slice on CPU.

Mirrors the reference's test strategy (SURVEY.md §4): the reference runs
`horovodrun -np 2` multi-process on localhost; we run an 8-device
host-platform mesh in one process — same closed-form collective math, real
XLA collectives, no TPU hardware needed.
"""

import os

# Must happen before jax initializes its backends.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# The suite neither reads nor leaves compiled programs on disk:
# hvd.init() points JAX's persistent cache at <checkout>/.jax_cache
# (common/compile_cache.py), which is for runs, not for unit tests.
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running multi-process integration test"
    )
    config.addinivalue_line(
        "markers", "ray: needs the real ray package (optional integration)"
    )


@pytest.fixture
def hvd():
    """Initialized horovod_tpu with clean state per test."""
    import horovod_tpu as hvd

    hvd.shutdown()
    hvd.init()
    yield hvd
    hvd.shutdown()


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def dense_attention_oracle(q, k, v, causal):
    """Shared dense-attention reference for the kernel/parallel tests:
    fp32 scores, -1e30 causal fill (matching the flash kernels'
    finite mask constant)."""
    import jax
    import jax.numpy as jnp

    d = q.shape[-1]
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(d).astype(jnp.float32)
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", p, v.astype(jnp.float32)
    ).astype(q.dtype)


def stripped_hlo(compiled_text: str) -> str:
    """A compiled program's text without what describes its source: each
    instruction's ``metadata={...}`` and the module's tables of files,
    functions, lines and stack frames (which hold the caller's own line
    numbers)."""
    import re

    text = re.sub(r", metadata=\{[^}]*\}", "", compiled_text)
    return re.sub(
        r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n"
        r"(\d+ .*\n)*", "\n", text)
