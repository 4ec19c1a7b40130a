"""The kernels of the 8k-token expert model compiled at their real widths for
a described TPU v5e (no chip: the TPU's compiler is installed here and
compiles for a chip that is described and not attached). What interpret
mode cannot show: a block that Mosaic refuses, more VMEM than a kernel may
use. Nothing runs, so this says nothing about results or times.

The topology is described inside a fixture, in this one file: only one
process at a time may load the TPU's library, and it keeps it until it
exits."""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.common import tracing
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.parallel import moe


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """The code asks the backend whether to interpret its kernels; this
    is a compile for the TPU."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compiled(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


def _dkv_grids():
    """What the blocked dK/dV calls traced so far said of their grids."""
    return [r["tags"] for r in tracing.recorder().spans()
            if r["name"] == "hvd.kernels.flash_call"
            and r["tags"]["kernel"] == "flash_dkv"
            and r["tags"]["staging"] == "blocked"]


@pytest.mark.parametrize("window, tiles", [(2048, 70), (None, 136)],
                         ids=["window", "full"])
def test_flash_kernels_compile_at_b2_s8192_gqa8_head128(
        window, tiles, one_chip, as_on_the_chip):
    shape = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16,
                              sharding=one_chip)
    q, kv = shape((2, 8192, 32, 128)), shape((2, 8192, 4, 128))
    assert not fa.fits_vmem(8192, 128, 8, 2, 512)  # dK/dV stages by block

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32))

    # forward, dQ, dK/dV
    traced = len(_dkv_grids())
    assert _compiled(jax.grad(loss, (0, 1, 2)), q, kv, kv) == 3
    # the dK/dV grid (its steps' table in scalar memory): the kept tiles of
    # 8 kv rows, each step a Q tile of the group's 8 query heads, where the
    # widest band's rectangle held 8 x 16 x 8 x (5 | 16) = 5,120 | 16,384
    # steps of one head each; their 10 MiB of blocks compile at Mosaic's
    # default limit
    (grid,) = _dkv_grids()[traced:]
    assert grid["grid_steps"] == grid["kept_tiles"] == 8 * tiles
    assert (grid["members_per_step"], grid["vmem_limit_bytes"]) == (8, 0)


def test_flash_kernels_compile_at_s16384_gqa8_head128_block_diffusion(
        one_chip, as_on_the_chip):
    """Block-diffusion training's shapes: one row of 8192 tokens twice, 32
    query heads on 4 key/value heads of 128, blocks of 4 tokens. K and V
    whole-sequence, twice, are 16 MiB, so the forward and dQ kernels
    compile with Mosaic's scoped limit raised (``_staging_params``); the
    dK/dV kernel stages by block, a grid step for each of the 288 tiles
    the mask keeps, taking the group's 8 query heads (a table of 288 int32
    in scalar memory; 10 MiB of q, dO, o and lse blocks, at Mosaic's
    default limit). The mask's vectors of one column and of one row, the
    integer division by the block length and the scalar prefetch are what
    interpret mode cannot vouch for."""
    shape = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16,
                              sharding=one_chip)
    q, kv = shape((1, 16384, 32, 128)), shape((1, 16384, 4, 128))
    assert not fa.fits_vmem(16384, 128, 8, 2, 512)
    assert fa.staged_vmem_bytes(16384, (128, 2), (128, 2)) == 16 * 2**20

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(
            q, k, v, block_diffusion=4).astype(jnp.float32))

    # forward, dQ, dK/dV
    traced = len(_dkv_grids())
    assert _compiled(jax.grad(loss, (0, 1, 2)), q, kv, kv) == 3
    # 4 kv rows x 288 tiles of 8 query heads, where a step of one head
    # each made 9,216 and the rectangle of the widest band (32 Q tiles)
    # 4 x 32 x 8 x 32 = 32,768
    (grid,) = _dkv_grids()[traced:]
    assert grid["grid_steps"] == grid["kept_tiles"] == 1152
    assert (grid["members_per_step"], grid["vmem_limit_bytes"]) == (8, 0)


def test_a_group_past_a_steps_share_of_vmem_compiles_in_two_parts(
        one_chip, as_on_the_chip):
    """32 query heads on one kv head of 128: the group's blocks would be 40
    MiB, so a step takes 16 query heads (20 MiB, the limit raised to 46) and
    the grid's innermost dimension the group's two parts."""
    shape = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16,
                              sharding=one_chip)
    q, kv = shape((1, 8192, 32, 128)), shape((1, 8192, 1, 128))
    assert not fa.fits_vmem(8192, 128, 32, 2, 512)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(
            q, k, v, causal=True, window=1024).astype(jnp.float32))

    traced = len(_dkv_grids())
    assert _compiled(jax.grad(loss, (0, 1, 2)), q, kv, kv) == 3
    # bands of 3 Q tiles, then 2 and 1: 45 steps, each twice
    (grid,) = _dkv_grids()[traced:]
    assert grid["grid_steps"] == grid["kept_tiles"] == 2 * 45
    assert grid["members_per_step"] == 16
    assert grid["vmem_limit_bytes"] == 46 * 2**20


@pytest.mark.parametrize("staging", ["whole-sequence", "by-block"])
def test_flash_kernels_compile_at_b2_s8192_key192_value128(
        staging, one_chip, as_on_the_chip, monkeypatch):
    """Latent attention's shapes: 32 heads, each with its own 192-wide key
    and 128-wide value. K and V whole-sequence, twice, are 12 MiB (a
    192-wide row takes 256 lanes), the dK/dV kernel's q, do, o and lse 24:
    all three kernels compile only with Mosaic's scoped limit raised
    (``_staging_params``; refused at 16.03, 16.03 and 25.5 MiB against
    16 without)."""
    assert fa.staged_vmem_bytes(8192, (192, 2), (128, 2)) == 12 * 2**20
    assert fa.staged_vmem_bytes(
        8192, (192, 2), (128, 2), (128, 2), (1, 4)) == 24 * 2**20
    if staging == "by-block":
        monkeypatch.setenv("HOROVOD_FLASH_VMEM_BUDGET", "1")
    shape = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16,
                              sharding=one_chip)
    qk, v = shape((2, 8192, 32, 192)), shape((2, 8192, 32, 128))
    assert fa.fits_vmem(8192, 192, 1, 2, 512, 128) == (
        staging == "whole-sequence")

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(
            q, k, v, causal=True).astype(jnp.float32))

    # forward, dQ, dK/dV
    assert _compiled(jax.grad(loss, (0, 1, 2)), qk, qk, v) == 3


@pytest.mark.parametrize("seq, d, d_v, staged_mib, raised", [
    (8192, 128, 128, 20, True), (8192, 64, 64, 20, True),
    (4096, 192, 128, 12, True), (4096, 128, 128, 10, False)])
def test_whole_sequence_dkv_compiles_wherever_the_gate_lets_it_through(
        seq, d, d_v, staged_mib, raised, one_chip, as_on_the_chip):
    """One head a group, so ``fits_vmem`` keeps the whole-sequence dK/dV
    kernel; Mosaic holds q, do, o and the lse twice and lane-rounded, and
    refused the first three at its default limit (21, 21 and 16.84 MiB
    held against 16) until the call asked for more; 10 MiB staged is the
    most that compiles without."""
    assert fa.fits_vmem(seq, d, 1, 2, 512, d_v)
    operands = ((d, 2), (d_v, 2), (d_v, 2), (1, 4))
    assert fa.staged_vmem_bytes(seq, *operands) == staged_mib * 2**20
    assert (fa._staging_params(seq, *operands) is not None) == raised
    shape = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16,
                              sharding=one_chip)
    qk, v = shape((2, seq, 32, d)), shape((2, seq, 32, d_v))

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(
            q, k, v, causal=True).astype(jnp.float32))

    assert _compiled(jax.grad(loss, (0, 1, 2)), qk, qk, v) == 3


def test_held_experts_compile_at_16384_tokens_top8_16_of_128(
        one_chip, as_on_the_chip):
    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    tokens, d, f, held = 16384, 2048, 1024, 16

    def loss(x, gates, w_gate, w_up, w_down, chosen):
        return jnp.sum(moe.held_experts_ffn(
            x, chosen, gates, w_gate, w_up, w_down, 0)[0].astype(jnp.float32))

    calls = _compiled(
        jax.grad(loss, (0, 1, 2, 3, 4)), shape((tokens, d)),
        shape((tokens, 8), jnp.float32), shape((held, d, f)),
        shape((held, d, f)), shape((held, f, d)),
        shape((tokens, 8), jnp.int32))
    # three grouped matmuls forward; for each, one for its rows' and one
    # for its weights' gradient; and five kernels without a body, whose
    # results are the buffers of tokens x top_k rows that the dispatch's
    # passes write up to their window (sorted rows and activation forward;
    # the combine's, the gate's and the up projection's cotangents
    # backward). A loop's body would count once; no grouped matmul is in one.
    assert calls == 14
