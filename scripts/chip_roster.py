"""Every ``pl.pallas_call`` site of the package, once, on the chip.

Builder-run (``chiprun -- python scripts/chip_roster.py``), not part of
the driver's check. Each site runs with interpret off at a production
geometry, at the precision the trainer runs it, and is compared with its
``jax.numpy`` reference computed under
``jax.default_matmul_precision("highest")``. The TPU's default-precision
fp32 matmul rounds operands to bf16, so an fp32 kernel agrees with a
true fp32 oracle to bf16 epsilon (~7e-3 here), not to fp32 epsilon: the
fp32 tolerance is 2e-2, the bf16 one 6e-2. A site that Mosaic
refuses is reported with the compiler's words; the roster keeps going
and exits non-zero at the end if any site failed. Results also land in
``chiprun_out/roster_<mode>.json``.

Sites: flash attention fwd / dQ / dK-dV (plain, ``lengths=``, GQA,
window), ``paged_attention``, ``scale_cast``, ``int8_quantize``,
``int8_block_quantize`` (the ``pltpu.prng_*`` branch) and
``adasum_pair`` (dots + apply). ``--vmem-sweep`` additionally walks the
GQA backward up in sequence length to find where Mosaic really runs out
of VMEM, against ``flash_attention.bwd_vmem_bytes``'s estimate.

Two end-to-end paths ``chip_smoke.py`` does not cover ride along as
modes of their own: ``--adasum`` (several chips: BERT-large with
``op=hvd.Adasum``, and the Pallas Adasum pair inside a process-set
collective) and ``--serve`` (one chip: ``hvd.serve`` on GPT-2-medium
weights against the full forward's argmax).
"""

import argparse
import glob
import json
import os
import sys
import traceback

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from horovod_tpu.ops import flash_attention as fa  # noqa: E402
from horovod_tpu.ops import paged_attention as pa  # noqa: E402
from horovod_tpu.ops import pallas_kernels as pk  # noqa: E402

RESULTS = []


def case(name):
    """Run one roster case; record ok / failed with the error text."""

    def deco(fn):
        try:
            detail = fn()
            RESULTS.append({"site": name, "ok": True, "detail": detail})
            print(f"OK    {name}: {detail}", flush=True)
        except Exception as e:  # noqa: BLE001 - the roster reports every site
            text = f"{type(e).__name__}: {e}"
            RESULTS.append({"site": name, "ok": False, "error": text[:4000]})
            print(f"FAIL  {name}: {text[:1500]}", flush=True)
            traceback.print_exc(limit=3)
        return fn

    return deco


def reference(fn, *args):
    """``fn(*args)`` as a true fp32 oracle."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn)(*args)


def maxerr(a, b):
    """Largest absolute difference, in units of the reference's scale
    (so a bf16 gradient of magnitude 8 is not held to an absolute 6e-2)."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.maximum(jnp.max(jnp.abs(b)), 1.0))


def check(errs, tol):
    bad = {k: v for k, v in errs.items() if not v <= tol}
    if bad:
        raise AssertionError(f"max error over tolerance {tol}: {bad}")
    return {k: float(f"{v:.3g}") for k, v in errs.items()}


# ------------------------------------------------------------------ flash


def dense_attention(q, k, v, causal, window=None, lengths=None):
    """fp32 reference with the kernels' documented masking: -1e30 fill,
    padded query rows zeroed."""
    t, d = q.shape[1], q.shape[-1]
    r = q.shape[2] // k.shape[2]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    k, v = jnp.repeat(k, r, axis=2), jnp.repeat(v, r, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    rows, cols = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    if causal:
        band = rows >= cols
        if window is not None:
            band = band & (rows - cols < window)
        s = jnp.where(band[None, None], s, -1e30)
    valid = None
    if lengths is not None:
        valid = jnp.arange(t)[None, :] < lengths[:, None]
        s = jnp.where(valid[:, None, None, :], s, -1e30)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    if valid is not None:
        o = jnp.where(valid[:, :, None, None], o, 0.0)
    return o


def flash_case(b, t, h, g, d, dtype, tol, causal=True, **kw):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, t, g, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, t, g, d)), dtype)

    def kernel_loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal, **kw).astype(
            jnp.float32
        ).sum()

    def ref_loss(q, k, v):
        return dense_attention(q, k, v, causal, **kw).sum()

    errs = {
        "fwd": maxerr(
            fa.flash_attention(q, k, v, causal=causal, **kw),
            reference(lambda q, k, v: dense_attention(q, k, v, causal, **kw),
                      q, k, v),
        )
    }
    got = jax.jit(jax.grad(kernel_loss, argnums=(0, 1, 2)))(q, k, v)
    want = reference(jax.grad(ref_loss, argnums=(0, 1, 2)), q, k, v)
    for name, a, w in zip(("dq", "dk", "dv"), got, want):
        errs[name] = maxerr(a, w)
    if "lengths" in kw:
        pad = float(jnp.max(jnp.abs(got[0][1, int(kw["lengths"][1]):])))
        if pad != 0.0:
            raise AssertionError(f"dq in the pad region is {pad}, not 0")
    return check(errs, tol)


def flash_roster():
    lens = jnp.asarray([512, 301], jnp.int32)

    @case("flash plain fp32 causal d64 t512 blk512")
    def _():
        return flash_case(2, 512, 4, 4, 64, jnp.float32, 2e-2)

    @case("flash plain bf16 GPT-2-medium geometry b8 h16 t512 d64 causal")
    def _():
        return flash_case(8, 512, 16, 16, 64, jnp.bfloat16, 6e-2)

    @case("flash plain bf16 BERT-large geometry b8 h16 t512 d64 bidirectional")
    def _():
        return flash_case(8, 512, 16, 16, 64, jnp.bfloat16, 6e-2, causal=False)

    @case("flash lengths= (SMEM lens) fp32 d64 t512")
    def _():
        return flash_case(2, 512, 4, 4, 64, jnp.float32, 2e-2, lengths=lens)

    @case("flash GQA 8q/2kv fp32 d64 t512")
    def _():
        return flash_case(2, 512, 8, 2, 64, jnp.float32, 2e-2)

    @case("flash GQA + window 128")
    def _():
        return flash_case(2, 512, 8, 2, 64, jnp.float32, 2e-2, window=128)

    @case("flash GQA + window 128 + lengths=")
    def _():
        return flash_case(
            2, 512, 8, 2, 64, jnp.float32, 2e-2, window=128, lengths=lens
        )

    @case("flash GQA 32q/8kv bf16 d128 t2048 (Llama-shaped)")
    def _():
        return flash_case(1, 2048, 32, 8, 128, jnp.bfloat16, 6e-2)


def vmem_sweep():
    """Where the dK/dV kernel really stops compiling, against the
    estimate the auto gates trust."""
    budget = fa._vmem_budget()
    for r in (1, 4, 8):
        for t in (1024, 2048, 4096, 8192, 16384):
            est = fa.bwd_vmem_bytes(t, 128, r, 2)
            name = (
                f"vmem sweep GQA r={r} t={t} d128 bf16: estimate "
                f"{est / 2**20:.1f} MiB, gate says "
                f"{'fits' if est <= budget else 'too big'}"
            )

            @case(name)
            def _(r=r, t=t):
                rng = np.random.default_rng(1)
                q = jnp.asarray(rng.normal(size=(1, t, r, 128)), jnp.bfloat16)
                k = jnp.asarray(rng.normal(size=(1, t, 1, 128)), jnp.bfloat16)
                g = jax.jit(
                    jax.grad(
                        lambda q, k, v: fa.flash_attention(
                            q, k, v, causal=True
                        ).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2),
                    )
                )(q, k, k)
                return {"finite": bool(all(jnp.isfinite(x).all() for x in g))}


# ------------------------------------------------------------------ paged


def paged_reference(q, k_pool, v_pool, table, lengths):
    """Gather the slot's pages into a contiguous view, then dense causal
    attention over the live prefix: the serving plane's gather read."""
    b, t, h, d = q.shape
    page_tokens, kvh = k_pool.shape[1], k_pool.shape[2]
    r = h // kvh
    k = k_pool[table].reshape(b, -1, kvh, d).astype(jnp.float32)
    v = v_pool[table].reshape(b, -1, kvh, d).astype(jnp.float32)
    k, v = jnp.repeat(k, r, axis=2), jnp.repeat(v, r, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k) / np.sqrt(d)
    q_pos = lengths[:, None] + jnp.arange(t)[None, :]  # [b, t]
    key_pos = jnp.arange(k.shape[1])
    ok = key_pos[None, None, :] <= q_pos[:, :, None]  # causal, global
    s = jnp.where(ok[:, None], s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def paged_case(b, t, h, kvh, d=128, page_tokens=16, n_logical=16,
               num_pages=128):
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.bfloat16)
    k_pool = jnp.asarray(
        rng.normal(size=(num_pages, page_tokens, kvh, d)), jnp.bfloat16
    )
    v_pool = jnp.asarray(
        rng.normal(size=(num_pages, page_tokens, kvh, d)), jnp.bfloat16
    )
    table = jnp.asarray(
        rng.permutation(num_pages)[: b * n_logical].reshape(b, n_logical),
        jnp.int32,
    )
    # ragged: one slot nearly full, one nearly empty, the rest between
    hi = n_logical * page_tokens - t
    lengths = jnp.asarray(
        [hi, 0] + list(rng.integers(1, hi, size=b - 2)), jnp.int32
    )
    got = jax.jit(pa.paged_attention)(q, k_pool, v_pool, table, lengths)
    want = reference(paged_reference, q, k_pool, v_pool, table, lengths)
    return check({"out": maxerr(got, want)}, 6e-2)


def paged_roster():
    reason = pa.unsupported_reason(64, 16, backend="tpu")
    print(f"paged gate at head_dim 64 (GPT-2 medium): {reason!r}", flush=True)

    @case("paged_attention decode t=1 MHA h8 d128 page16")
    def _():
        return paged_case(4, 1, 8, 8)

    @case("paged_attention decode t=1 GQA 8q/2kv d128 page16")
    def _():
        return paged_case(4, 1, 8, 2)

    @case("paged_attention prefill chunk t=64 MHA h8 d128 page16")
    def _():
        return paged_case(2, 64, 8, 8)

    @case("paged_attention prefill chunk t=64 GQA 8q/2kv d128 page16")
    def _():
        return paged_case(2, 64, 8, 2)


# ------------------------------------------------ elementwise / reductions


def quantize_checks(x, values, scales_per_elem, quantize):
    """Stochastic rounding cannot match a reference element for element:
    check what must hold - int8 range, error under one quantum, no bias,
    determinism in the seed."""
    deq = values.astype(jnp.float32) * scales_per_elem
    quanta = jnp.abs(deq - x) / scales_per_elem
    bias = float(jnp.mean((deq - x) / scales_per_elem))
    n = x.size
    again = quantize(x, 3)[0]
    other = quantize(x, 4)[0]
    out = {
        "max_quanta": float(jnp.max(quanta)),
        "bias_quanta": bias,
        "same_seed_equal": bool(jnp.array_equal(values, again)),
        "other_seed_differs": bool(jnp.any(values != other)),
    }
    # the rounding error is uniform on (-1, 1) quanta; its mean over n
    # elements has sigma < 0.58 / sqrt(n)
    if not (
        out["max_quanta"] <= 1.0 + 1e-4
        and abs(bias) < 5 * 0.58 / np.sqrt(n)
        and out["same_seed_equal"]
        and out["other_seed_differs"]
    ):
        raise AssertionError(str(out))
    return out


def elementwise_roster():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(1000, 333)) * 3.0, jnp.float32)

    @case("scale_cast fp32 -> bf16 (whole-array SMEM scalar)")
    def _():
        got = pk.scale_cast(x, 0.37, jnp.bfloat16)
        return check({"out": maxerr(got, (x * 0.37).astype(jnp.bfloat16))}, 0.0)

    @case("int8_quantize (pltpu.prng_* stochastic rounding)")
    def _():
        values, scale = pk.int8_quantize(x, 3)
        return quantize_checks(x, values, scale, pk.int8_quantize)

    @case("int8_dequantize (scale_cast on int8)")
    def _():
        values, scale = pk.int8_quantize(x, 3)
        got = pk.int8_dequantize(values, scale)
        return check({"out": maxerr(got, values.astype(jnp.float32) * scale)}, 0.0)

    @case("int8_block_quantize block 512 (pltpu.prng_* branch)")
    def _():
        quantize = lambda x, seed: pk.int8_block_quantize(x, 512, seed)  # noqa: E731
        values, scales = quantize(x, 3)
        per_elem = jnp.repeat(scales, 512)[: x.size].reshape(x.shape)
        return quantize_checks(x, values, per_elem, quantize)

    @case("adasum_pair dots + apply (SMEM accumulator across the grid)")
    def _():
        from horovod_tpu.ops.adasum import _pair_f32

        a = jnp.asarray(rng.normal(size=(1 << 20,)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(1 << 20,)) + 0.5 * np.asarray(a))
        want = reference(_pair_f32, a, b)
        return check({"out": maxerr(pk.adasum_pair(a, b), want)}, 1e-4)


# ------------------------------------------- end-to-end paths, builder-run


def adasum_on_mesh():
    """BERT-large + ``op=hvd.Adasum`` for two steps through the smoke's
    trainer (the bench_lm pairing), then Adasum over a two-rank process
    set: the full-axis path is VHDD in plain jnp, and only the
    process-set path reaches ``adasum_pair`` — the Pallas pair inside a
    collective program. Needs more than one chip."""
    import dataclasses
    from functools import partial

    from jax.sharding import PartitionSpec as P

    import chip_smoke
    import horovod_tpu as hvd
    from horovod_tpu.models import TransformerConfig
    from horovod_tpu.ops.adasum import _pair_f32

    @case("BERT-large + op=Adasum, 2 steps (VHDD over the world axis)")
    def _():
        cfg = dataclasses.replace(TransformerConfig.bert_large(), remat=True)
        report = chip_smoke.train_smoke(cfg, 2, op=hvd.Adasum)
        return {k: report[k] for k in ("world", "losses", "compile_s")}

    @case("Adasum over process set {0,1}: Pallas adasum_pair in a collective")
    def _():
        hvd.init()
        mesh, world = hvd.mesh(), hvd.size()
        pset = hvd.add_process_set([0, 1])
        rng = np.random.default_rng(5)
        rows = rng.normal(size=(world, 1 << 16)).astype(np.float32)
        x = jax.device_put(rows, hvd.rank_sharding(mesh))

        @jax.jit
        @partial(jax.shard_map, mesh=mesh, in_specs=P(hvd.WORLD_AXIS),
                 out_specs=P(hvd.WORLD_AXIS), check_vma=False)
        def combine(x):
            return hvd.traced.allreduce(x[0], op=hvd.Adasum,
                                        process_set=pset)[None]

        hlo = combine.lower(x).compile().as_text()
        got = np.asarray(combine(x))
        want = np.asarray(reference(_pair_f32, rows[0], rows[1]))
        errs = {f"member{r}": maxerr(jnp.asarray(got[r]), jnp.asarray(want))
                for r in (0, 1)}
        errs["bystanders"] = float(np.abs(got[2:] - rows[2:]).max())
        out = check(errs, 1e-4)
        out["tpu_custom_calls"] = hlo.count('"tpu_custom_call"')
        if not out["tpu_custom_calls"]:
            raise AssertionError("no Mosaic call in the compiled program")
        return out


def serve_on_chip():
    """``hvd.serve`` on GPT-2-medium weights: four POST /generate of
    different lengths; greedy tokens against the full forward's argmax;
    one decode executable; the paged kernel declining head_dim 64."""
    import urllib.request

    import horovod_tpu as hvd
    from horovod_tpu.models import Transformer, TransformerConfig

    cfg = TransformerConfig.gpt2_medium()
    model = Transformer(cfg)
    pad = 256
    params = jax.jit(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, pad), jnp.int32), train=False
        )
    )()
    handle = hvd.serve(
        model, params, port=0, addr="127.0.0.1", handle_sigterm=False,
        max_len=512,
    )
    forward = jax.jit(lambda tokens: model.apply(params, tokens, train=False))
    rng = np.random.default_rng(6)
    try:

        @case("hvd.serve GPT-2-medium: 4 requests, greedy == full-forward argmax")
        def _():
            out = {}
            for n_prompt, n_new in ((5, 8), (17, 12), (64, 6), (130, 10)):
                prompt = rng.integers(0, cfg.vocab_size, size=n_prompt).tolist()
                req = urllib.request.Request(
                    f"http://127.0.0.1:{handle.port}/generate",
                    data=json.dumps(
                        {"tokens": prompt, "max_tokens": n_new}
                    ).encode(),
                    headers={"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(req, timeout=600) as resp:
                    body = json.loads(resp.read())
                served = body["tokens"]
                if body["status"] != "done" or len(served) != n_new:
                    raise AssertionError(f"request failed: {body}")
                # teacher-force the served sequence through ONE padded
                # full forward (causal: later pad tokens cannot reach
                # earlier positions); position p predicts token p + 1
                seq = np.zeros((1, pad), np.int32)
                seq[0, : n_prompt + n_new] = prompt + served
                logits = np.asarray(forward(jnp.asarray(seq)))[0]
                flips = []
                for i, tok in enumerate(served):
                    row = logits[n_prompt - 1 + i]
                    if int(row.argmax()) != tok:
                        flips.append(float(row.max() - row[tok]))
                out[f"prompt{n_prompt}+{n_new}"] = {
                    "ttft_ms": body["ttft_ms"], "argmax_mismatches": flips,
                }
                if flips:
                    raise AssertionError(
                        f"served tokens differ from the full-forward argmax;"
                        f" reference logit margins at the flips: {flips}"
                    )
            return out

        @case("hvd.serve: one decode executable, paged kernel declined loudly")
        def _():
            stats = handle.engine.stats()
            out = {k: stats[k] for k in (
                "decode_compiles", "prefill_compiles", "paged_attn_calls",
                "paged_attn_fallbacks",
            )}
            if not (out["decode_compiles"] == 1
                    and out["paged_attn_calls"] == 0
                    and out["paged_attn_fallbacks"] >= 1):
                raise AssertionError(str(out))
            return out

    finally:
        handle.stop()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--vmem-sweep", action="store_true")
    ap.add_argument("--adasum", action="store_true",
                    help="only the multi-chip Adasum paths")
    ap.add_argument("--serve", action="store_true",
                    help="only hvd.serve on GPT-2-medium weights")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_roster: no TPU (platform={dev.platform!r})", file=sys.stderr)
        return 1
    print(f"chip_roster: {dev.platform} {dev.device_kind!r} x{len(jax.devices())}"
          f" jax {jax.__version__}")
    # what a launcher parent can see without JAX (runner/tpu_discovery.py)
    print("chip_roster: /dev/accel*", sorted(glob.glob("/dev/accel*")),
          "/dev/vfio/*", sorted(glob.glob("/dev/vfio/*")))
    print("chip_roster: TPU env", {k: v for k, v in os.environ.items()
                                   if k.startswith(("TPU_", "JAX_", "XLA_"))})
    if args.adasum:
        adasum_on_mesh()
    elif args.serve:
        serve_on_chip()
    else:
        flash_roster()
        paged_roster()
        elementwise_roster()
        if args.vmem_sweep:
            vmem_sweep()
    failed = [r for r in RESULTS if not r["ok"]]
    os.makedirs("chiprun_out", exist_ok=True)
    mode = "adasum" if args.adasum else "serve" if args.serve else "kernels"
    with open(f"chiprun_out/roster_{mode}.json", "w") as f:
        json.dump(
            {"device_kind": dev.device_kind, "count": len(jax.devices()),
             "results": RESULTS}, f, indent=1
        )
    print(f"chip_roster: {len(RESULTS) - len(failed)} ok, {len(failed)} failed")
    for r in failed:
        print(f"  FAILED {r['site']}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
