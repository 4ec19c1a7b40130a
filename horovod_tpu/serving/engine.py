"""InferenceEngine: prefill/decode split over compiled executables.

The serving analog of the PR 1 fusion-executor rework, with sequence
length where byte size was:

* **Prefill** is shape-polymorphic in the prompt length, so it compiles
  through a two-tier executor cache: a *bucket* tier keyed by the
  power-of-two padded length (any prompt length runs immediately, pad
  tokens are masked garbage the causal mask never attends), and an
  *exact* tier a recurring length is promoted into after
  ``promote_after`` sightings (no pad FLOPs for the lengths a workload
  actually serves). Prompts past the bucket ceiling run as successive
  ceiling-sized chunks through the SAME cache-threaded executables
  (each chunk attends to everything before it), so long prompts cost
  compile entries only for the ceiling and the remainder bucket.
* **Decode** is ONE fixed-shape jitted step — ``[slots]`` last tokens +
  ``[slots]`` cache indices in, ``[slots]`` next tokens + the updated
  cache out — over the slot-batched KV cache, which is DONATED through
  every prefill/decode executable so steady-state serving allocates no
  new cache buffers and never retraces: admissions, evictions and slot
  reuse change data, never shapes.
* **Memory plane**: the cache behind those executables is the paged
  block pool by default (`serving/paged_kv.py` — page tables ride the
  executables as extra int32 DATA inputs, so the zero-retrace invariant
  is untouched; prompt prefixes shared with the hash-keyed cache skip
  their prefill chunks outright). ``paged=False`` keeps the PR 8
  contiguous slab — the A/B baseline, bit-identical greedy output.

Executables are built ahead-of-time (``jit(...).lower(...).compile()``)
and held in engine-owned tables, so compile counts are exact, assertable
numbers (``stats()``), not inferences about jit's internal cache.

The model contract (``models/transformer.py``): ``model_fn(params,
tokens, cache, cache_index) -> (logits, new_cache)`` with per-slot
write positions and the global causal mask — any model implementing it
serves; flax Transformer modules are adapted automatically.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Callable, Dict, Optional

import numpy as np

from ..common import tracing as _tracing
from ..common.logging import get_logger
from ..common.metrics import registry as _metrics
from .paged_kv import PagePoolExhausted  # noqa: F401  (engine API)

_log = get_logger("serve.engine")

DEFAULT_MIN_BUCKET = 8
DEFAULT_PROMOTE_AFTER = 2
# exact-tier LRU bound: one executable per distinct recurring prompt
# length; the bucket tier below it is bounded by log2(ceiling) anyway
DEFAULT_EXACT_CAPACITY = 32


def next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def _as_model_fn(model) -> Callable:
    """Adapt a flax module (``.apply``; params or full variables dict)
    to the positional model contract; pass callables through. With the
    paged memory plane the contract grows a ``pages=`` kwarg (the
    per-row page table, `serving/paged_kv.py`) — custom callables only
    need to accept it when they are served with ``paged=True``, and a
    ``paged_attn=`` kwarg only when the fused pool-read kernel is
    resolved on (``HOROVOD_SERVE_PAGED_ATTN``) — both are forwarded
    only when engaged, so existing callables keep working."""
    apply = getattr(model, "apply", None)
    if apply is None:
        if not callable(model):
            raise TypeError(
                f"model must be a flax module or a model_fn callable, "
                f"got {type(model)!r}"
            )

        def passthrough(params, tokens, cache, cache_index, pages=None,
                        paged_attn=False):
            if pages is None:
                return model(params, tokens, cache, cache_index)
            if paged_attn:
                return model(
                    params, tokens, cache, cache_index, pages=pages,
                    paged_attn=True,
                )
            return model(params, tokens, cache, cache_index, pages=pages)

        return passthrough

    def model_fn(params, tokens, cache, cache_index, pages=None,
                 paged_attn=False):
        variables = (
            params
            if isinstance(params, dict) and "params" in params
            else {"params": params}
        )
        kwargs = dict(train=False, cache=cache, cache_index=cache_index)
        if pages is not None:
            kwargs["pages"] = pages
        if paged_attn:
            kwargs["paged_attn"] = True
        return apply(variables, tokens, **kwargs)

    return model_fn


def _sample_next(row, greedy, temps, topks, keys):
    """Per-slot sampled next token as pure DATA inside the ONE decode
    executable (the ROADMAP "parallel sampling" on-ramp): ``temps`` /
    ``topks`` are per-slot ``[slots]`` inputs, ``keys`` are per-slot
    raw uint32 PRNG keys riding the donated carry. Temperature 0 takes
    the UNTOUCHED greedy argmax branch through a ``jnp.where`` — the
    greedy token stream is bit-identical to the pre-sampling engine —
    and top-k 0 means no truncation. Keys split every step regardless
    of temperature (a constant-shape op; sampled slots stay
    reproducible however their neighbors are configured). Returns
    ``(next_tokens, new_keys)``."""
    import jax
    import jax.numpy as jnp

    vocab = row.shape[-1]
    # top-k truncation as data: threshold at the k-th largest logit
    # (k<=0 disables), then mask below it before temperature scaling
    srt = jnp.sort(row, axis=-1)[:, ::-1]
    kk = jnp.clip(topks, 1, vocab) - 1
    thr = jnp.take_along_axis(srt, kk[:, None], axis=-1)
    keep = jnp.where(topks[:, None] > 0, row >= thr, True)
    scaled = jnp.where(keep, row, -1e30) / jnp.maximum(
        temps, 1e-6
    )[:, None]

    def one(key, logits):
        next_key, sample_key = jax.random.split(key)
        return next_key, jax.random.categorical(sample_key, logits)

    new_keys, sampled = jax.vmap(one)(keys, scaled)
    nxt = jnp.where(temps > 0, sampled.astype(jnp.int32), greedy)
    return nxt, new_keys


def _default_cache_factory(model):
    cfg = getattr(model, "cfg", None)
    if cfg is None:
        raise TypeError(
            "cache_factory= is required when the model does not carry "
            "a TransformerConfig (.cfg) to derive the KV layout from"
        )
    from ..models.transformer import init_cache

    return lambda batch, max_len: init_cache(cfg, batch, max_len)


class InferenceEngine:
    """Compiled prefill/decode over a slot-batched, donated KV cache.

    Not thread-safe by design: exactly one consumer (the batcher's step
    loop) drives it, which is also what makes the donated cache carry
    sound — there is never a second reference to consume.
    """

    def __init__(
        self,
        model,
        params,
        *,
        slots: int,
        max_len: int,
        cache_factory=None,
        min_bucket: int = DEFAULT_MIN_BUCKET,
        prefill_ceiling: Optional[int] = None,
        promote_after: int = DEFAULT_PROMOTE_AFTER,
        exact_capacity: int = DEFAULT_EXACT_CAPACITY,
        donate: Optional[bool] = None,
        mesh=None,
        tp_axis: str = "tp",
        ep_axis: str = "ep",
        paged: Optional[bool] = None,
        page_tokens: Optional[int] = None,
        pages: Optional[int] = None,
        prefix_cache: Optional[bool] = None,
        page_watermark: Optional[int] = None,
        role: str = "unified",
        paged_attn=None,
    ) -> None:
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(
                f"role must be unified/prefill/decode, got {role!r}"
            )
        # Role-gated executable tables (disaggregated fleet,
        # serving/kv_transfer.py): a decode-role engine NEVER builds
        # prefill executables (its sequences arrive as ingested pages —
        # prefill() raises), and a prefill-role engine compiles the
        # decode step only on the transfer-fallback path (normal
        # operation hands finished pages off before any decode, so
        # ``decode_compiles == 0`` is the assertable steady state —
        # scripts/hlo_audit.py serve_prefill_role). Each role carries
        # only its own tables: half the compile time and executable HBM.
        self.role = role
        self._model_fn = _as_model_fn(model)
        # MoE decode (PR 12): a model whose config carries an expert
        # bank gets it sharded over the mesh's ep axis up front —
        # GSPMD then partitions the expert einsums inside the SAME
        # fixed-shape prefill/decode executables (routing is data, so
        # the zero-retrace invariant is untouched; tests assert
        # decode_compiles==1 across rolling admissions with MoE on).
        model_cfg = getattr(model, "cfg", None)
        if (
            mesh is not None
            and model_cfg is not None
            and getattr(model_cfg, "moe_experts", 0)
        ):
            from ..models.transformer import shard_moe_params

            params = shard_moe_params(params, mesh, ep_axis)
        self._params = params
        if cache_factory is None:
            cache_factory = _default_cache_factory(model)
        # memory plane: paged block pool + prefix cache by default
        # (serving/paged_kv.py); paged=False keeps the PR 8 contiguous
        # slab, the layout tests/test_paged_kv.py compares against.
        # None knobs resolve from the env contract (docs/env_vars.md).
        from ..common import basics
        from .kv_cache import create_kv_manager

        cfg = basics.live_config()
        self.paged = True if paged is None else bool(paged)
        self.manager = create_kv_manager(
            cache_factory, slots, max_len,
            paged=self.paged,
            page_tokens=(
                cfg.serve_page_tokens if page_tokens is None
                else int(page_tokens)
            ),
            num_pages=cfg.serve_pages if pages is None else int(pages),
            prefix_cache=(
                cfg.serve_prefix_cache if prefix_cache is None
                else bool(prefix_cache)
            ),
            watermark=(
                cfg.serve_page_watermark if page_watermark is None
                else int(page_watermark)
            ),
            mesh=mesh, tp_axis=tp_axis,
        )
        self.slots = self.manager.slots
        self.max_len = self.manager.max_len
        self.min_bucket = max(int(min_bucket), 1)
        # bucket ceiling: a power of two that FITS the cache — clamp to
        # the largest pow2 <= max_len, never round past it (a prefill
        # width beyond max_len would build kv updates larger than the
        # cache leaf and fail at compile)
        floor_pow2 = 1 << (self.max_len.bit_length() - 1)
        ceiling = int(prefill_ceiling) if prefill_ceiling else floor_pow2
        self.prefill_ceiling = min(next_pow2(ceiling), floor_pow2)
        self.promote_after = max(int(promote_after), 1)
        self._mesh = mesh
        if donate is None:
            import jax

            donate = jax.devices()[0].platform in (
                "tpu", "gpu", "cuda", "rocm",
            )
        self.donate = bool(donate)
        # two-tier prefill executor cache (PR 1 design on the length
        # axis) + the one decode executable
        self._prefill_exact: "collections.OrderedDict" = (
            collections.OrderedDict()
        )
        self._prefill_bucket: Dict[int, object] = {}
        self._seen: "collections.OrderedDict" = collections.OrderedDict()
        self._exact_capacity = max(int(exact_capacity), 1)
        self._decode_exe = None
        self._decode_swept = False
        self._lock = threading.Lock()  # guards counters for stats readers
        self._counters = collections.Counter()
        # per-slot sampling state (DATA through the one decode
        # executable — see _sample_next): temperature 0 / top-k 0 =
        # greedy, the boot default for every slot
        import jax.numpy as jnp

        self._sample_temps = np.zeros((self.slots,), np.float32)
        self._sample_topks = np.zeros((self.slots,), np.int32)
        self._sample_keys = jnp.zeros((self.slots, 2), jnp.uint32)
        # fused paged-attention read (ops/paged_attention.py): resolve
        # the tri-state once — the decision is baked into the traced
        # executables, so it cannot flip mid-flight and retrace
        self.paged_attn = self._resolve_paged_attn(
            cfg.serve_paged_attn if paged_attn is None else paged_attn,
            model_cfg,
        )
        # persistent executable disk tier (common/exe_cache.py): below
        # the in-memory exact/bucket tables. When HOROVOD_EXE_CACHE is
        # unset every path below is byte-identical to the memory-only
        # engine. ``_promoting`` tracks in-flight background
        # bucket→exact promotions (the PR 17 hot-path-compile fix).
        from ..common import exe_cache as _exe_cache

        self._exe_base = _exe_cache.cache_dir()
        self._exe_fp = (
            _exe_cache.topology_fingerprint() if self._exe_base else None
        )
        self._promoting: set = set()
        self._promote_threads: list = []
        if self._exe_base:
            self._warm_start()

    def _resolve_paged_attn(self, requested, model_cfg) -> bool:
        """Resolve the ``HOROVOD_SERVE_PAGED_ATTN`` tri-state against
        the fallback ladder (ops/paged_attention.py): ``auto`` engages
        the kernel only on real TPU backends (interpret mode is for
        tests, not production CPU decode — and the gather oracle keeps
        CPU serving bit-comparable with the slab baseline), ``on``
        forces it anywhere Pallas can run it, ``off`` — and the slab
        plane — always ride the gather read. A requested-but-impossible
        kernel falls back LOUDLY: warn log + the
        ``serve.paged_attn_fallbacks`` counter. The check here uses the
        decode geometry (one token per slot); wider prefill chunks are
        re-checked per trace inside ``_cached_attention`` and fall back
        per-executable the same loud way."""
        if isinstance(requested, bool):
            requested = "on" if requested else "off"
        requested = str(requested).lower()
        if requested not in ("auto", "on", "off"):
            raise ValueError(
                f"paged_attn must be auto/on/off, got {requested!r}"
            )
        if not self.paged or requested == "off":
            return False
        import jax

        backend = jax.default_backend()
        if requested == "auto" and backend != "tpu":
            return False
        from ..ops import paged_attention as _pa

        leaf = jax.tree_util.tree_leaves(self.manager.cache)[0]
        page_tokens, kv_heads, head_dim = leaf.shape[1:4]
        heads = (
            getattr(model_cfg, "num_heads", 0) or kv_heads
            if model_cfg is not None else kv_heads
        )
        group = max(int(heads) // int(kv_heads), 1)
        reason = _pa.unsupported_reason(
            int(head_dim), int(page_tokens), queries=group,
            backend=backend,
        )
        if reason is None and model_cfg is not None and getattr(
            model_cfg, "sliding_window", 0
        ):
            reason = "sliding_window is not implemented by the paged kernel"
        if reason is None:
            return True
        _log.warning(
            "paged_attn=%s requested but the kernel path is "
            "unsupported (%s); serving on the gather read",
            requested, reason,
        )
        with self._lock:
            self._counters["paged_attn_fallbacks"] += 1
        _metrics.counter("serve.paged_attn_fallbacks")
        return False

    # -------------------------------------------------------- compile layer

    def _out_shardings(self, decode: bool = False):
        """With a tp-sharded cache, pin the outputs: the cache keeps
        its sharding (a changed output sharding would break the donated
        carry on the NEXT call), the token output — and the decode
        step's PRNG-key carry — replicated."""
        if self.manager.sharding is None:
            return None
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(self._mesh, P())
        cache_sh = jax.tree_util.tree_map(
            lambda _: self.manager.sharding, self.manager.cache
        )
        return (rep, cache_sh, rep) if decode else (rep, cache_sh)

    def _lower(self, fn, args, decode: bool = False):
        """THE one jit-option assembly (donated cache carry at arg 1,
        pinned out-shardings): ``_compile`` finishes it into the
        executable, ``lowered_decode``/``lowered_prefill`` hand the
        Lowered to the static-analysis surface — one builder, so the
        audited program can never drift from the executed one. The
        decode step additionally donates its last argument — the
        per-slot PRNG keys, which carry exactly like the cache — and
        returns ``(tokens, cache, keys)``."""
        import jax

        kwargs = {}
        if self.donate:
            donate = (1,)  # the cache carry
            if decode:
                donate = donate + (len(args) - 1,)  # the key carry
            kwargs["donate_argnums"] = donate
        out_sh = self._out_shardings(decode=decode)
        if out_sh is not None:
            kwargs["out_shardings"] = out_sh
        return jax.jit(fn, **kwargs).lower(*args)

    def _donation_sig(self, n_args: int, decode: bool) -> str:
        from ..common import exe_cache as _exe_cache

        if not self.donate:
            return "none"
        donate = (1,) + ((n_args - 1,) if decode else ())
        return _exe_cache.donation_signature(donate)

    def _compile(self, fn, args, kind: str, decode: bool = False,
                 meta=None):
        """Compile through the disk tier when one is configured: a hit
        deserializes a previously-persisted executable
        (``{kind}_disk_hits``, NOT a compile — warm processes assert
        ``decode_compiles == 0``), a miss compiles and persists for
        the next process/standby."""
        lowered = self._lower(fn, args, decode=decode)
        if self._exe_base is not None:
            from ..common import exe_cache as _exe_cache

            exe, hit = _exe_cache.get_or_compile(
                lowered,
                family=f"serve.{kind}",
                donation=self._donation_sig(len(args), decode),
                meta=meta,
                fingerprint=self._exe_fp,
                base=self._exe_base,
            )
            with self._lock:
                self._counters[
                    f"{kind}_disk_hits" if hit else f"{kind}_compiles"
                ] += 1
            return exe
        exe = lowered.compile()
        with self._lock:
            self._counters[f"{kind}_compiles"] += 1
        return exe

    def _decode_args(self, tokens):
        lengths = self.manager.lengths_array()
        args = (self._params, self.manager.cache, tokens, lengths)
        if self.paged:
            args = args + (self.manager.tables_array(),)
        return args + (
            self._sample_temps.copy(),
            self._sample_topks.copy(),
            self._sample_keys,
        )

    def lowered_decode(self):
        """The decode step's ``jax.stages.Lowered`` under exactly the
        jit options the engine compiles with (shared :meth:`_lower`) —
        the static-analysis surface ``horovod_tpu.analysis`` parses
        for the donation / collective invariants
        (scripts/hlo_audit.py roster)."""
        return self._lower(
            self._decode_fn(),
            self._decode_args(np.zeros((self.slots,), np.int32)),
            decode=True,
        )

    def lowered_prefill(self, width: int):
        """A prefill executable's Lowered at ``width`` tokens, same
        contract as :meth:`lowered_decode`."""
        return self._lower(
            self._prefill_fn(int(width)), self._prefill_args(int(width))
        )

    def _prefill_fn(self, width: int):
        """Build the prefill computation for a fixed token width: run
        the cache-threaded model over the chunk, emit the greedy next
        token at ``last_pos`` (pad positions beyond it are causal-masked
        junk a later write overwrites before it is ever attendable).

        Slab layout: slice the slot's cache row, model over the row,
        write the row back. Paged layout: the model scatters straight
        into the donated block pool through the slot's page-table row
        (no slice/write-back — the table IS the slot)."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        model_fn = self._model_fn

        if self.paged:
            paged_attn = self.paged_attn

            def fn(params, cache, tokens, table_row, start, last_pos):
                logits, cache = model_fn(
                    params, tokens, cache, jnp.reshape(start, (1,)),
                    pages=table_row[None], paged_attn=paged_attn,
                )
                row = lax.dynamic_index_in_dim(
                    logits[0], last_pos, axis=0, keepdims=False
                )
                return jnp.argmax(row).astype(jnp.int32), cache

            return fn

        def fn(params, cache, tokens, slot, start, last_pos):
            slot_cache = jax.tree_util.tree_map(
                lambda leaf: lax.dynamic_slice_in_dim(leaf, slot, 1, 0),
                cache,
            )
            logits, new_slot = model_fn(
                params, tokens, slot_cache, jnp.reshape(start, (1,))
            )
            cache = jax.tree_util.tree_map(
                lambda leaf, upd: lax.dynamic_update_slice_in_dim(
                    leaf, upd, slot, 0
                ),
                cache,
                new_slot,
            )
            row = lax.dynamic_index_in_dim(
                logits[0], last_pos, axis=0, keepdims=False
            )
            return jnp.argmax(row).astype(jnp.int32), cache

        return fn

    def _decode_fn(self):
        import jax.numpy as jnp

        model_fn = self._model_fn

        if self.paged:
            paged_attn = self.paged_attn

            def fn(params, cache, tokens, lengths, tables, temps, topks,
                   keys):
                logits, cache = model_fn(
                    params, tokens[:, None], cache, lengths,
                    pages=tables, paged_attn=paged_attn,
                )
                row = logits[:, 0, :]
                greedy = jnp.argmax(row, axis=-1).astype(jnp.int32)
                nxt, keys = _sample_next(row, greedy, temps, topks, keys)
                return nxt, cache, keys

            return fn

        def fn(params, cache, tokens, lengths, temps, topks, keys):
            logits, cache = model_fn(
                params, tokens[:, None], cache, lengths
            )
            row = logits[:, 0, :]
            greedy = jnp.argmax(row, axis=-1).astype(jnp.int32)
            nxt, keys = _sample_next(row, greedy, temps, topks, keys)
            return nxt, cache, keys

        return fn

    def _prefill_args(self, width: int):
        if self.paged:
            return (
                self._params,
                self.manager.cache,
                np.zeros((1, width), np.int32),
                np.full(
                    self.manager.pages_per_slot,
                    self.manager.sentinel, np.int32,
                ),
                np.int32(0),
                np.int32(0),
            )
        return (
            self._params,
            self.manager.cache,
            np.zeros((1, width), np.int32),
            np.int32(0),
            np.int32(0),
            np.int32(0),
        )

    def _bucket_exe(self, width: int):
        """Bucket-tier lookup/compile for an executable of exactly
        ``width`` tokens (shared by the two-tier path and the
        chunked-prefill loop — one home for the hit accounting)."""
        exe = self._prefill_bucket.get(width)
        if exe is None:
            exe = self._compile(
                self._prefill_fn(width),
                self._prefill_args(width),
                "prefill",
                meta={"width": int(width), "tier": "bucket"},
            )
            self._prefill_bucket[width] = exe
        else:
            self._counters["prefill_bucket_hits"] += 1
        return exe

    def _get_prefill_exe(self, length: int, avail: Optional[int] = None):
        """Two-tier lookup for the final (or only) chunk of ``length``
        tokens: exact executable if promoted, else the power-of-two
        bucket. Returns ``(exe, width)``. ``avail`` is the room left in
        the slot (max_len − start): when the padded bucket would
        overrun it (possible only for a non-pow2-multiple max_len
        tail), the chunk compiles at its exact width instead — padding
        past the slot would clamp-shift the slab write or drop the pad
        pages' worth of paged writes."""
        exact = self._prefill_exact
        if length in exact:
            exact.move_to_end(length)
            self._counters["prefill_exact_hits"] += 1
            return exact[length], length
        count = self._seen.get(length, 0) + 1
        self._seen[length] = count
        self._seen.move_to_end(length)
        while len(self._seen) > 4 * self._exact_capacity:
            self._seen.popitem(last=False)  # bounded, PR 1 lesson
        bucket = min(
            max(next_pow2(length), self.min_bucket), self.prefill_ceiling
        )
        forced = avail is not None and bucket > avail
        if count >= self.promote_after or forced:
            # disk tier FIRST: a recurring prompt length a prior run
            # promoted deserializes instead of paying the promotion
            # compile (the PR 17 hot-path latency spike)
            exe = self._disk_prefill_exact(length)
            if exe is not None:
                self._install_exact(length, exe)
                return exe, length
            if forced:
                # the padded bucket would overrun the slot — no bucket
                # executable CAN serve this chunk, so the compile has
                # to happen here, synchronously
                exe = self._compile(
                    self._prefill_fn(length),
                    self._prefill_args(length),
                    "prefill",
                    meta={"width": int(length), "tier": "exact"},
                )
                self._install_exact(length, exe)
                return exe, length
            # off the hot path: the bucket executable keeps serving
            # while a background thread compiles (and persists) the
            # exact one; it installs under the lock when ready
            self._spawn_promotion(length)
        exe = self._bucket_exe(bucket)
        self._counters["prefill_pad_tokens"] += bucket - length
        return exe, bucket

    def _install_exact(self, length: int, exe) -> None:
        with self._lock:
            self._prefill_exact[length] = exe
            self._counters["prefill_promotions"] += 1
            while len(self._prefill_exact) > self._exact_capacity:
                self._prefill_exact.popitem(last=False)

    def _disk_prefill_exact(self, length: int):
        """Exact-width prefill entry from the disk tier, or None.
        Costs one trace (no XLA compile) + one file read — scheduler-
        thread safe."""
        if self._exe_base is None or self.role == "decode":
            return None
        from ..common import exe_cache as _exe_cache

        args = self._abstract_prefill_args(length)
        lowered = self._lower(self._prefill_fn(length), args)
        exe = _exe_cache.load(
            "serve.prefill",
            _exe_cache.hlo_fingerprint(lowered),
            donation=self._donation_sig(len(args), False),
            fingerprint=self._exe_fp,
            base=self._exe_base,
        )
        if exe is not None:
            with self._lock:
                self._counters["prefill_disk_hits"] += 1
        return exe

    def _spawn_promotion(self, length: int) -> None:
        """Background bucket→exact promotion: lowers from ABSTRACT
        avals (the live donated cache buffers are never touched off
        the scheduler thread), compiles, persists to the disk tier,
        installs under the lock. Deduplicated per length."""
        with self._lock:
            if length in self._promoting:
                return
            self._promoting.add(length)

        def work():
            try:
                exe = self._compile(
                    self._prefill_fn(length),
                    self._abstract_prefill_args(length),
                    "prefill",
                    meta={"width": int(length), "tier": "exact"},
                )
                self._install_exact(length, exe)
                with self._lock:
                    self._counters["prefill_bg_promotions"] += 1
            except Exception:  # pragma: no cover — keep serving on the
                _log.exception(  # bucket tier; promotion is an upgrade
                    "background promotion for width %d failed", length
                )
            finally:
                with self._lock:
                    self._promoting.discard(length)

        t = threading.Thread(
            target=work, daemon=True, name=f"serve-promote-{length}"
        )
        self._promote_threads.append(t)
        t.start()

    def drain_promotions(self, timeout: float = 60.0) -> bool:
        """Join outstanding background promotions (tests/bench warmup:
        deterministic compile counts need a join point). True when
        everything landed."""
        deadline = time.monotonic() + timeout
        for t in list(self._promote_threads):
            t.join(max(deadline - time.monotonic(), 0.0))
        self._promote_threads = [
            t for t in self._promote_threads if t.is_alive()
        ]
        return not self._promote_threads

    def _abstract_prefill_args(self, width: int):
        """:meth:`_prefill_args` as avals: background/warm-start
        lowering must not hold references to the donated cache carry
        (a decode step may consume it mid-trace)."""
        import jax

        from jax.sharding import NamedSharding

        def _sds(leaf):
            # keep a leaf's MESH sharding only: the abstract lowering
            # must hash to the same HLO fingerprint as the concrete
            # one, and an explicit SingleDeviceSharding on the aval
            # stamps mhlo.sharding attrs a committed array doesn't.
            # shape/dtype/sharding attributes survive donation (only
            # the buffer is deleted).
            sh = getattr(leaf, "sharding", None)
            if isinstance(sh, NamedSharding):
                return jax.ShapeDtypeStruct(
                    leaf.shape, leaf.dtype, sharding=sh
                )
            return jax.ShapeDtypeStruct(np.shape(leaf), np.asarray(leaf).dtype)

        params = jax.tree_util.tree_map(_sds, self._params)
        cache = jax.tree_util.tree_map(_sds, self.manager.cache)
        concrete = self._prefill_args(width)
        return (params, cache) + concrete[2:]

    # ----------------------------------------------------------- warm start

    def _warm_start(self) -> None:
        """Role-gated table warm-start from the disk tier at init: the
        decode executable loads by exact key; prefill entries are
        enumerated from the cache headers (the engine cannot know
        which widths prior runs promoted), each candidate re-lowered
        at its recorded width and loaded by key — an entry from a
        different model, world size, or JAX version simply misses
        (the invalidation rules live in ``exe_cache.load``). Decode
        workers load ONLY decode entries; prefill workers only
        prefill ones. Zero compiles happen here by construction: a
        miss leaves the table cold for the normal lazy path."""
        from ..common import exe_cache as _exe_cache

        t0 = time.monotonic()
        loaded = 0
        if self.role in ("unified", "decode"):
            args = self._decode_args(np.zeros((self.slots,), np.int32))
            lowered = self._lower(self._decode_fn(), args, decode=True)
            exe = _exe_cache.load(
                "serve.decode",
                _exe_cache.hlo_fingerprint(lowered),
                donation=self._donation_sig(len(args), True),
                fingerprint=self._exe_fp,
                base=self._exe_base,
            )
            if exe is not None:
                self._decode_exe = exe
                with self._lock:
                    self._counters["decode_disk_hits"] += 1
                loaded += 1
        if self.role in ("unified", "prefill"):
            candidates = []
            seen = set()
            for header in _exe_cache.scan(
                "serve.prefill", fingerprint=self._exe_fp,
                base=self._exe_base,
            ):
                meta = header.get("meta") or {}
                width, tier = meta.get("width"), meta.get("tier")
                if (
                    not isinstance(width, int)
                    or tier not in ("bucket", "exact")
                    or not 0 < width <= self.max_len
                    or (width, tier) in seen
                ):
                    continue
                seen.add((width, tier))
                candidates.append((width, tier))
            for width, tier in candidates[: self._exact_capacity + 16]:
                args = self._prefill_args(width)
                lowered = self._lower(self._prefill_fn(width), args)
                exe = _exe_cache.load(
                    "serve.prefill",
                    _exe_cache.hlo_fingerprint(lowered),
                    donation=self._donation_sig(len(args), False),
                    fingerprint=self._exe_fp,
                    base=self._exe_base,
                )
                if exe is None:
                    continue
                with self._lock:
                    self._counters["prefill_disk_hits"] += 1
                    if tier == "exact":
                        self._prefill_exact[width] = exe
                        while (
                            len(self._prefill_exact) > self._exact_capacity
                        ):
                            self._prefill_exact.popitem(last=False)
                    else:
                        self._prefill_bucket[width] = exe
                loaded += 1
        if loaded:
            ms = (time.monotonic() - t0) * 1e3
            _metrics.gauge("serve.warm_start_ms", ms)
            _metrics.counter("serve.warm_started_exes", loaded)
            _log.info(
                "warm-started %d executable(s) from %s in %.0f ms",
                loaded, self._exe_base, ms,
            )

    # ------------------------------------------------------------ execution

    def _slot_arg(self, slot: int):
        """The per-slot routing argument of a prefill executable: the
        page-table row under paging (re-fetched every chunk — earlier
        chunks may have allocated), the slot index for the slab."""
        if self.paged:
            return self.manager.table_row(slot)
        return np.int32(slot)

    def prefill(self, slot: int, prompt, trace=None) -> int:
        """Run the prompt through the slot's cache; returns the first
        greedy token. Prompts past the bucket ceiling stream as
        ceiling-sized chunks (each attends to the cache written so
        far), the remainder through the two-tier cache like any short
        prompt.

        Paged plane: the prompt's leading full pages are first looked
        up in the prefix cache — every hit is attached by page-table
        pointer write and its prefill chunk NEVER RUNS (the
        ``prefill_chunks_skipped`` counter). The final prompt token is
        always recomputed even on a full-prefix hit, so the first
        greedy token's logits exist and shared pages stay immutable.
        The remaining pages are allocated here (allocate-on-write);
        after the prefill the slot's full prompt pages are published
        back into the prefix index."""
        if self.role == "decode":
            raise RuntimeError(
                "prefill on a decode-role engine: decode workers take "
                "finished KV pages over the transfer wire "
                "(serving/kv_transfer.py), never prompts — the prefill "
                "executable table is role-gated out"
            )
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n = prompt.size
        if not 0 < n <= self.max_len:
            raise ValueError(
                f"prompt length {n} outside (0, {self.max_len}]"
            )
        start = 0
        hashes = []
        if self.paged:
            from .paged_kv import page_hashes

            mgr = self.manager
            if mgr.prefix_cache_enabled:
                hashes = page_hashes(prompt, mgr.page_tokens)
                hits = mgr.lookup_prefix(hashes)
                # cap: the LAST prompt token is always recomputed (its
                # logits produce the first output; recomputing it also
                # means no write ever targets a shared page)
                k = min(len(hits), (n - 1) // mgr.page_tokens)
                if k:
                    mgr.attach_prefix(slot, hits[:k])
                    start = k * mgr.page_tokens
                    self._counters["prefill_chunks_skipped"] += k
                    self._counters["prefill_tokens_skipped"] += start
            if not mgr.ensure_pages(slot, n, write_from=start):
                raise PagePoolExhausted([slot])
        ceiling = self.prefill_ceiling
        while n - start > ceiling:
            exe = self._bucket_exe(ceiling)
            self._counters["chunked_prefill_chunks"] += 1
            # trace plane: one span per streamed chunk — spans open
            # only for traced requests (trace=None ⇒ start_span is a
            # no-op returning None), so the default path is untouched
            cspan = _tracing.start_span(
                "engine.prefill_chunk", trace,
                start=int(start), width=int(ceiling), slot=slot,
            )
            tok, self.manager.cache = exe(
                self._params,
                self.manager.cache,
                prompt[None, start:start + ceiling],
                self._slot_arg(slot),
                np.int32(start),
                np.int32(ceiling - 1),
            )
            if cspan is not None:
                cspan.end()
            if self.paged_attn:
                self._counters["paged_attn_calls"] += 1
            start += ceiling
        tail = n - start
        exe, width = self._get_prefill_exe(tail, avail=self.max_len - start)
        tokens = np.zeros((1, width), np.int32)
        tokens[0, :tail] = prompt[start:]
        cspan = _tracing.start_span(
            "engine.prefill_chunk", trace,
            start=int(start), width=int(width), slot=slot, tail=True,
        )
        tok, self.manager.cache = exe(
            self._params,
            self.manager.cache,
            tokens,
            self._slot_arg(slot),
            np.int32(start),
            np.int32(tail - 1),
        )
        if cspan is not None:
            cspan.end()
        if self.paged_attn:
            self._counters["paged_attn_calls"] += 1
        self.manager.set_length(slot, n)
        self._counters["prefills"] += 1
        if self.paged and hashes:
            self.manager.publish_prefix(slot, hashes)
        return int(tok)

    def prepare_decode(self) -> list:
        """Pre-decode page sweep (paged plane): allocate each active
        slot's next-token page; returns the slots the pool could NOT
        supply (always ``[]`` for the slab). The batcher calls this
        BEFORE :meth:`decode_step` and pauses requests until the list
        is empty — exhaustion is a scheduling event, not an error."""
        if not self.paged:
            return []
        starved = self.manager.ensure_decode_pages()
        # a clean sweep is remembered so the next decode_step doesn't
        # repeat it (the batcher sweeps right before stepping); any
        # starvation leaves the flag down and decode_step re-checks
        self._decode_swept = not starved
        return starved

    def decode_step(self, tokens: np.ndarray) -> np.ndarray:
        """ONE fixed-shape step over every slot: feed each slot's last
        token at its cache index, return each slot's greedy next token.
        Inactive slots (length 0) compute masked junk at position 0
        that the next occupant's prefill overwrites — the price of a
        shape that never changes is a little wasted compute, never a
        retrace. (Paged: an inactive slot's page table is all sentinel,
        so even its junk write is dropped.)"""
        tokens = np.asarray(tokens, np.int32).reshape(self.slots)
        if self.paged:
            if not self._decode_swept:
                starved = self.prepare_decode()
                if starved:
                    raise PagePoolExhausted(starved)
            self._decode_swept = False
        args = self._decode_args(tokens)
        if self._decode_exe is None:
            self._decode_exe = self._compile(
                self._decode_fn(), args, "decode", decode=True,
                meta={"slots": int(self.slots)},
            )
        with _tracing.hot_span("hvd.engine.decode_step") as sp:
            if sp is not None:
                sp.tag(**self._decode_tags())
            out, self.manager.cache, self._sample_keys = self._decode_exe(
                *args
            )
        self._counters["decode_steps"] += 1
        if self.paged_attn:
            self._counters["paged_attn_calls"] += 1
        return np.asarray(out)

    def _decode_tags(self) -> dict:
        """Tags of ``hvd.engine.decode_step``: active slots and, on the
        paged plane, live pages of the pool."""
        mgr = self.manager
        tags = {"active": len(mgr.active_slots())}
        if self.paged:
            tags["pages"] = int(mgr.num_pages)
            tags["live_pages"] = int(
                mgr.num_pages - mgr.free_pages_available()
            )
        return tags

    # ------------------------------------------------------------- sampling

    def set_sampling(self, slot: int, temperature: float = 0.0,
                     top_k: int = 0, seed: Optional[int] = None) -> None:
        """Arm a slot's sampling knobs (pure DATA into the one decode
        executable — never a retrace): ``temperature<=0`` keeps the
        bit-identical greedy branch, ``top_k<=0`` disables truncation.
        ``seed`` re-seeds the slot's PRNG key (an eager ``.at[].set``
        data op on the key carry); the batcher derives a stable
        per-request default so replays reproduce."""
        import jax

        self._sample_temps[slot] = float(temperature)
        self._sample_topks[slot] = int(top_k)
        if seed is not None:
            self._sample_keys = self._sample_keys.at[int(slot)].set(
                jax.random.key_data(jax.random.PRNGKey(int(seed)))
            )

    def clear_sampling(self, slot: int) -> None:
        """Back to greedy on slot free — the next occupant inherits
        nothing."""
        self._sample_temps[slot] = 0.0
        self._sample_topks[slot] = 0

    def export_sampling(self, slot: int) -> dict:
        """Snapshot a slot's armed sampling state for live migration:
        knobs plus the RAW mid-stream PRNG key (NOT the seed — the key
        has been split once per decode step, so re-seeding on the
        receiver would fork the sampled sequence; importing the key
        data continues it bit-identically)."""
        key = np.asarray(self._sample_keys[int(slot)], np.uint32)
        return {
            "temperature": float(self._sample_temps[int(slot)]),
            "top_k": int(self._sample_topks[int(slot)]),
            "key": [int(x) for x in key.reshape(-1)],
        }

    def import_sampling(self, slot: int, state: dict) -> None:
        """Arm a slot from an :meth:`export_sampling` snapshot — data
        ops only (host arrays + an eager ``.at[].set`` on the key
        carry), so a migrated resume never retraces."""
        self._sample_temps[int(slot)] = float(state.get("temperature", 0.0))
        self._sample_topks[int(slot)] = int(state.get("top_k", 0))
        key = state.get("key")
        if key is not None:
            self._sample_keys = self._sample_keys.at[int(slot)].set(
                np.asarray(key, np.uint32)
            )

    # ----------------------------------------------- KV transfer primitives

    def gather_pages(self, kept):
        """Device-side gather of a detached slot's pages — the cheap,
        scheduler-thread half of :meth:`extract_pages`: one indexed
        read per cache leaf, dispatched asynchronously, materializing
        FRESH device buffers that share no storage with the
        executables' donated carry (so later decode steps can donate
        the pool away freely while these wait to be serialized).
        Returns per-leaf device arrays in ``tree_leaves`` order; hand
        them to :meth:`pages_to_host` OFF the scheduler thread."""
        if not self.paged:
            raise RuntimeError("gather_pages needs the paged plane")
        import jax

        idx = np.asarray([p for _, p in kept], np.int32)
        return [
            leaf[idx] for leaf in jax.tree_util.tree_leaves(
                self.manager.cache
            )
        ]

    def pages_to_host(self, raw, kept, length: int):
        """The blocking half of :meth:`extract_pages`: ONE batched
        ``jax.device_get`` over every leaf's gathered pages (not a
        device round-trip per page or per leaf), then zero the tail
        page at and past ``length`` — garbage rows must not travel and
        must not raise an int8 block scale (zeros never move an
        absmax). Thread-safe: ``raw`` are the fresh buffers
        :meth:`gather_pages` made, so this runs on the transfer
        handoff thread without touching engine state — an in-flight
        transfer can no longer stall decode admission rounds."""
        import jax

        pt = self.manager.page_tokens
        tail_valid = int(length) - (len(kept) - 1) * pt
        out = []
        for arr in jax.device_get(raw):
            arr = np.asarray(arr)
            if 0 <= tail_valid < pt:
                if not arr.flags.writeable:
                    arr = arr.copy()
                arr[-1, tail_valid:] = 0
            out.append(arr)
        return out

    def extract_pages(self, kept, length: int):
        """Host copies of a detached slot's pages for the transfer wire
        (serving/kv_transfer.py): one ``[n_pages, page_tokens, kv_heads,
        head_dim]`` ndarray per cache leaf, in ``tree_leaves`` order,
        with every position at or past ``length`` zeroed. Composed from
        :meth:`gather_pages` (scheduler-thread device gather) +
        :meth:`pages_to_host` (one batched ``device_get``) — the
        transfer sender splits the two halves across threads so only
        the async gather rides the scheduler hot path; this one-call
        form serves synchronous users (pack_pages, the audit
        roster)."""
        return self.pages_to_host(self.gather_pages(kept), kept, length)

    def ingest_attach(self, slot, logical, arrays, length, hashes=()):
        """Receiver side of a KV transfer: land foreign page payloads
        as refcounted LOCAL pages and point the slot's table at them.
        ``arrays`` are the per-leaf ``[n_pages, page_tokens, ...]``
        payloads (``extract_pages`` order, already dequantized to the
        pool dtype); ``hashes`` are the sender's chained prefix hashes
        so this worker's prefix cache warms from the transfer.

        Returns the kept-pages list now backing the slot, or None when
        the pool is dry (the server's 503 → the sender falls back).
        Pure data plane: the writes are eager device ops on the pool
        (the ``_cow`` pattern) and the table update is bookkeeping —
        shapes never change, so the decode executable compiled for the
        first admission serves every later ingest (zero retraces).
        Scheduler-thread only (single consumer of the pool)."""
        if not self.paged:
            raise RuntimeError("ingest_attach needs the paged plane")
        import jax

        mgr = self.manager
        phys = mgr.ingest_alloc(len(logical))
        if phys is None:
            return None
        idx = np.asarray(phys, np.int32)
        leaves = jax.tree_util.tree_leaves(mgr.cache)
        treedef = jax.tree_util.tree_structure(mgr.cache)
        new_leaves = [
            leaf.at[idx].set(np.asarray(arr, dtype=leaf.dtype))
            for leaf, arr in zip(leaves, arrays)
        ]
        mgr.cache = jax.tree_util.tree_unflatten(treedef, new_leaves)
        kept = list(zip([int(lp) for lp in logical], phys))
        mgr.reattach(slot, kept, int(length))
        if hashes:
            mgr.publish_hashes(kept, list(hashes))
        with self._lock:
            self._counters["transfer_ingests"] += 1
        return kept

    # ----------------------------------------------------------------- stats

    def stats(self) -> Dict[str, float]:
        with self._lock:
            out = dict(self._counters)
        for key in (
            "prefill_compiles", "decode_compiles", "prefills",
            "decode_steps", "prefill_exact_hits", "prefill_bucket_hits",
            "prefill_promotions", "prefill_pad_tokens",
            "chunked_prefill_chunks", "prefill_chunks_skipped",
            "prefill_tokens_skipped", "transfer_ingests",
            "paged_attn_calls", "paged_attn_fallbacks",
            "prefill_disk_hits", "decode_disk_hits",
            "prefill_bg_promotions",
        ):
            out.setdefault(key, 0)
        out["prefill_exact_entries"] = len(self._prefill_exact)
        out["prefill_bucket_entries"] = len(self._prefill_bucket)
        return out

    def publish(self) -> None:
        _metrics.update("serve", self.stats())
