"""ctypes bindings for libhvd_native.so.

The Python mirror of the reference's ``HorovodBasics`` ctypes bootstrap
(ref: horovod/common/basics.py [V] — SURVEY.md §2.4): one place loads
the shared library, declares every C signature, and exposes typed
wrappers. Set ``HOROVOD_NATIVE=0`` to force the pure-Python fallbacks
everywhere (useful for differential testing; the test suite runs both).
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

_lock = threading.Lock()
# name ("lib"/"ext") -> loaded object, or None after a failed attempt
_cache: dict = {}


def _native_disabled() -> bool:
    return (os.environ.get("HOROVOD_NATIVE", "1") == "0"
            or os.environ.get("HOROVOD_TPU_NATIVE", "1") == "0")


def _load_once(name: str, load) -> Optional[Any]:
    """Env-gated, lock-guarded, attempt-once loader cache shared by the
    ctypes library and the CPython extension halves."""
    if _native_disabled():
        return None
    with _lock:
        if name in _cache:
            return _cache[name]
        try:
            _cache[name] = load()
        except (ImportError, OSError) as e:
            from ..common.logging import get_logger

            get_logger("native").warning(
                "native %s failed to load (%s); the pure-Python "
                "fallbacks serve", name, e,
            )
            _cache[name] = None
        return _cache[name]


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    lib.hvd_tl_create.restype = c.c_void_p
    lib.hvd_tl_destroy.argtypes = [c.c_void_p]
    lib.hvd_tl_emit.argtypes = [c.c_void_p, c.c_char_p]
    lib.hvd_tl_count.argtypes = [c.c_void_p]
    lib.hvd_tl_count.restype = c.c_long
    lib.hvd_tl_drain_size.argtypes = [c.c_void_p]
    lib.hvd_tl_drain_size.restype = c.c_long
    lib.hvd_tl_drain.argtypes = [c.c_void_p, c.c_char_p, c.c_long]
    lib.hvd_tl_drain.restype = c.c_long

    for suffix, ptr in (("f32", c.POINTER(c.c_float)),
                        ("f64", c.POINTER(c.c_double))):
        pair = getattr(lib, f"hvd_adasum_pair_{suffix}")
        pair.argtypes = [ptr, ptr, ptr, c.c_long]
        tree = getattr(lib, f"hvd_adasum_tree_{suffix}")
        tree.argtypes = [ptr, c.c_long, c.c_long, ptr]

    dp = c.POINTER(c.c_double)
    lib.hvd_gp_create.argtypes = [c.c_double, c.c_double]
    lib.hvd_gp_create.restype = c.c_void_p
    lib.hvd_gp_destroy.argtypes = [c.c_void_p]
    lib.hvd_gp_fit.argtypes = [c.c_void_p, dp, dp, c.c_long, c.c_long]
    lib.hvd_gp_fit.restype = c.c_int
    lib.hvd_gp_predict.argtypes = [c.c_void_p, dp, c.c_long, dp, dp]
    lib.hvd_gp_predict.restype = c.c_int

    vp = c.POINTER(c.c_void_p)
    lp = c.POINTER(c.c_long)
    lib.hvd_pack.argtypes = [vp, lp, c.c_long, c.c_void_p]
    lib.hvd_unpack.argtypes = [c.c_void_p, vp, lp, c.c_long]

    lib.hvd_npy_open.argtypes = [c.c_char_p]
    lib.hvd_npy_open.restype = c.c_void_p
    lib.hvd_npy_rows.argtypes = [c.c_void_p]
    lib.hvd_npy_rows.restype = c.c_long
    lib.hvd_npy_row_bytes.argtypes = [c.c_void_p]
    lib.hvd_npy_row_bytes.restype = c.c_long
    lib.hvd_npy_gather.argtypes = [c.c_void_p, lp, c.c_long, c.c_void_p]
    lib.hvd_npy_gather.restype = c.c_long
    lib.hvd_npy_gather_scattered.argtypes = [vp, lp, lp, c.c_long,
                                             c.c_void_p]
    lib.hvd_npy_gather_scattered.restype = c.c_long
    lib.hvd_npy_close.argtypes = [c.c_void_p]

    u8p = c.POINTER(c.c_uint8)
    lib.hvd_kv_start.argtypes = [c.c_int, u8p, c.c_long, c.POINTER(c.c_int)]
    lib.hvd_kv_start.restype = c.c_void_p
    lib.hvd_kv_port.argtypes = [c.c_void_p]
    lib.hvd_kv_port.restype = c.c_int
    lib.hvd_kv_stop.argtypes = [c.c_void_p]
    lib.hvd_kv_put.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p, u8p,
                               c.c_long]
    lib.hvd_kv_get.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p, u8p,
                               c.c_long]
    lib.hvd_kv_get.restype = c.c_long
    lib.hvd_kv_keys.argtypes = [c.c_void_p, c.c_char_p, u8p, c.c_long]
    lib.hvd_kv_keys.restype = c.c_long
    lib.hvd_kv_drop_scope.argtypes = [c.c_void_p, c.c_char_p]


def _load_lib() -> Optional[ctypes.CDLL]:
    from . import build

    path = build.lib_path()
    if path is None:
        return None
    lib = ctypes.CDLL(path)
    _declare(lib)
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded library, building it on first call; None if disabled
    (HOROVOD_NATIVE=0; HOROVOD_TPU_NATIVE=0 is honored as an alias) or
    unbuildable."""
    return _load_once("lib", _load_lib)


def available() -> bool:
    return get_lib() is not None


def _load_ext() -> Optional[Any]:
    import importlib.util

    from . import build

    path = build.ext_path()
    if path is None:
        return None
    spec = importlib.util.spec_from_file_location(
        "horovod_tpu._native._hvd_cext", path
    )
    if spec is None or spec.loader is None:
        return None
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def get_ext() -> Optional[Any]:
    """The ``_hvd_cext`` CPython extension module (csrc/cext.cc) —
    the native binding half that reads framework tensors through the
    buffer protocol (zero-copy, GIL released during staging copies).
    None when native is disabled (same env gate as :func:`get_lib`) or
    unbuildable (e.g. no Python dev headers)."""
    return _load_once("ext", _load_ext)


def ext_available() -> bool:
    return get_ext() is not None


# ---------------------------------------------------------------- timeline

class TimelineBuffer:
    """Native event sink for common/timeline.py."""

    def __init__(self, lib: ctypes.CDLL):
        self._lib = lib
        self._h = lib.hvd_tl_create()

    def emit(self, json_str: str) -> None:
        self._lib.hvd_tl_emit(self._h, json_str.encode())

    def drain(self) -> List[str]:
        # An emit can land between the size query and the drain, making
        # hvd_tl_drain return -1 with the buffer intact — re-probe and
        # retry (mirrors NativeKVServer._read) so a final shutdown drain
        # never drops buffered events.
        for _ in range(8):
            size = self._lib.hvd_tl_drain_size(self._h)
            if size <= 0:
                return []
            buf = ctypes.create_string_buffer(size)
            n = self._lib.hvd_tl_drain(self._h, buf, size)
            if n >= 0:
                text = buf.raw[:n].decode()
                return [line for line in text.split("\n") if line]
        return []

    def __len__(self) -> int:
        return self._lib.hvd_tl_count(self._h)

    def __del__(self):
        lib, h = getattr(self, "_lib", None), getattr(self, "_h", None)
        if lib is not None and h:
            lib.hvd_tl_destroy(h)


def timeline_buffer() -> TimelineBuffer:
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable")
    return TimelineBuffer(lib)


# ------------------------------------------------------------------ adasum

def adasum_pair(a: np.ndarray, b: np.ndarray) -> Optional[np.ndarray]:
    """Native Adasum combine of two host vectors; None if unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    dtype = np.result_type(a.dtype, b.dtype)
    if dtype == np.float64:
        fn, ct = lib.hvd_adasum_pair_f64, ctypes.c_double
        dtype = np.float64
    else:
        fn, ct = lib.hvd_adasum_pair_f32, ctypes.c_float
        dtype = np.float32
    af = np.ascontiguousarray(a, dtype=dtype).ravel()
    bf = np.ascontiguousarray(b, dtype=dtype).ravel()
    out = np.empty_like(af)
    p = ctypes.POINTER(ct)
    fn(af.ctypes.data_as(p), bf.ctypes.data_as(p), out.ctypes.data_as(p),
       af.size)
    return out.reshape(a.shape)


def adasum_tree(stack: np.ndarray) -> Optional[np.ndarray]:
    """Pairwise-tree Adasum over stack[k, n]; None if unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    if stack.dtype == np.float64:
        fn, ct = lib.hvd_adasum_tree_f64, ctypes.c_double
        dtype = np.float64
    else:
        fn, ct = lib.hvd_adasum_tree_f32, ctypes.c_float
        dtype = np.float32
    k = stack.shape[0]
    flat = np.ascontiguousarray(stack, dtype=dtype).reshape(k, -1)
    out = np.empty(flat.shape[1], dtype=dtype)
    p = ctypes.POINTER(ct)
    fn(flat.ctypes.data_as(p), k, flat.shape[1], out.ctypes.data_as(p))
    return out.reshape(stack.shape[1:])


# ---------------------------------------------------------------------- GP

class NativeGaussianProcess:
    """Drop-in for common/autotune.py::GaussianProcess (same model)."""

    def __init__(self, noise: float = 0.8, length_scale: float = 0.2):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.hvd_gp_create(noise, length_scale)
        self.noise = noise
        self.length_scale = length_scale

    def fit(self, x: np.ndarray, y: np.ndarray) -> None:
        x = np.ascontiguousarray(np.atleast_2d(x), dtype=np.float64)
        y = np.ascontiguousarray(y, dtype=np.float64).ravel()
        dp = ctypes.POINTER(ctypes.c_double)
        rc = self._lib.hvd_gp_fit(
            self._h, x.ctypes.data_as(dp), y.ctypes.data_as(dp),
            x.shape[0], x.shape[1],
        )
        if rc != 0:
            raise np.linalg.LinAlgError("kernel matrix not positive definite")

    def predict(self, x: np.ndarray):
        x = np.ascontiguousarray(np.atleast_2d(x), dtype=np.float64)
        m = x.shape[0]
        mu = np.empty(m, dtype=np.float64)
        sigma = np.empty(m, dtype=np.float64)
        dp = ctypes.POINTER(ctypes.c_double)
        rc = self._lib.hvd_gp_predict(
            self._h, x.ctypes.data_as(dp), m,
            mu.ctypes.data_as(dp), sigma.ctypes.data_as(dp),
        )
        if rc != 0:
            raise RuntimeError("predict before fit")
        return mu, sigma

    def __del__(self):
        lib, h = getattr(self, "_lib", None), getattr(self, "_h", None)
        if lib is not None and h:
            lib.hvd_gp_destroy(h)


# -------------------------------------------------------------------- pack

def pack(arrays: List[np.ndarray]) -> Optional[np.ndarray]:
    """Concatenate the raw bytes of host arrays into one uint8 buffer
    with a single C call; None if unavailable. Prefers the CPython
    extension (buffer protocol, GIL released); falls back to the ctypes
    pointer-array path."""
    ext = get_ext()
    lib = None if ext is not None else get_lib()
    if ext is None and lib is None:
        return None
    arrays = [np.ascontiguousarray(a) for a in arrays]
    total = sum(a.nbytes for a in arrays)
    if ext is not None:
        out = np.empty(total, dtype=np.uint8)
        ext.pack_into(out, [a.view(np.uint8).reshape(-1) for a in arrays])
        return out
    k = len(arrays)
    out = np.empty(total, dtype=np.uint8)
    srcs = (ctypes.c_void_p * k)(*[a.ctypes.data for a in arrays])
    sizes = (ctypes.c_long * k)(*[a.nbytes for a in arrays])
    lib.hvd_pack(srcs, sizes, k, out.ctypes.data_as(ctypes.c_void_p))
    return out


def unpack(buf: np.ndarray, like: List[np.ndarray]) -> Optional[List[np.ndarray]]:
    """Split a packed uint8 buffer back into arrays shaped/typed like
    ``like``; None if unavailable."""
    ext = get_ext()
    if ext is None and get_lib() is None:
        return None
    buf = np.ascontiguousarray(buf)
    outs = [np.empty_like(np.ascontiguousarray(a)) for a in like]
    if ext is not None:
        ext.unpack_into(
            buf.view(np.uint8).reshape(-1),
            [o.view(np.uint8).reshape(-1) for o in outs],
        )
        return outs
    lib = get_lib()
    if lib is None:
        return None
    k = len(outs)
    dsts = (ctypes.c_void_p * k)(*[o.ctypes.data for o in outs])
    sizes = (ctypes.c_long * k)(*[o.nbytes for o in outs])
    lib.hvd_unpack(
        buf.ctypes.data_as(ctypes.c_void_p),
        dsts, sizes, k,
    )
    return outs


class PackedSnapshot:
    """One contiguous host block holding the raw bytes of a sequence of
    arrays — the native in-memory checkpoint behind the elastic State
    commit (ref: horovod/torch/adapter_v2.cc's zero-copy tensor access
    feeding the C core's staging buffers [V] — SURVEY.md §2.3). Commit
    cost is one allocation plus a GIL-released memcpy sweep instead of
    one Python-level clone per tensor; ``view(i)`` returns a zero-copy
    numpy window into the block (callers that hand views to consumers
    that copy anyway — e.g. ``Module.load_state_dict`` — never copy the
    snapshot at all)."""

    def __init__(self, buf: np.ndarray,
                 metas: List[Tuple[Tuple[int, ...], np.dtype, int]]):
        self.buf = buf
        self.metas = metas  # (shape, dtype, byte offset) per array

    def __len__(self) -> int:
        return len(self.metas)

    @property
    def nbytes(self) -> int:
        return self.buf.nbytes

    def view(self, i: int) -> np.ndarray:
        shape, dtype, off = self.metas[i]
        n = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
        return self.buf[off:off + n].view(dtype).reshape(shape)

    def arrays(self) -> List[np.ndarray]:
        """Fresh copies of every array (restore-to-owned-memory)."""
        return [self.view(i).copy() for i in range(len(self.metas))]


def snapshot_arrays(
    arrays: Sequence[np.ndarray],
) -> Optional[PackedSnapshot]:
    """Pack host arrays into a :class:`PackedSnapshot`; None when the
    native layer is unavailable (callers keep their pure-Python clone
    path)."""
    ext = get_ext()
    if ext is None and get_lib() is None:
        return None
    # Record shapes BEFORE ascontiguousarray: it promotes 0-d arrays to
    # (1,), and the snapshot must restore the original shape exactly
    # (e.g. Adam's 0-d 'step' tensors).
    shapes = [np.asarray(a).shape for a in arrays]
    arrays = [np.ascontiguousarray(a) for a in arrays]
    metas: List[Tuple[Tuple[int, ...], np.dtype, int]] = []
    off = 0
    for shape, a in zip(shapes, arrays):
        metas.append((shape, a.dtype, off))
        off += a.nbytes
    buf = pack(arrays)
    if buf is None:
        return None
    return PackedSnapshot(buf, metas)


# ----------------------------------------------------------------- kvstore

class NativeKVServer:
    """Native rendezvous server + direct store access (the ``.store``
    surface the elastic driver uses on the Python server)."""

    def __init__(self, port: int = 0, secret_key: Optional[bytes] = None):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        secret = secret_key or b""
        sec = (ctypes.c_uint8 * max(len(secret), 1))(*secret)
        out_port = ctypes.c_int(0)
        self._h = lib.hvd_kv_start(
            port, sec, len(secret), ctypes.byref(out_port)
        )
        if not self._h:
            raise OSError(f"native KV server failed to bind port {port}")
        self.port = out_port.value

    # -- KVStore-compatible surface --

    def put(self, scope: str, key: str, value: bytes) -> None:
        if value:
            buf = (ctypes.c_uint8 * len(value)).from_buffer_copy(value)
        else:
            buf = (ctypes.c_uint8 * 1)()
        self._lib.hvd_kv_put(
            self._h, scope.encode(), key.encode(), buf, len(value)
        )

    def _read(self, fn, *args) -> Optional[bytes]:
        """Size-probe-then-copy, retried: the two C calls lock
        separately, so a concurrent writer can change the length between
        them. The copy call reports the length it saw under its own
        lock — accept only a copy whose reported length fits the buffer
        we handed it (shorter is fine: the C side copied exactly that
        many bytes atomically)."""
        cap = fn(self._h, *args, None, 0)
        while True:
            if cap < 0:
                return None
            if cap == 0:
                return b""
            buf = (ctypes.c_uint8 * cap)()
            n = fn(self._h, *args, buf, cap)
            if n < 0:
                return None
            if n <= cap:
                return bytes(buf)[:n]
            cap = n  # grew underneath us — retry with the larger size

    def get(self, scope: str, key: str) -> Optional[bytes]:
        return self._read(self._lib.hvd_kv_get, scope.encode(), key.encode())

    def keys(self, scope: str) -> List[str]:
        joined = self._read(self._lib.hvd_kv_keys, scope.encode())
        if not joined:
            return []
        return joined.decode().split("\n")

    def drop_scope(self, scope: str) -> None:
        self._lib.hvd_kv_drop_scope(self._h, scope.encode())

    def stop(self) -> None:
        if self._h:
            self._lib.hvd_kv_stop(self._h)
            self._h = None

    def __del__(self):
        try:
            self.stop()
        except Exception:
            pass


# -------------------------------------------------------------------- npy IO

class NpyReader:
    """mmap'd row-gather view of a C-order .npy file (csrc/npyio.cc) —
    the native data-loader half behind ``data.ShardedFileDataset``'s
    uncompressed fast path. ``None`` from :func:`npy_reader` means no
    native library (or an unsupported file); callers fall back to
    ``np.load(mmap_mode='r')`` fancy indexing."""

    _native_gather = True  # data.ShardedFileDataset dispatch marker

    def __init__(self, lib, handle, path: str):
        # Validate BEFORE taking ownership of the handle: if anything
        # here raises (numpy rejecting a descr the C parser skipped,
        # stride disagreement), self._h is never set, __del__ is a
        # no-op, and npy_reader closes the handle exactly once.
        mm = np.load(path, mmap_mode="r")
        shape, dtype = mm.shape, mm.dtype
        del mm
        row_bytes = int(np.prod(shape[1:], dtype=np.int64)) * dtype.itemsize
        if (
            lib.hvd_npy_rows(handle) != shape[0]
            or lib.hvd_npy_row_bytes(handle) != row_bytes
        ):
            raise ValueError(f"native/numpy header disagreement: {path}")
        self.shape = shape
        self.dtype = dtype
        self._lib = lib
        self._h = handle

    def take(self, idx: np.ndarray) -> np.ndarray:
        """Rows ``idx`` as one contiguous array (single C gather)."""
        idx = np.ascontiguousarray(idx, dtype=np.int64)
        out = np.empty((len(idx),) + self.shape[1:], self.dtype)
        copied = self._lib.hvd_npy_gather(
            self._h,
            idx.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
            len(idx),
            out.ctypes.data_as(ctypes.c_void_p),
        )
        if copied != len(idx):
            raise IndexError(
                f"row index {int(idx[copied])} out of range "
                f"[0, {self.shape[0]})"
            )
        return out

    def close(self) -> None:
        if self._h:
            self._lib.hvd_npy_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def npy_reader(path: str) -> Optional[NpyReader]:
    """Open ``path`` with the native reader; None when the library is
    unavailable or the file is unsupported (compressed, Fortran-order,
    0-d)."""
    lib = get_lib()
    if lib is None:
        return None
    handle = lib.hvd_npy_open(os.fsencode(path))
    if not handle:
        return None
    try:
        return NpyReader(lib, handle, path)
    except Exception:
        lib.hvd_npy_close(handle)  # __init__ raised before taking ownership
        return None


def npy_gather_scattered(readers, hsel: np.ndarray, local: np.ndarray,
                         out: np.ndarray) -> bool:
    """One C call gathering out[i] = readers[hsel[i]].row(local[i])
    across many mapped shards (csrc/npyio.cc). All readers must share
    the row stride (caller-validated). False when unavailable."""
    lib = get_lib()
    if lib is None or not readers:
        return False
    handles = (ctypes.c_void_p * len(readers))(*[r._h for r in readers])
    hsel = np.ascontiguousarray(hsel, dtype=np.int64)
    local = np.ascontiguousarray(local, dtype=np.int64)
    lp = ctypes.POINTER(ctypes.c_long)
    copied = lib.hvd_npy_gather_scattered(
        handles,
        hsel.ctypes.data_as(lp),
        local.ctypes.data_as(lp),
        len(local),
        out.ctypes.data_as(ctypes.c_void_p),
    )
    if copied != len(local):
        raise IndexError(
            f"scattered gather stopped at position {int(copied)} "
            "(row index out of range or stride mismatch)"
        )
    return True
