"""ctypes loader and wrapper for the native ingest runtime.

Builds `ingest.cpp` with g++ on first use, next to the source under a
name that hashes the source, the flags and the host CPU;
every entry point degrades gracefully: `available()` is False when no
compiler exists, and callers fall back to the pure-NumPy host tier.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "ingest.cpp")
_FASTPATH_SRC = os.path.join(_HERE, "fastpath.cpp")
# -pthread: the parallel fold/drain entry points spawn std::threads
_LIB_FLAGS = ["-march=native", "-pthread"]


def _host_cpu() -> str:
    """What ``-march=native`` resolves against: the machine and, on
    Linux, its CPU model and feature flags."""
    import platform

    ident = [platform.machine()]
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("model name", "flags")):
                    ident.append(line.strip())
                if len(ident) == 3:
                    break
    except OSError:
        pass
    return "\n".join(ident)


def _built_path(stem: str, src: str, flags: list[str], suffix: str) -> str:
    """Library path named by a hash of its source, its flags and (for
    ``-march=native``) the host CPU: a library built elsewhere — copied
    along with the tree — never matches, so it is never loaded in place
    of one built on this host."""
    import hashlib

    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update(" ".join(flags).encode())
    if "-march=native" in flags:
        h.update(_host_cpu().encode())
    return os.path.join(_HERE, f"{stem}-{h.hexdigest()[:16]}{suffix}")


_LIB_PATH = _built_path("libloghisto_ingest", _SRC, _LIB_FLAGS, ".so")
# ABI-tagged filename: a CPython extension built under one interpreter
# must never be dlopened by another (unlike the ctypes lib above)
import sysconfig as _sysconfig

_FASTPATH_FLAGS = [f"-I{_sysconfig.get_paths()['include']}"]
_FASTPATH_PATH = _built_path(
    "loghisto_fastpath", _FASTPATH_SRC, _FASTPATH_FLAGS,
    _sysconfig.get_config_var("EXT_SUFFIX") or ".so",
)

_lib = None
_lib_lock = threading.Lock()
_build_error: str | None = None
_fastpath = None
_fastpath_error: str | None = None


def _compile(src: str, out_path: str, extra_flags: list[str]) -> str | None:
    """Compile `src` to `out_path` via a private temp file + atomic
    rename, so concurrent builders (e.g. pytest-xdist workers) can never
    dlopen a half-written .so.  Returns an error string or None."""
    import tempfile

    fd, tmp = tempfile.mkstemp(dir=_HERE, suffix=".so.tmp")
    os.close(fd)
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++17",
        *extra_flags, "-o", tmp, src,
    ]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=120
        )
        if proc.returncode != 0:
            return f"g++ failed: {proc.stderr[-2000:]}"
        os.replace(tmp, out_path)
        return None
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"g++ invocation failed: {e}"
    finally:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def _load():
    global _lib, _build_error
    with _lib_lock:
        if _lib is not None or _build_error is not None:
            return _lib
        if not os.path.exists(_LIB_PATH):
            _build_error = _compile(_SRC, _LIB_PATH, _LIB_FLAGS)
            if _build_error is not None:
                return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as e:
            _build_error = f"dlopen failed: {e}"
            return None

        lib.lh_create.restype = ctypes.c_void_p
        lib.lh_create.argtypes = [ctypes.c_int, ctypes.c_int64]
        lib.lh_destroy.argtypes = [ctypes.c_void_p]
        lib.lh_num_shards.restype = ctypes.c_int
        lib.lh_num_shards.argtypes = [ctypes.c_void_p]
        lib.lh_record.restype = ctypes.c_int64
        lib.lh_record.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int32, ctypes.c_double,
        ]
        lib.lh_record_batch.restype = ctypes.c_int64
        lib.lh_record_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_double),
            ctypes.c_int64,
        ]
        lib.lh_drain.restype = ctypes.c_int64
        lib.lh_drain.argtypes = [
            ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_double),
            ctypes.c_int64,
        ]
        lib.lh_dropped.restype = ctypes.c_uint64
        lib.lh_dropped.argtypes = [ctypes.c_void_p]
        lib.lh_compress.argtypes = [
            ctypes.POINTER(ctypes.c_double), ctypes.c_int64, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int16),
        ]
        lib.lh_decompress.argtypes = [
            ctypes.POINTER(ctypes.c_int16), ctypes.c_int64, ctypes.c_int,
            ctypes.POINTER(ctypes.c_double),
        ]
        lib.lh_accumulate_dense.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_double),
            ctypes.c_int64, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_int32,
        ]
        lib.lh_cells_create.restype = ctypes.c_void_p
        lib.lh_cells_create.argtypes = [ctypes.c_int64]
        lib.lh_cells_destroy.argtypes = [ctypes.c_void_p]
        lib.lh_cells_size.restype = ctypes.c_int64
        lib.lh_cells_size.argtypes = [ctypes.c_void_p]
        lib.lh_cells_add.restype = ctypes.c_int64
        lib.lh_cells_add.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64, ctypes.c_int,
            ctypes.c_int,
        ]
        lib.lh_cells_drain.restype = ctypes.c_int64
        lib.lh_cells_drain.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
        ]
        lib.lh_cells_drain_packed.restype = ctypes.c_int64
        lib.lh_cells_drain_packed.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32),
        ]
        lib.lh_packed_free.argtypes = [ctypes.POINTER(ctypes.c_int32)]
        lib.lh_fold_packed.restype = ctypes.c_int64
        lib.lh_fold_packed.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int64, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_int32)),
        ]
        lib.lh_fold_last_grows.restype = ctypes.c_int64
        lib.lh_fold_last_grows.argtypes = []
        lib.lh_cells_drain_packed_multi.restype = ctypes.c_int64
        lib.lh_cells_drain_packed_multi.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_int32)),
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def _load_fastpath():
    """Build+import the METH_FASTCALL per-call ingest extension."""
    global _fastpath, _fastpath_error
    with _lib_lock:
        if _fastpath is not None or _fastpath_error is not None:
            return _fastpath
        if not os.path.exists(_FASTPATH_PATH):
            _fastpath_error = _compile(
                _FASTPATH_SRC, _FASTPATH_PATH, _FASTPATH_FLAGS
            )
            if _fastpath_error is not None:
                return None
        try:
            import importlib.util

            spec = importlib.util.spec_from_file_location(
                "loghisto_fastpath", _FASTPATH_PATH
            )
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
        except Exception as e:  # ImportError, OSError
            _fastpath_error = f"import failed: {e}"
            return None
        _fastpath = mod
        return _fastpath


def fastpath_available() -> bool:
    return _load_fastpath() is not None


def fastpath_module():
    mod = _load_fastpath()
    if mod is None:
        raise RuntimeError(f"fastpath unavailable: {_fastpath_error}")
    return mod


def build_error() -> str | None:
    _load()
    return _build_error


def _i32(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def _f64(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_double))


def _i16(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int16))


def compress(values: np.ndarray, precision: int = 100) -> np.ndarray:
    """Native vectorized codec (matches ops.codec.compress_np exactly)."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_build_error}")
    values = np.ascontiguousarray(values, dtype=np.float64)
    out = np.empty(len(values), dtype=np.int16)
    lib.lh_compress(_f64(values), len(values), precision, _i16(out))
    return out


def preaggregate(
    ids: np.ndarray, values: np.ndarray, bucket_limit: int,
    precision: int = 100,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One-shot compress + dedup of a batch into unique (id, codec_bucket,
    count) cells.  A thin convenience over CellStore (one implementation
    of the codec/dedup contract, not two).  Returns
    (ids int32[m], codec_buckets int32[m], counts int64[m])."""
    store = CellStore(bucket_limit, precision,
                      initial_capacity=max(1024, 2 * len(ids)))
    try:
        consumed = store.add(ids, values)
        if consumed < len(ids):
            raise MemoryError("cell table allocation failed")
        return store.drain()
    finally:
        store.close()


def accumulate_dense(
    ids: np.ndarray, values: np.ndarray, num_metrics: int,
    bucket_limit: int, precision: int = 100,
    acc: np.ndarray | None = None,
) -> np.ndarray:
    """Native dense accumulate — CPU verification twin of the device kernel."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_build_error}")
    ids = np.ascontiguousarray(ids, dtype=np.int32)
    values = np.ascontiguousarray(values, dtype=np.float64)
    if acc is None:
        acc = np.zeros((num_metrics, 2 * bucket_limit + 1), dtype=np.uint32)
    lib.lh_accumulate_dense(
        _i32(ids), _f64(values), len(ids), precision, bucket_limit,
        acc.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)), num_metrics,
    )
    return acc


class CellStore:
    """Persistent (id, codec_bucket) -> count host accumulator.

    Batches fold in across flushes (`add`); `drain` empties it into
    unique-cell arrays for one weighted device merge.  This decouples
    sample rate from host->device wire bandwidth: the wire cost is the
    interval's unique cells, however many samples they absorbed."""

    def __init__(self, bucket_limit: int, precision: int = 100,
                 initial_capacity: int = 1 << 16):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native library unavailable: {_build_error}")
        self._lib = lib
        self._handle = lib.lh_cells_create(initial_capacity)
        if not self._handle:
            raise MemoryError("lh_cells_create failed")
        self.bucket_limit = bucket_limit
        self.precision = precision

    def __len__(self) -> int:
        return int(self._lib.lh_cells_size(self._handle))

    def add(self, ids: np.ndarray, values: np.ndarray) -> int:
        """Fold a batch in.  Returns the number of samples CONSUMED from
        the front of the batch: len(ids) on success, fewer only when the
        table could not grow — the consumed prefix is folded exactly
        once, so the caller retries ids[consumed:] (typically after
        draining).  Negative ids are consumed but skipped."""
        ids = np.ascontiguousarray(ids, dtype=np.int32)
        values = np.ascontiguousarray(values, dtype=np.float32)
        if ids.shape != values.shape:
            raise ValueError("ids and values must have the same shape")
        consumed = self._lib.lh_cells_add(
            self._handle, _i32(ids),
            values.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            len(ids), self.precision, self.bucket_limit,
        )
        return int(consumed)

    def drain(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Empty the store; returns (ids, codec_buckets, counts)."""
        m = len(self)
        ids_out = np.empty(m, dtype=np.int32)
        buckets_out = np.empty(m, dtype=np.int32)
        counts_out = np.empty(m, dtype=np.int64)
        got = self._lib.lh_cells_drain(
            self._handle, _i32(ids_out), _i32(buckets_out),
            counts_out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        )
        return ids_out[:got], buckets_out[:got], counts_out[:got]

    def drain_packed(self) -> np.ndarray:
        """Empty the store into one int32 [m, 3] array of
        (id, codec_bucket, count) rows — a single wire transfer for the
        device merge (ops.ingest.make_packed_ingest_fn), int32 end to
        end so no-x64 JAX canonicalization cannot truncate it.  A cell
        whose count exceeds the C side's 2^30-1 cap is emitted as
        multiple rows across passes (the drain loop below); histogram
        merges are additive, so split rows stay exact."""
        parts = []
        while True:
            m = len(self)
            if m == 0:
                break
            out = np.empty((m, 3), dtype=np.int32)
            got = self._lib.lh_cells_drain_packed(
                self._handle,
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            )
            parts.append(out[:got])
        if not parts:
            return np.empty((0, 3), dtype=np.int32)
        return parts[0] if len(parts) == 1 else np.concatenate(parts)

    def close(self) -> None:
        if self._handle:
            self._lib.lh_cells_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def unpack_cells(packed: np.ndarray):
    """Split the int32 [m, 3] (id, codec_bucket, count) wire array into
    (ids int32, codec_buckets int32, counts int64) columns — the host
    twin of the column reads in ops.ingest.make_packed_ingest_fn."""
    return (
        packed[:, 0],
        packed[:, 1],
        packed[:, 2].astype(np.int64),
    )


# -- packed-triple host fold (transport="sparse") -------------------------- #

# Per-row count cap of the packed wire format, mirroring ingest.cpp's
# LH_PACKED_COUNT_CAP: every emitted row stays < 2^30, below the
# aggregator's int32 spill threshold, and a larger count splits across
# rows (additive merges keep splits exact).
PACKED_COUNT_CAP = (1 << 30) - 1


def compress_np_host(values: np.ndarray, precision: int = 100) -> np.ndarray:
    """Float64 host codec, bit-for-bit the C side's compress_one (and
    ops.codec.compress_np) — duplicated here in pure NumPy so this module
    stays importable, and the preagg/sparse transports usable, without a
    compiler OR jax."""
    v = np.asarray(values, dtype=np.float64)
    mag = np.floor(precision * np.log1p(np.abs(v)) + 0.5)
    mag = np.where(np.isnan(mag), 0.0, mag)
    mag = np.minimum(mag, 32767.0)
    out = mag.astype(np.int32)
    return np.where(v < 0, -out, out).astype(np.int32)


def pack_cells(
    ids: np.ndarray, buckets: np.ndarray, counts: np.ndarray,
    cap: int = PACKED_COUNT_CAP,
) -> np.ndarray:
    """Assemble unique-cell columns into the int32 [m, 3] wire array,
    splitting any count > cap across rows (the NumPy twin of the C
    drain's split rule).  counts must be positive."""
    counts = np.asarray(counts, dtype=np.int64)
    if not len(counts):
        return np.empty((0, 3), dtype=np.int32)
    reps = (counts + cap - 1) // cap
    total = int(reps.sum())
    out = np.empty((total, 3), dtype=np.int32)
    out[:, 0] = np.repeat(np.asarray(ids, dtype=np.int64), reps)
    out[:, 1] = np.repeat(np.asarray(buckets, dtype=np.int64), reps)
    weights = np.full(total, cap, dtype=np.int64)
    ends = np.cumsum(reps) - 1
    weights[ends] = counts - (reps - 1) * cap
    out[:, 2] = weights
    return out


def fold_packed_numpy(
    ids: np.ndarray, values: np.ndarray, bucket_limit: int,
    precision: int = 100,
) -> np.ndarray:
    """Pure-NumPy fold of a raw batch into packed [m, 3] triples:
    compress (f64, same bits as the C/device codec boundary contract),
    key, unique — the compiler-less tier of transport="sparse"."""
    ids = np.asarray(ids, dtype=np.int32)
    values = np.asarray(values, dtype=np.float32)
    keep = ids >= 0
    if not keep.all():
        ids, values = ids[keep], values[keep]
    if not len(ids):
        return np.empty((0, 3), dtype=np.int32)
    b = np.clip(compress_np_host(values, precision),
                -bucket_limit, bucket_limit)
    keys = (ids.astype(np.int64) << 16) | (b.astype(np.int64) + 32768)
    ukeys, counts = np.unique(keys, return_counts=True)
    return pack_cells(ukeys >> 16, (ukeys & 0xFFFF) - 32768, counts)


def fold_packed_native(
    ids: np.ndarray, values: np.ndarray, bucket_limit: int,
    precision: int = 100, num_threads: int | None = None,
) -> np.ndarray:
    """Parallel native fold (lh_fold_packed), GIL released for the whole
    call.  With more than one thread it partitions cells by key hash, so
    each distinct (id, bucket) cell comes back as one row (more only past
    PACKED_COUNT_CAP); its tables, buffers and threads belong to the
    calling thread and are kept for that thread's next fold."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_build_error}")
    ids = np.ascontiguousarray(ids, dtype=np.int32)
    values = np.ascontiguousarray(values, dtype=np.float32)
    if ids.shape != values.shape:
        raise ValueError("ids and values must have the same shape")
    if num_threads is None:
        num_threads = min(8, os.cpu_count() or 1)
    out_ptr = ctypes.POINTER(ctypes.c_int32)()
    rows = lib.lh_fold_packed(
        _i32(ids),
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        len(ids), precision, bucket_limit, num_threads,
        ctypes.byref(out_ptr),
    )
    if rows < 0:
        raise MemoryError("lh_fold_packed allocation failed")
    try:
        if rows == 0:
            return np.empty((0, 3), dtype=np.int32)
        packed = np.ctypeslib.as_array(out_ptr, shape=(rows, 3)).copy()
    finally:
        lib.lh_packed_free(out_ptr)
    return packed


def fold_table_grows() -> int:
    """Hash-table grows during the calling thread's newest native fold
    (0 where the library is unavailable).  Near 0 once that thread's
    kept tables have grown to its items' cells."""
    lib = _load()
    return 0 if lib is None else int(lib.lh_fold_last_grows())


def fold_packed(
    ids: np.ndarray, values: np.ndarray, bucket_limit: int,
    precision: int = 100, num_threads: int | None = None,
) -> np.ndarray:
    """Fold a raw batch into packed triples via the fastest available
    tier: parallel native when the library built, pure NumPy otherwise
    (so the sparse transport never requires a compiler).  Both tiers run
    the same f64 codec, so their output cells are bit-identical."""
    if available():
        try:
            return fold_packed_native(
                ids, values, bucket_limit, precision, num_threads
            )
        except MemoryError:
            pass  # table/buffer allocation failed; NumPy tier below
    return fold_packed_numpy(ids, values, bucket_limit, precision)


class NumpyCellStore:
    """Pure-NumPy twin of CellStore (same add/drain/consumed-prefix
    contract) so transport="preagg" works without a compiler.  Each add
    deduplicates the batch vectorized (np.unique) and folds the unique
    cells into a dict keyed like the C table; drains share pack_cells'
    split rule."""

    def __init__(self, bucket_limit: int, precision: int = 100,
                 initial_capacity: int = 1 << 16):
        self._counts: dict[int, int] = {}
        self.bucket_limit = bucket_limit
        self.precision = precision

    def __len__(self) -> int:
        return len(self._counts)

    def add(self, ids: np.ndarray, values: np.ndarray) -> int:
        ids = np.asarray(ids, dtype=np.int32)
        values = np.asarray(values, dtype=np.float32)
        if ids.shape != values.shape:
            raise ValueError("ids and values must have the same shape")
        keep = ids >= 0
        kept_ids, kept_values = ids[keep], values[keep]
        if len(kept_ids):
            b = np.clip(
                compress_np_host(kept_values, self.precision),
                -self.bucket_limit, self.bucket_limit,
            )
            keys = (
                (kept_ids.astype(np.int64) << 16)
                | (b.astype(np.int64) + 32768)
            )
            ukeys, counts = np.unique(keys, return_counts=True)
            store = self._counts
            for k, c in zip(ukeys.tolist(), counts.tolist()):
                store[k] = store.get(k, 0) + c
        return len(ids)  # dict growth cannot partially fail mid-batch

    def drain_packed(self) -> np.ndarray:
        if not self._counts:
            return np.empty((0, 3), dtype=np.int32)
        keys = np.fromiter(
            self._counts.keys(), dtype=np.int64, count=len(self._counts)
        )
        counts = np.fromiter(
            self._counts.values(), dtype=np.int64, count=len(self._counts)
        )
        self._counts = {}
        return pack_cells(keys >> 16, (keys & 0xFFFF) - 32768, counts)

    def drain(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        return unpack_cells(self.drain_packed())

    def close(self) -> None:
        self._counts = {}


class ShardedCellStore:
    """K independent CellStores, each behind its own lock, with
    double-buffered draining (VERDICT r2 item 2: pipeline the preagg
    transport).

    * `add(ids, values)` folds into the CALLING THREAD's shard (sticky
      round-robin assignment) — ctypes releases the GIL during the C
      fold, so producer threads aggregate genuinely in parallel instead
      of serializing on one table lock.
    * `drain_packed_all()` swaps each shard's active store with its empty
      spare under the shard lock (O(1) critical section) and scans the
      detached table OUTSIDE the lock — producers never stall behind the
      O(capacity) drain, and the caller can overlap the device merge of
      shard k with the drain of shard k+1.

    Cell counts stay exact: a (key -> count) entry may exist in several
    shards; the device merge is additive, so duplicates across shards
    cost only wire bytes (bounded by K, worth it for lock-free-ish
    ingest)."""

    def __init__(self, bucket_limit: int, precision: int = 100,
                 num_shards: int | None = None,
                 initial_capacity: int = 1 << 14,
                 backend: str = "auto"):
        """``backend`` picks the per-shard store: "native" (C hash table,
        raises without a compiler), "numpy" (NumpyCellStore — slower adds
        but zero build dependency), or "auto" (native when available,
        NumPy otherwise — preagg no longer requires a compiler)."""
        if backend not in ("auto", "native", "numpy"):
            raise ValueError(
                f"backend={backend!r}: expected 'auto', 'native', or 'numpy'"
            )
        if backend == "auto":
            backend = "native" if available() else "numpy"
        self.backend = backend
        store_cls = CellStore if backend == "native" else NumpyCellStore
        if num_shards is None:
            num_shards = min(8, (os.cpu_count() or 1))
        self.num_shards = max(1, int(num_shards))
        self._locks = [threading.Lock() for _ in range(self.num_shards)]
        self._active = [
            store_cls(bucket_limit, precision, initial_capacity)
            for _ in range(self.num_shards)
        ]
        self._spare = [
            store_cls(bucket_limit, precision, initial_capacity)
            for _ in range(self.num_shards)
        ]
        # only one drainer manipulates the spare set at a time
        self._drain_lock = threading.Lock()
        self._tl = threading.local()
        self._assign = 0

    def _shard_idx(self) -> int:
        idx = getattr(self._tl, "idx", None)
        if idx is None:
            idx = self._assign % self.num_shards
            self._assign += 1  # benign race: placement heuristic only
            self._tl.idx = idx
        return idx

    def __len__(self) -> int:
        # racy sum (watermark heuristic, not an invariant)
        return sum(len(s) for s in self._active)

    def add(self, ids: np.ndarray, values: np.ndarray) -> int:
        """Fold a batch into this thread's shard.  Same exactness contract
        as CellStore.add: returns the consumed prefix length."""
        i = self._shard_idx()
        with self._locks[i]:
            return self._active[i].add(ids, values)

    def drain_packed_all(self) -> np.ndarray:
        """Drain every shard; returns one int32 [m, 3] packed array.
        Per shard: O(1) swap under the shard lock; the detached tables
        are then scanned OUTSIDE the locks — in ONE GIL-released parallel
        native call (lh_cells_drain_packed_multi) when the backend is
        native, shard-serial NumPy otherwise."""
        with self._drain_lock:
            detached = []
            for i in range(self.num_shards):
                with self._locks[i]:
                    self._active[i], self._spare[i] = (
                        self._spare[i], self._active[i]
                    )
                detached.append(self._spare[i])  # old active; drain unlocked
            if self.backend == "native":
                packed = self._drain_native_multi(detached)
                if packed is not None:
                    return packed
            parts = [s.drain_packed() for s in detached]
            parts = [p for p in parts if len(p)]
        if not parts:
            return np.empty((0, 3), dtype=np.int32)
        return np.concatenate(parts) if len(parts) > 1 else parts[0]

    @staticmethod
    def _drain_native_multi(stores) -> np.ndarray | None:
        """Parallel whole-set drain of detached native stores; None means
        the native call could not run (allocation failure) and the caller
        falls back to the per-shard Python drain."""
        lib = _load()
        handles = (ctypes.c_void_p * len(stores))(
            *[s._handle for s in stores]
        )
        threads = min(len(stores), os.cpu_count() or 1)
        out_ptr = ctypes.POINTER(ctypes.c_int32)()
        rows = lib.lh_cells_drain_packed_multi(
            handles, len(stores), threads, ctypes.byref(out_ptr)
        )
        if rows < 0:
            return None
        try:
            if rows == 0:
                return np.empty((0, 3), dtype=np.int32)
            return np.ctypeslib.as_array(out_ptr, shape=(rows, 3)).copy()
        finally:
            lib.lh_packed_free(out_ptr)

    def drain(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Compatibility form of drain_packed_all (ids, buckets, counts)."""
        return unpack_cells(self.drain_packed_all())

    def close(self) -> None:
        for s in self._active + self._spare:
            s.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeIngestBuffer:
    """Lock-striped native staging buffer for (metric_id, value) samples.

    Writers call record/record_batch (GIL released inside the C call);
    the reaper drains shards for vectorized compression + device upload.
    Full shards shed samples and count them (`dropped`), mirroring the
    reference's shed-don't-block policy."""

    def __init__(self, num_shards: int = 16, capacity_per_shard: int = 1 << 20):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native library unavailable: {_build_error}")
        self._lib = lib
        self._handle = lib.lh_create(num_shards, capacity_per_shard)
        if not self._handle:
            raise MemoryError("lh_create failed")
        self.num_shards = num_shards
        self.capacity_per_shard = capacity_per_shard
        self._shard_counter = 0
        self._tl = threading.local()

    def _shard(self) -> int:
        idx = getattr(self._tl, "idx", None)
        if idx is None:
            idx = self._shard_counter % self.num_shards
            self._shard_counter += 1
            self._tl.idx = idx
        return idx

    def record(self, metric_id: int, value: float) -> int:
        return self._lib.lh_record(
            self._handle, self._shard(), metric_id, value
        )

    def record_batch(self, ids: np.ndarray, values: np.ndarray) -> int:
        ids = np.ascontiguousarray(ids, dtype=np.int32)
        values = np.ascontiguousarray(values, dtype=np.float64)
        if ids.shape != values.shape:
            raise ValueError("ids and values must have the same shape")
        return int(self._lib.lh_record_batch(
            self._handle, self._shard(), _i32(ids), _f64(values), len(ids)
        ))

    def drain(self) -> tuple[np.ndarray, np.ndarray]:
        """Swap out and return all staged samples from every shard."""
        cap = self.capacity_per_shard
        all_ids, all_values = [], []
        ids = np.empty(cap, dtype=np.int32)
        values = np.empty(cap, dtype=np.float64)
        for shard in range(self.num_shards):
            n = self._lib.lh_drain(
                self._handle, shard, _i32(ids), _f64(values), cap
            )
            if n > 0:
                all_ids.append(ids[:n].copy())
                all_values.append(values[:n].copy())
        if not all_ids:
            return (np.empty(0, dtype=np.int32), np.empty(0, dtype=np.float64))
        return np.concatenate(all_ids), np.concatenate(all_values)

    @property
    def dropped(self) -> int:
        return int(self._lib.lh_dropped(self._handle))

    def close(self) -> None:
        if self._handle:
            self._lib.lh_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
