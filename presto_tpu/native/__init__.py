"""ctypes bindings for the native host data plane (native/src/ptnative.cpp).

The library is compiled on first use with g++ (toolchain is part of the
image; no pip/pybind11 — plain C ABI + ctypes, as the environment
prescribes).  Every entry point has a numpy fallback so the engine still
runs if a build is impossible; `available()` reports which path is live.

Reference parity: this plays the role of presto-bytecode/sql-gen's
"make the host path fast" layer plus PagesSerde's LZ4 codec
(presto-main/.../execution/buffer/PagesSerde.java:49-60).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "src", "ptnative.cpp")
_BUILD_DIR = os.path.join(_REPO_ROOT, "native", "build")

_lock = threading.Lock()
_lib = None
_tried = False


def _so_path() -> str:
    """The library is keyed by a hash of its source, in the file name: a
    copy of the checkout (which keeps no mtime order) can never pair a
    stale binary with a newer source."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"libptnative-{digest}.so")


def _build(so: str) -> bool:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = f"{so}.{os.getpid()}.tmp"  # concurrent builders never share it
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-fvisibility=hidden",
        "-std=c++17", "-o", tmp, _SRC,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
        return True
    except (subprocess.SubprocessError, OSError):
        return False


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    f64p = ctypes.POINTER(ctypes.c_double)
    lib.pt_xxh64.restype = ctypes.c_uint64
    lib.pt_xxh64.argtypes = [u8p, ctypes.c_int64, ctypes.c_uint64]
    lib.pt_lz4_max_compressed.restype = ctypes.c_int64
    lib.pt_lz4_max_compressed.argtypes = [ctypes.c_int64]
    lib.pt_lz4_compress.restype = ctypes.c_int64
    lib.pt_lz4_compress.argtypes = [u8p, ctypes.c_int64, u8p, ctypes.c_int64]
    lib.pt_lz4_decompress.restype = ctypes.c_int64
    lib.pt_lz4_decompress.argtypes = [u8p, ctypes.c_int64, u8p, ctypes.c_int64]
    lib.pt_rle_encode_i64.restype = ctypes.c_int64
    lib.pt_rle_encode_i64.argtypes = [i64p, ctypes.c_int64, i64p, i64p, ctypes.c_int64]
    lib.pt_rle_decode_i64.restype = ctypes.c_int64
    lib.pt_rle_decode_i64.argtypes = [i64p, i64p, ctypes.c_int64, i64p, ctypes.c_int64]
    lib.pt_minmax_i64.restype = None
    lib.pt_minmax_i64.argtypes = [i64p, ctypes.c_int64, i64p]
    lib.pt_minmax_f64.restype = None
    lib.pt_minmax_f64.argtypes = [f64p, ctypes.c_int64, f64p]
    lib.pt_delta_width_i64.restype = ctypes.c_int32
    lib.pt_delta_width_i64.argtypes = [i64p, ctypes.c_int64, i64p]
    lib.pt_delta_pack_i64.restype = ctypes.c_int64
    lib.pt_delta_pack_i64.argtypes = [i64p, ctypes.c_int64, ctypes.c_int32, u8p]
    lib.pt_delta_unpack_i64.restype = ctypes.c_int64
    lib.pt_delta_unpack_i64.argtypes = [
        u8p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int64, ctypes.c_int64, i64p]
    lib.pt_dict_encode.restype = ctypes.c_int64
    lib.pt_dict_encode.argtypes = [u8p, i64p, ctypes.c_int64, i32p, i64p, ctypes.c_int64]
    lib.pt_sel_to_idx.restype = ctypes.c_int64
    lib.pt_sel_to_idx.argtypes = [u8p, ctypes.c_int64, i64p]
    lib.pt_gather.restype = None
    lib.pt_gather.argtypes = [u8p, ctypes.c_int64, i64p, ctypes.c_int64, u8p]
    lib.pt_version.restype = ctypes.c_int32
    return lib


def get_lib():
    """Load (building if missing for this source) the native library,
    or None.  Only a loaded library is returned without the lock: a
    caller that arrives while the first one is still loading waits for
    it, instead of seeing "tried, none" and checksumming a page with
    the fallback function its reader will not use."""
    global _lib, _tried
    if _lib is not None:
        return _lib
    with _lock:
        if _lib is None and not _tried:
            _tried = True
            try:
                so = _so_path()
                if os.path.exists(so) or _build(so):
                    _lib = _bind(ctypes.CDLL(so))
            except OSError:
                _lib = None
        return _lib


def available() -> bool:
    return get_lib() is not None


def _u8(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _as_bytes_arr(data) -> np.ndarray:
    if isinstance(data, (bytes, bytearray, memoryview)):
        return np.frombuffer(data, dtype=np.uint8)
    return np.ascontiguousarray(data).view(np.uint8).reshape(-1)


# ---------------------------------------------------------------------------
# public API (native with numpy/zlib fallbacks)
# ---------------------------------------------------------------------------


def xxh64(data, seed: int = 0) -> int:
    a = _as_bytes_arr(data)
    lib = get_lib()
    if lib is not None:
        return int(lib.pt_xxh64(_u8(a), a.size, ctypes.c_uint64(seed)))
    import zlib  # fallback checksum (different function, same role)
    return zlib.crc32(a.tobytes(), seed & 0xFFFFFFFF)


def lz4_compress(data) -> bytes | None:
    """Compress; returns None if native codec unavailable."""
    a = _as_bytes_arr(data)
    lib = get_lib()
    if lib is None:
        return None
    cap = int(lib.pt_lz4_max_compressed(a.size))
    out = np.empty(cap, dtype=np.uint8)
    n = int(lib.pt_lz4_compress(_u8(a), a.size, _u8(out), cap))
    if n < 0:
        return None
    return out[:n].tobytes()


def lz4_decompress(data, raw_len: int) -> bytes:
    a = _as_bytes_arr(data)
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native codec unavailable for decompression")
    out = np.empty(raw_len, dtype=np.uint8)
    n = int(lib.pt_lz4_decompress(_u8(a), a.size, _u8(out), raw_len))
    if n != raw_len:
        raise ValueError(f"corrupt compressed block (got {n}, want {raw_len})")
    return out.tobytes()


def minmax(arr: np.ndarray):
    a = np.ascontiguousarray(arr)
    lib = get_lib()
    if lib is not None and a.size and a.dtype == np.int64:
        out = np.empty(2, dtype=np.int64)
        lib.pt_minmax_i64(a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                          a.size, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        return int(out[0]), int(out[1])
    if lib is not None and a.size and a.dtype == np.float64:
        out = np.empty(2, dtype=np.float64)
        lib.pt_minmax_f64(a.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                          a.size, out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
        return float(out[0]), float(out[1])
    if not a.size:
        return None, None
    return a.min().item(), a.max().item()


def delta_pack(arr: np.ndarray):
    """Delta+zigzag+bitpack an int64 array -> (packed bytes, width, base)
    or None when not beneficial / unsupported."""
    a = np.ascontiguousarray(arr, dtype=np.int64)
    lib = get_lib()
    if lib is None or a.size < 2:
        return None
    base = ctypes.c_int64(0)
    i64p = a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
    width = int(lib.pt_delta_width_i64(i64p, a.size, ctypes.byref(base)))
    if width > 56 or width * (a.size - 1) // 8 + 16 >= a.nbytes:
        return None
    out = np.empty((a.size - 1) * width // 8 + 16, dtype=np.uint8)
    n = int(lib.pt_delta_pack_i64(i64p, a.size, width, _u8(out)))
    if n < 0:
        return None
    return out[:n].tobytes(), width, int(base.value)


def delta_unpack(data, width: int, base: int, n: int) -> np.ndarray:
    a = _as_bytes_arr(data)
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native codec unavailable")
    out = np.empty(n, dtype=np.int64)
    r = int(lib.pt_delta_unpack_i64(
        _u8(a), a.size, width, base, n,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))))
    if r != n:
        raise ValueError("corrupt delta-packed block")
    return out


def rle_encode(arr: np.ndarray):
    """RLE an int64 array -> (values, runs) or None when not beneficial."""
    a = np.ascontiguousarray(arr, dtype=np.int64)
    lib = get_lib()
    if lib is None or a.size == 0:
        return None
    max_runs = max(1, a.size // 4)  # only worth it if it compresses 2x+
    values = np.empty(max_runs, dtype=np.int64)
    runs = np.empty(max_runs, dtype=np.int64)
    i64p = ctypes.POINTER(ctypes.c_int64)
    nr = int(lib.pt_rle_encode_i64(
        a.ctypes.data_as(i64p), a.size,
        values.ctypes.data_as(i64p), runs.ctypes.data_as(i64p), max_runs))
    if nr < 0:
        return None
    return values[:nr].copy(), runs[:nr].copy()


def rle_decode(values: np.ndarray, runs: np.ndarray, n: int) -> np.ndarray:
    lib = get_lib()
    v = np.ascontiguousarray(values, dtype=np.int64)
    r = np.ascontiguousarray(runs, dtype=np.int64)
    if lib is None:
        return np.repeat(v, r)
    out = np.empty(n, dtype=np.int64)
    i64p = ctypes.POINTER(ctypes.c_int64)
    got = int(lib.pt_rle_decode_i64(
        v.ctypes.data_as(i64p), r.ctypes.data_as(i64p), len(v),
        out.ctypes.data_as(i64p), n))
    if got != n:
        raise ValueError("corrupt RLE block")
    return out


def dict_encode(values: np.ndarray):
    """Dictionary-encode a host string array natively.

    Returns (codes int32[n], uniques str[k]) with codes in lexicographic
    order (same contract as batch.encode_strings), or None if the native
    library is unavailable.
    """
    lib = get_lib()
    if lib is None:
        return None
    strs = np.asarray(values, dtype=object).astype(str)
    n = len(strs)
    if n == 0:
        return np.empty(0, np.int32), np.empty(0, object)
    encoded = [s.encode("utf-8", "surrogatepass") for s in strs.tolist()]
    lens = np.fromiter(map(len, encoded), count=n, dtype=np.int64)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    data = np.frombuffer(b"".join(encoded), dtype=np.uint8)
    codes = np.empty(n, dtype=np.int32)
    uniq_idx = np.empty(n, dtype=np.int64)
    i64p = ctypes.POINTER(ctypes.c_int64)
    k = int(lib.pt_dict_encode(
        _u8(data), offsets.ctypes.data_as(i64p), n,
        codes.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        uniq_idx.ctypes.data_as(i64p), n))
    if k < 0:
        return None
    uniques = strs[uniq_idx[:k]]
    order = np.argsort(uniques)          # lexicographic code order
    remap = np.empty(k, dtype=np.int32)
    remap[order] = np.arange(k, dtype=np.int32)
    return remap[codes], uniques[order]


def gather(arr: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """Row gather of a fixed-width 1-D column by int64 indices (shard
    reader's row-group selection path)."""
    a = np.ascontiguousarray(arr)
    i = np.ascontiguousarray(idx, dtype=np.int64)
    lib = get_lib()
    if lib is None:
        return a[i]
    out = np.empty(i.size, dtype=a.dtype)
    lib.pt_gather(_u8(a.view(np.uint8).reshape(-1)), a.dtype.itemsize,
                  i.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), i.size,
                  _u8(out.view(np.uint8).reshape(-1)))
    return out


def sel_to_idx(mask: np.ndarray) -> np.ndarray:
    m = np.ascontiguousarray(mask, dtype=np.uint8)
    lib = get_lib()
    if lib is None:
        return np.flatnonzero(mask).astype(np.int64)
    out = np.empty(m.size, dtype=np.int64)
    c = int(lib.pt_sel_to_idx(_u8(m), m.size,
                              out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))))
    return out[:c].copy()
