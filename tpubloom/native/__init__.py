"""ctypes loader for the native C++ hash library (builds on first import).

No pybind11 in the environment, so the boundary is plain C ABI + ctypes
(SURVEY.md §2.1 native-component obligation). Everything degrades gracefully:
``HAS_NATIVE`` is False and callers fall back to the NumPy oracle if g++ or
the build is unavailable.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading

import numpy as np
from tpubloom.utils import locks

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "bloomhash.cpp")
_FLAGS = ("-O3", "-march=native", "-shared", "-fPIC", "-std=c++17")

_lock = locks.named_lock("native.build")
_lib = None
_load_failed = False  # negative cache: never re-fork a failing compiler
HAS_NATIVE = False


def _lib_path() -> str:
    """Path of the library built from the committed source with
    ``_FLAGS`` on this CPU: its name carries a hash of all three, so a
    tree copied to another host (or holding an older build) never loads
    a library made from other files or, under ``-march=native``, for
    another CPU."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_FLAGS).encode())
    try:
        with open("/proc/cpuinfo", "rb") as f:
            h.update(next((ln for ln in f if ln.startswith(b"flags")), b""))
    except OSError:
        pass
    return os.path.join(_HERE, f"libbloomhash-{h.hexdigest()[:16]}.so")


def _build(path: str) -> bool:
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", *_FLAGS, _SRC, "-o", tmp],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, path)  # concurrent builders never tear the file
    except (subprocess.SubprocessError, OSError):
        return False
    for stale in glob.glob(os.path.join(_HERE, "libbloomhash*.so")):
        if stale != path:
            try:
                os.remove(stale)
            except OSError:
                pass
    return True


def _load():
    global _lib, HAS_NATIVE, _load_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _load_failed:
            return None
        path = _lib_path()
        if not os.path.exists(path) and not _build(path):
            _load_failed = True
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            _load_failed = True
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i32p = ctypes.POINTER(ctypes.c_int32)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        lib.bh_murmur3_batch.argtypes = [u8p, i32p, ctypes.c_int64, ctypes.c_int32, ctypes.c_uint32, u32p]
        lib.bh_fnv1a_batch.argtypes = [u8p, i32p, ctypes.c_int64, ctypes.c_int32, u32p]
        lib.bh_positions.argtypes = [u8p, i32p, ctypes.c_int64, ctypes.c_int32, ctypes.c_uint64, ctypes.c_int32, ctypes.c_uint32, u64p]
        lib.bh_insert.argtypes = [u32p, u64p, ctypes.c_int64]
        lib.bh_query.argtypes = [u32p, u64p, ctypes.c_int64, ctypes.c_int32, u8p]
        lib.bh_hash_insert.argtypes = [u32p, u8p, i32p, ctypes.c_int64, ctypes.c_int32, ctypes.c_uint64, ctypes.c_int32, ctypes.c_uint32]
        lib.bh_hash_query.argtypes = [u32p, u8p, i32p, ctypes.c_int64, ctypes.c_int32, ctypes.c_uint64, ctypes.c_int32, ctypes.c_uint32, u8p]
        lib.bh_blocked_insert.argtypes = [u32p, u8p, i32p, ctypes.c_int64, ctypes.c_int32, ctypes.c_uint64, ctypes.c_int32, ctypes.c_int32, ctypes.c_uint32, ctypes.c_int32]
        lib.bh_blocked_query.argtypes = [u32p, u8p, i32p, ctypes.c_int64, ctypes.c_int32, ctypes.c_uint64, ctypes.c_int32, ctypes.c_int32, ctypes.c_uint32, ctypes.c_int32, u8p]
        lib.bh_pack.argtypes = [u8p, i32p, ctypes.c_int64, ctypes.c_int32, u8p]
        _lib = lib
        HAS_NATIVE = True
        return lib


def _ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


def available() -> bool:
    return _load() is not None


def murmur3_batch(keys: np.ndarray, lens: np.ndarray, seed: int) -> np.ndarray:
    lib = _load()
    assert lib is not None
    keys = np.ascontiguousarray(keys, dtype=np.uint8)
    lens = np.ascontiguousarray(lens, dtype=np.int32)
    B, L = keys.shape
    out = np.empty(B, dtype=np.uint32)
    lib.bh_murmur3_batch(
        _ptr(keys, ctypes.c_uint8), _ptr(lens, ctypes.c_int32), B, L,
        ctypes.c_uint32(seed), _ptr(out, ctypes.c_uint32),
    )
    return out


def fnv1a_batch(keys: np.ndarray, lens: np.ndarray) -> np.ndarray:
    lib = _load()
    assert lib is not None
    keys = np.ascontiguousarray(keys, dtype=np.uint8)
    lens = np.ascontiguousarray(lens, dtype=np.int32)
    B, L = keys.shape
    out = np.empty(B, dtype=np.uint32)
    lib.bh_fnv1a_batch(
        _ptr(keys, ctypes.c_uint8), _ptr(lens, ctypes.c_int32), B, L,
        _ptr(out, ctypes.c_uint32),
    )
    return out


def positions_batch(keys: np.ndarray, lens: np.ndarray, *, m: int, k: int, seed: int) -> np.ndarray:
    lib = _load()
    assert lib is not None
    keys = np.ascontiguousarray(keys, dtype=np.uint8)
    lens = np.ascontiguousarray(lens, dtype=np.int32)
    B, L = keys.shape
    out = np.empty((B, k), dtype=np.uint64)
    lib.bh_positions(
        _ptr(keys, ctypes.c_uint8), _ptr(lens, ctypes.c_int32), B, L,
        ctypes.c_uint64(m), k, ctypes.c_uint32(seed), _ptr(out, ctypes.c_uint64),
    )
    return out


def hash_insert(words: np.ndarray, keys: np.ndarray, lens: np.ndarray, *, m: int, k: int, seed: int) -> None:
    lib = _load()
    assert lib is not None
    keys = np.ascontiguousarray(keys, dtype=np.uint8)
    lens = np.ascontiguousarray(lens, dtype=np.int32)
    B, L = keys.shape
    lib.bh_hash_insert(
        _ptr(words, ctypes.c_uint32), _ptr(keys, ctypes.c_uint8),
        _ptr(lens, ctypes.c_int32), B, L, ctypes.c_uint64(m), k,
        ctypes.c_uint32(seed),
    )


def _check_chunk_pool(block_bits: int, k: int, block_hash: str) -> None:
    """The chunk spec slices k positions out of the 96-bit (h_b, g_a, g_b)
    pool; the C++ side indexes pool[3] unchecked, so validate here exactly
    like cpu_ref.blocked_positions_np / FilterConfig do."""
    if block_hash == "chunk":
        nb = (block_bits - 1).bit_length()
        if k * nb > 96:
            raise ValueError(
                f"block_hash='chunk' needs k*log2(block_bits) <= 96 "
                f"(k={k}, {nb} bits/position) — use 'ap'"
            )


def blocked_insert(words: np.ndarray, keys: np.ndarray, lens: np.ndarray, *, n_blocks: int, block_bits: int, k: int, seed: int, block_hash: str = "ap") -> None:
    """Fused blocked-spec insert into ``uint32[n_blocks, W]`` (in place)."""
    _check_chunk_pool(block_bits, k, block_hash)
    lib = _load()
    assert lib is not None
    keys = np.ascontiguousarray(keys, dtype=np.uint8)
    lens = np.ascontiguousarray(lens, dtype=np.int32)
    B, L = keys.shape
    lib.bh_blocked_insert(
        _ptr(words, ctypes.c_uint32), _ptr(keys, ctypes.c_uint8),
        _ptr(lens, ctypes.c_int32), B, L, ctypes.c_uint64(n_blocks),
        block_bits, k, ctypes.c_uint32(seed), int(block_hash == "chunk"),
    )


def blocked_query(words: np.ndarray, keys: np.ndarray, lens: np.ndarray, *, n_blocks: int, block_bits: int, k: int, seed: int, block_hash: str = "ap") -> np.ndarray:
    _check_chunk_pool(block_bits, k, block_hash)
    lib = _load()
    assert lib is not None
    keys = np.ascontiguousarray(keys, dtype=np.uint8)
    lens = np.ascontiguousarray(lens, dtype=np.int32)
    B, L = keys.shape
    out = np.empty(B, dtype=np.uint8)
    lib.bh_blocked_query(
        _ptr(words, ctypes.c_uint32), _ptr(keys, ctypes.c_uint8),
        _ptr(lens, ctypes.c_int32), B, L, ctypes.c_uint64(n_blocks),
        block_bits, k, ctypes.c_uint32(seed), int(block_hash == "chunk"),
        _ptr(out, ctypes.c_uint8),
    )
    return out


def hash_query(words: np.ndarray, keys: np.ndarray, lens: np.ndarray, *, m: int, k: int, seed: int) -> np.ndarray:
    lib = _load()
    assert lib is not None
    keys = np.ascontiguousarray(keys, dtype=np.uint8)
    lens = np.ascontiguousarray(lens, dtype=np.int32)
    B, L = keys.shape
    out = np.empty(B, dtype=np.uint8)
    lib.bh_hash_query(
        _ptr(words, ctypes.c_uint32), _ptr(keys, ctypes.c_uint8),
        _ptr(lens, ctypes.c_int32), B, L, ctypes.c_uint64(m), k,
        ctypes.c_uint32(seed), _ptr(out, ctypes.c_uint8),
    )
    return out


def pack_joined(joined: bytes, lens: np.ndarray, key_len: int) -> np.ndarray:
    """Scatter a concatenated key buffer into a zero-padded
    ``uint8[B, key_len]`` matrix (the C++ ingest hot loop)."""
    lib = _load()
    assert lib is not None
    lens = np.ascontiguousarray(lens, dtype=np.int32)
    B = lens.shape[0]
    if B:
        if int(lens.min()) < 0 or int(lens.max()) > key_len:
            raise ValueError(
                f"lens must be in [0, key_len={key_len}]; "
                f"got [{int(lens.min())}, {int(lens.max())}]"
            )
        if int(lens.sum()) != len(joined):
            raise ValueError(
                f"joined buffer is {len(joined)} bytes but lens sum to "
                f"{int(lens.sum())}"
            )
    out = np.zeros((B, key_len), dtype=np.uint8)
    src = np.frombuffer(joined, dtype=np.uint8)
    lib.bh_pack(
        _ptr(src, ctypes.c_uint8), _ptr(lens, ctypes.c_int32), B, key_len,
        _ptr(out, ctypes.c_uint8),
    )
    return out
