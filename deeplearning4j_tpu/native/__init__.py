"""ctypes bindings for the native host data-path library.

The device compute path is XLA; the HOST pipeline stages that the
reference implements natively (DataVec parsing, ND4J buffer fill —
SURVEY L0/L2) are native here too: native/dl4j_tpu_native.cpp provides
fast CSV->f32 parsing and fused u8->f32 (de)normalization/layout ops.

The library is compiled on demand with g++ (no pybind11 in this image;
plain C ABI + ctypes) and cached beside the source, under a name that
carries the hash of the source and of the CPU it was built for. Every
entry point has a NumPy fallback, so the package works — just slower —
without a toolchain. `available()` reports which path is active.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading
from typing import Optional

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC_DIR = os.path.join(_REPO_ROOT, "native")
_ABI_VERSION = 3

_lock = threading.Lock()
_lib = None
_tried = False


def _artifact_key(src: str, build: str) -> str:
    """What a built library is valid for: the source and build script
    it was made from and the CPU it was made on. build.sh compiles
    with -march=native, and the library is git-ignored but copied
    along with the tree, so an artifact may arrive on a machine whose
    CPU lacks the instructions it uses. The key is in the file name:
    anything it does not match is simply not found, and built here."""
    h = hashlib.sha256()
    for path in (src, build):
        with open(path, "rb") as f:
            h.update(f.read())
    h.update(platform.machine().encode())
    try:
        with open("/proc/cpuinfo") as f:
            h.update(next((line for line in f
                           if line.startswith(("flags", "Features"))),
                          "").encode())
    except OSError:
        pass
    return h.hexdigest()[:12]


def _build_and_load() -> Optional[ctypes.CDLL]:
    src = os.path.join(_SRC_DIR, "dl4j_tpu_native.cpp")
    build = os.path.join(_SRC_DIR, "build.sh")
    if not os.path.exists(src):
        return None
    out = os.path.join(
        _SRC_DIR, f"libdl4j_tpu_native-{_artifact_key(src, build)}.so")
    if not os.path.exists(out):
        # build beside the target and rename: a test worker that loads
        # while another builds never sees a half-written library
        tmp = f"{out}.{os.getpid()}.tmp"
        try:
            subprocess.run(["sh", build, tmp], check=True,
                           capture_output=True, timeout=120)
            os.replace(tmp, out)
        except (OSError, subprocess.SubprocessError):
            return None
    try:
        return _bind(ctypes.CDLL(out))
    except (OSError, AttributeError):
        return None


def _bind(lib: ctypes.CDLL) -> Optional[ctypes.CDLL]:
    if lib.dl4j_native_abi_version() != _ABI_VERSION:
        # source and bindings disagree: the NumPy paths serve
        raise AttributeError(
            f"native ABI {lib.dl4j_native_abi_version()} != "
            f"{_ABI_VERSION}")
    lib.dl4j_parse_csv_f32.restype = ctypes.c_int
    lib.dl4j_parse_csv_f32.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_char,
        ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64)]
    lib.dl4j_u8_to_f32.restype = None
    lib.dl4j_u8_to_f32.argtypes = [
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_float),
        ctypes.c_int64, ctypes.c_float, ctypes.c_float]
    lib.dl4j_chw_u8_to_hwc_f32.restype = None
    lib.dl4j_chw_u8_to_hwc_f32.argtypes = [
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_float),
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_float, ctypes.c_float]
    i32p = ctypes.POINTER(ctypes.c_int32)
    f32p = ctypes.POINTER(ctypes.c_float)
    for fn in (lib.dl4j_w2v_sg_pack, lib.dl4j_w2v_cbow_pack):
        fn.restype = ctypes.c_int64
        fn.argtypes = [i32p, i32p, ctypes.c_int64, ctypes.c_int64,
                       ctypes.c_int64, ctypes.c_int, ctypes.c_int,
                       f32p, i32p, ctypes.c_int64, ctypes.c_uint64,
                       i32p]
    return lib


def _get() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _tried:
        return _lib
    with _lock:
        if not _tried:
            _lib = _build_and_load()
            _tried = True
    return _lib


def available() -> bool:
    return _get() is not None


def parse_csv_f32(text, delimiter: str = ",") -> np.ndarray:
    """Parse an all-numeric delimited text into a float32 [N, C] array.
    '#'-comment and blank lines are skipped. Raises ValueError on ragged
    or non-numeric input (both paths)."""
    if isinstance(text, str):
        text = text.encode()
    lib = _get()
    if lib is None:
        return _parse_csv_fallback(text, delimiter)
    # capacity: numbers can't be denser than 2 bytes each ("1,1,...")
    max_vals = max(len(text) // 2 + 16, 16)
    out = np.empty(max_vals, np.float32)
    n_rows = ctypes.c_int64()
    n_cols = ctypes.c_int64()
    rc = lib.dl4j_parse_csv_f32(
        text, len(text), delimiter.encode()[0:1] or b",",
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), max_vals,
        ctypes.byref(n_rows), ctypes.byref(n_cols))
    if rc == -2:
        raise ValueError("ragged rows in CSV input")
    if rc == -3:
        raise ValueError("non-numeric value in CSV input")
    if rc != 0:
        raise ValueError(f"native CSV parse failed (code {rc})")
    r, c = n_rows.value, n_cols.value
    return out[:r * c].reshape(r, c).copy()


def _parse_csv_fallback(data: bytes, delimiter: str) -> np.ndarray:
    rows = []
    ncols = None
    for line in data.decode().splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        vals = [float(v) for v in line.split(delimiter)]
        if ncols is None:
            ncols = len(vals)
        elif len(vals) != ncols:
            raise ValueError("ragged rows in CSV input")
        rows.append(vals)
    if not rows:
        return np.zeros((0, 0), np.float32)
    return np.asarray(rows, np.float32)


def u8_to_f32(src: np.ndarray, scale: float = 1.0 / 255.0,
              shift: float = 0.0) -> np.ndarray:
    """u8 -> f32 affine normalize, single fused pass."""
    src = np.ascontiguousarray(src, np.uint8)
    lib = _get()
    if lib is None:
        return src.astype(np.float32) * scale + shift
    dst = np.empty(src.shape, np.float32)
    lib.dl4j_u8_to_f32(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        src.size, scale, shift)
    return dst


def chw_u8_to_hwc_f32(src: np.ndarray, scale: float = 1.0 / 255.0,
                      shift: float = 0.0) -> np.ndarray:
    """[N, C, H, W] u8 -> [N, H, W, C] f32 with fused normalization
    (the CIFAR-pickle layout fix-up)."""
    src = np.ascontiguousarray(src, np.uint8)
    if src.ndim != 4:
        raise ValueError(f"expected [N, C, H, W], got shape {src.shape}")
    n, c, h, w = src.shape
    lib = _get()
    if lib is None:
        return (np.transpose(src, (0, 2, 3, 1)).astype(np.float32)
                * scale + shift)
    dst = np.empty((n, h, w, c), np.float32)
    lib.dl4j_chw_u8_to_hwc_f32(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        n, c, h, w, scale, shift)
    return dst


def _w2v_pack(fn_name, corpus, sid, window, k_neg, alias_prob,
              alias_idx, seed, p0=0, p1=None):
    lib = _get()
    if lib is None:
        return None
    corpus = np.ascontiguousarray(corpus, np.int32)
    sid = np.ascontiguousarray(sid, np.int32)
    i32p = ctypes.POINTER(ctypes.c_int32)
    f32p = ctypes.POINTER(ctypes.c_float)
    if k_neg > 0:
        alias_prob = np.ascontiguousarray(alias_prob, np.float32)
        alias_idx = np.ascontiguousarray(alias_idx, np.int32)
        vocab = alias_prob.size
        ap = alias_prob.ctypes.data_as(f32p)
        ai = alias_idx.ctypes.data_as(i32p)
    else:
        vocab = 0
        ap = f32p()
        ai = i32p()
    fn = getattr(lib, fn_name)
    n = corpus.size
    if p1 is None:
        p1 = n
    count = fn(corpus.ctypes.data_as(i32p), sid.ctypes.data_as(i32p),
               n, p0, p1, window, k_neg, ap, ai, vocab, seed, i32p())
    cols = ((2 + k_neg) if fn_name == "dl4j_w2v_sg_pack"
            else (2 * window + 1 + k_neg))
    out = np.empty((count, cols), np.int32)
    if count:
        fn(corpus.ctypes.data_as(i32p), sid.ctypes.data_as(i32p),
           n, p0, p1, window, k_neg, ap, ai, vocab, seed,
           out.ctypes.data_as(i32p))
    return out


def w2v_sg_pack(corpus, sid, window, k_neg, alias_prob, alias_idx,
                seed, p0=0, p1=None) -> Optional[np.ndarray]:
    """Skip-gram epoch rows [center, positive, K negatives] in corpus
    order (reduced-window + alias negative sampling fused in one native
    pass); centers restricted to positions [p0, p1) so chunked callers
    can overlap windows. Returns None when the native library is
    unavailable."""
    return _w2v_pack("dl4j_w2v_sg_pack", corpus, sid, window, k_neg,
                     alias_prob, alias_idx, seed, p0, p1)


def w2v_cbow_pack(corpus, sid, window, k_neg, alias_prob, alias_idx,
                  seed, p0=0, p1=None) -> Optional[np.ndarray]:
    """CBOW epoch rows [2W context (-1 pad), center, K negatives];
    centers restricted to [p0, p1)."""
    return _w2v_pack("dl4j_w2v_cbow_pack", corpus, sid, window, k_neg,
                     alias_prob, alias_idx, seed, p0, p1)
