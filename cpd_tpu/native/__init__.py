"""Native host library: JIT-compiled C++ eXmY numerics via ctypes.

The reference compiles its native layer at import time with
`torch.utils.cpp_extension.load` (reference:
CPDtorch/quant/quant_function.py:10-17) and degrades to None on CPU-only
environments (:18-19).  Same contract here, minus the torch dependency:
`g++ -O2 -shared -fPIC` into a cached .so beside the source, ctypes
bindings, and degradation to the numpy paths (`available() == False`)
when no compiler exists — `load()` says on stderr, once, which of the
two is in use.  The .so is git-ignored and named by a hash of its two
sources, so a binary copied along with a checkout is only ever loaded
by the sources it was built from.

Public surface (numpy in/out, pure — no in-place mutation):
  * `float_quantize_np(x, exp, man)`   — elementwise eXmY cast
  * `quant_gemm_np(a, b, exp, man)`    — Kahan eXmY-accumulator GEMM
  * `ordered_sum_np(stacked, exp, man, kahan)` — rank-ordered quantized
    reduction over axis 0
These are bit-identical to the jnp implementations (tests/test_native.py
cross-checks all three) and serve host-side data-path quantization plus
independent oracles.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import sys
import tempfile
from typing import Optional

import numpy as np

__all__ = ["available", "float_quantize_np", "quant_gemm_np",
           "ordered_sum_np", "fused_augment_np", "build", "load"]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRCS = (os.path.join(_HERE, "quant_native.cpp"),
         os.path.join(_HERE, "augment_native.cpp"))
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False


def _so_path() -> str:
    h = hashlib.sha256()
    for src in _SRCS:
        with open(src, "rb") as f:
            h.update(f.read())
    return os.path.join(_HERE, f"_cpd_native.{h.hexdigest()[:12]}.so")


def build(force: bool = False) -> Optional[str]:
    """Compile the shared library for the current sources if absent;
    return its path or None when no toolchain is available."""
    so = _so_path()
    if not force and os.path.exists(so):
        return so
    for cxx in (os.environ.get("CXX"), "g++", "c++", "clang++"):
        if not cxx:
            continue
        # build into a temp file then rename: atomic under concurrent
        # imports (e.g. pytest-xdist workers racing).
        fd, tmp = tempfile.mkstemp(suffix=".so.tmp", dir=_HERE)
        os.close(fd)
        cmd = [cxx, "-O2", "-shared", "-fPIC", "-pthread", "-o", tmp,
               *_SRCS]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
            os.replace(tmp, so)
        except (OSError, subprocess.SubprocessError):
            if os.path.exists(tmp):
                os.unlink(tmp)
            continue
        for stale in glob.glob(os.path.join(_HERE, "_cpd_native*.so")):
            if stale != so:
                os.unlink(stale)
        return so
    return None


def load() -> Optional[ctypes.CDLL]:
    """Build-if-needed and dlopen; cached.  None when unavailable."""
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    so = build()
    if so is None:
        print("cpd_tpu.native: no C++ compiler found — numpy paths in use",
              file=sys.stderr)
        return None
    print(f"cpd_tpu.native: C++ library {os.path.basename(so)} in use",
          file=sys.stderr)
    lib = ctypes.CDLL(so)
    i64, i32 = ctypes.c_int64, ctypes.c_int
    fptr = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
    lib.cpd_cast_one.restype = ctypes.c_float
    lib.cpd_cast_one.argtypes = [ctypes.c_float, i32, i32]
    lib.cpd_quantize.restype = None
    lib.cpd_quantize.argtypes = [fptr, fptr, i64, i32, i32]
    lib.cpd_qgemm.restype = None
    lib.cpd_qgemm.argtypes = [fptr, fptr, fptr, i64, i64, i64, i32, i32]
    lib.cpd_ordered_sum.restype = None
    lib.cpd_ordered_sum.argtypes = [fptr, fptr, i64, i64, i32, i32, i32]
    iptr = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    bptr = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    lib.cpd_fused_augment.restype = None
    lib.cpd_fused_augment.argtypes = [
        fptr, iptr, i64, i64, i64, i64, iptr, iptr, i64, i64, bptr,
        iptr, iptr, i64, i64, fptr, i64]
    _LIB = lib
    return _LIB


def available() -> bool:
    return load() is not None


def _require() -> ctypes.CDLL:
    lib = load()
    if lib is None:
        raise NotImplementedError(
            "native quant library unavailable (no C++ compiler found); "
            "use the jnp path cpd_tpu.quant.float_quantize")
    return lib


def float_quantize_np(x: np.ndarray, exp: int, man: int) -> np.ndarray:
    """Elementwise eXmY cast on host (numpy), any shape."""
    lib = _require()
    x = np.ascontiguousarray(x, np.float32)
    out = np.empty_like(x)
    lib.cpd_quantize(x.reshape(-1), out.reshape(-1), x.size, exp, man)
    return out


def quant_gemm_np(a: np.ndarray, b: np.ndarray, exp: int, man: int
                  ) -> np.ndarray:
    """a(M,K) @ b(K,N) with the faithful Kahan eXmY accumulator."""
    lib = _require()
    a = np.ascontiguousarray(a, np.float32)
    b = np.ascontiguousarray(b, np.float32)
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise ValueError(f"expected (M,K)x(K,N), got {a.shape} x {b.shape}")
    M, K = a.shape
    N = b.shape[1]
    out = np.empty((M, N), np.float32)
    lib.cpd_qgemm(a, b, out, M, N, K, exp, man)
    return out


def ordered_sum_np(stacked: np.ndarray, exp: int, man: int,
                   kahan: bool = False) -> np.ndarray:
    """Rank-ordered quantized reduction over axis 0 of (W, ...)."""
    lib = _require()
    stacked = np.ascontiguousarray(stacked, np.float32)
    W = stacked.shape[0]
    n = stacked.size // max(W, 1)
    out = np.empty(stacked.shape[1:], np.float32)
    lib.cpd_ordered_sum(stacked.reshape(W, -1), out.reshape(-1), W, n,
                        exp, man, int(kahan))
    return out


def fused_augment_np(data: np.ndarray, indices: np.ndarray,
                     crop_y: np.ndarray, crop_x: np.ndarray,
                     oh: int, ow: int, flip: np.ndarray,
                     cut_y: Optional[np.ndarray] = None,
                     cut_x: Optional[np.ndarray] = None,
                     cut_h: int = 0, cut_w: int = 0,
                     n_threads: int = 0) -> np.ndarray:
    """Fused crop -> flip -> cutout over a padded fp32 NHWC dataset.

    `crop_*`/`flip`/`cut_*` are per-DATASET-sample pre-drawn choices
    (TransformPipeline.resample's layout); `indices` selects the batch.
    Bitwise identical to the numpy transform chain (pure copies/zeros).
    n_threads=0 -> hardware concurrency."""
    lib = _require()
    data = np.ascontiguousarray(data, np.float32)
    n_total, ih, iw, ch = data.shape
    indices = np.ascontiguousarray(indices, np.int64)
    b = indices.size
    if not cut_h:
        # unused by the kernel when cut_h == 0; a 1-element placeholder
        # satisfies the ctypes signature without an n_total-sized alloc
        cut_y = cut_x = np.zeros(1, np.int64)
    out = np.empty((b, oh, ow, ch), np.float32)
    lib.cpd_fused_augment(
        data.reshape(-1), indices, b, ih, iw, ch,
        np.ascontiguousarray(crop_y, np.int64),
        np.ascontiguousarray(crop_x, np.int64), oh, ow,
        np.ascontiguousarray(flip, np.uint8),
        np.ascontiguousarray(cut_y, np.int64),
        np.ascontiguousarray(cut_x, np.int64),
        cut_h, cut_w, out.reshape(-1), n_threads)
    return out
