"""ctypes loader for the fused quantization kernels (quant.cc).

Same build discipline as ``_native/store.py`` (``_native/build.py``):
compile the bundled source on first use when the .so is missing or was
built from another source, force-rebuild when dlopen rejects a binary
from a foreign toolchain.  ``lib()`` returns None when no
compiler is available — the numpy reference path in
``util/collective/quantize.py`` is always there as the fallback, and
both produce bit-identical wire bytes (quant.cc builds with
-ffp-contract=off for exactly that reason).
"""

from __future__ import annotations

import ctypes
import os
import threading

from ray_tpu._native.build import ensure_built

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "quant.cc")
_SO = os.path.join(_DIR, "libquant.so")

_lib = None
_lib_lock = threading.Lock()


def _build(force: bool = False) -> None:
    # -march=native is safe here: the .so is always compiled on the
    # host that dlopens it (build-at-first-use, foreign binaries are
    # rebuilt), and it unlocks the wide-SIMD quant loops.  Retry
    # without it for exotic toolchains that reject the flag.
    base = ["g++", "-O3", "-ffp-contract=off", "-fno-math-errno",
            "-fPIC", "-shared", "-std=c++17"]
    ensure_built(
        _SRC, _SO, [base[:1] + ["-march=native"] + base[1:], base],
        force=force,
    )


def _bind(lib) -> None:
    i64, fp, i8p, u16p, u32p = (
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_int8),
        ctypes.POINTER(ctypes.c_uint16),
        ctypes.POINTER(ctypes.c_uint32),
    )
    lib.rt_quant_int8_encode.restype = ctypes.c_int
    lib.rt_quant_int8_encode.argtypes = [fp, i64, i64, fp, i8p]
    lib.rt_quant_int8_decode.restype = None
    lib.rt_quant_int8_decode.argtypes = [fp, i8p, i64, i64, fp]
    lib.rt_quant_int8_decode_add.restype = None
    lib.rt_quant_int8_decode_add.argtypes = [fp, i8p, i64, i64, fp]
    lib.rt_quant_bf16_encode.restype = ctypes.c_int
    lib.rt_quant_bf16_encode.argtypes = [u32p, i64, u16p]
    lib.rt_quant_bf16_decode.restype = None
    lib.rt_quant_bf16_decode.argtypes = [u16p, i64, u32p]
    lib.rt_quant_bf16_decode_add.restype = None
    lib.rt_quant_bf16_decode_add.argtypes = [u16p, i64, fp]


def lib():
    """The loaded kernel library, or None when it cannot be built
    (no compiler in the image): callers fall back to numpy."""
    global _lib
    if _lib is False:
        return None
    if _lib is None:
        with _lib_lock:
            if _lib is None:
                try:
                    _build()
                    try:
                        loaded = ctypes.CDLL(_SO)
                    except OSError:
                        _build(force=True)  # foreign-toolchain binary
                        loaded = ctypes.CDLL(_SO)
                    _bind(loaded)
                    _lib = loaded
                except Exception:
                    _lib = False
                    return None
    return _lib or None
