"""ctypes bridge to the native tile packer (src/native/tile_pack.cc).

The shared library is compiled on demand with g++ and loaded via ctypes
— no pybind11 needed. It is cached beside the source under a name that
carries the source's content hash, so what is loaded was always built
from ``tile_pack.cc`` as it stands (a stale library left by another
tree has another name and is never picked up). :func:`pack_tile`
dispatches to the native kernel when available and otherwise to
:func:`pack_tile_py`, a numpy implementation with identical semantics
(the parity test compares them element-wise); :func:`packer_name` says
which one a run uses.

Reference: src/MS/data.cpp:522-664 (loadData hot loop).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import warnings

import numpy as np

C_M_S = 299792458.0

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "src", "native",
    "tile_pack.cc")
_lib = None
_lib_tried = False


def _build_lib() -> str | None:
    if not os.path.exists(_SRC):
        return None
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    path = os.path.join(os.path.dirname(_SRC),
                        f"libsagecal_io-{digest}.so")
    if os.path.exists(path):
        return path
    tmp = f"{path}.{os.getpid()}.tmp"   # concurrent builders: atomic land
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-o", tmp, _SRC],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, path)
        return path
    except (OSError, subprocess.SubprocessError) as e:
        warnings.warn(f"native tile packer build failed ({e}); "
                      "using the Python fallback")
        return None


def get_lib():
    """The loaded native library, or None (build failure / no source)."""
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    path = _build_lib()
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError as e:
        warnings.warn(f"native tile packer load failed ({e})")
        return None
    lib.pack_tile.restype = None
    lib.pack_tile.argtypes = [
        ctypes.POINTER(ctypes.c_double),   # vis
        ctypes.POINTER(ctypes.c_uint8),    # cflags
        ctypes.POINTER(ctypes.c_double),   # u
        ctypes.POINTER(ctypes.c_double),   # v
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_double, ctypes.c_double, ctypes.c_double,
        ctypes.c_double,
        ctypes.POINTER(ctypes.c_double),   # x8
        ctypes.POINTER(ctypes.c_uint8),    # rowflag
        ctypes.POINTER(ctypes.c_double),   # fratio
    ]
    _lib = lib
    return _lib


def packer_name() -> str:
    """Which packer :func:`pack_tile` dispatches to in this process."""
    return "native (src/native/tile_pack.cc)" if get_lib() is not None \
        else "numpy (pack_tile_py)"


def pack_tile_py(vis, cflags, u_m, v_m, nrow_total: int,
                 uvmin: float = 0.0, uvmax: float = 1e30,
                 uvtaper_m: float = 0.0, freq0: float = 0.0):
    """Pure-numpy packer with data.cpp:552-664 semantics.

    vis: [nrow, nchan, 2, 2] complex; cflags: [nrow, nchan] (nonzero =
    flagged); u_m/v_m in METERS. Returns (x8 [nrow_total, 8] f64,
    rowflag [nrow_total] u8, fratio).
    """
    vis = np.asarray(vis)
    nrow, nchan = vis.shape[:2]
    good = np.asarray(cflags) == 0                       # [nrow, nchan]
    nflag = good.sum(axis=1)                             # [nrow]
    v4 = vis.reshape(nrow, nchan, 4)
    acc = np.where(good[..., None], v4, 0.0).sum(axis=1)  # [nrow, 4] cplx
    uvd = np.sqrt(np.asarray(u_m) ** 2 + np.asarray(v_m) ** 2)
    taper = np.ones(nrow)
    if uvtaper_m > 0.0:
        taper = np.minimum(uvd * freq0 / (uvtaper_m * C_M_S), 1.0)
    rowgood = 2 * nflag > nchan
    avg = np.zeros((nrow, 4), complex)
    nz = np.maximum(nflag, 1)
    avg[rowgood] = (acc[rowgood] / nz[rowgood, None]
                    * taper[rowgood, None])
    rowflag = np.where(rowgood, 0, np.where(nflag == 0, 1, 2)) \
        .astype(np.uint8)
    rowflag = np.where((uvd < uvmin) | (uvd > uvmax), 2,
                       rowflag).astype(np.uint8)
    countgood = int(rowgood.sum())
    countbad = int((nflag == 0).sum())
    fratio = (countbad / (countgood + countbad)
              if countgood + countbad > 0 else 1.0)
    x8 = np.zeros((nrow_total, 8))
    x8[:nrow, 0::2] = avg.real
    x8[:nrow, 1::2] = avg.imag
    out_flags = np.ones(nrow_total, np.uint8)
    out_flags[:nrow] = rowflag
    return x8, out_flags, float(fratio)


def pack_tile(vis, cflags, u_m, v_m, nrow_total: int,
              uvmin: float = 0.0, uvmax: float = 1e30,
              uvtaper_m: float = 0.0, freq0: float = 0.0):
    """Native packer when available, numpy fallback otherwise."""
    lib = get_lib()
    if lib is None:
        return pack_tile_py(vis, cflags, u_m, v_m, nrow_total, uvmin,
                            uvmax, uvtaper_m, freq0)
    vis = np.asarray(vis)
    nrow, nchan = vis.shape[:2]
    vis8 = np.ascontiguousarray(
        np.stack([vis.reshape(nrow, nchan, 4).real,
                  vis.reshape(nrow, nchan, 4).imag], -1), dtype=np.float64)
    cf = np.ascontiguousarray(np.asarray(cflags) != 0, dtype=np.uint8)
    u_m = np.ascontiguousarray(u_m, dtype=np.float64)
    v_m = np.ascontiguousarray(v_m, dtype=np.float64)
    x8 = np.zeros((nrow_total, 8))
    rowflag = np.zeros(nrow_total, np.uint8)
    fratio = ctypes.c_double(0.0)
    dptr = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_double))
    bptr = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    lib.pack_tile(dptr(vis8), bptr(cf), dptr(u_m), dptr(v_m),
                  nrow, nchan, nrow_total, uvmin, uvmax, uvtaper_m,
                  freq0, dptr(x8), bptr(rowflag),
                  ctypes.byref(fratio))
    return x8, rowflag, float(fratio.value)


if __name__ == "__main__":
    import sys
    if "--build" in sys.argv:
        path = _build_lib()
        print(f"native kernel: {path or 'unavailable (g++ missing?)'}")
