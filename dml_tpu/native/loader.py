"""ctypes wrapper for the native batch image loader (native/dataloader.cpp).

Builds the shared library with g++ on first use, beside the source,
under a name keyed by a hash of the source and the build command:
`libdmlloader-<key>.so`. A library whose key does not match — built
from other source, with other flags, or copied in from another
checkout — is never loaded; it is rebuilt here. The flags name no
CPU (`-march=native` is gone), so a library that travels with a copy
of the tree still runs on the machine it lands on. The loader is the
fast path of `models.preprocess.load_images`: libjpeg DCT-scaled
decode + C++ bilinear resize + thread pool, producing the contiguous
NHWC uint8 batch the engine ships to HBM. Where a compiler or libjpeg
is unavailable the PIL path serves (`native_available()` -> False),
after ONE warning that carries the compiler's stderr.

Set DML_NATIVE_LOADER=0 to force the PIL path.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading
from typing import List, Optional, Sequence

import numpy as np

log = logging.getLogger(__name__)

_SRC_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "native")
)
_SRC = os.path.join(_SRC_DIR, "dataloader.cpp")

_lock = threading.Lock()
_loader: Optional["NativeLoader"] = None
_failed = False


def _build_cmd(out: str) -> List[str]:
    return [
        os.environ.get("CXX", "g++"),
        "-O3", "-fPIC", "-std=c++17", "-shared",
        "-o", out, _SRC, "-ljpeg", "-lpthread",
    ]


def lib_path() -> str:
    """The library this source and build command produce: the key is a
    hash of both, so nothing else on disk can stand in for it."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update("\0".join(_build_cmd("")).encode())
    return os.path.join(_SRC_DIR, f"libdmlloader-{h.hexdigest()[:16]}.so")


def _build(lib: str) -> None:
    """Compile `lib` unless it is already there. Raises on failure
    (CalledProcessError carries the compiler's stderr)."""
    if os.path.exists(lib):
        return
    # compile to a private temp path and rename into place: concurrent
    # processes (several nodes on one host) must never observe a
    # half-written .so
    tmp = f"{lib}.tmp.{os.getpid()}"
    try:
        subprocess.run(
            _build_cmd(tmp), check=True, capture_output=True, timeout=120
        )
        os.replace(tmp, lib)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    # libraries under any other key are stale by construction
    for old in glob.glob(os.path.join(_SRC_DIR, "libdmlloader*.so")):
        if old != lib:
            os.unlink(old)


class NativeLoader:
    def __init__(self, path: str):
        self._lib = ctypes.CDLL(path)
        self._lib.dml_decode_batch.restype = ctypes.c_int
        self._lib.dml_decode_batch.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.POINTER(ctypes.c_uint8), ctypes.c_int,
            ctypes.c_char_p, ctypes.c_int,
        ]
        assert self._lib.dml_loader_version() >= 1

    def decode_batch(
        self, paths: Sequence[str], size, n_threads: int = 0
    ) -> np.ndarray:
        """JPEG files -> uint8 (N, H, W, 3). Raises RuntimeError with
        the first file's error on failure."""
        n = len(paths)
        h, w = int(size[0]), int(size[1])
        out = np.empty((n, h, w, 3), np.uint8)
        if n == 0:
            return out
        arr = (ctypes.c_char_p * n)(*[os.fsencode(p) for p in paths])
        errbuf = ctypes.create_string_buffer(512)
        rc = self._lib.dml_decode_batch(
            arr, n, h, w,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            int(n_threads), errbuf, len(errbuf),
        )
        if rc != 0:
            raise RuntimeError(
                f"native decode failed: {errbuf.value.decode(errors='replace')}"
            )
        return out


def get_loader() -> Optional[NativeLoader]:
    """The process-wide loader, built on first call; None if disabled
    or unbuildable."""
    global _loader, _failed
    if os.environ.get("DML_NATIVE_LOADER", "1") == "0":
        return None
    if _loader is not None or _failed:
        return _loader
    with _lock:
        if _loader is not None or _failed:
            return _loader
        try:
            lib = lib_path()
            _build(lib)
            _loader = NativeLoader(lib)
        except Exception as e:
            # once per process (_failed latches): the serving path
            # silently becoming PIL is a finding, not a detail
            stderr = getattr(e, "stderr", None)
            log.warning(
                "native JPEG loader unavailable (%r); image decode "
                "falls back to PIL. %s", e,
                stderr.decode(errors="replace") if stderr else "",
            )
            _failed = True
    return _loader


def native_available() -> bool:
    return get_loader() is not None
