"""ctypes binding + on-demand build for the native IO library (io.cc).

Build strategy: compile ``io.cc`` with the system ``g++`` into
``{package}/native/_build/libtfdl_io-{hash of the source}.so`` the first time it is
needed. The name carries the content of what was compiled, so a library left in
``_build/`` by other source — a copied tree does not promise mtimes — can never
pass for fresh: it is simply not the file looked for. Concurrent processes may
each compile, but each writes to a pid-unique temp file and installs with an
atomic ``os.replace``, so the installed library is never torn. Falls back to
PIL decoding when no compiler or libpng is
available — same results, just slower and GIL-bound.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional, Sequence

import numpy as np

logger = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "io.cc")
_BUILD_DIR = os.path.join(_HERE, "_build")


def _library_path(src: str, stem: str) -> str:
    """``_build/{stem}-{sha256 of src, 16 hex}.so``: where the library built
    from exactly this source lives."""
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"{stem}-{digest}.so")


_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build_library(
    src: str, target: str, variant_flags: Sequence[Sequence[str]]
) -> Optional[str]:
    """Compile ``src`` into ``target`` trying flag variants in order (pid-unique
    temp + atomic install — the shared build core for every native library in
    this package). Returns the install path, or None with a warning."""
    tmp = f"{target}.{os.getpid()}.tmp"  # pid-unique: parallel builders never collide
    base = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread", src]
    last_err: Exception | None = None
    for flags in variant_flags:
        cmd = base + list(flags) + ["-o", tmp]
        try:
            os.makedirs(os.path.dirname(target), exist_ok=True)
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
            os.replace(tmp, target)  # atomic; concurrent winners are identical
            return target
        except (
            subprocess.CalledProcessError,
            subprocess.TimeoutExpired,
            OSError,  # includes read-only package dirs (makedirs/replace)
        ) as e:
            last_err = e
    detail = getattr(last_err, "stderr", b"")
    logger.warning(
        "native build of %s failed (%s); using Python fallback. %s",
        os.path.basename(src),
        last_err,
        detail.decode()[:500] if detail else "",
    )
    return None


def _build(target: str) -> bool:
    # Prefer full PNG+JPEG support; on hosts without libjpeg fall back to a
    # PNG-only build (TFDL_NO_JPEG) so the native PNG fast path survives —
    # decode_image_batch then PIL-decodes JPEG files one at a time.
    return (
        _build_library(
            _SRC, target, [["-lpng", "-ljpeg"], ["-DTFDL_NO_JPEG", "-lpng"]]
        )
        is not None
    )


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        target = _library_path(_SRC, "libtfdl_io")
        if not os.path.exists(target) and not _build(target):
            return None
        try:
            lib = ctypes.CDLL(target)
        except OSError as e:
            logger.warning("native IO load failed (%s); using PIL fallback", e)
            return None
        batch_sig = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_float),
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_int,
        ]
        lib.tfdl_decode_png_batch.restype = ctypes.c_int
        lib.tfdl_decode_png_batch.argtypes = batch_sig
        lib.tfdl_decode_image_batch.restype = ctypes.c_int
        lib.tfdl_decode_image_batch.argtypes = batch_sig
        lib.tfdl_version.restype = ctypes.c_char_p
        _lib = lib
        return _lib


def native_available() -> bool:
    """True when the C++ decoder built and loaded on this machine."""
    return _load() is not None


def _decode_pil(paths: Sequence[str], h: int, w: int, channels: int) -> np.ndarray:
    from PIL import Image

    out = np.empty((len(paths), h, w, channels), np.float32)
    for i, p in enumerate(paths):
        with Image.open(p) as im:
            arr = (
                np.asarray(im.convert("L" if channels == 1 else "RGB"), np.float32)
                / 255.0
            )
        if arr.shape[:2] != (h, w):
            raise ValueError(f"{p}: expected {h}x{w}, got {arr.shape[:2]}")
        out[i] = arr[:, :, None] if channels == 1 else arr
    return out


def _decode_pil_resize(
    paths: Sequence[str], h: int, w: int, channels: int
) -> np.ndarray:
    from PIL import Image

    out = np.empty((len(paths), h, w, channels), np.float32)
    for i, p in enumerate(paths):
        with Image.open(p) as im:
            im = im.convert("L" if channels == 1 else "RGB")
            if im.size != (w, h):
                im = im.resize((w, h), Image.BILINEAR)
            arr = np.asarray(im, np.float32) / 255.0
        out[i] = arr[:, :, None] if channels == 1 else arr
    return out


# default decode parallelism for IN-MEMORY BLOBS: the native decoders spawn
# fresh threads per CALL, so one-thread-per-core on a small blob batch (the
# streaming record path's batch-at-a-time shape) spends more wall time
# creating/joining threads than decoding — measured 2.4x SLOWER than a
# 4-thread decode for 64 blobs on a 24-core host, the end2end_decode
# regression RECORDS_BENCH.json recorded. Scale threads with the work
# instead: at least _MIN_ITEMS_PER_THREAD blobs each, capped by the core
# count. The PATH-based decoders keep the one-thread-per-core default: their
# per-item cost (full-size on-disk images + filesystem IO) dwarfs the spawn
# overhead this heuristic amortizes, and only the blob path was measured.
_MIN_ITEMS_PER_THREAD = 16


def _default_threads(n_items: int) -> int:
    return max(1, min(os.cpu_count() or 1, n_items // _MIN_ITEMS_PER_THREAD))


def _run_batch(fn, paths, out, h, w, channels, n_threads, what):
    c_paths = (ctypes.c_char_p * len(paths))(*[os.fsencode(p) for p in paths])
    rc = fn(
        c_paths,
        len(paths),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        h,
        w,
        channels,
        n_threads,
    )
    if rc != 0:
        raise ValueError(f"native {what} decode failed for {paths[rc - 1]!r}")
    return out


def decode_png_batch(
    paths: Sequence[str],
    h: int,
    w: int,
    channels: int = 1,
    n_threads: Optional[int] = None,
) -> np.ndarray:
    """Decode fixed-size PNGs into [N, h, w, channels] float32 in [0, 1].

    Uses the native multithreaded decoder when available (GIL-free, one thread per
    core by default), else PIL. Files must already be h x w — the TGS-salt
    contract; use ``decode_image_batch`` for variable-size/JPEG sources.
    """
    paths = list(paths)
    if not paths:
        return np.empty((0, h, w, channels), np.float32)
    lib = _load()
    if lib is None:
        return _decode_pil(paths, h, w, channels)
    if n_threads is None:
        n_threads = min(len(paths), os.cpu_count() or 1)
    out = np.empty((len(paths), h, w, channels), np.float32)
    return _run_batch(
        lib.tfdl_decode_png_batch, paths, out, h, w, channels, n_threads, "PNG"
    )


def decode_image_batch(
    paths: Sequence[str],
    h: int,
    w: int,
    channels: int = 3,
    n_threads: Optional[int] = None,
) -> np.ndarray:
    """Decode PNG/JPEG files of ANY size into [N, h, w, channels] float32 in
    [0, 1], antialias-bilinearly resized — the ImageNet-class decode path.

    Native multithreaded when available, else PIL. Files the native decoder
    cannot handle (exotic encodings; JPEGs on a PNG-only build) fall back to PIL
    ONE AT A TIME instead of failing the batch — real-world datasets always
    contain a few oddballs."""
    paths = list(paths)
    if not paths:
        return np.empty((0, h, w, channels), np.float32)
    lib = _load()
    if lib is None:
        return _decode_pil_resize(paths, h, w, channels)
    if n_threads is None:
        n_threads = min(len(paths), os.cpu_count() or 1)
    out = np.empty((len(paths), h, w, channels), np.float32)
    start = 0
    while start < len(paths):
        chunk = paths[start:]
        c_paths = (ctypes.c_char_p * len(chunk))(*[os.fsencode(p) for p in chunk])
        rc = lib.tfdl_decode_image_batch(
            c_paths,
            len(chunk),
            out[start:].ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            h,
            w,
            channels,
            n_threads,
        )
        if rc == 0:
            break
        bad = start + rc - 1  # absolute index of the first failing file
        out[bad] = _decode_pil_resize([paths[bad]], h, w, channels)[0]
        start = bad + 1
    return out


def _decode_pil_blobs(
    blobs: Sequence[bytes], h: int, w: int, channels: int
) -> np.ndarray:
    import io as io_lib

    from PIL import Image

    out = np.empty((len(blobs), h, w, channels), np.float32)
    for i, blob in enumerate(blobs):
        with Image.open(io_lib.BytesIO(blob)) as im:
            im = im.convert("L" if channels == 1 else "RGB")
            if im.size != (w, h):
                im = im.resize((w, h), Image.BILINEAR)
            arr = np.asarray(im, np.float32) / 255.0
        out[i] = arr[:, :, None] if channels == 1 else arr
    return out


def decode_image_blobs(
    blobs: Sequence[bytes],
    shape,
    channels: int = 3,
    n_threads: Optional[int] = None,
) -> np.ndarray:
    """Decode in-memory PNG/JPEG byte strings (record payloads) into
    [N, h, w, channels] float32 in [0, 1], antialias-resized — the blob twin of
    ``decode_image_batch``. Native multithreaded when available (fmemopen'd
    streams, GIL-free), else PIL; native per-blob failures fall back to PIL one
    at a time under the same minimal-failing-index contract."""
    h, w = shape
    blobs = list(blobs)
    if not blobs:
        return np.empty((0, h, w, channels), np.float32)
    lib = _load()
    if lib is None or not hasattr(lib, "tfdl_decode_image_blob_batch"):
        return _decode_pil_blobs(blobs, h, w, channels)
    if n_threads is None:
        n_threads = _default_threads(len(blobs))
    out = np.empty((len(blobs), h, w, channels), np.float32)
    bufs = [np.frombuffer(b, np.uint8) for b in blobs]  # keep refs alive
    start = 0
    while start < len(blobs):
        chunk = bufs[start:]
        ptrs = (ctypes.POINTER(ctypes.c_ubyte) * len(chunk))(
            *[b.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)) for b in chunk]
        )
        sizes = (ctypes.c_ulonglong * len(chunk))(*[b.size for b in chunk])
        rc = lib.tfdl_decode_image_blob_batch(
            ptrs,
            sizes,
            len(chunk),
            out[start:].ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            h,
            w,
            channels,
            n_threads,
        )
        if rc == 0:
            break
        bad = start + rc - 1
        out[bad] = _decode_pil_blobs([blobs[bad]], h, w, channels)[0]
        start = bad + 1
    return out


_extra_lock = threading.Lock()
_extra_libs: dict = {}


def load_extra_library(
    src_name: str, lib_stem: str, *, link_png: bool = False
) -> Optional[ctypes.CDLL]:
    """Build-and-load another single-source native library from this package
    directory via the shared build core (source-hash-named, atomic install);
    None when no toolchain is available."""
    with _extra_lock:
        if src_name in _extra_libs:
            return _extra_libs[src_name]
        src = os.path.join(_HERE, src_name)
        target = _library_path(src, lib_stem)
        lib = None
        try:
            if os.path.exists(target) or _build_library(
                src, target, [["-lpng"] if link_png else []]
            ):
                lib = ctypes.CDLL(target)
        except OSError as e:
            logger.warning("native %s load failed (%s); using Python fallback",
                           src_name, e)
            lib = None
        _extra_libs[src_name] = lib
        return lib
