"""TFRecord streaming: native threaded reader + writer + classification stream.

The reference's input runtime was tf.data's C++ pipeline reading from disk
(SURVEY §2.2 — inherited native machinery); this module is the first-party
equivalent for record-sharded datasets (the standard on-disk form of
ImageNet-scale corpora, where per-file ImageFolder IO is seek-bound):

- ``write_records`` / ``read_records``: the public TFRecord framing
  (length + masked crc32c + payload + crc), pure Python — the writer is a
  dataset-prep tool, the reader the fallback when no C++ toolchain exists.
- ``RecordStream``: ctypes binding over ``native/records.cc`` — one background
  C++ thread per stream reads ahead (file IO overlaps decode/augment on the
  consumer side, no GIL), verifies crcs, and serves from a shuffle pool.
- ``ClassificationRecords`` + ``train_stream``/``eval_stream``: the fit-loop
  source for record shards. Payload layout: ``int32 LE label | encoded image``
  (PNG/JPEG bytes, decoded by the native batch decoder in data/imagefolder's
  pipeline style); image decodes run ``decode_ahead`` batches ahead of the
  consumer so decode overlaps the (already background) read.
- ``write_shard_index``/``shard_offsets``: the ``.idx`` count/offset sidecar
  (written at shard-prep time, verified against the shard's byte size and
  mtime) — ``count_records`` and the data service skip the full-file scan.
- ``ShardRangeReader``: random-access record reads at indexed byte offsets
  (native fseek+crc via ``tfdl_ranges_*``, pure-Python fallback) — the
  read primitive under ``data/service.py``'s parallel workers.

Sharding contract for multi-host runs: pass each process a disjoint subset of
shard files (``host_shard_paths``), the record-level generalization of
pipeline.host_shard — or let ``data.service.epoch_shard_assignment`` re-deal
the full shard set every epoch (the global-shuffle generalization).
"""

from __future__ import annotations

import ctypes
import glob as glob_lib
import os
import struct
from zipfile import BadZipFile as zipfile_BadZipFile
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from tensorflowdistributedlearning_tpu.native import loader as native_loader
from tensorflowdistributedlearning_tpu.resilience import faults
import tensorflowdistributedlearning_tpu.resilience.retry as retry_lib


def _open_shard(path: str, mode: str = "rb"):
    """Shard-file open with transient-I/O retry (resilience/retry.py) — the
    failure mode network filesystems actually exhibit mid-epoch; the
    injectable ``io-read`` fault site lives inside the attempt."""

    def attempt():
        faults.fire(faults.SITE_IO)
        return open(path, mode)

    return retry_lib.call_with_retry(
        attempt, name="record_open", exceptions=(OSError,)
    )

# -- crc32c (Castagnoli), table-driven — mirrors native/records.cc ------------

_CRC_TABLE: List[int] = []


def _crc_table() -> List[int]:
    global _CRC_TABLE
    if not _CRC_TABLE:
        table = []
        for i in range(256):
            c = i
            for _ in range(8):
                c = (0x82F63B78 ^ (c >> 1)) if (c & 1) else (c >> 1)
            table.append(c)
        _CRC_TABLE = table
    return _CRC_TABLE


def _crc32c(data: bytes) -> int:
    table = _crc_table()
    c = 0xFFFFFFFF
    for b in data:
        c = table[(c ^ b) & 0xFF] ^ (c >> 8)
    return c ^ 0xFFFFFFFF


def masked_crc(data: bytes) -> int:
    crc = _crc32c(data)
    return (((crc >> 15) | (crc << 17)) + 0xA282EAD8) & 0xFFFFFFFF


# -- pure-Python framing ------------------------------------------------------


def write_records(path: str, records: Sequence[bytes]) -> None:
    """Write one TFRecord shard (public framing, readable by any TFRecord
    consumer)."""
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "wb") as f:
        for rec in records:
            header = struct.pack("<Q", len(rec))
            f.write(header)
            f.write(struct.pack("<I", masked_crc(header)))
            f.write(rec)
            f.write(struct.pack("<I", masked_crc(rec)))
    # a rewritten shard invalidates any existing .idx sidecar NOW: a
    # same-byte-size rewrite landing within one mtime tick would otherwise
    # pass shard_offsets' freshness check and serve stale offsets
    try:
        os.remove(shard_index_path(path))
    except FileNotFoundError:
        pass


def read_records(path: str, verify: bool = True) -> Iterator[bytes]:
    """Pure-Python shard reader (fallback + oracle for the native one)."""
    with _open_shard(path) as f:
        while True:
            header = f.read(12)
            if not header:
                return
            if len(header) != 12:
                raise ValueError(f"{path}: truncated record header")
            (length,) = struct.unpack("<Q", header[:8])
            if verify:
                (want,) = struct.unpack("<I", header[8:12])
                if masked_crc(header[:8]) != want:
                    raise ValueError(f"{path}: corrupt length crc")
            data = f.read(length)
            footer = f.read(4)
            if len(data) != length or len(footer) != 4:
                raise ValueError(f"{path}: truncated record body")
            if verify:
                (want,) = struct.unpack("<I", footer)
                if masked_crc(data) != want:
                    raise ValueError(f"{path}: corrupt data crc")
            yield data


# -- native streaming reader --------------------------------------------------


def _records_lib() -> Optional[ctypes.CDLL]:
    lib = native_loader.load_extra_library(
        "records.cc",
        "libtfdl_records",
        link_png=False,
    )
    if lib is None:
        return None
    lib.tfdl_rec_open.restype = ctypes.c_int64
    lib.tfdl_rec_open.argtypes = [
        ctypes.POINTER(ctypes.c_char_p),
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_uint64,
        ctypes.c_int,
    ]
    lib.tfdl_rec_next.restype = ctypes.c_int
    lib.tfdl_rec_next.argtypes = [
        ctypes.c_int64,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.tfdl_rec_close.restype = None
    lib.tfdl_rec_close.argtypes = [ctypes.c_int64]
    # offset-indexed range reads (data/service.py workers); absent on a stale
    # pre-rebuild .so — callers hasattr-check and fall back to pure Python
    if hasattr(lib, "tfdl_ranges_open"):
        lib.tfdl_ranges_open.restype = ctypes.c_int64
        lib.tfdl_ranges_open.argtypes = [ctypes.c_char_p]
        lib.tfdl_ranges_read.restype = ctypes.c_int
        lib.tfdl_ranges_read.argtypes = [
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_int,
            ctypes.c_int,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.tfdl_ranges_close.restype = None
        lib.tfdl_ranges_close.argtypes = [ctypes.c_int64]
    return lib


class RecordStream:
    """Iterator of record payload bytes over a list of TFRecord shards.

    Native path: background C++ reader thread + crc verification + shuffle
    pool. Fallback: pure-Python sequential read with an equivalent shuffle
    pool (same semantics, GIL-bound)."""

    def __init__(
        self,
        paths: Sequence[str],
        *,
        shuffle_buffer: int = 1,
        seed: int = 0,
        verify_crc: bool = True,
    ):
        if not paths:
            raise ValueError("RecordStream needs at least one shard path")
        self.paths = [os.path.abspath(p) for p in paths]
        self.shuffle_buffer = max(1, int(shuffle_buffer))
        self.seed = seed
        self.verify_crc = verify_crc

    def __iter__(self) -> Iterator[bytes]:
        lib = _records_lib()
        if lib is not None:
            yield from self._iter_native(lib)
        else:
            yield from self._iter_python()

    def _iter_native(self, lib) -> Iterator[bytes]:
        arr = (ctypes.c_char_p * len(self.paths))(
            *[p.encode() for p in self.paths]
        )
        handle = lib.tfdl_rec_open(
            arr,
            len(self.paths),
            self.shuffle_buffer,
            ctypes.c_uint64(self.seed),
            1 if self.verify_crc else 0,
        )
        if handle == 0:
            raise RuntimeError("tfdl_rec_open failed")
        try:
            data = ctypes.POINTER(ctypes.c_uint8)()
            length = ctypes.c_uint64()
            while True:
                rc = lib.tfdl_rec_next(
                    handle, ctypes.byref(data), ctypes.byref(length)
                )
                if rc == 0:
                    return
                if rc == -2:
                    raise IOError(
                        "failed to open/read a TFRecord shard (missing file or "
                        "permissions) among " + ", ".join(self.paths)
                    )
                if rc == -3:
                    raise RuntimeError(
                        "RecordStream handle is invalid or already closed "
                        "(handle-lifecycle bug, not data corruption)"
                    )
                if rc < 0:
                    raise ValueError(
                        "corrupt TFRecord stream (crc/framing mismatch) in "
                        + ", ".join(self.paths)
                    )
                yield ctypes.string_at(data, length.value)
        finally:
            lib.tfdl_rec_close(handle)

    def _iter_python(self) -> Iterator[bytes]:
        rng = np.random.default_rng(self.seed)
        order = list(self.paths)
        rng.shuffle(order)
        pool: List[bytes] = []
        source = (
            rec for path in order for rec in read_records(path, self.verify_crc)
        )
        for rec in source:
            pool.append(rec)
            if len(pool) >= self.shuffle_buffer:
                idx = int(rng.integers(len(pool))) if self.shuffle_buffer > 1 else 0
                pool[idx], pool[-1] = pool[-1], pool[idx]
                yield pool.pop()
        rng.shuffle(pool)
        yield from pool


# -- classification payloads (int32 label + encoded image) --------------------


def encode_classification_record(label: int, image_bytes: bytes) -> bytes:
    return struct.pack("<i", label) + image_bytes


def check_classification_labels(
    labels: np.ndarray, num_classes: Optional[int]
) -> None:
    """Label-range validation shared by every classification record consumer
    (``None`` skips — unknown class count)."""
    if num_classes is not None and labels.size:
        lo, hi = int(labels.min()), int(labels.max())
        if lo < 0 or hi >= num_classes:
            raise ValueError(
                f"record label out of range [0, {num_classes}): "
                f"saw {lo}..{hi} — the shards hold more classes than the "
                "model's num_classes"
            )


def decode_classification_batch(
    blobs: Sequence[bytes],
    labels: Sequence[int],
    valid_rows: int,
    *,
    image_shape: Tuple[int, int],
    channels: int,
    num_classes: Optional[int] = None,
) -> Dict[str, np.ndarray]:
    """THE blobs+labels -> ``{'images','labels','valid'}`` assembly: label
    validation (valid rows only), native blob decode behind the retryable
    ``io-data`` fault site, normalization. The single decode recipe shared by
    the legacy stream (``ClassificationRecords``) and the data service's
    workers (``data/service.py``) — one place for the semantics both paths
    must agree on."""
    from tensorflowdistributedlearning_tpu.data.imagefolder import _normalize

    h, w = image_shape
    arr_labels = np.asarray(labels, np.int32)
    check_classification_labels(arr_labels[:valid_rows], num_classes)

    def attempt():
        # decode is re-runnable from the buffered blobs, so a transient
        # decode-side I/O failure on the Nth batch (the injectable
        # ``io-data`` site) retries instead of killing the stream
        faults.fire(faults.SITE_DATA)
        return native_loader.decode_image_blobs(blobs, (h, w), channels)

    images = retry_lib.call_with_retry(
        attempt, name="record_batch", exceptions=(OSError,)
    )
    valid = np.zeros(len(blobs), np.float32)
    valid[:valid_rows] = 1.0
    return {
        "images": _normalize(images, channels),
        "labels": arr_labels,
        "valid": valid,
    }


def decode_classification_record(payload: bytes) -> Tuple[int, bytes]:
    (label,) = struct.unpack("<i", payload[:4])
    return label, payload[4:]


def write_classification_shards(
    out_dir: str,
    images: Sequence[np.ndarray],
    labels: Sequence[int],
    *,
    shards: int = 2,
    prefix: str = "train",
) -> List[str]:
    """Encode uint8 HWC images as PNG payload records across ``shards`` files
    (dataset-prep utility; also the test fixture generator)."""
    import io

    from PIL import Image

    paths = []
    records: List[List[bytes]] = [[] for _ in range(shards)]
    for i, (img, label) in enumerate(zip(images, labels)):
        buf = io.BytesIO()
        arr = np.asarray(img)
        Image.fromarray(arr).save(buf, format="PNG")
        records[i % shards].append(
            encode_classification_record(int(label), buf.getvalue())
        )
    for s in range(shards):
        path = os.path.join(out_dir, f"{prefix}-{s:05d}-of-{shards:05d}.tfrecord")
        write_records(path, records[s])
        # count/offset sidecar at prep time: count_records and the data
        # service's offset-indexed workers skip the full-file scan
        write_shard_index(path)
        paths.append(path)
    return paths


# -- shard record index (.idx sidecar) ----------------------------------------

INDEX_SUFFIX = ".idx"


def shard_index_path(path: str) -> str:
    return path + INDEX_SUFFIX


def _scan_offsets(path: str) -> np.ndarray:
    """Record start offsets via a header-only scan (seeks over payloads — no
    crc, no decode; cheap even for large shards). Raises on truncation."""
    offsets: List[int] = []
    size = os.path.getsize(path)
    with _open_shard(path) as f:
        pos = 0
        while True:
            header = f.read(12)
            if not header:
                break
            if len(header) != 12:
                raise ValueError(f"{path}: truncated record header")
            (length,) = struct.unpack("<Q", header[:8])
            f.seek(length + 4, os.SEEK_CUR)
            # seeking past EOF succeeds silently — without this check a
            # shard truncated mid-record would be COUNTED as whole while
            # the verifying reader later fails, desynchronizing the eval
            # batch count from what the stream can deliver
            if f.tell() > size:
                raise ValueError(f"{path}: truncated record body")
            offsets.append(pos)
            pos += 12 + length + 4
    return np.asarray(offsets, np.uint64)


def write_shard_index(path: str) -> np.ndarray:
    """Write the ``.idx`` count/offset sidecar for one shard: record start
    offsets plus the shard's byte size for staleness detection. Written by
    ``write_classification_shards`` at prep time so ``count_records`` and the
    data service never pay the full-file scan; atomic install, so a torn
    writer cannot leave a half-index that parses. Returns the offsets it
    indexed (callers wanting the count need not re-read the sidecar)."""
    idx = shard_index_path(path)
    offsets = _scan_offsets(path)
    tmp = f"{idx}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        np.savez(f, offsets=offsets, file_size=np.int64(os.path.getsize(path)))
    os.replace(tmp, idx)
    return offsets


def shard_offsets(path: str) -> np.ndarray:
    """Record start offsets for one shard: from the ``.idx`` sidecar when it
    is present and FRESH (stored byte size matches the shard and the sidecar
    is not older than it — a rewritten shard invalidates its index), else a
    header scan. Never trusts a stale index: wrong offsets would read garbage
    framing and fail far from the cause."""
    idx = shard_index_path(path)
    try:
        if os.path.getmtime(idx) >= os.path.getmtime(path):
            with np.load(idx) as z:
                if int(z["file_size"]) == os.path.getsize(path):
                    return z["offsets"].astype(np.uint64)
    except (OSError, KeyError, ValueError, zipfile_BadZipFile):
        pass  # missing/corrupt/legacy sidecar: the scan is the oracle
    return _scan_offsets(path)


def count_records(paths: Sequence[str]) -> int:
    """Number of records across shards — the ``.idx`` sidecar when fresh
    (O(1) per shard), else the header-only scan."""
    return sum(len(shard_offsets(p)) for p in paths)


class ShardRangeReader:
    """Random-access record reads at known byte offsets — the data-service
    worker read path (offsets come from ``shard_offsets``). Native fseek/fread
    with crc verification in C++ when available, pure-Python fallback with the
    same semantics. One reader serves ONE thread; each service worker opens
    its own."""

    def __init__(self, path: str, *, verify_crc: bool = True):
        self.path = os.path.abspath(path)
        self.verify_crc = verify_crc
        self._lib = None
        self._handle = 0
        self._file = None
        lib = _records_lib()
        if lib is not None and hasattr(lib, "tfdl_ranges_open"):
            handle = lib.tfdl_ranges_open(self.path.encode())
            if handle == 0:
                raise IOError(f"cannot open record shard {self.path}")
            self._lib, self._handle = lib, handle
        else:
            self._file = _open_shard(self.path)

    def read(self, offsets: Sequence[int]) -> List[bytes]:
        """Record payloads at ``offsets``, in the given order."""
        offsets = list(offsets)
        if not offsets:
            return []
        if self._lib is not None:
            n = len(offsets)
            arr = (ctypes.c_uint64 * n)(*[int(o) for o in offsets])
            datas = (ctypes.POINTER(ctypes.c_uint8) * n)()
            lens = (ctypes.c_uint64 * n)()
            rc = self._lib.tfdl_ranges_read(
                self._handle, arr, n, 1 if self.verify_crc else 0, datas, lens
            )
            if rc == -3:
                raise RuntimeError(
                    "ShardRangeReader handle is invalid or already closed"
                )
            if rc == -2:
                raise IOError(f"read failed in record shard {self.path}")
            if rc != 0:
                raise ValueError(
                    f"{self.path}: corrupt record at an indexed offset "
                    "(crc/framing mismatch — stale .idx or shard damage)"
                )
            return [ctypes.string_at(datas[i], lens[i]) for i in range(n)]
        out = []
        for off in offsets:
            self._file.seek(int(off))
            header = self._file.read(12)
            if len(header) != 12:
                raise ValueError(f"{self.path}: truncated record header")
            (length,) = struct.unpack("<Q", header[:8])
            if self.verify_crc:
                (want,) = struct.unpack("<I", header[8:12])
                if masked_crc(header[:8]) != want:
                    raise ValueError(f"{self.path}: corrupt length crc")
            data = self._file.read(length)
            footer = self._file.read(4)
            if len(data) != length or len(footer) != 4:
                raise ValueError(f"{self.path}: truncated record body")
            if self.verify_crc:
                (want,) = struct.unpack("<I", footer)
                if masked_crc(data) != want:
                    raise ValueError(f"{self.path}: corrupt data crc")
            out.append(data)
        return out

    def close(self) -> None:
        if self._lib is not None and self._handle:
            self._lib.tfdl_ranges_close(self._handle)
            self._handle = 0
            self._lib = None
        if self._file is not None:
            self._file.close()
            self._file = None

    def __enter__(self) -> "ShardRangeReader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # best-effort: workers cache readers thread-locally
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


def host_shard_paths(
    paths: Sequence[str],
    process_index: Optional[int] = None,
    process_count: Optional[int] = None,
) -> List[str]:
    """This process's round-robin subset of shard files (multi-host contract;
    the STATIC assignment — ``data.service.epoch_shard_assignment`` is the
    epoch-reshuffled generalization). Explicit process arguments exist for
    tests and tools; the default reads the jax cluster."""
    if process_index is None or process_count is None:
        import jax

        process_index = jax.process_index()
        process_count = jax.process_count()
    return [
        p
        for i, p in enumerate(sorted(paths))
        if i % process_count == process_index
    ]


class ClassificationRecords:
    """Record-sharded classification source for the fit loop.

    ``root`` holds ``{split}-*.tfrecord`` shards (see
    ``write_classification_shards``). Streams decode through the native image
    decoder in batches; infinite train stream re-opens the shards each epoch
    with a reseeded shuffle."""

    def __init__(
        self,
        root: str,
        *,
        split: str = "train",
        image_shape: Tuple[int, int] = (32, 32),
        channels: int = 3,
        num_classes: Optional[int] = None,
    ):
        self.paths = sorted(
            glob_lib.glob(os.path.join(root, f"{split}-*.tfrecord"))
        )
        if not self.paths:
            raise ValueError(f"No {split}-*.tfrecord shards under {root}")
        self.image_shape = image_shape
        self.channels = channels
        self.num_classes = num_classes

    def _emit(self, blobs: List[bytes], labels: List[int], valid_rows: int):
        return decode_classification_batch(
            blobs,
            labels,
            valid_rows,
            image_shape=self.image_shape,
            channels=self.channels,
            num_classes=self.num_classes,
        )

    def batches(
        self,
        batch_size: int,
        *,
        seed: int = 0,
        shuffle_buffer: int = 1024,
        repeat: bool = True,
        steps: Optional[int] = None,
        pad_to_batches: Optional[int] = None,
        decode_ahead: int = 1,
    ) -> Iterator[Dict[str, np.ndarray]]:
        """Batched {'images','labels','valid'} stream.

        ``repeat=True``: infinite (or ``steps``-bounded) shuffled training
        stream, every row valid. A partial batch at an epoch boundary is
        CARRIED into the next epoch (batches may span epochs; no records are
        dropped, and datasets smaller than ``batch_size`` still emit batches
        instead of spinning forever). ``repeat=False``: one ordered pass; with
        ``pad_to_batches`` the stream is EXTENDED to exactly that many batches
        by wrapping around to the start with ``valid=0`` rows (the streaming
        analogue of pipeline.eval_batches' wrap-around padding — metrics
        exclude the padding, and every multi-host process can run the same
        number of collective-bearing eval steps).

        ``decode_ahead``: image decodes run in a background thread up to this
        many batches ahead of the consumer, so decode OVERLAPS the (native,
        already-background) record read instead of serializing behind it —
        the end2end fix for RECORDS_BENCH's decode-loses-to-PIL regression.
        Batch order and contents are unchanged (one decode thread, in-order
        completion); 0 restores the fully in-line path."""
        assembled = self._assemble(
            batch_size,
            seed=seed,
            shuffle_buffer=shuffle_buffer,
            repeat=repeat,
            steps=steps,
            pad_to_batches=pad_to_batches,
        )
        if decode_ahead <= 0:
            for blobs, labels, valid_rows in assembled:
                yield self._emit(blobs, labels, valid_rows)
            return
        from collections import deque
        from concurrent.futures import ThreadPoolExecutor

        pending: deque = deque()
        with ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="records-decode"
        ) as pool:
            for work in assembled:
                pending.append(pool.submit(self._emit, *work))
                while len(pending) > decode_ahead:
                    yield pending.popleft().result()
            while pending:
                yield pending.popleft().result()

    def _assemble(
        self,
        batch_size: int,
        *,
        seed: int,
        shuffle_buffer: int,
        repeat: bool,
        steps: Optional[int],
        pad_to_batches: Optional[int],
    ) -> Iterator[Tuple[List[bytes], List[int], int]]:
        """The stream's accumulation half: yields ``(blobs, labels,
        valid_rows)`` work items in emission order; ``batches`` decodes them
        (inline or decode-ahead)."""
        emitted = 0
        epoch = 0
        labels: List[int] = []
        blobs: List[bytes] = []
        while True:
            stream = RecordStream(
                self.paths,
                shuffle_buffer=shuffle_buffer if repeat else 1,
                seed=seed + epoch,
            )
            seen_any = False
            for payload in stream:
                seen_any = True
                label, img = decode_classification_record(payload)
                labels.append(label)
                blobs.append(img)
                if len(blobs) == batch_size:
                    yield (blobs, labels, batch_size)
                    emitted += 1
                    labels, blobs = [], []
                    if repeat and steps is not None and emitted >= steps:
                        return
                    if (
                        not repeat
                        and pad_to_batches is not None
                        and emitted >= pad_to_batches
                    ):
                        return
            if not seen_any:
                raise ValueError(
                    "record shards contain zero records: " + ", ".join(self.paths)
                )
            if not repeat:
                tail_valid = len(blobs)
                if blobs or (pad_to_batches or 0) > emitted:
                    # wrap around for padding rows (valid=0): reopen the stream
                    refill = RecordStream(self.paths, shuffle_buffer=1, seed=seed)
                    refill_iter = iter(refill)
                    target = pad_to_batches if pad_to_batches is not None else (
                        emitted + 1 if blobs else emitted
                    )
                    while emitted < target:
                        while len(blobs) < batch_size:
                            payload = next(refill_iter, None)
                            if payload is None:
                                refill_iter = iter(
                                    RecordStream(
                                        self.paths, shuffle_buffer=1, seed=seed
                                    )
                                )
                                payload = next(refill_iter)
                            label, img = decode_classification_record(payload)
                            labels.append(label)
                            blobs.append(img)
                        yield (blobs, labels, tail_valid)
                        emitted += 1
                        labels, blobs = [], []
                        tail_valid = 0  # later padded batches are fully invalid
                return
            epoch += 1
