"""Runtime In-Memory File System — flat, read-only, zero-copy weight store.

Image layout (all little-endian):

  [0:4]   magic  b"RIMF"
  [4:6]   version
  [6:8]   flags
  [8:12]  n_files
  [12:16] index_bytes
  [16:..] index: per file a json-encoded entry
          {name, offset, nbytes, dtype, shape, crc32}
  [..]    128-byte aligned data region (one aligned blob per file)
  [-4:]   CRC-32 of everything before it

``mount()`` wraps a bytes-like object and serves **zero-copy numpy views**
via ``np.frombuffer`` — no deserialization, no copies; exactly the paper's
"returns physical addresses directly to the DMA engine" property (the view's
buffer pointer IS what ``jax.device_put`` consumes). The image doubles as
the checkpoint format (checkpoint/ckpt.py) and the network provisioning
payload (serving/protocol.py).
"""
from __future__ import annotations

import io
import itertools
import json
import os
import pathlib
import struct
import weakref
import zlib
from typing import Mapping, Optional, Union

import numpy as np

from repro.core.integrity import IntegrityError, payload_crc

MAGIC = b"RIMF"
ALIGN = 128          # GMIO-alignment analogue: TPU-friendly 128B lanes


class RIMFSError(IntegrityError, ValueError):
    """RIMFS-level integrity/format fault. Subclasses ``IntegrityError``
    so the unified taxonomy (DESIGN.md §11) narrows to one recoverable
    class at the recovery layer, and ``ValueError`` for the seed-era
    callers that catch it as a format error."""

    def __init__(self, message: str, kind: str = "rimfs"):
        super().__init__(message, kind=kind)


def _align(n: int) -> int:
    return (n + ALIGN - 1) // ALIGN * ALIGN


def _dtype_tag(dt: np.dtype) -> str:
    """Wire tag for one file's dtype. Numpy's ``.str`` collapses extension
    dtypes (ml_dtypes bfloat16 et al) to opaque void types (``|V2``), which
    cannot round-trip — tag those by NAME instead (LM weight images ship
    bfloat16)."""
    return dt.name if dt.kind == "V" else dt.str


def _dtype_of(tag: str) -> np.dtype:
    try:
        return np.dtype(tag)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, tag))


def pack(files: Mapping[str, np.ndarray], *, version: int = 1) -> bytes:
    """Flatten named arrays into one RIMFS image."""
    index = []
    blobs = []
    # header size depends on index size; compute index first with
    # placeholder offsets, then fix up (entries are fixed-length jsons once
    # offsets are known, so do two passes with stable formatting).
    metas = []
    for name, arr in files.items():
        arr = np.ascontiguousarray(arr)
        metas.append((name, arr))

    def build_index(data_start: int):
        out, off = [], data_start
        for name, arr in metas:
            off = _align(off)
            out.append({
                "name": name, "offset": off, "nbytes": int(arr.nbytes),
                "dtype": _dtype_tag(arr.dtype), "shape": list(arr.shape),
                "crc32": zlib.crc32(arr.tobytes()) & 0xFFFFFFFF,
            })
            off += arr.nbytes
        return out, off

    # iterate to fixed point: index length changes offset digits rarely; two
    # passes suffice in practice, loop defensively.
    data_start = 16
    for _ in range(5):
        index, total = build_index(data_start)
        blob = json.dumps(index, separators=(",", ":")).encode()
        new_start = 16 + len(blob)
        if new_start == data_start:
            break
        data_start = new_start
    index, total = build_index(data_start)
    blob = json.dumps(index, separators=(",", ":")).encode()

    buf = bytearray(_align(total) + 4)
    struct.pack_into("<4sHHII", buf, 0, MAGIC, version, 0, len(metas),
                     len(blob))
    buf[16:16 + len(blob)] = blob
    for entry, (name, arr) in zip(index, metas):
        o = entry["offset"]
        buf[o:o + arr.nbytes] = arr.tobytes()
    crc = zlib.crc32(bytes(buf[:-4])) & 0xFFFFFFFF
    struct.pack_into("<I", buf, len(buf) - 4, crc)
    return bytes(buf)


class RIMFS:
    """A mounted image. All reads are zero-copy views into the backing
    buffer; ``verify()`` checks per-file CRCs without copying.

    Integrity plane (DESIGN.md §11): with ``verify_reads`` on (default)
    every file's CRC is checked the FIRST time it is opened — ``read``,
    ``resident`` pinning, bind-time weight resolution all flow through
    here — so a poisoned weight image is rejected before it ever binds,
    not only when a caller remembers to ``verify()``. The check is
    memoized per file; ``fsck()`` re-verifies everything and resets the
    memo (bring-up / post-fault re-validation)."""

    def __init__(self, data: Union[bytes, bytearray, memoryview, np.memmap],
                 verify_reads: bool = True):
        self._data = data
        buf = memoryview(data) if not isinstance(data, np.memmap) else data
        magic, ver, _flags, n, ilen = struct.unpack_from("<4sHHII", buf, 0)
        if bytes(magic) != MAGIC:
            raise RIMFSError(f"bad RIMFS magic: {bytes(magic)!r}")
        self.version = ver
        index = json.loads(bytes(buf[16:16 + ilen]).decode())
        if len(index) != n:
            raise RIMFSError("index length mismatch")
        self._index = {e["name"]: e for e in index}
        # per-driver residency cache: id -> (weakref(driver), ResidentImage)
        self._resident: dict[int, tuple] = {}
        self.verify_reads = verify_reads
        self._verified: set = set()        # files whose CRC already checked

    # ------------------------------------------------------------------ api
    def files(self) -> list:
        return list(self._index)

    def stat(self, name: str) -> dict:
        return dict(self._index[name])

    def read(self, name: str, verify: Optional[bool] = None) -> np.ndarray:
        """Zero-copy ndarray view of one file (CRC-checked on first
        open unless ``verify=False`` / ``verify_reads`` off)."""
        e = self._index.get(name)
        if e is None:
            raise RIMFSError(f"no such file: {name!r}")
        view = np.frombuffer(
            self._data, dtype=_dtype_of(e["dtype"]),
            count=int(np.prod(e["shape"])) if e["shape"] else 1,
            offset=e["offset"]).reshape(e["shape"])
        check = self.verify_reads if verify is None else verify
        if check and name not in self._verified:
            if (zlib.crc32(view.tobytes()) & 0xFFFFFFFF) != e["crc32"]:
                raise RIMFSError(f"CRC mismatch in {name!r} (read)",
                                 kind="file_crc")
            self._verified.add(name)
        return view

    def address_of(self, name: str) -> tuple:
        """(offset, nbytes) — the paper's 'physical address' for DMA."""
        e = self._index[name]
        return e["offset"], e["nbytes"]

    def verify(self, name: Optional[str] = None) -> bool:
        names = [name] if name else self.files()
        for n in names:
            e = self._index[n]
            view = self.read(n, verify=False)
            if (zlib.crc32(view.tobytes()) & 0xFFFFFFFF) != e["crc32"]:
                raise RIMFSError(f"CRC mismatch in {n!r}", kind="file_crc")
            self._verified.add(n)
        return True

    def verify_image(self) -> bool:
        raw = bytes(self._data) if not isinstance(self._data, (bytes,)) \
            else self._data
        (crc,) = struct.unpack_from("<I", raw, len(raw) - 4)
        if crc != (zlib.crc32(raw[:-4]) & 0xFFFFFFFF):
            raise RIMFSError("image CRC mismatch", kind="image_crc")
        return True

    def fsck(self, strict: bool = True) -> dict:
        """Full consistency check: image trailer CRC + every per-file
        CRC, re-verified from scratch (the read memo is reset first, so
        corruption that landed AFTER a file's first read is caught).
        Invoked on platform bring-up and after any tile-group death.
        Returns a report dict; with ``strict`` (default) corruption
        raises ``RIMFSError`` instead. An image mounted from an
        ``ImageStore`` replays/rolls back the store's journal through
        ``ImageStore.fsck`` first — this method checks the mounted
        bytes."""
        self._verified.clear()
        report: dict = {"files": len(self._index), "bad_files": [],
                        "image_crc_ok": True}
        try:
            self.verify_image()
        except RIMFSError:
            report["image_crc_ok"] = False
            if strict:
                raise
        for n, e in self._index.items():
            view = self.read(n, verify=False)
            if (zlib.crc32(view.tobytes()) & 0xFFFFFFFF) != e["crc32"]:
                report["bad_files"].append(n)
                if strict:
                    raise RIMFSError(f"fsck: CRC mismatch in {n!r}",
                                     kind="file_crc")
            else:
                self._verified.add(n)
        report["ok"] = report["image_crc_ok"] and not report["bad_files"]
        return report

    def resident(self, driver, names: Optional[list] = None
                 ) -> "ResidentImage":
        """Device residency (zero re-upload): pin files into the driver's
        arena ONCE and serve the device buffers from then on.

        The upload consumes the same zero-copy host views ``read`` serves
        (``address_of`` gives each file's stable host "physical address"),
        so nothing is copied host-side; subsequent ``resident`` calls for
        the same driver — e.g. every RBL re-bind, every new
        ``ServingEngine`` over this image — return the cached
        ``ResidentImage`` and move zero bytes (asserted against the
        driver's DMA counters in tests/benchmarks). ``names`` restricts
        pinning to the files a program actually uses; later calls extend
        the pinned set incrementally (already-pinned files never move
        again). Cache entries for garbage-collected drivers are pruned —
        a dead driver's weight copy is not kept alive by this cache.
        """
        for key, (ref, _) in list(self._resident.items()):
            if ref() is None:                     # driver was collected
                del self._resident[key]
        entry = self._resident.get(id(driver))
        if entry is not None and entry[0]() is driver:
            ri = entry[1]
            ri.extend(names if names is not None else self.files())
            return ri
        ri = ResidentImage(self, driver, names)
        self._resident[id(driver)] = (weakref.ref(driver), ri)
        return ri

    def release(self) -> int:
        """Unpin every driver's resident copy of this image (arena ranges
        freed; the host image itself is untouched); returns the bytes
        released."""
        freed = 0
        for _ref, ri in list(self._resident.values()):
            freed += ri.nbytes()
            ri.unpin()
        self._resident.clear()
        return freed

    def total_bytes(self) -> int:
        return len(self._data)

    def overhead_bytes(self) -> int:
        """Non-payload bytes (header + index + padding) — the 'runtime
        memory overhead' the paper compares against OS file systems."""
        payload = sum(e["nbytes"] for e in self._index.values())
        return self.total_bytes() - payload


class ResidentImage:
    """Weight files pinned device-side, offset-registered in the driver's
    arena. Built once per (image, driver) pair by ``RIMFS.resident`` and
    extended incrementally as later binds request more files.

    The upload is split-phase when the driver has async DMA slots: every
    file's transfer is ISSUED before any is WAITED on (one batched
    descriptor when the driver supports it), so uploads overlap each
    other instead of paying one host round-trip per file. The driver is
    held by weakref: the cache never outlives the backend it pinned into.
    """

    def __init__(self, fs: RIMFS, driver, names: Optional[list] = None):
        self.fs = fs
        self._driver_ref = weakref.ref(driver)
        self._host_views: dict[str, np.ndarray] = {}
        self._offsets: dict[str, int] = {}
        self._bufs: dict[str, object] = {}
        self.extend(names if names is not None else fs.files())

    @property
    def driver(self):
        return self._driver_ref()

    def extend(self, names) -> None:
        """Pin any not-yet-resident files (already-pinned ones never
        re-upload; the DMA counters do not move for them)."""
        order = [n for n in names if n not in self._bufs]
        if not order:
            return
        driver = self.driver
        if driver is None:
            raise RIMFSError("resident image's driver was collected")
        for name in order:
            view = self.fs.read(name)          # zero-copy view of the image
            self._host_views[name] = view
            if getattr(driver, "arena", None) is not None:
                self._offsets[name] = driver.arena.alloc(view.nbytes)
        if getattr(driver, "dma_async_batch", None) is not None:
            # the whole file set under one batched issue
            tickets = driver.dma_async_batch(
                [self._host_views[n] for n in order], "h2d")
            for name, t in zip(order, tickets):
                self._bufs[name] = driver.dma_wait(t)
        elif getattr(driver, "dma_async", None) is not None:
            tickets = {n: driver.dma_async(self._host_views[n], "h2d")
                       for n in order}
            for name, t in tickets.items():    # redeem after ALL issues
                self._bufs[name] = driver.dma_wait(t)
        else:
            for name in order:
                self._bufs[name] = driver.initiate_dma(
                    self._host_views[name], "h2d")

    # ---------------------------------------------------------------- api
    def files(self) -> list:
        return list(self._bufs)

    def buffer(self, name: str):
        """The pinned device buffer for one file."""
        return self._bufs[name]

    __getitem__ = buffer

    def __contains__(self, name: str) -> bool:
        return name in self._bufs

    def buffers(self) -> dict:
        return dict(self._bufs)

    def host_view(self, name: str) -> np.ndarray:
        """The zero-copy host view the upload consumed (aliases the
        mounted image — tested, not assumed)."""
        return self._host_views[name]

    def offset_of(self, name: str) -> Optional[int]:
        """Arena offset of the pinned range (None without an arena)."""
        return self._offsets.get(name)

    def pinned_ranges(self) -> list:
        """Sorted [(arena_offset, nbytes), ...] of every pinned file —
        the hot-swap machinery asserts a shadow image's ranges are
        disjoint from (and do not displace) the live image's."""
        return sorted((off, self._host_views[name].nbytes)
                      for name, off in self._offsets.items())

    def revalidate(self) -> bool:
        """CRC-compare every pinned DEVICE buffer against its file's
        index CRC. This is the quarantine-lift check: after a watchdog
        kill the group's arena is poisoned until the weight copies it
        holds are proven bit-identical to the image
        (``TileMesh.revive``)."""
        for name, buf in self._bufs.items():
            if payload_crc(buf) != self.fs._index[name]["crc32"]:
                return False
        return True

    def nbytes(self) -> int:
        return sum(v.nbytes for v in self._host_views.values())

    def unpin(self) -> None:
        """Release the arena ranges and drop the buffer table."""
        driver = self.driver
        arena = getattr(driver, "arena", None) if driver is not None \
            else None
        if arena is not None:
            for off in self._offsets.values():
                arena.free(off)
        self._offsets.clear()
        self._bufs.clear()
        if driver is not None:
            self.fs._resident.pop(id(driver), None)


class Journal:
    """Write-ahead intent log for journaled image installs.

    Append-only records (dicts); when file-backed every append is
    flushed + fsynced BEFORE the caller proceeds — the write-ahead
    property an OS journal would provide. Record kinds:

      intent   {txid, crc, nbytes}  an install is about to stage
      commit   {txid}               staged payload is complete and valid
      applied  {txid}               the visible image was flipped
      rollback {txid}               fsck discarded the staging
    """

    def __init__(self, path: Optional[Union[str, pathlib.Path]] = None):
        self.path = pathlib.Path(path) if path is not None else None
        self._records: list = []
        if self.path is not None and self.path.exists():
            for line in self.path.read_text().splitlines():
                if line.strip():
                    self._records.append(json.loads(line))
        last = max((r["seq"] for r in self._records), default=0)
        self._seq = itertools.count(last + 1)

    def append(self, kind: str, txid: int, **meta) -> dict:
        rec = {"seq": next(self._seq), "kind": kind, "txid": txid, **meta}
        self._records.append(rec)
        if self.path is not None:
            with open(self.path, "a") as f:
                f.write(json.dumps(rec, separators=(",", ":")) + "\n")
                f.flush()
                os.fsync(f.fileno())
        return rec

    def records(self) -> list:
        return list(self._records)

    def pending(self) -> dict:
        """txid -> {"intent": rec, "committed": bool} for every intent
        without an applied/rollback resolution (the fsck worklist)."""
        state: dict = {}
        for r in self._records:
            if r["kind"] == "intent":
                state[r["txid"]] = {"intent": r, "committed": False}
            elif r["kind"] == "commit" and r["txid"] in state:
                state[r["txid"]]["committed"] = True
            elif r["kind"] in ("applied", "rollback"):
                state.pop(r["txid"], None)
        return state


class ImageStore:
    """Durable home of a serving image with journaled installs.

    Every install is write-ahead journaled: intent record -> stage the
    new bytes (side buffer; a ``.stage<txid>`` file when disk-backed)
    -> commit mark -> atomic flip (``os.replace``) -> applied mark. A
    fault at ANY point leaves the visible image either wholly old or
    wholly new, never a mixture; ``fsck()`` REPLAYS committed installs
    whose flip never landed (redo) and ROLLS BACK uncommitted staging
    (undo), then runs the mounted image's own per-file-CRC ``fsck``.

    ``fail_at`` on ``install`` is the chaos-injection hook: raise at a
    named step ("after_intent" / "after_stage" / "after_commit") to
    model a crash mid-write — the recovery path is then exercised by
    calling ``fsck()`` on the survivor.
    """

    def __init__(self, image: Optional[bytes] = None,
                 path: Optional[Union[str, pathlib.Path]] = None):
        self.path = pathlib.Path(path) if path is not None else None
        self.journal = Journal(
            f"{self.path}.journal" if self.path is not None else None)
        last_tx = max((r["txid"] for r in self.journal.records()),
                      default=0)
        self._txids = itertools.count(last_tx + 1)
        self._staging: dict[int, bytes] = {}
        self._image: Optional[bytes] = None
        if self.path is not None and self.path.exists():
            self._image = self.path.read_bytes()
        if image is not None:
            self.install(image)

    # ------------------------------------------------------------------ api
    def image(self) -> Optional[bytes]:
        """The committed (fully visible) image bytes."""
        return self._image

    def mount(self) -> RIMFS:
        if self._image is None:
            raise RIMFSError("image store is empty")
        return RIMFS(self._image)

    def _stage_path(self, txid: int) -> pathlib.Path:
        return pathlib.Path(f"{self.path}.stage{txid}")

    def install(self, image_bytes: bytes,
                fail_at: Optional[str] = None) -> int:
        """Journaled install; returns the transaction id."""
        txid = next(self._txids)
        self.journal.append("intent", txid,
                            crc=zlib.crc32(image_bytes) & 0xFFFFFFFF,
                            nbytes=len(image_bytes))
        if fail_at == "after_intent":
            raise IntegrityError(
                f"injected fault: crash after intent (tx {txid})",
                kind="journal_fault")
        self._staging[txid] = bytes(image_bytes)
        if self.path is not None:
            self._stage_path(txid).write_bytes(image_bytes)
        if fail_at == "after_stage":
            raise IntegrityError(
                f"injected fault: crash after stage (tx {txid})",
                kind="journal_fault")
        self.journal.append("commit", txid)
        if fail_at == "after_commit":
            raise IntegrityError(
                f"injected fault: crash after commit (tx {txid})",
                kind="journal_fault")
        self._apply(txid, image_bytes)
        return txid

    def _apply(self, txid: int, image_bytes: bytes) -> None:
        if self.path is not None:
            tmp = pathlib.Path(f"{self.path}.tmp")
            tmp.write_bytes(image_bytes)
            os.replace(tmp, self.path)           # the atomic flip
        self._image = bytes(image_bytes)
        self.journal.append("applied", txid)
        self._staging.pop(txid, None)
        if self.path is not None:
            sp = self._stage_path(txid)
            if sp.exists():
                sp.unlink()

    def fsck(self, strict: bool = True) -> dict:
        """Replay/roll back the journal, then fsck the mounted image.

        Committed transactions whose flip never became visible are
        re-applied from staging (CRC-checked against the intent record
        first); everything else pending is rolled back. The visible
        image is therefore always a fully-written, CRC-clean state."""
        report: dict = {"replayed": [], "rolled_back": [], "image": None}
        pend = self.journal.pending()
        for txid in sorted(pend):
            st = pend[txid]
            staged = self._staging.get(txid)
            if staged is None and self.path is not None:
                sp = self._stage_path(txid)
                if sp.exists():
                    staged = sp.read_bytes()
            intact = staged is not None and \
                (zlib.crc32(staged) & 0xFFFFFFFF) == st["intent"]["crc"]
            if st["committed"] and intact:
                self._apply(txid, staged)        # redo
                report["replayed"].append(txid)
            else:                                # undo
                self._staging.pop(txid, None)
                if self.path is not None:
                    sp = self._stage_path(txid)
                    if sp.exists():
                        sp.unlink()
                self.journal.append("rollback", txid)
                report["rolled_back"].append(txid)
        if self._image is not None:
            report["image"] = self.mount().fsck(strict=strict)
        return report


def mount(data: Union[bytes, bytearray, memoryview]) -> RIMFS:
    return RIMFS(data)


def mount_file(path: Union[str, pathlib.Path]) -> RIMFS:
    """mmap-backed mount: zero-copy straight from the page cache."""
    mm = np.memmap(str(path), dtype=np.uint8, mode="r")
    return RIMFS(mm)


def save_file(path: Union[str, pathlib.Path],
              files: Mapping[str, np.ndarray]) -> int:
    img = pack(files)
    pathlib.Path(path).write_bytes(img)
    return len(img)
