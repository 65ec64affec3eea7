"""Append-only write-ahead log for the serving delta segment.

``SearchService.insert`` acks a batch only after its WAL record is fsync'd
(group commit optionally batches the fsyncs — see ``fsync_every``), so the
durability contract is *acked implies recovered*: any insert whose call
returned is replayed into a reopened service even if the process is
SIGKILLed the next instruction.

On-disk format (everything little-endian, one file per segment):

    wal_<seq:08d>.log := header record*
    header            := magic "FPWAL001" | u32 words-per-row
    record            := u32 crc32(payload) | u32 len(payload) | payload
    payload           := u64 first_gid | u32 n_rows | rows (n_rows*words u32)

``first_gid`` makes replay idempotent against the snapshot it starts from:
records entirely at gids below the restored ``n_total`` are skipped, the
first new record must start exactly at ``n_total`` (a gap means segments
were lost — refuse to serve rather than silently drop acked data).

Segments **rotate** on compaction and before every snapshot; a snapshot's
manifest stores the first segment sequence number still needed
(``wal_from_seq``) and everything below it is garbage-collected after the
snapshot publishes. A crash between rotate and publish only leaves an
extra (fully replayable) segment behind.

A crash mid-append leaves a **torn tail**: a record whose length/crc check
fails at the end of a segment. Replay truncates it — those bytes were never
fsync'd, so the insert was never acked. A record that fails its crc midway
through a segment (actual corruption, not a crash) raises instead.

**GC pinning (ISSUE 9).** ``gc_below(seq)`` trusts its caller's floor —
but with ``snapshot(background=True)`` the floor computed from *published*
snapshot manifests can race an in-flight writer: the new snapshot rotated
the WAL (claiming ``wal_from_seq = s``) but has not published yet, so a
concurrent GC that floors at a *later* published snapshot would delete the
very segments the in-flight snapshot still depends on for its crash
window (crash before publish -> recovery = previous snapshot + full WAL
from *its* floor). :meth:`pin` registers a hard floor before the writer
starts; :meth:`gc_below` clamps every request to the minimum pinned
sequence until :meth:`unpin`. The replica-rehydration path pins the same
way so a catching-up replica's segments cannot vanish mid-replay.

The writer handle is also internally locked: the concurrent front end
(``serve/frontend.py``) appends from its insert path while housekeeping
threads rotate/sync/GC, and interleaved raw file writes would corrupt
records.
"""
from __future__ import annotations

import os
import struct
import threading
import zlib
from pathlib import Path

import numpy as np

from ..checkpoint.fs import DEFAULT_FS, Fs
from ..obs.trace import TRACER as _TR

MAGIC = b"FPWAL001"
_HEADER = struct.Struct("<8sI")            # magic, words-per-row
_REC = struct.Struct("<II")                # crc32(payload), len(payload)
_PAYLOAD_HEAD = struct.Struct("<QI")       # first_gid, n_rows


def _segment_name(seq: int) -> str:
    return f"wal_{seq:08d}.log"


def segment_seqs(directory: str | os.PathLike) -> list[int]:
    base = Path(directory)
    if not base.exists():
        return []
    return sorted(int(p.name[4:-4]) for p in base.glob("wal_*.log"))


def _encode_record(first_gid: int, rows: np.ndarray) -> bytes:
    rows = np.ascontiguousarray(rows, dtype="<u4")
    payload = _PAYLOAD_HEAD.pack(int(first_gid), rows.shape[0]) + rows.tobytes()
    return _REC.pack(zlib.crc32(payload), len(payload)) + payload


class WalCorruption(IOError):
    """A record failed its crc/length check somewhere other than the
    truncatable tail of the final segment."""


class WriteAheadLog:
    """Writer handle. Always opens a *new* segment (``rotate`` semantics on
    open) — recovery never appends to a file that may hold a torn tail.

    ``fsync_every=1`` fsyncs each append before returning (the default,
    full acked-implies-recovered). ``fsync_every=N`` group-commits: fsync
    every N appends, trading an N-1 record ack window for throughput.
    """

    def __init__(self, directory: str | os.PathLike, words: int, *,
                 fs: Fs = DEFAULT_FS, fsync_every: int = 1):
        self.dir = Path(directory)
        self.words = int(words)
        self.fsync_every = max(int(fsync_every), 1)
        self._fs = fs
        self._f = None
        self._unsynced = 0
        self._lock = threading.RLock()
        self._pins: dict[int, int] = {}    # token -> pinned floor seq
        self._next_pin = 0
        fs.mkdir(self.dir)
        existing = segment_seqs(self.dir)
        self.seq = (existing[-1] + 1) if existing else 0
        self._open_segment()

    # -- write path ----------------------------------------------------------
    def _open_segment(self) -> None:
        path = self.dir / _segment_name(self.seq)
        self._f = self._fs.open(path, "wb")
        self._f.write(_HEADER.pack(MAGIC, self.words))
        self._fs.fsync(self._f)
        self._fs.fsync_dir(self.dir)
        self._unsynced = 0

    def append(self, first_gid: int, rows: np.ndarray) -> None:
        """Log one insert batch; returns after the record is durable
        (modulo the group-commit window)."""
        rows = np.atleast_2d(np.asarray(rows, dtype=np.uint32))
        if rows.shape[1] != self.words:
            raise ValueError(f"row width {rows.shape[1]} != WAL width "
                             f"{self.words}")
        with self._lock, _TR.span("wal.append", rows=int(rows.shape[0]),
                                  seq=int(self.seq)):
            self._f.write(_encode_record(first_gid, rows))
            self._unsynced += 1
            if self._unsynced >= self.fsync_every:
                self.sync()

    def sync(self) -> None:
        with self._lock:
            if self._f is not None and self._unsynced:
                with _TR.span("wal.fsync", records=int(self._unsynced),
                              seq=int(self.seq)):
                    self._fs.fsync(self._f)
                self._unsynced = 0

    def flush(self) -> None:
        """Flush user-space buffers so the on-disk tail is record-complete
        (no durability promise — that's :meth:`sync`). Replica catch-up
        reads the live segment through the filesystem, so it must not see
        half a record still sitting in the writer's buffer."""
        with self._lock:
            if self._f is not None:
                self._f.flush()

    def rotate(self) -> int:
        """Close the current segment and start the next; returns the new
        sequence number (the first one a snapshot taken now depends on)."""
        with self._lock:
            self.sync()
            self._f.close()
            self.seq += 1
            self._open_segment()
            return self.seq

    # -- GC + pinning --------------------------------------------------------
    def pin(self, seq: int) -> int:
        """Register a hard GC floor (an in-flight snapshot's ``from_seq`` or
        a rehydrating replica's replay start); returns a token for
        :meth:`unpin`. While any pin is held, :meth:`gc_below` clamps to the
        minimum pinned sequence."""
        with self._lock:
            token = self._next_pin
            self._next_pin += 1
            self._pins[token] = int(seq)
            return token

    def unpin(self, token: int) -> None:
        with self._lock:
            self._pins.pop(token, None)

    def gc_below(self, seq: int) -> None:
        """Remove segments no snapshot needs anymore, clamped to the lowest
        pinned floor — a *published*-snapshot floor computed while another
        snapshot is mid-write must not delete the in-flight writer's tail."""
        with self._lock:
            if self._pins:
                seq = min(seq, min(self._pins.values()))
            for s in segment_seqs(self.dir):
                if s < seq:
                    self._fs.remove(self.dir / _segment_name(s))

    def set_fs(self, fs: Fs) -> None:
        """Swap the fs layer (fault-injection harness); rotates so the open
        file handle goes through the new layer too."""
        with self._lock:
            self.sync()
            self._f.close()
            self._fs = fs
            self.seq += 1
            self._open_segment()

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                self.sync()
                self._f.close()
                self._f = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def _read_segment(path: Path, *, words: int | None,
                  is_last: bool, truncate: bool, fs: Fs):
    """Yield ``(first_gid, rows)`` records; handle the torn tail."""
    data = path.read_bytes()
    if len(data) < _HEADER.size:
        if not (is_last or truncate):
            raise WalCorruption(f"{path}: truncated header")
        if truncate and len(data) > 0:
            fs.truncate(path, 0)
        return
    magic, seg_words = _HEADER.unpack_from(data, 0)
    if magic != MAGIC:
        raise WalCorruption(f"{path}: bad magic {magic!r}")
    if words is not None and seg_words != words:
        raise WalCorruption(f"{path}: words {seg_words} != expected {words}")
    off = _HEADER.size
    records = []
    while off < len(data):
        good = True
        if off + _REC.size > len(data):
            good = False
        else:
            crc, plen = _REC.unpack_from(data, off)
            payload = data[off + _REC.size: off + _REC.size + plen]
            if len(payload) != plen or zlib.crc32(payload) != crc:
                good = False
        if not good:
            # Torn tail: legal where a crash can leave one (the segment that
            # was being appended to). Truncate to the valid prefix.
            if truncate:
                fs.truncate(path, off)
                break
            raise WalCorruption(f"{path}: bad record at offset {off}")
        first_gid, n_rows = _PAYLOAD_HEAD.unpack_from(payload, 0)
        rows = np.frombuffer(payload, dtype="<u4",
                             offset=_PAYLOAD_HEAD.size).astype(np.uint32)
        if rows.size != n_rows * seg_words:
            raise WalCorruption(f"{path}: payload size mismatch at {off}")
        records.append((first_gid, rows.reshape(n_rows, seg_words)))
        off += _REC.size + plen
    return records


def replay(directory: str | os.PathLike, *, from_seq: int = 0,
           words: int | None = None, truncate: bool = True,
           fs: Fs = DEFAULT_FS):
    """Read every record in segments >= ``from_seq`` in order.

    Returns ``(records, stats)`` where records is a list of
    ``(first_gid, rows)`` and stats counts segments/records/truncations.
    With ``truncate=True`` (recovery) torn tails are cut back to the last
    valid record boundary; with ``truncate=False`` (read-only audit) a torn
    tail raises :class:`WalCorruption`.
    """
    seqs = [s for s in segment_seqs(directory) if s >= from_seq]
    base = Path(directory)
    records: list[tuple[int, np.ndarray]] = []
    stats = {"segments": len(seqs), "records": 0, "truncated": 0}
    for s in seqs:
        path = base / _segment_name(s)
        size_before = path.stat().st_size
        recs = _read_segment(path, words=words, is_last=(s == seqs[-1]),
                             truncate=truncate, fs=fs) or []
        if truncate and path.exists() and path.stat().st_size < size_before:
            stats["truncated"] += 1
        records.extend(recs)
        stats["records"] += len(recs)
    return records, stats
