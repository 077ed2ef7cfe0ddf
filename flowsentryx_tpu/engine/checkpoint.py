"""Checkpoint/resume of the device-resident serving state.

The reference's only persistence is BPF map pinning under /sys/fs/bpf
(``src/Makefile:22``, ``TODO.md:289``) — kernel state survives loader
restarts, user state does not exist.  Here the TPU-plane state (per-IP
limiter/blacklist table + global stats + the t0 clock anchor) round-
trips through one ``.npz``, so a restarted engine resumes with every
tracked flow, window counter, and blacklist expiry intact — the
user-plane analog of map pinning.

Production-scale upgrades (PR 8):

* **Atomic + durable writes** — the snapshot publishes through
  :func:`flowsentryx_tpu.core.durable.atomic_write` (same-directory
  temp, fsync the bytes, atomic ``os.replace``, fsync the parent
  dir), so a crash mid-snapshot can never truncate the live
  checkpoint, and a POWER crash after ``save_state`` returns can
  never lose it either (the periodic ``--checkpoint-every`` loop
  overwrites the same path forever; a torn or un-synced write there
  would destroy the only copy).  The ``fsx crash`` model checker
  drives this exact code against a simulated fs at every crash point
  (docs/CRASH.md).
* **Geometry header** — ``hash_salt`` (as before) plus ``n_shards``
  and ``capacity``: a table's global row indices are meaningful ONLY
  under the geometry that wrote them (owner = top hash bits, slot =
  probed low bits), so the header is what lets a restore detect a
  mesh/capacity change and RESHARD
  (:func:`flowsentryx_tpu.engine.table.reshard_rows`) instead of
  silently mislocating every key.  Arrays stay the flat per-column
  global layout (shard-major when sharded — exactly what
  ``device_get`` of a row-sharded array yields), so every pre-header
  snapshot still loads (``n_shards`` defaults to 1).

Integrity + retention (PR 13, the chaos campaign's forcing function):

* **CRC32 integrity** — the snapshot carries one CRC32 folded over
  every header AND payload section (each entry's name + raw bytes, in
  sorted-name order).  A bit-flipped or torn file can therefore never
  be *silently* loaded: :func:`load_checkpoint` refuses with
  :class:`CheckpointCorrupt` — a named ``ValueError`` — whether the
  damage shows as a zip/zlib decode error, a missing member, or clean
  decompression of wrong bytes (the case only the CRC catches).
  Pre-CRC snapshots (no ``integrity_crc32`` member) still load; their
  ``crc_checked`` flag reads False so callers can tell "verified" from
  "grandfathered".
* **Previous-generation retention** — before the atomic
  ``os.replace`` publishes a new snapshot, the incumbent is rotated to
  ``<name>.prev`` (same-directory rename, atomic on POSIX).  Restore
  paths that hit a corrupt checkpoint fall back to that retained good
  generation LOUDLY (``Engine.restore``), instead of crashing — or
  worse, resuming from garbage — on the only copy.

Streamed save (the 3.5 GB snapshot of a 2^26-row table):

* The table leaves the device a column at a time (one program splits
  the ``[N, 12]`` matrix into its columns on the device; each column is
  cut into pieces of ``PIECE_BYTES`` on the device as it is sent, and a
  piece's transfer starts ``AHEAD`` pieces before it is written, so the
  first is not queued behind the rest, which arrive while the ones
  before them are written), so every piece is a contiguous run of host
  memory.  Nothing else of the table's size is made on the host: it
  holds the pieces in flight and, for a host-side table, one column's
  copy at a time.
* The ``.npz`` is uncompressed and written section by section into the
  publish's same-directory temp file (:func:`durable.publish_stream`),
  ``integrity_crc32`` last.  Each section's bytes pass through one
  CRC32, computed in pieces on a thread pool while the section is
  written and joined with :func:`crc32_combine`; the integrity fold and
  the zip member's own CRC (written into its local header once known)
  are both derived from it.  The fold's value is what it always
  was, so every older snapshot, compressed or not, still loads and
  verifies.

Overlapped save (the periodic policy's; ``Engine._checkpoint_due``):

* Only the boundary's state is taken on the dispatch thread, the
  *hold*: every launched step sunk, then one device program
  (:func:`snapshot`) that writes the key column and the twelve state
  columns into buffers of their own (the key copied too: the next step
  donates the live one), and the small stats fetched.  The dispatch
  thread then returns to serving.
* A thread of its own (``fsx-ckpt``) runs :func:`write_sections` over
  those device arrays — the fetch, the CRC, the write and the publish —
  while the engine serves on.  At most one save is in flight.  A device
  column leaves in pieces of ``PIECE_BYTES``: a transfer's host buffer
  is made ready with the interpreter lock held, so a small piece holds
  the serving threads up for a millisecond or two, where a column held
  them for its whole transfer.  The CRC pool keeps to ``CRC_THREADS``,
  leaving the serving path's threads and processes their cores.
* An explicit save (``Engine.checkpoint``) runs the same code on the
  calling thread and blocks.

(Plain npz rather than orbax: the state is a flat dict of arrays;
zero-dependency and byte-inspectable wins here.)
"""

from __future__ import annotations

import contextlib
import functools
import io
import os
import struct
import time
import zipfile
import zlib
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Any, NamedTuple

import numpy as np

from flowsentryx_tpu.core import durable, schema

CHECKPOINT_SCHEMA_VERSION = 1


class CheckpointCorrupt(ValueError):
    """A checkpoint file failed validation: empty, truncated, torn,
    bit-flipped (CRC mismatch), or structurally unreadable.  A
    ``ValueError`` subclass so existing ``except ValueError`` callers
    keep refusing loudly; the distinct type lets restore paths fall
    back to the retained ``.prev`` generation on corruption while
    still propagating genuine contract errors (schema/salt mismatch)."""


#: np.load errors that mean "this file is damaged", not "this file
#: disagrees with me": zip central-directory tears, zlib stream
#: corruption, short reads, struct decode failures on truncated
#: members.  (KeyError/IndexError cover a torn-at-create file whose
#: zip opens but whose members are absent or empty.)
_DAMAGE_ERRORS = (OSError, EOFError, zipfile.BadZipFile, zlib.error,
                  KeyError, IndexError, ValueError)


def _fold_crc(entries: dict) -> int:
    """CRC32 over every section, sorted by name: ``name bytes`` then
    the array's raw bytes.  Folding the NAMES in means a section
    swapped for another section's bytes (or dropped entirely at
    truncation) also mismatches — header and payload are both under
    the same checksum, per the chaos campaign's torn-write faults."""
    crc = 0
    for name in sorted(entries):
        arr = np.ascontiguousarray(np.asarray(entries[name]))
        crc = zlib.crc32(name.encode(), crc)
        crc = zlib.crc32(arr.tobytes(), crc)
    return crc & 0xFFFFFFFF


# -- CRC32 in pieces -----------------------------------------------------------

_CRC_POLY = 0xEDB88320  # CRC-32 (zlib's), bit-reflected


def _multmodp(a: int, b: int) -> int:
    """a·b modulo the CRC polynomial (zlib's ``multmodp``)."""
    m, p = 1 << 31, 0
    while True:
        if a & m:
            p ^= b
            if not a & (m - 1):
                return p
        m >>= 1
        b = (b >> 1) ^ _CRC_POLY if b & 1 else b >> 1


_X2N = [1 << 30]  # x^(2^n) modulo the polynomial, n = 0..31
for _ in range(31):
    _X2N.append(_multmodp(_X2N[-1], _X2N[-1]))


def crc32_combine(crc1: int, crc2: int, len2: int) -> int:
    """The CRC32 of ``A + B`` from ``crc32(A)``, ``crc32(B)`` and
    ``len(B)`` (zlib's ``crc32_combine``, which Python's zlib does not
    export)."""
    p, k, n = 1 << 31, 3, len2  # x^(8·len2)
    while n:
        if n & 1:
            p = _multmodp(_X2N[k & 31], p)
        n >>= 1
        k += 1
    return _multmodp(p, crc1) ^ crc2


#: bytes a CRC worker takes at a time (zlib.crc32 drops the GIL)
CRC_PIECE = 16 << 20


def _crc32_start(buf: memoryview, pool: ThreadPoolExecutor) -> list:
    """``zlib.crc32(buf)`` begun in pieces on ``pool``; :func:`_crc32_join`
    waits for it."""
    return [(len(piece), pool.submit(zlib.crc32, piece))
            for piece in (buf[i:i + CRC_PIECE]
                          for i in range(0, len(buf), CRC_PIECE))]


def _crc32_join(pieces: list) -> int:
    crc = 0
    for n, c in pieces:
        crc = crc32_combine(crc, c.result(), n)
    return crc


# -- an uncompressed zip64, written front to back --------------------------------

_LOCAL = struct.Struct("<4s2B4HL2L2H")
_LOCAL_CRC = 14  # the CRC32's offset in a local header
_CENTRAL = struct.Struct("<4s4B4HL2L5H2L")
_END64 = struct.Struct("<4sQ2H2L4Q")
_END64_LOCATOR = struct.Struct("<4sLQL")
_END = struct.Struct("<4s4H2LH")
_ZIP64_VERSION = 45
_DOS_DATE = 1 << 5 | 1  # 1980-01-01, the stamp np.savez gives its members
_FF = 0xFFFFFFFF


class _StoredZip:
    """The container ``np.savez`` writes — a zip of ``<name>.npy``
    members, stored — written front to back with each member's CRC32
    given by the caller, so that a section's bytes are read by one CRC
    and not by the zip's second one.  A member's CRC may follow its
    body (:meth:`crc`), written into its local header in place, as
    ``zipfile`` does on a seekable file.  Every size and offset goes in
    the zip64 fields."""

    def __init__(self, f):
        self.f = f
        self.pos = 0
        self.members: list[tuple[bytes, int, int, int]] = []

    def put(self, data) -> None:
        """Bytes of the member begun last."""
        self.f.write(data)
        self.pos += len(data)

    def begin(self, name: str, header: bytes, body_size: int,
              body_crc: int | None = None) -> None:
        """One ``.npy`` member's local header and ``.npy`` header; its
        ``body_size`` bytes of body follow by :meth:`put`.  Without
        ``body_crc``, :meth:`crc` gives it."""
        raw = f"{name}.npy".encode()
        size = len(header) + body_size
        crc = (0 if body_crc is None else
               crc32_combine(zlib.crc32(header), body_crc, body_size))
        extra = struct.pack("<2H2Q", 1, 16, size, size)
        self.members.append((raw, crc, size, self.pos))
        self._header = header
        self.put(_LOCAL.pack(b"PK\x03\x04", _ZIP64_VERSION, 0, 0, 0, 0,
                              _DOS_DATE, crc, _FF, _FF, len(raw),
                              len(extra)) + raw + extra + header)


    def crc(self, body_crc: int) -> None:
        """The last member's CRC, from its body's."""
        raw, _, size, at = self.members[-1]
        crc = crc32_combine(zlib.crc32(self._header), body_crc,
                            size - len(self._header))
        self.members[-1] = (raw, crc, size, at)
        self.f.seek(at + _LOCAL_CRC)
        self.f.write(struct.pack("<L", crc))
        self.f.seek(self.pos)

    def close(self) -> int:
        """The central directory and the zip64 end records; returns the
        archive's size in bytes."""
        start = self.pos
        for raw, crc, size, offset in self.members:
            extra = struct.pack("<2H3Q", 1, 24, size, size, offset)
            self.put(_CENTRAL.pack(
                b"PK\x01\x02", _ZIP64_VERSION, 3, _ZIP64_VERSION, 0, 0, 0,
                0, _DOS_DATE, crc, _FF, _FF, len(raw), len(extra), 0, 0,
                0, 0o600 << 16, _FF) + raw + extra)
        end64, n = self.pos, len(self.members)
        self.put(_END64.pack(b"PK\x06\x06", _END64.size - 12,
                              _ZIP64_VERSION, _ZIP64_VERSION, 0, 0, n, n,
                              end64 - start, start))
        self.put(_END64_LOCATOR.pack(b"PK\x06\x07", 0, end64, 1))
        self.put(_END.pack(b"PK\x05\x06", 0, 0, n, n, _FF, _FF, 0))
        return self.pos


def _npy_header(dtype: np.dtype, shape: tuple) -> bytes:
    """The ``.npy`` header of a C-ordered array of ``dtype`` and
    ``shape``: what ``np.save`` writes for it."""
    buf = io.BytesIO()
    np.lib.format.write_array_header_1_0(buf, {
        "descr": np.lib.format.dtype_to_descr(np.dtype(dtype)),
        "fortran_order": False, "shape": tuple(shape)})
    return buf.getvalue()


def prev_path(path: str | Path) -> Path:
    """The retained previous-generation twin of a checkpoint path
    (``snap.npz`` -> ``snap.npz.prev``), kept same-directory so the
    rotation rides the existing atomic ``os.replace``."""
    p = Path(path)
    if p.suffix != ".npz":
        p = p.with_suffix(p.suffix + ".npz")
    return p.with_name(p.name + ".prev")


class Checkpoint(NamedTuple):
    """A loaded snapshot, HOST-side (numpy): the caller owns placement
    (direct when the geometry matches, through
    :func:`~flowsentryx_tpu.engine.table.reshard_rows` when not)."""

    table: schema.IpTableState   # numpy leaves, global shard-major rows
    stats: schema.GlobalStats    # numpy [2] u32 pairs
    t0_ns: int
    hash_salt: int
    n_shards: int                # geometry the rows were laid out under
    capacity: int
    missing_columns: tuple       # table columns the snapshot predates
    missing_stats: tuple         # stats counters the snapshot predates
    #: False only for pre-CRC snapshots (grandfathered in unverified);
    #: any snapshot written since PR 13 carries ``integrity_crc32`` and
    #: loads only after the fold re-verifies.
    crc_checked: bool = True


class SaveSpans(NamedTuple):
    """Where :func:`write_sections` charges its time (each a
    :class:`~flowsentryx_tpu.engine.metrics.Span`, in turn on the thread
    that writes: the save thread for the policy's saves, the caller for
    :func:`save_state`): the device table's fetch (the wait for each
    piece), the CRC (the wait for each piece's, which runs beside its
    write, and the folds), the writes of the sections into the temp
    file, and the publish around them (fsync, ``.prev`` rotation,
    rename, fsync of the directory)."""

    fetch: Any
    crc: Any
    write: Any
    publish: Any


_NO_SPANS = SaveSpans(*(contextlib.nullcontext(),) * 3, None)


def _columns(state) -> tuple:
    return tuple(state[:, i] for i in range(state.shape[1]))


@functools.cache
def _split_columns():
    import jax

    return jax.jit(_columns)


#: bytes of a device section a transfer moves at a time.  A transfer's
#: host buffer is made ready with the interpreter lock held, at the rate
#: the host backs fresh pages (1.3 GB/s on a TPU v5e host), so a piece
#: this small holds the lock for a millisecond or two and stays in the
#: allocator's reused memory: with 2 MiB pieces the engine served on at
#: 95 % of its rate during a save of 2^26 rows, with 32 MiB pieces at
#: 37 %, with whole columns at 4 %
PIECE_BYTES = 2 << 20
#: device pieces cut and sent ahead of the one being written: a piece is
#: cut on the device behind the serving steps already queued there (up
#: to ``readback_depth`` groups, tens of ms), so 128 MiB in flight keep
#: the write fed
AHEAD = 64
#: threads that CRC a save's pieces.  Four keep ahead of the write (one
#: reads 1.75 GB/s, the write runs at 1.3), and leave the serving path's
#: own threads and processes (dispatch, sink, ingest workers, ``fsxd``,
#: the save's writer) the rest of a 13-core host
CRC_THREADS = 4


def device_columns(state) -> tuple:
    """The ``[N, C]`` state's columns as C device arrays of ``[N]``, each
    already on its way to the host (``copy_to_host_async``): a column
    then arrives at 2.8 GB/s on a TPU v5e host, where the ``[N, 12]``
    matrix, or its transpose, crosses at 0.5 GB/s and twice its size in
    host memory (the host untiles it).  One jitted program per shape."""
    cols = _split_columns()(state)
    for c in cols:
        c.copy_to_host_async()
    return cols


@functools.cache
def _snapshot_program():
    """The key column copied (a buffer of its own, which the next step's
    donation of the live key leaves alone) and the state split into its
    columns: one program per shape."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda key, state: (jnp.copy(key), _columns(state)))


@functools.cache
def _piece_program(rows: int):
    """``rows`` rows of a device section from a row given at run time:
    one program per piece size, whichever piece it cuts."""
    import jax
    from jax import lax

    return jax.jit(lambda a, start: lax.dynamic_slice_in_dim(a, start, rows))


def _parts(arr) -> list:
    """A device column as arrays of one device each, in row order: its
    shards (one of each row range, where a range is replicated), so that
    a piece is cut by a program of one device, which the save thread
    may launch beside the serving steps: a program over several devices
    launched from two threads could order their collectives differently
    on each device."""
    shards = {(s.index[0].start or 0): s.data for s in arr.addressable_shards}
    return [shards[start] for start in sorted(shards)]


def _cut(arr) -> list:
    """A section as the pieces it is sent in: a device column as
    ``(array, first row, rows)`` of each piece of ``PIECE_BYTES`` of each
    of its :func:`_parts`, cut on the device as the piece is sent
    (:func:`_make`); anything else whole."""
    if not hasattr(arr, "copy_to_host_async") or arr.ndim != 1:
        return [arr]
    step = max(PIECE_BYTES // arr.dtype.itemsize, 1)
    return [(part, a, min(step, part.shape[0] - a))
            for part in _parts(arr) for a in range(0, part.shape[0], step)]


def _make(piece):
    """A piece of :func:`_cut` as an array: a device piece cut (a piece
    that is a whole part is the part itself)."""
    if isinstance(piece, tuple):
        arr, start, rows = piece
        return (arr if rows == arr.shape[0]
                else _piece_program(rows)(arr, start))
    return piece


def warm_snapshot(table: schema.IpTableState) -> None:
    """Compile the snapshot of a device table of this shape and the cut
    of its pieces, so that the first save compiles nothing."""
    if not isinstance(table.state, np.ndarray):
        import jax

        key, columns = _fetch(table)
        # each device's first piece, and the last, if it is shorter
        pieces = [p for a in (key, columns[0]) for p in _cut(a)]
        jax.block_until_ready([
            _make(p) for i, p in enumerate(pieces)
            if i + 1 == len(pieces) or p[1] == 0])


def _fetch(table: schema.IpTableState) -> tuple[Any, Any]:
    """The key column and the state's columns: a device table's taken by
    one device program into buffers of their own, each sent to the host
    in pieces as the write comes near it (:func:`write_sections`); a
    host table's as strided views, each copied as it is written."""
    if isinstance(table.state, np.ndarray):
        return np.asarray(table.key), table.state.T
    return _snapshot_program()(table.key, table.state)


def _sections(key, columns, stats, t0_ns, hash_salt, n_shards) -> dict:
    """The snapshot's sections by name.  One array per column (not the
    in-memory [N, 12] matrix): the column-per-key format predates the
    matrix layout, keeps old snapshots loadable, and lets future columns
    default cleanly."""
    return {
        "table_key": key,
        **{f"table_{name}": columns[i]
           for i, name in enumerate(schema.TABLE_COLUMN_NAMES)},
        **{f"stats_{k}": np.asarray(v) for k, v in stats._asdict().items()},
        "t0_ns": np.uint64(t0_ns),
        "hash_salt": np.uint64(hash_salt),
        "n_shards": np.uint64(n_shards),
        "capacity": np.uint64(key.shape[0]),
        "schema_version": np.int64(CHECKPOINT_SCHEMA_VERSION),
    }


def snapshot(table: schema.IpTableState, stats: schema.GlobalStats,
             t0_ns: int, hash_salt: int = 0, n_shards: int = 1) -> dict:
    """The state as it stands now, as :func:`write_sections` takes it:
    the table in buffers of its own (a device table's by one program,
    dispatched and not waited for), the stats on the host.  What the
    engine does with the live table after this call does not reach the
    snapshot."""
    return _sections(*_fetch(table), stats, t0_ns, hash_salt, n_shards)


def save_state(
    path: str | Path,
    table: schema.IpTableState,
    stats: schema.GlobalStats,
    t0_ns: int,
    hash_salt: int = 0,
    n_shards: int = 1,
    spans: SaveSpans | None = None,
) -> Path:
    """Snapshot serving state ATOMICALLY (module docstring), on the
    calling thread.  Arrays are fetched from device (the one deliberate
    D2H of the engine's lifetime); ``hash_salt``/``n_shards`` record the
    geometry the slot layout was built under, so a restore can detect
    and reshard a geometry change instead of mislocating keys.  The file
    is streamed (module docstring); ``spans`` times its phases."""
    spans = spans or _NO_SPANS
    with spans.fetch:
        sections = snapshot(table, stats, t0_ns, hash_salt, n_shards)
    return write_sections(path, sections, spans)


def write_sections(path: str | Path, sections: dict,
                   spans: SaveSpans | None = None) -> Path:
    """Write :func:`snapshot`'s sections to ``path`` through the
    five-step publish, streamed (module docstring), and return the path
    written.  ``sections`` is emptied as it is taken: each piece is let
    go once it is written, so a device table's pieces free their memory
    one by one."""
    path = Path(path)
    # np.savez silently appends .npz to a suffix-less path; normalize so
    # the returned path is the file actually written (same contract as
    # models.logreg._npz_path).
    if path.suffix != ".npz":
        path = path.with_suffix(path.suffix + ".npz")
    spans = spans or _NO_SPANS
    # the integrity fold runs over the sections in sorted-name order
    # (_fold_crc), so they are written in that order; `flat` holds the
    # only references to the pieces
    meta, flat = [], []
    for name, arr in sorted(sections.items()):
        pieces = _cut(arr)
        meta.append((name, len(pieces), np.dtype(arr.dtype),
                     tuple(arr.shape)))
        flat.extend(pieces)
    sections.clear()
    del arr, pieces
    in_write = 0.0

    def send(i: int) -> None:
        # a device piece cut and its transfer started AHEAD pieces
        # before it is written: the first is not queued behind the rest,
        # which arrive while the pieces before them are written
        if i < len(flat):
            flat[i] = _make(flat[i])
            if hasattr(flat[i], "copy_to_host_async"):
                flat[i].copy_to_host_async()

    def write(f) -> None:
        nonlocal in_write
        t0 = time.perf_counter()
        out = _StoredZip(f)
        fold = i = 0
        for j in range(AHEAD):
            send(j)
        with ThreadPoolExecutor(
                min(CRC_THREADS, os.cpu_count() or 1)) as pool:
            for name, n, dtype, shape in meta:
                size = dtype.itemsize * int(np.prod(shape, dtype=np.int64))
                with spans.write:
                    out.begin(name, _npy_header(dtype, shape), size)
                crc = 0
                for _ in range(n):
                    send(i + AHEAD)
                    # a device piece waits here for its transfer, a host
                    # table's column is copied: one at a time, each let
                    # go once it is written
                    with spans.fetch:
                        arr = np.asarray(flat[i], order="C")
                    flat[i] = None
                    i += 1
                    body = memoryview(arr).cast("B")
                    # the CRC reads the body on the pool while it is
                    # written
                    crcs = _crc32_start(body, pool)
                    with spans.write:
                        out.put(body)
                    with spans.crc:
                        crc = crc32_combine(crc, _crc32_join(crcs),
                                            len(body))
                    del arr, body
                with spans.crc:
                    out.crc(crc)
                    fold = crc32_combine(zlib.crc32(name.encode(), fold),
                                         crc, size)
        with spans.write:
            tail = np.uint32(fold & 0xFFFFFFFF).tobytes()
            out.begin("integrity_crc32", _npy_header(np.uint32, ()),
                      len(tail), zlib.crc32(tail))
            out.put(tail)
            out.close()
        in_write = time.perf_counter() - t0

    # the five-step publish fsyncs the bytes then the rename, and
    # retains the incumbent GOOD generation at .prev before publishing:
    # a later restore that finds `path` corrupt (torn disk, bit flip)
    # falls back to .prev instead of dying on the only copy.
    t0 = time.perf_counter()
    durable.publish_stream(path, write, rotate_prev=prev_path(path))
    if spans.publish is not None:
        spans.publish.add(time.perf_counter() - t0 - in_write)
    return path


class CheckpointPolicy:
    """Save the serving state to ``path`` every ``every_s`` seconds
    while the engine serves (``Engine.set_checkpoint_policy``; ``fsx
    serve --checkpoint-every``), so that a crash of the engine loses at
    most one interval of flow memory.

    The saves fall due on a grid of the host clock (``time.perf_counter``)
    that the engine starts when it takes the policy: the first
    ``first_s`` later (default ``every_s``), then one every ``every_s``,
    across ``run()`` calls.  The engine takes the save's state at the
    first group boundary after a slot falls due, on the dispatch thread,
    and writes it on the save thread while it serves on (module
    docstring); a slot that falls due while a save is in flight, or
    passes while nothing serves, is skipped and counted, not made up.
    The counters live here and are the engine report's ``checkpoint``
    block.  The save thread counts a save (:meth:`saved`) when its
    publish returns; the dispatch thread reads :attr:`next_at` only
    while no save is in flight, and the report only once the engine
    has joined the save."""

    def __init__(self, path: str | Path, every_s: float,
                 first_s: float | None = None):
        if every_s <= 0:
            raise ValueError(f"every_s must be > 0, got {every_s}")
        self.path = str(path)
        self.every_s = float(every_s)
        self.first_s = self.every_s if first_s is None else float(first_s)
        self.first_at = float("inf")
        self.next_slot = 0
        self.saves = self.skipped = self.bytes = 0
        #: the last save: when it began (host clock, the boundary) and
        #: took to its publish, the bytes written, and the point of the
        #: stream it holds; the policy's saves add how long they held the
        #: dispatch thread (``hold_s``) and the records it emitted
        #: between the boundary and the publish (``records_during``)
        self.last: dict | None = None

    def start(self, now: float) -> None:
        """Lay the grid from ``now``."""
        self.first_at = now + self.first_s

    @property
    def next_at(self) -> float:
        return self.first_at + self.next_slot * self.every_s

    def saved(self, at: float, took_s: float, nbytes: int,
              point: dict) -> None:
        """Count the save that served the due slot; every later slot
        that has passed by its end is skipped."""
        end = at + took_s
        after = int((end - self.first_at) // self.every_s) + 1
        self.skipped += max(after - self.next_slot - 1, 0)
        self.next_slot = max(after, self.next_slot + 1)
        self.saves += 1
        self.bytes += nbytes
        self.last = {"at": at, "seconds": took_s, "bytes": nbytes, **point}

    def report(self) -> dict:
        return {"path": self.path, "every_s": self.every_s,
                "saves": self.saves, "bytes": self.bytes,
                "skipped": self.skipped, "last": self.last}


def peek_header(path: str | Path) -> dict:
    """The geometry header WITHOUT loading the arrays — salt, shard
    count, capacity, schema version — so servers and the CLI can
    validate (or plan a reshard) before the multi-second JAX boot.
    Pre-header snapshots read as salt 0 / 1 shard; capacity falls back
    to the key column's length.

    A zero-length, truncated, or otherwise unreadable file raises
    :class:`CheckpointCorrupt` (a named ``ValueError``) — previously a
    file torn at create time leaked a raw struct/IndexError through
    the pre-boot validation path, which read as a code bug instead of
    the operational fact it is."""
    path = Path(path)
    fs = durable.get_fs()
    try:
        size = fs.size(path)
    except OSError as e:
        raise CheckpointCorrupt(
            f"checkpoint {path} is unreadable: {e}") from e
    if size == 0:
        raise CheckpointCorrupt(
            f"checkpoint {path} is empty (0 bytes): a file torn at "
            "create time, not a snapshot")
    try:
        with np.load(io.BytesIO(fs.read_bytes(path))) as z:
            cap = (int(z["capacity"]) if "capacity" in z
                   else int(z["table_key"].shape[0]))
            return {
                "schema_version": int(z["schema_version"]),
                "hash_salt": (int(z["hash_salt"])
                              if "hash_salt" in z else 0),
                "n_shards": int(z["n_shards"]) if "n_shards" in z else 1,
                "capacity": cap,
                "has_crc": "integrity_crc32" in z,
            }
    except CheckpointCorrupt:
        raise
    except _DAMAGE_ERRORS as e:
        raise CheckpointCorrupt(
            f"checkpoint {path} is corrupt or truncated "
            f"({size} bytes): {type(e).__name__}: {e}") from e


def peek_salt(path: str | Path) -> int:
    """The hash salt a checkpoint's table was built under, WITHOUT
    loading the arrays — so a server can adopt it before compiling its
    step (pre-salt checkpoints read as 0, the unsalted hash)."""
    return peek_header(path)["hash_salt"]


def load_checkpoint(path: str | Path) -> Checkpoint:
    """Load a snapshot to HOST arrays (placement is the caller's job —
    see :class:`Checkpoint`).  Columns or stats counters added after
    the snapshot was written load zero-filled and are named in the
    ``missing_*`` fields so the caller can apply the right default
    (e.g. ``Engine.restore`` refills byte-bucket credit).

    Integrity (module docstring): every member is decompressed and the
    folded CRC32 recomputed; a mismatch — or any structural damage on
    the way in — raises :class:`CheckpointCorrupt`.  A corrupt file
    can therefore never be silently loaded.  Snapshots predating the
    CRC load with ``crc_checked=False``."""
    path = Path(path)
    fs = durable.get_fs()
    entries: dict[str, np.ndarray] = {}
    stored_crc = None
    try:
        if fs.size(path) == 0:
            raise CheckpointCorrupt(
                f"checkpoint {path} is empty (0 bytes)")
        with np.load(io.BytesIO(fs.read_bytes(path))) as z:
            for name in z.files:
                if name == "integrity_crc32":
                    stored_crc = int(z[name])
                else:
                    entries[name] = np.asarray(z[name])
        if "schema_version" not in entries or "table_key" not in entries:
            raise CheckpointCorrupt(
                f"checkpoint {path} is missing its "
                "schema_version/table_key sections (torn write?)")
    except CheckpointCorrupt:
        raise
    except _DAMAGE_ERRORS as e:
        raise CheckpointCorrupt(
            f"checkpoint {path} is corrupt or truncated: "
            f"{type(e).__name__}: {e}") from e
    if stored_crc is not None:
        actual = _fold_crc(entries)
        if actual != stored_crc:
            raise CheckpointCorrupt(
                f"checkpoint {path} failed its integrity check: "
                f"stored CRC32 {stored_crc:#010x} != recomputed "
                f"{actual:#010x} — the bytes decompressed cleanly but "
                "are not the bytes that were written (bit flip or "
                "spliced sections); refusing to resume from garbage")
    version = int(entries["schema_version"])
    if version != CHECKPOINT_SCHEMA_VERSION:
        raise ValueError(
            f"checkpoint schema {version} != {CHECKPOINT_SCHEMA_VERSION}"
        )
    cap = int(entries["table_key"].shape[0])
    state = np.zeros((cap, schema.NUM_TABLE_COLS), np.float32)
    missing = []
    for i, name in enumerate(schema.TABLE_COLUMN_NAMES):
        if f"table_{name}" in entries:
            state[:, i] = entries[f"table_{name}"]
        else:
            missing.append(name)
    missing_stats = []
    stats_vals = {}
    for k in schema.GlobalStats._fields:
        if f"stats_{k}" in entries:
            stats_vals[k] = np.asarray(entries[f"stats_{k}"])
        else:
            # a counter added after the snapshot (e.g. ``evicted``
            # on pre-eviction-era snapshots): zero is the correct
            # resume value for a monotone counter
            stats_vals[k] = np.zeros((2,), np.uint32)
            missing_stats.append(k)
    return Checkpoint(
        table=schema.IpTableState(
            key=np.asarray(entries["table_key"]), state=state),
        stats=schema.GlobalStats(**stats_vals),
        t0_ns=int(entries["t0_ns"]),
        hash_salt=(int(entries["hash_salt"])
                   if "hash_salt" in entries else 0),
        n_shards=(int(entries["n_shards"])
                  if "n_shards" in entries else 1),
        capacity=cap,
        missing_columns=tuple(missing),
        missing_stats=tuple(missing_stats),
        crc_checked=stored_crc is not None,
    )


def load_state(
    path: str | Path,
) -> tuple[schema.IpTableState, schema.GlobalStats, int, int, tuple]:
    """Compatibility shim over :func:`load_checkpoint`: the historical
    5-tuple, with table/stats already on the default device.  The ONE
    jax touch in this module, imported lazily — everything else is
    host-side numpy, which is what lets the supervisor plane and the
    ``fsx crash`` checker drive the real checkpoint protocol on the
    sub-second jax-free import path."""
    import jax

    ck = load_checkpoint(path)
    table = schema.IpTableState(key=jax.device_put(ck.table.key),
                                state=jax.device_put(ck.table.state))
    stats = schema.GlobalStats(
        *(jax.device_put(v) for v in ck.stats))
    return table, stats, ck.t0_ns, ck.hash_salt, ck.missing_columns
