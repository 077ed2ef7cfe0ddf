"""Persistent AOT executable store: boot-to-serving without the
recompile.

Every ``Engine.warm()`` used to pay the full XLA compile for every
staged variant — singles, each coalescing-ladder rung, the eviction
epochs folded into each — seconds of wall per boot, paid
again by every crash-respawn and every elastic GROW spare while the
burst it was spawned for is already landing.  The compile is a pure
function of the staged shape and the toolchain, so it is paid ONCE:
``jit_fn.lower(*abstract_args).compile()`` produces an executable that
``jax.experimental.serialize_executable`` round-trips through bytes,
and later boots of the same shape deserialize it in tens of
milliseconds instead of recompiling (measured on the smoke geometry:
~1.4 s compile vs ~70 ms load per mega/ring variant —
``scripts/boot_smoke.py`` re-proves the ratio per verify run).

The key discipline is the repo's ONE staged-shape signature
(:func:`flowsentryx_tpu.core.signature.staging_signature` — the same
rule the audit boot cache keys on), with the toolchain layered on top
in each entry's header: jax / jaxlib versions, backend and its
platform version.  A serialized executable is only valid for the
exact toolchain that produced it, but a version bump must read as
*drift* (an ops-visible counter), not as a crash and not as silence.

Fail-open is the contract: any miss, version drift, corrupt entry, or
serialization failure recompiles through the live jit path,
loudly-counted in :meth:`CompileCache.report` (surfaced in
``EngineReport.boot`` and ``fsx monitor --alert-cold-boot``) — the
cache accelerates boots, it never refuses one.

Entry format (one file per (signature, variant))::

    b"FSXAOT1\\n"                      magic
    <u32 little-endian header length>
    <header JSON: sig digest, variant, jax/jaxlib/backend versions,
                  ids of the devices it was compiled for>
    <u32 little-endian CRC32 of the blob>
    <blob: pickle of (payload, in_tree, out_tree) from serialize()>

Entries publish through :func:`core.durable.atomic_write` (the
``durable_writes`` lint scope covers this module): a crash mid-store
leaves the previous complete entry or none — never a torn file that
a later boot would have to CRC-reject.
"""

from __future__ import annotations

import io
import json
import os
import pickle
import struct
import sys
import time
import zlib
from pathlib import Path
from typing import Any

import jax
from jax.experimental.serialize_executable import (
    deserialize_and_load, serialize,
)

from flowsentryx_tpu.core import durable
from flowsentryx_tpu.core.signature import signature_digest

MAGIC = b"FSXAOT1\n"


def toolchain_versions() -> dict:
    """The toolchain fields a serialized executable is only valid
    under — compared header-vs-live at load, mismatch counted as
    ``version_drift`` (distinct from miss and corrupt: a silent
    fleet-wide cold boot after an upgrade is an ops event)."""
    try:
        import jaxlib

        jaxlib_v = getattr(jaxlib, "__version__", "unknown")
    except Exception:  # pragma: no cover - jaxlib ships with jax
        jaxlib_v = "unknown"
    try:
        platform_v = str(jax.devices()[0].client.platform_version)
    except Exception:
        platform_v = "unknown"
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib_v,
        "backend": jax.default_backend(),
        "platform_version": platform_v,
    }


class CompileCache:
    """On-disk AOT executable store for one staged shape.

    One instance serves one engine boot: the signature is fixed at
    construction, entries are addressed by ``(digest, variant)``, and
    the counters tell the boot's whole cache story — ``hits`` loaded
    executables, ``misses`` absent entries, ``corrupt`` CRC/decode
    refusals, ``version_drift`` toolchain mismatches, ``stores``
    published entries.  Used by at most one thread at a time by
    protocol: the quiescent warm pass first, then the background warm
    fill thread it hands off to (sync registry: the engine's
    ``_cache`` reference is never rebound)."""

    def __init__(self, root: str | Path, sig: dict):
        self.root = Path(root)
        self.sig = sig
        self.digest = signature_digest(sig)
        self.versions = toolchain_versions()
        self.hits = 0
        self.misses = 0
        self.corrupt = 0
        self.version_drift = 0
        self.stores = 0
        self.store_errors = 0

    def path(self, variant: str) -> Path:
        return self.root / f"{self.digest[:20]}-{variant}.aot"

    # -- load (fail-open) ---------------------------------------------------

    def load(self, variant: str) -> Any | None:
        """Deserialize-and-load the entry for ``variant``; None on any
        miss/drift/corruption (counted — the caller recompiles)."""
        p = self.path(variant)
        try:
            data = durable.get_fs().read_bytes(p)
        except (OSError, KeyError):
            self.misses += 1
            return None
        try:
            if data[: len(MAGIC)] != MAGIC:
                raise ValueError("bad magic")
            off = len(MAGIC)
            (hlen,) = struct.unpack_from("<I", data, off)
            off += 4
            header = json.loads(data[off:off + hlen].decode())
            off += hlen
            (crc,) = struct.unpack_from("<I", data, off)
            off += 4
            blob = data[off:]
            if zlib.crc32(blob) & 0xFFFFFFFF != crc:
                raise ValueError("CRC mismatch")
        except Exception as e:
            self.corrupt += 1
            print(f"fsx compile-cache: corrupt entry {p.name} ({e}); "
                  "recompiling (fail-open)", file=sys.stderr)
            return None
        if header.get("sig_digest") != self.digest:
            # filename-prefix collision with a different shape: not our
            # entry — a plain miss, the store below will overwrite
            self.misses += 1
            return None
        if header.get("versions") != self.versions:
            self.version_drift += 1
            print(f"fsx compile-cache: toolchain drift on {p.name} "
                  f"(entry {header.get('versions')} vs live "
                  f"{self.versions}); recompiling (fail-open)",
                  file=sys.stderr)
            return None
        try:
            # load onto the devices the executable was compiled for
            # (recorded at store): left to its default, JAX loads over
            # EVERY local device and a one-device step then fails its
            # first call on any host that has more than one
            by_id = {d.id: d for d in jax.devices()}
            devices = [by_id[i] for i in header["device_ids"]]
            payload, in_tree, out_tree = pickle.loads(blob)
            exe = deserialize_and_load(payload, in_tree, out_tree,
                                       execution_devices=devices)
        except Exception as e:
            self.corrupt += 1
            print(f"fsx compile-cache: entry {p.name} failed to "
                  f"deserialize ({e!r}); recompiling (fail-open)",
                  file=sys.stderr)
            return None
        self.hits += 1
        return exe

    # -- store (atomic publish, never raises) -------------------------------

    def store(self, variant: str, compiled: Any) -> bool:
        """Serialize ``compiled`` and publish its entry atomically.
        Best-effort: a failure is counted and announced, never raised —
        the executable in memory still serves this boot."""
        try:
            payload, in_tree, out_tree = serialize(compiled)
            blob = pickle.dumps((payload, in_tree, out_tree))
            header = json.dumps({
                "sig_digest": self.digest,
                "variant": variant,
                "versions": self.versions,
                "device_ids": [
                    d.id for d in
                    compiled.runtime_executable().local_devices()],
                "created_s": round(time.time(), 3),
            }).encode()
            buf = io.BytesIO()
            buf.write(MAGIC)
            buf.write(struct.pack("<I", len(header)))
            buf.write(header)
            buf.write(struct.pack("<I", zlib.crc32(blob) & 0xFFFFFFFF))
            buf.write(blob)
            os.makedirs(self.root, exist_ok=True)
            durable.atomic_write(self.path(variant), buf.getvalue())
        except Exception as e:
            self.store_errors += 1
            print(f"fsx compile-cache: failed to store {variant} "
                  f"({e!r}); this boot serves from memory, the next "
                  "one recompiles", file=sys.stderr)
            return False
        self.stores += 1
        return True

    def report(self) -> dict:
        """The boot's cache story (``EngineReport.boot["cache"]``)."""
        return {
            "dir": str(self.root),
            "sig_digest": self.digest[:20],
            "hits": self.hits,
            "misses": self.misses,
            "corrupt": self.corrupt,
            "version_drift": self.version_drift,
            "stores": self.stores,
            "store_errors": self.store_errors,
        }
