"""The plain pieces of a cell whose sources are keyed by all 128 bits
(``table.key_bits`` 128): in numpy, sharing no code with
``flowsentryx_tpu/``.

* the 64 B ring record and the 32 B verdict record, as the daemon writes
  them (``kern/fsx_schema.h`` ``struct fsx_flow_record6`` and
  ``struct fsx_verdict_record6``): the source is the address's 16 wire
  bytes read as four little-endian u32 words, an IPv4 source carried as
  ``::ffff:a.b.c.d`` (RFC 4291 §2.5.5.2);
* the seal of such records to the 7-word row: the four key words, then
  the compact16 words 1-3 of :func:`reference.quantise_records`;
* :class:`Rename`: an injective map of the run's 128-bit keys onto u32
  ids, which is how the reference (it keys a source by one word and uses
  a key only as an identity) judges a 128-bit run exactly;
* the resident population's full sources, and the bytes a batch needs
  for :func:`step_bytes`.
"""

from __future__ import annotations

import numpy as np

from benchmark import reference, trafficgen

FLOW_RECORD6 = np.dtype([
    ("ts_ns", "<u8"), ("saddr6", "<u4", (4,)), ("pkt_len", "<u2"),
    ("ip_proto", "u1"), ("flags", "u1"), ("feat", "<u4", (8,)),
    ("_pad", "<u4"),
])
assert FLOW_RECORD6.itemsize == 64

VERDICT_RECORD6 = np.dtype([
    ("saddr6", "<u4", (4,)), ("until_ns", "<u8"), ("_pad", "<u8"),
])
assert VERDICT_RECORD6.itemsize == 32

#: words 0-2 of ``::ffff:0:0/96``, as wire words
V4_MAPPED = (0, 0, 0xFFFF0000)
#: the IPv6 flag of a record (``FSX_FLAG_IPV6``)
FLAG_IPV6 = 1

#: the schema's widths: the 7-word row, one table row of a 16 B key and
#: 12 f32 columns, the verdict word out
WIRE_RECORD_BYTES = 7 * 4
TABLE_ROW_BYTES = 16 + 12 * 4
VERDICT_BYTES = 4


def step_bytes(n_records: int) -> int:
    """HBM bytes the step needs for ``n_records`` at 128-bit keys: the
    row in, one table row read and one written a record, the verdict
    word out (``peaks.step_bytes``'s count at these widths)."""
    return n_records * (WIRE_RECORD_BYTES + 2 * TABLE_ROW_BYTES
                        + VERDICT_BYTES)


def is_v4_mapped(key: np.ndarray) -> np.ndarray:
    key = np.asarray(key)
    return ((key[:, 0] == V4_MAPPED[0]) & (key[:, 1] == V4_MAPPED[1])
            & (key[:, 2] == V4_MAPPED[2]))


def fold(key: np.ndarray) -> np.ndarray:
    """The 32-bit XOR fold of ``[n, 4]`` keys (``kern/parsing.h``
    ``fsx_fold_ip6``); an IPv4 source folds to its own address, as the
    48 B record carries it."""
    key = np.asarray(key, np.uint32)
    f = key[:, 0] ^ key[:, 1] ^ key[:, 2] ^ key[:, 3]
    return np.where(is_v4_mapped(key), key[:, 3], f).astype(np.uint32)


def widen(rec: np.ndarray, keys: np.ndarray) -> np.ndarray:
    """48 B records as 64 B ones with ``keys`` ``[n, 4]`` as sources."""
    out = np.zeros(len(rec), FLOW_RECORD6)
    for f in ("ts_ns", "pkt_len", "ip_proto", "feat"):
        out[f] = rec[f]
    out["saddr6"] = keys
    out["flags"] = rec["flags"] | np.where(is_v4_mapped(keys), 0,
                                           FLAG_IPV6).astype(np.uint8)
    return out


def quantise_records(rec: np.ndarray, model: dict,
                     base_ns: int) -> np.ndarray:
    """64 B records -> ``[n, 7]`` rows: the key words, then the
    compact16 words 1-3."""
    narrow = np.zeros(len(rec), trafficgen.FLOW_RECORD)
    for f in ("ts_ns", "pkt_len", "ip_proto", "flags", "feat"):
        narrow[f] = rec[f]
    out = np.empty((len(rec), 7), np.uint32)
    out[:, :4] = rec["saddr6"]
    out[:, 4:] = reference.quantise_records(narrow, model, base_ns)[:, 1:]
    return out


def background_keys(bg: trafficgen.Background, start: int, n: int,
                    v6_fraction: float, seed: int) -> np.ndarray:
    """The full sources of resident sources ``start .. start+n``: the
    first ``round(v6_fraction x bg.n)`` are IPv6 /128s in one seeded
    2000::/3 /64 (their last word the source's distinct 32-bit address,
    their third a seeded hash of it), the rest ``::ffff:`` of it."""
    addr = bg.keys(start, n)
    n6 = int(round(v6_fraction * bg.n))
    rng = np.random.default_rng([seed, 0x6B])
    w0, w1, mult = (int(x) for x in rng.integers(0, 1 << 32, 3,
                                                 dtype=np.uint64))
    out = np.empty((len(addr), 4), np.uint32)
    out[:, :3] = V4_MAPPED
    v6 = np.arange(start, start + len(addr)) < n6
    out[v6, 0] = (w0 & ~0xE0) | 0x20
    out[v6, 1] = w1
    with np.errstate(over="ignore"):
        out[v6, 2] = addr[v6] * np.uint32(mult | 1) ^ np.uint32(w1)
    out[:, 3] = addr
    return out


def _hash64(key: np.ndarray) -> np.ndarray:
    """A 64-bit mix of ``[n, 4]`` keys (splitmix64 over the two halves);
    :class:`Rename` checks every key against its class, so a collision
    can only refuse, never merge."""
    k = np.ascontiguousarray(key, np.uint32).view(np.uint64)
    with np.errstate(over="ignore"):
        h = k[:, 0] * np.uint64(0x9E3779B97F4A7C15) ^ k[:, 1]
        h ^= h >> np.uint64(30)
        h *= np.uint64(0xBF58476D1CE4E5B9)
        h ^= h >> np.uint64(27)
        h *= np.uint64(0x94D049BB133111EB)
        h ^= h >> np.uint64(31)
    return h


class Rename:
    """An injective map of the 128-bit keys in ``keys`` ``[n, 4]`` onto
    u32 ids 1 .. (distinct keys): never 0, 0xFFFFFFFE or 0xFFFFFFFF (the
    reference's sentinel rule).  A key it was not built from is named
    past every id it gives, one id a distinct key, so it pairs with
    nothing the reference saw."""

    def __init__(self, keys: np.ndarray):
        keys = np.ascontiguousarray(keys, np.uint32).reshape(-1, 4)
        h = _hash64(keys)
        self.hash, first, inv = np.unique(h, return_index=True,
                                          return_inverse=True)
        self.keys = keys[first]
        if not (self.keys[inv.reshape(-1)] == keys).all():
            raise SystemExit("benchmark: two 128-bit keys share a 64-bit "
                             "hash; the rename cannot be exact")
        self.ids = inv.reshape(-1).astype(np.uint32) + np.uint32(1)
        if len(self.hash) >= 0xFFFFFFFE - 1:
            raise SystemExit("benchmark: too many keys to rename")

    def __call__(self, key: np.ndarray) -> np.ndarray:
        key = np.ascontiguousarray(key, np.uint32).reshape(-1, 4)
        h = _hash64(key)
        pos = np.minimum(np.searchsorted(self.hash, h), len(self.hash) - 1)
        known = (self.hash[pos] == h) & (self.keys[pos] == key).all(axis=1)
        out = pos.astype(np.uint32) + np.uint32(1)
        if not known.all():
            _, other = np.unique(key[~known], axis=0, return_inverse=True)
            out[~known] = (np.uint32(len(self.hash) + 1)
                           + other.reshape(-1).astype(np.uint32))
        return out
