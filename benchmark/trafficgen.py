"""Seeded flow-record generator: the benchmark's own copy of what it
needs of ``flowsentryx_tpu/engine/traffic.py::TrafficGen`` (kept here so
that a change to the program cannot move the traffic).

Records are the 48 B kernel -> user flow record: attack flows carry flood
statistics (small uniform packets, µs arrivals), benign flows
interactive ones, on a synthetic clock at ``rate_pps``.  Everything comes
from the parameters of a workload file and the seed.

:class:`Background` is the table's resident population: the sources a
deployment of this size already holds when the measured traffic arrives
(``traffic.background`` of a configuration file).  Each sends one benign
record in set-up, through the program's own path, and is then silent.
"""

from __future__ import annotations

import numpy as np

#: the 48 B flow record (kern/fsx_schema.h ``struct fsx_flow_record``)
FLOW_RECORD = np.dtype([
    ("ts_ns", "<u8"), ("saddr", "<u4"), ("pkt_len", "<u2"),
    ("ip_proto", "u1"), ("flags", "u1"), ("feat", "<u4", (8,)),
])
assert FLOW_RECORD.itemsize == 48


def _feat(rng, n: int, attack: bool, ports) -> np.ndarray:
    f = np.zeros((n, 8), np.uint32)
    f[:, 0] = rng.choice(ports, n)
    if attack:
        f[:, 1] = rng.integers(60, 80, n)
        f[:, 2] = rng.integers(0, 3, n)
        iat = rng.integers(1, 50, n)
        npkts = rng.integers(100, 5000, n).astype(np.uint64)
    else:
        f[:, 1] = rng.integers(100, 1500, n)
        f[:, 2] = rng.integers(100, 600, n)
        iat = rng.integers(5_000, 500_000, n)
        npkts = rng.integers(2, 200, n).astype(np.uint64)
    dur_us = np.maximum(iat.astype(np.uint64) * npkts, 1)
    f[:, 3] = dur_us // 1000
    f[:, 4] = np.minimum(npkts * np.uint64(1_000_000_000) // dur_us,
                         0xFFFFFFFF)
    f[:, 5] = iat
    if attack:
        f[:, 6] = rng.integers(0, 20, n)
        f[:, 7] = iat * rng.integers(1, 4, n)
    else:
        f[:, 6] = iat // rng.integers(1, 4, n)
        f[:, 7] = iat * rng.integers(2, 8, n)
    return f


def flow_records(p: dict, seed: int) -> np.ndarray:
    """``p["array_records"]`` records of a two-pool attack/benign mix.

    Parameters: ``n_attack_ips``, ``n_benign_ips``, ``attack_fraction``,
    ``rate_pps`` (record clock), ``protos``, ``attack_ports``,
    ``benign_ports``."""
    rng = np.random.default_rng(seed)
    n = int(p["array_records"])
    attack_ips = rng.integers(1, 1 << 24, p["n_attack_ips"], dtype=np.uint32)
    benign_ips = (rng.integers(0, 1 << 24, p["n_benign_ips"],
                               dtype=np.uint32) + np.uint32(1 << 24))
    buf = np.zeros(n, FLOW_RECORD)
    is_attack = rng.random(n) < p["attack_fraction"]
    na = int(is_attack.sum())
    feat = np.zeros((n, 8), np.uint32)
    feat[is_attack] = _feat(rng, na, True, p["attack_ports"])
    feat[~is_attack] = _feat(rng, n - na, False, p["benign_ports"])
    buf["feat"] = feat
    buf["saddr"][is_attack] = rng.choice(attack_ips, na)
    buf["saddr"][~is_attack] = rng.choice(benign_ips, n - na)
    buf["ip_proto"] = rng.choice(p["protos"], n)
    buf["pkt_len"] = np.where(is_attack, rng.integers(60, 80, n),
                              rng.integers(100, 1500, n))
    dt_ns = max(1, int(1e9 / p["rate_pps"]))
    buf["ts_ns"] = 1_000_000_000 + np.arange(n, dtype=np.uint64) * dt_ns
    return buf


#: background addresses live in [2^26, 2^27): above both traffic pools
#: ([1, 2^24) attack, [2^24, 2^25) benign), clear of the reserved keys
BG_BASE = 1 << 26


class Background:
    """``spec["sources"]`` distinct resident sources, one benign record
    each, as a pure function of (spec, seed, index) — so that the driver
    hands them over and the reference regenerates them without either
    keeping 48 B a source.  Addresses are an odd-multiplier walk of
    [2^26, 2^27), features a seeded block of ``spec["block"]`` benign
    flows repeated."""

    def __init__(self, spec: dict, seed: int):
        self.n = int(spec["sources"])
        if not 0 < self.n <= BG_BASE:
            raise SystemExit("benchmark: background.sources out of range")
        rng = np.random.default_rng([seed, 0xB6])
        self.mult = int(rng.integers(0, 1 << 25)) * 2 + 1
        self.off = int(rng.integers(0, BG_BASE))
        blk = int(spec.get("block", 1 << 16))
        self.feat = _feat(rng, blk, False, spec.get("ports", [443]))
        self.pkt_len = rng.integers(100, 1500, blk).astype(np.uint16)

    def keys(self, start: int, n: int) -> np.ndarray:
        idx = np.arange(start, start + n, dtype=np.uint64)
        walk = (idx * np.uint64(self.mult) + np.uint64(self.off)) \
            & np.uint64(BG_BASE - 1)
        return (walk + np.uint64(BG_BASE)).astype(np.uint32)

    def records(self, start: int, n: int, t0_ns: int,
                dt_ns: int) -> np.ndarray:
        """Background records ``start .. start+n`` (clipped to the
        population), record ``i`` stamped ``t0_ns + i * dt_ns``."""
        n = max(0, min(n, self.n - start))
        buf = np.zeros(n, FLOW_RECORD)
        pos = np.arange(start, start + n) % len(self.feat)
        buf["saddr"] = self.keys(start, n)
        buf["feat"] = self.feat[pos]
        buf["pkt_len"] = self.pkt_len[pos]
        buf["ip_proto"] = 6
        buf["ts_ns"] = np.uint64(t0_ns) + np.arange(
            start, start + n, dtype=np.uint64) * np.uint64(dt_ns)
        return buf
