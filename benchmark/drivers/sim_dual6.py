"""Traffic driver ``sim_dual6``: ``sim_paced_vring`` for a table keyed by
the full 128-bit source (``table.key_bits`` 128).

``fsxd --sim --key-bits 128`` writes 64 B records (``key128.FLOW_RECORD6``)
into the rings and reads 32 B verdicts; ``--v6-fraction`` of each pool is
IPv6, and ``--fold-collide`` IPv6 attackers take the XOR fold of a
distinct benign IPv4 source, so that a plane keyed by the fold would
share their rows, votes and blocks.  The ingest workers seal 7-word rows
(the key's four words, then compact16's words 1-3).

The reference keys a source by one word and uses a key only as an
identity, so the judge is handed every dispatched batch as ``[n, 4]``:
column 0 the source's id under one injective map of the run's 128-bit
keys (``key128.Rename``), the three feature words as sealed.  The sink
hands over its blocks under the same map.  What the transport owes is
checked on the 128-bit records themselves: the ring's last records
quantised again against the sealed rows, bit for bit, and the verdict
ring's 32 B records against the blocks the engine handed to the sink.
"""

from __future__ import annotations

import subprocess
import time

import numpy as np

from benchmark import harness, key128, trafficgen

sim_paced_vring = harness.load_module("drivers", "sim_paced_vring")
sim_paced = sim_paced_vring.sim_paced


class SinkTap6(harness.SinkTap):
    """The sink tap whose :meth:`blocks` gives each block's source under
    the driver's rename (:meth:`raw_blocks`: as the engine wrote it)."""

    def __init__(self, rename):
        super().__init__()
        self.rename = rename

    def raw_blocks(self):
        if not self.key:
            return np.empty((0, 4), np.uint32), np.empty(0, np.float32)
        return np.concatenate(self.key), np.concatenate(self.until_s)

    def blocks(self):
        key, until = self.raw_blocks()
        return self.rename()(key), until


class Driver(sim_paced_vring.Driver):
    def build(self):
        source, _ = super().build()
        self.sink = SinkTap6(self.rename)
        self._rename = None
        return source, self.sink

    # -- the daemon ----------------------------------------------------

    def start(self) -> None:
        """``sim_paced.Driver.start`` with the 128-bit options; a daemon
        that ends before it has made its rings fails the run at once."""
        from flowsentryx_tpu.core import schema
        from flowsentryx_tpu.engine.shm import ShmVerdictSink

        p, t = self.p, self.ctx.config["traffic"]
        self.err = open(self.ctx.workdir / "fsxd.err", "w")
        self.t_start = time.perf_counter()
        self.proc = subprocess.Popen(
            [str(self.fsxd), "--sim", "--shards", str(self.shards),
             *(["--pace"] if p.get("pace", True) else []),
             "--rate", str(p["rate"]), "--packets", str(1 << 40),
             "--attack-fraction", str(t["attack_fraction"]),
             "--attack-ips", str(t["attack_ips"]),
             "--benign-ips", str(t["benign_ips"]),
             "--key-bits", "128",
             "--v6-fraction", str(t["v6_fraction"]),
             "--fold-collide", str(t["fold_collide"]),
             "--ring-capacity", str(p["ring_capacity"]),
             "--feature-ring", str(self.fring),
             "--verdict-ring", str(self.vring),
             "--seed", str(self.ctx.seed)],
            stdout=subprocess.PIPE, stderr=self.err, text=True,
            start_new_session=True)
        paths = [schema.shard_ring_path(str(self.fring), k, self.shards)
                 for k in range(self.shards)]
        deadline = time.monotonic() + 30.0
        while not all(map(self._ready, [self.vring, *paths])):
            if self.proc.poll() is not None:
                self.err.flush()
                tail = (self.ctx.workdir / "fsxd.err").read_text()[-800:]
                raise SystemExit(f"benchmark: fsxd ended at start "
                                 f"(exit {self.proc.returncode}): {tail}")
            if time.monotonic() > deadline:
                raise SystemExit("benchmark: fsxd made no rings in 30 s")
            time.sleep(0.01)
        self.sink.attach(ShmVerdictSink(self.vring, timeout_s=30.0))
        self.cursors = [sim_paced.RingCursor(path) for path in paths]
        if not p.get("pace", True):
            self.start_governor(paths)
        self.tap.wait_ready(60.0)

    @staticmethod
    def _ready(path) -> bool:
        from flowsentryx_tpu.core import schema

        try:
            hdr = np.fromfile(path, np.uint64, 1)
        except OSError:
            return False
        return len(hdr) == 1 and int(hdr[0]) == schema.SHM_MAGIC

    # -- the resident population ------------------------------------------

    def background_batches(self):
        """The resident population as full 7-word sealed batches, half of
        it (``traffic.v6_fraction``) IPv6, all stamped at the epoch."""
        t = self.ctx.config["traffic"]
        spec = t.get("background")
        if not spec:
            return
        bg = trafficgen.Background(spec, self.ctx.seed)
        b = self.ctx.config["batch"]["max_batch"]
        for start in range(0, bg.n, b):
            rec = bg.records(start, b, sim_paced.SIM_T0_NS, 0)
            keys = key128.background_keys(bg, start, len(rec),
                                          t["v6_fraction"], self.ctx.seed)
            yield key128.quantise_records(
                key128.widen(rec, keys), self.ctx.config["model"],
                sim_paced.SIM_T0_NS), 0

    # -- what the judge sees -------------------------------------------------

    def rename(self) -> key128.Rename:
        """The one map of the run's keys: built once, after the drain,
        over every batch dispatched (the prefill's first)."""
        if self._rename is None:
            self._batches = list(self.background_batches())
            self._batches += list(zip(self.tap.words, self.tap.base_us))
            keys = (np.concatenate([w[:, :4] for w, _ in self._batches])
                    if self._batches else np.empty((0, 4), np.uint32))
            self._rename = key128.Rename(keys)
        return self._rename

    def dispatched(self, config: dict):
        ids = self.rename().ids
        pos = 0
        for words, base_us in self._batches:
            n = len(words)
            out = np.empty((n, 4), np.uint32)
            out[:, 0] = ids[pos:pos + n]
            out[:, 1:] = words[:, 4:]
            pos += n
            yield out, base_us

    def counters(self) -> dict:
        out = super().counters()
        out["sink_blocks"] = sum(len(k) for k in self.sink.key)
        return out

    # -- what the transport owes -----------------------------------------------

    def ingest_words_gap(self, config: dict) -> tuple[int, dict]:
        """``sim_paced``'s check on the 64 B records: each worker's last
        whole sealed batches against its ring's last records, quantised
        again by :func:`key128.quantise_records`."""
        mismatched = compared = 0
        tap = self.tap
        for k, cur in enumerate(self.cursors):
            mine = [i for i, w in enumerate(tap.worker) if w == k]
            raw = cur.last(key128.FLOW_RECORD6, sim_paced.INGEST_CHECK_RECORDS)
            end = len(raw)
            for i in reversed(mine):
                n = len(tap.words[i])
                if n > end:
                    break
                rec = raw[end - n:end]
                end -= n
                if n == 0:
                    continue
                base = int(rec["ts_ns"][0])
                words = key128.quantise_records(rec, config["model"], base)
                bad = int((words != tap.words[i]).any(axis=1).sum())
                if (base - sim_paced.SIM_T0_NS) // 1000 != tap.base_us[i]:
                    bad = n
                mismatched += bad
                compared += n
        if not compared:  # nothing lined up: that is a failure, not a pass
            mismatched = 1
        return mismatched, {"compared": compared, "mismatched": mismatched}

    def verdict_ring_gap(self, sink) -> tuple[int, dict]:
        """``sim_paced``'s check on the 32 B verdict records: the ring's
        memory against the 128-bit blocks handed to the sink, in order;
        the engine's cursor against those blocks; the daemon's count
        against the cursor it left."""
        cur = sim_paced.RingCursor(self.vring)
        got = cur.last(key128.VERDICT_RECORD6, 1 << 30)
        key, until_s = sink.raw_blocks()
        m = len(got)
        want_ns = (until_s[len(key) - m:].astype(np.float64) * 1e9
                   ).astype(np.uint64) + np.uint64(sim_paced.SIM_T0_NS)
        differ = int(((got["saddr6"] != key[len(key) - m:]).any(axis=1)
                      | (got["until_ns"] != want_ns)).sum()) \
            if m <= len(key) else m
        lost = abs(cur.head() - len(key))
        uncounted = abs(self.final.get("verdicts", -1) - cur.tail())
        detail = {"ring_head": cur.head(), "ring_tail": cur.tail(),
                  "read_back": m, "differ": differ,
                  "sink_blocks": len(key),
                  "v6_blocks": int((~key128.is_v4_mapped(key)).sum()),
                  "fsxd_verdicts": self.final.get("verdicts"),
                  "fsxd_v6_sources": self.final.get("v6_sources"),
                  "fsxd_planted": self.final.get("planted"),
                  "sink_dropped": int(sink.real.dropped)}
        return differ + lost + uncounted, detail
