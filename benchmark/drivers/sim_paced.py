"""Traffic driver ``sim_paced``: ``fsxd --sim`` -> sharded shm feature
rings -> the engine's sealed-ingest path -> the shm verdict ring ->
``fsxd`` (which then suppresses blocked sources, as the kernel would).
Two modes, by the cell's ``traffic.pace`` (default true):

* open loop (``pace`` true): ``fsxd --sim --pace`` at a fixed offered
  ``rate``; the daemon keeps to its schedule whatever the engine does,
  and what a full ring cannot take is shed and counted.
* closed loop (``pace`` false): ``fsxd --sim`` free-running against ring
  backpressure.  ``rate`` is then each source's density in record time
  (the daemon stamps records from its own counter), and
  ``benchmark/governor.py`` holds the daemon once a ring holds
  ``high_water`` records and lets it go once all hold under
  ``low_water``: the fuller ring stays that full, nothing is shed, and
  the engine is served as fast as it asks.  ``ring_capacity`` stays well
  above ``high_water``: the room between is what a late poll may cost.

The launch is ``chip_smoke.py:239-255`` with the order turned round: the
engine is built and warmed first and the daemon started last, so that no
record waits for a compile.  The ingest workers therefore wait for the
rings (``timeout_s``), and the verdict sink is attached once the daemon
has made its ring.
"""

from __future__ import annotations

import json
import signal
import subprocess
import sys
import time

import numpy as np

from benchmark import governor, harness, reference, trafficgen
from benchmark.governor import HDR_SIZE, HEAD_OFFSET, TAIL_OFFSET

#: closed loop: how often the governor looks at the rings' cursors
GOVERNOR_POLL_US = 500
#: the sim generator's clock starts here (daemon/fsxd.cpp SimSource), so
#: this is the stream epoch the ingest workers agree on
SIM_T0_NS = 1_000_000_000
#: raw ring records compared with their sealed rows, a shard
INGEST_CHECK_RECORDS = 1 << 20
#: the 16 B verdict record (kern/fsx_schema.h ``struct fsx_verdict_record``)
VERDICT_RECORD = np.dtype([("saddr", "<u4"), ("_pad", "<u4"),
                           ("until_ns", "<u8")])


class RingCursor:
    """Read-only eyes on one shm ring: its cursors while it runs, and
    the records its memory still holds once it has stopped."""

    def __init__(self, path):
        self.path = path
        self.mm = np.memmap(path, np.uint64, "r", 0, (HDR_SIZE // 8,))

    def head(self) -> int:
        return int(self.mm[HEAD_OFFSET // 8])

    def tail(self) -> int:
        return int(self.mm[TAIL_OFFSET // 8])

    def last(self, dtype: np.dtype, n: int) -> np.ndarray:
        """The last ``n`` records produced (at most the ring's size), in
        order, copied out of the ring's memory."""
        slots = np.memmap(self.path, dtype, "r", HDR_SIZE)
        head = self.head()
        n = min(n, head, len(slots))
        idx = np.arange(head - n, head, dtype=np.uint64) \
            & np.uint64(len(slots) - 1)
        return np.array(slots[idx.astype(np.int64)])


class Driver:
    has_latency = True

    def __init__(self, ctx):
        self.ctx = ctx
        self.p = ctx.cell["traffic"]
        self.proc = self.gov = None
        self.cursors: list[RingCursor] = []
        self.final: dict = {}
        self.prefilled = 0

    def build(self):
        from flowsentryx_tpu.ingest import ShardedIngest

        cfg = self.ctx.config
        self.fsxd = harness.build_fsxd()
        self.fring = self.ctx.workdir / "fring"
        self.vring = self.ctx.workdir / "vring"
        self.shards = int(cfg["ingest_workers"])
        # cli.py:1427-1445, with the ring probe skipped and a long wait:
        # the daemon starts after the engine is warm
        real = ShardedIngest(str(self.fring), self.shards,
                             precompact=False, timeout_s=600.0)
        self.tap = harness.SourceTap(real, cfg["batch"]["max_batch"])
        self.sink = harness.SinkTap()
        return self.tap, self.sink

    def source_tap(self):
        return self.tap

    def start(self) -> None:
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
             "--ring-capacity", str(p["ring_capacity"]),
             "--feature-ring", str(self.fring),
             "--verdict-ring", str(self.vring),
             "--seed", str(self.ctx.seed)],
            stdout=subprocess.PIPE, stderr=self.err, text=True,
            start_new_session=True)
        self.sink.attach(ShmVerdictSink(self.vring, timeout_s=30.0))
        paths = [schema.shard_ring_path(str(self.fring), k, self.shards)
                 for k in range(self.shards)]
        self.cursors = [RingCursor(path) for path in paths]
        if not p.get("pace", True):
            self.start_governor(paths)
        self.tap.wait_ready(60.0)

    def start_governor(self, ring_paths) -> None:
        """Closed loop: hold the free-running daemon while a ring holds
        ``high_water`` records (``benchmark/governor.py``)."""
        p = self.p
        self.proc.send_signal(signal.SIGSTOP)  # held until the governor is up
        self.gov_status = self.ctx.workdir / "governor.status"
        self.gov = subprocess.Popen(
            [sys.executable, governor.__file__, "--pid", str(self.proc.pid),
             "--high-water", str(int(p["high_water"])),
             "--low-water", str(int(p["low_water"])),
             "--poll-us", str(GOVERNOR_POLL_US),
             "--status", str(self.gov_status),
             "--verdict-ring", str(self.vring), *map(str, ring_paths)],
            stdout=subprocess.PIPE, text=True, start_new_session=True)

    def backlog(self) -> int:
        return sum(c.head() - c.tail() for c in self.cursors)

    def background_batches(self):
        """The resident population as full sealed batches, all stamped at
        the stream's epoch (``(words, base_rel_us)``; the seal is the
        benchmark's own quantise, as in the file driver)."""
        spec = self.ctx.config["traffic"].get("background")
        if not spec:
            return
        bg = trafficgen.Background(spec, self.ctx.seed)
        b = self.ctx.config["batch"]["max_batch"]
        for start in range(0, bg.n, b):
            rec = bg.records(start, b, SIM_T0_NS, 0)
            yield reference.quantise_records(
                rec, self.ctx.config["model"], SIM_T0_NS), 0

    def prefill(self, eng) -> int:
        """Serve the resident population through the sealed path before
        the daemon starts: the tap stands in for the source until the
        batches run dry."""
        self.tap.begin_prefill(self.background_batches())
        eng.run()
        self.tap.end_prefill()
        self.prefilled = sum(self.tap.n_records[:self.tap.prefill_batches])
        return self.prefilled

    def warm_up(self, eng) -> None:
        """Serve for a fixed time.  (Not "until the rings are under a
        batch a shard": at a 2^27-row table they never are — PERF.md §5 —
        and a fixed time keeps the window at the same phase of the
        sources' block-and-return cycle in every run.)"""
        eng.run(max_seconds=float(self.p["warmup_s"]))

    def counters(self) -> dict:
        out = {"forwarded": self.prefilled
               + sum(c.head() for c in self.cursors),
               "backlog": self.backlog(),
               "ring_fill": [c.head() - c.tail() for c in self.cursors],
               "dropped_ring_full": self.final.get("dropped_ring_full", 0)}
        if getattr(self, "gov", None):  # closed loop: held and alive, ns
            out.update(self.final.get("governor")
                       or governor.read_status(self.gov_status) or {})
        return out

    @staticmethod
    def last_line_of(proc, timeout_s: float) -> dict:
        """Tell a child to end, wait for it, and read its last line."""
        proc.send_signal(signal.SIGTERM)
        try:
            out, _ = proc.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, _ = proc.communicate()
        return json.loads(out.strip().splitlines()[-1]) if out.strip() else {}

    def stop(self) -> dict:
        """End the governor, where there is one (it lets the daemon go),
        then the daemon, and read their last lines."""
        gov = None
        if self.gov is not None:
            gov = self.last_line_of(self.gov, 10)
            self.proc.send_signal(signal.SIGCONT)
        self.t_stop = time.perf_counter()
        self.final = self.last_line_of(self.proc, 30)
        self.err.close()
        self.final["elapsed_s"] = self.t_stop - self.t_start
        self.final["rate"] = self.p["rate"]
        if gov is not None:
            self.final["governor"] = gov
        return self.final

    def drain(self, eng) -> None:
        """Serve what the rings still hold, to the last record, so that
        every record the daemon forwarded is accounted for."""
        self.tap.request_stop()
        eng.run(max_seconds=float(self.p.get("drain_limit_s", 120)))

    def ingest_words_gap(self, config: dict) -> tuple[int, dict]:
        """The ingest workers' quantise and seal, against the raw ring.

        A ring's memory still holds its last records after the drain.
        Worker ``k`` consumes ring ``k`` in order, so the last whole
        batches it sealed are the ring's last records: the reference
        quantises those raw records for itself and counts every sealed
        row that differs in any bit (a base that differs counts its
        whole batch)."""
        want = INGEST_CHECK_RECORDS
        mismatched = compared = 0
        tap = self.tap
        for k, cur in enumerate(self.cursors):
            mine = [i for i, w in enumerate(tap.worker) if w == k]
            raw = cur.last(trafficgen.FLOW_RECORD, want)
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
                words = reference.quantise_records(rec, config["model"],
                                                   base)
                bad = int((words != tap.words[i]).any(axis=1).sum())
                if (base - SIM_T0_NS) // 1000 != tap.base_us[i]:
                    bad = n
                mismatched += bad
                compared += n
        if not compared:  # nothing lined up: that is a failure, not a pass
            mismatched = 1
        return mismatched, {"compared": compared, "mismatched": mismatched}

    def verdict_ring_gap(self, sink) -> tuple[int, dict]:
        """What landed in the verdict ring, read back from its memory,
        against what the engine handed to the sink, in order; the
        engine's cursor against the blocks it handed over; the daemon's
        count against the cursor it left."""
        cur = RingCursor(self.vring)
        got = cur.last(VERDICT_RECORD, 1 << 30)
        key, until_s = sink.blocks()
        m = len(got)
        want_ns = (until_s[len(key) - m:].astype(np.float64) * 1e9
                   ).astype(np.uint64) + np.uint64(SIM_T0_NS)
        differ = int(((got["saddr"] != key[len(key) - m:])
                      | (got["until_ns"] != want_ns)).sum()) \
            if m <= len(key) else m
        lost = abs(cur.head() - len(key))
        uncounted = abs(self.final.get("verdicts", -1) - cur.tail())
        detail = {"ring_head": cur.head(), "ring_tail": cur.tail(),
                  "read_back": m, "differ": differ,
                  "sink_blocks": len(key),
                  "fsxd_verdicts": self.final.get("verdicts"),
                  "sink_dropped": int(sink.real.dropped)}
        return differ + lost + uncounted, detail

    def transport_compared(self, config: dict, sink) -> dict:
        """What this transport owes beyond the verdicts themselves: each
        an exact count, limit 0."""
        igap, idetail = self.ingest_words_gap(config)
        vgap, vdetail = self.verdict_ring_gap(sink)
        return {
            "ingest_words_differ": {"value": igap, "limit": 0,
                                    "detail": idetail},
            "verdict_ring_differ": {"value": vgap, "limit": 0,
                                    "detail": vdetail},
            "verdict_ring_dropped": {"value": int(sink.real.dropped),
                                     "limit": 0},
        }

    def close(self) -> None:
        for proc in (self.gov, self.proc):
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait()
        self.tap.close()

    def dispatched(self, config: dict):
        yield from self.background_batches()
        yield from zip(self.tap.words, self.tap.base_us)
