"""Traffic driver ``sim_churn``: ``sim_paced_vring`` with a share of the
flood's records from sources that never repeat (``fsxd --sim
--spoof-fraction``, the configuration's ``traffic.spoof_fraction``)
against a table that ages rows out, and a window that has to lie in the
steady state.

The warm-up is a fixed wall time as in ``sim_paced`` (the cell's
``warmup_s``, set so that the record clock at the window's start is
past ``evict_ttl_s`` and one sweep cycle: the table has stopped
filling).  Whether it was long enough is part of ``correct``: the driver
keeps every report ``run.py`` takes from the engine between the
warm-up's end and the generator's stop (the window's two ends are the
first and the last) and ``transport_compared`` adds, beside what the
rings owe,

* ``occupancy_drift`` — occupied rows (the report's ``table`` summary)
  at the window's two ends, apart by this share of the capacity: a
  window taken while the table still fills measures the transient;
* ``evicted_gap`` — the window's ``evicted`` against the spoofed flows
  of the window's sealed batches less its ``untracked``: in the steady
  state what ages out is what came in (the pooled sources keep their
  rows), as a share of that count;
* ``untracked_share`` — the window's ``untracked`` over its flows: about
  ``load ** probes``; a table that stopped inserting reads near the
  spoofed share.

A ``daemon/fsxd.cpp`` from before ``--spoof-fraction`` ends the run in
``build``, before the engine exists.
"""

from __future__ import annotations

import subprocess
import time

from benchmark import churn, harness

vring = harness.load_module("drivers", "sim_paced_vring")
sim_paced = vring.sim_paced

#: limits of the three steady-state readings (PERF.md section 2)
OCCUPANCY_DRIFT_MAX = 0.05
EVICTED_GAP_MAX = 0.05
UNTRACKED_SHARE_MAX = 0.03


class Driver(vring.Driver):
    def build(self):
        """``sim_paced``'s, once the daemon is known to have the option:
        one older than ``--spoof-fraction`` ends the run here, seconds
        after its start, not after the engine's compile."""
        usage = subprocess.run([str(harness.build_fsxd()), "--help"],
                               capture_output=True, text=True).stderr
        if "--spoof-fraction" not in usage:
            raise SystemExit("benchmark: this fsxd has no --spoof-fraction "
                             "(daemon/fsxd.cpp before ISSUE 39)")
        return super().build()

    def start(self) -> None:
        """``sim_paced``'s launch with ``--spoof-fraction``."""
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
             "--spoof-fraction", str(t["spoof_fraction"]),
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
        self.cursors = [sim_paced.RingCursor(path) for path in paths]
        if not p.get("pace", True):
            self.start_governor(paths)
        self.tap.wait_ready(60.0)

    def warm_up(self, eng) -> None:
        """The fixed warm-up; from its end on, keep what ``run.py``
        reads from the engine."""
        super().warm_up(eng)
        self.reports: list[tuple[dict, int]] = []  # (report, tap batches)
        run = eng.run

        def kept(*args, **kw):
            rep = run(*args, **kw)
            if not self.final:  # until the generator is stopped
                self.reports.append((rep._asdict(), len(self.tap.words)))
            return rep

        eng.run = kept

    def counters(self) -> dict:
        """With the tap's batch count, so that a reader can find the
        window's sealed batches (``table.untracked_share.tput``)."""
        return dict(super().counters(), tap_batches=len(self.tap.words))

    def steady_state(self, config: dict) -> dict:
        """The three readings over the window (module docstring)."""
        (rep0, b0), (rep1, b1) = self.reports[0], self.reports[-1]
        cap = config["table"]["capacity"]
        drift = abs(rep1["table"]["tracked"] - rep0["table"]["tracked"]) / cap
        d = {k: rep1["stats"].get(k, 0) - rep0["stats"].get(k, 0)
             for k in ("evicted", "untracked")}
        flows, spoofed = churn.flows_between(self.tap.words, b0, b1)
        took_rows = spoofed - d["untracked"]
        detail = {"tracked": [rep0["table"]["tracked"],
                              rep1["table"]["tracked"]],
                  "capacity": cap, "evicted": d["evicted"],
                  "untracked": d["untracked"], "flows": flows,
                  "spoofed_flows": spoofed, "batches": b1 - b0,
                  "record_clock_s": [rep0["table"]["newest_seen_s"],
                                     rep1["table"]["newest_seen_s"]]}
        return {
            "occupancy_drift": {"value": drift, "limit": OCCUPANCY_DRIFT_MAX,
                                "detail": detail},
            "evicted_gap": {
                "value": abs(d["evicted"] - took_rows) / max(took_rows, 1),
                "limit": EVICTED_GAP_MAX},
            "untracked_share": {"value": d["untracked"] / max(flows, 1),
                                "limit": UNTRACKED_SHARE_MAX},
        }

    def transport_compared(self, config: dict, sink) -> dict:
        return {**super().transport_compared(config, sink),
                **self.steady_state(config)}
