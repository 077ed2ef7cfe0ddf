"""Traffic driver ``loop_file``: a seeded record array served closed-loop
through the engine's inline record path (what ``fsx serve --records``
takes, ``cli.py:1455-1468``), replayed for as long as the engine asks,
each pass with its timestamps moved on by the array's own span.

The engine asks for records and gets exactly as many as it asks for, so
the served rate is the engine's own.  What was handed over is a pure
function of (array, count), so the reference replays it from the count.

Where the configuration states a resident population
(``traffic.background``), the stream begins with one record of each such
source: set-up serves them (``prefill``), so that the table holds what a
deployment of its size holds before the window opens.
"""

from __future__ import annotations

import numpy as np

from benchmark import harness, reference, trafficgen


class LoopSource:
    def __init__(self, records: np.ndarray, dt_ns: int, background=None):
        self.records = records
        self.dt_ns = dt_ns
        self.span_ns = np.uint64(len(records) * dt_ns)
        self.bg = background
        self.n_bg = background.n if background else 0
        self.t0_ns = int(records["ts_ns"][0])
        self.handed = 0

    def take(self, start: int, n: int) -> np.ndarray:
        """Records ``start .. start+n`` of the stream: the background
        population once, then the endless replay, on one clock."""
        size = len(self.records)
        parts = []
        if start < self.n_bg:
            part = self.bg.records(start, n, self.t0_ns, self.dt_ns)
            parts.append(part)
            start += len(part)
            n -= len(part)
        shift = np.uint64(self.n_bg * self.dt_ns)
        while n > 0:
            lap, pos = divmod(start - self.n_bg, size)
            part = self.records[pos:pos + n].copy()
            part["ts_ns"] += np.uint64(lap) * self.span_ns + shift
            parts.append(part)
            start += len(part)
            n -= len(part)
        return parts[0] if len(parts) == 1 else np.concatenate(parts)

    def poll(self, max_records: int) -> np.ndarray:
        out = self.take(self.handed, max_records)
        self.handed += len(out)
        return out

    def exhausted(self) -> bool:
        return False


class Driver:
    has_latency = False

    def __init__(self, ctx):
        self.ctx = ctx
        self.p = ctx.cell["traffic"]

    def build(self):
        from flowsentryx_tpu.engine import NullSink

        recs = trafficgen.flow_records(self.p, self.ctx.seed)
        dt_ns = max(1, int(1e9 / self.p["rate_pps"]))
        spec = self.ctx.config["traffic"].get("background")
        bg = trafficgen.Background(spec, self.ctx.seed) if spec else None
        self.source = LoopSource(recs, dt_ns, bg)
        self.sink = harness.SinkTap(NullSink())
        return self.source, self.sink

    def source_tap(self):
        return None

    def start(self) -> None:
        pass

    def prefill(self, eng) -> int:
        """Serve the background population: as many whole batches as
        hold it (the stream runs straight on into the replay)."""
        b = self.ctx.config["batch"]["max_batch"]
        if self.source.n_bg:
            eng.run(max_batches=-(-self.source.n_bg // b))
        return self.source.n_bg

    def warm_up(self, eng) -> None:
        eng.run(max_seconds=float(self.p["warmup_s"]))

    def counters(self) -> dict:
        return {"forwarded": self.source.handed, "backlog": 0,
                "dropped_ring_full": 0}

    def stop(self) -> dict:
        return {}

    def drain(self, eng) -> None:
        pass

    def transport_compared(self, config: dict, sink) -> dict:
        """No transport of its own to hold to account."""
        return {}

    def dispatched(self, config: dict):
        """``(words, base_rel_us)`` of every batch the engine sealed: the
        reference seals the handed stream for itself."""
        b = config["batch"]["max_batch"]
        t0 = self.source.t0_ns
        group = 64 * b
        for start in range(0, self.source.handed, group):
            n = min(group, self.source.handed - start)
            yield from reference.seal_stream(
                self.source.take(start, n), b, t0, config["model"])
