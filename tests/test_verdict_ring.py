"""The verdict ring's writer (``ShmVerdictSink``, ISSUE 32): nothing is
discarded while the ring's reader advances, order is kept and the wait
is a span; a reader that stands still is given up after one bound, what
was left out is counted and degrades health, and the engine goes on.
The report finds the ring's accounting behind a sink that only
forwards, as the benchmark's tap does."""

import platform
import threading
import time

import numpy as np
import pytest

from flowsentryx_tpu.core import schema
from flowsentryx_tpu.core.config import (
    BatchConfig, FsxConfig, LimiterConfig, TableConfig,
)
from flowsentryx_tpu.engine import ArraySource, CollectSink, Engine, health
from flowsentryx_tpu.engine.shm import ShmRing, ShmVerdictSink
from flowsentryx_tpu.engine.traffic import Scenario, TrafficGen, TrafficSpec
from flowsentryx_tpu.engine.writeback import BlacklistUpdate
from flowsentryx_tpu.sync import tuning

pytestmark = pytest.mark.skipif(platform.machine() not in ("x86_64", "AMD64"),
                                reason="shm rings need x86-TSO")

SLOTS = 64
SPAN = "fsx.sink.vring_wait"


def make_ring(tmp_path):
    path = tmp_path / "vring"
    return path, ShmRing.create(path, SLOTS, schema.VERDICT_RECORD_DTYPE)


def updates(n_blocks: int, group: int):
    """``n_blocks`` blocks with keys 1..n, in updates of ``group``."""
    keys = np.arange(1, n_blocks + 1, dtype=np.uint32)
    for start in range(0, n_blocks, group):
        k = keys[start:start + group]
        yield BlacklistUpdate(key=k, until_s=(k % 97).astype(np.float32))


class SlowReader(threading.Thread):
    """Takes at most ``chunk`` blocks a turn and sleeps between turns,
    until told to stop and the ring is empty: the daemon, slowed.  With
    ``when_full`` it takes nothing until the ring is full (or it is
    told to stop), so that a writer of more than the ring holds has to
    wait whatever the machine's load."""

    def __init__(self, ring, chunk=24, sleep_s=0.0005, when_full=False):
        super().__init__(daemon=True)
        self.ring, self.chunk, self.sleep_s = ring, chunk, sleep_s
        self.when_full = when_full
        self.got: list[np.ndarray] = []
        self.stop = False

    def run(self):
        while True:
            lazy = self.when_full and not self.stop \
                and self.ring.readable() < self.ring.capacity
            rec = self.ring.consume(0 if lazy else self.chunk)
            if len(rec):
                self.got.append(rec)
            elif self.stop:
                return
            time.sleep(self.sleep_s)

    def finish(self) -> np.ndarray:
        self.stop = True
        self.join(timeout=30)
        assert not self.is_alive()
        return np.concatenate(self.got)


def flood_engine(sink, batches=40):
    """An engine over a seeded flood that blocks a couple of hundred
    sources, into ``sink``."""
    cfg = FsxConfig(
        table=TableConfig(capacity=1 << 12),
        batch=BatchConfig(max_batch=256, verdict_k=64),
        limiter=LimiterConfig(pps_threshold=20.0, bps_threshold=1e9))
    recs = TrafficGen(TrafficSpec(
        scenario=Scenario.UDP_FLOOD_MULTI, rate_pps=1e7,
        n_attack_ips=200, n_benign_ips=24, attack_fraction=0.9,
        seed=13)).next_records(256 * batches)
    return Engine(cfg, ArraySource(recs), sink, sink_thread=False)


class TestLiveReader:
    # (a) of ISSUE 32: a ring smaller than one update, a reader draining
    @pytest.mark.parametrize("group", [SLOTS + 1, 5 * SLOTS + 7, 10_000])
    def test_every_block_arrives_in_order_and_the_wait_is_a_span(
            self, tmp_path, group):
        path, reader_side = make_ring(tmp_path)
        sink = ShmVerdictSink(path, t0_ns=1_000)
        reader = SlowReader(reader_side)
        reader.start()
        for upd in updates(10_000, group):
            sink.apply(upd)
        got = reader.finish()
        assert sink.dropped == 0
        assert got["saddr"].tolist() == list(range(1, 10_001))
        want_ns = (np.arange(1, 10_001) % 97).astype(np.uint64) \
            * np.uint64(1_000_000_000) + np.uint64(1_000)
        assert (got["until_ns"] == want_ns).all()
        # every update was larger than the ring: each one waited, once
        n_updates = -(-10_000 // group)
        assert sink.waits == n_updates
        assert sink.vring_wait.hist.n == n_updates
        assert sink.vring_wait.hist.sum_us > 0
        assert sink.ring_accounting() == {
            "verdict_ring_dropped": 0, "verdict_ring_waits": n_updates,
            "verdict_ring_fill_peak": 1.0}

    def test_an_update_that_fits_does_not_wait(self, tmp_path):
        path, reader_side = make_ring(tmp_path)
        sink = ShmVerdictSink(path)
        for upd in updates(SLOTS // 2, group=16):
            sink.apply(upd)
        assert sink.ring_accounting() == {
            "verdict_ring_dropped": 0, "verdict_ring_waits": 0,
            "verdict_ring_fill_peak": 0.5}
        assert sink.vring_wait.hist.n == 0
        assert len(reader_side.consume(SLOTS)) == SLOTS // 2

    def test_the_wait_is_a_host_span_in_a_profiler_trace(self, tmp_path):
        import jax
        from jax.profiler import ProfileData

        path, reader_side = make_ring(tmp_path)
        sink = ShmVerdictSink(path)
        reader = SlowReader(reader_side)
        reader.start()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path / "trace"),
                                 profiler_options=opts)
        try:
            for upd in updates(6 * SLOTS, group=2 * SLOTS):
                sink.apply(upd)
        finally:
            jax.profiler.stop_trace()
            reader.finish()
        files = list((tmp_path / "trace").glob(
            "plugins/profile/*/*.xplane.pb"))
        assert len(files) == 1
        names = [ev.name
                 for plane in ProfileData.from_file(str(files[0])).planes
                 if plane.name == "/host:CPU"
                 for line in plane.lines for ev in line.events]
        assert names.count(SPAN) == 3

    def test_an_engine_behind_a_slow_reader_loses_no_block(self, tmp_path):
        """The engine's own sink section over a ring far smaller than
        what one sunk group blocks; the report carries the accounting
        and the span's histogram."""
        path, reader_side = make_ring(tmp_path)
        sink = ShmVerdictSink(path)
        reader = SlowReader(reader_side, chunk=8, when_full=True)
        reader.start()
        rep = flood_engine(sink).run()
        got = reader.finish()
        rb = rep.readback
        assert rb["verdict_ring_dropped"] == 0
        assert rb["verdict_ring_waits"] == sink.waits > 0
        assert rb["verdict_ring_fill_peak"] == 1.0
        assert len(got) == rep.blocked_sources > SLOTS
        assert rep.health["state"] == health.HEALTHY
        assert rep.spans[SPAN]["n"] == sink.waits


class TestDeadReader:
    # (b) of ISSUE 32: no reader at all
    @pytest.fixture(autouse=True)
    def short_bound(self, monkeypatch):
        monkeypatch.setattr(tuning, "VRING_WAIT_TIMEOUT_S", 0.25)

    def test_apply_returns_after_the_bound_and_counts_the_rest(
            self, tmp_path):
        path, _ = make_ring(tmp_path)
        sink = ShmVerdictSink(path)
        upd = next(updates(SLOTS + 36, group=SLOTS + 36))
        t0 = time.monotonic()
        sink.apply(upd)
        waited = time.monotonic() - t0
        assert 0.25 <= waited < 0.25 + 1.0
        assert sink.dropped == 36
        assert sink.waits == sink.vring_wait.hist.n == 1
        # given up: not waited for again while its cursor stands still
        t0 = time.monotonic()
        sink.apply(upd)
        assert time.monotonic() - t0 < 0.2
        assert sink.dropped == 36 + SLOTS + 36
        assert sink.waits == 1

    def test_a_reader_that_comes_back_is_waited_for_again(self, tmp_path):
        path, reader_side = make_ring(tmp_path)
        sink = ShmVerdictSink(path)
        sink.apply(next(updates(SLOTS + 1, group=SLOTS + 1)))
        assert sink.dropped == 1
        assert len(reader_side.consume(8)) == 8   # the cursor moves
        reader = SlowReader(reader_side)
        reader.start()
        sink.apply(next(updates(3 * SLOTS, group=3 * SLOTS)))
        reader.finish()
        assert sink.dropped == 1
        assert sink.waits == 2

    def test_the_engine_ends_degraded_and_counts_what_was_lost(
            self, tmp_path):
        path, _ = make_ring(tmp_path)
        sink = ShmVerdictSink(path)
        t0 = time.monotonic()
        rep = flood_engine(sink).run()           # run() ends by itself
        assert time.monotonic() - t0 < 60
        rb = rep.readback
        assert sink.dropped > 0
        assert rb["verdict_ring_dropped"] == sink.dropped
        assert rb["verdict_ring_dropped"] + SLOTS == rep.blocked_sources
        assert rb["verdict_ring_waits"] == 1     # one bound, not one a group
        assert rb["verdict_ring_fill_peak"] == 1.0
        assert rep.health["state"] == health.DEGRADED
        assert f"verdict_ring_dropped:{sink.dropped}" in rep.health["reasons"]
        span = rep.spans[SPAN]
        assert span["n"] == 1 and span["sum_us"] >= 0.25e6


def test_a_sink_without_a_ring_reports_no_ring():
    rep = flood_engine(CollectSink(), batches=2).run()
    assert rep.readback["verdict_ring_dropped"] is None
    assert "verdict_ring_waits" not in rep.readback
    assert "verdict_ring_fill_peak" not in rep.readback
    assert SPAN not in rep.spans
