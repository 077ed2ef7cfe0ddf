"""A record's wait in the feature ring, the latency plane's stage ahead
of the seal→verdict chain, and the serving window on the trace's clock.

The ingest worker logs when it saw its ring's producer cursor move
(``ingest/worker.py::CursorLog``), dates a batch's first record by that
log, and carries the wait in the sealed-batch header's word 7; the
engine charges it as ``latency.ring`` and ``latency.arrival_to_verdict``.
``fsx.serve`` marks a ``run()`` call's serving window, which the
benchmark's ``device.idle_serving.*`` read from the trace's host plane.
"""

import json
import platform
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from flowsentryx_tpu.core import schema
from flowsentryx_tpu.core.config import BatchConfig
from flowsentryx_tpu.engine import ArraySource, CollectSink, Engine
from flowsentryx_tpu.engine.metrics import (
    LatencyRecorder, _lat_bucket, span_store,
)
from flowsentryx_tpu.engine.shm import SealedBatchQueue
from flowsentryx_tpu.ingest import ShardedIngest
from flowsentryx_tpu.ingest.worker import CursorLog
from tests.test_ingest import _make_shard_rings, make_records
from tests.test_spans import flood, run_inline, run_sealed, small_cfg

linux = pytest.mark.skipif(platform.system() != "Linux",
                           reason="shm ingest requires Linux")


class _Ring:
    """A producer cursor a test moves by hand."""

    def __init__(self):
        self.h = 0

    def head(self):
        return self.h


def _log_of(heads, entries=8):
    """A log that saw the cursor at each of ``heads``, with its stamps."""
    ring = _Ring()
    log = CursorLog(ring, entries)
    stamps = []
    for h in heads:
        ring.h = h
        log.observe()
        stamps.append(int(log._t_ns[(log.n - 1) & log._mask]))
    return log, stamps


class TestCursorLog:
    def test_a_record_is_dated_by_the_first_look_that_passed_it(self):
        log, t = _log_of([0, 5, 9, 20])
        for cursor, entry in ((0, 1), (4, 1), (5, 2), (8, 2), (9, 3),
                              (19, 3)):
            assert log.published_ns(cursor) == t[entry]
        assert log.clamped == 0
        # monotone: a later cursor is never dated earlier
        dated = [log.published_ns(c) for c in range(20)]
        assert dated == sorted(dated)
        # a cursor no look has passed was published after the last one
        before = time.clock_gettime_ns(time.CLOCK_MONOTONIC)
        assert log.published_ns(20) >= before
        assert log.clamped == 0

    def test_an_unmoved_cursor_is_not_logged_again(self):
        log, _ = _log_of([0, 3, 3, 3, 7])
        assert log.n == 3 and log.observes == 5 and log.last == 7

    def test_a_cursor_older_than_the_log_is_clamped_and_counted(self):
        # the records under 6 were there at the first look: their wait
        # began before anything the log saw
        log, t = _log_of([6, 10])
        assert log.published_ns(2) == t[0]
        assert log.published_ns(5) == t[0]
        assert log.clamped == 2
        assert log.published_ns(7) == t[1] and log.clamped == 2

    def test_the_log_wraps_and_keeps_its_newest_entries(self):
        log, t = _log_of(range(0, 200, 10), entries=8)  # 20 looks
        assert log.n == 20
        # the 8 kept are heads 120..190; 135 lies under the entry 140
        assert log.published_ns(135) == t[14] and log.clamped == 0
        # 111 lies under the oldest kept, 120: its predecessor, which
        # may have passed 111 too, was overwritten
        assert log.published_ns(111) == t[12] and log.clamped == 1
        assert log.published_ns(3) == t[12] and log.clamped == 2

    @pytest.mark.parametrize("entries", [0, 1, 6])
    def test_the_size_is_a_power_of_two(self, entries):
        with pytest.raises(ValueError, match="power of two"):
            CursorLog(_Ring(), entries)


def _drain_batches(ing, n, deadline_s=20.0):
    got = []
    deadline = time.monotonic() + deadline_s
    while len(got) < n:
        got += ing.poll_batches(n - len(got))
        assert time.monotonic() < deadline, "batches never came"
        time.sleep(0.001)
    return got


@linux
def test_a_wait_on_a_full_queue_is_the_ring_wait_of_what_arrived(tmp_path):
    """Records published while the worker's sealed queue is full wait in
    the ring for as long as the queue stays full: the batch they start
    carries that wait, because the worker looks at the ring's cursor in
    the emit loop too.  Without that look it would date them when the
    wait ends, and the wait would read about 0."""
    hold_s = 0.050
    base = str(tmp_path / "fring")
    (ring,) = _make_shard_rings(base, 1)
    ing = ShardedIngest(base, 1, queue_slots=2, precompact=False,
                        t0_grace_s=0.05, spin_us=0)
    ing.start(BatchConfig(max_batch=8, deadline_us=10_000, verdict_k=0),
              schema.WIRE_RAW48, None)
    rec = make_records(32)
    try:
        ing.wait_ready()
        q = ing._queues[0]
        assert ring.produce(rec[:24]) == 24  # three batches
        deadline = time.monotonic() + 20
        while ing.t0_ns is None:
            ing.poll_batches(0)
            assert time.monotonic() < deadline
            time.sleep(0.005)
        while q.readable() < 2:  # two fill the queue, the third waits
            assert time.monotonic() < deadline
            time.sleep(0.001)
        time.sleep(0.02)
        assert ring.produce(rec[24:]) == 8   # the fourth, published now
        time.sleep(hold_s)                   # the queue stays full
        got = _drain_batches(ing, 4)
    finally:
        ing.close()
    assert [sb.n_records for sb in got] == [8, 8, 8, 8]
    # the worker looks every IDLE_SLEEP_S (200 us) while it waits; 10 ms
    # of room for a loaded host's late wake-ups
    assert hold_s - 0.010 <= got[3].ring_s < 5.0
    stats = ing.ingest_stats()["workers"]["0"]
    assert stats["ring_ms"]["n"] == 4
    assert stats["ring_ms"]["max"] >= 1e3 * (hold_s - 0.010)
    assert stats["ring_observes"] > 0


@linux
@pytest.mark.parametrize("ring_wait_us,want_s", [
    (0, 0.0), (1234, 1234e-6), (0xFFFFFFFF, 0xFFFFFFFF * 1e-6),
    (1 << 40, 0xFFFFFFFF * 1e-6)])
def test_the_ring_wait_rides_header_word_7(tmp_path, ring_wait_us, want_s):
    """SealedBatchQueue writes the microseconds (saturating) where the
    header's reserved word was, and ShardedIngest._note_batch reads them
    into the batch's stamps and the worker's ``fsx.ingest.w<k>.ring``."""
    words = 4
    q = SealedBatchQueue.create(tmp_path / "q", 2, 3 * words)
    assert q.produce_batch(np.zeros(3 * words, np.uint32), seq=1,
                           n_records=2, wire_id=schema.WIRE_ID_COMPACT16,
                           seal_ns=5 * 10**9, fill_dur_us=300,
                           ring_wait_us=ring_wait_us)
    hdr, _ = SealedBatchQueue(tmp_path / "q", 3 * words).consume_batch()
    assert int(hdr[schema.BATCHQ_RING_WAIT_US_WORD]) == min(ring_wait_us,
                                                            0xFFFFFFFF)
    ing = ShardedIngest(str(tmp_path / "r"), 1, precompact=False)
    from flowsentryx_tpu.ingest.sharded import SeqTracker

    ing._seqs = SeqTracker(1)
    seq, n, t_seal, fill_s, _, ring_s = ing._note_batch(0, hdr)
    assert (seq, n, t_seal, fill_s) == (1, 2, 5.0, pytest.approx(300e-6))
    assert ring_s == pytest.approx(want_s)
    ring_span = ing.ingest_spans()[0]
    assert ring_span.name == "fsx.ingest.w0.ring"
    assert ring_span.hist.n == 1
    assert ring_span.hist.sum_us == pytest.approx(want_s * 1e6)


def _arrival_spy(eng):
    """Per ``record`` call: (kwargs, the arrival plane's growth)."""
    lat = eng._lat
    out = []
    real = lat.record

    def spy(**kw):
        n0, s0 = lat.arrival.n, lat.arrival.sum_us
        real(**kw)
        out.append((kw, lat.arrival.n - n0, lat.arrival.sum_us - s0))

    lat.record = spy
    return out


class TestArrivalToVerdict:
    @linux
    @pytest.mark.parametrize("sink_thread", [False, True])
    def test_it_is_ring_plus_seal_to_verdict_for_every_entry(
            self, tmp_path, monkeypatch, sink_thread):
        spied = []
        real_engine_run = Engine.run

        def run(self, *a, **kw):
            spied.append(_arrival_spy(self))
            return real_engine_run(self, *a, **kw)

        monkeypatch.setattr(Engine, "run", run)
        rep, _ = run_sealed(tmp_path, sink_thread=sink_thread)
        (calls,) = spied
        assert rep.records == 256 * 12 and calls
        for kw, dn, dsum in calls:
            assert kw["ring_s"] is not None and kw["ring_s"] >= 0
            assert dn == kw["n"]
            want = (kw["ring_s"] + kw["total_s"]) * 1e6 * kw["n"]
            assert dsum == pytest.approx(want, rel=1e-9, abs=1e-6)
        sp = rep.spans
        assert sp["latency.ring"]["n"] == sp[
            "latency.arrival_to_verdict"]["n"] == rep.records
        assert sp["latency.arrival_to_verdict"]["sum_us"] == pytest.approx(
            sp["latency.ring"]["sum_us"]
            + sp["latency.seal_to_verdict"]["sum_us"], rel=1e-9)
        lat = rep.latency
        assert lat["ring"]["n"] == lat["arrival_to_verdict"]["n"] \
            == rep.records
        w = rep.ingest["workers"]["0"]
        assert w["ring_ms"]["n"] == rep.batches
        # every record was in the ring before the worker first looked:
        # each batch's wait is dated from that look, and counted
        assert w["ring_wait_clamped"] == rep.batches

    def test_the_inline_path_has_no_ring_and_records_neither(
            self, tmp_path):
        rep, calls = run_inline(tmp_path, sink_thread=False)
        assert calls and all(c["ring_s"] is None for c in calls)
        sp = rep.spans
        assert sp["latency.seal_to_verdict"]["n"] == rep.records > 0
        assert sp["latency.ring"]["n"] == 0
        assert sp["latency.arrival_to_verdict"]["n"] == 0
        assert rep.latency["ring"] == {"n": 0}
        assert rep.latency["arrival_to_verdict"] == {"n": 0}

    def test_a_recorder_without_ring_keeps_its_old_callers(self):
        r = LatencyRecorder()
        r.record(1e-3, 5e-4, 1e-5, 4e-4, 1e-4, n=3)
        assert r.ring.n == r.arrival.n == 0
        r.record(1e-3, 0, 0, 0, 0, n=2, ring_s=2e-3)
        assert r.ring.n == r.arrival.n == 2
        assert r.arrival.sum_us == pytest.approx(2 * 3e3)
        merged = LatencyRecorder().merge(r)
        assert merged.arrival.sum_us == r.arrival.sum_us
        assert set(merged.hists()) >= {"latency.ring",
                                       "latency.arrival_to_verdict"}


def _host_spans_of_a_traced_run(tmp_path, runs=1):
    from benchmark import trace_host

    eng = Engine(small_cfg(), ArraySource(flood(256 * 8)), CollectSink(),
                 mega_n="auto", sink_thread=True)
    eng.warm()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        reps = [eng.run()]
        for _ in range(runs - 1):
            eng.reset_stream(ArraySource(flood(256 * 2)))
            reps.append(eng.run())
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    return reps, {name: trace_host.host_spans(path, name)
                  for name in ("fsx.serve", "fsx.report",
                               "fsx.dispatch.launch", "fsx.sink.apply")}


class TestServingWindow:
    def test_serve_closes_before_the_report(self, tmp_path):
        reps, spans = _host_spans_of_a_traced_run(tmp_path, runs=2)
        serve, report = spans["fsx.serve"], spans["fsx.report"]
        assert len(serve) == len(report) == 2
        for (s0, s1), (r0, r1) in zip(serve, report):
            assert s0 < s1 <= r0 < r1
        # every launch of a call lies inside its serving window
        for a, b in spans["fsx.dispatch.launch"]:
            assert any(s0 <= a and b <= s1 for s0, s1 in serve)
        # reset_stream starts the store afresh: the second call alone
        assert reps[-1].spans["fsx.serve"]["n"] == 1

    def test_the_report_counts_one_serve_a_run(self):
        eng = Engine(small_cfg(), ArraySource(flood(256 * 2)), CollectSink(),
                     sink_thread=False)
        rep = eng.run()
        assert rep.spans["fsx.serve"]["n"] == 1
        assert rep.spans["fsx.report"]["n"] == 0  # it closes after


# -- the benchmark's readers, rehearsed on the CPU -------------------------

def _pb_varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b, v = v & 0x7F, v >> 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _pb(field: int, value) -> bytes:
    """One field: a varint for an int, length-delimited otherwise."""
    if isinstance(value, int):
        return _pb_varint(field << 3) + _pb_varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _pb_varint(field << 3 | 2) + _pb_varint(len(value)) + value


def _plane(name: str, line: str, events) -> bytes:
    """An XPlane with one line of ``(event name, start_ns, dur_ns)``."""
    names = sorted({e[0] for e in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    md = b"".join(_pb(4, _pb(1, i) + _pb(2, _pb(1, i) + _pb(2, n)))
                  for n, i in ids.items())
    evs = b"".join(_pb(4, _pb(1, ids[n]) + _pb(2, s * 1000) + _pb(3, d * 1000))
                   for n, s, d in events)
    ln = _pb(2, line) + _pb(3, 0) + evs
    return _pb(2, name) + _pb(3, ln) + md


def _xspace(tmp_path, host_events, devices, name="t") -> str:
    space = _pb(1, _plane("/host:CPU", "python-thread", host_events))
    for k, ops in enumerate(devices):
        space += _pb(1, _plane(f"/device:TPU:{k}", "XLA Ops", ops))
    path = tmp_path / f"{name}.xplane.pb"
    path.write_bytes(space)
    return str(path)


#: serving from 1,000 to 11,000 ns; device 0 busy 7,500 ns of it, device
#: 1 all 10,000: idle 25 % and 0 %, averaged 12.5 %
HOST = [("fsx.serve", 1000, 10000), ("fsx.report", 11500, 3000),
        ("fsx.dispatch.launch", 1200, 50)]
DEVICES = [[("fusion.1", 0, 2000), ("fusion.2", 3000, 6000),
            ("fusion.3", 10500, 1500), ("sort", 4000, 500)],
           [("fusion.1", 1000, 10000), ("fusion.2", 11000, 4000)]]


def _reader(name):
    from benchmark import harness

    return harness.load_module("metrics", name)


class TestReadersRehearsed:
    def test_the_idle_share_inside_the_serving_span(self, tmp_path):
        from benchmark import trace_host

        path = _xspace(tmp_path, HOST, DEVICES)
        assert trace_host.host_spans(path, "fsx.serve") == [
            (1_000_000, 11_000_000)]
        assert trace_host.idle_share(path) == pytest.approx(12.5)
        assert trace_host.idle_share(path, "fsx.report") == pytest.approx(
            100 * (1 - (500 + 3000) / 2 / 3000))
        # cut to the device's first and last operation, as the slice is:
        # a serving span that starts before the first launch reaches the
        # device is not charged for the wait
        late = _xspace(tmp_path, [("fsx.serve", 0, 4000)],
                       [[("fusion.1", 1000, 2000)]], "late_start")
        assert trace_host.idle_share(late) == pytest.approx(0.0)
        # a program without the span, or a trace without a device plane
        assert trace_host.idle_share(path, "fsx.absent") is None
        assert trace_host.idle_share(
            _xspace(tmp_path, HOST, [], "host_only")) is None

    @pytest.mark.parametrize("name", ["device.idle_serving.tput",
                                      "device.idle_serving.lat"])
    def test_the_idle_readers(self, tmp_path, monkeypatch, name):
        from benchmark import trace_host, trace_scopes

        path = _xspace(tmp_path, HOST, DEVICES)
        monkeypatch.setattr(trace_scopes, "newest_trace", lambda: path)
        monkeypatch.setattr(trace_host, "_cache", {})
        m = _reader(name)
        assert (m.NAME, m.UNIT, m.LAYER) == (name, "%", "device")
        assert m.read(SimpleNamespace(trace={"busy_s": 1})) \
            == pytest.approx(12.5)
        assert m.read(SimpleNamespace(trace=None)) is None
        # the parent program's trace: no fsx.serve, nothing to read
        monkeypatch.setattr(trace_host, "_cache", {})
        bare = _xspace(tmp_path, HOST[1:], DEVICES, "parent")
        monkeypatch.setattr(trace_scopes, "newest_trace", lambda: bare)
        assert m.read(SimpleNamespace(trace={"busy_s": 1})) is None

    @staticmethod
    def _window(ring_us, total_us, n):
        """Two reports' ``spans`` with one recorded entry between."""
        r = LatencyRecorder()
        r.record(1e-3, 0, 0, 0, 0, n=5, ring_s=1e-3)  # before the window
        snap0 = json.loads(json.dumps(span_store(r.hists())))
        for ring, total, k in zip(ring_us, total_us, n):
            r.record(total * 1e-6, 0, 0, 0, 0, n=k, ring_s=ring * 1e-6)
        snap1 = json.loads(json.dumps(span_store(r.hists())))
        return SimpleNamespace(snap0={"rep": {"spans": snap0}},
                               snap1={"rep": {"spans": snap1}})

    @pytest.mark.parametrize("name,q,unit_layer", [
        ("ring.wait_win_p50_ms.lat", 50, "daemon and shm rings"),
        ("path.arrival_win_p50_ms.lat", 50, "whole path"),
        ("path.arrival_win_p99_ms.lat", 99, "whole path")])
    def test_the_latency_readers(self, name, q, unit_layer):
        from benchmark import span_window

        m = _reader(name)
        assert m.NAME == name and m.UNIT == "ms"
        assert m.LAYER.startswith(unit_layer)
        ctx = self._window([900, 2000, 40_000], [23_000, 23_000, 60_000],
                           [90, 9, 1])
        # ring 900 us for 90 records, 2 ms for 9, 40 ms for 1; arrival
        # 23.9 ms for 90, 25 ms for 9, 100 ms for 1
        want_us = (900 if name.startswith("ring")
                   else {50: 23_900, 99: 25_000}[q])
        assert m.read(ctx) == pytest.approx(
            span_window.upper_edge_us(_lat_bucket(want_us)) / 1e3)
        # the window only: the entry before it does not count
        assert span_window.of(ctx, "latency.ring")["n"] == 100
        # a program without the histograms (the parent) reads nothing
        bare = SimpleNamespace(snap0={"rep": {"spans": {}}},
                               snap1={"rep": {"spans": {}}})
        assert m.read(bare) is None
