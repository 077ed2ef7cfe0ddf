"""The streamed save (``engine/checkpoint.py``): an uncompressed ``.npz``
written section by section through ``durable.publish_stream``, the
snapshots of the compressed format still loading, the host holding the
fetched table and one column at a time, the ``fsx crash`` checker
exploring the streamed publish; and the engine's save policy
(``CheckpointPolicy``) that ``fsx serve --checkpoint-every`` sets."""

import io
import json
import threading
import time
import tracemalloc
import zipfile
import zlib

import numpy as np
import pytest

from flowsentryx_tpu.core import durable, schema
from flowsentryx_tpu.crash import checker
from flowsentryx_tpu.crash.simfs import SimFS, Tracer
from flowsentryx_tpu.engine import checkpoint as ckpt

ROWS = 1 << 16


def host_table(rows: int = ROWS, seed: int = 0) -> schema.IpTableState:
    rng = np.random.default_rng(seed)
    occupied = rng.random(rows) < 0.125
    key = np.where(occupied, rng.integers(1, 1 << 32, rows, np.uint32), 0)
    state = rng.random((rows, schema.NUM_TABLE_COLS), np.float32)
    state[~occupied] = 0.0
    return schema.IpTableState(key=key.astype(np.uint32), state=state)


def stats_of(n: int = 7) -> schema.GlobalStats:
    return schema.GlobalStats(*(np.array([n + i, i], np.uint32)
                                for i in range(len(schema.GlobalStats._fields))))


def assert_same(ck, table, stats, t0_ns, salt):
    assert ck.crc_checked
    assert not ck.missing_columns and not ck.missing_stats
    np.testing.assert_array_equal(ck.table.key, np.asarray(table.key))
    np.testing.assert_array_equal(ck.table.state, np.asarray(table.state))
    for got, want in zip(ck.stats, stats):
        np.testing.assert_array_equal(got, np.asarray(want))
    assert (ck.t0_ns, ck.hash_salt, ck.capacity) == (
        t0_ns, salt, len(table.key))


class TestStreamedSave:
    @pytest.mark.parametrize("where", ["host", "device"])
    def test_round_trips_at_2_16_rows(self, tmp_path, where, monkeypatch):
        import jax

        # small CRC pieces: the thread pool and the combine both run
        monkeypatch.setattr(ckpt, "CRC_PIECE", 1 << 12)
        table, stats = host_table(), stats_of()
        saved = table if where == "host" else schema.IpTableState(
            key=jax.device_put(table.key), state=jax.device_put(table.state))
        path = ckpt.save_state(tmp_path / "snap", saved, stats, 1234,
                               hash_salt=0x77)
        assert path.name == "snap.npz"
        assert_same(ckpt.load_checkpoint(path), table, stats, 1234, 0x77)
        # a plain zip of stored .npy members, every CRC the zip's own
        with zipfile.ZipFile(path) as z:
            assert z.testzip() is None
            infos = z.infolist()
            assert {i.compress_type for i in infos} == {zipfile.ZIP_STORED}
            names = [i.filename for i in infos]
        assert names[-1] == "integrity_crc32.npy"
        assert names[:-1] == sorted(names[:-1])
        # each local header holds the member's CRC, put there after its
        # body was written
        raw = path.read_bytes()
        for i in infos:
            at = i.header_offset + ckpt._LOCAL_CRC
            assert int.from_bytes(raw[at:at + 4], "little") == i.CRC
        with np.load(path) as z:
            np.testing.assert_array_equal(
                z["table_blocked_until"],
                table.state[:, schema.TableCol.BLOCKED_UNTIL])
            assert z["t0_ns"].shape == ()

    def test_a_compressed_snapshot_still_loads_with_its_crc(self, tmp_path):
        """The format the save wrote before it streamed:
        ``np.savez_compressed`` of every section, the fold over them."""
        table, stats = host_table(1 << 12, seed=3), stats_of(11)
        entries = {
            "table_key": table.key,
            **{f"table_{n}": table.state[:, i]
               for i, n in enumerate(schema.TABLE_COLUMN_NAMES)},
            **{f"stats_{k}": v for k, v in stats._asdict().items()},
            "t0_ns": np.uint64(99), "hash_salt": np.uint64(3),
            "n_shards": np.uint64(1),
            "capacity": np.uint64(len(table.key)),
            "schema_version": np.int64(ckpt.CHECKPOINT_SCHEMA_VERSION),
        }
        buf = io.BytesIO()
        np.savez_compressed(buf, integrity_crc32=np.uint32(
            ckpt._fold_crc(entries)), **entries)
        old = tmp_path / "old.npz"
        old.write_bytes(buf.getvalue())
        assert_same(ckpt.load_checkpoint(old), table, stats, 99, 3)
        # and its CRC is checked, not skipped: a section swapped for
        # other bytes of the same size is refused
        entries["table_ml_votes"] = entries["table_ml_votes"] + 1
        buf = io.BytesIO()
        np.savez_compressed(buf, integrity_crc32=np.uint32(
            ckpt._fold_crc({**entries, "table_ml_votes":
                            table.state[:, schema.TableCol.ML_VOTES]})),
            **entries)
        old.write_bytes(buf.getvalue())
        with pytest.raises(ckpt.CheckpointCorrupt, match="integrity"):
            ckpt.load_checkpoint(old)

    def test_the_host_holds_the_table_and_one_column(self, tmp_path):
        """Besides the table it was handed, a save allocates one
        column's copy at a time (and small change): not a second table,
        not a buffer of the file."""
        rows = 1 << 18
        table, stats = host_table(rows), stats_of()
        column = rows * 4
        tracemalloc.start()
        try:
            base = tracemalloc.get_traced_memory()[0]
            tracemalloc.reset_peak()
            ckpt.save_state(tmp_path / "m.npz", table, stats, 1)
            peak = tracemalloc.get_traced_memory()[1] - base
        finally:
            tracemalloc.stop()
        assert column <= peak < column + (256 << 10), peak

    @pytest.mark.parametrize("n1,n2", [(0, 0), (1, 7), (4096, 5),
                                       (1 << 20, (1 << 20) + 3)])
    def test_crc32_combine_is_zlibs(self, n1, n2):
        rng = np.random.default_rng(n1 + n2)
        a = rng.integers(0, 256, n1, np.uint8).tobytes()
        b = rng.integers(0, 256, n2, np.uint8).tobytes()
        assert ckpt.crc32_combine(zlib.crc32(a), zlib.crc32(b), n2) \
            == zlib.crc32(a + b)


class TestStreamedPublish:
    def test_the_sim_fs_traces_the_same_five_steps(self):
        t = Tracer()
        t.enabled = True
        fs = SimFS(t)
        fs.write_atomic("/d/f", b"g1")
        fs.write_stream("/d/f", lambda f: (f.write(b"ab"), f.write(b"cd")),
                        rotate_prev="/d/f.prev")
        assert [op for _, op in t.ops][4:] == [
            "write f.tmp (4 B)", "fsync f.tmp", "rename f -> f.prev",
            "rename f.tmp -> f", "fsync parent dir of f"]
        assert fs.read_bytes("/d/f") == b"abcd"
        assert fs.read_bytes("/d/f.prev") == b"g1"

    def test_a_failed_stream_keeps_the_incumbent(self, tmp_path):
        p = tmp_path / "c.npz"
        durable.publish_stream(p, lambda f: f.write(b"good"))

        def half(f):
            f.write(b"par")
            raise OSError("disk full")

        with pytest.raises(OSError):
            durable.publish_stream(p, half, rotate_prev=tmp_path / "c.prev")
        assert p.read_bytes() == b"good"
        assert sorted(x.name for x in tmp_path.iterdir()) == ["c.npz"]

    def test_fsx_crash_explores_it_at_every_point(self, monkeypatch):
        """The checkpoint scenario's saves are streamed publishes, and
        every crash point of each, every torn temp file among them,
        keeps the checker's invariants."""
        streamed = []
        write_stream = SimFS.write_stream

        def spy(self, path, write, **kw):
            streamed.append(str(path))
            return write_stream(self, path, write, **kw)

        monkeypatch.setattr(SimFS, "write_stream", spy)
        res = checker.explore_scenario(checker.CheckpointScenario())
        assert res["violations"] == 0, res["counterexample"]
        # three saves: 4 points, then 5 each with the .prev rotation
        assert res["crash_points"] == 14
        assert res["states_explored"] > res["crash_points"]
        assert streamed and all(p.endswith(".npz") for p in streamed)


class TestCheckpointPolicy:
    def test_the_grid_skips_and_counts_an_overrun_slot(self):
        pol = ckpt.CheckpointPolicy("/x.npz", 10.0, first_s=5.0)
        pol.start(100.0)
        assert pol.next_at == 105.0
        pol.saved(106.0, 2.0, 8, {"batches": 1})    # slot 0, ends 108
        assert (pol.next_at, pol.skipped) == (115.0, 0)
        pol.saved(115.5, 21.0, 8, {"batches": 2})   # slot 1, ends 136.5
        assert (pol.next_at, pol.skipped) == (145.0, 2)  # 125, 135
        pol.saved(170.0, 1.0, 8, {"batches": 3})    # slot 4, served late
        assert (pol.next_at, pol.skipped) == (175.0, 4)  # 155, 165
        rep = pol.report()
        assert (rep["saves"], rep["bytes"], rep["skipped"]) == (3, 24, 4)
        assert rep["last"]["batches"] == 3
        with pytest.raises(ValueError):
            ckpt.CheckpointPolicy("/x.npz", 0.0)


def engine(src_records: int, **kw):
    from flowsentryx_tpu.engine import CollectSink, Engine, TrafficSource
    from flowsentryx_tpu.engine.traffic import TrafficSpec

    from test_engine import small_cfg

    eng = Engine(small_cfg(batch=128),
                 TrafficSource(TrafficSpec(seed=9), total=src_records),
                 CollectSink(), **kw)
    eng.warm()  # no compile inside the timed runs below
    return eng


class SlowFS(durable.RealFS):
    def __init__(self, delay_s: float):
        self.delay_s = delay_s

    def write_stream(self, path, write, **kw):
        time.sleep(self.delay_s)
        super().write_stream(path, write, **kw)


class TestEngineSavesOnItsGrid:
    def test_a_save_at_the_first_boundary_after_its_slot(self, tmp_path):
        eng = engine(128 * 40)
        pol = ckpt.CheckpointPolicy(tmp_path / "e.npz", 3600.0, first_s=0.0)
        eng.set_checkpoint_policy(pol)
        rep = eng.run()
        assert rep.records == 128 * 40
        c = rep.checkpoint
        assert (c["saves"], c["skipped"], c["every_s"]) == (1, 0, 3600.0)
        last = c["last"]
        assert c["bytes"] == last["bytes"] == (tmp_path / "e.npz").stat(
        ).st_size
        # the save stands at one point: its table holds the batches it
        # names, and its stats count those batches' records
        assert 1 <= last["batches"] <= rep.batches
        loaded = ckpt.load_checkpoint(tmp_path / "e.npz")
        counted = sum(schema.stat_value(getattr(loaded.stats, k))
                      for k in ("allowed", "dropped_blacklist",
                                "dropped_rate", "dropped_ml"))
        assert counted == last["records"] == 128 * last["batches"]
        assert schema.stat_value(loaded.stats.batches) == last["batches"]
        assert last["clock_s"] == pytest.approx(float(
            loaded.table.state[:, schema.TableCol.LAST_SEEN].max()))
        for name in ("fetch", "crc", "write", "publish"):
            assert rep.spans[f"fsx.ckpt.{name}"]["n"] >= 1
        json.dumps(c)

    def test_no_policy_saves_nothing(self, tmp_path):
        eng = engine(128 * 8)
        rep = eng.run()
        assert rep.checkpoint is None
        assert rep.spans["fsx.ckpt.fetch"]["n"] == 0
        assert list(tmp_path.iterdir()) == []

    def test_a_save_slower_than_the_stall_bound_trips_no_watchdog(
            self, tmp_path):
        """A save that the file system holds for three stall bounds:
        nothing sinks while it runs, and the watchdog does not take
        that for a stalled pipe.  The slots it overran are skipped."""
        eng = engine(128 * 20000, watchdog_s=0.1, readback_depth=4)
        pol = ckpt.CheckpointPolicy(tmp_path / "w.npz", 0.1, first_s=0.0)
        eng.set_checkpoint_policy(pol)
        with durable.use_fs(SlowFS(0.3)):
            rep = eng.run(max_seconds=1.0)
        assert eng._watchdog.to_dict() == {
            "stall_s": 0.1, "soft_trips": 0, "hard_tripped": False}
        c = rep.checkpoint
        # each save outlasts two more slots of the grid
        assert c["saves"] >= 2 and c["skipped"] >= 2 * c["saves"] - 1
        assert rep.records < 128 * 20000  # it served on after each save
        assert pol.last["seconds"] >= 0.3


class HeldFS(durable.RealFS):
    """A file system whose streamed publish waits for ``go`` before it
    writes a byte: a save held in flight.  It logs each publish's start
    and end by thread, and the most publishes it ever had open at once."""

    def __init__(self):
        self.go = threading.Event()
        self.entered = threading.Event()
        self.log: list[tuple[str, str]] = []
        self.open = self.most_open = 0
        self._lock = threading.Lock()

    def write_stream(self, path, write, **kw):
        me = threading.current_thread().name
        with self._lock:
            self.open += 1
            self.most_open = max(self.most_open, self.open)
            self.log.append(("start", me))
        self.entered.set()
        try:
            assert self.go.wait(120), "the held save was never let go"
            super().write_stream(path, write, **kw)
        finally:
            with self._lock:
                self.open -= 1
                self.log.append(("end", me))


def boundary_spy(monkeypatch) -> dict:
    """``checkpoint.snapshot`` wrapped: the host copy of the table, the
    stats and the clock anchor it is handed, at the boundary."""
    seen = {}
    real = ckpt.snapshot

    def spy(table, stats, t0_ns, **kw):
        seen.update(key=np.array(table.key), state=np.array(table.state),
                    stats=[np.array(v) for v in stats], t0_ns=t0_ns)
        return real(table, stats, t0_ns, **kw)

    monkeypatch.setattr(ckpt, "snapshot", spy)
    return seen


class TestTheSaveOverlapsServing:
    @pytest.mark.parametrize("shards", [1, 4])
    def test_the_held_save_is_the_boundarys_state_bit_for_bit(
            self, tmp_path, monkeypatch, shards):
        """The save is held in flight while the engine serves on, each
        step donating and changing the table; what it publishes is the
        table, stats and clock of its boundary to the bit, taken by one
        device program into buffers of their own (the key copied too),
        sent in pieces."""
        from flowsentryx_tpu.parallel import make_mesh

        monkeypatch.setattr(ckpt, "PIECE_BYTES", 4000)  # 5 a column
        eng = engine(128 * 4000, mesh=make_mesh(shards) if shards > 1
                     else None)
        seen = boundary_spy(monkeypatch)
        # the save thread cuts its pieces with programs of one device
        # each: one over several, launched beside the serving steps,
        # could order its collectives differently on each device
        cut_on = []
        piece_program = ckpt._piece_program

        def one_device(rows):
            def cut(a, start):
                cut_on.append(len(a.sharding.device_set))
                return piece_program(rows)(a, start)
            return cut

        monkeypatch.setattr(ckpt, "_piece_program", one_device)
        fs = HeldFS()
        after = []

        def on_reap(n, t):
            if fs.entered.is_set():
                after.append(n)
                if len(after) == 16:
                    fs.go.set()

        eng.on_reap = on_reap
        pol = ckpt.CheckpointPolicy(tmp_path / "h.npz", 3600.0, first_s=0.0)
        eng.set_checkpoint_policy(pol)
        with durable.use_fs(fs):
            rep = eng.run()
        assert len(after) >= 16 and fs.go.is_set()
        assert fs.log == [("start", "fsx-ckpt"), ("end", "fsx-ckpt")]
        # serving changed the table after the boundary
        assert not np.array_equal(np.asarray(eng.table.state),
                                  seen["state"])
        loaded = ckpt.load_checkpoint(tmp_path / "h.npz")
        assert loaded.crc_checked and loaded.n_shards == shards
        np.testing.assert_array_equal(loaded.table.key, seen["key"])
        assert loaded.table.state.view(np.uint32).tobytes() == \
            seen["state"].view(np.uint32).tobytes()
        for got, want in zip(loaded.stats, seen["stats"]):
            np.testing.assert_array_equal(got, want)
        assert loaded.t0_ns == seen["t0_ns"] == eng.batcher.t0_ns
        last = rep.checkpoint["last"]
        assert last["clock_s"] == pytest.approx(float(
            seen["state"][:, schema.TableCol.LAST_SEEN].max()))
        assert schema.stat_value(loaded.stats.batches) == last["batches"]
        # the hold is a sliver of the save, and serving went on beside it
        assert 0 < last["hold_s"] < last["seconds"]
        assert last["records_during"] >= 128 * 16
        assert rep.spans["fsx.ckpt.hold"]["n"] == 1
        assert rep.spans["fsx.ckpt.fetch"]["n"] >= 4 * 13
        assert len(cut_on) >= 4 * 13 and set(cut_on) == {1}

    def test_the_snapshots_key_outlives_the_next_donated_step(
            self, monkeypatch):
        import jax

        table = host_table(1 << 12)
        dev = schema.IpTableState(key=jax.device_put(table.key),
                                  state=jax.device_put(table.state))
        sections = ckpt.snapshot(dev, stats_of(), 5)
        key = sections["table_key"]
        assert key.unsafe_buffer_pointer() != dev.key.unsafe_buffer_pointer()
        step = jax.jit(lambda k, s: (k + 1, s * 2), donate_argnums=(0, 1))
        step(dev.key, dev.state)
        assert dev.key.is_deleted() and not key.is_deleted()
        np.testing.assert_array_equal(np.asarray(key), table.key)
        np.testing.assert_array_equal(
            np.asarray(sections["table_ml_votes"]),
            table.state[:, schema.TableCol.ML_VOTES])
        # sent in pieces: 1,000 rows, then the 96 left
        monkeypatch.setattr(ckpt, "PIECE_BYTES", 4000)
        pieces = [ckpt._make(p) for p in ckpt._cut(key)]
        assert [len(p) for p in pieces] == [1000] * 4 + [96]
        np.testing.assert_array_equal(np.concatenate(pieces), table.key)

    def test_policy_off_and_an_explicit_save_wait_for_the_save_in_flight(
            self, tmp_path):
        """``set_checkpoint_policy(None)`` and ``checkpoint()`` each wait
        for the policy's save in flight: the explicit save publishes
        after it, so ``.prev`` holds the policy's generation."""
        eng = engine(128 * 8)
        eng.run()
        path = tmp_path / "p.npz"
        for stop in (lambda: eng.set_checkpoint_policy(None),
                     lambda: eng.checkpoint(path)):
            pol = ckpt.CheckpointPolicy(path, 3600.0, first_s=0.0)
            eng.set_checkpoint_policy(pol)
            fs = HeldFS()
            with durable.use_fs(fs):
                eng._checkpoint_due()  # the boundary: the hold, then go
                assert fs.entered.wait(60) and pol.saves == 0
                waiter = threading.Thread(target=stop)
                waiter.start()
                waiter.join(0.3)
                assert waiter.is_alive()  # held behind the save in flight
                fs.go.set()
                waiter.join(60)
            assert not waiter.is_alive() and pol.saves == 1
            assert fs.most_open == 1
        # the explicit save ran after the policy's had published
        assert fs.log == [("start", "fsx-ckpt"), ("end", "fsx-ckpt"),
                          ("start", waiter.name), ("end", waiter.name)]
        prev = ckpt.prev_path(path)
        assert prev.exists()
        assert ckpt.load_checkpoint(prev).crc_checked
        assert ckpt.load_checkpoint(path).crc_checked
        assert eng._save is None

    def test_a_fault_in_the_save_thread_raises_on_the_dispatch_thread(
            self, tmp_path):
        class FullFS(durable.RealFS):
            def write_stream(self, path, write, **kw):
                raise OSError("disk full")

        total = 128 * 20000
        eng = engine(total)
        pol = ckpt.CheckpointPolicy(tmp_path / "f.npz", 3600.0, first_s=0.0)
        eng.set_checkpoint_policy(pol)
        with durable.use_fs(FullFS()), pytest.raises(OSError,
                                                     match="disk full"):
            eng.run()
        # raised at a boundary soon after, not at the end of the stream
        assert eng.batcher.records_emitted < total
        assert pol.saves == 0 and eng._save is None
        assert list(tmp_path.iterdir()) == []
        eng.set_checkpoint_policy(None)  # nothing left to raise

    def test_a_slot_due_while_a_save_is_in_flight_is_skipped_and_counted(
            self, tmp_path):
        eng = engine(128 * 20000)
        fs = HeldFS()
        pol = ckpt.CheckpointPolicy(tmp_path / "s.npz", 0.05, first_s=0.0)
        eng.set_checkpoint_policy(pol)
        threading.Timer(0.4, fs.go.set).start()
        with durable.use_fs(fs):
            rep = eng.run(max_seconds=1.0)
        c = rep.checkpoint
        # one save in flight at a time, the first held for 0.4 s: the
        # seven or more slots that fell due meanwhile were skipped
        assert fs.most_open == 1
        assert c["saves"] >= 1 and c["skipped"] >= 7
        assert c["saves"] == rep.spans["fsx.ckpt.hold"]["n"]
        assert rep.spans["fsx.ckpt.publish"]["n"] == c["saves"]


def test_serve_checkpoint_every_goes_through_the_policy(tmp_path, capsys,
                                                        monkeypatch):
    from flowsentryx_tpu import cli
    from flowsentryx_tpu.engine import Engine

    taken = []
    set_policy = Engine.set_checkpoint_policy

    def spy(self, policy):
        taken.append(policy)
        set_policy(self, policy)

    monkeypatch.setattr(Engine, "set_checkpoint_policy", spy)
    path = tmp_path / "serve.npz"
    assert cli.main(["serve", "--scenario", "syn_benign_mix", "--rate",
                     "1e6", "--packets", "4096", "--checkpoint", str(path),
                     "--checkpoint-every", "30"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert len(taken) == 1 and isinstance(taken[0], ckpt.CheckpointPolicy)
    assert (taken[0].path, taken[0].every_s) == (str(path), 30.0)
    assert rep["checkpoint"]["every_s"] == 30.0
    assert rep["records"] == 4096
    ckpt.load_checkpoint(path)  # the save after the run
