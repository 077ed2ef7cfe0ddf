"""Cluster engine-process entrypoints.

:func:`engine_main` is what one ``fsx cluster`` engine process runs: a
full serving engine (jax, drain workers, dispatch arena, optional
device loop) owning one IP-space shard span, wired into the gossip
plane, honoring the supervisor's lifecycle protocol (status block
states, heartbeats via the gossip tick, stop-drain on ``c_stop``) and
writing its :class:`~flowsentryx_tpu.engine.engine.EngineReport` as
JSON where the supervisor can aggregate it.

:func:`stub_engine_main` is the lifecycle-protocol conformance stub:
it speaks the SAME status-block protocol (spawning → serving →
done/failed, heartbeats, stop, scripted crash) but boots in
milliseconds with no jax import — the supervisor's restart machinery
is tested against it in tier-1 without paying four engine boots, and
the real-engine integration is proved once per verify run by
``scripts/cluster_smoke.py``.

Both run as ``multiprocessing`` spawn targets and immediately move
into their OWN process group: the engine's drain workers inherit it,
so the supervisor can ``killpg`` the whole tree when cleaning up a
crashed engine — an orphaned worker left consuming a ring shard while
its replacement boots would be a second consumer on an SPSC ring.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback
from pathlib import Path

from flowsentryx_tpu.cluster.gossip import GossipPlane
from flowsentryx_tpu.cluster.mailbox import StatusBlock, status_path
from flowsentryx_tpu.core import schema
from flowsentryx_tpu.sync import tuning


def _own_process_group() -> None:
    try:
        os.setpgid(0, 0)
    except OSError:
        pass  # already a group leader, or a platform without setpgid


def pin_core_for(rank: int, n_engines: int, mode: str = "auto",
                 ncpu: int | None = None) -> int | None:
    """Pinning policy (pure): which core rank ``rank`` of ``n_engines``
    should own, or None to leave placement to the scheduler.

    ``auto`` pins rank r to core r exactly when the fleet fits the
    host (``n_engines <= ncpu``) — the per-core deployment shape
    (FENXI-style parallel pipelines): each engine and the drain
    workers that inherit its mask own one core, so co-scheduled
    engines never thrash each other's XLA pools.  An oversubscribed
    fleet is left unpinned (forcing two engines to time-slice one
    core while another idles is strictly worse than letting the
    scheduler balance).  ``on`` pins regardless (modulo the host);
    ``off`` never pins.
    """
    ncpu = ncpu or os.cpu_count() or 1
    if mode == "off":
        return None
    if mode == "auto" and n_engines > ncpu:
        return None
    return rank % ncpu


def pin_to_core(core: int) -> None:
    """Pin this engine process to ``core`` and right-size the XLA:CPU
    intra-op pool to match.  The pool is sized from
    ``hardware_concurrency``, which ignores the affinity mask — a
    pinned rank would otherwise time-slice an ncpu-thread pool on its
    single core (measured ~10-20% per-core throughput loss on the
    sealed-drain shape).  XLA reads ``XLA_FLAGS`` at backend
    initialization, not at import, so setting it here — before the
    engine's first jax use — is early enough even though the spawn
    target's module imports already pulled jax in."""
    os.sched_setaffinity(0, {core})
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_cpu_multi_thread_eigen=false"
          " intra_op_parallelism_threads=1").strip()


def _wait_for_token(path: str, timeout_s: float = 120.0) -> None:
    deadline = time.monotonic() + timeout_s
    while not os.path.exists(path):
        if time.monotonic() > deadline:
            raise TimeoutError(f"start token {path} never appeared")
        time.sleep(0.005)


def engine_main(spec: dict) -> int:
    """One cluster engine process (module docstring).  ``spec`` is a
    plain JSON-able dict assembled by the supervisor/CLI — see
    ``supervisor.py::engine_spec`` for the fields."""
    _own_process_group()
    if spec.get("pin_core") is not None:
        pin_to_core(spec["pin_core"])
    net = None
    if spec.get("net"):
        # the multi-host gossip leg (cluster/transport.py): built in
        # the child — the socket must live in the engine process, its
        # counters ride EngineReport.cluster.net.  Jax-free, so this
        # stays on the fast half of the boot.
        from flowsentryx_tpu.cluster.transport import engine_net_mailbox

        net = engine_net_mailbox(spec["net"], spec["rank"],
                                 spec["t0_ns"], spec["t0_wall_ns"])
    plane = GossipPlane(spec["cluster_dir"], spec["rank"],
                        spec["n_engines"], net=net)
    # pid in the status block: the adopt path's liveness probe
    # (``boot(adopt=True)`` judges an unowned rank by os.kill(pid, 0)
    # + heartbeat freshness — a proc handle it never had can't help)
    plane.status.ctl_set("c_pid", os.getpid())
    plane.set_state(schema.CSTATE_SPAWNING)
    try:
        _serve(spec, plane)
        plane.set_state(schema.CSTATE_DONE)
        return 0
    except BaseException:  # noqa: BLE001 — the crash IS the payload
        traceback.print_exc()
        plane.set_state(schema.CSTATE_FAILED)
        return 1
    finally:
        if net is not None:
            net.close()


def _serve(spec: dict, plane: GossipPlane) -> None:
    # jax and the engine import only here, inside the child — timed,
    # because import wall is part of boot-to-serving and the compile
    # cache cannot help with it (EngineReport.boot["import_s"])
    _t_imp = time.perf_counter()
    from flowsentryx_tpu.core.config import FsxConfig
    from flowsentryx_tpu.engine import Engine, NullSink
    from flowsentryx_tpu.ingest import ShardedIngest

    import_s = time.perf_counter() - _t_imp
    # the platform comes from the environment this child inherited: a
    # rank that cannot get its device dies here (the supervisor's
    # ladder shows it FAILED), it does not carry on on the CPU
    from flowsentryx_tpu.core import runtime

    runtime.require_platform(f"fsx cluster rank {spec['rank']}")
    runtime.place_compile_cache()

    rank, n = spec["rank"], spec["n_engines"]
    w = spec["workers"]
    cfg = FsxConfig.from_json(spec["cfg_json"])
    source = ShardedIngest(
        spec["ring_base"], w,
        shard_offset=rank * w,
        total_shards=spec["total_shards"],
        precompact=spec.get("precompact"),
        queue_slots=spec.get("queue_slots", 8),
        quarantine_dir=spec.get("quarantine_dir"),
    )
    if spec.get("verdict_ring"):
        from flowsentryx_tpu.engine.shm import ShmVerdictSink

        sink = ShmVerdictSink(spec["verdict_ring"])
    else:
        sink = NullSink()
    if spec.get("gossip_ring"):
        # multi-host deployments: merged PEER verdicts also reach this
        # host's daemon (single-host clusters leave it unset — the
        # peer's own verdict ring already fed the shared kernel map)
        from flowsentryx_tpu.engine.shm import ShmVerdictSink

        plane.sink = ShmVerdictSink(spec["gossip_ring"])
    params = None
    if spec.get("artifact"):
        from flowsentryx_tpu.models.registry import load_artifact

        params = load_artifact(cfg.model.name, spec["artifact"])
    eng = Engine(
        cfg, source, sink,
        params=params,
        t0_ns=spec["t0_ns"],
        mega_n=spec.get("mega") or 0,
        slo_us=spec.get("slo_us") or 0,
        predict=bool(spec.get("predict")),
        watchdog_s=spec.get("watchdog_s"),
        gossip=plane,
        compile_cache=spec.get("compile_cache"),
    )
    eng.boot_import_s = round(import_s, 4)
    restore_info = None
    if spec.get("restore"):
        restore_info = eng.restore(spec["restore"])
    # live-rebalance hooks (cluster/rebalance.py): boot-time reconcile
    # first — adopt a committed-but-uninserted staged spool and drop
    # rows the committed layout says this rank no longer owns (the two
    # post-flip death windows) — then step the handoff state machine
    # between run chunks below, where the engine is quiescent.
    from flowsentryx_tpu.cluster.rebalance import EngineRebalancer

    rebalancer = EngineRebalancer(
        spec["cluster_dir"], rank, plane.status,
        crash_midship=bool(spec.get("handoff_crash_midship")))
    reconciled = rebalancer.reconcile(eng)
    # tiered: SERVING opens on the top-rung tier while a background
    # thread fills the rest of the ladder from the compile cache —
    # the sub-second-boot path for crash-respawns and GROW spares
    eng.warm(tiered=bool(spec.get("tiered_warm")))
    if spec.get("ready_token"):
        Path(spec["ready_token"]).touch()
    if spec.get("start_token"):
        _wait_for_token(spec["start_token"])
    if plane.net is not None:
        # peer discovery with retry/backoff — and FAIL OPEN on
        # timeout: a silent peer host is its supervisor's incident,
        # not a reason to withhold serving this span; when it appears
        # its first HELLO triggers a full-map resync (transport.py)
        from flowsentryx_tpu.cluster.transport import NetHandshakeTimeout

        try:
            plane.net.handshake(
                spec["net"].get("handshake_timeout_s",
                                tuning.NET_HANDSHAKE_TIMEOUT_S))
        except NetHandshakeTimeout as e:
            print(f"fsx cluster rank {rank}: {e} — serving fail-open",
                  file=sys.stderr)
    plane.set_state(schema.CSTATE_SERVING)

    chunk_s = spec.get("chunk_s", 0.5)
    ckpt = spec.get("checkpoint")
    every = spec.get("checkpoint_every") or 0
    max_seconds = spec.get("max_seconds")
    max_batches = spec.get("max_batches")
    t0 = time.perf_counter()
    next_ckpt = time.monotonic() + every if (ckpt and every) else None
    rep = None
    stopped = False
    if spec.get("drain"):
        # drain mode (bench/smoke): the ring shards are prefilled and
        # the fleet runs stop-to-exhaustion in ONE timed run — the
        # sealed-drain trial shape every paced artifact uses, with no
        # chunk-boundary overhead inside the measured wall
        source.request_stop()
        rep = eng.run()
        plane.note_progress(rep.batches, rep.records)
    else:
        while True:
            rep = eng.run(max_seconds=chunk_s)
            plane.note_progress(rep.batches, rep.records)
            rebalancer.step(eng)
            if next_ckpt is not None and time.monotonic() >= next_ckpt:
                eng.checkpoint(ckpt)
                # the checkpoint now covers any adopted rows: release
                # the staged spool (their durable copy until this save)
                rebalancer.note_checkpointed()
                next_ckpt = time.monotonic() + every
            if plane.stop_requested() and not stopped:
                # drain-on-stop: workers empty their ring shards, the
                # engine serves the tail, THEN we exit — the fleet's
                # drain-on-shutdown contract, cluster-wide
                stopped = True
                source.request_stop()
                rep = eng.run()
                plane.note_progress(rep.batches, rep.records)
                break
            if source.exhausted():
                break
            if (max_seconds is not None
                    and time.perf_counter() - t0 >= max_seconds):
                break
            if max_batches is not None and rep.batches >= max_batches:
                break
    wall = time.perf_counter() - t0
    # Converge-on-shutdown: serving is done and the LOCAL wall is
    # closed, but peers draining the same fleet may still be sinking
    # their tails — stamp DRAINING (every publish this engine will
    # ever make happened-before the store) and keep force-merging
    # peers' wires until each peer has ALSO left SERVING and the
    # mailboxes run dry, so co-terminating drains write byte-identical
    # blacklist views into their reports (the smoke's convergence
    # check).
    plane.set_state(schema.CSTATE_DRAINING)
    peers = {p: StatusBlock(status_path(spec["cluster_dir"], p))
             for p in range(n) if p != rank}
    _QUIET = (schema.CSTATE_DRAINING, schema.CSTATE_DONE,
              schema.CSTATE_FAILED)
    plane.quiesce(
        spec.get("gossip_quiesce_s", tuning.GOSSIP_QUIESCE_S),
        peers_quiet=lambda: all(st.ctl_get("c_state") in _QUIET
                                for st in peers.values()))
    # re-snapshot the gossip accounting: the quiesce merges above are
    # exactly what the report's convergence digests exist to show
    rep = rep._replace(cluster=plane.report())
    if ckpt:
        eng.checkpoint(ckpt)
    source.close()
    rep = rep._replace(
        wall_s=round(wall, 4),
        records_per_s=round(rep.records / max(wall, 1e-9), 1),
        ingest=source.ingest_stats(),
    )
    if spec.get("report_path"):
        out = {
            "rank": rank, "n_engines": n, "gen": spec.get("gen", 0),
            "restored": restore_info,
            "reconciled": reconciled,
            "report": rep._asdict(),
        }
        p = Path(spec["report_path"])
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(out, indent=2) + "\n")  # noqa: report file, informational


def prewarm_main(spec: dict) -> int:
    """One-shot fleet pre-warm: compile the fleet's staged geometry
    into the persistent compile cache so a later GROW spare (or a
    crash respawn) warms on pure cache hits — sub-second to SERVING
    while the burst it was spawned for is still landing.

    Spawned by the supervisor at elastic-fleet boot when the engine
    specs carry ``compile_cache``.  Spare ranks are provisioned at max
    with the SAME spec (same cfg/mega/params geometry), so one child
    with a null source covers every rank: ``warm()`` the FULL ladder,
    storing each executable, then exit.  Best-effort and non-blocking: the fleet
    never waits on it, and any failure just means the spare compiles
    (fail-open, like every cache path)."""
    _own_process_group()
    try:
        import numpy as np

        from flowsentryx_tpu.core.config import FsxConfig
        from flowsentryx_tpu.core.schema import RECORD_WORDS
        from flowsentryx_tpu.engine import Engine, NullSink
        from flowsentryx_tpu.engine.sources import ArraySource

        from flowsentryx_tpu.core import runtime

        runtime.require_platform("fsx cluster prewarm")
        runtime.place_compile_cache()
        cfg = FsxConfig.from_json(spec["cfg_json"])
        params = None
        if spec.get("artifact"):
            from flowsentryx_tpu.models.registry import load_artifact

            params = load_artifact(cfg.model.name, spec["artifact"])
        eng = Engine(
            cfg,
            ArraySource(np.empty((0, RECORD_WORDS), np.uint32)),
            NullSink(),
            params=params,
            mega_n=spec.get("mega") or 0,
            slo_us=spec.get("slo_us") or 0,
            sink_thread=False,
            compile_cache=spec["compile_cache"],
        )
        eng.warm()
        rep = eng._cache.report() if eng._cache is not None else {}
        print(f"fsx cluster prewarm: cache ready at {rep.get('dir')} "
              f"(stores {rep.get('stores', 0)}, hits "
              f"{rep.get('hits', 0)}) — GROW spares warm from it",
              file=sys.stderr)
        return 0
    except BaseException:  # noqa: BLE001 — best-effort, announced
        traceback.print_exc()
        return 1


def stub_engine_main(spec: dict) -> int:
    """Lifecycle-protocol stub (module docstring): heartbeats, honors
    stop, optionally crashes on schedule (``stub_crash_after_s``, first
    generation only — the restart must then succeed; with
    ``stub_crash_every_gen`` EVERY generation — the chaos campaign's
    crash-loop fault, which the supervisor must park, not chase), and
    records the restore path the supervisor handed it, so tier-1 can
    prove the supervision protocol in milliseconds."""
    _own_process_group()
    plane = GossipPlane(spec["cluster_dir"], spec["rank"],
                        spec["n_engines"])
    plane.status.ctl_set("c_pid", os.getpid())  # adopt-path liveness
    plane.set_state(schema.CSTATE_SPAWNING)
    gen = spec.get("gen", 0)
    crash_after = spec.get("stub_crash_after_s")
    serve_s = spec.get("stub_serve_s", 0.5)
    plane.set_state(schema.CSTATE_SERVING)
    t0 = time.monotonic()
    while time.monotonic() - t0 < serve_s:
        plane.tick(force=True)  # heartbeat + merge, the engine cadence
        if plane.stop_requested() and not spec.get("stub_ignore_stop"):
            break
        if crash_after is not None \
                and (gen == 0 or spec.get("stub_crash_every_gen")) \
                and time.monotonic() - t0 >= crash_after:
            os._exit(17)  # simulated hard death: no cleanup, no DONE
        time.sleep(0.01)
    if spec.get("report_path"):
        p = Path(spec["report_path"])
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps({  # noqa: report file, informational
            "rank": spec["rank"], "gen": gen, "stub": True,
            "restored": spec.get("restore"),
            "report": {"records": 0, "batches": 0},
        }) + "\n")
    plane.set_state(schema.CSTATE_DONE)
    return 0
