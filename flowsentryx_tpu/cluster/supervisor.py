"""Cluster supervisor: spawn, watch, restart — never on the data path.

"Coordinator-less" is a DATA-plane property: verdict gossip is
pairwise SPSC mailboxes, every engine owns its IP-space shard
end-to-end, and no packet ever waits on anything cluster-wide.  The
supervisor here is pure CONTROL plane — it creates the shm plane,
stamps the shared t0 epoch, spawns one engine process per rank,
watches liveness, and restarts the dead from their last checkpoint.
Its own death changes nothing for the engines already serving; a new
supervisor re-attaches to the same status blocks.

Crash-fail-open (docs/CLUSTER.md §fail-open): when an engine dies,

* its IP-space shard keeps being mitigated at the XDP tier — the
  blocks it published are already in the kernel map (its own verdict
  ring) and in every peer's merged view (the gossip plane), and the
  kernel limiter stands alone for NEW flows in that span, the same
  posture every other degradation in this system takes;
* the supervisor ``killpg``\\s the corpse's process group first (an
  orphaned drain worker still consuming a ring shard would be a
  second consumer on an SPSC ring the moment the replacement boots),
  then respawns the rank with ``gen+1`` and ``restore=`` its last
  checkpoint, so the replacement resumes with its flow memory intact
  (PR 8 restore/reshard machinery);
* surviving engines never notice: their mailboxes to the dead rank
  fill and drop (counted), their own serving is untouched.
"""

from __future__ import annotations

import json
import multiprocessing as mp
import os
import signal
import time
from pathlib import Path

from flowsentryx_tpu.cluster import gossip as gplane
from flowsentryx_tpu.cluster.mailbox import StatusBlock, status_path
from flowsentryx_tpu.core import durable, schema
# jax-free engine leaves (engine/__init__ is lazy — no jax rides in):
# the HDR histogram class whose bucket counts the per-rank reports
# carry, merged here into the cluster latency view, and the health
# ladder the aggregate folds worst-of across ranks
from flowsentryx_tpu.engine import health as health_mod
from flowsentryx_tpu.engine.metrics import LatencyHist
from flowsentryx_tpu.sync import tuning


def _pid_alive(pid: int) -> bool:
    """Liveness of a process this supervisor never spawned (adopted
    ranks): signal 0 probes existence without touching it."""
    if pid <= 0:
        return False
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True  # exists, different uid
    return True


class ClusterSupervisor:
    """Supervise ``len(specs)`` engine processes (module docstring).

    ``specs[r]`` is the rank-r engine spec consumed by
    :func:`~flowsentryx_tpu.cluster.runner.engine_main` (or the
    ``entry`` override — the lifecycle stub in tier-1 tests).  The
    supervisor fills in the lifecycle fields it owns: ``gen``,
    ``t0_ns``, ``report_path`` and — on a restart, when the rank's
    checkpoint exists — ``restore``.
    """

    def __init__(
        self,
        cluster_dir: str | Path,
        specs: list[dict],
        *,
        entry=None,
        max_restarts: int = 2,
        heartbeat_timeout_s: float = tuning.SUPERVISOR_HEARTBEAT_TIMEOUT_S,
        restart_backoff_s: float = tuning.RESPAWN_BACKOFF_BASE_S,
        restart_backoff_max_s: float = tuning.RESPAWN_BACKOFF_MAX_S,
        restart_window_s: float = tuning.RESTART_WINDOW_S,
        k_max: int = 64,
        mailbox_slots: int = 256,
        t0_ns: int | None = None,
        t0_wall_ns: int | None = None,
        net: dict | None = None,
        elastic=None,
        n_live: int | None = None,
    ):
        if len(specs) < 2 and net is None:
            raise ValueError(
                f"a cluster needs >= 2 engines, got {len(specs)} "
                "(one engine is fsx serve)")
        if len(specs) < 1:
            raise ValueError("a cluster needs >= 1 engine")
        self.cluster_dir = Path(cluster_dir)
        self.n = len(specs)
        self.specs = specs
        #: Real engines vs lifecycle stubs: pre-warming the compile
        #: cache only makes sense when spares will boot REAL engines
        #: (an entry override is tier-1's millisecond stub fleet).
        self._entry_is_real = entry is None
        if entry is None:
            from flowsentryx_tpu.cluster.runner import engine_main

            entry = engine_main
        self._entry = entry
        self.max_restarts = max_restarts
        self.heartbeat_timeout_s = heartbeat_timeout_s
        # crash-loop discipline (sync/tuning.py rationale): respawns
        # back off exponentially, and only deaths inside the sliding
        # window count against the budget — a rank that dies instantly
        # N times PARKS as failed (its span announced) instead of
        # burning the whole budget in milliseconds.
        self.restart_backoff_s = restart_backoff_s
        self.restart_backoff_max_s = restart_backoff_max_s
        self.restart_window_s = restart_window_s
        self.k_max = k_max
        self.mailbox_slots = mailbox_slots
        self.t0_ns = t0_ns
        self.t0_wall_ns = t0_wall_ns
        #: multi-host net spec (``fsx cluster --hosts``): hosts/
        #: host_id/engines_per_host/listen — consumed by
        #: transport.engine_net_mailbox in each child and by the
        #: federation beacon below.  None = single-host, net-free.
        self.net = net
        self.federation = None
        self._dead_hosts_announced: set[int] = set()
        self._ctx = mp.get_context("spawn")  # engines own jax + workers
        self._procs: list[mp.process.BaseProcess | None] = [None] * self.n
        self._status: list[StatusBlock] = []
        self._gen = [0] * self.n
        self.restarts = [0] * self.n
        #: monotonic stamps of each rank's deaths inside the window
        self._death_times: list[list[float]] = [[] for _ in range(self.n)]
        #: rank -> monotonic due-time of a backoff-delayed respawn
        self._respawn_at: dict[int, float] = {}
        self._failed: set[int] = set()
        self._done: set[int] = set()
        self._stalled: set[int] = set()
        self._booted = False
        self._stop_sent = False
        # -- elastic fleet (ISSUE 16; cluster/rebalance.py+elastic.py)
        #: Autoscaling policy (cluster/elastic.py ElasticPolicy) or
        #: None for a fixed fleet.  The plane is provisioned at
        #: ``len(specs)`` ( = max_engines) so a grow is JUST a spawn:
        #: status blocks, mailboxes and ring files for every possible
        #: rank exist from boot; mailboxes to unspawned ranks fill and
        #: drop (counted), the universal fail-open posture.
        self._elastic = elastic
        #: Ranks this supervisor currently runs.  run()/poll() judge
        #: completion against this set, not ``range(n)`` — parked
        #: (shrunk) ranks leave it without counting as failed.
        self._active: set[int] = set(range(
            self.n if n_live is None else max(1, min(n_live, self.n))))
        #: Ranks adopted live from a previous supervisor
        #: (boot(adopt=True)): no proc handle — poll() judges them by
        #: os.kill(c_pid, 0) + heartbeat freshness instead.
        self._adopted: set[int] = set()
        #: The ONE in-flight handoff (serialized fleet-wide: the flip
        #: rule's "every rank converges before the fence lifts" is a
        #: statement about a single layout generation at a time).
        self._handoff: dict | None = None
        self._handoff_seq = 0
        self.rebalance_counters = {
            "rows_shipped": 0, "flips": 0, "fences": 0, "aborts": 0,
            "adoptions": 0}
        self.adopted_spans: list[dict] = []
        self.elastic_executed = 0
        self._elastic_next = 0.0
        self._pending_grow: dict | None = None
        self._pending_shrink: dict | None = None
        #: the one-shot compile-cache pre-warm child (elastic fleets
        #: with ``compile_cache`` specs; :meth:`_maybe_prewarm`)
        self._prewarm_proc: mp.process.BaseProcess | None = None
        self.prewarm_spawned = 0
        self._shrunk: set[int] = set()
        self._last_records: dict[int, tuple[float, int]] = {}
        self._rates: dict[int, float] = {}

    # -- lifecycle ----------------------------------------------------------

    def boot(self, adopt: bool = False) -> None:
        """Create the shm plane, stamp the epoch, spawn every rank.

        ``adopt=True`` re-attaches to an EXISTING plane instead of
        creating one (:meth:`_adopt_plane`): the live-engine scan that
        makes a cold boot refuse is exactly the adopt path's rank
        census — live ranks keep serving untouched (judged by pid +
        heartbeat from here on), dead ranks respawn ``gen+1`` from
        their checkpoints.  A supervisor death is thereby a non-event
        for the fleet, both directions.
        """
        if self._booted:
            raise RuntimeError("ClusterSupervisor already booted")
        self._booted = True
        self.cluster_dir.mkdir(parents=True, exist_ok=True)
        if adopt:
            self._adopt_plane()
            return
        self._refuse_live_plane()
        gplane.create_plane(self.cluster_dir, self.n, k_max=self.k_max,
                            slots=self.mailbox_slots,
                            net=self.net is not None)
        self._write_initial_layout()
        if self.t0_ns is None:
            # the shared epoch: every engine's device clock and every
            # gossiped `until` is relative to this one anchor, which is
            # what makes cross-engine untils byte-comparable — and the
            # wall twin stamped at the SAME instant is what lets a
            # PEER HOST rebase this host's wires into its own epoch
            # (monotonic clocks are per-host; cluster/transport.py)
            self.t0_ns = time.clock_gettime_ns(time.CLOCK_MONOTONIC)
            if self.t0_wall_ns is None:
                self.t0_wall_ns = time.time_ns()
        if self.t0_wall_ns is None:
            # externally-supplied monotonic epoch (tests, re-anchored
            # fleets): derive the wall stamp so the pair still names
            # one instant
            self.t0_wall_ns = time.time_ns() - (
                time.clock_gettime_ns(time.CLOCK_MONOTONIC)
                - self.t0_ns)
        for r in range(self.n):
            st = StatusBlock(status_path(self.cluster_dir, r))
            st.ctl_set("c_t0", self.t0_ns)
            st.ctl_set("c_t0_wall", self.t0_wall_ns)
            st.ctl_set("c_gen", 0)
            self._status.append(st)
        if self.net is not None:
            from flowsentryx_tpu.cluster import transport

            self.federation = transport.host_beacon(
                self.net, self.t0_wall_ns,
                interval_s=self.net.get(
                    "beacon_interval_s", tuning.NET_BEACON_INTERVAL_S),
                timeout_s=self.net.get(
                    "host_timeout_s", tuning.NET_HOST_TIMEOUT_S))
        for r in range(self.n):
            if r in self._active:
                self._spawn(r)
        self._maybe_prewarm()

    def _maybe_prewarm(self) -> None:
        """Fleet pre-warm: when the fleet is elastic and its specs
        carry a compile cache, spawn ONE short-lived background child
        (:func:`runner.prewarm_main`) that compiles the fleet's staged
        geometry into the cache at boot.  Spare ranks are provisioned
        at max with the same spec, so a later GROW spawn's ``warm()``
        is pure cache hits — the spare reaches SERVING in well under a
        second instead of paying the full ladder compile while the
        burst it was spawned for is already landing.  Best-effort and
        non-blocking: the fleet never waits on it (daemon child), and
        if it dies the spare just compiles — fail-open like every
        cache path.  Stub fleets (entry override) skip: their spares
        boot in milliseconds with no jax at all."""
        if self._elastic is None or not self._entry_is_real:
            return
        spec = next(
            (s for s in self.specs if s.get("compile_cache")), None)
        if spec is None:
            return
        from flowsentryx_tpu.cluster.runner import prewarm_main

        p = self._ctx.Process(target=prewarm_main, args=(dict(spec),),
                              name="fsx-cluster-prewarm", daemon=True)
        p.start()
        self._prewarm_proc = p
        self.prewarm_spawned += 1

    def _uniform_workers(self) -> int:
        """The per-rank ring width when every spec agrees on one (the
        shard-assignment precondition); 0 when specs carry none (the
        lifecycle stubs — no rings, no layout)."""
        ws = {s.get("workers") for s in self.specs}
        return int(next(iter(ws))) if len(ws) == 1 and None not in ws \
            else 0

    def _write_initial_layout(self) -> None:
        """Publish the generation-0 shard assignment (layout.json):
        ``total_shards = n * w`` FIXED for the fleet's lifetime, spans
        of unspawned ranks folded onto the live ones — every shard has
        one live owner from the first record (rebalance.py)."""
        from flowsentryx_tpu.cluster import rebalance as rb

        w = self._uniform_workers()
        if not w:
            return
        rb.ShardAssignment.initial(
            self.n * w, w, len(self._active)).save(self.cluster_dir)

    def _adopt_plane(self) -> None:
        """boot(adopt=True): attach to a plane a previous supervisor
        left behind.  Precondition: the plane exists and matches this
        fleet's shape (the inverse of :meth:`_refuse_live_plane` — a
        live plane is exactly what this path wants).  Live ranks (pid
        alive + fresh heartbeat) are adopted as-is; dead ranks respawn
        ``gen+1`` from their checkpoints through the normal crash
        path."""
        plane_file = self.cluster_dir / "plane.json"
        if not plane_file.exists():
            raise RuntimeError(
                f"adopt=True but {plane_file} does not exist — nothing "
                "to adopt; boot without adopt to create the plane")
        meta = json.loads(plane_file.read_text())
        if int(meta.get("n_engines", -1)) != self.n:
            raise RuntimeError(
                f"adopt=True: plane has {meta.get('n_engines')} "
                f"engines, this supervisor supervises {self.n} — an "
                "adopted fleet must match the plane's shape")
        now_ns = time.clock_gettime_ns(time.CLOCK_MONOTONIC)
        _LIVE = (schema.CSTATE_SPAWNING, schema.CSTATE_SERVING,
                 schema.CSTATE_DRAINING)
        dead: list[int] = []
        for r in range(self.n):
            st = StatusBlock(status_path(self.cluster_dir, r))
            self._status.append(st)
            # the shared epoch is the PLANE's, not ours: every gossiped
            # `until` in flight is relative to it
            if self.t0_ns is None and st.ctl_get("c_t0"):
                self.t0_ns = st.ctl_get("c_t0")
                self.t0_wall_ns = st.ctl_get("c_t0_wall") or None
            self._gen[r] = st.ctl_get("c_gen")
            state = st.ctl_get("c_state")
            hb = st.ctl_get("c_hbeat")
            pid = st.ctl_get("c_pid")
            fresh = (hb and 0 <= now_ns - hb
                     < 2 * self.heartbeat_timeout_s * 1e9)
            if state in _LIVE and fresh and pid and _pid_alive(pid):
                # serving: adopt untouched (no proc handle — poll()
                # judges this rank by its pid from now on)
                self._adopted.add(r)
                self._active.add(r)
            elif state == schema.CSTATE_DONE:
                self._done.add(r)
                self._active.discard(r)
            elif r in self._active:
                dead.append(r)
        if self.t0_ns is None:
            raise RuntimeError(
                "adopt=True: no rank ever stamped the shared epoch — "
                "this plane never served; boot without adopt")
        self._neutralize_stale_handoff()
        if self.net is not None:
            from flowsentryx_tpu.cluster import transport

            self.federation = transport.host_beacon(
                self.net, self.t0_wall_ns,
                interval_s=self.net.get(
                    "beacon_interval_s", tuning.NET_BEACON_INTERVAL_S),
                timeout_s=self.net.get(
                    "host_timeout_s", tuning.NET_HOST_TIMEOUT_S))
        for r in dead:
            # died under the previous supervisor: the normal crash
            # path — gen+1, restore from its last checkpoint
            self.restarts[r] += 1
            self._gen[r] += 1
            self._spawn(r)

    def _refuse_live_plane(self) -> None:
        """Booting over a LIVE plane must refuse: ``create_plane``
        re-truncates every mailbox/status file, which yanks the pages
        out from under serving engines' mmaps (SIGBUS on their next
        publish/tick) and would attach this fleet as a SECOND consumer
        to ring shards the orphans still drain.  A dead fleet's
        leftover plane is fine to stomp; to take over a LIVE fleet,
        use ``boot(adopt=True)`` — the same scan, inverted into the
        adopt path's rank census (:meth:`_adopt_plane`)."""
        now_ns = time.clock_gettime_ns(time.CLOCK_MONOTONIC)
        _LIVE = (schema.CSTATE_SPAWNING, schema.CSTATE_SERVING,
                 schema.CSTATE_DRAINING)
        live = []
        for r in range(self.n):
            p = Path(status_path(self.cluster_dir, r))
            if not p.exists():
                continue
            try:
                st = StatusBlock(p)
                state, hb = st.ctl_get("c_state"), st.ctl_get("c_hbeat")
            except Exception:
                continue  # partial/corrupt leftover: not a live fleet
            # a heartbeat FROM THE FUTURE (now_ns - hb < 0) is a stale
            # plane from before a host reboot — CLOCK_MONOTONIC
            # restarted under it; only a non-negative fresh age is live
            if (state in _LIVE and hb
                    and 0 <= now_ns - hb
                    < 2 * self.heartbeat_timeout_s * 1e9):
                live.append((r, (now_ns - hb) * 1e-9))
        if live:
            detail = ", ".join(
                f"rank {r} heartbeated {age:.1f}s ago"
                for r, age in live)
            raise RuntimeError(
                f"cluster dir {self.cluster_dir} has live engines "
                f"({detail}; liveness bound "
                f"{2 * self.heartbeat_timeout_s:.0f}s): re-creating "
                "the plane would truncate their mmap'd mailboxes "
                "mid-serve (SIGBUS on their next publish) and attach "
                "this fleet as a second consumer on their SPSC ring "
                "shards. Remediation: adopt the live fleet instead "
                "(boot(adopt=True) / fsx cluster --adopt), stop the "
                "old fleet (its own supervisor's stop-drain, or kill "
                "the listed ranks and wait for their heartbeats to go "
                "stale), or point --cluster-dir at a fresh directory")

    def _spawn(self, rank: int) -> None:
        spec = dict(self.specs[rank])
        gen = self._gen[rank]
        spec["rank"] = rank
        spec["n_engines"] = self.n
        spec["cluster_dir"] = str(self.cluster_dir)
        spec["gen"] = gen
        spec["t0_ns"] = self.t0_ns
        spec["t0_wall_ns"] = self.t0_wall_ns
        if self.net is not None:
            spec["net"] = self.net
        # per-gen default; a caller-provided report_path is honored for
        # every generation (later gens overwrite it — aggregate()'s
        # latest-gen pick only needs the per-rank dedup)
        spec.setdefault(
            "report_path",
            str(self.cluster_dir / f"report_r{rank}_g{gen}.json"))
        if gen > 0:
            ckpt = spec.get("checkpoint")
            if ckpt:
                ck_file = Path(self._ckpt_file(ckpt))
                # `<name>.npz.prev` is checkpoint.prev_path's layout
                # (inlined: engine/checkpoint.py imports jax, and this
                # module must stay on the jax-free import path): the
                # retained generation covers both a corrupt live file
                # (Engine.restore falls back itself) and the crash
                # window between save_state's two renames, where the
                # live file is briefly absent.
                prev = ck_file.with_name(ck_file.name + ".prev")
                if ck_file.exists() or prev.exists():
                    # resume with flow memory intact (Engine.restore;
                    # geometry matches by construction — same spec).
                    # Always hand over the LIVE path: when it is
                    # absent or corrupt, Engine.restore performs the
                    # .prev fallback ITSELF — announced and counted in
                    # the health ladder (restore_fallbacks); adopting
                    # .prev here would launder a stale-generation
                    # resume into a clean-looking restore.
                    spec["restore"] = str(ck_file)
        p = self._ctx.Process(target=self._entry, args=(spec,),
                              name=f"fsx-cluster-r{rank}")
        p.start()
        self._procs[rank] = p
        self._adopted.discard(rank)  # ours now: judged by proc handle
        self._status[rank].ctl_set("c_gen", gen)

    @staticmethod
    def _ckpt_file(path: str) -> str:
        """checkpoint.save_state normalizes suffix-less paths to .npz —
        mirror that when probing for a restorable file."""
        p = Path(path)
        return str(p if p.suffix == ".npz"
                   else p.with_suffix(p.suffix + ".npz"))

    def _killpg(self, proc: mp.process.BaseProcess) -> None:
        """Kill a dead engine's whole process group (module docstring:
        orphaned drain workers must not outlive their engine)."""
        if proc.pid is None:
            return
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError, OSError):
            pass

    def kill(self, rank: int) -> None:
        """Chaos hook: SIGKILL one rank's whole process group, exactly
        the death the crash-fail-open path must absorb (the smoke and
        the fail-open tests drive this; the next :meth:`poll` observes
        the corpse and restarts it from its last checkpoint)."""
        p = self._procs[rank]
        if p is not None and p.is_alive():
            self._killpg(p)
            # a child killed before its setpgid makes killpg a no-op
            # (no such group yet) — SIGKILL the process itself too, so
            # the chaos hook's contract ("rank is dead on return") holds
            # at every point of the child's life
            p.kill()
            p.join(timeout=2.0)

    def _announce_park(self, rank: int, recent: int) -> None:
        """A rank exhausted its sliding-window restart budget: park it
        as failed with its IP-space span ANNOUNCED — the operator must
        know which flows just fell to the kernel limiter alone, and a
        log line at death #1 scrolled away long ago."""
        import sys

        w = self.specs[rank].get("workers")
        span = (f"ring shards [{rank * w}, {(rank + 1) * w})"
                if w else f"rank {rank}'s shard span")
        print(
            f"fsx cluster: rank {rank} PARKED as failed — {recent} "
            f"death(s) within the {self.restart_window_s:.0f}s restart "
            f"window (budget {self.max_restarts}); {span} fails open "
            "to the kernel tier. Fix the crash cause and restart the "
            "fleet to re-serve it.", file=sys.stderr)

    def _announce_dead_host(self, host: int) -> None:
        """A peer HOST went silent past the federation timeout: its
        whole engine fleet — every IP-hash span it owned — is now
        mitigated by its local kernel tier alone.  Announced with the
        span and the remediation, the _announce_park discipline one
        level up."""
        import sys

        n_eng = int(self.net.get("engines_per_host", 0) or 0)
        hosts = self.net.get("hosts") or []
        addr = (f"{hosts[host][0]}:{hosts[host][1]}"
                if host < len(hosts) else "?")
        span = (f"its {n_eng} engine span(s)" if n_eng
                else "its engine spans")
        print(
            f"fsx cluster: peer host {host} ({addr}) DEAD — no "
            f"federation beacon for "
            f"{self.federation.timeout_s:.0f}s; {span} fail open to "
            "that host's kernel tier. Fleet health folds FAILED until "
            "the host returns (its first beacon/HELLO re-joins it and "
            "triggers a gossip resync).", file=sys.stderr)

    def poll(self) -> None:
        """One supervision pass: liveness, heartbeat staleness,
        restart-or-fail decisions under the crash-loop discipline
        (exponential backoff + sliding-window budget; sync/tuning.py
        has the measured rationale for both)."""
        now_ns = time.clock_gettime_ns(time.CLOCK_MONOTONIC)
        now = time.monotonic()
        if self.federation is not None:
            # federation heartbeats: beacon our liveness, ingest
            # peers', and announce a peer host's death ONCE per
            # incident — its span falls open to its local kernel tier
            # and fleet health folds FAILED (aggregate below)
            self.federation.tick()
            dead = set(self.federation.dead_hosts())
            for h in sorted(dead - self._dead_hosts_announced):
                self._announce_dead_host(h)
            # a revived host leaves the set, so a relapse re-announces
            self._dead_hosts_announced = dead
        for r in range(self.n):
            if (r not in self._active or r in self._failed
                    or r in self._done):
                continue
            # a backoff-delayed respawn whose delay elapsed fires now
            if r in self._respawn_at:
                if now >= self._respawn_at[r]:
                    del self._respawn_at[r]
                    self.restarts[r] += 1
                    self._gen[r] += 1
                    self._spawn(r)
                continue
            p = self._procs[r]
            st = self._status[r]
            state = st.ctl_get("c_state")
            if p is None and r in self._adopted:
                # adopted rank: no proc handle — pid + heartbeat are
                # the liveness evidence (boot(adopt=True)).  DONE is
                # judged BEFORE pid liveness: the exited child is a
                # zombie only its original (dead) supervisor could
                # reap, so its pid can read alive indefinitely
                if state == schema.CSTATE_DONE:
                    self._adopted.discard(r)
                    self._done.add(r)
                    continue
                pid = st.ctl_get("c_pid")
                if not _pid_alive(pid):
                    self._adopted.discard(r)
                    if state == schema.CSTATE_DONE:
                        self._done.add(r)
                        continue
                    if pid:
                        try:  # orphaned drain workers, same as killpg
                            os.killpg(pid, signal.SIGKILL)
                        except (ProcessLookupError, PermissionError,
                                OSError):
                            pass
                    self._decide_respawn(r, now)
                    continue
            elif p is not None and not p.is_alive():
                if state == schema.CSTATE_DONE:
                    self._done.add(r)
                    continue
                # died without DONE: crash-fail-open — clean up the
                # whole tree, then decide restart-vs-park against the
                # sliding window (deaths older than the window are
                # yesterday's incident, not this crash loop's)
                self._killpg(p)
                p.join(timeout=1.0)
                self._procs[r] = None  # corpse handled
                self._decide_respawn(r, now)
                continue
            hb = st.ctl_get("c_hbeat")
            if (hb and state == schema.CSTATE_SERVING
                    and now_ns - hb > self.heartbeat_timeout_s * 1e9):
                self._stalled.add(r)
            else:
                self._stalled.discard(r)
        self._handoff_tick(now)

    def _decide_respawn(self, r: int, now: float) -> None:
        """Restart-vs-park under the crash-loop discipline (sliding
        window + exponential backoff) — shared by the proc-handle and
        adopted-pid death paths."""
        self._death_times[r] = [
            t for t in self._death_times[r]
            if now - t < self.restart_window_s]
        recent = len(self._death_times[r])
        self._death_times[r].append(now)
        if recent < self.max_restarts:
            delay = min(
                self.restart_backoff_s * (2 ** recent),
                self.restart_backoff_max_s)
            self._respawn_at[r] = now + delay
        else:
            self._failed.add(r)
            self._announce_park(r, recent + 1)

    # -- live shard handoff coordination (cluster/rebalance.py) -------------

    def live_ranks(self) -> list[int]:
        """Active ranks currently able to serve (spawned or adopted,
        not failed/done/parked)."""
        return [r for r in sorted(self._active)
                if r not in self._failed and r not in self._done
                and r not in self._shrunk
                and (self._procs[r] is not None or r in self._adopted)]

    def start_handoff(self, shards, donor: int, recipient: int, *,
                      rows=None) -> int:
        """Open one handoff (module docstring of rebalance.py has the
        full state machine): write the descriptor, create the mailbox,
        stamp the fence — the engines do the rest between run chunks;
        :meth:`poll` advances the supervisor half.  ``rows`` switches
        to checkpoint-sourced adoption: the SUPERVISOR is the donor
        (``donor=-1``) and publishes the rows itself — the dead rank
        has no process to ask."""
        from flowsentryx_tpu.cluster import rebalance as rb

        if self._handoff is not None:
            raise RuntimeError(
                "a handoff is already in flight (one shard span moves "
                "at a time, fleet-wide)")
        asg = rb.ShardAssignment.load(self.cluster_dir)
        if asg is None:
            raise RuntimeError("no layout.json: this fleet has no "
                               "shard assignment to rebalance")
        shards = sorted(int(s) for s in shards)
        self._handoff_seq += 1
        hid = self._handoff_seq
        mbx_path = rb.handoff_mailbox_path(self.cluster_dir, hid)
        n_rows = None
        if rows is not None:
            keys, states = rows
            n_rows = len(keys)
            # size the mailbox to hold the WHOLE stream: the
            # supervisor must not block its control loop waiting for
            # the recipient to drain mid-publish
            per = 512
            need = max(2, (n_rows + per - 1) // per + 2)
            slots = 1
            while slots < need:
                slots *= 2
            mbx = rb.mailbox_cls().create(mbx_path, slots=slots,
                                          rows_per_slot=per)
            rb.ship_rows(mbx, keys, states)
        else:
            rb.mailbox_cls().create(mbx_path)
        rb._write_atomic(rb.handoff_json_path(self.cluster_dir),
                         json.dumps({
                             "id": hid, "shards": shards,
                             "donor": donor, "recipient": recipient,
                             "to_gen": asg.generation + 1,
                             "total_shards": asg.total_shards,
                             "source": "ckpt" if rows is not None
                             else "engine",
                         }) + "\n")
        for r in ([recipient] if donor < 0 else [donor, recipient]):
            self._status[r].ctl_set("c_fence", hid)
        self.rebalance_counters["fences"] += 1
        self._handoff = {
            "id": hid, "shards": shards, "donor": donor,
            "recipient": recipient, "to_gen": asg.generation + 1,
            "phase": "shipping", "n_rows": n_rows,
            "deadline": time.monotonic() + tuning.HANDOFF_TIMEOUT_S,
        }
        return hid

    def _handoff_phase_of(self, rank: int, hid: int) -> int:
        from flowsentryx_tpu.cluster import rebalance as rb

        return rb._phase_of(self._status[rank].ctl_get("c_handoff"),
                            hid)

    def _redeliver_stamps(self, h: dict | None) -> None:
        """Idempotent re-delivery of the supervisor's cross-party
        stamps (found by ``fsx live``'s ``handoff_drop`` scenario: a
        LOST stamp — torn ctl write, a respawning rank racing the
        write, the model's dropped edge — was previously written
        exactly once, and a rank waiting on it waited forever; the
        committing phase never aborts, so the whole fleet wedged
        behind one lost message).  Re-asserted every tick, guarded by
        a read so the steady state writes nothing — the crash
        checker's trace-point census stays unchanged on clean runs.

        Two stamps qualify (both supervisor-owned, both idempotent):
        the fence LIFT (no handoff in flight ⇒ every ``c_fence`` must
        read 0) and the commit's ``c_layout_gen`` (in committing phase
        every rank must observe the new generation — the flip is
        already durable in layout.json, so re-stamping can never
        disagree with it)."""
        if h is None:
            for st in self._status:
                if st.ctl_get("c_fence"):
                    st.ctl_set("c_fence", 0)
            return
        if h["phase"] == "committing":
            for r in range(self.n):
                st = self._status[r]
                if st.ctl_get("c_layout_gen") != h["to_gen"]:
                    st.ctl_set("c_layout_gen", h["to_gen"])

    def _handoff_tick(self, now: float) -> None:
        from flowsentryx_tpu.cluster import rebalance as rb

        h = self._handoff
        if h is None:
            self._redeliver_stamps(None)
            return
        if h["phase"] == "shipping":
            # pre-commit, abort is always safe: nothing moved — the
            # donor owns the span until layout.json says otherwise
            live = self.live_ranks()
            party_dead = (h["recipient"] not in live
                          or (h["donor"] >= 0 and h["donor"] not in live))
            if party_dead or now > h["deadline"]:
                self._abort_handoff(
                    "party died" if party_dead else "timed out")
                return
            donor_ok = (h["donor"] < 0
                        or self._handoff_phase_of(h["donor"], h["id"])
                        >= schema.HP_SHIPPED)
            recip_ok = (self._handoff_phase_of(h["recipient"], h["id"])
                        >= schema.HP_STAGED)
            if donor_ok and recip_ok:
                # COMMIT: the atomic flip — layout.json first (the
                # durable truth a crashed rank reconciles against),
                # then the generation stamp every rank observes
                asg = rb.ShardAssignment.load(self.cluster_dir)
                asg = asg.reassign(h["shards"], h["recipient"])
                asg.save(self.cluster_dir)
                for r in range(self.n):
                    self._status[r].ctl_set("c_layout_gen",
                                            asg.generation)
                self.rebalance_counters["flips"] += 1
                if h["n_rows"] is None:
                    try:  # the staged spool is the shipped-row census
                        sp = rb.load_spool(rb.staged_path(
                            self.cluster_dir, h["recipient"]))
                        h["n_rows"] = (int(len(sp["keys"]))
                                       if sp is not None else 0)
                    except (OSError, ValueError, KeyError):
                        h["n_rows"] = 0
                h["phase"] = "committing"
            return
        # committing: the flip is DURABLE — never aborted.  The fence
        # lifts only when every live active rank has echoed the new
        # generation (a dead rank's respawn acks via its boot-time
        # reconcile, so this converges without a force)
        self._redeliver_stamps(h)
        waiting = [r for r in sorted(self._active)
                   if r not in self._failed and r not in self._done
                   and self._status[r].ctl_get("c_layout_ack")
                   < h["to_gen"]]
        if not waiting:
            self._finish_handoff()

    def _clear_fences(self) -> None:
        for st in self._status:
            st.ctl_set("c_fence", 0)

    def _finish_handoff(self) -> None:
        from flowsentryx_tpu.cluster import rebalance as rb

        h = self._handoff
        self._clear_fences()
        self.rebalance_counters["rows_shipped"] += int(h["n_rows"] or 0)
        fs = durable.get_fs()
        # NOT unlinked here: the recipient's staged spool.  Until the
        # recipient's next checkpoint covers the adopted rows, the
        # spool is their only durable copy — the recipient releases it
        # itself (EngineRebalancer.note_checkpointed).  Unlinking at
        # finish lost the rows at power crash (fsx crash checker).
        for p in (rb.handoff_json_path(self.cluster_dir),
                  Path(rb.handoff_mailbox_path(self.cluster_dir,
                                               h["id"]))):
            try:
                fs.unlink(p)
            except OSError:
                pass
        self._handoff = None

    def _abort_handoff(self, why: str) -> None:
        """Pre-commit unwind: clear the fence, delete the descriptor /
        mailbox / spool.  The recipient discards its staged rows on
        observing the cleared fence (counted); the donor never stopped
        owning the span — exact conservation by doing nothing."""
        import sys

        from flowsentryx_tpu.cluster import rebalance as rb

        h = self._handoff
        self._clear_fences()
        fs = durable.get_fs()
        for p in (rb.handoff_json_path(self.cluster_dir),
                  Path(rb.handoff_mailbox_path(self.cluster_dir,
                                               h["id"]))):
            try:
                fs.unlink(p)
            except OSError:
                pass
        # the spool goes only if it was staged for THIS (uncommitted)
        # attempt — one kept from an earlier committed flip is still
        # the recipient's durable copy (rebalance.py helper docstring)
        rb.discard_uncommitted_spool(self.cluster_dir, h["recipient"])
        self.rebalance_counters["aborts"] += 1
        print(f"fsx cluster: handoff {h['id']} (shards {h['shards']} "
              f"rank {h['donor']} -> {h['recipient']}) ABORTED: {why}; "
              "donor keeps the span, nothing moved", file=sys.stderr)
        self._handoff = None

    def _neutralize_stale_handoff(self) -> None:
        """Adopt-path hygiene (found by the fsx crash checker's
        supervisor-crash mode): a supervisor that died mid-handoff
        leaves the fence stamped and handoff.json/mailbox/spool
        behind, and a successor's handoff ids restart at 1 — so its
        FIRST handoff would collide with the dead attempt's id, read
        the stale ``c_handoff`` acks and spool as its own, and commit
        a flip whose rows were never shipped (row loss).  On adopt:
        clear every fence (a fence with no live coordinator wedges the
        span's ingest forever), seed the id counter past the stale id,
        then either RESUME the handoff (flip already committed — the
        layout is durable truth, the fleet just has to finish
        observing it) or delete the dead attempt's artifacts (not
        committed — nothing moved, the donor still owns the span, the
        next handoff retries under a fresh id)."""
        from flowsentryx_tpu.cluster import rebalance as rb

        fs = durable.get_fs()
        self._clear_fences()
        p = rb.handoff_json_path(self.cluster_dir)
        if not fs.exists(p):
            return
        try:
            stale = json.loads(fs.read_text(p))
        except (OSError, ValueError):
            stale = {}
        hid = int(stale.get("id", 0) or 0)
        self._handoff_seq = max(self._handoff_seq, hid)
        asg = rb.ShardAssignment.load(self.cluster_dir)
        committed = (asg is not None and "to_gen" in stale
                     and asg.generation >= int(stale["to_gen"]))
        if committed and "recipient" in stale and "shards" in stale:
            # the flip is DURABLE: RESUME it instead of cleaning it.
            # The dead supervisor may have committed layout.json and
            # then died before stamping c_layout_gen — without this
            # re-stamp no live rank ever learns the new generation
            # (engines react to ctl stamps, not to layout.json polls),
            # the donor never drops, the recipient never inserts, and
            # the fleet wedges on an un-announced flip (found by the
            # fsx crash checker's supervisor-crash mode).  Re-stamping
            # is idempotent for ranks that already observed it, and
            # the normal committing -> finish path then converges and
            # deletes the artifacts.
            for st in self._status:
                st.ctl_set("c_layout_gen", asg.generation)
            self._handoff = {
                "id": hid,
                "shards": [int(s) for s in stale["shards"]],
                "donor": int(stale.get("donor", -1)),
                "recipient": int(stale["recipient"]),
                "to_gen": int(stale["to_gen"]),
                "phase": "committing",
                "n_rows": None,
                "deadline": time.monotonic()
                + tuning.HANDOFF_TIMEOUT_S,
            }
            return
        doomed = [p]
        if hid:
            doomed.append(Path(rb.handoff_mailbox_path(
                self.cluster_dir, hid)))
        for d in doomed:
            try:
                fs.unlink(d)
            except OSError:
                pass
        if "recipient" in stale:
            # guarded: a spool from an earlier COMMITTED flip is the
            # recipient's durable copy and must survive this cleanup
            rb.discard_uncommitted_spool(self.cluster_dir,
                                         int(stale["recipient"]))

    def adopt_dead_span(self, dead_rank: int, recipient: int) -> dict:
        """Dead-span adoption: ship a confirmed-dead rank's span to a
        survivor from its LAST CHECKPOINT (the supervisor is the
        donor — jax-free npz read, rebalance.load_ckpt_rows).  Rows
        newer than the checkpoint died with the rank (the same loss
        window every gen+1 restart has always had); what the
        checkpoint holds is conserved exactly.  Announced in
        :meth:`aggregate` as ``adopted_spans``."""
        from flowsentryx_tpu.cluster import rebalance as rb

        asg = rb.ShardAssignment.load(self.cluster_dir)
        if asg is None:
            raise RuntimeError("no layout.json: nothing to adopt")
        span = asg.spans_of(dead_rank)
        if not span:
            raise RuntimeError(f"rank {dead_rank} owns no shards")
        ckpt = self.specs[dead_rank].get("checkpoint")
        keys = states = None
        if ckpt:
            ck_file = Path(self._ckpt_file(ckpt))
            prev = ck_file.with_name(ck_file.name + ".prev")
            for cand in (ck_file, prev):
                if durable.get_fs().exists(cand):
                    try:
                        keys, states = rb.load_ckpt_rows(cand)
                        break
                    except (OSError, ValueError, KeyError):
                        continue
        if keys is None:
            import numpy as np

            keys = np.empty(0, np.uint32)
            states = np.empty((0, schema.NUM_TABLE_COLS), np.float32)
        # only the dead rank's span rows ship (its checkpoint should
        # hold nothing else, but a pre-flip snapshot may)
        import numpy as np

        sel = np.isin(schema.shard_of(keys, asg.total_shards),
                      np.asarray(span, np.uint32))
        hid = self.start_handoff(span, -1, recipient,
                                 rows=(keys[sel], states[sel]))
        entry = {"dead_rank": dead_rank, "recipient": recipient,
                 "shards": list(span), "rows": int(np.sum(sel)),
                 "handoff_id": hid}
        self.adopted_spans.append(entry)
        self.rebalance_counters["adoptions"] += 1
        return entry

    # -- autoscaling (cluster/elastic.py) ------------------------------------

    def _ring_backlog(self) -> dict[int, int]:
        """Unread records per live rank, straight off the shm ring
        cursors (head u64 minus tail u64 — the producer/consumer
        cursor pair every ring publishes).  This is the REAL ingest
        queue depth, readable without attaching as a consumer and
        without waiting for a report."""
        out: dict[int, int] = {}
        w = self._uniform_workers()
        if not w:
            return out
        for r in self.live_ranks():
            base = self.specs[r].get("ring_base")
            total = self.specs[r].get("total_shards", self.n * w)
            if not base:
                continue
            depth = 0
            for s in range(r * w, (r + 1) * w):
                p = schema.shard_ring_path(base, s, total)
                try:
                    with open(p, "rb") as f:
                        f.seek(schema.SHM_HEAD_OFFSET)
                        head = int.from_bytes(f.read(8), "little")
                        f.seek(schema.SHM_TAIL_OFFSET)
                        tail = int.from_bytes(f.read(8), "little")
                    depth += max(0, head - tail)
                except OSError:
                    continue
            out[r] = depth
        return out

    def _sample_signals(self, now: float) -> dict:
        """The elastic signal vector: ring backlog (above) + per-rank
        record-rate skew from the c_records counters.  Report-borne
        signals (p99 vs slo, gossip tx_drop, watchdog trips) ride in
        when the caller merges the last aggregate — mid-run, the ctl
        plane is what exists."""
        backlog = self._ring_backlog()
        live = self.live_ranks()
        rates = []
        for r in live:
            rec = self._status[r].ctl_get("c_records")
            prev = self._last_records.get(r)
            self._last_records[r] = (now, rec)
            if prev and now > prev[0]:
                rate = max(0.0, (rec - prev[1]) / (now - prev[0]))
                self._rates[r] = rate
                rates.append(rate)
        signals: dict = {}
        if backlog:
            vals = [backlog.get(r, 0) for r in live]
            signals["backlog_per_engine"] = (
                sum(vals) / max(1, len(vals)))
            signals["backlog_max"] = max(vals) if vals else 0
            signals["backlog"] = {str(r): backlog.get(r, 0)
                                  for r in live}
        if rates and max(rates) > 0:
            mean = sum(rates) / len(rates)
            signals["rate_skew"] = (max(rates) / mean) if mean else 1.0
        return signals

    def elastic_tick(self, now: float | None = None) -> dict | None:
        """One autoscaler tick (run() calls this each poll when a
        policy is installed): sample → decide → execute.  Every
        executed plan is printed WITH its signal vector — an
        unauditable autoscaler is an outage generator."""
        if self._elastic is None:
            return None
        now = time.monotonic() if now is None else now
        if now < self._elastic_next:
            return None
        self._elastic_next = now + tuning.ELASTIC_TICK_S
        self._finish_pending_grow()
        self._finish_pending_shrink()
        signals = self._sample_signals(now)
        plan = self._elastic.decide(signals, len(self.live_ranks()),
                                    now)
        if plan["action"] != "hold":
            self._execute_plan(plan, now)
        return plan

    def _log_plan(self, plan: dict, what: str) -> None:
        import sys

        print(f"fsx cluster elastic: {plan['action'].upper()} {what} "
              f"— {plan['reason']} | signals={json.dumps(plan['signals'])}",
              file=sys.stderr)

    def _finish_pending_grow(self) -> None:
        """Second half of a grow: once the new rank is SERVING and the
        handoff lane is free, hand it half the hottest live span."""
        g = self._pending_grow
        if g is None or self._handoff is not None:
            return
        r = g["rank"]
        if r in self._failed:
            self._pending_grow = None
            return
        if self._status[r].ctl_get("c_state") != schema.CSTATE_SERVING:
            return
        from flowsentryx_tpu.cluster import rebalance as rb

        asg = rb.ShardAssignment.load(self.cluster_dir)
        donors = [d for d in self.live_ranks() if d != r
                  and len(asg.spans_of(d)) >= 2]
        if not donors:
            self._pending_grow = None
            return
        donor = max(donors, key=lambda d: (
            self._rates.get(d, 0.0), len(asg.spans_of(d))))
        span = asg.spans_of(donor)
        self.start_handoff(span[len(span) // 2:], donor, r)
        self._pending_grow = None

    def _execute_plan(self, plan: dict, now: float) -> None:
        if self._handoff is not None or self._pending_grow is not None:
            return  # lane busy: the plan re-emits next tick
        from flowsentryx_tpu.cluster import rebalance as rb

        action = plan["action"]
        if action == "grow":
            spare = [r for r in range(self.n)
                     if r not in self._active and r not in self._shrunk]
            if not spare:
                return
            r = spare[0]
            self._active.add(r)
            self._gen[r] = 0
            self._spawn(r)
            self._pending_grow = {"rank": r}
            self.elastic_executed += 1
            self._elastic.executed(now)
            self._log_plan(plan, f"-> spawn rank {r} gen-0")
            return
        asg = rb.ShardAssignment.load(self.cluster_dir)
        if asg is None:
            return
        live = self.live_ranks()
        if action == "shrink" and len(live) >= 2:
            victim = max(live)
            span = asg.spans_of(victim)
            survivors = [r for r in live if r != victim]
            coldest = min(survivors,
                          key=lambda r: self._rates.get(r, 0.0))
            if span:
                self.start_handoff(span, victim, coldest)
            self._pending_shrink = {"rank": victim}
            self.elastic_executed += 1
            self._elastic.executed(now)
            self._log_plan(plan, f"-> drain rank {victim} span to "
                                 f"rank {coldest}, then park")
        elif action == "rebalance" and len(live) >= 2:
            hottest = max(live, key=lambda r: self._rates.get(r, 0.0))
            coldest = min(live, key=lambda r: self._rates.get(r, 0.0))
            span = asg.spans_of(hottest)
            if hottest == coldest or len(span) < 2:
                return
            self.start_handoff(span[len(span) // 2:], hottest, coldest)
            self.elastic_executed += 1
            self._elastic.executed(now)
            self._log_plan(plan, f"-> move {len(span) // 2} shard(s) "
                                 f"rank {hottest} -> {coldest}")

    def _finish_pending_shrink(self) -> None:
        """After a shrink's handoff committed: the victim owns nothing
        — stop-drain it alone and park it as SHRUNK (not failed: its
        span is served, this is the fleet getting smaller on
        purpose)."""
        s = self._pending_shrink
        if s is None or self._handoff is not None:
            return
        victim = s["rank"]
        self._status[victim].ctl_set("c_stop", 1)
        self._shrunk.add(victim)
        self._pending_shrink = None

    def request_stop(self) -> None:
        """Ask every engine to drain its shard and exit (the fleet's
        drain-on-shutdown contract, cluster-wide)."""
        self._stop_sent = True
        for st in self._status:
            st.ctl_set("c_stop", 1)

    def run(self, max_seconds: float | None = None,
            poll_s: float = tuning.SUPERVISOR_POLL_S,
            drain_timeout_s: float = tuning.SUPERVISOR_DRAIN_TIMEOUT_S
            ) -> dict:
        """Supervise until every rank is DONE (or terminally failed).
        ``max_seconds`` bounds the SERVING phase: when it trips, the
        supervisor requests stop-drain and waits (bounded) for the
        tails to be served."""
        t0 = time.monotonic()
        deadline = None if max_seconds is None else t0 + max_seconds
        while len(self._done) + len(self._failed) < len(self._active):
            self.poll()
            self.elastic_tick()
            if (deadline is not None and not self._stop_sent
                    and time.monotonic() >= deadline):
                self.request_stop()
                deadline = time.monotonic() + drain_timeout_s
            elif (self._stop_sent and deadline is not None
                    and time.monotonic() >= deadline):
                break  # drain overran its bound: terminate below
            time.sleep(poll_s)
        self.close()
        return self.aggregate()

    def close(self,
              timeout_s: float = tuning.SUPERVISOR_CLOSE_TIMEOUT_S) -> None:
        if not self._stop_sent:
            self.request_stop()
        deadline = time.monotonic() + timeout_s
        for r, p in enumerate(self._procs):
            if p is None:
                if r in self._respawn_at and r not in self._done:
                    # died, was awaiting its backoff respawn when the
                    # terminal stop landed: no restart is coming, so
                    # the rank is failed, not lost
                    self._respawn_at.pop(r, None)
                    self._failed.add(r)
                continue
            p.join(timeout=max(0.0, deadline - time.monotonic()))
            if p.is_alive():
                # force-killed mid-drain: this rank did NOT finish
                # serving its shard — it must surface in failed_ranks
                # (and flip the CLI exit code), never read as success
                self._killpg(p)
                p.terminate()
                p.join(timeout=1.0)
                self._failed.add(r)
            elif self._status[r].ctl_get("c_state") == schema.CSTATE_DONE:
                self._done.add(r)
            elif r not in self._done:
                # exited without DONE after the terminal stop: no
                # restart is coming, so the rank is failed, not lost
                self._failed.add(r)
        if self.federation is not None:
            self.federation.close()

    # -- reporting ----------------------------------------------------------

    def aggregate(self) -> dict:
        """Collect every generation's report JSON into one cluster
        view: per-rank reports, totals, and the aggregate serving rate
        (total records over the SLOWEST rank's wall — the honest
        cluster number; a sum of rates would hide a straggler)."""
        reports = []
        for f in sorted(self.cluster_dir.glob("report_r*_g*.json")):
            try:
                reports.append(json.loads(f.read_text()))
            except (OSError, ValueError):
                continue
        latest: dict[int, dict] = {}
        for rep in reports:
            r = rep.get("rank", -1)
            if r not in latest or rep.get("gen", 0) >= latest[r].get(
                    "gen", 0):
                latest[r] = rep
        # totals and walls BOTH come from each rank's latest
        # generation: a rank that wrote a report and was then killed
        # and restarted would otherwise have its records counted
        # twice against a single (latest-gen) wall
        total_records = sum(r["report"].get("records", 0)
                            for r in latest.values() if "report" in r)
        total_batches = sum(r["report"].get("batches", 0)
                            for r in latest.values() if "report" in r)
        walls = [r["report"].get("wall_s", 0.0)
                 for r in latest.values() if "report" in r]
        max_wall = max(walls) if walls else 0.0
        # per-rank latency merge (ISSUE 11): each rank's report
        # carries its HDR bucket counts precisely so the cluster
        # percentiles can be computed EXACTLY (bucket-resolution)
        # here, instead of averaging per-rank percentiles — which is
        # statistically meaningless for a p99.  Latest gen only, same
        # double-count rule as the totals.
        latency = None
        merged = LatencyHist()
        per_rank_p99: dict[str, float] = {}
        for r, rep in sorted(latest.items()):
            lat = rep.get("report", {}).get("latency")
            if not lat or not lat.get("hist"):
                continue
            try:
                merged.merge(LatencyHist.from_counts(lat["hist"]))
            except ValueError:
                continue  # foreign scheme: skip, never mis-merge
            per_rank_p99[str(r)] = (
                lat.get("seal_to_verdict") or {}).get("p99")
        if merged.n:
            latency = {
                "unit": "us",
                "seal_to_verdict": merged.to_dict(),
                "per_rank_p99": per_rank_p99,
            }
        # cluster health ladder (engine/health.py): worst-of every
        # rank's self-reported health, with the supervisor's own
        # terminal observations (parked/stalled ranks) layered on top
        per_rank_health = {
            r: rep["report"]["health"]
            for r, rep in latest.items()
            if isinstance(rep.get("report"), dict)
            and rep["report"].get("health")
        }
        # federation view (multi-host fleets): per-peer-host beacon
        # ages and the dead list — a dead peer host folds fleet health
        # FAILED (its whole IP span is down to its local kernel tier)
        hosts_block = None
        dead_hosts: list[int] = []
        if self.federation is not None:
            hosts_block = self.federation.report()
            dead_hosts = self.federation.dead_hosts()
        health = health_mod.cluster_health(
            per_rank_health, sorted(self._failed),
            sorted(self._stalled), dead_hosts=dead_hosts)
        # elastic/rebalance reasons the engines cannot see (a
        # suppressed plan or an aborted handoff is supervisor state):
        # folded here so `fsx monitor --alert-degraded` alerts on them
        sup_reasons = []
        if self._elastic is not None and self._elastic.suppressed:
            sup_reasons.append(
                f"elastic_plans_suppressed:{self._elastic.suppressed}")
        if self.rebalance_counters["aborts"]:
            sup_reasons.append(
                f"rebalance_aborts:{self.rebalance_counters['aborts']}")
        if sup_reasons:
            health["reasons"] = list(health["reasons"]) + sup_reasons
            health["state"] = health_mod.worst(health["state"],
                                              health_mod.DEGRADED)
        # predictive-governor merge (ISSUE 18): counters sum, the
        # fleet "confident" is any-of, and the representative estimate
        # is the highest-confidence rank's — each rank forecasts its
        # OWN shard's arrival process, so averaging periods across
        # ranks would blend unrelated waveforms into nonsense.
        predict_block = None
        predict_blocks = [
            rep["report"]["predict"]
            for _, rep in sorted(latest.items())
            if isinstance(rep.get("report"), dict)
            and rep["report"].get("predict")
        ]
        if predict_blocks:
            from flowsentryx_tpu.engine.predict import DispatchGovernor
            predict_block = DispatchGovernor.merge_reports(predict_blocks)
        # boot-latency merge (compile-cache tentpole): each rank's
        # boot-to-serving story — cache hits/misses, serving-ready
        # wall, import wall — summed/maxed into the fleet view.  A
        # rank with ZERO hits under a configured cache dir is a cold
        # boot the cache should have prevented (`fsx monitor
        # --alert-cold-boot` reads exactly this block).
        boot_block = None
        boots = {
            str(r): rep["report"]["boot"]
            for r, rep in sorted(latest.items())
            if isinstance(rep.get("report"), dict)
            and rep["report"].get("boot")
        }
        if boots:
            caches = [b["cache"] for b in boots.values()
                      if isinstance(b.get("cache"), dict)]
            boot_block = {
                "per_rank": boots,
                "cache_hits": sum(c.get("hits", 0) for c in caches),
                "cache_misses": sum(c.get("misses", 0) for c in caches),
                "cache_stores": sum(c.get("stores", 0) for c in caches),
                "max_serving_ready_s": round(max(
                    (b.get("serving_ready_s") or 0.0
                     for b in boots.values()), default=0.0), 4),
                "prewarm_spawned": self.prewarm_spawned,
            }
        # where each rank placed its table (EngineReport.device)
        device_block = health_mod.fleet_devices({
            r: rep["report"].get("device")
            for r, rep in latest.items()
            if isinstance(rep.get("report"), dict)
        })
        elastic_block = None
        if self._elastic is not None:
            elastic_block = {
                "min_engines": self._elastic.min_engines,
                "max_engines": self._elastic.max_engines,
                "executed": self.elastic_executed,
                "suppressed": self._elastic.suppressed,
                "shrunk_ranks": sorted(self._shrunk),
                # every decision with the signal vector that drove it
                "decisions": self._elastic.decisions[-200:],
            }
        return {
            "engines": self.n,
            "active_ranks": sorted(self._active),
            "adopted_ranks": sorted(self._adopted),
            "rebalance": dict(self.rebalance_counters,
                              adopted_spans=list(self.adopted_spans)),
            "elastic": elastic_block,
            "t0_ns": self.t0_ns,
            "t0_wall_ns": self.t0_wall_ns,
            "restarts": list(self.restarts),
            "failed_ranks": sorted(self._failed),
            "stalled_ranks": sorted(self._stalled),
            "hosts": hosts_block,
            "health": health,
            "records": total_records,
            "batches": total_batches,
            "max_wall_s": round(max_wall, 4),
            "aggregate_records_per_s": round(
                total_records / max(max_wall, 1e-9), 1),
            "latency": latency,
            "predict": predict_block,
            "boot": boot_block,
            "device": device_block,
            "reports": reports,
        }
