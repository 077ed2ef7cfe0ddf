"""The PROGRESS registry: every blocking/polling loop in the protocol
scope, with its declared wake source, fairness assumption, progress
obligation and bound.

The ``sync/contracts.py`` idiom, turned toward liveness: a wait that is
not WRITTEN DOWN here is a wait nobody proved anything about.  Each
entry declares

* **wake** — which event un-parks the loop (a cv notify, a mailbox
  publish, a ctl stamp, a deadline),
* **fairness** — what the proof assumes of the scheduler (weak
  fairness: a continuously runnable thread eventually runs),
* **obligation** — what must keep happening while the loop is live,
* **bound** — the NAME of the :mod:`flowsentryx_tpu.sync.tuning`
  constant bounding the wait, so the runtime and the checker share one
  number (a retune re-proves the model in the same verify run),
* **proof** — the ``fsx live`` check that drives this loop's real code
  (empty for loops whose liveness story is a hard timeout only).

:func:`validate` closes the loop both ways against an ``ast`` scan of
the protocol modules: a scanned blocking loop with no entry is a
finding (unregistered wait), an entry matching no scanned loop is a
finding (stale registry), and an entry whose named proof did not run
in this report is a finding (never-exercised claim).  The
``liveness_waits`` lint stage (scripts/lint.py) consumes
:func:`registered_sites` as its wake-edge whitelist — registering a
loop here is what licenses its ``while True:``.

Jax-free: pure ``ast`` + :mod:`tuning`.
"""

from __future__ import annotations

import ast
import dataclasses
from pathlib import Path

from flowsentryx_tpu.sync import tuning


@dataclasses.dataclass(frozen=True)
class ProgressEntry:
    """One registered blocking/polling loop (class docstring)."""

    name: str        # registry key, unique
    path: str        # repo-relative module path
    qualname: str    # enclosing function/method (dotted, class-level)
    kind: str        # "cv-wait" | "poll" | "retry"
    wake: str        # declared wake source
    fairness: str    # scheduler assumption the proof leans on
    obligation: str  # what must keep happening
    bound: str       # tuning constant name bounding the wait
    proof: str       # fsx live check exercising it ("" = bound-only)


#: The registry.  Ordered by module for the docs table
#: (docs/LIVENESS.md mirrors this).
PROGRESS: tuple[ProgressEntry, ...] = (
    # -- SinkChannel (sync/channel.py) --------------------------------------
    ProgressEntry(
        name="channel_wait_below",
        path="flowsentryx_tpu/sync/channel.py",
        qualname="SinkChannel.wait_below",
        kind="cv-wait",
        wake="complete()/record_exc() notify_all",
        fairness="weak (worker thread keeps completing)",
        obligation="pending drains below the backpressure depth",
        bound="BACKPRESSURE_WAIT_S",
        proof="channel_stop_drain_live"),
    ProgressEntry(
        name="channel_pop",
        path="flowsentryx_tpu/sync/channel.py",
        qualname="SinkChannel.pop",
        kind="cv-wait",
        wake="submit()/submit_many()/request_stop() notify_all",
        fairness="weak (dispatch thread keeps submitting or stops)",
        obligation="queued work is popped; stop+drained returns None",
        bound="POP_WAIT_S",
        proof="channel_stop_drain_live"),
    # -- engine workers (engine/engine.py) ----------------------------------
    ProgressEntry(
        name="engine_sink_worker",
        path="flowsentryx_tpu/engine/engine.py",
        qualname="Engine._sink_worker",
        kind="poll",
        wake="SinkChannel.pop (submit/stop notify_all)",
        fairness="weak (dispatch thread lives while work is queued)",
        obligation="every submitted group is sunk or the exc recorded",
        bound="POP_WAIT_S",
        proof="channel_stop_drain_live"),
    ProgressEntry(
        name="engine_run_inline",
        path="flowsentryx_tpu/engine/engine.py",
        qualname="Engine._run_inline",
        kind="poll",
        wake="staged work / ingest arrivals (bounded idle sleep)",
        fairness="none needed (sleep-bounded poll)",
        obligation="the single-thread loop re-polls within one sleep",
        bound="IDLE_SLEEP_S",
        proof=""),
    # -- gossip plane (cluster/gossip.py) -----------------------------------
    ProgressEntry(
        name="gossip_tick_rx",
        path="flowsentryx_tpu/cluster/gossip.py",
        qualname="GossipPlane.tick",
        kind="poll",
        wake="peer publish_wire into the rx mailbox",
        fairness="weak (peer ticks keep draining their tx side)",
        obligation="anti-entropy merge runs despite shed deferrals",
        bound="SHED_MAX_DEFER",
        proof="shed_bounded"),
    ProgressEntry(
        name="gossip_quiesce",
        path="flowsentryx_tpu/cluster/gossip.py",
        qualname="GossipPlane._quiesce_steps",
        kind="poll",
        wake="idle-tick streak or deadline",
        fairness="none needed (deadline-bounded)",
        obligation="quiesce returns within the timeout",
        bound="GOSSIP_QUIESCE_S",
        proof="quiesce_terminates"),
    # -- fenced handoff (cluster/rebalance.py) ------------------------------
    ProgressEntry(
        name="handoff_ship",
        path="flowsentryx_tpu/cluster/rebalance.py",
        qualname="ship_rows",
        kind="retry",
        wake="recipient pop_slots frees mailbox capacity",
        fairness="weak (recipient steps between run chunks)",
        obligation="the span ships or the handoff aborts at the bound",
        bound="HANDOFF_SHIP_TIMEOUT_S",
        proof="handoff_drop"),
    ProgressEntry(
        name="net_handoff_send",
        path="flowsentryx_tpu/cluster/rebalance.py",
        qualname="NetHandoff.send_stream",
        kind="retry",
        wake="peer cumulative ack datagram",
        fairness="none needed (deadline-bounded retransmit)",
        obligation="all slots acked or TimeoutError at the bound",
        bound="NET_HANDOFF_TIMEOUT_S",
        proof=""),
    ProgressEntry(
        name="net_handoff_recv",
        path="flowsentryx_tpu/cluster/rebalance.py",
        qualname="NetHandoff.recv_stream",
        kind="retry",
        wake="peer slot datagram",
        fairness="none needed (deadline-bounded)",
        obligation="the gap-free stream arrives or TimeoutError",
        bound="NET_HANDOFF_TIMEOUT_S",
        proof=""),
    # -- supervisor (cluster/supervisor.py) ---------------------------------
    ProgressEntry(
        name="supervisor_run",
        path="flowsentryx_tpu/cluster/supervisor.py",
        qualname="ClusterSupervisor.run",
        kind="poll",
        wake="rank state/heartbeat ctl stamps (bounded poll sleep)",
        fairness="weak (ranks keep stamping while alive)",
        obligation="handoffs finish or abort; stop-drain is bounded",
        bound="SUPERVISOR_DRAIN_TIMEOUT_S",
        proof="handoff_drop"),
    # -- net transport (cluster/transport.py) -------------------------------
    ProgressEntry(
        name="net_pump_tx",
        path="flowsentryx_tpu/cluster/transport.py",
        qualname="NetMailbox.pump",
        kind="poll",
        wake="tx queue drains (bounded by the queue cap)",
        fairness="none needed (loop bounded by queue depth)",
        obligation="queued wires leave within one pump",
        bound="NET_OUTQ_MAX",
        proof=""),
    ProgressEntry(
        name="net_handshake",
        path="flowsentryx_tpu/cluster/transport.py",
        qualname="NetMailbox.handshake",
        kind="retry",
        wake="peer HELLO/ack datagram",
        fairness="none needed (deadline-bounded, fails open)",
        obligation="converges or fails open at the bound",
        bound="NET_HANDSHAKE_TIMEOUT_S",
        proof=""),
)

#: Modules the :func:`scan_blocking_sites` pass walks — the protocol
#: scope of ISSUE 19 (engine dispatch/sink, SinkChannel, gossip,
#: rebalance, supervisor, elastic autoscale, predict shedding, net
#: transport).  ``cluster/runner.py`` is deliberately absent: its
#: chunk loop is the serve driver, not a blocking protocol (it is
#: bounded by ``max_seconds``/record budgets and exits through the
#: stop protocol the registered loops implement).
SCAN_MODULES: tuple[str, ...] = (
    "flowsentryx_tpu/sync/channel.py",
    "flowsentryx_tpu/engine/engine.py",
    "flowsentryx_tpu/cluster/gossip.py",
    "flowsentryx_tpu/cluster/rebalance.py",
    "flowsentryx_tpu/cluster/supervisor.py",
    "flowsentryx_tpu/cluster/transport.py",
    "flowsentryx_tpu/cluster/elastic.py",
    "flowsentryx_tpu/engine/predict.py",
)


def repo_root() -> Path:
    return Path(__file__).resolve().parents[2]


def registered_sites() -> set[tuple[str, str]]:
    """``(path, qualname)`` of every registered loop — the lint
    stage's wake-edge whitelist."""
    return {(e.path, e.qualname) for e in PROGRESS}


def _noqa_lines(src: str) -> set[int]:
    return {i for i, line in enumerate(src.splitlines(), 1)
            if "noqa" in line}


def scan_blocking_sites(root: Path | None = None) -> list[dict]:
    """AST scan of the protocol scope for blocking/polling loops:
    any ``*.wait(...)`` call, any ``while True:`` loop, and any
    conditional ``while`` whose body sleeps or yields (a poll/retry
    loop).  Returns one record per ``(path, qualname)`` — the unit an
    entry registers — with every matching line.  ``# noqa`` on the
    loop/call line exempts, same as every lint stage."""
    root = repo_root() if root is None else Path(root)
    sites: dict[tuple[str, str], dict] = {}

    def note(path: str, qualname: str, lineno: int, kind: str) -> None:
        rec = sites.setdefault(
            (path, qualname),
            {"path": path, "qualname": qualname, "lines": [],
             "kinds": []})
        rec["lines"].append(lineno)
        if kind not in rec["kinds"]:
            rec["kinds"].append(kind)

    for rel in SCAN_MODULES:
        p = root / rel
        if not p.exists():
            continue
        src = p.read_text()
        noqa = _noqa_lines(src)
        tree = ast.parse(src)

        def walk(node, stack, rel=rel, noqa=noqa):
            for ch in ast.iter_child_nodes(node):
                sub = stack
                if isinstance(ch, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef)):
                    sub = stack + [ch.name]
                if isinstance(ch, ast.While) and ch.lineno not in noqa:
                    qn = ".".join(stack) or "<module>"
                    if (isinstance(ch.test, ast.Constant)
                            and ch.test.value is True):
                        note(rel, qn, ch.lineno, "while-true")
                    else:
                        sleeps = any(
                            isinstance(n, ast.Call)
                            and isinstance(n.func, ast.Attribute)
                            and n.func.attr == "sleep"
                            for n in ast.walk(ch))
                        yields = any(
                            isinstance(n, (ast.Yield, ast.YieldFrom))
                            for n in ast.walk(ch))
                        if sleeps or yields:
                            note(rel, qn, ch.lineno, "poll")
                if (isinstance(ch, ast.Call)
                        and isinstance(ch.func, ast.Attribute)
                        and ch.func.attr == "wait"
                        and ch.lineno not in noqa):
                    qn = ".".join(stack) or "<module>"
                    note(rel, qn, ch.lineno, "cv-wait")
                walk(ch, sub)

        walk(tree, [])
    return sorted(sites.values(),
                  key=lambda r: (r["path"], r["qualname"]))


def validate(root: Path | None = None,
             exercised: set[str] | None = None) -> dict:
    """Close the registry against the scan (module docstring).
    ``exercised`` is the set of check names a run actually executed;
    when given, entries claiming a proof that did not run are
    findings."""
    findings: list[str] = []
    seen: set[str] = set()
    for e in PROGRESS:
        if e.name in seen:
            findings.append(f"duplicate entry name {e.name!r}")
        seen.add(e.name)
        if not e.bound or not hasattr(tuning, e.bound):
            findings.append(
                f"{e.name}: bound {e.bound!r} is not a sync/tuning "
                "constant")
        if not e.wake or not e.obligation:
            findings.append(
                f"{e.name}: wake and obligation must be declared")
    sites = scan_blocking_sites(root)
    reg = registered_sites()
    for rec in sites:
        if (rec["path"], rec["qualname"]) not in reg:
            findings.append(
                "unregistered blocking loop: "
                f"{rec['path']}::{rec['qualname']} "
                f"(lines {rec['lines']}, {'/'.join(rec['kinds'])})")
    scanned = {(r["path"], r["qualname"]) for r in sites}
    for e in PROGRESS:
        if (e.path, e.qualname) not in scanned:
            findings.append(
                f"stale entry {e.name}: no blocking loop at "
                f"{e.path}::{e.qualname}")
    if exercised is not None:
        for e in PROGRESS:
            if e.proof and e.proof not in exercised:
                findings.append(
                    f"never exercised: {e.name} claims proof "
                    f"{e.proof!r} but that check did not run")
    return {"ok": not findings, "findings": findings,
            "entries": len(PROGRESS), "sites": len(sites)}
