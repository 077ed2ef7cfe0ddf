"""``fsx`` command-line interface.

The reference has no CLI — loading is manual ``bpftool prog load``
(``TODO.md:282-289``) and its loader script crashes on run
(``src/fsx_load.py:15`` references an undefined variable).  This CLI is
the operator surface the reference's README promises
(``README.md:142-147``: load/attach, stats display, dynamic rules).

Subcommands grow with the framework; each delegates to the owning
module so it stays a thin shell.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path


def _mega_arg(s: str):
    """argparse type of ``--mega``: a fixed group size or ``auto``, the
    adaptive power-of-two coalescing ladder
    (``Engine(mega_n="auto")``)."""
    if s == "auto":
        return "auto"
    try:
        return int(s)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--mega takes an integer or 'auto', got {s!r}")


def _cmd_codegen(args: argparse.Namespace) -> int:
    from flowsentryx_tpu.core import codegen

    print(f"wrote {codegen.write_header(args.out)}")
    return 0


#: fsx config --set surface: the runtime-tunable limiter policy fields.
#: ``valid`` (daemon lifecycle), ``rule_count`` (owned by fsx rules),
#: and ``hash_salt`` (fixed at serve boot; changing it live would strand
#: every user-plane table row) are deliberately NOT settable.
_CONFIG_SETTABLE = {
    "limiter_kind", "pps_threshold", "bps_threshold", "window_ns",
    "block_ns", "bucket_rate_pps", "bucket_burst", "bucket_rate_bps",
    "bucket_burst_bytes",
}


def _limiter_codes() -> dict:
    """CLI short name → wire code, derived from the canonical mapping
    (``FsxConfig._KIND_CODE``) so a future limiter kind appears here
    automatically: "fixed_window" → "fixed" etc."""
    from flowsentryx_tpu.core.config import FsxConfig

    return {k.value.split("_")[0]: code
            for k, code in FsxConfig._KIND_CODE.items()}


def _validate_kernel_config(vals: dict) -> str | None:
    """Range checks mirroring ``FsxConfig.__post_init__`` — the live
    path must not admit policy the offline path forbids."""
    if vals["limiter_kind"] not in set(_limiter_codes().values()):
        return f"limiter_kind {vals['limiter_kind']} unknown"
    if vals["window_ns"] <= 0 or vals["block_ns"] <= 0:
        return "window and block durations must be positive"
    for f in ("pps_threshold", "bps_threshold", "bucket_rate_pps",
              "bucket_burst", "bucket_rate_bps", "bucket_burst_bytes"):
        if not 0 <= vals[f] < 1 << 64:
            return f"{f} must be a u64 (got {vals[f]})"
    if (vals["bucket_rate_bps"] == 0) != (vals["bucket_burst_bytes"] == 0):
        return ("bucket_rate_bps and bucket_burst_bytes must be both "
                "zero or both positive")
    return None


def _cmd_config(args: argparse.Namespace) -> int:
    """Show/pack a config — or, with ``--pin``, read and live-update the
    KERNEL's config map (the reference's "configure the XDP program
    parameters" line, README.md:145; the program re-reads the map per
    packet, so updates take effect on the next packet, no reload)."""
    from flowsentryx_tpu.core.config import DEFAULT_CONFIG, FsxConfig

    if args.pin:
        from flowsentryx_tpu.bpf import rules as fsx_rules

        if args.pack:
            print("fsx config: --pack reads a config FILE; it does not "
                  "combine with --pin", file=sys.stderr)
            return 1
        kinds = _limiter_codes()
        # Parse every --set spec BEFORE touching the map: an error
        # mid-application inside config_map_edit would otherwise exit
        # the context cleanly and publish a half-applied config.
        pending: dict = {}
        for spec in args.set or ():
            field, eq, raw = spec.partition("=")
            if not eq:
                print(f"fsx config: --set wants FIELD=VALUE, got "
                      f"{spec!r}", file=sys.stderr)
                return 1
            # seconds-friendly aliases for the ns fields
            mult = 1.0
            if field in ("window_s", "block_s"):
                field = field[:-2] + "_ns"
                mult = 1e9
            if field not in _CONFIG_SETTABLE:
                print(f"fsx config: field {field!r} is not "
                      f"runtime-settable (choose from "
                      f"{sorted(_CONFIG_SETTABLE)})", file=sys.stderr)
                return 1
            if field == "limiter_kind" and raw in kinds:
                pending[field] = kinds[raw]
            else:
                try:
                    pending[field] = int(float(raw) * mult)
                except ValueError:
                    print(f"fsx config: {field} value {raw!r} is not "
                          f"a number", file=sys.stderr)
                    return 1
        try:
            with fsx_rules.config_map_edit(args.pin) as vals:
                vals.update(pending)
                if pending:
                    err = _validate_kernel_config(vals)
                    if err:
                        # raising skips config_map_edit's write-back
                        raise ValueError(err)
                shown = dict(vals)
        except ValueError as e:
            print(f"fsx config: rejected: {e}", file=sys.stderr)
            return 1
        except (OSError, RuntimeError) as e:
            print(f"fsx config: cannot read config_map under "
                  f"{args.pin}: {e}", file=sys.stderr)
            return 1
        shown["window_s"] = shown["window_ns"] / 1e9
        shown["block_s"] = shown["block_ns"] / 1e9
        print(json.dumps({"pin": args.pin, "updated": bool(args.set),
                          "kernel_config": shown}, indent=2))
        return 0

    if args.set:
        print("fsx config: --set requires --pin (live kernel update)",
              file=sys.stderr)
        return 1
    if args.file:
        cfg = FsxConfig.from_json(Path(args.file).read_text())
    else:
        cfg = DEFAULT_CONFIG
    if args.pack:
        sys.stdout.buffer.write(cfg.pack_kernel_config())
    else:
        print(cfg.to_json())
    return 0


def _cmd_version(args: argparse.Namespace) -> int:
    import flowsentryx_tpu

    print(json.dumps({"version": flowsentryx_tpu.__version__}))
    return 0


def _cmd_check(args: argparse.Namespace) -> int:
    """Static verification of the data plane, no kernel needed.

    Two halves (see docs/VERIFIER.md):

    * every hand-assembled program (both emit variants, plus any
      ``--image`` blob) runs through the in-repo abstract-interpreter
      verifier — packet bounds proofs, stack initialization, map-value
      bounds, helper contracts, CFG/reference checks;
    * the cross-layer contract checker diffs the struct offsets baked
      into the bytecode against core.schema, the generated
      kern/fsx_schema.h (which the C daemon compiles), and the sealed
      images under kern/build/.

    Exit 0 only when everything agrees; rejections carry the failing
    instruction index, its disassembly and the abstract register file.
    """
    import struct as _struct

    from flowsentryx_tpu.bpf import contracts, image, progs, verifier

    out: dict = {"programs": [], "ok": True}
    jobs: list[tuple[str, object]] = [
        ("fsx[raw48]", lambda: progs.build()),
        ("fsx[compact16]", lambda: progs.build(compact=True)),
        # the kernel-tier classifier variants (fsx distill): same fast
        # path + fn_ml_score and the ml_model_map band dispatch
        ("fsx[ml_raw48]", lambda: progs.build(ml=True)),
        ("fsx[ml_compact16]", lambda: progs.build(compact=True, ml=True)),
    ]
    for path in args.image or ():
        def _from_image(p: str = path):
            prog, maps = image.to_program(Path(p).read_bytes(), name=p)
            infos = {m.name: verifier.MapInfo(m.name, m.map_type,
                                              m.key_size, m.value_size)
                     for m in maps}
            return prog, infos
        jobs.append((path, _from_image))

    for name, build in jobs:
        try:
            built = build()
            prog, infos = built if isinstance(built, tuple) else (built,
                                                                  None)
            if infos is None:
                rep = verifier.check_program_cached(prog,
                                                    budget=args.budget)
            else:
                rep = verifier.check_program(prog, infos, name=name,
                                             budget=args.budget)
            out["programs"].append({"ok": True, **rep.to_json(),
                                    "program": name})
            if not args.json:
                print(f"fsx check: {name}: OK ({rep.n_insns} insns, "
                      f"{rep.insns_visited} states explored)")
        except (verifier.StaticVerifierError, OSError, ValueError,
                _struct.error) as e:
            out["ok"] = False
            entry = {"ok": False, "program": name, "error": str(e)}
            if isinstance(e, verifier.StaticVerifierError):
                entry.update(insn=e.insn_idx, insn_txt=e.insn_txt,
                             reason=e.reason, state=e.state_dump)
            out["programs"].append(entry)
            if not args.json:
                print(f"fsx check: {name}: REJECTED\n  {e}",
                      file=sys.stderr)

    crep = contracts.run_all(with_images=not args.no_images)
    out["contracts"] = crep.to_json()
    out["ok"] = out["ok"] and crep.ok
    if not args.json:
        for cname, msgs in crep.checks.items():
            if msgs:
                print(f"fsx check: contract {cname}: FAILED",
                      file=sys.stderr)
                for msg in msgs:
                    print(f"  {msg}", file=sys.stderr)
            else:
                print(f"fsx check: contract {cname}: OK")
        print(f"fsx check: {'PASS' if out['ok'] else 'FAIL'}")
    else:
        print(json.dumps(out, indent=2))
    return 0 if out["ok"] else 1


def _quick_shapes(cfg):
    """The --quick staging shapes (small table/batch; the static
    contracts are shape-generic) — one definition for every verb that
    stages the variant set."""
    import dataclasses as _dc

    return _dc.replace(
        cfg,
        table=_dc.replace(cfg.table, capacity=1 << 12),
        batch=_dc.replace(cfg.batch, max_batch=256),
    )


def _stage_mesh_and_mega(args: argparse.Namespace) -> tuple:
    """THE one resolution of the staged-variant sizing flags the
    ``audit`` and ``ranges`` verbs expose identically (``--mesh 0`` =
    every visible device when they form a >1 power-of-two mesh;
    ``--mega auto`` = the adaptive power-of-two ladder) — shared so
    the two static legs can never stage different variant sets for
    the same flags.  Returns ``(mesh, mega_kwargs)``."""
    mesh = None
    n_mesh = args.mesh
    if n_mesh == 0:
        import jax

        n = len(jax.devices())
        n_mesh = n if n > 1 and not (n & (n - 1)) else 1
    if n_mesh > 1:
        from flowsentryx_tpu.parallel import make_mesh

        mesh = make_mesh(n_mesh)
    if args.mega == "auto":
        from flowsentryx_tpu.engine.engine import MEGA_AUTO_MAX
        from flowsentryx_tpu.ops.fused import pow2_group_sizes

        return mesh, {"mega_n": MEGA_AUTO_MAX,
                      "mega_sizes": pow2_group_sizes(MEGA_AUTO_MAX)}
    return mesh, {"mega_n": args.mega}


def _cmd_audit(args: argparse.Namespace) -> int:
    """Static dtype/donation/transfer audit of the staged TPU step
    graphs — the device-plane half of the static-analysis suite
    (``fsx check`` is the kernel-plane half; docs/AUDIT.md).

    Stages every step variant to jaxpr + compiled executable and proves
    the serving contracts without executing a batch: no f64, donation
    really aliases, the steady-state D2H is exactly the
    ``[2*verdict_k+4]``-word wire, staging is retrace-stable, and the
    sharded step's collectives are exactly the designed set."""
    import dataclasses as _dc

    _place_compile_cache()
    from flowsentryx_tpu.audit import run_audit, runner

    # Flag validation BEFORE any JAX/mesh boot (the fsx serve
    # fail-fast ordering): a usage error must not cost the user the
    # multi-second backend init.
    cfg = _load_cfg(args)
    if args.verdict_k is not None:
        if args.verdict_k < 1:
            print("fsx audit: --verdict-k must be >= 1 (the transfer "
                  "contract is about the compact wire)", file=sys.stderr)
            return 1
        cfg = _dc.replace(cfg, batch=_dc.replace(
            cfg.batch, verdict_k=args.verdict_k))
    if args.evict_ttl < 0:
        print("fsx audit: --evict-ttl must be >= 0", file=sys.stderr)
        return 1
    if args.evict_every < 1:
        print("fsx audit: --evict-every must be >= 1", file=sys.stderr)
        return 1
    if args.evict_ttl:
        # stage the EVICTION-EPOCH variants: the in-step aging sweep
        # changes every staged graph (a rolling window at step start:
        # gather + victim-only scatter here, a slice in the branch
        # lowered for a TPU), so its donation/transfer/
        # collective contracts must be proved on the graphs an
        # eviction-enabled engine actually serves — and the boot cache
        # keys on the config, so these stage (and cache) as their own
        # artifacts
        cfg = _dc.replace(cfg, table=_dc.replace(
            cfg.table, evict_ttl_s=args.evict_ttl,
            evict_every=args.evict_every))
    if args.quick:
        # small shapes, same contracts: every check here is
        # shape-generic except the byte budgets, which scale with the
        # quick config and are labeled as such in the report
        cfg = _quick_shapes(cfg)
    mesh, mega = _stage_mesh_and_mega(args)
    rep = run_audit(cfg, mesh=mesh, **mega)
    if args.out:
        runner.write_artifact(rep, args.out)
    if args.json:
        print(json.dumps(rep.to_json(), indent=2))
    else:
        for note in rep.notes:
            print(f"fsx audit: note: {note}")
        for v in rep.variants:
            if v.ok:
                print(f"fsx audit: {v.name}: OK ({v.n_eqns} eqns, "
                      f"steady-state D2H {v.steady_state_d2h_bytes} B "
                      f"= [{v.wire_words}]-word wire)")
            else:
                print(f"fsx audit: {v.name}: FAILED", file=sys.stderr)
                for f in v.findings:
                    print(f"  {f}", file=sys.stderr)
        print(f"fsx audit: {'PASS' if rep.ok else 'FAIL'}")
    return 0 if rep.ok else 1


def _cmd_sync(args: argparse.Namespace) -> int:
    """Static verification of the HOST concurrency plane — the third
    leg of the static suite (``fsx check`` proves the BPF layer,
    ``fsx audit`` the device graphs; docs/CONCURRENCY.md).

    Two halves, one diagnostic idiom:

    * the thread-contract lint (sync/contracts.py): every registered
      shared field's access discipline re-proved over the real source
      by AST walk — plus the unregistered-shared-state, SPSC-cursor
      and ctl-block single-writer detectors;
    * the bounded interleaving model checker (sync/interleave.py):
      exhaustive cooperative schedules over the REAL protocol objects
      (SinkChannel, SealedBatchQueue, DispatchArena), including the
      arena reuse-bound tightness proof — all interleavings pass at
      ``safe_slots`` and a concrete staged-copy-overwrite
      schedule is printed one slot below it.

    Both are jax-free; ``--quick`` runs the contract lint only (the
    ``sync_contracts`` lint-gate stage), full mode adds the model
    checker (a few seconds).
    """
    from flowsentryx_tpu.sync.contracts import run_contracts

    crep = run_contracts(quick=args.quick)
    out: dict = {"ok": crep.ok, "contracts": crep.to_json(),
                 "interleave": None}
    if not args.json:
        st = crep.stats
        print(f"fsx sync: contracts: "
              f"{'OK' if crep.ok else 'FAILED'} "
              f"({st['classes']} classes, {st['registered_fields']} "
              f"fields, {st['cursor_classes']} cursor protocols, "
              f"{st['ctl_sites']} ctl sites)")
        for f in crep.findings:
            print(f"  {f}", file=sys.stderr)

    if not args.quick:
        from flowsentryx_tpu.sync.interleave import run_interleave

        irep = run_interleave()
        out["interleave"] = irep.to_json()
        out["ok"] = out["ok"] and irep.ok
        if not args.json:
            for c in irep.checks:
                tag = ("counterexample found" if c.expect_violation
                       else f"{c.interleavings} interleavings pass")
                status = "OK" if c.ok else "FAILED"
                print(f"fsx sync: model {c.check}: {status} ({tag}, "
                      f"{c.steps} steps"
                      + (", CAPPED" if c.capped else "") + ")")
                if not c.ok:
                    detail = (c.counterexample or
                              "expected counterexample not found")
                    print(f"  {detail}", file=sys.stderr)
            b = irep.bound
            if b["counterexample_found"] and b["safe_ok"]:
                cx = next(c.counterexample for c in irep.checks
                          if c.expect_violation
                          and c.check.startswith("arena"))
                print(f"fsx sync: arena bound TIGHT: depth+2 = "
                      f"{b['safe_slots']} slots pass all "
                      f"{b['interleavings_at_safe']} interleavings; "
                      f"{b['counterexample_at']} slots fail:")
                print("  " + str(cx).replace("\n", "\n  "))

    if args.out:
        p = Path(args.out)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(out, indent=2) + "\n")
        if not args.json:
            print(f"fsx sync: report -> {p}")
    if args.json:
        print(json.dumps(out, indent=2))
    elif out["ok"]:
        print("fsx sync: PASS")
    else:
        print("fsx sync: FAIL", file=sys.stderr)
    return 0 if out["ok"] else 1


def _cmd_crash(args: argparse.Namespace) -> int:
    """Crash-consistency model checking of the durable-state
    protocols — the fifth static leg (docs/CRASH.md, docs/STATIC.md).

    Runs the REAL protocol code — the fenced handoff and dead-span
    adoption (cluster/rebalance.py + cluster/supervisor.py), the
    layout generation flip, and checkpoint write/rotate/fallback
    (engine/checkpoint.py) — over a simulated filesystem with honest
    POSIX semantics, forks a crash at every atomic step (power loss
    and per-party process death), reconstructs every legal post-crash
    durable state (namespace-journal prefixes × torn un-fsynced
    files, plus a media-fault flavor), runs the real recovery path,
    and asserts the named invariant catalog: exact row conservation,
    no dual ownership, monotone layout generation, checkpoint always
    loadable from current-or-.prev, fresh handoff ids on retry,
    single SPSC consumer, convergence.  Planted regressions must each
    be caught with a printed crash schedule, from runs whose
    unplanted controls are clean.

    jax-free; ``--quick`` trims the torn-file fan-out (same crash
    points and protocols, fewer tear variants per un-synced file).
    """
    from flowsentryx_tpu.crash import run_crash

    rep = run_crash(quick=args.quick)
    if not args.json:
        for s in rep["scenarios"]:
            status = "OK" if s["violations"] == 0 else "FAILED"
            print(f"fsx crash: {s['scenario']}: {status} "
                  f"({s['crash_points']} crash points, "
                  f"{s['states_explored']} durable states, "
                  f"{s['recoveries']} recoveries"
                  + (", CAPPED" if s["capped"] else "") + ")")
            if s["counterexample"]:
                print("  " + s["counterexample"].replace("\n", "\n  "),
                      file=sys.stderr)
        for p in rep["plants"]:
            ok = p["caught"] and p["control_ok"]
            why = ("caught by " + p["caught_by"] if p["caught"]
                   else "NOT CAUGHT")
            if not p["control_ok"]:
                why += "; control run dirty"
            print(f"fsx crash: plant {p['plant']}: "
                  f"{'OK' if ok else 'FAILED'} ({why})")
            if p["schedule"] and not args.quiet_plants:
                print("  " + p["schedule"].replace("\n", "\n  "))
    if args.out:
        p = Path(args.out)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(rep, indent=2) + "\n")
        if not args.json:
            print(f"fsx crash: report -> {p}")
    if args.json:
        print(json.dumps(rep, indent=2))
    elif rep["ok"]:
        t = rep["totals"]
        print(f"fsx crash: PASS ({t['crash_points']} crash points, "
              f"{t['states_explored']} durable states, "
              f"{t['recoveries']} recoveries, {rep['elapsed_s']} s)")
    else:
        print("fsx crash: FAIL", file=sys.stderr)
    return 0 if rep["ok"] else 1


def _cmd_live(args: argparse.Namespace) -> int:
    """Liveness & progress model checking — the sixth static leg
    (docs/LIVENESS.md, docs/STATIC.md).

    Builds the full state graph of the REAL protocol objects — the
    ``SinkChannel`` submit/backpressure/stop drain, the supervisor's
    fenced handoff with a message dropped at every stamp edge, the
    elastic autoscale hysteresis, gossip pressure-shedding, and
    quiesce — and proves deadlock-freedom (every park names its wake
    edge), livelock-freedom under weak fairness (no reachable
    no-progress cycle), and bounded starvation (every declared
    obligation fires within its registered bound).  The PROGRESS
    registry (flowsentryx_tpu/live/registry.py) is audited against an
    AST scan of the protocol scope: every blocking loop must declare
    its wake source and fairness assumption, and every registry entry
    must still point at real code that the checker exercises.
    Planted regressions (a deleted notify, a dropped fence-lift with
    re-delivery removed, the shed streak cap removed, a zeroed
    cooldown) must each be caught with a printed schedule, from runs
    whose unplanted controls are clean.

    jax-free, a few seconds; ``--quick`` trims the handoff drop-edge
    fan-out (same protocols and plants, fewer dropped edges)."""
    from flowsentryx_tpu.live.checker import run_live

    rep = run_live(quick=args.quick)
    if not args.json:
        for c in rep["checks"]:
            status = "OK" if c["ok"] else "FAILED"
            print(f"fsx live: {c['check']}: {status} "
                  f"({c['states']} states, {c['edges']} edges, "
                  f"{c['terminals']} terminals"
                  + (", CAPPED" if c["capped"] else "") + ")")
            if c["counterexample"] and not c["ok"]:
                cx = c["counterexample"]
                print(f"  {cx['detail']}", file=sys.stderr)
                print("  schedule: "
                      + " -> ".join(cx["schedule"]), file=sys.stderr)
        for p in rep["plants"]:
            ok = p["caught"] and p["control_ok"]
            why = ("caught by " + str(p["caught_by"]) if p["caught"]
                   else "NOT CAUGHT")
            if not p["control_ok"]:
                why += "; control run dirty"
            print(f"fsx live: plant {p['plant']}: "
                  f"{'OK' if ok else 'FAILED'} ({why})")
            if p["schedule"] and not args.quiet_plants:
                print("  " + p["detail"])
                print("  schedule: " + " -> ".join(p["schedule"]))
        reg = rep["registry"]
        print(f"fsx live: registry: "
              f"{'OK' if reg['ok'] else 'FAILED'} "
              f"({reg['entries']} entries, {reg['sites']} blocking "
              f"sites)")
        for f in reg["findings"]:
            print(f"  {f}", file=sys.stderr)
    if args.out:
        p = Path(args.out)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(rep, indent=2) + "\n")
        if not args.json:
            print(f"fsx live: report -> {p}")
    if args.json:
        print(json.dumps(rep, indent=2))
    elif rep["ok"]:
        t = rep["totals"]
        print(f"fsx live: PASS ({t['checks']} checks, "
              f"{t['states']} states, {t['steps']} steps, "
              f"{t['plants']} plants, {rep['elapsed_s']} s)")
    else:
        print("fsx live: FAIL", file=sys.stderr)
    return 0 if rep["ok"] else 1


def _cmd_chaos(args: argparse.Namespace) -> int:
    """Deterministic fault-injection campaign over the REAL stack —
    the robustness leg of the verification suite (the static legs
    prove what the code cannot do; the chaos campaign proves what the
    system DOES under faults; docs/CHAOS.md).

    One seed fixes the whole campaign: the traffic, the corruption
    offsets, the kill schedule.  Every scenario drives real protocol
    objects — a compiled serving engine, a live drain-worker fleet
    over real shm rings, the cluster supervisor with real child
    processes, gossip mailbox pairs — and is judged by the named
    invariant catalog.  The planted regressions (split-atomicity
    crash, checkpoint CRC skipped, backoff removed) are negative
    controls: the campaign fails unless each is CAUGHT by its named
    invariant."""
    from flowsentryx_tpu.chaos import faults as chaos_faults

    if args.list:
        for name, (cls, desc) in chaos_faults.FAULTS.items():
            print(f"{name:20s} [{cls}]\n    {desc}")
        return 0
    _place_compile_cache()
    from flowsentryx_tpu.chaos import run_campaign

    rep = run_campaign(seed=args.seed, quick=args.quick,
                       workdir=args.workdir, out=args.out)
    if args.json:
        print(json.dumps(rep, indent=2))
    else:
        for r in rep["faults"]:
            status = "OK" if r["ok"] else "FAILED"
            invs = ", ".join(
                f"{i['name']}{'' if i['ok'] else '!'}"
                for i in r["invariants"])
            print(f"fsx chaos: {r['fault']:40s} {status}  ({invs})")
            if not r["ok"]:
                for i in r["invariants"]:
                    if not i["ok"]:
                        print(f"  INVARIANT {i['name']}: {i['detail']}",
                              file=sys.stderr)
        for p in rep["planted_regressions"]:
            status = "CAUGHT" if p["ok"] else "MISSED"
            print(f"fsx chaos: plant {p['plant']:32s} {status}  "
                  f"(by {p['caught_by']})")
        print(f"fsx chaos: {rep['n_fault_classes']} fault classes, "
              f"{rep['invariants_checked']} invariant checks, "
              f"{len(rep['planted_regressions'])} planted regressions, "
              f"seed {rep['seed']}, {rep['wall_s']}s")
    if args.out and not args.json:
        print(f"fsx chaos: report -> {args.out}")
    if rep["ok"]:
        if not args.json:
            print("fsx chaos: PASS")
        return 0
    print("fsx chaos: FAIL", file=sys.stderr)
    return 1


def _cmd_ranges(args: argparse.Namespace) -> int:
    """Static integer value-range proof over the staged step graphs —
    the fourth leg of the static suite (``fsx check`` proves the BPF
    bytecode, ``fsx audit`` the device graphs' transfer contracts,
    ``fsx sync`` the host concurrency plane; docs/RANGES.md,
    docs/STATIC.md).

    Stages every step variant (same staging as ``fsx audit``), seeds
    the inputs from the declared range registry, and proves no
    equation can silently wrap a fixed-width integer — modulo the
    audited ``WRAP_OK`` registry, itself checked for staleness every
    run.  Also re-proves the three planted negative controls fire and,
    when the shipped distill artifact is present, the BPF↔jaxpr
    interval-containment bridge."""
    import dataclasses as _dc

    _place_compile_cache()
    from flowsentryx_tpu.ranges import runner as ranges_runner

    cfg = _load_cfg(args)
    if args.evict_ttl < 0:
        print("fsx ranges: --evict-ttl must be >= 0", file=sys.stderr)
        return 1
    if args.evict_every < 1:
        print("fsx ranges: --evict-every must be >= 1", file=sys.stderr)
        return 1
    if args.evict_ttl:
        cfg = _dc.replace(cfg, table=_dc.replace(
            cfg.table, evict_ttl_s=args.evict_ttl,
            evict_every=args.evict_every))
    if args.quick:
        cfg = _quick_shapes(cfg)
    mesh, mega = _stage_mesh_and_mega(args)
    rep = ranges_runner.run_ranges(
        cfg, mesh=mesh, artifact=args.artifact, **mega)
    if args.out:
        ranges_runner.write_artifact(rep, args.out)
    if args.json:
        print(json.dumps(rep.to_json(), indent=2))
    else:
        for note in rep.notes:
            print(f"fsx ranges: note: {note}")
        for v in rep.variants:
            if v.ok:
                wraps = sum(v.wrap_ok_matches.values())
                print(f"fsx ranges: {v.name}: OK ({v.n_eqns} eqns, "
                      f"{v.n_checked} checked, {wraps} audited "
                      "wrap-ok)")
            else:
                print(f"fsx ranges: {v.name}: FAILED", file=sys.stderr)
                for f in v.findings:
                    print(f"  {f}", file=sys.stderr)
        for f in rep.registry_findings:
            print(f"fsx ranges: registry: {f}", file=sys.stderr)
        neg = rep.negatives
        print("fsx ranges: negative controls: "
              + ("all fire" if neg.get("ok") else "FAILED (a finding "
                 "class no longer fires — prover regression)"))
        if rep.bridge is not None:
            b = rep.bridge
            if b.get("ok"):
                print("fsx ranges: BPF<->jaxpr containment: OK (acc "
                      f"{b['kernel_acc']} within the verifier's MAC "
                      "range; bands "
                      f"{b['jax_bands']} within "
                      f"[{b['bpf_band']['umin']}, "
                      f"{b['bpf_band']['umax']}])")
            else:
                print(f"fsx ranges: BPF<->jaxpr containment: FAILED "
                      f"({b.get('error', b)})", file=sys.stderr)
        print(f"fsx ranges: {'PASS' if rep.ok else 'FAIL'}")
    return 0 if rep.ok else 1


def _cmd_distill(args: argparse.Namespace) -> int:
    """Compile a trained int8 artifact into the kernel tier.

    The fourth static-toolchain verb (check / audit / distill / serve):
    inverts the artifact's float observer + score tail into exact
    integer tables (``flowsentryx_tpu/distill/``), packs them into the
    hot-swappable ``ml_model_map`` blob the ``--ml`` XDP images band
    packets with, and — with ``--emulate`` — proves JAX↔BPF verdict
    parity by running the REAL emitted bytecode over a vector corpus.
    See docs/DISTILL.md for the fixed-point scheme and the two-tier
    escalation protocol.
    """
    import time as _time

    import numpy as np

    try:
        t_lo_s, _, t_hi_s = args.thresholds.partition(",")
        t_lo, t_hi = float(t_lo_s), float(t_hi_s)
    except ValueError:
        print(f"fsx distill: --thresholds wants LO,HI in [0,1], got "
              f"{args.thresholds!r}", file=sys.stderr)
        return 1
    _place_compile_cache()
    from flowsentryx_tpu.distill import plan as dplan
    from flowsentryx_tpu.models.registry import (
        load_artifact,
        require_distillable,
    )

    # distillability gate BEFORE any artifact parsing surprises
    try:
        params = load_artifact(args.model, args.artifact)
        require_distillable(args.model, params)
    except (ValueError, KeyError, OSError) as e:
        print(f"fsx distill: {e}", file=sys.stderr)
        return 1
    t0 = _time.perf_counter()
    try:
        plan = dplan.compile_plan(params, t_lo=t_lo, t_hi=t_hi)
    except dplan.DistillError as e:
        print(f"fsx distill: {e}", file=sys.stderr)
        return 1
    out: dict = {
        "ok": True,
        "artifact": args.artifact,
        "model": args.model,
        "compile_s": round(_time.perf_counter() - t0, 3),
        "plan": plan.to_json(),
    }
    blob = dplan.pack_blob(plan)
    if args.out:
        out["plan_file"] = dplan.save_plan(plan, args.out)
    if args.blob:
        Path(args.blob).write_bytes(blob)
        out["blob_file"] = args.blob

    if args.check:
        # every program that could carry this blob must pass the static
        # verifier, and the offsets the scorer bakes must match schema
        from flowsentryx_tpu.bpf import contracts, progs, verifier

        checks: dict = {}
        for compact in (False, True):
            tag = "ml_" + ("compact16" if compact else "raw48")
            try:
                rep = verifier.check_program_cached(
                    progs.build(compact=compact, ml=True))
                checks[tag] = {"ok": True, **rep.to_json()}
            except verifier.StaticVerifierError as e:
                checks[tag] = {"ok": False, "error": str(e)}
                out["ok"] = False
        for name, fails in (
                ("progs_offsets", contracts.check_progs_offsets()),
                ("map_specs", contracts.check_map_specs())):
            checks[name] = {"ok": not fails, "failures": fails}
            out["ok"] = out["ok"] and not fails
        rt = dplan.unpack_blob(blob)
        probe = np.arange(64, dtype=np.uint32).reshape(8, 8) * 0x01010101
        checks["blob_roundtrip"] = {
            "ok": bool((rt.bands(probe) == plan.bands(probe)).all())}
        out["ok"] = out["ok"] and checks["blob_roundtrip"]["ok"]
        out["check"] = checks

    if args.emulate:
        out["emulate"] = _distill_emulate(params, plan, blob,
                                          n=args.emulate_n)
        out["ok"] = out["ok"] and out["emulate"]["ok"]

    if args.report:
        Path(args.report).parent.mkdir(parents=True, exist_ok=True)
        Path(args.report).write_text(json.dumps(out, indent=2) + "\n")
    if args.pin:
        if not out["ok"]:
            # --check/--emulate are deployment gates when combined with
            # --pin: never hot-swap a model that just failed them
            print("fsx distill: refusing --pin: checks failed (see "
                  "report); the live model is unchanged", file=sys.stderr)
            if args.json:
                print(json.dumps(out, indent=2))
            return 1
        try:
            from flowsentryx_tpu.bpf import loader
            from flowsentryx_tpu.core import schema

            fd = loader.obj_get(f"{args.pin}/ml_model_map")
            m = loader.Map(fd, loader.MAP_TYPE_ARRAY, 4,
                           schema.ML_MODEL_SIZE, 1, "ml_model_map")
            try:
                m.update(b"\x00" * 4, blob)
            finally:
                m.close()
            out["pushed"] = args.pin
        except OSError as e:
            print(f"fsx distill: cannot push the model blob under "
                  f"{args.pin}: {e} (is an --ml image attached with "
                  "maps pinned there?)", file=sys.stderr)
            return 1
    if args.json:
        print(json.dumps(out, indent=2))
    else:
        p = out["plan"]
        print(f"fsx distill: {args.artifact} [{args.model}] -> "
              f"{p['n_bounds'][0]} boundaries/feature, bands "
              f"s<={p['acc_pass']} pass | s>={p['acc_drop']} drop "
              f"(scores {args.thresholds})")
        for key in ("plan_file", "blob_file", "pushed"):
            if key in out:
                print(f"fsx distill: {key.replace('_', ' ')}: {out[key]}")
        if "check" in out:
            for tag, c in out["check"].items():
                print(f"fsx distill: check {tag}: "
                      f"{'OK' if c['ok'] else 'FAILED'}")
                for f in c.get("failures", []) or (
                        [c["error"]] if c.get("error") else []):
                    print(f"  {f}", file=sys.stderr)
        if "emulate" in out:
            e = out["emulate"]
            print(f"fsx distill: emulate: {e['vectors']} vectors, "
                  f"jax/emulator band mismatches: {e['jax_mismatches']} "
                  f"(sim twin: {e['sim_mismatches']}), split "
                  f"pass={e['split']['pass']} "
                  f"escalate={e['split']['escalate']} "
                  f"drop={e['split']['drop']} "
                  f"(escalation ratio {e['escalation_ratio']})")
        print(f"fsx distill: {'PASS' if out['ok'] else 'FAIL'}")
    return 0 if out["ok"] else 1


def _distill_emulate(params, plan, blob: bytes, n: int = 10000) -> dict:
    """JAX↔BPF parity run: the served int8 lane vs the REAL emitted
    bytecode (distill/emulate.py) vs the numpy sim twin, over a corpus
    of CICIDS-shaped vectors + uniform u32 noise + saturation and
    boundary edges.  The acceptance contract is zero band mismatches."""
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from flowsentryx_tpu.distill.emulate import emulate_scorer
    from flowsentryx_tpu.models import logreg

    rng = np.random.default_rng(7)
    corpora = []
    # CICIDS-calibrated flow statistics (what production features look
    # like), clipped into the u32 wire domain
    from flowsentryx_tpu.train import fixture

    X, _ = fixture.cicids_fixture(n=max(n // 2, 256), seed=3)
    corpora.append(np.clip(X, 0, (1 << 32) - 1).astype(np.uint32))
    corpora.append(rng.integers(0, 1 << 32, size=(max(n // 4, 256), 8),
                                dtype=np.uint64).astype(np.uint32))
    # saturation + zero-point edges, and every quantization boundary ±1
    edges = np.array([0, 1, 8, 255, (1 << 16) - 1, (1 << 24) - 1,
                      1 << 24, (1 << 24) + 1, 1 << 31, (1 << 32) - 1],
                     np.uint32)
    corpora.append(np.tile(edges[:, None], (1, 8)))
    b = plan.bounds_m1[0]
    real = b[b != 0xFFFFFFFF].astype(np.uint64)
    near = np.unique(np.concatenate([real, real + 1, real + 2]))
    near = near[near <= (1 << 32) - 1].astype(np.uint32)
    if len(near):
        corpora.append(
            near[rng.integers(0, len(near), size=(max(n // 4, 256), 8))])
    feats = np.concatenate(corpora)[:max(n, 512)]

    x = jnp.asarray(feats).astype(jnp.float32)
    # jit, because the ENGINE serves this lane jitted: an eager call
    # can differ by 1 ULP at round-half boundaries (fused XLA codegen
    # vs per-op dispatch), and the distilled boundaries match the
    # compiled graph — the one production scores with
    scores = np.asarray(jax.jit(logreg.classify_batch_int8_matmul)(
        params, x))
    jax_bands = np.where(
        scores > plan.t_hi, 2, np.where(scores < plan.t_lo, 0, 1)
    ).astype(np.uint8)
    t0 = _time.perf_counter()
    em_bands = emulate_scorer(blob, feats)
    em_s = _time.perf_counter() - t0
    sim_bands = plan.bands(feats)
    split = {name: int((em_bands == code).sum())
             for name, code in (("pass", 0), ("escalate", 1), ("drop", 2))}
    return {
        "ok": bool((em_bands == jax_bands).all()
                   and (sim_bands == em_bands).all()),
        "vectors": int(len(feats)),
        "jax_mismatches": int((em_bands != jax_bands).sum()),
        "sim_mismatches": int((sim_bands != em_bands).sum()),
        "split": split,
        "escalation_ratio": round(split["escalate"] / len(feats), 6),
        "emulator_wall_s": round(em_s, 3),
        "emulator_vectors_per_s": round(len(feats) / max(em_s, 1e-9)),
        "thresholds": {"t_lo": plan.t_lo, "t_hi": plan.t_hi,
                       "acc_pass": plan.acc_pass,
                       "acc_drop": plan.acc_drop},
    }


def _cmd_block(args: argparse.Namespace) -> int:
    """Manually blacklist a source (reference README.md:70-74: "Block
    specified IP addresses").  v6 addresses block EXACTLY (the 16-byte
    blacklist_v6) — never by their 32-bit fold."""
    from flowsentryx_tpu.bpf import blacklist

    m = blacklist.open_map_for(args.ip, args.pin)
    try:
        e = blacklist.block(m, args.ip, ttl_s=args.ttl)
        print(json.dumps({"blocked": args.ip, **e.to_json()}))
    finally:
        m.close()
    return 0


def _cmd_unblock(args: argparse.Namespace) -> int:
    from flowsentryx_tpu.bpf import blacklist

    m = blacklist.open_map_for(args.ip, args.pin)
    try:
        removed = blacklist.unblock(m, args.ip)
        print(json.dumps({"unblocked": args.ip, "was_present": removed}))
    finally:
        m.close()
    return 0 if removed else 1


def _cmd_blacklist(args: argparse.Namespace) -> int:
    """Pretty-print (or clear) the live blacklist — the reference's
    planned "display network statistics" surface (README.md:142-147)."""
    from flowsentryx_tpu.bpf import blacklist

    m = blacklist.open_map(args.pin)
    try:
        m6 = blacklist.open_v6_map(args.pin)
    except OSError:
        m6 = None  # pin dir from a pre-v6-map image
    try:
        if args.clear:
            n = blacklist.clear(m) + (blacklist.clear(m6) if m6 else 0)
            print(json.dumps({"cleared": n}))
            return 0
        entries = [e.to_json() for e in blacklist.entries(m)]
        if m6 is not None:
            entries += [e.to_json() for e in blacklist.entries(m6)]
        if args.json:
            print(json.dumps({"entries": entries}))
        else:
            print(f"{'key':>10}  {'source':>40}  remaining")
            for e in entries:
                src = e.get("addr") or e.get("v4")
                key = "exact-v6" if e.get("exact") else e["key"]
                print(f"{key:>10}  {src:>40}  {e['remaining_s']:.1f}s")
            print(f"{len(entries)} entr{'y' if len(entries) == 1 else 'ies'}")
    finally:
        m.close()
        if m6 is not None:
            m6.close()
    return 0


def _cmd_rules(args: argparse.Namespace) -> int:
    """List / add / remove live stateless-firewall rules (the
    reference's planned dynamic rule management, README.md:70-74,
    142-147; per-IP rules live under ``fsx block``)."""
    from flowsentryx_tpu.bpf import rules

    m = rules.open_map(args.pin)
    try:
        if args.add:
            # order: validate the spec first (nothing touched on
            # malformed input), probe the kernel gate (fails cleanly if
            # no config was pushed yet - daemon not started), insert,
            # then reconcile the gate to the map's actual count - ALSO
            # on a failed insert, so the count can never stay inflated
            try:
                rule = rules.parse_spec(args.add)
                rules.set_enabled(args.pin, len(rules.entries(m)) + 1)
                try:
                    r = rules.add(m, rule)
                finally:
                    rules.set_enabled(args.pin, len(rules.entries(m)))
            except (ValueError, RuntimeError, OSError) as e:
                raise SystemExit(f"fsx rules: {e}") from None
            print(json.dumps({"added": r.to_json()}))
            return 0
        if args.remove:
            try:
                ok = rules.remove(m, rules.parse_spec(args.remove))
                rules.set_enabled(args.pin, len(rules.entries(m)))
            except (ValueError, RuntimeError, OSError) as e:
                raise SystemExit(f"fsx rules: {e}") from None
            print(json.dumps({"removed": bool(ok)}))
            return 0
        ents = [r.to_json() for r in rules.entries(m)]
        if args.json:
            print(json.dumps({"entries": ents}))
        else:
            print(f"{'proto':>8}  {'dport':>6}  action")
            for e in ents:
                print(f"{e['proto']:>8}  {e['dport']:>6}  {e['action']}")
            print(f"{len(ents)} rule{'' if len(ents) == 1 else 's'}")
    finally:
        m.close()
    return 0


def _place_compile_cache() -> None:
    """JAX's persistent compilation cache goes where the environment
    says, else ``<checkout>/.jax_cache`` (core/runtime.py).  Called by
    the jax-using subcommands before their first compile — the others
    stay free of the multi-second jax import."""
    from flowsentryx_tpu.core import runtime

    runtime.place_compile_cache()


def _load_cfg(args: argparse.Namespace):
    from flowsentryx_tpu.core.config import DEFAULT_CONFIG, FsxConfig

    if getattr(args, "config", None):
        return FsxConfig.from_json(Path(args.config).read_text())
    return DEFAULT_CONFIG


def _boot_salt(cache_dir: str | None, label: str) -> int:
    """The auto boot-time hash salt, compile-cache aware.

    ``TableConfig.salt`` is a jit closure constant — it is BAKED into
    every staged executable — so a fresh random salt per boot would
    miss the persistent AOT cache on every variant, silently, forever
    (`fsx monitor --alert-cold-boot` would page on every restart).
    With ``--compile-cache`` the salt is therefore drawn once and
    PINNED in the cache dir: zero added exposure, because the
    serialized executables beside it bake the very same salt — an
    attacker who can read ``boot_salt`` can already read the salt out
    of any ``.aot`` entry.  Rotating the salt is exactly "wipe the
    cache dir" (or fix ``table.salt`` in the config file).  Without a
    cache dir, behavior is unchanged: fresh random salt per boot."""
    import secrets

    if not cache_dir:
        return secrets.randbits(32) | 1
    path = os.path.join(cache_dir, "boot_salt")
    try:
        salt = int(Path(path).read_text().strip(), 0)
        if salt & 1 and 0 < salt < 1 << 32:
            return salt
        print(f"fsx {label}: ignoring malformed {path} "
              f"(value {salt:#x}); drawing a fresh boot salt",
              file=sys.stderr)
    except FileNotFoundError:
        pass
    except (OSError, ValueError) as e:
        print(f"fsx {label}: ignoring unreadable {path} ({e}); "
              "drawing a fresh boot salt", file=sys.stderr)
    salt = secrets.randbits(32) | 1
    from flowsentryx_tpu.core import durable

    os.makedirs(cache_dir, exist_ok=True)
    try:
        durable.atomic_write(path, f"{salt:#010x}\n")
    except OSError as e:
        print(f"fsx {label}: could not pin boot salt in {path} ({e}) "
              "— the compile cache will miss on the next boot",
              file=sys.stderr)
    else:
        print(f"fsx {label}: --compile-cache: boot salt {salt:#x} "
              f"pinned in {path} so cached executables (which bake "
              "the salt) stay valid across restarts; rotate by "
              "wiping the cache dir or fixing table.salt in config",
              file=sys.stderr)
    return salt


def _key128_serve_problem(args: argparse.Namespace) -> str | None:
    """What ``fsx serve`` was asked for that a table keyed by 128 bits
    (``table.key_bits``) cannot do yet, or None."""
    if args.checkpoint or args.restore:
        return ("no checkpoint save or restore yet (the snapshot's key "
                "column carries 32-bit keys)")
    if args.mesh and args.mesh > 1:
        return ("one chip only (the sharded step's owner hash and "
                "routing carry 32-bit keys)")
    if not (args.feature_ring and args.ingest_workers):
        return ("serve the sealed ring path (--feature-ring with "
                "--ingest-workers N, fsxd --key-bits 128): the inline / "
                "record-file path seals 48 B records with a 32-bit source")
    return None


def _cmd_serve(args: argparse.Namespace) -> int:
    """Run the serving engine over a record source.

    ``--feature-ring`` consumes the daemon's shm ring (production);
    ``--scenario`` runs an in-process synthetic scenario (no daemon)."""
    # Argument validation BEFORE any engine work: rejecting a flag
    # combination after the multi-second JAX boot + compile is hostile.
    # Negativity first: `--checkpoint-every -1` without --checkpoint
    # must name ITS problem, not the unrelated missing-path one.
    if args.checkpoint_every < 0:
        print("fsx serve: --checkpoint-every must be >= 0 (0 disables)",
              file=sys.stderr)
        return 1
    if args.checkpoint_every and not args.checkpoint:
        print("fsx serve: --checkpoint-every requires --checkpoint PATH",
              file=sys.stderr)
        return 1
    if args.ingest_workers < 0:
        print("fsx serve: --ingest-workers must be >= 0 (0 = inline)",
              file=sys.stderr)
        return 1
    if args.ingest_workers and not args.feature_ring:
        print("fsx serve: --ingest-workers requires --feature-ring "
              "(the sharded drain fronts the daemon's shm rings)",
              file=sys.stderr)
        return 1
    if args.strict_ingest and not args.ingest_workers:
        print("fsx serve: --strict-ingest requires --ingest-workers N "
              "(>= 1): the crash posture governs the sharded drain "
              "fleet — there is no ingest worker to die on the inline "
              "path", file=sys.stderr)
        return 1
    if args.quarantine_dir and not args.ingest_workers:
        # a silently-inert flag is the failure class this refusal
        # discipline exists for: slot validation/quarantine lives on
        # the sealed-batch dequeue paths only
        print("fsx serve: --quarantine-dir requires --ingest-workers "
              "N (>= 1): sealed-slot validation and quarantine happen "
              "on the sharded-ingest dequeue path; the inline record "
              "path has no sealed slots to refuse", file=sys.stderr)
        return 1
    if args.verdict_k is not None and args.verdict_k < 0:
        print("fsx serve: --verdict-k must be >= 0 (0 disables the "
              "compact verdict wire)", file=sys.stderr)
        return 1
    if args.slo_us < 0:
        print("fsx serve: --slo-us must be >= 0 (0 = throughput-tuned "
              "serving, no latency budget)", file=sys.stderr)
        return 1
    if args.predict and not args.slo_us:
        print("fsx serve: --predict requires --slo-us > 0 — the "
              "governor's flush/pre-warm/shed decisions are all "
              "phrased against the latency budget; without one there "
              "is nothing to govern", file=sys.stderr)
        return 1
    if args.sim_kernel_tier and args.ingest_workers:
        print("fsx serve: --sim-kernel-tier needs the inline record "
              "path; sealed-batch ingest bypasses the record stream "
              "(deploy the real tier via fsx distill --pin instead)",
              file=sys.stderr)
        return 1
    if args.tiered_warm and not args.mega:
        print("fsx serve: --tiered-warm requires --mega N|auto: the "
              "serving tier IS the top coalescing rung — with no "
              "ladder there is nothing to tier (plain warm() already "
              "compiles the one staged step)", file=sys.stderr)
        return 1
    if args.artifact_reload and not args.artifact:
        print("fsx serve: --artifact-reload requires --artifact PATH "
              "(it hot-swaps that file when its mtime changes)",
              file=sys.stderr)
        return 1
    # Cluster-member refusals (docs/CLUSTER.md), still jax-free.  A
    # rank is one engine of an `fsx cluster` fleet: it owns ring
    # shards [R*W, (R+1)*W) of the N*W-shard fan-out end-to-end and
    # shares ONLY the gossip plane, so every structural requirement is
    # checkable (and refused, naming its problem) before any backend
    # boots.
    cluster_rank = cluster_n = None
    gossip = None
    t0_ns = None
    if args.cluster_rank is not None:
        r_s, sep, n_s = args.cluster_rank.partition("/")
        try:
            cluster_rank, cluster_n = int(r_s), int(n_s)
        except ValueError:
            sep = ""
        if not sep:
            print(f"fsx serve: --cluster-rank wants R/N (e.g. 0/2), "
                  f"got {args.cluster_rank!r}", file=sys.stderr)
            return 1
        if cluster_n < 2:
            print(f"fsx serve: --cluster-rank {args.cluster_rank}: a "
                  f"{cluster_n}-engine cluster is just fsx serve — "
                  "drop the flag, or run >= 2 engines",
                  file=sys.stderr)
            return 1
        if not 0 <= cluster_rank < cluster_n:
            print(f"fsx serve: --cluster-rank {args.cluster_rank}: "
                  f"rank must be in [0, {cluster_n})", file=sys.stderr)
            return 1
        if not args.ingest_workers:
            print("fsx serve: --cluster-rank requires --ingest-workers "
                  "W >= 1: rank R of N owns ring shards [R*W, (R+1)*W) "
                  "of the daemon's N*W-shard IP-hash fan-out (pair "
                  "with fsxd --shards N*W)", file=sys.stderr)
            return 1
        if not args.cluster_dir:
            print("fsx serve: --cluster-rank requires --cluster-dir "
                  "DIR: the gossip mailboxes and status blocks live "
                  "there (fsx cluster creates them)", file=sys.stderr)
            return 1
        from flowsentryx_tpu.cluster import GossipPlane
        from flowsentryx_tpu.engine.shm import RingNotReady

        try:
            gossip = GossipPlane(args.cluster_dir, cluster_rank,
                                 cluster_n)
        except ValueError as e:
            # plane exists but disagrees with the flags (e.g. the
            # stamped fleet size != N): the plane's own message names
            # the problem better than "not initialized" would
            print(f"fsx serve: {e}", file=sys.stderr)
            return 1
        except (OSError, RingNotReady) as e:
            print(f"fsx serve: cluster dir {args.cluster_dir!r} is not "
                  f"an initialized gossip plane: {e} (fsx cluster "
                  "creates the mailboxes and status blocks before any "
                  "engine boots)", file=sys.stderr)
            return 1
        t0_ns = gossip.status.ctl_get("c_t0")
        if not t0_ns:
            print("fsx serve: cluster epoch not published (status "
                  "c_t0 == 0): every engine's device clock — and "
                  "every gossiped blacklist `until` — must share one "
                  "t0; boot the fleet through fsx cluster, which "
                  "stamps it", file=sys.stderr)
            return 1
    # Table-geometry validation, still BEFORE the JAX boot: config
    # parsing and the geometry validators (engine/table.py) are
    # jax-free, so a bad --table-capacity or an unrestorable
    # checkpoint refuses in milliseconds with its actual problem
    # named, not after a multi-second backend init (or worse, after
    # silently corrupting the slot layout).
    import dataclasses as _dck

    cfg = _load_cfg(args)
    if cfg.table.key_bits == 128:
        problem = _key128_serve_problem(args)
        if problem:
            print(f"fsx serve: table.key_bits 128: {problem}",
                  file=sys.stderr)
            return 1
    if args.verdict_k is not None:
        cfg = _dck.replace(cfg, batch=_dck.replace(
            cfg.batch, verdict_k=args.verdict_k))
    if args.mesh:
        # --mesh N is a spelling of table.shards: the engine builds the
        # mesh from the table (Engine, parallel.make_mesh)
        from flowsentryx_tpu.engine.table import validate_capacity

        problems = validate_capacity(
            args.table_capacity or cfg.table.capacity, 0, args.mesh)
        if problems:
            for p in problems:
                print(f"fsx serve: --mesh: {p}", file=sys.stderr)
            return 1
        cfg = _dck.replace(cfg, table=_dck.replace(
            cfg.table, shards=args.mesh))
    if args.table_capacity is not None:
        from flowsentryx_tpu.engine.table import validate_capacity

        problems = validate_capacity(args.table_capacity,
                                     cfg.batch.max_batch,
                                     cfg.table.shards)
        if problems:
            for p in problems:
                print(f"fsx serve: --table-capacity: {p}",
                      file=sys.stderr)
            return 1
        cfg = _dck.replace(cfg, table=_dck.replace(
            cfg.table, capacity=args.table_capacity))
    ck_hdr = None
    if args.restore:
        import zipfile as _zf

        from flowsentryx_tpu.engine.checkpoint import (
            CheckpointCorrupt, peek_header, prev_path,
        )

        try:
            ck_hdr = peek_header(args.restore)
        except CheckpointCorrupt as e:
            # corrupt/truncated live checkpoint: the retained previous
            # generation is what will actually load — validate
            # geometry/salt against ITS header, but leave
            # ``args.restore`` pointing at the original file so
            # ``Engine.restore`` performs the fallback itself and
            # COUNTS it (``restore_fallbacks`` is a DEGRADED reason;
            # re-pointing here would silently launder the fallback
            # into a clean-looking restore)
            prev = prev_path(args.restore)
            try:
                ck_hdr = peek_header(prev)
            except (OSError, ValueError, KeyError, _zf.BadZipFile):
                print(f"fsx serve: checkpoint {args.restore!r} is "
                      f"corrupt ({e}) and no restorable previous "
                      "generation exists — refusing to boot from "
                      "garbage", file=sys.stderr)
                return 1
            print(f"fsx serve: checkpoint {args.restore!r} REFUSED "
                  f"({e}); the retained previous generation {prev} "
                  "will be restored instead (flow memory resumes one "
                  "generation stale; counted in the health ladder)",
                  file=sys.stderr)
        except (OSError, ValueError, KeyError, _zf.BadZipFile) as e:
            print(f"fsx serve: cannot read checkpoint "
                  f"{args.restore!r}: {e}", file=sys.stderr)
            return 1
        if cfg.table.salt and cfg.table.salt != ck_hdr["hash_salt"]:
            # an EXPLICITLY configured salt that disagrees with the
            # checkpoint's is refused, not silently overridden:
            # proceeding under either value breaks one side's slot
            # layout (the config owner asked for one hash universe,
            # the checkpoint was built in another)
            print(
                f"fsx serve: config salt {cfg.table.salt:#x} != "
                f"checkpoint salt {ck_hdr['hash_salt']:#x} — refusing "
                "to restore (the table's slot layout is bound to the "
                "salt it was built under). Drop the config salt to "
                "adopt the checkpoint's, or retire the checkpoint.",
                file=sys.stderr)
            return 1
        if args.table_capacity is None and not getattr(args, "config",
                                                       None):
            # no capacity was asked for: adopt the checkpoint's so a
            # plain `fsx serve --restore` resumes bit-identically
            # instead of resharding into the config default — but the
            # adopted geometry passes the SAME validation an explicit
            # --table-capacity would (a checkpoint from a smaller-batch
            # era must refuse loudly, not degrade via arbitration drops)
            from flowsentryx_tpu.engine.table import validate_capacity

            problems = validate_capacity(ck_hdr["capacity"],
                                         cfg.batch.max_batch,
                                         cfg.table.shards)
            if problems:
                for p in problems:
                    print(f"fsx serve: checkpoint capacity: {p}",
                          file=sys.stderr)
                print("fsx serve: pass --table-capacity to reshard "
                      "the restore into a serving-valid geometry",
                      file=sys.stderr)
                return 1
            cfg = _dck.replace(cfg, table=_dck.replace(
                cfg.table, capacity=ck_hdr["capacity"]))
        if (ck_hdr["capacity"] != cfg.table.capacity
                or ck_hdr["n_shards"] != cfg.table.shards):
            print(
                f"fsx serve: checkpoint geometry "
                f"{ck_hdr['capacity']} rows x {ck_hdr['n_shards']} "
                f"shard(s) != boot geometry {cfg.table.capacity} rows "
                f"x {cfg.table.shards} shard(s): occupied rows will "
                "be resharded at restore (engine/table.py)",
                file=sys.stderr)
    # the engine-stack import wall is part of boot-to-serving and the
    # compile cache cannot shave it — measured and surfaced in the
    # report's boot block next to the compile/cache-load timings
    import time as _time

    _t_imp = _time.perf_counter()
    from flowsentryx_tpu.engine import Engine, NullSink, TrafficSource
    from flowsentryx_tpu.engine.traffic import Scenario, TrafficSpec

    import_s = _time.perf_counter() - _t_imp
    from flowsentryx_tpu.core import runtime

    runtime.require_platform("fsx serve")
    jax_compiles = runtime.CompileCounters(runtime.place_compile_cache())
    if args.feature_ring:
        from flowsentryx_tpu.engine.shm import ShmRingSource, ShmVerdictSink

        if args.ingest_workers:
            # Sharded parallel ingest (flowsentryx_tpu/ingest/): N drain
            # workers front N ring shards (fsxd --shards N; N=1 fronts
            # an unsharded daemon) and hand the engine sealed batches.
            # A cluster rank fronts only ITS contiguous span of the
            # N*W-shard fan-out (parallel/layout.py ClusterLayout).
            from flowsentryx_tpu.ingest import ShardedIngest

            span = {}
            if cluster_rank is not None:
                span = dict(
                    shard_offset=cluster_rank * args.ingest_workers,
                    total_shards=cluster_n * args.ingest_workers)
            source = ShardedIngest(args.feature_ring, args.ingest_workers,
                                   strict=args.strict_ingest,
                                   quarantine_dir=args.quarantine_dir,
                                   **span)
        else:
            source = ShmRingSource(args.feature_ring)
        sink = (
            ShmVerdictSink(args.verdict_ring) if args.verdict_ring else NullSink()
        )
    elif args.records:
        import numpy as np

        from flowsentryx_tpu.core import schema
        from flowsentryx_tpu.engine import ArraySource

        arr = np.frombuffer(
            Path(args.records).read_bytes(), schema.FLOW_RECORD_DTYPE
        )
        if args.packets:
            arr = arr[: args.packets]
        source = ArraySource(arr)
        sink = NullSink()
    else:
        source = TrafficSource(
            TrafficSpec(scenario=Scenario(args.scenario), rate_pps=args.rate),
            total=args.packets or None,
        )
        sink = NullSink()
    # Boot-time hash salt (TableConfig.salt docstring): a restore must
    # hash with the salt the checkpoint's slot layout was built under
    # (an EXPLICIT conflicting config salt was already refused
    # pre-boot); otherwise an unspecified salt (0 = auto) draws a
    # fresh random one so slot/owner collisions can't be precomputed
    # by an attacker.
    import dataclasses as _dc

    if args.restore:
        ck_salt = ck_hdr["hash_salt"]
        if ck_salt == 0:
            print(
                "fsx serve: WARNING restoring a pre-salt checkpoint - "
                "running with the UNSALTED public hash (slot/owner "
                "collisions are precomputable). Retire the checkpoint "
                "to re-enable the boot-time salt defense.",
                file=sys.stderr,
            )
        cfg = _dc.replace(cfg, table=_dc.replace(cfg.table, salt=ck_salt))
    elif cfg.table.salt == 0:
        cfg = _dc.replace(cfg, table=_dc.replace(
            cfg.table, salt=_boot_salt(args.compile_cache, "serve")))
    params = None
    if args.artifact:
        from flowsentryx_tpu.models.registry import load_artifact

        params = load_artifact(cfg.model.name, args.artifact)
    if args.mega:
        # Mirror Engine's wire choice up front: --mega needs compact16,
        # which the engine picks only for a compact-emit ring or an
        # observer-carrying artifact.  Catching it here turns a
        # post-compile ValueError traceback into a clean refusal.
        from flowsentryx_tpu.models import get_model

        probe = params if params is not None else get_model(cfg.model.name).init()
        if not (getattr(source, "precompact", False)
                or hasattr(probe, "in_scale")):
            print(
                "fsx serve: --mega requires the compact16 wire, but the "
                "selected model exposes no input observer so the engine "
                "would serve raw48; pass an observer-carrying artifact "
                "(e.g. --artifact artifacts/logreg_int8.npz) or drop "
                "--mega", file=sys.stderr)
            return 1
    kernel_tier = None
    if args.sim_kernel_tier:
        from flowsentryx_tpu.distill import SimKernelTier
        from flowsentryx_tpu.distill.plan import load_plan

        if getattr(source, "precompact", False):
            # Engine would refuse this too, but with a raw traceback;
            # mirror the --ingest-workers refusal (records off a
            # compact-emit ring are kernel-quantized — unscoreable)
            print("fsx serve: --sim-kernel-tier cannot rescore a "
                  "compact-emit feature ring (records arrive kernel-"
                  "quantized); serve a 48 B ring or deploy the real "
                  "tier via fsx distill --pin", file=sys.stderr)
            return 1
        import zipfile

        try:
            kernel_tier = SimKernelTier(load_plan(args.sim_kernel_tier),
                                        block_s=cfg.model.ml_block_s)
        # ValueError covers DistillError (its base) AND np.load's
        # complaints about corrupt/pickled npz payloads; BadZipFile is
        # what a non-zip file raises
        except (OSError, ValueError, KeyError, zipfile.BadZipFile) as e:
            print(f"fsx serve: cannot load the distill plan "
                  f"{args.sim_kernel_tier!r}: {e} (generate one with "
                  "fsx distill ARTIFACT --out PLAN.npz)", file=sys.stderr)
            return 1
    eng = Engine(cfg, source, sink, params=params,
                 mega_n=args.mega or 0,
                 t0_ns=t0_ns,
                 sink_thread=False if args.no_sink_thread else None,
                 audit=True if args.audit else None,
                 kernel_tier=kernel_tier,
                 gossip=gossip,
                 slo_us=args.slo_us,
                 predict=args.predict,
                 watchdog_s=args.watchdog_s,
                 compile_cache=args.compile_cache)
    eng.boot_import_s = round(import_s, 4)
    eng.boot_jax_compiles = jax_compiles
    if args.restore:
        from flowsentryx_tpu.engine.checkpoint import CheckpointCorrupt

        try:
            eng.restore(args.restore)
        except CheckpointCorrupt as e:
            # both generations corrupt (a CRC-level .prev flip passes
            # the pre-boot peek — only the load verifies payload
            # bytes): refuse with the named diagnostic, never a raw
            # traceback, even this late
            print(f"fsx serve: cannot restore: {e} — refusing to "
                  "serve from garbage", file=sys.stderr)
            return 1
    if args.artifact_reload:
        # live model hot-swap: re-stat the artifact and swap it in
        # mid-serve on mtime change (Engine.watch_artifact; the
        # distill --pin push, brought to the TPU tier)
        eng.watch_artifact(args.artifact)
    if args.mega or args.slo_us:
        # pay every staged compile (each ladder rung) at boot, not on
        # the first traffic backlog; SLO
        # mode additionally needs warm()'s timed pass to seed the
        # per-rung step-time EWMA the budget policy reads.  Tiered:
        # only the serving tier (singles + top rung) blocks boot, a
        # background thread fills the rest — with --compile-cache the
        # fill is milliseconds of deserialization per rung
        eng.warm(tiered=args.tiered_warm)
    if gossip is not None:
        from flowsentryx_tpu.core import schema as _schema

        gossip.set_state(_schema.CSTATE_SERVING)
    import contextlib

    if args.profile:
        # device+host trace viewable in TensorBoard / Perfetto
        # (SURVEY.md §5.1: jax.profiler traces for the rebuild)
        import jax

        ctx = jax.profiler.trace(args.profile)
    else:
        ctx = contextlib.nullcontext()
    if args.checkpoint and args.checkpoint_every:
        # Periodic checkpointing (SURVEY.md §5.4 made operational): the
        # engine saves table/stats/clock every checkpoint_every seconds
        # at a group boundary while it serves, so a crash loses at most
        # one interval of flow memory
        from flowsentryx_tpu.engine.checkpoint import CheckpointPolicy

        eng.set_checkpoint_policy(
            CheckpointPolicy(args.checkpoint, args.checkpoint_every))
    with ctx:
        rep = eng.run(
            max_batches=args.batches or None,
            max_seconds=args.seconds or None,
        )
    if args.checkpoint:
        eng.checkpoint(args.checkpoint)
    if gossip is not None:
        from flowsentryx_tpu.core import schema as _schema

        gossip.set_state(_schema.CSTATE_DONE)
    if hasattr(source, "close"):
        source.close()  # stop + join the ingest worker fleet
        if rep.ingest is not None and hasattr(source, "ingest_stats"):
            # close() is what counts drain-on-shutdown losses
            # (dropped_tail_batches, late emit_drops): re-snapshot so
            # the printed report carries them instead of the stale
            # zeros captured while the fleet was still live.
            rep = rep._replace(ingest=source.ingest_stats())
    print(json.dumps(rep._asdict(), indent=2))
    return 0


def _parse_gossip_addr(text: str, engines: int):
    """``IP:PORT`` → (``[ip, port]``, None) or (None, error string) —
    the one parser for --hosts entries AND --gossip-listen, so the
    derived-engine-port bound (the federation beacon binds PORT,
    engine r binds PORT+1+r) is enforced identically everywhere."""
    ip, _, port_s = text.strip().rpartition(":")
    try:
        port = int(port_s)
    except ValueError:
        port = -1
    if not ip or not 0 < port < 65536:
        return None, ("is not IP:PORT (the gossip base port; the "
                      "supervisor beacon binds it, engine r binds "
                      "PORT+1+r)")
    if port + engines > 65535:
        # the derived engine ports must fit too, or the refusal would
        # surface as a bind crash-loop in a spawned child instead of
        # a named pre-boot message
        return None, (f"base port {port} + {engines} engine port(s) "
                      "exceeds 65535 (engine r binds PORT+1+r) — "
                      "pick a lower base port")
    return [ip, port], None


def _cmd_cluster(args: argparse.Namespace) -> int:
    """Coordinator-less multi-engine scale-out (docs/CLUSTER.md).

    N full engine processes, each owning ring shards
    ``[r*W, (r+1)*W)`` of the daemon's ``N*W``-shard IP-hash fan-out
    end-to-end (``fsxd --shards N*W``) — its own drain workers,
    dispatch arena, device loop and flow-table partition — sharing
    ONLY the verdict-gossip blacklist plane.  The supervisor here is
    pure control plane: it creates the shm plane, stamps the shared
    t0 epoch, spawns the engines, and restarts any that die from
    their last checkpoint (crash-fail-open: the survivors keep
    serving, and the dead engine's blocks are already replicated).
    """
    # Pre-boot refusals, all jax-free, each naming its actual problem
    # (the fsx serve fail-fast ordering).
    if args.engines < 2 and not args.hosts:
        print(f"fsx cluster: --engines must be >= 2 (got "
              f"{args.engines}): a 1-engine cluster is fsx serve "
              "(unless --hosts makes it one rank of a multi-host "
              "fleet)", file=sys.stderr)
        return 1
    if args.engines < 1:
        print(f"fsx cluster: --engines must be >= 1 (got "
              f"{args.engines})", file=sys.stderr)
        return 1
    # Elastic-fleet shape (docs/CLUSTER.md §elastic): the plane is
    # PROVISIONED at --max-engines (rings, status blocks, mailboxes
    # all pre-exist) and only --engines of them spawn at boot — the
    # autoscaler grows/shrinks the live set inside that envelope, so
    # total_shards = max * W never changes and every reshape is a
    # pure ownership flip.
    if (args.min_engines is not None or args.max_engines is not None) \
            and not args.elastic:
        print("fsx cluster: --min-engines/--max-engines require "
              "--elastic (they bound the autoscaler's live-rank "
              "envelope)", file=sys.stderr)
        return 1
    if args.elastic and args.hosts:
        print("fsx cluster: --elastic is single-host for now (the "
              "handoff mailbox and fence protocol ride the shm "
              "plane; cross-host handoff coordination is a "
              "documented follow-up — docs/CLUSTER.md §elastic)",
              file=sys.stderr)
        return 1
    provision = args.engines
    if args.elastic:
        provision = args.max_engines or max(args.engines + 1,
                                            args.engines)
        if provision < args.engines:
            print(f"fsx cluster: --max-engines {provision} < "
                  f"--engines {args.engines}: the initial live set "
                  "cannot exceed the provisioned envelope",
                  file=sys.stderr)
            return 1
        if (args.min_engines or 1) > args.engines:
            print(f"fsx cluster: --min-engines {args.min_engines} > "
                  f"--engines {args.engines}: the fleet would boot "
                  "already below its floor", file=sys.stderr)
            return 1
    if args.shards < provision:
        print(f"fsx cluster: --shards {args.shards} cannot feed "
              f"{provision} provisioned engines: every engine needs "
              "at least one ring shard to drain (pair with fsxd "
              "--shards N*W)", file=sys.stderr)
        return 1
    if args.shards % provision:
        print(f"fsx cluster: --shards {args.shards} is not a multiple "
              f"of {provision} (the provisioned engine count: "
              "--max-engines under --elastic, --engines otherwise): "
              "each engine owns an equal contiguous span of the "
              "ring-shard fan-out (rank r drains shards "
              "[r*W, (r+1)*W), W = shards/provisioned)",
              file=sys.stderr)
        return 1
    w = args.shards // provision
    if args.checkpoint:
        # validate by FORMATTING, not substring: '{rank:02d}' is a
        # fine placeholder, '{host}' is a KeyError waiting to fire
        # after the jax boot, and a rank-invariant template means N
        # engines overwriting one file
        try:
            distinct = (args.checkpoint.format(rank=0)
                        != args.checkpoint.format(rank=1))
        except (KeyError, IndexError, ValueError) as e:
            print(f"fsx cluster: --checkpoint {args.checkpoint!r} "
                  f"does not format with rank= alone ({e!r}): the "
                  "template may use only a {rank} placeholder",
                  file=sys.stderr)
            return 1
        if not distinct:
            print(f"fsx cluster: --checkpoint {args.checkpoint!r} has "
                  "no {rank} placeholder: "
                  + str(args.engines) + " engines "
                  "checkpointing the same path would overwrite each "
                  "other's flow memory (and a restart would restore "
                  "the wrong shard's table)", file=sys.stderr)
            return 1
    if args.checkpoint_every < 0:
        print("fsx cluster: --checkpoint-every must be >= 0 "
              "(0 disables)", file=sys.stderr)
        return 1
    if args.checkpoint_every and not args.checkpoint:
        print("fsx cluster: --checkpoint-every requires --checkpoint "
              "TEMPLATE (with a {rank} placeholder)", file=sys.stderr)
        return 1
    if args.verdict_k is not None and args.verdict_k < 0:
        print("fsx cluster: --verdict-k must be >= 0", file=sys.stderr)
        return 1
    if args.tiered_warm and not args.mega:
        print("fsx cluster: --tiered-warm requires --mega N|auto "
              "(the serving tier IS the top coalescing rung)",
              file=sys.stderr)
        return 1
    if args.slo_us < 0:
        print("fsx cluster: --slo-us must be >= 0", file=sys.stderr)
        return 1
    if args.predict and not args.slo_us:
        print("fsx cluster: --predict requires --slo-us > 0 (the "
              "governor acts against each rank's latency budget)",
              file=sys.stderr)
        return 1
    if not args.feature_ring:
        print("fsx cluster: --feature-ring BASE is required: engines "
              f"front the daemon's ring shards (pair with fsxd "
              f"--shards {args.shards})", file=sys.stderr)
        return 1
    # Multi-host leg (docs/CLUSTER.md §multi-host): --hosts names every
    # host's gossip base address, --host-id says which one WE are, and
    # the port arithmetic (supervisor beacon at base, engine r at
    # base+1+r) assumes a uniform --engines per host — all refused
    # jax-free with the actual problem named.
    netspec = None
    if args.hosts or args.host_id is not None or args.gossip_listen:
        if not args.hosts:
            print("fsx cluster: --host-id/--gossip-listen require "
                  "--hosts IP:PORT,IP:PORT,... (the fleet's host "
                  "table — every host runs the same list)",
                  file=sys.stderr)
            return 1
        if args.host_id is None:
            print("fsx cluster: --hosts requires --host-id I (this "
                  "host's index into the --hosts list; the port "
                  "layout and the federation identity both derive "
                  "from it)", file=sys.stderr)
            return 1
        hosts = []
        for ent in args.hosts.split(","):
            addr, err = _parse_gossip_addr(ent, args.engines)
            if err:
                print(f"fsx cluster: --hosts entry {ent.strip()!r} "
                      f"{err}", file=sys.stderr)
                return 1
            hosts.append(addr)
        if len(hosts) < 2:
            print(f"fsx cluster: --hosts names {len(hosts)} host(s): "
                  "a 1-host fleet is fsx cluster without --hosts (the "
                  "shm gossip plane already covers it)",
                  file=sys.stderr)
            return 1
        if not 0 <= args.host_id < len(hosts):
            print(f"fsx cluster: --host-id {args.host_id} not in "
                  f"[0, {len(hosts)}) (the --hosts list has "
                  f"{len(hosts)} entries)", file=sys.stderr)
            return 1
        listen = None
        if args.gossip_listen:
            listen, err = _parse_gossip_addr(args.gossip_listen,
                                             args.engines)
            if err:
                print(f"fsx cluster: --gossip-listen "
                      f"{args.gossip_listen!r} {err}",
                      file=sys.stderr)
                return 1
        netspec = {"hosts": hosts, "host_id": args.host_id,
                   "engines_per_host": args.engines, "listen": listen}

    import dataclasses as _dc

    cfg = _load_cfg(args)
    if cfg.table.key_bits == 128:
        print("fsx cluster: table.key_bits 128 is not built for the "
              "cluster: its gossip, rebalance, hand-off and saves carry "
              "32-bit keys (serve it with fsx serve)", file=sys.stderr)
        return 1
    if args.verdict_k is not None:
        cfg = _dc.replace(cfg, batch=_dc.replace(
            cfg.batch, verdict_k=args.verdict_k))
    if args.table_capacity is not None:
        from flowsentryx_tpu.engine.table import validate_capacity

        problems = validate_capacity(args.table_capacity,
                                     cfg.batch.max_batch)
        if problems:
            for p in problems:
                print(f"fsx cluster: --table-capacity: {p}",
                      file=sys.stderr)
            return 1
        cfg = _dc.replace(cfg, table=_dc.replace(
            cfg.table, capacity=args.table_capacity))
    if cfg.table.salt == 0:
        # one shared random salt: every engine's table (and every
        # checkpoint) lives in the same hash universe, so operators
        # can reason about the fleet as one table split N ways
        cfg = _dc.replace(cfg, table=_dc.replace(
            cfg.table, salt=_boot_salt(args.compile_cache, "cluster")))
    if args.mega:
        # mirror the serve-side compact16 probe: refuse a model the
        # engines would refuse, once, here — not N times in N children
        _place_compile_cache()
        from flowsentryx_tpu.models import get_model

        if args.artifact:
            from flowsentryx_tpu.models.registry import load_artifact

            probe = load_artifact(cfg.model.name, args.artifact)
        else:
            probe = get_model(cfg.model.name).init()
        if not hasattr(probe, "in_scale"):
            print("fsx cluster: --mega requires the compact16 wire, "
                  "but the selected model exposes no input observer; "
                  "pass an observer-carrying artifact (e.g. "
                  "--artifact artifacts/logreg_int8.npz) or drop "
                  "--mega", file=sys.stderr)
            return 1

    from flowsentryx_tpu.cluster.runner import pin_core_for
    from flowsentryx_tpu.cluster.supervisor import ClusterSupervisor

    cluster_dir = args.cluster_dir or f"{args.feature_ring}.cluster"
    specs = []
    for r in range(provision):
        specs.append({
            # the per-core deployment shape (runner.pin_core_for):
            # rank r owns core r when the fleet fits the host, with
            # the XLA pool sized to match
            "pin_core": pin_core_for(r, provision, args.pin_cores),
            "cfg_json": cfg.to_json(),
            "ring_base": args.feature_ring,
            "workers": w,
            "total_shards": args.shards,
            "verdict_ring": (f"{args.verdict_ring}.r{r}"
                             if args.verdict_ring else None),
            "mega": args.mega or 0,
            "slo_us": args.slo_us,
            "predict": bool(args.predict),
            "artifact": args.artifact,
            # one shared cache dir across the fleet: every rank (and
            # every provisioned-at-max SPARE) stages the same shape,
            # so a GROW spawn's warm() hits the entries the boot-time
            # pre-warm child stored (supervisor._maybe_prewarm)
            "compile_cache": args.compile_cache,
            "tiered_warm": bool(args.tiered_warm),
            "checkpoint": (args.checkpoint.format(rank=r)
                           if args.checkpoint else None),
            "checkpoint_every": args.checkpoint_every,
        })
    policy = None
    if args.elastic:
        from flowsentryx_tpu.cluster.elastic import ElasticPolicy

        policy = ElasticPolicy(min_engines=args.min_engines or 1,
                               max_engines=provision)
    sup = ClusterSupervisor(cluster_dir, specs,
                            max_restarts=args.max_restarts,
                            net=netspec, elastic=policy,
                            n_live=(args.engines if args.elastic
                                    else None))
    try:
        sup.boot(adopt=args.adopt)
    except RuntimeError as e:
        # e.g. a live fleet already owns this plane (booting over it
        # would truncate mmaps under its serving engines)
        print(f"fsx cluster: {e}", file=sys.stderr)
        return 1
    net_note = ""
    if netspec:
        net_note = (f", host {netspec['host_id']} of "
                    f"{len(netspec['hosts'])} (UDP gossip + "
                    "federation beacons)")
    if args.elastic:
        net_note += (f", elastic "
                     f"[{args.min_engines or 1}, {provision}]")
    print(f"fsx cluster: {args.engines} engines x {w} worker(s), "
          f"shards 0..{args.shards - 1}, gossip plane {cluster_dir}"
          f"{net_note}", file=sys.stderr)
    try:
        agg = sup.run(max_seconds=args.seconds or None)
    except KeyboardInterrupt:
        sup.close()
        agg = sup.aggregate()
    print(json.dumps(agg, indent=2))
    return 0 if not agg["failed_ranks"] else 1


def _iter_engine_reports(globs: list):
    """Shared engine-report walk for the ``--engine-report GLOB``
    consumers: expand each (repeatable) glob, dedupe by realpath so
    overlapping globs never double-merge a report, and yield
    ``(path, doc, error)`` — ``doc`` parsed JSON on success, ``error``
    a string when the file is unreadable/unparseable (the caller
    decides whether that is a skip or a DEGRADED signal).  A pattern
    matching nothing yields itself as an unreadable entry rather than
    vanishing — a typo'd path must surface, not silently merge zero
    reports."""
    import glob as _glob

    seen: set[str] = set()
    for pat in globs:
        for path in sorted(_glob.glob(pat)) or [pat]:
            key = os.path.realpath(path)
            if key in seen:
                continue
            seen.add(key)
            try:
                yield path, json.loads(Path(path).read_text()), None
            except (OSError, ValueError) as e:
                yield path, None, str(e)


def _report_body(doc: dict) -> dict:
    """The engine report inside a parsed report file: ``fsx serve``
    prints it bare, the cluster runner wraps it as ``{"report": ...}``."""
    rep = doc.get("report")
    return rep if isinstance(rep, dict) else doc


def _merged_latency(globs: list[str], reports: list | None = None) -> dict:
    """Merge the ``latency`` blocks of engine-report JSONs (``fsx
    serve`` output, or a cluster dir's per-rank ``report_r*_g*.json``
    wrappers) into ONE seal→verdict percentile view — the HDR bucket
    counts are mergeable by construction (engine/metrics.py), which is
    the whole reason the report carries them.  Shared by ``fsx status
    --engine-report`` and ``fsx monitor --engine-report``; jax-free.
    ``reports`` = a pre-materialized :func:`_iter_engine_reports` list,
    so one read/parse pass feeds this AND the health merge (the
    monitor calls both every tick)."""
    from flowsentryx_tpu.engine.metrics import LatencyHist

    merged = LatencyHist()
    sources = []
    per_report = {}
    for path, doc, err in (reports if reports is not None
                           else _iter_engine_reports(globs)):
        if err is not None:
            per_report[path] = {"error": err}
            continue
        lat = (doc.get("latency")
               or doc.get("report", {}).get("latency"))
        if not lat or not lat.get("hist"):
            per_report[path] = {"error": "no latency block"}
            continue
        try:
            h = LatencyHist.from_counts(lat["hist"])
        except ValueError as e:
            per_report[path] = {"error": str(e)}
            continue
        merged.merge(h)
        sources.append(path)
        sv = lat.get("seal_to_verdict") or {}
        per_report[path] = {
            "n": sv.get("n", 0),
            "p99_us": sv.get("p99"),
        }
    return {
        "reports_merged": len(sources),
        "per_report": per_report,
        "seal_to_verdict_us": merged.to_dict(),
    }


def _merged_engine_health(globs: list, reports: list | None = None) -> dict:
    """Merge the ``health`` + gossip-counter blocks of engine-report
    JSONs into one operator view: per-report state/reasons, the gossip
    plane's drop/seq-gap counters (recorded since PR 10, SHOWN since
    PR 13 — they feed the DEGRADED reasons), and the worst-of fold.
    A report that cannot be read folds in as DEGRADED — "the rank
    whose health cannot be read is not healthy" (engine/health.py),
    and a crashed-mid-write report is most likely exactly when the
    fleet is most broken.  Jax-free; shares
    :func:`_iter_engine_reports` with the latency merge."""
    from flowsentryx_tpu.engine import health as health_mod

    per_report: dict = {}
    states: list[str] = []
    rebalance_totals: dict = {}
    for path, doc, err in (reports if reports is not None
                           else _iter_engine_reports(globs)):
        if err is not None:
            per_report[path] = {
                "state": health_mod.DEGRADED,
                "reasons": [f"report_unreadable:{err}"],
                "error": err,
            }
            states.append(health_mod.DEGRADED)
            continue
        rep = _report_body(doc)
        h = rep.get("health") or {}
        g = rep.get("cluster") or {}
        entry: dict = {
            "state": h.get("state"),
            "reasons": h.get("reasons", []),
        }
        if g:
            entry["gossip"] = {
                "tx_wires": g.get("tx_wires"),
                "tx_dropped": g.get("tx_dropped"),
                "rx_wires": g.get("rx_wires"),
                "rx_seq_gaps": g.get("rx_seq_gaps"),
                "merged_digest": g.get("merged_digest"),
            }
            net = g.get("net")
            if net:
                # the multi-host transport's counters (cluster/
                # transport.py) — the net_* DEGRADED reasons' raw
                # numbers, so "why is this rank degraded" is the same
                # one query
                entry["gossip"]["net"] = {
                    k: net.get(k)
                    for k in ("tx_wires", "tx_drop", "rx_wires",
                              "rx_gap", "rx_dup", "reorder_evict",
                              "epoch_skew_dropped", "epoch_skew_max",
                              "net_digest")
                }
        rb = rep.get("rebalance")
        if rb:
            # live-handoff / adoption accounting (cluster/
            # rebalance.py): per-rank here, summed below — "did rows
            # move, and did any fall off the happy path" is the same
            # one query as the health ladder
            entry["rebalance"] = rb
            for k, v in rb.items():
                if isinstance(v, int):
                    rebalance_totals[k] = rebalance_totals.get(k, 0) + v
        per_report[path] = entry
        if h.get("state"):
            states.append(h["state"])
    out = {
        "state": (health_mod.worst(*states) if states else None),
        "reports": per_report,
    }
    if rebalance_totals:
        out["rebalance"] = rebalance_totals
    return out


def _merged_predict(reports: list) -> dict | None:
    """Merge the ``predict`` blocks of engine-report JSONs (the
    dispatch governor's forecast + actuation counters, ISSUE 18) into
    one fleet view via :meth:`DispatchGovernor.merge_reports` — the
    same fold the cluster supervisor's ``aggregate()`` applies, so
    ``fsx status`` on a report glob and the supervisor's own aggregate
    never disagree.  Jax-free (engine/predict.py is numpy-only).
    Returns None when no report carries a predict block (predictor-off
    fleets don't grow an empty stanza)."""
    blocks = []
    for _path, doc, err in reports:
        if err is not None:
            continue
        rep = _report_body(doc)
        if rep.get("predict"):
            blocks.append(rep["predict"])
    if not blocks:
        return None
    from flowsentryx_tpu.engine.predict import DispatchGovernor

    return DispatchGovernor.merge_reports(blocks)


def _merged_boot(reports: list) -> dict | None:
    """Merge the ``boot`` blocks of engine-report JSONs (compile-cache
    hit/miss story, serving-ready and import walls) into one fleet
    view — the same fold the cluster supervisor's ``aggregate()``
    applies, so ``fsx status`` on a report glob never disagrees with
    it.  Jax-free.  Returns None when no report carries a boot block
    (engines that never warm()ed don't grow an empty stanza)."""
    per_report: dict = {}
    hits = misses = stores = 0
    max_ready = 0.0
    for path, doc, err in reports:
        if err is not None:
            continue
        rep = _report_body(doc)
        boot = rep.get("boot")
        if not boot:
            continue
        per_report[path] = boot
        cache = boot.get("cache")
        if isinstance(cache, dict):
            hits += cache.get("hits", 0)
            misses += cache.get("misses", 0)
            stores += cache.get("stores", 0)
        max_ready = max(max_ready, boot.get("serving_ready_s") or 0.0)
    if not per_report:
        return None
    return {
        "per_report": per_report,
        "cache_hits": hits,
        "cache_misses": misses,
        "cache_stores": stores,
        "max_serving_ready_s": round(max_ready, 4),
    }


def _merged_device(reports: list) -> dict | None:
    """Merge the ``device`` blocks of engine-report JSONs (where each
    engine placed its table) via :func:`health.fleet_devices` — the
    fold the cluster supervisor's ``aggregate()`` applies.  Jax-free."""
    from flowsentryx_tpu.engine.health import fleet_devices

    per_engine = {}
    for path, doc, err in reports:
        if err is not None:
            continue
        rep = _report_body(doc)
        per_engine[path] = rep.get("device")
    return fleet_devices(per_engine)


def _merged_spans(reports: list) -> dict | None:
    """Merge the ``spans`` blocks of engine-report JSONs (the span
    store, engine/metrics.py) name by name: counts, sums and buckets
    add across engines.  The result has the reports' own schema and is
    cumulative like them, so two ``fsx status --engine-report`` reads
    subtracted are the window between them.  Jax-free."""
    from flowsentryx_tpu.engine.metrics import LatencyHist, span_store

    merged: dict = {}
    for _path, doc, err in reports:
        if err is not None:
            continue
        rep = _report_body(doc)
        for name, entry in (rep.get("spans") or {}).items():
            try:
                h = LatencyHist.from_counts(entry["hist"])
            except (KeyError, ValueError):
                continue  # a foreign or torn block: skipped, not merged
            # the entry's own sum is exact; the hist's copy is rounded
            h.sum_us = float(entry.get("sum_us", h.sum_us))
            merged.setdefault(name, LatencyHist()).merge(h)
    return span_store(merged) if merged else None


def _cmd_status(args: argparse.Namespace) -> int:
    """Inspect the shm transport: ring cursors and backlog."""
    import numpy as np

    from flowsentryx_tpu.core import schema

    out = {}
    for name, path in (("feature_ring", args.feature_ring),
                       ("verdict_ring", args.verdict_ring)):
        p = Path(path)
        if not p.exists():
            out[name] = {"present": False}
            continue
        with open(p, "rb") as f:
            import mmap

            m = mmap.mmap(f.fileno(), 0, prot=mmap.PROT_READ)
        hdr = np.frombuffer(m, np.uint64, schema.SHM_HDR_SIZE // 8, 0)
        head = int(hdr[schema.SHM_HEAD_OFFSET // 8])
        tail = int(hdr[schema.SHM_TAIL_OFFSET // 8])
        out[name] = {
            "present": True,
            "magic_ok": int(hdr[0]) == schema.SHM_MAGIC,
            "capacity": int(hdr[1]),
            "record_size": int(hdr[2]),
            "produced": head,
            "consumed": tail,
            "backlog": head - tail,
        }

    if args.pin:
        # live kernel counters off the pinned maps (the reference's
        # planned "display network statistics", README.md:143-146)
        out["kernel"] = _read_kernel(args.pin)
    if args.engine_report:
        # ONE read/parse pass feeds both merges: the engine-side
        # seal->verdict latency (the report JSON is the interface —
        # the kernel maps can't carry it), and the health ladder +
        # gossip drop/seq-gap counters (always recorded; surfaced
        # here so "is the fleet OK?" is one query, not a log grep)
        reports = list(_iter_engine_reports(args.engine_report))
        out["latency"] = _merged_latency(args.engine_report,
                                         reports=reports)
        out["health"] = _merged_engine_health(args.engine_report,
                                              reports=reports)
        predict = _merged_predict(reports)
        if predict is not None:
            out["predict"] = predict
        boot = _merged_boot(reports)
        if boot is not None:
            out["boot"] = boot
        device = _merged_device(reports)
        if device is not None:
            out["device"] = device
        spans = _merged_spans(reports)
        if spans is not None:
            out["spans"] = spans
    print(json.dumps(out, indent=2))
    return 0


def _read_kernel(pin: str) -> dict:
    """Aggregated kernel counters + blacklist size off a bpffs pin dir
    (shared by ``fsx status`` and ``fsx monitor``).  Layout derived
    from the same schema the C struct is generated from — field names
    AND types."""
    import struct as _struct

    from flowsentryx_tpu.bpf import blacklist, loader
    from flowsentryx_tpu.core import schema

    _STRUCT_CH = {"u64": "Q", "u32": "I", "u16": "H", "u8": "B"}
    names = [n for n, _ in schema.KERNEL_STATS_FIELDS]
    fmt = "<" + "".join(_STRUCT_CH[t] for _, t in
                        schema.KERNEL_STATS_FIELDS)
    vsize = _struct.calcsize(fmt)
    kern: dict = {}
    # try/finally around every map: fsx monitor calls this in an
    # unbounded loop, so an error path that skipped close() would leak
    # one fd per tick until EMFILE.
    m = None
    try:
        fd = loader.obj_get(f"{pin}/stats_map")
        m = loader.Map(fd, loader.MAP_TYPE_PERCPU_ARRAY, 4, vsize,
                       1, "stats_map")
        tot = [0] * len(names)
        for v in m.lookup_percpu(b"\x00\x00\x00\x00"):
            for i, x in enumerate(_struct.unpack(fmt, v)):
                tot[i] += x
        kern["stats"] = dict(zip(names, tot))
    except OSError as e:
        kern["stats"] = {"error": str(e)}
    finally:
        if m is not None:
            m.close()
    # v6 blocks live exclusively in the exact-match v6 map; a status
    # that counted only the folded map would report 0 while
    # dropped_blacklist climbs under a v6 flood.  Images predating the
    # v6 map simply have no pinned map: count 0.
    n = 0
    err = None
    for i, opener in enumerate((blacklist.open_map,
                                blacklist.open_v6_map)):
        bm = None
        try:
            bm = opener(pin)
            n += len(blacklist.entries(bm))
        except OSError as e:
            if i == 0:
                err = e
        finally:
            if bm is not None:
                bm.close()
    kern["blacklist_entries"] = n if err is None else {"error": str(err)}
    return kern


def _cmd_monitor(args: argparse.Namespace) -> int:
    """Periodic kernel-counter snapshots → JSONL + threshold alerts.

    The reference's "Reporting and Logging" line (README.md:146: store
    logs, generate alerts, maintain historical data).  Each tick
    appends one JSON line with absolute counters, per-second deltas,
    and the blacklist size; alert conditions print to stderr and are
    flagged in the record, so `fsx monitor --out history.jsonl` is both
    the log store and the alert source."""
    import time as _time

    if args.alert_degraded and not args.engine_report:
        print("fsx monitor: --alert-degraded requires --engine-report "
              "GLOB (health rides the engine reports; the kernel maps "
              "cannot carry it)", file=sys.stderr)
        return 1
    if args.alert_p99_us and not args.engine_report:
        # the latency alert is evaluated off the merged engine-report
        # block; without a report source it would silently never fire
        # — refuse up front, the fsx serve/cluster flag-pair idiom
        print("fsx monitor: --alert-p99-us requires --engine-report "
              "GLOB (the p99 comes from merged engine reports; the "
              "kernel maps cannot carry it)", file=sys.stderr)
        return 1
    if args.alert_prewarm_miss and not args.engine_report:
        print("fsx monitor: --alert-prewarm-miss requires "
              "--engine-report GLOB (the governor's pre-warm counters "
              "ride the engine reports; the kernel maps cannot carry "
              "them)", file=sys.stderr)
        return 1
    if args.alert_cold_boot and not args.engine_report:
        print("fsx monitor: --alert-cold-boot requires "
              "--engine-report GLOB (the compile-cache hit/miss story "
              "rides the engine reports' boot block; the kernel maps "
              "cannot carry it)", file=sys.stderr)
        return 1
    prev: dict | None = None
    prev_t = 0.0
    fh = open(args.out, "a") if args.out else None
    try:
        for tick in range(args.count) if args.count else iter(int, 1):
            t = _time.time()
            kern = _read_kernel(args.pin)
            rec: dict = {"ts": round(t, 3), "kernel": kern}
            stats = kern.get("stats", {})
            alerts = []
            if args.engine_report:
                # one read/parse pass per tick for both merges (this
                # loop is the monitoring hot path)
                reports = list(_iter_engine_reports(args.engine_report))
                lat = _merged_latency(args.engine_report,
                                      reports=reports)
                rec["latency"] = lat
                p99 = lat["seal_to_verdict_us"].get("p99", 0)
                if (args.alert_p99_us and p99
                        and p99 >= args.alert_p99_us):
                    alerts.append(
                        f"engine p99 latency {p99:.0f} us >= "
                        f"{args.alert_p99_us:.0f}")
                hl = _merged_engine_health(args.engine_report,
                                           reports=reports)
                rec["health"] = hl
                if (args.alert_degraded and hl["state"]
                        and hl["state"] != "healthy"):
                    reasons = sorted({
                        r for e in hl["reports"].values()
                        for r in e.get("reasons", [])})
                    # the elastic fleet's reshaping friction gets its
                    # own alert line (cluster/rebalance.py counters:
                    # refused handoff streams, discarded stages,
                    # suppressed autoscale plans...) so an operator
                    # can tell "serving is degraded" from "reshaping
                    # is degraded" without decoding reason prefixes
                    reshape = [r for r in reasons if r.startswith(
                        ("rebalance_", "elastic_"))]
                    steady = [r for r in reasons if r not in reshape]
                    if steady or not reshape:
                        alerts.append(
                            f"engine health {hl['state'].upper()}: "
                            + (", ".join(steady)
                               or "rank-level failure"))
                    if reshape:
                        alerts.append(
                            f"fleet reshaping {hl['state'].upper()}: "
                            + ", ".join(reshape))
                boot = _merged_boot(reports)
                if boot is not None:
                    rec["boot"] = boot
                    if args.alert_cold_boot:
                        # a rank whose boot block names a cache dir
                        # yet loaded ZERO variants from it paid the
                        # full ladder compile the cache exists to
                        # prevent — a wiped/mispointed cache dir or a
                        # silent toolchain drift, fleet-wide exactly
                        # after the upgrades that most need fast
                        # respawns
                        cold = sorted(
                            p for p, b in boot["per_report"].items()
                            if isinstance(b.get("cache"), dict)
                            and b["cache"].get("hits", 0) == 0)
                        if cold:
                            alerts.append(
                                "cold boot under a configured "
                                "compile cache (zero hits): "
                                + ", ".join(cold))
                predict = _merged_predict(reports)
                if predict is not None:
                    rec["predict"] = predict
                    misses = predict.get("prewarm_misses", 0)
                    if (args.alert_prewarm_miss
                            and misses >= args.alert_prewarm_miss):
                        alerts.append(
                            f"governor prewarm misses {misses} >= "
                            f"{args.alert_prewarm_miss} (forecast "
                            "pre-warmed rungs the traffic never "
                            "filled — compile/warm work wasted on a "
                            "stale or wrong burst model)")
            if prev is not None and "error" not in stats:
                dt = max(t - prev_t, 1e-9)
                rec["per_s"] = {
                    k: round((stats[k] - prev.get(k, 0)) / dt, 1)
                    for k in stats
                }
                drop_pps = (rec["per_s"].get("dropped_blacklist", 0)
                            + rec["per_s"].get("dropped_rate", 0)
                            + rec["per_s"].get("dropped_ml", 0)
                            + rec["per_s"].get("dropped_rule", 0))
                if args.alert_drop_pps and drop_pps >= args.alert_drop_pps:
                    alerts.append(f"drop rate {drop_pps:.0f} pps >= "
                                  f"{args.alert_drop_pps}")
            # absolute gauge: must fire even on a one-shot first tick
            nbl = kern.get("blacklist_entries", 0)
            if (args.alert_blacklist and isinstance(nbl, int)
                    and nbl >= args.alert_blacklist):
                alerts.append(f"blacklist size {nbl} >= "
                              f"{args.alert_blacklist}")
            if alerts:
                rec["alerts"] = alerts
                for a in alerts:
                    print(f"fsx monitor: ALERT {a}", file=sys.stderr)
            if "error" not in stats:
                prev, prev_t = stats, t
            line = json.dumps(rec)
            print(line)
            if fh:
                fh.write(line + "\n")
                fh.flush()
            if args.count and tick == args.count - 1:
                break
            _time.sleep(args.interval)
    except KeyboardInterrupt:
        pass
    finally:
        if fh:
            fh.close()
    return 0


def _cmd_top(args: argparse.Namespace) -> int:
    """Per-flow/per-IP kernel-table display.

    The reference planned this and never built it ("Read the data from
    the table and print it in a nice format", README.md:143-146); its
    per-IP state was ``struct ip_stats`` (fsx_struct.h:17-22).  Reads
    the pinned LRU maps directly via raw bpf(2) — works against a live
    ``fsxd --pin`` deployment with no daemon cooperation.  Flow keys
    are ``saddr ^ (dport << 16)``; the stored dst_port recovers saddr.

    IPv6 caveat: the kernel keys v6 flows by the 32-bit FOLD of the
    source address (the flow/limiter maps are fold-keyed by design;
    only the blacklist has an exact-v6 map), and a fold is not
    invertible — v6 rows therefore display their fold in dotted-quad
    form.  The ``ip`` column is the map key, not always a routable v4
    address."""
    import socket as _socket
    import struct as _struct

    from flowsentryx_tpu.bpf import blacklist, loader
    from flowsentryx_tpu.core import schema

    _CH = {"u64": "Q", "u32": "I", "u16": "H", "u8": "B"}
    fs_names = [n for n, _ in schema.FLOW_STATS_FIELDS]
    fs_fmt = "<" + "".join(_CH[t] for _, t in schema.FLOW_STATS_FIELDS)
    ip_names = [n for n, _ in schema.IP_STATE_FIELDS]
    ip_fmt = "<" + "".join(_CH[t] for _, t in schema.IP_STATE_FIELDS)

    # Both blacklist maps: v6 blocks live EXCLUSIVELY in the exact-v6
    # map (the _cmd_status pitfall); entries() keys exact-v6 rows by
    # their 32-bit fold, which is exactly how v6 flows key flow_stats.
    blocked: dict[int, float] = {}
    for opener in (blacklist.open_map, blacklist.open_v6_map):
        try:
            m = opener(args.pin)
            for e in blacklist.entries(m):
                blocked[e.key] = e.remaining_s
            m.close()
        except OSError:
            pass  # map not pinned (pre-attach / old image) — degrade

    rows = []
    try:
        fd = loader.obj_get(f"{args.pin}/flow_stats_map")
    except OSError as e:
        print(f"fsx top: no flow_stats_map pinned under {args.pin}: {e}",
              file=sys.stderr)
        return 1
    m = loader.Map(fd, loader.MAP_TYPE_LRU_HASH, 4,
                   _struct.calcsize(fs_fmt), 0, "flow_stats_map")
    for kb in m.keys():
        vb = m.lookup(kb)
        if vb is None:
            continue  # raced an LRU eviction
        (fkey,) = _struct.unpack("<I", kb)
        d = dict(zip(fs_names, _struct.unpack(fs_fmt, vb)))
        # dst_port is STORED host-order (fsx_kern.c:142 swaps the wire
        # value); the flow key XORed the NETWORK-order dport, so swap
        # back for saddr recovery and display the stored value as-is.
        dport_net = _socket.htons(d["dst_port"])
        saddr = fkey ^ ((dport_net << 16) & 0xFFFFFFFF)
        pkts = d["pkt_count"]
        dur_s = max(d["last_ts_ns"] - d["first_ts_ns"], 0) / 1e9
        rows.append({
            "ip": _socket.inet_ntoa(_struct.pack("<I", saddr)),
            "_saddr": saddr,
            "dport": d["dst_port"],
            "pkts": pkts,
            "bytes": d["byte_sum"],
            "len_mean": round(d["byte_sum"] / pkts, 1) if pkts else 0.0,
            "dur_s": round(dur_s, 3),
            "pps": round(pkts / dur_s, 1) if dur_s > 0 else float(pkts),
            "iat_mean_us": (round(d["iat_sum_ns"] / (pkts - 1) / 1e3, 1)
                            if pkts > 1 else 0.0),
            "iat_max_ms": round(d["iat_max_ns"] / 1e6, 3),
            "win_pps": 0,
            "win_bps": 0,
            "blocked_s": round(blocked.get(saddr, 0.0), 1),
        })
    m.close()
    rows.sort(key=lambda r: -r["pkts"])
    rows = rows[: args.n]

    # Limiter-window state ONLY for the displayed rows: ip_state_map is
    # sized FSX_MAX_TRACK_IPS (≈1M) and a full scan is ~2 bpf(2)
    # syscalls per entry — N point lookups, not a million-entry walk.
    try:
        fd = loader.obj_get(f"{args.pin}/ip_state_map")
        m = loader.Map(fd, loader.MAP_TYPE_LRU_HASH, 4,
                       _struct.calcsize(ip_fmt), 0, "ip_state_map")
        for r in rows:
            vb = m.lookup(_struct.pack("<I", r["_saddr"]))
            if vb is not None:
                st = dict(zip(ip_names, _struct.unpack(ip_fmt, vb)))
                r["win_pps"] = st["win_pps"]
                r["win_bps"] = st["win_bps"]
        m.close()
    except OSError:
        pass
    for r in rows:
        del r["_saddr"]
    if args.json:
        print(json.dumps({"flows": rows, "n_blocked": len(blocked)},
                         indent=2))
        return 0
    cols = ("ip", "dport", "pkts", "bytes", "len_mean", "dur_s", "pps",
            "iat_mean_us", "iat_max_ms", "win_pps", "blocked_s")
    widths = {c: max(len(c), *(len(str(r[c])) for r in rows), 1)
              for c in cols}
    print("  ".join(c.rjust(widths[c]) for c in cols))
    for r in rows:
        print("  ".join(str(r[c]).rjust(widths[c]) for c in cols))
    print(f"{len(rows)} flow(s) shown; {len(blocked)} source(s) "
          "blacklisted")
    return 0


def _cmd_pcap(args: argparse.Namespace) -> int:
    """Convert a capture to flow records (kernel-mirror parsing +
    streaming features).  The output file holds raw fsx_flow_record
    structs — consumable by ``fsxd --replay``, ``fsx serve --records``,
    and the training pipeline."""
    from flowsentryx_tpu.engine import pcap

    tracker = pcap.FlowTracker(emit_all=args.emit_all)
    rec = pcap.pcap_to_records(args.pcap, emit_all=args.emit_all,
                               limit=args.limit or None, tracker=tracker)
    Path(args.out).write_bytes(rec.tobytes())
    print(json.dumps({
        "packets_emitted": int(len(rec)),
        "flows": len(tracker.flows),  # (saddr, dport) flow keys
        "out": args.out,
        "bytes": len(rec) * rec.dtype.itemsize,
    }))
    return 0


def _cmd_train(args: argparse.Namespace) -> int:
    """Train a model and export the deployable artifact.

    ``--data`` globs CICIDS2017/CICDDoS2019 CSVs (model.py:53-66 path);
    without it, trains on the synthetic labeled set."""
    import numpy as np

    from flowsentryx_tpu.train import data, evaluate, qat

    _place_compile_cache()
    if args.epochs < 1:
        raise SystemExit("--epochs must be >= 1")
    # Recipe flags are family-specific: reject silently-ignored combos
    # (a user reproducing the MODEL_METRICS_r05 recipes must not get a
    # differently-trained artifact with exit code 0).
    if getattr(args, "slow_weight", 1.0) != 1.0 and args.model != "logreg_int8":
        raise SystemExit("--slow-weight applies to --model logreg_int8 only")
    if getattr(args, "augment_shift", 0) and args.model != "mlp":
        raise SystemExit("--augment-shift applies to --model mlp only")

    if args.model == "multiclass":
        # needs subtype labels — the calibrated fixture provides them
        # (CSV datasets are binary-labeled); handled before the generic
        # loader so no dataset is built just to be discarded.
        if args.data not in (None, "fixture"):
            raise SystemExit(
                "multiclass training needs subtype labels; use "
                "--data fixture (CSV datasets are binary-labeled)")
        from flowsentryx_tpu.models import multiclass
        from flowsentryx_tpu.train import fixture as fx

        n = args.synthetic if args.synthetic is not None else 200_000
        X, _, y_class = fx.cicids_fixture(n=n, seed=args.seed,
                                          return_classes=True)
        Xtr, Xte, ytr, yte = data.train_test_split(X, y_class)
        params, losses = qat.train_multiclass(
            Xtr, ytr, epochs=args.epochs, seed=args.seed)
        out = {
            "model": args.model, "train_n": len(Xtr), "test_n": len(Xte),
            "final_loss": float(losses[-1]),
            "test": evaluate.multiclass_report(params, Xte, yte),
        }
        if args.out:
            out["artifact"] = multiclass.save_params(params, args.out)
        print(json.dumps(out, indent=2))
        return 0

    y_class = None
    if args.data == "fixture":
        # the documented CICIDS-calibrated stand-in (train/fixture.py);
        # --synthetic sets its size (default: the real cleaned-set size)
        from flowsentryx_tpu.train import fixture

        n = args.synthetic if args.synthetic is not None else fixture.N_CLEANED
        X, y, y_class = fixture.cicids_fixture(n=n, seed=args.seed,
                                               return_classes=True)
    elif args.data:
        X, y = data.load_csvs(args.data)
    else:
        n = args.synthetic if args.synthetic is not None else 50_000
        X, y = data.synthetic_dataset(n, seed=args.seed)
    Xtr, Xte, ytr, yte = data.train_test_split(X, y)

    out: dict = {"model": args.model, "train_n": len(Xtr), "test_n": len(Xte)}
    if args.model == "logreg_int8":
        from flowsentryx_tpu.models import logreg

        sw = None
        if getattr(args, "slow_weight", 1.0) != 1.0:
            # slow-attack BCE upweight (train/stress.py train_binary
            # rationale): needs the fixture's subtype labels, split with
            # the same seed so the permutation aligns with (X, y)
            if y_class is None:
                raise SystemExit("--slow-weight needs --data fixture "
                                 "(CSV datasets carry no subtype labels)")
            from flowsentryx_tpu.train.fixture import CLASS_SLOW

            ctr, _cte, _, _ = data.train_test_split(y_class, y)
            sw = 1.0 + (ctr == CLASS_SLOW) * (args.slow_weight - 1.0)
        res = qat.train_logreg_qat(Xtr, ytr, epochs=args.epochs,
                                   sample_weight=sw)
        out["final_loss"] = float(res.losses[-1])
        out["test"] = evaluate.evaluate_model(
            logreg.classify_batch_int8_matmul, res.params, Xte, yte
        )
        if args.out:
            out["artifact"] = logreg.save_params(res.params, args.out)
    elif args.model == "mlp":
        from flowsentryx_tpu.models import mlp

        if getattr(args, "augment_shift", 0):
            # sweep-matched domain randomization (train/stress.py
            # shift_augment): the robust-detector training recipe
            from flowsentryx_tpu.train.stress import shift_augment

            rng = np.random.default_rng(args.seed)
            Xtr = np.concatenate(
                [Xtr] + [shift_augment(Xtr, rng)
                         for _ in range(args.augment_shift)])
            ytr = np.concatenate([ytr] * (args.augment_shift + 1))
        params, losses = qat.train_mlp(
            Xtr, ytr, epochs=args.epochs, seed=args.seed
        )
        out["final_loss"] = float(losses[-1])
        out["test"] = evaluate.evaluate_model(mlp.classify_batch, params, Xte, yte)
        if args.out:
            out["artifact"] = mlp.save_params(params, args.out)
    else:
        raise SystemExit(f"unknown trainable model {args.model!r}")
    print(json.dumps(out, indent=2))
    return 0


def _cmd_bench(args: argparse.Namespace) -> int:
    """Run the headline benchmark (delegates to bench.py), or the
    five-scenario BASELINE suite with --scenarios."""
    import subprocess
    import sys as _sys

    if args.scenarios or args.scaling:
        from flowsentryx_tpu.core import runtime

        runtime.require_platform("fsx bench")
        runtime.place_compile_cache()

    if args.scenarios:
        from flowsentryx_tpu import benchmarks

        for result in benchmarks.run_suite(
            scale=args.scale, names=args.only or None
        ):
            print(json.dumps(result), flush=True)
        return 0

    if args.scaling:
        from flowsentryx_tpu import benchmarks

        print(json.dumps(benchmarks.run_scaling()), flush=True)
        return 0

    if args.cluster:
        # the paced scale-out comparison (docs/CLUSTER.md §evidence):
        # persistent warmed engines, ABAB-interleaved sealed drains vs
        # a pre-cluster worktree, writing the "paced" half of
        # artifacts/CLUSTER_r14.json
        script = Path(__file__).resolve().parents[1] \
            / "scripts" / "cluster_bench.py"
        if not script.exists():
            print("fsx bench --cluster requires a source checkout "
                  f"(cluster_bench.py not found at {script})",
                  file=sys.stderr)
            return 1
        cmd = [_sys.executable, str(script),
               "--baseline-repo", args.baseline_repo]
        return subprocess.run(cmd, cwd=script.parents[1]).returncode

    bench = Path(__file__).resolve().parents[1] / "bench.py"
    if not bench.exists():
        print("fsx bench requires a source checkout (bench.py not found "
              f"at {bench})", file=sys.stderr)
        return 1
    cmd = [_sys.executable, str(bench)] + (["--smoke"] if args.smoke else [])
    return subprocess.run(cmd, cwd=bench.parent).returncode


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="fsx",
        description="flowsentryx-tpu: TPU-native DoS/DDoS mitigation framework",
    )
    sub = p.add_subparsers(dest="cmd", required=True)

    g = sub.add_parser("codegen", help="regenerate kern/fsx_schema.h from Python schemas")
    g.add_argument("--out", help="output path (default: kern/fsx_schema.h)")
    g.set_defaults(fn=_cmd_codegen)

    c = sub.add_parser("config", help="show or pack the active config")
    c.add_argument("--file", help="JSON config file (default: built-in defaults)")
    c.add_argument("--pack", action="store_true",
                   help="emit the binary kernel config-map blob to stdout")
    c.add_argument("--pin",
                   help="read (and with --set, live-update) the KERNEL "
                        "config map off this bpffs pin dir")
    c.add_argument("--set", action="append", metavar="FIELD=VALUE",
                   help="update a limiter field in the pinned kernel "
                        "config (repeatable; e.g. pps_threshold=5000, "
                        "window_s=2, limiter_kind=token); takes effect "
                        "on the next packet")
    c.set_defaults(fn=_cmd_config)

    v = sub.add_parser("version", help="print version")
    v.set_defaults(fn=_cmd_version)

    ck = sub.add_parser(
        "check",
        help="statically verify the BPF fast path + cross-layer "
             "schema contracts (no kernel needed)")
    ck.add_argument("--image", action="append", metavar="PATH",
                    help="also verify this sealed FSXPROG image "
                         "(repeatable)")
    ck.add_argument("--no-images", action="store_true",
                    help="skip the checked-in kern/build image "
                         "freshness contract")
    ck.add_argument("--budget", type=int, default=1_000_000,
                    help="verifier state budget per program (mirrors "
                         "the kernel's 1M-insn analysis cap)")
    ck.add_argument("--json", action="store_true",
                    help="machine-readable report")
    ck.set_defaults(fn=_cmd_check)

    au = sub.add_parser(
        "audit",
        help="statically audit the staged TPU step graphs: dtypes, "
             "donation aliasing, D2H transfer budget, retrace "
             "stability, collectives (no batch executed)")
    au.add_argument("--config", help="JSON config file")
    au.add_argument("--verdict-k", type=int, default=None,
                    help="audit with this compact-wire K (>= 1; "
                         "default: config batch.verdict_k)")
    au.add_argument("--mesh", type=int, default=0,
                    help="stage the sharded variant over an N-device "
                         "mesh (0 = auto: every visible device when "
                         "they form a power-of-two mesh > 1)")
    au.add_argument("--mega", type=_mega_arg, default=2,
                    help="chunk count for the staged megastep variant, "
                         "or 'auto' to audit every rung of the "
                         "adaptive power-of-two ladder (one staged "
                         "artifact per group size)")
    au.add_argument("--evict-ttl", type=float, default=0.0,
                    metavar="S",
                    help="also prove the eviction-epoch step variants: "
                         "stage every graph with the in-step aging "
                         "sweep enabled at this idle TTL (0 = the "
                         "sweepless graphs, the default)")
    au.add_argument("--evict-every", type=int, default=64, metavar="N",
                    help="sweep epoch period in batches for "
                         "--evict-ttl (default 64)")
    au.add_argument("--quick", action="store_true",
                    help="small table/batch shapes (CI gate); the "
                         "contracts are shape-generic, only the "
                         "recorded byte budgets shrink")
    au.add_argument("--json", action="store_true",
                    help="machine-readable report")
    au.add_argument("--out", metavar="PATH",
                    help="also write the JSON report here (the "
                         "artifacts/AUDIT_*.json evidence file)")
    au.set_defaults(fn=_cmd_audit)

    sy = sub.add_parser(
        "sync",
        help="statically verify the host concurrency plane: thread "
             "contracts over the real source + bounded-interleaving "
             "model checks of the real protocol objects (jax-free)")
    sy.add_argument("--quick", action="store_true",
                    help="thread-contract lint only (milliseconds; "
                         "what the sync_contracts lint stage runs) — "
                         "skip the interleaving model checker")
    sy.add_argument("--json", action="store_true",
                    help="machine-readable report")
    sy.add_argument("--out", metavar="PATH",
                    help="also write the JSON report here (the "
                         "artifacts/SYNC_*.json evidence file)")
    sy.set_defaults(fn=_cmd_sync)

    cr = sub.add_parser(
        "crash",
        help="crash-consistency model checking: run the real "
             "durable-state protocols (handoff, adoption, layout "
             "flip, checkpoint rotation) over a simulated fs with "
             "honest POSIX semantics, crash every atomic step, and "
             "assert the invariant catalog (jax-free; the fifth "
             "static leg)")
    cr.add_argument("--quick", action="store_true",
                    help="trim the torn-file fan-out per crash point "
                         "(same crash points and protocols; what the "
                         "tier-1 gate runs)")
    cr.add_argument("--json", action="store_true",
                    help="machine-readable report")
    cr.add_argument("--out", metavar="PATH",
                    help="also write the JSON report here (the "
                         "artifacts/CRASH_*.json evidence file)")
    cr.add_argument("--quiet-plants", action="store_true",
                    help="suppress the planted regressions' printed "
                         "crash schedules (kept in the JSON report)")
    cr.set_defaults(fn=_cmd_crash)

    lv = sub.add_parser(
        "live",
        help="liveness & progress model checking: state-graph search "
             "over the real protocol objects proving deadlock-"
             "freedom, livelock-freedom under weak fairness and "
             "bounded starvation, plus the PROGRESS registry audit "
             "of every blocking loop (jax-free; the sixth static "
             "leg)")
    lv.add_argument("--quick", action="store_true",
                    help="trim the handoff drop-edge fan-out (same "
                         "protocols and plants; what the tier-1 gate "
                         "runs)")
    lv.add_argument("--json", action="store_true",
                    help="machine-readable report")
    lv.add_argument("--out", metavar="PATH",
                    help="also write the JSON report here (the "
                         "artifacts/LIVE_*.json evidence file)")
    lv.add_argument("--quiet-plants", action="store_true",
                    help="suppress the planted regressions' printed "
                         "catching schedules (kept in the JSON "
                         "report)")
    lv.set_defaults(fn=_cmd_live)

    rg = sub.add_parser(
        "ranges",
        help="statically prove no staged step variant can silently "
             "wrap a fixed-width integer (interval abstract "
             "interpretation over the jaxprs; the fourth static leg)")
    rg.add_argument("--config", help="JSON config file")
    rg.add_argument("--mesh", type=int, default=0,
                    help="stage the sharded variants over an N-device "
                         "mesh (0 = auto, as fsx audit)")
    rg.add_argument("--mega", type=_mega_arg, default=2,
                    help="megastep chunk count, or 'auto' for every "
                         "rung of the adaptive ladder")
    rg.add_argument("--evict-ttl", type=float, default=0.0,
                    metavar="S",
                    help="prove the eviction-epoch variants (the "
                         "batches-counter window arithmetic stages "
                         "only when eviction is on)")
    rg.add_argument("--evict-every", type=int, default=64, metavar="N",
                    help="sweep epoch period for --evict-ttl "
                         "(default 64)")
    rg.add_argument("--quick", action="store_true",
                    help="small table/batch shapes (CI gate); the "
                         "interval contracts are shape-generic")
    rg.add_argument("--artifact",
                    default="artifacts/logreg_int8.npz",
                    help="distill artifact for the BPF<->jaxpr "
                         "containment bridge (skipped with a note "
                         "when absent; pass '' to disable)")
    rg.add_argument("--json", action="store_true",
                    help="machine-readable report")
    rg.add_argument("--out", metavar="PATH",
                    help="also write the JSON report here (the "
                         "artifacts/RANGES_*.json evidence file)")
    rg.set_defaults(fn=_cmd_ranges)

    ch = sub.add_parser(
        "chaos",
        help="deterministic fault-injection campaign over the real "
             "stack: kills, crash loops, corrupt checkpoints, shm "
             "slot corruption, poisoned batches, gossip floods, "
             "clock jumps, a wedged sink — judged by named "
             "invariants, with planted regressions as negative "
             "controls (docs/CHAOS.md)")
    ch.add_argument("--seed", type=int, default=17,
                    help="campaign seed: fixes traffic, corruption "
                         "offsets and kill schedule (default 17)")
    ch.add_argument("--quick", action="store_true",
                    help="trim traffic volume, keep full fault-class "
                         "and plant coverage (the tier-1 smoke shape)")
    ch.add_argument("--workdir", metavar="DIR",
                    help="scratch dir for rings/checkpoints/"
                         "quarantine spools (default: a fresh tempdir)")
    ch.add_argument("--list", action="store_true",
                    help="print the fault registry and exit")
    ch.add_argument("--json", action="store_true",
                    help="machine-readable report")
    ch.add_argument("--out", metavar="PATH",
                    help="also write the JSON report here (the "
                         "artifacts/CHAOS_*.json evidence file)")
    ch.set_defaults(fn=_cmd_chaos)

    # Mirrors bpf.blacklist.DEFAULT_PIN_DIR; kept inline so parser
    # construction never imports the bpf loader (lazy-import rule).
    DEFAULT_PIN_DIR = "/sys/fs/bpf/fsx"

    di = sub.add_parser(
        "distill",
        help="compile a trained int8 artifact into the kernel XDP tier "
             "(two-tier escalation; docs/DISTILL.md)")
    di.add_argument("artifact",
                    help="trained model artifact (.npz), e.g. "
                         "artifacts/logreg_int8.npz")
    di.add_argument("--model", default="logreg_int8",
                    help="model family the artifact was trained as "
                         "(must be distillable; default logreg_int8)")
    di.add_argument("--thresholds", default="0.1,0.9", metavar="LO,HI",
                    help="escalation band edges in probability space: "
                         "score<LO passes in-kernel (emit suppressed), "
                         "score>HI drops in-kernel (blacklist), the "
                         "band between escalates to the TPU tier "
                         "(default 0.1,0.9)")
    di.add_argument("--out", metavar="PLAN.npz",
                    help="write the compiled plan here (consumed by "
                         "fsx serve --sim-kernel-tier and --pin runs)")
    di.add_argument("--blob", metavar="PATH",
                    help="write the raw ml_model_map value bytes "
                         "(struct fsx_ml_model) here")
    di.add_argument("--check", action="store_true",
                    help="statically verify both --ml program variants "
                         "(bpf/verifier.py) + the scorer's schema "
                         "contracts + a blob pack/unpack roundtrip")
    di.add_argument("--emulate", action="store_true",
                    help="prove JAX<->BPF verdict parity: execute the "
                         "emitted scorer bytecode (SIMD emulator) over "
                         "CICIDS-shaped + saturation-edge vectors and "
                         "require bit-exact band agreement with the "
                         "served int8 lane")
    di.add_argument("--emulate-n", type=int, default=10000,
                    help="parity corpus size (default 10000)")
    di.add_argument("--report", metavar="PATH",
                    help="also write the JSON report here (the "
                         "artifacts/DISTILL_*.json evidence file)")
    di.add_argument("--pin",
                    help="push the blob into the ml_model_map pinned "
                         "under this bpffs dir (LIVE hot-swap: the "
                         "attached --ml program bands with the new "
                         "model on the next packet)")
    di.add_argument("--json", action="store_true",
                    help="machine-readable report")
    di.set_defaults(fn=_cmd_distill)

    blk = sub.add_parser("block", help="manually blacklist a source IP")
    blk.add_argument("ip", help="IPv4 or IPv6 address")
    blk.add_argument("--ttl", type=float, default=10.0,
                     help="seconds until expiry (default 10, as the "
                          "kernel's rate-limit blocks)")
    blk.add_argument("--pin", default=DEFAULT_PIN_DIR,
                     help=f"bpffs pin dir (default {DEFAULT_PIN_DIR})")
    blk.set_defaults(fn=_cmd_block)

    ublk = sub.add_parser("unblock", help="remove a source from the blacklist")
    ublk.add_argument("ip")
    ublk.add_argument("--pin", default=DEFAULT_PIN_DIR)
    ublk.set_defaults(fn=_cmd_unblock)

    bl = sub.add_parser("blacklist", help="show or clear the live blacklist")
    bl.add_argument("--pin", default=DEFAULT_PIN_DIR)
    bl.add_argument("--json", action="store_true")
    bl.add_argument("--clear", action="store_true",
                    help="delete every entry")
    bl.set_defaults(fn=_cmd_blacklist)

    ru = sub.add_parser("rules",
                        help="list/add/remove stateless firewall rules")
    ru.add_argument("--pin", default=DEFAULT_PIN_DIR)
    ru.add_argument("--json", action="store_true")
    ru.add_argument("--add", metavar="PROTO:DPORT",
                    help="insert a drop rule (proto any/tcp/udp/icmp[v6]"
                         "/number; dport 0 = any)")
    ru.add_argument("--remove", metavar="PROTO:DPORT")
    ru.set_defaults(fn=_cmd_rules)

    s = sub.add_parser("serve", help="run the serving engine")
    s.add_argument("--config", help="JSON config file")
    s.add_argument("--artifact",
                   help="trained model artifact (.npz) to serve; default is "
                        "the embedded golden params — the REFERENCE's "
                        "artifact, a near-constant benign predictor (see "
                        "MODEL_METRICS.json); serve "
                        "artifacts/logreg_int8.npz for a working detector")
    s.add_argument("--feature-ring", help="daemon shm feature ring path")
    s.add_argument("--verdict-ring", help="daemon shm verdict ring path")
    s.add_argument("--ingest-workers", type=int, default=0,
                   help="drain the feature ring with N parallel worker "
                        "processes that hand the engine sealed batches "
                        "(pair with fsxd --shards N; N=1 fronts an "
                        "unsharded daemon; 0 = the inline single-"
                        "threaded drain, bit-identical to pre-ingest "
                        "engines)")
    s.add_argument("--strict-ingest", action="store_true",
                   help="surface an ingest-worker crash as the same "
                        "loud RuntimeError the engine's sink/pipeline "
                        "workers die with (after the corpse's queue "
                        "drains), instead of the default per-shard "
                        "fail-open posture")
    s.add_argument("--records",
                   help="replay a raw fsx_flow_record file (fsx pcap output)")
    s.add_argument("--scenario", default="syn_benign_mix",
                   help="synthetic scenario when no ring is given")
    s.add_argument("--rate", type=float, default=1e6, help="synthetic pps")
    s.add_argument("--packets", type=int, default=0, help="stop after N records")
    s.add_argument("--batches", type=int, default=0, help="stop after N batches")
    s.add_argument("--seconds", type=float, default=0, help="stop after S seconds")
    s.add_argument("--mesh", type=int, default=0,
                   help="sets table.shards = N: the table's rows split "
                        "by IP hash over an N-device mesh and the "
                        "sharded step serves it (N>1; 0 = the config "
                        "file's table.shards)")
    s.add_argument("--mega", type=_mega_arg, default=0,
                   help="group N backlogged batches into one lax.scan "
                        "dispatch (amortizes per-dispatch cost at "
                        "high rates; compact16 wire; "
                        "composes with --mesh via the sharded mega-step)."
                        " 'auto' = adaptive coalescing: stage every "
                        "power-of-two group size up to 8 and dispatch "
                        "the largest the instantaneous backlog fills, "
                        "so partial backlogs amortize too")
    s.add_argument("--compile-cache", metavar="DIR",
                   help="persistent AOT executable store: staged "
                        "variants (singles and each --mega rung) "
                        "serialize here on first boot and later "
                        "boots of the same staged shape + toolchain "
                        "load them in milliseconds instead of "
                        "recompiling — sub-second boot-to-serving. "
                        "Fail-open: any miss/drift/corrupt entry "
                        "recompiles, counted in the report's boot "
                        "block (fsx monitor --alert-cold-boot)")
    s.add_argument("--tiered-warm", action="store_true",
                   help="open serving on the top-rung tier (singles + "
                        "largest --mega rung) and fill the remaining "
                        "rungs from a background thread — "
                        "byte-identical verdicts throughout (unready "
                        "rungs degrade to top-rung flushes); pair "
                        "with --compile-cache for the sub-second "
                        "cached boot (requires --mega)")
    s.add_argument("--cluster-rank", metavar="R/N", default=None,
                   help="serve as engine R of an N-engine cluster "
                        "(docs/CLUSTER.md): own ring shards "
                        "[R*W, (R+1)*W) of the daemon's N*W-shard "
                        "fan-out end-to-end (W = --ingest-workers) "
                        "and gossip verdicts with the peers; requires "
                        "--ingest-workers and --cluster-dir (fsx "
                        "cluster is the supervised form)")
    s.add_argument("--cluster-dir", default=None,
                   help="cluster gossip/status plane directory "
                        "(created by fsx cluster before any engine "
                        "boots)")
    s.add_argument("--table-capacity", type=int, default=None,
                   metavar="N",
                   help="flow-table rows (overrides config "
                        "table.capacity; default 2^20): power of two, "
                        ">= max_batch, divisible by table.shards — validated "
                        "with clear refusals BEFORE the JAX boot. "
                        "Production scale is 2^22 (4M) and up; rows "
                        "shard by IP hash across table.shards devices")
    s.add_argument("--artifact-reload", action="store_true",
                   help="watch --artifact's mtime and hot-swap the "
                        "model live when the file changes — no drain, "
                        "no recompile, in-flight rounds finish on the "
                        "old model (requires the same artifact "
                        "family/shape; a bad push is announced and "
                        "serving continues on the incumbent)")
    s.add_argument("--checkpoint", help="save table+stats here on exit")
    s.add_argument("--checkpoint-every", type=float, default=0,
                   help="ALSO checkpoint every S seconds while serving "
                        "(crash loses at most one interval; requires "
                        "--checkpoint)")
    s.add_argument("--profile",
                   help="write a jax.profiler trace to this directory")
    s.add_argument("--restore", help="resume from a checkpoint file")
    s.add_argument("--verdict-k", type=int, default=None,
                   help="compact verdict-wire slots per batch (overrides "
                        "config batch.verdict_k; default 64): the step "
                        "compacts newly-blocked flows into a K-slot D2H "
                        "buffer, falling back to the full [B] fetch only "
                        "on overflow; 0 = disable compaction (full fetch "
                        "every batch)")
    s.add_argument("--sim-kernel-tier", metavar="PLAN",
                   help="simulate the distilled kernel tier in front of "
                        "the engine with this fsx-distill plan (.npz): "
                        "confident-attack records drop (plus a "
                        "simulated blacklist TTL), confident-benign "
                        "records are suppressed, only the uncertain "
                        "band reaches the TPU step; per-band counters "
                        "land in the report's escalation block. Record "
                        "path only (no --ingest-workers / compact-emit "
                        "ring); rootless stand-in for fsx distill --pin")
    s.add_argument("--audit", action="store_true",
                   help="statically audit the serving step's graph "
                        "contracts (dtypes/donation/transfer/retrace/"
                        "collectives) at boot and refuse to serve on a "
                        "violation; also on via FSX_AUDIT=1 (fsx audit "
                        "is the standalone form)")
    s.add_argument("--slo-us", type=int, default=0, metavar="N",
                   help="latency-budget serving mode: bound the "
                        "feature->verdict path at N µs — the oldest "
                        "staged record's age caps coalescing (rungs "
                        "whose warm-measured EWMA step time would "
                        "breach the budget are skipped), the device-"
                        "loop round sizer stops waiting for full "
                        "rings, and the batcher deadline-flush fires "
                        "at the budget — so under pulse load the "
                        "engine degrades to smaller groups/singles "
                        "instead of queueing.  0 (default) is the "
                        "throughput-tuned engine, bit-identical to "
                        "prior releases.  The report's latency block "
                        "carries p50/p90/p99/p999 and budget-miss "
                        "accounting either way")
    s.add_argument("--predict", action="store_true",
                   help="predictive dispatch governor (requires "
                        "--slo-us > 0): an online burst forecaster "
                        "over per-record arrival stamps drives "
                        "proactive rung pre-warming before each "
                        "predicted burst onset, burst-end early "
                        "flushes inside the budget, and anti-entropy "
                        "deferral under budget pressure.  Confidence-"
                        "gated: on aperiodic traffic the governor "
                        "stays quiescent and the engine behaves "
                        "exactly like plain --slo-us.  Forecast + "
                        "actuation counters land in the report's "
                        "predict block (fsx status/monitor surface "
                        "them; fsx monitor --alert-prewarm-miss "
                        "alerts on wasted pre-warms)")
    s.add_argument("--quarantine-dir", metavar="DIR",
                   help="spool refused sealed batches (RANGE_* "
                        "contract violations) here for post-mortem; "
                        "default: count-only quarantine (they are "
                        "never dispatched either way; docs/CHAOS.md)")
    s.add_argument("--watchdog-s", type=float, default=None,
                   metavar="S",
                   help="dispatch-watchdog stall bound: batches in "
                        "flight with zero completions for S seconds "
                        "dump per-thread stacks (soft trip), for 2xS "
                        "fail the drain loudly (default: sync/tuning "
                        "WATCHDOG_STALL_S; 0 disables)")
    s.add_argument("--no-sink-thread", action="store_true",
                   help="run the verdict sink on the dispatch thread "
                        "(the pre-threaded single-loop engine). Default "
                        "auto: a dedicated sink thread — so fetch/"
                        "writeback/metrics never block dispatch — on "
                        "hosts with >=3 cores, single-thread below that "
                        "(the extra thread would only contend)")
    s.set_defaults(fn=_cmd_serve)

    cl = sub.add_parser(
        "cluster",
        help="coordinator-less multi-engine scale-out: N supervised "
             "engine processes, each owning an IP-space shard "
             "end-to-end, sharing only the gossip blacklist plane "
             "(docs/CLUSTER.md)")
    cl.add_argument("--engines", type=int, default=2, metavar="N",
                    help="engine processes (>= 2; each owns "
                         "shards/engines ring shards end-to-end)")
    cl.add_argument("--shards", type=int, default=2,
                    help="TOTAL daemon ring shards (fsxd --shards "
                         "value); must be a multiple of --engines")
    cl.add_argument("--config", help="JSON config file (shared)")
    cl.add_argument("--feature-ring", default="/tmp/fsx_feature_ring",
                    help="daemon shm feature-ring base path")
    cl.add_argument("--verdict-ring", default=None,
                    help="verdict-ring base path: engine r produces "
                         "BASE.r<r> (pair with fsxd --verdict-shards "
                         "N); omit for NullSink engines (bench)")
    cl.add_argument("--cluster-dir", default=None,
                    help="gossip/status plane directory (default: "
                         "<feature-ring>.cluster)")
    cl.add_argument("--artifact",
                    help="trained model artifact (.npz), served by "
                         "every engine")
    cl.add_argument("--mega", type=_mega_arg, default=0,
                    help="per-engine coalescing ladder (fsx serve "
                         "--mega)")
    cl.add_argument("--compile-cache", metavar="DIR",
                    help="per-fleet persistent AOT executable store "
                         "(fsx serve --compile-cache; every rank "
                         "shares DIR — same staged shape, same "
                         "entries).  With --elastic the supervisor "
                         "additionally spawns a one-shot pre-warm "
                         "child at boot so a GROW spare's warm() is "
                         "pure cache hits")
    cl.add_argument("--tiered-warm", action="store_true",
                    help="per-engine tiered warm (fsx serve "
                         "--tiered-warm): SERVING opens on the "
                         "top-rung tier, a background thread fills "
                         "the rest of the ladder; requires --mega")
    cl.add_argument("--verdict-k", type=int, default=None,
                    help="compact verdict-wire slots (fsx serve "
                         "--verdict-k)")
    cl.add_argument("--table-capacity", type=int, default=None,
                    metavar="N",
                    help="PER-ENGINE flow-table rows (validated "
                         "pre-boot, same refusal list as fsx serve)")
    cl.add_argument("--seconds", type=float, default=0,
                    help="serve for S seconds, then stop-drain every "
                         "engine (0 = until ^C)")
    cl.add_argument("--checkpoint", metavar="TEMPLATE",
                    help="per-engine checkpoint path template; MUST "
                         "contain {rank} (restarts restore from it)")
    cl.add_argument("--checkpoint-every", type=float, default=0,
                    help="checkpoint every S seconds while serving "
                         "(requires --checkpoint)")
    cl.add_argument("--max-restarts", type=int, default=2,
                    help="crash-restarts per rank before the rank is "
                         "declared failed (default 2)")
    cl.add_argument("--slo-us", type=int, default=0, metavar="N",
                    help="per-engine latency budget (fsx serve "
                         "--slo-us); the aggregate report merges every "
                         "rank's latency histogram")
    cl.add_argument("--predict", action="store_true",
                    help="per-engine predictive dispatch governor "
                         "(fsx serve --predict; requires --slo-us); "
                         "each rank forecasts its OWN shard's arrival "
                         "process, and the aggregate report folds "
                         "every rank's predict counters")
    cl.add_argument("--hosts", default=None, metavar="IP:PORT,...",
                    help="multi-host fleet: every host's gossip base "
                         "address, same list on every host (the "
                         "supervisor's federation beacon binds the "
                         "base port, engine r binds PORT+1+r; verdict "
                         "wires gossip over UDP with epoch rebase — "
                         "docs/CLUSTER.md §multi-host)")
    cl.add_argument("--host-id", type=int, default=None, metavar="I",
                    help="this host's index into --hosts (required "
                         "with --hosts)")
    cl.add_argument("--gossip-listen", default=None, metavar="IP:PORT",
                    help="local bind override for this host's --hosts "
                         "entry (e.g. 0.0.0.0:9000 behind NAT); "
                         "default: bind the --hosts[--host-id] "
                         "address itself")
    cl.add_argument("--pin-cores", choices=("auto", "on", "off"),
                    default="auto",
                    help="pin rank r to core r with a matching "
                         "1-thread XLA pool (auto: only when the "
                         "fleet fits the host's cores; the per-core "
                         "deployment shape, docs/CLUSTER.md)")
    cl.add_argument("--elastic", action="store_true",
                    help="self-reshaping fleet: provision the plane "
                         "at --max-engines, boot --engines of them "
                         "live, and let the autoscaler grow/shrink/"
                         "rebalance via live shard handoffs "
                         "(hysteresis + cooldown; every decision "
                         "logged with its signal vector — "
                         "docs/CLUSTER.md §elastic)")
    cl.add_argument("--min-engines", type=int, default=None,
                    metavar="N",
                    help="autoscaler floor: never shrink the live "
                         "set below N engines (requires --elastic; "
                         "default 1)")
    cl.add_argument("--max-engines", type=int, default=None,
                    metavar="N",
                    help="autoscaler ceiling AND the provisioned "
                         "plane size: rings/status blocks/mailboxes "
                         "for N ranks exist from boot so growth is "
                         "spawn-only (requires --elastic; default "
                         "--engines + 1; --shards must divide by it)")
    cl.add_argument("--adopt", action="store_true",
                    help="re-attach to a LIVE plane instead of "
                         "refusing it: census the ranks from their "
                         "status blocks (serving ranks keep serving "
                         "un-respawned; dead ranks respawn; their "
                         "spans can be adopted by survivors via "
                         "checkpoint-sourced handoffs — docs/"
                         "CLUSTER.md §elastic)")
    cl.set_defaults(fn=_cmd_cluster)

    tp = sub.add_parser("top", help="per-IP kernel table, formatted")
    tp.add_argument("--pin", default="/sys/fs/bpf/fsx",
                    help="bpffs pin dir of a live fsxd deployment")
    tp.add_argument("-n", type=int, default=20, help="show top N flows")
    tp.add_argument("--json", action="store_true")
    tp.set_defaults(fn=_cmd_top)

    mo = sub.add_parser("monitor",
                        help="periodic kernel snapshots -> JSONL + alerts")
    mo.add_argument("--pin", default="/sys/fs/bpf/fsx",
                    help="bpffs pin dir of a live fsxd deployment")
    mo.add_argument("--interval", type=float, default=2.0,
                    help="seconds between snapshots")
    mo.add_argument("--count", type=int, default=0,
                    help="stop after N snapshots (0 = run until ^C)")
    mo.add_argument("--out", help="append JSONL history to this file")
    mo.add_argument("--alert-drop-pps", type=float, default=0,
                    help="alert when total drop rate reaches N pps")
    mo.add_argument("--alert-blacklist", type=int, default=0,
                    help="alert when blacklist size reaches N sources")
    mo.add_argument("--engine-report", action="append", default=None,
                    metavar="GLOB",
                    help="also merge engine-report JSONs matching this "
                         "glob each tick (fsx serve output, or a "
                         "cluster dir's report_r*_g*.json) into one "
                         "seal->verdict latency block; repeatable")
    mo.add_argument("--alert-p99-us", type=float, default=0,
                    help="alert when the merged engine p99 "
                         "seal->verdict latency reaches N µs "
                         "(requires --engine-report)")
    mo.add_argument("--alert-degraded", action="store_true",
                    help="alert when any merged engine report's "
                         "health ladder reads DEGRADED or FAILED, "
                         "naming the reasons; rebalance_*/elastic_* "
                         "reshaping reasons get their own alert line "
                         "(requires --engine-report; docs/CHAOS.md "
                         "§health, docs/CLUSTER.md §elastic)")
    mo.add_argument("--alert-prewarm-miss", type=int, default=0,
                    metavar="N",
                    help="alert when the merged governor prewarm-miss "
                         "count reaches N (pre-warmed rungs the "
                         "traffic never filled — a stale or wrong "
                         "burst model burning compile/warm work; "
                         "requires --engine-report; "
                         "docs/ENGINE.md §prediction)")
    mo.add_argument("--alert-cold-boot", action="store_true",
                    help="alert when a rank's boot block names a "
                         "compile-cache dir yet loaded ZERO variants "
                         "from it (the full ladder recompile the "
                         "cache exists to prevent — a wiped or "
                         "mispointed cache dir, or silent toolchain "
                         "drift after an upgrade); requires "
                         "--engine-report; docs/ENGINE.md §boot)")
    mo.set_defaults(fn=_cmd_monitor)

    st = sub.add_parser("status", help="inspect the shm transport")
    st.add_argument("--feature-ring", default="/tmp/fsx_feature_ring")
    st.add_argument("--verdict-ring", default="/tmp/fsx_verdict_ring")
    st.add_argument("--pin",
                    help="also read kernel stats/blacklist off this "
                         "bpffs pin dir (e.g. /sys/fs/bpf/fsx)")
    st.add_argument("--engine-report", action="append", default=None,
                    metavar="GLOB",
                    help="also merge engine-report JSONs matching this "
                         "glob (fsx serve output, or a cluster dir's "
                         "report_r*_g*.json) into one seal->verdict "
                         "latency block (HDR bucket merge; "
                         "repeatable) plus the health ladder with "
                         "per-rank and summed handoff/adoption "
                         "counters (docs/CLUSTER.md §elastic)")
    st.set_defaults(fn=_cmd_status)

    pc = sub.add_parser("pcap", help="convert a capture to flow records")
    pc.add_argument("pcap", help="classic-pcap capture file")
    pc.add_argument("out", help="output file (raw fsx_flow_record structs)")
    pc.add_argument("--emit-all", action="store_true",
                    help="emit every packet (default: kernel gating — "
                         "every packet while young, then every 16th)")
    pc.add_argument("--limit", type=int, default=0,
                    help="stop after N emitted records")
    pc.set_defaults(fn=_cmd_pcap)

    t = sub.add_parser("train", help="train a model, export the artifact")
    t.add_argument("--model", default="logreg_int8",
                   choices=["logreg_int8", "mlp", "multiclass"])
    t.add_argument("--data",
                   help="CSV glob (CICIDS2017/CICDDoS2019 format), or "
                        "'fixture' for the CICIDS-calibrated stand-in")
    t.add_argument("--synthetic", type=int, default=None,
                   help="dataset size for synthetic/fixture data "
                        "(default 50000 synthetic; full 2.52M fixture; "
                        "200000 for multiclass)")
    t.add_argument("--epochs", type=int, default=200)
    t.add_argument("--seed", type=int, default=0)
    t.add_argument("--slow-weight", type=float, default=1.0,
                   dest="slow_weight",
                   help="BCE upweight for slow-attack rows (fixture "
                        "data only; x4 is the deployed default's "
                        "training recipe — see MODEL_METRICS_r05)")
    t.add_argument("--augment-shift", type=int, default=0,
                   dest="augment_shift",
                   help="add N domain-randomized training copies "
                        "(stress.shift_augment; 2 is the robust-MLP "
                        "recipe — see MODEL_METRICS_r05)")
    t.add_argument("--out", help="artifact output path (.npz)")
    t.set_defaults(fn=_cmd_train)

    b = sub.add_parser("bench", help="run the headline benchmark")
    b.add_argument("--smoke", action="store_true",
                   help="small shapes, CPU-friendly")
    b.add_argument("--scenarios", action="store_true",
                   help="run the five BASELINE configs instead")
    b.add_argument("--scale", type=float, default=1.0,
                   help="packet-count multiplier for --scenarios")
    b.add_argument("--only", action="append",
                   help="substring filter on scenario names (repeatable)")
    b.add_argument("--scaling", action="store_true",
                   help="step-time vs 1/2/4/8-device mesh at 1M-row capacity")
    b.add_argument("--cluster", action="store_true",
                   help="paced 2-engine-vs-single scaling comparison "
                        "(scripts/cluster_bench.py; interleaved "
                        "sealed-drain trials, writes the paced half of "
                        "artifacts/CLUSTER_r14.json)")
    b.add_argument("--baseline-repo", default="/tmp/fsx_pr9_worktree",
                   help="pre-cluster checkout the --cluster baseline "
                        "engine runs from (git worktree add it first)")
    b.set_defaults(fn=_cmd_bench)

    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    try:
        return args.fn(args)
    except BrokenPipeError:
        # stdout went away (e.g. piped to `head`); standard CLI etiquette.
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        return 0


if __name__ == "__main__":
    sys.exit(main())
