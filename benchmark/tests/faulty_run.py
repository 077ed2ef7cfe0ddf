#!/usr/bin/env python3
"""A benchmark run with the timed path broken underneath (tests only).

    python3 benchmark/tests/faulty_run.py <fault> <run.py arguments...>

Plants one fault in the PROGRAM's side of the run — never in the taps or
the reference — and then drives ``run.py`` as it is.  ``correct`` has to
come out false.  Faults:

* ``state_unchanged`` — every step hands back an empty table: the state a
  step returns is the state the first step was given;
* ``half_batch``     — every dispatched batch is cut to half its records
  (the wire's record count halved on its way to the device);
* ``answer_altered`` — one block of every sunk group has its source
  address altered where the sink section extracts it;
* ``ring_block_lost`` — the verdict ring's writer leaves out the first block
  of every update it is handed (ring cells only): what the engine decided
  and the sink tap saw never lands in the ring;
* ``none``           — no fault: the same run has to come out correct.
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def plant(fault: str) -> None:
    import jax.numpy as jnp

    from benchmark import harness

    build = harness.build_engine

    def wrap_steps(eng, around):
        eng.step = around(eng.step)
        eng.megasteps = {n: around(f) for n, f in eng.megasteps.items()}
        if eng.megastep is not None:
            eng.megastep = eng.megasteps[max(eng.megasteps)]

    def state_unchanged(step):
        def f(table, stats, params, raw):
            table, stats, out = step(table, stats, params, raw)
            return type(table)(*(jnp.zeros_like(a) for a in table)), \
                stats, out
        return f

    def half_batch(step):
        def f(table, stats, params, raw):
            raw = jnp.asarray(raw)
            raw = raw.at[..., -1, 0].set(raw[..., -1, 0] // 2)
            return step(table, stats, params, raw)
        return f

    def broken(*a, **kw):
        eng = build(*a, **kw)
        if fault == "state_unchanged":
            wrap_steps(eng, state_unchanged)
        elif fault == "half_batch":
            wrap_steps(eng, half_batch)
        elif fault == "answer_altered":
            from flowsentryx_tpu.engine import engine as eng_mod

            extract = eng_mod.extract_updates

            def altered(keys, untils):
                upd = extract(keys, untils)
                if len(upd.key):
                    key = upd.key.copy()
                    key[0] ^= 1
                    upd = upd._replace(key=key)
                return upd

            eng_mod.extract_updates = altered
        elif fault == "ring_block_lost":
            from flowsentryx_tpu.engine import shm

            apply = shm.ShmVerdictSink.apply

            def lossy(self, update):
                apply(self, update._replace(key=update.key[1:],
                                            until_s=update.until_s[1:]))

            shm.ShmVerdictSink.apply = lossy
        elif fault != "none":
            raise SystemExit(f"unknown fault {fault!r}")
        return eng

    harness.build_engine = broken


if __name__ == "__main__":
    fault = sys.argv[1]
    sys.argv = ["benchmark/run.py", *sys.argv[2:]]
    import runpy

    sys.path.insert(0, str(ROOT / "benchmark"))
    plant(fault)
    runpy.run_path(str(ROOT / "benchmark" / "run.py"), run_name="__main__")
