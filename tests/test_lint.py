"""The lint gate's AST stages (scripts/lint.py) — above all the
local-import stage the PR-3 cleanup motivated: function-local jax
imports under a module-level jax import, and locals shadowing
module-level import bindings."""

import importlib.util
import sys
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "fsx_lint", Path(__file__).resolve().parents[1] / "scripts" / "lint.py")
lint = importlib.util.module_from_spec(_spec)
sys.modules["fsx_lint"] = lint
_spec.loader.exec_module(lint)


def _findings(tmp_path, src):
    p = tmp_path / "mod.py"
    p.write_text(src)
    # _local_import_findings reports paths relative to the repo root;
    # point it at the temp module directly
    old = lint.REPO
    lint.REPO = tmp_path
    try:
        return lint._local_import_findings(p)
    finally:
        lint.REPO = old


class TestLocalImportStage:
    def test_local_jax_under_module_jax_flagged(self, tmp_path):
        out = _findings(tmp_path, (
            "import jax.numpy as jnp\n\n"
            "def f():\n"
            "    import jax\n"
            "    return jax.devices()\n"))
        assert len(out) == 1
        assert "function-local jax import" in out[0]
        assert "mod.py:4" in out[0]

    def test_shadowing_local_import_flagged(self, tmp_path):
        out = _findings(tmp_path, (
            "from flowsentryx_tpu.core import schema\n\n"
            "def f():\n"
            "    from flowsentryx_tpu.core import schema\n"
            "    return schema\n"))
        assert len(out) == 1
        assert "shadows module-level import 'schema'" in out[0]

    def test_lazy_jax_in_jax_free_module_allowed(self, tmp_path):
        # the CLI idiom: jax-free module lazily imports jax in the one
        # command that needs it — NOT a finding
        out = _findings(tmp_path, (
            "import argparse\n\n"
            "def serve():\n"
            "    import jax\n"
            "    return jax.devices()\n"))
        assert out == []

    def test_noqa_exempts(self, tmp_path):
        out = _findings(tmp_path, (
            "import jax\n\n"
            "def f():\n"
            "    import jax  # noqa: deliberate re-import\n"
            "    return jax\n"))
        assert out == []

    def test_nested_function_reported_once(self, tmp_path):
        out = _findings(tmp_path, (
            "import jax\n\n"
            "def outer():\n"
            "    def inner():\n"
            "        import jax.numpy as jnp\n"
            "        return jnp\n"
            "    return inner\n"))
        assert len(out) == 1  # not duplicated by the nested-def walk

    def test_module_level_conditional_import_not_flagged(self, tmp_path):
        # module-level try/if imports are module-level, not
        # function-local
        out = _findings(tmp_path, (
            "import jax\n"
            "if hasattr(jax, 'shard_map'):\n"
            "    from jax import shard_map\n"
            "else:\n"
            "    from jax.experimental.shard_map import shard_map\n"))
        assert out == []

    def test_repo_is_clean(self):
        assert lint.stage_local_imports() == []


def _purity_findings(tmp_path, src):
    p = tmp_path / "fused.py"
    p.write_text(src)
    old = lint.REPO
    lint.REPO = tmp_path
    try:
        return lint._traced_purity_findings(p)
    finally:
        lint.REPO = old


class TestTracedRegionPurityStage:
    """The traced-region gate: no device_get/callback may appear in
    ops/ (the step every engine serves is written there and runs
    inside jit — fsx audit proves it on the staged graph, this stage
    catches it at review speed)."""

    def test_device_get_flagged(self, tmp_path):
        out = _purity_findings(tmp_path, (
            "import jax\n\n"
            "def loop(x):\n"
            "    return jax.device_get(x)\n"))
        assert len(out) == 1
        assert "device_get" in out[0] and "fused.py:4" in out[0]

    def test_callbacks_flagged(self, tmp_path):
        for snippet, name in (
                ("jax.pure_callback(f, x, x)", "pure_callback"),
                ("io_callback(f, x, x)", "io_callback"),
                ("jax.debug.print('{}', x)", "debug.print"),
                ("jax.experimental.io_callback(f, x, x)",
                 "io_callback")):
            out = _purity_findings(tmp_path, (
                "import jax\n\n"
                "def loop(f, x):\n"
                f"    return {snippet}\n"))
            assert out, snippet
            assert name in out[0]

    def test_noqa_exempts(self, tmp_path):
        out = _purity_findings(tmp_path, (
            "import jax\n\n"
            "def loop(x):\n"
            "    return jax.device_get(x)  # noqa: doc example\n"))
        assert out == []

    def test_clean_traced_code_passes(self, tmp_path):
        out = _purity_findings(tmp_path, (
            "import jax\nimport jax.numpy as jnp\n\n"
            "def loop(base, slots):\n"
            "    ring = jnp.stack(slots)\n"
            "    return jax.lax.scan(base, None, ring)\n"))
        assert out == []

    def test_repo_traced_region_is_clean(self):
        """The stage reads the tree the step is written in, and the
        one host-side fetch there (``table_summary``'s, outside any
        jit) is exempt by ``noqa``, not by being out of sight."""
        read = {p.name for p in
                (lint.REPO / lint.TRACED_REGION_TREE).rglob("*.py")}
        assert {"fused.py", "hashtable.py", "agg.py", "limiters.py",
                "pallas_kernels.py"} <= read
        assert lint.stage_traced_region_purity() == []


class TestSyncContractsStage:
    """The thread-contract gate (fsx sync --quick as a lint stage): a
    regression in the stage plumbing must not pass silently."""

    def test_repo_is_clean(self):
        assert lint.stage_sync_contracts() == []

    def test_stage_surfaces_findings(self, tmp_path):
        # point the stage at a tree where the registered modules are
        # missing: every registry entry must surface as a finding —
        # proof the stage actually runs the checker (a stage that
        # silently returned [] on error would pass this repo forever)
        old = lint.REPO
        lint.REPO = tmp_path
        try:
            out = lint.stage_sync_contracts()
        finally:
            lint.REPO = old
        assert out
        assert any("registered module does not exist" in f for f in out)

    def test_stage_catches_planted_discipline_violation(self, tmp_path):
        # a full end-to-end plant: copy the real tree layout with ONE
        # engine violation — a worker-reachable method writing a
        # dispatch-owned field — and run the stage against it
        import shutil

        repo = Path(lint.REPO)
        for rel in ("flowsentryx_tpu/engine/engine.py",
                    "flowsentryx_tpu/engine/shm.py",
                    "flowsentryx_tpu/sync/channel.py",
                    "flowsentryx_tpu/ingest/sharded.py",
                    "flowsentryx_tpu/ingest/worker.py"):
            dst = tmp_path / rel
            dst.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(repo / rel, dst)
        eng = tmp_path / "flowsentryx_tpu/engine/engine.py"
        src = eng.read_text()
        # plant: the sink worker touches the dispatch-owned staging
        # counter (exactly the drift class the registry exists to stop)
        needle = "    def _sink_worker(self) -> None:"
        assert needle in src
        planted = src.replace(
            needle,
            "    def _sink_worker(self) -> None:\n"
            "        self._staged_batches += 1\n", 1)
        eng.write_text(planted)
        old = lint.REPO
        lint.REPO = tmp_path
        try:
            out = lint.stage_sync_contracts()
        finally:
            lint.REPO = old
        assert any("_staged_batches" in f and "worker" in f
                   for f in out), out


def _np_findings(tmp_path, src, rel="flowsentryx_tpu/ops/mod.py"):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(src)
    old = lint.REPO
    lint.REPO = tmp_path
    try:
        return lint.stage_np_default_int()
    finally:
        lint.REPO = old


class TestNpDefaultIntStage:
    """The dtype-less-constructor gate: platform-C-long width is an
    overflow hazard the fsx ranges prover cannot see."""

    def test_dtype_less_arange_flagged(self, tmp_path):
        out = _np_findings(tmp_path, (
            "import numpy as np\n"
            "idx = np.arange(10)\n"))
        assert len(out) == 1
        assert "np.arange" in out[0] and "mod.py:2" in out[0]

    def test_dtype_less_full_flagged(self, tmp_path):
        out = _np_findings(tmp_path, (
            "import numpy as np\n"
            "proto = np.full(8, 6)\n"))
        assert len(out) == 1 and "np.full" in out[0]

    def test_dtype_kwarg_clean(self, tmp_path):
        out = _np_findings(tmp_path, (
            "import numpy as np\n"
            "idx = np.arange(10, dtype=np.int64)\n"
            "z = np.zeros(4, dtype=np.uint32)\n"))
        assert out == []

    def test_positional_dtype_clean(self, tmp_path):
        out = _np_findings(tmp_path, (
            "import numpy as np\n"
            "z = np.zeros(4, np.uint32)\n"
            "b = np.zeros((3,), bool)\n"
            "f = np.full(8, 6, np.uint8)\n"))
        assert out == []

    def test_noqa_exempts(self, tmp_path):
        out = _np_findings(tmp_path, (
            "import numpy as np\n"
            "idx = np.arange(10)  # noqa: host-only index math\n"))
        assert out == []

    def test_outside_hot_path_not_scanned(self, tmp_path):
        out = _np_findings(tmp_path, (
            "import numpy as np\n"
            "idx = np.arange(10)\n"), rel="flowsentryx_tpu/train/m.py")
        assert out == []

    def test_repo_is_clean(self):
        assert lint.stage_np_default_int() == []


def _cluster_jax_findings(tmp_path, src):
    p = tmp_path / "newmod.py"
    p.write_text(src)
    old = lint.REPO
    lint.REPO = tmp_path
    try:
        return lint._cluster_jax_findings(p)
    finally:
        lint.REPO = old


class TestClusterJaxFreeStage:
    """The cluster plane's import hygiene: module-level jax (or
    jax-importing-module) imports are banned under cluster/ — one
    there puts a multi-second jax pay on every fleet boot, adopt
    census, and chaos stub spawn."""

    def test_module_level_jax_flagged(self, tmp_path):
        out = _cluster_jax_findings(tmp_path, (
            "import jax\n\n"
            "def f():\n"
            "    return jax.devices()\n"))
        assert len(out) == 1
        assert "module-level import of 'jax'" in out[0]
        assert "newmod.py:1" in out[0]

    def test_from_jax_submodule_flagged(self, tmp_path):
        out = _cluster_jax_findings(tmp_path, (
            "from jax.numpy import asarray\n"))
        assert len(out) == 1 and "'jax.numpy'" in out[0]

    def test_jax_importing_repo_module_flagged(self, tmp_path):
        out = _cluster_jax_findings(tmp_path, (
            "from flowsentryx_tpu.engine.writeback import "
            "decode_verdict_wire\n"))
        assert len(out) == 1
        assert "'flowsentryx_tpu.engine.writeback'" in out[0]

    def test_function_local_writeback_allowed(self, tmp_path):
        # the GossipPlane.tick discipline: lazy-importing the jax
        # surface inside the function that needs it stays legal
        out = _cluster_jax_findings(tmp_path, (
            "def tick():\n"
            "    from flowsentryx_tpu.engine.writeback import (\n"
            "        decode_verdict_wire,\n"
            "    )\n"
            "    return decode_verdict_wire\n"))
        assert out == []

    def test_jax_free_engine_modules_allowed(self, tmp_path):
        # health/metrics/shm are jax-free by design and legal at
        # module level (the supervisor imports all three)
        out = _cluster_jax_findings(tmp_path, (
            "from flowsentryx_tpu.engine import health\n"
            "from flowsentryx_tpu.engine.metrics import LatencyHist\n"
            "from flowsentryx_tpu.engine.shm import RingNotReady\n"))
        assert out == []

    def test_jaxlib_lookalike_not_flagged(self, tmp_path):
        # the prefix match is per-component: 'jaxtools' is not 'jax'
        out = _cluster_jax_findings(tmp_path, (
            "import jaxtools\n"))
        assert out == []

    def test_noqa_exempts(self, tmp_path):
        out = _cluster_jax_findings(tmp_path, (
            "import jax  # noqa: measured, spawn path unaffected\n"))
        assert out == []

    def test_repo_cluster_tree_is_clean(self):
        assert lint.stage_cluster_jax_free() == []


def _durable_findings(tmp_path, src,
                      rel="flowsentryx_tpu/cluster/mod.py"):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(src)
    old = lint.REPO
    lint.REPO = tmp_path
    try:
        return lint.stage_durable_writes()
    finally:
        lint.REPO = old


class TestDurableWritesStage:
    """The durable-write gate: protocol state under cluster/ and
    engine/checkpoint.py must publish through durable.atomic_write —
    a bare write is exactly the fsync_skipped regression the fsx
    crash checker demonstrates losing state at power loss."""

    def test_open_write_mode_flagged(self, tmp_path):
        out = _durable_findings(tmp_path, (
            "def publish(path, data):\n"
            "    with open(path, 'wb') as f:\n"
            "        f.write(data)\n"))
        assert len(out) == 1
        assert "open(..., 'wb')" in out[0] and "mod.py:2" in out[0]

    def test_open_mode_kwarg_flagged(self, tmp_path):
        out = _durable_findings(tmp_path, (
            "f = open('layout.json', mode='w')\n"))
        assert len(out) == 1 and "open(..., 'w')" in out[0]

    def test_open_read_modes_clean(self, tmp_path):
        # r is a read; r+b is the shm mmap-update idiom, not a publish
        out = _durable_findings(tmp_path, (
            "def peek(path):\n"
            "    with open(path, 'rb') as f:\n"
            "        return f.read()\n"
            "def mmap_update(path):\n"
            "    return open(path, 'r+b')\n"))
        assert out == []

    def test_write_text_flagged(self, tmp_path):
        out = _durable_findings(tmp_path, (
            "from pathlib import Path\n"
            "def save(d):\n"
            "    Path('handoff.json').write_text(d)\n"))
        assert len(out) == 1 and ".write_text(...)" in out[0]

    def test_path_targeted_savez_flagged(self, tmp_path):
        out = _durable_findings(tmp_path, (
            "import numpy as np\n"
            "def spool(keys):\n"
            "    np.savez_compressed('staged.npz', keys=keys)\n"))
        assert len(out) == 1
        assert "np.savez_compressed(<path>" in out[0]

    def test_bytesio_savez_clean(self, tmp_path):
        # the checkpoint idiom: savez into an in-memory handle whose
        # bytes then publish through atomic_write
        out = _durable_findings(tmp_path, (
            "import io\nimport numpy as np\n"
            "from flowsentryx_tpu.core import durable\n"
            "def save(path, keys):\n"
            "    buf = io.BytesIO()\n"
            "    np.savez_compressed(buf, keys=keys)\n"
            "    durable.atomic_write(path, buf.getvalue())\n"))
        assert out == []

    def test_noqa_exempts(self, tmp_path):
        out = _durable_findings(tmp_path, (
            "def mk(path):\n"
            "    with open(path, 'wb') as f:  # noqa: shm create\n"
            "        f.truncate(64)\n"))
        assert out == []

    def test_outside_scope_not_scanned(self, tmp_path):
        out = _durable_findings(tmp_path, (
            "def save(path, d):\n"
            "    with open(path, 'w') as f:\n"
            "        f.write(d)\n"), rel="flowsentryx_tpu/engine/other.py")
        assert out == []

    def test_checkpoint_module_in_scope(self, tmp_path):
        out = _durable_findings(
            tmp_path,
            "open('ck.npz', 'wb')\n",
            rel="flowsentryx_tpu/engine/checkpoint.py")
        assert len(out) == 1

    def test_repo_is_clean(self):
        assert lint.stage_durable_writes() == []
