"""The chip's published peaks, one file a ``device_kind`` under
``peaks/`` (the kind as JAX reports it, spaces as ``_``), and the bytes
and operations the ALGORITHM needs for a batch — from the record count,
the schema's widths and the model family's own count, nothing from the
program, so that a later kernel is read against the same work.  An
unknown kind is an error, never a default.
"""

import json
from pathlib import Path

#: schema widths: the compact16 wire record, one flow-table row (u32 key
#: + 12 f32 columns, as the schema states it; the 68 B a row takes as
#: compiled for the v5e is the implementation's padding, not the work)
WIRE_RECORD_BYTES = 16
TABLE_ROW_BYTES = 4 + 12 * 4
VERDICT_BYTES = 4


def peaks_for(kind: str) -> dict:
    path = Path(__file__).resolve().parent / "peaks" / \
        f"{kind.replace(' ', '_')}.json"
    if not path.is_file():
        raise SystemExit(f"benchmark: no peaks for device kind {kind!r} "
                         f"({path.name})")
    return json.loads(path.read_text())


def step_bytes(n_records: int) -> int:
    """HBM bytes the step needs for ``n_records``: the wire in, one row
    read and one row written a record, the verdict word out."""
    return n_records * (WIRE_RECORD_BYTES + 2 * TABLE_ROW_BYTES
                        + VERDICT_BYTES)


def step_ops(n_records: int, model_name: str) -> int:
    """Classifier operations for ``n_records``, by the model family's
    own count (``models/<name>.py`` ``OPS_PER_RECORD``)."""
    from benchmark import harness

    return n_records * harness.load_module(
        "models", model_name).OPS_PER_RECORD
