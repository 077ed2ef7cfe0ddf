"""Fixed-window limiter for the plain reference: a window is
``[start, start + window_s)``; a delta landing past the edge opens a
fresh window seeded with the delta.  Over the limit when the window's
packets or bytes pass their thresholds.  float32 throughout."""

import numpy as np

F32 = np.float32


def new_state(n: int) -> dict:
    return {k: np.zeros(n, F32) for k in ("start", "pps", "bps")}


def apply(st: dict, uid, new, d_pkts, d_bytes, now, cfg: dict):
    """Advance rows ``uid`` (``new`` rows start from zeros); returns the
    over-limit mask."""
    start = np.where(new, F32(0), st["start"][uid])
    pps = np.where(new, F32(0), st["pps"][uid])
    bps = np.where(new, F32(0), st["bps"][uid])
    expired = now - start >= F32(cfg["window_s"])
    pps = np.where(expired, d_pkts, pps + d_pkts).astype(F32)
    bps = np.where(expired, d_bytes, bps + d_bytes).astype(F32)
    st["start"][uid] = np.where(expired, now, start)
    st["pps"][uid] = pps
    st["bps"][uid] = bps
    return (pps > F32(cfg["pps_threshold"])) | (bps > F32(cfg["bps_threshold"]))
