"""Model family ``logreg_int8`` for the plain reference: eight u8 wire
features -> a quantised probability, in the artifact's own integer
pipeline (quint8 in, qint8 weights, quint8 out).  The numbers are the
configuration file's (``FIELDS`` are checked against the artifact the
engine loads); nothing of the program is imported.

``precision="int4"`` is the control: weights and activations cut to 4
bits on the int8 grids' own scales."""

import numpy as np

F32 = np.float32

#: the configuration's ``model`` keys that must equal the artifact's
FIELDS = ("w_int8", "bias", "w_scale", "in_scale", "in_zp", "out_scale",
          "out_zp", "log1p")
#: classifier work a record: 8 int8 multiply-accumulates
OPS_PER_RECORD = 2 * 8


def _sigmoid(x):
    return F32(1.0) / (F32(1.0) + np.exp(-x, dtype=F32))


def score(q: np.ndarray, model: dict, precision: str) -> np.ndarray:
    """``[n, 8]`` u8 wire features -> ``[n]`` f32 quantised probability."""
    w = np.asarray(model["w_int8"], np.int32)
    qi = q.astype(np.int32)
    if precision == "int4":
        w = np.clip(np.rint(w / 16.0), -8, 7).astype(np.int32) * 16
        qi = np.clip(np.rint(qi / 16.0), 0, 15).astype(np.int32) * 16
    elif precision != "int8":
        raise ValueError(f"unknown precision {precision!r}")
    acc = ((qi - model["in_zp"]) * w[None, :]).sum(axis=1, dtype=np.int32)
    y = acc.astype(F32) * (F32(model["in_scale"]) * F32(model["w_scale"])) \
        + F32(model["bias"])
    q_y = np.clip(np.rint(y / F32(model["out_scale"])) + model["out_zp"],
                  0, 255).astype(np.int32)
    y_dq = (q_y - model["out_zp"]).astype(F32) * F32(model["out_scale"])
    p = _sigmoid(y_dq)
    return (np.clip(np.rint(p * F32(256.0)), 0, 255) * F32(1.0 / 256.0)
            ).astype(F32)
