"""Quantization-aware training in JAX — torch ``prepare_qat``/``convert``
semantics (``model.py:163-166,221-222``), functionally.

The reference QAT-trains ``QuantStub → Linear(8,1) → sigmoid →
DeQuantStub`` with MinMax observers, then converts to int8.  Here the
same pieces are explicit pure functions:

* **observers** are ``(min, max)`` carried in the train state, updated
  from each batch (quint8 affine for activations, int8 symmetric for
  weights — torch's default QAT qconfig);
* **fake-quant** with a straight-through estimator stands in for
  torch's FakeQuantize modules;
* **convert** reads the final observers into a deployable
  :class:`~flowsentryx_tpu.models.logreg.LogRegParams` — the actual
  quantized artifact (the reference's script saved the *unconverted*
  model by mistake, SURVEY.md §7.5).

Loss/optimizer mirror the reference: summed BCE + Adagrad full-batch
(``model.py:169-190``), both configurable.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from flowsentryx_tpu.core.schema import NUM_FEATURES
from flowsentryx_tpu.models.logreg import LogRegParams, make_params


class Observer(NamedTuple):
    """Moving-average min/max (torch MovingAverageMinMaxObserver, the
    default QAT activation observer).  A sticky min/max would be
    poisoned forever by one early-training excursion — e.g. a first
    epoch that swings the linear output to -2e5 locks in a quant step
    of ~1e3 and saturates the sigmoid for the rest of training."""

    lo: jnp.ndarray  # [] f32
    hi: jnp.ndarray  # [] f32
    momentum: float = 0.9

    def update(self, x: jnp.ndarray) -> "Observer":
        return self.update_minmax(x.min(), x.max())

    def update_minmax(self, blo: jnp.ndarray, bhi: jnp.ndarray) -> "Observer":
        """Momentum update from a precomputed batch range — the seam the
        data-parallel trainer uses: each device contributes its shard's
        min/max, ``pmin``/``pmax`` merge them into the GLOBAL batch
        range, and this update then runs identically (replicated) on
        every device, so observers never diverge across the mesh."""
        fresh = ~jnp.isfinite(self.lo)
        m = self.momentum
        return Observer(
            lo=jnp.where(fresh, blo, m * self.lo + (1 - m) * blo),
            hi=jnp.where(fresh, bhi, m * self.hi + (1 - m) * bhi),
            momentum=self.momentum,
        )

    def quint8_qparams(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Affine quint8 (scale, zero_point), torch determination rules:
        range always includes 0; zp clamped to [0, 255]."""
        lo = jnp.minimum(self.lo, 0.0)
        hi = jnp.maximum(self.hi, 0.0)
        scale = jnp.maximum((hi - lo) / 255.0, 1e-12)
        zp = jnp.clip(jnp.round(-lo / scale), 0, 255)
        return scale, zp


def fresh_observer() -> Observer:
    return Observer(lo=jnp.float32(jnp.inf), hi=jnp.float32(-jnp.inf))


def fake_quant(
    x: jnp.ndarray, scale: jnp.ndarray, zp: jnp.ndarray, qmin: float, qmax: float
) -> jnp.ndarray:
    """Quantize→dequantize with a straight-through gradient."""
    q = jnp.clip(jnp.round(x / scale) + zp, qmin, qmax)
    dq = (q - zp) * scale
    return x + jax.lax.stop_gradient(dq - x)


class QatState(NamedTuple):
    w: jnp.ndarray          # [8] f32 master weights
    b: jnp.ndarray          # [] f32
    obs_in: Observer
    obs_out: Observer
    opt_state: optax.OptState


class TrainResult(NamedTuple):
    state: QatState
    losses: np.ndarray      # [epochs] f32
    params: LogRegParams    # converted int8 artifact


def _weight_scale(w: jnp.ndarray) -> jnp.ndarray:
    """Per-tensor symmetric int8 weight scale (zp=0), torch
    ``default_weight_observer``: scale = absmax / 127."""
    return jnp.maximum(jnp.abs(w).max() / 127.0, 1e-12)


def qat_forward(
    w: jnp.ndarray,
    b: jnp.ndarray,
    obs_in: Observer,
    obs_out: Observer,
    x: jnp.ndarray,
    quantize: bool = True,
) -> tuple[jnp.ndarray, Observer, Observer]:
    """One QAT forward pass: returns probabilities + updated observers.

    ``quantize=False`` is the observer-only warmup phase (observers
    track ranges but the forward stays float) — fake-quant switches on
    once ranges reflect a roughly-converged model, the standard cure
    for early-training range thrash."""
    obs_in = obs_in.update(x)
    if quantize:
        in_s, in_zp = obs_in.quint8_qparams()
        x = fake_quant(x, in_s, in_zp, 0, 255)

        w_s = _weight_scale(w)
        w = fake_quant(w, w_s, jnp.float32(0.0), -127, 127)

    y = x @ w + b
    obs_out = obs_out.update(y)
    if quantize:
        out_s, out_zp = obs_out.quint8_qparams()
        y = fake_quant(y, out_s, out_zp, 0, 255)
    return jax.nn.sigmoid(y), obs_in, obs_out


def train_logreg_qat(
    X: np.ndarray,
    y: np.ndarray,
    epochs: int = 200,
    lr: float = 0.05,
    warmup_fraction: float = 0.5,
    log_features: bool = True,
    optimizer: optax.GradientTransformation | None = None,
    log_every: int = 0,
    sample_weight: np.ndarray | None = None,
) -> TrainResult:
    """Full-batch QAT (the reference trains full-batch 1000 epochs with
    Adagrad lr=0.05, ``model.py:169-190``; 200 epochs converges for the
    synthetic sets and is a flag for the real ones).

    ``log_features`` trains in the log1p domain (recorded in the
    exported artifact, see LogRegParams.log1p): raw CIC features span
    1e0..1e6, where a per-tensor quint8 input step wipes out every
    small-magnitude feature — the reference artifact's exact pathology.
    The first ``warmup_fraction`` of epochs run observer-only, and the
    optimizer restarts when fake-quant engages (warmup-scale Adagrad
    accumulators would otherwise freeze the quant-finetune phase).

    ``sample_weight`` scales each row's BCE term — the lever for
    minority-mode recall (a slow-attack upweight trades a little benign
    precision for the recall a uniform loss averages away)."""
    X = jnp.asarray(X, jnp.float32)
    if log_features:
        X = jnp.log1p(X)
    y = jnp.asarray(y, jnp.float32)
    sw = (None if sample_weight is None
          else jnp.asarray(sample_weight, jnp.float32))
    opt = optimizer or optax.adagrad(lr)

    w0 = jnp.zeros((NUM_FEATURES,), jnp.float32)
    b0 = jnp.float32(0.0)
    state = QatState(
        w=w0, b=b0,
        obs_in=fresh_observer(), obs_out=fresh_observer(),
        opt_state=opt.init((w0, b0)),
    )

    def loss_fn(wb, obs_in, obs_out, X, y, quantize):
        w, b = wb
        p, obs_in, obs_out = qat_forward(w, b, obs_in, obs_out, X, quantize)
        eps = 1e-7  # BCE on probabilities, summed (BCELoss(sum))
        losses = -(y * jnp.log(p + eps) + (1 - y) * jnp.log(1 - p + eps))
        if sw is not None:
            losses = losses * sw
        return losses.sum(), (obs_in, obs_out)

    @partial(jax.jit, static_argnames=("quantize",))
    def epoch(state: QatState, X, y, quantize: bool):
        (loss, (obs_in, obs_out)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )((state.w, state.b), state.obs_in, state.obs_out, X, y, quantize)
        updates, opt_state = opt.update(grads, state.opt_state)
        w, b = optax.apply_updates((state.w, state.b), updates)
        return QatState(w, b, obs_in, obs_out, opt_state), loss

    n_warm = int(epochs * warmup_fraction)
    losses = np.zeros(epochs, np.float32)
    for e in range(epochs):
        if e == n_warm:  # phase switch: fresh optimizer for finetune
            state = state._replace(opt_state=opt.init((state.w, state.b)))
        state, loss = epoch(state, X, y, quantize=e >= n_warm)
        losses[e] = float(loss)
        if log_every and (e + 1) % log_every == 0:
            print(f"epoch {e + 1}/{epochs}: loss {losses[e]:.1f}")

    return TrainResult(
        state=state, losses=losses, params=convert(state, log_features)
    )


def convert(state: QatState, log_features: bool = True) -> LogRegParams:
    """torch ``convert``: bake observers + weights into the deployable
    int8 artifact (this is what the reference FAILED to save)."""
    w_s = _weight_scale(state.w)
    w_int8 = np.clip(
        np.round(np.asarray(state.w) / float(w_s)), -127, 127
    ).astype(np.int8)
    in_s, in_zp = state.obs_in.quint8_qparams()
    out_s, out_zp = state.obs_out.quint8_qparams()
    return make_params(
        w_int8=w_int8,
        bias=float(state.b),
        w_scale=float(w_s),
        in_scale=float(in_s),
        in_zp=int(in_zp),
        out_scale=float(out_s),
        out_zp=int(out_zp),
        log1p=log_features,
    )


# ---------------------------------------------------------------------------
# Data-parallel QAT over a device mesh
# ---------------------------------------------------------------------------


def train_logreg_qat_dp(
    X: np.ndarray,
    y: np.ndarray,
    mesh,
    epochs: int = 200,
    lr: float = 0.05,
    warmup_fraction: float = 0.5,
    log_features: bool = True,
    optimizer: optax.GradientTransformation | None = None,
) -> TrainResult:
    """:func:`train_logreg_qat` sharded over a ``jax.sharding.Mesh``.

    Same full-batch semantics, data-parallel: each device holds an
    ``N/n`` shard of the training set; per epoch it computes its
    shard's loss terms and gradients, which ``psum`` into the exact
    full-batch sums (the loss is summed BCE, so data parallelism is
    lossless up to float reassociation).  The interesting correctness
    question is the **observers**: min/max ranges are NOT additive, so
    each device contributes its shard's range and ``pmin``/``pmax``
    merge them into the global batch range *before* the momentum
    update, which then runs replicated — observers stay bit-identical
    across the mesh and match the single-device trainer (asserted in
    tests/test_train.py).  Ragged ``N`` is zero-padded and masked out
    of loss, gradients, and ranges.
    """
    axis = mesh.axis_names[0]
    n_dev = int(mesh.devices.size)
    X = jnp.asarray(X, jnp.float32)
    if log_features:
        X = jnp.log1p(X)
    y = jnp.asarray(y, jnp.float32)
    n = X.shape[0]
    pad = (-n) % n_dev
    mask = jnp.concatenate([jnp.ones((n,), bool), jnp.zeros((pad,), bool)])
    X = jnp.concatenate([X, jnp.zeros((pad, X.shape[1]), jnp.float32)])
    y = jnp.concatenate([y, jnp.zeros((pad,), jnp.float32)])
    opt = optimizer or optax.adagrad(lr)

    w0 = jnp.zeros((NUM_FEATURES,), jnp.float32)
    b0 = jnp.float32(0.0)
    state = QatState(
        w=w0, b=b0,
        obs_in=fresh_observer(), obs_out=fresh_observer(),
        opt_state=opt.init((w0, b0)),
    )

    def device_epoch(state: QatState, X_l, y_l, m_l, quantize: bool):
        # Observer updates run PRIMAL-ONLY, before autodiff: pmin/pmax
        # have no differentiation rule, and none is needed — fake-quant's
        # straight-through estimator blocks every gradient path through
        # the quant params, so computing them outside value_and_grad is
        # gradient-identical to the single-device trainer (which updates
        # observers inside the differentiated forward).
        x = X_l
        blo = jax.lax.pmin(jnp.min(jnp.where(m_l[:, None], x, jnp.inf)), axis)
        bhi = jax.lax.pmax(jnp.max(jnp.where(m_l[:, None], x, -jnp.inf)), axis)
        obs_in = state.obs_in.update_minmax(blo, bhi)
        in_s, in_zp = obs_in.quint8_qparams()
        xq = fake_quant(x, in_s, in_zp, 0, 255) if quantize else x
        wq = (fake_quant(state.w, _weight_scale(state.w), jnp.float32(0.0),
                         -127, 127) if quantize else state.w)
        yl = xq @ wq + state.b
        ylo = jax.lax.pmin(jnp.min(jnp.where(m_l, yl, jnp.inf)), axis)
        yhi = jax.lax.pmax(jnp.max(jnp.where(m_l, yl, -jnp.inf)), axis)
        obs_out = state.obs_out.update_minmax(ylo, yhi)
        out_s, out_zp = obs_out.quint8_qparams()

        def loss_fn(wb):
            w, b = wb
            x = X_l
            if quantize:
                x = fake_quant(x, in_s, in_zp, 0, 255)
                w = fake_quant(w, _weight_scale(w), jnp.float32(0.0),
                               -127, 127)
            yl = x @ w + b
            if quantize:
                yl = fake_quant(yl, out_s, out_zp, 0, 255)
            p = jax.nn.sigmoid(yl)
            eps = 1e-7  # BCE on probabilities, summed (BCELoss(sum))
            losses = -(y_l * jnp.log(p + eps)
                       + (1 - y_l) * jnp.log(1 - p + eps))
            return jax.lax.psum(jnp.sum(jnp.where(m_l, losses, 0.0)), axis)

        loss, grads = jax.value_and_grad(loss_fn)((state.w, state.b))
        # shard_map AD leaves each device with d(local loss)/dw; the
        # full-batch gradient is their sum
        grads = jax.lax.psum(grads, axis)
        updates, opt_state = opt.update(grads, state.opt_state)
        w, b = optax.apply_updates((state.w, state.b), updates)
        return QatState(w, b, obs_in, obs_out, opt_state), loss

    state_specs = jax.tree.map(lambda _: P(), state,
                               is_leaf=lambda x: x is None)
    epochs_jit = {}
    for quantize in (False, True):
        epochs_jit[quantize] = jax.jit(jax.shard_map(
            partial(device_epoch, quantize=quantize),
            mesh=mesh,
            in_specs=(state_specs, P(axis), P(axis), P(axis)),
            out_specs=(state_specs, P()),
            check_vma=False,
        ))

    n_warm = int(epochs * warmup_fraction)
    losses = np.zeros(epochs, np.float32)
    for e in range(epochs):
        if e == n_warm:  # phase switch: fresh optimizer (see train_logreg_qat)
            state = state._replace(opt_state=opt.init((state.w, state.b)))
        state, loss = epochs_jit[e >= n_warm](state, X, y, mask)
        losses[e] = float(loss)

    return TrainResult(
        state=state, losses=losses, params=convert(state, log_features)
    )


# ---------------------------------------------------------------------------
# Float trainers (logreg without quant; MLP family)
# ---------------------------------------------------------------------------


def train_mlp(
    X: np.ndarray,
    y: np.ndarray,
    epochs: int = 100,
    batch_size: int = 4096,
    lr: float = 1e-3,
    hidden: int = 32,
    seed: int = 0,
):
    """Minibatch Adam for the MLP family (models/mlp.py)."""
    from flowsentryx_tpu.models import mlp

    X = jnp.asarray(X, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    params = mlp.init_params(jax.random.PRNGKey(seed), hidden=hidden)
    opt = optax.adam(lr)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, xb, yb):
        loss, grads = jax.value_and_grad(mlp.loss_fn)(params, xb, yb)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    n = len(X)
    rng = np.random.default_rng(seed)
    losses = []
    for _ in range(epochs):
        order = rng.permutation(n)
        for s in range(0, n, batch_size):
            idx = order[s : s + batch_size]
            params, opt_state, loss = step(params, opt_state, X[idx], y[idx])
        losses.append(float(loss))
    return params, np.asarray(losses, np.float32)


def train_multiclass(
    X: np.ndarray,
    y_class: np.ndarray,
    epochs: int = 60,
    batch_size: int = 4096,
    lr: float = 1e-3,
    hidden: int = 32,
    seed: int = 0,
):
    """Minibatch Adam for the per-attack-class expert heads
    (models/multiclass.py — the SURVEY §2.3 EP extension point)."""
    from flowsentryx_tpu.models import multiclass

    X = jnp.asarray(X, jnp.float32)
    y_class = jnp.asarray(y_class, jnp.int32)
    params = multiclass.init_params(jax.random.PRNGKey(seed), hidden=hidden)
    opt = optax.adam(lr)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, xb, yb):
        loss, grads = jax.value_and_grad(multiclass.loss_fn)(params, xb, yb)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    n = len(X)
    rng = np.random.default_rng(seed)
    losses = []
    for _ in range(epochs):
        order = rng.permutation(n)
        for s in range(0, n, batch_size):
            idx = order[s : s + batch_size]
            params, opt_state, loss = step(params, opt_state,
                                           X[idx], y_class[idx])
        losses.append(float(loss))
    return params, np.asarray(losses, np.float32)
