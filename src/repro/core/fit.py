"""Gradient-based detector calibration: fit physics fields of ``LArTPCConfig``
to target ADC waveforms by differentiating THROUGH the simulation chain.

The paper's portability argument is about running the forward sim fast on
many architectures; the differentiable-programming follow-ups to its
workload (larnd-sim's gradient calibration, arXiv:2309.04639) show the same
pipelines pay off twice when ``jax.grad`` flows through them: detector
parameters — electron lifetime, recombination, diffusion, noise level,
electronics gain/shaping — can be *recovered* from data by gradient descent
on a waveform loss instead of hand-tuned scans.

Three things make the stage graph differentiable without touching the
default bit-exact path (see docs/calibration.md):

  * ``rng_strategy="relaxed"`` — the counter fluctuation draw with the
    zero-variance sqrt reparameterized (``repro.core.fluctuate``); forward
    values are bit-for-bit with ``"counter"``.
  * ``cfg.digitize_ste=True`` — straight-through estimator around the ADC
    round/clip; forward values equal the quantized ones (round and clip
    commute on integer rails) but stay float32 with pass-through gradients
    inside the rails.
  * traced config rebuild — the loss closes over a *frozen* config and
    rebuilds it inside the traced function via ``dataclasses.replace`` with
    tracer-valued physics fields, so the response, noise spectrum, drift
    attenuation, and digitizer gain all become functions of theta.

Self-calibration contract: a loss built by ``make_fit_loss`` against targets
from ``make_fit_targets`` uses the SAME per-event keys as the target run, so
the noise and fluctuation realizations match and the loss is exactly zero at
the true parameters — gradient descent recovers them rather than fitting the
noise (``launch/fit.py --smoke`` gates this in CI).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.config import LArTPCConfig
from repro.core.batch import (PhysicalEventBatch, event_keys,
                              pack_physical_events)
from repro.core.stages import SimGraph, SimOutput, build_sim_graph

#: config fields the differentiable graph supports as free fit parameters —
#: each one's consumers are audited for trace-safety (no Python branching on
#: the value) and covered by ``tests/test_gradcheck.py``
FITTABLE_FIELDS = (
    "electron_lifetime_us",
    "recombination",
    "diffusion_scale",
    "noise_rms_adc",
    "adc_per_electron",
    "adc_baseline",
    "response_gain",
    "response_shaping_us",
)

#: (registry op, config strategy field, differentiable fallback) — the
#: strategy choices ``fit_config`` audits against the registry's
#: ``differentiable`` flags
_STRATEGY_FIELDS = (
    ("drift", "drift_strategy", "jnp"),
    ("charge_grid", "charge_grid_strategy", "unfused"),
    ("scatter_add", "scatter_strategy", "xla"),
    ("fft_convolve", "fft_strategy", "rfft2"),
    ("deconvolve", "deconv_strategy", "rfft2"),
)


# ---------------------------------------------------------------------------
# FitSpec: which fields are free, with bounds/transforms
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FitParam:
    """One free parameter of a fit.

    field     : ``LArTPCConfig`` field name (must be in ``FITTABLE_FIELDS``).
    init      : starting value (None -> the config's current value).
    lo / hi   : optional bounds, enforced by the transform (not by clipping).
    transform : how the unconstrained optimizer coordinate theta maps to the
                physical value:
                  identity : value = theta
                  log      : value = lo + exp(theta)       (positivity)
                  sigmoid  : value = lo + (hi-lo)*sigmoid(theta)  (box)
                None picks automatically: both bounds -> sigmoid, a lower
                bound alone -> log, unbounded -> identity.
    """

    field: str
    init: Optional[float] = None
    lo: Optional[float] = None
    hi: Optional[float] = None
    transform: Optional[str] = None

    def __post_init__(self):
        if self.field not in FITTABLE_FIELDS:
            raise ValueError(
                f"{self.field!r} is not a fittable config field; supported: "
                f"{list(FITTABLE_FIELDS)} (see docs/calibration.md to add one)")
        kind = self.resolved_transform
        if kind not in ("identity", "log", "sigmoid"):
            raise ValueError(f"unknown transform {kind!r} for {self.field!r}; "
                             "valid: identity | log | sigmoid")
        if kind == "sigmoid" and (self.lo is None or self.hi is None
                                  or not self.hi > self.lo):
            raise ValueError(f"sigmoid transform for {self.field!r} needs "
                             "bounds with hi > lo")

    @property
    def resolved_transform(self) -> str:
        if self.transform is not None:
            return self.transform
        if self.lo is not None and self.hi is not None:
            return "sigmoid"
        if self.lo is not None:
            return "log"
        return "identity"

    # -- theta <-> value ----------------------------------------------------

    def to_value(self, theta):
        kind = self.resolved_transform
        if kind == "log":
            return (self.lo or 0.0) + jnp.exp(theta)
        if kind == "sigmoid":
            return self.lo + (self.hi - self.lo) * jax.nn.sigmoid(theta)
        return theta

    def to_theta(self, value: float) -> float:
        kind = self.resolved_transform
        if kind == "log":
            return math.log(max(value - (self.lo or 0.0), 1e-12))
        if kind == "sigmoid":
            u = (value - self.lo) / (self.hi - self.lo)
            u = min(max(u, 1e-6), 1.0 - 1e-6)
            return math.log(u / (1.0 - u))
        return float(value)


@dataclasses.dataclass(frozen=True)
class FitSpec:
    """The free-parameter set of a calibration fit.

    Maps between the optimizer's unconstrained theta vector (one float32
    entry per param, in declaration order) and config field values; ``apply``
    rebuilds a (traced) config from theta inside the loss.
    """

    params: Tuple[FitParam, ...]

    def __post_init__(self):
        names = [p.field for p in self.params]
        if not names:
            raise ValueError("FitSpec needs at least one FitParam")
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate fit fields: {names}")

    @property
    def fields(self) -> Tuple[str, ...]:
        return tuple(p.field for p in self.params)

    @property
    def n(self) -> int:
        return len(self.params)

    def init_theta(self, cfg: LArTPCConfig) -> jax.Array:
        """Starting theta: each param's ``init`` (or the config's value)
        pushed through its inverse transform."""
        vals = [p.init if p.init is not None else getattr(cfg, p.field)
                for p in self.params]
        return jnp.asarray([p.to_theta(v) for p, v in zip(self.params, vals)],
                           jnp.float32)

    def true_theta(self, cfg: LArTPCConfig) -> jax.Array:
        """Theta at the config's CURRENT values (ignores ``init``) — the
        ground truth of a self-calibration test."""
        return jnp.asarray(
            [p.to_theta(getattr(cfg, p.field)) for p in self.params],
            jnp.float32)

    def unpack(self, theta: jax.Array) -> Dict[str, jax.Array]:
        """theta vector -> {field: scalar value} (traced-safe)."""
        return {p.field: p.to_value(theta[i])
                for i, p in enumerate(self.params)}

    def values(self, theta) -> Dict[str, float]:
        """Concrete {field: float} view of theta (host-side logging)."""
        return {k: float(v) for k, v in
                self.unpack(jnp.asarray(theta, jnp.float32)).items()}

    def apply(self, cfg: LArTPCConfig, theta: jax.Array) -> LArTPCConfig:
        """Rebuild ``cfg`` with the theta-valued fields (inside a trace the
        replaced fields become tracers — the frozen dataclass carries them
        fine; it just stops being hashable, which the loss never needs)."""
        return dataclasses.replace(cfg, **self.unpack(theta))


def spec_from_names(names: Sequence[str], cfg: LArTPCConfig,
                    rel_bounds: float = 4.0) -> FitSpec:
    """Convenience FitSpec: box-bound each named field to
    [value/rel_bounds, value*rel_bounds] around the config's current value
    (positive fields), identity for fields currently at zero."""
    params = []
    for name in names:
        v = float(getattr(cfg, name))
        if v > 0:
            params.append(FitParam(name, lo=v / rel_bounds,
                                   hi=v * rel_bounds))
        else:
            params.append(FitParam(name))
    return FitSpec(params=tuple(params))


# ---------------------------------------------------------------------------
# Differentiable-config plumbing
# ---------------------------------------------------------------------------


def fit_config(cfg: LArTPCConfig) -> LArTPCConfig:
    """The differentiable variant of ``cfg``.

    Forward values are IDENTICAL to the default graph (as float32): the
    relaxed fluctuation draw is bit-for-bit with ``counter``, and the STE
    digitizer's forward equals the quantized ADC. Strategy fields whose
    registered candidate is not differentiable (Pallas kernels without a
    VJP, ``auto`` picks that could resolve to one) fall back to the audited
    XLA implementations.
    """
    from repro.tune import registry

    if cfg.fluctuate and cfg.rng_strategy == "pool":
        raise ValueError(
            "the paper-faithful 'pool' fluctuation stream has no "
            "reparameterized form — its normals are consumed by data-"
            "dependent offsets; calibrate with rng_strategy='counter' "
            "(mapped to 'relaxed') or 'none'")
    updates: Dict[str, object] = {"digitize_ste": True}
    if cfg.fluctuate and cfg.rng_strategy in ("counter", "relaxed"):
        updates["rng_strategy"] = "relaxed"
    for op, field, fallback in _STRATEGY_FIELDS:
        cur = getattr(cfg, field)
        if cur == "auto" or not registry.is_differentiable(op, cur):
            updates[field] = fallback
    return dataclasses.replace(cfg, **updates)


def assert_differentiable_config(cfg: LArTPCConfig) -> None:
    """Raise unless every strategy/flag choice of ``cfg`` supports
    reverse-mode autodiff (the precondition of ``make_fit_loss``)."""
    from repro.tune import registry

    problems = []
    if cfg.fluctuate and cfg.rng_strategy not in ("relaxed", "none"):
        problems.append(
            f"rng_strategy={cfg.rng_strategy!r} (need 'relaxed' or 'none')")
    if not cfg.digitize_ste:
        problems.append("digitize_ste=False (the quantizer has zero "
                        "gradient almost everywhere)")
    for op, field, _ in _STRATEGY_FIELDS:
        cur = getattr(cfg, field)
        if cur == "auto" or not registry.is_differentiable(op, cur):
            problems.append(f"{field}={cur!r} (non-differentiable candidate "
                            f"of op {op!r})")
    if problems:
        raise ValueError("config is not differentiable: "
                         + "; ".join(problems)
                         + " — pass it through repro.core.fit.fit_config")


def _drop_stage(graph: SimGraph, name: str) -> SimGraph:
    return SimGraph(stages=tuple(s for s in graph.stages if s.name != name))


# ---------------------------------------------------------------------------
# Targets and loss
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FitTargets:
    """Self-generated calibration targets: the inputs and reference outputs
    of a fit, produced by the DEFAULT (bit-exact, int16) graph at the true
    config."""

    batch: PhysicalEventBatch
    keys: jax.Array            # (E,) per-event PRNG keys
    adc: jax.Array             # (E, W, T) int16 reference waveforms
    decon: Optional[jax.Array] = None  # (E, W, T) reference deconvolved charge


def make_fit_targets(cfg: LArTPCConfig, key: jax.Array, num_events: int = 2,
                     num_depos: Optional[int] = None, add_noise: bool = True,
                     recon: bool = False) -> FitTargets:
    """Generate events and run the default graph at ``cfg``'s (true) physics.

    The returned per-event keys are the fit's too: reusing them makes the
    loss's noise/fluctuation realizations match the target's exactly, so the
    loss is zero at the true parameters (the self-calibration contract).
    """
    from repro.core.depo import generate_physical_depos

    kgen, krun = jax.random.split(key)
    events = [generate_physical_depos(jax.random.fold_in(kgen, e), cfg,
                                      n=num_depos)
              for e in range(num_events)]
    batch = pack_physical_events(events)
    keys = event_keys(krun, range(num_events))
    graph = build_sim_graph(cfg, None, add_noise=add_noise, recon=recon)
    if recon:
        graph = _drop_stage(graph, "hit_find")
    out: SimOutput = jax.jit(graph.run_events)(keys, batch.physical_set())
    return FitTargets(batch=batch, keys=keys, adc=out.adc, decon=out.decon)


def make_fit_loss(cfg: LArTPCConfig, spec: FitSpec, targets: FitTargets,
                  add_noise: bool = True, decon_weight: float = 0.0,
                  ) -> Callable[[jax.Array], jax.Array]:
    """Build the batched scalar loss ``theta -> mean squared ADC error``.

    The loss rebuilds the config — and therefore the detector response, the
    noise spectrum, and every stage closure — inside the traced function
    from ``spec.apply(fit_config(cfg), theta)``, runs the differentiable
    graph over the target events with the batched executor
    (``SimGraph.run_events``; the same per-event keys as the target run),
    and returns

        mean((adc - target_adc)^2)
          [+ decon_weight * mean((decon - target_decon)^2)]

    The deconvolved-charge term (``decon_weight > 0``, requires targets
    built with ``recon=True``) adds the recon chain's view of the same
    waveforms — useful when fitting response parameters, whose imprint the
    inverse filter amplifies. jit the result (it is trace-stable: the theta
    vector is its only traced input).
    """
    fcfg = fit_config(cfg)
    assert_differentiable_config(fcfg)
    use_decon = decon_weight > 0.0
    if use_decon and targets.decon is None:
        raise ValueError("decon_weight > 0 needs targets built with "
                         "make_fit_targets(..., recon=True)")
    depos = targets.batch.physical_set()
    target_adc = targets.adc.astype(jnp.float32)

    def loss(theta: jax.Array) -> jax.Array:
        tcfg = spec.apply(fcfg, theta)
        graph = build_sim_graph(tcfg, None, add_noise=add_noise,
                                recon=use_decon)
        if use_decon:
            graph = _drop_stage(graph, "hit_find")
        out = graph.run_events(targets.keys, depos)
        val = jnp.mean((out.adc - target_adc) ** 2)
        if use_decon:
            val = val + decon_weight * jnp.mean((out.decon - targets.decon) ** 2)
        return val

    return loss


# ---------------------------------------------------------------------------
# Optimizer drivers
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FitResult:
    """Outcome of a fit run."""

    theta: jax.Array                 # final unconstrained coordinates
    values: Dict[str, float]         # final physical parameter values
    loss: float                      # final loss
    history: List[Tuple[int, float]]  # (step, loss) log
    steps: int

    def relative_errors(self, truth: Dict[str, float]) -> Dict[str, float]:
        """|fit - truth| / max(|truth|, eps) per field."""
        return {k: abs(self.values[k] - v) / max(abs(v), 1e-12)
                for k, v in truth.items()}


def run_fit(loss_fn: Callable, spec: FitSpec, theta0: jax.Array, *,
            steps: int = 200, lr: float = 0.05, optimizer: str = "adam",
            b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
            log_every: int = 0,
            callback: Optional[Callable[[int, float, Dict[str, float]], None]]
            = None) -> FitResult:
    """Minimize ``loss_fn`` over theta.

    optimizer="adam"  : Adam on the unconstrained theta vector, ``steps``
                        jit-compiled value_and_grad evaluations with
                        per-step (step, loss) history.
    optimizer="bfgs"  : ``jax.scipy.optimize.minimize(method="BFGS")`` —
                        quasi-Newton, usually far fewer evaluations on these
                        few-parameter smooth losses; history holds the start
                        and end points only.

    ``callback(step, loss, values)`` fires every ``log_every`` steps (and on
    the last) when set — the launch driver's per-step logging hook.
    """
    theta = jnp.asarray(theta0, jnp.float32)
    history: List[Tuple[int, float]] = []

    if optimizer == "bfgs":
        from jax.scipy.optimize import minimize

        l0 = float(loss_fn(theta))
        history.append((0, l0))
        if callback:
            callback(0, l0, spec.values(theta))
        res = minimize(loss_fn, theta, method="BFGS",
                       options={"maxiter": steps})
        theta = res.x.astype(jnp.float32)
        lf = float(res.fun)
        n_steps = int(res.nit)
        history.append((n_steps, lf))
        if callback:
            callback(n_steps, lf, spec.values(theta))
        return FitResult(theta=theta, values=spec.values(theta), loss=lf,
                         history=history, steps=n_steps)
    if optimizer != "adam":
        raise ValueError(f"unknown optimizer {optimizer!r}; "
                         "valid: adam | bfgs")

    vg = jax.jit(jax.value_and_grad(loss_fn))
    m = jnp.zeros_like(theta)
    v = jnp.zeros_like(theta)
    val = float("nan")
    for step in range(1, steps + 1):
        val_arr, g = vg(theta)
        val = float(val_arr)
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        mhat = m / (1.0 - b1 ** step)
        vhat = v / (1.0 - b2 ** step)
        theta = theta - lr * mhat / (jnp.sqrt(vhat) + eps)
        history.append((step, val))
        if callback and (step == steps
                         or (log_every and step % log_every == 0)):
            callback(step, val, spec.values(theta))
    return FitResult(theta=theta, values=spec.values(theta), loss=val,
                     history=history, steps=steps)


def calibrate(cfg: LArTPCConfig, spec: FitSpec, targets: FitTargets, *,
              steps: int = 200, lr: float = 0.05, optimizer: str = "adam",
              add_noise: bool = True, decon_weight: float = 0.0,
              log_every: int = 0, callback=None) -> FitResult:
    """End-to-end convenience: build the loss for ``targets`` and fit from
    ``spec``'s init values. ``cfg`` supplies the truth for the target run
    ONLY through ``targets``; the fit starts from each param's ``init``."""
    loss_fn = make_fit_loss(cfg, spec, targets, add_noise=add_noise,
                            decon_weight=decon_weight)
    return run_fit(loss_fn, spec, spec.init_theta(cfg), steps=steps, lr=lr,
                   optimizer=optimizer, log_every=log_every,
                   callback=callback)
