"""Empirical autotuner: time registered candidates, cache the winner to disk.

The decision the paper's authors made by hand — "which scatter-add strategy
for this architecture?" — is made here by measurement on the *live* backend
at the *actual* problem shape, then cached so later runs skip re-tuning:

  key   = (op, backend, device_kind, shape-bucket)
  value = {strategy, timings_us, tuned_at, jax_version, shape}

Shape dims are bucketed to the next power of two, so e.g. 100_000 and
120_000 depos share one decision but 1_000 does not. The cache is a single
JSON file (default ``<repo>/.repro_tune/tune_cache.json``, override with
``$REPRO_TUNE_CACHE``) — human-readable, diffable, safe to delete.

Resolution order for a strategy-valued config field:

  explicit name  >  disk cache  >  (tune now, if asked)  >  backend default

``resolve_config`` must run *before* ``jax.jit`` traces the pipeline: the
chosen strategy is baked into the traced program, exactly like the paper's
per-architecture builds — but chosen by data, not by hand.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import os
import time
import uuid
from typing import Callable, Dict, Mapping, Optional, Tuple

import jax

from repro.tune import registry
from repro.tune.registry import TuneContext

CACHE_ENV = "REPRO_TUNE_CACHE"

#: cache record schema version. Bump on incompatible record changes: entries
#: with a different (or missing) ``schema`` field are ignored per-entry —
#: a stale or foreign record degrades to a cache miss, never a crash.
SCHEMA_VERSION = 1

#: op -> the config field that names its strategy
OP_FIELDS: Dict[str, str] = {
    "drift": "drift_strategy",
    "scatter_add": "scatter_strategy",
    "charge_grid": "charge_grid_strategy",
    "fft_convolve": "fft_strategy",
    "deconvolve": "deconv_strategy",
    "hit_find": "hitfind_strategy",
}

#: ops whose tuning decision is keyed by the plane KIND (their transforms
#: differ between bipolar induction and unipolar collection planes) — on
#: multi-plane "auto" configs the field stays "auto" and every dispatch
#: resolves with its own plane key (see resolve_config_with_decisions)
PLANE_KEYED_OPS = ("fft_convolve", "deconvolve")


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------


def default_cache_path() -> str:
    from repro.cache import TUNE_CACHE

    return os.environ.get(CACHE_ENV) or str(TUNE_CACHE)


class TuneCache:
    """A {cache_key: decision-record} JSON file, loaded lazily, written on put.

    Robust to the failure modes a shared cache file actually sees
    (docs/robustness.md):

    * **Concurrent writers** — each ``put`` writes to a per-process temp name
      (pid + random suffix) and atomically ``os.replace``s it in, so two
      processes can never interleave bytes; and it *merges on write* (re-read
      disk, overlay this process's own entries) so the last writer keeps the
      other's decisions instead of clobbering them.
    * **Corrupt files** — torn writes / garbage bytes / non-dict JSON degrade
      to an empty cache (a re-tune), never a crash.
    * **Foreign entries** — records without ``schema == SCHEMA_VERSION`` (or
      that are not dicts at all) are dropped per-entry on read.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_cache_path()
        self._data: Optional[Dict[str, dict]] = None
        #: entries written by THIS process — re-overlaid on every merge
        self._local: Dict[str, dict] = {}

    @staticmethod
    def _valid(entry: object) -> bool:
        return isinstance(entry, dict) and entry.get("schema") == SCHEMA_VERSION

    def _read_disk(self) -> Dict[str, dict]:
        try:
            with open(self.path) as f:
                raw = json.load(f)
        except (OSError, ValueError, UnicodeDecodeError):
            return {}
        if not isinstance(raw, dict):
            return {}
        return {k: v for k, v in raw.items() if self._valid(v)}

    def _load(self) -> Dict[str, dict]:
        if self._data is None:
            self._data = self._read_disk()
        return self._data

    def get(self, key: str) -> Optional[dict]:
        return self._load().get(key)

    def put(self, key: str, record: dict) -> None:
        record = dict(record, schema=SCHEMA_VERSION)
        self._local[key] = record
        # merge-on-write: a concurrent tuner may have landed entries since we
        # loaded — keep theirs, overlay ours
        data = self._read_disk()
        data.update(self._local)
        self._data = data
        os.makedirs(os.path.dirname(os.path.abspath(self.path)), exist_ok=True)
        tmp = f"{self.path}.{os.getpid()}.{uuid.uuid4().hex[:8]}.tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(data, f, indent=2, sort_keys=True)
                f.write("\n")
            os.replace(tmp, self.path)
        finally:
            with contextlib.suppress(OSError):
                os.unlink(tmp)


# ---------------------------------------------------------------------------
# Shape buckets and cache keys
# ---------------------------------------------------------------------------


def _bucket(n: int) -> int:
    """Next power of two >= n (0 stays 0): 100_000 -> 131_072."""
    return 0 if n <= 0 else 1 << (int(n) - 1).bit_length()


def shape_bucket(shape: Mapping[str, object]) -> str:
    """Numeric dims bucket to the next power of two; categorical dims
    (e.g. the fft_convolve ``plane`` kind) pass through verbatim."""
    return ";".join(
        f"{k}={v}" if isinstance(v, str) else f"{k}={_bucket(v)}"
        for k, v in sorted(shape.items())
    )


def cache_key(
    op: str,
    backend: str,
    device_kind: str,
    shape: Mapping[str, int],
) -> str:
    return f"{op}|{backend}|{device_kind}|{shape_bucket(shape)}"


def op_shape(op: str, cfg) -> Dict[str, int]:
    """The problem dims op's tuning decision depends on."""
    if op == "drift":
        return {"num_depos": cfg.num_depos}
    if op in ("scatter_add", "charge_grid"):
        shape = {
            "num_depos": cfg.num_depos,
            "num_wires": cfg.num_wires,
            "num_ticks": cfg.num_ticks,
            "patch_wires": cfg.patch_wires,
            "patch_ticks": cfg.patch_ticks,
        }
        if op == "charge_grid":
            # the plane count changes the PROBLEM, not just its size: a
            # 3-plane dispatch compares single-plane candidates (paying the
            # per-plane loop) against the fused multi-plane kernels, so a
            # single-plane winner must not key multi-plane dispatches
            shape["num_planes"] = getattr(cfg, "num_planes", 1)
        return shape
    if op in ("fft_convolve", "deconvolve"):
        from repro.config import plane_specs

        return {
            "num_wires": cfg.num_wires,
            "num_ticks": cfg.num_ticks,
            "response_wires": cfg.response_wires,
            "response_ticks": cfg.response_ticks,
            # the response TYPE is part of the problem: a decision timed
            # against the bipolar induction transform must not key
            # collection-plane dispatches (and likewise for the inverse
            # filters). This default is the first plane's kind (the readout
            # plane of a single-plane config); multi-plane "auto" configs
            # never bake one answer into the field — resolve_config leaves
            # "auto" so every dispatch resolves with plane=resp.plane, and
            # tuning runs once per distinct kind (``_resolve_per_plane``)
            "plane": plane_specs(cfg)[0].kind,
        }
    if op == "hit_find":
        return {
            "num_wires": cfg.num_wires,
            "num_ticks": cfg.num_ticks,
            "max_hits_per_wire": cfg.max_hits_per_wire,
        }
    raise KeyError(f"no shape extractor for op {op!r}")


# ---------------------------------------------------------------------------
# Timing
# ---------------------------------------------------------------------------

#: a timer maps (candidate name, zero-arg thunk) -> median seconds; tests
#: inject fakes here to make the winner deterministic without a clock
Timer = Callable[[str, Callable[[], object]], float]


def median_timer(
    name: str,
    thunk: Callable[[], object],
    *,
    warmup: int = 1,
    iters: int = 3,
) -> float:
    """Default wall-clock timer (median of ``iters``, after ``warmup``)."""
    del name
    for _ in range(warmup):
        jax.block_until_ready(thunk())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(thunk())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


# ---------------------------------------------------------------------------
# Per-op problem builders: representative inputs + one thunk per candidate
# ---------------------------------------------------------------------------


def _problem_depos(cfg, sample_depos: Optional[int]):
    from repro.core.depo import generate_depos

    n = sample_depos or cfg.num_depos
    return generate_depos(jax.random.key(0), cfg, n)


def _drift_problem(cfg, ctx: TuneContext, sample_depos: Optional[int]):
    from repro.core.depo import generate_physical_depos

    n = sample_depos or cfg.num_depos
    pdepos = generate_physical_depos(jax.random.key(0), cfg, n)
    jax.block_until_ready(pdepos)

    def make(strat):
        f = jax.jit(functools.partial(strat.fn, cfg=cfg))
        return lambda: f(pdepos)

    avail = registry.available_strategies("drift", ctx)
    return {name: make(s) for name, s in avail.items()}


def _scatter_problem(cfg, ctx: TuneContext, sample_depos: Optional[int]):
    from repro.core.rasterize import rasterize

    depos = _problem_depos(cfg, sample_depos)
    patches, w0, t0 = jax.jit(lambda d: rasterize(d, cfg))(depos)
    jax.block_until_ready(patches)

    def make(strat):
        f = jax.jit(functools.partial(strat.fn, cfg=cfg))
        return lambda: f(patches, w0, t0)

    avail = registry.available_strategies("scatter_add", ctx)
    return {name: make(s) for name, s in avail.items()}


def _charge_grid_problem(cfg, ctx: TuneContext, sample_depos: Optional[int]):
    key = jax.random.key(1)
    avail = registry.available_strategies("charge_grid", ctx)
    if getattr(cfg, "num_planes", 1) > 1:
        from repro.config import plane_specs
        from repro.core.depo import generate_plane_depos
        from repro.core.stages import MULTIPLANE_CHARGE_GRID

        n = sample_depos or cfg.num_depos
        depos = generate_plane_depos(jax.random.key(0), cfg, n)
        jax.block_until_ready(depos)
        specs = plane_specs(cfg)

        def make_mp(name, strat):
            if name in MULTIPLANE_CHARGE_GRID:
                # fused multi-plane kernels take the (P, N) depos whole
                f = jax.jit(lambda k, d: strat.fn(k, d, cfg, None))
                return lambda: f(key, depos)

            # single-plane candidates pay the FULL per-plane loop (the
            # same fold_in schedule the executor runs), so the board
            # compares like against like: all P planes either way
            def loop(k, d):
                return jax.numpy.stack([
                    strat.fn(jax.random.fold_in(k, s.index),
                             jax.tree.map(lambda x, i=i: x[i], d), cfg, None)
                    for i, s in enumerate(specs)])

            f = jax.jit(loop)
            return lambda: f(key, depos)

        return {name: make_mp(name, s) for name, s in avail.items()}

    depos = _problem_depos(cfg, sample_depos)

    def make(strat):
        f = jax.jit(lambda k, d: strat.fn(k, d, cfg, None))
        return lambda: f(key, depos)

    return {name: make(s) for name, s in avail.items()}


def _fft_problem(cfg, ctx: TuneContext, sample_depos: Optional[int]):
    from repro.core.response import make_response

    del sample_depos
    # time against the response the decision is keyed to: the tuning shape
    # carries the plane kind, so collection-plane tunings measure the
    # collection transform instead of silently reusing induction
    resp = make_response(cfg, plane=ctx.shape.get("plane", "induction"))
    shape = (cfg.num_wires, cfg.num_ticks)
    grid = jax.random.uniform(jax.random.key(2), shape)

    def make(strat):
        f = jax.jit(lambda g: strat.fn(g, resp))
        return lambda: f(grid)

    avail = registry.available_strategies("fft_convolve", ctx)
    return {name: make(s) for name, s in avail.items()}


def _deconv_problem(cfg, ctx: TuneContext, sample_depos: Optional[int]):
    from repro.core.deconvolve import make_deconv_filter
    from repro.core.response import make_response

    del sample_depos
    # like _fft_problem: the inverse filter of the plane kind the decision
    # is keyed to, applied to a measured-signal-sized grid
    resp = make_response(cfg, plane=ctx.shape.get("plane", "induction"))
    filt = make_deconv_filter(resp, cfg)
    shape = (cfg.num_wires, cfg.num_ticks)
    meas = jax.random.normal(jax.random.key(3), shape)

    def make(strat):
        f = jax.jit(lambda m: strat.fn(m, filt))
        return lambda: f(meas)

    avail = registry.available_strategies("deconvolve", ctx)
    return {name: make(s) for name, s in avail.items()}


def _hitfind_problem(cfg, ctx: TuneContext, sample_depos: Optional[int]):
    del sample_depos
    # noise-scale deconvolved grid: candidate runs appear at a realistic
    # (sparse) rate relative to the threshold
    shape = (cfg.num_wires, cfg.num_ticks)
    decon = jax.random.normal(jax.random.key(4), shape) * cfg.hit_threshold

    def make(strat):
        f = jax.jit(lambda d: strat.fn(d, cfg))
        return lambda: f(decon)

    avail = registry.available_strategies("hit_find", ctx)
    return {name: make(s) for name, s in avail.items()}


_PROBLEMS = {
    "drift": _drift_problem,
    "scatter_add": _scatter_problem,
    "charge_grid": _charge_grid_problem,
    "fft_convolve": _fft_problem,
    "deconvolve": _deconv_problem,
    "hit_find": _hitfind_problem,
}

TUNABLE_OPS = tuple(_PROBLEMS)


def _usable_hit(op: str, hit: Optional[dict], ctx: TuneContext) -> bool:
    """A cached decision is only usable if its strategy still exists AND its
    availability predicate passes for the *current* context: the cache key
    carries (backend, device_kind, shape) but not config predicates like
    ``fluctuate``, so e.g. a ``fused_pallas`` winner tuned under a
    no-fluctuation config must not leak into a run that needs fluctuation."""
    if not isinstance(hit, dict):  # None, or a foreign non-record entry
        return False
    return hit.get("strategy") in registry.available_strategies(op, ctx)


def candidate_thunks(
    op: str,
    cfg,
    *,
    sample_depos: Optional[int] = None,
    shape: Optional[Mapping[str, int]] = None,
) -> Dict[str, Callable[[], object]]:
    """Zero-arg jit'd thunks for every *available* candidate of ``op``,
    built on representative inputs for ``cfg`` (shared by the tuner and the
    ``benchmarks/tune.py`` sweep)."""
    registry.ensure_registered()
    shape = dict(shape) if shape is not None else op_shape(op, cfg)
    ctx = registry.make_context(cfg, shape)
    return _PROBLEMS[op](cfg, ctx, sample_depos)


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TuneDecision:
    """How a strategy name was arrived at for one op."""

    op: str
    strategy: str
    source: str  # explicit | cache | tuned | default
    cache_key: str = ""
    timings_us: Tuple[Tuple[str, float], ...] = ()

    @property
    def cache_hit(self) -> bool:
        return self.source == "cache"

    def describe(self) -> str:
        if self.source == "tuned":
            ordered = sorted(self.timings_us, key=lambda it: it[1])
            board = ", ".join(f"{n}={t:.0f}us" for n, t in ordered)
            return (
                f"tune[{self.op}]: selected {self.strategy!r} "
                f"(tuned: {board}) -> cached as {self.cache_key}"
            )
        if self.source == "cache":
            return (
                f"tune[{self.op}]: selected {self.strategy!r} "
                f"(cache hit: {self.cache_key})"
            )
        return f"tune[{self.op}]: selected {self.strategy!r} ({self.source})"


def tune_op(
    op: str,
    cfg,
    *,
    cache: Optional[TuneCache] = None,
    timer: Optional[Timer] = None,
    force: bool = False,
    sample_depos: Optional[int] = None,
    shape: Optional[Mapping[str, int]] = None,
) -> TuneDecision:
    """Pick the fastest available candidate of ``op`` for this config/backend.

    Consults the disk cache first (unless ``force``); on a miss, times every
    available candidate with ``timer`` and persists the winner.
    """
    registry.ensure_registered()
    cache = cache or TuneCache()
    timer = timer or median_timer
    shape = dict(shape) if shape is not None else op_shape(op, cfg)
    ctx = registry.make_context(cfg, shape)
    key = cache_key(op, ctx.backend, ctx.device_kind, shape)

    if not force:
        hit = cache.get(key)
        if _usable_hit(op, hit, ctx):
            return TuneDecision(
                op=op, strategy=hit["strategy"], source="cache", cache_key=key
            )

    candidates = candidate_thunks(op, cfg, sample_depos=sample_depos, shape=shape)
    if not candidates:
        return TuneDecision(
            op=op,
            strategy=registry.default_strategy(op),
            source="default",
            cache_key=key,
        )
    timings = {name: timer(name, thunk) for name, thunk in candidates.items()}
    winner = min(timings, key=timings.get)
    timings_us = {n: t * 1e6 for n, t in timings.items()}
    record = {
        "strategy": winner,
        "timings_us": timings_us,
        "shape": dict(shape),
        "backend": ctx.backend,
        "device_kind": ctx.device_kind,
        "jax_version": jax.__version__,
        "tuned_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    cache.put(key, record)
    return TuneDecision(
        op=op,
        strategy=winner,
        source="tuned",
        cache_key=key,
        timings_us=tuple(sorted(timings_us.items())),
    )


def resolve(
    op: str,
    cfg,
    *,
    tune: bool = False,
    cache: Optional[TuneCache] = None,
    timer: Optional[Timer] = None,
    force: bool = False,
    sample_depos: Optional[int] = None,
    shape: Optional[Mapping[str, int]] = None,
) -> TuneDecision:
    """Resolve ``op``'s strategy for ``cfg``: explicit > cache > tune > default.

    Safe to call at trace time (pure Python + file read; never times unless
    ``tune=True``, which callers must only do *outside* jit). ``cfg`` may be
    None for a cache/default-only lookup when ``shape`` is given.
    """
    if cfg is not None:
        explicit = getattr(cfg, OP_FIELDS[op], "auto")
        if explicit != "auto":
            return TuneDecision(op=op, strategy=explicit, source="explicit")
    registry.ensure_registered()
    cache = cache or TuneCache()
    shape = dict(shape) if shape is not None else op_shape(op, cfg)
    ctx = registry.make_context(cfg, shape)
    key = cache_key(op, ctx.backend, ctx.device_kind, shape)
    if not force:
        hit = cache.get(key)
        if _usable_hit(op, hit, ctx):
            return TuneDecision(
                op=op, strategy=hit["strategy"], source="cache", cache_key=key
            )
    if tune and cfg is not None:
        return tune_op(
            op,
            cfg,
            cache=cache,
            timer=timer,
            force=force,
            sample_depos=sample_depos,
            shape=shape,
        )
    name = registry.default_strategy(op, ctx.backend)
    return TuneDecision(op=op, strategy=name, source="default", cache_key=key)


def resolve_config(
    cfg,
    *,
    tune: bool = False,
    cache: Optional[TuneCache] = None,
    timer: Optional[Timer] = None,
    force: bool = False,
    sample_depos: Optional[int] = None,
):
    """Replace every ``"auto"`` strategy field of ``cfg`` with a concrete name.

    Call this *before* jit so the traced program is fixed. Returns the
    resolved config (non-auto fields pass through untouched).
    """
    cfg, _ = resolve_config_with_decisions(
        cfg,
        tune=tune,
        cache=cache,
        timer=timer,
        force=force,
        sample_depos=sample_depos,
    )
    return cfg


def resolve_config_with_decisions(
    cfg,
    *,
    tune: bool = False,
    cache: Optional[TuneCache] = None,
    timer: Optional[Timer] = None,
    force: bool = False,
    sample_depos: Optional[int] = None,
    tune_explicit: bool = False,
):
    """Like ``resolve_config`` but also returns the per-op decisions.

    ``tune_explicit=True`` re-tunes ops even when their config field already
    names a concrete strategy (the ``--tune`` launcher flag: measure and
    override, don't trust the hand-picked value).
    """
    cache = cache or TuneCache()
    decisions = []
    for op, fld in OP_FIELDS.items():
        if tune and tune_explicit and getattr(cfg, fld) != "auto":
            cfg = dataclasses.replace(cfg, **{fld: "auto"})
        if (
            op in PLANE_KEYED_OPS
            and getattr(cfg, "num_planes", 1) > 1
            and getattr(cfg, fld) == "auto"
        ):
            # Multi-plane: ONE config field cannot name a per-plane winner,
            # so "auto" stays in the config and each dispatch resolves from
            # the cache with its own plane key at trace time (the ops' auto
            # paths only read cache/defaults — they never time). Tuning
            # here measures every distinct plane kind so those per-plane
            # cache entries exist before jit.
            decisions.extend(
                _resolve_per_plane(
                    op,
                    cfg,
                    tune=tune,
                    cache=cache,
                    timer=timer,
                    force=force,
                    sample_depos=sample_depos,
                )
            )
            continue
        d = resolve(
            op,
            cfg,
            tune=tune,
            cache=cache,
            timer=timer,
            force=force,
            sample_depos=sample_depos,
        )
        decisions.append(d)
        if getattr(cfg, fld) != d.strategy:
            cfg = dataclasses.replace(cfg, **{fld: d.strategy})
    return cfg, decisions


def _resolve_per_plane(
    op: str,
    cfg,
    *,
    tune: bool,
    cache: TuneCache,
    timer: Optional[Timer],
    force: bool,
    sample_depos: Optional[int],
):
    """One decision of a plane-keyed op per distinct plane kind of a
    multi-plane config (the field itself stays "auto"; see the caller)."""
    from repro.config import plane_specs

    decisions = []
    for kind in sorted({s.kind for s in plane_specs(cfg)}):
        shape = dict(op_shape(op, cfg), plane=kind)
        if tune:
            d = tune_op(
                op,
                cfg,
                cache=cache,
                timer=timer,
                force=force,
                sample_depos=sample_depos,
                shape=shape,
            )
        else:
            # cache/default lookup only — cfg=None skips the explicit-name
            # branch (the field is "auto" by construction here)
            d = resolve(op, None, cache=cache, shape=shape)
        decisions.append(d)
    return decisions
