"""Performance-portability autotuner: measured per-hardware config search.

The paper's central claim is that its back-projection kernels are
*performance portable over a wide range of CPUs* — and its own Table 4
shows the winning (variant, loop order, blocking) choice differs per
machine, as Treibig et al. (arXiv:1104.5243) demonstrated for RabbitCT
and iFDK (arXiv:1909.02724) at cluster scale. Everywhere else in this
repo the planner resolves its knobs (variant fallback, ``schedule``,
``proj_loop``, ``pipeline``, tile/chunk sizes) from static heuristics.
This module is the subsystem that *measures* instead of guesses:

  * :func:`autotune` — given a request (the same façade options every
    entry point takes), enumerate the candidate configuration space and
    time each candidate on the LIVE device with warm
    :class:`~repro.runtime.executor.ProgramCache` programs (compile is
    paid outside the timed region; warmup + median-of-k inside), under
    a wall-clock search budget. The search is a greedy per-axis sweep —
    variant ladder, ``KernelSpec.tuning_space`` options (e.g.
    ``proj_loop`` on/off), tile-spec and projection-chunk candidates
    pruned by the existing ``core.tiling.tile_working_set_bytes``
    model, ``schedule`` "step"/"chunk", ``pipeline`` "sync"/"async"
    with depths — so ~15 measurements cover a space whose cross product
    has hundreds of points. The heuristic config is ALWAYS measured
    first, so any budget leaves a valid winner.
  * :class:`TunedConfig` — the resolved winner: every knob an executor
    needs, self-contained and JSON-serializable
    (``PlanExecutor.from_config`` turns it back into a running
    executor; ``build_plan`` into a :class:`ReconPlan`).
  * :class:`TuningCache` — winners persist on disk (JSON under
    ``~/.cache/repro/tuning.json``, or ``$REPRO_TUNING_CACHE``, or any
    user path), keyed by a hardware fingerprint ``(backend, device
    kind, cpu count, jax version)`` x the request's
    ``ReconPlan.bucket_key``. A second process on the same machine
    resolves the same winner with ZERO re-measurement; a different
    machine (fingerprint mismatch) re-tunes. Missing or corrupt cache
    files degrade to the heuristics — never to an error. Entries are
    SELF-MAINTAINING: an :func:`autotune` resolve of an entry older
    than ``revalidate_s`` re-measures the heuristic baseline once
    (cheap) and invalidates + re-tunes when it drifted beyond
    :data:`DRIFT_RATIO` from the recorded baseline — a stale winner
    from a changed machine heals itself instead of pinning a bad
    configuration forever.
  * :func:`resolve_config` / :func:`resolve_plan` — the LOOKUP-ONLY
    path consulted by ``plan_reconstruction(variant="auto")``, the
    ``fdk_reconstruct`` façade, and ``ReconService``: cache hit returns
    the tuned config, miss falls back to today's heuristics. Planning
    stays microseconds either way; measurement only ever happens inside
    :func:`autotune` (e.g. ``ReconService.warmup(tune=True)``).

Exactness contract
------------------
The searched knobs split into two classes, and the default respects the
split:

  * **order-only knobs** — ``schedule`` ("step"/"chunk" walk the same
    chunk grid in the same per-voxel addition order) and ``pipeline`` /
    ``pipeline_depth`` (the async flusher only moves WHEN host adds
    happen, never their order). Tuning these is bit-identical to the
    heuristic config by construction (asserted in
    tests/test_autotune.py and tests/test_service.py).
  * **numeric knobs** — ``variant``, ``proj_loop``, tile shape, chunk
    size. These change float-op order; parity is at tolerance, not bit
    level.

``autotune(..., exact=True)`` — the default whenever the caller names
a variant, including through ``ReconService.warmup(tune=True,
variant=...)`` — searches only order-only knobs, so the tuned output is
bit-identical to the heuristic config. ``variant="auto"`` (or
``exact=False``) widens to the full space. Winners are keyed per
request KIND as well as shape: an "auto" winner (which may carry a
different variant) is never resolved by an explicitly-named-variant
request (:func:`request_key`).
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.tiling import tile_working_set_bytes
from repro.core.variants import get_spec
from repro.runtime import telemetry

_DEFAULT_VARIANT = "algorithm1_mp"

# measurement priority for variant="auto": the pure-JAX ladder first
# (strongest heuristics up front so early budget exhaustion still
# leaves a good winner), Pallas kernels last (interpret-mode timing on
# CPU CI is real but slow).
_LADDER = ("algorithm1_mp", "symmetry_mp", "subline_batch_mp",
           "subline_mp", "share_mp", "transpose_mp",
           "subline_pl", "onehot_pl", "banded_pl")

# cache self-maintenance: a resolved entry older than ``revalidate_s``
# gets ONE cheap heuristic-baseline probe; a probe/recorded-baseline
# ratio beyond DRIFT_RATIO (either direction) invalidates the entry and
# re-runs the search — the machine the entry was measured on is no
# longer the machine we are running on, performance-wise.
DRIFT_RATIO = 2.0


# --------------------------------------------------------------------------
# Hardware fingerprint
# --------------------------------------------------------------------------

def hardware_fingerprint() -> Tuple[str, str, int, str]:
    """(backend, device kind, cpu count, jax version) of THIS process.

    The tuple every cached winner is scoped to: a measured choice is
    only trusted on hardware indistinguishable under this key — any
    mismatch re-tunes rather than importing another machine's winner.
    """
    import jax
    devs = jax.devices()
    kind = devs[0].device_kind if devs else "unknown"
    return (str(jax.default_backend()), str(kind),
            int(os.cpu_count() or 1), str(jax.__version__))


def fingerprint_key(fp: Optional[Tuple] = None) -> str:
    """Flat string form of the fingerprint (the JSON cache's outer key)."""
    return "|".join(str(p) for p in (hardware_fingerprint()
                                     if fp is None else fp))


def _scope(variant) -> str:
    """Key namespace of a request: "auto" when the tuner may switch
    variants, "explicit" when the caller named one."""
    return "auto" if variant in (None, "auto") else "explicit"


def request_key(base_plan, scope: str = "explicit") -> str:
    """Stable identity of one request SHAPE: the heuristic base plan's
    ``bucket_key`` (the exact tuple the serving layer buckets on),
    rendered with ``repr`` — scalars/short tuples only, so the string
    is deterministic across processes. ``scope`` ("auto" | "explicit",
    see :func:`_scope`) keeps the two request kinds in separate
    namespaces: a ``variant="auto"`` winner may carry a DIFFERENT
    variant than the default the base plan was built with, and an
    explicitly-named-variant request must never resolve it (the
    exactness contract promises explicit requests stay on their
    variant)."""
    return f"{scope}|{base_plan.bucket_key!r}"


# --------------------------------------------------------------------------
# TunedConfig: one fully resolved configuration
# --------------------------------------------------------------------------

def _tupleize(v):
    """JSON round-trip repair: lists back to tuples (plan options and
    tile shapes must stay hashable — they sit inside bucket keys)."""
    if isinstance(v, list):
        return tuple(_tupleize(x) for x in v)
    return v


@dataclasses.dataclass(frozen=True)
class TunedConfig:
    """Every knob one reconstruction execution needs, fully resolved.

    Self-contained: ``build_plan(geom)`` re-plans it and
    ``PlanExecutor.from_config`` runs it, with no reference back to the
    search that produced it. ``wall_us``/``baseline_us`` record the
    measured winner and heuristic medians; ``source`` says where the
    config came from ("measured" — this process timed it, "cache" — a
    persisted winner, "heuristic" — no tuning information) and
    ``trials`` how many candidates were measured (0 on a cache hit —
    the acceptance assertion).
    """

    variant: str
    schedule: str                       # "step" | "chunk"
    pipeline: str                       # "sync" | "async"
    pipeline_depth: int
    tile_shape: Tuple[int, int, int]
    proj_batch: Optional[int]           # None = single chunk
    nb: int
    out: str                            # "host" | "device"
    interpret: bool
    options: Tuple[Tuple[str, object], ...] = ()
    # cross-request batch cap (service-tier rb): how many same-bucket
    # requests the BatchFormer may coalesce into one dispatch stream
    # under this config. Order-only per lane (vmap adds an axis, never
    # reassociates a lane's reductions), so it is searched even in
    # exact mode; wall_us under max_batch > 1 is AMORTIZED per request.
    max_batch: int = 1
    # numeric-precision data path: "f32" | "bf16" (bf16 samples with
    # f32 accumulators — a tolerance-contract knob like ``variant``,
    # searched only in the wide space). Pre-existing cache entries lack
    # the field -> dataclass default "f32".
    precision: str = "f32"
    # iterative-solver family ("none" = plain FDK). Solver winners are
    # measured on AMORTIZED per-iteration wall (see _measure_solver)
    # and live under their own request keys (solver is in bucket_key).
    solver: str = "none"
    wall_us: float = 0.0
    baseline_us: float = 0.0
    source: str = "heuristic"           # "measured" | "cache" | "heuristic"
    trials: int = 0
    # wall-clock stamp (time.time()) of the measurement that produced
    # or last REVALIDATED this entry. Entries older than the caller's
    # ``revalidate_s`` get a cheap baseline probe on resolve: within
    # DRIFT_RATIO of the recorded baseline the stamp refreshes, beyond
    # it the entry is invalidated and re-tuned (self-maintenance).
    # Pre-existing cache files lack the field -> 0.0 == always stale.
    tuned_at: float = 0.0
    # candidates the search could not run, as (variant, error) pairs —
    # e.g. a kernel Mosaic refuses to lower for this chip. Kept with the
    # winner so a refusal is visible instead of silently dropped.
    refused: Tuple[Tuple[str, str], ...] = ()

    @property
    def key(self) -> Tuple:
        """Knob identity (measurement/bookkeeping fields excluded)."""
        return (self.variant, self.schedule, self.pipeline,
                self.pipeline_depth, self.tile_shape, self.proj_batch,
                self.nb, self.out, self.interpret, self.options,
                self.max_batch, self.precision, self.solver)

    @property
    def speedup(self) -> float:
        """Measured heuristic/tuned wall ratio (>1 = tuning helped)."""
        return self.baseline_us / self.wall_us if self.wall_us else 1.0

    def build_plan(self, geom):
        """Re-plan this config (pure — the normal planner path)."""
        from repro.runtime.planner import plan_reconstruction
        return plan_reconstruction(
            geom, self.variant, tile_shape=self.tile_shape, nb=self.nb,
            proj_batch=self.proj_batch, out=self.out,
            interpret=self.interpret, schedule=self.schedule,
            request_batch=self.max_batch, precision=self.precision,
            solver=self.solver, **dict(self.options))

    def to_json(self) -> Dict:
        doc = dataclasses.asdict(self)
        doc["options"] = [list(kv) for kv in self.options]
        doc["tile_shape"] = list(self.tile_shape)
        return doc

    @classmethod
    def from_json(cls, doc: Dict) -> "TunedConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in doc.items() if k in fields}
        kw["tile_shape"] = tuple(int(v) for v in doc["tile_shape"])
        kw["options"] = tuple(
            (str(k), _tupleize(v)) for k, v in doc.get("options", []))
        pb = doc.get("proj_batch")
        kw["proj_batch"] = None if pb is None else int(pb)
        # pre-batching cache entries lack the field: default to 1
        kw["max_batch"] = int(doc.get("max_batch", 1))
        kw["refused"] = tuple((str(v), str(e))
                              for v, e in doc.get("refused", []))
        return cls(**kw)


def config_from_plan(plan, *, pipeline: str = "sync",
                     pipeline_depth: int = 2,
                     source: str = "heuristic") -> TunedConfig:
    """Snapshot a planned request as a :class:`TunedConfig` (the
    heuristic baseline every search starts from)."""
    return TunedConfig(
        variant=plan.variant, schedule=plan.schedule, pipeline=pipeline,
        pipeline_depth=int(pipeline_depth), tile_shape=plan.tile_shape,
        proj_batch=(plan.chunk_size if plan.streams_projections else None),
        nb=plan.nb, out=plan.out, interpret=plan.interpret,
        options=plan.options, source=source,
        max_batch=int(plan.request_batch), precision=plan.precision,
        solver=plan.solver)


# --------------------------------------------------------------------------
# TuningCache: persistent fingerprint-keyed winners
# --------------------------------------------------------------------------

def default_cache_path() -> str:
    """``$REPRO_TUNING_CACHE`` if set, else ``~/.cache/repro/tuning.json``."""
    env = os.environ.get("REPRO_TUNING_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "tuning.json")


# one lock per cache PATH, process-wide: distinct TuningCache
# instances over the same file (as_tuning_cache builds one per call)
# must still serialize their read-modify-write cycles
_PATH_LOCKS: Dict[str, threading.Lock] = {}
_PATH_LOCKS_GUARD = threading.Lock()


def _path_lock(path: str) -> threading.Lock:
    key = os.path.abspath(path)
    with _PATH_LOCKS_GUARD:
        return _PATH_LOCKS.setdefault(key, threading.Lock())


# parsed-document memo keyed on (mtime_ns, size): a tuning-enabled
# service resolves every request through lookup(), and the file only
# changes when a tuner stores a winner — re-parsing per request would
# be pure repeated work. Entries are treated as READ-ONLY by lookup().
_DOC_CACHE: Dict[str, Tuple[Tuple[int, int], Dict]] = {}
_DOC_CACHE_GUARD = threading.Lock()


class TuningCache:
    """On-disk JSON store of measured winners.

    Layout: ``{"version": 1, "fingerprints": {<fp>: {<request_key>:
    <TunedConfig doc>}}}``. Reads are tolerant by design — a missing
    file, unreadable JSON, a wrong version, or a malformed entry all
    behave as a cache miss (the caller falls back to heuristics), never
    as an error: a stale cache must not be able to break
    reconstruction. Writes are read-modify-write under a process-wide
    per-PATH lock with an atomic ``os.replace``, so concurrent tuners
    within one process never clobber each other's entries even through
    distinct ``TuningCache`` instances. Across PROCESSES the last
    writer wins for the load->replace window; the worst case is a
    just-stored entry dropping out, which costs one re-tune — never
    corruption (the replace is atomic) and never an error.
    """

    VERSION = 1

    def __init__(self, path: Optional[str] = None):
        self.path = str(path) if path is not None else default_cache_path()
        self._lock = _path_lock(self.path)

    # ---- tolerant IO -----------------------------------------------------

    def _load(self, memo: bool = True) -> Dict:
        """Parse the cache file (tolerantly). ``memo=True`` (the lookup
        path) serves the parsed doc from the (mtime, size)-stamped memo
        when the file is unchanged; the doc is shared read-only, so
        writers must pass ``memo=False`` for a private copy."""
        empty = {"version": self.VERSION, "fingerprints": {}}
        key = os.path.abspath(self.path)
        try:
            st = os.stat(self.path)
            stamp = (st.st_mtime_ns, st.st_size)
        except OSError:
            return empty
        if memo:
            with _DOC_CACHE_GUARD:
                hit = _DOC_CACHE.get(key)
            if hit is not None and hit[0] == stamp:
                return hit[1]
        try:
            with open(self.path) as f:
                doc = json.load(f)
        except FileNotFoundError:
            return empty
        except (OSError, ValueError, UnicodeDecodeError):
            return empty    # corrupt cache == no cache, never an error
        if (not isinstance(doc, dict) or doc.get("version") != self.VERSION
                or not isinstance(doc.get("fingerprints"), dict)):
            return empty
        if memo:
            with _DOC_CACHE_GUARD:
                _DOC_CACHE[key] = (stamp, doc)
        return doc

    def lookup(self, fp_key: str, req_key: str) -> Optional[TunedConfig]:
        """The persisted winner for (hardware, request shape), or None."""
        entry = self._load()["fingerprints"].get(fp_key, {}).get(req_key)
        if entry is None:
            return None
        try:
            return TunedConfig.from_json(entry)
        except (KeyError, TypeError, ValueError):
            return None     # malformed entry == miss

    def _write(self, doc: Dict) -> None:
        """Atomic write + memo refresh (call holding ``self._lock``)."""
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
        os.replace(tmp, self.path)
        try:
            st = os.stat(self.path)
            with _DOC_CACHE_GUARD:
                _DOC_CACHE[os.path.abspath(self.path)] = \
                    ((st.st_mtime_ns, st.st_size), doc)
        except OSError:
            pass

    def store(self, fp_key: str, req_key: str, config: TunedConfig) -> None:
        with self._lock:
            doc = self._load(memo=False)   # private copy — mutated below
            doc["fingerprints"].setdefault(fp_key, {})[req_key] = \
                config.to_json()
            self._write(doc)

    def invalidate(self, fp_key: str, req_key: str) -> bool:
        """Drop one persisted winner (the self-maintenance path: a stale
        entry whose recorded baseline no longer matches this hardware).
        Returns whether an entry was removed."""
        with self._lock:
            doc = self._load(memo=False)
            bucket = doc["fingerprints"].get(fp_key)
            if not bucket or req_key not in bucket:
                return False
            del bucket[req_key]
            if not bucket:
                del doc["fingerprints"][fp_key]
            self._write(doc)
            return True

    def entries(self) -> Dict[str, Dict[str, Dict]]:
        """Raw {fingerprint: {request_key: config doc}} view —
        READ-ONLY (may be the shared memoized document)."""
        return self._load()["fingerprints"]

    def __len__(self) -> int:
        return sum(len(v) for v in self.entries().values())


def default_tuning_cache() -> TuningCache:
    """Cache at the default path (env resolved at construction, so
    ``REPRO_TUNING_CACHE`` changes take effect per instance)."""
    return TuningCache()


def as_tuning_cache(obj) -> TuningCache:
    """Coerce a façade ``tuning=`` argument: a :class:`TuningCache`,
    a filesystem path, or None (the default cache)."""
    if isinstance(obj, TuningCache):
        return obj
    if obj is None:
        return default_tuning_cache()
    return TuningCache(os.fspath(obj))


# --------------------------------------------------------------------------
# Heuristic baseline + lookup-only resolution
# --------------------------------------------------------------------------

def _base_kernel_options(variant, kernel_options: Dict) -> Dict:
    """Kernel options for the heuristic BASE plan.

    An "auto" request may carry options for variants other than the
    default the base plan is built with (e.g. ``proj_loop`` for the
    Pallas candidates): validate them against the WHOLE registry — a
    typo still fails fast — then filter to what the base variant
    accepts, so planning the base never rejects a legitimate
    cross-variant knob. Explicit-variant requests pass through
    untouched (the planner validates them as usual)."""
    if variant not in (None, "auto"):
        return dict(kernel_options)
    from repro.core.variants import REGISTRY
    known = {"nb", "interpret"}
    for spec in REGISTRY.values():
        known |= set(spec.options)
    unknown = set(kernel_options) - known
    if unknown:
        raise ValueError(
            f"variant='auto' got option(s) {sorted(unknown)} accepted "
            f"by no registered variant")
    allowed = get_spec(_DEFAULT_VARIANT).options
    return {k: v for k, v in kernel_options.items() if k in allowed}


def _request_key(variant, base_plan, kernel_options: Dict) -> str:
    """Full cache key for one request. Explicit-variant requests are
    covered by the base plan's bucket_key (its options are the resolved
    caller options); "auto" requests append the raw caller options —
    the base plan silently drops the cross-variant ones, and two auto
    requests differing only there must not collide."""
    key = request_key(base_plan, _scope(variant))
    if variant in (None, "auto") and kernel_options:
        key += f"|opts={tuple(sorted(kernel_options.items()))!r}"
    return key


def _heuristic_config(geom, variant="auto", *, nb=8, interpret=None,
                      tiling=None, memory_budget=None, proj_batch=None,
                      out=None, schedule=None, precision="f32",
                      solver="none", **kernel_options):
    """(heuristic TunedConfig, its base plan) for one façade request —
    exactly what every entry point runs today without tuning."""
    from repro.core.fdk import _build_plan
    name = _DEFAULT_VARIANT if variant in (None, "auto") else variant
    plan = _build_plan(geom, name, nb=nb, interpret=interpret,
                       tiling=tiling, memory_budget=memory_budget,
                       proj_batch=proj_batch, out=out, schedule=schedule,
                       precision=precision, solver=solver,
                       **_base_kernel_options(variant, kernel_options))
    return config_from_plan(plan), plan


def resolve_config(geom, variant: str = "auto", *, cache=None,
                   **request) -> TunedConfig:
    """LOOKUP-ONLY config resolution (never measures): the persisted
    winner for this (hardware, request shape) if one exists
    (``source == "cache"``), today's heuristics otherwise
    (``source == "heuristic"``). ``request`` takes the façade options
    (``nb``/``tiling``/``memory_budget``/``proj_batch``/``out``/
    ``schedule``/kernel options)."""
    cache = as_tuning_cache(cache)
    base_cfg, base_plan = _heuristic_config(geom, variant, **request)
    extra = {k: v for k, v in request.items()
             if k not in ("nb", "interpret", "tiling", "memory_budget",
                          "proj_batch", "out", "schedule", "precision",
                          "solver")}
    hit = cache.lookup(fingerprint_key(),
                       _request_key(variant, base_plan, extra))
    if hit is not None:
        return dataclasses.replace(hit, source="cache", trials=0)
    return base_cfg


def resolve_plan(geom, *, variant="auto", tuning=None, tile_shape=None,
                 memory_budget=None, nb=8, proj_batch=None, out="host",
                 interpret=None, schedule=None, request_batch=1,
                 precision="f32", solver="none", **kernel_options):
    """Planner-level twin of :func:`resolve_config` (planner argument
    conventions; returns the plan only — the executor-level pipeline
    choice needs :func:`resolve_config`). This is what
    ``plan_reconstruction(variant="auto" / tuning=...)`` delegates to.
    The caller's ``request_batch`` overrides a cached winner's
    ``max_batch`` on the returned plan (rb is an execution multiplicity
    the caller commits to, not a shape fact — ``bucket_key`` ignores
    it either way)."""
    from repro.runtime.planner import plan_reconstruction
    cache = as_tuning_cache(tuning)
    name = _DEFAULT_VARIANT if variant in (None, "auto") else variant
    base = plan_reconstruction(
        geom, name, tile_shape=tile_shape, memory_budget=memory_budget,
        nb=nb, proj_batch=proj_batch, out=out, interpret=interpret,
        schedule=schedule, request_batch=request_batch,
        precision=precision, solver=solver,
        **_base_kernel_options(variant, kernel_options))
    hit = cache.lookup(fingerprint_key(),
                       _request_key(variant, base, kernel_options))
    if hit is None:
        return base
    return hit.build_plan(geom).batched(int(request_batch))


# --------------------------------------------------------------------------
# Measurement
# --------------------------------------------------------------------------

def _measure_config(geom, config: TunedConfig, projections,
                    program_cache, *, iters: int = 3,
                    warmup: int = 1) -> float:
    """Median wall seconds of one full ``reconstruct`` under ``config``.

    Programs are compiled via ``PlanExecutor.warm`` BEFORE the timed
    region (the cache makes repeat candidates nearly free), then
    ``warmup`` untimed calls absorb first-call allocation effects and
    the median of ``iters`` timed calls is returned.

    ``config.max_batch > 1`` measures the BATCHED path — one
    ``execute_batch`` of max_batch copies of the projections — and
    returns wall / max_batch: the amortized per-request time, directly
    comparable against the unbatched candidates so the sweep picks the
    rb sweet spot (or rejects batching where vmap pressure eats the
    dispatch saving on this hardware).
    """
    import jax
    from repro.runtime.executor import PlanExecutor
    ex = PlanExecutor.from_config(geom, config, cache=program_cache)
    ex.warm()
    rb = max(1, int(config.max_batch))
    if rb > 1:
        if not ex.supports_request_batching:
            raise ValueError("config cannot batch (chunk-major plan)")
        ex.warm_batch(rb)
        reqs = [projections] * rb
        run = lambda: ex.execute_batch(reqs)      # noqa: E731
    else:
        run = lambda: ex.reconstruct(projections)  # noqa: E731
    for _ in range(int(warmup)):
        jax.block_until_ready(run())
    times = []
    for _ in range(max(1, int(iters))):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] / rb


def _measure_solver(geom, config: TunedConfig, projections,
                    program_cache, *, iters_per_solve: int = 3,
                    warmup: int = 1) -> float:
    """Median AMORTIZED wall seconds per solver ITERATION under
    ``config`` (``config.solver`` names the method).

    Compiles + normalizers are paid via ``IterativeExecutor.warm``
    before the timed region — the quantity a deployment cares about is
    the warm per-iteration cost the whole solve multiplies, not the
    one-time setup. Each timed sample runs a short
    ``iters_per_solve``-iteration solve and bills wall /
    iters_per_solve, so loop overhead amortizes the same way a real
    N-iteration run amortizes it.
    """
    import jax
    from repro.runtime.solvers import IterativeExecutor
    ex = IterativeExecutor(geom, config.build_plan(geom),
                           cache=program_cache)
    ex.warm()
    k = max(1, int(iters_per_solve))
    run = lambda: ex.solve(projections, n_iters=k)[0]  # noqa: E731
    for _ in range(int(warmup)):
        jax.block_until_ready(run())
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] / k


# --------------------------------------------------------------------------
# Candidate axes (greedy per-axis sweep)
# --------------------------------------------------------------------------

def _fits_budget(tile, geom, nb: int, variant: str,
                 memory_budget: Optional[int]) -> bool:
    """Prune a tile candidate with the SAME working-set model the
    planner's auto-picker uses (mirror-paired slabs billed at their
    virtual 2*tk depth)."""
    if memory_budget is None:
        return True
    ti, tj, tk = tile
    nz = geom.volume_shape_xyz[2]
    eff = min(2 * tk, nz) if (get_spec(variant).uses_symmetry
                              and tk < nz) else tk
    ws = tile_working_set_bytes((ti, tj, eff), (geom.nw, geom.nh), nb=nb)
    return ws <= int(memory_budget)


def _variant_axis(cur: TunedConfig, requested: str,
                  kernel_options: Dict) -> List[TunedConfig]:
    if requested not in (None, "auto"):
        return []
    out = []
    for name in _LADDER:
        if name == cur.variant:
            continue
        spec = get_spec(name)
        if spec.backend == "reference":
            continue
        opts = spec.resolve_options(dict(kernel_options))
        if spec.proj_loop and "proj_loop" not in opts:
            # mirror the planner's default so the candidate's key
            # matches the plan it measures (else _option_axis would
            # re-measure the identical plan under a second key)
            opts["proj_loop"] = True
        out.append(dataclasses.replace(
            cur, variant=name, options=tuple(sorted(opts.items()))))
    return out


def _option_axis(cur: TunedConfig) -> List[TunedConfig]:
    """Flip each KernelSpec-advertised tuning option (e.g. proj_loop)."""
    spec = get_spec(cur.variant)
    have = dict(cur.options)
    out = []
    for name, values in spec.tuning_space:
        for v in values:
            if have.get(name) == v:
                continue
            opts = dict(have)
            opts[name] = v
            out.append(dataclasses.replace(
                cur, options=tuple(sorted(opts.items()))))
    return out


def _tile_axis(geom, cur: TunedConfig,
               memory_budget: Optional[int]) -> List[TunedConfig]:
    nx, ny, nz = geom.volume_shape_xyz
    ti, tj, tk = cur.tile_shape
    cands = [(nx, ny, nz),                                   # untiled
             (max(1, ti // 2), max(1, tj // 2), tk),         # finer (i, j)
             (max(1, ti // 2), max(1, tj // 2), max(1, tk // 2))]
    out = []
    for tile in cands:
        if tile == cur.tile_shape:
            continue
        if not _fits_budget(tile, geom, cur.nb, cur.variant, memory_budget):
            continue
        out.append(dataclasses.replace(cur, tile_shape=tile))
    return out


def _chunk_axis(geom, cur: TunedConfig,
                memory_budget: Optional[int]) -> List[TunedConfig]:
    nb = cur.nb
    n_pad = -(-int(geom.n_proj) // nb) * nb
    cands = {None}
    half = -(-(n_pad // 2) // nb) * nb
    if nb <= half < n_pad:
        cands.add(half)
    if nb < n_pad:
        cands.add(nb)
    if memory_budget is not None:
        # an explicit budget is the caller's device-byte contract and
        # the chunk bound is part of it: never offer a LARGER chunk
        # (and None == the whole set — the same residency
        # _schedule_axis refuses "step" for)
        cap = cur.proj_batch if cur.proj_batch is not None else n_pad
        cands = {pb for pb in cands if pb is not None and pb <= cap}
    out = []
    for pb in sorted(cands, key=lambda v: -1 if v is None else v):
        if pb == cur.proj_batch:
            continue
        out.append(dataclasses.replace(cur, proj_batch=pb))
    return out


def _schedule_axis(cur: TunedConfig, memory_budget: Optional[int],
                   pinned: Optional[str] = None) -> List[TunedConfig]:
    # a schedule the caller NAMED is a contract, not a default — e.g.
    # "chunk" is chosen for its bounded device residency — so the tuner
    # never offers the other one (``pinned``); likewise an explicit
    # memory_budget is the caller's device-byte contract, which only
    # the chunk-major loop honors (the step-major scan stacks the
    # whole filtered set on device) — do not offer "step"
    if pinned is not None:
        return []
    allowed = ("chunk",) if memory_budget is not None else ("step", "chunk")
    return [dataclasses.replace(cur, schedule=s)
            for s in allowed if s != cur.schedule]


def _batch_axis(cur: TunedConfig) -> List[TunedConfig]:
    """Cross-request batch cap candidates (the service-tier rb sweet
    spot). Only step-major plans batch; per-lane output is
    bit-identical to unbatched (vmap adds an axis, never reassociates
    a lane), so this axis is searched even in exact mode. Candidates
    are measured AMORTIZED (wall / rb — see :func:`_measure_config`),
    so rb only wins where one dispatch genuinely serves rb requests
    cheaper than rb dispatches."""
    if cur.schedule != "step":
        return []
    return [dataclasses.replace(cur, max_batch=rb)
            for rb in (1, 2, 4, 8) if rb != cur.max_batch]


def _precision_axis(cur: TunedConfig) -> List[TunedConfig]:
    """Flip the reduced-precision data path (bf16 samples / f32
    accumulators). A tolerance-contract knob like ``variant`` — only
    offered in the wide (non-exact) search."""
    return [dataclasses.replace(cur, precision=p)
            for p in ("f32", "bf16") if p != cur.precision]


def _pipeline_axis(cur: TunedConfig) -> List[TunedConfig]:
    if cur.out != "host":
        return []    # the flush pipeline only exists for host placement
    combos = (("sync", 2), ("async", 2), ("async", 4))
    return [dataclasses.replace(cur, pipeline=p, pipeline_depth=d)
            for p, d in combos
            if (p, d) != (cur.pipeline, cur.pipeline_depth)]


# --------------------------------------------------------------------------
# The tuner
# --------------------------------------------------------------------------

def autotune(geom, variant: str = "auto", *, method: str = "fdk",
             nb: int = 8,
             interpret: Optional[bool] = None, tiling=None,
             memory_budget: Optional[int] = None,
             proj_batch: Optional[int] = None, out: Optional[str] = None,
             schedule: Optional[str] = None, precision: str = "f32",
             budget_s: float = 20.0, iters: int = 3, warmup: int = 1,
             exact: Optional[bool] = None,
             variants: Optional[Sequence[str]] = None,
             cache=None, force: bool = False, projections=None,
             program_cache=None, revalidate_s: float = 3600.0,
             **kernel_options) -> TunedConfig:
    """Measured configuration search for one request shape.

    Returns the winning :class:`TunedConfig` and persists it in the
    :class:`TuningCache` (``cache``: a TuningCache, a path, or None for
    the default). A persisted winner for this (hardware fingerprint,
    request ``bucket_key``) short-circuits the search entirely unless
    ``force=True`` — the returned config then has ``source == "cache"``
    and ``trials == 0``.

    ``budget_s`` bounds the SEARCH wall clock: the heuristic baseline
    is always measured, then greedy per-axis candidates are measured in
    priority order until the budget is spent (a candidate's compile
    time counts against the budget — it is real wall time). ``exact``
    (default: True for an explicitly requested variant, False for
    ``variant="auto"``) restricts the search to the order-only knobs
    (``schedule``/``pipeline``) whose output is bit-identical to the
    heuristic config; the wide space adds variant, KernelSpec
    ``tuning_space`` options, and working-set-pruned tile/chunk
    candidates (``variants`` optionally restricts the ladder).
    ``projections`` supplies measurement input (default: synthetic
    random projections of the geometry's shape); ``program_cache``
    shares compiled programs with the caller (e.g. the serving layer's
    cache, so tuning doubles as warmup).

    ``method`` widens the tuner beyond FDK: a solver method ("sart" /
    "os_sart" / "cgls" / "fista_tv") measures the AMORTIZED
    per-iteration wall of a short warm solve (:func:`_measure_solver`)
    and searches subset count (the ``proj_batch`` chunk axis — the
    ordered-subset structure), ``precision`` ("f32"/"bf16"), and the
    order-only ``schedule`` knob. Solver winners persist under their
    own request keys (``solver`` sits in ``bucket_key``) and never
    collide with FDK entries.

    The cache is SELF-MAINTAINING: a hit younger than ``revalidate_s``
    wall seconds resolves with zero measurement (the fast path above);
    an older hit pays ONE cheap heuristic-baseline probe. If the probe
    lands within :data:`DRIFT_RATIO` of the entry's recorded baseline
    the entry is restamped as fresh and returned (``source ==
    "cache"``, ``trials == 0`` still); beyond it — the machine's
    performance character changed (new hardware step, contended host,
    migrated cache file) — the entry is invalidated and the full search
    re-runs. Entries written before this field existed carry
    ``tuned_at == 0`` and always revalidate on first resolve.
    """
    import numpy as np
    import jax.numpy as jnp
    from repro.runtime.executor import ProgramCache

    solver = "none" if method == "fdk" else method
    if method not in ("fdk", "sart", "os_sart", "cgls", "fista_tv"):
        raise ValueError(
            f"method must be 'fdk' or a solver "
            f"('sart'|'os_sart'|'cgls'|'fista_tv'), got {method!r}")

    def _measure(cfg, projs, pc, *, m_iters, m_warmup):
        # solver methods optimize the AMORTIZED per-iteration wall —
        # the cost a real N-iteration deployment multiplies — instead
        # of one-shot reconstruct wall
        if solver == "none":
            return _measure_config(geom, cfg, projs, pc, iters=m_iters,
                                   warmup=m_warmup)
        return _measure_solver(geom, cfg, projs, pc,
                               iters_per_solve=m_iters, warmup=m_warmup)

    tcache = as_tuning_cache(cache)
    base_cfg, base_plan = _heuristic_config(
        geom, variant, nb=nb, interpret=interpret, tiling=tiling,
        memory_budget=memory_budget, proj_batch=proj_batch, out=out,
        schedule=schedule, precision=precision, solver=solver,
        **kernel_options)
    fp = fingerprint_key()
    rkey = _request_key(variant, base_plan, kernel_options)
    if not force:
        hit = tcache.lookup(fp, rkey)
        if hit is not None:
            age = time.time() - float(hit.tuned_at)
            if age <= float(revalidate_s) or hit.baseline_us <= 0.0:
                # fresh (or unvalidatable: no recorded baseline to
                # compare against) — the zero-measurement fast path
                return dataclasses.replace(hit, source="cache", trials=0)
            # stale: one cheap baseline probe decides keep vs re-tune
            if projections is None:
                rng = np.random.RandomState(0)
                projections = jnp.asarray(rng.rand(
                    geom.n_proj, geom.nh, geom.nw).astype(np.float32))
            if program_cache is None:
                program_cache = ProgramCache()
            try:
                probe_us = _measure(
                    base_cfg, projections, program_cache,
                    m_iters=1, m_warmup=1) * 1e6
            except Exception:
                probe_us = None     # unmeasurable probe: let the full
                                    # search below re-establish reality
            if probe_us is not None and probe_us > 0.0:
                drift = max(probe_us / hit.baseline_us,
                            hit.baseline_us / probe_us)
                if drift <= DRIFT_RATIO:
                    # still believable — refresh the stamp only (the
                    # recorded baseline is kept: restamping it too
                    # would let slow drift creep under the threshold)
                    tcache.store(fp, rkey, dataclasses.replace(
                        hit, tuned_at=time.time()))
                    return dataclasses.replace(hit, source="cache",
                                               trials=0)
            tcache.invalidate(fp, rkey)
            # fall through to the full search (which re-stores)

    if exact is None:
        # solver tuning is inherently non-exact: subset count changes
        # the ITERATION (OS-SART) and precision the data path, and both
        # are the axes the search exists for
        exact = variant not in (None, "auto") and solver == "none"
    if projections is None:
        rng = np.random.RandomState(0)
        projections = jnp.asarray(
            rng.rand(geom.n_proj, geom.nh, geom.nw).astype(np.float32))
    pcache = program_cache if program_cache is not None else ProgramCache()

    t_start = time.perf_counter()
    measured: Dict[Tuple, float] = {}
    refused: List[Tuple[str, str]] = []

    def timed(cfg: TunedConfig) -> float:
        if cfg.key not in measured:
            # one span per *measured* candidate (cache hits are free)
            with telemetry.span("autotune.candidate", cat="autotune",
                                variant=cfg.variant, key=repr(cfg.key)):
                measured[cfg.key] = _measure(cfg, projections, pcache,
                                             m_iters=iters,
                                             m_warmup=warmup)
        return measured[cfg.key]

    best = base_cfg
    best_t = baseline_t = timed(base_cfg)

    axes = []
    if solver != "none":
        # subset count (the plan's projection chunking IS the ordered-
        # subset structure) x precision x the order-only schedule knob;
        # pipeline/batch axes do not apply (device-resident volume,
        # stateful loop — no request batching, no host flush)
        axes.append(lambda c: _chunk_axis(geom, c, memory_budget))
        if not exact:
            axes.append(_precision_axis)
        axes.append(lambda c: _schedule_axis(c, memory_budget,
                                             pinned=schedule))
    else:
        if not exact:
            axes.append(lambda c: _variant_axis(c, variant,
                                                kernel_options))
            axes.append(_option_axis)
            axes.append(lambda c: _tile_axis(geom, c, memory_budget))
            axes.append(lambda c: _chunk_axis(geom, c, memory_budget))
            # no precision axis: a bf16 FDK volume is ~5e-4 off the f32
            # one, outside the 1e-5 contract of the wide search
        axes.append(lambda c: _schedule_axis(c, memory_budget,
                                             pinned=schedule))
        axes.append(_pipeline_axis)
        axes.append(_batch_axis)

    for axis in axes:
        for cand in axis(best):
            if variants is not None and cand.variant != best.variant \
                    and cand.variant not in variants:
                continue
            if time.perf_counter() - t_start > float(budget_s):
                break
            try:
                t = timed(cand)
            except Exception as e:
                # an unrunnable candidate never kills tuning, but its
                # refusal is recorded with the result
                first = (str(e).splitlines() or [""])[0][:300]
                refused.append((cand.variant, f"{type(e).__name__}: {first}"))
                continue
            if t < best_t:
                best, best_t = cand, t

    # normalize options through a real plan (e.g. the planner's
    # proj_loop default) so the persisted config re-plans IDENTICALLY
    best = config_from_plan(
        best.build_plan(geom), pipeline=best.pipeline,
        pipeline_depth=best.pipeline_depth)
    winner = dataclasses.replace(
        best, wall_us=best_t * 1e6, baseline_us=baseline_t * 1e6,
        source="measured", trials=len(measured), tuned_at=time.time(),
        refused=tuple(refused))
    tcache.store(fp, rkey, winner)
    # tuner-outcome trajectory: one record per full search, keyed by
    # fingerprint, so the portability claim is a tracked number
    telemetry.record_tuning({
        "fingerprint": fp, "bucket_key": rkey,
        "heuristic_wall": winner.baseline_us,
        "tuned_wall": winner.wall_us, "ratio": winner.speedup,
        "tuned_at": winner.tuned_at})
    return winner
