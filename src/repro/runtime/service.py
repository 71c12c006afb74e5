"""Reconstruction serving layer: shape-bucketed requests over the
plan/compile/execute core.

iFDK (arXiv:1909.02724) frames the end-game for CPU back-projection as
instant reconstruction as a *service*; the repo's last two PRs built
exactly the substrate that makes that cheap — a pure, hashable
:class:`~repro.runtime.planner.ReconPlan` and a process-shared
:class:`~repro.runtime.executor.ProgramCache` keyed so repeated
same-shape work never retraces. :class:`ReconService` is the layer that
exploits it:

  * **shape bucketing** — every request (geometry + projections +
    façade options) is planned (pure, microseconds) and bucketed on
    ``(geometry, plan.bucket_key)``. The first request into a bucket
    builds its :class:`~repro.runtime.executor.PlanExecutor` and
    pre-compiles every program the plan needs (``PlanExecutor.warm``);
    every later same-shape request reuses them — zero new compiles, by
    construction and by test (tests/test_service.py).
  * **warmup** — ``warmup(geometries, **options)`` drives the same
    bucket-creation path without data, so a deployment can pay all
    compilation before the first real request arrives.
  * **async step pipeline** — bucket executors default to
    ``pipeline="async"``: a depth-bounded flusher thread overlaps each
    step's device->host accumulator copy with the next step's scan
    dispatch (``runtime.executor._AsyncFlushQueue``), with output
    bit-identical to the sequential flush.
  * **bounded, fair execution** — requests enter ONE FIFO queue and are
    drained by ``max_inflight`` worker threads: admission order is
    completion-start order (no shape starves another), and at most
    ``max_inflight`` reconstructions hold device memory at once.
  * **cross-request batching** — a :class:`_BatchFormer` sits between
    the FIFO queue and the workers: up to ``max_batch`` SAME-bucket
    requests (any interleaving — mixed buckets never cross-batch)
    coalesce into one ``PlanExecutor.execute_batch`` dispatch stream,
    amortizing per-dispatch overhead exactly like the paper's O5
    in-batch ``nb`` axis, one tier up. Forming is deadline/priority
    aware: a partial batch waits at most ``max_wait_ms`` for peers,
    never past any member's deadline headroom, and a ``priority > 0``
    (latency-critical) request dispatches immediately. Per-lane output
    is bit-identical to the unbatched request. The autotuner searches
    ``max_batch`` (``TunedConfig.max_batch``) so tuned buckets cap
    batches at the measured per-hardware sweet spot.
  * **streaming sessions** — ``open_stream(geom, ...)`` returns a
    :class:`StreamSession`: projections are PUSHED as the scanner
    produces them and each view-chunk back-projects the moment it
    completes (``runtime.executor.StreamingExecutor``), hiding
    reconstruction wall behind acquisition. Sessions bucket on
    ``bucket_key`` like requests; a dedicated stream worker folds
    same-phase chunks of concurrent same-bucket sessions through ONE
    batched dispatch (the ``_BatchFormer`` machinery, keyed per view
    chunk). ``close() -> volume`` is bit-identical to the offline
    chunk-major reconstruction; per-session overlap metrics
    (hidden-fraction, last-view-to-volume tail) stream into
    :class:`ServiceStats`.
  * **measured tuning** — ``warmup(..., tune=True)`` runs the
    per-hardware autotuner (``runtime.autotune``) for each bucket
    before traffic: persisted winners resolve with zero re-measurement,
    fresh hardware pays a bounded search once, and every bucket's
    ``ServiceStats`` row reports whether its configuration was tuned or
    heuristic (``source``). ``variant="auto"`` requests resolve through
    the same cache at plan time (lookup only).
  * **introspection** — ``stats()`` returns a :class:`ServiceStats`
    snapshot: per-bucket request/hit/miss/compile counts plus the
    shared ProgramCache totals (the same numbers bench_smoke surfaces
    in the BENCH_*.json meta block), and STREAMED latency accounting —
    each completed request lands in its bucket's
    :class:`LatencyHistogram` as it finishes, so per-bucket (and
    merged) p50/p99/mean are live numbers, not poll-time samples.

Usage
-----
    from repro.runtime.service import ReconService

    svc = ReconService(max_inflight=2)
    svc.warmup([geom_a, geom_b], variant="algorithm1_mp",
               tiling=(32, 32, 64), proj_batch=32)     # pay compiles now

    h = svc.submit(projections, geom_a, variant="algorithm1_mp",
                   tiling=(32, 32, 64), proj_batch=32)  # non-blocking
    vol = h.result()                                    # (nz, ny, nx)

    vol = svc.reconstruct(projections, geom_b)          # synchronous
    print(svc.stats())                                  # buckets + cache
    svc.close()

``fdk_reconstruct(..., service=svc)`` routes the façade through the
same buckets, so existing call sites join the serving path unchanged.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from concurrent.futures import Future
from typing import Dict, Iterable, List, Optional, Tuple

import jax.numpy as jnp

from repro.core.fdk import _build_plan
from repro.core.geometry import CTGeometry
from repro.runtime import telemetry
from repro.runtime.executor import FleetConfig, PlanExecutor, \
    ProgramCache, as_fleet_config, default_program_cache
from repro.runtime.planner import ReconPlan


# --------------------------------------------------------------------------
# Streamed latency accounting
# --------------------------------------------------------------------------

# The streamed log-2 latency histogram was absorbed into the telemetry
# metrics registry (runtime/telemetry.py — one histogram type for the
# whole runtime); the serving-layer name survives as an alias.
LatencyHistogram = telemetry.Histogram


# --------------------------------------------------------------------------
# Stats snapshots (immutable — safe to hand out across threads)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BucketStats(telemetry.EmitMixin):
    """One shape bucket's counters at snapshot time.

    ``misses`` is 1 for every live bucket (its creation); ``hits`` are
    the requests that reused it; ``programs_built`` is how many jit
    programs its warm-up compiled (0 when another bucket already
    populated the shared cache with the same program keys). ``source``
    records how the bucket's configuration was chosen — "heuristic"
    (the planner's static rules), "tuned-measured" (this process ran
    the autotuner search), or "tuned-cache" (a persisted per-hardware
    winner) — and ``pipeline`` the flush discipline that choice
    resolved. ``completed``/``p50_ms``/``p99_ms``/``mean_ms`` stream
    from the bucket's :class:`LatencyHistogram`.
    """

    variant: str
    vol_shape_xyz: Tuple[int, int, int]
    n_proj: int
    schedule: str
    requests: int
    hits: int
    misses: int
    programs_built: int
    source: str = "heuristic"
    pipeline: str = "async"
    completed: int = 0
    p50_ms: Optional[float] = None
    p99_ms: Optional[float] = None
    mean_ms: Optional[float] = None
    # cross-request batching: ``dispatches`` counts executor calls
    # (a formed batch of k requests is ONE dispatch), so
    # ``mean_occupancy`` = completed requests / dispatches is the
    # realized batch fill; ``batch_p50_ms`` streams the formed-batch
    # wall times and ``amortized_us_per_request`` divides total
    # execution wall over all completed requests — the number that
    # must drop as occupancy rises. ``max_batch`` is this bucket's
    # effective cap (the tuned ``TunedConfig.max_batch`` when the
    # bucket is tuned, the service default otherwise).
    dispatches: int = 0
    mean_occupancy: Optional[float] = None
    batch_p50_ms: Optional[float] = None
    amortized_us_per_request: Optional[float] = None
    max_batch: int = 1
    # fleet placement (all zero on a single-device service): device
    # count of the last fleet run, plus lifetime steal / failover-rerun
    # / retired-device totals from the bucket executor's fleet_totals
    devices: int = 0
    steals: int = 0
    failovers: int = 0
    dead_devices: int = 0
    # streaming sessions: ``streams`` opened / ``streams_closed``
    # finished; one stream "dispatch" per folded chunk batch with
    # ``stream_mean_lanes`` its realized cross-session fill;
    # ``stream_tail_ms`` is the mean time from last view arrival to
    # finished volume and ``stream_hidden_fraction`` the mean fraction
    # of back-projection wall hidden behind acquisition (both over
    # closed sessions)
    streams: int = 0
    streams_closed: int = 0
    stream_dispatches: int = 0
    stream_mean_lanes: Optional[float] = None
    stream_tail_ms: Optional[float] = None
    stream_hidden_fraction: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class ServiceStats(telemetry.EmitMixin):
    """Whole-service snapshot: totals + per-bucket rows + cache stats.

    ``p50_ms``/``p99_ms`` aggregate the per-bucket streamed histograms
    (merged bin counts, not an average of quantiles). ``as_dict()`` /
    ``emit()`` follow the shared telemetry report contract;
    :meth:`export_prometheus` renders the snapshot as Prometheus text
    exposition for a scrape endpoint."""

    requests: int
    bucket_hits: int
    bucket_misses: int
    buckets: Tuple[BucketStats, ...]
    cache: Dict[str, int]
    max_inflight: int
    queued: int
    p50_ms: Optional[float] = None
    p99_ms: Optional[float] = None
    # batching totals across buckets: executor dispatches and the
    # realized completed-requests / dispatches fill (None pre-traffic)
    max_batch: int = 1
    dispatches: int = 0
    mean_occupancy: Optional[float] = None
    # streaming totals across buckets: sessions opened, plus the mean
    # tail (last view -> volume) and hidden-fraction over all CLOSED
    # sessions (None before any stream finishes)
    streams: int = 0
    stream_tail_ms: Optional[float] = None
    stream_hidden_fraction: Optional[float] = None

    @property
    def hit_rate(self) -> float:
        total = self.bucket_hits + self.bucket_misses
        return self.bucket_hits / total if total else 0.0

    def export_prometheus(self) -> str:
        """This snapshot as Prometheus text exposition (version 0.0.4).

        Service totals are unlabeled samples; per-bucket rows carry
        ``{variant, schedule, source, vol, n_proj}`` labels (together
        unique per bucket). Empty quantiles render as NaN — present but
        unobserved, the exposition-format convention.
        """
        rows = [
            ("repro_requests_total", "counter",
             "requests admitted via submit()", [({}, self.requests)]),
            ("repro_bucket_hits_total", "counter",
             "requests that reused a live bucket",
             [({}, self.bucket_hits)]),
            ("repro_bucket_misses_total", "counter",
             "buckets created", [({}, self.bucket_misses)]),
            ("repro_hit_rate", "gauge", "bucket hit rate",
             [({}, self.hit_rate)]),
            ("repro_queued", "gauge", "requests waiting in the former",
             [({}, self.queued)]),
            ("repro_dispatches_total", "counter",
             "executor dispatches (a formed batch is one)",
             [({}, self.dispatches)]),
            ("repro_mean_occupancy", "gauge",
             "completed requests per dispatch",
             [({}, self.mean_occupancy)]),
            ("repro_latency_p50_ms", "gauge",
             "request latency p50 (merged streamed histograms)",
             [({}, self.p50_ms)]),
            ("repro_latency_p99_ms", "gauge",
             "request latency p99 (merged streamed histograms)",
             [({}, self.p99_ms)]),
            ("repro_streams_total", "counter",
             "streaming sessions opened", [({}, self.streams)]),
            ("repro_stream_tail_ms", "gauge",
             "mean last-view-to-volume tail over closed sessions",
             [({}, self.stream_tail_ms)]),
            ("repro_stream_hidden_fraction", "gauge",
             "mean fold wall hidden behind acquisition",
             [({}, self.stream_hidden_fraction)]),
            ("repro_program_cache_hits_total", "counter",
             "jit-program cache hits", [({}, self.cache.get("hits", 0))]),
            ("repro_program_cache_misses_total", "counter",
             "jit-program cache misses (== programs built)",
             [({}, self.cache.get("misses", 0))]),
        ]

        def lab(b: "BucketStats") -> Dict[str, object]:
            return {"variant": b.variant, "schedule": b.schedule,
                    "source": b.source,
                    "vol": "x".join(str(v) for v in b.vol_shape_xyz),
                    "n_proj": b.n_proj}

        bs = self.buckets
        rows += [
            ("repro_bucket_requests", "counter",
             "per-bucket requests", [(lab(b), b.requests) for b in bs]),
            ("repro_bucket_completed", "counter",
             "per-bucket completed requests",
             [(lab(b), b.completed) for b in bs]),
            ("repro_bucket_dispatches", "counter",
             "per-bucket executor dispatches",
             [(lab(b), b.dispatches) for b in bs]),
            ("repro_bucket_p50_ms", "gauge", "per-bucket latency p50",
             [(lab(b), b.p50_ms) for b in bs]),
            ("repro_bucket_p99_ms", "gauge", "per-bucket latency p99",
             [(lab(b), b.p99_ms) for b in bs]),
            ("repro_bucket_programs_built", "counter",
             "programs compiled by this bucket's warm-up",
             [(lab(b), b.programs_built) for b in bs]),
        ]
        return telemetry.prom_render(rows)


def _ms(seconds: Optional[float]) -> Optional[float]:
    return None if seconds is None else round(seconds * 1e3, 3)


@dataclasses.dataclass
class _Request:
    """One queued reconstruction plus its batching identity/constraints.

    ``key`` is ``(geometry, plan.bucket_key)`` — the batchability
    identity (``request_batch`` is deliberately not in ``bucket_key``,
    so any k same-bucket requests share a key). ``deadline_s`` is the
    ABSOLUTE ``time.perf_counter`` deadline (None = none); ``priority
    > 0`` marks a latency-critical request that never waits to fill a
    batch (and releases any batch it joins immediately)."""

    fut: Future
    projections: object
    geom: CTGeometry
    plan: ReconPlan
    config: object
    key: tuple
    deadline_s: Optional[float] = None
    priority: int = 0
    # iterative-request knobs (n_iters/relax/...), forwarded to the
    # bucket's IterativeExecutor; None for plain FDK requests
    solver_kw: Optional[Dict] = None
    # per-request telemetry identity (telemetry.new_trace_id): carried
    # into the worker's dispatch span so a k-wide batched dispatch
    # links back to all k request traces
    trace_id: str = ""


@dataclasses.dataclass
class _StreamWork:
    """One READY view-chunk of one open stream session.

    Duck-types the :class:`_BatchFormer` item contract (``key`` /
    ``priority`` / ``deadline_s``): ``key`` is the session's bucket key
    PLUS the chunk index, so the former coalesces the same rotation
    phase across concurrent same-bucket sessions into one batched fold
    and never mixes phases (different chunk indices -> different keys).
    """

    session: "StreamSession"
    chunk: int
    key: tuple
    deadline_s: Optional[float] = None
    priority: int = 0


class _BatchFormer:
    """The coalescing stage between ``submit``'s FIFO queue and the
    worker threads.

    ``take`` pops the FIFO head — the head's bucket DEFINES the batch;
    requests of other buckets are never pulled in (their relative order
    is preserved) — then gathers every queued same-bucket request up to
    the head's cap (``cap_fn``). A still-partial batch may wait for
    late peers, bounded by the TIGHTEST of: the service ``max_wait_s``,
    and each member's deadline headroom minus the bucket's running
    latency estimate (``est_fn`` — a deadline that cannot absorb the
    wait dispatches the batch immediately). Members with ``priority >
    0`` never wait: the batch ships as soon as one is aboard. With
    ``cap == 1`` or ``max_wait_s == 0`` and no queued peers this
    degenerates to exactly the old FIFO queue — one request per take,
    admission order preserved.

    ``put`` / ``close`` are atomic w.r.t. each other, so a request
    either raises (closed) or is guaranteed a consumer: workers drain
    the queue to empty before honoring the close. ``cap_fn``/``est_fn``
    are called while holding the former's condition — they must never
    take a lock that a ``put``/``close`` caller holds (the service
    passes lock-free readers).
    """

    def __init__(self, *, max_wait_s: float, cap_fn, est_fn=None):
        self._dq: "collections.deque[_Request]" = collections.deque()
        self._cond = threading.Condition()
        self._closed = False
        self._cap_fn = cap_fn
        # est_fn returns the bucket's expected run seconds, or None
        # while NO estimate exists (cold start) — the default knows
        # nothing, so it must say so rather than claim "instant"
        self._est_fn = est_fn if est_fn is not None else (lambda r: None)
        self.max_wait_s = float(max_wait_s)

    def put(self, req: _Request) -> None:
        with self._cond:
            if self._closed:
                raise RuntimeError("ReconService is closed")
            self._dq.append(req)
            self._cond.notify_all()

    def qsize(self) -> int:
        with self._cond:
            return len(self._dq)

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    def _gather(self, batch: List[_Request], cap: int) -> None:
        """Pull queued same-bucket requests into ``batch`` (FIFO order,
        call under the condition); other buckets keep their positions."""
        key = batch[0].key
        if len(batch) >= cap:
            return
        keep: "collections.deque[_Request]" = collections.deque()
        while self._dq and len(batch) < cap:
            r = self._dq.popleft()
            if r.key == key:
                batch.append(r)
            else:
                keep.append(r)
        keep.extend(self._dq)
        self._dq = keep

    def _wait_limit(self, batch: List[_Request], t0: float) -> float:
        """Absolute time until which this batch may keep waiting."""
        limit = t0 + self.max_wait_s
        est = self._est_fn(batch[0])
        for r in batch:
            if r.priority > 0:
                return t0            # latency-critical: ship now
            if r.deadline_s is not None:
                if est is None:
                    # cold start: no latency estimate exists yet, so
                    # deadline headroom cannot be computed — a 0
                    # estimate would let the batch wait out the whole
                    # deadline against a fictitious instant run. A
                    # deadline-carrying member therefore never waits
                    # until the bucket has completed traffic.
                    return t0
                # the wait must fit inside the member's deadline with
                # the (estimated) reconstruction still to run
                limit = min(limit, r.deadline_s - est)
        return limit

    def take(self) -> Optional[List[_Request]]:
        """The next formed batch, or None when closed AND drained."""
        with self._cond:
            while not self._dq:
                if self._closed:
                    return None
                self._cond.wait(0.05)
            # the forming window is a span (not the idle head wait):
            # its duration is the wait-for-peers cost and its args the
            # realized occupancy — the coalescing trade made visible
            with telemetry.span("batch.form") as sp:
                batch = [self._dq.popleft()]
                cap = max(1, int(self._cap_fn(batch[0])))
                self._gather(batch, cap)
                if len(batch) >= cap or self.max_wait_s <= 0.0:
                    sp.set(k=len(batch), cap=cap, waited=False)
                    return batch
                t0 = time.perf_counter()
                while len(batch) < cap and not self._closed:
                    now = time.perf_counter()
                    limit = self._wait_limit(batch, t0)
                    if now >= limit:
                        break
                    self._cond.wait(min(0.01, limit - now))
                    self._gather(batch, cap)
                sp.set(k=len(batch), cap=cap, waited=True)
                return batch


class _Bucket:
    """A cached (geometry, plan) pair: executor + per-bucket counters."""

    def __init__(self, geom: CTGeometry, plan: ReconPlan,
                 executor: PlanExecutor, programs_built: int,
                 config=None, source: str = "heuristic"):
        self.geom = geom
        self.plan = plan
        self.executor = executor
        self.programs_built = programs_built
        self.config = config          # TunedConfig provenance (or None)
        self.source = source
        self.latency = LatencyHistogram()
        self.requests = 0
        self.hits = 0
        # batching counters (mutated under the service lock): one
        # "dispatch" per executor call, however many requests it served
        self.cap = 1                   # effective max_batch
        self.dispatches = 0
        self.batched_requests = 0      # completed requests, all batches
        self.exec_total_s = 0.0        # wall summed once per dispatch
        self.batch_latency = LatencyHistogram()
        # streaming counters (mutated under the service lock): one
        # stream "dispatch" per folded chunk batch, ``stream_lanes``
        # its summed lane count; tail/hidden accumulate each closed
        # session's StreamReport for the overlap means in stats()
        self.stream_sessions = 0
        self.stream_closed = 0
        self.stream_dispatches = 0
        self.stream_lanes = 0
        self.stream_tail_s = 0.0
        self.stream_hidden = 0.0

    def snapshot(self) -> BucketStats:
        with self.executor._fleet_lock:
            fleet = dict(self.executor.fleet_totals)
        return BucketStats(
            devices=fleet["devices"],
            steals=fleet["stolen"],
            failovers=fleet["retried"],
            dead_devices=fleet["dead_devices"],
            variant=self.plan.variant,
            vol_shape_xyz=self.plan.vol_shape_xyz,
            n_proj=self.plan.n_proj,
            schedule=self.plan.schedule,
            requests=self.requests,
            hits=self.hits,
            misses=1,
            programs_built=self.programs_built,
            source=self.source,
            pipeline=self.executor.pipeline,
            completed=self.latency.count,
            p50_ms=_ms(self.latency.quantile(0.50)),
            p99_ms=_ms(self.latency.quantile(0.99)),
            mean_ms=_ms(self.latency.mean()),
            dispatches=self.dispatches,
            mean_occupancy=(round(self.batched_requests / self.dispatches,
                                  3) if self.dispatches else None),
            batch_p50_ms=_ms(self.batch_latency.quantile(0.50)),
            amortized_us_per_request=(
                round(self.exec_total_s / self.batched_requests * 1e6, 1)
                if self.batched_requests else None),
            max_batch=self.cap,
            streams=self.stream_sessions,
            streams_closed=self.stream_closed,
            stream_dispatches=self.stream_dispatches,
            stream_mean_lanes=(round(self.stream_lanes /
                                     self.stream_dispatches, 3)
                               if self.stream_dispatches else None),
            stream_tail_ms=(_ms(self.stream_tail_s / self.stream_closed)
                            if self.stream_closed else None),
            stream_hidden_fraction=(round(self.stream_hidden /
                                          self.stream_closed, 3)
                                    if self.stream_closed else None))


# --------------------------------------------------------------------------
# The service
# --------------------------------------------------------------------------

class ReconService:
    """Shape-bucketed reconstruction server over the shared ProgramCache.

    Parameters
    ----------
    max_inflight : worker-thread count == the bound on concurrently
        executing reconstructions (each holds at most one tile
        accumulator + the pipelined flush buffers on device). Requests
        beyond it wait in the FIFO queue — admission order is start
        order, so mixed-shape traffic shares the service fairly.
    pipeline : step-major flush discipline for bucket executors
        ("async" by default — the serving layer is exactly the caller
        that benefits from overlap; "sync" restores the in-thread
        double buffer).
    cache : optional private :class:`ProgramCache`; default is the
        process-shared one, so the service inherits programs compiled
        by any earlier façade call (and vice versa).
    tuning : the autotuner's persisted-winner store consulted by
        ``warmup(tune=True)`` and by ``variant="auto"`` requests — a
        ``runtime.autotune.TuningCache``, a cache-file path, or None
        (the default cache: ``$REPRO_TUNING_CACHE`` or
        ``~/.cache/repro/tuning.json``).
    devices : multi-device placement for every bucket. ``None`` (the
        default) keeps single-device execution; ``"all"`` spreads each
        reconstruction's step schedule over every local device; an int
        N uses the first N local devices; a device sequence or a
        :class:`~repro.runtime.executor.FleetConfig` is used as-is.
        Fleet buckets plan ``out="host"`` / ``schedule="step"`` by
        default (the fleet's required placement) and run with
        straggler-aware work stealing + per-step failover
        (``PlanExecutor.execute_fleet``); per-bucket steal/failover
        totals surface in :class:`ServiceStats`.
    fleet_max_retries : per-STEP failover budget of fleet buckets
        (``FleetConfig.max_retries_per_step``); ignored without
        ``devices``.
    max_batch : cross-request batching cap — how many SAME-bucket
        queued requests one executor dispatch may serve
        (``PlanExecutor.execute_batch``). 1 (the default) disables
        batching and preserves the exact pre-batching FIFO behavior.
        Tuned buckets whose measured ``TunedConfig.max_batch`` is
        smaller cap there instead (the operator's value stays the hard
        upper bound). Per-lane output is bit-identical to an unbatched
        request; only latency shaping changes.
    max_wait_ms : how long a PARTIAL batch may hold the queue head
        waiting for same-bucket peers. 0 (the default) never waits —
        batching then only coalesces requests that are ALREADY queued
        together (a burst). Deadline-aware: the wait never exceeds any
        member's ``deadline_ms`` headroom (minus the bucket's running
        latency estimate), and ``priority > 0`` members ship at once.
    """

    def __init__(self, *, max_inflight: int = 2, pipeline: str = "async",
                 cache: Optional[ProgramCache] = None, tuning=None,
                 devices=None, fleet_max_retries: int = 2,
                 max_batch: int = 1, max_wait_ms: float = 0.0):
        if max_inflight < 1:
            raise ValueError(f"max_inflight must be >= 1, got {max_inflight}")
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if max_wait_ms < 0:
            raise ValueError(f"max_wait_ms must be >= 0, got {max_wait_ms}")
        self.cache = cache if cache is not None else default_program_cache()
        self.pipeline = pipeline
        self.tuning = tuning
        self.fleet: Optional[FleetConfig] = as_fleet_config(
            devices, max_retries_per_step=fleet_max_retries)
        self.max_inflight = int(max_inflight)
        self.max_batch = int(max_batch)
        self.max_wait_ms = float(max_wait_ms)
        self._buckets: Dict[tuple, _Bucket] = {}
        self._lock = threading.Lock()          # buckets + counters
        # cap_fn/est_fn run under the former's condition: lock-free
        # bucket reads only (append-only dict + GIL), never the
        # service lock — put()/close() callers may hold it
        self._former = _BatchFormer(
            max_wait_s=self.max_wait_ms / 1e3,
            cap_fn=self._cap_for, est_fn=self._run_estimate)
        # streaming: a dedicated former + ONE worker thread, created
        # lazily by the first open_stream (most services never stream)
        self._stream_former: Optional[_BatchFormer] = None
        self._stream_thread: Optional[threading.Thread] = None
        self._closed = False
        self._workers = [
            threading.Thread(target=self._worker, name=f"recon-serve-{i}",
                             daemon=True)
            for i in range(self.max_inflight)]
        for t in self._workers:
            t.start()

    # ---- batching policy -------------------------------------------------

    def _effective_cap(self, config) -> int:
        """Batch cap for a bucket with tuned provenance ``config``: the
        service ``max_batch`` bounded by a MEASURED winner's
        ``max_batch`` (the tuner searched rb amortized — a measured 1
        means batching lost on this hardware and disables it here;
        heuristic configs carry no measurement and keep the default)."""
        cap = self.max_batch
        if cap > 1 and config is not None \
                and getattr(config, "source", "heuristic") != "heuristic":
            cap = min(cap, max(1, int(getattr(config, "max_batch", 1))))
        return cap

    def _cap_for(self, req: _Request) -> int:
        bucket = self._buckets.get(req.key)   # lock-free: see __init__
        if bucket is not None:
            return bucket.cap
        return self._effective_cap(req.config)

    def _run_estimate(self, req: _Request) -> Optional[float]:
        """Expected reconstruction seconds for deadline headroom math,
        or ``None`` while the bucket has NO completed traffic — the
        explicit cold-start contract: with no estimate, a deadline-
        carrying batch ships immediately instead of waiting out its
        deadline against an estimate of 0 (see ``_wait_limit``)."""
        bucket = self._buckets.get(req.key)   # lock-free: see __init__
        if bucket is None:
            return None
        return bucket.latency.mean()          # None while empty

    # ---- bucketing -------------------------------------------------------

    def _tuning_cache(self, tuning=None):
        from repro.runtime.autotune import as_tuning_cache
        return as_tuning_cache(tuning if tuning is not None
                               else self.tuning)

    def _plan(self, geom: CTGeometry, options: Dict):
        """Façade options -> (plan, TunedConfig-or-None) (pure;
        validation errors raise here, in the submitting thread, not in
        a worker). ``variant="auto"`` / ``tuning=`` resolve through the
        tuning cache (lookup only — a miss is the heuristic config)."""
        opts = dict(options)
        variant = opts.pop("variant", None)
        tuning = opts.pop("tuning", None)
        solver = opts.pop("solver", "none")
        precision = opts.pop("precision", "f32")
        # per-request loop knobs ride the request, not the bucket
        solver_kw = {k: opts.pop(k) for k in
                     ("n_iters", "relax", "x0", "tv_weight", "tv_inner",
                      "oversample") if k in opts}
        if tuning is None:
            # ONE read (under the lock warmup(tune=True) writes under):
            # both decisions below must see the same store, or a
            # request racing a tuned warmup could resolve half-tuned
            with self._lock:
                tuning = self.tuning
        if variant is None:
            # a tuning-enabled service (constructed with tuning=, or
            # warmed with tune=True) defaults requests to the tuned
            # resolution so they land in the tuned buckets; otherwise
            # keep the façade's heuristic default
            variant = "auto" if tuning is not None else "algorithm1_mp"
        kw = dict(
            nb=opts.pop("nb", 8), interpret=opts.pop("interpret", None),
            tiling=opts.pop("tiling", None),
            memory_budget=opts.pop("memory_budget", None),
            proj_batch=opts.pop("proj_batch", None),
            out=opts.pop("out", None), schedule=opts.pop("schedule", None),
            precision=precision)
        if solver != "none":
            # solver buckets: the loop owns a device-resident volume
            # and pairs FP with BP — no fleet sharding, and tuned
            # resolution is method-aware (autotune(method=...)), not
            # the FDK lookup, so requests resolve heuristically here
            if self.fleet is not None:
                raise ValueError(
                    "iterative solver requests run single-device (the "
                    "solve loop owns the volume); they cannot ride a "
                    "fleet service (ReconService(devices=...))")
            if variant == "auto":
                variant = "algorithm1_mp"
            tuning = None
            kw["solver"] = solver
            kw["out"] = "device"
        ingest = opts.pop("ingest", "offline")
        if ingest != "offline":
            # stream plans resolve heuristically (TunedConfig carries no
            # ingest axis) and are chunk-major by construction; offline
            # requests never carry the key, so the tuning-cache request
            # key is unchanged by its existence (the planner validates
            # the value)
            if variant == "auto":
                variant = "algorithm1_mp"
            tuning = None
            kw["ingest"] = ingest
        if self.fleet is not None:
            # fleet execution requires host accumulation over the step
            # schedule; default unset knobs to that placement (explicit
            # contrary choices fail fast in PlanExecutor's validation)
            kw["out"] = kw["out"] or "host"
            kw["schedule"] = kw["schedule"] or "step"
        if solver == "none" and solver_kw:
            raise ValueError(
                f"solver knobs {sorted(solver_kw)} need an iterative "
                f"request (pass solver='sart'|'os_sart'|'cgls'|"
                f"'fista_tv')")
        if variant == "auto" or tuning is not None:
            from repro.runtime.autotune import resolve_config
            cfg = resolve_config(geom, variant,
                                 cache=self._tuning_cache(tuning),
                                 **kw, **opts)
            return cfg.build_plan(geom), cfg, None
        return (_build_plan(geom, variant, **kw, **opts), None,
                solver_kw or None)

    @staticmethod
    def _source_of(config) -> str:
        if config is None or config.source == "heuristic":
            return "heuristic"
        return "tuned-" + config.source      # "measured" | "cache"

    def _bucket(self, geom: CTGeometry, plan: ReconPlan,
                config=None) -> _Bucket:
        """Find-or-create the bucket for ``(geom, plan.bucket_key)``.

        Creation happens under the service lock so the warm-up compile
        count is attributable to THIS bucket even with concurrent
        workers: the cache-miss delta across ``PlanExecutor.warm`` is
        the bucket's ``programs_built``. ``config`` (a resolved
        ``TunedConfig``) carries the tuned pipeline choice and the
        choice provenance surfaced per bucket in :class:`ServiceStats`.
        """
        key = (geom, plan.bucket_key)
        with self._lock:
            bucket = self._buckets.get(key)
            if bucket is not None:
                bucket.hits += 1
                if config is not None and config.source != "heuristic" \
                        and bucket.source == "heuristic":
                    # a measured winner that differs only in executor-
                    # level knobs (pipeline/depth — not part of the
                    # bucket_key) lands on an existing heuristic
                    # bucket: upgrade it in place rather than dropping
                    # the tuned choice. In-flight requests finish on
                    # the old executor (bit-identical output either
                    # way); new requests get the tuned one.
                    ex = PlanExecutor(
                        geom, plan, cache=self.cache,
                        pipeline=config.pipeline,
                        pipeline_depth=config.pipeline_depth,
                        tuned=config, fleet=self.fleet)
                    ex.warm()
                    cap = self._effective_cap(config)
                    if cap > 1 and ex.supports_request_batching:
                        ex.warm_batch(cap)
                    bucket.executor = ex
                    bucket.config = config
                    bucket.source = self._source_of(config)
                    bucket.cap = cap
                return bucket
            misses_before = self.cache.stats()["misses"]
            tuned = config is not None and config.source != "heuristic"
            if plan.solver != "none":
                # iterative bucket: the persistent FP+BP pairing, warm
                # like any other bucket (normalizers + every program a
                # solve needs compile HERE, attributed to this bucket;
                # warm requests then iterate without compiling)
                from .solvers import IterativeExecutor
                ex = IterativeExecutor(geom, plan, self.cache,
                                       pipeline=self.pipeline)
            else:
                ex = PlanExecutor(
                    geom, plan, cache=self.cache,
                    pipeline=config.pipeline if tuned else self.pipeline,
                    pipeline_depth=(config.pipeline_depth if tuned else 2),
                    tuned=config if tuned else None, fleet=self.fleet)
            ex.warm()
            cap = self._effective_cap(config)
            if cap > 1 and ex.supports_request_batching:
                # the first FORMED batch must compile nothing either
                ex.warm_batch(cap)
            built = self.cache.stats()["misses"] - misses_before
            bucket = _Bucket(geom, plan, ex, programs_built=built,
                             config=config, source=self._source_of(config))
            bucket.cap = cap
            self._buckets[key] = bucket
            return bucket

    def warmup(self, geometries: Iterable[CTGeometry], *,
               tune: bool = False, tune_budget_s: float = 20.0,
               **options) -> ServiceStats:
        """Pre-compile (and optionally pre-TUNE) the buckets a
        deployment will serve.

        One bucket per geometry, same options for all (call repeatedly
        for mixed option sets). After warmup, the first real request of
        each warmed shape is a bucket hit with zero new compiles.

        ``tune=True`` runs the measured autotuner
        (``runtime.autotune.autotune``) per bucket before any traffic:
        a persisted winner for this hardware resolves with ZERO
        re-measurement (bucket ``source == "tuned-cache"``), otherwise
        the search runs under ``tune_budget_s`` wall seconds per bucket
        and the winner is persisted (``source == "tuned-measured"``).
        Tuning shares this service's ProgramCache, so every program the
        winning config needs is already compiled when the bucket opens.
        """
        for geom in geometries:
            if tune:
                from repro.runtime.autotune import autotune
                opts = dict(options)
                cache = self._tuning_cache(opts.pop("tuning", None))
                with self._lock:
                    if self.tuning is None:
                        # later requests must resolve through the SAME
                        # cache to land in the tuned buckets
                        self.tuning = cache
                cfg = autotune(geom, opts.pop("variant", "auto"),
                               budget_s=tune_budget_s, cache=cache,
                               program_cache=self.cache, **opts)
                self._bucket(geom, cfg.build_plan(geom), config=cfg)
            else:
                plan, cfg, _skw = self._plan(geom, options)
                self._bucket(geom, plan, config=cfg)
        return self.stats()

    # ---- request path ----------------------------------------------------

    def submit(self, projections: jnp.ndarray, geom: CTGeometry, *,
               deadline_ms: Optional[float] = None, priority: int = 0,
               **options) -> "Future":
        """Enqueue one reconstruction; returns a ``Future`` whose
        ``result()`` is the volume (same contract as the façade the
        options mirror — ``fdk_reconstruct``). FIFO across callers.

        ``deadline_ms`` (relative to now) and ``priority`` shape BATCH
        FORMING only — they never reorder the FIFO queue: a deadline
        caps how long a partial batch this request joins may wait for
        peers, and ``priority > 0`` marks it latency-critical (any
        batch it joins dispatches immediately). Both are no-ops when
        batching is off (``max_batch == 1``)."""
        plan, config, solver_kw = self._plan(geom, options)
        # (validation above happens in the submitting thread)
        if deadline_ms is not None and deadline_ms < 0:
            raise ValueError(
                f"deadline_ms must be >= 0, got {deadline_ms}")
        with telemetry.span("plan.bucket_key"):
            key = (geom, plan.bucket_key)
        trace_id = telemetry.new_trace_id()
        telemetry.instant("request.submit", trace_id=trace_id,
                          variant=plan.variant, priority=int(priority))
        fut: Future = Future()
        fut.trace_id = trace_id      # exposed to the caller for linkage
        req = _Request(
            fut=fut, projections=projections, geom=geom, plan=plan,
            config=config, key=key,
            deadline_s=(None if deadline_ms is None
                        else time.perf_counter() + deadline_ms / 1e3),
            priority=int(priority), solver_kw=solver_kw,
            trace_id=trace_id)
        # put() checks closed under the former's condition, so a
        # request either raises here or is guaranteed a consumer
        # (workers drain the queue to empty before honoring close)
        self._former.put(req)
        return fut

    def reconstruct(self, projections: jnp.ndarray, geom: CTGeometry,
                    **options):
        """Synchronous request: ``submit(...).result()``."""
        return self.submit(projections, geom, **options).result()

    def _worker(self) -> None:
        while True:
            batch = self._former.take()
            if batch is None:
                return
            live = [r for r in batch
                    if r.fut.set_running_or_notify_cancel()]
            if not live:
                continue
            try:
                head = live[0]
                bucket = self._bucket(head.geom, head.plan,
                                      config=head.config)
                k = len(live)
                with self._lock:
                    bucket.requests += k
                t0 = time.perf_counter()
                # the dispatch span carries EVERY member's trace id —
                # the k-wide batched dispatch links back to all k
                # request traces (request.submit instants)
                with telemetry.span(
                        "service.dispatch", k=k,
                        variant=bucket.plan.variant,
                        trace_ids=[r.trace_id for r in live]):
                    if k == 1:
                        results = [bucket.executor.reconstruct(
                            head.projections, **(head.solver_kw or {}))]
                    elif bucket.executor.supports_request_batching:
                        # ONE dispatch stream serves all k lanes —
                        # bit-identical per lane to the k==1 path
                        results = bucket.executor.execute_batch(
                            [r.projections for r in live])
                    else:
                        # chunk-major and solver buckets can't batch: the
                        # formed group still runs back-to-back on one
                        # worker (each solve keeps its own request knobs)
                        results = [bucket.executor.reconstruct(
                            r.projections, **(r.solver_kw or {}))
                                   for r in live]
                wall = time.perf_counter() - t0
                # streamed accounting: every member's service time IS
                # the batch wall (they complete together); the batch
                # itself lands once in the occupancy/amortized counters
                for _ in live:
                    bucket.latency.record(wall)
                bucket.batch_latency.record(wall)
                with self._lock:
                    bucket.dispatches += 1
                    bucket.batched_requests += k
                    bucket.exec_total_s += wall
                for r, vol in zip(live, results):
                    r.fut.set_result(vol)
            except BaseException as exc:
                for r in live:
                    if not r.fut.done():
                        r.fut.set_exception(exc)

    # ---- streaming sessions ----------------------------------------------

    def open_stream(self, geom: CTGeometry, *, priority: int = 0,
                    max_pending_chunks: int = 2,
                    **options) -> "StreamSession":
        """Open an online reconstruction session (the service-level twin
        of ``PlanExecutor.open_stream``): push projections as the
        scanner produces them, ``close()`` returns the volume —
        bit-identical to the offline chunk-major reconstruction of the
        same views.

        Sessions bucket on ``(geometry, plan.bucket_key)`` exactly like
        requests (``ingest="stream"`` is part of the key, so stream and
        offline traffic never share a bucket) and reuse the bucket's
        warmed programs. Concurrent same-bucket sessions at the same
        rotation phase coalesce: the stream worker folds up to
        ``max_batch`` ready chunk-``c`` arrivals through ONE batched
        dispatch (``ProgramCache.program`` with ``rb``), per-lane
        bit-identical to an unbatched session. ``max_pending_chunks``
        bounds the per-session arrival queue (``push`` blocks beyond
        it); ``priority > 0`` ships this session's chunks without
        waiting for peers. Options mirror ``submit`` (``proj_batch``
        defaults to ~n_proj/8 views per chunk, the streaming grain).
        """
        if self.fleet is not None:
            raise ValueError(
                "streaming sessions do not compose with fleet "
                "execution; construct the service without devices=")
        opts = dict(options)
        opts["ingest"] = "stream"
        if opts.get("proj_batch") is None:
            # a stream needs a real chunk grain: ~8 chunks per rotation
            # (bounded below by nb so the planner's rounding is a no-op)
            opts["proj_batch"] = max(int(opts.get("nb", 8)),
                                     geom.n_proj // 8)
        plan, config, _skw = self._plan(geom, opts)
        bucket = self._bucket(geom, plan, config=config)
        self._ensure_stream_worker()
        with self._lock:
            bucket.stream_sessions += 1
        return StreamSession(self, bucket, priority=int(priority),
                             max_pending_chunks=max_pending_chunks)

    def _ensure_stream_worker(self) -> None:
        with self._lock:
            if self._closed:
                raise RuntimeError("ReconService is closed")
            if self._stream_former is not None:
                return
            self._stream_former = _BatchFormer(
                max_wait_s=self.max_wait_ms / 1e3,
                cap_fn=lambda w: max(1, self.max_batch),
                est_fn=lambda w: None)   # chunk folds carry no deadlines
            self._stream_thread = threading.Thread(
                target=self._stream_worker, name="recon-stream",
                daemon=True)
            self._stream_thread.start()

    def _stream_worker(self) -> None:
        former = self._stream_former
        while True:
            batch = former.take()
            if batch is None:
                return
            # the fold-order contract: chunk c of a session may only
            # fold when it IS that session's next_fold. Out-of-order
            # pushes can complete chunk c+1 first — its work item
            # requeues until chunk c lands (whose own completion event
            # wakes this worker again).
            runnable: List[_StreamWork] = []
            for w in batch:
                if w.session._core.next_fold == w.chunk:
                    runnable.append(w)
                else:
                    try:
                        former.put(w)
                    except RuntimeError as exc:
                        w.session._core.fail(exc)
            if not runnable:
                time.sleep(0.002)      # only deferred items are queued
                continue
            try:
                self._fold_stream_chunk(runnable)
            except BaseException as exc:
                for w in runnable:
                    w.session._core.fail(exc)

    def _fold_stream_chunk(self, works: List[_StreamWork]) -> None:
        """Fold one ready view-chunk for k same-bucket sessions.

        k == 1 delegates to the session core's own ``fold`` (which
        overlaps the next chunk's filtering and self-times). k > 1
        stacks the k filtered chunks on a leading lane axis and runs ONE
        rb-lane program per plan step — vmap adds a batch axis and never
        reassociates a lane's reduction, so each lane's accumulator
        receives exactly the unbatched partial (the
        ``PlanExecutor.execute_batch`` argument, per chunk)."""
        c = works[0].chunk
        bucket = works[0].session._bucket
        cores = [w.session._core for w in works]
        with telemetry.span("service.stream_dispatch", chunk=c,
                            k=len(cores),
                            trace_ids=[w.session.trace_id
                                       for w in works]):
            self._fold_stream_chunk_inner(c, bucket, cores)
        with self._lock:
            bucket.stream_dispatches += 1
            bucket.stream_lanes += len(cores)

    def _fold_stream_chunk_inner(self, c, bucket, cores) -> None:
        if len(cores) == 1:
            cores[0].fold(c)
        else:
            ex = bucket.executor
            plan = bucket.plan
            t0 = time.perf_counter()
            pairs = [core.filtered(c) for core in cores]
            for core in cores:
                core.prefilter(c + 1)  # overlap next chunk's filtering
            img_b = jnp.stack([img for img, _ in pairs])
            mat_c = pairs[0][1]        # same geometry -> same matrices
            for i, step in enumerate(plan.steps):
                prog = ex._program(step.variant, step.call_shape,
                                   rb=len(cores))
                out_b = prog(img_b, mat_c, ex._origin(step))
                for r, core in enumerate(cores):
                    core.accept_part(i, out_b[r])
            wall = time.perf_counter() - t0
            for core in cores:
                core.chunk_done(c)
                core.add_busy(wall)

    # ---- lifecycle / introspection ---------------------------------------

    def stats(self) -> ServiceStats:
        with self._lock:
            live = list(self._buckets.values())
            buckets = tuple(b.snapshot() for b in live)
            s_open = sum(b.stream_sessions for b in live)
            s_closed = sum(b.stream_closed for b in live)
            s_tail = sum(b.stream_tail_s for b in live)
            s_hidden = sum(b.stream_hidden for b in live)
        overall = LatencyHistogram.merged(b.latency for b in live)
        dispatches = sum(b.dispatches for b in buckets)
        completed = sum(b.completed for b in buckets)
        return ServiceStats(
            requests=sum(b.requests for b in buckets),
            bucket_hits=sum(b.hits for b in buckets),
            bucket_misses=len(buckets),
            buckets=buckets,
            cache=self.cache.stats(),
            max_inflight=self.max_inflight,
            queued=self._former.qsize(),
            p50_ms=_ms(overall.quantile(0.50)),
            p99_ms=_ms(overall.quantile(0.99)),
            max_batch=self.max_batch,
            dispatches=dispatches,
            mean_occupancy=(round(completed / dispatches, 3)
                            if dispatches else None),
            streams=s_open,
            stream_tail_ms=(_ms(s_tail / s_closed) if s_closed else None),
            stream_hidden_fraction=(round(s_hidden / s_closed, 3)
                                    if s_closed else None))

    def close(self, wait: bool = True) -> None:
        """Stop accepting requests; drain workers (idempotent).
        Already-queued requests complete — workers exit only once the
        queue is empty."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        # outside the service lock: the former's condition is also
        # taken by forming workers that read buckets (lock ordering)
        self._former.close()
        if self._stream_former is not None:
            self._stream_former.close()
        if wait:
            for t in self._workers:
                t.join()
            if self._stream_thread is not None:
                self._stream_thread.join()

    def __enter__(self) -> "ReconService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class StreamSession:
    """One open projection stream bound to a service bucket.

    ``push(views)`` hands view rows to the session's
    :class:`~repro.runtime.executor.StreamingExecutor` core; each
    completed view-chunk queues a :class:`_StreamWork` to the service's
    stream worker, which folds same-phase chunks of concurrent
    same-bucket sessions through one batched dispatch. ``close()``
    blocks for the tail folds and returns the volume; the session's
    :class:`~repro.runtime.executor.StreamReport` then lands in the
    bucket's overlap counters (``ServiceStats.stream_tail_ms`` /
    ``stream_hidden_fraction``)."""

    def __init__(self, service: ReconService, bucket: _Bucket, *,
                 priority: int = 0, max_pending_chunks: int = 2):
        self._service = service
        self._bucket = bucket
        self._priority = int(priority)
        self._key_base = (bucket.geom, bucket.plan.bucket_key)
        # per-session trace identity: carried by every batched chunk
        # dispatch this session participates in (service.stream_dispatch
        # spans), the stream twin of _Request.trace_id
        self.trace_id = telemetry.new_trace_id("stream")
        telemetry.instant("stream.open", trace_id=self.trace_id,
                          variant=bucket.plan.variant)
        self._core = bucket.executor.open_stream(
            max_pending_chunks=max_pending_chunks, on_ready=self._ready)

    def _ready(self, chunk: int) -> None:
        """StreamingExecutor callback: chunk complete -> queue its fold.
        Runs on the pushing thread with the core's condition RELEASED
        (the core guarantees it), so the former's put is safe here."""
        work = _StreamWork(session=self, chunk=chunk,
                           key=self._key_base + (chunk,),
                           priority=self._priority)
        try:
            self._service._stream_former.put(work)
        except RuntimeError as exc:      # service closed mid-stream
            self._core.fail(exc)

    def push(self, views, start: Optional[int] = None) -> None:
        """Deliver view rows (blocks only on arrival-queue backpressure)."""
        self._core.push(views, start=start)

    @property
    def report(self):
        """The core's :class:`StreamReport` (None until closed)."""
        return self._core.report

    def close(self):
        """Finish the stream and return the volume (nz, ny, nx)."""
        vol = self._core.close()
        rep = self._core.report
        with self._service._lock:
            self._bucket.stream_closed += 1
            if rep is not None:
                self._bucket.stream_tail_s += rep.tail_s
                self._bucket.stream_hidden += rep.hidden_fraction
        return vol

    def __enter__(self) -> "StreamSession":
        return self

    def __exit__(self, *exc) -> None:
        if exc and exc[0] is not None:
            self._core.fail(exc[1])
        elif not self._core._ingest_closed:   # tolerate explicit close()
            self.close()
