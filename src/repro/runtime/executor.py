"""Compile + execute stages of the plan/compile/execute architecture.

``runtime.planner`` produces a pure :class:`~repro.runtime.planner.ReconPlan`;
this module turns it into arrays:

  * :class:`ProgramCache` — the **compile** stage. One builder,
    :meth:`ProgramCache.program`, keyed ``(variant, call_shape, nb,
    dtype, interpret, options, grid, rb)``: the kernel placed at a
    traced box origin (``core.variants.at_origin``), under a
    ``lax.scan`` over the projection-chunk axis when ``grid`` is set
    (the step-major megaprogram) and a leading request ``vmap`` when
    ``rb`` is. Interior tiles of equal shape, repeated ``reconstruct``
    calls and a fleet's steps on any device share one program.
    Hits/misses are introspectable (``cache.stats()``), and a
    module-level default cache persists across executors so repeated
    façade calls stay warm.

  * :class:`PlanExecutor` — the **execute** stage. One step walk
    (``_walk``) serves every single-process loop order. The default
    (``plan.schedule == "step"``) is STEP-MAJOR: for each tile step,
    one scan megaprogram carries the tile accumulator across ALL
    projection chunks device-resident and the result crosses to the
    host exactly once — O(vol) device->host volume traffic and one
    dispatch per step, vs the chunk-major O(n_chunks x vol) traffic and
    O(n_chunks x n_steps) dispatches. Chunk filtering is hoisted into a
    filter-once producer that feeds every step. ``schedule == "chunk"``
    walks the steps once per projection chunk (kept as the parity
    oracle and for workloads where the filtered projection set must
    stay chunk-bounded on device), with input-side double buffering:
    the next chunk's filtering is dispatched before the current chunk's
    programs. Step outputs land through one :class:`_Accumulator`:
    host placement double-buffered (the ``np.asarray`` device->host
    copy of step ``n`` is issued only after step ``n+1``'s program has
    been dispatched), or with ``pipeline="async"`` a depth-bounded
    :class:`_AsyncFlushQueue` flusher thread that performs the
    ``block_until_ready`` + host accumulate off the dispatch thread
    (the serving layer, ``runtime/service.py``, runs this by default);
    device placement a donated in-place add. ``execute_fleet`` is the
    one path across devices: per-device step queues with work stealing
    and failover over the same step programs. The executor can also be
    built straight from an autotuned winner:
    :meth:`PlanExecutor.from_config` consumes a
    ``runtime.autotune.TunedConfig`` (the measured per-hardware choice
    of schedule/pipeline/variant/tile/chunk knobs).

  * :class:`StreamingExecutor` — ONLINE execution
    (``PlanExecutor.open_stream`` on an ``ingest="stream"`` plan):
    projections are pushed as the scanner produces them and each view
    chunk is filtered + folded into the per-step device accumulators
    the moment it completes, so reconstruction wall hides behind
    acquisition; the chunk-index fold order makes ``close()``
    bit-identical to the offline chunk-major ``reconstruct``.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import queue
import threading
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import backproject as bp
from repro.core.filtering import fdk_filter_chunk
from repro.core.geometry import CTGeometry, projection_matrices
from repro.core.tiling import (
    TileSpec, pad_projection_batch, plan_proj_chunks,
)
from repro.core.variants import at_origin, get_spec
from repro.runtime import telemetry
from repro.runtime.planner import (
    PlanStep, ReconPlan, StepMajorSchedule, build_step_major,
    partition_steps, resolve_tile_variant,
)
from repro.runtime.straggler import FleetStragglerBoard


# --------------------------------------------------------------------------
# Compile: the keyed jit-program cache
# --------------------------------------------------------------------------

def _plan_dtype(plan: ReconPlan) -> str:
    """ProgramCache dtype key of a plan's precision axis."""
    return "bfloat16" if plan.precision == "bf16" else "float32"


def _precision_adapter(variant: str, dtype: str):
    """Input-side precision transform for one kernel program, or None.

    ``dtype == "bfloat16"`` implements the plan-level ``precision=
    "bf16"`` contract: projection samples are rounded to bfloat16 on
    the way into the kernel (the reduced-precision data path — the
    bytes every gather streams), while the per-view matrices, the
    interpolation weights derived from them, and every accumulator stay
    float32. Pure-JAX kernels receive the bf16 array directly (mixed
    bf16xf32 arithmetic promotes to f32, so the multiply-accumulate
    chain is f32 over bf16-rounded samples); Pallas kernels receive the
    bf16-rounded values upcast back to f32 — identical rounding, but
    the kernel's refs keep the dtype its block specs declare. Either
    way the program's OUTPUT is float32 (the builders re-assert it), so
    downstream accumulation never narrows.
    """
    if str(dtype) == "float32":
        return None
    if str(dtype) != "bfloat16":
        raise ValueError(
            f"unsupported program dtype {dtype!r}: 'float32' or "
            f"'bfloat16'")
    if get_spec(variant).backend == "pallas":
        return lambda img: img.astype(jnp.bfloat16).astype(jnp.float32)
    return lambda img: img.astype(jnp.bfloat16)


def _with_precision(fn, variant: str, dtype: str):
    """Wrap a kernel fn with the precision adapter (f32 = pass-through)."""
    cast = _precision_adapter(variant, dtype)
    if cast is None:
        return fn

    def wrapped(img, mat, shape, **opts):
        return fn(cast(img), mat, shape, **opts).astype(jnp.float32)

    return wrapped


def _over_grid(call: Callable, shape, n_chunks: int, jittable: bool):
    """``call`` summed over the stacked chunk axis of its inputs: one
    ``lax.scan`` carrying the accumulator on device, or for a
    non-jittable kernel a python chunk loop with a donated accumulator."""
    if jittable:
        def scanned(img_s, mat_s, origin):
            def body(acc, xs):
                img_c, mat_c = xs
                return acc + call(img_c, mat_c, origin), None
            acc, _ = jax.lax.scan(
                body, jnp.zeros(shape, jnp.float32), (img_s, mat_s))
            return acc
        return scanned

    def looped(img_s, mat_s, origin):
        acc = None
        for c in range(n_chunks):
            part = call(img_s[c], mat_s[c], origin)
            acc = part if acc is None else _acc_add(acc, part)
        return acc
    return looped


def _over_lanes(call: Callable, rb: int, jittable: bool):
    """``call`` over a leading request axis of ``img`` (``mat`` and
    ``origin`` shared): a ``vmap``, or a stacked loop over lanes."""
    if jittable:
        return jax.vmap(call, in_axes=(0, None, None))
    return lambda img_b, mat, origin: jnp.stack(
        [call(img_b[r], mat, origin) for r in range(rb)])


class ProgramCache:
    """Keyed cache of jitted programs.

    Every back-projection program comes from :meth:`program`, keyed
    ``("kernel", variant, call_shape, nb, dtype, interpret, options,
    grid, rb)``; :meth:`get_or_build` is the cache under it, which the
    solvers' forward-projection programs use with keys of their own.
    The cache is thread-safe and introspectable: ``stats()`` reports
    hits, misses (== programs built), and the live key count.
    """

    def __init__(self):
        self._programs: Dict[tuple, Callable] = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get_or_build(self, key: tuple, builder: Callable[[], Callable]):
        with self._lock:
            prog = self._programs.get(key)
            if prog is not None:
                self.hits += 1
                return prog
        # build outside the lock (tracing can be slow); last writer wins.
        # The span wraps builder() and nothing else, so "compile" span
        # count == self.misses EXACTLY (both tick once per build, even
        # when two threads race on the same key).
        with telemetry.span("compile", cat="compile", key=repr(key)):
            prog = builder()
        with self._lock:
            self._programs.setdefault(key, prog)
            self.misses += 1
            return self._programs[key]

    def program(self, variant: str, call_shape: Tuple[int, int, int],
                nb: int, dtype: str, interpret: bool,
                options: Tuple = (), *,
                grid: Optional[Tuple[int, int]] = None,
                rb: Optional[int] = None) -> Callable:
        """Jitted ``prog(img, mat, origin=None) -> vol_t(call_shape)``.

        ``origin`` is the call box's voxel origin ``(i0, j0, k0)``, a
        (3,) float32 array placed inside the program
        (:func:`~repro.core.variants.at_origin`); ``None`` traces the
        kernel as it is, the untiled plan's one call. A traced origin
        makes one program serve every same-shape step on any device, so
        a fleet step and a single-device step share this key.

        ``grid=(n_chunks, chunk_size)`` takes the STACKED chunk axes
        ``(n_chunks, chunk_size, ...)`` and carries the call-shape
        accumulator across them in one ``lax.scan``: the step-major
        megaprogram, one host crossing per step. ``rb`` adds a leading
        request axis to ``img`` (one ``vmap`` lane per request over the
        shared ``mat``); a lane never reassociates its reduction, so
        each lane is bit-identical to the ``rb=None`` program. Kernels
        that read concrete matrix values at trace time
        (``KernelSpec.jittable=False``) get a python loop over chunks
        (with a donated accumulator) and lanes instead, not wrapped in
        ``jax.jit``.
        """
        key = ("kernel", variant, tuple(call_shape), int(nb), str(dtype),
               bool(interpret), tuple(options),
               None if grid is None else tuple(int(g) for g in grid),
               None if rb is None else int(rb))

        def build():
            spec = get_spec(variant)
            opts = spec.resolve_options(
                {**dict(options), "nb": int(nb), "interpret": bool(interpret)})
            shape = tuple(call_shape)
            fn = at_origin(spec, _with_precision(spec.fn, variant, dtype))

            def call(img, mat, origin):
                return fn(img, mat, shape, origin, **opts)

            if grid is not None:
                call = _over_grid(call, shape, int(grid[0]), spec.jittable)
            if rb is not None:
                call = _over_lanes(call, int(rb), spec.jittable)

            def prog(img, mat, origin=None):
                return call(img, mat, origin)
            return jax.jit(prog) if spec.jittable else prog

        return self.get_or_build(key, build)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "programs": len(self._programs)}

    def clear(self) -> None:
        with self._lock:
            self._programs.clear()
            self.hits = self.misses = 0


_DEFAULT_CACHE = ProgramCache()


def default_program_cache() -> ProgramCache:
    """The process-wide cache shared by every executor (and façade)."""
    return _DEFAULT_CACHE


# --------------------------------------------------------------------------
# Execute: placement primitives
# --------------------------------------------------------------------------

# out="device" placement: donated dynamic read-add-update so each tile
# accumulates into the volume buffer in place — NOT vol.at[].add outside
# jit, which would copy the full volume once per tile.
@functools.partial(jax.jit, donate_argnums=0)
def _place_device_add(vol, tile, idx):
    org = (idx[0], idx[1], idx[2])
    cur = jax.lax.dynamic_slice(vol, org, tile.shape)
    return jax.lax.dynamic_update_slice(vol, cur + tile, org)


# donated-carry accumulation for the non-jittable scan fallback: the
# accumulator buffer is reused across chunk iterations instead of
# allocating a fresh volume per chunk.
@functools.partial(jax.jit, donate_argnums=0)
def _acc_add(acc, part):
    return acc + part


def _pad_rows(img: jnp.ndarray, mat: jnp.ndarray, n_rows: int):
    """Pad projections + matrices to ``n_rows`` leading rows — zero
    images (back-projection is linear: they add nothing) paired with
    :func:`_pad_mats`' repeated-last-matrix padding."""
    pad = int(n_rows) - img.shape[0]
    if pad <= 0:
        return img, mat
    img = jnp.concatenate(
        [img, jnp.zeros((pad,) + img.shape[1:], img.dtype)], axis=0)
    return img, _pad_mats(mat, int(n_rows))


def _stack_chunks(img_p: jnp.ndarray, mat_p: jnp.ndarray,
                  sched: StepMajorSchedule):
    """Reshape padded projections to the scan grid ``(n_chunks,
    chunk_size, ...)``, zero-padding the tail chunk's slack rows."""
    img_p, mat_p = _pad_rows(img_p, mat_p, sched.n_scan)
    img_s = img_p.reshape((sched.n_chunks, sched.chunk_size)
                          + img_p.shape[1:])
    mat_s = mat_p.reshape(sched.n_chunks, sched.chunk_size, 3, 4)
    return img_s, mat_s


def _grid(sched: StepMajorSchedule) -> Tuple[int, int]:
    """The scan grid ``(n_chunks, chunk_size)`` of a step-major
    schedule: the ``grid`` of its step programs."""
    return (sched.n_chunks, sched.chunk_size)


def _to_native(vol_t):
    """(nx, ny, nz) -> (nz, ny, nx): a free numpy view of a host volume
    (it may exceed device memory, never round-trip it), else on device."""
    if isinstance(vol_t, np.ndarray):
        return np.transpose(vol_t, (2, 1, 0))
    return bp.volume_to_native(vol_t)


class _AsyncFlushQueue:
    """Depth-bounded device->host flush pipeline (the "real streams"
    seam): step N's accumulator flush overlaps step N+1's dispatch.

    The executor enqueues one step's ``(host volume, slices, device
    piece)`` writes right after dispatching that step's program and
    moves on; a single flusher thread dequeues in FIFO order, calls
    ``jax.block_until_ready`` — the ONLY place the pipeline blocks on
    the device — and accumulates the ``np.asarray`` copy into the host
    volume. ``depth`` bounds how many steps' device outputs may be live
    at once (double-buffered by default: the scanning step plus the
    flushing one); a full queue applies backpressure to the dispatcher.
    Exactly one thread writes the host volumes, in the order the writes
    were enqueued, so the result is bit-identical to the sequential
    flush.
    """

    def __init__(self, depth: int = 2):
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, int(depth)))
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._drain, name="recon-flush", daemon=True)
        self._thread.start()

    def _drain(self) -> None:
        while True:
            writes = self._q.get()
            try:
                if writes is None:
                    return
                if self._error is None:   # keep consuming after failure
                    with telemetry.span("flush", n_writes=len(writes)):
                        for tgt, sl, piece in writes:
                            piece = jax.block_until_ready(piece)
                            tgt[sl] += np.asarray(piece)
            except BaseException as exc:   # surfaced at put()/close()
                self._error = exc
            finally:
                self._q.task_done()

    def put(self, writes) -> None:
        """Enqueue one step's writes; blocks only when ``depth`` steps
        are already in flight (backpressure, not device sync)."""
        if self._error is not None:
            raise self._error
        self._q.put(writes)

    def close(self) -> None:
        """Drain the queue, join the flusher, re-raise any failure."""
        self._q.put(None)
        self._thread.join()
        if self._error is not None:
            raise self._error


class _Accumulator:
    """Where a single-process walk's step outputs land: ``n_vols``
    volumes (one per request lane) in the plan's placement.

    ``put(writes)`` takes one step's ``(lane, volume slices, device
    piece)`` writes right after the step's program is dispatched;
    ``close()`` finishes every pending add and returns the volumes
    (transposed layout). Per voxel the adds happen in ``put`` order, so
    the three disciplines give the same bits:

      * host, ``pipeline="sync"``: a double buffer, step n-1's pieces
        are added only after step n has been dispatched, so the copy
        overlaps compute;
      * host, ``pipeline="async"``: the :class:`_AsyncFlushQueue`
        flusher thread does the adds;
      * device: the donated :func:`_place_device_add`. A whole-volume
        first write IS the volume (the untiled plan's one call): no
        zero volume is made and nothing is added to one.
    """

    def __init__(self, ex: "PlanExecutor", n_vols: int = 1):
        self._shape = tuple(ex.plan.vol_shape_xyz)
        self._host = ex.plan.out == "host"
        self._vols = [np.zeros(self._shape, np.float32) if self._host
                      else None for _ in range(n_vols)]
        self._flush = (_AsyncFlushQueue(depth=ex.pipeline_depth)
                       if self._host and ex.pipeline == "async" else None)
        self._pending = ()

    def put(self, writes) -> None:
        if not self._host:
            for r, sl, piece in writes:
                self._vols[r] = self._device_add(self._vols[r], sl, piece)
        elif self._flush is not None:
            self._flush.put(tuple((self._vols[r], sl, piece)
                                  for r, sl, piece in writes))
        else:
            self._add_pending()
            self._pending = writes

    def _device_add(self, vol, sl, piece):
        if vol is None:
            if piece.shape == self._shape:
                return piece
            vol = jnp.zeros(self._shape, jnp.float32)
        idx = jnp.asarray([s.start for s in sl], jnp.int32)
        return _place_device_add(vol, piece, idx)

    def _add_pending(self) -> None:
        for r, sl, piece in self._pending:
            self._vols[r][sl] += np.asarray(piece)
        self._pending = ()

    def close(self) -> list:
        if self._flush is not None:
            self._flush.close()
        self._add_pending()
        return self._vols


def _to_device(x, **args):
    """``x`` on the default device: a host (numpy) array through an
    explicit ``jax.device_put`` under a ``transfer.h2d`` span; a
    ``jax.Array`` as it is. The span closes when ``device_put`` returns
    and never waits for the copy, so a traced run queues the same device
    work as an untraced one; the copy's end is in the profiler's own
    transfer events."""
    if isinstance(x, jax.Array):
        return x
    with telemetry.span("transfer.h2d", bytes=int(x.nbytes), **args):
        return jax.device_put(x)


def _pad_mats(mats: jnp.ndarray, n_pad: int) -> jnp.ndarray:
    """Pad (np, 3, 4) matrices to n_pad rows by repeating the last one
    (a valid geometry: no 1/z poles — pairs with zero-image padding)."""
    pad = int(n_pad) - mats.shape[0]
    if pad <= 0:
        return mats
    return jnp.concatenate(
        [mats, jnp.broadcast_to(mats[-1:], (pad, 3, 4))], axis=0)


class _FilteredChunkProducer:
    """Filter-once projection-chunk source for ``reconstruct``.

    Memoizes the filtered + transposed chunks of ``plan.chunks`` so the
    filtering cost is paid once per chunk regardless of how many
    consumers (tile steps) read it, and exposes ``prefetch`` so the
    NEXT chunk's filtering is dispatched — asynchronously, under JAX's
    lazy execution — while the current chunk's programs and host flush
    run: PR 2's output-side double buffering extended to the input
    side. ``stacked`` hoists the whole producer for the step-major
    scan: every chunk filtered exactly once, stacked onto the scan
    grid. ``drop`` releases a consumed chunk in chunk-major streaming
    so device residency stays two-chunk-bounded (the consumed chunk +
    the prefetched next one).
    """

    def __init__(self, ex: "PlanExecutor", projections: jnp.ndarray,
                 mat_p: jnp.ndarray):
        self._ex = ex
        self._projections = projections
        self._mat_p = mat_p
        self._chunks = ex.plan.chunks
        self._memo: Dict[int, tuple] = {}

    def get(self, c: int):
        """Filtered ``(img_c, mat_c)`` of chunk ``c`` (memoized)."""
        if c not in self._memo:
            s0, s1 = self._chunks[c]
            with telemetry.span("filter.chunk", chunk=c,
                                n_views=int(s1 - s0)):
                self._memo[c] = self._ex._chunk_inputs(
                    self._projections, self._mat_p, s0, s1)
        return self._memo[c]

    def prefetch(self, c: int) -> None:
        """Dispatch chunk ``c``'s filtering now (no-op out of range)."""
        if 0 <= c < len(self._chunks):
            self.get(c)

    def drop(self, c: int) -> None:
        self._memo.pop(c, None)

    def streamed(self):
        """Chunk-major: yield each chunk's ``(img_c, mat_c)`` with the
        next chunk's filtering dispatched first (it overlaps this
        chunk's compute), releasing each chunk once consumed."""
        for c in range(len(self._chunks)):
            pair = self.get(c)
            self.prefetch(c + 1)
            yield pair
            self.drop(c)

    def stacked(self, sched: StepMajorSchedule):
        """All chunks, filtered once each, as the scan grid stack."""
        imgs, mats = [], []
        for c in range(sched.n_chunks):
            img_c, mat_c = self.get(c)
            self.drop(c)   # the stack is the only remaining consumer
            # tail chunk -> uniform scan slot
            img_c, mat_c = _pad_rows(img_c, mat_c, sched.chunk_size)
            imgs.append(img_c)
            mats.append(mat_c)
        return jnp.stack(imgs), jnp.stack(mats)


# --------------------------------------------------------------------------
# Fleet execution: multi-device step-schedule sharding
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """How a :class:`PlanExecutor` spreads a step-major plan across
    devices (``execute_fleet``).

    devices : explicit jax devices to use; ``None`` resolves to all
        local devices at run time (``jax.local_devices()`` — under
        ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` that is
        the N forced host devices, the no-hardware CI lane).
    max_retries_per_step : failover budget PER STEP INDEX — the
        :class:`~repro.runtime.fault_tolerance.FaultTolerantLoop` retry
        contract: counted per index, never reset by successes elsewhere.
        A step that fails more than this many times across the whole
        fleet aborts the run (a poison step; skipping would corrupt the
        volume, unlike a training batch).
    device_strikes : step failures charged to one device before it is
        RETIRED: its worker exits, its unclaimed queue is drained by the
        surviving devices through the normal stealing path, and its
        already-failed steps re-run elsewhere (disjoint output boxes ⇒
        idempotent re-execution).
    straggler_window / straggler_ratio : the
        :class:`~repro.runtime.straggler.FleetStragglerBoard` knobs — a
        device whose recent median step time exceeds ``ratio`` x the
        fleet median is flagged, and idle devices steal from flagged
        queues first.
    step_hook : test seam called as ``hook(device_index, step_index)``
        before a step's program runs — raise to inject a device fault,
        sleep to simulate a straggler. ``None`` in production.
    """

    devices: Optional[Tuple] = None
    max_retries_per_step: int = 2
    device_strikes: int = 2
    straggler_window: int = 32
    straggler_ratio: float = 1.5
    step_hook: Optional[Callable[[int, int], None]] = None

    def resolve_devices(self) -> Tuple:
        return (tuple(self.devices) if self.devices
                else tuple(jax.local_devices()))


@dataclasses.dataclass(frozen=True)
class FleetReport(telemetry.EmitMixin):
    """What one ``execute_fleet`` run did: per-device completion counts,
    how many steps migrated (``stolen``), how many re-ran after a
    failure (``retried``), which devices were retired (``dead_devices``)
    and which the straggler board flagged (``flagged_devices``).
    ``late_copies`` counts the step inputs a worker had to copy off
    another chip once the step programs were dispatching: a copy that
    leaves a chip waits behind whatever that chip has queued. Origins
    come from the host and replicas for every device with queued work
    are issued before the first step, so only a device whose initial
    queue was empty and that then steals adds one (its lazy replica).
    ``as_dict()``/``emit()`` follow the shared
    :class:`~repro.runtime.telemetry.EmitMixin` report contract."""

    n_devices: int
    n_steps: int
    steps_by_device: Tuple[int, ...]
    stolen: int
    retried: int
    dead_devices: Tuple[int, ...]
    flagged_devices: Tuple[int, ...]
    late_copies: int = 0


def as_fleet_config(devices, *, max_retries_per_step: int = 2,
                    step_hook=None) -> Optional[FleetConfig]:
    """Normalize a façade/service ``devices=`` argument.

    ``None`` -> no fleet (single-device walks); ``"all"`` -> every local
    device, resolved lazily at run time; an ``int`` N -> the first N of
    ``jax.local_devices()`` (resolved now); a sequence of jax devices ->
    exactly those; an existing :class:`FleetConfig` passes through.
    """
    if devices is None:
        return None
    if isinstance(devices, FleetConfig):
        return devices
    if devices == "all":
        devs = None
    elif isinstance(devices, int):
        local = jax.local_devices()
        if not 1 <= devices <= len(local):
            raise ValueError(
                f"devices={devices} but {len(local)} local devices "
                f"are available")
        devs = tuple(local[:devices])
    else:
        devs = tuple(devices)
        if not devs:
            raise ValueError("devices sequence must be non-empty")
    return FleetConfig(devices=devs,
                       max_retries_per_step=max_retries_per_step,
                       step_hook=step_hook)


def _replicate(img_s: jnp.ndarray, mat_s: jnp.ndarray, dev):
    """Start copying the fleet's chunk stack and matrices onto device
    ``dev``; None where both are there already. The copies run
    asynchronously: :func:`_landed` waits for them."""
    if all(isinstance(a, jax.Array) and a.devices() == {dev}
           for a in (img_s, mat_s)):
        return None
    return jax.device_put(img_s, dev), jax.device_put(mat_s, dev)


def _landed(copy, d: int):
    """Worker ``d``'s replica ``copy`` once it is on the device. The
    wait is made traced or not: the ``fleet.replicate`` span then lasts
    until the copy has landed, and the worker's first step needs it
    anyway."""
    with telemetry.span("fleet.replicate", device=d,
                        bytes=int(sum(a.nbytes for a in copy))):
        return jax.block_until_ready(copy)


class PlanExecutor:
    """Executes a :class:`ReconPlan` against projection data.

    One executor serves any number of calls; programs come from the
    (shared) :class:`ProgramCache`, so repeated calls and same-shape
    tiles never retrace. The loop ORDER follows ``plan.schedule``:
    step-major scanned device accumulators by default, the chunk-major
    PR-2 loop on request.

    ``pipeline`` selects the host flush discipline: ``"sync"``
    (the PR-3 in-thread double buffer — flush step N-1 after
    dispatching step N) or ``"async"`` (a :class:`_AsyncFlushQueue`
    flusher thread: step N's device->host accumulator copy overlaps
    step N+1's scan dispatch, ``jax.block_until_ready`` only at
    dequeue). Async only changes WHEN host adds happen, never their
    FIFO order, so output is bit-identical; it engages on host-placed
    single-process walks and is a no-op elsewhere. ``pipeline_depth``
    bounds the in-flight step outputs (2 = double buffered).
    """

    def __init__(self, geom: CTGeometry, plan: ReconPlan,
                 cache: Optional[ProgramCache] = None, *,
                 pipeline: str = "sync", pipeline_depth: int = 2,
                 tuned=None, fleet: Optional[FleetConfig] = None):
        if pipeline not in ("sync", "async"):
            raise ValueError(
                f"pipeline must be 'sync' or 'async', got {pipeline!r}")
        if fleet is not None:
            if plan.schedule != "step":
                raise ValueError(
                    "fleet execution shards the STEP schedule "
                    "(disjoint output boxes are the shard axis); plan "
                    f"with schedule='step', got {plan.schedule!r}")
            if plan.out != "host":
                raise ValueError(
                    "fleet execution accumulates per-device step "
                    "outputs into a host volume; plan with out='host', "
                    f"got {plan.out!r}")
            if plan.precision != "f32":
                raise ValueError(
                    "fleet execution does not support the reduced-"
                    "precision data path yet (no fleet run has been "
                    "checked against it); plan with precision='f32', "
                    f"got {plan.precision!r}")
        self.geom = geom
        self.plan = plan
        self._dtype = _plan_dtype(plan)
        self.cache = cache if cache is not None else default_program_cache()
        self.pipeline = pipeline
        self.pipeline_depth = int(pipeline_depth)
        self.tuned = tuned    # TunedConfig provenance, None = heuristic
        self.fleet = fleet    # FleetConfig, None = single-device walks
        self.last_fleet_report: Optional[FleetReport] = None
        self._fleet_lock = threading.Lock()
        # accumulated across runs (the serving layer snapshots these —
        # per-run reports on a shared bucket executor would race)
        self.fleet_totals: Dict[str, int] = {
            "runs": 0, "devices": 0, "stolen": 0, "retried": 0,
            "dead_devices": 0}

    @classmethod
    def from_config(cls, geom: CTGeometry, config,
                    cache: Optional[ProgramCache] = None) -> "PlanExecutor":
        """Executor for a resolved ``runtime.autotune.TunedConfig``: the
        config plans itself (pure) and carries the executor-level knobs
        (``pipeline``/``pipeline_depth``) the plan cannot."""
        return cls(geom, config.build_plan(geom), cache=cache,
                   pipeline=config.pipeline,
                   pipeline_depth=config.pipeline_depth, tuned=config)

    # ---- compile-stage access -------------------------------------------

    def _program(self, variant: str, call_shape, *,
                 grid: Optional[Tuple[int, int]] = None,
                 rb: Optional[int] = None) -> Callable:
        return self.cache.program(variant, call_shape, self.plan.nb,
                                  self._dtype, self.plan.interpret,
                                  self.plan.options, grid=grid, rb=rb)

    def warm(self) -> Dict[str, int]:
        """Compile every distinct program the plan needs; return stats.
        A fleet runs the single-device step programs (XLA specializes
        per device on first dispatch)."""
        grid = (_grid(self.plan.step_major) if self.plan.schedule == "step"
                else None)
        for variant, shape in self.plan.program_keys:
            self._program(variant, shape, grid=grid)
        return self.cache.stats()

    @property
    def supports_request_batching(self) -> bool:
        """Whether :meth:`execute_batch` can coalesce k requests into
        one dispatch stream here. True for step-major plans (the scan
        megaprogram takes the leading ``vmap`` lane); chunk-major plans
        fall back to sequential execution in the service."""
        return self.plan.schedule == "step"

    def warm_batch(self, rb: int) -> Dict[str, int]:
        """Compile the rb-batched program per (variant, shape) so the
        first formed batch of ``rb`` requests compiles nothing. No-op
        for plans that don't support request batching."""
        if rb < 2 or not self.supports_request_batching:
            return self.cache.stats()
        grid = _grid(self.plan.step_major)
        for variant, shape in self.plan.program_keys:
            self._program(variant, shape, grid=grid, rb=rb)
        return self.cache.stats()

    # ---- execute-stage helpers ------------------------------------------

    def _host_volume(self) -> np.ndarray:
        """A zero host volume for the fleet's disjoint step writes."""
        return np.zeros(self.plan.vol_shape_xyz, np.float32)

    def _origin(self, step: PlanStep) -> Optional[jnp.ndarray]:
        """The step's box origin ``(i0, j0, k_off)`` for its program
        (``None`` for the untiled plan's one call, whose programs then
        trace as without an origin)."""
        if self._single_full_call():
            return None
        return jnp.asarray([step.i0, step.j0, step.k_off], jnp.float32)

    def _chunks_for(self, n_padded: int):
        """Chunk schedule for the ACTUAL (padded) projection count.

        ``backproject`` accepts any (np, nw, nh) input, not just
        ``geom.n_proj`` views (the plan's count): the plan contributes
        the streaming *policy* (chunk size, or all-at-once), the data
        contributes the extent."""
        plan = self.plan
        _, _, chunks = plan_proj_chunks(
            n_padded, plan.nb,
            plan.chunk_size if plan.streams_projections else None)
        return chunks

    def _single_full_call(self) -> bool:
        """One unpaired step covering the whole volume (the untiled plan)."""
        steps = self.plan.steps
        return (len(steps) == 1 and not steps[0].paired
                and steps[0].call_shape == self.plan.vol_shape_xyz
                and (steps[0].i0, steps[0].j0, steps[0].k_off) == (0, 0, 0))

    @staticmethod
    def _step_writes(step: PlanStep, out: jnp.ndarray):
        """(volume slices, device piece) pairs of one step's output."""
        isl = slice(step.i0, step.i0 + step.ni)
        jsl = slice(step.j0, step.j0 + step.nj)
        return tuple(((isl, jsl, slice(w.k0, w.k0 + w.nk)),
                      out[..., w.lo:w.hi]) for w in step.writes)

    def _lane_writes(self, step: PlanStep, out: jnp.ndarray,
                     rb: Optional[int] = None):
        """``(lane, volume slices, device piece)`` writes of one step's
        output; ``rb`` lanes along ``out``'s leading axis."""
        if rb is None:
            return tuple((0, sl, piece)
                         for sl, piece in self._step_writes(step, out))
        return tuple((r, sl, piece) for r in range(rb)
                     for sl, piece in self._step_writes(step, out[r]))

    def _step_span(self, step: PlanStep, n_views: int, **extra):
        """Telemetry span for one step dispatch (the args are only
        built when tracing is live)."""
        sp = telemetry.span("step.dispatch")
        if sp.live:
            sp.set(variant=step.variant, call_shape=list(step.call_shape),
                   n_views=int(n_views), **extra)
        return sp

    def _walk(self, units, *, grid: Optional[Tuple[int, int]] = None,
              rb: Optional[int] = None) -> list:
        """The single-process step walk: every plan step dispatched once
        per ``(img, mat)`` unit, its outputs through one
        :class:`_Accumulator`. Returns the volumes (transposed layout),
        one per request lane.

        Step-major (``grid`` set) has one unit, the stacked scan grid:
        one scanned device-resident accumulator per step and one host
        crossing, O(vol) device->host traffic and O(n_steps) dispatches.
        Chunk-major has one unit per projection chunk, each step's
        output added into the volume per chunk. ``rb`` stacks request
        lanes on ``img``'s leading axis (step-major only).
        """
        acc = _Accumulator(self, 1 if rb is None else rb)
        schedule = "chunk" if grid is None else "step"
        extra = {} if rb is None else {"rb": rb}
        try:
            for img, mat in units:
                n_views = (grid[0] * grid[1] if grid is not None
                           else img.shape[0])
                for step in self.plan.steps:
                    prog = self._program(step.variant, step.call_shape,
                                         grid=grid, rb=rb)
                    with self._step_span(step, n_views, schedule=schedule,
                                         **extra):
                        out = prog(img, mat, self._origin(step))
                    acc.put(self._lane_writes(step, out, rb))
        finally:
            vols = acc.close()
        return vols

    def execute_fleet(self, vol, img_s: jnp.ndarray,
                      mat_s: jnp.ndarray, sched: StepMajorSchedule, *,
                      fleet: Optional[FleetConfig] = None) -> np.ndarray:
        """Shard a step-major schedule across a device fleet.

        The step list is partitioned into per-device work queues
        (``runtime.planner.partition_steps`` — LPT-balanced on modeled
        voxel work); the filtered chunk stack is replicated onto each
        device whose queue holds work before any step program is
        dispatched (an idle spare pays nothing; one that steals copies
        lazily), and one dispatcher thread per device drains its queue
        through the single-device walk's origin-traced step program
        (``ProgramCache.program`` with the scan grid), each step's
        origin put onto the worker's device from the host. A copy off
        another chip would wait behind that chip's queued steps, so
        once steps dispatch no worker reads an input that must leave
        another chip (``FleetReport.late_copies`` counts the
        exceptions). Step
        outputs land in the host volume's disjoint boxes, so completion
        order is irrelevant and the result equals the single-device
        step-major walk.

        **Work stealing**: an idle device first drains the fleet retry
        queue, then steals from the tail of another device's queue —
        preferring devices the :class:`FleetStragglerBoard` has flagged
        as slow, so a straggler's unclaimed steps migrate first.

        **Failover**: a failed step is requeued fleet-wide and re-run
        on whichever device takes it — re-execution is idempotent
        (disjoint, not-yet-flushed output). Failures are budgeted PER
        STEP INDEX (``max_retries_per_step`` — the FaultTolerantLoop
        contract); exceeding it raises (a poison step would corrupt the
        volume). A device accumulating ``device_strikes`` failures is
        retired and its remaining queue drains to the survivors.

        ``vol`` may be a LIST of rb host volumes (the batched path):
        ``img_s`` then carries a leading request axis and each step's
        batched output fans out to every lane's disjoint box — one
        dispatch per (device, step) serves all rb requests, and the
        stealing/failover machinery is untouched (a retried batched
        step re-runs all lanes; still idempotent, the writes were
        never flushed).

        **Spans**, on each worker's lane: ``fleet.replicate`` (the copy
        of the chunk stack onto a device that lacks it, until it has
        landed), ``fleet.flush_wait`` (asking for the flush lock until
        holding it) and ``fleet.flush`` (the step's downloads and host
        adds under the lock).
        """
        cfg = fleet if fleet is not None else (self.fleet or FleetConfig())
        rb = len(vol) if isinstance(vol, (list, tuple)) else None
        vols = list(vol) if rb is not None else [vol]
        grid = _grid(sched)
        devices = cfg.resolve_devices()
        n_dev = len(devices)
        steps = tuple(w.step for w in sched.steps)
        n_steps = len(steps)
        if n_steps == 0:
            self._record_fleet(FleetReport(n_dev, 0, (0,) * n_dev,
                                           0, 0, (), ()))
            return vol
        fs = partition_steps(steps, n_dev)
        # before any step program: a copy off device 0 issued after its
        # first step would wait out that whole step
        copies = [_replicate(img_s, mat_s, dev) if q else None
                  for dev, q in zip(devices, fs.queues)]
        board = FleetStragglerBoard(n_dev, window=cfg.straggler_window,
                                    ratio=cfg.straggler_ratio)

        cond = threading.Condition()
        deques = [collections.deque(q) for q in fs.queues]
        retry: collections.deque = collections.deque()
        counts = {"outstanding": 0, "stolen": 0, "retried": 0, "done": 0,
                  "late_copies": 0}
        failures: collections.Counter = collections.Counter()  # per index
        strikes: collections.Counter = collections.Counter()   # per device
        dead: set = set()
        done_by_device = [0] * n_dev
        fatal: list = []                 # [(step index, exception)]
        flush_lock = threading.Lock()

        def take(d: int):
            """Next step index for device ``d`` (call under ``cond``):
            own queue in schedule order, then the fleet retry queue,
            then steal from the tail of the neediest victim — flagged
            (straggling) devices first, longest backlog next."""
            if deques[d]:
                return deques[d].popleft()
            if retry:
                return retry.popleft()
            flagged = set(board.flagged)
            victims = [v for v in range(n_dev) if v != d and deques[v]]
            if not victims:
                return None
            victims.sort(key=lambda v: (v not in flagged,
                                        -len(deques[v]), v))
            counts["stolen"] += 1
            telemetry.instant("fleet.steal", thief=d, victim=victims[0])
            return deques[victims[0]].pop()

        def worker(d: int) -> None:
            dev = devices[d]
            img_d = mat_d = None
            while True:
                with cond:
                    while True:
                        if fatal or d in dead:
                            return
                        idx = take(d)
                        if idx is not None:
                            counts["outstanding"] += 1
                            break
                        if counts["outstanding"] == 0 and not retry \
                                and not any(deques):
                            return      # fleet drained
                        cond.wait(0.05)
                step = steps[idx]
                t0 = time.perf_counter()
                try:
                    if cfg.step_hook is not None:
                        cfg.step_hook(d, idx)
                    if img_d is None:
                        copy = copies[d]
                        if copy is None and not fs.queues[d]:
                            # a spare that steals copies now, lazily:
                            # one that never takes work never pays
                            copy = _replicate(img_s, mat_s, dev)
                            if copy is not None:
                                with cond:
                                    counts["late_copies"] += 1
                        img_d, mat_d = ((img_s, mat_s) if copy is None
                                        else _landed(copy, d))
                    prog = self._program(step.variant, step.call_shape,
                                         grid=grid, rb=rb)
                    origin = jax.device_put(
                        np.asarray([step.i0, step.j0, step.k_off],
                                   np.float32), dev)
                    with self._step_span(step, sched.n_chunks *
                                         sched.chunk_size, schedule="fleet",
                                         device=d, step_index=idx):
                        out = jax.block_until_ready(
                            prog(img_d, mat_d, origin))
                except Exception as exc:  # noqa: BLE001 — any step fault
                    with cond:
                        counts["outstanding"] -= 1
                        failures[idx] += 1
                        strikes[d] += 1
                        if failures[idx] > cfg.max_retries_per_step:
                            fatal.append((idx, exc))
                        else:
                            retry.append(idx)
                            counts["retried"] += 1
                            telemetry.instant("fleet.failover", device=d,
                                              step_index=idx,
                                              retries=failures[idx])
                        if strikes[d] >= cfg.device_strikes:
                            dead.add(d)
                            telemetry.instant("fleet.retire", device=d,
                                              strikes=strikes[d])
                        cond.notify_all()
                    if fatal or d in dead:
                        return
                    continue
                dur = time.perf_counter() - t0
                # flush the step's disjoint writes; order across steps
                # is irrelevant (disjoint boxes into a zeroed volume)
                writes = [(vols[r], sl, piece)
                          for r, sl, piece in self._lane_writes(step, out,
                                                                rb)]
                with telemetry.span("fleet.flush_wait", device=d,
                                    step_index=idx):
                    flush_lock.acquire()
                try:
                    with telemetry.span("fleet.flush", device=d,
                                        step_index=idx,
                                        bytes=sum(p.nbytes
                                                  for _, _, p in writes)):
                        for tgt, sl, piece in writes:
                            tgt[sl] += np.asarray(piece)
                finally:
                    flush_lock.release()
                board.record(d, idx, dur)
                with cond:
                    counts["outstanding"] -= 1
                    done_by_device[d] += 1
                    counts["done"] += 1
                    cond.notify_all()

        threads = [threading.Thread(target=worker, args=(d,),
                                    name=f"recon-fleet-{d}", daemon=True)
                   for d in range(n_dev)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if fatal:
            idx, exc = fatal[0]
            raise RuntimeError(
                f"fleet step {idx} failed more than "
                f"max_retries_per_step={cfg.max_retries_per_step} times "
                f"across devices — poison step, volume would be "
                f"incomplete") from exc
        if counts["done"] < n_steps:
            raise RuntimeError(
                f"fleet lost all devices with {n_steps - counts['done']} "
                f"of {n_steps} steps unfinished "
                f"(retired devices: {sorted(dead)})")
        self._record_fleet(FleetReport(
            n_devices=n_dev, n_steps=n_steps,
            steps_by_device=tuple(done_by_device),
            stolen=counts["stolen"], retried=counts["retried"],
            dead_devices=tuple(sorted(dead)),
            flagged_devices=board.flagged,
            late_copies=counts["late_copies"]))
        return vol

    def _record_fleet(self, report: FleetReport) -> None:
        with self._fleet_lock:
            self.last_fleet_report = report
            t = self.fleet_totals
            t["runs"] += 1
            t["devices"] = report.n_devices
            t["stolen"] += report.stolen
            t["retried"] += report.retried
            t["dead_devices"] += len(report.dead_devices)

    # ---- full-volume drivers --------------------------------------------

    def _data_step_major(self, chunks) -> StepMajorSchedule:
        """Step-major schedule over a DATA-dependent chunk list (the
        plan contributes the steps, the input contributes the extent)."""
        return build_step_major(self.plan.steps, chunks,
                                chunks[0][1] - chunks[0][0])

    def backproject(self, img_t: jnp.ndarray, mats: jnp.ndarray):
        """Back-project pre-filtered transposed projections.

        img_t: (np, nw, nh); mats: (np, 3, 4). Returns vol_t (nx, ny, nz)
        — numpy when ``plan.out == "host"``. The tail batch is padded
        ONCE here (the plan's padded count); no per-call re-padding.
        """
        plan = self.plan
        img_p, mat_p = pad_projection_batch(img_t, mats, plan.nb)
        chunks = self._chunks_for(img_p.shape[0])
        if plan.schedule == "step":
            sched = self._data_step_major(chunks)
            img_s, mat_s = _stack_chunks(img_p, mat_p, sched)
            if self.fleet is not None:
                return self.execute_fleet(self._host_volume(), img_s,
                                          mat_s, sched)
            return self._walk([(img_s, mat_s)], grid=_grid(sched))[0]
        return self._walk((img_p[s0:s1], mat_p[s0:s1])
                          for s0, s1 in chunks)[0]

    def backproject_tile(self, img_t: jnp.ndarray, mats: jnp.ndarray,
                         tile: TileSpec) -> jnp.ndarray:
        """Back-project one arbitrary sub-box; exact for every variant
        (slab-safe fallback resolved here for non-centered boxes)."""
        plan = self.plan
        name = resolve_tile_variant(plan.variant, tile, plan.vol_shape_xyz[2])
        img_p, mat_p = pad_projection_batch(img_t, mats, plan.nb)
        origin = jnp.asarray([tile.i0, tile.j0, tile.k0], jnp.float32)
        chunks = self._chunks_for(img_p.shape[0])
        if plan.schedule == "step":
            sched = self._data_step_major(chunks)
            img_s, mat_s = _stack_chunks(img_p, mat_p, sched)
            return self._program(name, tile.shape, grid=_grid(sched))(
                img_s, mat_s, origin)
        prog = self._program(name, tile.shape)
        acc = None
        for s0, s1 in chunks:
            part = prog(img_p[s0:s1], mat_p[s0:s1], origin)
            acc = part if acc is None else acc + part
        return acc

    # ---- streamed filtered reconstruction --------------------------------

    def _chunk_inputs(self, projections: jnp.ndarray, mat_p: jnp.ndarray,
                      s0: int, s1: int):
        """Upload, filter + transpose the raw rows of one padded chunk
        [s0, s1)."""
        plan = self.plan
        raw = _to_device(projections[s0:min(s1, plan.n_proj)],
                         chunk=s0 // plan.chunk_size)
        img_c = bp.transpose_projections(
            fdk_filter_chunk(raw, self.geom, plan.n_proj))
        pad = (s1 - s0) - img_c.shape[0]
        if pad > 0:   # tail chunk: zero images pair with repeated matrices
            img_c = jnp.concatenate(
                [img_c, jnp.zeros((pad,) + img_c.shape[1:], img_c.dtype)],
                axis=0)
        return img_c, mat_p[s0:s1]

    def reconstruct(self, projections: jnp.ndarray):
        """Filtered FDK: (np, nh, nw) raw -> (nz, ny, nx) volume.

        Pre-weighting + ramp filtering run inside the projection-chunk
        pipeline, each chunk filtered exactly once (the hoisted
        :class:`_FilteredChunkProducer` feeds every tile step). Under
        the default step-major schedule the filtered chunk stack rides
        on device for the scan; ``schedule="chunk"`` keeps device
        residency two-chunk-bounded — the consumed chunk plus the
        prefetched next one, whose filtering is dispatched early so it
        overlaps the current chunk's compute. Returns numpy when
        ``plan.out == "host"`` (a free transposed view of the host
        accumulator).
        """
        plan = self.plan
        if projections.shape[0] != plan.n_proj:
            raise ValueError(
                f"reconstruct expects the geometry's full scan of "
                f"{plan.n_proj} projections (the FDK angular weighting "
                f"assumes it), got {projections.shape[0]}; for arbitrary "
                f"view subsets filter upstream and call backproject()")
        mat_p = _pad_mats(projection_matrices(self.geom),
                          plan.n_proj_padded)
        producer = _FilteredChunkProducer(self, projections, mat_p)
        if plan.schedule == "chunk":
            return _to_native(self._walk(producer.streamed())[0])
        sched = plan.step_major
        img_s, mat_s = producer.stacked(sched)
        if self.fleet is not None:
            return _to_native(self.execute_fleet(self._host_volume(),
                                                 img_s, mat_s, sched))
        return _to_native(self._walk([(img_s, mat_s)], grid=_grid(sched))[0])

    def open_stream(self, *, max_pending_chunks: int = 2,
                    on_ready: Optional[Callable[[int], None]] = None
                    ) -> "StreamingExecutor":
        """Open an online (push-driven) reconstruction on this executor.

        Projections are PUSHED as the scanner produces them
        (``push(views)``); each view chunk is back-projected the moment
        it completes, so reconstruction wall hides behind acquisition,
        and ``close()`` returns a volume bit-identical to
        :meth:`reconstruct` on the assembled set (same chunk partition
        ⇒ same reduction order). Requires a chunk-major plan — build it
        with ``ingest="stream"``. See :class:`StreamingExecutor`.
        """
        return StreamingExecutor(self, max_pending_chunks=max_pending_chunks,
                                 on_ready=on_ready)

    def execute_batch(self, projections_seq: Sequence[jnp.ndarray]):
        """Reconstruct k same-bucket requests with ONE dispatch stream.

        ``projections_seq`` holds k raw projection stacks, each exactly
        what :meth:`reconstruct` takes. Per-request filtering runs
        unchanged (identical code path, identical float-op order), the
        k filtered scan grids are stacked onto a leading request axis,
        and every step of the step-major walk dispatches the rb-batched
        megaprogram once instead of k times — cross-request batching
        amortizes the per-dispatch fixed cost the same way the in-batch
        ``nb`` axis amortizes per-projection cost (paper O5, lifted to
        the service tier). The matrix stack is shared across lanes
        (same bucket == same geometry + chunk grid). Output is a list
        of k volumes, each BIT-IDENTICAL to ``reconstruct`` on that
        request alone (``vmap`` adds an axis, it never reassociates
        the per-lane reductions — asserted in tests/test_batching.py).

        Requires a step-major plan (``supports_request_batching``);
        k == 1 just delegates to :meth:`reconstruct`.
        """
        reqs = list(projections_seq)
        k = len(reqs)
        if k == 0:
            return []
        if k == 1:
            return [self.reconstruct(reqs[0])]
        plan = self.plan
        if not self.supports_request_batching:
            raise ValueError(
                "execute_batch amortizes dispatch over the step-major "
                "scan; plan with schedule='step', got "
                f"{plan.schedule!r} (callers should check "
                "supports_request_batching and fall back to sequential "
                "reconstruct calls)")
        for p in reqs:
            if p.shape[0] != plan.n_proj:
                raise ValueError(
                    f"execute_batch expects {plan.n_proj} projections "
                    f"per request (the plan's full scan), got "
                    f"{p.shape[0]}")
        mat_p = _pad_mats(projection_matrices(self.geom),
                          plan.n_proj_padded)
        sched = plan.step_major
        lanes = []
        mat_s = None
        for p in reqs:
            img_s, mat_s = _FilteredChunkProducer(
                self, p, mat_p).stacked(sched)
            lanes.append(img_s)
        img_b = jnp.stack(lanes)
        del lanes
        if self.fleet is not None:
            vols = [self._host_volume() for _ in range(k)]
            self.execute_fleet(vols, img_b, mat_s, sched)
        else:
            vols = self._walk([(img_b, mat_s)], grid=_grid(sched), rb=k)
        return [_to_native(v) for v in vols]


# --------------------------------------------------------------------------
# Online (streaming) execution: fold view chunks as they arrive
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StreamReport(telemetry.EmitMixin):
    """What one closed stream did, in overlap terms.

    ``acquire_s`` is first-view to last-view arrival wall (the simulated
    or real scanner rotation), ``compute_s`` the total fold + finish
    busy wall, and ``tail_s`` the wall from LAST view arrival to the
    finished volume — the end-to-end latency a streaming deployment
    actually adds on top of acquisition. ``hidden_fraction`` is the
    share of compute that ran during acquisition instead of after it.
    """

    n_views: int
    n_chunks: int
    acquire_s: float
    compute_s: float
    tail_s: float

    @property
    def hidden_fraction(self) -> float:
        if self.compute_s <= 0.0:
            return 1.0
        return max(0.0, min(1.0, 1.0 - self.tail_s / self.compute_s))


class StreamingExecutor:
    """Online reconstruction: push projections as they arrive, fold each
    view chunk the moment it completes.

    The arrival-queue contract (docs/ARCHITECTURE.md Stage 8):

      * ``push(views, start=None)`` accepts one or more raw views;
        ``start`` defaults to sequential delivery, an explicit row index
        allows ANY arrival order within a chunk (each view lands in its
        chunk buffer by row, so within-chunk permutations cannot change
        the result). Each view may arrive exactly once.
      * Chunk ``c`` becomes *ready* when all of its raw rows are
        present. Ready chunks are folded strictly in chunk-index order
        — the order the offline chunk-major loop uses — which is the
        whole exactness argument: per step, the device-side running sum
        ``((p0 + p1) + p2)…`` over chunk parts is the same
        left-associated f32 reduction the offline loop performs, so
        ``close()`` is bit-identical to ``reconstruct`` on the
        assembled set.
      * At most ``max_pending_chunks`` ready-but-unfolded chunks may
        exist; a faster-than-compute producer blocks in ``push`` until
        the folder catches up (bounded buffering, real backpressure).
        ``max_pending_seen`` records the high-water mark.
      * ``close()`` requires every view; it then waits for the final
        fold + host flush and returns the volume. ``report`` carries
        the overlap metrics afterwards.

    Two drive modes: by default an internal folder thread consumes ready
    chunks (push-and-forget for callers); with ``on_ready=`` the
    completion of each chunk is reported to the callback instead and an
    EXTERNAL driver (the service's stream worker, which batches lanes
    across sessions) runs ``fold``/``filtered``/``accept_part``/
    ``chunk_done``. Folding overlaps acquisition three ways: device
    compute of chunk c, filtering of ready chunk c+1 (dispatched early,
    async under JAX), and the final per-step host flushes through
    :class:`_AsyncFlushQueue` when the executor pipelines.
    """

    def __init__(self, ex: PlanExecutor, *, max_pending_chunks: int = 2,
                 on_ready: Optional[Callable[[int], None]] = None):
        plan = ex.plan
        if plan.schedule != "chunk":
            raise ValueError(
                "streaming folds view chunks as they arrive (chunk-major "
                "by construction); plan with ingest='stream' (or "
                f"schedule='chunk'), got schedule={plan.schedule!r}")
        if ex.fleet is not None:
            raise ValueError(
                "streaming does not compose with fleet execution yet — "
                "open the stream on a single-device executor")
        if max_pending_chunks < 1:
            raise ValueError(
                f"max_pending_chunks must be >= 1, got {max_pending_chunks}")
        self._ex = ex
        self.geom = ex.geom
        self._plan = plan
        self._chunk_bounds = plan.chunks
        self._n_chunks = len(self._chunk_bounds)
        self._n_views = plan.n_proj
        self._chunk_size = plan.chunk_size
        self._max_pending = int(max_pending_chunks)
        self._on_ready = on_ready
        self._mat_p = _pad_mats(projection_matrices(ex.geom),
                                plan.n_proj_padded)

        self._cond = threading.Condition()
        self._buffers: Dict[int, np.ndarray] = {}
        self._missing = {c: self._raw_rows(c) for c in range(self._n_chunks)}
        self._seen = np.zeros(self._n_views, bool)
        self._filtered_memo: Dict[int, tuple] = {}
        self._complete: set = set()
        self._accs: list = [None] * len(plan.steps)
        self._next_fold = 0
        self._next_row = 0
        self._rows = 0
        self._ingest_closed = False
        self._error: Optional[BaseException] = None
        self._result = None
        self._finished = threading.Event()
        self.max_pending_seen = 0

        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None
        self._t_done: Optional[float] = None
        self._busy = 0.0

        if on_ready is None:
            self._thread = threading.Thread(
                target=self._drive, name="recon-stream-fold", daemon=True)
            self._thread.start()

    # ---- ingest side ------------------------------------------------------

    def _raw_rows(self, c: int) -> int:
        """Raw (un-padded) views chunk ``c`` must receive."""
        s0, s1 = self._chunk_bounds[c]
        return min(s1, self._n_views) - s0

    def _raise_if_failed(self) -> None:
        if self._error is not None:
            raise self._error

    def push(self, views, start: Optional[int] = None) -> None:
        """Deliver view rows ``[start, start + k)`` (default: the next
        sequential rows). Blocks only for backpressure — when
        ``max_pending_chunks`` ready chunks are already waiting."""
        views = np.asarray(views, np.float32)
        if views.ndim == 2:
            views = views[None]
        if views.ndim != 3 or views.shape[1:] != (self.geom.nh,
                                                  self.geom.nw):
            raise ValueError(
                f"push expects (k, nh, nw) or (nh, nw) views of detector "
                f"shape ({self.geom.nh}, {self.geom.nw}), got "
                f"{tuple(views.shape)}")
        k = views.shape[0]
        with self._cond:
            self._raise_if_failed()
            if self._ingest_closed:
                raise RuntimeError("push() after close()")
            first = self._next_row if start is None else int(start)
            if first < 0 or first + k > self._n_views:
                raise ValueError(
                    f"views [{first}, {first + k}) outside the stream's "
                    f"[0, {self._n_views}) scan")
            if self._t_first is None:
                self._t_first = time.perf_counter()
            for off in range(k):
                r = first + off
                if self._seen[r]:
                    raise ValueError(f"view {r} pushed twice")
                c = r // self._chunk_size
                s0, _ = self._chunk_bounds[c]
                buf = self._buffers.get(c)
                if buf is None:
                    buf = np.zeros(
                        (self._raw_rows(c), self.geom.nh, self.geom.nw),
                        np.float32)
                    self._buffers[c] = buf
                buf[r - s0] = views[off]
                self._seen[r] = True
                self._rows += 1
                self._missing[c] -= 1
                if self._missing[c] == 0:
                    self._admit_ready(c)
            self._next_row = max(self._next_row, first + k)
            self._t_last = time.perf_counter()
            telemetry.instant("stream.push", first=first, k=k,
                              rows=self._rows)
            self._cond.notify_all()

    def _admit_ready(self, c: int) -> None:
        """Mark chunk ``c`` ready (under ``_cond``): backpressure first,
        then hand it to the folder (thread or ``on_ready`` callback)."""
        while (len(self._complete) >= self._max_pending
               and self._error is None):
            self._cond.wait(0.05)
        self._raise_if_failed()
        self._complete.add(c)
        self.max_pending_seen = max(self.max_pending_seen,
                                    len(self._complete))
        self._cond.notify_all()
        if self._on_ready is not None:
            # deliver OUTSIDE the lock: the callback may enqueue into
            # structures with their own locks (the service's former)
            self._cond.release()
            try:
                self._on_ready(c)
            finally:
                self._cond.acquire()

    def close(self):
        """Finish the stream: requires every view delivered; waits for
        the remaining folds + final flush, returns the volume."""
        with self._cond:
            if self._ingest_closed:
                raise RuntimeError("stream already closed")
            self._ingest_closed = True
            if self._error is None and self._rows < self._n_views:
                self._error = RuntimeError(
                    f"stream closed after {self._rows} of "
                    f"{self._n_views} views — every view must be pushed "
                    f"before close()")
                self._finished.set()
            self._cond.notify_all()
        self._finished.wait()
        with self._cond:
            self._raise_if_failed()
            return self._result

    def fail(self, exc: BaseException) -> None:
        """Poison the stream (external drivers report fold errors here);
        ``push``/``close`` re-raise it."""
        with self._cond:
            if self._error is None:
                self._error = exc
            self._finished.set()
            self._cond.notify_all()

    # ---- fold side (internal thread, or the service's stream worker) -----

    @property
    def next_fold(self) -> int:
        """Index of the next chunk that must fold (order contract)."""
        with self._cond:
            return self._next_fold

    def _filter_pair(self, buf: np.ndarray, c: int):
        """Filter + transpose one ready chunk — the same float-op path
        as the offline :meth:`PlanExecutor._chunk_inputs`."""
        s0, s1 = self._chunk_bounds[c]
        with telemetry.span("filter.chunk", chunk=c, n_views=int(s1 - s0)):
            img_c = bp.transpose_projections(
                fdk_filter_chunk(_to_device(buf, chunk=c), self.geom,
                                 self._plan.n_proj))
            pad = (s1 - s0) - img_c.shape[0]
            if pad > 0:   # tail chunk: zero images pair with repeated mats
                img_c = jnp.concatenate(
                    [img_c, jnp.zeros((pad,) + img_c.shape[1:],
                                      img_c.dtype)], axis=0)
        return img_c, self._mat_p[s0:s1]

    def filtered(self, c: int):
        """Filtered ``(img_c, mat_c)`` of ready chunk ``c``."""
        with self._cond:
            pair = self._filtered_memo.pop(c, None)
            if pair is not None:
                return pair
            if c not in self._complete:
                raise RuntimeError(f"chunk {c} is not ready")
            buf = self._buffers[c]
        return self._filter_pair(buf, c)

    def prefilter(self, c: int) -> None:
        """Dispatch chunk ``c``'s filtering now if it is ready (lazy
        under JAX's async dispatch — overlaps the current fold)."""
        with self._cond:
            if (c >= self._n_chunks or c in self._filtered_memo
                    or c not in self._complete):
                return
            buf = self._buffers[c]
        pair = self._filter_pair(buf, c)
        with self._cond:
            self._filtered_memo.setdefault(c, pair)

    def accept_part(self, i: int, part) -> None:
        """Fold one kernel output into step ``i``'s device accumulator
        (donated add — the chunk-index running sum)."""
        acc = self._accs[i]
        self._accs[i] = part if acc is None else _acc_add(acc, part)

    def add_busy(self, seconds: float) -> None:
        with self._cond:
            self._busy += max(0.0, seconds)

    def fold(self, c: int) -> None:
        """Fold ready chunk ``c`` into every step accumulator (single
        lane; the service's batched path drives ``filtered`` /
        ``accept_part`` / ``chunk_done`` itself)."""
        t0 = time.perf_counter()
        with telemetry.span("stream.fold", chunk=c):
            img_c, mat_c = self.filtered(c)
            self.prefilter(c + 1)   # overlap next chunk's filtering
            ex = self._ex
            for i, step in enumerate(self._plan.steps):
                prog = ex._program(step.variant, step.call_shape)
                self.accept_part(i, prog(img_c, mat_c, ex._origin(step)))
            self.chunk_done(c)
        self.add_busy(time.perf_counter() - t0)

    def chunk_done(self, c: int) -> None:
        """Retire folded chunk ``c``; the LAST chunk triggers the final
        per-step volume flush."""
        with self._cond:
            if c != self._next_fold:
                raise RuntimeError(
                    f"chunk {c} folded out of order (expected "
                    f"{self._next_fold}) — the chunk-index fold order is "
                    f"the exactness contract")
            self._complete.discard(c)
            self._buffers.pop(c, None)
            self._next_fold = c + 1
            finish = self._next_fold == self._n_chunks
            self._cond.notify_all()
        if finish:
            self._finish()

    def _finish(self) -> None:
        """Place every step accumulator into the volume through the
        offline walk's :class:`_Accumulator` (the same placement and
        float-op order), one add per write into the zeroed volume."""
        ex = self._ex
        plan = self._plan
        with telemetry.span("stream.tail", n_chunks=self._n_chunks):
            acc = _Accumulator(ex)
            try:
                for step, part in zip(plan.steps, self._accs):
                    acc.put(ex._lane_writes(step, part))
            finally:
                vol_t = acc.close()[0]
            result = _to_native(vol_t)
        with self._cond:
            self._accs = [None] * len(plan.steps)
            self._result = result
            self._t_done = time.perf_counter()
            self._finished.set()
            self._cond.notify_all()

    def _drive(self) -> None:
        """Internal folder thread: consume ready chunks in index order."""
        try:
            for c in range(self._n_chunks):
                with self._cond:
                    while c not in self._complete and self._error is None:
                        self._cond.wait(0.1)
                    if self._error is not None:
                        return
                self.fold(c)
        except BaseException as exc:  # noqa: BLE001 — surfaced at close()
            self.fail(exc)

    # ---- introspection ----------------------------------------------------

    @property
    def report(self) -> Optional[StreamReport]:
        """Overlap metrics once the stream finished, else None."""
        with self._cond:
            if self._t_done is None:
                return None
            t_first = self._t_first if self._t_first is not None else 0.0
            t_last = (self._t_last if self._t_last is not None
                      else self._t_done)
            return StreamReport(
                n_views=self._n_views, n_chunks=self._n_chunks,
                acquire_s=max(0.0, t_last - t_first),
                compute_s=self._busy,
                tail_s=max(0.0, self._t_done - t_last))
