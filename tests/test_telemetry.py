"""Telemetry layer tests (``runtime/telemetry.py``).

Covers the observability contracts the rest of the runtime now leans
on: the disabled path records NOTHING (shared no-op span singleton),
span trees are well-formed (every span closed, parent ends after its
children, parent/child share a thread lane) across the sync, async,
fleet, and streaming execution paths, ``compile`` spans match
ProgramCache miss counts EXACTLY, ``step.dispatch`` spans are recorded,
every host chunk's upload is one ``transfer.h2d`` span inside its
``filter.chunk``, spans are profiler annotations in a
``jax.profiler`` trace, garbage collections are ``python.gc`` spans
while tracing is on, ``dump_trace`` emits valid Chrome
trace-event JSON with one lane per thread, request trace IDs link
k-wide batched dispatches back to all k submitted futures,
``ServiceStats`` survives concurrent submit+snapshot hammering without
torn reads, and the absorbed ``LatencyHistogram`` keeps its exact API.
"""

import gc
import glob
import json
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.runtime import executor, telemetry
from repro.runtime.executor import FleetConfig, PlanExecutor, ProgramCache
from repro.runtime.planner import plan_reconstruction
from repro.runtime.service import LatencyHistogram, ReconService


# ---------------------------------------------------------------------------
# helpers


def _x_events(events=None):
    evs = telemetry.events() if events is None else events
    return [e for e in evs if e.get("ph") == "X"]


def _check_span_tree(events=None):
    """Every span closed; parent/child share a lane; parent brackets
    its children in time (same monotonic clock per thread)."""
    assert telemetry.open_span_count() == 0
    spans = {e["args"]["span_id"]: e for e in _x_events(events)}
    assert spans, "no spans recorded"
    for e in spans.values():
        pid = e["args"].get("parent_id")
        if pid is None:
            continue
        parent = spans[pid]
        assert parent["tid"] == e["tid"], \
            f"{e['name']} parented across threads"
        assert parent["ts"] <= e["ts"] + 1.0
        assert parent["ts"] + parent["dur"] >= e["ts"] + e["dur"] - 1.0
    return spans


def _small_inputs(small_geom):
    rng = np.random.RandomState(0)
    return jnp.asarray(rng.rand(small_geom.n_proj, small_geom.nh,
                                small_geom.nw).astype(np.float32))


# ---------------------------------------------------------------------------
# core span machinery


def test_disabled_records_nothing():
    telemetry.disable()
    telemetry.clear()
    s1 = telemetry.span("a", x=1)
    s2 = telemetry.span("b")
    assert s1 is s2                       # shared no-op singleton
    assert not s1.live                    # call sites skip arg building
    with s1:
        telemetry.instant("tick")
    assert telemetry.events() == []
    assert not telemetry.enabled()


def test_span_nesting_records_parent_links():
    with telemetry.tracing():
        with telemetry.span("outer"):
            with telemetry.span("inner"):
                pass
        with telemetry.span("sibling"):
            pass
    spans = {e["name"]: e for e in _x_events()}
    assert spans["inner"]["args"]["parent_id"] == \
        spans["outer"]["args"]["span_id"]
    assert spans["sibling"]["args"]["parent_id"] is None
    _check_span_tree()


def test_tracing_restores_prev_state_and_span_errors_propagate():
    telemetry.disable()
    with pytest.raises(ValueError):
        with telemetry.tracing():
            assert telemetry.enabled()
            with telemetry.span("boom"):
                raise ValueError("x")
    assert not telemetry.enabled()
    ev = next(e for e in _x_events() if e["name"] == "boom")
    assert ev["args"]["error"] == "ValueError"
    assert telemetry.open_span_count() == 0


# ---------------------------------------------------------------------------
# metrics registry + the absorbed LatencyHistogram


def test_latency_histogram_is_telemetry_histogram():
    assert LatencyHistogram is telemetry.Histogram
    h = LatencyHistogram()
    for ms in (0.1, 1.0, 10.0, 100.0):
        h.record(ms / 1e3)
    assert h.count == 4
    assert h.quantile(0.0) <= h.quantile(1.0)
    m = LatencyHistogram.merged([h, h])
    assert m.count == 8
    assert m.mean() == pytest.approx(h.mean())


def test_metrics_registry_get_or_create_and_prometheus():
    reg = telemetry.MetricsRegistry()
    c = reg.counter("reqs")
    c.inc()
    c.inc(2)
    assert reg.counter("reqs") is c
    reg.gauge("depth").set(3.5)
    reg.histogram("lat").record(0.01)
    d = reg.as_dict()
    assert d["reqs"] == 3.0 and d["depth"] == 3.5
    text = reg.prometheus(prefix="repro")
    assert "repro_reqs_total 3.0" in text
    reg.clear()
    assert reg.as_dict() == {}


def test_emit_mixin_as_dict_includes_properties(small_geom, small_ct_data):
    img, _ = small_ct_data
    with ReconService() as svc:
        svc.submit(img, small_geom).result()
        stats = svc.stats()
    d = stats.as_dict()
    assert d["requests"] == 1
    assert "hit_rate" in d                # @property values included
    # emit() lands the numeric leaves in the registry as gauges
    reg = telemetry.MetricsRegistry()
    stats.emit(registry=reg, prefix="svc")
    assert reg.as_dict()["svc.requests"] == 1.0


# ---------------------------------------------------------------------------
# instrumented paths: compile parity, roofline, span trees, lanes


def test_compile_spans_match_cache_misses_exactly(small_geom,
                                                  small_ct_data):
    img, _ = small_ct_data
    plan = plan_reconstruction(small_geom, "algorithm1_mp", nb=4)
    cache = ProgramCache()
    ex = PlanExecutor(small_geom, plan, cache)
    with telemetry.tracing():
        ex.reconstruct(img)
        cold = sum(1 for e in _x_events() if e["name"] == "compile")
        assert cold == cache.stats()["misses"] > 0
        ex.reconstruct(img)               # warm: zero new compile spans
        warm = sum(1 for e in _x_events() if e["name"] == "compile")
    assert warm == cold == cache.stats()["misses"]
    _check_span_tree()


def test_step_dispatch_spans_are_recorded(small_geom, small_ct_data):
    img, _ = small_ct_data
    plan = plan_reconstruction(small_geom, "algorithm1_mp", nb=4)
    ex = PlanExecutor(small_geom, plan, ProgramCache())
    with telemetry.tracing():
        ex.reconstruct(img)
    steps = [e for e in _x_events() if e["name"] == "step.dispatch"]
    assert len(steps) == len(plan.steps)
    for e in steps:
        a = e["args"]
        assert a["variant"] == plan.steps[0].variant
        assert a["n_views"] > 0 and len(a["call_shape"]) == 3


# ---------------------------------------------------------------------------
# host-to-device transfers, profiler annotations, garbage collection


def _chunked_plan(small_geom):
    return plan_reconstruction(small_geom, "algorithm1_mp", nb=4,
                               proj_batch=4)


def test_transfer_h2d_once_per_host_chunk(small_geom, small_ct_data):
    img, _ = small_ct_data
    host = np.asarray(img)
    plan = _chunked_plan(small_geom)
    assert len(plan.chunks) == 2
    ex = PlanExecutor(small_geom, plan, ProgramCache())
    with telemetry.tracing():
        ex.reconstruct(host)
    spans = _check_span_tree()
    ups = sorted((e for e in spans.values() if e["name"] == "transfer.h2d"),
                 key=lambda e: e["args"]["chunk"])
    assert [e["args"]["chunk"] for e in ups] == [0, 1]
    for e, (s0, s1) in zip(ups, plan.chunks):
        assert e["args"]["bytes"] == host[s0:s1].nbytes
        parent = spans[e["args"]["parent_id"]]
        assert parent["name"] == "filter.chunk"
        assert parent["args"]["chunk"] == e["args"]["chunk"]


def test_traced_upload_does_not_wait_for_the_copy(monkeypatch):
    # a traced run must queue the same device work as an untraced one
    def no_wait(*a, **k):
        raise AssertionError("the upload span waited for the copy")

    monkeypatch.setattr(jax, "block_until_ready", no_wait)
    host = np.arange(12, dtype=np.float32).reshape(3, 4)
    with telemetry.tracing():
        got = executor._to_device(host, chunk=0)
    assert isinstance(got, jax.Array)
    np.testing.assert_array_equal(np.asarray(got), host)
    up, = [e for e in _x_events() if e["name"] == "transfer.h2d"]
    assert up["args"]["bytes"] == host.nbytes and up["args"]["chunk"] == 0


def test_no_transfer_span_for_device_input(small_geom, small_ct_data):
    img, _ = small_ct_data
    assert isinstance(img, jax.Array)
    ex = PlanExecutor(small_geom, _chunked_plan(small_geom), ProgramCache())
    with telemetry.tracing():
        ex.reconstruct(img)
    names = [e["name"] for e in _x_events()]
    assert names.count("filter.chunk") == 2
    assert "transfer.h2d" not in names


def test_stream_chunks_upload_inside_their_filter_span(small_geom,
                                                       small_ct_data):
    img, _ = small_ct_data
    pa = np.asarray(img)
    with telemetry.tracing():
        with ReconService() as svc:
            session = svc.open_stream(small_geom, nb=4, proj_batch=4,
                                      out="host")
            for v in range(small_geom.n_proj):
                session.push(pa[v], start=v)
            session.close()
    spans = _check_span_tree()
    ups = [e for e in spans.values() if e["name"] == "transfer.h2d"]
    assert sorted(e["args"]["chunk"] for e in ups) == [0, 1]
    for e in ups:
        assert e["args"]["bytes"] == pa[:4].nbytes
        assert spans[e["args"]["parent_id"]]["name"] == "filter.chunk"


def test_spans_are_profiler_annotations(small_geom, small_ct_data,
                                        tmp_path):
    img, _ = small_ct_data
    ex = PlanExecutor(small_geom, _chunked_plan(small_geom), ProgramCache())
    ex.reconstruct(np.asarray(img))       # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        with telemetry.tracing():
            with telemetry.span("probe.outer"):
                ex.reconstruct(np.asarray(img))
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    profile = jax.profiler.ProfileData.from_file(path)
    host = {ev.name for plane in profile.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}
    assert {"probe.outer", "filter.chunk", "transfer.h2d",
            "step.dispatch"} <= host


def test_gc_collections_are_spans_while_tracing():
    telemetry.disable()
    assert telemetry._gc_hook not in gc.callbacks
    with telemetry.tracing():
        assert telemetry._gc_hook in gc.callbacks
        with telemetry.span("outer"):
            gc.collect()
    assert telemetry._gc_hook not in gc.callbacks
    spans = _check_span_tree()
    full = [e for e in spans.values() if e["name"] == "python.gc"
            and e["args"]["generation"] == 2]
    assert full
    outer = next(e for e in spans.values() if e["name"] == "outer")
    assert full[-1]["args"]["parent_id"] == outer["args"]["span_id"]
    assert full[-1]["args"]["collected"] >= 0
    telemetry.clear()
    gc.collect()                          # tracing off: nothing recorded
    assert telemetry.events() == []


def test_enable_and_disable_install_and_remove_the_gc_hook():
    telemetry.enable()
    telemetry.enable()
    assert gc.callbacks.count(telemetry._gc_hook) == 1
    telemetry.disable()
    assert telemetry._gc_hook not in gc.callbacks


def test_span_tree_sync_and_async_paths(small_geom, small_ct_data):
    img, _ = small_ct_data
    plan = plan_reconstruction(small_geom, "algorithm1_mp", nb=4)
    for pipeline in ("sync", "async"):
        ex = PlanExecutor(small_geom, plan, ProgramCache(),
                          pipeline=pipeline)
        with telemetry.tracing():
            ex.reconstruct(img)
        spans = _check_span_tree()
        names = {e["name"] for e in spans.values()}
        assert "step.dispatch" in names


def test_span_tree_and_lanes_async_fleet(small_geom, small_ct_data,
                                         tmp_path):
    """The acceptance-criteria trace: one traced session covering an
    async-pipeline run (flusher lane) and a fleet run (dispatcher
    lanes), exported as Chrome JSON with distinct thread lanes."""
    img, _ = small_ct_data
    dev = jax.local_devices()[0]
    kw = dict(nb=4, tile_shape=(8, 8, small_geom.nz), proj_batch=4,
              out="host", schedule="step")
    plan = plan_reconstruction(small_geom, "algorithm1_mp", **kw)
    with telemetry.tracing():
        # async pipeline: step writes flush on the recon-flush thread
        ex_async = PlanExecutor(small_geom, plan, ProgramCache(),
                                pipeline="async")
        ref = np.asarray(ex_async.reconstruct(img))
        # two-lane fleet on one real device (duplicated entry): the
        # dispatcher threads and stealing machinery are fully real
        ex_fleet = PlanExecutor(small_geom, plan, ProgramCache(),
                                fleet=FleetConfig(devices=(dev, dev)))
        vol = np.asarray(ex_fleet.reconstruct(img))
    scale = float(np.max(np.abs(ref))) or 1.0
    assert float(np.max(np.abs(vol - ref))) / scale < 1e-5
    spans = _check_span_tree()
    lanes = {e["tid"] for e in spans.values()}
    assert "recon-flush" in lanes
    assert {"recon-fleet-0", "recon-fleet-1"} <= lanes
    fleet_steps = [e for e in spans.values()
                   if e["name"] == "step.dispatch"
                   and e["args"].get("schedule") == "fleet"]
    assert len(fleet_steps) == ex_fleet.last_fleet_report.n_steps
    assert all(e["args"]["variant"] for e in fleet_steps)

    # the exported trace is valid Chrome trace-event JSON with one
    # tid per thread and a thread_name metadata row per lane
    path = tmp_path / "fleet.trace.json"
    telemetry.dump_trace(str(path))
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    meta_names = {e["args"]["name"] for e in evs
                  if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert {"recon-flush", "recon-fleet-0", "recon-fleet-1"} <= meta_names
    tids = {e["tid"] for e in evs if e.get("ph") == "X"}
    assert len(tids) >= 3                 # distinct integer lanes
    for e in evs:
        if e.get("ph") == "X":
            assert isinstance(e["tid"], int)
            assert e["dur"] >= 0 and e["ts"] >= 0


def test_span_tree_stream_path(small_geom, small_ct_data):
    img, _ = small_ct_data
    pa = np.asarray(img)
    with telemetry.tracing():
        with ReconService() as svc:
            session = svc.open_stream(small_geom, nb=4, proj_batch=4,
                                      out="host")
            assert session.trace_id.startswith("stream-")
            for v in range(small_geom.n_proj):
                session.push(pa[v], start=v)
            session.close()
    spans = _check_span_tree()
    names = [e["name"] for e in spans.values()]
    assert "stream.fold" in names and "stream.tail" in names
    instants = [e["name"] for e in telemetry.events()
                if e.get("ph") == "i"]
    assert "stream.push" in instants and "stream.open" in instants


def test_solver_iteration_spans(small_geom, small_ct_data):
    from repro.runtime.solvers import solve
    img, _ = small_ct_data
    with telemetry.tracing():
        _, report = solve(img, small_geom, method="sart", n_iters=3)
    spans = _check_span_tree()
    iters = [e for e in spans.values() if e["name"] == "solve.iter"]
    assert len(iters) == 3
    top = next(e for e in spans.values() if e["name"] == "solve")
    assert all(e["args"]["parent_id"] == top["args"]["span_id"]
               for e in iters)
    assert report.as_dict()["n_iters"] == 3   # EmitMixin contract


# ---------------------------------------------------------------------------
# service: trace IDs, concurrent stats, Prometheus


def test_trace_ids_link_batched_dispatch(small_geom, small_ct_data):
    img, _ = small_ct_data
    with telemetry.tracing():
        with ReconService(max_inflight=1, max_batch=4,
                          max_wait_ms=50.0) as svc:
            svc.warmup([small_geom], nb=4)
            futs = [svc.submit(img, small_geom, nb=4) for _ in range(4)]
            for f in futs:
                f.result()
    submitted = {f.trace_id for f in futs}
    assert len(submitted) == 4            # unique per request
    dispatched = set()
    for e in _x_events():
        if e["name"] == "service.dispatch":
            dispatched.update(e["args"]["trace_ids"])
    assert dispatched == submitted        # every request linked to a
    #                                       dispatch span, none invented
    instants = {e["args"]["trace_id"] for e in telemetry.events()
                if e.get("name") == "request.submit"}
    assert instants == submitted


def test_service_stats_concurrent_submit_and_snapshot(small_geom,
                                                      small_ct_data):
    img, _ = small_ct_data
    n_threads, per_thread = 4, 3
    errors = []
    with ReconService(max_inflight=2, max_batch=2,
                      max_wait_ms=2.0) as svc:
        svc.warmup([small_geom], nb=4)
        stop = threading.Event()
        seen = []

        def snapshotter():
            while not stop.is_set():
                try:
                    s = svc.stats()
                    # torn reads would violate these at some snapshot
                    done = sum(b.completed for b in s.buckets)
                    assert s.requests >= done >= 0
                    s.export_prometheus()
                    seen.append(s.requests)
                except Exception as exc:  # noqa: BLE001
                    errors.append(exc)
                    return

        def hammer():
            try:
                futs = [svc.submit(img, small_geom, nb=4)
                        for _ in range(per_thread)]
                for f in futs:
                    f.result(timeout=120)
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        snap = threading.Thread(target=snapshotter)
        snap.start()
        workers = [threading.Thread(target=hammer)
                   for _ in range(n_threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join()
        stop.set()
        snap.join()
        assert not errors
        assert seen and seen == sorted(seen)   # monotone, no going back
        stats = svc.stats()
    total = n_threads * per_thread
    assert stats.requests == total
    assert sum(b.completed for b in stats.buckets) == total
    d = stats.as_dict()
    assert d["requests"] == total


def test_prometheus_exposition_format(small_geom, small_ct_data):
    img, _ = small_ct_data
    with ReconService() as svc:
        svc.submit(img, small_geom, nb=4).result()
        text = svc.stats().export_prometheus()
    lines = text.splitlines()
    assert "repro_requests_total 1.0" in lines
    for family in ("repro_requests_total", "repro_hit_rate",
                   "repro_bucket_requests"):
        assert f"# TYPE {family} " in text and f"# HELP {family} " in text
    # sample lines parse: name{labels} value
    for ln in lines:
        if ln.startswith("#") or not ln:
            continue
        name, _, value = ln.rpartition(" ")
        assert name and (value == "NaN" or float(value) is not None)


# ---------------------------------------------------------------------------
# tuner-outcome trajectory


def test_record_tuning_appends_and_mirrors(tmp_path, monkeypatch):
    path = tmp_path / "TUNE_TRAJECTORY.json"
    monkeypatch.setenv(telemetry.TUNE_TRAJECTORY_ENV, str(path))
    rec = dict(fingerprint="cpu|x", bucket_key="algorithm1_mp|...",
               heuristic_wall=120.0, tuned_wall=80.0, ratio=1.5,
               tuned_at=1700000000.0)
    telemetry.record_tuning(rec)
    telemetry.record_tuning(dict(rec, bucket_key="share_mp|..."))
    doc = json.loads(path.read_text())
    assert doc["suite"] == "tune_trajectory"
    assert len(doc["records"]) >= 2
    tail = doc["records"][-1]
    assert set(rec) <= set(tail)
    assert tail["ratio"] == 1.5
    assert any(r["bucket_key"].startswith("algorithm1_mp")
               for r in doc["records"])
