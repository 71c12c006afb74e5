"""Autotuner (ISSUE 5): measured config search + persistent TuningCache.

Covers the seams the ISSUE pins down:
  * TuningCache robustness — fingerprint mismatch re-tunes, a corrupt
    or missing cache file degrades to the heuristics (never an error),
    winners survive the JSON round trip with hashable tuples intact;
  * exactness contract — the default (explicit-variant) search tunes
    only order-only knobs, so the tuned config's volume is
    BIT-identical to the heuristic config across >= 4 variants;
  * zero re-measurement — a persisted winner resolves as a cache hit
    with ``trials == 0`` and without ever entering ``_measure_config``
    (asserted in-process with a poisoned measurer AND across real
    processes via ``ReconService.warmup(tune=True)`` — the acceptance
    scenario);
  * end-to-end integration — ``plan_reconstruction(variant="auto" /
    tuning=...)`` and the ``fdk_reconstruct`` façade resolve the tuned
    plan; the service reports tuned-vs-heuristic per bucket.
"""

import dataclasses
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import fdk_reconstruct, standard_geometry
from repro.runtime import autotune as at
from repro.runtime.autotune import (TunedConfig, TuningCache, autotune,
                                    fingerprint_key, request_key,
                                    resolve_config)
from repro.runtime.executor import PlanExecutor, ProgramCache
from repro.runtime.planner import plan_reconstruction
from repro.runtime.service import ReconService

from conftest import rel_rmse

# one program cache for the whole module: candidates repeat across
# tests, so programs compile once and the searches stay CI-sized
_PCACHE = ProgramCache()

OPTS = dict(nb=2, tiling=(8, 8, 16), proj_batch=4)


@pytest.fixture(scope="module")
def setup():
    geom = standard_geometry(n=16, n_det=24, n_proj=6)
    rng = np.random.RandomState(3)
    projs = jnp.asarray(rng.rand(geom.n_proj, geom.nh,
                                 geom.nw).astype(np.float32))
    return geom, projs


def _tune(geom, projs, variant, cache, **kw):
    kw.setdefault("budget_s", 30.0)
    kw.setdefault("iters", 1)
    return autotune(geom, variant, **OPTS, cache=cache,
                    program_cache=_PCACHE, projections=projs, **kw)


# ---- fingerprint + request key --------------------------------------------

def test_fingerprint_shape_and_stability():
    a, b = at.hardware_fingerprint(), at.hardware_fingerprint()
    assert a == b and len(a) == 4
    assert fingerprint_key(a) == fingerprint_key(b)
    assert fingerprint_key(a).count("|") == 3


def test_request_key_tracks_bucket_key(setup):
    geom, _ = setup
    a = plan_reconstruction(geom, "algorithm1_mp", nb=2, proj_batch=4)
    b = plan_reconstruction(geom, "algorithm1_mp", nb=2, proj_batch=4)
    c = plan_reconstruction(geom, "algorithm1_mp", nb=2, proj_batch=2)
    assert request_key(a) == request_key(b)
    assert request_key(a) != request_key(c)


# ---- TuningCache robustness -----------------------------------------------

def test_cache_roundtrip_restores_tuples(setup, tmp_path):
    geom, _ = setup
    cache = TuningCache(str(tmp_path / "t.json"))
    plan = plan_reconstruction(geom, "subline_pl", nb=2,
                               tile_shape=(8, 8, 16), proj_batch=4,
                               block=(4, 8))
    cfg = at.config_from_plan(plan, pipeline="async", pipeline_depth=4)
    cache.store("fp", "rk", cfg)
    back = cache.lookup("fp", "rk")
    assert back is not None and back.key == cfg.key
    # tuple-ness survives JSON (bucket keys must stay hashable):
    # subline_pl carries block=(4, 8) in its options
    assert dict(back.options)["block"] == (4, 8)
    assert isinstance(back.tile_shape, tuple)
    hash(back.build_plan(geom).bucket_key)    # must not raise


def test_missing_cache_file_is_heuristic_fallback(setup, tmp_path):
    geom, _ = setup
    missing = str(tmp_path / "nope" / "t.json")
    assert TuningCache(missing).lookup("fp", "rk") is None
    cfg = resolve_config(geom, "subline_batch_mp",
                         cache=TuningCache(missing), **OPTS)
    assert cfg.source == "heuristic"
    # the planner path degrades identically (plan equality, not error)
    tuned = plan_reconstruction(geom, "subline_batch_mp", nb=2,
                                tile_shape=(8, 8, 16), proj_batch=4,
                                tuning=missing)
    plain = plan_reconstruction(geom, "subline_batch_mp", nb=2,
                                tile_shape=(8, 8, 16), proj_batch=4)
    assert tuned == plain


def test_corrupt_cache_file_is_heuristic_fallback(setup, tmp_path):
    geom, _ = setup
    bad = tmp_path / "t.json"
    for garbage in ("{not json", '{"version": 99}', '[1, 2]', ""):
        bad.write_text(garbage)
        cache = TuningCache(str(bad))
        assert cache.lookup("fp", "rk") is None
        assert resolve_config(geom, "subline_batch_mp", cache=cache,
                              **OPTS).source == "heuristic"
    # a corrupt file is also recoverable: store() rewrites it whole
    bad.write_text("{not json")
    cache = TuningCache(str(bad))
    plan = plan_reconstruction(geom, "subline_batch_mp", nb=2)
    cache.store("fp", "rk", at.config_from_plan(plan))
    assert cache.lookup("fp", "rk") is not None
    json.load(open(str(bad)))                 # valid JSON again


def test_malformed_entry_is_a_miss(tmp_path):
    p = tmp_path / "t.json"
    p.write_text(json.dumps({"version": 1, "fingerprints": {
        "fp": {"rk": {"variant": "algorithm1_mp"}}}}))   # missing fields
    assert TuningCache(str(p)).lookup("fp", "rk") is None


# ---- measured search + persistence ----------------------------------------

def test_autotune_measures_then_hits_cache(setup, tmp_path, monkeypatch):
    """Fresh cache: the search measures (trials > 0, heuristic always
    included). Second resolution: cache hit with ZERO re-measurement —
    the measurer is poisoned to prove it is never entered."""
    geom, projs = setup
    cache = TuningCache(str(tmp_path / "t.json"))
    cfg = _tune(geom, projs, "subline_batch_mp", cache)
    assert cfg.source == "measured" and cfg.trials > 0
    assert cfg.baseline_us > 0 and cfg.wall_us > 0
    assert len(cache) == 1

    def boom(*a, **k):
        raise AssertionError("cache hit must not re-measure")

    monkeypatch.setattr(at, "_measure_config", boom)
    again = _tune(geom, projs, "subline_batch_mp", cache)
    assert again.source == "cache" and again.trials == 0
    assert again.key == cfg.key               # the SAME config


def test_fingerprint_mismatch_retunes(setup, tmp_path, monkeypatch):
    """A winner recorded under different hardware is never trusted:
    the lookup misses and the search runs again."""
    geom, projs = setup
    cache = TuningCache(str(tmp_path / "t.json"))
    _tune(geom, projs, "subline_batch_mp", cache)
    monkeypatch.setattr(at, "hardware_fingerprint",
                        lambda: ("cpu", "other-machine", 128, "9.9.9"))
    cfg = _tune(geom, projs, "subline_batch_mp", cache)
    assert cfg.source == "measured" and cfg.trials > 0
    assert len(cache) == 2                    # both fingerprints persisted


# ---- exactness contract ----------------------------------------------------

@pytest.mark.parametrize("variant", ["algorithm1_mp", "subline_batch_mp",
                                     "share_mp", "symmetry_mp"])
def test_tuned_config_bit_identical(setup, tmp_path, variant):
    """Default (exact) tuning searches only order-only knobs
    (schedule/pipeline/depth) -> the tuned config's volume is
    BIT-identical to the heuristic config, for >= 4 variants."""
    geom, projs = setup
    cache = TuningCache(str(tmp_path / "t.json"))
    cfg = _tune(geom, projs, variant, cache)
    assert cfg.variant == variant             # exact mode never switches
    ref = fdk_reconstruct(projs, geom, variant=variant, **OPTS)
    tuned = PlanExecutor.from_config(geom, cfg,
                                     cache=_PCACHE).reconstruct(projs)
    assert np.array_equal(np.asarray(ref), np.asarray(tuned)), cfg


def test_wide_search_parity_at_tolerance(setup, tmp_path):
    """variant="auto" widens to numeric knobs (variant/tile/chunk):
    parity vs the heuristic is at tolerance, and the winner never loses
    to the measured heuristic baseline."""
    geom, projs = setup
    cache = TuningCache(str(tmp_path / "t.json"))
    cfg = _tune(geom, projs, "auto", cache,
                variants=("algorithm1_mp", "subline_batch_mp"))
    assert cfg.wall_us <= cfg.baseline_us
    ref = fdk_reconstruct(projs, geom, variant="algorithm1_mp", **OPTS)
    tuned = PlanExecutor.from_config(geom, cfg,
                                     cache=_PCACHE).reconstruct(projs)
    assert rel_rmse(tuned, ref) < 1e-5


def test_refused_candidates_are_recorded(setup, tmp_path, monkeypatch):
    """A candidate that cannot run (say a kernel the chip's compiler
    refuses) does not end the search, and the winner names it with its
    error, through the cache's JSON round trip."""
    geom, projs = setup
    measure = at._measure_config

    def refuse(geom_, cfg, *a, **k):
        if cfg.variant == "subline_batch_mp":
            raise RuntimeError("Mosaic refused subline_batch_mp\nmore")
        return measure(geom_, cfg, *a, **k)

    monkeypatch.setattr(at, "_measure_config", refuse)
    cache = TuningCache(str(tmp_path / "t.json"))
    cfg = _tune(geom, projs, "auto", cache,
                variants=("algorithm1_mp", "subline_batch_mp"))
    assert cfg.variant == "algorithm1_mp"
    assert ("subline_batch_mp",
            "RuntimeError: Mosaic refused subline_batch_mp") in cfg.refused
    (entry,) = cache.entries()[fingerprint_key()].values()
    assert TunedConfig.from_json(entry).refused == cfg.refused


def test_explicit_request_never_resolves_auto_winner(setup, tmp_path):
    """An auto-tuned winner may carry a different variant; a request
    that NAMES a variant must not resolve it (scoped request keys) —
    it stays on its own (heuristic or explicitly-tuned) config."""
    geom, projs = setup
    cache = TuningCache(str(tmp_path / "t.json"))
    cfg = _tune(geom, projs, "auto", cache,
                variants=("algorithm1_mp", "subline_batch_mp"))
    # the auto scope resolves, the explicit scope does not
    assert resolve_config(geom, "auto", cache=cache,
                          **OPTS).source == "cache"
    explicit = resolve_config(geom, "algorithm1_mp", cache=cache, **OPTS)
    assert explicit.source == "heuristic"
    assert explicit.variant == "algorithm1_mp"
    # tuning the explicit request stores its own entry alongside
    _tune(geom, projs, "algorithm1_mp", cache)
    explicit = resolve_config(geom, "algorithm1_mp", cache=cache, **OPTS)
    assert explicit.source == "cache"
    assert explicit.variant == "algorithm1_mp"
    assert cfg is not None


# ---- end-to-end resolution -------------------------------------------------

def test_facade_auto_uses_persisted_winner(setup, tmp_path):
    geom, projs = setup
    cache = TuningCache(str(tmp_path / "t.json"))
    cfg = _tune(geom, projs, "auto", cache, exact=True)
    resolved = resolve_config(geom, "auto", cache=cache, **OPTS)
    assert resolved.source == "cache" and resolved.key == cfg.key
    ref = fdk_reconstruct(projs, geom, variant="algorithm1_mp", **OPTS)
    via = fdk_reconstruct(projs, geom, variant="auto",
                          tuning=str(tmp_path / "t.json"), **OPTS)
    assert np.array_equal(np.asarray(ref), np.asarray(via))


def test_service_reports_tuned_vs_heuristic(setup, tmp_path, monkeypatch):
    """warmup(tune=True) buckets report their choice source; plain
    requests stay heuristic; a second tuned warmup over the persisted
    cache is a pure hit (poisoned measurer)."""
    geom, projs = setup
    path = str(tmp_path / "t.json")
    with ReconService(max_inflight=1, cache=_PCACHE, tuning=path) as svc:
        stats = svc.warmup([geom], tune=True, tune_budget_s=30.0,
                           variant="subline_batch_mp", iters=1, **OPTS)
        assert stats.buckets[0].source == "tuned-measured"
        v = svc.reconstruct(projs, geom, variant="subline_batch_mp", **OPTS)
        stats = svc.stats()
        assert stats.bucket_hits == 1         # request joined the bucket
        assert stats.buckets[0].completed == 1

    def boom(*a, **k):
        raise AssertionError("persisted winner must not re-measure")

    monkeypatch.setattr(at, "_measure_config", boom)
    with ReconService(max_inflight=1, cache=_PCACHE, tuning=path) as svc:
        stats = svc.warmup([geom], tune=True,
                           variant="subline_batch_mp", **OPTS)
        b = stats.buckets[0]
        assert b.source == "tuned-cache"
        v2 = svc.reconstruct(projs, geom, variant="subline_batch_mp", **OPTS)
    assert np.array_equal(np.asarray(v), np.asarray(v2))


def test_second_process_cache_hit(setup, tmp_path):
    """The acceptance scenario, with REAL process isolation: process 1
    tunes on a fresh cache; process 2 resolves the persisted winner
    with zero measurements and picks the identical config."""
    path = str(tmp_path / "t.json")
    script = r"""
import sys, json
sys.path.insert(0, "src")
import numpy as np
import jax.numpy as jnp
from repro.core import standard_geometry
from repro.runtime import autotune as at
from repro.runtime.service import ReconService

calls = []
orig = at._measure_config
def spy(*a, **k):
    calls.append(1)
    return orig(*a, **k)
at._measure_config = spy

geom = standard_geometry(n=16, n_det=24, n_proj=6)
svc = ReconService(max_inflight=1, tuning=PATH)
stats = svc.warmup([geom], tune=True, tune_budget_s=20.0, iters=1,
                   variant="subline_batch_mp", nb=2, tiling=(8, 8, 16),
                   proj_batch=4)
b = stats.buckets[0]
key = list(svc._buckets.values())[0].config.key
print("RESULT:" + json.dumps({"measured": len(calls), "source": b.source,
                              "key": repr(key)}))
svc.close()
""".replace("PATH", repr(path))

    def run_once():
        env = dict(os.environ)
        env["PYTHONPATH"] = "src"
        proc = subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True, timeout=600,
                              cwd=os.path.dirname(os.path.dirname(
                                  os.path.abspath(__file__))), env=env)
        assert proc.returncode == 0, proc.stderr[-3000:]
        line = [ln for ln in proc.stdout.splitlines()
                if ln.startswith("RESULT:")][-1]
        return json.loads(line[len("RESULT:"):])

    first = run_once()
    assert first["measured"] > 0 and first["source"] == "tuned-measured"
    second = run_once()
    assert second["measured"] == 0            # zero re-measurement
    assert second["source"] == "tuned-cache"  # cache hit asserted
    assert second["key"] == first["key"]      # the same config


def test_default_requests_land_in_tuned_bucket(setup, tmp_path):
    """warmup(tune=True) flips the service into tuned resolution: a
    later request with DEFAULT options (no variant named) resolves
    through the same cache and hits the tuned bucket — zero new
    buckets, zero new compiles."""
    geom, projs = setup
    with ReconService(max_inflight=1, cache=_PCACHE) as svc:
        svc.warmup([geom], tune=True, tune_budget_s=30.0,
                   tuning=TuningCache(str(tmp_path / "t.json")),
                   exact=True, iters=1, **OPTS)
        misses = svc.stats().cache["misses"]
        svc.reconstruct(projs, geom, **OPTS)      # no variant named
        stats = svc.stats()
    assert stats.bucket_misses == 1 and stats.bucket_hits == 1
    assert stats.cache["misses"] == misses
    assert stats.buckets[0].source == "tuned-measured"


def test_auto_accepts_cross_variant_options(setup, tmp_path):
    """variant="auto" requests may carry options only SOME variants
    accept (e.g. proj_loop for the Pallas candidates): the base plan
    must not reject them, a registry-wide bogus option still fails
    fast, and option-differing auto requests get distinct cache keys."""
    geom, projs = setup
    cache = TuningCache(str(tmp_path / "t.json"))
    cfg = resolve_config(geom, "auto", cache=cache, proj_loop=False, **OPTS)
    assert cfg.source == "heuristic"          # no crash, no entry yet
    v = fdk_reconstruct(projs, geom, variant="auto",
                        tuning=str(tmp_path / "t.json"), proj_loop=False,
                        **OPTS)
    assert np.asarray(v).shape == (16, 16, 16)
    with pytest.raises(ValueError, match="no registered variant"):
        resolve_config(geom, "auto", cache=cache, bogus_knob=1, **OPTS)
    # distinct keys: a winner tuned WITH the option is invisible to a
    # request without it (and vice versa)
    _tune(geom, projs, "auto", cache, exact=True, proj_loop=False)
    assert resolve_config(geom, "auto", cache=cache, proj_loop=False,
                          **OPTS).source == "cache"
    assert resolve_config(geom, "auto", cache=cache,
                          **OPTS).source == "heuristic"


def test_explicit_schedule_is_pinned(setup, tmp_path):
    """A caller-named schedule is a contract (chunk-major = bounded
    device residency): the tuner must not flip it."""
    geom, projs = setup
    cache = TuningCache(str(tmp_path / "t.json"))
    cfg = _tune(geom, projs, "subline_batch_mp", cache, schedule="chunk")
    assert cfg.schedule == "chunk"
    assert cfg.trials > 1                     # pipeline axis still ran


def test_tuned_warmup_upgrades_existing_bucket(setup, tmp_path):
    """A heuristic bucket created by early traffic is UPGRADED in
    place when warmup(tune=True) resolves a winner with the same
    bucket_key (pipeline/depth are not part of the key) — the tuned
    choice must not be silently dropped."""
    geom, projs = setup
    path = str(tmp_path / "t.json")
    with ReconService(max_inflight=1, cache=_PCACHE) as svc:
        svc.reconstruct(projs, geom, variant="subline_batch_mp", **OPTS)
        assert svc.stats().buckets[0].source == "heuristic"
        svc.warmup([geom], tune=True, tuning=TuningCache(path), iters=1,
                   tune_budget_s=30.0, variant="subline_batch_mp", **OPTS)
        stats = svc.stats()
        b = stats.buckets[0]
        if stats.bucket_misses == 1:          # same bucket_key: upgraded
            assert b.source == "tuned-measured"
            cfg = list(svc._buckets.values())[0].config
            assert b.pipeline == cfg.pipeline
        else:                                 # winner re-planned: own bucket
            assert {x.source for x in stats.buckets} == \
                {"heuristic", "tuned-measured"}
        v = svc.reconstruct(projs, geom, variant="subline_batch_mp", **OPTS)
    ref = fdk_reconstruct(projs, geom, variant="subline_batch_mp", **OPTS)
    assert np.array_equal(np.asarray(v), np.asarray(ref))


# ---- TunedConfig mechanics -------------------------------------------------

def test_config_speedup_and_replace(setup):
    geom, _ = setup
    plan = plan_reconstruction(geom, "algorithm1_mp", nb=2)
    cfg = at.config_from_plan(plan)
    cfg = dataclasses.replace(cfg, wall_us=50.0, baseline_us=100.0)
    assert cfg.speedup == pytest.approx(2.0)
    assert at.config_from_plan(plan).speedup == 1.0   # unmeasured


# ---- self-maintaining cache: stale-entry revalidation ----------------------

def _entry_key(cache):
    fp = list(cache.entries())[0]
    return fp, list(cache.entries()[fp])[0]


def test_stale_drifted_entry_invalidates_and_retunes(setup, tmp_path):
    """A stale entry whose recorded baseline is wildly off for this
    machine (planted: 1000x) must be invalidated on resolve and the
    full search re-run — the self-maintenance contract."""
    geom, projs = setup
    cache = TuningCache(str(tmp_path / "t.json"))
    cfg = _tune(geom, projs, "algorithm1_mp", cache)
    assert cfg.source == "measured" and cfg.tuned_at > 0
    fp, rkey = _entry_key(cache)
    bad = dataclasses.replace(cfg, baseline_us=cfg.baseline_us / 1000.0,
                              tuned_at=time.time() - 7 * 86400)
    cache.store(fp, rkey, bad)
    redo = _tune(geom, projs, "algorithm1_mp", cache)
    assert redo.source == "measured" and redo.trials > 0
    assert cache.lookup(fp, rkey).tuned_at > time.time() - 600


def test_stale_consistent_entry_restamps_without_retune(setup, tmp_path):
    """A stale entry whose baseline still matches reality keeps its
    winner: one cheap probe, a freshness restamp, zero search trials.
    The RECORDED baseline is kept (restamping it too would let slow
    drift creep under the threshold)."""
    geom, projs = setup
    cache = TuningCache(str(tmp_path / "t.json"))
    cfg = _tune(geom, projs, "algorithm1_mp", cache)
    fp, rkey = _entry_key(cache)
    old = dataclasses.replace(cfg, tuned_at=time.time() - 7 * 86400)
    cache.store(fp, rkey, old)
    hit = _tune(geom, projs, "algorithm1_mp", cache)
    assert hit.source == "cache" and hit.trials == 0
    restamped = cache.lookup(fp, rkey)
    assert restamped.tuned_at > time.time() - 600
    assert restamped.baseline_us == old.baseline_us


def test_fresh_entry_still_resolves_without_measuring(setup, tmp_path,
                                                      monkeypatch):
    """The revalidation probe must not tax the fast path: a FRESH hit
    (younger than revalidate_s) never enters _measure_config."""
    geom, projs = setup
    cache = TuningCache(str(tmp_path / "t.json"))
    _tune(geom, projs, "algorithm1_mp", cache)

    def boom(*a, **k):
        raise AssertionError("fresh cache hit must not measure")

    monkeypatch.setattr(at, "_measure_config", boom)
    hit = _tune(geom, projs, "algorithm1_mp", cache)
    assert hit.source == "cache" and hit.trials == 0


def test_invalidate_and_legacy_staleness(setup, tmp_path):
    geom, projs = setup
    cache = TuningCache(str(tmp_path / "t.json"))
    cfg = _tune(geom, projs, "algorithm1_mp", cache)
    fp, rkey = _entry_key(cache)
    # documents written before the tuned_at field existed deserialize
    # as always-stale (first resolve revalidates them)
    doc = cfg.to_json()
    del doc["tuned_at"]
    assert TunedConfig.from_json(doc).tuned_at == 0.0
    assert cache.invalidate(fp, "missing-key") is False
    assert cache.invalidate(fp, rkey) is True
    assert cache.lookup(fp, rkey) is None
