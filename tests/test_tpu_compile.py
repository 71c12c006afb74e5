"""Compile the chip's programs for a described TPU v5e, without the chip.

The TPU compiler is installed with jaxlib, and it compiles for a chip
that is described rather than attached. These tests catch what the
Pallas interpreter cannot: Mosaic lowering refusals (gathers, reversals,
vector loads from SMEM, unaligned slices), scoped-VMEM and SMEM
overflows, and step programs that do not fit the chip's HBM.

Every kernel registered as ``backend="pallas"`` in ``core.variants`` is
compiled, per projection and fused (``proj_loop``), at the widths of
paper Table 3 P5 and P9, and the step-major scan programs and the FDK
filter are compiled at the tiles and view chunks ``chip_smoke.py`` runs.

The sub-line kernel's Mosaic module is also lowered for a TPU at the
benchmark's widths and read op by op: its stage-2 ``k`` chunks must be
unrolled, so that the chip's scheduler can interleave their gathers.

The topology is described inside a module fixture (never at import
time): only one process may load the TPU library, and the test workers
must all collect the same tests.
"""

import base64
import importlib.util
import json
import os

import pytest

import jax
import jax.numpy as jnp
from jax.extend.mlir import ir

from repro.core import backproject as bp
from repro.core.variants import REGISTRY
from repro.kernels.backproject_banded import BAND_TABLE_ENTRIES, _banded_call
from repro.kernels.backproject_onehot import backproject_onehot_pallas
from repro.kernels.backproject_subline import (LANES,
                                               backproject_subline_pallas,
                                               padded_lanes, padded_rows)
from repro.kernels.ops import default_block
from repro.runtime.executor import ProgramCache

GiB = 2 ** 30
# v5e: 16 GiB of HBM, of which XLA may allocate 15.75 GiB.
HBM_LIMIT = 15.75 * GiB
# Paper Table 3: (label, detector, volume); 512 views each.
WIDTHS = [("P5", 512, 512), ("P9", 1024, 1024)]
N_PROJ = 512


def _chip_smoke():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # compiles for a described chip are written to the persistent cache
    # but can never be read back without one: keep the cache off
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                     # noqa: BLE001
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # the default backend is still the CPU: trace the TPU's forms
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(bp, "_interp_by_hat", lambda: True)
        jax.clear_caches()
        yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_program(variant, det, vol, nb, sharding):
    """(jitted kernel, argument shapes) for one padded kernel call."""
    block = default_block(vol, vol)
    rows, nh_p = padded_rows(det), padded_lanes(det)
    shape = (vol, vol, vol)
    if variant == "banded_pl":
        bw = 32
        tiles = (vol // block[0]) * (vol // block[1])
        n = nb * max(1, BAND_TABLE_ENTRIES // tiles)
        fn = lambda i, m, b: _banded_call(             # noqa: E731
            i, m, b, None, shape, block=block, bw=bw, nw=det, nh=det,
            nb=nb, interpret=False)
        args = (_spec(sharding, (n, -(-det // bw), 2 * bw, nh_p)),
                _spec(sharding, (n, 3, 4)),
                _spec(sharding, (n // nb * tiles,), jnp.int32))
        return jax.jit(fn), args
    kernel = {"subline_pl": backproject_subline_pallas,
              "onehot_pl": backproject_onehot_pallas}[variant]
    fn = lambda i, m: kernel(i, m, shape, block=block, nb=nb,  # noqa: E731
                             nw=det, nh=det, interpret=False)
    n = 16
    return jax.jit(fn), (_spec(sharding, (n, rows, nh_p)),
                         _spec(sharding, (n, 3, 4)))


PALLAS = sorted(n for n, s in REGISTRY.items() if s.is_pallas)


def test_every_pallas_kernel_is_covered():
    assert PALLAS == ["banded_pl", "onehot_pl", "subline_pl"]


@pytest.mark.parametrize("label,det,vol", WIDTHS, ids=[w[0] for w in WIDTHS])
@pytest.mark.parametrize("nb", [1, 8], ids=["per_projection", "proj_loop"])
@pytest.mark.parametrize("variant", PALLAS)
def test_pallas_kernel_compiles_for_v5e(one_chip, variant, nb, label, det,
                                        vol):
    fn, args = _kernel_program(variant, det, vol, nb, one_chip)
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _mosaic_tree(det, vol):
    """The sub-line kernel's Mosaic module at ``block=(8, 32)``, ``nb=8``
    (the benchmark's options), lowered for a TPU on this host, as a
    ``(op name, [children])`` tree."""
    rows, nh_p = padded_rows(det), padded_lanes(det)
    fn = jax.jit(lambda i, m: backproject_subline_pallas(
        i, m, (vol, vol, vol), block=(8, 32), nb=8, nw=det, nh=det,
        interpret=False))
    module = fn.trace(
        jax.ShapeDtypeStruct((16, rows, nh_p), jnp.float32),
        jax.ShapeDtypeStruct((16, 3, 4), jnp.float32),
    ).lower(lowering_platforms=("tpu",)).compiler_ir("stablehlo")
    # each Pallas kernel is a tpu_custom_call whose backend config (JSON)
    # holds the serialized Mosaic module
    bodies = []

    def collect(op):
        if (op.name == "stablehlo.custom_call"
                and ir.StringAttr(op.attributes["call_target_name"]).value
                == "tpu_custom_call"):
            config = json.loads(
                ir.StringAttr(op.attributes["backend_config"]).value)
            bodies.append(config.get("custom_call_config", {}).get("body"))
        return ir.WalkResult.ADVANCE

    module.operation.walk(collect)
    assert len(bodies) == 1 and bodies[0], (
        "expected one tpu_custom_call whose backend config holds "
        f"custom_call_config.body, found {len(bodies)}: the TPU lowering "
        "of Pallas kernels has changed")
    ctx = module.context
    ctx.allow_unregistered_dialects = True
    with ctx:
        kernel = ir.Module.parse(base64.b64decode(bodies[0]))

        def tree(op):
            return (op.name, [tree(o) for r in op.regions
                              for b in r.blocks for o in b.operations])

        return tree(kernel.operation)


def _find(node, suffix):
    """Every node of the tree whose op name ends with ``suffix``."""
    name, kids = node
    found = [node] if name.endswith(suffix) else []
    return found + [n for k in kids for n in _find(k, suffix)]


@pytest.mark.parametrize("label,det,vol", [("P5", 512, 512),
                                           ("P7", 1024, 256)],
                         ids=["P5", "P7"])
def test_subline_stage2_chunks_unrolled(label, det, vol):
    tree = _mosaic_tree(det, vol)
    # the line-group loop: the innermost loop around stage 1's blend
    groups = [f for f in _find(tree, "scf.for")
              if _find(f, "vector.multi_reduction")
              and not any(_find(g, "vector.multi_reduction")
                          for g in _find(f, "scf.for")[1:])]
    assert len(groups) == 1, label
    assert len(_find(groups[0], "scf.for")) == 1, \
        f"{label}: a loop is left inside the line-group loop"
    # one gather per 128 detector rows, for both taps, in every chunk
    n_gathers = 2 * (vol // LANES) * (padded_lanes(det) // LANES)
    assert len(_find(tree, "tpu.dynamic_gather")) == n_gathers, label


def _fits(compiled):
    ma = compiled.memory_analysis()
    used = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)
    return used < HBM_LIMIT, used / GiB


@pytest.mark.parametrize("variant,problem", [
    ("baseline", "P5"), ("algorithm1_mp", "P5"), ("subline_pl", "P5"),
    ("subline_pl", "P9")])
def test_chip_smoke_step_program_fits_v5e(one_chip, variant, problem):
    smoke = _chip_smoke()
    _, _, bi, bj = smoke.REF_BOXES[0]
    tile, det, chunk = {
        ("baseline", "P5"): ((bi, bj, 512), 512, N_PROJ),
        ("algorithm1_mp", "P5"): (smoke.P5_TILE, 512, N_PROJ),
        ("subline_pl", "P5"): ((512, 512, 512), 512, N_PROJ),
        ("subline_pl", "P9"): (smoke.P9_TILE, 1024, smoke.P9_PROJ_BATCH),
    }[(variant, problem)]
    opts = (("proj_loop", True),) if REGISTRY[variant].is_pallas else ()
    n_chunks = N_PROJ // chunk
    prog = ProgramCache().program(
        variant, tile, 8, "float32", False, opts, grid=(n_chunks, chunk))
    ok, used = _fits(prog.lower(
        _spec(one_chip, (n_chunks, chunk, det, det)),
        _spec(one_chip, (n_chunks, chunk, 3, 4))).compile())
    assert ok, (variant, problem, used)


def test_fleet_step_program_fits_v5e(one_chip):
    """The step program at the P9 tile, with the step origin a traced
    argument that reaches the sub-line kernel as scalar-prefetched
    voxel indices: the program every fleet step runs."""
    smoke = _chip_smoke()
    chunk = smoke.P9_PROJ_BATCH
    n_chunks = N_PROJ // chunk
    prog = ProgramCache().program(
        "subline_pl", smoke.P9_TILE, 8, "float32", False,
        (("proj_loop", True),), grid=(n_chunks, chunk))
    compiled = prog.lower(
        _spec(one_chip, (n_chunks, chunk, 1024, 1024)),
        _spec(one_chip, (n_chunks, chunk, 3, 4)),
        _spec(one_chip, (3,))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ok, used = _fits(compiled)
    assert ok, used


@pytest.mark.parametrize("problem", ["P5", "P9"])
def test_chip_smoke_filter_fits_v5e(one_chip, problem):
    """The FDK filter of the views the smoke filters at once: all of
    them at P5, ``P9_PROJ_BATCH`` at P9 (all 512 ask for 16 GiB)."""
    from repro.configs.ct_paper import get_problem
    from repro.core.filtering import fdk_filter_chunk

    smoke = _chip_smoke()
    geom = get_problem(problem).geometry()
    chunk = N_PROJ if problem == "P5" else smoke.P9_PROJ_BATCH
    fn = jax.jit(lambda p: fdk_filter_chunk(p, geom, N_PROJ))
    ok, used = _fits(fn.lower(
        _spec(one_chip, (chunk, geom.nh, geom.nw))).compile())
    assert ok, (problem, used)
