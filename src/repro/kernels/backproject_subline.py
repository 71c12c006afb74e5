"""Pallas TPU kernel: sub-line back-projection (paper Algorithm 1 + O6).

TPU-native schedule (see DESIGN.md §2 for the CPU->TPU mapping):

  grid = (ni/BI, nj/BJ, np/nb)       # projection batches innermost
  img block   (nb, nw, nh) <- indexed by the batch: streamed through VMEM,
                            Pallas double-buffers it across grid steps =
                            the paper's Algorithm 2 prefetch, for free.
  mat block   (nb, 3, 4) <- SMEM scalars (the 48-byte matrix of §3.2.1-I),
                            read one scalar at a time.
  out block   (BI,BJ,nz) <- indexed by (ti,tj) only: VMEM-resident across
                            the whole projection sweep (output-stationary),
                            zeroed at the first batch, written back to HBM
                            exactly once. This is the nb->np limit of the
                            paper's batching: volume HBM traffic = one write.
  scratch     (8, nh)    <- the sMem sub-line buffer (Fig. 3a) in VMEM.

``nb == 1`` is the per-projection grid; ``nb > 1`` (``proj_loop``) walks
the batch with an in-kernel ``fori_loop``, so the Z-slab accumulator is
read-modified-written once per nb projections (paper O5 in the kernel).

Inside each grid cell the voxel lines of the (BI, BJ) tile are processed
in groups of 8 (TPU sublanes). Per line the k-invariant scalars
F = 1/z, W = F*F, X (paper lines 4..7) are computed on the scalar core
from SMEM matrix entries — the hoisting of O2 — and X selects the two
detector columns whose blend is the sub-line (O4). The columns are read
as one 8-aligned 16-row slab and blended by a masked sublane reduction
(Mosaic loads at dynamic sublane offsets only when they are 8-aligned).

The vertical coordinate is affine in k, evaluated over 128-lane k chunks.
O3 is the hoisted mirror intercept of ``core.backproject``: the upper
half's row is y'(k) = (nh-1) - y(nz-1-k) = a_m + b*k, so both halves are
one select + FMA and nothing is reversed. Stage 2 interpolates inside the
sub-line with single-vreg lane gathers (one per 128-row chunk of the
sub-line, merged by select): the only gather Mosaic lowers on v5e.

Stage 2's k chunks are unrolled, so the scheduler interleaves them. A
lane gather is a round trip through the XLU (push the pattern, permute,
pop, with the pop 60 to 100 bundles after the push on v5e), longer than
the rest of a chunk's work; in a rolled loop each chunk waited on its
own gathers, and most of stage 2's bundles were empty. The chunks are
independent (each writes its own 128 lanes of the output and reads only
the sub-line and the group's scalars), so one chunk's gathers overlap
the next one's arithmetic.

Alignment: the wrappers in ops.py pad nw to a multiple of 8 (at least 16)
and nh to a multiple of 128 with zeros, and pass the TRUE nw/nh for the
validity masks and the mirror. nz is never padded; a partial last k chunk
is stored with a lane mask. CPU validation runs the same kernel with
interpret=True.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128            # k-chunk width and sub-line gather granule
SLAB = 16              # detector columns read per line (8-aligned window)
# Default scoped VMEM of a v5e TensorCore; kernels that need more raise
# the limit explicitly (the chip has 128 MiB).
_DEFAULT_VMEM = 16 * 2 ** 20
_MAX_VMEM = 100 * 2 ** 20


def fused_batch_ok(n_proj: int, nb: int, proj_loop: bool) -> bool:
    """Whether the fused multi-batch (``proj_loop``) kernel may run: an
    in-kernel batch needs nb >= 2 and an nb-divisible projection count
    (the executor pads globally; raw callers fall back silently). The
    ONE eligibility rule, shared by all three kernel wrappers."""
    return bool(proj_loop) and nb > 1 and n_proj % nb == 0


def _pad_to(n: int, b: int) -> int:
    return ((n + b - 1) // b) * b


def padded_rows(nw: int) -> int:
    """Detector-column extent of the image block: 8-aligned, >= SLAB."""
    return max(SLAB, _pad_to(nw, 8))


def padded_lanes(nh: int) -> int:
    """Detector-row extent of the image block: a multiple of 128."""
    return _pad_to(nh, LANES)


def vmem_bytes(block, nz: int, rows: int, nh_p: int, nb: int,
               k_work: int = 0) -> int:
    """Modeled VMEM of one kernel instance: double-buffered image and
    output blocks, the sub-line scratch, and ``k_work`` extra bytes of
    per-chunk temporaries (the one-hot kernel's interpolation matrix)."""
    BI, BJ = block
    img = 2 * nb * rows * nh_p * 4
    out = 2 * BI * BJ * _pad_to(nz, LANES) * 4
    return img + out + 8 * nh_p * 4 + SLAB * nh_p * 4 + k_work


def compiler_params(vmem: int):
    """Raise the scoped-VMEM limit only when the model needs it."""
    if vmem + vmem // 4 <= _DEFAULT_VMEM:
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(min(_MAX_VMEM, vmem + vmem // 4 + 2 ** 20)))


class _Mat:
    """Projection ``b`` of an (nb, 3, 4) SMEM matrix block, read one
    scalar at a time (``m[r, c]`` loads ``mat_ref[b, r, c]``; Mosaic
    loads only scalars from SMEM)."""

    def __init__(self, ref, b):
        self.ref, self.b = ref, b

    def __getitem__(self, rc):
        return self.ref[self.b, rc[0], rc[1]]


def _line_scalars(m, i_g, j_g, nw):
    """Scalar-core computation of z, F, W, X, x-column and blend weight
    for one voxel line (i_g, j_g). Everything here is k-invariant (O2)."""
    i_f = i_g.astype(jnp.float32)
    j_f = j_g.astype(jnp.float32)
    z = m[2, 0] * i_f + m[2, 1] * j_f + m[2, 3]
    f = 1.0 / z
    x = (m[0, 0] * i_f + m[0, 1] * j_f + m[0, 3]) * f
    x0 = jnp.floor(x)
    ix = x0.astype(jnp.int32)
    dx = x - x0
    ok = (ix >= 0) & (ix <= nw - 2) & (z > 0)
    ixc = jnp.clip(ix, 0, nw - 2)
    w = f * f
    # Fold the line validity into the weight: invalid lines contribute 0.
    w_eff = jnp.where(ok, w, 0.0)
    return f, w_eff, ixc, dx


def _stage1_lines(m, img, smem_ref, i_g, j_base, nw, band=None):
    """Stage 1 for one 8-line group (O4, Fig. 3a): blend the two
    detector columns of each line into the sMem scratch; returns the
    (8, 1) ``f`` and effective-weight vectors.

    ``m`` is the :class:`_Mat` matrix view, ``img`` the
    (rows, nh) image view. ``band=(col0, two_bw)`` remaps detector
    columns into a 2*bw band block starting at global column ``col0``
    (lines whose columns miss the band are zeroed).
    """
    rows = img.shape[0]
    slab_row = jax.lax.broadcasted_iota(jnp.int32, (SLAB, 1), 0)
    line = jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0)
    f_vec = jnp.zeros((8, 1), jnp.float32)
    w_vec = jnp.zeros((8, 1), jnp.float32)
    for jj in range(8):
        f, w_eff, ixc, dx = _line_scalars(m, i_g, j_base + jj, nw)
        if band is not None:
            col0, two_bw = band
            rel = ixc - col0
            # zero the line if the band misses (never happens when
            # the wrapper's span check passed; belt+braces)
            w_eff = jnp.where((rel >= 0) & (rel <= two_bw - 2),
                              w_eff, 0.0)
            ixc = jnp.clip(rel, 0, two_bw - 2)
        base = pl.multiple_of(jnp.minimum((ixc // 8) * 8, rows - SLAB), 8)
        cols = img[pl.ds(base, SLAB), :]                  # (SLAB, nh)
        off = ixc - base
        wgt = jnp.where(slab_row == off, 1.0 - dx,
                        jnp.where(slab_row == off + 1, dx, 0.0))
        smem_ref[jj:jj + 1, :] = jnp.sum(cols * wgt, axis=0, keepdims=True)
        f_vec = jnp.where(line == jj, f, f_vec)
        w_vec = jnp.where(line == jj, w_eff, w_vec)
    return f_vec, w_vec


def _y_affine(m, i_g, j_base, f_vec):
    """The (8, 1) y-coefficients a, b with y(k) = a + b*k (O2 hoist)."""
    i_f = i_g.astype(jnp.float32)
    j_vec = (j_base + jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0)
             ).astype(jnp.float32)
    a = (m[1, 0] * i_f + m[1, 1] * j_vec + m[1, 3]) * f_vec
    b = m[1, 2] * f_vec
    return a, b


def _row_coords(y, nh: int):
    y0 = jnp.floor(y)
    iy = y0.astype(jnp.int32)
    dy = y - y0
    ok = (iy >= 0) & (iy <= nh - 2)
    return jnp.clip(iy, 0, nh - 2), dy, ok


def gather_interp(nh: int):
    """Stage 2 of the sub-line kernel (Fig. 3b): linear interpolation in
    the (8, nh_p) sub-line at (8, 128) row coordinates by lane gathers.

    A v5e lane gather stays inside one (8, 128) vreg, so the sub-line is
    read in 128-row chunks and each chunk's gather is kept where the
    row falls in that chunk."""

    def interp(sm_ref, y):
        iyc, dy, ok = _row_coords(y, nh)
        i1 = iyc + 1
        s0 = s1 = None
        for c in range(sm_ref.shape[1] // LANES):
            src = sm_ref[:, c * LANES:(c + 1) * LANES]
            g0 = jnp.take_along_axis(src, iyc % LANES, axis=1,
                                     mode="promise_in_bounds")
            g1 = jnp.take_along_axis(src, i1 % LANES, axis=1,
                                     mode="promise_in_bounds")
            if s0 is None:
                s0, s1 = g0, g1
            else:
                s0 = jnp.where(iyc // LANES == c, g0, s0)
                s1 = jnp.where(i1 // LANES == c, g1, s1)
        return jnp.where(ok, s0 * (1.0 - dy) + s1 * dy, 0.0)

    return interp


def _accumulate_projection(m, img, out_ref, smem_ref, i0, j0, *, BI: int,
                           GJ: int, nz: int, nw: int, nh: int, interp,
                           kw: int = LANES, band=None):
    """Accumulate ONE projection into the (BI, BJ, nz) output block.

    Shared by the sub-line, one-hot and banded kernels (``interp`` is the
    stage-2 interpolation, ``band`` the banded column remap, see
    :func:`_stage1_lines`). ``kw`` is the k-chunk width.
    """
    khp = nz - nz // 2     # direct half (includes the odd-nz middle plane)
    n_full, tail = divmod(nz, kw)

    def group(t, carry):
        ii = t // GJ
        jlo = pl.multiple_of((t % GJ) * 8, 8)
        i_g = i0 + ii
        j_base = j0 + jlo
        f_vec, w_vec = _stage1_lines(m, img, smem_ref, i_g, j_base, nw,
                                     band=band)
        a, b = _y_affine(m, i_g, j_base, f_vec)
        a_m = (nh - 1.0) - a - b * (nz - 1.0)           # O3 mirror fold

        def chunk(c0, width):
            k = (c0 + jax.lax.broadcasted_iota(jnp.int32, (8, kw), 1)
                 ).astype(jnp.float32)
            y = jnp.where(k < khp, a, a_m) + b * k
            v = interp(smem_ref, y) * w_vec
            if width < kw:
                v = v[:, :width]
            out_ref[ii, pl.ds(jlo, 8), pl.ds(c0, width)] += v

        # Unrolled: the chunks are independent, so the scheduler can
        # interleave their gather chains (see the module docstring).
        for c in range(n_full):
            chunk(c * kw, kw)
        if tail:
            chunk(n_full * kw, tail)
        return carry

    jax.lax.fori_loop(0, BI * GJ, group, 0)


def backproject_call(img_t, mat, vol_shape_xyz, *, block, nb: int,
                     nw: int, nh: int, interp, interpret: bool,
                     kw: int = LANES, k_work: int = 0, band=None,
                     bw: int = 0, acc=None, origin=None):
    """The one ``pallas_call`` behind all three kernels.

    ``img_t`` is the padded (np, rows, nh_p) image stack — or, with
    ``band`` (the flattened (np/nb * ni/BI * nj/BJ,) int32 band index
    array, see ``backproject_banded.tile_bands``), the (np, n_bands,
    2*bw, nh_p) band layout. ``nw``/``nh`` are the TRUE detector
    extents. ``nb == 1`` is the per-projection grid. ``acc`` (an
    (ni, nj, nz) volume, aliased to the output) is accumulated into
    instead of starting from zero. ``origin`` (a (2,) int32 array) is
    the whole-volume voxel index (i, j) of the call's first line: the
    detector coordinates are then computed from whole-volume indices and
    the untranslated matrices, bit for bit as an untiled call computes
    them, so a sub-box's samples at the detector's edge fall on the same
    side as the whole volume's.
    """
    n_proj, nh_p = img_t.shape[0], img_t.shape[-1]
    rows = img_t.shape[-2]
    ni, nj, nz = vol_shape_xyz
    BI, BJ = block
    assert ni % BI == 0 and nj % BJ == 0 and BJ % 8 == 0, (ni, nj, block)
    assert n_proj % nb == 0 and nb >= 1, (n_proj, nb)
    assert rows % 8 == 0 and rows >= SLAB and nh_p % LANES == 0, \
        img_t.shape
    GJ = BJ // 8
    body = functools.partial(_accumulate_projection, BI=BI, GJ=GJ, nz=nz,
                             nw=nw, nh=nh, interp=interp, kw=kw)

    T_i, T_j = ni // BI, nj // BJ

    def kernel(*refs):
        refs = list(refs)
        band_ref = refs.pop(0) if band is not None else None
        org_ref = refs.pop(0) if origin is not None else None
        mat_ref, img_ref = refs[:2]
        acc_ref = refs[2] if acc is not None else None
        out_ref, smem_ref = refs[-2:]
        ti = pl.program_id(0)
        tj = pl.program_id(1)
        sb = pl.program_id(2)

        @pl.when(sb == 0)
        def _init():
            if acc_ref is None:
                out_ref[...] = jnp.zeros_like(out_ref)
            else:
                out_ref[...] = acc_ref[...]

        span = None if band is None else (
            band_ref[(sb * T_i + ti) * T_j + tj] * bw, 2 * bw)

        def one(b, carry):
            i0, j0 = ti * BI, tj * BJ
            if org_ref is not None:
                i0, j0 = i0 + org_ref[0], j0 + org_ref[1]
            body(_Mat(mat_ref, b), img_ref.at[b], out_ref, smem_ref,
                 i0, j0, band=span)
            return carry

        jax.lax.fori_loop(0, nb, one, 0)

    grid = (T_i, T_j, n_proj // nb)
    out_spec = pl.BlockSpec((BI, BJ, nz), lambda ti, tj, s, *_: (ti, tj, 0))
    mat_spec = pl.BlockSpec((nb, 3, 4), lambda ti, tj, s, *_: (s, 0, 0),
                            memory_space=pltpu.SMEM)
    if band is None:
        img_spec = pl.BlockSpec((nb, rows, nh_p),
                                lambda ti, tj, s, *_: (s, 0, 0))
    else:
        img_spec = pl.BlockSpec(
            (nb, None, rows, nh_p),
            lambda ti, tj, s, band, *_: (
                s, band[(s * T_i + ti) * T_j + tj], 0, 0))
    in_specs = [mat_spec, img_spec]
    args = [mat.astype(jnp.float32), img_t.astype(jnp.float32)]
    if acc is not None:
        in_specs.append(out_spec)
        args.append(acc)
    prefetch = ([] if band is None else [band]) + (
        [] if origin is None else [jnp.asarray(origin, jnp.int32)])
    vmem = vmem_bytes(block, nz, rows, nh_p, nb, k_work)
    if acc is not None:
        vmem += 2 * BI * BJ * _pad_to(nz, LANES) * 4
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((ni, nj, nz), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=grid,
            in_specs=in_specs, out_specs=out_spec,
            scratch_shapes=[pltpu.VMEM((8, nh_p), jnp.float32)]),
        input_output_aliases=({} if acc is None
                              else {len(prefetch) + 2: 0}),
        compiler_params=compiler_params(vmem),
        interpret=interpret,
    )(*prefetch, *args)


@functools.partial(
    jax.jit,
    static_argnames=("vol_shape_xyz", "block", "nb", "nw", "nh",
                     "interpret"),
)
def backproject_subline_pallas(img_t: jnp.ndarray, mat: jnp.ndarray,
                               vol_shape_xyz, *, block=(8, 32), nb: int = 1,
                               nw: int, nh: int,
                               interpret: bool = False,
                               origin=None) -> jnp.ndarray:
    """Back-project padded transposed projections with the sub-line kernel.

    img_t (np, rows, nh_p) f32 padded as :func:`padded_rows` /
    :func:`padded_lanes` say; ``nw``/``nh`` the true detector extents;
    mat (np, 3, 4) f32. ``nb > 1`` runs the fused in-kernel batch loop
    (requires ``np % nb == 0``). Returns vol_t (ni, nj, nz) f32.
    Requires ni % BI == nj % BJ == 0 (ops.py pads arbitrary i/j); any nz
    (odd handled by uneven halves). ``origin``: see
    :func:`backproject_call`.
    """
    return backproject_call(img_t, mat, tuple(vol_shape_xyz), block=block,
                            nb=nb, nw=nw, nh=nh, interp=gather_interp(nh),
                            interpret=interpret, origin=origin)
