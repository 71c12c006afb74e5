"""Traffic: the scans a cell's client sends, made from ``--seed``.

One general generator for every traffic mix. A mix is a data file
``bench/mixes/<traffic>.json`` with the keys of :data:`MIX_KEYS`:

``pool``          distinct scans made in set-up and sent in turn;
``coarse``        the projections are uniform noise on a grid this many
                  times coarser than the detector, upsampled cubically;
``check_voxels``  voxels of every scan that the reference recomputes.

The client is a closed loop: one lab reconstructing scans back to back,
each request sent when the previous volume is on the host.

The scans are smooth random fields: projections of a real object are
smooth at the pixel scale, and white noise there puts the float32
rounding of the detector coordinates at the paper's bar (relative RMSE
1.2e-5 on a v5e at P5, 8.3e-6 on the CPU).
"""

from __future__ import annotations

import functools

import numpy as np

MIX_KEYS = ("pool", "coarse", "check_voxels")


def _entropy(seed: int) -> int:
    return int(seed) & (2 ** 64 - 1)


def seed_key(seed: int, stream: int):
    """A JAX PRNG key for ``(seed, stream)``. Every whole number is its
    own seed (``jax.random.PRNGKey`` keeps only the low 32 bits)."""
    import jax
    state = np.random.SeedSequence(
        [_entropy(seed), int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(state, np.uint32))


@functools.lru_cache(maxsize=None)
def _maker(n_proj: int, nh: int, nw: int, coarse: int):
    import jax
    import jax.numpy as jnp

    def make(key):
        grid = jax.random.uniform(key, (n_proj, nh // coarse, nw // coarse),
                                  jnp.float32)
        return jax.image.resize(grid, (n_proj, nh, nw), method="cubic")

    return jax.jit(make)


def make_projections(key, n_proj: int, nh: int, nw: int, coarse: int):
    """(n_proj, nh, nw) float32 smooth random projections, made on the
    device in one jitted call."""
    return _maker(int(n_proj), int(nh), int(nw), int(coarse))(key)


def make_pool(geom: dict, mix: dict, seed: int) -> list:
    """The mix's pool of distinct scans in host memory (numpy), each made
    on the device from ``(seed, index)`` and copied back."""
    return [np.asarray(make_projections(
        seed_key(seed, s), geom["n_proj"], geom["nh"], geom["nw"],
        mix["coarse"])) for s in range(mix["pool"])]


def sample_voxels(geom: dict, n: int, seed: int) -> np.ndarray:
    """(n, 3) int voxel indices (i, j, k) drawn from the seed, every
    voxel of the volume equally likely."""
    rng = np.random.default_rng([_entropy(seed), 1])
    return np.stack([rng.integers(0, geom[a], n)
                     for a in ("nx", "ny", "nz")], axis=1)
