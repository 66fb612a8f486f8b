"""Banded density matrix: McWeeny's initial guess from a gapped banded
insulator Hamiltonian, the operand of CP2K's linear-scaling SCF.

The same matrix as ``banded_hamiltonian`` followed by ``initial_density``
in the program's ``sparsity/workloads.py`` (same random draws, same
arithmetic), kept here so that a change to the program cannot change the
yardstick.  It is built as band blocks, ``(nb, 2 hb + 1, bs, bs)``, on
the host (a few MB at n = 11,264) and assembled into the dense payload on
the device in one jitted call, so no dense n x n array ever exists on the
host.
"""
from __future__ import annotations

import numpy as np

from bench.operands import Operands, seed32


def band_blocks(n: int, bs: int, *, half_bandwidth: int, gap: float,
                coupling: float, decay: float, seed: int) -> np.ndarray:
    """The Hamiltonian H as float64 band blocks: ``band[i, hb + d]`` is
    block ``(i, i + d)``, zero where ``i + d`` falls off the matrix.

    Orbitals alternate between an occupied level (-gap/2, even index)
    and a virtual one (+gap/2, odd); block distance d in [1, hb] carries
    symmetric random coupling of Frobenius norm ``coupling * decay**(d-1)``
    between same-parity orbitals only.  ``np.random.RandomState(seed)``
    is drawn in the order ``sparsity/workloads.py`` draws it."""
    if n % bs or bs % 2:
        raise ValueError(f"n={n} must be a multiple of an even block {bs}")
    nb = n // bs
    hb = min(half_bandwidth, nb - 1)
    rng = np.random.RandomState(seed)
    band = np.zeros((nb, 2 * hb + 1, bs, bs))
    levels = np.where(np.arange(n) % 2 == 0, -gap / 2.0, gap / 2.0)
    band[:, hb] = levels.reshape(nb, bs)[:, :, None] * np.eye(bs)
    parity = ((np.arange(bs)[:, None] + np.arange(bs)[None, :]) % 2) == 0
    for d in range(1, hb + 1):
        scale = coupling * decay ** (d - 1)
        for i in range(nb - d):
            blk = rng.randn(bs, bs) * parity
            blk *= scale / max(np.linalg.norm(blk), 1e-300)
            band[i, hb + d] = blk
            band[i + d, hb - d] = blk.T
    return band


def density_band(band: np.ndarray) -> np.ndarray:
    """McWeeny's linear initial guess ``P0 = I/2 - H / (2 lam)`` at
    chemical potential 0, with ``lam`` the Gershgorin bound of H, in band
    form."""
    nb, width, bs, _ = band.shape
    hb = (width - 1) // 2
    diag = np.einsum("ijj->ij", band[:, hb]).reshape(-1)
    radii = np.abs(band).sum(axis=(1, 3)).reshape(-1) - np.abs(diag)
    lam = max(float(np.max(diag + radii)), float(np.max(radii - diag)),
              1e-12)
    p = -band / (2.0 * lam)
    p[:, hb] += 0.5 * np.eye(bs)
    return p


def band_mask(nb: int, hb: int) -> np.ndarray:
    i = np.arange(nb)
    return np.abs(i[:, None] - i[None, :]) <= hb


def band_norms(band: np.ndarray) -> np.ndarray:
    """(nb, nb) float64 Frobenius norms of the blocks, zero off the band."""
    nb, width = band.shape[:2]
    hb = (width - 1) // 2
    out = np.zeros((nb, nb))
    norms = np.sqrt((band ** 2).sum(axis=(2, 3)))
    for w in range(width):
        rows = np.arange(max(0, hb - w), min(nb, nb + hb - w))
        out[rows, rows + w - hb] = norms[rows, w]
    return out


def assemble(band, n: int):
    """Dense (n, n) payload from band blocks, under ``jax.jit``."""
    import jax.numpy as jnp

    nb, width, bs, _ = band.shape
    hb = (width - 1) // 2
    i = jnp.arange(nb)[:, None]
    d = jnp.arange(nb)[None, :] - i + hb
    inside = (d >= 0) & (d < width)
    blocks = band[i, jnp.clip(d, 0, width - 1)]
    blocks = jnp.where(inside[:, :, None, None], blocks, 0.0)
    return blocks.transpose(0, 2, 1, 3).reshape(n, n)


def make(config: dict, seed: int, sharding) -> Operands:
    """P0 as the left and the right operand of ``P0 @ P0``."""
    import jax

    n, bs = config["n"], config["block_size"]
    band = density_band(band_blocks(
        n, bs, half_bandwidth=config["half_bandwidth"], gap=config["gap"],
        coupling=config["coupling"], decay=config["decay"],
        seed=seed32(seed)))
    nb, width = band.shape[:2]
    mask = band_mask(nb, (width - 1) // 2)
    a = jax.jit(lambda b: assemble(b, n), out_shardings=sharding)(
        band.astype(config["dtype"]))
    return Operands(a=a, b=None, a_mask=mask, b_mask=mask,
                    a_norms=band_norms(band), b_norms=None)
