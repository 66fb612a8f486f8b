"""Operands, traffic and the count of work, against plain versions of
each.  CPU only."""
import itertools
import json

import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, traffic, work
from bench.operands import banded, seed32
from repro.sparsity.workloads import banded_hamiltonian, initial_density

BIG_SEED = 2 ** 33 + 12345


@pytest.mark.parametrize("n", [22 * 12, 22 * 3])
def test_banded_operand_equals_the_programs(n):
    config = dict(n=n, dtype="float32", block_size=22, half_bandwidth=4,
                  gap=2.0, coupling=0.3, decay=0.4)
    ops = banded.make(config, BIG_SEED, None)
    H, mask = banded_hamiltonian(n, 22, seed=seed32(BIG_SEED))
    want = initial_density(H).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(ops.a), want)
    np.testing.assert_array_equal(ops.a_mask, mask)
    blocks = want.astype(np.float64).reshape(n // 22, 22, n // 22, 22)
    np.testing.assert_allclose(
        ops.a_norms, np.sqrt((blocks ** 2).sum(axis=(1, 3))), rtol=1e-6,
        atol=1e-7)


def test_seed32_takes_large_seeds():
    assert seed32(BIG_SEED) == seed32(BIG_SEED)
    assert seed32(BIG_SEED) != seed32(BIG_SEED + 1)
    assert 0 <= seed32(2 ** 63 + 7, 3) < 2 ** 31


def brute_force(am, bm, an, bn, eps):
    nbr, nbk = am.shape
    nbc = bm.shape[1]
    count = 0
    a_used = np.zeros_like(am)
    b_used = np.zeros_like(bm)
    c_used = np.zeros((nbr, nbc), bool)
    for i, k, j in itertools.product(range(nbr), range(nbk), range(nbc)):
        if am[i, k] and bm[k, j] and an[i, k] * bn[k, j] >= eps:
            count += 1
            a_used[i, k] = b_used[k, j] = c_used[i, j] = True
    return count, a_used, b_used, c_used


@pytest.mark.parametrize("eps", [0.0, 0.05, 0.3])
def test_retained_triples_equal_brute_force(eps):
    rng = np.random.default_rng(7)
    am = rng.random((9, 7)) < 0.5
    bm = rng.random((7, 8)) < 0.5
    an = rng.random((9, 7)) * am
    bn = rng.random((7, 8)) * bm
    got = work.retained(am, bm, an, bn, eps)
    want = brute_force(am, bm, an, bn, eps)
    assert got[0] == want[0]
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(g, w)


def test_band_work_at_the_cell_size():
    """P0 @ P0 at n = 11,264, block 22, half-bandwidth 4: every band
    triple is retained, sum over k of (blocks in column k)^2."""
    nb, hb = 512, 4
    mask = banded.band_mask(nb, hb)
    norms = mask * 0.01
    w, support = work.blocked_work(mask, mask, norms, norms, 1e-6, 22, 4)
    assert w.triples == 41172
    assert w.flops == 2 * 22 ** 3 * 41172
    assert support.sum() == banded.band_mask(nb, 2 * hb).sum()
    assert w.bound({"flops_per_s": 197e12,
                    "hbm_bytes_per_s": 819e9}) == "memory"


def test_dense_work():
    w = work.dense_work(8, 4, 2, 4, 2)
    assert w.flops == 2 * 8 * 4 * 2 / 2
    assert w.bytes == 4 * (32 + 8 + 16) / 2
    assert w.roofline_s({"flops_per_s": 1.0, "hbm_bytes_per_s": 1e9}) == 64


def test_traffic_scales():
    params = traffic.load("closed_fresh_scale")
    a = list(itertools.islice(traffic.scales(params, BIG_SEED), 200))
    b = list(itertools.islice(traffic.scales(params, BIG_SEED), 200))
    c = list(itertools.islice(traffic.scales(params, BIG_SEED + 1), 200))
    assert a == b and a != c
    assert all(params["scale_low"] <= s <= params["scale_high"] for s in a)
    assert min(abs(x - y) for x, y in zip(a, a[1:])) >= params["min_change"]


def test_traffic_names_its_loop_and_step(tmp_path, monkeypatch):
    """A mix finds its loop and its step by name; one that names a kind
    with no module is refused, naming the file it looked for."""
    params = traffic.load("closed_fresh_scale")
    assert (params["loop"], params["step"]) == ("closed", "scaled_multiply")
    monkeypatch.setattr(traffic, "HERE", tmp_path)
    (tmp_path / "open_burst.json").write_text(
        json.dumps(dict(params, loop="open_burst")))
    with pytest.raises(ValueError, match="bench/loops/open_burst.py"):
        traffic.load("open_burst")


def test_reference_numbers_equal_plain_arithmetic():
    """The panelled reference gives the numbers that float64 arithmetic
    on the whole product gives, at shapes that no panel count divides."""
    rng = np.random.default_rng(7)
    a = rng.standard_normal((70, 50)).astype(np.float32)
    b = rng.standard_normal((50, 30)).astype(np.float32)
    ref = a.astype(np.float64) @ b.astype(np.float64)
    c = (ref + 1e-3 * rng.standard_normal(ref.shape)).astype(np.float32)
    got = reference.numbers(jnp.asarray(c), jnp.asarray(a), jnp.asarray(b),
                            row_panels=3, inner_panels=4)
    mag = np.abs(a).astype(np.float64) @ np.abs(b).astype(np.float64)
    err = np.abs(c - ref)
    assert got["max_elem_err"] == pytest.approx(np.max(err / mag), rel=1e-4)
    assert got["rel_fro_err"] == pytest.approx(
        np.linalg.norm(err) / np.linalg.norm(ref), rel=1e-4)
