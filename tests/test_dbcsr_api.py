"""DBCSRMatrix API semantics (single-device: the ops are mesh-agnostic;
the distributed multiply itself is covered by test_distributed.py)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import dbcsr
from repro.core.blocking import GridSpec
from repro.launch.mesh import make_mesh


@pytest.fixture(scope="module")
def setup(rng):
    mesh = make_mesh((1, 1), ("data", "model"))
    grid = GridSpec("data", "model")
    A = rng.randn(128, 128).astype(np.float32)
    B = rng.randn(128, 128).astype(np.float32)
    return mesh, grid, A, B


def test_create_and_roundtrip(setup, rng):
    mesh, grid, A, B = setup
    Am = dbcsr.create(A, mesh=mesh, grid=grid, block_size=32)
    np.testing.assert_array_equal(np.asarray(Am.data), A)
    assert Am.layout.nblocks == 16
    assert Am.occupancy == 1.0


def test_add_trace_transpose_scale(setup):
    mesh, grid, A, B = setup
    Am = dbcsr.create(A, mesh=mesh, grid=grid, block_size=32)
    Bm = dbcsr.create(B, mesh=mesh, grid=grid, block_size=32)
    np.testing.assert_allclose(np.asarray(dbcsr.add(Am, Bm).data), A + B,
                               rtol=1e-6)
    np.testing.assert_allclose(float(dbcsr.trace(Am)), np.trace(A), rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(dbcsr.transpose(Am).data), A.T)
    np.testing.assert_allclose(np.asarray(Am.scale(2.5).data), 2.5 * A,
                               rtol=1e-6)


def test_multiply_vector(setup, rng):
    mesh, grid, A, B = setup
    Am = dbcsr.create(A, mesh=mesh, grid=grid, block_size=32)
    x = jnp.asarray(rng.randn(128).astype(np.float32))
    np.testing.assert_allclose(np.asarray(dbcsr.multiply_vector(Am, x)),
                               A @ np.asarray(x), rtol=1e-4, atol=1e-4)


def test_block_sparse_semantics(setup):
    mesh, grid, A, B = setup
    mask = np.zeros((4, 4), bool)
    mask[0, :] = True
    mask[:, 0] = True
    Am = dbcsr.create(A, mesh=mesh, grid=grid, block_size=32, block_mask=mask)
    assert abs(Am.occupancy - 7 / 16) < 1e-9
    dense_mask = np.repeat(np.repeat(mask, 32, 0), 32, 1)
    np.testing.assert_array_equal(np.asarray(Am.data), A * dense_mask)
    # sparse x sparse result mask = boolean matmul of the masks
    Bm = dbcsr.create(B, mesh=mesh, grid=grid, block_size=32, block_mask=mask)
    Cm = dbcsr.multiply(Am, Bm, mesh=mesh, algorithm="cannon")
    expected_mask = (mask.astype(int) @ mask.astype(int)) > 0
    np.testing.assert_array_equal(Cm.block_mask, expected_mask)


def test_block_mask_survives_jit_roundtrip(setup):
    """The pytree aux carries (shape, bytes) of the mask, so block
    sparsity must survive jit (tree_unflatten used to rebuild None)."""
    mesh, grid, A, B = setup
    mask = np.zeros((4, 4), bool)
    mask[0, :] = True
    Am = dbcsr.create(A, mesh=mesh, grid=grid, block_size=32, block_mask=mask)

    @jax.jit
    def scale(m: dbcsr.DBCSRMatrix) -> dbcsr.DBCSRMatrix:
        return m.scale(2.0)

    out = scale(Am)
    assert out.block_mask is not None
    np.testing.assert_array_equal(out.block_mask, mask)
    np.testing.assert_allclose(np.asarray(out.data), np.asarray(Am.data) * 2,
                               rtol=1e-6)
    # explicit flatten/unflatten round-trip too
    leaves, treedef = jax.tree_util.tree_flatten(Am)
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    np.testing.assert_array_equal(back.block_mask, mask)
    # dense matrices still round-trip with no mask
    Bm = dbcsr.create(B, mesh=mesh, grid=grid, block_size=32)
    assert scale(Bm).block_mask is None


def test_multiply_single_masked_operand_mask_flows(setup):
    """multiply() with exactly one masked operand: the symbolic product
    mask (missing mask treated as all-present) lands on the result and
    matches the numeric block support; add() stays dense (documented)."""
    mesh, grid, A, B = setup
    mask = np.zeros((4, 4), bool)
    mask[0, :] = True
    mask[2, 1] = True
    Am = dbcsr.create(A, mesh=mesh, grid=grid, block_size=32, block_mask=mask)
    Bm = dbcsr.create(B, mesh=mesh, grid=grid, block_size=32)
    Cm = dbcsr.multiply(Am, Bm, mesh=mesh, algorithm="cannon")
    expected = (mask.astype(np.int64) @ np.ones((4, 4), np.int64)) > 0
    np.testing.assert_array_equal(Cm.block_mask, expected)
    # symbolic mask == numeric support (random data: no exact cancels)
    Cb = np.asarray(Cm.data).reshape(4, 32, 4, 32)
    support = np.abs(Cb).max(axis=(1, 3)) > 0
    np.testing.assert_array_equal(support, expected)
    # blocked sparse path agrees with the densified product
    Cm_blocked = dbcsr.multiply(Am, Bm, mesh=mesh, algorithm="cannon",
                                densify=False, local_kernel="ref")
    np.testing.assert_allclose(np.asarray(Cm_blocked.data),
                               np.asarray(Cm.data), rtol=0, atol=1e-3)
    # add: union with a dense operand is dense -> mask is None
    assert dbcsr.add(Am, Bm).block_mask is None
    np.testing.assert_array_equal(dbcsr.add(Am, Am).block_mask, mask)


def _materialized():
    return obs.counter("scale.materialized").value


@pytest.fixture
def telemetry():
    obs.clear_metrics()
    obs.enable()
    yield
    obs.disable()
    obs.clear_metrics()


@pytest.mark.parametrize("alpha", [2.5, np.float32(0.75), np.float64(-1.25), 3],
                         ids=["float", "float32", "float64", "int"])
def test_scale_defers_and_data_is_the_eager_product(setup, telemetry, alpha):
    """A concrete factor stays pending: shape reads the payload, the
    first read of ``data`` multiplies once, bitwise as ``data * alpha``,
    and keeps the product on the instance."""
    mesh, grid, A, B = setup
    Am = dbcsr.create(A, mesh=mesh, grid=grid, block_size=32)
    scaled = Am.scale(alpha)
    assert obs.counter("scale.deferred").value == 1
    assert scaled.shape == A.shape and _materialized() == 0
    np.testing.assert_array_equal(np.asarray(scaled.data),
                                  np.asarray(Am.data * alpha))
    assert _materialized() == 1
    assert scaled.data is scaled.data and _materialized() == 1


def test_composed_scale_is_one_factor(setup, telemetry):
    mesh, grid, A, B = setup
    Am = dbcsr.create(A, mesh=mesh, grid=grid, block_size=32)
    twice = Am.scale(1.1).scale(-0.3)
    np.testing.assert_array_equal(np.asarray(twice.data),
                                  np.asarray(Am.data * (1.1 * -0.3)))
    # (x a1) a2 and x (a1 a2) differ by at most one float32 ulp
    stepwise = np.asarray(Am.data * 1.1 * -0.3)
    ulp = np.spacing(np.abs(stepwise).astype(np.float32))
    assert np.all(np.abs(np.asarray(twice.data) - stepwise) <= ulp)
    assert _materialized() == 1


def _consumer_results(m, Bm, x):
    t = dbcsr.transpose(m)
    f = m.filter(0.5 * float(np.median(m.norms())))
    return {"transpose": (t.data, t.block_norms, t.block_mask),
            "add": (dbcsr.add(m, Bm).data,),
            "filter": (f.data, f.block_mask, f.block_norms),
            "norms": (m.norms(), m.norms(recompute=True)),
            "multiply_vector": (dbcsr.multiply_vector(m, x),),
            "trace": (dbcsr.trace(m),)}


@pytest.mark.parametrize("consumer", ["transpose", "add", "filter", "norms",
                                      "multiply_vector", "trace"])
def test_scaled_matrix_consumers_equal_the_eager_results(setup, telemetry,
                                                         consumer):
    """Every consumer of a matrix with a pending factor gives bitwise
    what it gives for the same factor applied eagerly (a device scalar,
    which ``scale`` multiplies at once)."""
    mesh, grid, A, B = setup
    mask = np.ones((4, 4), bool)
    mask[1, 2] = mask[3, 0] = False
    Am = dbcsr.create(A, mesh=mesh, grid=grid, block_size=32,
                      block_mask=mask, compute_norms=True)
    Bm = dbcsr.create(B, mesh=mesh, grid=grid, block_size=32)
    x = jnp.asarray(np.linspace(-1, 1, 128, dtype=np.float32))
    eager = Am.scale(jnp.float32(-1.5))
    assert obs.counter("scale.deferred").value == 0
    got = _consumer_results(Am.scale(-1.5), Bm, x)[consumer]
    want = _consumer_results(eager, Bm, x)[consumer]
    assert obs.counter("scale.deferred").value == 1
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_traced_alpha_keeps_the_eager_path(setup, telemetry):
    mesh, grid, A, B = setup
    Am = dbcsr.create(A, mesh=mesh, grid=grid, block_size=32)
    out = jax.jit(lambda m, s: m.scale(s))(Am, 2.0)
    assert obs.counter("scale.deferred").value == 0
    np.testing.assert_array_equal(np.asarray(out.data),
                                  np.asarray(Am.data * 2.0))


def test_tree_flatten_multiplies_the_pending_factor_out(setup, telemetry):
    mesh, grid, A, B = setup
    Am = dbcsr.create(A, mesh=mesh, grid=grid, block_size=32)
    (leaf,), _ = jax.tree_util.tree_flatten(Am.scale(0.5))
    assert _materialized() == 1
    np.testing.assert_array_equal(np.asarray(leaf), np.asarray(Am.data * 0.5))
    out = jax.jit(lambda m: m)(Am.scale(0.5))
    assert _materialized() == 2
    np.testing.assert_array_equal(np.asarray(out.data),
                                  np.asarray(Am.data * 0.5))
