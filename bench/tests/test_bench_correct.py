"""The comparison that decides ``correct``, at sizes a test run holds, on
the CPU: a run of each one-chip cell passes; a run with the control in
the program's place comes out not correct; and so does a run whose timed
path is broken underneath, once for each fault the cells can have.  The look for a
chip is skipped; everything else of a run is driven as on the chip."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference, traffic
from repro.core import dbcsr

SEED = 2 ** 32 + 99
# the block-sparse path, as the banded density-matrix cell left for a
# later benchmark PR runs it, at a small order
BANDED = harness.Cell(
    name="banded-small", chips=1, mesh=(1, 1),
    config=dict(name="banded", operand="banded", n=22 * 16, dtype="float32",
                block_size=22,
                half_bandwidth=4, gap=2.0, coupling=0.3, decay=0.4,
                multiply=dict(densify=False, filter_eps=1e-6, align=False)),
    traffic=traffic.load("closed_fresh_scale"),
    limits={"max_elem_err": 0.02, "mask_mismatch": 0},
    end_to_end=[("setup_s", "s")], per_layer=[])
# the benchmark's one-chip dense cell
DENSE = next(w["name"] for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]
    if w["chips"] == 1 and w["name"].startswith("dense-"))
# shapes (m, k, n) that the configuration file supplies: the dense cell's
# at a small square order, and a tall-skinny product through the same
# harness, loop and step
SHAPES = {DENSE: (22 * 16,) * 3, "dense-tall-skinny": (22 * 4, 22 * 24, 22 * 2)}
ONE_CHIP = [DENSE, "banded-small", "dense-tall-skinny"]


def small_cell(name):
    if name == BANDED.name:
        return BANDED
    cell = harness.load_cell(DENSE)
    m, k, n = SHAPES[name]
    cell.config = dict(cell.config, m=m, k=k, n=n)
    return cell


def run(cell, seconds=0.01):
    return harness.run_cell(
        cell, seed=SEED, seconds=seconds, traced=False,
        devices=jax.devices()[:cell.chips],
        peaks=harness.peaks_for("TPU v5 lite"),
        t_process=harness.clock(), log=lambda msg: None)


@pytest.mark.parametrize("name", ONE_CHIP)
def test_sound_run_is_correct(name):
    result = run(small_cell(name))
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert {m for m in result["metrics"]} == {
        m for m, _ in small_cell(name).end_to_end}


def control(multiply):
    """The control in the program's place: the reference's product of
    the same operands at the precision below (``reference.control``)."""
    def f(a, b, **kw):
        c = multiply(a, b, **kw)
        return dataclasses.replace(c, data=reference.control(a.data, b.data))
    return f


@pytest.mark.parametrize("name", ONE_CHIP)
def test_control_fails_the_limits(name, monkeypatch):
    monkeypatch.setattr(dbcsr, "multiply", control(dbcsr.multiply))
    result = run(small_cell(name))
    assert not result["correct"], result["checks"]
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


def altered(multiply):
    """One answer altered where it is produced: the largest element of
    the product changes sign."""
    def f(a, b, **kw):
        c = multiply(a, b, **kw)
        i = jnp.argmax(jnp.abs(c.data))
        flat = c.data.reshape(-1)
        data = flat.at[i].set(-flat[i]).reshape(c.data.shape)
        return dataclasses.replace(c, data=data)
    return f


def stale(multiply):
    """The step returns what the step before it returned."""
    last = []

    def f(a, b, **kw):
        c = multiply(a, b, **kw)
        out = last[0] if last else c
        last[:] = [c]
        return out
    return f


def half_inner(multiply):
    """Half of the inner dimension left out of the sum."""
    def f(a, b, **kw):
        k = a.shape[1]
        keep = (jnp.arange(k) < k // 2).astype(a.data.dtype)
        return multiply(dataclasses.replace(a, data=a.data * keep), b, **kw)
    return f


@pytest.mark.parametrize("fault", [altered, stale, half_inner],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", ONE_CHIP)
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    monkeypatch.setattr(dbcsr, "multiply", fault(dbcsr.multiply))
    result = run(small_cell(name))
    assert not result["correct"], result["checks"]


def test_reservoir_keeps_a_uniform_sample():
    rng = np.random.default_rng(0)
    counts = np.zeros(10)
    for _ in range(2000):
        kept = []
        for t in range(10):
            harness.keep_sample(kept, t, t, rng)
        assert len(kept) == harness.SAMPLES
        counts[kept] += 1
    assert np.all(np.abs(counts / 2000 - harness.SAMPLES / 10) < 0.05)
