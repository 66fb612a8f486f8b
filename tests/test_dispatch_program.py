"""The densified dispatch runs one cached jitted program per key.

A warm densified ``dbcsr.multiply`` with fresh values and the shapes it
has seen neither lowers nor compiles, and its dispatch span says
``program_cache="hit"``; a changed precision, algorithm or shape is a
miss with a program of its own; the blocked path keeps its eager
dispatch and records neither; and a call under an outer ``jax.jit``
still works.

Each case runs its battery in one subprocess, on its own host mesh, so
that the program cache starts empty: ``ts_m`` on 1x1 and ``cannon`` on
a 2x2 virtual-CPU mesh.
"""
import json

import jax
import numpy as np
import pytest

from conftest import run_subprocess_devices
from repro import obs
from repro.compat import make_mesh
from repro.core import dbcsr

BATTERY = r"""
import json, sys
import numpy as np, jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.compat import make_mesh
from repro.core import dbcsr, multiply
from repro.core.blocking import GridSpec
from repro.core.densify import blocked_local_matmul
from repro.core.multiply import distributed_matmul, _schedule_matmul
from repro.obs import runtime

algorithm, pr, pc = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
mesh = make_mesh((pr, pc), ("data", "model"))
rng = np.random.RandomState(0)
M, K, N, BS = 64, 96, 128, 16
runtime.install()
out = {}


def operands(m=M, k=K, n=N):
    a = rng.randn(m, k).astype(np.float32)
    b = rng.randn(k, n).astype(np.float32)
    return (a, b, dbcsr.create(a, mesh=mesh, block_size=BS),
            dbcsr.create(b, mesh=mesh, block_size=BS))


def call(am, bm, **kw):
    # one multiply under telemetry: its product, the runtime's counts
    # over the call and the dispatch span's program_cache
    kw = {"algorithm": algorithm, "densify": True, **kw}
    jax.block_until_ready((am.data, bm.data))
    n_programs = len(multiply._programs)
    obs.enable()
    mark = runtime.mark()
    c = dbcsr.multiply(am, bm, mesh=mesh, **kw)
    c = np.asarray(jax.block_until_ready(c.data))
    counts = runtime.since(mark)
    (disp,) = [s for s in obs.last_trace() if s.name == "dispatch"]
    hits = obs.counter("dispatch.program_cache.hits").value
    misses = obs.counter("dispatch.program_cache.misses").value
    obs.disable()
    return c, {"lowerings": counts["lowerings"],
               "compiles": counts["compiles"],
               "program_cache": disp.attrs.get("program_cache"),
               "hits": hits, "misses": misses,
               "new_programs": len(multiply._programs) - n_programs}


a1, b1, a1m, b1m = operands()
c_miss, out["miss"] = call(a1m, b1m)
a2, b2, a2m, b2m = operands()   # fresh values, the same shapes
c_warm, out["warm"] = call(a2m, b2m)
ref = a2.astype(np.float64) @ b2.astype(np.float64)
out["warm_vs_f64"] = bool(np.allclose(c_warm, ref, rtol=1e-4, atol=1e-4))
c_again, out["again"] = call(a1m, b1m)
out["hit_equals_miss_bitwise"] = bool(np.array_equal(c_again, c_miss))

other = "summa" if algorithm != "summa" else "cannon"
a3, b3, a3m, b3m = operands(n=2 * N)
changed = {
    "precision": (a1m, b1m, {"precision": jax.lax.Precision.HIGHEST}),
    "algorithm": (a1m, b1m, {"algorithm": other}),
    "shape": (a3m, b3m, {}),
}
for name, (am, bm, kw) in changed.items():
    _, out[name] = call(am, bm, **kw)
    _, out[name + "_repeat"] = call(am, bm, **kw)

# the blocked path: eager dispatch as before, no program_cache
c_blocked, out["blocked"] = call(a1m, b1m, densify=False,
                                 local_kernel="ref")
grid = GridSpec()
if algorithm == "cannon":
    ml, kl, nl = M // pr, K // pr, N // pr
else:
    ml, kl, nl = M // (pr * pc), K, N
lm = blocked_local_matmul(ml, kl, nl, block_m=BS, block_k=BS, block_n=BS,
                          kernel="ref")
eager = _schedule_matmul(algorithm, a1m.data, b1m.data, mesh=mesh,
                         grid=grid, local_matmul=lm,
                         precision=jax.lax.Precision.DEFAULT,
                         pipeline_depth=2)
out["blocked_equals_eager_schedule"] = bool(
    np.array_equal(c_blocked, np.asarray(eager)))
ref1 = a1.astype(np.float64) @ b1.astype(np.float64)
out["blocked_vs_f64"] = bool(np.allclose(c_blocked, ref1, rtol=1e-4,
                                         atol=1e-4))

# under an outer jit the cached program is traced into the caller's
sh = NamedSharding(mesh, P("data", "model"))
ad, bd = jax.device_put(a1, sh), jax.device_put(b1, sh)
outer = jax.jit(lambda x, y: distributed_matmul(
    x, y, mesh=mesh, algorithm=algorithm, densify=True))
c_outer = np.asarray(outer(ad, bd))
out["outer_jit_vs_f64"] = bool(np.allclose(c_outer, ref1, rtol=1e-4,
                                           atol=1e-4))
out["outer_jit_equals_miss"] = bool(np.allclose(c_outer, c_miss,
                                                rtol=1e-6, atol=1e-6))
print(json.dumps(out))
"""

CASES = {"ts_m-1x1": ("ts_m", 1, 1), "cannon-2x2": ("cannon", 2, 2)}


@pytest.fixture(scope="module", params=sorted(CASES))
def battery(request):
    algorithm, pr, pc = CASES[request.param]
    code = (f"import sys; sys.argv = ['battery', {algorithm!r}, "
            f"'{pr}', '{pc}']\n" + BATTERY)
    stdout = run_subprocess_devices(code, n_devices=pr * pc)
    return json.loads(stdout.strip().splitlines()[-1])


def test_first_call_is_a_miss_with_its_own_program(battery):
    miss = battery["miss"]
    assert miss["program_cache"] == "miss"
    assert miss["new_programs"] == 1
    assert (miss["hits"], miss["misses"]) == (0, 1)
    assert miss["lowerings"] >= 1 and miss["compiles"] >= 1


def test_warm_call_lowers_and_compiles_nothing(battery):
    warm = battery["warm"]
    assert warm["program_cache"] == "hit"
    assert (warm["hits"], warm["misses"]) == (1, 1)
    assert warm["new_programs"] == 0
    assert warm["lowerings"] == 0
    assert warm["compiles"] == 0


def test_hit_product_is_exact_and_bitwise_the_miss(battery):
    assert battery["warm_vs_f64"]
    assert battery["again"]["program_cache"] == "hit"
    assert battery["hit_equals_miss_bitwise"]


@pytest.mark.parametrize("change", ["precision", "algorithm", "shape"])
def test_changed_key_is_a_miss_with_its_own_program(battery, change):
    first, repeat = battery[change], battery[change + "_repeat"]
    assert first["program_cache"] == "miss"
    assert first["new_programs"] == 1
    assert first["lowerings"] >= 1
    assert repeat["program_cache"] == "hit"
    assert repeat["lowerings"] == repeat["compiles"] == 0


def test_blocked_call_records_neither_and_is_unchanged(battery):
    blocked = battery["blocked"]
    assert blocked["program_cache"] is None
    assert blocked["new_programs"] == 0
    # the counters stand where the densified calls left them
    assert (blocked["hits"], blocked["misses"]) == (
        battery["shape_repeat"]["hits"], battery["shape_repeat"]["misses"])
    assert battery["blocked_equals_eager_schedule"]
    assert battery["blocked_vs_f64"]


def test_distributed_matmul_inside_an_outer_jit(battery):
    assert battery["outer_jit_vs_f64"]
    assert battery["outer_jit_equals_miss"]


def test_profiled_warm_dispatch_carries_its_hit(tmp_path):
    # the profiler's trace of a warm densified call: the dispatch span's
    # metadata says "hit", and the call lowered and compiled nothing
    mesh = make_mesh((1, 1), ("data", "model"))
    rng = np.random.RandomState(1)

    def operand():
        return dbcsr.create(rng.randn(88, 88).astype(np.float32),
                            mesh=mesh, block_size=22)

    dbcsr.multiply(operand(), operand(), mesh=mesh, densify=True)
    a, b = operand(), operand()
    jax.block_until_ready((a.data, b.data))
    with jax.profiler.trace(str(tmp_path)):
        c = dbcsr.multiply(a, b, mesh=mesh, densify=True)
        jax.block_until_ready(c.data)
    spans = obs.profile_spans(str(tmp_path))
    (root,) = [s for s in spans if s.name == "dbcsr.multiply"]
    (disp,) = [s for s in spans if s.name == "dbcsr.dispatch"]
    assert disp.attrs["program_cache"] == "hit"
    assert root.attrs["lowerings"] == root.attrs["compiles"] == 0
