"""The densified dispatch runs one cached jitted program per key.

A warm densified ``dbcsr.multiply`` with fresh values and the shapes it
has seen neither lowers nor compiles, and its dispatch span says
``program_cache="hit"``; a changed precision, algorithm or shape is a
miss with a program of its own; the blocked path keeps its eager
dispatch and records neither; and a call under an outer ``jax.jit``
still works.  The operands' pending ``scale`` factors fold into the
cached program as one traced factor of C: a new factor is a hit that
compiles nothing, the dispatch reads ``alpha_fused``, and the blocked
path and ABFT take the scaled data instead.

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
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.compat import make_mesh
from repro.core import dbcsr, multiply, schedule
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
    am = dbcsr.create(a, mesh=mesh, block_size=BS)
    bm = dbcsr.create(b, mesh=mesh, block_size=BS)
    jax.block_until_ready((am.data, bm.data))
    return a, b, am, bm


def call(am, bm, **kw):
    # one multiply under telemetry: its product, the runtime's counts
    # over the call and the dispatch span's program_cache
    kw = {"algorithm": algorithm, "densify": True, **kw}
    n_programs = len(multiply._programs)
    obs.enable()
    mark = runtime.mark()
    c = dbcsr.multiply(am, bm, mesh=mesh, **kw)
    c = np.asarray(jax.block_until_ready(c.data))
    counts = runtime.since(mark)
    (disp,) = [s for s in obs.last_trace() if s.name == "dispatch"]
    hits = obs.counter("dispatch.program_cache.hits").value
    misses = obs.counter("dispatch.program_cache.misses").value
    materialized = obs.counter("scale.materialized").value
    obs.disable()
    return c, {"lowerings": counts["lowerings"],
               "compiles": counts["compiles"],
               "program_cache": disp.attrs.get("program_cache"),
               "alpha_fused": disp.attrs.get("alpha_fused"),
               "skew_panels": disp.attrs.get("skew_panels"),
               "materialized": materialized,
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

# pending scale factors folded into the program, on blocks large enough
# for Cannon's panelled program once its threshold is lowered to the
# lane width (local 304 x 176 @ 176 x 224 on 2x2)
schedule.SKEW_PANEL_MIN = 128
a4, b4, a4m, b4m = operands(608, 352, 448)
c_plain, out["plain"] = call(a4m, b4m)
s, r = 1.25, np.float32(-0.5)
c_scaled, out["scaled"] = call(a4m.scale(s), b4m.scale(r))
c_new_alpha, out["new_alpha"] = call(a4m.scale(0.875), b4m)
c_eager, _ = call(a4m.scale(jnp.float32(s)), b4m.scale(jnp.float32(r)))
mag = np.abs(a4).astype(np.float64) @ np.abs(b4).astype(np.float64)


def err(c, ref, factor):
    return float(np.max(np.abs(c - factor * ref) / (abs(factor) * mag)))


out["scaled_err"] = err(c_scaled, c_plain, s * float(r))
out["new_alpha_err"] = err(c_new_alpha, c_plain, 0.875)
out["scaled_vs_eager_err"] = err(c_scaled, c_eager, 1.0)
# the blocked path and ABFT take the scaled data
c_blocked_scaled, out["blocked_scaled"] = call(
    a4m.scale(s), b4m.scale(r), densify=False, local_kernel="ref")
c_blocked_eager, _ = call(a4m.scale(jnp.float32(s)),
                          b4m.scale(jnp.float32(r)), densify=False,
                          local_kernel="ref")
out["blocked_scaled_equals_eager"] = bool(
    np.array_equal(c_blocked_scaled, c_blocked_eager))
c_verified, out["verified"] = call(a4m.scale(s), b4m.scale(r),
                                   verify="checksum")
out["verified_err"] = err(c_verified, c_eager, 1.0)
print(json.dumps(out))
"""

CASES = {"ts_m-1x1": ("ts_m", 1, 1), "cannon-2x2": ("cannon", 2, 2)}


@pytest.fixture(scope="module", params=sorted(CASES))
def battery(request):
    algorithm, pr, pc = CASES[request.param]
    code = (f"import sys; sys.argv = ['battery', {algorithm!r}, "
            f"'{pr}', '{pc}']\n" + BATTERY)
    stdout = run_subprocess_devices(code, n_devices=pr * pc)
    return dict(json.loads(stdout.strip().splitlines()[-1]),
                case=request.param)


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


# one bf16 pass on each operand: the worst element's error over |A||B|
ONE_BF16_PASS = 2.0 ** -8


def test_scaled_multiply_folds_its_factor_into_the_product(battery):
    """multiply(A.scale(s), B.scale(r)) is multiply(A, B) times s r, and
    the eagerly scaled product, within one bf16 pass; the dispatch says
    the factor was folded, and nothing multiplied A or B out."""
    assert battery["scaled_err"] <= ONE_BF16_PASS
    assert battery["new_alpha_err"] <= ONE_BF16_PASS
    assert battery["scaled_vs_eager_err"] <= ONE_BF16_PASS
    for name in ("scaled", "new_alpha"):
        assert battery[name]["alpha_fused"] == 1
        assert battery[name]["materialized"] == 0
    assert battery["plain"]["alpha_fused"] == 0


def test_warm_call_with_a_new_factor_compiles_nothing(battery):
    for name in ("scaled", "new_alpha"):
        assert battery[name]["program_cache"] == "hit"
        assert battery[name]["new_programs"] == 0
        assert battery[name]["lowerings"] == battery[name]["compiles"] == 0


def test_scaled_multiply_keeps_the_skew_panels_rule(battery):
    # Cannon on 2x2 takes the panelled program, as the unscaled call does
    panels = 2 if battery["case"] == "cannon-2x2" else 1
    assert battery["plain"]["skew_panels"] == panels
    assert battery["scaled"]["skew_panels"] == panels


def test_blocked_and_verified_multiplies_take_the_scaled_data(battery):
    blocked, verified = battery["blocked_scaled"], battery["verified"]
    assert blocked["alpha_fused"] == verified["alpha_fused"] == 0
    # each reads both operands' data: two passes each
    assert (blocked["materialized"], verified["materialized"]) == (2, 4)
    assert battery["blocked_scaled_equals_eager"]
    assert battery["verified_err"] <= ONE_BF16_PASS


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


def test_benchmark_step_never_multiplies_the_scale_out():
    # the timed path of ``closed_fresh_scale``: form scales A, call
    # multiplies, wait blocks on C; the factor is folded every step
    from types import SimpleNamespace

    from bench.steps import scaled_multiply

    mesh = make_mesh((1, 1), ("data", "model"))
    rng = np.random.RandomState(2)
    a, b = (rng.randn(88, 88).astype(np.float32) for _ in range(2))
    ops = SimpleNamespace(a=a, b=b, a_mask=None, b_mask=None,
                          a_norms=None, b_norms=None)
    config = {"block_size": 22, "multiply": {"densify": True}}
    step = scaled_multiply.build(config, ops, mesh, chips=1)
    obs.clear_metrics()
    obs.enable()
    try:
        for s in (0.95, 1.05, 0.9):
            c = step.call(step.form(s))
            step.wait(c)
            (disp,) = [x for x in obs.last_trace() if x.name == "dispatch"]
            assert disp.attrs["alpha_fused"] == 1
            np.testing.assert_allclose(np.asarray(c.data), s * (a @ b),
                                       rtol=1e-4, atol=1e-3)
        assert obs.counter("scale.deferred").value == 3
        assert obs.counter("scale.materialized").value == 0
    finally:
        obs.disable()
        obs.clear_metrics()
