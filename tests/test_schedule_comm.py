"""The schedule's collectives on a 2x2 mesh: named, counted, unchanged.

At N = 22 * 16 on four virtual CPU devices: densified Cannon matches the
plain float32 reference; a warm call with a freshly scaled A hits the
program cache; the compiled program's collectives carry the schedule's
phase names (``dbcsr.skew``, ``dbcsr.shift``); the names change no op
(every algorithm and depth is bitwise the same with the scopes taken
out); depth 2 is bitwise depth 1; and the dispatch span's
``comm_bytes`` is the closed form for Cannon, with telemetry on and
off, and for ``ts_m`` counts the resharding at the ``shard_map``
boundary instead of reading 0; the blocked path counts only where a
record or a profiler session takes the count, and an accounting that
fails leaves the multiply as it was.

One subprocess runs the battery and prints JSON, as
``tests/test_distributed.py`` does.
"""
import json

import pytest

from conftest import run_subprocess_devices

N = 22 * 16

BATTERY = r"""
import contextlib, json, sys, tempfile
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.compat import make_mesh
from repro.core import dbcsr, multiply
from repro.core.blocking import GridSpec
from repro.core.densify import densified_local_matmul
from repro.core.multiply import _schedule_matmul

N = int(sys.argv[1])
mesh = make_mesh((2, 2), ("data", "model"))
key_a, key_b = jax.random.split(jax.random.key(20260))
a_raw = np.asarray(jax.random.normal(key_a, (N, N), jnp.float32))
b_raw = np.asarray(jax.random.normal(key_b, (N, N), jnp.float32))
A = dbcsr.create(a_raw, mesh=mesh, block_size=22)
B = dbcsr.create(b_raw, mesh=mesh, block_size=22)
out = {}


def dispatch_attrs(**kw):
    # one multiply under telemetry: its product and the dispatch span
    obs.enable()
    c = dbcsr.multiply(kw.pop("a", A), B, mesh=mesh, **kw)
    c = np.asarray(jax.block_until_ready(c.data))
    (disp,) = [s for s in obs.last_trace() if s.name == "dispatch"]
    counted = obs.counter("schedule.comm_bytes").value
    obs.disable()
    obs.clear_metrics()
    return c, dict(disp.attrs, counted=counted)


# densified Cannon, pinned, against the plain reference
cannon = dict(algorithm="cannon", densify=True)
c_cannon, out["cannon"] = dispatch_attrs(**cannon)
ref = np.asarray(jnp.dot(a_raw, b_raw, precision=jax.lax.Precision.HIGHEST))
mag = np.abs(a_raw) @ np.abs(b_raw)
out["cannon_elem_err"] = float(np.max(np.abs(c_cannon - ref) / mag))

# a warm call with a freshly scaled A
_, out["warm"] = dispatch_attrs(a=A.scale(1.0625), **cannon)

# the compiled program's op names
(key,) = [k for k in multiply._programs if k[0] == "cannon"]
program = multiply._programs[key][0]
hlo = program.lower(A.data, B.data).compile().as_text()
out["op_names"] = sorted({line.split('op_name="', 1)[1].split('"', 1)[0]
                          for line in hlo.splitlines() if 'op_name="' in line})

# pipeline depth 1 against the default 2
c_serial, out["serial"] = dispatch_attrs(pipeline_depth=1, **cannon)
out["depth2_equals_depth1"] = bool(np.array_equal(c_cannon, c_serial))

# ts_m moves A and B at the shard_map boundary
_, out["ts_m"] = dispatch_attrs(algorithm="ts_m", densify=True)
# the blocked path counts its own schedule on every call
_, out["blocked"] = dispatch_attrs(algorithm="cannon", densify=False,
                                   local_kernel="ref")

# telemetry off and no profiler session: the blocked path counts
# nothing; an accounting that fails leaves the product as it was
calls = []
comm_count = multiply._comm_count


def spy(*args, **kw):
    calls.append(args[0])
    return comm_count(*args, **kw)


multiply._comm_count = spy
blocked_kw = dict(algorithm="cannon", densify=False, local_kernel="ref")
c_quiet = np.asarray(jax.block_until_ready(
    dbcsr.multiply(A, B, mesh=mesh, **blocked_kw).data))
out["blocked_quiet_counts"] = len(calls)


def broken(*args, **kw):
    raise RuntimeError("accounting fault")


multiply._comm_count = broken
try:
    c_broken, out["broken"] = dispatch_attrs(**blocked_kw)
finally:
    multiply._comm_count = comm_count
out["broken_product_unchanged"] = bool(np.array_equal(c_quiet, c_broken))

# telemetry off: the profiler's dispatch span carries the count
log_dir = tempfile.mkdtemp()
with jax.profiler.trace(log_dir):
    jax.block_until_ready(dbcsr.multiply(A.scale(0.9375), B, mesh=mesh,
                                         **cannon).data)
(disp,) = [s for s in obs.profile_spans(log_dir)
           if s.name == "dbcsr.dispatch"]
out["profiled"] = {k: disp.attrs.get(k) for k in
                   ("comm_bytes", "comm_steps", "program_cache")}
out["registry_untouched"] = len(obs.registry()) == 0


# the scopes change no op: every algorithm and depth, bitwise, with
# jax.named_scope made a no-op
def products():
    lm = densified_local_matmul(jax.lax.Precision.DEFAULT)
    got = {}
    for algorithm in ("cannon", "summa", "ts_k", "ts_m"):
        for depth in (0, 1, 2):
            f = jax.jit(lambda a, b: _schedule_matmul(
                algorithm, a, b, mesh=mesh, grid=GridSpec(),
                local_matmul=lm, precision=jax.lax.Precision.DEFAULT,
                pipeline_depth=depth))
            got[f"{algorithm}@{depth}"] = np.asarray(f(A.data, B.data))
    return got


scoped = products()
named_scope = jax.named_scope
jax.named_scope = lambda name: contextlib.nullcontext()
try:
    bare = products()
finally:
    jax.named_scope = named_scope
out["unchanged_without_scopes"] = {
    k: bool(np.array_equal(scoped[k], bare[k])) for k in scoped}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def battery():
    code = f"import sys; sys.argv = ['battery', '{N}']\n" + BATTERY
    stdout = run_subprocess_devices(code, n_devices=4)
    return json.loads(stdout.strip().splitlines()[-1])


def cannon_bytes(n, itemsize=4):
    """Cannon on 2x2: the skew brings the busiest chip its A and B blocks
    of (n/2)^2 each, and the one shift brings the next pair."""
    return 2 * 2 * (n // 2) ** 2 * itemsize


def test_densified_cannon_matches_the_float32_reference(battery):
    # one bf16 pass rounds each operand once, by at most 2^-8 relative;
    # each product term then errs by under 2 * 2^-8 of |a||b| and the
    # float32 sum adds far less, so an element stays within
    # 2^-7 * (|A||B|) of the HIGHEST reference, whichever precision the
    # local dot runs at
    assert battery["cannon_elem_err"] <= 2.0 ** -7


def test_warm_call_with_fresh_scale_hits(battery):
    assert battery["cannon"]["program_cache"] == "miss"
    assert battery["warm"]["program_cache"] == "hit"


def test_collectives_carry_the_phase_names(battery):
    names = battery["op_names"]
    assert any("dbcsr.skew" in n and "ppermute" in n for n in names), names
    assert any("dbcsr.shift" in n and "ppermute" in n for n in names), names
    assert any("dbcsr.local_dot" in n for n in names), names


def test_depth_two_is_bitwise_depth_one(battery):
    assert battery["depth2_equals_depth1"]
    assert battery["serial"]["pipeline_depth"] == 1
    assert battery["cannon"]["pipeline_depth"] == 2


def test_scopes_change_no_op(battery):
    unchanged = battery["unchanged_without_scopes"]
    assert len(unchanged) == 12 and all(unchanged.values()), unchanged


@pytest.mark.parametrize("call", ["cannon", "warm", "serial", "blocked"])
def test_cannon_comm_bytes_is_the_closed_form(battery, call):
    attrs = battery[call]
    assert attrs["comm_bytes"] == cannon_bytes(N)
    assert attrs["comm_steps"] == 2
    assert attrs["counted"] == cannon_bytes(N)


def test_ts_m_counts_its_boundary_or_leaves_it_out(battery):
    attrs = battery["ts_m"]
    if "comm_bytes" in attrs:
        # chip (0, 0) holds A[:n/2, :n/2] and needs A[:n/4, :]: a quarter
        # row panel of half width is missing; B is replicated, so it
        # needs the three quarters of B that it does not hold
        quarter, half = N // 4, N // 2
        assert attrs["comm_bytes"] == 4 * (quarter * half + 3 * N * N // 4)
        assert attrs["comm_steps"] == 1


def test_blocked_path_counts_only_for_a_reader(battery):
    assert battery["blocked_quiet_counts"] == 0


def test_failed_accounting_leaves_the_multiply_whole(battery):
    assert battery["broken_product_unchanged"]
    assert "comm_bytes" not in battery["broken"]
    assert battery["broken"]["counted"] == 0


def test_profiled_dispatch_carries_the_count_with_telemetry_off(battery):
    profiled = battery["profiled"]
    assert profiled["comm_bytes"] == cannon_bytes(N)
    assert profiled["comm_steps"] == 2
    assert profiled["program_cache"] == "hit"
    assert battery["registry_untouched"]
