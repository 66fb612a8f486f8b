#!/usr/bin/env python3
"""Chip smoke: ``dbcsr.multiply`` on a TPU at the sizes DBCSR's users run.

    python chip_smoke.py               # one chip, 1x1 mesh: phases (a)-(c)
    python chip_smoke.py --four-chips  # 2x2 mesh over four chips

Phases, float32 at block size 22 (arXiv:1910.04796), N = 22 * 512:

  (a) dense square: densified Cannon against ``jnp.dot`` at HIGHEST;
  (b) block-sparse density-matrix iteration, CP2K's use: McWeeny
      purification of a banded insulator Hamiltonian
      (``sparsity/workloads.py``) with ``filter_eps=1e-6`` on the blocked
      path through the Pallas smm kernel.  The first ``P @ P`` is checked
      against a HIGHEST dense product, ``tr(P)`` against n/2 and the
      idempotency error for a fall at every iteration;
  (c) the product of (b) again, checksum-verified (``verify="checksum"``),
      which must detect nothing on clean data.

``--four-chips`` runs only the distributed path on a 2x2 mesh: phase (a)
with Cannon and the first multiply of (b), blocked with rank-exact plans,
each against the single-device HIGHEST product of the same operands.

Every choice the planner or the autotune table would make (algorithm,
densify, align, stack size) is pinned, so only committed code decides
what compiles.  The precision each path ran at is probed on the chip
(``probe_precision``) and every error bound follows from it.  Any failed
check, a platform other than ``tpu`` or another device count than the
one asked for exits non-zero.  The last line of standard output is one
JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.compat import make_mesh  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import dbcsr  # noqa: E402
from repro.core.densify import densified_local_matmul  # noqa: E402
from repro.kernels.smm.ops import smm_process_stack  # noqa: E402
from repro.planner import calibrate  # noqa: E402
from repro.robustness.guards import CorruptionDetectedError  # noqa: E402
from repro.sparsity.workloads import (banded_hamiltonian,  # noqa: E402
                                      initial_density, mcweeny_purify)

BS = 22                     # DBCSR's block size in the paper
N = BS * 512                # 11,264
FILTER_EPS = 1e-6
N_ITER = 4
STACK_SIZE = 30000          # the executor's stack tile without a winners table
# the blocked path, pinned: Cannon through the Pallas smm kernel
BLOCKED = dict(algorithm="cannon", densify=False, local_kernel="smm",
               align=False, stack_size=STACK_SIZE)
U32 = 2.0 ** -24            # float32 unit roundoff
# Relative error of one float32 product a*b, before accumulation, for
# each pass scheme the MXU may run a float32 matmul with.
PRODUCT_ERR = {
    # six bf16 passes: the split keeps all 24 bits; the dropped cross
    # terms and the partial sums cost at most ~9 units of 2^-24
    "float32": 2.0 ** -20,
    # three passes: each operand kept to 16 bits, three dropped terms
    "bf16x3": 2.0 ** -14,
    # one pass: each operand rounded to bf16 (unit roundoff 2^-8)
    "bf16": 2.0 ** -7 + 2.0 ** -16,
}
# Tail factor of the probabilistic bound on float32 accumulation error
# (Higham & Mary, SIAM J. Sci. Comput. 41(5), 2019): lambda*sqrt(n)*u
# holds with probability >= 1 - 2 exp(-lambda^2 / 2) per sum.
LAMBDA = 6.0


class PhaseError(RuntimeError):
    """A check of a phase failed."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseError(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileCounter:
    """XLA backend compiles (persistent-cache hits included, and counted
    apart) as reported through ``jax.monitoring``."""

    def __init__(self):
        self.n = 0
        self.secs = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def measure(self, fn, sync):
        """Run ``fn``, wait for ``sync(result)`` on the device, and return
        ``(result, stats)`` with the compiles and wall time of the call."""
        n0, s0, h0 = self.n, self.secs, self.hits
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(sync(out))
        return out, {"compiles": self.n - n0,
                     "compile_s": round(self.secs - s0, 3),
                     "cache_hits": self.hits - h0,
                     "wall_s": round(time.perf_counter() - t0, 4)}


def log_calls(first: dict, repeat: dict) -> None:
    for name, s, note in (("first call: ", first, ""),
                          ("repeat call:", repeat, " (informal)")):
        log(f"    {name} {s['compiles']} XLA compilations "
            f"({s['compiles'] - s['cache_hits']} compiled, "
            f"{s['cache_hits']} read from the persistent cache), "
            f"{s['compile_s']} s in them; {s['wall_s']} s wall{note}")


# ---------------------------------------------------------------------------
# precision: probed on the chip, and the bounds that follow from it
# ---------------------------------------------------------------------------


def probe_precision(matmul, size: int) -> str:
    """The pass scheme a float32 ``matmul(x, eye)`` runs with here.

    x = 1 + 2^-10 + 2^-20 times the identity comes back as 1 from one
    bf16 pass, 1 + 2^-10 from three passes and exactly x from six."""
    x = jnp.full((size, size), 1.0 + 2.0 ** -10 + 2.0 ** -20, jnp.float32)
    eye = jnp.eye(size, dtype=jnp.float32)
    err = float(jnp.max(jnp.abs(matmul(x, eye) - x)))
    if err >= 2.0 ** -12:
        return "bf16"
    if err >= 2.0 ** -22:
        return "bf16x3"
    return "float32"


def path_precisions() -> dict:
    """Probe the three matmuls the phases run: the densified path's
    ``lax.dot`` at DEFAULT, the Pallas smm kernel, and the HIGHEST
    reference."""
    dense = jax.jit(densified_local_matmul(jax.lax.Precision.DEFAULT))
    one_triple = jnp.array([[0, 0, 0, 1]], jnp.int32)

    @jax.jit
    def smm(x, eye):
        c = jnp.zeros((1,) + x.shape, jnp.float32)
        return smm_process_stack(x[None], eye[None], c, one_triple,
                                 align=False)[0]

    @jax.jit
    def highest(x, eye):
        return jnp.dot(x, eye, precision=jax.lax.Precision.HIGHEST)

    out = {"densified (lax.dot, DEFAULT)": probe_precision(dense, 256),
           "blocked (Pallas smm)": probe_precision(smm, BS),
           "reference (jnp.dot, HIGHEST)": probe_precision(highest,
                                                           256)}
    for path, cls in out.items():
        log(f"  precision {path}: {cls} "
            f"(per-product relative error <= {PRODUCT_ERR[cls]:.3g})")
    require(out["reference (jnp.dot, HIGHEST)"] == "float32",
            "the HIGHEST reference does not run at float32")
    return out


def product_gamma(cls: str, k_terms: int) -> float:
    """Componentwise bound on |C - ref| / (|A| |B|): both products'
    rounding plus two float32 accumulations of ``k_terms`` terms."""
    return PRODUCT_ERR[cls] + PRODUCT_ERR["float32"] + 2 * k_terms * U32


@jax.jit
def _errors(c, a, b, dropped, gamma):
    hi = jax.lax.Precision.HIGHEST
    ref = jnp.dot(a, b, precision=hi)
    mag = jnp.dot(jnp.abs(a), jnp.abs(b), precision=hi)
    err = jnp.abs(c - ref)
    drop = jnp.repeat(jnp.repeat(dropped, BS, axis=0), BS, axis=1)
    bound = gamma * mag + drop
    # an element with a zero bound must match exactly
    over = jnp.where(bound > 0, err / jnp.where(bound > 0, bound, 1.0),
                     jnp.where(err > 0, jnp.inf, 0.0))
    rel = jnp.where(mag > 0, err / jnp.where(mag > 0, mag, 1.0), 0.0)
    return jnp.max(over), jnp.max(rel), jnp.max(err), jnp.max(jnp.abs(ref))


def check_product(name, c, a, b, *, gamma, dropped):
    """Hold ``c`` to ``|c - a@b| <= gamma |a||b| + dropped`` elementwise
    against a HIGHEST product computed on the first device."""
    dev = jax.devices()[0]
    c, a, b = (jax.device_put(x, dev) for x in (c, a, b))
    over, rel, err, ref_max = (float(v) for v in _errors(
        c, a, b, jax.device_put(jnp.asarray(dropped, jnp.float32), dev),
        jnp.float32(gamma)))
    extra = (f" + dropped mass (max {float(np.max(dropped)):.3g})"
             if np.any(dropped) else "")
    log(f"    {name}: max |C-ref|/(|A||B|) = {rel:.3e}, bound "
        f"{gamma:.3e}{extra}; max |C-ref| = {err:.3e} "
        f"(max |ref| = {ref_max:.3e}); worst element at {over:.3f} of "
        f"its bound")
    require(over <= 1.0, f"{name}: error exceeds its bound ({over:.3f}x)")


def dropped_mass(an, bn, eps):
    """(nbr, nbc) norm mass of the triples the filter may drop (norm
    product below eps, with a relative margin for the float32 norms)."""
    an = an.astype(np.float64)
    bn = bn.astype(np.float64)
    out = np.zeros((an.shape[0], bn.shape[1]))
    for k in range(an.shape[1]):
        p = np.outer(an[:, k], bn[k])
        out += np.where(p < eps * (1 + 1e-5), p, 0.0)
    return out


def max_terms(am, bm) -> int:
    """Most nonzero terms in one element's inner product: retained
    k-blocks times the block size."""
    return int((am.astype(np.int64) @ bm.astype(np.int64)).max()) * BS


def placement(C, n_devices: int) -> str:
    shards = C.data.addressable_shards
    require(len({s.device for s in shards}) == n_devices,
            f"product spans {len({s.device for s in shards})} devices, "
            f"expected {n_devices}")
    return ", ".join(f"dev{s.device.id}:{tuple(s.data.shape)}"
                     for s in shards)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_dense(counter, mesh, prec, *, n, n_devices):
    log(f"(a) dense square {n}x{n} @ {n}x{n}, block {BS}, densified "
        f"Cannon on a {'x'.join(map(str, mesh.devices.shape))} mesh")
    ka, kb = jax.random.split(jax.random.PRNGKey(0))
    A = dbcsr.create(jax.random.normal(ka, (n, n), jnp.float32), mesh=mesh,
                     block_size=BS)
    B = dbcsr.create(jax.random.normal(kb, (n, n), jnp.float32), mesh=mesh,
                     block_size=BS)
    kw = dict(mesh=mesh, algorithm="cannon", densify=True, align=False,
              stack_size=STACK_SIZE, precision=jax.lax.Precision.DEFAULT,
              return_plan=True)
    (C, plan), first = counter.measure(lambda: dbcsr.multiply(A, B, **kw),
                                       lambda r: r[0].data)
    _, repeat = counter.measure(lambda: dbcsr.multiply(A, B, **kw),
                                lambda r: r[0].data)
    cls = prec["densified (lax.dot, DEFAULT)"]
    log(f"    occupancy A {A.occupancy:.3f}, B {B.occupancy:.3f}; executed "
        f"algorithm={plan.algorithm} densify={plan.densify}; precision "
        f"DEFAULT ran as {cls}")
    log(f"    placement: {placement(C, n_devices)}")
    log_calls(first, repeat)
    check_product("C vs HIGHEST", C.data, A.data, B.data,
                  gamma=product_gamma(cls, n),
                  dropped=np.zeros((n // BS, n // BS)))


def exact_trace_drift(P0, n_iter: int):
    """tr(P_t) - n/2 of the exact McWeeny iteration, from P0's
    eigenvalues in float64.  The Hamiltonian couples same-parity
    orbitals only, so its two parity blocks are diagonalized apart."""
    n = P0.shape[0]
    even, odd = np.arange(0, n, 2), np.arange(1, n, 2)
    if np.any(P0[np.ix_(even, odd)]):
        lam = np.linalg.eigvalsh(P0)
    else:
        lam = np.concatenate([np.linalg.eigvalsh(P0[np.ix_(even, even)]),
                              np.linalg.eigvalsh(P0[np.ix_(odd, odd)])])
    drifts = []
    for _ in range(n_iter):
        lam = 3.0 * lam ** 2 - 2.0 * lam ** 3
        drifts.append(float(lam.sum() - n / 2))
    return drifts


def purification_operand(mesh, n):
    H, mask = banded_hamiltonian(n, BS)
    P0_host = initial_density(H)
    del H
    P0 = dbcsr.create(P0_host.astype(np.float32), mesh=mesh, block_size=BS,
                      block_mask=mask)
    return P0, P0_host, mask


def first_purification_product(counter, mesh, prec, P0, mask,
                               *, n_devices):
    """P0 @ P0 on the blocked path, checked against HIGHEST."""
    kw = dict(BLOCKED, mesh=mesh, filter_eps=FILTER_EPS)
    (P2, plan), first = counter.measure(
        lambda: dbcsr.multiply(P0, P0, return_plan=True, **kw),
        lambda r: r[0].data)
    _, repeat = counter.measure(
        lambda: dbcsr.multiply(P0, P0, return_plan=True, **kw),
        lambda r: r[0].data)
    es = plan.executor_stats
    cls = prec["blocked (Pallas smm)"]
    log(f"    P0 occupancy {P0.occupancy:.4f} ({int(mask.sum())} of "
        f"{mask.size} blocks); P0@P0 occupancy {P2.occupancy:.4f}")
    if es.get("rank_exact"):
        # n_entries sums each step's busiest rank: the slab length
        work = (f"{sum(es['rank_entries'])} triples over "
                f"{len(es['rank_entries'])} ranks (rank-exact), busiest "
                f"rank {es['max_rank_entries']}, imbalance "
                f"{es['rank_imbalance']:.3f}")
    else:
        work = (f"{es['n_entries']} triples "
                f"({es['n_norm_filtered_triples']} dropped by the filter)")
    log(f"    executed algorithm={plan.algorithm} densify={plan.densify} "
        f"stack_tile={plan.stack_tile} align={plan.align} kernel=smm; "
        f"{work} in {es['n_steps'] - es['n_empty_steps']} of "
        f"{es['n_steps']} steps")
    log(f"    precision: smm kernel ran as {cls}")
    log(f"    placement: {placement(P2, n_devices)}")
    log_calls(first, repeat)
    an = P0.norms()
    check_product("P0@P0 vs HIGHEST", P2.data, P0.data,
                  P0.data, gamma=product_gamma(cls, max_terms(mask, mask)),
                  dropped=dropped_mass(an, an, FILTER_EPS))
    return es


def phase_purification(counter, mesh, prec, *, n, n_iter):
    log(f"(b) McWeeny purification, banded Hamiltonian n={n}, block {BS}, "
        f"filter_eps={FILTER_EPS:g}, blocked Cannon + Pallas smm, "
        f"{n_iter} iterations")
    P0, P0_host, mask = purification_operand(mesh, n)
    drift = exact_trace_drift(P0_host, n_iter)
    del P0_host
    first_purification_product(counter, mesh, prec, P0, mask,
                               n_devices=1)

    t0 = time.perf_counter()
    _, trace = mcweeny_purify(P0, mesh=mesh, n_iter=n_iter,
                              filter_eps=FILTER_EPS, multiply_kw=BLOCKED)
    wall = time.perf_counter() - t0
    # trace error one iteration can add: the rounding of 3 P^2 - 2 P^3
    # (operand rounding bounded outright, float32 accumulation by the
    # probabilistic bound; sum_i (|P||P|)_ii = ||P||_F^2 <= n/2 for a
    # spectrum in [0, 1]) plus the filter: every dropped diagonal triple
    # moves the trace by less than eps, every dropped diagonal block by
    # less than sqrt(bs) eps.
    cls = prec["blocked (Pallas smm)"]
    nb = n // BS
    per_iter = (5 * (n / 2) * (PRODUCT_ERR[cls] + LAMBDA * n ** 0.5 * U32)
                + 5 * nb * nb * FILTER_EPS + nb * BS ** 0.5 * FILTER_EPS)
    log(f"    {'iter':>4} {'occupancy':>9} {'idempotency':>12} "
        f"{'tr(P)-n/2':>11} {'exact':>9} {'bound':>9}")
    for t, e in enumerate(trace):
        dev = e["trace_P"] - n / 2
        bound = abs(drift[t]) + (t + 1) * per_iter
        log(f"    {t:4d} {e['occupancy']:9.4f} {e['idempotency']:12.4e} "
            f"{dev:11.4f} {drift[t]:9.4f} {bound:9.3f}")
        require(abs(dev) <= bound,
                f"tr(P) drifted {dev:.3f} from n/2 at iteration {t}")
    idem = [e["idempotency"] for e in trace]
    require(all(b < a for a, b in zip(idem, idem[1:])),
            f"idempotency did not fall at every iteration: {idem}")
    log(f"    {n_iter} iterations ({2 * n_iter} multiplies) in {wall:.2f} s "
        f"wall (informal, compiles included)")
    return P0


def phase_verified(counter, mesh, P0):
    log("(c) checksum-verified P0@P0 (verify='checksum') on clean data")
    kw = dict(BLOCKED, mesh=mesh, filter_eps=FILTER_EPS, verify="checksum",
              return_plan=True)

    def run():
        try:
            return dbcsr.multiply(P0, P0, **kw)
        except CorruptionDetectedError as e:
            log_margin(e.report)
            raise

    (C, _), first = counter.measure(run, lambda r: r[0].data)
    _, repeat = counter.measure(run, lambda r: r[0].data)
    report = C.verification["report"]
    log_margin(report)
    log_calls(first, repeat)
    require(not report.detected,
            f"ABFT flagged {len(report.flagged_blocks)} blocks of a clean "
            f"product")


def log_margin(report):
    worst = max(float(np.max(report.row_residual / report.row_tol)),
                float(np.max(report.col_residual / report.col_tol)))
    log(f"    checksum residual / tolerance: max {worst:.3e} "
        f"(detected={report.detected}, flagged blocks "
        f"{len(report.flagged_blocks)})")


def phase_four_chips(counter, mesh, prec, *, n):
    phase_dense(counter, mesh, prec, n=n, n_devices=4)
    log(f"(b, first multiply) P0@P0 of the banded Hamiltonian n={n}, "
        f"blocked Cannon + Pallas smm, rank-exact plans, 2x2 mesh")
    P0, P0_host, mask = purification_operand(mesh, n)
    del P0_host
    es = first_purification_product(counter, mesh, prec, P0,
                                    mask, n_devices=4)
    require(bool(es.get("rank_exact")), "the blocked multiply did not run "
            "rank-exact plans")


def planner_constants_source() -> str:
    found = [p for p in (Path(calibrate.DEFAULT_CALIBRATION),
                         *sorted(Path(calibrate.DEFAULT_BENCH_DIR).glob(
                             "*.json")))
             if p.exists()]
    if not found:
        return "built-in defaults (no calibration or bench artifacts)"
    return "built-in defaults overridden by " + ", ".join(map(str, found))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the distributed path on a 2x2 mesh")
    args = ap.parse_args(argv)
    want = 4 if args.four_chips else 1

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {platform!r} "
              f"({len(devices)} device(s))", file=sys.stderr)
        return 2
    if len(devices) != want:
        print(f"chip_smoke: asked for {want} chip(s), JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    log(f"device: {platform} {devices[0].device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")
    log(f"planner constants: {planner_constants_source()}; algorithm, "
        f"densify, align and stack size are pinned")
    counter = CompileCounter()
    prec = path_precisions()

    t0 = time.perf_counter()
    if args.four_chips:
        mesh = make_mesh((2, 2), ("data", "model"))
        phase_four_chips(counter, mesh, prec, n=N)
    else:
        mesh = make_mesh((1, 1), ("data", "model"))
        phase_dense(counter, mesh, prec, n=N, n_devices=1)
        P0 = phase_purification(counter, mesh, prec, n=N,
                                n_iter=N_ITER)
        phase_verified(counter, mesh, P0)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s; "
        f"{counter.n} XLA compilations in total ({counter.hits} from the "
        f"persistent cache, {counter.secs:.1f} s compiling)")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
