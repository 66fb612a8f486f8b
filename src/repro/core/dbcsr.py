"""DBCSRMatrix — user-facing distributed blocked matrix container.

Mirrors the DBCSR API surface (create / multiply / add / trace /
transpose / to-from ScaLAPACK-style layouts) on top of JAX arrays with
NamedSharding.  The payload of a dense DBCSR matrix is simply a 2D
array sharded over the (row_axis, col_axis) process grid; the blocked
structure is metadata (BlockLayout) consumed by the local-multiply
strategies.

Block-sparse matrices carry an additional static block mask (numpy
bool, (nblock_rows, nblock_cols)); absent blocks are stored as zeros in
the dense payload (occupancy handling is metadata-level: the stack
generator skips absent blocks, which is where sparse wins come from in
DBCSR).  This keeps every array shape static — mandatory for pjit.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs

from .blocking import BlockLayout, GridSpec

__all__ = ["DBCSRMatrix", "create", "multiply", "multiply_batched",
           "multiply_vector", "add", "trace", "transpose",
           "contract", "create_tensor"]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DBCSRMatrix:
    """A distributed blocked matrix.

    data       : (rows, cols) jax.Array, sharded P(row_axis, col_axis)
    layout     : block structure metadata
    grid       : mesh-axis names of the process grid
    block_mask : optional (nbr, nbc) numpy bool — block-sparse occupancy
    block_norms: optional (nbr, nbc) numpy float32 — per-block Frobenius
                 norms (repro.sparsity), lazily computed/cached by
                 ``norms()`` and consumed by the ``filter_eps`` multiply
                 path and ``filter()``

    Products returned by ``multiply`` additionally carry the executed
    ``MultiplyPlan`` as a plain ``last_plan`` attribute (host-side
    observability only — not part of the pytree, does not survive jit).
    """

    data: jax.Array
    layout: BlockLayout
    grid: GridSpec
    block_mask: Optional[np.ndarray] = None
    block_norms: Optional[np.ndarray] = None

    # -- pytree protocol (data is a leaf; the rest is static) ----------
    def tree_flatten(self):
        # mask AND norms ride in aux as (shape, bytes): hashable (jit
        # cache key) AND sufficient to reconstruct the arrays on
        # unflatten, so block sparsity — and its norms — survive
        # jit/vmap/scan round-trips.
        mask_aux = (None if self.block_mask is None
                    else (self.block_mask.shape, self.block_mask.tobytes()))
        norms_aux = None
        if self.block_norms is not None:
            norms = np.ascontiguousarray(self.block_norms, dtype=np.float32)
            norms_aux = (norms.shape, norms.tobytes())
        return (self.data,), (self.layout, self.grid, mask_aux, norms_aux)

    @classmethod
    def tree_unflatten(cls, aux, children):
        layout, grid, mask_aux, norms_aux = aux
        mask = None
        if mask_aux is not None:
            shape, raw = mask_aux
            mask = np.frombuffer(raw, dtype=bool).reshape(shape).copy()
        norms = None
        if norms_aux is not None:
            shape, raw = norms_aux
            norms = np.frombuffer(raw, dtype=np.float32).reshape(shape).copy()
        return cls(children[0], layout, grid, mask, norms)

    # -- DBCSR-like API -------------------------------------------------
    @property
    def shape(self):
        return self.data.shape

    @property
    def occupancy(self) -> float:
        if self.block_mask is None:
            return 1.0
        return float(self.block_mask.mean())

    def norms(self, recompute: bool = False) -> np.ndarray:
        """Per-block Frobenius norms ((nbr, nbc) float32 numpy), cached
        on the matrix after the first call (one blockwise device
        reduction per geometry — repro.sparsity.norms).  Mask-absent
        blocks report 0.  Pass ``recompute=True`` after mutating
        ``data`` through a non-DBCSR op (the cache cannot observe
        that)."""
        if self.block_norms is None or recompute:
            from repro.sparsity.norms import block_norms_of

            self.block_norms = block_norms_of(
                self.data, self.layout.block_rows, self.layout.block_cols,
                self.block_mask)
        return self.block_norms

    def filter(self, eps: float) -> "DBCSRMatrix":
        """DBCSR's post-multiply filtering pass: re-derive the
        occupancy from the *actual* block norms, dropping every block
        with ``norm < eps`` (blocks exactly at eps survive, matching
        the triple-filter contract), zeroing the dropped blocks'
        payload so dense math keeps matching sparse semantics.  Never
        resurrects a block the current mask declares absent."""
        norms = self.norms()
        mask = norms >= float(eps)
        if self.block_mask is not None:
            mask &= self.block_mask
        bs_r, bs_c = self.layout.block_rows, self.layout.block_cols
        full = np.repeat(np.repeat(mask, bs_r, 0), bs_c, 1)
        data = self.data * jnp.asarray(full, dtype=self.data.dtype)
        new_norms = np.where(mask, norms, np.float32(0.0)).astype(np.float32)
        return DBCSRMatrix(data, self.layout, self.grid, mask, new_norms)

    def transpose(self) -> "DBCSRMatrix":
        layout = BlockLayout(self.layout.cols, self.layout.rows,
                             self.layout.block_cols, self.layout.block_rows)
        mask = None if self.block_mask is None else self.block_mask.T.copy()
        norms = (None if self.block_norms is None
                 else self.block_norms.T.copy())
        return DBCSRMatrix(self.data.T, layout, self.grid, mask, norms)

    def trace(self) -> jax.Array:
        return jnp.trace(self.data)

    def scale(self, alpha) -> "DBCSRMatrix":
        norms = None
        if self.block_norms is not None:
            try:
                # |alpha| rescales Frobenius norms exactly — but only a
                # concrete scalar can update the host-side cache; under
                # a tracer the cache is dropped (recomputed lazily)
                norms = (self.block_norms
                         * np.float32(abs(float(alpha)))).astype(np.float32)
            except Exception:  # traced alpha cannot reach host numpy
                norms = None
        return dataclasses.replace(self, data=self.data * alpha,
                                   block_norms=norms)


def _sharding(mesh: Mesh, grid: GridSpec) -> NamedSharding:
    return NamedSharding(mesh, P(grid.row_axis, grid.col_axis))


def create(
    array,
    *,
    mesh: Mesh,
    grid: GridSpec = GridSpec(),
    block_size: int = 64,
    block_mask: Optional[np.ndarray] = None,
    compute_norms: bool = False,
) -> DBCSRMatrix:
    """Create a DBCSR matrix from a host/global array (library owns the
    distribution, like dbcsr_create + dbcsr_put_block).
    ``compute_norms=True`` eagerly populates the per-block Frobenius
    norm cache (otherwise ``norms()`` computes it on first use)."""
    rows, cols = array.shape
    layout = BlockLayout(rows, cols, block_size, block_size)
    data = jax.device_put(array, _sharding(mesh, grid))
    if block_mask is not None:
        if block_mask.shape != (layout.nblock_rows, layout.nblock_cols):
            raise ValueError("block_mask shape mismatch")
        # zero out absent blocks so dense math matches sparse semantics
        mask_full = np.repeat(np.repeat(block_mask, block_size, 0), block_size, 1)
        data = data * jnp.asarray(mask_full, dtype=data.dtype)
    out = DBCSRMatrix(data, layout, grid, block_mask)
    if compute_norms:
        out.norms()
    return out


def add(a: DBCSRMatrix, b: DBCSRMatrix,
        recompute_norms: bool = False) -> DBCSRMatrix:
    """C = A + B.  Result occupancy is the union of the operands'.

    A missing mask means *dense* (every block present), so when exactly
    one operand carries a mask the union with the dense operand is
    dense and the result mask is deliberately ``None`` — not a dropped
    mask, but the correct all-present occupancy (contrast multiply(),
    where a one-sided mask does constrain the product's support).

    Norms are NOT propagated: ``||A + B||_F`` per block is not
    derivable from the operands' norms (only bounded), and the cache
    must never hold a bound where ``filter()`` expects the truth — so
    by default the result's norm cache is empty and recomputes lazily
    via ``norms()``.  ``recompute_norms=True`` is a convenience that
    eagerly computes the sum's true norms from its payload before
    returning (one blockwise reduction — exactly what the first
    ``norms()`` call would do; handy when the caller filters or
    eps-multiplies the sum immediately, e.g. purification iterations).
    """
    mask = None
    if a.block_mask is not None and b.block_mask is not None:
        mask = a.block_mask | b.block_mask
    out = DBCSRMatrix(a.data + b.data, a.layout, a.grid, mask)
    if recompute_norms:
        out.norms()
    return out


def trace(a: DBCSRMatrix) -> jax.Array:
    return a.trace()


def transpose(a: DBCSRMatrix) -> DBCSRMatrix:
    return a.transpose()


def multiply_vector(a: DBCSRMatrix, x: jax.Array) -> jax.Array:
    """y = A @ x (paper section II lists matrix-vector among the ops).

    The 2D-sharded payload contracts its column-sharded dim against the
    replicated vector; GSPMD reduces the row partials (the degenerate
    N=1 tall-skinny case)."""
    return a.data @ x


def _product_mask(a: DBCSRMatrix, b: DBCSRMatrix, an, bn,
                  filter_eps: Optional[float]):
    """The result support of C = A @ B, shared by ``multiply`` and
    ``multiply_batched``: ``(mask, needs_zeroing)`` where ``mask`` is
    the symbolic product support ``(a_mask @ b_mask) > 0`` (None when
    both operands are dense and no filter applies) or, under
    ``filter_eps``, the eps-*retained* support — in which case the
    payload outside it must be zeroed (``needs_zeroing``) to keep the
    mask/zeros invariant on both local paths."""
    if (a.block_mask is None and b.block_mask is None
            and filter_eps is None):
        return None, False
    from .stacks import normalize_block_masks

    am, bm = normalize_block_masks(
        a.layout.nblock_rows, a.layout.nblock_cols,
        b.layout.nblock_cols, a.block_mask, b.block_mask)
    if filter_eps is not None:
        from repro.sparsity.filter import product_mask

        return product_mask(am, bm, an, bn, filter_eps), True
    return (am.astype(np.int64) @ bm.astype(np.int64)) > 0, False


def _apply_result_mask(c_data: jax.Array, mask: Optional[np.ndarray],
                       needs_zeroing: bool, block_rows: int,
                       block_cols: int) -> jax.Array:
    """Zero the payload outside the retained support (eps path only —
    the symbolic-product mask never needs it, absent blocks are already
    exact zeros)."""
    if mask is None or not needs_zeroing:
        return c_data
    full = np.repeat(np.repeat(mask, block_rows, 0), block_cols, 1)
    return c_data * jnp.asarray(full, dtype=c_data.dtype)


def multiply(
    a: DBCSRMatrix,
    b: DBCSRMatrix,
    *,
    mesh: Mesh,
    algorithm: str = "auto",
    densify: Optional[bool] = None,
    filter_eps: Optional[float] = None,
    verify: Optional[str] = None,
    return_plan: bool = False,
    **kw,
) -> DBCSRMatrix:
    """C = A @ B — with ``algorithm="auto"`` (the default) the
    cost-model planner (repro.planner.plan_multiply) picks the
    data-exchange algorithm AND the local path for this (shape,
    occupancy, mesh); a fixed ``algorithm=``/``densify=`` pins them
    (``densify=None`` under a fixed algorithm means densified, the
    legacy default).

    Block occupancy flows end to end: the operands' masks are handed to
    the distributed dispatcher (the blocked path plans only present
    triples and skips empty shift/panel steps), and the result carries
    the symbolic product mask ``(a_mask @ b_mask) > 0`` — with a missing
    operand mask treated as all-present, so a single masked operand
    still constrains the product's support.

    ``filter_eps`` — norm-based on-the-fly filtering (repro.sparsity),
    the interaction with ``block_mask`` being strictly *subtractive*:

      * the binary masks still decide which blocks exist at all; on top
        of them, product contributions with ``norm(A_ik) * norm(B_kj) <
        filter_eps`` are dropped before they reach a multiplication
        stack (operand norms come from ``norms()``, computed on the fly
        when not already cached),
      * the result's ``block_mask`` is the *retained* support — C
        blocks with at least one surviving contribution — which is a
        subset of the symbolic mask product, and the payload is zeroed
        outside it (so the mask/zeros invariant holds on the densified
        path too, whose single big GEMM does not drop triples),
      * ``filter_eps=0.0`` retains everything: identical result, mask
        and payload to the unfiltered path; ``None`` (default) disables
        the norm machinery entirely,
      * per-block truncation error is bounded by ``nbk * filter_eps``
        (at most nbk dropped contributions, each below eps).

    The executed plan is observable without re-deriving it: the product
    carries it as ``C.last_plan`` (a ``MultiplyPlan`` with per-candidate
    predicted costs via ``.explain()``, the executed blocked-path stack
    statistics as ``.executor_stats`` — including retained-vs-filtered
    triple counts under eps), and ``return_plan=True`` additionally
    returns ``(C, plan)``.  ``last_plan`` is a plain host-side
    attribute — it does not survive pytree flatten/jit round-trips
    (only ``data``/``layout``/``grid``/``block_mask``/``block_norms``
    do).

    ``verify`` — ABFT self-verification (repro.robustness):

      * ``"checksum"`` verifies the raw product against independently
        computed Huang–Abraham block checksums *before* the result mask
        is applied; detection tolerances scale with the PR 5 norm cache
        (``||A_ik||_F * ||B_kj||_F`` bounds plus the eps-filtered
        dropped mass), so float accumulation order and ``filter_eps``
        dropping never false-positive.  A detected corruption is
        localized to its exact block coordinates, repaired by ONE
        deterministic recompute of the flagged blocks (bitwise equal to
        a clean run), and reported; corruption that survives repair
        raises ``repro.robustness.guards.CorruptionDetectedError``.
        Operands are screened by the NaN/Inf tripwires first
        (``NonFiniteOperandError`` — poison inputs are not a checksum
        problem).
      * ``"auto"`` enables verification only when the planner prices
        its checksum overhead (extra flops + comm for the augmented
        row/column — ``cost_model.verify_overhead_s``) within
        ``verify_budget`` (default 25%) of the plan's predicted time.
      * ``None`` (default) adds zero work — bit-identical to the
        unverified multiply.

    The outcome is observable as ``C.verification`` (and
    ``plan.verification``): a dict with the pricing decision and the
    ``VerificationReport`` (``detected``, flagged ``(i, j)`` blocks,
    residuals vs tolerances, ``repaired``) when verification ran.

    Many small products?  See ``multiply_batched``: it fuses
    same-geometry requests into one dispatch, amortizing the per-call
    trace/launch cost that dominates small multiplies.  Batching and
    filtering compose — a fused bucket is (geometry, occupancy-bin,
    eps)-uniform by construction, so ``filter_eps`` semantics inside a
    batch are identical to this single-product path.
    """
    from .multiply import _distributed_matmul, is_live, root_attrs

    def finish(c_data, plan):
        c_layout = BlockLayout(a.layout.rows, b.layout.cols,
                               a.layout.block_rows, b.layout.block_cols)
        # the eps path zeroes the payload outside the retained support —
        # load-bearing on BOTH local paths: the densified GEMM computes
        # sub-eps blocks the retained mask excludes, and the blocked
        # path's SPMD union-of-max steps let a rank deposit small
        # contributions into blocks outside the global retained support
        mask, zero = _product_mask(a, b, an, bn, filter_eps)
        c_data = _apply_result_mask(c_data, mask, zero,
                                    a.layout.block_rows, b.layout.block_cols)
        c = DBCSRMatrix(c_data, c_layout, a.grid, mask)
        c.last_plan = plan
        c.verification = plan.verification
        return (c, plan) if return_plan else c

    live = is_live(a.data, b.data)
    with obs.maybe_span(live, "multiply", cat="multiply", counters=True,
                        **root_attrs(algorithm, a.data, b.data)):
        an = bn = None
        if filter_eps is not None:
            an, bn = a.norms(), b.norms()
        return _distributed_matmul(
            a.data, b.data, mesh=mesh, grid=a.grid,
            algorithm=algorithm, densify=densify,
            block_m=a.layout.block_rows, block_k=a.layout.block_cols,
            block_n=b.layout.block_cols,
            a_mask=a.block_mask, b_mask=b.block_mask,
            a_norms=an, b_norms=bn, filter_eps=filter_eps,
            verify=verify, return_plan=True, _live=live, _finish=finish,
            **kw)


def create_tensor(array, *, mesh, grid=GridSpec(), block_sizes,
                  block_mask=None, compute_norms=False):
    """Create a blocked N-d ``DBCSRTensor`` (repro.tensor) — the tensor
    analogue of ``create``: uniform per-axis blocking, an optional N-d
    block occupancy mask (absent blocks' payload zeroed) and a lazily
    cached per-block Frobenius norm tensor.  Tensors are contracted
    with ``contract``."""
    from repro.tensor import create_tensor as _create_tensor

    return _create_tensor(array, mesh=mesh, grid=grid,
                          block_sizes=block_sizes, block_mask=block_mask,
                          compute_norms=compute_norms)


def contract(
    spec: str,
    a,
    b,
    *,
    mesh: Mesh,
    algorithm: str = "auto",
    layout="auto",
    densify: Optional[bool] = None,
    filter_eps: Optional[float] = None,
    verify: Optional[str] = None,
    rank_exact: Optional[bool] = None,
    return_plan: bool = False,
    **kw,
):
    """C = contraction of two blocked tensors per an einsum ``spec``
    (``"ijk,kl->ijl"``) — the N-d sibling of ``multiply`` /
    ``multiply_batched`` (repro.tensor, after arXiv:1910.13555): the
    spec is parsed into (contracted, A-free, B-free) index groups, the
    tensors are MATRICIZED — each group fused into one blocked matrix
    dimension at the block level, so masks lower by a pure block-grid
    transpose (an N-d block is retained iff its 2D image is) and the
    Frobenius norm cache lowers exactly (norms are invariant to the
    intra-block permutation) — the 2D product runs through the ordinary
    ``multiply``, and the result folds back into the spec's output
    frame as a ``DBCSRTensor`` carrying the retained N-d mask.

    ``layout`` — the matricization is a COSTED choice, not a
    convention: every legal layout (fusion orders of the three index
    groups x the transposed variant) is priced by the planner as its
    own 2D multiply plan (per-layout occupancy and rank-imbalance from
    the matricized masks) plus its unfold/refold copy cost
    (``cost_model.matricize_cost_s``).  ``"auto"`` (default) lets
    ``planner.plan_contract`` pick — LRU-cached on the contraction
    signature, so a repeated contraction replans for free; a
    ``Layout`` instance or its label string (e.g. ``"(ij|k)@(k|l)"``)
    pins it.  The decision is observable: the result carries the
    executed ``ContractionPlan`` as ``C.last_plan``, whose
    ``explain()`` prints the per-layout table above the winning
    layout's per-candidate multiply breakdown.

    ``algorithm`` / ``densify`` / ``filter_eps`` / ``verify`` /
    ``rank_exact`` and any further kwargs thread through to the
    underlying ``multiply`` with identical semantics — eps filtering
    uses the lowered norms (same subtractive contract, ``filter_eps=0``
    bit-identical to unfiltered), ABFT verification detects/localizes/
    repairs corruption before the refold (so the guarantee lands in the
    tensor frame, reported as ``C.verification``), and rank-exact
    per-rank plans see the matricized masks.

    At a FIXED layout the result is bitwise equal to hand-matricizing
    the operands and calling ``multiply`` directly (the fold is a pure
    element permutation); different layouts change the fused
    accumulation order and agree to float tolerance only.

    ``return_plan=True`` returns ``(C, ContractionPlan)``.
    """
    from repro.tensor import contract as _contract

    return _contract(spec, a, b, mesh=mesh, algorithm=algorithm,
                     layout=layout, densify=densify,
                     filter_eps=filter_eps, verify=verify,
                     rank_exact=rank_exact, return_plan=return_plan, **kw)


def _bucket_key(a: DBCSRMatrix, b: DBCSRMatrix,
                filter_eps: Optional[float]) -> tuple:
    """The batching bucket contract: requests fuse only when they agree
    on (geometry, occupancy-bin, eps).

      geometry       operand shapes + block sizes + grid axis names —
                     everything the traced dispatch program's shape
                     depends on
      occupancy-bin  ``fill_bin`` of each operand's block-mask fill
                     (the autotune table's log-spaced bins): requests in
                     one bin share stack params and pad little against
                     each other; finer distinctions stay per-request
                     via the content-fingerprinted plan memo
      eps            the norm-filter threshold — it shapes the
                     per-group plans, so it must be bucket-uniform

    This is the same key contract the serving layer
    (repro.serve.multiply_service) buckets queued requests by.
    """
    from repro.kernels.smm.autotune import fill_bin

    return (
        tuple(a.shape), tuple(b.shape),
        a.layout.block_rows, a.layout.block_cols, b.layout.block_cols,
        a.grid.row_axis, a.grid.col_axis,
        fill_bin(a.occupancy), fill_bin(b.occupancy),
        None if filter_eps is None else float(filter_eps),
    )


def _execute_bucket(group, *, mesh, algorithm, densify, filter_eps,
                    fused, verify=None, **kw):
    """Run one bucket of same-key requests: fused (one batched
    dispatch) or looped (per-request ``multiply``), per the planner's
    fuse-or-loop pricing unless ``fused`` pins it.

    ``verify`` forces the looped path: ABFT checksums verify one
    product at a time (verification of the fused batched dispatch is an
    open ROADMAP item), so a verified bucket trades the fusion win for
    per-request detection/repair."""
    from .multiply_batched import BATCHED_ALGORITHMS

    if verify is not None:
        if fused:
            raise ValueError(
                "verify= requires the looped path (ABFT on the fused "
                "batched dispatch is not implemented); drop fused=True")
        fused = False
    a0, b0 = group[0]
    g = len(group)
    an = bn = None
    if filter_eps is not None:
        an = [a.norms() for a, _ in group]
        bn = [b.norms() for _, b in group]

    batchable = (algorithm in ("auto",) + BATCHED_ALGORITHMS
                 and kw.get("bcast") != "gather")
    if fused and not batchable:
        raise ValueError(
            f"fused=True requires a batch-capable algorithm "
            f"{BATCHED_ALGORITHMS}, got {algorithm!r}"
            + (" with bcast='gather'" if kw.get("bcast") == "gather"
               else ""))
    plan = None
    fuse = fused
    if fuse is None:
        fuse = batchable and g > 1
        if fuse:
            from repro.planner.plan import plan_multiply_batched

            from .multiply import _global_occupancy

            pr, pc = a0.grid.grid_shape(mesh)
            occs = [
                _global_occupancy(
                    a.layout.rows, a.layout.cols, b.layout.cols,
                    a.layout.block_rows, a.layout.block_cols,
                    b.layout.block_cols, a.block_mask, b.block_mask,
                    an[i] if an else None, bn[i] if bn else None,
                    filter_eps)
                for i, (a, b) in enumerate(group)
            ]
            occ = sum(occs) / len(occs)
            occ_max = max(occs)
            plan = plan_multiply_batched(
                g, a0.layout.rows, a0.layout.cols, b0.layout.cols,
                blocks=(a0.layout.block_rows, a0.layout.block_cols,
                        b0.layout.block_cols),
                mesh_shape=(pr, pc), occupancy=occ,
                dtype=a0.data.dtype,
                algorithm=None if algorithm == "auto" else algorithm,
                densify=densify,
                padding_frac=(1.0 - occ / occ_max if occ_max > 0 else 0.0))
            fuse = plan.fuse

    if not fuse:
        out = [multiply(a, b, mesh=mesh, algorithm=algorithm,
                        densify=densify, filter_eps=filter_eps,
                        verify=verify, **kw)
               for a, b in group]
        return out, {"fused": False, "plan": plan}

    from .multiply_batched import distributed_matmul_batched

    a_masks = [a.block_mask for a, _ in group]
    b_masks = [b.block_mask for _, b in group]
    if all(x is None for x in a_masks):
        a_masks = None
    if all(x is None for x in b_masks):
        b_masks = None
    c_data, bplan = distributed_matmul_batched(
        jnp.stack([a.data for a, _ in group]),
        jnp.stack([b.data for _, b in group]),
        mesh=mesh, grid=a0.grid, algorithm=algorithm, densify=densify,
        block_m=a0.layout.block_rows, block_k=a0.layout.block_cols,
        block_n=b0.layout.block_cols,
        a_masks=a_masks, b_masks=b_masks, a_norms=an, b_norms=bn,
        filter_eps=filter_eps, return_plan=True, **kw)
    c_layout = BlockLayout(a0.layout.rows, b0.layout.cols,
                           a0.layout.block_rows, b0.layout.block_cols)
    out = []
    for gi, (a, b) in enumerate(group):
        mask, zero = _product_mask(
            a, b, an[gi] if an else None, bn[gi] if bn else None,
            filter_eps)
        cd = _apply_result_mask(c_data[gi], mask, zero,
                                a.layout.block_rows, b.layout.block_cols)
        c = DBCSRMatrix(cd, c_layout, a.grid, mask)
        c.last_plan = bplan
        out.append(c)
    return out, {"fused": True, "plan": bplan}


def multiply_batched(
    requests,
    *,
    mesh: Mesh,
    algorithm: str = "auto",
    densify: Optional[bool] = None,
    filter_eps: Optional[float] = None,
    fused: Optional[bool] = None,
    verify: Optional[str] = None,
    return_plan: bool = False,
    **kw,
):
    """Many products, one dispatch: ``requests`` is a sequence of
    ``(A, B)`` DBCSRMatrix pairs; returns their products in input
    order.

    Requests are bucketed by the ``(geometry, occupancy-bin, eps)``
    key (see ``_bucket_key``) and each bucket executes either FUSED —
    operands stacked ``(G, m, k)``, ONE schedule / ONE fused stack
    dispatch for the whole bucket
    (core/multiply_batched.distributed_matmul_batched) — or LOOPED
    (per-request ``multiply``), whichever the planner prices cheaper
    (``plan_multiply_batched``: amortized trace/launch/latency vs
    cross-request padding waste).  ``fused=True``/``False`` pins the
    choice; ``None`` (default) lets the planner decide per bucket.

    Semantics match per-request ``multiply`` exactly: per-request
    product masks, eps-retained support and payload zeroing, and each
    result carries its bucket's executed ``BatchedMultiplyPlan`` as
    ``last_plan``.  At ``pipeline_depth=1`` with ``filter_eps`` in
    {None, 0.0} the fused blocked path is bit-identical to the looped
    one (core/multiply_batched bit-identity contract).

    ``verify`` (repro.robustness): per-request ABFT verification with
    the same semantics as ``multiply(verify=...)``; it forces the
    looped path (checksums on the fused batched dispatch are an open
    ROADMAP item), so a verified bucket trades the fusion win for
    per-request corruption detection and repair.

    ``return_plan=True`` returns ``(results, report)`` where the
    report carries per-bucket fusion stats: request count, the
    fuse-or-loop decision, and the executed plan (padding fractions,
    cross-request plan sharing, predicted fused-vs-looped times).
    """
    requests = list(requests)
    if not requests:
        return ([], {"n_requests": 0, "n_buckets": 0, "buckets": []}) \
            if return_plan else []
    buckets: dict = {}
    for i, (a, b) in enumerate(requests):
        buckets.setdefault(_bucket_key(a, b, filter_eps), []).append(i)
    results: list = [None] * len(requests)
    bucket_reports = []
    for key, idxs in buckets.items():
        out, rep = _execute_bucket(
            [requests[i] for i in idxs], mesh=mesh, algorithm=algorithm,
            densify=densify, filter_eps=filter_eps, fused=fused,
            verify=verify, **kw)
        for i, c in zip(idxs, out):
            results[i] = c
        if obs.enabled():
            # fuse-or-loop decision accounting (planner or pinned)
            obs.counter("batched.requests_fused" if rep["fused"]
                        else "batched.requests_looped").inc(len(idxs))
            obs.counter("batched.buckets").inc()
        bucket_reports.append({
            "key": key, "n_requests": len(idxs), "request_indices": idxs,
            **rep})
    if not return_plan:
        return results
    report = {
        "n_requests": len(requests),
        "n_buckets": len(buckets),
        "n_fused_requests": sum(r["n_requests"] for r in bucket_reports
                                if r["fused"]),
        "buckets": bucket_reports,
    }
    return results, report
