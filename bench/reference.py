"""The plain reference, and the comparison that decides ``correct``.

The reference product is ``jnp.dot`` at ``Precision.HIGHEST`` (float32 on
the TPU) of the same scaled operands the timed call multiplied, which the
benchmark forms itself from its own arrays; on a mesh it is XLA's own
sharded product.  It is built in panels, so that it fits beside the
products it checks.  Nothing here imports the program.

Two numbers are compared, each against a limit of its cell:

  max_elem_err  max over elements of |C - ref| / (|A| |B|), the error of
                the worst element against what its terms could add up to
                (an element whose |A| |B| is 0 must be exactly 0);
  rel_fro_err   ||C - ref||_F / ||ref||_F, the error of the product as a
                whole.

The control computes the reference from operands rounded to float8 e4m3
with one scale per operand (``fp8``): the step below the one bf16 pass
the configurations run at.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0     # largest finite float8 e4m3fn


def scaled(raw, s: float):
    """The step's operand: ``raw`` times the factor ``s``, in its dtype."""
    return raw * jnp.asarray(s, raw.dtype)


@functools.partial(jax.jit, static_argnames=("rows", "ks"),
                   donate_argnums=(0, 1))
def _add_panel(ref, mag, a, b, *, rows, ks):
    """Adds the terms of inner panel ``ks`` to row panel ``rows`` of the
    product and of its magnitude ``|a| @ |b|``."""
    ap, bp = a[rows[0]:rows[1], ks[0]:ks[1]], b[ks[0]:ks[1]]
    return (ref + jnp.dot(ap, bp, precision=HIGHEST,
                          preferred_element_type=jnp.float32),
            mag + jnp.dot(jnp.abs(ap), jnp.abs(bp), precision=HIGHEST,
                          preferred_element_type=jnp.float32))


@functools.partial(jax.jit, static_argnames=("rows",))
def _panel_errors(c, ref, mag, *, rows):
    err = jnp.abs(c[rows[0]:rows[1]].astype(jnp.float32) - ref)
    elem = jnp.where(mag > 0, err / jnp.where(mag > 0, mag, 1.0),
                     jnp.where(err > 0, jnp.inf, 0.0))
    return jnp.max(elem), jnp.sum(err * err), jnp.sum(ref * ref)


def _bounds(size: int, panels: int):
    step = -(-size // panels)
    return [(i, min(i + step, size)) for i in range(0, size, step)]


def numbers(c, a, b, *, row_panels: int = 4, inner_panels: int = 4) -> dict:
    """The compared numbers of the product ``c`` of ``a @ b``.  The
    reference is built one row panel at a time, each summed over panels
    of the inner dimension in separate calls, so that it needs a few
    panels of memory beside the operands, and on a mesh no chip holds
    more than a panel of another chip's rows or columns."""
    worst, sq_err, sq_ref = [], 0.0, 0.0
    for rows in _bounds(a.shape[0], row_panels):
        shape = (rows[1] - rows[0], b.shape[1])
        ref = jnp.zeros(shape, jnp.float32)
        mag = jnp.zeros(shape, jnp.float32)
        for ks in _bounds(a.shape[1], inner_panels):
            ref, mag = _add_panel(ref, mag, a, b, rows=rows, ks=ks)
        e, se, sr = _panel_errors(c, ref, mag, rows=rows)
        del ref, mag
        worst.append(float(e))
        sq_err += float(se)
        sq_ref += float(sr)
    return {"max_elem_err": max(worst),
            "rel_fro_err": (sq_err / sq_ref) ** 0.5}


def fp8(x):
    """``x`` rounded to float8 e4m3 under one scale that maps its largest
    magnitude to the largest finite float8, and back to float32."""
    scale = FP8_MAX / jnp.max(jnp.abs(x))
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


@jax.jit
def control(a, b):
    """The control product: the reference at the precision below."""
    return jnp.dot(fp8(a), fp8(b), precision=HIGHEST,
                   preferred_element_type=jnp.float32)
