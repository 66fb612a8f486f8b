"""Production meshes.

Target hardware: TPU v5e pods — 256 chips per pod in a 16x16 ICI
torus; the multi-pod configuration stacks 2 pods (512 chips) with the
'pod' axis crossing the inter-pod links.

Axis roles:
  data  — batch / sequence sharding (DP); also the DBCSR engine's grid
          rows.
  model — TP / EP / vocab sharding; the DBCSR engine's grid columns.
  pod   — outer data parallelism for LM training; the 2.5D replication
          (stack) axis for the DBCSR engine (cannon25d).

Defined as functions (never module-level constants) so importing this
module does not touch jax device state.
"""
from __future__ import annotations

from repro.compat import make_mesh

__all__ = ["make_production_mesh", "make_mesh", "HW"]


# TPU v5e per-chip hardware constants (roofline denominators)
HW = {
    "name": "tpu_v5e",
    "peak_flops_bf16": 197e12,     # FLOP/s
    "hbm_bw": 819e9,               # B/s
    "ici_bw": 50e9,                # B/s per link
}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
