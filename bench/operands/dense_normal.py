"""Dense operands with standard normal entries, the dense products of
arXiv:1910.04796: ``A`` of ``m x k`` and ``B`` of ``k x n`` in the
configuration's ``dtype``.  Made on the device from the seed, each shard
where it lives, so an operand larger than one chip never passes through
one device or the host."""
from __future__ import annotations

from bench.operands import Operands, seed32


def make(config: dict, seed: int, sharding) -> Operands:
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(config["dtype"])
    m, k, n = config["m"], config["k"], config["n"]

    def both(ka, kb):
        return (jax.random.normal(ka, (m, k), dtype),
                jax.random.normal(kb, (k, n), dtype))

    a, b = jax.jit(both, out_shardings=(sharding, sharding))(
        jax.random.key(seed32(seed, 1)), jax.random.key(seed32(seed, 2)))
    return Operands(a=a, b=b)
