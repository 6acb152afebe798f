"""Plain reference of AXPYDOT, ``(a * x + y) . w``, on the host in
float64, block by block so that no float64 copy of a whole input is
held."""
from __future__ import annotations

import numpy as np

BLOCK = 1 << 24


def axpydot(a, x, y, w) -> float:
    a = np.float64(a)
    total = 0.0
    for i in range(0, x.shape[0], BLOCK):
        z = a * x[i:i + BLOCK].astype(np.float64) + y[i:i + BLOCK]
        total += float(np.dot(z, w[i:i + BLOCK].astype(np.float64)))
    return total


def axpydot_low(a, x, y, w, dtype):
    """The control: the same arithmetic with inputs, products and the
    sum in ``dtype`` (a JAX dtype such as bfloat16), on the device."""
    import jax.numpy as jnp
    a, x, y, w = (jnp.asarray(v).astype(dtype) for v in (a, x, y, w))
    return float(jnp.sum((a * x + y) * w, dtype=dtype))
