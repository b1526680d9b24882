"""jit'd public wrapper for the tropical matmul."""
import functools

import jax
import jax.numpy as jnp

from .kernel import minplus_pallas
from .ref import minplus_ref


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def minplus(a: jnp.ndarray, b: jnp.ndarray, bm: int = 128, bn: int = 128,
            bk: int = 128, *, interpret: bool) -> jnp.ndarray:
    """``out[i, j] = min_k a[i, k] + b[k, j]`` via the Pallas kernel.

    ``interpret=True`` runs the kernel body in the Pallas interpreter (any
    backend); ``False`` compiles it for the TPU.
    """
    return minplus_pallas(a, b, bm=bm, bn=bn, bk=bk, interpret=interpret)


__all__ = ["minplus", "minplus_ref"]
