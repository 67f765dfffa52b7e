"""Multi-layer pools for the paged-attention kernel tests.

The kernels read layer ``layer`` of the engine's whole pool
``[L, KV, NB, BS, lanes]`` in place; these helpers build a pool whose
other layers are NaN, so a kernel that reads any layer but its own
returns NaN.
"""
import jax.numpy as jnp
import pytest


def layered(pages, layers, layer):
    """A ``layers``-deep pool holding ``pages`` at ``layer`` and NaN in
    every other layer."""
    pool = jnp.full((layers,) + pages.shape, jnp.nan, pages.dtype)
    return pool.at[layer].set(pages)


def layer_cases(cases, deep):
    """Parameters ``(*case, layers, layer)``: each of ``cases`` on a
    one-layer pool, under the id it has always had, then ``deep`` at the
    first, a middle and the last layer of a three-layer pool."""
    def name(case):
        return "-".join(map(str, case))
    return ([pytest.param(*c, 1, 0, id=name(c)) for c in cases]
            + [pytest.param(*deep, 3, layer,
                            id=f"{name(deep)}-layer{layer}of3")
               for layer in range(3)])
