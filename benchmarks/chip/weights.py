"""Weights of a cell, made from the seed on the device in one call.

Every hospital's segment is drawn from its own key; the server's segment
is hospital 0's draw, as SplitFed starts it.  The program and the plain
reference both start from these arrays.
"""

from __future__ import annotations

import jax


def key_of(seed: int, stream: int):
    from chip.gen import seed_rng
    return jax.random.key(int(seed_rng(seed, stream).integers(2**31)))


def make(family, model: dict, seed: int, n_clients: int):
    """``(fronts, server)``: hospital segments stacked on a leading axis
    of ``n_clients``, and the server's segment."""
    @jax.jit
    def build(key):
        keys = jax.random.split(key, n_clients)
        fronts = jax.vmap(lambda k: family.init(k, model)["front"])(keys)
        server = family.init(keys[0], model)["middle"]
        return fronts, server

    return build(key_of(seed, 3))
