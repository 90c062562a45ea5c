"""Seeded weights of the MLA / routed-expert family with a multi-stream
residual path, made by the benchmark and handed to the program and to the
reference alike: ``weights_mla_moe.py``'s tree (the same generator, the same
initialisation, said there) and, in ``dense`` and in ``moe``, the leaves of
each layer's two sets of maps, ``hc_att`` and ``hc_ffn`` (the layout is the
program's parameter interface, ``models/mla_moe.py:init_params``; the values
are this file's own).

The maps' initialisation (``assumed`` in the configuration's file), chosen so
that all three maps VARY BY TOKEN and stay clear of the clamp, since a seeded
model whose maps were constants would measure a static mix and check nothing
of the dynamic part: ``phi`` [n C, 2n + n^2] normal with standard deviation
``(n C) ** -0.5`` in the served type (``x~`` has unit RMS, so each dynamic
coefficient is about unit normal); ``alpha`` = 1; ``b_pre``, ``b_post`` =
0.02 z (``H_pre`` = sigmoid of a unit normal, 0.27-0.73; ``H_post`` twice
that); ``b_res`` = 2 I + 0.02 z, so ``exp`` of the entries is about e^2 on
the diagonal and e^0 off it, every entry within e^+-5 of 1 and nowhere near
the clamp at +-30, and Sinkhorn's matrix leans to the identity (diagonal
about 0.6) while its off-diagonal entries move by a factor of e from token
to token. ``b`` and ``alpha`` are float32, as the router's bias is.
"""

import functools

import jax
import jax.numpy as jnp

from benchmarks import weights_mla_moe
from benchmarks.costs_mhc_mla_moe import MhcMlaMoeShape
from benchmarks.weights import seed_key

_SUBLAYERS = ("hc_att", "hc_ffn")


@functools.partial(jax.jit, static_argnums=(1,))
def _make_maps(key: jax.Array, s: MhcMlaMoeShape) -> dict:
    n, width, dtype = s.hc_mult, s.stream_width, jnp.dtype(s.dtype)
    out = {}
    for stack, layers in (("dense", s.n_dense_layer), ("moe", s.n_moe_layer)):
        out[stack] = {}
        for sub in _SUBLAYERS:
            key, k_phi, k_b = jax.random.split(key, 3)
            phi = (jax.random.normal(
                k_phi, (layers, width, s.hc_coefficients), jnp.float32)
                * width ** -0.5).astype(dtype)
            b = 0.02 * jax.random.normal(
                k_b, (layers, s.hc_coefficients), jnp.float32)
            b = b.at[:, 2 * n:].add(2.0 * jnp.eye(n).reshape(-1))
            out[stack][sub] = {"phi": phi, "b": b,
                               "alpha": jnp.ones((layers, 3), jnp.float32)}
    return out


def make_weights(seed: int, shape: MhcMlaMoeShape) -> dict:
    weights = weights_mla_moe.make_weights(seed, shape)
    if shape.hc_mult > 1:
        maps = _make_maps(jax.random.fold_in(seed_key(seed), 1), shape)
        for stack, leaves in maps.items():
            weights[stack].update(leaves)
    return weights
