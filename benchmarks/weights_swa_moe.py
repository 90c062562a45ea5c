"""Seeded weights of the window/global grouped-query routed family, made by
the benchmark and handed to the program and to the reference alike
(``weights_mla_moe.py`` does the same for its family).

One jitted call on the device, straight in the served type. The tree's
layout is the program's parameter interface (``models/swa_moe.py:
init_params``): the expert matrices are the HELD experts' only (this chip's
share), the router's all of its outputs; the values are this file's own.
The large leaves are drawn one ``[rows, columns]`` slab at a time (``lax.map``
over the layer and expert axes), so that beside 12 GB of results the
generator's temporaries stay the size of one matrix.

Initialisation (``assumed`` in the configuration's file): every matrix
normal with standard deviation ``fan_in ** -0.5``, so a unit-RMS input gives
a unit-RMS output: the router's logits have standard deviation about 1
(sigmoid scores between 0.05 and 0.95, the top 8 of 128 decided by the
scores and not by ties), the attention scores about 1 (q and k are
normalised over the head, so ``q . k / sqrt(Dh)`` is), and so have the
output logits. Norm weights are 1 + 0.02 z so that a dropped weight shows;
``e_score_correction_bias`` is 0.02 z: the spacing of neighbouring scores
near the eighth place, so it moves a good part of the choices without
deciding them all.
"""

import functools

import jax
import jax.numpy as jnp

from benchmarks.costs_swa_moe import SwaMoeShape
from benchmarks.weights import seed_key
from benchmarks.weights_mla_moe import _normal


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key: jax.Array, s: SwaMoeShape) -> dict:
    d, dh = s.d_model, s.head_dim
    dtype = jnp.dtype(s.dtype)
    keys = iter(jax.random.split(key, 48))

    def dense(shape, fan_in):
        return _normal(next(keys), shape, fan_in ** -0.5, dtype)

    def near_one(shape):
        return 1 + jax.random.normal(next(keys), shape, dtype) * 0.02

    def attention(n):
        return {
            "norm1": near_one((n, d)),
            "wq": dense((n, d, s.n_head * dh), d),
            "wk": dense((n, d, s.kv_width), d),
            "wv": dense((n, d, s.kv_width), d),
            "q_norm": near_one((n, dh)),
            "k_norm": near_one((n, dh)),
            "wo": dense((n, s.n_head * dh, d), s.n_head * dh),
            "norm2": near_one((n, d)),
        }

    nd, nm, e = s.n_dense_layer, s.n_moe_layer, s.experts_held
    f, fe, fs = s.d_ff, s.d_expert, s.d_expert * s.n_shared_experts
    return {
        "embed": {"tok": dense((s.vocab_size, d), d)},
        "dense": dict(
            attention(nd),
            w_gate=dense((nd, d, f), d), w_up=dense((nd, d, f), d),
            w_down=dense((nd, f, d), f)),
        "moe": dict(
            attention(nm),
            router=dense((nm, d, s.n_experts), d),
            router_bias=0.02 * jax.random.normal(
                next(keys), (nm, s.n_experts), jnp.float32),
            w_gate=dense((nm, e, d, fe), d), w_up=dense((nm, e, d, fe), d),
            w_down=dense((nm, e, fe, d), fe),
            ws_gate=dense((nm, d, fs), d), ws_up=dense((nm, d, fs), d),
            ws_down=dense((nm, fs, d), fs)),
        "final_norm": near_one((d,)),
        "head": dense((d, s.vocab_size), d),
    }


def make_weights(seed: int, shape: SwaMoeShape) -> dict:
    return _make(seed_key(seed), shape)
