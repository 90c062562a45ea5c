"""Seeded weights, made by the benchmark and handed to the program and to the
reference alike: neither takes anything the other has made.

One jitted call on the device, straight in the served type (bfloat16): no
float32 staging copy, so the process's memory peak is the serving peak and
not the initialiser's. The tree's layout is the program's parameter
interface (``models/gpt.py:init_params``); the values are this file's own.
Biases and layer-norm parameters are random too (the program's own
initialiser leaves them at 0 and 1, which would hide a dropped bias).
"""

import functools

import jax
import jax.numpy as jnp

from benchmarks.costs import GptShape


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number; the driver's seeds pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key: jax.Array, s: GptShape) -> dict:
    d, f, n = s.d_model, s.d_ff, s.n_layer
    dtype = jnp.bfloat16
    keys = iter(jax.random.split(key, 20))

    def dense(shape, fan_in):
        return jax.random.normal(next(keys), shape, dtype) * (fan_in ** -0.5)

    def small(shape):
        return jax.random.normal(next(keys), shape, dtype) * 0.02

    def near_one(shape):
        return 1 + small(shape)

    return {
        "embed": {"tok": dense((s.vocab_size, d), d),
                  "pos": dense((s.n_positions, d), d)},
        "layers": {
            "wqkv": dense((n, d, 3 * d), d), "bqkv": small((n, 3 * d)),
            "wo": dense((n, d, d), d), "bo": small((n, d)),
            "ln1_scale": near_one((n, d)), "ln1_bias": small((n, d)),
            "w_in": dense((n, d, f), d), "b_in": small((n, f)),
            "w_out": dense((n, f, d), f), "b_out": small((n, d)),
            "ln2_scale": near_one((n, d)), "ln2_bias": small((n, d)),
        },
        "final_ln": {"scale": near_one((d,)), "bias": small((d,))},
    }


def make_weights(seed: int, shape: GptShape) -> dict:
    return _make(seed_key(seed), shape)
