"""Seeded weights of the MLA / routed-expert family, made by the benchmark
and handed to the program and to the reference alike (``weights.py`` does
the same for the GPT family).

One jitted call on the device, straight in the served type (bfloat16 for
every cell; a test-only configuration may say float32). The
tree's layout is the program's parameter interface
(``models/mla_moe.py:init_params``); the values are this file's own. The
large leaves are drawn one ``[rows, columns]`` slab at a time (``lax.map``
over the layer and expert axes), so that beside 11 GB of results the
generator's temporaries stay the size of one expert's matrix.

Initialisation (``assumed`` in the configuration's file): every matrix
normal with standard deviation ``fan_in ** -0.5``, so a unit-RMS input gives
a unit-RMS output: the router's logits have standard deviation about 1
(sigmoid scores between 0.05 and 0.95, far from saturation, the top 8 of
256 decided by the scores and not by ties) and so have the output logits
(the best of 129280 about 4.5 above the mean, the runner-up a tenth or two
behind: a gap that bfloat16 holds and float8 does not). Norm weights are
1 + 0.02 z so that a dropped weight shows; ``e_score_correction_bias`` is
0.02 z: the spacing of neighbouring scores near the eighth place, so it
moves a good part of the choices without deciding them all.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.costs_mla_moe import MlaMoeShape
from benchmarks.weights import seed_key

def _normal(key, shape, scale, dtype):
    """Normal * scale in ``dtype``, the leading axes one slab at a time."""
    slabs = math.prod(shape[:-2])
    draw = lambda k: jax.random.normal(k, shape[-2:], dtype) * scale  # noqa: E731
    if slabs == 1:
        return draw(key).reshape(shape)
    return lax.map(draw, jax.random.split(key, slabs)).reshape(shape)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key: jax.Array, s: MlaMoeShape) -> dict:
    d, h = s.d_model, s.n_head
    dtype = jnp.dtype(s.dtype)
    keys = iter(jax.random.split(key, 48))

    def dense(shape, fan_in):
        return _normal(next(keys), shape, fan_in ** -0.5, dtype)

    def near_one(shape):
        return 1 + jax.random.normal(next(keys), shape, dtype) * 0.02

    def attention(n):
        return {
            "norm1": near_one((n, d)),
            "wq_a": dense((n, d, s.q_lora_rank), d),
            "q_norm": near_one((n, s.q_lora_rank)),
            "wq_b": dense((n, s.q_lora_rank, h * s.qk_head_dim),
                          s.q_lora_rank),
            "wkv_a": dense((n, d, s.latent_dim), d),
            "kv_norm": near_one((n, s.kv_lora_rank)),
            "wkv_b": dense((n, s.kv_lora_rank,
                            h * (s.qk_nope_head_dim + s.v_head_dim)),
                           s.kv_lora_rank),
            "wo": dense((n, h * s.v_head_dim, d), h * s.v_head_dim),
            "norm2": near_one((n, d)),
        }

    nd, nm, e = s.n_dense_layer, s.n_moe_layer, s.n_experts
    f, fe, fs = s.d_ff, s.d_expert, s.d_expert * s.n_shared_experts
    return {
        "embed": {"tok": dense((s.vocab_size, d), d)},
        "dense": dict(
            attention(nd),
            w_gate=dense((nd, d, f), d), w_up=dense((nd, d, f), d),
            w_down=dense((nd, f, d), f)),
        "moe": dict(
            attention(nm),
            router=dense((nm, d, e), d),
            router_bias=0.02 * jax.random.normal(next(keys), (nm, e),
                                                 jnp.float32),
            w_gate=dense((nm, e, d, fe), d), w_up=dense((nm, e, d, fe), d),
            w_down=dense((nm, e, fe, d), fe),
            ws_gate=dense((nm, d, fs), d), ws_up=dense((nm, d, fs), d),
            ws_down=dense((nm, fs, d), fs)),
        "final_norm": near_one((d,)),
        "head": dense((d, s.vocab_size), d),
    }


def make_weights(seed: int, shape: MlaMoeShape) -> dict:
    return _make(seed_key(seed), shape)
