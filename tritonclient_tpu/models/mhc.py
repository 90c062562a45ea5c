"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880; after
hyper-connections, arXiv:2409.19606): a residual state of ``n`` streams a
token, ``X [n, C]``, read and written whole around a sublayer ``F`` through
three maps computed from the token's own state:

    x~     = vec(X) / sqrt(mean(vec(X)^2) + rms_eps)         float32, no weight
    H_pre  = sigmoid(a_pre (x~ phi_pre) + b_pre)             [n]
    H_post = 2 sigmoid(a_post (x~ phi_post) + b_post)        [n]
    M^0    = exp(clip(a_res mat(x~ phi_res) + b_res, lo, hi))    [n, n]
    M^t    = rownorm(colnorm(M^(t-1))), norm(M) = M / (sum + eps);  H_res = M^iters
    h      = H_pre . X                  the sublayer's input, [C]
    X'     = H_res X + H_post^T F(h)    [n, C]

A sublayer's leaves (``init_maps``): ``phi [n C, 2 n + n^2]`` in the model's
type, its columns ``[pre | post | res]``, ``b [2 n + n^2]`` and ``alpha [3]``
in float32.

How it is laid out for the chip. The coefficients are ``2 n + n^2`` numbers
a token and are kept TOKEN-MINOR, ``[2 n + n^2, N]`` float32: a coefficient
is a vector over the tokens, nothing lies ``[N, 4, 4]`` with 4 of 128 lanes
used. From the projection's result they are made by ONE small Pallas kernel
a sublayer (``mhc_maps``: the sigmoids, ``exp(clip(.))`` and the Sinkhorn
iterations on sixteen ``(8, 128)`` tiles of 1,024 tokens, in registers):
written in ``jax.numpy`` the 20 iterations are either 40 launches a
sublayer (a traced loop; 640 a decode step of 8 layers) or, unrolled, a
chain the compiler's fusion pass duplicates into itself (each normalisation
feeds four divisions: 8 s to compile at 5 iterations, 50 s at 10, over 15
minutes at 20, for the described v5e). Off the TPU the kernel runs under
the Pallas interpreter, as ``ops/paged_attention.py`` does; there is no
second implementation. The streams' products ``H . X`` are plain
``jax.numpy``, ``n`` or ``n^2`` broadcast multiply-adds over ``[N, C]``
accumulated in float32 and stored in the streams' type: one pass over ``X``
each, no batched 4 x 4 matrix product. ``x~ phi`` is computed as ``(vec(X)
phi) * rsqrt(...)``: the scale is a scalar a token, so the streams go into
the product in their own type (a bfloat16 x bfloat16 product accumulated in
float32 is exact a term).
"""

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

_HI = lax.Precision.HIGHEST
_TILE = (8, 128)        # the tokens of one grid step: a float32 register


def n_coefficients(n: int) -> int:
    return 2 * n + n * n


def init_maps(key: jax.Array, layers: int, n: int, width: int,
              dtype) -> Dict:
    """Seeded leaves of one sublayer's maps, ``layers`` of them stacked:
    ``phi`` normal with standard deviation ``(n C)^-1/2`` (``x~`` has unit
    RMS, so every dynamic coefficient is about unit normal and all three
    maps vary by token), ``alpha`` 1, ``b_res`` = 2 I + 0.02 z (the mixing
    matrix leans to the identity, far from the clamp), ``b_pre`` and
    ``b_post`` 0.02 z."""
    k_phi, k_b = jax.random.split(key)
    phi = (jax.random.normal(k_phi, (layers, n * width, n_coefficients(n)),
                             jnp.float32) / np.sqrt(n * width)).astype(dtype)
    b = 0.02 * jax.random.normal(k_b, (layers, n_coefficients(n)),
                                 jnp.float32)
    b = b.at[:, 2 * n:].add(2.0 * jnp.eye(n, dtype=jnp.float32).reshape(-1))
    return {"phi": phi, "b": b,
            "alpha": jnp.ones((layers, 3), jnp.float32)}


def sinkhorn(m: List[List[jax.Array]], iters: int,
             eps: float) -> List[List[jax.Array]]:
    """``m[i][j]``: entry (row i, column j) of a positive matrix a token,
    each an array of one shape. ``iters`` times: every column divided by its
    sum + eps, then every row by its sum + eps; the sums are explicit adds
    of the entries (elementwise over the tokens), the iterations a loop."""
    n = len(m)

    def one(_, flat):
        m = [list(flat[i * n:(i + 1) * n]) for i in range(n)]
        cols = [sum(m[i][j] for i in range(n)) + eps for j in range(n)]
        m = [[m[i][j] / cols[j] for j in range(n)] for i in range(n)]
        rows = [sum(m[i]) + eps for i in range(n)]
        return tuple(m[i][j] / rows[i] for i in range(n) for j in range(n))

    flat = lax.fori_loop(0, iters, one,
                         tuple(m[i][j] for i in range(n) for j in range(n)))
    return [list(flat[i * n:(i + 1) * n]) for i in range(n)]


def _maps_kernel(z_ref, out_ref, *, n: int, iters: int, eps: float,
                 clamp: Tuple[float, float]):
    """z_ref, out_ref [2n + n^2, 1, 8, 128]: the affine coefficients of
    1,024 tokens in, the maps' entries out (pre | post | res row-major)."""
    for j in range(n):
        out_ref[j, 0] = 1.0 / (1.0 + jnp.exp(-z_ref[j, 0]))
        out_ref[n + j, 0] = 2.0 / (1.0 + jnp.exp(-z_ref[n + j, 0]))
    lo, hi = clamp
    m = sinkhorn([[jnp.exp(jnp.clip(z_ref[2 * n + i * n + j, 0], lo, hi))
                   for j in range(n)] for i in range(n)], iters, eps)
    for i in range(n):
        for j in range(n):
            out_ref[2 * n + i * n + j, 0] = m[i][j]


def maps(x, hp: Dict, cfg) -> Tuple[list, list, list]:
    """x [N, n, C] -> (H_pre, H_post: lists of n vectors [N]; H_res: n lists
    of n vectors [N]), float32. ``cfg`` has ``hc_sinkhorn_iters``,
    ``hc_eps``, ``hc_res_clamp`` and ``rms_norm_eps``."""
    rows, n, width = x.shape
    k = n_coefficients(n)
    flat = x.reshape(rows, n * width)
    x32 = flat.astype(jnp.float32)
    inv_rms = lax.rsqrt(jnp.mean(x32 * x32, axis=-1) + cfg.rms_norm_eps)
    # [2n + n^2, N]: a coefficient is a vector over the tokens.
    raw = lax.dot_general(hp["phi"], flat, (((0,), (1,)), ((), ())),
                          precision=_HI,
                          preferred_element_type=jnp.float32) * inv_rms
    alpha = jnp.concatenate([jnp.full((count,), hp["alpha"][i])
                             for i, count in enumerate((n, n, n * n))])
    z = alpha[:, None] * raw + hp["b"][:, None]
    tile = _TILE[0] * _TILE[1]
    steps = -(-rows // tile)
    z = jnp.pad(z, ((0, 0), (0, steps * tile - rows)))
    block = pl.BlockSpec((k, 1) + _TILE, lambda g: (0, g, 0, 0))
    out = pl.pallas_call(
        functools.partial(_maps_kernel, n=n, iters=cfg.hc_sinkhorn_iters,
                          eps=cfg.hc_eps, clamp=cfg.hc_res_clamp),
        out_shape=jax.ShapeDtypeStruct((k, steps) + _TILE, jnp.float32),
        grid=(steps,), in_specs=[block], out_specs=block, name="mhc_maps",
        interpret=jax.default_backend() != "tpu",
    )(z.reshape((k, steps) + _TILE)).reshape(k, steps * tile)[:, :rows]
    return ([out[j] for j in range(n)], [out[n + j] for j in range(n)],
            [[out[2 * n + i * n + j] for j in range(n)] for i in range(n)])


def _mix(coefficients: List[jax.Array], streams: List[jax.Array]):
    """``sum_j c_j[:, None] * streams_j`` in float32."""
    return sum(c[:, None] * s for c, s in zip(coefficients, streams))


def pre(x, hp: Dict, cfg):
    """x [N, n, C] -> (h [N, C]: the sublayer's input, in x's type; the
    maps that ``post`` writes the streams back through)."""
    with jax.named_scope("mhc_pre"):
        h_pre, h_post, h_res = maps(x, hp, cfg)
        streams = [x[:, j].astype(jnp.float32) for j in range(x.shape[1])]
        return _mix(h_pre, streams).astype(x.dtype), (h_post, h_res)


def post(x, y, back):
    """``H_res X + H_post^T y``: x [N, n, C], y [N, C] -> [N, n, C]."""
    h_post, h_res = back
    with jax.named_scope("mhc_post"):
        streams = [x[:, j].astype(jnp.float32) for j in range(x.shape[1])]
        y32 = y.astype(jnp.float32)
        return jnp.stack(
            [_mix(h_res[i], streams) + h_post[i][:, None] * y32
             for i in range(len(streams))], axis=1).astype(x.dtype)
