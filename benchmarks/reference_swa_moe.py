"""The plain reference of the window/global grouped-query routed family: the
forward pass in straightforward ``jax.numpy``, float32, every matrix product
at ``highest`` precision, no cache, no kernel, no batching, a dense mask for
the window, the experts as a plain loop over the HELD ones. It imports
nothing of the program.

The layer, per token ``x`` (RMSNorm everywhere, eps as configured, no bias):

    q = x W_q -> H x Dh;  k = x W_k, v = x W_v -> H_kv x Dh
    q, k normalised over Dh (weights q_norm, k_norm), then rotated:
        [a, b] -> [a cos - b sin, b cos + a sin], angle pos * theta^(-2i/Dh)
    query head i reads K/V head i // (H / H_kv)
    scores = q . k / sqrt(Dh); key j is seen by row i where j <= i, and in a
        window layer where also j > i - window
    attention out = softmax(scores) v, heads concatenated, W_o
    dense layers:  W_down(silu(x W_gate) * x W_up)
    expert layers: s = sigmoid(x W_g) over ALL the router's experts
                   (float32); top k of s + b; weights s[chosen] / sum *
                   routed_scaling_factor (over all k chosen, held or not);
                   y = sum over the HELD experts e of w_e SwiGLU_e(x)
                       + SwiGLU_shared(x)
    final norm, untied head.

The share: the configuration states which of the router's experts this chip
holds (``first_expert``, ``experts_held``); the weights hold those experts'
matrices only, and a chosen expert that another chip holds adds nothing
here, in this reference as in the program. Nothing stands in for the other
chips.

It decides ``correct`` as ``reference_mla_moe.py`` does: after the window a
sample of finished requests is run through it, one sequence a call, and for
every served token the gap by which its logit lies below the reference's
best is read: the largest, the 99th percentile and the mean. A sequence is
padded to the next power of two (causal: the padding is never attended by a
judged row; past 2,048 to the next multiple of 2,048), so a 400-token
request is not computed at the 13,824 of the longest. So that a 14k-token
sequence fits in the 3.8 GB a chip has beside 12 GB of resident bfloat16
weights, a layer first makes every position's keys and values and then
goes through its rows a block at a time (attention over all the keys, one
K/V head and a few hundred query rows at a time; the residual; the
feed-forward), and a matrix is upcast where it is used: one K/V head's
columns, one block of the dense layer's width, one expert. Only the hidden
states and one layer's keys and values are ever whole (the first version
upcast a layer at a time and asked the chip for 16.7 GB).

The control is the same pass with every matrix product's inputs rounded to
scaled float8 (e4m3); the router stays float32 there too (as float8
recipes keep it), so the control is the milder of the two possible and the
limit under it the stricter.
"""

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.costs_swa_moe import SwaMoeShape
from benchmarks.reference import _fp8, _matmul, pick_sample

_HI = lax.Precision.HIGHEST
_ROW_BLOCK = 1024       # rows a projection or feed-forward takes at once
_QUERY_BLOCK = 256      # query rows attention takes at once, a K/V head
_HEAD_BLOCKS = 8        # the vocabulary, in at most this many equal blocks
_FFN_BLOCKS = 6         # the dense layer's width, in at most this many blocks
_PAD_TO = 2048          # a sequence is computed at a multiple of this


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def _rope(x, positions, theta):
    """x [L, heads, Dh]: the halves [a, b] of row l rotated by
    positions[l] * theta ** (-2i / Dh)."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
    angle = positions.astype(jnp.float32)[:, None, None] * jnp.asarray(
        inv_freq, jnp.float32)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], -1)


def _by_rows(fn, x, block: int):
    """``fn`` over ``x`` [L, ...] a block of rows at a time."""
    block = min(block, x.shape[0])
    out = lax.map(fn, x.reshape((-1, block) + x.shape[1:]))
    return out.reshape((x.shape[0],) + out.shape[2:])


def _swiglu(x, w_gate, w_up, w_down, low):
    hidden = jax.nn.silu(_matmul(x, w_gate, low)) * _matmul(x, w_up, low)
    return _matmul(hidden, w_down, low)


def _keys_and_values(a, lp, s: SwaMoeShape, low: bool):
    """The normed rows ``a`` [L, d] -> every position's keys (normalised
    and rotated) and values, [H_kv, L, Dh] each: what a later row attends."""
    l = a.shape[0]
    f32 = lambda w: w.astype(jnp.float32)  # noqa: E731
    k = _by_rows(lambda r: _matmul(r, f32(lp["wk"]), low), a, _ROW_BLOCK)
    v = _by_rows(lambda r: _matmul(r, f32(lp["wv"]), low), a, _ROW_BLOCK)
    k = _rope(_rms_norm(k.reshape(l, s.n_kv_head, s.head_dim), lp["k_norm"],
                        s.rms_norm_eps), jnp.arange(l), s.rope_theta)
    v = v.reshape(l, s.n_kv_head, s.head_dim)
    if low:
        k, v = _fp8(k, -1), _fp8(v, 0)
    return k.transpose(1, 0, 2), v.transpose(1, 0, 2)


def _attention(a, rows, keys, values, lp, s: SwaMoeShape, in_window,
               low: bool):
    """Attention of the normed rows ``a`` [B, d] at positions ``rows`` [B]
    over ``keys`` / ``values`` [H_kv, L, Dh]: one K/V head at a time, its
    ``group`` query heads with it (their columns of W_q and rows of W_o, in
    the served type, upcast a head at a time), ``_QUERY_BLOCK`` rows at a
    time, under a dense mask of the layer's kind (``in_window``: whether
    the layer is a window layer)."""
    b, d = a.shape
    hk, dh = s.n_kv_head, s.head_dim
    group = s.n_head // hk
    positions = jnp.arange(keys.shape[1])
    block = min(_QUERY_BLOCK, b)
    f32 = lambda w: w.astype(jnp.float32)  # noqa: E731

    def one_kv_head(y, w):
        wq, wo, kh, vh = w              # [d, group * Dh], ..., [L, Dh]
        qh = _matmul(a, f32(wq), low).reshape(b, group, dh)
        qh = _rope(_rms_norm(qh, lp["q_norm"], s.rms_norm_eps), rows,
                   s.rope_theta)
        if low:
            qh = _fp8(qh, -1)

        def one_block(args):
            qb, at = args                # [block, group, Dh], [block]
            scores = jnp.einsum("qgd,kd->gqk", qb, kh,
                                precision=_HI) / np.sqrt(dh)
            seen = positions[None, :] <= at[:, None]
            seen &= ~in_window | (positions[None, :] > at[:, None] - s.window)
            probs = jax.nn.softmax(
                jnp.where(seen[None], scores, -jnp.inf), axis=-1)
            if low:
                probs = _fp8(probs, -1)
            return jnp.einsum("gqk,kd->qgd", probs, vh, precision=_HI)

        out = lax.map(one_block, (qh.reshape(-1, block, group, dh),
                                  rows.reshape(-1, block)))
        return y + _matmul(out.reshape(b, group * dh), f32(wo), low), None

    y, _ = lax.scan(one_kv_head, jnp.zeros_like(a), (
        lp["wq"].reshape(d, hk, group * dh).transpose(1, 0, 2),
        lp["wo"].reshape(hk, group * dh, d), keys, values))
    return y


def _dense_ffn(x, lp, s: SwaMoeShape, low: bool):
    """The dense layer's feed-forward, a block of its width at a time (the
    matrices in the served type, upcast a block at a time)."""
    d, width = lp["w_gate"].shape
    blocks = next(b for b in range(_FFN_BLOCKS, 0, -1) if width % b == 0)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731

    def columns(w):                     # [d, width] -> [blocks, d, width / b]
        return w.reshape(d, blocks, -1).transpose(1, 0, 2)

    def one(y, w):
        w_gate, w_up, w_down = w
        return y + _swiglu(x, f32(w_gate), f32(w_up), f32(w_down), low), None

    y, _ = lax.scan(one, jnp.zeros_like(x), (
        columns(lp["w_gate"]), columns(lp["w_up"]),
        lp["w_down"].reshape(blocks, -1, d)))
    return y


def _routed(x, lp, at, s: SwaMoeShape, low: bool):
    """The expert layer's routed part: the held experts one after another,
    each computing every row, its result weighed by the row's weight for it
    (0 where the row did not choose it). The choice and the weights are
    over all the router's experts. The experts' matrices come as the whole
    stack ``[layers, held, ...]`` in the served type, ``at`` the layer's
    place in it: one expert's are upcast at a time."""
    scores = jax.nn.sigmoid(jnp.matmul(
        x, lp["router"].astype(jnp.float32), precision=_HI))
    _, chosen = lax.top_k(scores + lp["router_bias"].astype(jnp.float32),
                          s.experts_per_token)
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = weight / weight.sum(-1, keepdims=True) * s.routed_scaling_factor
    per_expert = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(weight)    # [L, E]

    def one(e, y):
        f32 = lambda a: a[at, e].astype(jnp.float32)  # noqa: E731
        return y + per_expert[:, s.first_expert + e, None] * _swiglu(
            x, f32(lp["w_gate"]), f32(lp["w_up"]), f32(lp["w_down"]), low)

    return lax.fori_loop(0, s.experts_held, one, jnp.zeros_like(x))


def _layer(x, lp, in_window, feed_forward, s: SwaMoeShape, low: bool):
    """One layer over the whole sequence ``x`` [L, d]: first every
    position's keys and values, then the rows a block at a time: attention
    over all the keys, the residual, ``feed_forward`` of the normed rows."""
    l, d = x.shape
    block = min(_ROW_BLOCK, l)
    keys, values = _keys_and_values(
        _rms_norm(x, lp["norm1"], s.rms_norm_eps), lp, s, low)

    def rows_of_the_layer(args):
        xb, rows = args
        xb = xb + _attention(_rms_norm(xb, lp["norm1"], s.rms_norm_eps),
                             rows, keys, values, lp, s, in_window, low)
        return xb + feed_forward(_rms_norm(xb, lp["norm2"], s.rms_norm_eps))

    return lax.map(rows_of_the_layer, (
        x.reshape(-1, block, d), jnp.arange(l).reshape(-1, block))
    ).reshape(l, d)


def _hidden(weights: Dict, tokens: jax.Array, s: SwaMoeShape,
            low: bool) -> jax.Array:
    """tokens [L] int32 -> the final-normed hidden states [L, d] float32.

    The dense layers, then the expert layers, one after another (two plain
    loops over the stacked layers: whether a layer is a window layer is its
    mask's data, so one body serves both kinds). Only the hidden states and
    one layer's keys and values are ever whole. The matrices stay in the
    served type and are upcast where they are used; vectors are upcast
    here."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    experts = ("w_gate", "w_up", "w_down")
    in_window = jnp.asarray([k == "window" for k in s.layer_kinds])
    nd = s.n_dense_layer

    def vectors_upcast(lp):
        return {k: f32(v) if v.ndim == 1 else v for k, v in lp.items()}

    def dense_layer(x, xs):
        lp, window = vectors_upcast(xs[0]), xs[1]
        return _layer(x, lp, window, lambda m: _dense_ffn(m, lp, s, low), s,
                      low), None

    def expert_layer(x, xs):
        lp, window, at = vectors_upcast(xs[0]), xs[1], xs[2]
        # The routed experts' matrices stay stacked for their loop.
        lp.update({k: weights["moe"][k] for k in experts})

        def feed_forward(m):
            return (_routed(m, lp, at, s, low)
                    + _swiglu(m, f32(lp["ws_gate"]), f32(lp["ws_up"]),
                              f32(lp["ws_down"]), low))

        return _layer(x, lp, window, feed_forward, s, low), None

    x = f32(weights["embed"]["tok"][tokens])
    x, _ = lax.scan(dense_layer, x, (weights["dense"], in_window[:nd]))
    x, _ = lax.scan(expert_layer, x, (
        {k: v for k, v in weights["moe"].items() if k not in experts},
        in_window[nd:], jnp.arange(s.n_moe_layer)))
    return _rms_norm(x, f32(weights["final_norm"]), s.rms_norm_eps)


def logits(weights: Dict, tokens, s: SwaMoeShape, low: bool = False):
    """tokens [L] -> logits [L, vocab] float32: the whole pass at once, for
    tests at small sizes."""
    return _matmul(_hidden(weights, jnp.asarray(tokens, jnp.int32), s, low),
                   weights["head"].astype(jnp.float32), low)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _read(weights, tokens, rows, probe, s: SwaMoeShape, low: bool):
    """At the positions ``rows`` [R] of the sequence ``tokens`` [L]: (best
    logit, logit of ``probe`` [R], first-placed token), each [R]. The head
    runs over the vocabulary in blocks, on those rows alone."""
    x = _hidden(weights, tokens, s, low)[rows]
    blocks = next(b for b in range(_HEAD_BLOCKS, 0, -1)
                  if s.vocab_size % b == 0)
    block = s.vocab_size // blocks

    def one(carry, first):
        best, at, top = carry
        head = lax.dynamic_slice_in_dim(weights["head"], first, block, axis=1)
        out = _matmul(x, head.astype(jnp.float32), low)        # [R, block]
        here = out.max(-1)
        top = jnp.where(here > best, first + jnp.argmax(out, -1), top)
        inside = (probe >= first) & (probe < first + block)
        at = jnp.where(inside, jnp.take_along_axis(
            out, jnp.clip(probe - first, 0, block - 1)[:, None],
            axis=-1)[:, 0], at)
        return (jnp.maximum(best, here), at, top), None

    r = rows.shape[0]
    (best, at, top), _ = lax.scan(
        one, (jnp.full((r,), -jnp.inf), jnp.full((r,), -jnp.inf),
              jnp.zeros((r,), jnp.int32)),
        jnp.arange(blocks) * block)
    return best, at, top.astype(jnp.int32)


def padded_length(n: int) -> int:
    """The sequence length a sample of ``n`` input tokens is computed at:
    the next multiple of ``_PAD_TO``; below it the next power of two, 32 at
    least (the blocks of rows divide either)."""
    if n > _PAD_TO:
        return -(-n // _PAD_TO) * _PAD_TO
    return max(32, 1 << (max(n, 1) - 1).bit_length())


def served_gaps(weights: Dict, s: SwaMoeShape, samples: Sequence[dict],
                length: Optional[int] = None,
                control: bool = False) -> List[np.ndarray]:
    """For each sample (``prompt`` [L] and ``tokens`` served after it), the
    gap of every served token below the reference's best logit there; one
    sequence a call, padded to ``padded_length`` of its own (``length``, the
    mix's longest request, is only what none may exceed). With ``control``
    the tokens judged are those the float8 pass puts first at the same
    positions."""
    judged = max((len(np.asarray(g["tokens"]).reshape(-1)) for g in samples),
                 default=0)
    judged = -(-max(judged, 1) // 32) * 32      # one shape for every call
    gaps: List[Optional[np.ndarray]] = []
    for sample in samples:
        prompt = np.asarray(sample["prompt"], np.int32).reshape(-1)
        served = np.asarray(sample["tokens"], np.int32).reshape(-1)
        seq = np.concatenate([prompt, served])
        if length is not None and len(seq) - 1 > length:
            raise ValueError(f"sample of {len(seq)} tokens exceeds the "
                             f"reference length {length}")
        tokens = np.zeros((padded_length(len(seq) - 1),), np.int32)
        tokens[:len(seq) - 1] = seq[:-1]
        # Position i predicts seq[i + 1]: the served tokens are predicted at
        # len(prompt) - 1 ... len(seq) - 2.
        rows = np.full((judged,), len(prompt) - 1, np.int32)
        probe = np.full((judged,), served[0], np.int32)
        rows[:len(served)] = np.arange(len(prompt) - 1, len(seq) - 1)
        probe[:len(served)] = served
        tokens_d, rows_d, probe_d = map(jnp.asarray, (tokens, rows, probe))
        if control:
            probe_d = _read(weights, tokens_d, rows_d, probe_d, s, True)[2]
        best, at, _ = _read(weights, tokens_d, rows_d, probe_d, s, False)
        gaps.append(np.asarray(best - at)[:len(served)])
    return gaps


def check_outputs(cell, shape: SwaMoeShape, weights: dict, obs,
                  seed: int) -> Dict[str, dict]:
    """Each number compared, beside its limit: ``reference_mla_moe.
    check_outputs`` with this family's reference."""
    from benchmarks.reference import pad_length

    settings = cell.config["check"]
    sample = pick_sample(obs.logs, int(settings["sample_requests"]), seed)
    gaps = served_gaps(
        weights, shape,
        [{"prompt": g.request.prompt[0], "tokens": g.tokens} for g in sample],
        pad_length(cell.traffic))
    every = np.concatenate([np.asarray(g, np.float64) for g in gaps]
                           or [np.zeros(0)])
    if every.size:
        readings = {"served_logit_gap_max": float(every.max()),
                    "served_logit_gap_p99": float(np.percentile(every, 99)),
                    "served_logit_gap_mean": float(every.mean())}
    else:
        readings = dict.fromkeys(
            ("served_logit_gap_max", "served_logit_gap_p99",
             "served_logit_gap_mean"), float("inf"))
    check = {}
    for name, value in readings.items():
        limit = settings.get(name + "_limit")
        if limit is not None:           # a number without a limit is not compared
            check[name] = {"value": value, "limit": float(limit)}
    check["failed_requests"] = {"value": len(obs.failed()), "limit": 0}
    check["checked_tokens"] = {
        "value": int(every.size),
        "at_least": int(settings["min_checked_tokens"])}
    return check
