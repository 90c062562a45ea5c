"""The plain reference of the MLA / routed-expert family with a multi-stream
residual path (mHC) and YaRN-scaled rotary positions: the forward pass in
straightforward ``jax.numpy``, float32, every matrix product at ``highest``
precision, attention EXPANDED (per-head keys and values made from the
latent), no cache, no batching, the experts as a plain loop. It imports
nothing of the program.

The residual state of a token is ``X [n, C]`` (``n`` = ``hc_mult``), every
stream the token's embedding at the start. Each layer has two sublayers,
``F_att(h) = MLA(RMSNorm_1(h))`` and ``F_ffn(h) = FFN(RMSNorm_2(h))`` (dense
SwiGLU in the leading ``first_k_dense_replace`` layers; routed + shared
experts after), each wrapped by maps of its own (``phi [n C, 2n + n^2]`` in
three column blocks pre | post | res, ``b`` alike, ``alpha [3]``):

    x~     = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)     no learned scale
    H_pre  = sigmoid(a_pre (x~ phi_pre) + b_pre)              [n]
    H_post = 2 sigmoid(a_post (x~ phi_post) + b_post)         [n]
    M^0    = exp(clip(a_res mat(x~ phi_res) + b_res, -30, 30))   [n, n], row-major
    M^t    = rownorm(colnorm(M^(t-1))), t = 1..20, norm(M) = M / (sum + hc_eps)
    h      = H_pre . X;    X' = H_res X + H_post^T F(h)

At the end ``sum_j X_j`` goes through the final RMSNorm and the untied head.

MLA, per token ``x`` (RMSNorm everywhere, weights as given):

    c_q = norm(x W_qa);  q = c_q W_qb -> H x (nope + rope)
    x W_kva -> c_kv = norm(first kv_lora_rank), k_r = RoPE(last rope)
    [k_nope, v] = c_kv W_kvb -> H x (nope + v);  q_r = RoPE(q's rope part)
    scores = (q_nope . k_nope + q_r . k_r) * (nope + rope)^-1/2 * m^2, causal
    m = 0.1 mscale_all_dim ln(factor) + 1   (YaRN; 1 without scaling)

RoPE rotates the interleaved pairs (2i, 2i + 1) by ``pos * f_i``, with
``f_i = (1 - k_i) theta^(-2i/rope) / factor + k_i theta^(-2i/rope)``,
``k_i = 1 - clip((i - low) / (high - low), 0, 1)``, ``low`` / ``high`` the
floor / ceiling of ``rope ln(original / (beta 2 pi)) / (2 ln theta)`` at
``beta_fast`` / ``beta_slow``; cos and sin are multiplied by
``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``. The expert layer
is ``reference_mla_moe.py``'s: sigmoid scores in float32, the top k of ``s +
b``, weights ``s`` over the chosen's sum times ``routed_scaling_factor``.
Departures from the published descriptions, noted: the de-interleaved
half-split rotation of the family's inference code is the same scores (q_r
and k_r are permuted alike); what the config's keys leave open about the
maps is listed under ``assumed`` in the configuration's file.

It decides ``correct`` as ``reference_mla_moe.py`` does (the served-logit
gaps of a sample of finished requests: largest, 99th percentile, mean). So
that a 6.7k-token sequence fits in the 3.7 GB a chip has beside 12 GB of
resident bfloat16 weights, a sequence is padded to a power of two (past
2,048 to a multiple of 2,048) of its own, a sublayer goes through its rows a
block at a time (the maps, the collapse, the sublayer, the mix), attention
first making every position's keys and values, and a matrix is upcast where
it is used: one layer's attention, one block of the dense layer's width, one
expert, one block of the vocabulary. Only the streams, one layer's keys and
values and a block's scores are ever whole.

The control is the same pass with every matrix product's inputs rounded to
scaled float8 (e4m3); the router and the maps' coefficients stay float32
there too (the configuration states both so, and float8 recipes keep such
small projections in higher precision), so the control is the milder of the
possible ones and the limit under it the stricter.
"""

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.costs_mhc_mla_moe import MhcMlaMoeShape
from benchmarks.reference import _fp8, _matmul, pick_sample
from benchmarks.reference_swa_moe import padded_length

_HI = lax.Precision.HIGHEST
_ROW_BLOCK = 1024       # rows the maps, a mix or a feed-forward take at once
_QUERY_BLOCK = 256      # query rows attention takes at once
_HEAD_BLOCKS = 8        # the vocabulary, in at most this many equal blocks
_FFN_BLOCKS = 6         # the dense layer's width, in at most this many blocks
_UPCAST_WHERE_USED = ("w_gate", "w_up", "w_down", "ws_gate", "ws_up",
                      "ws_down", "router", "router_bias", "hc_att", "hc_ffn")


def _f32(a):
    return a.astype(jnp.float32)


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * np.log(factor) + 1.0


def rope_frequencies(s: MhcMlaMoeShape) -> np.ndarray:
    """The rotation frequency of each of the ``rope / 2`` pairs (float64)."""
    rope = s.qk_rope_head_dim
    plain = s.rope_theta ** (-np.arange(0, rope, 2, dtype=np.float64) / rope)
    if s.yarn_factor <= 1:
        return plain

    def pair(beta):
        return rope * np.log(s.yarn_original_positions / (beta * 2 * np.pi)) / (
            2 * np.log(s.rope_theta))

    low = max(np.floor(pair(s.yarn_beta_fast)), 0)
    high = min(np.ceil(pair(s.yarn_beta_slow)), rope - 1)
    kept = 1 - np.clip((np.arange(rope // 2) - low) / max(high - low, 1e-3),
                       0, 1)
    return (1 - kept) * plain / s.yarn_factor + kept * plain


def softmax_scale(s: MhcMlaMoeShape) -> float:
    scale = (s.qk_nope_head_dim + s.qk_rope_head_dim) ** -0.5
    if s.yarn_factor > 1 and s.yarn_mscale_all_dim:
        scale *= _mscale(s.yarn_factor, s.yarn_mscale_all_dim) ** 2
    return scale


def _rope(x, positions, s: MhcMlaMoeShape):
    """x [L, ..., rope]; rotates pair (2i, 2i + 1) of row l by
    positions[l] * f_i."""
    angle = positions.astype(jnp.float32).reshape(
        (-1,) + (1,) * (x.ndim - 1)) * jnp.asarray(rope_frequencies(s),
                                                   jnp.float32)
    magnitude = 1.0
    if s.yarn_factor > 1:
        magnitude = (_mscale(s.yarn_factor, s.yarn_mscale)
                     / _mscale(s.yarn_factor, s.yarn_mscale_all_dim))
    cos, sin = jnp.cos(angle) * magnitude, jnp.sin(angle) * magnitude
    even, odd = x[..., 0::2], x[..., 1::2]
    rotated = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return rotated.reshape(x.shape)


def _by_rows(fn, args, block: int):
    """``fn`` over the arrays of ``args`` (a tuple, each [L, ...]) a block of
    rows at a time; ``fn``'s result (an array or a tuple of them) whole."""
    rows = args[0].shape[0]
    block = min(block, rows)
    out = lax.map(fn, tuple(a.reshape((-1, block) + a.shape[1:])
                            for a in args))
    return jax.tree.map(lambda o: o.reshape((rows,) + o.shape[2:]), out)


def _swiglu(x, w_gate, w_up, w_down, low):
    hidden = jax.nn.silu(_matmul(x, w_gate, low)) * _matmul(x, w_up, low)
    return _matmul(hidden, w_down, low)


# --------------------------------------------------------------------------- #
# the maps                                                                    #
# --------------------------------------------------------------------------- #


def sinkhorn(m, iters: int, eps: float):
    """m [..., n, n] positive: ``iters`` times, every column divided by its
    sum + eps, then every row by its sum + eps."""
    for _ in range(iters):
        m = m / (m.sum(-2, keepdims=True) + eps)
        m = m / (m.sum(-1, keepdims=True) + eps)
    return m


def maps(x, hp, s: MhcMlaMoeShape):
    """x [B, n, C] float32 -> (H_pre [B, n], H_post [B, n], H_res [B, n, n])."""
    n = s.hc_mult
    flat = x.reshape(x.shape[0], -1)
    unit = flat / jnp.sqrt((flat * flat).mean(-1, keepdims=True)
                           + s.rms_norm_eps)
    raw = jnp.matmul(unit, _f32(hp["phi"]), precision=_HI)
    alpha, b = _f32(hp["alpha"]), _f32(hp["b"])
    pre = jax.nn.sigmoid(alpha[0] * raw[:, :n] + b[:n])
    post = 2 * jax.nn.sigmoid(alpha[1] * raw[:, n:2 * n] + b[n:2 * n])
    res = (alpha[2] * raw[:, 2 * n:] + b[2 * n:]).reshape(-1, n, n)
    res = jnp.exp(jnp.clip(res, s.hc_res_clamp_min, s.hc_res_clamp_max))
    return pre, post, sinkhorn(res, s.hc_sinkhorn_iters, s.hc_eps)


def _wrapped(x, hp, sublayer, s: MhcMlaMoeShape):
    """One sublayer around the streams x [L, n, C]: the maps and the
    collapse a block of rows at a time, ``sublayer`` on all the collapsed
    rows [L, C] (it blocks its own work), the mix a block at a time."""
    def collapse(args):
        (xb,) = args
        pre, post, res = maps(xb, hp, s)
        return jnp.einsum("bj,bjc->bc", pre, xb, precision=_HI), post, res

    h, post, res = _by_rows(collapse, (x,), _ROW_BLOCK)
    y = sublayer(h)

    def mix(args):
        xb, yb, post_b, res_b = args
        return (jnp.einsum("bij,bjc->bic", res_b, xb, precision=_HI)
                + post_b[:, :, None] * yb[:, None, :])

    return _by_rows(mix, (x, y, post, res), _ROW_BLOCK)


# --------------------------------------------------------------------------- #
# the sublayers                                                               #
# --------------------------------------------------------------------------- #


def _attention(a, lp, s: MhcMlaMoeShape, low: bool):
    """a [L, C] (normed) -> attention's output [L, C]: every position's keys
    and values first, then the query rows a block at a time."""
    l = a.shape[0]
    h, dn, dr, dv = (s.n_head, s.qk_nope_head_dim, s.qk_rope_head_dim,
                     s.v_head_dim)
    dc = s.kv_lora_rank
    positions = jnp.arange(l)
    kv_a = _matmul(a, lp["wkv_a"], low)
    c_kv = _rms_norm(kv_a[:, :dc], lp["kv_norm"], s.rms_norm_eps)
    k_r = _rope(kv_a[:, dc:], positions, s)                      # [L, dr]
    kv = _matmul(c_kv, lp["wkv_b"], low).reshape(l, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    if low:
        k_nope, k_r, v = _fp8(k_nope, -1), _fp8(k_r, -1), _fp8(v, 0)
    scale = softmax_scale(s)

    def rows_of(args):
        ab, at = args                       # [B, C], [B]
        c_q = _rms_norm(_matmul(ab, lp["wq_a"], low), lp["q_norm"],
                        s.rms_norm_eps)
        q = _matmul(c_q, lp["wq_b"], low).reshape(-1, h, dn + dr)
        q_nope, q_r = q[..., :dn], _rope(q[..., dn:], at, s)
        if low:
            q_nope, q_r = _fp8(q_nope, -1), _fp8(q_r, -1)
        scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope, precision=_HI)
                  + jnp.einsum("qhd,kd->hqk", q_r, k_r, precision=_HI))
        seen = positions[None, :] <= at[:, None]
        probs = jax.nn.softmax(
            jnp.where(seen[None], scores * scale, -jnp.inf), axis=-1)
        if low:
            probs = _fp8(probs, -1)
        out = jnp.einsum("hqk,khd->qhd", probs, v, precision=_HI)
        return _matmul(out.reshape(-1, h * dv), lp["wo"], low)

    return _by_rows(rows_of, (a, positions), _QUERY_BLOCK)


def _dense_ffn(m, lp, low: bool):
    """The dense layer's feed-forward, a block of its width at a time (the
    matrices in the served type, upcast a block at a time)."""
    d, width = lp["w_gate"].shape
    blocks = next(b for b in range(_FFN_BLOCKS, 0, -1) if width % b == 0)

    def columns(w):                     # [d, width] -> [blocks, d, width / b]
        return w.reshape(d, blocks, -1).transpose(1, 0, 2)

    def rows_of(args):
        (mb,) = args

        def one(y, w):
            w_gate, w_up, w_down = w
            return y + _swiglu(mb, _f32(w_gate), _f32(w_up), _f32(w_down),
                               low), None

        y, _ = lax.scan(one, jnp.zeros_like(mb), (
            columns(lp["w_gate"]), columns(lp["w_up"]),
            lp["w_down"].reshape(blocks, -1, d)))
        return y

    return _by_rows(rows_of, (m,), _ROW_BLOCK)


def _expert_ffn(m, lp, s: MhcMlaMoeShape, low: bool):
    """The expert layer's feed-forward: the experts one after another, each
    computing every row of a block, its result weighed by the row's weight
    for it (0 where the row did not choose it), and the shared expert."""
    def rows_of(args):
        (mb,) = args
        scores = jax.nn.sigmoid(jnp.matmul(mb, _f32(lp["router"]),
                                           precision=_HI))
        _, chosen = lax.top_k(scores + _f32(lp["router_bias"]),
                              s.experts_per_token)
        weight = jnp.take_along_axis(scores, chosen, axis=-1)
        weight = (weight / weight.sum(-1, keepdims=True)
                  * s.routed_scaling_factor)
        per_expert = jnp.zeros_like(scores).at[
            jnp.arange(mb.shape[0])[:, None], chosen].set(weight)   # [B, E]

        def one(e, y):
            return y + per_expert[:, e, None] * _swiglu(
                mb, _f32(lp["w_gate"][e]), _f32(lp["w_up"][e]),
                _f32(lp["w_down"][e]), low)

        routed = lax.fori_loop(0, s.n_experts, one, jnp.zeros_like(mb))
        return routed + _swiglu(mb, _f32(lp["ws_gate"]), _f32(lp["ws_up"]),
                                _f32(lp["ws_down"]), low)

    return _by_rows(rows_of, (m,), _ROW_BLOCK)


def _hidden(weights: Dict, tokens: jax.Array, s: MhcMlaMoeShape,
            low: bool) -> jax.Array:
    """tokens [L] int32 -> the final-normed hidden states [L, d] float32."""
    def upcast(lp):
        """A layer's attention matrices and norm vectors in float32; the
        feed-forward's, the router's and the maps' leaves stay as they are
        and are upcast where they are used."""
        return {k: v if k in _UPCAST_WHERE_USED else _f32(v)
                for k, v in lp.items()}

    def layer(x, lp, feed_forward):
        x = _wrapped(x, lp["hc_att"], lambda h: _attention(
            _rms_norm(h, lp["norm1"], s.rms_norm_eps), lp, s, low), s)
        return _wrapped(x, lp["hc_ffn"], lambda h: feed_forward(
            _rms_norm(h, lp["norm2"], s.rms_norm_eps)), s)

    def dense_layer(x, lp):
        lp = upcast(lp)
        return layer(x, lp, lambda m: _dense_ffn(m, lp, low)), None

    def expert_layer(x, lp):
        lp = upcast(lp)
        return layer(x, lp, lambda m: _expert_ffn(m, lp, s, low)), None

    x = _f32(weights["embed"]["tok"][tokens])
    x = jnp.broadcast_to(x[:, None, :], (x.shape[0], s.hc_mult, x.shape[1]))
    x, _ = lax.scan(dense_layer, x, weights["dense"])
    x, _ = lax.scan(expert_layer, x, weights["moe"])
    return _rms_norm(x.sum(axis=1), _f32(weights["final_norm"]),
                     s.rms_norm_eps)


def logits(weights: Dict, tokens, s: MhcMlaMoeShape, low: bool = False):
    """tokens [L] -> logits [L, vocab] float32: the whole pass at once, for
    tests at small sizes."""
    return _matmul(_hidden(weights, jnp.asarray(tokens, jnp.int32), s, low),
                   _f32(weights["head"]), low)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _read(weights, tokens, rows, probe, s: MhcMlaMoeShape, low: bool):
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
        out = _matmul(x, _f32(head), low)                      # [R, block]
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


def served_gaps(weights: Dict, s: MhcMlaMoeShape, samples: Sequence[dict],
                length: Optional[int] = None,
                control: bool = False) -> List[np.ndarray]:
    """For each sample (``prompt`` [L] and ``tokens`` served after it), the
    gap of every served token below the reference's best logit there; one
    sequence a call, padded to ``padded_length`` of its own (causal: the
    padding is never attended by a judged row; ``length``, the mix's longest
    request, is only what none may exceed). With ``control`` the tokens
    judged are those the float8 pass puts first at the same positions."""
    judged = max((len(np.asarray(g["tokens"]).reshape(-1)) for g in samples),
                 default=0)
    judged = -(-max(judged, 1) // 32) * 32      # one shape for every call
    gaps: List[np.ndarray] = []
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


def check_outputs(cell, shape: MhcMlaMoeShape, weights: dict, obs,
                  seed: int) -> Dict[str, dict]:
    """Each number compared, beside its limit: ``reference_mla_moe.
    check_outputs`` with this configuration's reference."""
    from benchmarks.reference import pad_length

    settings = cell.config["check"]
    sample = pick_sample(obs.logs, int(settings["sample_requests"]), seed)
    gaps = served_gaps(
        weights, shape,
        [{"prompt": g.request.prompt[0], "tokens": g.tokens} for g in sample],
        pad_length(cell.traffic))
    every = np.concatenate([np.asarray(g, np.float64) for g in gaps]
                           or [np.zeros(0)])
    names = ("served_logit_gap_max", "served_logit_gap_p99",
             "served_logit_gap_mean")
    if every.size:
        readings = dict(zip(names, (float(every.max()),
                                    float(np.percentile(every, 99)),
                                    float(every.mean()))))
    else:
        readings = dict.fromkeys(names, float("inf"))
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
