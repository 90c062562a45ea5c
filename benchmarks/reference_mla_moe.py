"""The plain reference of the MLA / routed-expert family: the forward pass
in straightforward ``jax.numpy``, float32, every matrix product at
``highest`` precision, attention EXPANDED (per-head keys and values made
from the latent), no cache, no batching, the experts as a plain loop. It
imports nothing of the program.

The layer, per token ``x`` (RMSNorm everywhere, weights as given):

    c_q = norm(x W_qa);  q = c_q W_qb -> H x (nope + rope)
    x W_kva -> c_kv = norm(first kv_lora_rank), k_r = RoPE(last rope)
    [k_nope, v] = c_kv W_kvb -> H x (nope + v);  q_r = RoPE(q's rope part)
    scores = (q_nope . k_nope + q_r . k_r) / sqrt(nope + rope), causal
    attention out = softmax(scores) v, heads concatenated, W_o
    dense layers:  W_down(silu(x W_gate) * x W_up)
    expert layers: s = sigmoid(x W_g) (float32); top k of s + b; weights
                   s[chosen] / sum * routed_scaling_factor;
                   y = sum_e w_e SwiGLU_e(x) + SwiGLU_shared(x)
    final norm, untied head.

RoPE rotates the interleaved pairs (2i, 2i + 1) by pos * theta^(-2i/rope).
Departure from the published inference code, noted: that code de-interleaves
the rope part before a half-split rotation; the scores are the same, since
q_r and k_r are permuted alike.

It decides ``correct`` as ``reference.py`` does for the GPT family: after the
window, a sample of finished requests is run through it, one sequence a
call, and for every served token the gap by which its logit lies below the
reference's best is read. Besides the largest gap and the 99th percentile
the MEAN is read, and for this family it is the reading that tells the
stated precision from the one below: with 256 experts and the top 8 taken,
a bfloat16 activation moves an eighth-place choice in about a fifth of the
(token, layer) pairs, each flip swaps a whole expert's output, and so a
quarter of the served tokens lie a little under the float32 reference's best
and a few lie far under it (PERF.md section 2: the program in float32
reads 0, with 8 experts of which all 8 are taken bfloat16 reads 0.03). The
tails of the two precisions then nearly meet while their means stay a
factor of eight apart. So that it fits beside 11 GB of resident bfloat16
weights it upcasts one layer (and one expert) at a time and computes the
head in blocks of the vocabulary, only at the positions that are judged.

The control is the same pass with every matrix product's inputs rounded to
scaled float8 (e4m3); the router stays float32 there too, as the
configuration states it and as float8 recipes keep it, so the control is
the milder of the two possible and the limit under it the stricter.
"""

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.costs_mla_moe import MlaMoeShape
from benchmarks.reference import _fp8, _matmul, pad_length, pick_sample

_HI = lax.Precision.HIGHEST
_HEAD_BLOCKS = 8        # the vocabulary, in at most this many equal blocks


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def _rope(x, positions, theta):
    """x [L, ..., rope]; rotates pair (2i, 2i + 1) of row l by
    positions[l] * theta ** (-2i / rope)."""
    rope = x.shape[-1]
    inv_freq = 1.0 / theta ** (np.arange(0, rope, 2, dtype=np.float64) / rope)
    angle = positions.astype(jnp.float32).reshape(
        (-1,) + (1,) * (x.ndim - 1)) * jnp.asarray(inv_freq, jnp.float32)
    even, odd = x[..., 0::2], x[..., 1::2]
    rotated = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                         even * jnp.sin(angle) + odd * jnp.cos(angle)], -1)
    return rotated.reshape(x.shape)


def _swiglu(x, w_gate, w_up, w_down, low):
    hidden = jax.nn.silu(_matmul(x, w_gate, low)) * _matmul(x, w_up, low)
    return _matmul(hidden, w_down, low)


def _attention(x, lp, s: MlaMoeShape, low: bool):
    l = x.shape[0]
    h, dn, dr, dv = (s.n_head, s.qk_nope_head_dim, s.qk_rope_head_dim,
                     s.v_head_dim)
    positions = jnp.arange(l)
    c_q = _rms_norm(_matmul(x, lp["wq_a"], low), lp["q_norm"], s.rms_norm_eps)
    q = _matmul(c_q, lp["wq_b"], low).reshape(l, h, dn + dr)
    kv_a = _matmul(x, lp["wkv_a"], low)
    c_kv = _rms_norm(kv_a[:, :s.kv_lora_rank], lp["kv_norm"], s.rms_norm_eps)
    k_r = _rope(kv_a[:, s.kv_lora_rank:], positions, s.rope_theta)   # [L, dr]
    kv = _matmul(c_kv, lp["wkv_b"], low).reshape(l, h, dn + dv)
    q_nope, q_r = q[..., :dn], _rope(q[..., dn:], positions, s.rope_theta)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    if low:
        q_nope, q_r, k_nope, k_r, v = (
            _fp8(q_nope, -1), _fp8(q_r, -1), _fp8(k_nope, -1),
            _fp8(k_r, -1), _fp8(v, 0))
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope, precision=_HI)
              + jnp.einsum("qhd,kd->hqk", q_r, k_r, precision=_HI))
    scores = scores / np.sqrt(dn + dr)
    scores = jnp.where(jnp.tril(jnp.ones((l, l), bool))[None], scores,
                       -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    if low:
        probs = _fp8(probs, -1)
    out = jnp.einsum("hqk,khd->qhd", probs, v, precision=_HI)
    return _matmul(out.reshape(l, h * dv), lp["wo"], low)


def _routed(x, lp, s: MlaMoeShape, low: bool):
    """The expert layer's feed-forward, the experts one after another: each
    computes every row and its result is weighed by the row's weight for it
    (0 where the row did not choose it)."""
    scores = jax.nn.sigmoid(jnp.matmul(x, lp["router"], precision=_HI))
    _, chosen = lax.top_k(scores + lp["router_bias"], s.experts_per_token)
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = weight / weight.sum(-1, keepdims=True) * s.routed_scaling_factor
    per_expert = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(weight)    # [L, E]

    def one(e, y):
        f32 = lambda a: a[e].astype(jnp.float32)  # noqa: E731
        return y + per_expert[:, e, None] * _swiglu(
            x, f32(lp["w_gate"]), f32(lp["w_up"]), f32(lp["w_down"]), low)

    return lax.fori_loop(0, s.n_experts, one, jnp.zeros_like(x))


def _hidden(weights: Dict, tokens: jax.Array, s: MlaMoeShape,
            low: bool) -> jax.Array:
    """tokens [L] int32 -> the final-normed hidden states [L, d] float32."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    x = f32(weights["embed"]["tok"][tokens])

    def dense_layer(x, lp):
        lp = jax.tree.map(f32, lp)
        x = x + _attention(_rms_norm(x, lp["norm1"], s.rms_norm_eps), lp, s,
                           low)
        m = _rms_norm(x, lp["norm2"], s.rms_norm_eps)
        return x + _swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"],
                           low), None

    def expert_layer(x, lp):
        # The routed experts' matrices stay in the served type here and are
        # upcast one expert at a time inside the loop.
        experts = {k: lp[k] for k in ("w_gate", "w_up", "w_down")}
        lp = dict(jax.tree.map(
            f32, {k: v for k, v in lp.items() if k not in experts}), **experts)
        x = x + _attention(_rms_norm(x, lp["norm1"], s.rms_norm_eps), lp, s,
                           low)
        m = _rms_norm(x, lp["norm2"], s.rms_norm_eps)
        return (x + _routed(m, lp, s, low)
                + _swiglu(m, lp["ws_gate"], lp["ws_up"], lp["ws_down"],
                          low)), None

    x, _ = lax.scan(dense_layer, x, weights["dense"])
    x, _ = lax.scan(expert_layer, x, weights["moe"])
    return _rms_norm(x, f32(weights["final_norm"]), s.rms_norm_eps)


def logits(weights: Dict, tokens, s: MlaMoeShape, low: bool = False):
    """tokens [L] -> logits [L, vocab] float32: the whole pass at once, for
    tests at small sizes."""
    return _matmul(_hidden(weights, jnp.asarray(tokens, jnp.int32), s, low),
                   weights["head"].astype(jnp.float32), low)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _read(weights, tokens, rows, probe, s: MlaMoeShape, low: bool):
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


def served_gaps(weights: Dict, s: MlaMoeShape, samples: Sequence[dict],
                length: int, control: bool = False) -> List[np.ndarray]:
    """For each sample (``prompt`` [L] and ``tokens`` served after it), the
    gap of every served token below the reference's best logit there; one
    sequence a call, padded to ``length`` (causal: the padding is never
    attended by a judged row). With ``control`` the tokens judged are those
    the float8 pass puts first at the same positions."""
    judged = max((len(np.asarray(g["tokens"]).reshape(-1)) for g in samples),
                 default=0)
    judged = -(-max(judged, 1) // 32) * 32      # one shape for every call
    gaps: List[Optional[np.ndarray]] = []
    for sample in samples:
        prompt = np.asarray(sample["prompt"], np.int32).reshape(-1)
        served = np.asarray(sample["tokens"], np.int32).reshape(-1)
        seq = np.concatenate([prompt, served])
        if len(seq) - 1 > length:
            raise ValueError(f"sample of {len(seq)} tokens exceeds the "
                             f"reference length {length}")
        tokens = np.zeros((length,), np.int32)
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


def check_outputs(cell, shape: MlaMoeShape, weights: dict, obs,
                  seed: int) -> Dict[str, dict]:
    """Each number compared, beside its limit: ``reference.check_outputs``
    with this family's reference in the GPT one's place."""
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
