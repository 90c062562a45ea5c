"""The plain reference: a GPT-2-family forward pass in straightforward
``jax.numpy``, float32, matrix multiplications at ``highest`` precision, no
cache, no batching tricks, no kernel. It imports nothing of the program.

It decides ``correct``: once the window has closed, a sample of the requests
the window finished is run through it, each prompt with its served tokens,
and for every served token the gap by which its logit lies below the
reference's best is read. Greedy serving at the stated precision keeps that
gap small; a wrong token, a corrupted page or a coarser precision does not.

The control is this same reference with every matrix multiplication's inputs
rounded to scaled float8 (e4m3), the step below bfloat16 that would tempt a
later PR. It need not decode: at each position of the same prompts and
tokens, the gap of the token the lower precision puts first is read.

Weights arrive in the served type (bfloat16, made by benchmarks/weights.py)
and are upcast one layer at a time inside the scan, so no second copy of the
model is held.
"""

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.costs import GptShape
from benchmarks.traffic import longest_request

_HI = lax.Precision.HIGHEST
_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round to e4m3 with one scale along ``axis`` (the contraction axis):
    per row for activations, per output column for weights, as fp8 serving
    recipes do."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(_F8).astype(jnp.float32) * scale


def _matmul(x, w, low: bool):
    """x [..., k] @ w [k, n] in float32 at highest precision; ``low`` rounds
    both inputs to scaled float8 first (the control)."""
    if low:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=_HI)


def _layer_norm(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _gelu_tanh(x):
    # "gelu_new": 0.5 x (1 + tanh(sqrt(2/pi) (x + 0.044715 x^3))). The program
    # serves this form for every configuration (see each config's `assumed`).
    return 0.5 * x * (1 + jnp.tanh(
        np.sqrt(2 / np.pi) * (x + 0.044715 * x ** 3)))


def _forward(weights: Dict, tokens: jax.Array, s: GptShape,
             low: bool) -> jax.Array:
    """tokens [B, L] int32 -> logits [B, L, vocab] float32."""
    b, l = tokens.shape
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    tok = f32(weights["embed"]["tok"])
    x = tok[tokens] + f32(weights["embed"]["pos"])[:l][None]
    causal = jnp.tril(jnp.ones((l, l), bool))[None, None]

    def layer(x, lp):
        lp = jax.tree.map(f32, lp)
        a = _layer_norm(x, lp["ln1_scale"], lp["ln1_bias"],
                        s.layer_norm_epsilon)
        qkv = _matmul(a, lp["wqkv"], low) + lp["bqkv"]
        q, k, v = (t.reshape(b, l, s.n_head, s.head_dim)
                   for t in jnp.split(qkv, 3, axis=-1))
        if low:
            q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, 1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=_HI)
        scores = scores / np.sqrt(s.head_dim)
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        if low:
            probs = _fp8(probs, -1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=_HI)
        x = x + _matmul(out.reshape(b, l, s.d_model), lp["wo"], low) + lp["bo"]
        m = _layer_norm(x, lp["ln2_scale"], lp["ln2_bias"],
                        s.layer_norm_epsilon)
        hidden = _gelu_tanh(_matmul(m, lp["w_in"], low) + lp["b_in"])
        return x + _matmul(hidden, lp["w_out"], low) + lp["b_out"], None

    x, _ = lax.scan(layer, x, weights["layers"])
    x = _layer_norm(x, f32(weights["final_ln"]["scale"]),
                    f32(weights["final_ln"]["bias"]), s.layer_norm_epsilon)
    return _matmul(x, tok.T, low)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _read(weights, tokens, probe, s: GptShape, low: bool):
    """(best logit, logit of ``probe``, first-placed token), each [B, L]."""
    logits = _forward(weights, tokens, s, low)
    best = logits.max(-1)
    at = jnp.take_along_axis(logits, probe[..., None], axis=-1)[..., 0]
    return best, at, jnp.argmax(logits, -1).astype(jnp.int32)


def pad_length(mix: dict) -> int:
    """One sequence length for every reference call of a mix: its longest
    prompt and output together, rounded up to a multiple of 32."""
    return -(-longest_request(mix) // 32) * 32


def served_gaps(weights: Dict, s: GptShape, samples: Sequence[dict],
                length: int, control: bool = False,
                rows_per_call: int = 2) -> List[np.ndarray]:
    """For each sample (``prompt`` [L] and ``tokens`` served after it), the
    gap of every served token below the reference's best logit there.

    With ``control`` the tokens judged are not the served ones but those the
    float8 reference puts first at the same positions.
    """
    gaps: List[Optional[np.ndarray]] = [None] * len(samples)
    for first in range(0, len(samples), rows_per_call):
        block = list(samples[first:first + rows_per_call])
        tokens = np.zeros((rows_per_call, length), np.int32)
        probe = np.zeros((rows_per_call, length), np.int32)
        spans = []
        for row, sample in enumerate(block):
            prompt = np.asarray(sample["prompt"], np.int32).reshape(-1)
            served = np.asarray(sample["tokens"], np.int32).reshape(-1)
            seq = np.concatenate([prompt, served])
            if len(seq) - 1 > length:
                raise ValueError(f"sample of {len(seq)} tokens exceeds the "
                                 f"reference length {length}")
            # Position i predicts seq[i + 1]; the last served token is only
            # ever a target, never an input.
            tokens[row, :len(seq) - 1] = seq[:-1]
            probe[row, :len(seq) - 1] = seq[1:]
            spans.append((len(prompt) - 1, len(seq) - 1))
        tokens_d, probe_d = jnp.asarray(tokens), jnp.asarray(probe)
        if control:
            probe_d = _read(weights, tokens_d, probe_d, s, True)[2]
        best, at_probe, _ = _read(weights, tokens_d, probe_d, s, False)
        gap = np.asarray(best - at_probe)
        for row, (lo, hi) in enumerate(spans):
            gaps[first + row] = gap[row, lo:hi]
    return gaps


def pick_sample(logs: list, count: int, seed: int
                ) -> list:
    """``count`` finished requests drawn from the seed, the longest among
    them."""
    finished = [log for log in logs if log.error is None]
    if not finished:
        return []
    longest = max(finished, key=lambda g: (g.request.prompt.shape[1]
                                           + len(g.tokens), -g.request.index))
    others = [g for g in finished if g is not longest]
    rng = np.random.default_rng([int(seed), 7])
    chosen = rng.permutation(len(others))[:max(count - 1, 0)]
    return [longest] + [others[int(i)] for i in sorted(chosen)]


def check_outputs(cell, shape: GptShape, weights: dict,
                  obs, seed: int) -> Dict[str, dict]:
    """Each number compared, beside its limit (see reference.py)."""
    settings = cell.config["check"]
    sample = pick_sample(obs.logs, int(settings["sample_requests"]), seed)
    gaps = served_gaps(
        weights, shape,
        [{"prompt": g.request.prompt[0], "tokens": g.tokens} for g in sample],
        pad_length(cell.traffic))
    checked = int(sum(len(g) for g in gaps))
    every = np.concatenate([np.asarray(g, np.float64) for g in gaps]
                           or [np.zeros(0)])
    check = {}
    if checked:
        readings = {"served_logit_gap_max": float(every.max()),
                    "served_logit_gap_p99": float(np.percentile(every, 99))}
    else:
        readings = {"served_logit_gap_max": float("inf"),
                    "served_logit_gap_p99": float("inf")}
    for name, value in readings.items():
        limit = settings.get(name + "_limit")
        if limit is not None:           # a number without a limit is not compared
            check[name] = {"value": value, "limit": float(limit)}
    check["failed_requests"] = {"value": len(obs.failed()), "limit": 0}
    check["checked_tokens"] = {
        "value": checked, "at_least": int(settings["min_checked_tokens"])}
    return check
