"""The plain reference: the same model the program serves (against the
program's own float32 forward, as a test and never as the check), what the
served-gap reading does, and the control coming out as not correct."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference, spec
from benchmarks.costs import gpt_shape
from benchmarks.traffic import RequestSource
from benchmarks.weights import make_weights, seed_key

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "configs", "tiny-test.json")) as _f:
    TINY = json.load(_f)
with open(os.path.join(HERE, "traffic", "tiny-chat.json")) as _f:
    TINY_CHAT = json.load(_f)
SHAPE = gpt_shape(TINY)
LIMIT = TINY["check"]["served_logit_gap_max_limit"]


def test_weights_come_from_the_seed_in_the_served_type():
    a, b, c = (make_weights(s, SHAPE) for s in (2**31 + 3, 2**31 + 3, 4))
    leaves = jax.tree.leaves(a)
    assert all(leaf.dtype == jnp.bfloat16 for leaf in leaves)
    assert all(np.array_equal(x, y) for x, y in zip(leaves, jax.tree.leaves(b)))
    assert not np.array_equal(a["layers"]["wqkv"], c["layers"]["wqkv"])
    assert a["layers"]["wqkv"].shape == (2, 64, 192)
    # biases and layer norms are random too: a dropped bias would show
    assert float(jnp.abs(a["layers"]["bqkv"].astype(jnp.float32)).max()) > 0
    assert not np.array_equal(jax.random.key_data(seed_key(5)),
                              jax.random.key_data(seed_key(5 + 2**31)))


def test_reference_is_the_model_the_program_serves():
    from tritonclient_tpu.models import gpt

    weights = make_weights(11, SHAPE)
    cfg = gpt.GptConfig(vocab_size=SHAPE.vocab_size, d_model=SHAPE.d_model,
                        n_layers=SHAPE.n_layer, n_heads=SHAPE.n_head,
                        d_ff=SHAPE.d_ff, max_len=SHAPE.n_positions,
                        dtype=jnp.float32)
    tokens = np.random.default_rng(0).integers(0, SHAPE.vocab_size, (2, 40))
    as_f32 = jax.tree.map(lambda a: a.astype(jnp.float32), weights)
    with jax.default_matmul_precision("highest"):
        theirs = gpt.forward(as_f32, jnp.asarray(tokens, jnp.int32), cfg)
    ours = reference._forward(weights, jnp.asarray(tokens, jnp.int32), SHAPE,
                              False)
    assert float(jnp.abs(ours - theirs).max()) < 2e-4


def _greedy_samples(weights, seed, count, new_tokens=10):
    """Prompts of the tiny mix with the reference's own greedy tokens."""
    source = RequestSource(TINY_CHAT, SHAPE.vocab_size, seed)
    samples = []
    for _ in range(count):
        seq = list(source.take().prompt[0])
        prompt_len = len(seq)
        for _ in range(new_tokens):
            padded = jnp.asarray([seq + [0] * (96 - len(seq))], jnp.int32)
            first = reference._read(weights, padded, padded, SHAPE, False)[2]
            seq.append(int(first[0, len(seq) - 1]))
        samples.append({"prompt": np.array(seq[:prompt_len]),
                        "tokens": seq[prompt_len:]})
    return samples


@pytest.fixture(scope="module")
def greedy():
    weights = make_weights(21, SHAPE)
    return weights, _greedy_samples(weights, 21, 3)


def test_gap_of_the_references_own_greedy_tokens_is_nought(greedy):
    weights, samples = greedy
    gaps = reference.served_gaps(weights, SHAPE, samples, 96)
    assert [len(g) for g in gaps] == [10, 10, 10]
    assert max(float(g.max()) for g in gaps) == 0.0


def test_an_altered_token_reads_a_wide_gap_where_it_stands(greedy):
    weights, samples = greedy
    broken = [dict(s, tokens=list(s["tokens"])) for s in samples]
    broken[1]["tokens"][4] = (broken[1]["tokens"][4] + 1) % SHAPE.vocab_size
    gaps = reference.served_gaps(weights, SHAPE, broken, 96)
    assert float(gaps[1][4]) > LIMIT
    assert float(gaps[0].max()) == 0.0
    with pytest.raises(ValueError):
        reference.served_gaps(weights, SHAPE, samples, 16)


@pytest.mark.parametrize("seed", [31, 32, 2**31 + 33])
def test_control_in_float8_comes_out_as_not_correct(seed):
    """The contract's control at a size a test run can hold: the reference
    in the program's place, one precision step below the stated bfloat16.
    Its widest gap lies above the limit the tiny configuration sets."""
    weights = make_weights(seed, SHAPE)
    source = RequestSource(TINY_CHAT, SHAPE.vocab_size, seed)
    samples = []
    for _ in range(8):
        request = source.take()
        samples.append({"prompt": request.prompt[0],
                        "tokens": [0] * request.max_tokens})
    control = reference.served_gaps(weights, SHAPE, samples, 96, control=True)
    assert max(float(g.max()) for g in control) > LIMIT


def test_pad_length_holds_the_longest_request():
    with open(os.path.join(spec.ROOT, "benchmarks", "traffic",
                           "chat.json")) as f:
        assert reference.pad_length(json.load(f)) == 864
    assert reference.pad_length(TINY_CHAT) == 96
