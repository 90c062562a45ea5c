"""The multi-stream residual path (mHC, ``models/mhc.py``) and YaRN-scaled
rotary positions in the MLA / routed-expert family, at a small size on the
CPU (float32, so the tolerances can be tight): Sinkhorn's sums, the wrapped
sublayer's limiting forms, chunked prefill and then decode through the
latent cache against the plain reference's full forward pass ON LOGITS, the
same comparison failing for four faults, one stream being the programs the
family had before, and the dispatch record's two new fields. Nothing here is
a device number."""

import dataclasses
import hashlib
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import reference_mhc_mla_moe as reference  # noqa: E402
from benchmarks.costs_mhc_mla_moe import MhcMlaMoeShape  # noqa: E402
from test_mla_moe import _collect, _served_logits  # noqa: E402
from test_mla_moe import shape_of as plain_shape_of  # noqa: E402
from tritonclient_tpu import _stepscope  # noqa: E402
from tritonclient_tpu.models import mhc, mla_moe  # noqa: E402
from tritonclient_tpu.models.gpt_engine import GenerationEngine  # noqa: E402

# The served logits against the float32 reference's, largest difference over
# every judged position and the whole vocabulary. Both sides are float32 on
# the CPU and differ in the order of their sums (absorbed against expanded
# attention, grouped against looped experts, the maps' coefficients as
# vectors over the tokens against [n, n] matrices a token, x~ phi as (x phi)
# / rms): 2e-6 to 8e-6 is read on logits of size 3. Each of the four faults
# below moves the logits by 3e-2 and more, so 1e-4 holds the first and fails
# the others.
LOGIT_TOLERANCE = 1e-4


def shape_of(cfg: mla_moe.MlaMoeConfig) -> MhcMlaMoeShape:
    plain = plain_shape_of(cfg)
    ys = cfg.rope_scaling
    return MhcMlaMoeShape(
        **{f.name: getattr(plain, f.name)
           for f in dataclasses.fields(plain) if f.name != "dtype"},
        dtype="float32", hc_mult=cfg.hc_mult,
        hc_sinkhorn_iters=cfg.hc_sinkhorn_iters, hc_eps=cfg.hc_eps,
        hc_res_clamp_min=cfg.hc_res_clamp[0],
        hc_res_clamp_max=cfg.hc_res_clamp[1], yarn_factor=ys.factor,
        yarn_original_positions=ys.original_max_len,
        yarn_beta_fast=ys.beta_fast, yarn_beta_slow=ys.beta_slow,
        yarn_mscale=ys.mscale, yarn_mscale_all_dim=ys.mscale_all_dim)


@pytest.fixture(scope="module")
def tiny():
    """hidden 64 in 4 streams, 2 dense + 2 expert layers, 8 experts top-2,
    YaRN by 8 over 16 original positions (a request here holds 41: the
    scaled pairs are far from the plain ones), theta 100 so that the four
    rotary pairs span the blend: pair 0 kept, 1 blended, 2 and 3 scaled."""
    cfg = dataclasses.replace(
        mla_moe.mla_moe_tiny(), n_layers=4, n_dense_layers=2, hc_mult=4,
        rope_theta=100.0, rope_scaling=mla_moe.YarnScaling(
            factor=8.0, original_max_len=16, beta_fast=4.0, beta_slow=1.0,
            mscale=1.0, mscale_all_dim=1.0))
    params = mla_moe.init_params(jax.random.PRNGKey(3), cfg)
    assert set(params["dense"]["hc_att"]) == {"phi", "b", "alpha"}
    assert params["moe"]["hc_ffn"]["phi"].shape == (2, 4 * 64, 24)
    return cfg, params


# --------------------------------------------------------------------------- #
# the maps                                                                    #
# --------------------------------------------------------------------------- #


def test_sinkhorn_makes_rows_and_columns_sum_to_one():
    """20 iterations from exp of clamped random inputs (normal, standard
    deviation 0.7: entries within about e^+-2 of each other): every row sum
    and every column sum within 1e-5 of 1. The last half-step normalises the
    rows, so they are 1 to ``eps`` whatever went in; the COLUMNS are what has
    to converge, and how fast depends on the spread: the seeded maps' own
    inputs (unit normal + 2 on the diagonal) leave a third of the tokens'
    column sums more than 1e-5 off after the published 20, the worst 1e-2.
    That is the model's arithmetic, not a fault: program and reference both
    run exactly ``hc_sinkhorn_iters`` and are compared with each other."""
    rng = np.random.default_rng(0)
    raw = np.clip(rng.normal(0, 0.7, (4, 4, 256)), -30, 30)
    as_lists = lambda a: [[jnp.exp(jnp.asarray(  # noqa: E731
        a[i, j], jnp.float32)) for j in range(4)] for i in range(4)]
    out = np.asarray(mhc.sinkhorn(as_lists(raw), 20, 1e-6))  # [4, 4, tokens]
    assert (out > 0).all()
    np.testing.assert_allclose(out.sum(axis=1), 1.0, atol=1e-5)  # rows
    np.testing.assert_allclose(out.sum(axis=0), 1.0, atol=1e-5)  # columns
    # entries AT the clamp, 1e13 from their neighbours: finite, rows still 1
    raw[0, 1, :8], raw[2, 2, :8] = 30.0, -30.0
    seeded = np.clip(rng.normal(0, 1, (4, 4, 256)) + 2 * np.eye(4)[:, :, None],
                     -30, 30)
    for inputs, columns_within in ((raw, 1.0), (seeded, 2e-2)):
        wide = np.asarray(mhc.sinkhorn(as_lists(inputs), 20, 1e-6))
        assert np.isfinite(wide).all() and (wide >= 0).all()
        np.testing.assert_allclose(wide.sum(axis=1), 1.0, atol=1e-5)
        np.testing.assert_allclose(wide.sum(axis=0), 1.0, atol=columns_within)
        # and the reference's [.., n, n] form is the same matrix
        plain = np.asarray(reference.sinkhorn(jnp.exp(jnp.asarray(
            inputs.transpose(2, 0, 1), jnp.float32)), 20, 1e-6))
        np.testing.assert_allclose(wide.transpose(2, 0, 1), plain, rtol=1e-5,
                                   atol=1e-7)


class _Maps:
    """The fields ``mhc.maps`` reads of a configuration."""

    rms_norm_eps, hc_sinkhorn_iters, hc_eps = 1e-6, 20, 1e-6
    hc_res_clamp = (-30.0, 30.0)


def _sublayer(h):
    return jnp.tanh(h) * 3.0 + 0.5


def test_one_stream_is_a_gated_residual():
    """n = 1: the doubly stochastic 1 x 1 matrix is 1 (to eps), so the
    wrapped sublayer is ``X + 2 s(.) F(s(.) X)``."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(6, 1, 32)), jnp.float32)
    hp = jax.tree.map(lambda a: a[0], mhc.init_maps(
        jax.random.PRNGKey(0), 1, 1, 32, jnp.float32))
    h, back = mhc.pre(x, hp, _Maps)
    out = mhc.post(x, _sublayer(h), back)
    flat = x[:, 0]
    unit = flat / jnp.sqrt((flat * flat).mean(-1, keepdims=True) + 1e-6)
    raw = unit @ hp["phi"]
    s_pre = jax.nn.sigmoid(hp["alpha"][0] * raw[:, 0] + hp["b"][0])[:, None]
    s_post = jax.nn.sigmoid(hp["alpha"][1] * raw[:, 1] + hp["b"][1])[:, None]
    assert 0.05 < float(s_pre.min()) < float(s_pre.max()) < 0.95
    np.testing.assert_allclose(
        out[:, 0], flat + 2 * s_post * _sublayer(s_pre * flat), atol=1e-5)


def test_unit_maps_are_the_plain_residual_of_the_first_stream(monkeypatch):
    """``H_pre = e_1``, ``H_post = e_1``, ``H_res = I``: stream 1 becomes
    ``X_1 + F(X_1)`` and the others pass unchanged."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(5, 4, 32)), jnp.float32)
    one, zero = jnp.ones((5,)), jnp.zeros((5,))
    e1 = [one, zero, zero, zero]
    eye = [[one if i == j else zero for j in range(4)] for i in range(4)]
    monkeypatch.setattr(mhc, "maps", lambda x, hp, cfg: (e1, e1, eye))
    h, back = mhc.pre(x, None, _Maps)
    np.testing.assert_array_equal(h, x[:, 0])
    out = mhc.post(x, _sublayer(h), back)
    np.testing.assert_allclose(out[:, 0], x[:, 0] + _sublayer(x[:, 0]),
                               atol=1e-6)
    np.testing.assert_array_equal(out[:, 1:], x[:, 1:])


def test_the_seeded_maps_vary_by_token_and_stay_clear_of_the_clamp(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(64, 4, cfg.d_model)), jnp.float32)
    hp = jax.tree.map(lambda a: a[0], params["moe"]["hc_att"])
    pre, post, res = (np.asarray(m) for m in mhc.maps(x, hp, cfg))
    assert pre.std(axis=1).min() > 0.1 and post.std(axis=1).min() > 0.2
    assert 0.01 < pre.min() and pre.max() < 0.99 and post.max() < 1.98
    assert res.std(axis=2).min() > 0.03
    diagonal = res[np.arange(4), np.arange(4)]
    assert 0.4 < diagonal.mean() < 0.8        # leans to the identity
    raw = hp["phi"].T @ (x.reshape(64, -1) / jnp.sqrt(
        (x.reshape(64, -1) ** 2).mean(-1, keepdims=True))).T
    assert float(jnp.abs(raw[8:] + hp["b"][8:, None]).max()) < 8   # of 30


# --------------------------------------------------------------------------- #
# YaRN                                                                        #
# --------------------------------------------------------------------------- #


def test_yarn_frequencies_at_the_published_keys():
    """theta 10000, rotary width 64, factor 64 over 4096 positions, beta 32
    and 1: low / high are 10 and 23; pairs to 10 keep their frequency, pairs
    from 23 have it divided by 64, the 12 between blend linearly; the
    softmax scale gains (0.1 ln 64 + 1)^2."""
    cfg = mla_moe.MlaMoeConfig(
        rope_theta=10000.0, rope_scaling=mla_moe.YarnScaling(
            factor=64.0, original_max_len=4096, beta_fast=32.0,
            beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0))
    freq, magnitude = mla_moe.rope_frequencies(cfg, 64)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    freq = np.asarray(freq, np.float64)
    np.testing.assert_allclose(freq[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(freq[23:], plain[23:] / 64, rtol=1e-6)
    kept = 1 - (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(
        freq[11:23], plain[11:23] * (kept + (1 - kept) / 64), rtol=1e-6)
    assert magnitude == 1.0
    mscale = 0.1 * np.log(64) + 1
    assert abs(mscale - 1.4159) < 1e-4
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * mscale ** 2)
    # without scaling: the family's frequencies and scale as they were
    plain_cfg = mla_moe.MlaMoeConfig(rope_theta=10000.0)
    np.testing.assert_allclose(
        mla_moe.rope_frequencies(plain_cfg, 64)[0], plain, rtol=1e-6)
    assert plain_cfg.softmax_scale == 1.0 / np.sqrt(192)
    # the reference's own arithmetic gives the same numbers
    shape = dataclasses.replace(
        shape_of(cfg), qk_rope_head_dim=64, qk_nope_head_dim=128)
    np.testing.assert_allclose(reference.rope_frequencies(shape), freq,
                               rtol=1e-6)
    assert reference.softmax_scale(shape) == pytest.approx(cfg.softmax_scale)


# --------------------------------------------------------------------------- #
# the program against the reference                                           #
# --------------------------------------------------------------------------- #


def _worst_logit_error(cfg, params, monkeypatch, fuse=1, n_new=12,
                       reference_params=None):
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, (1, 29)).astype(np.int32)
    tokens, seen = _served_logits(cfg, params, prompt, n_new, monkeypatch,
                                  fuse)
    assert len(tokens) == n_new
    sequence = np.concatenate([prompt[0], np.asarray(tokens, np.int32)])
    expected = np.asarray(reference.logits(
        reference_params or params, sequence, shape_of(cfg)))
    return max(float(np.abs(seen[step] - expected[28 + step]).max())
               for step in range(n_new))


@pytest.mark.parametrize("fuse", [1, 4], ids=["unfused", "fused"])
def test_chunked_prefill_then_decode_agrees_with_the_reference_on_logits(
        tiny, monkeypatch, fuse):
    """29 prompt tokens in four chunks over two pages, then 11 decode steps
    (fused: windows of 4, 4, 2 and a step) through the latent cache, four
    streams around every sublayer of 2 dense and 2 expert layers, positions
    past YaRN's original 16: every served token's logits are the reference's
    full forward pass's."""
    cfg, params = tiny
    assert _worst_logit_error(cfg, params, monkeypatch, fuse) < LOGIT_TOLERANCE


def _identity_mixing(monkeypatch, cfg, params):
    def identity(m, iters, eps):
        one, zero = jnp.ones_like(m[0][0]), jnp.zeros_like(m[0][0])
        return [[one if i == j else zero for j in range(len(m))]
                for i in range(len(m))]

    monkeypatch.setattr(mhc, "sinkhorn", identity)
    return cfg, params


def _static_maps(monkeypatch, cfg, params):
    """alpha = 0: the maps' dynamic part dropped, their biases kept."""
    def still(stack):
        return {k: ({**v, "alpha": jnp.zeros_like(v["alpha"])}
                    if k.startswith("hc_") else v) for k, v in stack.items()}

    return cfg, dict(params, dense=still(params["dense"]),
                     moe=still(params["moe"]))


def _unscaled_rotary(monkeypatch, cfg, params):
    frequencies = mla_moe.rope_frequencies
    monkeypatch.setattr(
        mla_moe, "rope_frequencies", lambda cfg, dim: frequencies(
            dataclasses.replace(cfg, rope_scaling=None), dim))
    return cfg, params


def _scale_without_mscale(monkeypatch, cfg, params):
    attend = mla_moe._attend
    lost = mla_moe.yarn_mscale(cfg.rope_scaling.factor,
                               cfg.rope_scaling.mscale_all_dim) ** 2

    def wrong(q_nope, q_rope, *rest):
        return attend(q_nope / lost, q_rope / lost, *rest)

    monkeypatch.setattr(mla_moe, "_attend", wrong)
    return cfg, params


@pytest.mark.parametrize(
    "fault", [_identity_mixing, _static_maps, _unscaled_rotary,
              _scale_without_mscale],
    ids=["h_res_identity", "alpha_zero", "unscaled_rotary",
         "scale_without_mscale"])
def test_the_logit_tolerance_fails_each_part_left_out(tiny, monkeypatch,
                                                      fault):
    cfg, params = tiny
    served_cfg, served_params = fault(monkeypatch, cfg, params)
    worst = _worst_logit_error(served_cfg, served_params, monkeypatch,
                               n_new=6, reference_params=params)
    assert worst > 10 * LOGIT_TOLERANCE


def test_a_wide_table_is_attended_a_table_at_a_time_and_gives_the_same(
        tiny, monkeypatch):
    """Past ``_EXPAND_AT_ONCE`` positions a prefill chunk expands and
    attends its tables one after another: two lanes of 8 rows over three
    pages (48 positions) with the cap at 16 write the latent pool the whole
    batch writes (every later layer's latent hangs on the attention before
    it) and pick the same first tokens."""
    cfg, params = tiny
    assert mla_moe._EXPAND_AT_ONCE == 2048
    model = mla_moe.MlaMoePaged(cfg)
    rng = np.random.default_rng(7)
    chunks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 8)), jnp.int32)
    btabs = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    args = (chunks, btabs, jnp.asarray([37, 24], jnp.int32),
            jnp.asarray([8, 5], jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.zeros((2,), jnp.float32), jnp.zeros((2,), jnp.int32))

    def run():
        (pool,) = model.pool_arrays(7, 16)
        pool = pool.at[:, 1:].set(jnp.asarray(
            rng_pool.normal(size=pool[:, 1:].shape), pool.dtype))
        firsts, pool, _ = jax.jit(model.prefill_chunk(16))(params, pool, *args)
        return np.asarray(firsts), np.asarray(pool)

    rng_pool = np.random.default_rng(8)
    whole = run()
    monkeypatch.setattr(mla_moe, "_EXPAND_AT_ONCE", 16)
    rng_pool = np.random.default_rng(8)
    one_by_one = run()
    np.testing.assert_array_equal(whole[0], one_by_one[0])
    np.testing.assert_allclose(whole[1], one_by_one[1], atol=1e-5, rtol=1e-5)


# --------------------------------------------------------------------------- #
# one stream: the family as it was                                            #
# --------------------------------------------------------------------------- #

# sha256 (first 16 hex digits) of the StableHLO text of the three programs
# of ``mla_moe_tiny()`` as an engine of 2 slots and chunks of 8 lowers them,
# recorded on the commit BEFORE the residual and scaling fields existed
# (0d21d25): with ``hc_mult`` 1 and ``rope_scaling`` None the family's
# programs are those programs, operation for operation, so every output of
# ``tests/test_mla_moe.py`` is what it was, bit for bit. A PR that changes
# the one-stream path on purpose records them anew (the text has no file
# names or line numbers in it). PR 35 did: the routed experts' product
# became the kernel of ``ops/grouped_experts.py`` (interpreted here) in
# place of three ``lax.ragged_dot``; recorded with ``_program_digests`` on
# its final tree. PR 37 did for the two decode programs (their attention
# became a ``lax.switch`` over the table's widths); the chunk's is PR 35's.
_ONE_STREAM_PROGRAMS = {
    "decode": "aa358c5658c05f37",
    "fused_2": "e93f44f6c5885f08",
    "prefill_chunk": "a0061543d29cf202",
}


def _program_digests(cfg, params):
    engine = GenerationEngine(mla_moe.MlaMoePaged(cfg), params, max_slots=2,
                              prefill_chunk=8)
    try:
        (pool,) = engine._pools
        bank = (engine.params, pool, engine._btabs, engine._tokens,
                engine._pos, engine._seeds, engine._steps, engine._temps,
                engine._topks)
        z = jnp.zeros((1,), jnp.int32)
        chunk = (engine.params, pool, jnp.zeros((1, 8), jnp.int32),
                 jnp.zeros((1, 1), jnp.int32), z, jnp.ones((1,), jnp.int32),
                 z, jnp.zeros((1,), jnp.float32), z)
        programs = {"decode": (engine._step, bank),
                    "fused_2": (engine._multi_step_fn(2), bank),
                    "prefill_chunk": (engine._prefill_chunk_fn, chunk)}
        return {name: hashlib.sha256(
            fn.lower(*args).as_text().encode()).hexdigest()[:16]
            for name, (fn, args) in programs.items()}
    finally:
        engine.shutdown()


def test_one_stream_and_no_scaling_are_the_programs_the_family_had():
    cfg = mla_moe.mla_moe_tiny()
    assert cfg.hc_mult == 1 and cfg.rope_scaling is None
    params = mla_moe.init_params(jax.random.PRNGKey(3), cfg)
    assert not [k for k in params["dense"] if k.startswith("hc_")]
    assert _program_digests(cfg, params) == _ONE_STREAM_PROGRAMS
    # and four streams are other programs, whose weights beside the maps
    # are the same draw
    wide = dataclasses.replace(cfg, hc_mult=4)
    wide_params = mla_moe.init_params(jax.random.PRNGKey(3), wide)
    assert _program_digests(wide, wide_params)["decode"] != (
        _ONE_STREAM_PROGRAMS["decode"])
    np.testing.assert_array_equal(wide_params["moe"]["wq_a"],
                                  params["moe"]["wq_a"])


# --------------------------------------------------------------------------- #
# stepscope                                                                   #
# --------------------------------------------------------------------------- #


def test_dispatch_records_gain_the_streams_and_the_rows_that_passed_the_maps(
        tiny):
    cfg, params = tiny
    was = _stepscope.mode()
    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    try:
        engine = GenerationEngine(mla_moe.MlaMoePaged(cfg), params,
                                  max_slots=2, prefill_chunk=8,
                                  scope_name="mhc_test")
        try:
            prompt = np.arange(1, 20, dtype=np.int32).reshape(1, 19)
            assert len(_collect(engine.submit(prompt, 9))) == 9
            deadline = time.time() + 10
            while time.time() < deadline:
                records = [r for r in _stepscope.dump()["records"]
                           if r["model"] == "mhc_test"
                           and r["phase"] in ("decode", "prefill_chunk")]
                if all("hc_rows" in r for r in records):
                    break
                time.sleep(0.02)  # tpulint: disable=TPU001
        finally:
            engine.shutdown()
    finally:
        _stepscope.configure(was)
        _stepscope.reset()
    assert _stepscope.RESIDUAL_FIELDS == ("hc_streams", "hc_rows")
    chunks = [r for r in records if r["phase"] == "prefill_chunk"]
    # 19 prompt tokens in chunks of 8: each live row passes 2 sublayers'
    # maps in each of 4 layers
    assert [r["hc_rows"] for r in chunks] == [8 * 8, 8 * 8, 3 * 8]
    for r in records:
        assert r["hc_streams"] == 4
        assert r["hc_rows"] == r["routed_tokens"] * 2 * cfg.n_layers
    decodes = [r for r in records if r["phase"] == "decode"
               and r["routed_tokens"]]
    assert {r["hc_rows"] // r["micro_steps"] for r in decodes} == {8}
