"""The MLA / routed-expert family through the paged engine, at a small size
on the CPU (float32, so the tolerances can be tight): prefill in chunks and
then decode through the paged latent cache, unfused and fused, against the
plain reference's full forward pass ON LOGITS; absorbed against expanded
attention; the router's choice by ``s + b`` and weight by ``s``; the seam
the engine holds the family by. Nothing here is a device number."""

import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import reference_mla_moe  # noqa: E402
from benchmarks.costs_mla_moe import MlaMoeShape  # noqa: E402
from tritonclient_tpu import _stepscope  # noqa: E402
from tritonclient_tpu.models import gpt_engine, mla_moe  # noqa: E402
from tritonclient_tpu.models.gpt_engine import GenerationEngine  # noqa: E402

# The served logits against the float32 reference's, largest difference over
# every judged position and the whole vocabulary. Both sides are float32 on
# the CPU here and differ only in the order of their sums (absorbed against
# expanded attention, grouped against looped experts): 3e-6 to 6e-6 is read
# on logits of size 4.5. One bfloat16 rounding of a router score (relative
# 4e-3) or a softmax scale of 1/sqrt(nope) for 1/sqrt(nope + rope) moves the
# logits by 1e-2 and more, so 1e-4 holds the first and fails the others.
LOGIT_TOLERANCE = 1e-4


def shape_of(cfg: mla_moe.MlaMoeConfig) -> MlaMoeShape:
    return MlaMoeShape(
        n_layer=cfg.n_layers, n_dense_layer=cfg.n_dense_layers,
        d_model=cfg.d_model, n_head=cfg.n_heads,
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        d_ff=cfg.d_ff, n_experts=cfg.n_experts,
        experts_per_token=cfg.experts_per_token, d_expert=cfg.d_expert,
        n_shared_experts=cfg.n_shared_experts,
        routed_scaling_factor=cfg.routed_scaling_factor,
        rope_theta=cfg.rope_theta, rms_norm_eps=cfg.rms_norm_eps,
        n_positions=cfg.max_len, vocab_size=cfg.vocab_size)


@pytest.fixture(scope="module")
def tiny():
    """hidden 64, 4 heads, 1 dense + 2 expert layers, 8 experts top-2, a
    seeded non-zero bias."""
    cfg = mla_moe.mla_moe_tiny()
    params = mla_moe.init_params(jax.random.PRNGKey(3), cfg)
    assert float(jnp.abs(params["moe"]["router_bias"]).min()) > 0
    return cfg, params


def _collect(req):
    toks = []
    while True:
        t = req.out.get(timeout=120)
        if t is None:
            return toks
        if isinstance(t, BaseException):
            raise t
        toks.append(int(t[0]))


def _served_logits(cfg, params, prompt, n_new, monkeypatch, fuse):
    """Serve ``prompt`` through a one-request engine (chunks of 8, pages of
    16) and return (tokens, {sampling step: the logits it was picked
    from}): step 0 is the last prefill chunk's, the rest decode's."""
    seen = {}
    pick = mla_moe._pick

    def keep(logits, steps):
        # The request sits in slot 0 / lane 0 of every dispatch it is in.
        # Every chunk of the prompt says step 0: the last one's stay.
        seen[int(steps[0])] = np.asarray(logits[0])

    def spy(logits, seeds, steps, temps, topks):
        jax.debug.callback(keep, logits, steps)
        return pick(logits, seeds, steps, temps, topks)

    monkeypatch.setattr(mla_moe, "_pick", spy)
    monkeypatch.setenv("TPU_ENGINE_FUSE_STEPS", str(fuse))
    engine = GenerationEngine(mla_moe.MlaMoePaged(cfg), params, max_slots=2,
                              prefill_chunk=8)
    try:
        tokens = _collect(engine.submit(prompt, n_new))
    finally:
        engine.shutdown()
    jax.effects_barrier()
    return tokens, seen


def _worst_logit_error(cfg, params, monkeypatch, fuse=1, n_new=12):
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, (1, 29)).astype(np.int32)
    tokens, seen = _served_logits(cfg, params, prompt, n_new, monkeypatch,
                                  fuse)
    assert len(tokens) == n_new
    sequence = np.concatenate([prompt[0], np.asarray(tokens, np.int32)])
    reference = np.asarray(reference_mla_moe.logits(
        params, sequence, shape_of(cfg)))
    worst = 0.0
    for step in range(n_new):          # later steps are the pipeline's surplus
        at = prompt.shape[1] - 1 + step
        worst = max(worst, float(np.abs(seen[step] - reference[at]).max()))
        assert int(np.argmax(reference[at])) == tokens[step]
    return worst


@pytest.mark.parametrize("fuse", [1, 4], ids=["unfused", "fused"])
def test_chunked_prefill_then_decode_agrees_with_the_reference_on_logits(
        tiny, monkeypatch, fuse):
    """29 prompt tokens in four chunks over two pages, then 11 decode steps
    (fused: windows of 4, 4, 2 and a step) read the latent cache back: every
    served token's logits are the reference's full forward pass's."""
    cfg, params = tiny
    assert _worst_logit_error(cfg, params, monkeypatch, fuse) < LOGIT_TOLERANCE


def _bfloat16_router(monkeypatch):
    route = mla_moe.route

    def low(x, router, bias, cfg):
        x = x.astype(jnp.bfloat16).astype(x.dtype)
        router = router.astype(jnp.bfloat16).astype(router.dtype)
        experts, weights = route(x, router, bias, cfg)
        return experts, weights.astype(jnp.bfloat16).astype(weights.dtype)

    monkeypatch.setattr(mla_moe, "route", low)


def _scale_by_nope_alone(monkeypatch):
    attend = mla_moe._attend

    def wrong(q_nope, q_rope, table, mask, lp, cfg, absorbed):
        # scores / sqrt(nope) where the layer says / sqrt(nope + rope)
        up = np.sqrt(cfg.qk_head_dim / cfg.qk_nope_head_dim)
        return attend(q_nope * up, q_rope * up, table, mask, lp, cfg,
                      absorbed)

    monkeypatch.setattr(mla_moe, "_attend", wrong)


@pytest.mark.parametrize("fault", [_bfloat16_router, _scale_by_nope_alone],
                         ids=["bfloat16_router", "scale_by_nope_alone"])
def test_the_logit_tolerance_fails_a_lower_precision_router_and_a_wrong_scale(
        tiny, monkeypatch, fault):
    cfg, params = tiny
    fault(monkeypatch)
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, (1, 29)).astype(np.int32)
    tokens, seen = _served_logits(cfg, params, prompt, 6, monkeypatch, 1)
    sequence = np.concatenate([prompt[0], np.asarray(tokens, np.int32)])
    reference = np.asarray(reference_mla_moe.logits(
        params, sequence, shape_of(cfg)))
    worst = max(float(np.abs(seen[step] - reference[28 + step]).max())
                for step in range(6))
    assert worst > 10 * LOGIT_TOLERANCE


def test_absorbed_and_expanded_attention_agree(tiny):
    """W_UK into the query and W_UV onto the output, against keys and
    values expanded from the same latent: one mathematics, two orders of
    summation (float32: 1e-5 of values of size 1)."""
    cfg, params = tiny
    rng = np.random.default_rng(5)
    tables, rows, length = 2, 3, 32
    lp = jax.tree.map(lambda a: a[0], params["moe"])
    normal = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape), jnp.float32)
    q_nope = normal(tables, rows, cfg.n_heads, cfg.qk_nope_head_dim)
    q_rope = normal(tables, rows, cfg.n_heads, cfg.qk_rope_head_dim)
    table = normal(tables, length, cfg.pool_width).at[
        ..., cfg.latent_dim:].set(0.0)
    held = jnp.asarray([[5, 9, 20], [32, 1, 17]])        # keys each row sees
    mask = (jnp.arange(length)[None, None, :] < held[:, :, None])[:, :, None]
    absorbed = mla_moe._attend(q_nope, q_rope, table, mask, lp, cfg, True)
    expanded = mla_moe._attend(q_nope, q_rope, table, mask, lp, cfg, False)
    assert absorbed.shape == (tables, rows, cfg.n_heads * cfg.v_head_dim)
    np.testing.assert_allclose(absorbed, expanded, atol=1e-5, rtol=1e-5)


def test_the_router_picks_by_score_plus_bias_and_weighs_by_score():
    cfg = mla_moe.MlaMoeConfig(d_model=4, n_experts=4, experts_per_token=2,
                               routed_scaling_factor=2.5, dtype=jnp.float32)
    # One token whose logits over the 4 experts are 2, 1, 0, -1.
    x = jnp.asarray([[1.0, 0.0, 0.0, 0.0]])
    router = jnp.zeros((4, 4)).at[0].set(jnp.asarray([2.0, 1.0, 0.0, -1.0]))
    s = np.asarray(jax.nn.sigmoid(jnp.asarray([2.0, 1.0, 0.0, -1.0])))
    experts, weights = mla_moe.route(x, router, jnp.zeros((4,)), cfg)
    assert sorted(np.asarray(experts[0]).tolist()) == [0, 1]
    # A bias lifts the last expert past the second: chosen by s + b ...
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.6])
    experts, weights = mla_moe.route(x, router, bias, cfg)
    chosen = np.asarray(experts[0]).tolist()
    assert sorted(chosen) == [0, 3]
    # ... weighed by s alone, over the chosen's sum, times the scaling.
    expected = {0: 2.5 * s[0] / (s[0] + s[3]), 3: 2.5 * s[3] / (s[0] + s[3])}
    for e, w in zip(chosen, np.asarray(weights[0])):
        assert abs(w - expected[e]) < 1e-6
    assert weights.dtype == jnp.float32


def test_rows_that_carry_no_request_reach_no_expert(tiny):
    cfg, params = tiny
    layer = 1           # the second expert layer's turn in the banks
    lp = jax.tree.map(lambda a: a[layer], params["moe"])
    banks = mla_moe.expert_banks(params["moe"])
    assert banks["w_gate"].shape[0] == cfg.n_moe_layers * cfg.n_experts
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((6, cfg.d_model)), jnp.float32)
    experts, weights = mla_moe.route(x, lp["router"], lp["router_bias"], cfg)
    live = jnp.asarray([True, False, True, True, False, True])
    y, counts = mla_moe.routed_experts(x, experts, weights, live, banks, cfg,
                                       layer)
    # every expert is held here: the histogram, then no pair elsewhere
    assert counts.shape == (cfg.n_experts + 1,) and int(counts[-1]) == 0
    assert int(counts.sum()) == 4 * cfg.experts_per_token
    assert not np.asarray(y[1]).any() and not np.asarray(y[4]).any()
    # a live row's result is its own whatever its neighbours are
    alone, _ = mla_moe.routed_experts(
        x[:1], experts[:1], weights[:1], live[:1], banks, cfg, layer)
    np.testing.assert_allclose(y[0], alone[0], atol=1e-6)
    # and it is the plain sum over its chosen experts of ITS layer
    plain = sum(
        float(weights[0, j]) * mla_moe._swiglu(
            x[:1], lp["w_gate"][int(e)], lp["w_up"][int(e)],
            lp["w_down"][int(e)])[0]
        for j, e in enumerate(np.asarray(experts[0])))
    np.testing.assert_allclose(y[0], plain, atol=1e-5)


def test_requests_batched_together_get_the_tokens_they_get_alone(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, (1, n)).astype(np.int32)
               for n in (9, 21, 14)]
    engine = GenerationEngine(mla_moe.MlaMoePaged(cfg), params, max_slots=2,
                              prefill_chunk=8)
    try:
        alone = [_collect(engine.submit(p, 7)) for p in prompts]
        together = [engine.submit(p, 7) for p in prompts]
        assert [_collect(r) for r in together] == alone
    finally:
        engine.shutdown()
    assert engine._pool.used_count == 1          # the scratch page


@pytest.mark.parametrize("program", ["decode", "fused_2", "prefill_chunk"])
def test_the_latent_pool_is_the_carry_of_both_layer_scans(tiny, program):
    """One pool of [layers, pages, 16, latent padded to whole lane tiles],
    carried through the dense layers' scan and the expert layers' scan and
    neither scanned in nor stacked out (ROADMAP A10, as the GPT pools)."""
    cfg, params = tiny
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
        fn, args = {
            "decode": (engine._step, bank),
            "fused_2": (engine._multi_step_fn(2), bank),
            "prefill_chunk": (engine._prefill_chunk_fn, chunk),
        }[program]
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
        name = fn.lower(*args).as_text()[:200]
    finally:
        engine.shutdown()
    assert tuple(pool.shape) == (3, 1 + 2 * (cfg.max_len // 16), 16, 128)
    assert cfg.latent_dim == 40 and cfg.pool_width == 128
    assert {"decode": "module @jit_mla_moe_decode_step ",
            "fused_2": "module @jit_mla_moe_decode_fused_2 ",
            "prefill_chunk": "module @jit_mla_moe_prefill_chunk ",
            }[program] in name
    from test_gpt_engine import _scan_eqns

    shape = tuple(pool.shape)
    pool_shaped = (shape, shape[1:], (1,) + shape[1:])
    layer_scans = 0
    for eqn in _scan_eqns(jaxpr):
        n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
        carried = [tuple(v.aval.shape)
                   for v in eqn.invars[n_consts:n_consts + n_carry]]
        scanned = [tuple(v.aval.shape)
                   for v in eqn.invars[n_consts + n_carry:]]
        stacked = [tuple(v.aval.shape) for v in eqn.outvars[n_carry:]]
        assert not [s for s in scanned + stacked if s in pool_shaped]
        if any(len(s) == 3 and s[1:] == (cfg.d_model, cfg.q_lora_rank)
               for s in scanned):          # a scan over layers' wq_a
            layer_scans += 1
            assert carried.count(shape) == 1
    assert layer_scans == 2


def test_a_mesh_is_refused_with_the_reason(tiny):
    cfg, params = tiny
    from tritonclient_tpu.parallel import build_mesh

    mesh = build_mesh({"tp": 2}, jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="one device"):
        GenerationEngine(mla_moe.MlaMoePaged(cfg), params, mesh=mesh)


def test_dispatch_records_gain_what_the_router_did(tiny):
    """stepscope on: the delivery thread reads each step's histogram behind
    its tokens and the dispatch record in the ring gains the counters, chunk
    dispatches that finish no prompt included; the GPT family's records
    gain nothing."""
    cfg, params = tiny
    was = _stepscope.mode()
    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    try:
        engine = GenerationEngine(mla_moe.MlaMoePaged(cfg), params,
                                  max_slots=2, prefill_chunk=8,
                                  scope_name="mla_moe_test")
        try:
            prompt = np.arange(1, 20, dtype=np.int32).reshape(1, 19)
            assert len(_collect(engine.submit(prompt, 9))) == 9
            deadline = time.time() + 10
            while time.time() < deadline:
                records = [r for r in _stepscope.dump()["records"]
                           if r["model"] == "mla_moe_test"
                           and r["phase"] in ("decode", "prefill_chunk")]
                if all("experts_hit" in r for r in records):
                    break
                time.sleep(0.02)  # tpulint: disable=TPU001
        finally:
            engine.shutdown()
    finally:
        _stepscope.configure(was)
        _stepscope.reset()
    chunks = [r for r in records if r["phase"] == "prefill_chunk"]
    decodes = [r for r in records if r["phase"] == "decode"
               and r["routed_tokens"]]
    assert [r["routed_tokens"] for r in chunks] == [8, 8, 3]
    layers, e, k = cfg.n_moe_layers, cfg.n_experts, cfg.experts_per_token
    for r in chunks + decodes:
        assert set(_stepscope.ROUTING_FIELDS) <= set(r)
        assert r["experts_held"] == layers * e * r["micro_steps"]
        assert 0 < r["experts_hit"] <= min(
            r["experts_held"], layers * k * r["routed_tokens"])
        assert r["expert_load_max"] >= r["expert_load_mean"] > 0
        assert abs(r["expert_load_mean"] * r["experts_held"]
                   - layers * k * r["routed_tokens"]) < 1e-6
        # the product takes these widths' f whole: each hit expert once
        assert r["expert_passes"] == r["experts_hit"]
    # one request alone: a decode micro-step routes one token
    assert {r["routed_tokens"] // r["micro_steps"] for r in decodes} == {1}
    assert any(r["micro_steps"] > 1 for r in decodes)


def test_routing_counters_from_a_hand_histogram():
    """Two micro-steps of two expert layers of 4 held experts, top 2: the
    fields by hand. The passes are the experts hit where the widths let the
    product take ``f`` whole, and one more for the expert whose 600 rows lie
    in two row tiles of 512 where it takes ``f`` in tiles (6144 x 2048)."""
    histograms = np.asarray([
        [[600, 0, 20, 0, 4], [0, 0, 310, 200, 4]],
        [[1, 1, 0, 0, 0], [2, 0, 0, 0, 0]]])
    narrow = mla_moe.MlaMoeConfig(
        n_layers=3, n_experts=4, experts_per_token=2, d_model=2048,
        d_expert=768, dtype=jnp.bfloat16)
    assert narrow.n_moe_layers == 2 and narrow.experts_held == 4
    want = {"routed_tokens": 313, "experts_hit": 7, "experts_held": 16,
            "expert_load_max": 600, "expert_load_mean": 1134 / 16,
            "pairs_elsewhere": 8, "expert_passes": 7}
    assert mla_moe.routing_counters(histograms, narrow) == want
    assert set(want) == set(_stepscope.ROUTING_FIELDS)
    wide = dataclasses.replace(narrow, d_model=6144, d_expert=2048)
    assert mla_moe.routing_counters(histograms, wide) == dict(
        want, expert_passes=8)


def test_the_gpt_family_goes_through_the_same_seam():
    from tritonclient_tpu.models import gpt

    cfg = gpt.gpt_tiny()
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    engine = GenerationEngine(cfg, params, max_slots=2, prefill_chunk=8)
    try:
        assert isinstance(engine._model, gpt_engine.GptPaged)
        assert engine._model.routing(()) is None
        assert len(engine._pools) == 2
        assert engine._k is engine._pools[0] and engine._v is engine._pools[1]
        assert engine._block_kv_bytes == engine._model.block_bytes(16)
    finally:
        engine.shutdown()
        engine.release_pools()
    assert engine._pools == (None, None)


@pytest.mark.parametrize("family", ["gpt", "mla_moe"])
def test_a_familys_step_factories_take_the_block_size_and_the_width_alone(
        family, monkeypatch):
    """The seam the scheduler calls: ``decode_step(block_size)``,
    ``decode_fused(block_size, n_steps)``, ``prefill_chunk(block_size)``,
    in the base class and in the family, no private argument; and an engine
    built from the family decodes four tokens through the first and
    through the second, the same four."""
    import inspect

    from tritonclient_tpu.models import gpt

    if family == "gpt":
        cfg = gpt.gpt_tiny()
        model = gpt_engine.GptPaged(cfg)
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    else:
        cfg = mla_moe.mla_moe_tiny()
        model = mla_moe.MlaMoePaged(cfg)
        params = mla_moe.init_params(jax.random.PRNGKey(3), cfg)
    seam = {"decode_step": ["block_size"],
            "decode_fused": ["block_size", "n_steps"],
            "prefill_chunk": ["block_size"]}
    for factory, takes in seam.items():
        for cls in (gpt_engine.PagedModel, type(model)):
            assert list(inspect.signature(
                getattr(cls, factory)).parameters)[1:] == takes, (cls, factory)
    prompt = np.arange(1, 10, dtype=np.int32).reshape(1, 9)
    served = {}
    for fuse in (1, 4):
        monkeypatch.setenv("TPU_ENGINE_FUSE_STEPS", str(fuse))
        engine = GenerationEngine(model, params, max_slots=2, prefill_chunk=8)
        try:
            # the first token is the last chunk's; four more are decode's
            served[fuse] = _collect(engine.submit(prompt, 5))
            assert sorted(engine._multi_step) == ([4] if fuse == 4 else [])
        finally:
            engine.shutdown()
    assert len(served[1]) == 5 and served[4] == served[1]
