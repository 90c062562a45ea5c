"""The MLA / routed-expert family through the paged engine, at a small size
on the CPU (float32, so the tolerances can be tight): prefill in chunks and
then decode through the paged latent cache, unfused and fused, against the
plain reference's full forward pass ON LOGITS; absorbed against expanded
attention; the router's choice by ``s + b`` and weight by ``s``; the seam
the engine holds the family by. Nothing here is a device number."""

import contextlib
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


_PAGE = 16      # the engine's default page, positions


def _bank_by_hand(cfg, held, seed=0):
    """A slot bank as the engine would hold it, by hand: slot s has ``held[s]``
    positions in the cache and is about to write the next (its ``pos``); its
    table row names pages of its own for those and four more positions, the
    rest the scratch page. The pool holds seeded latents (the padding lanes
    zero, as a step writes them). ``None``: a freed slot, its row the
    scratch page throughout and its ``pos`` STALE, near the table's end.
    Returns (pool, btabs, tokens, pos)."""
    rng = np.random.default_rng(seed)
    slots, wide = len(held), cfg.max_len // _PAGE
    pool = rng.standard_normal(
        (cfg.n_layers, 1 + slots * wide, _PAGE, cfg.pool_width))
    pool[..., cfg.latent_dim:] = 0.0
    btabs = np.zeros((slots, wide), np.int32)
    for s, n in enumerate(held):
        if n is not None:
            pages = -(-(n + 4) // _PAGE)
            btabs[s, :pages] = 1 + s * wide + np.arange(pages)
    pos = [cfg.max_len - 8 if n is None else n for n in held]
    return (jnp.asarray(pool, jnp.float32), jnp.asarray(btabs),
            jnp.asarray(rng.integers(0, cfg.vocab_size, slots), jnp.int32),
            jnp.asarray(pos, jnp.int32))


def _decode_by_hand(cfg, params, bank, n_steps, monkeypatch):
    """``n_steps`` decode micro-steps of ``bank`` in ONE program (1: the
    plain step; more: the fused one). Returns (tokens [n_steps, S], the
    logits each micro-step picked from, the positions each layer's
    attention gathered a table, sorted)."""
    logits, gathered = {}, []
    pick, attend = mla_moe._pick, mla_moe._attend

    def spy_pick(lg, seeds, steps, temps, topks):
        jax.debug.callback(
            lambda lg, steps: logits.__setitem__(int(steps[0]),
                                                 np.asarray(lg)), lg, steps)
        return pick(lg, seeds, steps, temps, topks)

    def spy_attend(q_nope, q_rope, table, mask, lp, cfg, absorbed):
        # Inside a branch: only the branch TAKEN calls back.
        jax.debug.callback(lambda n: gathered.append(int(n)),
                           jnp.int32(table.shape[1]))
        return attend(q_nope, q_rope, table, mask, lp, cfg, absorbed)

    monkeypatch.setattr(mla_moe, "_pick", spy_pick)
    monkeypatch.setattr(mla_moe, "_attend", spy_attend)
    pool, btabs, tokens, pos = bank
    z = jnp.zeros_like(pos)
    sampling = (z, z, jnp.zeros(pos.shape, jnp.float32), z)
    if n_steps == 1:
        nxt, _, _ = jax.jit(lambda *a: mla_moe._decode_step_latent(
            *a, cfg=cfg, block_size=_PAGE))(
                params, pool, btabs, tokens, pos, *sampling)
        toks = nxt[None]
    else:
        toks = jax.jit(lambda *a: mla_moe._decode_multi_step_latent(
            *a, cfg=cfg, block_size=_PAGE, n_steps=n_steps))(
                params, pool, btabs, tokens, pos, *sampling)[0]
    toks = np.asarray(toks)
    jax.effects_barrier()
    return toks, logits, sorted(gathered)


def test_the_decode_widths_are_the_tables_halvings_and_the_least_that_holds():
    assert mla_moe.decode_widths(256) == (16, 32, 64, 128, 256)
    assert mla_moe.decode_widths(512) == (32, 64, 128, 256, 512)
    assert mla_moe.decode_widths(8) == (1, 2, 4, 8)
    assert mla_moe.decode_widths(6) == (3, 6)
    taken = [mla_moe.decode_width(n, 8, _PAGE)
             for n in (1, 16, 17, 32, 33, 64, 65, 128, 500)]
    assert taken == [0, 0, 1, 1, 2, 2, 3, 3, 3]
    model = mla_moe.MlaMoePaged(mla_moe.mla_moe_tiny())
    assert [model.pages_gathered(n, 8, _PAGE) for n in (1, 17, 64, 65)] == [
        1, 2, 4, 8]
    # the step's own reading of the same rule, on a traced scalar
    assert [int(jax.jit(lambda n: mla_moe.decode_width(n, 8, _PAGE))(n))
            for n in (16, 17, 65)] == [0, 1, 3]


@pytest.mark.parametrize("n_steps", [1, 2], ids=["decode", "fused_2"])
@pytest.mark.parametrize("longest,width", [(9, 16), (20, 32), (50, 64),
                                           (100, 128)])
def test_decode_attends_the_least_width_over_the_longest_live_context(
        tiny, monkeypatch, longest, width, n_steps):
    """A bank whose longest context falls in each of the table's widths in
    turn (8 pages: 16, 32, 64, 128 positions): every layer of every
    micro-step gathers that width and no other, and the logits are the
    whole-width form's (the keys dropped were masked: exact zeros in the
    sums). The third slot is FREED, its position stale at 120: it widens
    nothing."""
    cfg, params = tiny
    bank = _bank_by_hand(cfg, [longest, 7, None])
    toks, narrow, gathered = _decode_by_hand(cfg, params, bank, n_steps,
                                             monkeypatch)
    assert gathered == [width] * (cfg.n_layers * n_steps)
    monkeypatch.setattr(mla_moe, "decode_widths", lambda pages: (pages,))
    toks_whole, whole, gathered = _decode_by_hand(cfg, params, bank, n_steps,
                                                  monkeypatch)
    assert gathered == [cfg.max_len] * (cfg.n_layers * n_steps)
    assert sorted(narrow) == sorted(whole) == list(range(n_steps))
    for step in range(n_steps):
        np.testing.assert_allclose(narrow[step][:2], whole[step][:2],
                                   atol=1e-5, rtol=1e-5)
    assert (toks[:, :2] == toks_whole[:, :2]).all()


def test_a_bank_with_no_live_slot_runs_at_the_least_width(tiny, monkeypatch):
    cfg, params = tiny
    bank = _bank_by_hand(cfg, [None, None])
    toks, logits, gathered = _decode_by_hand(cfg, params, bank, 2,
                                             monkeypatch)
    assert gathered == [_PAGE] * (cfg.n_layers * 2)
    assert toks.shape == (2, 2) and all(
        np.isfinite(lg).all() for lg in logits.values())


def test_a_context_that_crosses_a_widths_edge_inside_a_fused_dispatch(
        tiny, monkeypatch):
    """31 positions held: four fused micro-steps attend 32, 33, 34 and 35,
    so the first takes the 32-wide branch and the rest the 64-wide one,
    chosen from the carried ``pos`` on the device; the tokens are those of
    four plain steps."""
    cfg, params = tiny
    pool, btabs, tokens, pos = _bank_by_hand(cfg, [31, 12])
    fused, _, gathered = _decode_by_hand(
        cfg, params, (pool, btabs, tokens, pos), 4, monkeypatch)
    assert gathered == [32] * cfg.n_layers + [64] * (3 * cfg.n_layers)
    z = jnp.zeros_like(pos)
    step = jax.jit(lambda *a: mla_moe._decode_step_latent(
        *a, cfg=cfg, block_size=_PAGE))
    plain = []
    for i in range(4):
        tokens, pool, _ = step(params, pool, btabs, tokens, pos + i, z, z + i,
                               jnp.zeros(pos.shape, jnp.float32), z)
        plain.append(np.asarray(tokens))
    assert (fused == np.stack(plain)).all()


def _traced_program(cfg, params, program):
    """(jaxpr, the lowered module's first 200 characters, the pool) of one
    of a two-slot engine's three step programs."""
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
    return jaxpr, name, pool


@pytest.mark.parametrize("program", ["decode", "fused_2", "prefill_chunk"])
def test_the_latent_pool_is_the_carry_of_both_layer_scans(tiny, program):
    """One pool of [layers, pages, 16, latent padded to whole lane tiles],
    carried through the dense layers' scan and the expert layers' scan and
    neither scanned in nor stacked out (ROADMAP A10, as the GPT pools)."""
    cfg, params = tiny
    jaxpr, name, pool = _traced_program(cfg, params, program)
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


def _eqns(jaxpr, inside_branch=None):
    """(equation, the index of the ``cond`` branch it lies in or None) of a
    jaxpr and every jaxpr nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn, inside_branch
        for key, value in eqn.params.items():
            subs = value if isinstance(value, (tuple, list)) else (value,)
            for i, sub in enumerate(subs):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    branch = (i if eqn.primitive.name == "cond"
                              and key == "branches"
                              and len(subs) > 2 else inside_branch)
                    yield from _eqns(sub, branch)


@pytest.mark.parametrize("program", ["decode", "fused_2"])
def test_a_decode_program_gathers_the_whole_table_in_its_widest_branch_alone(
        tiny, program):
    """Each layer scan's attention is one ``cond`` over the table's four
    widths (8 pages: 1, 2, 4, 8), branch i gathering ``[slots, width_i]``
    pages of the pool and nothing wider; outside the branches the program
    gathers nothing of a table's shape, and no equation anywhere makes a
    pool-shaped copy."""
    cfg, params = tiny
    jaxpr, _, pool = _traced_program(cfg, params, program)
    widths = mla_moe.decode_widths(cfg.max_len // 16)
    assert widths == (1, 2, 4, 8)
    page = tuple(pool.shape[2:])
    gathered = {}       # branch -> the page counts its gathers take a slot
    for eqn, branch in _eqns(jaxpr):
        assert eqn.primitive.name != "copy"
        for out in eqn.outvars:
            shape = tuple(out.aval.shape)
            if eqn.primitive.name == "gather" and shape[2:] == page:
                assert shape[0] == 2
                gathered.setdefault(branch, set()).add(shape[1])
    assert gathered == {i: {w} for i, w in enumerate(widths)}


def test_a_mesh_is_refused_with_the_reason(tiny):
    cfg, params = tiny
    from tritonclient_tpu.parallel import build_mesh

    mesh = build_mesh({"tp": 2}, jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="one device"):
        GenerationEngine(mla_moe.MlaMoePaged(cfg), params, mesh=mesh)


@contextlib.contextmanager
def _counting():
    """stepscope in counters mode with empty rings; then as it was."""
    was = _stepscope.mode()
    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    try:
        yield
    finally:
        _stepscope.configure(was)
        _stepscope.reset()


def test_dispatch_records_gain_what_the_router_did(tiny):
    """stepscope on: the delivery thread reads each step's histogram behind
    its tokens and the dispatch record in the ring gains the counters, chunk
    dispatches that finish no prompt included; the GPT family's records
    gain nothing."""
    cfg, params = tiny
    with _counting():
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


def test_decode_records_stamp_the_width_taken_and_the_report_its_share(tiny):
    """A gathering family's dispatch record carries the table entries its
    attention gathered: for a decode dispatch every slot of the bank times
    the width the step's own rule takes over the longest live context,
    micro-step by micro-step, and ``kv_bytes`` are those pages' bytes.
    ``step_report.py``'s pages-read share of the table is then a quarter
    for a bank of 20 to 28 positions of 128 (2 pages of 8), 1 for one of
    over 64, and 1 for the chunks, which gather the table they are given."""
    from test_stepscope import _load_script

    cfg, params = tiny
    model = mla_moe.MlaMoePaged(cfg)
    with _counting():
        engine = GenerationEngine(model, params, max_slots=2,
                                  prefill_chunk=32, scope_name="mla_moe_w")
        try:
            for held, new in ((19, 9), (70, 6)):
                prompt = np.arange(1, held + 1, dtype=np.int32)[None]
                assert len(_collect(engine.submit(prompt, new))) == new
        finally:
            engine.shutdown()
        doc = _stepscope.dump()
    records = [r for r in doc["records"] if r["model"] == "mla_moe_w"
               and r["phase"] in ("decode", "prefill_chunk")]
    table = cfg.max_len // _PAGE
    for r in records:
        assert r["kv_bytes"] == r["pages_gathered"] * model.block_bytes(_PAGE)
        if r["phase"] == "decode":      # one request: its length the longest
            assert r["pages_gathered"] == r["slots"] * sum(
                model.pages_gathered(r["ctx_tokens"] + i, table, _PAGE)
                for i in range(r["micro_steps"]))
        else:
            assert r["pages_gathered"] == r["lanes"] * r["ctx_blocks"]
    step_report = _load_script("step_report.py", "step_report_widths")

    def share(phase, keep):
        kept = dict(doc, records=[r for r in records
                                  if r["phase"] == phase and keep(r)])
        assert kept["records"]
        analysis = step_report.analyze(step_report.load_records(kept))
        return analysis["models"]["mla_moe_w"]["phases"][phase][
            "pages_read_share"]

    assert share("decode", lambda r: r["ctx_tokens"] < 32) == 0.25
    assert share("decode", lambda r: r["ctx_tokens"] > 64) == 1.0
    assert share("prefill_chunk", lambda r: True) == 1.0


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
