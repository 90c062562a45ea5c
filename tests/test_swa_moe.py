"""The window/global grouped-query routed family through the paged engine,
at a small size on the CPU (float32, so the tolerances can be tight):
prefill in chunks and then decode through the two kinds of cache, unfused
and fused, against the plain reference's full forward pass ON LOGITS; the
share of the experts; what the cache holds by kind; no prefix sharing; the
gRPC front end. Nothing here is a device number."""

import os
import queue
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import reference_swa_moe  # noqa: E402
from benchmarks.costs_swa_moe import SwaMoeShape  # noqa: E402
from tritonclient_tpu import _stepscope  # noqa: E402
from tritonclient_tpu.models import mla_moe, swa_moe  # noqa: E402
from tritonclient_tpu.models.gpt_engine import GenerationEngine  # noqa: E402

# The served logits against the float32 reference's, largest difference over
# every judged position and the whole vocabulary. Both sides are float32 on
# the CPU here and differ only in the order of their sums (the kernel's
# running softmax over chunks of pages against a dense masked one, grouped
# against looped experts): 2e-6 to 8e-6 is read on logits of size 4. A key
# one position outside the window let in, a ring page read after it was
# overwritten, or a rotation of interleaved pairs for the half-split one
# moves the logits by 1e-2 and more, so 1e-4 holds the first and fails the
# others.
LOGIT_TOLERANCE = 1e-4
_BLOCK, _CHUNK = 8, 16      # ring: (24 + 16) / 8 + 1 = 6 pages, 48 positions


def shape_of(cfg: swa_moe.SwaMoeConfig, **changes) -> SwaMoeShape:
    return SwaMoeShape(**dict(dict(
        n_layer=cfg.n_layers, n_dense_layer=cfg.n_dense_layers,
        layer_kinds=cfg.layer_kinds, window=cfg.window, d_model=cfg.d_model,
        n_head=cfg.n_heads, n_kv_head=cfg.n_kv_heads, head_dim=cfg.head_dim,
        d_ff=cfg.d_ff, n_experts=cfg.n_experts,
        experts_held=cfg.experts_held, first_expert=cfg.first_expert,
        experts_per_token=cfg.experts_per_token, d_expert=cfg.d_expert,
        n_shared_experts=cfg.n_shared_experts,
        routed_scaling_factor=cfg.routed_scaling_factor,
        rope_theta=cfg.rope_theta, rms_norm_eps=cfg.rms_norm_eps,
        n_positions=cfg.max_len, vocab_size=cfg.vocab_size,
        dtype="float32"), **changes))


@pytest.fixture(scope="module")
def tiny():
    """hidden 64, 8 query heads on 2 K/V heads of 16, two periods LLLG of
    window 24, 1 dense + 7 expert layers, 8 experts top-2 of which experts
    2-4 are held, a seeded non-zero bias."""
    cfg = swa_moe.swa_moe_tiny()
    params = swa_moe.init_params(jax.random.PRNGKey(3), cfg)
    assert cfg.layer_kinds.count("global") == 2 and cfg.window == 24
    assert cfg.n_heads // cfg.n_kv_heads == 4
    assert (cfg.n_experts, cfg.experts_held, cfg.first_expert) == (8, 3, 2)
    assert params["moe"]["w_gate"].shape[:2] == (7, 3)
    assert params["moe"]["router"].shape == (7, 64, 8)
    return cfg, params


def _engine(cfg, params, slots=2, **kw):
    return GenerationEngine(
        swa_moe.SwaMoePaged(cfg, slots, _CHUNK), params, max_slots=slots,
        block_size=_BLOCK, prefill_chunk=_CHUNK, **kw)


def _collect(req):
    toks = []
    while True:
        t = req.out.get(timeout=300)
        if t is None:
            return toks
        if isinstance(t, BaseException):
            raise t
        toks.append(int(t[0]))


def _spy_on_logits(monkeypatch):
    """{sampling step: the logits slot 0 / lane 0 was picked from}: step 0
    is the last prefill chunk's (every chunk says 0: the last one's stay)."""
    seen = {}
    pick = swa_moe._pick

    def keep(logits, steps):
        seen[int(steps[0])] = np.asarray(logits[0])

    def spy(logits, seeds, steps, temps, topks):
        jax.debug.callback(keep, logits, steps)
        return pick(logits, seeds, steps, temps, topks)

    monkeypatch.setattr(swa_moe, "_pick", spy)
    return seen


@pytest.mark.parametrize("prompt_len,n_new,fuse", [
    (29, 12, 1), (29, 12, 4), (100, 30, 4), (64, 16, 1)],
    ids=["crosses_the_window", "crosses_the_window_fused",
         "wraps_the_ring_twice", "ends_on_a_page_edge"])
def test_chunked_prefill_then_decode_agrees_with_the_reference_on_logits(
        tiny, monkeypatch, prompt_len, n_new, fuse):
    """Prompts in chunks of 16 over pages of 8, then decode through the
    cache, fused and not. 29 + 12 crosses the window of 24 and the chunk's
    edge inside a page; 100 + 30 writes 130 positions through a ring of 48
    (it wraps twice and more) while the global layers keep them all; 64 +
    16 starts decode on a page edge and ends on one (position 79 is a
    page's last). Every served token's logits are the reference's full
    forward pass's: a dense mask, no cache, the held experts in a loop."""
    cfg, params = tiny
    seen = _spy_on_logits(monkeypatch)
    monkeypatch.setenv("TPU_ENGINE_FUSE_STEPS", str(fuse))
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, (1, prompt_len)).astype(np.int32)
    engine = _engine(cfg, params)
    try:
        assert engine._ring == 6
        tokens = _collect(engine.submit(prompt, n_new))
    finally:
        engine.shutdown()
    jax.effects_barrier()
    assert len(tokens) == n_new
    sequence = np.concatenate([prompt[0], np.asarray(tokens, np.int32)])
    reference = np.asarray(reference_swa_moe.logits(
        params, sequence, shape_of(cfg)))
    worst = 0.0
    for step in range(n_new):          # later steps are the pipeline's surplus
        at = prompt_len - 1 + step
        worst = max(worst, float(np.abs(seen[step] - reference[at]).max()))
        assert int(np.argmax(reference[at])) == tokens[step]
    assert worst < LOGIT_TOLERANCE


def test_the_reference_is_sensitive_to_what_the_tolerance_has_to_catch(tiny):
    """The faults the tolerance is set against, made in the reference: a
    window one key longer, and the global mask in a window layer, each move
    the logits past 1e-2 at positions past the window."""
    cfg, params = tiny
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, cfg.vocab_size, (64,)).astype(np.int32)
    right = np.asarray(reference_swa_moe.logits(params, tokens, shape_of(cfg)))
    longer = np.asarray(reference_swa_moe.logits(
        params, tokens, shape_of(cfg, window=cfg.window + 1)))
    all_global = np.asarray(reference_swa_moe.logits(
        params, tokens, shape_of(cfg, layer_kinds=("global",) * 8)))
    inside = slice(0, cfg.window)       # rows whose window is the context
    np.testing.assert_allclose(longer[inside], right[inside], atol=1e-5)
    np.testing.assert_allclose(all_global[inside], right[inside], atol=1e-5)
    assert np.abs(longer[40:] - right[40:]).max() > 1e-2
    assert np.abs(all_global[40:] - right[40:]).max() > 1e-2


# --------------------------------------------------------------------------- #
# the share                                                                   #
# --------------------------------------------------------------------------- #


def test_all_shares_parts_and_the_shared_expert_once_are_the_uncut_layer():
    """THE SHARE TEST. A tiny layer of 8 experts, top 2, cut into the
    shares [0, 3), [3, 6), [6, 8): each share routes over all 8, computes
    its own experts' part with the weights normalised over both chosen, and
    leaves out the rest. The parts of all shares, plus the shared expert
    counted once, sum to the uncut reference's layer (all 8 held); so do
    the reference's own parts. A share's histogram counts its held experts
    and the pairs that fell elsewhere; over the shares every pair is counted
    once as held."""
    cfg = swa_moe.swa_moe_tiny(first_expert=0)
    rng = np.random.default_rng(2)
    d, fe, e, k = cfg.d_model, cfg.d_expert, cfg.n_experts, 2

    def matrix(*shape):
        return jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[-2]),
                           jnp.float32)

    full = {"router": matrix(d, e),
            "router_bias": jnp.asarray(0.05 * rng.standard_normal(e),
                                       jnp.float32),
            "w_gate": matrix(1, e, d, fe), "w_up": matrix(1, e, d, fe),
            "w_down": matrix(1, e, fe, d),
            "ws_gate": matrix(d, fe), "ws_up": matrix(d, fe),
            "ws_down": matrix(fe, d)}
    x = jnp.asarray(rng.standard_normal((10, d)), jnp.float32)
    live = jnp.ones((10,), bool)
    shared = reference_swa_moe._swiglu(
        x, full["ws_gate"], full["ws_up"], full["ws_down"], False)
    uncut = shape_of(cfg, experts_held=e, first_expert=0)
    want = reference_swa_moe._routed(x, full, 0, uncut, False) + shared
    assert float(jnp.abs(want - shared).max()) > 0.1    # the experts matter

    experts, weights = mla_moe.route(x, full["router"], full["router_bias"],
                                     cfg)
    np.testing.assert_allclose(weights.sum(-1), cfg.routed_scaling_factor,
                               rtol=1e-6)
    program, reference, held_pairs = shared, shared, 0
    for first, held in ((0, 3), (3, 3), (6, 2)):
        import dataclasses

        share_cfg = dataclasses.replace(cfg, first_expert=first,
                                        experts_held=held)
        part = {key: full[key][:, first:first + held]
                for key in ("w_gate", "w_up", "w_down")}
        y, counts = mla_moe.routed_experts(
            x, experts, weights, live, mla_moe.expert_banks(part), share_cfg)
        assert counts.shape == (held + 1,)
        assert int(counts.sum()) == 10 * k          # held here or elsewhere
        held_pairs += int(counts[:-1].sum())
        program = program + y
        reference = reference + reference_swa_moe._routed(
            x, dict(full, **part), 0,
            shape_of(cfg, experts_held=held, first_expert=first), False)
    assert held_pairs == 10 * k
    np.testing.assert_allclose(program, want, atol=2e-5)
    np.testing.assert_allclose(reference, want, atol=2e-5)


def test_a_share_that_holds_nothing_chosen_adds_nothing_and_reads_no_expert(
        tiny):
    cfg, params = tiny
    x = jnp.ones((3, cfg.d_model), jnp.float32)
    experts = jnp.asarray([[0, 1], [6, 7], [5, 0]], jnp.int32)   # none of 2-4
    weights = jnp.full((3, 2), 1.25, jnp.float32)
    y, counts = mla_moe.routed_experts(
        x, experts, weights, jnp.asarray([True, True, False]),
        mla_moe.expert_banks(params["moe"]), cfg, 3)
    assert not np.asarray(y).any()
    assert counts.tolist() == [0, 0, 0, 4]      # two live rows' pairs


# --------------------------------------------------------------------------- #
# the cache by kind                                                           #
# --------------------------------------------------------------------------- #


def test_the_pools_have_two_regions_and_a_table_row_is_ring_then_table(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params, slots=2)
    try:
        ring, width = engine._ring, cfg.max_len // _BLOCK
        assert (ring, engine._table_width) == (6, 6 + width)
        n_blocks = 1 + 2 * width
        k_pool, v_pool = engine._pools
        # 2 global layers of n_blocks pages, 6 window layers of a scratch
        # page and 2 rings
        assert k_pool.shape == v_pool.shape == (
            1, 2 * n_blocks + 6 * (1 + 2 * ring), _BLOCK, 2 * 16)
        page = 2 * _BLOCK * 32 * 4              # keys and values, float32
        assert engine._kind_bytes == (2 * page, 6 * page)
        assert engine._block_kv_bytes == engine._model.block_bytes(_BLOCK)
        row = engine._table_row(1, [7, 9, 4], width)
        assert row[:ring].tolist() == [7, 8, 9, 10, 11, 12]   # 1 + 1 * 6 + j
        assert row[ring:ring + 4].tolist() == [7, 9, 4, 0]
        pages = engine._model._pages(_BLOCK, k_pool)
        assert pages.n_blocks == n_blocks and pages.ring_region == 13
        assert pages.is_global.tolist() == [False] * 3 + [True] + [
            False] * 3 + [True]
        assert pages.base.tolist() == [
            2 * n_blocks, 2 * n_blocks + 13, 2 * n_blocks + 26, 0,
            2 * n_blocks + 39, 2 * n_blocks + 52, 2 * n_blocks + 65, n_blocks]
    finally:
        engine.shutdown()


def test_a_requests_held_bytes_in_window_layers_stop_growing(tiny):
    """Admission's reckoning and the dispatch records' agree: the global
    layers hold a page a block of positions, the window layers at most the
    ring, whatever the length; a window layer's kernel reads the window's
    pages and not the context's."""
    cfg, params = tiny
    was = _stepscope.mode()
    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    try:
        engine = _engine(cfg, params, scope_name="swa_moe_test")
        try:
            g, w = engine._kind_bytes
            held = [engine.held_bytes(n) for n in (1, 5, 6, 7, 20, 32)]
            assert [h[0] for h in held] == [n * g for n in (1, 5, 6, 7, 20, 32)]
            assert [h[1] for h in held] == [w, 5 * w, 6 * w, 6 * w, 6 * w,
                                            6 * w]
            estimate = swa_moe.SwaMoeEngineModel.estimate_request_bytes
            model = type("M", (), {"engine": engine})()
            assert estimate(model, {"INPUT_IDS": [1, 184]}) == 25 * g + 6 * w
            prompt = np.arange(1, 101, dtype=np.int32).reshape(1, 100)
            assert len(_collect(engine.submit(prompt, 20))) == 20
            deadline = time.time() + 10
            while time.time() < deadline:
                records = [r for r in _stepscope.dump()["records"]
                           if r["model"] == "swa_moe_test"
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
    assert len(chunks) == 7 and decodes
    # 120 positions reserved: 15 pages in the global layers, the ring in
    # the window layers, in every record of the request
    for r in chunks + decodes:
        assert r["kv_held_global_bytes"] == 15 * g
        assert r["kv_held_window_bytes"] == 6 * w
        assert r["ctx_pages"] == r["ctx_pages_global"]
        assert r["kv_bytes"] == (r["ctx_pages_global"] * g
                                 + r["ctx_pages_window"] * w)
    # a chunk of 16 rows ending at 16, 32, ... reads the pages under
    # [end - 16 + 1 - 24, end): all the context in a global layer
    assert [r["ctx_pages_global"] for r in chunks] == [2, 4, 6, 8, 10, 12, 13]
    assert [r["ctx_pages_window"] for r in chunks] == [2, 4, 5, 5, 5, 5, 4]
    for r in decodes:
        steps = r["micro_steps"]
        assert r["ctx_pages_global"] >= steps * 13
        assert steps * 3 <= r["ctx_pages_window"] <= steps * 4
    # the share's routing counters, through the helper both families use
    layers, k = cfg.n_moe_layers, cfg.experts_per_token
    for r in chunks + decodes:
        assert set(_stepscope.ROUTING_FIELDS) <= set(r)
        assert r["experts_held"] == layers * 3 * r["micro_steps"]
        pairs = r["expert_load_mean"] * r["experts_held"]
        assert abs(pairs + r["pairs_elsewhere"]
                   - layers * k * r["routed_tokens"]) < 1e-6
        assert r["pairs_elsewhere"] > 0
        assert r["expert_passes"] == r["experts_hit"]     # f is whole here


def test_a_repeated_prompt_is_computed_again_and_gives_the_same_logits(
        tiny, monkeypatch):
    """The family declines prefix sharing: the second request of the same
    prompt matches no page (its window layers' keys are in no shared page),
    registers none, and its logits are the first's."""
    cfg, params = tiny
    seen = _spy_on_logits(monkeypatch)
    prompt = np.arange(3, 60, dtype=np.int32).reshape(1, 57)
    engine = _engine(cfg, params)
    try:
        assert engine._model.shares_prefix is False
        first = _collect(engine.submit(prompt, 6))
        jax.effects_barrier()
        first_logits = {step: seen[step].copy() for step in range(6)}
        seen.clear()
        second = _collect(engine.submit(prompt, 6))
        jax.effects_barrier()
        events = engine._prefix.snapshot_events()
        cached = engine._prefix.evictable_count
    finally:
        engine.shutdown()
    assert second == first
    for step in range(6):
        np.testing.assert_array_equal(seen[step], first_logits[step])
    assert cached == 0 and not any(events.values())


def test_requests_batched_together_get_the_tokens_they_get_alone(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, (1, n)).astype(np.int32)
               for n in (9, 61, 34)]
    engine = _engine(cfg, params)
    try:
        alone = [_collect(engine.submit(p, 7)) for p in prompts]
        together = [engine.submit(p, 7) for p in prompts]
        assert [_collect(r) for r in together] == alone
    finally:
        engine.shutdown()
    assert engine._pool.used_count == 1          # the scratch page


@pytest.mark.parametrize("program", ["decode", "fused_2", "prefill_chunk"])
def test_the_pools_are_the_carry_of_both_layer_scans(tiny, program):
    """Two flat pools carried through the dense layers' scan and the expert
    layers' scan and neither scanned in nor stacked out; the executables
    carry the family's names; the prefill takes the whole table."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    try:
        pools = engine._pools
        bank = (engine.params, *pools, engine._btabs, engine._tokens,
                engine._pos, engine._seeds, engine._steps, engine._temps,
                engine._topks)
        z = jnp.zeros((1,), jnp.int32)
        chunk = (engine.params, *pools, jnp.zeros((1, _CHUNK), jnp.int32),
                 jnp.zeros((1, engine._table_width), jnp.int32), z,
                 jnp.ones((1,), jnp.int32), z, jnp.zeros((1,), jnp.float32), z)
        fn, args = {
            "decode": (engine._step, bank),
            "fused_2": (engine._multi_step_fn(2), bank),
            "prefill_chunk": (engine._prefill_chunk_fn, chunk),
        }[program]
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
        name = fn.lower(*args).as_text()[:200]
    finally:
        engine.shutdown()
    assert {"decode": "module @jit_swa_moe_decode_step ",
            "fused_2": "module @jit_swa_moe_decode_fused_2 ",
            "prefill_chunk": "module @jit_swa_moe_prefill_chunk ",
            }[program] in name
    from test_gpt_engine import _scan_eqns

    shape = tuple(pools[0].shape)
    layer_scans = 0
    for eqn in _scan_eqns(jaxpr):
        n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
        carried = [tuple(v.aval.shape)
                   for v in eqn.invars[n_consts:n_consts + n_carry]]
        scanned = [tuple(v.aval.shape)
                   for v in eqn.invars[n_consts + n_carry:]]
        stacked = [tuple(v.aval.shape) for v in eqn.outvars[n_carry:]]
        assert shape not in scanned + stacked
        if any(len(s) == 3 and s[1:] == (cfg.d_model, 8 * 16)
               for s in scanned):          # a scan over layers' wq
            layer_scans += 1
            assert carried.count(shape) == 2
    assert layer_scans == 2


def test_a_prefill_is_one_program_a_lane_bucket_whatever_the_context(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    try:
        assert engine._model.prefill_by_context is False
        engine.warm_prefill(ctx_blocks=(1, 3, 9, 30))
        assert engine._prefill_chunk_fn._cache_size() == 2      # lanes 1, 2
        prompt = np.arange(1, 150, dtype=np.int32).reshape(1, 149)
        assert len(_collect(engine.submit(prompt, 3))) == 3
        assert engine._prefill_chunk_fn._cache_size() == 2
    finally:
        engine.shutdown()


def test_a_mesh_is_refused_with_the_reason(tiny):
    cfg, params = tiny
    from tritonclient_tpu.parallel import build_mesh

    mesh = build_mesh({"tp": 2}, jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="one device"):
        GenerationEngine(swa_moe.SwaMoePaged(cfg, 2, _CHUNK), params,
                         mesh=mesh)


def test_a_config_is_refused_where_the_family_is_not_what_it_says():
    base = swa_moe.swa_moe_tiny()
    import dataclasses

    for change in ({"layer_kinds": ("window",) * 8},
                   {"layer_kinds": ("window", "global")},
                   {"n_kv_heads": 3}, {"first_expert": 6}):
        with pytest.raises(ValueError):
            dataclasses.replace(base, **change)


# --------------------------------------------------------------------------- #
# the front end                                                               #
# --------------------------------------------------------------------------- #


def test_the_family_is_served_through_the_grpc_front_end(tiny):
    """gRPC stream -> InferenceServer -> GenerationEngine -> SwaMoePaged, by
    the entry points the other families use: two streams at once, each
    token a response, the tokens those the engine gives directly."""
    import tritonclient_tpu.grpc as grpcclient
    from tritonclient_tpu.server import InferenceServer

    cfg, params = tiny
    model = swa_moe.SwaMoeEngineModel(cfg, params=params, max_slots=2,
                                      block_size=_BLOCK,
                                      prefill_chunk=_CHUNK)
    assert model.name == "swa_moe_engine"
    prompts = [np.arange(5, 5 + n, dtype=np.int32).reshape(1, n)
               for n in (40, 75)]
    served = []
    with InferenceServer(models=[model], http=False) as server:
        clients = []
        for prompt in prompts:
            client = grpcclient.InferenceServerClient(server.grpc_address)
            responses: "queue.Queue" = queue.Queue()
            client.start_stream(
                callback=lambda result, error, q=responses: q.put(
                    (result, error)))
            inputs = []
            for name, value in (("INPUT_IDS", prompt),
                                ("MAX_TOKENS", np.array([9], np.int32))):
                tensor = grpcclient.InferInput(name, list(value.shape),
                                               "INT32")
                tensor.set_data_from_numpy(value)
                inputs.append(tensor)
            client.async_stream_infer(model.name, inputs,
                                      enable_empty_final_response=True)
            clients.append((client, responses))
        for client, responses in clients:
            tokens = []
            while True:
                result, error = responses.get(timeout=300)
                assert error is None, error
                out = result.as_numpy("OUTPUT_IDS")
                if out is not None and out.size:
                    tokens.append(int(out.reshape(-1)[0]))
                final = result.get_response().parameters.get(
                    "triton_final_response")
                if final is not None and final.bool_param:
                    break
            served.append(tokens)
            client.stop_stream()
            client.close()
        direct = [_collect(model.engine.submit(p, 9)) for p in prompts]
    model.engine.shutdown()
    assert served == direct and all(len(t) == 9 for t in served)
