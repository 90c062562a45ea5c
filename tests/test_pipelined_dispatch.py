"""Pipelined decode dispatch (PR 13): fused multi-step dispatch must be
invisible except in throughput.

Token identity is checked three ways — fused vs lockstep
(TPU_ENGINE_FUSE_STEPS=4 vs 1) vs the contiguous single-request
reference — at tp=1 and on the tp=2 virtual mesh (where GSPMD's
all-reduces are live), greedy and sampled. Cancellation must still take
effect within the in-flight window (max_inflight x fuse micro-steps).
"""

import time

import jax
import numpy as np
import pytest

from tritonclient_tpu.models import gpt
from tritonclient_tpu.models.gpt_engine import GenerationEngine


def _collect(req):
    toks = []
    while True:
        t = req.out.get(timeout=120)
        if t is None:
            return toks
        if isinstance(t, BaseException):
            raise t
        toks.append(int(t[0]))


def _reference(params, prompt, max_new, cfg, **kw):
    return [int(np.asarray(t).flatten()[0])
            for t in gpt.generate_tokens(params, prompt, max_new, cfg, **kw)]


@pytest.fixture(scope="module")
def tiny():
    cfg = gpt.gpt_tiny(max_len=64)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture()
def tp2_mesh():
    from tritonclient_tpu.parallel import build_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    return build_mesh({"dp": 1, "tp": 2}, jax.devices()[:2])


def _run_engine(cfg, params, prompts, max_news, mesh=None, **samp):
    engine = GenerationEngine(cfg, params, max_slots=4, mesh=mesh)
    try:
        reqs = [engine.submit(p, n, **samp)
                for p, n in zip(prompts, max_news)]
        return [_collect(r) for r in reqs]
    finally:
        engine.shutdown()


def _prompts(cfg, rng, lens):
    return [rng.integers(0, cfg.vocab_size, (1, l)).astype(np.int32)
            for l in lens]


@pytest.mark.parametrize("samp", [
    {},
    {"temperature": 0.7, "top_k": 20, "seed": 1234},
], ids=["greedy", "sampled"])
def test_fused_matches_lockstep_and_reference_tp1(tiny, monkeypatch, samp):
    cfg, params = tiny
    rng = np.random.default_rng(5)
    prompts = _prompts(cfg, rng, [7, 16, 23])
    max_news = [14, 11, 9]
    refs = [_reference(params, p, n, cfg, **samp)
            for p, n in zip(prompts, max_news)]
    monkeypatch.setenv("TPU_ENGINE_FUSE_STEPS", "1")
    lockstep = _run_engine(cfg, params, prompts, max_news, **samp)
    monkeypatch.setenv("TPU_ENGINE_FUSE_STEPS", "4")
    fused = _run_engine(cfg, params, prompts, max_news, **samp)
    assert fused == lockstep == refs


@pytest.mark.parametrize("samp", [
    {},
    {"temperature": 0.7, "top_k": 20, "seed": 99},
], ids=["greedy", "sampled"])
def test_fused_matches_lockstep_tp2(tiny, tp2_mesh, monkeypatch, samp):
    """On the tp=2 mesh the fused window's streams equal the lockstep
    run's exactly: the scan body is the single step, all-reduces
    included."""
    cfg, params = tiny
    rng = np.random.default_rng(6)
    prompts = _prompts(cfg, rng, [9, 17])
    max_news = [10, 8]
    monkeypatch.setenv("TPU_ENGINE_FUSE_STEPS", "1")
    plain = _run_engine(cfg, params, prompts, max_news, mesh=tp2_mesh,
                        **samp)
    monkeypatch.setenv("TPU_ENGINE_FUSE_STEPS", "4")
    fused = _run_engine(cfg, params, prompts, max_news, mesh=tp2_mesh,
                        **samp)
    assert fused == plain


def test_cancel_takes_effect_within_inflight_window(tiny, monkeypatch):
    """With fused dispatch the cancel poll happens at the loop top, so a
    cancel lands within max_inflight x fuse micro-steps — tokens already
    dispatched may still arrive, but the stream must terminate and the
    slot must free long before max_new."""
    cfg, params = tiny
    monkeypatch.setenv("TPU_ENGINE_FUSE_STEPS", "4")
    engine = GenerationEngine(cfg, params, max_slots=2)
    try:
        prompt = np.arange(8, dtype=np.int32).reshape(1, 8)
        max_new = cfg.max_len - 9  # long enough to straddle many windows
        req = engine.submit(prompt, max_new)
        got = [req.out.get(timeout=120)]  # first token: engine is rolling
        req.cancelled = True
        while True:
            t = req.out.get(timeout=120)
            if t is None or isinstance(t, BaseException):
                break
            got.append(t)
        # In-flight window: pipelining may deliver tokens dispatched
        # before the cancel was observed, but never an unbounded tail.
        window = engine._dist.max_inflight * engine._fuse_steps + \
            engine._fuse_steps
        assert len(got) <= 1 + window
        deadline = time.time() + 30
        while time.time() < deadline:
            if all(r is None for r in engine._slot_req):
                break
            time.sleep(0.02)  # tpulint: disable=TPU001
        assert all(r is None for r in engine._slot_req)
    finally:
        engine.shutdown()
