"""The routed experts' product (ops/grouped_experts.py) under the Pallas
interpreter, against a plain loop over the experts in float32; the rule
that cuts a call's shapes; the pass counter; and the one jitted function
(models/mla_moe.py ``routed_experts``) that keeps the kernel's trace to once
a shape a process."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tritonclient_tpu.models import mla_moe
from tritonclient_tpu.ops import grouped_experts
from tritonclient_tpu.ops.grouped_experts import (Tiling, grouped_swiglu,
                                                  passes, plan_visits, tiling)

_LAYERS = 3


@pytest.fixture(params=["whole", "tiled"])
def cut(request, monkeypatch):
    """How the rule cuts a case: as it cuts any call (``f`` whole, one
    row tile at these sizes), or with the module's sizes shrunk so that the
    same few hundred rows take ``f`` in tiles of 128 columns, rows in tiles
    of 32 and products of 16 rows (the shape of K-EXAONE's calls at a size
    the interpreter can run)."""
    if request.param == "tiled":
        monkeypatch.setattr(grouped_experts, "_SUB_ROWS", 16)
        monkeypatch.setattr(grouped_experts, "_TILE_ROWS", 32)
        monkeypatch.setattr(grouped_experts, "_BANK_BYTES", 6 * 128 * 128 * 2)
    return request.param


def _banks(held, d, f, dtype, layers=_LAYERS):
    keys = jax.random.split(jax.random.PRNGKey(d + f), 3)
    groups = layers * held
    return tuple(
        (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)
        for k, shape, scale in zip(
            keys, [(groups, d, f), (groups, d, f), (groups, f, d)],
            [d ** -0.5, d ** -0.5, f ** -0.5]))


def _rows(n, d, dtype, live):
    """``n`` rows of which the first ``live`` belong to an expert; the rest
    (rows without a request, pairs held elsewhere: the caller sorts them
    last) are NaN, which a product that touched them would spread."""
    x = jax.random.normal(jax.random.PRNGKey(n), (n, d), jnp.float32)
    return x.at[live:].set(jnp.nan).astype(dtype)


def _loop(rows, counts, w_gate, w_up, w_down, layer):
    """Expert by expert in float32 NumPy, at the stated precision: operands
    as stored, float32 sums, the hidden rounded to the rows' type."""
    x = np.asarray(rows.astype(jnp.float32))
    out = np.zeros(x.shape, np.float32)
    start = 0
    for e, n in enumerate(counts):
        group = layer * len(counts) + e
        gate, up, down = (np.asarray(w[group].astype(jnp.float32))
                          for w in (w_gate, w_up, w_down))
        mine = x[start:start + n]
        pre = mine @ gate
        hidden = pre / (1.0 + np.exp(-pre)) * (mine @ up)
        hidden = np.asarray(jnp.asarray(hidden).astype(rows.dtype)
                            .astype(jnp.float32))
        out[start:start + n] = hidden @ down
        start += n
    return out[:start]


def _agrees(rows, counts, banks, layer, dtype):
    got = np.asarray(grouped_swiglu(
        rows, jnp.asarray(counts, jnp.int32), *banks, layer))
    want = _loop(rows, counts, *banks, layer)
    assert got.shape == rows.shape and got.dtype == np.float32
    # float32 operands multiply at the highest precision; a bfloat16 hidden
    # can round the other way where the two sums differ in their last bit.
    tol = (1e-5 if dtype == jnp.float32 else 1e-2) * np.abs(want).max()
    np.testing.assert_allclose(got[:len(want)], want, atol=tol, rtol=0)


# (name, rows in the call, rows of each held expert)
_CASES = [
    # a decode step's pairs: fewer rows than a product takes
    ("decode_64", 64, [3, 0, 9, 1, 0, 20, 7, 24]),
    ("decode_32", 32, [0, 5, 0, 27]),
    # empty experts at the front, in the middle and at the end; dead rows
    ("empty_ends", 200, [0, 0, 70, 0, 0, 61, 0]),
    # an expert over two row tiles (three where tiles are 32 rows), and
    # one that ends exactly where a tile does
    ("spans_tiles", 600, [100, 412, 0, 50]),
    ("one_expert_all_rows", 96, [0, 96, 0]),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name,n_rows,counts", _CASES,
                         ids=[c[0] for c in _CASES])
def test_kernel_is_the_loop_over_experts(cut, name, n_rows, counts, dtype):
    """Every live row is its expert's SwiGLU of layer 1's bank (the other
    layers' experts are never the answer), whatever NaN the dead rows past
    the live ones hold."""
    held, d, f = len(counts), 128, 512
    if cut == "tiled":
        assert tiling(n_rows, held, d, f, dtype)[:3] == (
            32, min(16, n_rows), 128)
    else:
        assert tiling(n_rows, held, d, f, dtype).tf == f
    banks = _banks(held, d, f, dtype)
    _agrees(_rows(n_rows, d, dtype, sum(counts)), counts, banks, 1, dtype)


def test_rows_over_two_tiles_of_the_real_height():
    """At the rule's own sizes: 700 rows are two tiles of 512, products of
    128 rows; expert 2 has rows in both, expert 0 ends inside a product's
    rows and the last live row is not the call's last."""
    counts, d, f = [300, 0, 290, 60], 128, 256
    assert tiling(700, 4, d, f, jnp.float32) == Tiling(512, 128, f, 5)
    banks = _banks(4, d, f, jnp.float32)
    _agrees(_rows(700, d, jnp.float32, 650), counts, banks, 2, jnp.float32)


def test_layer_is_traced_and_picks_its_bank(cut):
    """One program, ``layer`` an argument: each layer's call reads that
    layer's experts out of the whole banks."""
    counts, d, f = [10, 0, 30], 128, 512
    banks = _banks(3, d, f, jnp.float32)
    rows = _rows(48, d, jnp.float32, 40)
    program = jax.jit(lambda layer: grouped_swiglu(
        rows, jnp.asarray(counts, jnp.int32), *banks, layer))
    outs = [np.asarray(program(jnp.int32(layer)))[:40]
            for layer in range(_LAYERS)]
    for layer, got in enumerate(outs):
        want = _loop(rows, counts, *banks, layer)
        np.testing.assert_allclose(got, want, atol=1e-5 * np.abs(want).max(),
                                   rtol=0)
    assert not np.allclose(outs[0], outs[1])


def test_no_live_row_is_one_visit_of_no_rows(cut):
    """Nothing routed here (an idle engine's step, a chunk whose pairs all
    fell to other chips): the call runs, and the plan is one visit that
    covers no row."""
    d, f = 128, 512
    counts = jnp.zeros((5,), jnp.int32)
    banks = _banks(5, d, f, jnp.float32)
    out = grouped_swiglu(_rows(64, d, jnp.float32, 0), counts, *banks, 1)
    assert out.shape == (64, d)
    plan = plan_visits(counts, 1, tiling(64, 5, d, f, jnp.float32))
    assert int(plan.n_visits) == 1
    assert int(plan.lo_of[0]) == 0 and int(plan.hi_of[0]) == 0


def test_plan_visits_tile_by_tile_in_sorted_order():
    """Rows 0-99 expert 0, 100-511 expert 1 (tile 0), 512 expert 1 (tile
    1), 513-562 expert 3: four visits, the maps one entry past the longest
    grid and every entry past the live ones the last live one."""
    cut = Tiling(512, 128, 256, 5)
    plan = plan_visits(jnp.asarray([100, 413, 0, 50], jnp.int32), 2, cut)
    assert int(plan.n_visits) == 4
    assert plan.group_of.shape == (cut.visits + 1,)
    np.testing.assert_array_equal(plan.group_of, [8, 9, 9, 11, 11, 11])
    np.testing.assert_array_equal(plan.tile_of, [0, 0, 1, 1, 1, 1])
    np.testing.assert_array_equal(plan.lo_of, [0, 100, 0, 1, 1, 1])
    np.testing.assert_array_equal(plan.hi_of, [100, 512, 1, 51, 51, 51])


@pytest.mark.parametrize("rows,held,d,f,want", [
    # joyai-llm-flash: 256 experts of 2048 x 768, a decode step's 64 pairs
    # and a chunk of one lane; whole f
    (64, 256, 2048, 768, Tiling(64, 64, 768, 64)),
    (2048, 256, 2048, 768, Tiling(512, 128, 768, 259)),
    (16384, 256, 2048, 768, Tiling(512, 128, 768, 287)),
    # xing4.0-29b-a4b: 64 of 3584 x 1024, 32 pairs a decode step; whole f
    (32, 64, 3584, 1024, Tiling(32, 32, 1024, 32)),
    (8192, 64, 3584, 1024, Tiling(512, 128, 1024, 79)),
    # k-exaone-236b-a23b: 16 held of 6144 x 2048: f in four tiles of 512
    (64, 16, 6144, 2048, Tiling(64, 64, 512, 16)),
    (4096, 16, 6144, 2048, Tiling(512, 128, 512, 23)),
])
def test_tiling_at_the_cells_shapes(rows, held, d, f, want):
    assert tiling(rows, held, d, f, jnp.bfloat16) == want


def test_tiling_fits_fast_memory_at_the_cells_shapes():
    """What the blocks of a call take, double buffered, with the float32
    temporaries of a product, stays under the limit the call asks for."""
    for rows, held, d, f in [(16384, 256, 2048, 768), (8192, 64, 3584, 1024),
                             (32768, 16, 6144, 2048)]:
        cut = tiling(rows, held, d, f, jnp.bfloat16)
        blocks = 2 * (3 * d * cut.tf * 2 + cut.tm * d * (2 + 4))
        temporaries = 4 * cut.sub * (d + cut.tf) * 4
        assert blocks + temporaries <= grouped_experts._VMEM_BYTES
        assert f % cut.tf == 0 and cut.tm % cut.sub == 0


def test_passes_against_a_hand_count():
    # f whole (joyai's widths): every hit expert once, however its rows lie
    counts = np.zeros((2, 256), np.int64)
    counts[0, [3, 9, 200]] = [600, 1, 40]
    counts[1, [0, 255]] = [2000, 48]
    assert passes(counts, 2048, 768, jnp.bfloat16) == 5
    # f in tiles (k-exaone's): once a (row tile of 512, expert) visit.
    # call 0: expert 0 rows 0-599 (tiles 0, 1), expert 2 rows 600-609 (tile
    # 1), expert 3 rows 610-1111 (tiles 1, 2): 5; call 1: expert 15 rows
    # 0-511, one tile: 1; call 2: nothing live.
    counts = np.zeros((3, 16), np.int64)
    counts[0, [0, 2, 3]] = [600, 10, 502]
    counts[1, 15] = 512
    assert passes(counts, 6144, 2048, jnp.bfloat16) == 6
    assert passes(counts[2], 6144, 2048, jnp.bfloat16) == 0
    # and it is the plan's own visits
    cut = tiling(4096, 16, 6144, 2048, jnp.bfloat16)
    plan = plan_visits(jnp.asarray(counts[0], jnp.int32), 0, cut)
    assert int(plan.n_visits) == 5


def test_two_programs_of_the_same_shapes_trace_the_kernel_once(monkeypatch):
    """What ``routed_experts``' one jitted function is for: the kernel's
    body (and the sort and the plan around it) is traced once for all the
    call sites of all the outer programs whose calls have the same shapes
    (a step program calls the layer from its scan and from each micro-step
    of a fused decode; an engine holds twenty-odd programs), and once more
    for another shape."""
    traced = []
    body = grouped_experts._kernel

    def counted(*refs, **static):
        traced.append(refs[4].shape)       # the rows' block
        return body(*refs, **static)

    monkeypatch.setattr(grouped_experts, "_kernel", counted)
    mla_moe._routed_experts.clear_cache()
    cfg = dataclasses.replace(mla_moe.mla_moe_tiny(), n_experts=4,
                              experts_per_token=2, d_model=128, d_expert=384)
    banks = dict(zip(("w_gate", "w_up", "w_down"),
                     _banks(4, 128, 384, jnp.float32, layers=2)))

    def routed(x, layer):
        experts = jnp.stack([jnp.arange(len(x)) % 4,
                             (jnp.arange(len(x)) + 1) % 4], axis=1)
        weights = jnp.full(experts.shape, 0.5, jnp.float32)
        live = jnp.arange(len(x)) != 3
        return mla_moe.routed_experts(x, experts, weights, live, banks, cfg,
                                      layer)[0]

    @jax.jit
    def scanned(x):
        def layer(carry, i):
            return carry + routed(x, i), None
        return jax.lax.scan(layer, jnp.zeros_like(x), jnp.arange(2))[0]

    @jax.jit
    def twice(x, layer):
        return routed(x, layer) + routed(x + 1.0, layer)

    x = jax.random.normal(jax.random.PRNGKey(0), (20, 128), jnp.float32)
    scanned(x), twice(x, jnp.int32(1))
    assert traced == [(40, 128)]                 # 20 tokens x top 2
    twice(x[:12], jnp.int32(0))
    assert traced == [(40, 128), (24, 128)]
    mla_moe._routed_experts.clear_cache()
