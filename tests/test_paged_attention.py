"""The ragged paged-attention kernel (ops/paged_attention.py) under the
Pallas interpreter, against the float32 masked einsum it replaced in the
paged engine's layers (models/gpt.py ``_masked_cache_attention``) on the
same pool."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from tritonclient_tpu.models.gpt import _masked_cache_attention
from tritonclient_tpu.ops.paged_attention import paged_attention, straight_line

_KERNEL = sys.modules[paged_attention.__module__]


@pytest.fixture(params=["ruled", "looped"])
def body(request, monkeypatch):
    """Which body of the kernel a case runs: the one the rule gives its
    shapes (``straight_line``), or the looped one whatever they are (the
    straight-line body then takes no rows at all). Both are one algorithm,
    so every few-row shape is held to the reference on each. Call the
    fixture's value with the case's shapes before the kernel."""
    def choose(rows, q_heads, kv_heads):
        if request.param == "ruled":
            return
        if not straight_line(rows, q_heads, kv_heads):
            pytest.skip("the rule gives these shapes the looped body already")
        monkeypatch.setattr(_KERNEL, "_STRAIGHT_ROWS", 0)

    return choose


_BS, _N_CTX, _LAYERS, _BLOCKS = 16, 6, 3, 40
_NAN_PAGE = _BLOCKS - 1


def _bank(heads, head_dim, dtype, rows):
    """One bank of six tables: the longest row of each holds 1, 15, 16, 17
    and all of the table's positions, and the last is an idle slot whose
    every entry is the scratch page. With ``rows`` > 1 a table's rows hold
    one position more each (a prefill chunk's causal lengths). Every entry
    past a table's live pages points at a page full of NaN."""
    width = heads * head_dim
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(heads * head_dim), 3)
    pool_shape = (_LAYERS, _BLOCKS, _BS, width)
    k_pool = jax.random.normal(kk, pool_shape, jnp.float32).astype(dtype)
    v_pool = jax.random.normal(kv, pool_shape, jnp.float32).astype(dtype)
    k_pool = k_pool.at[:, _NAN_PAGE].set(jnp.nan)
    v_pool = v_pool.at[:, _NAN_PAGE].set(jnp.nan)
    longest = [1, 15, 16, 17, _N_CTX * _BS, 1]
    q = jax.random.normal(
        kq, (len(longest) * rows, heads, head_dim), jnp.float32).astype(dtype)
    rng = np.random.RandomState(0)
    lengths = np.zeros((len(longest), rows), np.int32)
    btabs = np.full((len(longest), _N_CTX), _NAN_PAGE, np.int32)
    for t, top in enumerate(longest):
        lengths[t] = np.maximum(1, top - np.arange(rows)[::-1])
        live = -(-top // _BS)
        btabs[t, :live] = rng.choice(
            np.arange(1, _NAN_PAGE), live, replace=False)
    btabs[-1] = 0                               # the idle slot
    return (q, k_pool, v_pool, jnp.asarray(btabs),
            jnp.asarray(lengths.reshape(-1)))


def _reference(q, k_pool, v_pool, layer, btabs, lengths, rows):
    """The masked einsum over the gathered, head-shaped float32 view of
    each table: what ``_scan_layers_over_pool`` did before the kernel. Its
    gather takes the dead entries from the scratch page: it reads the
    table's whole width, and 0 x NaN is NaN."""
    n, heads, head_dim = q.shape
    live = (jnp.arange(_N_CTX)[None, :] * _BS
            < lengths.reshape(-1, rows).max(axis=1)[:, None])
    tables = jnp.where(live, btabs, 0)

    def view(pool):
        table = pool[layer, tables].reshape(
            tables.shape[0], 1, _N_CTX * _BS, heads, head_dim)
        return jnp.broadcast_to(
            table, (tables.shape[0], rows) + table.shape[2:]
        ).reshape((n,) + table.shape[2:])

    mask = (jnp.arange(_N_CTX * _BS)[None, :] < lengths[:, None])[:, None, :]
    with jax.default_matmul_precision("highest"):
        return _masked_cache_attention(q, view(k_pool), view(v_pool), mask)


@pytest.mark.parametrize("rows", [1, 8, 32])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("heads,head_dim", [(25, 64), (16, 128), (4, 32)])
def test_kernel_is_the_masked_einsum_over_the_pages_held(
        heads, head_dim, dtype, rows, body):
    """Lengths 1, 15, 16, 17 and the full table in one bank, an idle slot
    on the scratch page, rows of one table with lengths of their own: the
    kernel's float32 result is the einsum's to 1e-5. A bfloat16 pool is
    read as stored by both, so it agrees as closely (the stored values'
    rounding is in both). No page past a table's longest row reaches the
    result: those entries point at NaN. One row a table (decode) takes the
    straight-line body at every head shape, 8 and 32 rows (a prefill
    chunk) the looped one but for four heads of 32 at 8 rows; each few-row
    shape is run on the looped body too."""
    body(rows, heads, heads)
    q, k_pool, v_pool, btabs, lengths = _bank(heads, head_dim, dtype, rows)
    layer = 1
    got = jax.jit(
        lambda *a: paged_attention(*a, rows_per_table=rows)
    )(q, k_pool, v_pool, jnp.int32(layer), btabs, lengths)
    assert got.shape == q.shape and got.dtype == jnp.float32
    assert not bool(jnp.isnan(got).any())
    want = _reference(q, k_pool, v_pool, layer, btabs, lengths, rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5)
    # Another layer's pages give another answer: the index is honoured.
    other = paged_attention(q, k_pool, v_pool, jnp.int32(2), btabs, lengths,
                            rows_per_table=rows)
    assert float(jnp.max(jnp.abs(other - got))) > 1e-2


def test_kernel_on_a_tp_mesh_attends_each_shards_own_heads():
    """Pools and heads sharded over two devices on ``tp``, heads whole a
    shard: the result is the one-device result, sharded the same way."""
    heads, head_dim, rows = 4, 32, 1
    q, k_pool, v_pool, btabs, lengths = _bank(heads, head_dim,
                                              jnp.bfloat16, rows)
    want = paged_attention(q, k_pool, v_pool, jnp.int32(0), btabs, lengths)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    pool_sharding = NamedSharding(mesh, P(None, None, None, "tp"))
    got = jax.jit(
        lambda *a: paged_attention(*a, mesh=mesh)
    )(jax.device_put(q, NamedSharding(mesh, P(None, "tp", None))),
      jax.device_put(k_pool, pool_sharding),
      jax.device_put(v_pool, pool_sharding), jnp.int32(0), btabs, lengths)
    assert got.sharding.spec == P(None, "tp", None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-6)


def test_kernel_refuses_rows_that_do_not_fill_the_tables():
    q, k_pool, v_pool, btabs, lengths = _bank(4, 32, jnp.float32, 1)
    with pytest.raises(ValueError, match="tables x"):
        paged_attention(q, k_pool, v_pool, jnp.int32(0), btabs, lengths,
                        rows_per_table=4)


# --------------------------------------------------------------------------- #
# grouped query heads and a window                                            #
# --------------------------------------------------------------------------- #

_WIDE_CTX = 12      # pages a table: contexts that leave a window's first chunk


def _grouped_bank(kv_heads, group, head_dim, dtype, rows):
    """Six tables of ``_WIDE_CTX`` live pages each (a ring has no dead
    entry), their longest rows at ``rows``, 40, 64, 100, 129 and the whole
    table; ``group`` query heads a K/V head."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(kv_heads + head_dim), 3)
    pool_shape = (2, 60, _BS, kv_heads * head_dim)
    k_pool = jax.random.normal(kk, pool_shape, jnp.float32).astype(dtype)
    v_pool = jax.random.normal(kv, pool_shape, jnp.float32).astype(dtype)
    longest = [rows, 40, 64, 100, 129, _WIDE_CTX * _BS]
    q = jax.random.normal(kq, (len(longest) * rows, kv_heads * group,
                               head_dim), jnp.float32).astype(dtype)
    rng = np.random.RandomState(1)
    lengths = np.stack([np.maximum(1, top - np.arange(rows)[::-1])
                        for top in longest]).astype(np.int32)
    btabs = np.stack([rng.choice(np.arange(1, 60), _WIDE_CTX, replace=False)
                      for _ in longest]).astype(np.int32)
    return q, k_pool, v_pool, jnp.asarray(btabs), jnp.asarray(
        lengths.reshape(-1))


def _grouped_reference(q, k_pool, v_pool, layer, btabs, lengths, rows,
                       window):
    """A masked einsum over each table's gathered view: query head i against
    K/V head i // group, keys [length - window, length)."""
    tables, n_ctx = btabs.shape
    head_dim = q.shape[2]
    kv_heads = k_pool.shape[3] // head_dim
    f32 = jnp.float32
    keys = k_pool[layer][btabs].reshape(tables, n_ctx * _BS, kv_heads,
                                        head_dim).astype(f32)
    values = v_pool[layer][btabs].reshape(keys.shape).astype(f32)
    grouped = q.astype(f32).reshape(tables, rows, kv_heads, -1, head_dim)
    at = jnp.arange(n_ctx * _BS)[None, None, :]
    upto = lengths.reshape(tables, rows)[:, :, None]
    seen = at < upto
    if window is not None:
        seen &= at >= upto - window
    with jax.default_matmul_precision("highest"):
        scores = jnp.einsum("trkgd,tlkd->trkgl", grouped, keys) / np.sqrt(
            head_dim)
        probs = jax.nn.softmax(
            jnp.where(seen[:, :, None, None, :], scores, -jnp.inf), axis=-1)
        out = jnp.einsum("trkgl,tlkd->trkgd", probs, values)
    return out.reshape(q.shape)


@pytest.mark.parametrize("tile_rows", [None, 16], ids=["one_tile", "tiles"])
@pytest.mark.parametrize("window", [None, 24, 128],
                         ids=["global", "window24", "window128"])
@pytest.mark.parametrize("kv_heads,group,head_dim,dtype,rows", [
    (2, 4, 16, jnp.float32, 1), (2, 4, 16, jnp.float32, 8),
    (1, 8, 128, jnp.bfloat16, 1), (2, 4, 64, jnp.bfloat16, 8),
    (8, 8, 128, jnp.bfloat16, 1)],
    ids=["2x4x16_decode", "2x4x16_chunk", "1x8x128_decode", "2x4x64_chunk",
         "8x8x128_decode"])
def test_grouped_and_windowed_kernel_is_the_masked_einsum(
        kv_heads, group, head_dim, dtype, rows, window, tile_rows,
        monkeypatch, body):
    """Fewer K/V heads than query heads (a K/V head's columns read by
    ``group`` row blocks), rows that see their last ``window`` keys only
    (the grid starts at the chunk that holds the earliest of them: tables of
    40 to 192 positions under windows of 24 and 128), and a table's rows
    cut into tiles of 16 (each a table of its own to the kernel): the
    result is the masked einsum's to 1e-5, in float32 and over a bfloat16
    pool read as stored. ``8x8x128`` is K-EXAONE's decode call: 8 K/V heads
    each read by 8 query heads. Every few-row shape runs the straight-line
    body and the looped one."""
    if tile_rows is not None:
        if rows * group <= tile_rows:
            pytest.skip("one tile either way")
        monkeypatch.setattr(_KERNEL, "_TILE_ROWS", tile_rows)
    body(rows, kv_heads * group, kv_heads)
    q, k_pool, v_pool, btabs, lengths = _grouped_bank(
        kv_heads, group, head_dim, dtype, rows)
    got = paged_attention(q, k_pool, v_pool, jnp.int32(1), btabs, lengths,
                          rows_per_table=rows, window=window)
    assert got.shape == q.shape and got.dtype == jnp.float32
    want = _grouped_reference(q, k_pool, v_pool, 1, btabs, lengths, rows,
                              window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5)


def test_a_windows_grid_holds_the_windows_pages_and_not_the_contexts():
    """The plan of a window layer: a table of 192 positions under a window
    of 24 takes the one chunk of 8 pages that holds positions 168-191, not
    the two under the context; a row at 130 takes chunk 0 too (its window
    starts at 106, page 6) and one at 160 only chunk 1."""
    from tritonclient_tpu.ops.paged_attention import plan_pages

    btabs = jnp.arange(1, 1 + 3 * _WIDE_CTX, dtype=jnp.int32).reshape(3, -1)
    lengths = jnp.asarray([192, 130, 160], jnp.int32)
    whole = plan_pages(btabs, lengths, block_size=_BS)
    window = plan_pages(btabs, lengths, block_size=_BS, window=24)
    assert int(whole.n_steps) == 2 + 2 + 2 and whole.lows is None
    assert int(window.n_steps) == 1 + 2 + 1
    steps = int(window.n_steps)
    assert window.table_of[:steps].tolist() == [0, 1, 1, 2]
    assert window.chunk_of[:steps].tolist() == [1, 0, 1, 1]
    assert window.lows[:, 0, 0].tolist() == [168, 106, 136]
    # table 0's chunk 1: entries 8-11 of its row; operands past the last
    # live entry stay where chunk 0 would have left them (entries 4-7: read
    # once, and masked by their positions, 192 and up)
    assert window.page_of[:, 0].tolist() == [9, 10, 11, 12, 5, 6, 7, 8]


def test_a_full_multi_head_call_traces_as_it_did_before_grouping():
    """What the GPT cells run: no group, no window, one tile. The plan has
    no lower bounds and the kernel is given none (one ``lens`` operand, the
    ``chunk == 0`` start), so nothing of the additions is in its trace."""
    q, k_pool, v_pool, btabs, lengths = _bank(16, 128, jnp.bfloat16, 8)
    jaxpr = str(jax.make_jaxpr(
        lambda *a: paged_attention(*a, rows_per_table=8)
    )(q, k_pool, v_pool, jnp.int32(0), btabs, lengths))
    assert "transpose" not in jaxpr.split("pallas_call")[0]
    call = jax.make_jaxpr(
        lambda *a: paged_attention(*a, rows_per_table=8)
    )(q, k_pool, v_pool, jnp.int32(0), btabs, lengths)
    (eqn,) = [e for e in call.jaxpr.eqns if e.primitive.name == "pallas_call"]
    # the grid's length, layer, 4 maps, q, lens, then the K and V operands
    # of a chunk's pages (the table's 6): no lows
    assert len(eqn.invars) == 1 + 5 + 2 + 2 * _N_CTX


# --------------------------------------------------------------------------- #
# which body a call takes: read off the traced program                        #
# --------------------------------------------------------------------------- #


def _kernel_equations(heads, head_dim, rows):
    """``(equations, primitives)`` of the kernel a call of these shapes
    traces, nested bodies (``cond``, the column groups' loop) counted in."""
    q, k_pool, v_pool, btabs, lengths = _bank(heads, head_dim, jnp.bfloat16,
                                              rows)
    call = jax.make_jaxpr(
        lambda *a: paged_attention(*a, rows_per_table=rows)
    )(q, k_pool, v_pool, jnp.int32(0), btabs, lengths)
    (eqn,) = [e for e in call.jaxpr.eqns if e.primitive.name == "pallas_call"]

    def walk(jaxpr):
        count, names = 0, set()
        for e in jaxpr.eqns:
            count, names = count + 1, names | {e.primitive.name}
            for param in e.params.values():
                for sub in (param if isinstance(param, (list, tuple))
                            else [param]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        n, more = walk(sub)
                        count, names = count + n, names | more
        return count, names

    return walk(eqn.params["jaxpr"])


def test_the_rule_is_read_off_the_shapes_and_the_traced_kernel_obeys_it():
    """A one-row call's kernel holds no loop (every head's row goes through
    one product), a 32-row call's holds the column groups' one; and the
    exported rule says the same of every call the four configurations make:
    the GPT cells' decode straight and their 32-row chunks looped, K-EXAONE's
    512-row tiles looped; JoyAI's latent attention is no call of this
    kernel, so its family answers None."""
    from tritonclient_tpu.models import gpt, mla_moe, swa_moe
    from tritonclient_tpu.models.gpt_engine import GptPaged

    loops = {"while", "scan"}
    for heads, head_dim in ((25, 64), (16, 128)):
        _, one_row = _kernel_equations(heads, head_dim, 1)
        _, chunk = _kernel_equations(heads, head_dim, 32)
        assert not one_row & loops, one_row & loops
        assert len(chunk & loops) == 1, chunk & loops
        assert straight_line(1, heads, heads)
        assert not straight_line(32, heads, heads)
    # gpt2-xl, cerebras-gpt-1.3b: decode and the 32-row prefill chunk
    for heads, head_dim in ((25, 64), (16, 128)):
        family = GptPaged(gpt.GptConfig(
            vocab_size=64, d_model=heads * head_dim, n_layers=1,
            n_heads=heads, d_ff=64, max_len=64))
        assert family.attends_straight(1) is True
        assert family.attends_straight(32) is False
    # k-exaone-236b-a23b: 64 query heads on 8 K/V heads of 128; a 512-row
    # chunk is cut into tiles of 64 positions x 8 heads
    exaone = swa_moe.SwaMoePaged(
        swa_moe.SwaMoeConfig(n_heads=64, n_kv_heads=8, head_dim=128), 8, 512)
    assert exaone.attends_straight(1) is True and straight_line(1, 64, 8)
    assert exaone.attends_straight(512) is False
    assert not straight_line(512, 64, 8)
    # joyai-llm-flash: the latent pool is gathered, not read by this kernel
    joyai = mla_moe.MlaMoePaged(mla_moe.mla_moe_tiny())
    assert joyai.attends_straight(1) is None


@pytest.mark.parametrize("heads,head_dim", [(25, 64), (16, 128)])
def test_the_straight_body_stays_as_short_as_it_shipped(heads, head_dim):
    """What a set-up pays for the straight-line body is tracing and lowering
    it, once an executable: its kernel's equations (nested ones counted)
    stay under one and a half times the count it shipped with, at both GPT
    head shapes. (A count, not a time: five workers share the cores.)"""
    shipped = 171       # both head shapes (PR 32); the looped body: 222, 114
    count, _ = _kernel_equations(heads, head_dim, 1)
    assert count <= 1.5 * shipped, (count, shipped)
