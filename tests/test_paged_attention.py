"""The ragged paged-attention kernel (ops/paged_attention.py) under the
Pallas interpreter, against the float32 masked einsum it replaced in the
paged engine's layers (models/gpt.py ``_masked_cache_attention``) on the
same pool."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from tritonclient_tpu.models.gpt import _masked_cache_attention
from tritonclient_tpu.ops.paged_attention import paged_attention

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


@pytest.mark.parametrize("rows", [1, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("heads,head_dim", [(25, 64), (16, 128), (4, 32)])
def test_kernel_is_the_masked_einsum_over_the_pages_held(
        heads, head_dim, dtype, rows):
    """Lengths 1, 15, 16, 17 and the full table in one bank, an idle slot
    on the scratch page, rows of one table with lengths of their own: the
    kernel's float32 result is the einsum's to 1e-5. A bfloat16 pool is
    read as stored by both, so it agrees as closely (the stored values'
    rounding is in both). No page past a table's longest row reaches the
    result: those entries point at NaN."""
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
