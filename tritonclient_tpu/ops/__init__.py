"""Compute ops: attention and friends, written MXU-first.

`dot_product_attention` is the plain jnp implementation;
`flash_attention` is the Pallas-fused TPU kernel (tile-streamed online
softmax, interpreter-backed off-TPU); `paged_attention` is the Pallas
kernel the paged engine's layers read their KV pages through (the pages a
request holds, where they lie in the pool); `grouped_swiglu` is the Pallas
kernel of the routed families' expert layers (each hit expert's three
matrices streamed once, SwiGLU fused). The sequence-parallel variants
live in tritonclient_tpu.parallel (ring_attention, ulysses_attention).
"""

from tritonclient_tpu.ops.attention import dot_product_attention
from tritonclient_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_path,
)
from tritonclient_tpu.ops.grouped_experts import grouped_swiglu
from tritonclient_tpu.ops.paged_attention import paged_attention, plan_pages

__all__ = ["dot_product_attention", "flash_attention", "flash_attention_path",
           "grouped_swiglu", "paged_attention", "plan_pages"]
