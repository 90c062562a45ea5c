"""Mesh-serving validation flow shared by tests and the driver dry-run.

Serves a MESH-SHARDED BERT (params by partition rules, ring attention on
sp) through the full gRPC + mesh-spanning-shm-region stack and checks the
pooled output against the single-device model — the long-context serving
story end to end: tokens arrive sharded, the output parks back sharded,
nothing congregates on one chip (SURVEY §5.7/§5.8).
"""

import contextlib
from typing import Optional

import numpy as np


@contextlib.contextmanager
def full_matmul_precision():
    """float32 matmuls at full precision, process-wide, while entered.

    For comparing float32 models across shardings or code paths on the
    TPU: at its default precision f32 matmuls run bfloat16 passes, so two
    shardings of one model are two roundings (9.2e-3 apart on the sharded
    bert_tiny below, four v5e chips) and a tight tolerance or a token
    equality compares rounding, not function. Set through ``jax.config``
    and not the thread-local ``jax.default_matmul_precision`` context,
    because servers and engines trace on their own threads.
    """
    import jax

    previous = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        yield
    finally:
        jax.config.update("jax_default_matmul_precision", previous)


@full_matmul_precision()
def serve_sharded_bert_roundtrip(mesh, seq_len: int = 64,
                                 rtol: float = 2e-4, atol: float = 2e-4,
                                 prefix: str = "msv") -> None:
    """Raises on any serving error or numeric mismatch."""
    import jax
    from jax.sharding import PartitionSpec as P

    import tritonclient_tpu.grpc as grpcclient
    import tritonclient_tpu.utils.tpu_shared_memory as tpushm
    from tritonclient_tpu.models import bert
    from tritonclient_tpu.server import InferenceServer

    cfg = bert.bert_tiny(seq_len=seq_len)
    sharded = bert.BertBaseModel(cfg=cfg, mesh=mesh)
    reference = bert.BertBaseModel(cfg=cfg)
    dp = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
    sp = mesh.shape.get("sp", 1)
    b, l = 2 * dp, min(max(8 * sp, 16), seq_len // sp * sp)
    x = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (b, l)
    ).astype(np.int32)
    ref = np.asarray(reference._fwd(reference._params, x))

    client: Optional[object] = None
    in_region = out_region = None
    with InferenceServer(models=[sharded], http=False) as server:
        try:
            client = grpcclient.InferenceServerClient(server.grpc_address)
            # Region layouts match the model's data sharding: batch on
            # dp(/fsdp), sequence on sp (input); batch only (output).
            in_region = tpushm.create_sharded_memory_region(
                f"{prefix}_in", x.nbytes, mesh,
                partition_spec=P(("dp",), "sp"),
            )
            out_bytes = b * cfg.d_model * 4
            out_region = tpushm.create_sharded_memory_region(
                f"{prefix}_out", out_bytes, mesh,
                partition_spec=P(("dp",), None),
            )
            client.register_tpu_shared_memory(
                f"{prefix}_in", tpushm.get_raw_handle(in_region), 0, x.nbytes
            )
            client.register_tpu_shared_memory(
                f"{prefix}_out", tpushm.get_raw_handle(out_region), 0,
                out_bytes,
            )
            # Park the tokens SHARDED over the mesh.
            tpushm.set_shared_memory_region_from_dlpack(
                in_region, [jax.device_put(x, in_region.sharding)]
            )
            inp = grpcclient.InferInput("INPUT_IDS", [b, l], "INT32")
            inp.set_shared_memory(f"{prefix}_in", x.nbytes, 0)
            out = grpcclient.InferRequestedOutput("POOLED_OUTPUT")
            out.set_shared_memory(f"{prefix}_out", out_bytes, 0)
            client.infer("bert_base", [inp], outputs=[out])
            # The parked output stays a sharded device array until read.
            parked = out_region._parked[0]
            assert hasattr(parked, "sharding"), type(parked)
            got = tpushm.get_contents_as_numpy(
                out_region, "FP32", (b, cfg.d_model), 0
            )
        finally:
            if client is not None:
                # Unregister before destroy: tearing down a region the
                # server still maps would leave a dangling registry entry
                # (TPU006 destroy-while-registered).
                try:
                    client.unregister_tpu_shared_memory()
                except Exception:
                    pass  # server may already be down; destroy regardless
            for region in (in_region, out_region):
                if region is not None:
                    tpushm.destroy_shared_memory_region(region)
            if client is not None:
                client.close()
    np.testing.assert_allclose(got, ref, rtol=rtol, atol=atol)
