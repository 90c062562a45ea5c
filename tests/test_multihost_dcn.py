"""Real multi-process DCN-path test (VERDICT r4 #8).

parallel/multihost.py was only ever exercised single-process; this spawns
TWO ``jax.distributed``-initialized subprocesses on localhost forming a
2-host hybrid mesh (dp over "DCN" = the inter-process plane, tp over each
process's 2 virtual CPU devices) and runs one sharded step whose
collectives cross the process boundary. Both processes must agree on the
global loss. Skips if the coordinator port can't be claimed or the
backend lacks multi-process support.
"""

import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import os, sys
sys.path.insert(0, os.environ["TPU_REPO"])
import jax
import numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from tritonclient_tpu.parallel import multihost

ok = multihost.initialize()
assert ok, "distributed runtime did not initialize"
assert jax.process_count() == 2, jax.process_count()
assert jax.local_device_count() == 2, jax.local_device_count()
mesh = multihost.hybrid_mesh(dcn={"dp": 2}, ici={"tp": 2})
pid = jax.process_index()

# Every process feeds ONLY its own rows of the global [4, 8] batch
# (the multi-host data-loading contract).
local = np.arange(2 * 8, dtype=np.float32).reshape(2, 8) + 100.0 * pid
x = multihost.process_local_batch(mesh, (4, 8), local, P("dp", None))
w = jax.device_put(
    np.linspace(-1, 1, 8 * 6, dtype=np.float32).reshape(8, 6),
    NamedSharding(mesh, P(None, "tp")),
)

@jax.jit
def step(x, w):
    y = x @ w            # dp-sharded rows x tp-sharded columns
    return jnp.mean(y * y)  # global reduction crosses BOTH axes

loss = float(step(x, w))
assert np.isfinite(loss)
print(f"DCN_LOSS {loss:.6f}", flush=True)
"""


def _free_port():
    s = socket.socket()
    try:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
    finally:
        s.close()


def test_two_process_dcn_mesh():
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update(
            TPU_REPO=REPO,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=2",
            JAX_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
            JAX_NUM_PROCESSES="2",
            JAX_PROCESS_ID=str(pid),
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", _CHILD],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
                cwd=REPO,
            )
        )
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=150)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    if any(rc != 0 for rc, _, _ in outs):
        blob = "\n".join(err for _, _, err in outs)
        if "UNAVAILABLE" in blob or "bind" in blob.lower():
            pytest.skip(f"coordinator port unavailable: {blob[-400:]}")
        raise AssertionError(
            "\n".join(
                f"[proc rc={rc}]\n{out}\n{err}" for rc, out, err in outs
            )
        )
    losses = []
    for _, out, _ in outs:
        for line in out.splitlines():
            if line.startswith("DCN_LOSS "):
                losses.append(float(line.split()[1]))
    assert len(losses) == 2, outs
    # One global computation: both hosts must see the identical loss.
    assert losses[0] == pytest.approx(losses[1], rel=1e-6), losses
