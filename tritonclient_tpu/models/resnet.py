"""ResNet-50, TPU-first: NHWC convs on the MXU, inference-mode BatchNorm.

The classification flagship behind BASELINE.json's image_client configs
("image_client.py — densenet_onnx / ResNet50 classification"). The serving
wrapper exposes the Triton-style contract the reference's image_client
expects: model-metadata-driven preprocessing (image_client.py:60-217) and
the classification extension (class_count → "value:index:label" BYTES).
"""

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tritonclient_tpu.models._base import Model, TensorSpec

STAGES = (3, 4, 6, 3)
WIDTHS = (64, 128, 256, 512)
EXPANSION = 4


def _conv(x, w, stride=1):
    return lax.conv_general_dilated(
        x, w,
        window_strides=(stride, stride),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _bn(x, p):
    inv = lax.rsqrt(p["var"].astype(jnp.float32) + 1e-5)
    xf = x.astype(jnp.float32)
    out = (xf - p["mean"]) * inv * p["scale"] + p["bias"]
    return out.astype(x.dtype)


def _init_conv(key, kh, kw, cin, cout, dtype):
    fan_in = kh * kw * cin
    return (jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
            * np.sqrt(2.0 / fan_in)).astype(dtype)


def _init_bn(c, dtype):
    return {
        "scale": jnp.ones((c,), jnp.float32),
        "bias": jnp.zeros((c,), jnp.float32),
        "mean": jnp.zeros((c,), jnp.float32),
        "var": jnp.ones((c,), jnp.float32),
    }


def init_params(key: jax.Array, num_classes: int = 1000,
                dtype=jnp.bfloat16) -> Dict:
    keys = jax.random.split(key, 64)
    ki = iter(keys)
    params = {
        "stem": {"conv": _init_conv(next(ki), 7, 7, 3, 64, dtype),
                 "bn": _init_bn(64, dtype)},
        "stages": [],
    }
    cin = 64
    for stage, (blocks, width) in enumerate(zip(STAGES, WIDTHS)):
        stage_params = []
        for b in range(blocks):
            cout = width * EXPANSION
            blk = {
                "conv1": _init_conv(next(ki), 1, 1, cin, width, dtype),
                "bn1": _init_bn(width, dtype),
                "conv2": _init_conv(next(ki), 3, 3, width, width, dtype),
                "bn2": _init_bn(width, dtype),
                "conv3": _init_conv(next(ki), 1, 1, width, cout, dtype),
                "bn3": _init_bn(cout, dtype),
            }
            if cin != cout:
                blk["proj"] = _init_conv(next(ki), 1, 1, cin, cout, dtype)
                blk["proj_bn"] = _init_bn(cout, dtype)
            stage_params.append(blk)
            cin = cout
        params["stages"].append(stage_params)
    params["fc"] = {
        "w": (jax.random.normal(next(ki), (cin, num_classes), jnp.float32)
              / np.sqrt(cin)).astype(dtype),
        "b": jnp.zeros((num_classes,), dtype),
    }
    return params


def forward(params: Dict, images: jax.Array) -> jax.Array:
    """images [B, 224, 224, 3] → logits [B, num_classes]."""
    x = _conv(images, params["stem"]["conv"], stride=2)
    x = jax.nn.relu(_bn(x, params["stem"]["bn"]))
    x = lax.reduce_window(
        x, -jnp.inf, lax.max,
        window_dimensions=(1, 3, 3, 1),
        window_strides=(1, 2, 2, 1),
        padding="SAME",
    )
    for stage, blocks in enumerate(params["stages"]):
        for b, blk in enumerate(blocks):
            stride = 2 if (stage > 0 and b == 0) else 1
            y = jax.nn.relu(_bn(_conv(x, blk["conv1"]), blk["bn1"]))
            y = jax.nn.relu(_bn(_conv(y, blk["conv2"], stride), blk["bn2"]))
            y = _bn(_conv(y, blk["conv3"]), blk["bn3"])
            if "proj" in blk:
                x = _bn(_conv(x, blk["proj"], stride), blk["proj_bn"])
            elif stride != 1:  # pragma: no cover - never hit for resnet50
                x = x[:, ::stride, ::stride, :]
            x = jax.nn.relu(x + y)
    x = x.mean(axis=(1, 2))
    return (x @ params["fc"]["w"] + params["fc"]["b"]).astype(jnp.float32)


class ResNet50Model(Model):
    """Serves resnet50: INPUT fp32 [-1, 224, 224, 3] NHWC → OUTPUT fp32 logits.

    Labels enable the classification extension; image_client-equivalent
    clients pass class_count and get "value:index:label" BYTES rows.
    """

    name = "resnet50"
    platform = "jax"
    dynamic_batching = True
    max_batch_size = 16

    def __init__(self, num_classes: int = 1000, seed: int = 0,
                 labels: Optional[list] = None):
        super().__init__()
        self.inputs = [TensorSpec("INPUT", "FP32", [-1, 224, 224, 3])]
        self.outputs = [TensorSpec("OUTPUT", "FP32", [-1, num_classes])]
        self.labels = labels or [f"class_{i}" for i in range(num_classes)]
        self._params = init_params(jax.random.PRNGKey(seed))
        # Parameter bytes on the device-memory ledger (per-device, from
        # the actual shardings).
        from tritonclient_tpu import _memscope

        _memscope.register_params(self.name, self._params)

        @jax.jit
        def fwd(params, images):
            return forward(params, images.astype(jnp.bfloat16))

        self._fwd = fwd

    def infer(self, inputs, parameters=None):
        x = inputs["INPUT"]
        if isinstance(x, jax.Array):
            # Zero-copy path (tpu shm): already on device — a host hop
            # here would move ~MB of image twice per request (images
            # dominate this model's wire traffic).
            images = x if x.dtype == jnp.float32 else x.astype(jnp.float32)
        else:
            images = jnp.asarray(np.asarray(x, dtype=np.float32))
        # Un-materialized: the response path parks it in a tpu shm region
        # zero-copy or serializes it for the wire.
        return {"OUTPUT": self._fwd(self._params, images)}

    def warmup(self):
        z = jnp.zeros((1, 224, 224, 3), jnp.float32)
        jax.block_until_ready(self._fwd(self._params, z))
