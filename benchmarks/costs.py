"""The table of peaks, and what a model's work needs in operations and bytes.

Everything here is a function of the configuration's published shapes: it
counts the work the algorithm needs, not what the program does to get there,
so the shares built on it stay valid when a kernel replaces a gather.
"""

from dataclasses import dataclass
from typing import Optional

# Peaks of one chip, keyed by ``device_kind`` as JAX reports it.
# Source: Google Cloud documentation, "TPU v5e" system architecture
# (197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip); copied
# from bench.py:DEVICE_PEAKS. A device not listed is an error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; add it to "
            "benchmarks/costs.py:DEVICE_PEAKS with its source") from None


@dataclass(frozen=True)
class GptShape:
    """The sizes of a GPT-2-family decoder, from a published config.json."""

    n_layer: int
    d_model: int
    n_head: int
    d_ff: int
    n_positions: int
    vocab_size: int
    layer_norm_epsilon: float
    param_bytes: int = 2    # bfloat16
    kv_bytes: int = 2       # bfloat16

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head


def gpt_shape(config: dict) -> GptShape:
    """From a configuration file that keeps the Hugging Face GPT-2 keys."""
    d = int(config["n_embd"])
    inner: Optional[int] = config.get("n_inner")
    if config.get("dtype", "bfloat16") != "bfloat16":
        raise ValueError("byte counts here assume bfloat16 parameters and KV")
    return GptShape(
        n_layer=int(config["n_layer"]), d_model=d,
        n_head=int(config["n_head"]),
        d_ff=int(inner) if inner is not None else 4 * d,
        n_positions=int(config["n_positions"]),
        vocab_size=int(config["vocab_size"]),
        layer_norm_epsilon=float(config["layer_norm_epsilon"]),
    )


def layer_matmul_params(s: GptShape) -> int:
    """Weights of one layer's four matrix multiplications."""
    return 4 * s.d_model * s.d_model + 2 * s.d_model * s.d_ff


def param_count(s: GptShape) -> int:
    """Every parameter: matrices, biases, layer norms, both embeddings."""
    d, f = s.d_model, s.d_ff
    per_layer = layer_matmul_params(s) + (3 * d + d + f + d) + 4 * d
    return (s.n_layer * per_layer + s.vocab_size * d + s.n_positions * d
            + 2 * d)


def step_weight_bytes(s: GptShape) -> int:
    """Bytes of parameters one forward step has to read, once: every layer
    and the token embedding (the tied output head reads all of it). The
    position table is read by row and not counted."""
    return (param_count(s) - s.n_positions * s.d_model) * s.param_bytes


def kv_bytes_per_position(s: GptShape) -> int:
    """K and V of one position, over every layer."""
    return 2 * s.n_layer * s.d_model * s.kv_bytes


def kv_pool_bytes(s: GptShape, n_blocks: int, block_size: int) -> int:
    return n_blocks * block_size * kv_bytes_per_position(s)


def token_flops(s: GptShape, context: int, with_head: bool = True) -> int:
    """Operations to push one token through the model with ``context``
    positions of keys to attend (its own included): 2 per multiply-add in
    the layer matrices, QK^T and PV, and the output head."""
    layers = s.n_layer * (2 * layer_matmul_params(s) + 4 * context * s.d_model)
    head = 2 * s.d_model * s.vocab_size if with_head else 0
    return layers + head


def decode_dispatch(s: GptShape, active: int, micro_steps: int,
                    mean_context: float) -> dict:
    """One decode dispatch: ``micro_steps`` steps, each advancing ``active``
    requests one token against ``mean_context`` held positions each."""
    flops = micro_steps * active * token_flops(s, int(round(mean_context)))
    nbytes = micro_steps * (
        step_weight_bytes(s)
        + active * mean_context * kv_bytes_per_position(s))
    return {"flops": float(flops), "bytes": float(nbytes)}


def prefill_dispatch(s: GptShape, lanes: int, mean_tokens: float,
                     mean_context: float) -> dict:
    """One prefill-chunk dispatch: ``lanes`` prompts, each feeding
    ``mean_tokens`` tokens that attend ``mean_context`` positions on average
    (the prompt so far). Each lane reads the K/V it already holds once and
    computes the head for its last row only."""
    per_token = token_flops(s, int(round(mean_context)), with_head=False)
    flops = lanes * (mean_tokens * per_token
                     + 2 * s.d_model * s.vocab_size)
    nbytes = (step_weight_bytes(s)
              + lanes * mean_context * kv_bytes_per_position(s))
    return {"flops": float(flops), "bytes": float(nbytes)}


def roofline_seconds(work: dict, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and needed bytes over peak bytes/s."""
    return max(work["flops"] / peaks["flops_per_s"],
               work["bytes"] / peaks["hbm_bytes_per_s"])
