"""What the MLA / routed-expert family's work needs in operations and bytes
when its residual path is ``hc_mult`` streams mixed by per-token maps (mHC)
and its rotary positions are YaRN-scaled: ``costs_mla_moe.py``'s counts, which
hold for the attention and the expert layer as they are, and the maps' own.

The maps (``tritonclient_tpu/models/mhc.py``), per token and sublayer: the
``n x C`` streams are read for the norm and the projection onto ``2n + n^2``
coefficients (``2 n C (2n + n^2)`` operations), read for the collapse
``H_pre . X``, and read and written for the mix ``H_res X + H_post^T F``. If
every one of those is ONE pass and the passes over an unchanged ``X`` share
their read, that is three passes of ``n C`` elements: read (norm, projection,
collapse), read (mix), write (mix): ``3 n C`` x 2 B a row of ``hc_rows``
(stepscope: live rows x sublayers that passed the maps). Sinkhorn's 20
iterations work on 16 numbers a token and need no bytes of HBM at all. The
count is the algorithm's: it is the same whatever implements the maps.
"""

from dataclasses import dataclass, fields

from benchmarks import costs_mla_moe as base
from benchmarks.costs_mla_moe import (  # noqa: F401 - one family's counts
    MlaMoeShape, attend_flops, attention_params, dense_ffn_params,
    expert_params, latent_bytes_per_position, roofline_seconds, shared_params)

_YARN_KEYS = {"type", "factor", "original_max_position_embeddings",
              "beta_fast", "beta_slow", "mscale", "mscale_all_dim"}


@dataclass(frozen=True)
class MhcMlaMoeShape(MlaMoeShape):
    """``MlaMoeShape`` and the published keys of the residual path and of
    the scaled positions (see ``mhc_mla_moe_shape``)."""

    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp_min: float = -30.0
    hc_res_clamp_max: float = 30.0
    # rope_scaling (type yarn); factor 1 = no scaling
    yarn_factor: float = 1.0
    yarn_original_positions: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    @property
    def hc_coefficients(self) -> int:
        return 2 * self.hc_mult + self.hc_mult ** 2

    @property
    def stream_width(self) -> int:
        return self.hc_mult * self.d_model


def mhc_mla_moe_shape(config: dict) -> MhcMlaMoeShape:
    """From a configuration file that keeps the published keys; what is not
    implemented is refused by its key's name (here, or by
    ``costs_mla_moe.mla_moe_shape`` for the keys the family shares)."""
    for key, only in (("ep_size", 1), ("rope_interleave", True),
                      ("topk_method", "noaux_tc")):
        if config.get(key, only) != only:       # may be absent
            raise ValueError(f"{key}: only {only!r} is implemented, the "
                             f"configuration says {config[key]!r}")
    scaling = config.get("rope_scaling")
    yarn = {}
    if scaling is not None:
        if scaling.get("type") != "yarn" or set(scaling) - _YARN_KEYS:
            raise ValueError(
                "rope_scaling: only null or type 'yarn' with the keys "
                f"{sorted(_YARN_KEYS)} is implemented, the configuration "
                f"says {scaling!r}")
        yarn = dict(
            yarn_factor=float(scaling["factor"]),
            yarn_original_positions=int(
                scaling["original_max_position_embeddings"]),
            yarn_beta_fast=float(scaling.get("beta_fast", 32)),
            yarn_beta_slow=float(scaling.get("beta_slow", 1)),
            yarn_mscale=float(scaling.get("mscale", 1)),
            yarn_mscale_all_dim=float(scaling.get("mscale_all_dim", 0)))
    if int(config["hc_mult"]) < 1:
        raise ValueError(f"hc_mult: at least 1, the configuration says "
                         f"{config['hc_mult']!r}")
    plain = base.mla_moe_shape(dict(
        config, rope_scaling=None, rope_interleave=True))
    return MhcMlaMoeShape(
        **{f.name: getattr(plain, f.name) for f in fields(plain)},
        hc_mult=int(config["hc_mult"]),
        hc_sinkhorn_iters=int(config["hc_sinkhorn_iters"]),
        hc_eps=float(config["hc_eps"]),
        hc_res_clamp_min=float(config["mhc_h_res_clamp_min"]),
        hc_res_clamp_max=float(config["mhc_h_res_clamp_max"]),
        **yarn)


def maps_params(s: MhcMlaMoeShape) -> int:
    """One layer's two sets of maps: ``phi`` of attention's and of the
    feed-forward's (their 2 x (2n + n^2 + 3) biases and scales left out)."""
    return 2 * s.stream_width * s.hc_coefficients if s.hc_mult > 1 else 0


def param_count(s: MhcMlaMoeShape) -> int:
    return base.param_count(s) + s.n_layer * maps_params(s)


def fixed_weight_bytes(s: MhcMlaMoeShape) -> int:
    return (base.fixed_weight_bytes(s)
            + s.param_bytes * s.n_layer * maps_params(s))


def maps_work(s: MhcMlaMoeShape, hc_rows: float) -> dict:
    """The maps of ``hc_rows`` (row, sublayer) pairs: the projection's
    operations, and three passes over the streams (module docstring)."""
    return {"flops": float(hc_rows * 2.0 * s.stream_width
                           * s.hc_coefficients),
            "bytes": float(hc_rows * 3 * s.stream_width * s.kv_bytes)}


def token_flops(s: MhcMlaMoeShape, context: float,
                with_head: bool = True) -> float:
    """``costs_mla_moe.token_flops`` and the token's maps: two sublayers a
    layer."""
    return (base.token_flops(s, context, with_head)
            + (maps_work(s, 2 * s.n_layer)["flops"] if s.hc_mult > 1 else 0))


def dispatch_work(s: MhcMlaMoeShape, record: dict):
    """The work of one stepscope dispatch record of this family, the maps'
    included (``hc_rows``); None where the record is of another phase or
    the delivery thread had not yet added its counters."""
    work = base.dispatch_work(s, record)
    if work is None or (s.hc_mult > 1 and "hc_rows" not in record):
        return None
    steps = record["micro_steps"] if record["phase"] == "decode" else 1
    extra = maps_work(s, record.get("hc_rows", 0))
    return {"flops": work["flops"] + extra["flops"],
            "bytes": work["bytes"] + extra["bytes"]
            + steps * s.param_bytes * s.n_layer * maps_params(s)}
