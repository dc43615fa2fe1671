"""Doubly-gated short convolution (the LFM2 family's ``conv`` layers) — the
mixer of a layer whose :class:`~horovod_tpu.models.transformer.LayerSpec`
says ``mixer="sconv"``, as ``models/ssm.py`` and ``models/kda.py`` are of
theirs. It keeps NO state beyond ``d_conv - 1`` positions: no scan, no
chunk, no kernel.

One layer, ``h`` the normed layer input (B, L, d), ``K = d_conv`` taps::

    [B | C | u] = h w_in                       w_in: d -> 3 d, in THIS order
    z_t = sum_k conv_w[k] * (B * u)_{t - (K - 1) + k}    depthwise, causal,
                                               zeros before the sequence
    out = (C * z) w_out                        w_out: d -> d

No bias and no activation function anywhere. ``B``, ``C`` and ``u`` are
the projection's float32 accumulator rounded to ``dtype`` (bf16) once;
the two gate products and the tap sum between them then run in float32
from those inputs and the result is rounded to ``dtype`` ONCE, before
``w_out`` — no rounding between the three steps.

Device scopes: ``hvd_sconv`` around ``hvd_sconv_in_proj``,
``hvd_sconv_gate`` (``B * u``, the convolution, ``C *`` and the rounding)
and ``hvd_sconv_out_proj``.

Training only: decode and serve would have to keep the last ``K - 1``
products a sequence, sequence parallelism to hand them from shard to
shard; each refuses the layer by name.
"""

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from .ssm import causal_conv1d


@dataclasses.dataclass(frozen=True)
class SConvConfig:
    """What ``TransformerConfig.sconv_cfg`` hands the mixer; the defaults
    live there (``sconv_kernel``)."""
    d_model: int
    d_conv: int
    dtype: Any
    param_dtype: Any


def init_sconv_params(key, cfg):
    """The projections normal over sqrt(fan in) like the model's other
    matrices; the taps as torch's Conv1d draws them (uniform within
    1 / sqrt(d_conv): one input channel a group)."""
    pd, d = cfg.param_dtype, cfg.d_model
    k = jax.random.split(key, 3)
    bound = 1.0 / math.sqrt(cfg.d_conv)
    return {
        "w_in": jax.random.normal(k[0], (d, 3 * d), pd) / math.sqrt(d),
        "conv_w": jax.random.uniform(k[1], (cfg.d_conv, d), pd, -bound,
                                     bound),
        "w_out": jax.random.normal(k[2], (d, d), pd) / math.sqrt(d),
    }


def sconv_specs():
    """PartitionSpecs of :func:`init_sconv_params`: every leaf replicated
    (a layer is whole on its chip)."""
    from jax.sharding import PartitionSpec as P
    return {name: P() for name in ("w_in", "conv_w", "w_out")}


def sconv_mixer(params, h, cfg):
    """The mixer of one short-convolution layer. h: the normed layer input
    (B, L, d) -> out (B, L, d) float32, as its matmul accumulated it."""
    dtype, f32, d = cfg.dtype, jnp.float32, cfg.d_model
    with jax.named_scope("hvd_sconv"):
        with jax.named_scope("hvd_sconv_in_proj"):
            bcu = jnp.einsum("bld,de->ble", h, params["w_in"].astype(dtype),
                             preferred_element_type=f32).astype(dtype)
        with jax.named_scope("hvd_sconv_gate"):
            b, c, u = (bcu[..., i * d:(i + 1) * d].astype(f32)
                       for i in range(3))
            y = (c * causal_conv1d(b * u, params["conv_w"], None)
                 ).astype(dtype)
        with jax.named_scope("hvd_sconv_out_proj"):
            return jnp.einsum("ble,ed->bld", y,
                              params["w_out"].astype(dtype),
                              preferred_element_type=f32)
