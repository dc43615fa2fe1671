"""Transformer LM — the framework's flagship distributed workload.

The reference framework is data-parallel only (SURVEY.md §2.5); this model is
where the TPU build goes beyond it: one codebase expressing

- **DP** over mesh axis ``dp`` (batch sharded; gradient reduction falls out of
  shard_map's transpose of replicated params),
- **TP** over ``tp`` — Megatron-style sharding written manually: vocab-parallel
  embedding + logits/loss, head-parallel attention, column/row-parallel MLP
  with a single psum per block (the scaling-book recipe: pick a mesh, shard,
  let the collectives ride ICI),
- **SP** over ``sp`` — exact long-context attention via ring streaming
  (:func:`~horovod_tpu.parallel.ring_attention.ring_attention`) or
  Ulysses all-to-all (:func:`~horovod_tpu.parallel.ulysses.
  ulysses_attention`), selected by ``cfg.sp_impl``.

Long-context options compose on top: grouped-query attention
(``n_kv_heads``), rotary embeddings (``positional="rope"``),
sliding-window attention (``attention_window``), chunked cross entropy
(``loss_chunk`` — no (B, S, vocab) logits tensor), and KV-cache decoding
(:func:`generate`, greedy or temperature/top-k).

The same functions run single-device when ``axes=None`` (collectives elided,
dense attention), which is the jit-compile-check path for ``entry()``.

Per-shard tensor convention inside shard_map: tokens ``(B_loc, S_loc)``;
activations ``(B_loc, S_loc, d_model)`` in ``cfg.dtype`` (bf16 on TPU) with
f32 accumulation in every matmul via ``preferred_element_type``.
"""

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.ring_attention import dense_attention, ring_attention


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """Rotary embedding of one kind of layer. The defaults are the plain
    whole-head RoPE every ``positional="rope"`` layer had before layers
    could differ."""
    theta: float = 10000.0
    # leading features of each head that are rotated (pairs i, i +
    # rotary_dim / 2); None = the whole head
    rotary_dim: Optional[int] = None
    # YaRN (arXiv:2309.00071): frequencies below the correction range of
    # beta_fast / beta_slow at the original length are divided by
    # yarn_factor, those above are kept, a linear ramp between; cos and
    # sin are scaled by attention_factor. None = no rescaling.
    yarn_factor: Optional[float] = None
    yarn_original_max_seq: Optional[int] = None
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One layer of a model whose layers differ."""
    n_heads: int                       # query heads (held on this chip)
    window: Optional[int] = None       # None = full causal attention
    rope: Optional[RopeSpec] = None    # None = no rotary embedding
    mlp: str = "dense"                 # "dense" | "sparse" (MoE FFN)
    # "attention" | "mamba2" (models/ssm.py; n_heads / window / rope then
    # say nothing, the ssm_* sizes of the configuration do) | "kda"
    # (models/kda.py; likewise, the kda_* sizes) | "mla" (latent attention:
    # n_heads heads with q and k of mla_qk_nope + mla_qk_shared beside v of
    # mla_v_dim, k and v from one latent of mla_kv_rank; no positions) |
    # "sconv" (models/sconv.py: the doubly-gated short convolution over
    # d_model channels, sconv_kernel taps)
    mixer: str = "attention"


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32768
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    max_seq: int = 2048
    # Grouped-query attention: K/V head count (None = n_heads, plain
    # MHA). Composes with tp (both head counts shard over tp), with
    # sp_impl="ulysses", and with ring SP under both tile impls (the
    # ring streams the reduced K/V heads over ICI).
    n_kv_heads: int = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # "dense" | "flash" (Pallas fused kernel, ops/flash_attention.py).
    # Applies both without sequence parallelism and, under sp, as the
    # per-tile compute of the ring (ring x flash composition) or the
    # full-sequence kernel of the ulysses re-shard.
    attention_impl: str = "dense"
    # Sequence-parallel strategy when the sp axis is active:
    # "ring" (K/V ppermute streaming, parallel/ring_attention.py) |
    # "ulysses" (head<->sequence all-to-all, parallel/ulysses.py —
    # requires local head count divisible by the sp axis size).
    sp_impl: str = "ring"
    # run the Pallas kernels in the interpreter (CPU tests)
    flash_interpret: bool = False
    # Positional encoding: "learned" (absolute table, the default) |
    # "rope" (rotary embeddings applied to q/k inside attention; no pos
    # table parameter). RoPE composes with sp (each shard rotates with
    # its global offsets before any K/V movement) and with the decode
    # cache (K rows are stored rotated).
    positional: str = "learned"
    # Sliding-window attention (Mistral-style causal band): each query
    # attends only the previous `attention_window` positions. Supported
    # on the dense/flash single-shard paths, under ulysses SP (the
    # kernel sees the gathered global sequence), and under ring SP with
    # either tile impl (the ring skips out-of-window shards entirely;
    # flash tiles use the band-offset kernels on partially-banded
    # visiting shards).
    attention_window: int = None
    # Chunked cross entropy: compute the LM head + loss over sequence
    # chunks of this many positions, each chunk's logits computed again
    # in the backward, so the (B, S, vocab) f32 logits tensor never
    # materializes — at 32k vocab the logits, not K/V, are what OOMs
    # first at long context. What is live is one chunk's (B, chunk,
    # V_loc) f32 logits plus, in the backward, the logit cotangents of a
    # group of chunks in `dtype` (at most 128 MiB): the head's weight
    # gradient is one product a group of up to 2,048 tokens, the group
    # chosen from the shapes (_head_grad_chunks; gauge
    # hvd_head_grad_chunks). None = whole-sequence logits (the default;
    # required if callers want forward() logits anyway).
    loss_chunk: int = None
    # Rematerialization: wrap each transformer layer in jax.checkpoint so
    # the backward recomputes activations instead of storing them — trades
    # ~1/3 more FLOPs for O(n_layers) less activation HBM, the standard
    # lever for fitting larger batch x seq on a chip (HBM, not FLOPs, is
    # what runs out first at d_model >= 2048 on a 16G v5e).
    remat: bool = False
    # Layer indices whose FFN is a Mixture-of-Experts block (models/moe.py)
    # routed over the mesh ep axis — the fifth parallelism dimension of the
    # flagship model. Empty = all-dense (the default).
    moe_layers: tuple = ()
    moe_num_experts: int = 4
    moe_top_k: int = 2
    # --- models whose layers differ, and a chip's share of a layer ---
    # One LayerSpec per layer (n_layers of them): query heads, window,
    # rotary embedding and kind of FFN of each. Set, it takes the place
    # of n_heads / attention_window / moe_layers, which describe one kind
    # of layer. Training only: the decode, serve and pipeline paths take
    # one kind of layer.
    layers: tuple = ()
    # Width of a head where it is not d_model / n_heads.
    head_size: Optional[int] = None
    # Per-head output gate: a_head * sigmoid(h @ wg.T)[head] before wo
    # (headwise gated attention, arXiv:2505.06708).
    attn_gate: bool = False
    # Gated SiLU FFNs, (silu(h w1) * (h w3)) w2, dense and expert alike;
    # False = the two-matrix GELU FFN.
    mlp_gated: bool = False
    # Expert width (None = d_ff), the shared expert's width (0 = none),
    # and the scale on the renormalised top-k probabilities.
    moe_d_ff: Optional[int] = None
    moe_shared_d_ff: int = 0
    moe_routed_scale: float = 1.0
    # The share of each layer this chip holds, where a layer is divided
    # over more chips than the mesh has: n_heads / n_kv_heads / the
    # LayerSpecs' n_heads and vocab_size count what is HELD, and
    # moe_experts_held = (first index, count) names the routed experts
    # held while moe_num_experts stays the router's published width. The
    # layers compute their part of the result from what is held (a
    # partial sum over heads before wo, over experts in the FFN) and no
    # code stands in for the absent chips. Set, the sparse layers are
    # models/moe.py moe_dropless (no capacity, no ep axis); None = the
    # capacity layer moe_layer over the mesh's ep axis.
    moe_experts_held: Optional[tuple] = None
    # --- what a published configuration may state beside the widths ---
    # Scale on q.k before the softmax (None = 1 / sqrt(head_dim)).
    attention_scale: Optional[float] = None
    # x0 = embedding_multiplier * embed[tokens]; every residual branch
    # (mixer and FFN) is added times residual_multiplier; the logits are
    # divided by logits_scaling.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # The head reads ``embed`` transposed: one leaf, whose gradient is
    # the sum of both uses, and no ``lm_head``.
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    # Mamba-2 layers (LayerSpec.mixer == "mamba2"; models/ssm.py): heads,
    # features a head, state size, convolution kernel, chunk of the scan,
    # and how many chunks' decay blocks are live at once.
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # KDA layers (LayerSpec.mixer == "kda"; models/kda.py): heads, features
    # a head (keys and values alike), convolution kernel.
    kda_heads: int = 0
    kda_head_dim: int = 128
    kda_conv: int = 4
    # Latent-attention layers (LayerSpec.mixer == "mla"): the rank of the
    # latent k and v are expanded from, the per-head and the shared part
    # of a key (the shared part is one for all heads), v's head size.
    mla_kv_rank: int = 512
    mla_qk_nope: int = 128
    mla_qk_shared: int = 64
    mla_v_dim: int = 128
    # The sparse layers' router: "softmax" | "sigmoid" (models/moe.py
    # MoEConfig.router: sigmoid scores, a balancing bias in the choice).
    moe_router: str = "softmax"
    # Short-convolution layers (LayerSpec.mixer == "sconv";
    # models/sconv.py): the taps of the depthwise causal convolution.
    sconv_kernel: int = 3
    # RMS norm over each head's features of q and of k, after the
    # projection and before the rotary embedding: one weight of head_dim
    # for q and one for k (leaves q_norm, k_norm), shared by the layer's
    # heads, eps norm_eps. Training forward only.
    qk_norm: bool = False

    def __post_init__(self):
        if self.layers:
            if len(self.layers) != self.n_layers:
                raise ValueError(
                    f"layers describes {len(self.layers)} layers, "
                    f"n_layers is {self.n_layers}")
            if self.positional != "rope" or self.head_size is None:
                raise ValueError(
                    "a per-layer description needs positional='rope' "
                    "(each LayerSpec carries its rotary embedding or "
                    "None) and an explicit head_size")
            if any(l.mixer not in ("attention", "mamba2", "kda", "mla",
                                   "sconv") for l in self.layers):
                raise ValueError(
                    "a layer's mixer is 'attention', 'mamba2', 'kda', "
                    "'mla' or 'sconv'")
            if self.has_ssm and self.ssm_heads < 1:
                raise ValueError("a Mamba-2 layer needs ssm_heads")
            if self.kda_layers and self.kda_heads < 1:
                raise ValueError("a KDA layer needs kda_heads")
            if any(l.mixer == "mla" and (l.rope or l.window)
                   for l in self.layers):
                raise ValueError(
                    "a latent-attention layer here has neither positions "
                    "nor a window (LayerSpec.rope and .window None)")
            if self.n_kv_heads and any(l.n_heads % self.n_kv_heads
                                       for l in self.layers
                                       if l.mixer == "attention"):
                raise ValueError(
                    "every layer's n_heads must be divisible by "
                    f"n_kv_heads ({self.n_kv_heads})")
        if self.attention_impl not in ("dense", "flash"):
            raise ValueError(
                f"unknown attention_impl {self.attention_impl!r}; "
                "expected 'dense' or 'flash'")
        if self.sp_impl not in ("ring", "ulysses"):
            raise ValueError(
                f"unknown sp_impl {self.sp_impl!r}; "
                "expected 'ring' or 'ulysses'")
        if self.n_kv_heads is not None and not self.layers \
                and self.n_heads % self.n_kv_heads != 0:
            raise ValueError(
                f"n_heads ({self.n_heads}) must be divisible by "
                f"n_kv_heads ({self.n_kv_heads})")
        if self.attention_window is not None and self.attention_window < 1:
            raise ValueError(
                f"attention_window must be >= 1, got "
                f"{self.attention_window}")
        if self.positional not in ("learned", "rope"):
            raise ValueError(
                f"unknown positional {self.positional!r}; expected "
                "'learned' or 'rope'")
        if self.positional == "rope" and self.head_dim % 2 != 0:
            raise ValueError(
                f"rope needs an even head_dim, got {self.head_dim}")
        if self.loss_chunk is not None and self.loss_chunk <= 0:
            raise ValueError(
                f"loss_chunk must be a positive chunk length, got "
                f"{self.loss_chunk}")

    @property
    def head_dim(self):
        return self.head_size or self.d_model // self.n_heads

    @property
    def has_ssm(self):
        return any(l.mixer == "mamba2" for l in self.layers)

    @property
    def kda_layers(self):
        """How many layers mix with KDA."""
        return sum(l.mixer == "kda" for l in self.layers)

    @property
    def sconv_layers(self):
        """How many layers mix with the gated short convolution."""
        return sum(l.mixer == "sconv" for l in self.layers)

    @property
    def has_sparse(self):
        return bool(self.moe_layers) or any(
            l.mlp == "sparse" for l in self.layers)

    def layer_spec(self, i=None):
        """The description of layer ``i``. ``i=None`` is for the paths
        that take one kind of layer (decode, serve, pipeline): the common
        description, or an error where the layers differ."""
        if self.layers:
            if i is None:
                why = {"mamba2": "Mamba-2 layers, whose state has no "
                       "decode step or pipeline stage here",
                       "kda": "KDA layers, whose state has no decode step "
                       "or pipeline stage here",
                       "mla": "latent-attention layers, whose unequal qk / "
                       "v head sizes the cache and the stage program do "
                       "not hold",
                       "sconv": "short-convolution layers, whose last "
                       "products have no decode step or pipeline stage "
                       "here",
                       "qk_norm": "attention layers with the per-head QK "
                       "norm, which only the training forward applies"}
                mixers = {l.mixer for l in self.layers}
                if self.qk_norm:
                    mixers.add("qk_norm")
                raise ValueError(
                    "this path takes one kind of layer; the "
                    "configuration describes its layers one by one "
                    "(TransformerConfig.layers)" + "".join(
                        f", some of them {text}"
                        for mixer, text in why.items() if mixer in mixers))
            return self.layers[i]
        if i is None and (self.attention_scale, self.embedding_multiplier,
                          self.residual_multiplier, self.logits_scaling,
                          self.qk_norm) != (None, 1.0, 1.0, 1.0, False):
            raise ValueError(
                "this path computes the plain block: attention_scale, "
                "embedding_multiplier, residual_multiplier, "
                "logits_scaling and the per-head QK norm (qk_norm) are "
                "applied by the training forward only")
        return LayerSpec(
            n_heads=self.n_heads, window=self.attention_window,
            rope=RopeSpec() if self.positional == "rope" else None,
            mlp="sparse" if i in self.moe_layers else "dense")

    @property
    def moe_cfg(self):
        from .moe import MoEConfig
        return MoEConfig(d_model=self.d_model,
                         d_ff=self.moe_d_ff or self.d_ff,
                         num_experts=self.moe_num_experts,
                         top_k=self.moe_top_k, dtype=self.dtype,
                         param_dtype=self.param_dtype,
                         experts_held=self.moe_experts_held,
                         gated=self.mlp_gated,
                         routed_scale=self.moe_routed_scale,
                         shared_d_ff=self.moe_shared_d_ff,
                         interpret=self.flash_interpret,
                         router=self.moe_router)

    @property
    def ssm_cfg(self):
        from .ssm import SSMConfig
        return SSMConfig(d_model=self.d_model, n_heads=self.ssm_heads,
                         head_dim=self.ssm_head_dim,
                         d_state=self.ssm_state, d_conv=self.ssm_conv,
                         chunk=self.ssm_chunk,
                         norm_eps=self.norm_eps, dtype=self.dtype,
                         param_dtype=self.param_dtype)


    @property
    def kda_cfg(self):
        from .kda import KDAConfig
        return KDAConfig(d_model=self.d_model, n_heads=self.kda_heads,
                         head_dim=self.kda_head_dim, d_conv=self.kda_conv,
                         norm_eps=self.norm_eps,
                         dtype=self.dtype, param_dtype=self.param_dtype,
                         interpret=self.flash_interpret)

    @property
    def sconv_cfg(self):
        from .sconv import SConvConfig
        return SConvConfig(d_model=self.d_model, d_conv=self.sconv_kernel,
                           dtype=self.dtype, param_dtype=self.param_dtype)


@dataclasses.dataclass(frozen=True)
class ShardAxes:
    """Mesh axis names the forward runs over; None elides the collective."""
    dp: Optional[str] = "dp"
    sp: Optional[str] = "sp"
    tp: Optional[str] = "tp"
    ep: Optional[str] = None  # expert parallel (MoE layers only)


def init_params(key, cfg):
    """Full (unsharded) parameter pytree; shard by placing with
    :func:`param_specs` NamedShardings (or pass per-shard slices under
    shard_map)."""
    keys = jax.random.split(key, 3 + cfg.n_layers)
    pd = cfg.param_dtype
    d, hd, ff = cfg.d_model, cfg.head_dim, cfg.d_ff

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, pd) / math.sqrt(fan_in))

    layers = []
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[3 + i], 4)
        spec = cfg.layer_spec(i)
        h = spec.n_heads
        layer = {"ln1": jnp.ones((d,), pd), "ln2": jnp.ones((d,), pd)}
        h_kv = cfg.n_kv_heads
        if spec.mixer == "mamba2":
            from .ssm import init_ssm_params
            layer["ssm"] = init_ssm_params(lk[0], cfg.ssm_cfg)
        elif spec.mixer == "kda":
            from .kda import init_kda_params
            layer["kda"] = init_kda_params(lk[0], cfg.kda_cfg)
        elif spec.mixer == "sconv":
            from .sconv import init_sconv_params
            layer["sconv"] = init_sconv_params(lk[0], cfg.sconv_cfg)
        elif spec.mixer == "mla":
            mk = jax.random.split(lk[0], 3)
            rank, vd = cfg.mla_kv_rank, cfg.mla_v_dim
            nope, shared = cfg.mla_qk_nope, cfg.mla_qk_shared
            layer["mla"] = {
                "wq": dense(mk[0], (d, h, nope + shared), d),
                "w_kva": dense(mk[1], (d, rank + shared), d),
                "kv_norm": jnp.ones((rank,), pd),
                "w_kvb": dense(mk[2], (rank, h, nope + vd), rank),
                "wo": dense(lk[1], (h, vd, d), h * vd)}
        elif h_kv is not None and h_kv != h:
            qk = jax.random.split(lk[0])
            layer["wq"] = dense(qk[0], (d, h, hd), d)
            layer["wkv"] = dense(qk[1], (d, 2, h_kv, hd), d)
        else:
            layer["wqkv"] = dense(lk[0], (d, 3, h, hd), d)
        if spec.mixer == "attention":
            layer["wo"] = dense(lk[1], (h, hd, d), d)
            if cfg.qk_norm:
                layer["q_norm"] = jnp.ones((hd,), pd)
                layer["k_norm"] = jnp.ones((hd,), pd)
            if cfg.attn_gate:
                # (heads, d_model): a minor dimension of 6 or 9 heads
                # would be padded to 128 lanes wherever XLA keeps the
                # leaf 2-D
                layer["wg"] = dense(jax.random.fold_in(lk[1], 1), (h, d),
                                    d)
        if spec.mlp == "sparse":
            from .moe import init_moe_params
            layer["moe"] = init_moe_params(lk[2], cfg.moe_cfg)
        else:
            layer["w1"] = dense(lk[2], (d, ff), d)
            layer["w2"] = dense(lk[3], (ff, d), ff)
            if cfg.mlp_gated:
                layer["w3"] = dense(jax.random.fold_in(lk[2], 1), (d, ff),
                                    d)
        layers.append(layer)
    out = {
        "embed": dense(keys[0], (cfg.vocab_size, d), d),
        "layers": layers,
        "ln_f": jnp.ones((d,), pd),
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = dense(keys[2], (d, cfg.vocab_size), d)
    if cfg.positional == "learned":
        out["pos"] = dense(keys[1], (cfg.max_seq, d), d)
    return out


def param_specs(cfg, axes=ShardAxes()):
    """PartitionSpec pytree (Megatron-style TP sharding; MoE layers carry
    their expert slices over the ep axis, models/moe.py:moe_specs)."""
    from jax.sharding import PartitionSpec as P

    from .moe import dropless_specs, moe_specs
    tp = axes.tp
    layers = []
    for i in range(cfg.n_layers):
        spec = cfg.layer_spec(i)
        layer = {"ln1": P(), "ln2": P()}
        if spec.mixer == "mamba2":
            from .ssm import ssm_specs
            layer["ssm"] = ssm_specs()         # a layer whole on its chip
        elif spec.mixer == "kda":
            from .kda import kda_specs
            layer["kda"] = kda_specs()
        elif spec.mixer == "sconv":
            from .sconv import sconv_specs
            layer["sconv"] = sconv_specs()
        elif spec.mixer == "mla":
            layer["mla"] = {name: P() for name in (
                "wq", "w_kva", "kv_norm", "w_kvb", "wo")}
        elif cfg.n_kv_heads is not None \
                and cfg.n_kv_heads != spec.n_heads:
            layer["wq"] = P(None, tp, None)        # q heads sharded
            layer["wkv"] = P(None, None, tp, None)  # kv heads sharded
        else:
            layer["wqkv"] = P(None, None, tp, None)  # heads sharded
        if spec.mixer == "attention":
            layer["wo"] = P(tp, None, None)    # row-parallel (psum after)
            if cfg.qk_norm:
                layer["q_norm"], layer["k_norm"] = P(), P()
            if cfg.attn_gate:
                layer["wg"] = P(tp, None)      # one gate per q head
        if spec.mlp == "sparse":
            layer["moe"] = (moe_specs(axes.ep)
                            if cfg.moe_experts_held is None
                            else dropless_specs(cfg.moe_cfg))
        else:
            layer["w1"] = P(None, tp)          # column-parallel
            layer["w2"] = P(tp, None)          # row-parallel (psum after)
            if cfg.mlp_gated:
                layer["w3"] = P(None, tp)      # column-parallel gate
        layers.append(layer)
    out = {
        "embed": P(tp, None),              # vocab-parallel
        "layers": layers,
        "ln_f": P(),
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = P(None, tp)       # vocab-parallel logits
    if cfg.positional == "learned":
        out["pos"] = P()
    return out


def _spec_mentions(spec, name):
    """True when a PartitionSpec entry shards a dim over ``name``
    (entries may be axis tuples)."""
    for e in spec:
        if e == name or (isinstance(e, (tuple, list)) and name in e):
            return True
    return False


def model_parallel_keys(cfg, axes=None):
    """Exact tree paths (jax.tree_util.keystr strings) of every
    tensor-parallel leaf in :func:`param_specs` — the ``model_keys``
    input of ``DistributedOptimizer``'s per-leaf sharding spec
    (optimizers.py; docs/performance.md "Composable parallelism").

    Full paths, not bare names, because the spec classifies leaves by
    keystr substring: ``"wq"`` would also match ``wqkv``, and the dense
    ``w1``/``w2`` names reappear inside MoE expert stacks (which shard
    over ``ep``, never ``tp``). ``axes`` defaults to the training mesh's
    model axis (``tp="model"``)."""
    axes = axes or ShardAxes(dp=None, sp=None, tp="model", ep="ep")
    if axes.tp is None:
        return ()
    specs = param_specs(cfg, axes)
    from jax.tree_util import keystr, tree_flatten_with_path
    return tuple(keystr(path)
                 for path, spec in tree_flatten_with_path(specs)[0]
                 if _spec_mentions(spec, axes.tp))


def slice_param_shards(params, specs, mesh):
    """Fake-replicated shards for shard_map consumption: every leaf keeps
    a replicated P() placement but per-device VALUES differ — each shard
    holds its dynamic slice of every dim its spec shards over a mesh
    axis. This is the layout the spec-driven compiled step trains on
    (expert stacks over ``ep``, the TP trunk over ``model``); leaves
    whose spec names no mesh axis come back replicated untouched."""
    from jax.sharding import PartitionSpec as P

    def slice_leaf(p, spec):
        for dim, entry in enumerate(spec):
            names = entry if isinstance(entry, (tuple, list)) else (entry,)
            for name in names:
                if name is None or name not in mesh.shape:
                    continue
                n = mesh.shape[name]
                if n == 1:
                    continue
                loc = p.shape[dim] // n
                p = lax.dynamic_slice_in_dim(
                    p, lax.axis_index(name) * loc, loc, dim)
        return p

    def shard_fn(p):
        return jax.tree.map(slice_leaf, p, specs)

    return jax.jit(jax.shard_map(shard_fn, mesh=mesh, in_specs=(P(),),
                                 out_specs=P(), check_vma=False))(params)


def _rope(x, positions, theta=10000.0):
    """Rotary embedding: rotate feature pairs of x (B, S, H, D) by
    per-position angles; positions (S,) are GLOBAL indices, so sharded
    callers pass their shard's offsets and the rotation commutes with
    any later K/V movement (ring ppermute / ulysses all-to-all)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs[None]  # (S, half)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def rope_inv_freq(spec, head_dim):
    """Rotation frequencies (numpy float64, ``rotary_dim / 2`` of them) of
    a :class:`RopeSpec`: ``theta ** (-2 i / rotary_dim)``, under YaRN
    blended with the same divided by ``yarn_factor``."""
    import numpy as np
    rot = spec.rotary_dim or head_dim
    inv = spec.theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    if spec.yarn_factor is None:
        return inv

    def correction_dim(rotations):
        return rot * math.log(spec.yarn_original_max_seq
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(spec.theta))

    low = max(math.floor(correction_dim(spec.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(spec.yarn_beta_slow)), rot - 1)
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0, 1)
    return inv / spec.yarn_factor * ramp + inv * (1 - ramp)


def _rope_spec(x, positions, spec):
    """:func:`_rope` as a :class:`RopeSpec` describes it: the plain spec
    IS :func:`_rope`; otherwise the first ``rotary_dim`` features of each
    head are rotated at :func:`rope_inv_freq` and the rest pass through."""
    if spec.rotary_dim is None and spec.yarn_factor is None \
            and spec.attention_factor == 1.0:
        return _rope(x, positions, spec.theta)
    d = x.shape[-1]
    rot = spec.rotary_dim or d
    half = rot // 2
    freqs = jnp.asarray(rope_inv_freq(spec, d), jnp.float32)
    ang = positions[:, None].astype(jnp.float32) * freqs[None]
    cos = (jnp.cos(ang) * spec.attention_factor)[None, :, None, :]
    sin = (jnp.sin(ang) * spec.attention_factor)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., rot:]], axis=-1)
    return out.astype(x.dtype)


def _rope_b(x, positions, theta=10000.0):
    """:func:`_rope` with PER-SEQUENCE positions (B, S) — the decode-time
    variant: each sequence in a continuous batch sits at its own offset,
    so the rotation angle varies along the batch dim too. Bit-identical
    to :func:`_rope` when every row carries the same position (same cos/
    sin values, same multiply-add order; tests/test_serving.py pins the
    prefill-vs-decode parity this relies on)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _rmsnorm(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def _axis_index(axis):
    return lax.axis_index(axis) if axis else 0


def _psum(x, axis):
    return lax.psum(x, axis) if axis else x


def _pmax(x, axis):
    """Cross-shard elementwise max that stays differentiable-traceable:
    lax.pmax has no JVP rule, so gather-then-max (all_gather transposes to
    psum_scatter) is used instead; callers stop_gradient the result."""
    if not axis:
        return x
    return jnp.max(lax.all_gather(x, axis, axis=0), axis=0)


def _pmean(x, axes):
    for a in axes:
        if a:
            x = lax.pmean(x, a)
    return x


def _embed_rows(params, tokens, axes):
    """Vocab-parallel embedding rows (no positional): each tp shard holds a
    contiguous vocab stripe; out-of-stripe tokens contribute zero, one psum
    restores the full row. Shared by training (embed_tokens) and decoding
    (prefill_cache/decode_step), which add their own position handling."""
    emb = params["embed"]
    vloc = emb.shape[0]
    tp_idx = _axis_index(axes.tp)
    local = tokens - tp_idx * vloc
    valid = (local >= 0) & (local < vloc)
    rows = jnp.take(emb, jnp.clip(local, 0, vloc - 1), axis=0)
    rows = jnp.where(valid[..., None], rows, 0)
    return _psum(rows, axes.tp)


def _gather_vocab(logits, tp_axis):
    """Reassemble full-vocab logits from contiguous tp stripes (decode-time
    only: (B, V_loc) is tiny at serving batch sizes, and every shard needs
    the full distribution to select the same next token)."""
    if not tp_axis:
        return logits
    return lax.all_gather(logits, tp_axis, axis=-1, tiled=True)


def embed_tokens(params, tokens, cfg, axes):
    """Vocab-parallel embedding lookup + learned positions (training path:
    positions start at this sp shard's offset). Device scope
    ``hvd_embed``; the embedding gradient's scatter-add inherits it."""
    with jax.named_scope("hvd_embed"):
        x = _embed_rows(params, tokens, axes)
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier

        if cfg.positional != "learned":
            return x.astype(cfg.dtype)  # rope: rotation happens on q/k
        s_loc = tokens.shape[1]
        sp_idx = _axis_index(axes.sp)
        pos = lax.dynamic_slice_in_dim(params["pos"], sp_idx * s_loc, s_loc)
        return (x + pos[None]).astype(cfg.dtype)


def _qkv_proj(p, h, cfg):
    """Shared q/k/v projection (training blocks and the decode path must
    stay in lockstep — test_decode_matches_forward depends on it)."""
    if "wq" in p:
        # GQA: separate projections; K/V carry fewer heads (per-shard
        # kv head count = n_kv_heads / tp)
        q = jnp.einsum("bsd,dhx->bshx", h, p["wq"].astype(cfg.dtype),
                       preferred_element_type=jnp.float32
                       ).astype(cfg.dtype)
        kv = jnp.einsum("bsd,dchx->bschx", h, p["wkv"].astype(cfg.dtype),
                        preferred_element_type=jnp.float32
                        ).astype(cfg.dtype)
        return q, kv[:, :, 0], kv[:, :, 1]
    # wqkv per-shard: (d, 3, h_loc, hd)
    qkv = jnp.einsum("bsd,dchx->bschx", h, p["wqkv"].astype(cfg.dtype),
                     preferred_element_type=jnp.float32).astype(cfg.dtype)
    return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]


def _attention_block(p, x, cfg, axes, spec=None):
    out, _, _ = _attention_block_kv(p, x, cfg, axes, spec)
    return out


def _attention_block_kv(p, x, cfg, axes, spec=None):
    """:func:`_attention_block`, also returning the (post-rope) K/V this
    block computed — the serve prefill path (serve/engine.py) scatters
    them into the paged KV cache while keeping the trunk ops literally
    the ones the training forward runs (the prefill-vs-forward bitwise
    parity in tests/test_serving.py depends on this sharing, exactly
    like test_decode_matches_forward depends on _qkv_proj).

    ``spec`` is this layer's :class:`LayerSpec` (``None``: the one kind
    of layer the configuration has). The attention itself runs under the
    device scope ``hvd_attn_window`` or ``hvd_attn_full``; the
    projections, rope and the per-head gate around it under
    ``hvd_attn_proj``; with ``cfg.qk_norm`` the two per-head norms
    between projection and rope under ``hvd_qk_norm``."""
    spec = spec or cfg.layer_spec()
    h = _pre_norm(x, p["ln1"], cfg)
    with jax.named_scope("hvd_attn_proj"):
        q, k, v = _qkv_proj(p, h, cfg)
    if cfg.qk_norm:
        if axes.sp or axes.tp:
            raise ValueError(
                "an attention layer with the per-head QK norm runs whole "
                "on its chip: the sequence-parallel paths and the heads' "
                "tensor parallelism (axes.sp, axes.tp) do not carry the "
                "norm's shared weights")
        with jax.named_scope("hvd_qk_norm"):
            q = _head_norm(q, p["q_norm"], cfg.norm_eps)
            k = _head_norm(k, p["k_norm"], cfg.norm_eps)
    with jax.named_scope("hvd_attn_proj"):
        if spec.rope is not None:
            s_loc = x.shape[1]
            start = _axis_index(axes.sp) * s_loc
            positions = start + jnp.arange(s_loc)
            q = _rope_spec(q, positions, spec.rope)
            k = _rope_spec(k, positions, spec.rope)
    win = spec.window
    with jax.named_scope("hvd_attn_window" if win else "hvd_attn_full"):
        attn = _attend(q, k, v, win, cfg, axes)
    with jax.named_scope("hvd_attn_proj"):
        if "wg" in p:
            gate = jax.nn.sigmoid(jnp.einsum(
                "bsd,hd->bsh", h, p["wg"].astype(cfg.dtype),
                preferred_element_type=jnp.float32))
            attn = attn * gate[..., None].astype(cfg.dtype)
        out = _psum(jnp.einsum(
            "bshx,hxd->bsd", attn, p["wo"].astype(cfg.dtype),
            preferred_element_type=jnp.float32), axes.tp)
    return _residual(x, out, cfg), k, v


def _head_norm(x, scale, eps):
    """RMS norm over each head's features, x (B, S, H, D), ``scale`` (D,)
    shared by the heads: float32 throughout like :func:`_rmsnorm`, rounded
    to x's type once, after the weight."""
    return _rmsnorm(x.astype(jnp.float32), scale.astype(jnp.float32),
                    eps).astype(x.dtype)


def _pre_norm(x, scale, cfg):
    """A block's RMS norm of its input, under the device scope
    ``hvd_block_io`` (with :func:`_residual`: what a block does around
    its mixer or FFN). XLA fuses most of them into the matmul that
    consumes them; such a fusion keeps the matmul's name."""
    with jax.named_scope("hvd_block_io"):
        return _rmsnorm(x, scale, cfg.norm_eps)


def _residual(x, branch, cfg):
    """``x + residual_multiplier * branch``; the branch arrives as its
    matmul accumulated it (float32) and is rounded once."""
    with jax.named_scope("hvd_block_io"):
        if cfg.residual_multiplier != 1.0:
            branch = branch * cfg.residual_multiplier
        return x + branch.astype(cfg.dtype)


def _ssm_block(p, x, cfg, axes):
    """The mixer half of a Mamba-2 layer (models/ssm.py): ``(x +
    residual_multiplier * mixer(rmsnorm(x)), state_rms)``."""
    if axes.sp:
        raise ValueError(
            "a Mamba-2 layer carries its state along the sequence; "
            "sequence parallelism (axes.sp) would have to hand it from "
            "shard to shard and is not supported")
    from .ssm import mamba2_mixer
    out, rms = mamba2_mixer(p["ssm"], _pre_norm(x, p["ln1"], cfg),
                            cfg.ssm_cfg)
    return _residual(x, out, cfg), rms


def _kda_block(p, x, cfg, axes):
    """The mixer half of a KDA layer (models/kda.py): ``(x +
    residual_multiplier * mixer(rmsnorm(x)), state_rms)``."""
    if axes.sp:
        raise ValueError(
            "a KDA layer carries its state along the sequence; sequence "
            "parallelism (axes.sp) would have to hand it from shard to "
            "shard and is not supported")
    from .kda import kda_mixer
    out, rms = kda_mixer(p["kda"], _pre_norm(x, p["ln1"], cfg),
                         cfg.kda_cfg)
    return _residual(x, out, cfg), rms


def _sconv_block(p, x, cfg, axes):
    """The mixer half of a short-convolution layer (models/sconv.py):
    ``x + residual_multiplier * mixer(rmsnorm(x))``."""
    if axes.sp:
        raise ValueError(
            "a short-convolution layer reads the positions before its "
            "own; sequence parallelism (axes.sp) would have to hand them "
            "from shard to shard and is not supported")
    from .sconv import sconv_mixer
    out = sconv_mixer(p["sconv"], _pre_norm(x, p["ln1"], cfg),
                      cfg.sconv_cfg)
    return _residual(x, out, cfg)


def _mla_block(p, x, cfg, axes):
    """The mixer half of a latent-attention layer without positions: q
    one projection a head (``mla_qk_nope + mla_qk_shared`` wide); k's
    per-head part and v expanded from one normed latent of
    ``mla_kv_rank``, k's shared part the same for every head; softmax
    over ``q . k / sqrt(q's width)``, full causal. The five projections
    and the latent's norm run under the device scope ``hvd_mla_proj``,
    the attention under ``hvd_attn_full``."""
    if axes.sp or axes.tp:
        raise ValueError(
            "a latent-attention layer (unequal qk / v head sizes, one "
            "shared key part) is whole on its chip: no sequence or "
            "tensor parallelism (axes.sp, axes.tp)")
    m, dt, f32 = p["mla"], cfg.dtype, jnp.float32
    rank, nope = cfg.mla_kv_rank, cfg.mla_qk_nope
    h = _pre_norm(x, p["ln1"], cfg)
    with jax.named_scope("hvd_mla_proj"):
        q = jnp.einsum("bsd,dhx->bshx", h, m["wq"].astype(dt),
                       preferred_element_type=f32).astype(dt)
        kva = jnp.einsum("bsd,de->bse", h, m["w_kva"].astype(dt),
                         preferred_element_type=f32).astype(dt)
        latent = _rmsnorm(kva[..., :rank], m["kv_norm"], cfg.norm_eps)
        kvb = jnp.einsum("bsr,rhx->bshx", latent.astype(dt),
                         m["w_kvb"].astype(dt),
                         preferred_element_type=f32).astype(dt)
        shared = jnp.broadcast_to(
            kva[:, :, None, rank:], kvb.shape[:3] + (cfg.mla_qk_shared,))
        k = jnp.concatenate([kvb[..., :nope], shared], axis=-1)
        v = kvb[..., nope:]
    with jax.named_scope("hvd_attn_full"):
        attn = _attend(q, k, v, None, cfg, axes)
    with jax.named_scope("hvd_mla_proj"):
        out = jnp.einsum("bshx,hxd->bsd", attn, m["wo"].astype(dt),
                         preferred_element_type=f32)
    return _residual(x, out, cfg)


def _attend(q, k, v, win, cfg, axes):
    """Causal attention of one layer by the configured implementation
    (flash / dense; ring or ulysses under sp), keys within ``win``."""
    scale = cfg.attention_scale
    if axes.sp and scale is not None:
        raise ValueError(
            "attention_scale is not carried through the sequence-parallel "
            "attention paths (ring / ulysses)")
    if axes.sp and cfg.sp_impl == "ulysses":
        # ulysses: all-to-all re-shards to (full seq, local heads); the
        # chosen kernel then runs whole over the global sequence (so a
        # sliding window applies in global positions, correctly).
        from ..parallel.ulysses import ulysses_attention

        if cfg.attention_impl == "flash":
            from ..ops.flash_attention import flash_attention

            def attn_fn(qg, kg, vg, causal, scale):
                assert scale is None  # kernel applies 1/sqrt(D)
                return flash_attention(qg, kg, vg, causal,
                                       interpret=cfg.flash_interpret,
                                       window=win)
        else:
            def attn_fn(qg, kg, vg, causal, scale):
                return dense_attention(qg, kg, vg, causal=causal,
                                       scale=scale, window=win)

        return ulysses_attention(q, k, v, axis_name=axes.sp, causal=True,
                                 attn_fn=attn_fn)
    if axes.sp:
        # ring x flash: the Pallas kernel computes each visiting tile when
        # attention_impl == "flash" (band-offset kernels under a window);
        # partials merge by log-sum-exp. With a window the ring runs
        # 1 + ceil((W-1)/S_local) rotations instead of sp_size — cost
        # follows the window, not the context.
        return ring_attention(q, k, v, axis_name=axes.sp, causal=True,
                              impl=cfg.attention_impl,
                              interpret=cfg.flash_interpret,
                              window=win)
    if cfg.attention_impl == "flash":
        from ..ops.flash_attention import flash_attention
        return flash_attention(q, k, v, True,
                               interpret=cfg.flash_interpret, window=win,
                               scale=scale)
    return dense_attention(q, k, v, causal=True, window=win, scale=scale)


def _mlp_block(p, x, cfg, axes, moe_full_capacity=False):
    """Dense or MoE FFN, depending on the layer's params.
    Returns (output, aux_loss) — aux is the MoE load-balancing loss
    (0 for dense layers). ``moe_full_capacity`` is the serving mode:
    capacity covers every (token, expert) assignment so no token is
    dropped and each token's output is independent of who else is in
    the batch (continuous batching joins/evicts mid-stream; a capacity
    drop that depended on batch composition would make a sequence's
    tokens change when its neighbors change)."""
    out, aux, _ = _mlp_block_stats(p, x, cfg, axes, moe_full_capacity)
    return out, aux


def _mlp_block_stats(p, x, cfg, axes, moe_full_capacity=False):
    """:func:`_mlp_block` plus the routing counters of a dropless sparse
    layer (models/moe.py ``moe_dropless`` ``stats``; ``None`` for every
    other layer). The parameters pick the FFN: ``moe`` a sparse layer —
    dropless when the configuration states the experts held, else the
    capacity layer over ``axes.ep`` —, ``w3`` the gated SiLU FFN
    (:func:`_gated_ffn`). The two dense FFNs run under the device scope
    ``hvd_ffn`` (the sparse ones under models/moe.py's own)."""
    h = _pre_norm(x, p["ln2"], cfg)
    zero = jnp.zeros((), jnp.float32)
    if "moe" in p:
        with jax.named_scope("hvd_block_io"):
            h = h.astype(cfg.dtype)
        if cfg.moe_experts_held is not None:
            from .moe import moe_dropless
            y, stats = moe_dropless(p["moe"], h, cfg.moe_cfg)
            return _residual(x, y, cfg), zero, stats
        from .moe import moe_layer
        y, aux = moe_layer(p["moe"], h, cfg.moe_cfg, ep_axis=axes.ep,
                           full_capacity=moe_full_capacity)
        return _residual(x, y, cfg), aux, None
    with jax.named_scope("hvd_ffn"):
        if "w3" in p:
            out = _gated_ffn(
                h.astype(jnp.float32), p["w1"].astype(cfg.dtype),
                p["w3"].astype(cfg.dtype), p["w2"].astype(cfg.dtype),
                _gate_slices(h.size // h.shape[-1], p["w1"].shape[1],
                             cfg))
        else:
            u = jax.nn.gelu(jnp.einsum(
                "bsd,df->bsf", h, p["w1"].astype(cfg.dtype),
                preferred_element_type=jnp.float32))
            out = jnp.einsum("bsf,fd->bsd", u.astype(cfg.dtype),
                             p["w2"].astype(cfg.dtype),
                             preferred_element_type=jnp.float32)
        out = _psum(out, axes.tp)
    return _residual(x, out, cfg), zero, None


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _cotangent_once(x, dt):
    """``x``; its cotangent is rounded to ``dt`` and stored, once, for
    every consumer. Both consumers of the gated FFN's two gate cotangents
    are matmuls at the default precision, which multiply float32 operands
    rounded to bfloat16: the rounding is theirs, moved in front of them
    (PERF.md section 6 PR 32: stored in float32 and stored rounded, the
    first update of a step is the same in every bit). Left to itself XLA
    fuses the cotangent's producer (an exp, a divide, five multiplies an
    element) into each consumer and evaluates it there again and again:
    the weight gradients of ``w1`` / ``w3`` ran at a third of the matmul
    unit's rate."""
    return x


_cotangent_once.defvjp(
    lambda x, dt: (x, None),
    lambda dt, _, ct: (
        lax.optimization_barrier(ct.astype(dt)).astype(ct.dtype),))


def _gated_ffn_rows(h, w1, w3, w2, dt):
    """``(silu(h w1) * (h w3)) w2`` over rows of tokens ``h`` (t, d), the
    activations' type ``dt``: what :func:`_gated_ffn` computes and what
    its backward hands to autodiff, a slice of the rows at a time."""
    u1 = _cotangent_once(_mm("td,df->tf", h, w1), dt)
    u3 = _cotangent_once(_mm("td,df->tf", h, w3), dt)
    with jax.named_scope("hvd_ffn_gate"):
        act = (jax.nn.silu(u1) * u3).astype(dt)
    return _mm("tf,fd->td", act, w2)


# The gated FFN's backward takes the tokens in slices while one float32
# (tokens, ff) array of a slice - u1, u3 - is larger than this, in a model
# whose FFNs are all dense: there the step's memory peak lies in a gated
# layer's backward, and slices lower it. In a model with sparse layers it
# does not, and slices of its few dense layers only make XLA schedule the
# sparse layers' backward anew and the heap fragment. What was observed on
# four shapes, not a law (PERF.md section 6 PR 32; a rule on the shapes
# alone, tokens against d, held the two cells and chose wrongly on the
# third shape it was tried on). The whole step
# compiled for a described v5e (benchmark/tools/fit_mode.py, GiB) / on the
# chip the allocator's GiB / ms a step:
#   granite4h-micro_s16k, all dense, 16,384 tokens, ff 8192 (512 MiB; its
#   parent 12.15 / 12.350 / 1,075.6): whole 12.34 / 12.478 / 940.7, over
#   peak_hbm_gib's bound of 1 %; 2 slices 12.03 / 12.192 / 953.9 (taken);
#   4 slices 11.86 offline. Over 32,768 tokens: whole 15.59,
#   4 slices 14.46 offline
#   laguna-s21_s8k, 1 dense layer of 5, 16,384 tokens, ff 12288 (768 MiB;
#   its parent 9.67 / 9.496): whole 9.51 / 9.492 / 357.7 (taken); 2 slices
#   12.09 offline. Over 32,768 tokens: whole 11.85, 2 slices 14.82 offline
_GATE_SLICE_BYTES = 256 * 2 ** 20


def _gate_slices(tokens, ff, cfg):
    """Slices of the tokens the gated FFN's backward runs over: a power
    of two that leaves whole (8, 128) tiles (see the constant above)."""
    n = 1
    while (not cfg.has_sparse and tokens * ff * 4 > n * _GATE_SLICE_BYTES
           and tokens % (256 * n) == 0):
        n *= 2
    return n


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gated_ffn(h, w1, w3, w2, slices):
    """The gated SiLU FFN ``(silu(h w1) * (h w3)) w2`` of a dense layer:
    ``h`` (..., d) float32 as :func:`_rmsnorm` returns it, the weights
    already cast to the activations' type; float32 out.

    The arithmetic is autodiff's of :func:`_gated_ffn_rows`, forward and
    backward. A ``custom_vjp`` only to run the backward over ``slices``
    of the tokens (:func:`_gate_slices`) one after the other, so that the
    float32 ``u1`` / ``u3`` and the stored gate cotangents of one slice
    are live at a time: whole, granite4h-micro_s16k's step passes its
    parent's peak by 1.04 % on the chip. It keeps ``h`` and the weights
    and computes ``u1`` / ``u3`` again in the backward, as
    ``jax.checkpoint`` of the layer does anyway; a model WITHOUT remat
    pays for that with two of the layer's eight FFN matmuls more (no cell
    runs one)."""
    return _gated_ffn_rows(h.reshape(-1, h.shape[-1]), w1, w3, w2,
                           w1.dtype).reshape(h.shape)


def _gated_ffn_fwd(h, w1, w3, w2, slices):
    return _gated_ffn(h, w1, w3, w2, slices), (h, w1, w3, w2)


def _gated_ffn_bwd(n, res, dout):
    h, w1, w3, w2 = res
    dt = w1.dtype
    hd, g = (a.reshape(-1, h.shape[-1]) for a in (h, dout))
    tokens = hd.shape[0]
    # float32 views of the weights (exact): autodiff then hands back a
    # slice's weight gradients unrounded, to be summed in float32 and
    # rounded to dt once, where the transpose of the caller's cast
    # rounds the whole gradient
    ws = tuple(w.astype(jnp.float32) for w in (w1, w3, w2))

    def rows(a, k):
        return a[k * tokens // n:(k + 1) * tokens // n]

    dw, dh = None, []
    hk, gk = rows(hd, 0), rows(g, 0)
    for k in range(n):
        dhk, *part = jax.vjp(
            functools.partial(_gated_ffn_rows, dt=dt), hk, *ws)[1](gk)
        dh.append(dhk)
        dw = part if dw is None else jax.tree.map(jnp.add, dw, part)
        if k + 1 < n:
            # the next slice starts when this one's sums exist: left to
            # itself the scheduler runs the slices side by side
            dw, hk, gk = lax.optimization_barrier(
                (dw, rows(hd, k + 1), rows(g, k + 1)))
    dh = dh[0] if n == 1 else jnp.concatenate(dh)
    return (dh.reshape(h.shape),) + tuple(a.astype(dt) for a in dw)


_gated_ffn.defvjp(_gated_ffn_fwd, _gated_ffn_bwd)


MOE_AUX_COEF = 0.01  # Switch-style load-balance coefficient


def trunk_with_aux(params, tokens, cfg, axes=None):
    """Pre-head activations (B, S_loc, d) + total MoE aux loss."""
    return trunk_with_stats(params, tokens, cfg, axes)[:2]


def trunk_with_stats(params, tokens, cfg, axes=None):
    """:func:`trunk_with_aux` plus what the layers count, stacked over
    the layers that count it, in order: the dropless sparse layers'
    routing counters ``expert_load`` (layers, experts held) and
    ``unrouted_tokens`` (layers,); the Mamba-2 layers' ``ssm_state_rms``
    and the KDA layers' ``kda_state_rms`` (layers, heads), the root mean
    square of each head's final state. ``{}`` when the model has none of
    these layers."""
    axes = axes or ShardAxes(dp=None, sp=None, tp=None)
    from .. import metrics
    # the gated dense layers of THIS model, by what _mlp_block_stats
    # branches on (jax.checkpoint traces layers of one shape once, so
    # the branch itself cannot count them; other callers of _mlp_block
    # leave the gauge as it was)
    metrics.FFN_GATED_LAYERS.set(sum("w3" in p for p in params["layers"]))
    metrics.KDA_LAYERS.set(cfg.kda_layers)
    metrics.KDA_FUSED_LAYERS.set(
        cfg.kda_layers if cfg.kda_cfg.fused else 0)
    metrics.SCONV_LAYERS.set(cfg.sconv_layers)
    x = embed_tokens(params, tokens, cfg, axes)
    aux_total = jnp.zeros((), jnp.float32)

    def one_layer(p, x, spec):
        rms = None
        if spec.mixer == "mamba2":
            x, rms = _ssm_block(p, x, cfg, axes)
        elif spec.mixer == "kda":
            x, rms = _kda_block(p, x, cfg, axes)
        elif spec.mixer == "mla":
            x = _mla_block(p, x, cfg, axes)
        elif spec.mixer == "sconv":
            x = _sconv_block(p, x, cfg, axes)
        else:
            x = _attention_block(p, x, cfg, axes, spec)
        return _mlp_block_stats(p, x, cfg, axes) + (rms,)

    if cfg.remat:
        one_layer = jax.checkpoint(one_layer, static_argnums=(2,))
    routing, state_rms = [], {"mamba2": [], "kda": []}
    for i, p in enumerate(params["layers"]):
        spec = cfg.layer_spec(i)
        x, aux, stats, rms = one_layer(p, x, spec)
        aux_total = aux_total + aux
        if stats is not None:
            routing.append(stats)
        if rms is not None:
            state_rms[spec.mixer].append(rms)
    stats = jax.tree.map(lambda *a: jnp.stack(a), *routing) if routing \
        else {}
    if state_rms["mamba2"]:
        stats["ssm_state_rms"] = jnp.stack(state_rms["mamba2"])
    if state_rms["kda"]:
        stats["kda_state_rms"] = jnp.stack(state_rms["kda"])
    return x, aux_total, stats


def forward_with_aux(params, tokens, cfg, axes=None):
    """(logits, total_moe_aux_loss) over the (possibly vocab-sharded)
    head; logits (B, S_loc, V_loc)."""
    x, aux_total = trunk_with_aux(params, tokens, cfg, axes)
    return _head(params, x, cfg), aux_total  # f32


def forward(params, tokens, cfg, axes=None):
    """Logits over the (possibly vocab-sharded) head: (B, S_loc, V_loc)."""
    return forward_with_aux(params, tokens, cfg, axes)[0]


def _nll(logits, targets, axes):
    """Per-token negative log likelihood over (possibly tp-sharded)
    logits, shape (B, S).

    The softmax over a tp-sharded vocab runs without materializing full
    logits: global max via pmax, normalizer via psum, target logit via a
    masked-gather psum (Megatron's parallel cross-entropy pattern)."""
    vloc = logits.shape[-1]
    tp_idx = _axis_index(axes.tp)

    # The max is only a numerical-stability shift: gradients through it
    # cancel exactly, and pmax has no transpose rule — stop_gradient is the
    # correct (not approximate) treatment.
    m = lax.stop_gradient(_pmax(jnp.max(logits, axis=-1), axes.tp))  # (B, S)
    z = _psum(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), axes.tp)
    local_t = targets - tp_idx * vloc
    valid = (local_t >= 0) & (local_t < vloc)
    tgt_logit = jnp.take_along_axis(
        logits, jnp.clip(local_t, 0, vloc - 1)[..., None], axis=-1)[..., 0]
    tgt_logit = _psum(jnp.where(valid, tgt_logit, 0.0), axes.tp)
    return jnp.log(z) + m - tgt_logit


def _cross_entropy(logits, targets, axes):
    return jnp.mean(_nll(logits, targets, axes))


# The tokens one product into the head's weight gradient should contract
# over, and the most the group's stored logit cotangents may take. Under
# the scan's own transpose every chunk adds its product into the whole
# float32 (d, V_loc) gradient, which is read and written once a chunk: at
# one sequence of 16,384 positions in chunks of 512 the gradient's bytes,
# not its FLOPs, set the product's time (sc2-3b_s16k: 32 x 1.2 GB = 47 ms
# at 819 GB/s for 25.1 ms of FLOPs, 56.7 ms measured). The sweep, PR 36's
# chip runs of sc2-3b_s16k_noremat on one seed (PERF.md section 6): chunks
# a product (tokens) / the product's ms a step / dev_head_ce_ms /
# tokens/s / the allocator's peak GiB:
#   1 (512)    56.7 / 143.9 / 28,262 / 14.261, the plain scan
#   2 (1,024)  34.7 / 124.0 / 29,266 / 14.361 (taken: 96 MiB stored)
#   4 (2,048)  27.0 / 117.2 / 29,581 / 14.472, over peak_hbm_gib's bound
#              of 1 % by 0.07 GiB: the 192 MiB it stores are ALL that cell
#              adds, its peak lies in the head's backward
#   8 (4,096)  34.8 / 126.1 / 29,140 / 14.683: a product of 4,096 tokens
#              runs SLOWER than two of 2,048 (8.7 ms against 2 x 3.4)
# So 2,048 tokens is the target and the bytes are what stops short of it:
# sc2-3b_s16k itself (remat; 10.339 GiB at 2 and at 4) would take 4 chunks
# for 25,762 tokens/s against 25,505, but the two cells have one shape.
_HEAD_GROUP_TOKENS = 2048
_HEAD_GROUP_BYTES = 128 * 2 ** 20


def _head_grad_chunks(chunks, chunk_tokens, vloc, itemsize):
    """Chunks of the chunked cross entropy per product into the head's
    weight gradient: the smallest power of two dividing ``chunks`` that
    gives a product ``_HEAD_GROUP_TOKENS`` tokens, while the group's logit
    cotangents (tokens x ``vloc`` x ``itemsize``) stay under
    ``_HEAD_GROUP_BYTES``; a chunk count it cannot divide that far keeps
    the largest it can (1: every chunk its own product)."""
    g = 1
    while (g * chunk_tokens < _HEAD_GROUP_TOKENS and chunks % (2 * g) == 0
           and 2 * g * chunk_tokens * vloc * itemsize <= _HEAD_GROUP_BYTES):
        g *= 2
    return g


def _grouped_nll(cfg, axes):
    """``f(carry, head, xg, tg)``: ``carry`` plus the negative log
    likelihoods of a GROUP of chunks, ``xg`` (g, B, chunk, d) and ``tg``
    (g, B, chunk), added chunk by chunk as the plain scan adds them, under
    the head's leaves ``head`` (``ln_f`` and the weight).

    The arithmetic is autodiff's of :func:`_head` and :func:`_nll`, chunk
    by chunk in both directions (the backward computes each chunk's logits
    again, as ``jax.checkpoint`` would). A ``custom_vjp`` only to make the
    weight gradient ONE product over the group's tokens: the transpose of
    a scan cannot defer a product across iterations, so it adds every
    chunk's ``xn^T d`` into the whole float32 (d, V_loc) gradient. Here
    each chunk's logit cotangent ``d`` is kept rounded to ``cfg.dtype``
    (the product is at the default precision, which multiplies operands
    rounded to bfloat16: the rounding is its own, moved in front of it as
    in :func:`_cotangent_once`), and after the last chunk autodiff's
    transpose of :func:`_head_product` in the weight runs once over all
    of them."""

    def norm(ln, x):
        return _rmsnorm(x, ln, cfg.norm_eps)

    def chunk_nll(raw, tk):
        return jnp.sum(_nll(_head_scale(raw, cfg), tk, axes))

    def total(carry, head, xg, tg):
        def one(carry, xt):
            xk, tk = xt
            return carry + chunk_nll(
                _head_product(head, norm(head["ln_f"], xk), cfg), tk), None
        return lax.scan(one, carry, (xg, tg))[0]

    group = jax.custom_vjp(total)

    def bwd(res, ct):
        # as jax.checkpoint does: what the forward left is not to be
        # touched before the cotangent is there. Without it XLA moves
        # the recompute about and granite4h-micro_s16k's step compiles to
        # 13.06 GiB for its parent's 12.00 (11.97 with it; offline)
        head, xg, tg, ct = lax.optimization_barrier(res + (ct,))
        weight = {k: v for k, v in head.items() if k != "ln_f"}

        def one(dln, xt):
            xk, tk = xt
            xn, norm_vjp = jax.vjp(norm, head["ln_f"], xk)
            raw, xn_vjp = jax.vjp(
                lambda a: _head_product(weight, a, cfg), xn)
            d, = jax.vjp(lambda r: chunk_nll(r, tk), raw)[1](ct)
            d = d.astype(cfg.dtype)
            dlnk, dxk = norm_vjp(*xn_vjp(d.astype(raw.dtype)))
            return dln + dlnk, (dxk, d)

        dln, (dxg, dg) = lax.scan(one, jnp.zeros_like(head["ln_f"]),
                                  (xg, tg))
        # one product over the group's tokens
        xn = norm(head["ln_f"], xg).reshape(1, -1, xg.shape[-1])
        dweight, = jax.vjp(lambda w: _head_product(w, xn, cfg), weight)[1](
            dg.reshape(1, -1, dg.shape[-1]).astype(jnp.float32))
        return ct, dict(dweight, ln_f=dln), dxg, None

    group.defvjp(lambda carry, head, xg, tg: (
        total(carry, head, xg, tg), (head, xg, tg)), bwd)
    return group


def _chunked_cross_entropy(params, x, targets, cfg, axes):
    """Mean CE with the head applied per sequence chunk: the logits that
    are live are one chunk's (B, chunk, V_loc) float32 in both directions
    (the backward computes each chunk's logits again), instead of the
    full (B, S, V_loc) — the long-context memory wall at real vocab
    sizes — plus, in the backward, the logit cotangents of one GROUP of
    chunks in ``cfg.dtype``, (g, B, chunk, V_loc): the head's weight
    gradient is one product a group (:func:`_grouped_nll`), ``g`` chosen
    from the shapes by :func:`_head_grad_chunks` and shown by the gauge
    ``hvd_head_grad_chunks``. At ``g`` = 1 (a chunk of
    ``_HEAD_GROUP_TOKENS`` tokens or more, as in cgpt13b_dp1 / _dp4 and
    sc2-3b_s4k, whose compiled steps are their parent's instruction for
    instruction) it is the plain scan under ``jax.checkpoint``."""
    from .. import metrics
    chunk = cfg.loss_chunk
    b, s_loc, d = x.shape
    if s_loc % chunk != 0:
        # Silently materializing full logits here would OOM exactly the
        # long-context runs the option exists for — fail with the fix.
        raise ValueError(
            f"loss_chunk ({chunk}) must divide the per-shard sequence "
            f"length ({s_loc}); pick a divisor (e.g. "
            f"{math.gcd(s_loc, chunk)})")
    n = s_loc // chunk
    key = "embed" if cfg.tie_embeddings else "lm_head"
    vloc = params[key].shape[0 if cfg.tie_embeddings else 1]
    g = _head_grad_chunks(n, b * chunk, vloc, jnp.dtype(cfg.dtype).itemsize)
    metrics.HEAD_GRAD_CHUNKS.set(g)
    xc = jnp.moveaxis(x.reshape(b, n, chunk, d), 1, 0)       # (n,B,c,d)
    tc = jnp.moveaxis(targets.reshape(b, n, chunk), 1, 0)    # (n,B,c)

    if g == 1:
        @jax.checkpoint
        def one(carry, ct):
            xk, tk = ct
            nll = _nll(_head(params, xk, cfg), tk, axes)
            return carry + jnp.sum(nll), None
    else:
        xc = xc.reshape((n // g, g) + xc.shape[1:])
        tc = tc.reshape((n // g, g) + tc.shape[1:])
        group = _grouped_nll(cfg, axes)
        head = {k: params[k] for k in ("ln_f", key)}

        def one(carry, ct):
            return group(carry, head, *ct), None

    total, _ = lax.scan(one, jnp.float32(0), (xc, tc))
    return total / (b * s_loc)


def _head_product(params, x, cfg):
    """The normed rows ``x`` (B, S, d) times the (possibly vocab-sharded)
    head, tied or not: f32 (B, S, V_loc)."""
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", x,
                          params["embed"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def _head_scale(logits, cfg):
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def _head(params, x, cfg):
    """Final norm + (possibly vocab-sharded) LM head: (B, S, d) -> f32
    logits (B, S, V_loc)."""
    x = _rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return _head_scale(_head_product(params, x, cfg), cfg)


def loss_fn(params, tokens, targets, cfg, axes=None):
    """Mean causal-LM cross entropy with vocab-parallel logits (+ the
    Switch load-balancing aux term when the model has capacity MoE
    layers). With cfg.loss_chunk set, the head + CE run per sequence chunk
    and full logits never materialize."""
    return loss_and_stats(params, tokens, targets, cfg, axes)[0]


def loss_and_stats(params, tokens, targets, cfg, axes=None):
    """``(loss_fn, routing counters)``: the ``has_aux=True`` form of the
    loss for ``hvd.compiled_train_step``, whose aux then carries
    :func:`trunk_with_stats`'s per-layer ``expert_load`` and
    ``unrouted_tokens`` out of the step (feed them to
    ``hvd.metrics.record_moe_routing``)."""
    axes = axes or ShardAxes(dp=None, sp=None, tp=None)
    x, aux, stats = trunk_with_stats(params, tokens, cfg, axes)
    # Head matmul + cross entropy under a device name of their own
    # (docs/diagnostics.md: the trace readers' `hvd_head_ce`), forward
    # and backward alike.
    with jax.named_scope("hvd_head_ce"):
        if cfg.loss_chunk:
            nll = _chunked_cross_entropy(params, x, targets, cfg, axes)
        else:
            nll = _cross_entropy(_head(params, x, cfg), targets, axes)
    loss = nll + MOE_AUX_COEF * aux
    return _pmean(loss, (axes.dp, axes.sp)), stats


def _pipeline_is_mixed(cfg):
    """True when the config interleaves dense and MoE layers — the
    per-position stacked layout (list over in-stage positions) replaces
    the single homogeneous stack (round-4 verdict #4)."""
    return bool(cfg.moe_layers) and \
        set(cfg.moe_layers) != set(range(cfg.n_layers))


def _pipeline_units(n_layers, interleave, num_stages):
    """Canonical (units, layers-per-position) split for the pipelined
    layouts — the ONE place the divisibility contract lives (specs,
    stacking, and the MoE pattern check all call it, so they cannot
    drift into divergent errors for the same invalid shape)."""
    units = interleave * num_stages
    if n_layers % units != 0:
        raise ValueError(f"n_layers ({n_layers}) not divisible by "
                         f"interleave x num_stages ({units})")
    return units, n_layers // units


def pipeline_param_specs(cfg, axes=ShardAxes(), pp_axis="pp",
                         interleave=1, num_stages=None):
    """PartitionSpecs for the pipelined layout: ``layers`` carries a
    stacked leading layer dim sharded over ``pp_axis`` (each stage holds a
    contiguous run of n_layers/|pp| layers); everything else keeps the
    Megatron TP sharding and is pp-replicated.

    ``interleave=V`` > 1 describes the virtual-chunk layout instead:
    layers shaped (V, S, layers_per_chunk, ...) with dim 1 sharded over
    ``pp_axis`` — device s holds virtual stages {c*S + s}.

    Mixed dense/MoE configs use the per-position layout (``num_stages``
    required): ``layers`` is a LIST over in-stage positions, each a
    (V*S, ...) stack over pipeline units of that position's layer — kind
    may vary by position but not across units, which is what keeps the
    SPMD stage program uniform (see :func:`_check_pipeline_moe`)."""
    from jax.sharding import PartitionSpec as P
    specs = param_specs(cfg, axes)
    if _pipeline_is_mixed(cfg):
        if num_stages is None:
            raise ValueError(
                "mixed dense/MoE pipeline specs need num_stages")
        _, lpp = _pipeline_units(cfg.n_layers, interleave, num_stages)
        lead = (None, pp_axis) if interleave > 1 else (pp_axis,)
        specs["layers"] = [
            jax.tree.map(lambda s: P(*lead, *s), specs["layers"][j])
            for j in range(lpp)]
        return specs
    layer = specs["layers"][0]
    if interleave > 1:
        specs["layers"] = jax.tree.map(
            lambda s: P(None, pp_axis, None, *s), layer)
    else:
        specs["layers"] = jax.tree.map(lambda s: P(pp_axis, *s), layer)
    return specs


def stack_pipeline_params(params, interleave=1, num_stages=None):
    """Stack the per-layer list into the pipelined layout (leading layer
    dim; place with :func:`pipeline_param_specs`). ``interleave=V`` with
    ``num_stages=S`` reshapes to the virtual-chunk layout (V, S, L', ...)
    where layer (c*S + s)*L' + l sits at [c, s, l].

    Mixed dense/MoE layer lists (heterogeneous pytrees that cannot form
    one stack) become the per-position layout: a list over the L' in-
    stage positions, each entry stacking that position's layer across the
    V*S pipeline units — shaped (S, ...) or (V, S, ...). Requires
    ``num_stages`` and a per-position kind pattern identical across units
    (checked here; :func:`_check_pipeline_moe` re-validates at trace
    time)."""
    from ..parallel.pipeline import stack_layers
    out = dict(params)
    layers = params["layers"]
    n = len(layers)
    if len({jax.tree.structure(l) for l in layers}) > 1:
        if num_stages is None:
            raise ValueError(
                "mixed dense/MoE pipeline layout needs num_stages")
        units, lpp = _pipeline_units(n, interleave, num_stages)
        pos_stacks = []
        for j in range(lpp):
            group = [layers[u * lpp + j] for u in range(units)]
            if len({jax.tree.structure(g) for g in group}) > 1:
                raise NotImplementedError(
                    f"in-stage position {j} mixes dense and MoE layers "
                    f"across pipeline units; mixed configs need the kind "
                    f"pattern to repeat every {lpp} layers (e.g. "
                    f"alternating dense/MoE aligned to stage boundaries)")
            stk = stack_layers(group)
            if interleave > 1:
                stk = jax.tree.map(
                    lambda a: a.reshape((interleave, num_stages)
                                        + a.shape[1:]), stk)
            pos_stacks.append(stk)
        out["layers"] = pos_stacks
        return out
    stacked = stack_layers(layers)
    if interleave > 1:
        if num_stages is None or n % (interleave * num_stages) != 0:
            raise ValueError(
                f"interleave={interleave} needs num_stages and n_layers "
                f"({n}) divisible by interleave x num_stages")
        lpc = n // (interleave * num_stages)
        stacked = jax.tree.map(
            lambda a: a.reshape((interleave, num_stages, lpc)
                                + a.shape[1:]), stacked)
    out["layers"] = stacked
    return out


def _apply_stage_layers(stage_layers, h, block):
    """Apply one pipeline stage's layers. Homogeneous layout: lax.scan
    over the stacked (L', ...) shard. Mixed per-position layout (list):
    an unrolled Python loop — every device runs the same per-position
    program (position kind is static and identical across units), so SPMD
    uniformity and any in-layer collectives (tp psum, ep alltoall) stay
    mesh-uniform."""
    from ..parallel.pipeline import apply_stacked_layers
    if isinstance(stage_layers, list):
        for p in stage_layers:
            h = block(jax.tree.map(lambda a: a[0], p), h)
        return h
    return apply_stacked_layers(block, stage_layers, h)


def pipeline_loss_fn(params, tokens, targets, cfg, axes=None,
                     num_microbatches=4, pp_axis="pp"):
    """GPipe-pipelined mean CE loss over the ``pp`` mesh axis.

    ``params["layers"]`` must be the stacked layout
    (:func:`stack_pipeline_params`) sharded over ``pp_axis``; tokens and
    targets are (B, S) per shard with B divisible by ``num_microbatches``.
    Composes with the TP/SP shardings of the non-pipelined path (each
    stage's blocks still psum over tp and ring-attend over sp).
    """
    from ..parallel.pipeline import last_stage_value, pipeline
    axes = axes or ShardAxes(dp=None, sp=None, tp=None)
    moe = _check_pipeline_moe(cfg, num_stages=_pp_size(pp_axis))
    m = num_microbatches
    b, s = tokens.shape
    assert b % m == 0, f"batch {b} not divisible by microbatches {m}"
    tokens_mb = tokens.reshape(m, b // m, s)
    targets_mb = targets.reshape(m, b // m, s)

    # MoE stages thread the load-balancing aux loss THROUGH the pipe as
    # part of the activation pytree — only the last stage's collect sees
    # the total, exactly like the sequential forward's accumulation.
    def block(p, h):
        x, aux = h
        x = _attention_block(p, x, cfg, axes)
        x, a = _mlp_block(p, x, cfg, axes)  # dense layers: aux is 0
        return (x, aux + a)

    def stage_fn(h):
        return _apply_stage_layers(params["layers"], h, block)

    def inject(toks):
        return (embed_tokens(params, toks, cfg, axes), jnp.float32(0))

    def collect(h, mb):
        # loss_chunk composes with PP: the microbatch bounds logits by
        # B/m, the chunk additionally bounds them by (B/m, chunk, V_loc)
        # — at real vocab sizes both levers are needed.
        y, aux = h
        with jax.named_scope("hvd_head_ce"):
            if cfg.loss_chunk:
                ce = _chunked_cross_entropy(params, y, targets_mb[mb], cfg,
                                            axes)
            else:
                ce = _cross_entropy(_head(params, y, cfg), targets_mb[mb],
                                    axes)
        return ce + MOE_AUX_COEF * aux if moe else ce

    losses = pipeline(
        stage_fn, tokens_mb, axis_name=pp_axis,
        num_microbatches=m, inject_fn=inject, collect_fn=collect,
        collect_shape=jax.ShapeDtypeStruct((), jnp.float32))
    loss = last_stage_value(jnp.mean(losses), pp_axis)
    return _pmean(loss, (axes.dp, axes.sp))


def _pp_size(pp_axis):
    """Stage count from the surrounding shard_map axis env; None when
    called outside one (the mixed-MoE check then fails with its own
    actionable message instead of an unbound-axis NameError)."""
    try:
        return lax.axis_size(pp_axis)
    except NameError:
        return None


def _check_pipeline_moe(cfg, num_stages=None, interleave=1):
    """MoE x PP composition check. All-MoE models stack homogeneously.
    Mixed dense/MoE composes via the per-position layout when every
    pipeline unit (chunk, stage) sees the SAME per-position kind pattern
    — the stage program is then one uniform unrolled position loop on
    every device (round-4 verdict #4 lifted the previous all-or-nothing
    refusal). Kind patterns that differ across units (e.g. all the MoE
    layers in the first stage) would need per-stage programs, which SPMD
    cannot express. Returns whether MoE is active."""
    cfg.layer_spec()  # the stage program takes one kind of attention layer
    if not cfg.moe_layers:
        return False
    if set(cfg.moe_layers) == set(range(cfg.n_layers)):
        return True
    if num_stages is None:
        raise NotImplementedError(
            "mixed dense/MoE pipeline schedules need the stage count to "
            "validate the per-position kind pattern")
    units, lpp = _pipeline_units(cfg.n_layers, interleave, num_stages)
    for j in range(lpp):
        kinds = {(u * lpp + j) in cfg.moe_layers for u in range(units)}
        if len(kinds) > 1:
            raise NotImplementedError(
                f"mixed dense/MoE pipeline stages need a per-position "
                f"kind pattern identical across all {units} pipeline "
                f"units (in-stage position {j} mixes dense and MoE); "
                f"e.g. every-other-layer MoE aligned to stage boundaries "
                f"composes, MoE-only-in-stage-0 does not — use loss_fn "
                f"(pp=1) for such shapes")
    return True


def pipeline_value_and_grad_1f1b(params, tokens, targets, cfg, axes=None,
                                 num_microbatches=4, pp_axis="pp",
                                 interleave=1, stage_collectives=None):
    """1F1B-scheduled (loss, grads) over the ``pp`` axis — the
    bounded-activation-memory alternative to differentiating
    :func:`pipeline_loss_fn` (which is GPipe: autodiff stacks one
    residual set per scan step, so stashes grow with M; 1F1B holds at
    most S — see parallel/pipeline.py::pipeline_1f1b).

    Same layout contract as :func:`pipeline_loss_fn`; returns what
    ``jax.value_and_grad`` of the shard_mapped GPipe loss returns:
    pp-replicated grads for embedding/head (psummed over pp), shard-local
    grads for the stacked layers, everything dp/sp-meaned. Call INSIDE
    the same shard_map placement as pipeline_loss_fn; do not wrap in
    jax.grad.

    ``stage_collectives=None`` auto-detects: when no tp/sp/ep axis is
    active inside the stages (pp-only), the cond-gated single-phase
    schedule runs and interleave=V cuts bubble work ~V-fold; with in-
    stage collectives the masked uniform-phase schedule keeps the mesh
    rendezvous-safe (parallel/pipeline.py::pipeline_1f1b docs).
    """
    from ..parallel.pipeline import pipeline_1f1b
    axes = axes or ShardAxes(dp=None, sp=None, tp=None)
    moe = _check_pipeline_moe(cfg, num_stages=_pp_size(pp_axis),
                              interleave=interleave)
    if stage_collectives is None:
        stage_collectives = bool(axes.tp or axes.sp
                                 or (moe and axes.ep))
    m = num_microbatches
    b, s = tokens.shape
    assert b % m == 0, f"batch {b} not divisible by microbatches {m}"
    tokens_mb = tokens.reshape(m, b // m, s)
    targets_mb = targets.reshape(m, b // m, s)
    shared = {k: v for k, v in params.items() if k != "layers"}

    def block(p, h):
        x, aux = h
        x = _attention_block(p, x, cfg, axes)
        x, a = _mlp_block(p, x, cfg, axes)
        return (x, aux + a)

    def stage(stage_layers, h):
        if interleave > 1 and not isinstance(stage_layers, list):
            # one chunk's params arrive shaped (1, L', ...) — the sharded
            # device axis of the (V, S, L', ...) layout, squeezed (the
            # mixed per-position layout squeezes inside
            # _apply_stage_layers instead)
            stage_layers = jax.tree.map(lambda a: a[0], stage_layers)
        return _apply_stage_layers(stage_layers, h, block)

    def inject(sh, toks):
        return (embed_tokens(sh, toks, cfg, axes), jnp.float32(0))

    def loss_f(sh, h, mb):
        y, aux = h
        with jax.named_scope("hvd_head_ce"):
            if cfg.loss_chunk:
                ce = _chunked_cross_entropy(sh, y, targets_mb[mb], cfg,
                                            axes)
            else:
                ce = _cross_entropy(_head(sh, y, cfg), targets_mb[mb],
                                    axes)
        return ce + MOE_AUX_COEF * aux if moe else ce

    # The per-(stage, microbatch) loss value is REPLICATED across the tp
    # group (_nll psums over tp) and, with expert parallelism, across the
    # ep group (moe_layer's dispatch/return alltoalls hand every ep
    # shard the identical reassembled expert outputs — replication by
    # reconstruction, no psum involved). Seeding each replica's in-body
    # vjp with the full cotangent would differentiate the SUM of the
    # identical copies, so the seed divides by the replication product
    # and leaves replicated over those axes psum afterwards; see
    # pipeline_1f1b's loss_replicas docs.
    rep_axes = [a for a in (axes.tp, axes.ep if moe else None) if a]
    replicas = 1
    for a in rep_axes:
        replicas *= lax.axis_size(a)
    loss, d_layers, d_shared = pipeline_1f1b(
        stage, params["layers"], shared, tokens_mb, axis_name=pp_axis,
        num_microbatches=m, inject_fn=inject, loss_fn=loss_f,
        loss_replicas=replicas, num_chunks=interleave,
        stage_collectives=stage_collectives)
    grads = dict(d_shared)
    grads["layers"] = d_layers
    if rep_axes:
        specs = pipeline_param_specs(cfg, axes, pp_axis=pp_axis,
                                     interleave=interleave,
                                     num_stages=lax.axis_size(pp_axis))

        def _rep_fix(g, spec):
            names = set()
            for el in spec:
                if isinstance(el, (tuple, list)):
                    names.update(el)
                elif el is not None:
                    names.add(el)
            for a in rep_axes:
                if a not in names:
                    g = lax.psum(g, a)
            return g

        grads = jax.tree.map(_rep_fix, grads, {k: specs[k] for k in grads})
    # dp/sp replication: mirror shard_map's transpose of the pmean'd loss
    # (grads of dp/sp-replicated params average over those axes).
    grads = jax.tree.map(lambda g: _pmean(g, (axes.dp, axes.sp)), grads)
    return _pmean(loss, (axes.dp, axes.sp)), grads


class TransformerLM:
    """Thin OO wrapper bundling config + functional API."""

    def __init__(self, cfg=TransformerConfig()):
        self.cfg = cfg

    def init(self, key):
        return init_params(key, self.cfg)

    def apply(self, params, tokens, axes=None):
        return forward(params, tokens, self.cfg, axes)

    def loss(self, params, tokens, targets, axes=None):
        return loss_fn(params, tokens, targets, self.cfg, axes)

    def generate(self, params, prompt, max_new_tokens, max_len=None):
        return generate(params, prompt, self.cfg, max_new_tokens,
                        max_len=max_len)


# --------------------------------------------------------------- decoding

def init_cache(cfg, batch, max_len, axes=None):
    """Per-layer K/V cache for incremental decoding. Under GQA the cache
    carries n_kv_heads — the feature's payoff: an 8->2 head reduction
    shrinks the decode-time cache 4x (the HBM that bounds batch x context
    at serving time). With ``axes.tp`` set (inside shard_map), each shard
    caches only its local K/V heads — serving shares training's
    head-sharded layout."""
    cfg.layer_spec()  # decoding takes one kind of layer
    h_kv = cfg.n_kv_heads or cfg.n_heads
    if axes is not None and axes.tp:
        tp_size = lax.axis_size(axes.tp)
        if h_kv % tp_size != 0:
            raise ValueError(
                f"kv head count ({h_kv}) must be divisible by the tp axis "
                f"size ({tp_size})")
        h_kv //= tp_size
    hd = cfg.head_dim
    zeros = jnp.zeros((batch, max_len, h_kv, hd), cfg.dtype)
    return {
        "layers": [{"k": zeros, "v": zeros} for _ in range(cfg.n_layers)],
        "pos": jnp.zeros((), jnp.int32),
    }


def _cache_attention(q, k, v, length, window=None):
    """Single-position attention against the first ``length`` cache rows
    (optionally only the last ``window`` of them — decode must apply the
    same sliding window the model trained with).
    q: (B, 1, H, D); k/v: (B, L_max, H_kv, D) with H % H_kv == 0."""
    from ..parallel.ring_attention import gqa_group

    rep = gqa_group(q.shape[2], k.shape[2], v.shape[2])
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / (d ** 0.5)
    idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
    mask = idx < length
    if window is not None:
        mask = jnp.logical_and(mask, idx >= length - window)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _check_fresh_cache(cache):
    """prefill overwrites rows at offset 0 and attends only the prompt —
    on a warm cache that silently corrupts earlier entries, so concrete
    nonzero positions fail loudly. (A traced pos — cache threaded through
    jit/scan — cannot be checked; the contract is documented instead.)"""
    pos = cache["pos"]
    if not isinstance(pos, jax.core.Tracer) and int(pos) != 0:
        raise ValueError(
            f"prefill_cache requires a fresh cache (pos == 0), got pos="
            f"{int(pos)}; use decode_step to append to a warm cache")


def prefill_cache(params, cache, tokens, cfg, axes=None):
    """Fill the cache for a whole prompt in ONE fused forward pass instead
    of S sequential decode steps. Returns (last-position f32 logits
    (B, vocab), cache with pos advanced by S).

    Attention runs through the flash kernel when cfg.attention_impl ==
    "flash" (causal + window + GQA all supported) — at the long prompts
    (16k-128k) this path exists for, dense would materialize the S x S
    score matrix the kernel avoids. Dense remains the fallback.

    With ``axes.tp`` set (inside shard_map over the mesh), the prompt runs
    through the SAME Megatron shardings as training: vocab-parallel
    embedding, head-sharded QKV into a head-sharded cache, psum after wo
    and the MLP row matmul, vocab-parallel head gathered to full logits.

    Must be called on a FRESH cache (pos == 0): K/V land at offset 0 and
    the prompt attends only itself — appending to a non-empty cache needs
    decode_step."""
    axes = axes or ShardAxes(dp=None, sp=None, tp=None)
    _check_fresh_cache(cache)
    b, s_len = tokens.shape
    x = _embed_rows(params, tokens, axes)
    if cfg.positional == "learned":
        x = x + params["pos"][:s_len][None]
    x = x.astype(cfg.dtype)
    positions = jnp.arange(s_len)

    new_layers = []
    for p, lc in zip(params["layers"], cache["layers"]):
        h = _rmsnorm(x, p["ln1"], cfg.norm_eps)
        q, k_new, v_new = _qkv_proj(p, h, cfg)
        if cfg.positional == "rope":
            q = _rope(q, positions)
            k_new = _rope(k_new, positions)
        k = lax.dynamic_update_slice_in_dim(lc["k"], k_new, 0, axis=1)
        v = lax.dynamic_update_slice_in_dim(lc["v"], v_new, 0, axis=1)
        new_layers.append({"k": k, "v": v})
        if cfg.attention_impl == "flash":
            from ..ops.flash_attention import flash_attention
            attn = flash_attention(q, k_new, v_new, True,
                                   interpret=cfg.flash_interpret,
                                   window=cfg.attention_window)
        else:
            attn = dense_attention(q, k_new, v_new, causal=True,
                                   window=cfg.attention_window)
        out = jnp.einsum("bshx,hxd->bsd", attn, p["wo"].astype(cfg.dtype),
                         preferred_element_type=jnp.float32)
        out = _psum(out, axes.tp).astype(cfg.dtype)
        x = x + out
        x, _ = _mlp_block(p, x, cfg, axes)

    logits = _head(params, x[:, -1:], cfg)[:, 0]       # (B, V_loc)
    logits = _gather_vocab(logits, axes.tp)            # (B, vocab)
    return logits, {"layers": new_layers, "pos": cache["pos"] + s_len}


def decode_step(params, cache, token, cfg, axes=None):
    """One incremental decode step. With ``axes.tp`` set (inside
    shard_map), serving uses training's mesh shardings: vocab-parallel
    embedding, head-sharded K/V cache, psum after wo/MLP, vocab-parallel
    head gathered to full logits — the decode analog of _attention_block.

    token: (B,) int32 for the current position. Returns (f32 logits
    (B, vocab), updated cache)."""
    axes = axes or ShardAxes(dp=None, sp=None, tp=None)
    pos = cache["pos"]
    # embedding lookup without embed_tokens (that helper bakes in the
    # position slice starting at 0; here the position is the cache cursor)
    x = _embed_rows(params, token[:, None], axes)
    if cfg.positional == "learned":
        x = x + lax.dynamic_slice_in_dim(params["pos"], pos, 1)[None]
    x = x.astype(cfg.dtype)

    new_layers = []
    for p, lc in zip(params["layers"], cache["layers"]):
        h = _rmsnorm(x, p["ln1"], cfg.norm_eps)
        q, k_new, v_new = _qkv_proj(p, h, cfg)
        if cfg.positional == "rope":
            q = _rope(q, pos[None])
            k_new = _rope(k_new, pos[None])  # cache stores rotated K
        k = lax.dynamic_update_slice_in_dim(lc["k"], k_new, pos, axis=1)
        v = lax.dynamic_update_slice_in_dim(lc["v"], v_new, pos, axis=1)
        new_layers.append({"k": k, "v": v})
        attn = _cache_attention(q, k, v, pos + 1,
                                window=cfg.attention_window)
        out = jnp.einsum("bshx,hxd->bsd", attn, p["wo"].astype(cfg.dtype),
                         preferred_element_type=jnp.float32)
        x = x + _psum(out, axes.tp).astype(cfg.dtype)
        x, _ = _mlp_block(p, x, cfg, axes)

    logits = _head(params, x, cfg)[:, 0]               # (B, V_loc)
    logits = _gather_vocab(logits, axes.tp)            # (B, vocab)
    return logits, {"layers": new_layers, "pos": pos + 1}


def _select_token(logits, temperature, top_k, key, dtype):
    """argmax when temperature == 0, else softmax sampling at the given
    temperature over the top_k-filtered logits."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(dtype)
    if top_k is not None:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits >= kth, logits, -jnp.inf)
    return jax.random.categorical(key, logits / temperature,
                                  axis=-1).astype(dtype)


def generate(params, prompt, cfg, max_new_tokens, max_len=None,
             temperature=0.0, top_k=None, key=None, axes=None):
    """Autoregressive decoding through the KV cache: greedy by default,
    softmax sampling when ``temperature > 0`` (optionally top_k-filtered;
    ``key`` required). Returns (B, S + max_new_tokens). jit-compatible
    (static lengths, lax.scan over positions).

    With ``axes.tp`` set (called inside shard_map with param_specs-placed
    params), prefill and every decode step run TP-sharded on the training
    mesh; logits are gathered so every shard selects the same next token
    (same key on every shard → identical draws on the sampling path)."""
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if temperature > 0 and key is None:
        raise ValueError("sampling (temperature > 0) needs a PRNG key")
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if key is None:
        key = jax.random.PRNGKey(0)  # unused on the greedy path
    b, s = prompt.shape
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}")
    max_len = max_len or (s + max_new_tokens)
    if max_len < s + max_new_tokens:
        raise ValueError(
            f"max_len ({max_len}) must cover prompt + new tokens "
            f"({s} + {max_new_tokens}); an undersized cache would be "
            f"silently clobbered by the clamped update slice")
    if max_len > cfg.max_seq:
        raise ValueError(
            f"generation length {max_len} exceeds cfg.max_seq "
            f"({cfg.max_seq})")
    cache = init_cache(cfg, b, max_len, axes)
    # one fused forward fills the whole prompt (vs S sequential decode
    # steps) and yields the last position's logits directly
    logits, cache = prefill_cache(params, cache, prompt, cfg, axes)

    def step(carry, sk):
        cache, tok = carry
        logits, cache = decode_step(params, cache, tok, cfg, axes)
        nxt = _select_token(logits, temperature, top_k, sk, prompt.dtype)
        return (cache, nxt), nxt

    keys = jax.random.split(key, max_new_tokens)
    first = _select_token(logits, temperature, top_k, keys[0],
                          prompt.dtype)
    (_, _), rest = lax.scan(step, (cache, first), keys[1:])
    new = jnp.concatenate([first[None], rest], axis=0)   # (new, B)
    return jnp.concatenate([prompt, new.T], axis=1)
