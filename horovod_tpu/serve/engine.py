"""Serve-side compiled programs: shape-binned prefill and decode
through the engine's step-program cache.

No reference analog — the reference runtime trains; this is the decode
engine the ROADMAP's serving item asks for. Two program families:

- **prefill** runs the TRAINING forward trunk (literally
  models/transformer.py:_attention_block_kv — same helpers, same op
  order) over a (batch_bin, len_bin) padded prompt batch, scattering
  each layer's K/V into the paged pool as a side output and returning
  the last-real-position logits per sequence.
- **decode** advances every active sequence one token: one-row
  attention against the paged pool
  (ops/flash_attention.py:paged_attention_decode), per-sequence rope
  positions (models/transformer.py:_rope_b), scatter of the new K/V
  row, and full-vocab logits.

Both compile once per SHAPE BIN — batch and page-table width round up
to powers of two (config.next_power_of_two), so a continuous batch
that breathes between 3 and 7 sequences reuses one (8, pages) decode
executable instead of recompiling per membership. Programs are fetched
through :func:`horovod_tpu.ops.step_program.engine_cached_program` —
the same membership-scoped cache tier as the compiled train step, with
the same elastic-abort invalidation — fronted by module-level
``functools.lru_cache`` builders registered via
``register_wire_program_builder`` (ops/engine.py clears them with its
own on abort). Steady state is one cached executable per live bin:
the decode hit rate after warmup is >= 0.9 by construction and the
serve bench + CI smoke assert it.

Numerics (docs/serving.md "Numerics"): the decode row is bit-identical
to the forward row at the same position when the gathered K extent
(pages * page_size) matches the padded forward length — the einsum
contraction drops the singleton q dim, the softmax masks with the same
NEG_INF fill, and masked tail positions contribute exact zeros, so the
reduction trees agree. tests/test_serving.py pins this bitwise for
rope (f32 and bf16, MHA and GQA) and learned+bf16; learned+f32 sits
within ~1 ulp of the fused forward (XLA CPU reassociates the fused
embed+pos-add+rmsnorm differently at SIMD boundaries) and is pinned at
exact-greedy-token level instead.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import metrics
from ..config import next_power_of_two
from ..models import transformer as tfm
from ..ops.engine import register_wire_program_builder
from ..ops.flash_attention import paged_attention_decode
from .kv_cache import PagedKVCache

# Knob defaults (config.py from_env: HOROVOD_SERVE_*).
DEFAULT_PAGES = 512
DEFAULT_PAGE_SIZE = 16
DEFAULT_MAX_BATCH = 8


# ------------------------------------------------------------ the cores


def _pool_scatter_prefill(pool, li, page_tables, positions, rows,
                          page_size):
    """Scatter (B, S, h, d) prefill rows into layer ``li`` of the pool.
    Positions past a sequence's reserved pages hit null-page table
    slots, so padded prompt tails land on page 0 by construction."""
    pages = jnp.take(page_tables, positions // page_size, axis=1)  # (B,S)
    offs = jnp.broadcast_to((positions % page_size)[None], pages.shape)
    return pool.at[li, pages, offs].set(rows)


def _prefill_core(params, k_pool, v_pool, tokens, lengths, page_tables,
                  cfg, axes, page_size, moe_full):
    """Forward trunk + paged K/V capture + last-position logits."""
    with jax.named_scope("hvd_prefill"):
        x = tfm.embed_tokens(params, tokens, cfg, axes)
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        for li, p in enumerate(params["layers"]):
            x, k, v = tfm._attention_block_kv(p, x, cfg, axes)
            k_pool = _pool_scatter_prefill(k_pool, li, page_tables,
                                           positions, k, page_size)
            v_pool = _pool_scatter_prefill(v_pool, li, page_tables,
                                           positions, v, page_size)
            x, _ = tfm._mlp_block(p, x, cfg, axes,
                                  moe_full_capacity=moe_full)
        logits = tfm._head(params, x, cfg)  # (B, S, V_loc) f32
        last = jnp.clip(lengths - 1, 0, tokens.shape[1] - 1)
        logits = jnp.take_along_axis(
            logits, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = tfm._gather_vocab(logits, axes.tp)
    return logits, k_pool, v_pool


def _decode_core(params, k_pool, v_pool, tokens, lengths, page_tables,
                 cfg, axes, page_size, moe_full):
    """One token for every row: scatter the new K/V row at position
    ``lengths`` and attend over ``lengths + 1`` visible positions."""
    with jax.named_scope("hvd_decode"):
        b = tokens.shape[0]
        ar = jnp.arange(b)
        x = tfm._embed_rows(params, tokens[:, None], axes)
        if cfg.positional == "learned":
            x = x + jnp.take(params["pos"], lengths, axis=0)[:, None]
        x = x.astype(cfg.dtype)
        pages = page_tables[ar, lengths // page_size]
        offs = lengths % page_size
        for li, p in enumerate(params["layers"]):
            h = tfm._rmsnorm(x, p["ln1"], cfg.norm_eps)
            q, k_new, v_new = tfm._qkv_proj(p, h, cfg)
            if cfg.positional == "rope":
                q = tfm._rope_b(q, lengths[:, None])
                k_new = tfm._rope_b(k_new, lengths[:, None])
            k_pool = k_pool.at[li, pages, offs].set(k_new[:, 0])
            v_pool = v_pool.at[li, pages, offs].set(v_new[:, 0])
            attn = paged_attention_decode(q, k_pool[li], v_pool[li],
                                          page_tables, lengths + 1)
            out = jnp.einsum("bshx,hxd->bsd", attn,
                             p["wo"].astype(cfg.dtype),
                             preferred_element_type=jnp.float32)
            out = tfm._psum(out, axes.tp).astype(cfg.dtype)
            x = x + out
            x, _ = tfm._mlp_block(p, x, cfg, axes,
                                  moe_full_capacity=moe_full)
        logits = tfm._head(params, x, cfg)[:, 0]  # (B, V_loc) f32
        logits = tfm._gather_vocab(logits, axes.tp)
    return logits, k_pool, v_pool


# ----------------------------------------------------------- builders
#
# Module-level lru builders, registered so elastic aborts clear them
# together with the engine's own (their signatures embed a Mesh when
# sharded). Every argument is static and hashable; cfg is the frozen
# TransformerConfig dataclass.


def _shard_mapped(core, mesh, tp_axis, cfg, donate):
    axes = tfm.ShardAxes(dp=None, sp=None, tp=tp_axis, ep=None)
    pool_spec = P(None, None, None, tp_axis, None)
    specs = tfm.param_specs(cfg, axes)
    fn = jax.shard_map(
        lambda pr, k, v, t, le, pt: core(pr, k, v, t, le, pt, axes),
        mesh=mesh,
        in_specs=(specs, pool_spec, pool_spec, P(), P(), P()),
        out_specs=(P(), pool_spec, pool_spec), check_vma=False)
    return jax.jit(fn, donate_argnums=(1, 2) if donate else ())


@functools.lru_cache(maxsize=64)
def _build_prefill_program(cfg, mesh, tp_axis, batch_bin, len_bin,
                           page_bin, page_size, donate, moe_full):
    del batch_bin, len_bin, page_bin  # shapes arrive with the operands

    def core(params, k_pool, v_pool, tokens, lengths, page_tables,
             axes):
        return _prefill_core(params, k_pool, v_pool, tokens, lengths,
                             page_tables, cfg, axes, page_size, moe_full)

    if mesh is not None:
        return _shard_mapped(core, mesh, tp_axis, cfg, donate)
    axes = tfm.ShardAxes(dp=None, sp=None, tp=None, ep=None)
    return jax.jit(
        lambda pr, k, v, t, le, pt: core(pr, k, v, t, le, pt, axes),
        donate_argnums=(1, 2) if donate else ())


@functools.lru_cache(maxsize=64)
def _build_decode_program(cfg, mesh, tp_axis, batch_bin, page_bin,
                          page_size, donate, moe_full):
    del batch_bin, page_bin

    def core(params, k_pool, v_pool, tokens, lengths, page_tables,
             axes):
        return _decode_core(params, k_pool, v_pool, tokens, lengths,
                            page_tables, cfg, axes, page_size, moe_full)

    if mesh is not None:
        return _shard_mapped(core, mesh, tp_axis, cfg, donate)
    axes = tfm.ShardAxes(dp=None, sp=None, tp=None, ep=None)
    return jax.jit(
        lambda pr, k, v, t, le, pt: core(pr, k, v, t, le, pt, axes),
        donate_argnums=(1, 2) if donate else ())


register_wire_program_builder(_build_prefill_program)
register_wire_program_builder(_build_decode_program)


# ------------------------------------------------------------- engine


class ServeEngine:
    """Owns the paged pools and runs binned prefill/decode programs.

    ``mesh``/``tp_axis`` shard the model Megatron-style and the KV pool
    on the kv-head dim alongside it (h_kv must divide the axis size);
    without a mesh everything runs single-device. ``batch_bin_floor``/
    ``page_bin_floor`` pin the minimum program shape — beyond warmup
    economics, a pinned bin makes decode streams independent of batch
    membership at the PROGRAM level too (same executable whether 1 or
    7 neighbors ride along), which the churn-exactness test uses.

    Programs are fetched through the hvd engine's step-program cache
    when the runtime is initialized; otherwise a process-local cache
    with the same signature keys (unit tests without hvd.init()).
    ``fallback_steps`` counts engine-cache errors only — the acceptance
    criterion is that it stays 0."""

    def __init__(self, params, cfg, *, mesh=None, tp_axis=None,
                 num_pages=DEFAULT_PAGES, page_size=DEFAULT_PAGE_SIZE,
                 max_pages_per_seq=None, batch_bin_floor=1,
                 page_bin_floor=1, len_bin_floor=1,
                 moe_full_capacity=True):
        cfg.layer_spec()  # serving takes one kind of layer
        self.cfg = cfg
        self.mesh = mesh
        self.tp_axis = tp_axis if mesh is not None else None
        self.batch_bin_floor = max(int(batch_bin_floor), 1)
        self.page_bin_floor = max(int(page_bin_floor), 1)
        self.len_bin_floor = max(int(len_bin_floor), 1)
        self.moe_full_capacity = bool(moe_full_capacity)
        h_kv = cfg.n_kv_heads or cfg.n_heads
        if max_pages_per_seq is None:
            max_pages_per_seq = max(
                1, -(-cfg.max_seq // int(page_size)))
        self.cache = PagedKVCache(cfg.n_layers, h_kv, cfg.head_dim,
                                  num_pages, page_size,
                                  max_pages_per_seq, cfg.dtype)
        shape = (cfg.n_layers, num_pages, page_size, h_kv, cfg.head_dim)
        pool_sh = None
        if mesh is not None:
            if tp_axis is None:
                raise ValueError("mesh serving needs tp_axis")
            pool_sh = NamedSharding(mesh, P(None, None, None, tp_axis,
                                            None))
        # born sharded: the whole pool never sits on the default device
        self._k_pool = jnp.zeros(shape, cfg.dtype, device=pool_sh)
        self._v_pool = jnp.zeros(shape, cfg.dtype, device=pool_sh)
        if mesh is not None:
            axes = tfm.ShardAxes(dp=None, sp=None, tp=tp_axis, ep=None)
            params = jax.device_put(params, jax.tree.map(
                lambda s: NamedSharding(mesh, s),
                tfm.param_specs(cfg, axes),
                is_leaf=lambda x: isinstance(x, P)))
        self.params = params
        # Donate pool buffers only off-CPU (the CPU client aliases
        # host buffers; same policy as the train step programs).
        self._donate = jax.devices()[0].platform != "cpu"
        self._local_progs = {}
        self.prefill_hits = 0
        self.prefill_misses = 0
        self.decode_hits = 0
        self.decode_misses = 0
        self.fallback_steps = 0

    # --------------------------------------------------------- caching

    def _program(self, kind, signature, build):
        from .. import runtime
        was_hit = None
        if runtime.is_initialized():
            try:
                from ..ops.step_program import engine_cached_program
                prog, was_hit = engine_cached_program(signature, build)
            except Exception:
                self.fallback_steps += 1
                metrics.SERVE_FALLBACK_STEPS.inc()
                was_hit = None
        if was_hit is None:
            if signature in self._local_progs:
                was_hit = True
                prog = self._local_progs[signature]
            else:
                was_hit = False
                prog = self._local_progs[signature] = build()
        if kind == "prefill":
            self.prefill_hits += was_hit
            self.prefill_misses += not was_hit
            metrics.SERVE_PROGRAM_CACHE_HITS.labels(
                phase="prefill").set(self.prefill_hits)
            metrics.SERVE_PROGRAM_CACHE_MISSES.labels(
                phase="prefill").set(self.prefill_misses)
        else:
            self.decode_hits += was_hit
            self.decode_misses += not was_hit
            metrics.SERVE_PROGRAM_CACHE_HITS.labels(
                phase="decode").set(self.decode_hits)
            metrics.SERVE_PROGRAM_CACHE_MISSES.labels(
                phase="decode").set(self.decode_misses)
        return prog

    def decode_hit_rate(self):
        total = self.decode_hits + self.decode_misses
        return self.decode_hits / total if total else 0.0

    def _page_bin(self, seq_ids, extra_pages=0):
        widest = max((len(self.cache.pages_of(s)) for s in seq_ids
                      if s is not None), default=1)
        return next_power_of_two(max(widest + extra_pages,
                                     self.page_bin_floor))

    # ------------------------------------------------------------ runs

    def prefill(self, seq_ids, prompts):
        """Run prompts (list of token lists) for already-allocated
        sequences; returns (B, V) f32 logits at each prompt's last
        position — the distribution the FIRST generated token samples
        from."""
        b = len(seq_ids)
        ps = self.cache.page_size
        lens = [len(p) for p in prompts]
        len_bin = next_power_of_two(max(max(lens), self.len_bin_floor))
        batch_bin = next_power_of_two(max(b, self.batch_bin_floor))
        page_bin = max(self._page_bin(seq_ids),
                       next_power_of_two(-(-len_bin // ps)))
        tokens = np.zeros((batch_bin, len_bin), np.int32)
        lengths = np.zeros((batch_bin,), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, :len(p)] = p
            lengths[i] = len(p)
        rows = self.cache.page_table_rows(
            list(seq_ids) + [None] * (batch_bin - b), page_bin)
        tables = np.asarray(rows, np.int32)
        sig = ("serve_prefill", self.cfg, self.mesh, self.tp_axis,
               batch_bin, len_bin, page_bin, ps, self.moe_full_capacity)
        prog = self._program(
            "prefill", sig,
            lambda: _build_prefill_program(
                self.cfg, self.mesh, self.tp_axis, batch_bin, len_bin,
                page_bin, ps, self._donate, self.moe_full_capacity))
        t0 = time.perf_counter()
        logits, self._k_pool, self._v_pool = prog(
            self.params, self._k_pool, self._v_pool, tokens, lengths,
            tables)
        logits = np.asarray(logits[:b])
        dt = time.perf_counter() - t0
        metrics.SERVE_STEP_SECONDS.labels(phase="prefill").observe(dt)
        metrics.SERVE_TOKENS.labels(phase="prefill").inc(sum(lens))
        self._observe_sentry(f"serve_prefill|b{batch_bin}|s{len_bin}",
                             dt)
        return logits

    def decode(self, seq_ids, tokens, lengths):
        """One decode step for the active rows: ``tokens``/``lengths``
        are the per-sequence last token and current visible length.
        Returns (B, V) f32 logits for the NEXT token."""
        b = len(seq_ids)
        ps = self.cache.page_size
        batch_bin = next_power_of_two(max(b, self.batch_bin_floor))
        page_bin = self._page_bin(seq_ids)
        tok = np.zeros((batch_bin,), np.int32)
        tok[:b] = tokens
        lng = np.zeros((batch_bin,), np.int32)
        lng[:b] = lengths
        rows = self.cache.page_table_rows(
            list(seq_ids) + [None] * (batch_bin - b), page_bin)
        tables = np.asarray(rows, np.int32)
        sig = ("serve_decode", self.cfg, self.mesh, self.tp_axis,
               batch_bin, page_bin, ps, self.moe_full_capacity)
        prog = self._program(
            "decode", sig,
            lambda: _build_decode_program(
                self.cfg, self.mesh, self.tp_axis, batch_bin, page_bin,
                ps, self._donate, self.moe_full_capacity))
        t0 = time.perf_counter()
        logits, self._k_pool, self._v_pool = prog(
            self.params, self._k_pool, self._v_pool, tok, lng, tables)
        logits = np.asarray(logits[:b])
        dt = time.perf_counter() - t0
        metrics.SERVE_STEP_SECONDS.labels(phase="decode").observe(dt)
        metrics.SERVE_TOKENS.labels(phase="decode").inc(b)
        self._observe_sentry(f"serve_decode|b{batch_bin}|p{page_bin}",
                             dt)
        return logits

    def _observe_sentry(self, signature, dt):
        """Feed the perf-regression sentry (diag/sentry.py) — decode
        signatures get the same EMA-baseline watch as train steps."""
        from ..diag import sentry as _sentry
        s = _sentry.get()
        if s is not None:
            s.observe(signature, dt)

    # ------------------------------------------------------ pool admin

    def defrag(self):
        """Compact live pages to the low end of the pool (one gather per
        cache side); returns the number of pages moved."""
        moves = self.cache.defrag()
        if not moves:
            return 0
        perm = np.arange(self.cache.num_pages)
        for src, dst in moves.items():
            perm[dst] = src
        self._k_pool = self._k_pool[:, perm]
        self._v_pool = self._v_pool[:, perm]
        return len(moves)

    def update_pool_metrics(self):
        st = self.cache.stats()
        metrics.SERVE_KV_FREE_PAGES.set(st["free_pages"])
        metrics.SERVE_KV_PAGE_UTILIZATION.set(st["utilization"])
        metrics.SERVE_ACTIVE_SEQUENCES.set(st["active_sequences"])
        return st
