"""The chunked cross entropy's grouped head gradient (models/transformer.py
``_grouped_nll``: one product into the head's weight gradient per GROUP of
chunks) against the plain scan it takes the place of: the same loss in
every bit and the same gradients up to the order of a float32 sum, for
every kind of head; the rule that picks the group from the shapes
(``_head_grad_chunks``) on the benchmark's eight cells; and the trace-time
gauge that says which group a loss took.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu import metrics
from horovod_tpu.models import transformer as tfm

CHUNK, BATCH = 8, 2


def gauge():
    return metrics.snapshot()["hvd_head_grad_chunks"]["values"][""]


def loss_and_grads(cfg, params, tokens, tp):
    targets = jnp.roll(tokens, -1, axis=1)
    if not tp:
        return jax.jit(jax.value_and_grad(
            lambda p: tfm.loss_fn(p, tokens, targets, cfg)))(params)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    axes = tfm.ShardAxes(dp=None, sp=None, tp="tp")
    specs = tfm.param_specs(cfg, axes)
    sharded = jax.shard_map(
        lambda p, t, y: tfm.loss_fn(p, t, y, cfg, axes), mesh=mesh,
        in_specs=(specs, P(), P()), out_specs=P(), check_vma=False)
    params = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs)
    # through the shard_mapped loss, as tests/test_models.py takes them
    return jax.jit(jax.value_and_grad(
        lambda p: sharded(p, tokens, targets)))(params)


# chunks of the sequence, tokens a product should hold, the group that
# gives, and what differs from the plain untied head
CASES = {
    "g1": (8, 16, 1, {}),
    "g2": (8, 32, 2, {}),
    "g4": (8, 64, 4, {}),
    "tied-g2": (8, 32, 2, dict(tie_embeddings=True)),
    "tied-g4": (8, 64, 4, dict(tie_embeddings=True)),
    "scaled-g4": (8, 64, 4, dict(logits_scaling=3.0)),
    "tied-scaled-g2": (8, 32, 2, dict(tie_embeddings=True,
                                      logits_scaling=3.0)),
    "tp-g2": (8, 32, 2, dict(tp=True)),
    "tp-g4": (8, 64, 4, dict(tp=True)),
    # 4 does not divide 6 chunks, 2 does not divide 3: the rule keeps the
    # largest power of two that does
    "chunks6-want4-g2": (6, 64, 2, {}),
    "chunks3-want4-g1": (3, 64, 1, {}),
}


@pytest.mark.parametrize("chunks, want_tokens, g, kw", CASES.values(),
                         ids=CASES.keys())
def test_grouped_head_gradient_matches_plain_scan(hvd_init, monkeypatch,
                                                  chunks, want_tokens, g,
                                                  kw):
    kw = dict(kw)
    tp = kw.pop("tp", False)
    seq = chunks * CHUNK
    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=64,
        max_seq=seq, dtype=jnp.float32, loss_chunk=CHUNK, **kw)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (BATCH, seq), 0, 64)

    monkeypatch.setattr(tfm, "_HEAD_GROUP_TOKENS", want_tokens)
    loss, grads = loss_and_grads(cfg, params, tokens, tp)
    assert gauge() == g
    monkeypatch.setattr(tfm, "_HEAD_GROUP_TOKENS", 0)
    want_loss, want = loss_and_grads(cfg, params, tokens, tp)
    assert gauge() == 1

    # the group adds its chunks' sums to the running total one by one, as
    # the plain scan does: not one bit of the loss may move
    assert np.asarray(loss).tobytes() == np.asarray(want_loss).tobytes()
    # the head's weight gradient sums over a group's tokens in one
    # contraction where the scan summed chunk by chunk, and ln_f's sums
    # the chunks in another order: float32 sums of <= 128 terms in
    # another order, 2**-23 * sqrt(128) ~ 1.4e-6 of the largest term
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for a, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        a, w = np.asarray(a), np.asarray(w)
        assert a.dtype == w.dtype and a.shape == w.shape
        assert np.abs(a - w).max() <= 4e-6 * np.abs(w).max()


# sequence, sequences a chip, vocabulary rows a chip, chunks a product:
# benchmark/workloads/*.json and benchmark/configs/*.json, loss_chunk 512,
# bfloat16 activations
CELLS = {
    "cgpt13b_dp1": (2048, 8, 50257, 1),
    "cgpt13b_dp4": (2048, 8, 50257, 1),
    "sc2-3b_s4k": (4096, 4, 49152, 1),
    # 4 chunks are 2,048 tokens and 192 MiB of cotangents: the bytes stop
    # the group at 2 (96 MiB)
    "sc2-3b_s16k": (16384, 1, 49152, 2),
    "sc2-3b_s16k_noremat": (16384, 1, 49152, 2),
    "laguna-s21_s8k": (8192, 2, 12544, 2),
    "granite4h-micro_s16k": (16384, 1, 12544, 4),
    "kimi-linear_s16k": (16384, 1, 20480, 4),
    # 2 x 512 x 65,536 x 2 B is the 128 MiB the stored cotangents may
    # take; one row of the vocabulary more and every chunk is on its own
    "vocab64k_at_the_bytes_cap": (16384, 1, 65536, 2),
    "vocab64k_over_the_bytes_cap": (16384, 1, 65537, 1),
}


@pytest.mark.parametrize("seq, batch, vocab, g", CELLS.values(),
                         ids=CELLS.keys())
def test_group_rule_on_the_benchmark_cells(seq, batch, vocab, g):
    assert tfm._head_grad_chunks(seq // 512, batch * 512, vocab, 2) == g


@pytest.mark.parametrize("seq, g", [(4096, 4), (512, 1)],
                         ids=["one-sequence-in-8-chunks", "one-chunk"])
def test_gauge_reads_the_group_of_the_loss_traced_last(seq, g):
    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=64,
        max_seq=seq, loss_chunk=512)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tok = jnp.zeros((1, seq), jnp.int32)
    metrics.HEAD_GRAD_CHUNKS.set(7)  # whatever an earlier trace left
    jax.jit(jax.grad(lambda p: tfm.loss_fn(p, tok, tok, cfg))).lower(params)
    snap = metrics.snapshot()["hvd_head_grad_chunks"]
    assert snap["type"] == "gauge"
    assert snap["values"][""] == g
