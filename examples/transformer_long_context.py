"""Long-context transformer training: dp x sp x tp on one mesh.

No reference analog — the reference is data-parallel only. This is the
TPU-native capability the framework adds: the flagship TransformerLM with
ring-attention sequence parallelism (context length sharded over ``sp``),
Megatron-style tensor parallelism over ``tp``, and data parallelism over
``dp``, all expressed in one shard_map program.

Run: python examples/transformer_long_context.py [--dp N --sp N --tp N]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel import create_mesh

parser = argparse.ArgumentParser()
parser.add_argument("--dp", type=int, default=-1)
parser.add_argument("--sp", type=int, default=1)
parser.add_argument("--tp", type=int, default=1)
parser.add_argument("--seq-len", type=int, default=2048)
parser.add_argument("--d-model", type=int, default=512)
parser.add_argument("--positional", choices=["learned", "rope"],
                    default="learned")
parser.add_argument("--generate", type=int, default=0, metavar="N",
                    help="after training, greedy-decode N tokens through "
                         "the KV cache from a prompt slice (single-shard "
                         "configs only: --sp 1 --tp 1)")
parser.add_argument("--loss-chunk", type=int, default=None,
                    help="chunked cross entropy: compute LM head + loss "
                         "per chunk of this many positions so the "
                         "(B, S, vocab) logits never materialize — at "
                         "32k vocab the logits OOM before K/V does")
parser.add_argument("--window", type=int, default=None,
                    help="sliding-window attention span (causal band); "
                         "flash prunes compute and K/V DMAs outside it")
parser.add_argument("--kv-heads", type=int, default=None,
                    help="grouped-query attention: K/V head count "
                         "(default: equal to the 8 query heads). Cuts "
                         "K/V HBM by 8/kv_heads at long context; works "
                         "with every --attention choice (the ring "
                         "streams the reduced heads over ICI)")
parser.add_argument("--layers", type=int, default=4)
parser.add_argument("--steps", type=int, default=10)
parser.add_argument("--cpu-devices", type=int, default=0,
                    help="force an N-device virtual CPU mesh (hermetic "
                         "multi-device smoke runs without a slice)")
parser.add_argument("--attention",
                    choices=["ring", "ring-flash", "ulysses",
                             "ulysses-flash", "dense", "flash"],
                    default="ring",
                    help="ring[-flash] = sequence-parallel ring attention "
                         "over sp (tiles computed dense or by the fused "
                         "Pallas kernel); ulysses[-flash] = all-to-all "
                         "head<->sequence re-shard with dense or flash "
                         "full-sequence attention; dense/flash = "
                         "single-shard attention")
args = parser.parse_args()

if args.cpu_devices:
    from horovod_tpu.utils.devices import force_host_device_count
    force_host_device_count(args.cpu_devices)


def main():
    mesh = create_mesh(dp=args.dp, sp=args.sp, tp=args.tp)
    dp = mesh.shape["dp"]
    print(f"mesh: dp={dp} sp={args.sp} tp={args.tp} "
          f"({len(jax.devices())} devices), seq={args.seq_len}")
    seq_par = args.attention.startswith(("ring", "ulysses"))
    if not seq_par and args.sp != 1:
        parser.error("--attention dense/flash requires --sp 1")
    # --window composes with every attention choice, including
    # ring-flash (band-offset tile kernels mask partially-windowed
    # visiting shards; the ring still prunes wholly-out-of-window ones).
    axes = tfm.ShardAxes(dp="dp", sp="sp" if seq_par else "", tp="tp")
    cfg = tfm.TransformerConfig(
        vocab_size=32768, d_model=args.d_model, n_heads=8,
        n_layers=args.layers, d_ff=4 * args.d_model, max_seq=args.seq_len,
        dtype=jnp.bfloat16,
        attention_impl="flash" if args.attention.endswith("flash")
        else "dense",
        sp_impl="ulysses" if args.attention.startswith("ulysses")
        else "ring",
        n_kv_heads=args.kv_heads,
        attention_window=args.window,
        loss_chunk=args.loss_chunk,
        positional=args.positional,
        # off-TPU the Pallas kernels only run in the interpreter
        flash_interpret=bool(args.cpu_devices))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    specs = tfm.param_specs(cfg, axes)
    tx = optax.adamw(3e-4)
    opt_state = tx.init(params)

    def opt_specs(state):
        def one(s):
            if hasattr(s, "mu"):
                return type(s)(count=P(), mu=specs, nu=specs)
            return jax.tree.map(lambda _: P(), s)
        return tuple(one(s) for s in state)

    def train_step(p, s, t, y):
        loss, g = jax.value_and_grad(
            lambda pp: tfm.loss_fn(pp, t, y, cfg, axes))(p)
        updates, s = tx.update(g, s, p)
        return optax.apply_updates(p, updates), s, loss

    tok_spec = P(("pp", "dp", "ep"), "sp")
    step = jax.jit(jax.shard_map(
        train_step, mesh=mesh,
        in_specs=(specs, opt_specs(opt_state), tok_spec, tok_spec),
        out_specs=(specs, opt_specs(opt_state), P()), check_vma=False))

    batch = 2 * dp
    tokens = jax.random.randint(jax.random.PRNGKey(1),
                                (batch, args.seq_len), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)

    # two untimed calls: the first traces with host avals, the second with
    # the program's own outputs — both specializations compile pre-timing
    params, opt_state, loss = step(params, opt_state, tokens, targets)
    print(f"compiled; initial loss={float(loss):.4f}")
    params, opt_state, loss = step(params, opt_state, tokens, targets)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
    loss = float(loss)
    dt = time.perf_counter() - t0
    toks = batch * args.seq_len * args.steps / dt
    print(f"loss={loss:.4f}  {toks:,.0f} tokens/sec")
    maybe_generate(params, cfg)


def maybe_generate(params, cfg):
    if not args.generate:
        return
    if args.sp != 1 or args.tp != 1:
        print("skipping --generate (single-shard configs only)")
        return
    prompt = jax.random.randint(jax.random.PRNGKey(7), (1, 16), 0,
                                cfg.vocab_size)
    out = jax.jit(lambda p, t: tfm.generate(
        p, t, cfg, args.generate,
        max_len=min(cfg.max_seq, 16 + args.generate)))(params, prompt)
    toks = np.asarray(out)[0, 16:]
    print(f"generated {args.generate} tokens through the KV cache: "
          f"{toks[:16].tolist()}{'...' if args.generate > 16 else ''}")


if __name__ == "__main__":
    main()
