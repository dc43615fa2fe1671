#!/usr/bin/env python3
"""Does a cell's train step fit one v5e? Answered here, without the chip.

Compiles the cell's real step program (the one ``hvd.compiled_train_step``
runs: forward, backward, fused exchange, adamw apply, donated) for a
DESCRIBED v5e (``jax.experimental.topologies``, libtpu's compiler is
installed in the sandbox) and prints XLA's ``memory_analysis()`` per device:
arguments + temporaries + outputs - aliased (donated) bytes. This is how the
depth (``reduced``) and ``remat`` of every cell were chosen; the table is in
PERF.md section 4. Nothing runs: it says nothing about times or results.

    JAX_PLATFORMS=cpu python3 benchmark/tools/fit.py                # all cells
    JAX_PLATFORMS=cpu python3 benchmark/tools/fit.py sc2-3b_s16k --layers 5 --remat 1

It builds the program through ``ops/step_program._build_step_program``
because ``CompiledTrainStep`` only builds for the devices that are attached;
a tool may reach in like that, the measured harness does not.
"""

import argparse
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

GIB = 2.0 ** 30
V5E_LIMIT_GIB = 15.75  # memory_stats()["bytes_limit"] on the chip (PR 21)


def compile_cell(cell, layers=None, remat=None, global_batch=None):
    """AOT-compile ``cell``'s step for a described v5e; returns the
    compiled executable and the model configuration."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark.modes import train_dp
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.ops import step_program

    # a described chip cannot read the cache back; keep the runs silent
    jax.config.update("jax_enable_compilation_cache", False)
    if layers is not None:
        conf = dict(cell["config"])
        conf[conf["maps_to"]["n_layers"]] = layers
        cell = dict(cell, config=conf)
    if remat is not None:
        cell = dict(cell, cell=dict(cell["cell"], remat=bool(remat)))
    if global_batch is not None:
        cell = dict(cell, traffic=dict(cell["traffic"],
                                       global_batch=global_batch))
    cfg = train_dp.model_config(cell, interpret=False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    n = cell["chips"]
    mesh = Mesh(np.array(topo.devices[:n]), ("hvd",))
    rep, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("hvd"))

    def loss_fn(p, tokens, targets):
        return tfm.loss_fn(p, tokens, targets, cfg,
                           tfm.ShardAxes(dp=None, sp=None, tp=None))

    tx = train_dp.base_optimizer(cell["cell"]["optimizer"])
    params = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(tx.init, params)

    def shaped(tree, sharding):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    seq, gb = cell["traffic"]["seq_len"], cell["traffic"]["global_batch"]
    tok = jax.ShapeDtypeStruct((gb, seq), jnp.int32, sharding=split)
    prog = step_program._build_step_program(
        mesh, loss_fn, tx, 2, "psum", True, None, False, True, False)
    compiled = prog.lower(shaped(params, rep), shaped(opt, rep), tok,
                          tok).compile()
    return compiled, cfg


def report(name, compiled, cfg, seconds):
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
             + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    hlo = compiled.as_text()
    print(f"{name}: layers {cfg.n_layers} remat {cfg.remat} | arguments "
          f"{ma.argument_size_in_bytes / GIB:.2f} + temporaries "
          f"{ma.temp_size_in_bytes / GIB:.2f} + outputs "
          f"{ma.output_size_in_bytes / GIB:.2f} - aliased "
          f"{ma.alias_size_in_bytes / GIB:.2f} = {total / GIB:.2f} GiB "
          f"per device of {V5E_LIMIT_GIB} | tpu_custom_call "
          f"{hlo.count('custom_call_target=\"tpu_custom_call\"')} | "
          f"all-reduce {hlo.count(' all-reduce')} | compiled in "
          f"{seconds:.0f} s", flush=True)
    return total


def main(argv=None):
    from benchmark.lib import cells
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workloads", nargs="*",
                    help="cells to compile (default: all in BENCHMARK.json)")
    ap.add_argument("--layers", type=int, help="override the depth")
    ap.add_argument("--remat", type=int, choices=(0, 1),
                    help="override the cell's remat")
    ap.add_argument("--global-batch", type=int,
                    help="override the traffic's global batch")
    ap.add_argument("--hlo-out", help="write the optimized HLO text here")
    args = ap.parse_args(argv)
    names = args.workloads or [w["name"] for w in
                               cells.load_benchmark()["workloads"]]
    for name in names:
        t0 = time.perf_counter()
        try:
            compiled, cfg = compile_cell(cells.load_cell(name), args.layers,
                                         args.remat, args.global_batch)
        except Exception as e:  # noqa: BLE001 - the refusal IS the answer
            print(f"{name}: REFUSED by the compiler: "
                  f"{str(e).splitlines()[0][:300]}", flush=True)
            continue
        report(name, compiled, cfg, time.perf_counter() - t0)
        if args.hlo_out:
            with open(args.hlo_out, "w", encoding="utf-8") as f:
                f.write(compiled.as_text())


if __name__ == "__main__":
    main()
