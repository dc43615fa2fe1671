#!/usr/bin/env python3
"""``fit.py`` for a cell of any mode: does the cell's train step fit one
v5e? Answered here, without the chip.

``fit.py`` builds its configuration through ``train_dp.model_config``;
this tool takes ``model_config`` from the module the cell's ``mode`` names
(``benchmark/modes/<mode>.py``) and, where that mode's loss carries
counters out of the step (``LOSS_HAS_AUX``), compiles the step that way.
The rest — the described v5e, the real step program, XLA's
``memory_analysis()`` — is ``fit.py``'s, whose report it prints. Nothing
runs: it says nothing about times or results.

    JAX_PLATFORMS=cpu python3 benchmark/tools/fit_mode.py laguna-s21_s8k
    JAX_PLATFORMS=cpu python3 benchmark/tools/fit_mode.py laguna-s21_s8k --set num_hidden_layers=6
"""

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools import fit  # noqa: E402  (sets the described chip)


def compile_cell(cell):
    """AOT-compile ``cell``'s step for a described v5e; returns the
    compiled executable and the model configuration."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark.modes import train_dp
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.ops import step_program

    jax.config.update("jax_enable_compilation_cache", False)
    mode = importlib.import_module(f"benchmark.modes.{cell['cell']['mode']}")
    cfg = mode.model_config(cell, interpret=False)
    has_aux = getattr(mode, "LOSS_HAS_AUX", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:cell["chips"]]), ("hvd",))
    rep, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("hvd"))
    axes = tfm.ShardAxes(dp=None, sp=None, tp=None)

    def loss_fn(p, tokens, targets):
        if has_aux:
            return tfm.loss_and_stats(p, tokens, targets, cfg, axes)
        return tfm.loss_fn(p, tokens, targets, cfg, axes)

    tx = getattr(mode, "base_optimizer", train_dp.base_optimizer)(
        cell["cell"]["optimizer"])
    params = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(tx.init, params)

    def shaped(tree, sharding):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    seq, gb = cell["traffic"]["seq_len"], cell["traffic"]["global_batch"]
    tok = jax.ShapeDtypeStruct((gb, seq), jnp.int32, sharding=split)
    prog = step_program._build_step_program(
        mesh, loss_fn, tx, 2, "psum", True, None, False, True, has_aux)
    compiled = prog.lower(shaped(params, rep), shaped(opt, rep), tok,
                          tok).compile()
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    return compiled, cfg, n_params


def main(argv=None):
    from benchmark.lib import cells
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workloads", nargs="+", help="cells to compile")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="override a key of the configuration file")
    ap.add_argument("--cell-set", action="append", default=[],
                    metavar="KEY=JSON", help="override a key of the cell "
                    "file (e.g. remat=false)")
    ap.add_argument("--hlo-out", help="write the optimized HLO text here")
    args = ap.parse_args(argv)
    for name in args.workloads:
        cell = cells.load_cell(name)
        for target, pairs in (("config", args.set), ("cell", args.cell_set)):
            over = dict(kv.split("=", 1) for kv in pairs)
            cell[target] = dict(cell[target], **{
                k: json.loads(v) for k, v in over.items()})
        t0 = time.perf_counter()
        try:
            compiled, cfg, n_params = compile_cell(cell)
        except Exception as e:  # noqa: BLE001 - the refusal IS the answer
            print(f"{name}: REFUSED by the compiler: "
                  f"{str(e).splitlines()[0][:300]}", flush=True)
            continue
        fit.report(f"{name} ({n_params / 1e6:.1f} M parameters)", compiled,
                   cfg, time.perf_counter() - t0)
        if args.hlo_out:
            with open(args.hlo_out, "w", encoding="utf-8") as f:
                f.write(compiled.as_text())


if __name__ == "__main__":
    main()
