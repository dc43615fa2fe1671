#!/usr/bin/env python3
"""``precision_control.py`` for a ``train_share_linear`` cell: the plain
reference recomputed in a LOWER precision than the cell states, handed to
the harness's own comparison (``train_share_linear.compare_with_reference``
with the cell file's ``tolerances``) as if the timed path had produced it.
The variants, what stands in for the first update and the exit code are
that tool's (``fp32`` must pass; ``bf16``, ``fp8`` and ``state_bf16`` must
each miss at least one limit); what differs is the reference
(``reference_kimi_linear``: the delta rule's state and decay take
``STATE_DTYPE``) and the comparison's statistics.

    chiprun -- python3 benchmark/tools/precision_control_linear.py kimi-linear_s16k --seeds 2147483777 77
    JAX_PLATFORMS=cpu python3 benchmark/tools/precision_control_linear.py kimi-linear_s16k --seeds 7 --cpu-rehearsal benchmark/tests/tiny_kimi_linear.json
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools.precision_control import VARIANTS, _fp8  # noqa: E402

#: what each variant has to come out as
EXPECTED = {"fp32": True, "bf16": False, "fp8": False, "state_bf16": False}


def _bf16_loss_and_grads(ref):
    """``reference_kimi_linear.loss_and_grads`` with everything in bfloat16
    (``loss`` itself widens the parameters to float32, so the head is
    written out here; blocks of 2048 positions)."""
    import jax
    import jax.numpy as jnp

    def loss(params, tokens, targets, arch):
        params = jax.tree.map(lambda t: t.astype(jnp.bfloat16), params)
        x, states, loads = ref.trunk(params, tokens, arch)
        total = 0.0
        for s0 in range(0, tokens.shape[1], 2048):
            total = total + jax.checkpoint(
                lambda xb, tb, ln, head: ref._nll_block(
                    xb, tb, ln, head, arch["rms_norm_eps"]))(
                x[:, s0:s0 + 2048], targets[:, s0:s0 + 2048],
                params["ln_f"], params["lm_head"]).astype(jnp.float32)
        return total / tokens.size, {
            "rms": jnp.stack(states).astype(jnp.float32),
            "load": jnp.stack(loads)}

    def loss_and_grads(params, tokens, targets, arch, leaf_paths):
        def f(leaves):
            p = params
            for path, leaf in zip(leaf_paths, leaves):
                p = ref._put(p, path, leaf)
            return loss(p, tokens, targets, arch)
        return jax.value_and_grad(f, has_aux=True)(
            [ref.get_leaf(params, p) for p in leaf_paths])

    return loss_and_grads


def reading(variant, cfg, cell, seed, source):
    """``train_share_linear.reference_reading`` with the reference set to
    ``variant``; the module's two knobs are put back afterwards."""
    import jax.numpy as jnp

    from benchmark.lib import reference_kimi_linear as ref
    from benchmark.modes import train_share_linear as mode
    plain_state, plain_proj = ref.STATE_DTYPE, ref._proj
    try:
        if variant in ("bf16", "state_bf16"):
            ref.STATE_DTYPE = jnp.bfloat16
        if variant == "fp8":
            ref._proj = lambda h, w: plain_proj(_fp8(h), _fp8(w))
        return mode.reference_reading(
            cfg, cell, seed, source,
            _bf16_loss_and_grads(ref) if variant == "bf16" else None)
    finally:
        ref.STATE_DTYPE, ref._proj = plain_state, plain_proj


def control(cell, seed, variants=VARIANTS):
    """One seed: ``[{"variant", "correct", "failed_limits", ...what was
    compared}]``."""
    import numpy as np

    from benchmark.lib import data
    from benchmark.modes import train_share_linear as mode
    cfg = mode.model_config(cell, interpret=False)
    run_cfg = cell["cell"]
    tol, lr = run_cfg["tolerances"], run_cfg["optimizer"]["learning_rate"]
    source = data.make_source(cell["traffic"], seed, cfg.vocab_size)
    want = reading("fp32", cfg, cell, seed, source)
    rows = []
    for variant in variants:
        t0 = time.perf_counter()
        got = want if variant == "fp32" else reading(variant, cfg, cell,
                                                     seed, source)
        p1 = [before - lr * np.sign(g)
              for before, g in zip(want["before"], got["grads"])]
        ok, out = mode.compare_with_reference(
            cfg, want, got["loss"], got["rms"], got["load"], p1, tol)
        rows.append({"seed": seed, "variant": variant, "correct": ok,
                     "failed_limits": out["limits_missed"],
                     "loss0_abs_err": out["loss0_abs_err"],
                     "kda_state_rms_rel_err_max":
                         out["kda_state_rms_rel_err_max"],
                     "kda_state_rms_rel_err_max_by_layer":
                         out["kda_state_rms_rel_err_max_by_layer"],
                     "assignments_moved_share":
                         out["assignments_moved_share"],
                     "sign_agreement": out["sign_agreement"],
                     "seconds": round(time.perf_counter() - t0, 1)})
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", default=list(VARIANTS),
                    choices=VARIANTS)
    ap.add_argument("--cpu-rehearsal", metavar="PRESET",
                    help="toy sizes on the CPU (never a reading of the "
                    "cell's limits: they are set at the timed size)")
    ap.add_argument("--out", help="also write the rows to this file")
    args = ap.parse_args(argv)
    import jax

    from benchmark.lib import cells
    from benchmark.modes import train_share_linear as mode
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = cells.load_cell(args.workload)
    if args.cpu_rehearsal:
        cell = mode.apply_tiny(cell, cells.load_json(args.cpu_rehearsal))
    tol = {k: v for k, v in cell["cell"]["tolerances"].items()
           if k != "reason"}
    print(json.dumps({"workload": args.workload, "tolerances": tol,
                      "platform": jax.devices()[0].platform,
                      "rehearsal": bool(args.cpu_rehearsal)}), flush=True)
    rows, bad = [], []
    for seed in args.seeds:
        for row in control(cell, seed, args.variants):
            print(json.dumps(row), flush=True)
            rows.append(row)
            if row["correct"] != EXPECTED[row["variant"]]:
                bad.append(f"{row['variant']} @ seed {seed}: correct = "
                           f"{row['correct']}")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(rows, f, indent=1)
    if bad and not args.cpu_rehearsal:
        print("precision_control_linear: " + "; ".join(bad),
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
