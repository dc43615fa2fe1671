#!/usr/bin/env python3
"""The control of a ``train_hybrid`` cell's tolerances: the plain reference
recomputed in a LOWER precision than the cell states, handed to the
harness's own comparison (``train_hybrid.compare_with_reference`` with the
cell file's ``tolerances``) as if the timed path had produced it. A
tolerance that such a reading passes tells nothing, so the tool says, for
each variant, whether it comes out not correct and by which limits:

- ``fp32``      the reading against itself: the tool's own control (passes);
- ``bf16``      parameters, activations, matmul outputs, the recurrence's
                state and decay all bfloat16;
- ``fp8``       float32 with the operands of every projection rounded to
                fp8 e4m3 under a per-tensor scale (straight-through
                gradient);
- ``state_bf16`` float32 with the recurrence's state and per-step decay in
                bfloat16 (the sequential form has no cumulative sum: what
                the chunked form keeps in its sums lives in the state here).

What stands in for the timed path's first update is ``-lr * sign(g)`` on
the sampled leaves, adamw's first step. Exit code 1 if ``fp32`` fails, or
``fp8`` or ``state_bf16`` passes. On the chip at the timed size (about 20 s
a reading, 9.4 GiB), or on the CPU with the toy preset:

    chiprun -- python3 benchmark/tools/precision_control.py granite4h-micro_s16k --seeds 2147483777 77
    JAX_PLATFORMS=cpu python3 benchmark/tools/precision_control.py granite4h-micro_s16k --seeds 7 --cpu-rehearsal benchmark/tests/tiny_granite.json
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

VARIANTS = ("fp32", "bf16", "fp8", "state_bf16")
#: what each variant has to come out as (bf16 is reported, not judged)
EXPECTED = {"fp32": True, "fp8": False, "state_bf16": False}


def _fp8(t):
    """Rounded to e4m3 under a per-tensor scale; the gradient passes
    straight through."""
    import jax
    import jax.numpy as jnp
    s = jax.lax.stop_gradient(jnp.max(jnp.abs(t)) / 448.0)
    rounded = (t / s).astype(jnp.float8_e4m3fn).astype(t.dtype) * s
    return t + jax.lax.stop_gradient(rounded - t)


def _bf16_loss_and_grads(ref):
    """``reference_granite.loss_and_grads`` with everything in bfloat16
    (``loss`` itself widens the parameters to float32, so the head is
    written out here; blocks of 2048 positions)."""
    import jax
    import jax.numpy as jnp

    def loss(params, tokens, targets, arch):
        params = jax.tree.map(lambda t: t.astype(jnp.bfloat16), params)
        x, states = ref.trunk(params, tokens, arch)
        total = 0.0
        for s0 in range(0, tokens.shape[1], 2048):
            total = total + jax.checkpoint(
                lambda xb, tb, ln, emb: ref._nll_block(xb, tb, ln, emb,
                                                       arch))(
                x[:, s0:s0 + 2048], targets[:, s0:s0 + 2048],
                params["ln_f"], params["embed"]).astype(jnp.float32)
        return total / tokens.size, jnp.stack(states).astype(jnp.float32)

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
    """``train_hybrid.reference_reading`` with the reference set to
    ``variant``; the module's two knobs are put back afterwards."""
    import jax.numpy as jnp

    from benchmark.lib import reference_granite as ref
    from benchmark.modes import train_hybrid
    plain_state, plain_proj = ref.STATE_DTYPE, ref._proj
    try:
        if variant in ("bf16", "state_bf16"):
            ref.STATE_DTYPE = jnp.bfloat16
        if variant == "fp8":
            ref._proj = lambda h, w: plain_proj(_fp8(h), _fp8(w))
        return train_hybrid.reference_reading(
            cfg, cell, seed, source,
            _bf16_loss_and_grads(ref) if variant == "bf16" else None)
    finally:
        ref.STATE_DTYPE, ref._proj = plain_state, plain_proj


def control(cell, seed):
    """One seed: ``[{"variant", "correct", "failed_limits", ...what was
    compared}]``."""
    import numpy as np

    from benchmark.lib import data
    from benchmark.modes import train_hybrid
    cfg = train_hybrid.model_config(cell, interpret=False)
    run_cfg = cell["cell"]
    tol, lr = run_cfg["tolerances"], run_cfg["optimizer"]["learning_rate"]
    source = data.make_source(cell["traffic"], seed, cfg.vocab_size)
    want = reading("fp32", cfg, cell, seed, source)
    rows = []
    for variant in VARIANTS:
        t0 = time.perf_counter()
        got = want if variant == "fp32" else reading(variant, cfg, cell,
                                                     seed, source)
        p1 = [before - lr * np.sign(g)
              for before, g in zip(want["before"], got["grads"])]
        ok, out = train_hybrid.compare_with_reference(
            cfg, want, got["loss"], got["rms"], p1, tol)
        failed = [name for name, bad in (
            ("loss0_abs", out["loss0_abs_err"] > tol["loss0_abs"]),
            ("ssm_state_rms_rel_max", out["ssm_state_rms_rel_err_max"]
             > tol["ssm_state_rms_rel_max"]),
            ("sign_agreement_min", min(out["sign_agreement"].values())
             < tol["sign_agreement_min"])) if bad]
        assert ok == (not failed)
        rows.append({"seed": seed, "variant": variant, "correct": ok,
                     "failed_limits": failed,
                     "loss0_abs_err": out["loss0_abs_err"],
                     "ssm_state_rms_rel_err_max":
                         out["ssm_state_rms_rel_err_max"],
                     "ssm_state_rms_rel_err_by_layer":
                         out["ssm_state_rms_rel_err_by_layer"],
                     "sign_agreement": out["sign_agreement"],
                     "seconds": round(time.perf_counter() - t0, 1)})
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--cpu-rehearsal", metavar="PRESET",
                    help="toy sizes on the CPU (never a reading of the "
                    "cell's limits: they are set at the timed size)")
    ap.add_argument("--out", help="also write the rows to this file")
    args = ap.parse_args(argv)
    import jax

    from benchmark.lib import cells
    from benchmark.modes import train_hybrid
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = cells.load_cell(args.workload)
    if args.cpu_rehearsal:
        cell = train_hybrid.apply_tiny(cell,
                                       cells.load_json(args.cpu_rehearsal))
    tol = {k: v for k, v in cell["cell"]["tolerances"].items()
           if k != "reason"}
    print(json.dumps({"workload": args.workload, "tolerances": tol,
                      "platform": jax.devices()[0].platform,
                      "rehearsal": bool(args.cpu_rehearsal)}), flush=True)
    rows, bad = [], []
    for seed in args.seeds:
        for row in control(cell, seed):
            print(json.dumps(row), flush=True)
            rows.append(row)
            if row["correct"] != EXPECTED.get(row["variant"],
                                              row["correct"]):
                bad.append(f"{row['variant']} @ seed {seed}: correct = "
                           f"{row['correct']}")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(rows, f, indent=1)
    if bad and not args.cpu_rehearsal:
        print("precision_control: " + "; ".join(bad), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
