#!/usr/bin/env python3
"""``precision_control_linear.py`` for a ``train_share_conv`` cell: the
plain reference recomputed in a LOWER precision than the cell states,
handed to the harness's own comparison
(``train_share_conv.compare_with_reference`` with the cell file's
``tolerances``) as if the timed path had produced it. What stands in for
the first update (``-lr * sign(g)`` applied in float32, and ``(1 -
ADAM_B1) * g`` as the first moment), ``_fp8`` and the shape of the rows are
``precision_control.py``'s; the variants are

- ``fp32``      the reading against itself: the tool's own control (passes);
- ``bf16``      everything bfloat16: the parameters as the matmuls see
                them, activations, matmul outputs, norms, the router, the
                loss. On a TPU a bfloat16 matmul accumulates in float32
                inside the matrix unit and rounds its output, which is
                what the program does too, and a model without a
                recurrent state has nowhere to pile the rest up;
- ``fp8``       float32 with the operands of every projection rounded to
                fp8 e4m3 under a per-tensor scale (straight-through
                gradient);
- ``gate_bf16`` float32 with the short-convolution mixer's chain rounded
                to bfloat16 after EACH of its three steps (``B * u``, the
                tap sum, ``C *``) where the program rounds once, at the
                end.

Exit code 1 if ``fp32`` fails or ``fp8`` passes; ``bf16`` and ``gate_bf16``
are reported with every statistic the comparison reads (PERF.md section 7
says why no limit separates them).

    chiprun -- python3 benchmark/tools/precision_control_conv.py lfm2-24b-a2b_s16k --seeds 2147483777 77
    JAX_PLATFORMS=cpu python3 benchmark/tools/precision_control_conv.py lfm2-24b-a2b_s16k --seeds 7 --cpu-rehearsal benchmark/tests/tiny_lfm2.json
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools.precision_control import _fp8  # noqa: E402

VARIANTS = ("fp32", "bf16", "fp8", "gate_bf16")
#: what each variant has to come out as (the other two are reported)
EXPECTED = {"fp32": True, "fp8": False}


def _bf16_loss(ref):
    """``reference_lfm2.loss`` with everything in bfloat16 (``loss`` itself
    widens the parameters to float32)."""
    import jax
    import jax.numpy as jnp

    def loss(params, tokens, targets, arch):
        params = jax.tree.map(lambda t: t.astype(jnp.bfloat16), params)
        x, loads = ref.trunk(params, tokens, arch)
        return ref.total_nll(params, x, targets, arch) / tokens.size, {
            "load": jnp.stack(loads)}

    return loss


def reading(variant, model, cfg, cell, seed, source):
    """``train_share_conv.reference_reading`` with the reference set to
    ``variant``; the module's two knobs are put back afterwards."""
    import jax.numpy as jnp

    from benchmark.lib import reference_lfm2 as ref
    from benchmark.modes import train_share_conv as mode
    plain_proj, plain_step = ref._proj, ref._gate_step
    try:
        if variant == "fp8":
            ref._proj = lambda h, w: plain_proj(_fp8(h), _fp8(w))
        if variant == "gate_bf16":
            ref._gate_step = lambda x: x.astype(jnp.bfloat16).astype(x.dtype)
        bf16 = _bf16_loss(ref) if variant == "bf16" else None
        return mode.reference_reading(
            model, cfg, cell, seed, source,
            lambda *a: ref.loss_and_grads(*a, loss_fn=bf16))
    finally:
        ref._proj, ref._gate_step = plain_proj, plain_step


def control(cell, seed, variants=VARIANTS):
    """One seed: ``[{"variant", "correct", "failed_limits", ...what was
    compared}]``."""
    import numpy as np

    from benchmark.lib import data
    from benchmark.modes import train_share_conv as mode
    model = mode.LFM2
    cfg = model.model_config(cell, interpret=False)
    run_cfg = cell["cell"]
    lr = mode.first_rate(run_cfg["optimizer"])
    source = data.make_source(cell["traffic"], seed, cfg.vocab_size)
    want = reading("fp32", model, cfg, cell, seed, source)
    rows = []
    for variant in variants:
        t0 = time.perf_counter()
        got = (want if variant == "fp32"
               else reading(variant, model, cfg, cell, seed, source))
        p1 = [np.float32(before) - np.float32(lr) * np.sign(g)
              for before, g in zip(want["before"], got["grads"])]
        m1 = [(1 - mode.ADAM_B1) * g for g in got["grads"]]
        ok, out = mode.compare_with_reference(
            model, cfg, want, got["loss"], got["aux"], p1, m1,
            run_cfg["tolerances"], lr)
        rows.append({"seed": seed, "variant": variant, "correct": ok,
                     "failed_limits": out["limits_missed"],
                     "loss0_abs_err": out["loss0_abs_err"],
                     "assignments_moved_share":
                         out["assignments_moved_share"],
                     "sign_agreement": out["sign_agreement"],
                     "first_moment_share": out["first_moment_share"],
                     "grad_rel_err": out["grad_rel_err"],
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
    from benchmark.modes import train_share_conv as mode
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
        print("precision_control_conv: " + "; ".join(bad), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
