"""Least time per step of ONE named attention kernel, for the
``roofline_share`` reduce: the least time of one call
(``flops.flash_kernel_work`` at the larger of FLOPs / peak and bytes /
bandwidth) times the calls the trace shows were made, so that a call
made twice (remat's recomputed forward) does not depress the kernel's
share of its roofline the way it depresses ``flash_roofline``.

The kernels are found by the names the program gives them
(``hvd_flash_fwd`` / ``hvd_flash_dq`` / ``hvd_flash_dkv``,
``ops/flash_attention.py``); a trace of a program without the names
matches nothing and the metric is left out.
"""

import re

from . import flops, trace_reduce


def calls_per_step(trace, pattern):
    """Ops whose scope matches, per step, mean over the devices."""
    rx = re.compile(pattern)
    return trace_reduce.mean_over_devices(
        trace, lambda _, d: sum(1 for o in d["ops"] if rx.search(o["scope"]))
        / d["steps"])


def _least(ctx, kernels):
    work = flops.flash_kernel_work(ctx["shape"], ctx["seqs_per_chip"])
    total, bound_by = 0.0, set()
    for kernel, scope in kernels:
        t, bound = flops.roofline_seconds(*work[kernel], ctx["peaks"])
        total += t * calls_per_step(ctx["trace"], scope)
        bound_by.add(bound)
    return total, "+".join(sorted(bound_by))


def flash_fwd(ctx):
    return _least(ctx, [("fwd", "hvd_flash_fwd")])


def flash_bwd(ctx):
    return _least(ctx, [("dq", "hvd_flash_dq"), ("dkv", "hvd_flash_dkv")])
