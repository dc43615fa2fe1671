"""The benchmark's library. This file only registers what later PRs added
as files of their own with the general reader (``layer_metrics``): a new
reader source or ``work`` function is a new module plus one line here, and
no file that exists is edited.

- ``program_span`` (PR 23): metrics read from the program's own spans.
- ``flash_fwd`` / ``flash_bwd`` (PR 23): per-kernel roofline work.
"""

from . import kernel_work, layer_metrics, program_span

layer_metrics.READERS["program_span"] = program_span.read
layer_metrics.WORK["flash_fwd"] = kernel_work.flash_fwd
layer_metrics.WORK["flash_bwd"] = kernel_work.flash_bwd
