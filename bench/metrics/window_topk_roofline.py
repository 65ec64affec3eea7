"""The row-window stage-1 kernel's share of the HBM roofline: the least
bytes of the traced batches (the union of each batch's Eq. 2 windows of
folded rows) at the chip's peak bandwidth, over the kernel's device time.
The kernel is found by the name the trace gives its Mosaic custom call,
that of the jitted wrapper in ``kernels/ops.py`` that launches it."""
import math

import numpy as np

from bench import roofline, tracereduce

KERNEL = ("_window_topk_impl",)


def read(run):
    if run.trace is None or run.peak is None or not run.window.traced:
        return None
    t = tracereduce.kernel_s(run.trace, KERNEL)
    cfg = run.cfg
    k, m = int(cfg["k"]), int(cfg["fold_m"])
    k1 = max(int(k * m * math.log2(2 * m)), k)
    srt = np.sort(run.library_counts)
    least = sum(roofline.window_topk_bytes(srt, run.library_counts[rows],
                                           cfg, k1)
                for rows in run.window.traced)
    return roofline.hbm_share(least, t, run.peak)
