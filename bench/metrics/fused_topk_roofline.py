"""The query-blocked exhaustive top-k kernel's share of the HBM roofline:
the least bytes of the traced batches (the whole library once per batch)
at the chip's peak bandwidth, over the kernel's device time. The kernel is
found by the name the trace gives its Mosaic custom call, that of the
jitted wrapper in ``kernels/ops.py`` that launches it."""
from bench import roofline, tracereduce

KERNEL = ("_tanimoto_topk_impl",)


def read(run):
    if run.trace is None or run.peak is None or not run.window.traced:
        return None
    t = tracereduce.kernel_s(run.trace, KERNEL)
    least = sum(roofline.fused_topk_bytes(run.n_rows, len(rows), run.cfg)
                for rows in run.window.traced)
    return roofline.hbm_share(least, t, run.peak)
