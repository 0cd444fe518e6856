"""Kernels (`ssd.ssd_scan`) in the hybrid cell: the least time for the
SSD scan over each prompt's true length, over the kernel's device time,
in percent; the Mamba-2 layers only (the family's `layer_count`), where
`ssd_scan_roofline` counts every layer."""
from bench import programs
from bench.peaks import least_seconds


def read(run):
    pairs = programs.matched_prefills(run)
    if not pairs or not hasattr(run.family, "layer_count"):
        return None
    took = programs.kernel_seconds(run, pairs, programs.SSD_SCAN)
    if took <= 0:
        return None
    layers = run.family.layer_count(run.config, "mamba")
    least = sum(layers * least_seconds(
        *run.family.ssd_scan_work(run.config, r.length), run.peaks)
        for _, r in pairs)
    return 100.0 * least / took
