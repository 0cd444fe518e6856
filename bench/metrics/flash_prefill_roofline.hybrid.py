"""Kernels (`flash_attention.flash_attention`) in the hybrid cell: the
least time for causal attention over each prompt's true length, over the
kernel's device time, in percent; the attention layers only (the
family's `layer_count`), where `flash_prefill_roofline` counts every
layer."""
from bench import programs
from bench.peaks import least_seconds


def read(run):
    pairs = programs.matched_prefills(run)
    if not pairs or not hasattr(run.family, "layer_count"):
        return None
    took = programs.kernel_seconds(run, pairs, programs.FLASH_PREFILL)
    if took <= 0:
        return None
    layers = run.family.layer_count(run.config, "full")
    least = sum(layers * least_seconds(
        *run.family.flash_prefill_work(run.config, r.length), run.peaks)
        for _, r in pairs)
    return 100.0 * least / took
