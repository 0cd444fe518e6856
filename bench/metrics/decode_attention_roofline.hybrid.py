"""Kernels (`flash_attention.decode_attention_fused`) in the hybrid cell:
the least time for the valid KV each call must read, over the kernel's
device time, in percent.  Counts per call from the live rows' true
positions, for the attention layers only (the family's `layer_count`),
where `decode_attention_roofline` counts every layer."""
from bench import programs
from bench.peaks import least_seconds


def read(run):
    pairs = programs.matched_segments(run)
    if not pairs or not hasattr(run.family, "layer_count"):
        return None
    took = programs.kernel_seconds(run, pairs, programs.DECODE_ATTENTION)
    if took <= 0:
        return None
    layers = run.family.layer_count(run.config, "full")
    least = sum(layers * least_seconds(
        *run.family.decode_attention_work(run.config, pos), run.peaks)
        for _, r in pairs for pos in r.positions if len(pos))
    return 100.0 * least / took
