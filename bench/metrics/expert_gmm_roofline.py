"""Kernels (`gmm.expert_gmm`): the least time for the held experts'
grouped matmuls in the traced decode segments (gate/up and down of every
MoE layer, counted per step from its live rows by the family's
`expert_gmm_work`), over the kernel's device time, in percent."""
from bench import programs
from bench.peaks import least_seconds

EXPERT_GMM = "%expert_gmm."       # ops.expert_gmm (kernels.gmm)


def read(run):
    pairs = programs.matched_segments(run)
    if not pairs or not hasattr(run.family, "expert_gmm_work"):
        return None
    took = programs.kernel_seconds(run, pairs, EXPERT_GMM)
    if took <= 0:
        return None
    layers = run.family.sizes(run.config)["L"]
    least = sum(layers * least_seconds(
        *run.family.expert_gmm_work(run.config, len(pos)), run.peaks)
        for _, r in pairs for pos in r.positions if len(pos))
    return 100.0 * least / took
