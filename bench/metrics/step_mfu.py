"""Useful training FLOPs per second over the chips' bf16 peak: the family's
analytic FLOPs per sample (3 x forward), times the traced run's samples/s,
over chips x peak."""
from bench.peaks import peak


def read(run):
    if run.samples_per_s <= 0:
        return None
    return 100.0 * run.flops_per_sample * run.samples_per_s / (
        run.chips * peak(run.device_kind, "bf16_flops_per_s"))
