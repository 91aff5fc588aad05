"""A layer's share of its roofline: the least time the chip could take for
the work of ``work`` (FLOPs over peak FLOP/s, or bytes over peak bytes/s,
whichever is larger; from shapes, ``benchmarks/work.py``) over the device
time of the scopes that ``match``. No device time read: nothing returned,
never 0."""

from benchmarks import trace


def bound_of(work: dict, peaks: dict) -> tuple:
    t_flops = work["flops"] / (peaks["bf16_tflops"] * 1e12)
    t_bytes = work["bytes"] / (peaks["hbm_gbps"] * 1e9)
    return max(t_flops, t_bytes), "compute" if t_flops >= t_bytes else "memory"


def reduce(reduced: dict, spec: dict):
    seconds = trace.scope_seconds(reduced, spec)
    if not seconds or not reduced["images"]:
        return None
    least, _ = bound_of(reduced["work"][spec["work"]], reduced["peaks"])
    return 100.0 * least / (seconds / reduced["images"])
