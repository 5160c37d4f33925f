"""Roofline share of the group-reduce kernels (``group_min_scale`` and
``group_max``): the least time the chip could take for every call in the
window, the larger of its bytes over HBM bandwidth and its operations
over peak, over the calls' summed device time.  Nothing when no call ran."""
from bench import flops as F
from bench import trace as T

KERNELS = ("group_min_scale", "group_max")


def read(run):
    pk = run["peaks"]
    least = spent = 0.0
    for text, (seconds, calls) in run["trace"]["ops"].items():
        if T.kernel_name(text) in KERNELS:
            ops, nbytes = F.group_reduce_cost(text)
            least += calls * max(nbytes / pk["hbm_bytes_per_s"],
                                 ops / pk["bf16_flops_per_s"])
            spent += seconds
    return 100.0 * least / spent if spent else None
