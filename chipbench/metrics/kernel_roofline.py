"""The codec kernels' share of their roofline: for every call in the
window of a kernel that ``arith.kernel_work`` knows, the least time the
chip could take -- the larger of the algorithm's bytes over peak HBM
bandwidth and its operations over peak rate -- summed, over those calls'
device time.  The codecs are bound by bytes: about 12.5 bytes for 8
operations an element, against 819 GB/s and 197 TFLOP/s on a v5e."""
import arith


def read(ctx):
    pk = ctx["peaks"]
    least = spent = 0.0
    for k in ctx["trace"]["pallas"].values():
        work = arith.kernel_work(k["hlo"])
        if work is None:
            continue
        nbytes, ops = work
        least += k["n"] * max(nbytes / pk["hbm_bytes_per_s"],
                              ops / pk["bf16_flops_per_s"])
        spent += k["s"]
    if not spent:
        return None
    return 100.0 * least / spent
