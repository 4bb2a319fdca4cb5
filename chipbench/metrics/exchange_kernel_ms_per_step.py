"""Device milliseconds per exchanging step of the Pallas codec kernels
(the encode and decode-accumulate calls that ``arith.kernel_work`` knows;
any other Pallas call is left out), per chip, from the trace."""
import arith


def read(ctx):
    total = sum(k["s"] for k in ctx["trace"]["pallas"].values()
                if arith.kernel_work(k["hlo"]) is not None)
    if not ctx["sync_steps"] or not total:
        return None
    return 1e3 * total / ctx["sync_steps"]
