"""batched_moments.gb_per_s: the rate at which the ``batched_moments``
kernel (each device's sum of squares and sum over its gradient) moves its
operands and results, in GB/s over every call in the traced stretch.

A call's bytes are all of its operands and results at the padded shapes
the wrapper hands the kernel, read from the call's own instruction; the
rate is their sum over the calls' summed device time.  It is no share of
a roofline: the compiler keeps the kernel's operand in on-chip memory,
whose bandwidth is not published (``bench/roofline.py``).  The streamed
round takes its statistics without this kernel: there it reads nothing."""
from bench import roofline, trace


def read(run):
    if run.trace is None:
        return None
    calls = trace.kernel_calls(run.trace, "batched_moments")
    if not calls:
        return None
    return roofline.calls_gb_per_s(calls)
