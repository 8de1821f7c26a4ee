"""ota_superpose.gb_per_s: the rate at which the ``ota_superpose`` kernel
(the fused superposition y = a (sum_k s_k g_k + z)) moves its operands and
results, in GB/s over every call in the traced stretch, dense or streamed.

A call's bytes are all of its operands and results at the padded shapes
the wrapper hands the kernel, read from the call's own instruction; the
rate is their sum over the calls' summed device time.  It is no share of
a roofline: the compiler keeps the kernel's operands in on-chip memory,
whose bandwidth is not published (``bench/roofline.py``)."""
from bench import roofline, trace


def read(run):
    if run.trace is None:
        return None
    calls = trace.kernel_calls(run.trace, "ota_superpose")
    if not calls:
        return None
    return roofline.calls_gb_per_s(calls)
