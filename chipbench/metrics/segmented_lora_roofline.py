"""The segmented LoRA kernel's share of its roofline: the least time its
calls in the window could take (operations and bytes from chipbench/flops.py,
every call bandwidth-bound at these shapes) over the device time of its
events in the trace."""
from chipbench.flops import roofline_seconds
from chipbench.trace import time_of

KERNEL = "segmented"


def read(rec):
    s = rec.get("serve")
    if rec.get("trace") is None or s is None or not s["steps"]:
        return None
    secs, n = time_of(rec["trace"], "ops", KERNEL)
    if not n:
        return None
    least, _ = roofline_seconds(s["kernel_flops"], s["kernel_bytes"], rec["peak"])
    return 100.0 * least / secs
