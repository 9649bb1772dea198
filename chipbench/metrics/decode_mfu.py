"""The decode step's share of the chip's peak: over the window's steps, the
least time the chip could take for the work each step requires (the larger
of its operations over peak FLOP/s and its bytes over peak bytes/s, from
chipbench/flops.py), over the device time of the step program."""
from chipbench.flops import roofline_seconds
from chipbench.trace import time_of

STEP = "jit_step_fn"


def read(rec):
    s = rec.get("serve")
    if rec.get("trace") is None or s is None or not s["steps"]:
        return None
    secs, n = time_of(rec["trace"], "modules", STEP)
    if not n:
        return None
    least = sum(roofline_seconds(f, b, rec["peak"])[0] for f, b in zip(s["step_flops"], s["step_bytes"]))
    return 100.0 * least / secs
