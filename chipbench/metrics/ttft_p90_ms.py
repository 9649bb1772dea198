"""90th percentile of time to first token over every request due in the
window, from when it was due; one with no token at the close counts with
its wait so far."""
import numpy as np


def read(rec):
    s = rec.get("serve")
    return None if s is None or not s["ttft_ms"] else float(np.percentile(s["ttft_ms"], 90))
