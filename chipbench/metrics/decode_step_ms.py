"""Device milliseconds per call of the batcher's decode step program."""
from chipbench.trace import time_of

STEP = "jit_step_fn"


def read(rec):
    if rec.get("trace") is None or rec.get("serve") is None:
        return None
    secs, n = time_of(rec["trace"], "modules", STEP)
    return secs * 1e3 / n if n else None
