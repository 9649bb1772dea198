"""Device milliseconds per round in the cohort client program(s)."""
from chipbench.trace import time_of


def read(rec):
    if rec.get("trace") is None or rec.get("round") is None or not rec["round"]["rounds"]:
        return None
    secs, n = time_of(rec["trace"], "modules", "cohort_round")
    return secs * 1e3 / rec["round"]["rounds"] if n else None
