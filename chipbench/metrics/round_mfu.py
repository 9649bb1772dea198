"""The whole round's share of the chip's peak: the operations the window's
client steps require (chipbench/flops.py, at the active fraction each round
reported) over the window's wall time and the peak FLOP/s."""


def read(rec):
    r = rec.get("round")
    if r is None or not r["required_flops"]:
        return None
    return 100.0 * r["required_flops"] / rec["window_s"] / rec["peak"]["flops_per_s"]
