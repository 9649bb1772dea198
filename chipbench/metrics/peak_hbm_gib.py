"""Peak device memory of the run, read after the window, in GiB."""


def read(rec):
    return rec["memory_peak_bytes"] / 2**30
