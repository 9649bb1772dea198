"""Client training tokens per second: every client's tokens over all local
steps of the rounds completed in the window, over the window's wall time."""


def read(rec):
    r = rec.get("round")
    return None if r is None else r["tokens"] / rec["window_s"]
