"""Share of the traced window in which no operation ran on the device,
in a round cell."""


def read(rec):
    if rec.get("trace") is None or rec.get("round") is None:
        return None
    return 100.0 * rec["trace"]["idle_share"]
