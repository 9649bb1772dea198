"""Seconds from process start to the window's opening: init, compile or
cache load, and warm-up."""


def read(rec):
    return rec["setup_s"]
