"""Seconds from process start to the first timed product (host clock)."""


def read(r):
    return r.setup_s
