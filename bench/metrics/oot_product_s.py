"""Seconds per out-of-core product: the window over the products completed in it."""


def read(r):
    return r.window_s / r.count
