"""Seconds in ``.compile()`` of the cell's programs during set-up, cache hit
or not."""


def read(r):
    return r.get("compile_s")
