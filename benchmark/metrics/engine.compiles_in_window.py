"""Prefill and decode compiles (promotions included) inside the window."""


def read(r):
    return r["compiles_in_window"]
