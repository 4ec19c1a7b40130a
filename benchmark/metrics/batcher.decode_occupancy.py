"""Active slots per decode step over the slots there are."""


def read(r):
    steps = r["spans"].named("engine.decode_step")
    if not steps:
        return None
    active = [s[3]["active"] for s in steps]
    return 100.0 * sum(active) / (len(active) * r["slots"])
