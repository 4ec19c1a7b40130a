"""95th percentile of the time a request waited for a slot and its pages
(the reply's ``queue_ms``: submission to first admission). None where the
program's replies do not carry it."""

import numpy as np


def read(r):
    waits = [reply["queue_ms"]
             for _, reply in r.get("summary", {}).get("completed", ())
             if "queue_ms" in reply]
    return float(np.percentile(waits, 95)) if waits else None
