"""95th percentile of the gaps between consecutive output tokens, over all
completed requests: the reply's ``token_ms`` (the batcher's stamp of every
token from submission). A tail of gaps, where ``tpot`` from ``gen_ms`` is a
mean per request. None where the program's replies carry no stamps."""

import numpy as np


def read(r):
    gaps = []
    for _, reply in r.get("summary", {}).get("completed", ()):
        stamps = reply.get("token_ms") or ()
        gaps.extend(b - a for a, b in zip(stamps, stamps[1:]))
    return float(np.percentile(gaps, 95)) if gaps else None
