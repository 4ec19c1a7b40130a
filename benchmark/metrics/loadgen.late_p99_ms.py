"""How late the generator sent: send time - due time, 99th percentile."""

import numpy as np


def read(r):
    late = r["summary"]["late_ms"]
    return float(np.percentile(late, 99)) if late else None
