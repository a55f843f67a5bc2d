"""95th percentile of every inter-token gap (host clock), in ms: of every
measured request in an open loop, of every gap ending in the window in a
backlog."""
import numpy as np


def read(rec):
    gaps = rec.gaps()
    return 1e3 * float(np.percentile(gaps, 95)) if gaps else None
