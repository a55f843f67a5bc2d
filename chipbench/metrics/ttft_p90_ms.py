"""90th percentile of time to first token over every request due in the
window, timed from its due time (host clock), in ms. A request that got
no token before the drain ended counts at its wait until then."""
import numpy as np


def read(rec):
    waits = [(r.times[0] if r.times else rec.t_end) - r.due
             for r in rec.measured() if r.due is not None]
    return 1e3 * float(np.percentile(waits, 90)) if waits else None
