"""95th percentile of the wait from a request's due time to its admission
into a slot (the handle's ``admitted`` stamp), over the requests due in the
window."""

import numpy as np


def read(ctx):
    w = ctx.stats["queue_wait_s"]
    return 1e3 * float(np.percentile(w, 95)) if w else None
