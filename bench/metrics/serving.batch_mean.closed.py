"""Mean batch size the serving tier dispatched, over the window's answers
(the program's ``SearchResponse.batch_size``, request-weighted)."""

import numpy as np


def read(r):
    if not r.responses:
        return None
    return float(np.mean([x.batch_size for x in r.responses]))
