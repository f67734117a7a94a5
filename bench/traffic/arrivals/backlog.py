"""A backlog: every request waits at the start and the system drains the
queue at its own pace (closed loop). A run offers the cell's
``backlog_requests``, enough that the queue never empties in the window."""

import numpy as np

OPEN_LOOP = False


def count(mix, cell, seconds, rate_per_s):
    return int(cell["backlog_requests"])


def due_s(mix, n, rate_per_s, rng):
    return np.zeros(n)
