"""ttft_p90_ms: 90th percentile, over every request due in the window, of
the time from its due time to the first token its client received.  A
request that failed or never finished counts as infinitely slow."""

import math

from bench.lib.traffic import nearest_rank


def read(run):
    vals = [
        (o.first - o.due) * 1e3 if o.finished else math.inf for o in run.outcomes
    ]
    return nearest_rank(vals, 0.9)
