"""tpot_p90_ms: 90th percentile, over every request due in the window, of
(last token received - first token received) / (tokens - 1) at the client.
A request that failed or never finished counts as infinitely slow."""

import math

from bench.lib.traffic import nearest_rank


def read(run):
    vals = []
    for o in run.outcomes:
        if not o.finished:
            vals.append(math.inf)
        elif len(o.tokens) > 1:
            vals.append((o.last - o.first) * 1e3 / (len(o.tokens) - 1))
    return nearest_rank(vals, 0.9)
