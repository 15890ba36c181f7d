"""queue_wait_ms.p50: median, over requests admitted in the window, of the
time from a request's due time to the start of the `admit` call that
prefilled it (request plane: submit, queue, lease)."""

from bench.lib.traffic import nearest_rank


def read(run):
    admitted = {}
    for name, t0, _, info, _ in run.spans.records:
        if name == "admit":
            for req_id, _ in info:
                admitted.setdefault(req_id, t0 * 1e-9)
    waits = [
        (admitted[o.req.req_id] - o.due) * 1e3
        for o in run.outcomes
        if o.req.req_id in admitted
    ]
    return nearest_rank(waits, 0.5) if waits else None
