"""host_gap_ms.chat: mean device-idle time between consecutive decode
programs in the traced window (engine loop: sampling, host sync, request
plane between chunks); the breakdown attributes each idle gap to the
host span it falls in."""


def read(run):
    if run.trace is None:
        return None
    gaps = run.trace.decode_gaps_ns()
    return sum(gaps) / len(gaps) * 1e-6 if gaps else None
