"""lease_wait_ms.chat: mean time from a request's submit stamp to the
engine's lease of it, over every request leased in the run (the engine's
`lease_wait_ns / leased`, wall clock; request plane: queue and lease)."""

from bench.lib import program_spans


def read(run):
    return program_spans.mean_ms(run.stats, "lease_wait_ns", "leased")
