"""step_host_ms.chat: the engine thread's time per decode step outside
the token read-back (device puts, dispatch, slot bookkeeping; the
engine's `decode_host_ns / decode_steps`)."""

from bench.lib import program_spans


def read(run):
    return program_spans.mean_ms(run.stats, "decode_host_ns", "decode_steps")
