"""first_token_hold_ms.chat: mean time a first token, sampled at
admission, waits in the engine until the stream push that carries it has
returned (the engine's `first_token_hold_ns / first_tokens_streamed`)."""

from bench.lib import program_spans


def read(run):
    return program_spans.mean_ms(run.stats, "first_token_hold_ns", "first_tokens_streamed")
