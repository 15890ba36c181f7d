"""readback_idle_ms.chat: mean, over consecutive decode programs in the
traced window, of the device-idle time between them that lies inside the
program's `serve.readback` spans (the engine waiting for sampled tokens).
A part of `host_gap_ms.chat` of the same run."""

from bench.lib import program_spans


def read(run):
    return program_spans.readback_idle_ms(run, program_spans.serve_records())
