"""step_mfu.chat: model FLOPs of every prompt prefilled and every token
decoded by whole calls in the traced window, over the window times the
chip's peak bf16 rate, in %."""

from bench.lib import readings


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    flops = readings.model_flops(run.trace, run.cell.config)
    return 100.0 * flops / (run.trace.window_s * run.peaks["bf16_flops_per_s"]) if flops else None
