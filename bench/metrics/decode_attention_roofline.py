"""decode_attention_roofline: the kernel's least time (the larger of its
operations over peak FLOP/s and its bytes over peak bandwidth, counted
over the live cache positions only) over the summed time of its device
events, for the whole decode chunks in the traced window, in %."""

from bench.lib import cost, readings


def read(run):
    if run.trace is None:
        return None
    least = spent = 0.0
    cfg = run.cell.config
    for progs, ctx in readings.decode_chunks(run.trace):
        least += cost.least_time(cost.decode_attention_cost(cfg, ctx), run.peaks)
        spent += sum(p.kernels.get("decode_attention", 0) for p in progs) * 1e-9
    return 100.0 * least / spent if spent > 0 else None
