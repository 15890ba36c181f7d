"""decode_step_ms: device time per execution of the decode program (the
program that holds `decode_attention`), mean over the traced window."""


def read(run):
    if run.trace is None:
        return None
    dec = run.trace.of_kind("decode")
    return sum(p.end - p.start for p in dec) / len(dec) * 1e-6 if dec else None
