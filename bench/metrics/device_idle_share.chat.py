"""device_idle_share.chat: 1 - (union of device-op intervals) / (traced
window), in %."""


def read(run):
    share = None if run.trace is None else run.trace.idle_share()
    return None if share is None else 100.0 * share
