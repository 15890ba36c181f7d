"""setup_s: seconds from process start to the first due request (weights,
warm-up with compiles or compile-cache loads, traffic generation)."""


def read(run):
    return run.setup_s
