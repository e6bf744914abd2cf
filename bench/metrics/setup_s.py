"""Set-up time: process start to ready to measure (load or make the
weights, boot the engine, deserialize or compile its programs, warm
every prefill bucket the mix reaches). Host clock."""


def read(run):
    return run.setup_s
