"""Output tokens delivered to clients in the window, over the window's
seconds. Host clock, where each client thread receives the token."""


def read(run):
    n = sum(1 for _ in run.tokens_between(run.w0, run.w1))
    return n / run.seconds
