"""Whole served step: the useful model FLOPs that ran on the device in
the traced window, over the window (on the trace's clock) times the
chip's bf16 peak (%). The calls come from the trace, each prefill and
decode call counted in the share of its device time that lies in the
window. Useful: per decode call, one token for each live slot (the
program's ``decode_step`` span), attention over the mean live context of
the tokens decoded in the window; per prefill call, every prompt token
of the requests it admitted, attention over each token's own context.
A windowed layer attends over at most its window. All at the served
shapes (factor shapes for a compressed model), as the configuration's
reference module counts them (``run.work``); padding is not counted."""
from bench import counts


def read(run):
    if run.trace is None or not run.calls:
        return None
    w = run.work
    lin = w["linear_flops_per_token"]
    shape = (w["windows"], w["n_heads"], w["head_dim"])
    ctx = run.decode_context()
    prefills = [c for c in run.calls if c.role == "prefill"]
    prompts = run.admitted([run.host(c.span.end) for c in prefills])
    useful = dict(zip(map(id, prefills),
                      (sum(P * lin + counts.prompt_attention_flops(P, *shape)
                           for P in ps)
                       for ps in prompts)))
    flops = 0.0
    for c in run.calls:
        if c.device_s <= 0:
            continue
        if c.role == "decode_step":
            if ctx is None:
                return None
            f = int(c.span.stats["live"]) * (
                lin + counts.attention_flops(ctx, *shape))
        else:
            f = useful[id(c)]
        flops += f * c.in_window_s / c.device_s
    if not flops:
        return None
    return 100.0 * flops / ((run.trace_hi - run.trace_lo)
                            * run.peaks["bf16_flops_per_s"])
