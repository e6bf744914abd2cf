"""Kernels: the least time the decode calls of the traced window need on
this chip, over the device time they took (%). Both come from the
trace: each call whose first run starts in the traced window, its live
slots from the program's ``decode_step`` span. Work is counted from
shapes by the configuration's reference module (``run.work``) with
bench/counts.py: per call, the parameter bytes a step over its live
slots reads, K and V of each live slot over its live positions (a
windowed layer's over at most its window), the live slots' logits out;
FLOPs of every linear and of attention over the live context. The live
context of a slot is the mean over the tokens clients received in the
traced window. The least time is the larger of FLOPs over peak and
bytes over HBM bandwidth; on this path it is the bytes."""
from bench import counts


def read(run):
    cs = run.whole_calls("decode_step")
    ctx = run.decode_context()
    if not cs or ctx is None:
        return None
    w = run.work
    least = spent = 0.0
    for c in cs:
        live = int(c.span.stats["live"])
        nbytes = (w["decode_param_bytes"](live)
                  + counts.kv_bytes(live, ctx, w["windows"],
                                    w["n_kv_heads"], w["head_dim"],
                                    w["kv_dtype"])
                  + live * w["vocab_size"] * 2)
        flops = live * (w["linear_flops_per_token"]
                        + counts.attention_flops(ctx, w["windows"],
                                                 w["n_heads"],
                                                 w["head_dim"]))
        t, _ = counts.roofline_seconds(flops, nbytes,
                                       run.peaks["bf16_flops_per_s"],
                                       run.peaks["hbm_bytes_per_s"])
        least += t
        spent += c.device_s
    return 100.0 * least / spent if spent > 0 else None
