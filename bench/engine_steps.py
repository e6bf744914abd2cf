"""The engine's decode steps in a traced run, read from the program's own
spans: each ``engine_step`` that lies wholly in the traced window (host
clock) and ran a ``decode_step``. The spans inside an engine step name it
by their ``step`` argument; ``logits_wait`` is the host blocked on the
device and on the copy of the logits back to it."""
import collections


def decode_steps(run):
    """``(engine_step span, seconds of its logits_wait spans)`` per decode
    step of the window; the seconds are ``None`` where the step has no
    ``logits_wait`` span (a program that does not record them)."""
    decoded = set()
    waits = collections.defaultdict(float)
    for s in run.spans:
        if s["name"] == "decode_step":
            decoded.add(s["args"].get("step"))
        elif s["name"] == "logits_wait":
            waits[s["args"].get("step")] += s["t1"] - s["t0"]
    return [(s, waits.get(s["args"].get("step"))) for s in run.spans
            if s["name"] == "engine_step" and run.t0 <= s["t0"]
            and s["t1"] <= run.t1 and s["args"].get("step") in decoded]
