"""Scheduler: share of prefilled rows x positions that are padding, over
the prefills of the traced window. Prefilled: the slot count times the
bucket of each ``prefill`` span (the program's own span arguments).
Real: the prompt tokens of the requests each prefill admitted, matched
by their first token arriving after the span (%)."""


def read(run):
    spans = sorted((s for s in run.spans if s["name"] == "prefill"),
                   key=lambda s: s["t0"])
    if not spans:
        return None
    real = sum(map(sum, run.admitted([s["t1"] for s in spans])))
    padded = sum(run.batch * int(s["args"]["bucket"]) for s in spans)
    return 100.0 * (1.0 - real / padded)
