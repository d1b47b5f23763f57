"""Serving loop: real members over batch slots, summed over modules, from the
loop's metrics registry in the traced run (moves ``served_rps``)."""


def read(run):
    f = run.fill
    if not f or not f["slots"]:
        return None
    return 100.0 * (f["members"] - f["phantoms"]) / f["slots"]
