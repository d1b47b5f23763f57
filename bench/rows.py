"""The metrics registry's rows of a traced run, for the readers that sum them.

Every chunk's ``ServeResult.metrics`` is a snapshot of the one registry the
window shares, and each snapshot holds the registry's one row list, so each
list, and each row, is counted once.  A program whose rows lack a counter
gives no reading (None).
"""


def rows(run):
    seen, out = set(), []
    for res in getattr(run, "results", None) or ():
        snap = getattr(res, "metrics", None)
        if snap is None or id(snap.rows) in seen:
            continue
        seen.add(id(snap.rows))
        out.extend(snap.rows)
    return out


def total(run, key):
    """Σ ``key`` over the rows that carry it, or None if none does."""
    vals = [r[key] for r in rows(run) if key in r]
    return float(sum(vals)) if vals else None


def mean_ms(run, key):
    """Σ ``key`` / Σ ``waited``, in ms (None without a waited member)."""
    s, n = total(run, key), total(run, "waited")
    if s is None or not n:
        return None
    return 1e3 * s / n


def window_share(run, key):
    """100 × Σ ``key`` / the window's wall seconds."""
    s = total(run, key)
    if s is None or run.window_s <= 0:
        return None
    return 100.0 * s / run.window_s
