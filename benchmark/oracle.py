"""The comparisons that decide `correct`: exact counts against the
copied oracles.  Every comparison is (name, got, want, limit, ok); an
exact comparison has the limit 0."""


def compare(name, got, want, ok=None, limit=0):
    if ok is None:
        ok = got == want
    return {"name": name, "got": got, "want": want, "limit": limit,
            "ok": bool(ok)}


def level_comparisons(levels, oracle, partial_last):
    """`levels` are the run's level sizes from Init.  Every complete
    level equals the oracle's; with `partial_last` the last level was
    cut by the time limit and may hold no more than the oracle's."""
    out = []
    levels = [int(x) for x in levels]
    out.append(compare("levels.within_pinned_depth", len(levels),
                       f"1..{len(oracle)}",
                       ok=1 <= len(levels) <= len(oracle)))
    n = min(len(levels), len(oracle))
    whole = n - 1 if partial_last else n
    lost = sum(abs(levels[d] - oracle[d]) for d in range(whole))
    out.append(compare(f"levels.complete[0..{whole - 1}]",
                       levels[:whole], oracle[:whole]))
    if partial_last and n:
        out.append(compare(f"levels.partial[{n - 1}]", levels[n - 1],
                           f"<= {oracle[n - 1]}",
                           ok=0 <= levels[n - 1] <= oracle[n - 1]))
        lost += max(0, levels[n - 1] - oracle[n - 1])
    return out, lost


def verdict(comparisons):
    return all(c["ok"] for c in comparisons)
