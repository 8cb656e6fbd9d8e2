"""Bytes the mirrors served over what their links could carry while the
restores ran: the sum of rate x alive time of each mirror within each
restore's interval (%).  The mirrors' own pacing keeps it under 100%, but
for the one bandwidth-delay product each may send ahead.  Nothing to read
when a mirror is unthrottled."""


def _alive_s(alive, lo, hi):
    """Seconds of [lo, hi] inside the alive intervals."""
    return sum(max(0.0, min(hi, hi if stop is None else stop) - max(lo, start))
               for start, stop in alive)


def read(run):
    served = capacity = 0.0
    for r in run.done:
        for m in r.mirrors:
            if m["rate"] <= 0:
                return None
            served += m["served"]
            capacity += m["rate"] * _alive_s(m["alive"], r.t_call, r.t_ready)
    return 100.0 * served / capacity if capacity else None
