"""Body bytes all mirrors served in the window, counted on the mirrors'
side, over checkpoint bytes restored (B/B): duplicate fetches show here."""


def read(run):
    done = run.done
    if not done:
        return None
    served = sum(m["served"] for r in done for m in r.mirrors)
    return served / sum(r.nbytes for r in done)
