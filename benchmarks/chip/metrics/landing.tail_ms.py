"""From the last body byte any mirror sent in a restore to that restore's
``block_until_ready``, median over the window's restores (ms)."""

import statistics


def read(run):
    tails = []
    for r in run.done:
        last = [m["last_byte"] for m in r.mirrors if m["last_byte"]]
        if last:
            tails.append((r.t_ready - max(last)) * 1e3)
    return statistics.median(tails) if tails else None
