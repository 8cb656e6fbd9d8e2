"""Checkpoint bytes made resident over the window's timed intervals, each
from the ``restore_checkpoint`` call to ``block_until_ready`` (MiB/s)."""


def read(run):
    done = run.done
    if not done:
        return None
    return sum(r.nbytes for r in done) / sum(r.seconds for r in done) / 2**20
