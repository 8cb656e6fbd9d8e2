"""CPU seconds (user + system) of the restoring process over the timed
intervals, per GiB made resident (s/GiB).  The mirrors run in processes
of their own and are not counted."""


def read(run):
    done = run.done
    if not done:
        return None
    return sum(r.cpu_s for r in done) / (sum(r.nbytes for r in done) / 2**30)
