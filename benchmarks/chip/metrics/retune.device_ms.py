"""Device time of one between-wave retune: the executions of the fused
sweep (``core.autotune._fused_sweep``, compiled as ``jit__sweep_core``) in
the traced restore, summed over devices, per execution (ms)."""


def read(run):
    if run.trace is None:
        return None
    secs, calls = run.trace.module_time("jit__sweep_core")
    return secs / calls * 1e3 if calls else None
