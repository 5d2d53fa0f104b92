"""Device time per step of the operations that move optimizer state
between ``pinned_host`` and HBM (the slice loop of the Adam update): the
ops of the traced window whose HLO text names host memory (``S(5)``),
the ``while`` that holds them left out.  These are the asynchronous
slice starts and the waits on their completion, so the time is the part
of the step the device spends issuing or waiting on host transfers.  A
step with no such operation reports nothing."""


def read(run):
    if run.trace is None:
        return None
    t = run.trace.op_time_ns(host=True)
    if t == 0:
        return None
    return t / 1e6 / run.steps
