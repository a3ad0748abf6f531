"""gemm_s: the window's seconds over the jobs it completed, each job timed
from the call to ``HGemms.execute`` until C is on the host."""


def read(run):
    return run.window_s / len(run.jobs) if run.jobs else None
