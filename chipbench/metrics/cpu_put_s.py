"""cpu_put_s: the host CPU partition's ``put`` phase (its A rows and B
committed to the CPU device, inside its compute interval), the mean over
the jobs that gave it rows."""
from chipbench.phases import mean_phase_s


def read(run):
    return mean_phase_s(run, {"host-cpu"}, "compute", "put")
