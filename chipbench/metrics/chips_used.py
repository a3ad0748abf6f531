"""chips_used: per job, the accelerators (profiles with a host link) that
computed rows of C, read from ``ExecutionReport.placement``; the mean over
the window's jobs.  None where the reports carry no placement."""
import math


def read(run):
    linked = {p.name for p in run.profiles
              if not math.isinf(p.copy.bandwidth_bytes_per_s)}
    per_job = []
    for job in run.jobs:
        placement = getattr(job.report, "placement", None)
        if placement is None:
            return None
        per_job.append(sum(1 for name, devs in placement.items()
                           if name in linked and devs))
    return sum(per_job) / len(per_job) if per_job else None
