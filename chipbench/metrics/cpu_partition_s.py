"""cpu_partition_s: the host CPU partition's compute interval (its operand
put included, since it computes in place), the mean over the jobs that gave
it rows."""


def read(run):
    per_job = [e.duration for j in run.jobs for e in j.report.measured.events
               if e.device == "host-cpu" and e.kind == "compute"]
    return sum(per_job) / len(per_job) if per_job else None
