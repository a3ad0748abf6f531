"""chip_copy_out_s: per job, the seconds of every chip partition's copy_out
intervals (device to host, into C) summed, the mean over the window's jobs."""


def read(run):
    chips = {p.name for p in run.profiles if p.kind == "tpu"}
    per_job = [[e.duration for e in j.report.measured.events
                if e.device in chips and e.kind == "copy_out"]
               for j in run.jobs]
    if not any(per_job):
        return None
    return sum(map(sum, per_job)) / len(per_job)
