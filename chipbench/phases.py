"""What the program's stage events hold beside their interval: the phases
a stage's callable timed (``BusEvent.phases``, each (name, start, end)) and
the seconds the stage waited for its link ticket (``BusEvent.wait``).  A
program whose events carry no phases gives these readers nothing."""


def has_phases(run) -> bool:
    """Whether any stage event of the run's jobs carries phases."""
    return any(getattr(e, "phases", ()) for j in run.jobs
               for e in j.report.measured.events)


def chip_names(run) -> set[str]:
    return {p.name for p in run.profiles if p.kind == "tpu"}


def mean_phase_s(run, devices: set[str], kind: str, name: str):
    """Per job, the seconds of the ``name`` phases of the ``kind`` stages
    of ``devices`` summed; the mean over the jobs in which those stages
    ran.  None where the program records no phases or the stages never
    ran."""
    if not has_phases(run):
        return None
    per_job = []
    for job in run.jobs:
        events = [e for e in job.report.measured.events
                  if e.device in devices and e.kind == kind]
        if events:
            per_job.append(sum(end - start for e in events
                               for n, start, end in e.phases if n == name))
    return sum(per_job) / len(per_job) if per_job else None
