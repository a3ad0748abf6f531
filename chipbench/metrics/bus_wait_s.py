"""bus_wait_s: per job, the seconds every stage that takes a link waited
for its ticket (``BusEvent.wait``) summed, the mean over the window's jobs.
Device-seconds of waiting: with several devices on one link the sum can
exceed the job's time."""
from chipbench.phases import has_phases


def read(run):
    if not has_phases(run):
        return None
    per_job = [sum(e.wait for e in j.report.measured.events
                   if e.link is not None) for j in run.jobs]
    return sum(per_job) / len(per_job)
