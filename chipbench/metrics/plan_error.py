"""plan_error: |measured - planned| / planned makespan, the mean over the
window's jobs; measured is the executor's own stage intervals
(``ExecutionReport.measured``).  The paper's Table 4, taken on the chip."""


def read(run):
    errs = [abs(j.report.measured.makespan - j.report.predicted_makespan)
            / j.report.predicted_makespan for j in run.jobs]
    return sum(errs) / len(errs) if errs else None
