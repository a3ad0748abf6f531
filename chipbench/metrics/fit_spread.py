"""fit_spread: the largest ``spread`` of the program's pools of alike
devices (``ExecutionReport.pools``: per group, max / min - 1 of the
members' fitted seconds per MAC and host-link bandwidths), in percent.
The fits' noise that pooling keeps out of the plan.  None where the
program pools nothing or its reports carry no pools."""


def read(run):
    spreads = [p.spread for j in run.jobs
               for p in getattr(j.report, "pools", None) or ()]
    return 100.0 * max(spreads) if spreads else None
