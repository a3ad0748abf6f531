"""plan_ms: host milliseconds of ``HGemms.plan`` (Optimize, Adapt and
Schedule) on an emptied plan cache, the median of the calls in set-up."""
import statistics


def read(run):
    return statistics.median(run.plan_s) * 1e3 if run.plan_s else None
