"""mxu_roofline: the chip partitions' least time over their matmul program's
device time, in percent, summed over the window's jobs and the cell's chips.

Least time per kernel call is the larger of 2*m*n*k FLOP over the peak
FLOP/s and (A and B in bf16, C in f32) bytes over the peak bytes/s, from the
unpadded shape (``chipbench/roofline.py``, peaks by ``device_kind``).  Device
time is every run of the chip partition's jitted program in the trace, its
padding and cropping included.  Where the trace does not hold one run of the
program for each kernel call the plans made, nothing is read."""
from chipbench.roofline import least_time, peaks_for

PROGRAM = "jit_matmul"      # kernels/ops.matmul, the chip partition's program


def read(run):
    if run.trace is None or not run.trace.programs:
        return None
    peaks = peaks_for(run.chips[0].device_kind)
    chip_of = {f"tpu{i}": c.id for i, c in enumerate(run.chips)}
    least, calls = 0.0, {c.id: 0 for c in run.chips}
    for job in run.jobs:
        _, n, k = job.shape
        for asg in job.report.plan.adapted.assignments:
            if asg.device in chip_of:
                for rows in asg.chunk_rows:
                    least += least_time(rows, n, k, peaks)[0]
                    calls[chip_of[asg.device]] += 1
    device, seen = 0.0, {}
    for dev, progs in run.trace.programs.items():
        runs = progs.get(PROGRAM, [])
        seen[dev] = len(runs)
        device += sum(runs)
    if not device or seen != calls:
        return None
    return 100.0 * least / device
