"""chip_d2h_s: per job, the seconds of the chip partitions' ``d2h`` phases
(the device-to-host read inside copy_out) summed, the mean over the
window's jobs."""
from chipbench.phases import chip_names, mean_phase_s


def read(run):
    return mean_phase_s(run, chip_names(run), "copy_out", "d2h")
