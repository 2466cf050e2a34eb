"""The verify kernels' share of the memory roofline: bytes a launch must
move (readers.launch_bytes) over the chip's published HBM bandwidth, over
the launch's device time.  The ladder is int32 VPU arithmetic with no
published peak, so this bytes bound is the only roofline stated, and it is
expected far under 1%: the kernel is bound by neither published peak."""
from benchmark import readers


def read(run):
    timed = readers.verify_kernel_time(run)
    lanes = readers.lanes_per_dispatch(run)
    if not timed or not lanes:
        return None
    seconds, launches = timed
    least = (readers.launch_bytes(lanes, run.cell["config"]["committee"])
             / readers.peaks(run)["hbm_bytes_s"])
    return 100.0 * least / (seconds / launches)
