"""Device time of the unknown-signer kernel per signature slot, all of it
read over the traced window: the device seconds of one verify_blob launch
in the trace over the lanes of one launch of that kernel as the service
counted them between the profiler's arming and the traced window's end
(KERNEL_DISPATCHES, kernel "blob").  A lane is one signature's slot.  The
service does not count the signatures of a launch by kernel, so padding
lanes are paid for AND counted here: this is the kernel's speed, and how
full its launches are is sigs_per_dispatch.transfers.  Block signatures
ride the indexed kernel and are not in it."""
from benchmark import transfer_readers


def read(run):
    seconds = transfer_readers.blob_launch_seconds(run)
    lanes = transfer_readers.blob_lanes(run)
    if not seconds or not lanes:
        return None
    return 1e6 * seconds / lanes
