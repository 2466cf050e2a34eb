"""The unknown-signer kernel's share of the memory roofline: bytes a launch
must move (transfer_readers.blob_launch_bytes, at the mean lanes of that
kernel's launches in the traced window) over the chip's published HBM
bandwidth, over the launch's device time in the same trace.  As
kernel_hbm_share: the ladder is int32 VPU arithmetic with no published
peak, so this bytes bound is the only roofline stated."""
from benchmark import readers, transfer_readers


def read(run):
    seconds = transfer_readers.blob_launch_seconds(run)
    lanes = transfer_readers.blob_lanes(run)
    if not seconds or not lanes:
        return None
    least = (transfer_readers.blob_launch_bytes(lanes)
             / readers.peaks(run)["hbm_bytes_s"])
    return 100.0 * least / seconds
