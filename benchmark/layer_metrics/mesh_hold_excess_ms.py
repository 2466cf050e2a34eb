"""What the host adds to a mesh frame beyond its link's delay: the growth
over the window of block_stage_seconds{stage="mesh_hold"} (handed to the
connection -> written, one sample a frame) less frames x configured delay
by peer (mesh_delayed_frames_total, mesh_link_delay_seconds), over the
frames; median over nodes."""
from benchmark import wan_readers


def read(run):
    return wan_readers.mesh_hold_excess_ms(run)
