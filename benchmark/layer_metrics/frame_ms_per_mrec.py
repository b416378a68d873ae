"""Host time of a response's frame build: the flow phase `frame` (the
response encoded into one length-prefixed buffer, a copy of its record
slab), summed over the slice flows that ended in the window, per million
input records. It left `send`, so `send_ack_ms_per_mrec` reads without
it. None where no flow has the phase (a program that builds the frame
inside `send`)."""

from spubench.consume_spans import frame_ms_per_mrec


def read(obs):
    return frame_ms_per_mrec(obs)
