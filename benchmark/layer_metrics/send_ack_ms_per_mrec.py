"""Time the serving task spent handing a response to the socket and
waiting for the consumer's ack (flow phases `send` + `ack_wait`; the
consumer's own decode is inside the wait), net of the part of a wait that
ran on under another slice's working phases (the event loop was busy, the
consumer was not slow), per million input records."""

from spubench.xplane_scopes import flow_wait_ms_per_mrec


def read(obs):
    return flow_wait_ms_per_mrec(obs)
