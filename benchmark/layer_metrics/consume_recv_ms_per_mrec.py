"""The localhost socket's own cost on the shared event loop: the phase
`consume_recv` (a response's payload arriving after its length prefix)
of the consumer's records that ended in the window, net of every phase
that holds a thread on either side (slice phases other than `send` /
`ack_wait`; `consume_decode`, `consume_ack`), per million input
records. None where the program keeps no such records."""

from spubench.consume_spans import consume_recv_net_ms_per_mrec


def read(obs):
    return consume_recv_net_ms_per_mrec(obs)
