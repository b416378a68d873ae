"""The in-process consumer's own work on the event loop it shares with
the SPU: the phases `consume_decode` (payload to the decoded stream
response) and `consume_ack` (the offset ack's encode and write) of the
consumer's records that ended in the window, per million input records.
None where the program keeps no such records."""

from spubench.consume_spans import consume_phase_ms_per_mrec


def read(obs):
    return consume_phase_ms_per_mrec(obs, ("consume_decode", "consume_ack"))
