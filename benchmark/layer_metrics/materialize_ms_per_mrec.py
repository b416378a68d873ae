"""Host time of a slice's split-back: the flow phase `materialize`, summed
over the slice flows that ended in the window, per million input records.
Since ISSUE 31 the phase is the join of the chunks' split-back thunks,
which ran on the fetch worker since `finish`, and the run of the last
chunk's, after the next slice's dispatch; since ISSUE 34 an int-output
split-back renders no decimal (mask to source rows, the delta decode, the
offset gather). Flows with phases and none of them `materialize` (the
order before ISSUE 31, where `finish` held the split-back) read 0."""

from spubench.xplane_scopes import flow_phase_ms_per_mrec


def read(obs):
    return flow_phase_ms_per_mrec(obs, ("materialize",))
