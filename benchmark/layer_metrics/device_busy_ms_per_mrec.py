"""Device time per million input records: the device's busy share of the
traced span (union of device-operation intervals over the span between
the trace's two markers) times the window's seconds per million input
records. The traced span is the second half of the window. On a cell of
several chips the busy time is the mean over its chips (`trace_reduce`),
so this is the average chip's time per million records of them all."""


def read(obs):
    t = obs.get("trace")
    if not t or t["window_s"] <= 0 or not obs["records_in"]:
        return None
    busy_share = t["busy_s"] / t["window_s"]
    return busy_share * obs["window_s"] * 1e3 / (obs["records_in"] / 1e6)
