"""1 - device busy time / traced span, from the profiler trace. On a cell
of several chips the busy time is the mean over the cell's chips
(`trace_reduce`): the idle share of the average chip."""


def read(obs):
    t = obs.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
