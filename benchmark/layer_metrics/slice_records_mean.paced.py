"""Input records per served slice in the window: how much the broker
coalesces under the cell's rate."""


def read(obs):
    slices = obs["delta"]["fastpath_slices"] + obs["delta"]["fallback_slices"]
    return obs["records_in"] / slices if slices else None
