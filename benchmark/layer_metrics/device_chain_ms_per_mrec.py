"""Device time of the chain itself: busy time under the scopes
`stage<i>.<kind>` and `compact`, scaled as `device_busy_ms_per_mrec` is."""

from spubench.xplane_scopes import CHAIN_SCOPES, device_scope_ms_per_mrec


def read(obs):
    return device_scope_ms_per_mrec(obs, CHAIN_SCOPES)
