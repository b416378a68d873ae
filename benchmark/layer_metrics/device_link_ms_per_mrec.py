"""Device time that exists because bytes cross a link in a packed form:
busy time under the scopes `link_decode`, `repad`, `pack`, `link_encode`,
scaled as `device_busy_ms_per_mrec` is."""

from spubench.xplane_scopes import LINK_SCOPES, device_scope_ms_per_mrec


def read(obs):
    return device_scope_ms_per_mrec(obs, LINK_SCOPES)
