"""server_ms: device milliseconds a round after the clients (the
program's ``server`` scope and its children: cohort, uplink, aggregate,
server step, round metrics), over the traced rounds, the mean over the
cell's chips (chipbench/scopes.py)."""
from chipbench import scopes


def read(rec):
    return scopes.stage_ms(rec, "server")
