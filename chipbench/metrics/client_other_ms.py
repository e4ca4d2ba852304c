"""client_other_ms: device milliseconds a round of client work outside
the model's layers (the program's ``client`` scope with no model scope
inside it, and ``specaugment``: SpecAugment, the local SGD updates, the
deltas), over the traced rounds, the mean over the cell's chips
(chipbench/scopes.py)."""
from chipbench import scopes


def read(rec):
    return scopes.stage_ms(rec, "client_other")
