"""prednet_ms: device milliseconds a round in the prediction network,
forward and backward (the program's ``prednet`` scope), over the traced
rounds, the mean over the cell's chips (chipbench/scopes.py)."""
from chipbench import scopes


def read(rec):
    return scopes.stage_ms(rec, "prednet")
