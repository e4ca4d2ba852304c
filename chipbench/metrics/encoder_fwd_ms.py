"""encoder_fwd_ms: device milliseconds a round in the encoder LSTM's
forward (the program's ``encoder`` scope, not under ``transpose(``),
over the traced rounds, the mean over the cell's chips
(chipbench/scopes.py)."""
from chipbench import scopes


def read(rec):
    return scopes.stage_ms(rec, "encoder_fwd")
