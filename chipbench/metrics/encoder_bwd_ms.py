"""encoder_bwd_ms: device milliseconds a round in the encoder LSTM's
backward (the program's ``encoder`` scope under ``transpose(``), over
the traced rounds, the mean over the cell's chips (chipbench/scopes.py)."""
from chipbench import scopes


def read(rec):
    return scopes.stage_ms(rec, "encoder_bwd")
