"""enc_dense_ms: device milliseconds a round in the encoder outside its
attention: the log-mel front end, the projections, norms and MLPs (the
program's ``encoder`` and ``frontend`` scopes outside ``enc_attention``,
forward, recompute and backward), over the traced rounds, the mean over
the cell's chips (chipbench/scopes_encdec.py)."""
from chipbench import scopes_encdec


def read(rec):
    return scopes_encdec.stage_ms(rec, "enc_dense")
