"""vocab_loss_ms: device milliseconds a round in the tied output head and
the chunked cross-entropy over the vocabulary (the program's ``lm_loss``
scope, forward and backward), over the traced rounds, the mean over the
cell's chips (chipbench/scopes_encdec.py)."""
from chipbench import scopes_encdec


def read(rec):
    return scopes_encdec.stage_ms(rec, "vocab_loss")
