"""enc_attention_ms: device milliseconds a round in the encoder's
self-attention, scores, softmax and values (the program's
``enc_attention`` scope, forward, recompute and backward), over the
traced rounds, the mean over the cell's chips (chipbench/scopes_encdec.py)."""
from chipbench import scopes_encdec


def read(rec):
    return scopes_encdec.stage_ms(rec, "enc_attention")
