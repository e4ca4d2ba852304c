"""decoder_ms: device milliseconds a round in the decoder's blocks,
self-attention, cross-attention on the encoder's output and MLPs (the
program's ``decoder`` scope, forward, recompute and backward), over the
traced rounds, the mean over the cell's chips (chipbench/scopes_encdec.py)."""
from chipbench import scopes_encdec


def read(rec):
    return scopes_encdec.stage_ms(rec, "decoder")
