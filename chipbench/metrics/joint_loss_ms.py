"""joint_loss_ms: device milliseconds a round in the joint network and
the RNN-T loss, forward and backward (the program's ``joint`` and
``rnnt_loss`` scopes), over the traced rounds, the mean over the cell's
chips (chipbench/scopes.py)."""
from chipbench import scopes


def read(rec):
    return scopes.stage_ms(rec, "joint_loss")
