"""joint_kernel_pct: the share, in %, of the joint and RNN-T loss stage's
device self-time (``joint_loss_ms``'s events, chipbench/scopes.py) that
the stage spends in Pallas kernel calls (``tpu_custom_call``
instructions, chipbench/kernels.py), over the traced rounds and the
cell's chips. The rest is the loss's dynamic program and the layout work
around the kernels. Reads 0 where the stage runs no kernel; nothing to
read without a trace or without the stage."""
from chipbench import kernels, scopes, scopes_encdec


def read(rec):
    t = rec.trace
    if t is None or not t.get("devices") or not rec.hlo:
        return None
    names = scopes.op_scopes(rec.hlo)
    calls = kernels.custom_calls(rec.hlo)
    lo, hi = t["window"]
    stage = kernel = 0.0
    for events in t["devices"].values():
        for name, start, own in scopes_encdec.self_times(events):
            if lo <= start < hi and scopes.stage(names.get(name, "")) == "joint_loss":
                stage += own
                if name in calls:
                    kernel += own
    return 100.0 * kernel / stage if stage else None
