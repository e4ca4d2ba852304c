"""Device time per stage of the round, read from the names the program
puts on its compiled instructions.

The program runs each stage of the round and each layer of the RNN-T
under a ``jax.named_scope`` (``core/fedavg.py``, ``models/rnnt.py``).
The names reach every compiled instruction's ``op_name``, wrapped in
the transformations they ran under, e.g.::

    jit(round_step)/client/vmap()/while/body/closed_call/transpose(jvp(encoder))/while/body/mul

A traced run keeps the compiled step's HLO text (``Record.hlo``) and
each device event's instruction name (``Record.trace["devices"]``), so
each event falls in the stage of the innermost known scope on its
instruction's path. ``encoder`` under ``transpose(`` is the encoder's
backward. An event whose instruction has no known scope is
``unscoped``: a program without the scopes reads nothing else.

XLA fuses across scopes (the aggregate's mean lands in the server
step's fusion), so the server's children count as one stage.
"""
from __future__ import annotations

import re

from chipbench import trace

# each scope name the program uses -> the stage it counts to
SCOPES = {
    "client": "client_other",
    "specaugment": "client_other",
    "encoder": "encoder",
    "prednet": "prednet",
    "joint": "joint_loss",
    "rnnt_loss": "joint_loss",
    "server": "server",
    "cohort": "server",
    "uplink": "server",
    "aggregate": "server",
    "server_step": "server",
    "round_metrics": "server",
}
STAGES = ("encoder_fwd", "encoder_bwd", "prednet", "joint_loss", "client_other", "server")
UNSCOPED = "unscoped"

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_WRAPPER = re.compile(r"^([\w.-]*)\((.*)\)$")


def op_scopes(hlo_text: str) -> dict:
    """{instruction name: its ``op_name``} of every instruction of the
    compiled HLO text that carries one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            n = _OP_NAME.search(line)
            if n:
                out[m.group(1)] = n.group(1)
    return out


def stage(op_name: str) -> str:
    """The stage of an instruction from its ``op_name``: the innermost
    known scope on the path, with transformation wrappers (``vmap(``,
    ``jvp(``, ``transpose(``, ``jit(`` ...) taken off; the
    encoder splits into forward and backward by a ``transpose(`` on the
    path up to it."""
    found, backward, transposed = UNSCOPED, False, False
    for part in op_name.split("/"):
        while m := _WRAPPER.match(part):
            transposed |= m.group(1) == "transpose"
            part = m.group(2)
        if part in SCOPES:
            found, backward = SCOPES[part], transposed
    if found == "encoder":
        return "encoder_bwd" if backward else "encoder_fwd"
    return found


def stage_seconds(rec) -> dict | None:
    """{stage: device seconds a round} over the traced rounds: the
    summed durations of the leaf events that start inside the traced
    window, the mean over the devices, over the rounds. Holds only the
    stages some event fell in; ``unscoped`` among them. None without a
    trace."""
    t = rec.trace
    if t is None or not t.get("devices"):
        return None
    names = op_scopes(rec.hlo)
    lo, hi = t["window"]
    total: dict = {}
    for events in t["devices"].values():
        for name, start, dur in trace.leaves(events):
            if lo <= start < hi:
                key = stage(names.get(name, ""))
                total[key] = total.get(key, 0.0) + dur
    per = 1e-9 / (len(t["devices"]) * t["rounds"])
    return {k: v * per for k, v in total.items()}


def stage_ms(rec, *stages: str):
    """Milliseconds a round of device time in ``stages``; None where no
    event fell in any of them (a trace of a program without the scopes)."""
    got = stage_seconds(rec)
    if not got or not any(s in got for s in stages):
        return None
    return 1e3 * sum(got.get(s, 0.0) for s in stages)
