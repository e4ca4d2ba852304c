"""Device time per stage of an encoder-decoder round, read from the names
the program puts on its compiled instructions: ``scopes.py``'s rule with
the encoder-decoder's table, summed over self times.

The program runs the round's stages (``core/fedavg.py``: ``client``,
``server`` and the server's children) and the model's parts
(``models/encdec.py``: ``encoder`` with ``frontend`` and each layer's
``enc_attention`` inside it, ``decoder``, ``lm_loss``) under
``jax.named_scope``s. Each device event falls in the stage of the
innermost known scope on its instruction's ``op_name``, with the
transformations it ran under (``jvp(``, ``transpose(``, ``vmap(``,
``checkpoint`` ...) taken off: forward, recompute and backward count
together. An event whose instruction has no known scope is
``unscoped``.

Each instant of device time counts once, to the innermost event that
covers it (``self_times``), so the stages add up to the busy time.
``trace.leaves`` would drop every event that another event starts and
ends inside: on the chip the encoder's attention fusions overlap shorter
operations on the same line, and dropping them left 4% of the Whisper
round uncounted.
"""
from __future__ import annotations

import re

from chipbench import scopes

# each scope name the program uses -> the stage it counts to
SCOPES = {
    "client": "client_other",
    "frontend": "enc_dense",
    "encoder": "enc_dense",
    "enc_attention": "enc_attention",
    "decoder": "decoder",
    "lm_loss": "vocab_loss",
    "server": "server",
    "cohort": "server",
    "uplink": "server",
    "aggregate": "server",
    "server_step": "server",
    "round_metrics": "server",
}
STAGES = ("enc_attention", "enc_dense", "decoder", "vocab_loss", "client_other", "server")
UNSCOPED = scopes.UNSCOPED

_WRAPPER = re.compile(r"^([\w.-]*)\((.*)\)$")


def stage(op_name: str) -> str:
    """The stage of the innermost known scope on ``op_name``'s path."""
    found = UNSCOPED
    for part in op_name.split("/"):
        while m := _WRAPPER.match(part):
            part = m.group(2)
        found = SCOPES.get(part, found)
    return found


def self_times(events) -> list:
    """[name, start, self ns] of each event: its duration less the part
    of it that the events starting inside it cover."""
    done, stack = [], []            # stack: [name, start, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= start:
            done.append(stack.pop())
        if stack:
            stack[-1][3] -= min(start + dur, stack[-1][2]) - start
        stack.append([name, start, start + dur, dur])
    return [[name, start, own] for name, start, _, own in done + stack]


def stage_seconds(rec) -> dict | None:
    """{stage: device seconds a round} over the traced rounds: the self
    times of the events that start inside the traced window, the mean
    over the devices, over the rounds. None without a trace."""
    t = rec.trace
    if t is None or not t.get("devices"):
        return None
    names = scopes.op_scopes(rec.hlo)
    lo, hi = t["window"]
    total: dict = {}
    for events in t["devices"].values():
        for name, start, dur in self_times(events):
            if lo <= start < hi:
                key = stage(names.get(name, ""))
                total[key] = total.get(key, 0.0) + dur
    per = 1e-9 / (len(t["devices"]) * t["rounds"])
    return {k: v * per for k, v in total.items()}


def stage_ms(rec, name: str):
    """Milliseconds a round of device time in stage ``name``; None where no
    event fell in it (a trace of a program without the scope)."""
    got = stage_seconds(rec)
    if not got or name not in got:
        return None
    return 1e3 * got[name]
