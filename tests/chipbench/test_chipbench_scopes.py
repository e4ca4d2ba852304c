"""The reduction from the program's scopes to device time per stage, on
hand-made compiled text and trace events."""
from types import SimpleNamespace

import pytest

from chipbench import harness, scopes

STEP = "jit(round_step)"
CLIENT = STEP + "/client/vmap()/while/body/closed_call"
HLO = "\n".join([
    "HloModule jit_round_step, is_scheduled=true",
    "%body.2 (p.1: (s32[], f32[8,1152,4608])) -> (s32[], f32[8,1152,4608]) {",
    '  %select_add_fusion.9 = f32[8,1152,4608]{2,1,0} fusion(f32[8,1152,4608]{2,1,0} %a.1),'
    ' kind=kLoop, calls=%fused_computation.3, metadata={op_type="add_any" op_name="'
    + CLIENT + '/transpose(jvp(encoder))/while/body/closed_call/add_any"'
    ' source_file="lstm.py" source_line=12}',
    '  %closed_call.321 = (bf16[4,1152]{1,0}, f32[4,1152]{1,0}) custom-call(bf16[4,4608]{1,0}'
    ' %g.1, f32[4,1152]{1,0} %c.2), custom_call_target="tpu_custom_call",'
    ' backend_config={"custom_call_config": {"body": "x"}}, metadata={op_name="'
    + CLIENT + '/jvp(encoder)/while/body/pallas_call"}',
    "}",
    "ENTRY %main.1 (p.0: f32[4]) -> f32[4] {",
    '  %fusion.4 = f32[4]{0} fusion(f32[4]{0} %p.0), kind=kLoop, calls=%fused_computation.1,'
    ' metadata={op_name="' + CLIENT + '/jvp(joint)/dot_general"}',
    '  %while.7 = (s32[], f32[4]) while((s32[], f32[4]) %tuple.2), condition=%cond.1,'
    ' body=%body.2, metadata={op_name="' + STEP + '/client/vmap()/while"}',
    '  %multiply_add_fusion.5 = f32[4]{0} fusion(f32[4]{0} %p.0), kind=kLoop,'
    ' calls=%fused_computation.2, metadata={op_name="' + STEP + '/server/server_step/mul"}',
    "  %copy.6 = f32[4]{0} copy(f32[4]{0} %p.0)",
    "  ROOT %tuple.8 = (f32[4]) tuple(f32[4]{0} %copy.6)",
    "}",
])


def test_op_names_from_fusion_custom_call_and_while_lines():
    names = scopes.op_scopes(HLO)
    assert names["select_add_fusion.9"].endswith("transpose(jvp(encoder))/while/body/closed_call/add_any")
    assert names["closed_call.321"].endswith("/jvp(encoder)/while/body/pallas_call")
    assert names["while.7"] == STEP + "/client/vmap()/while"
    assert names["multiply_add_fusion.5"] == STEP + "/server/server_step/mul"
    assert "copy.6" not in names and "tuple.8" not in names  # no metadata


@pytest.mark.parametrize("op_name, want", [
    (CLIENT + "/jvp(encoder)/while/body/dot_general", "encoder_fwd"),
    (CLIENT + "/transpose(jvp(encoder))/while/body/closed_call/add_any", "encoder_bwd"),
    (STEP + "/client/vmap(transpose(jvp(encoder)))/while/body/mul", "encoder_bwd"),
    (CLIENT + "/jvp(prednet)/while/body/tanh", "prednet"),
    (CLIENT + "/transpose(jvp(joint))/while/body/checkpoint/rematted_computation/exp", "joint_loss"),
    (CLIENT + "/jvp(rnnt_loss)/vmap()/while/body/closed_call/add", "joint_loss"),
    (CLIENT + "/jvp(specaugment)/while/body/closed_call/jit(_randint)/add", "client_other"),
    (CLIENT + "/add", "client_other"),
    (STEP + "/server/aggregate/vmap()/add", "server"),
    (STEP + "/server/cohort/mul", "server"),
    (STEP + "/server/round_metrics/reduce_sum", "server"),
    (STEP + "/jit(_threefry_fold_in)/fvn_key/add", "unscoped"),
    ("reduce_sum", "unscoped"),
    ("", "unscoped"),
])
def test_stage_rules(op_name, want):
    assert scopes.stage(op_name) == want


def test_the_innermost_scope_wins():
    # a model scope inside the client scope is the model's, not the client's
    assert scopes.stage(STEP + "/client/vmap()/jvp(prednet)/add") == "prednet"
    # the server's children all count to the server
    for child in ("cohort", "uplink", "aggregate", "server_step", "round_metrics"):
        assert scopes.stage(f"{STEP}/server/{child}/add") == "server"


# two devices; times in ns; two traced rounds in the window [100, 300)
EVENTS = {
    "/device:TPU:0": [
        ["while.7", 100, 80],                 # holds the loop body's events: not a leaf
        ["closed_call.321", 100, 20],
        ["select_add_fusion.9", 120, 60],
        ["fusion.4", 200, 10],
        ["multiply_add_fusion.5", 220, 30],
        ["copy.6", 260, 10],                  # no scope
        ["fusion.4", 300, 50],                # starts after the window
    ],
    "/device:TPU:1": [
        ["closed_call.321", 100, 40],
        ["select_add_fusion.9", 150, 100],
        ["multiply_add_fusion.5", 260, 10],
        ["fusion.4", 50, 30],                 # starts before the window
    ],
}


def _record(hlo=HLO, events=EVENTS):
    return SimpleNamespace(hlo=hlo, trace={"devices": events, "window": (100.0, 300.0),
                                           "rounds": 2, "stats": {}})


def test_stage_seconds_is_the_mean_over_devices_a_round():
    got = scopes.stage_seconds(_record())
    per = 1e-9 / (2 * 2)
    assert got == pytest.approx({
        "encoder_fwd": (20 + 40) * per,
        "encoder_bwd": (60 + 100) * per,
        "joint_loss": 10 * per,
        "server": (30 + 10) * per,
        "unscoped": 10 * per,
    })


def test_stages_close_on_the_busy_time():
    """Where the leaf events do not overlap, the stages and ``unscoped``
    add up to each device's busy time in the window, over the rounds."""
    from chipbench import trace

    rec = _record()
    lo, hi = rec.trace["window"]
    busy = [trace.busy_ns([e for e in ev if lo <= e[1] < hi], lo, hi)
            for ev in rec.trace["devices"].values()]
    total = sum(scopes.stage_seconds(rec).values())
    assert total == pytest.approx(sum(busy) / 2 / 2 * 1e-9)


def test_readers_read_their_stage():
    rec = _record()
    per_ms = 1e3 * 1e-9 / 4
    want = {"encoder_fwd_ms": 60 * per_ms, "encoder_bwd_ms": 160 * per_ms,
            "joint_loss_ms": 10 * per_ms, "server_ms": 40 * per_ms,
            "prednet_ms": None, "client_other_ms": None}
    for name, value in want.items():
        got = harness.load_reader(name)(rec)
        assert got == (pytest.approx(value) if value is not None else None), name


def test_a_program_without_scopes_reads_nothing():
    bare = "\n".join(line.split(", metadata=")[0] for line in HLO.splitlines())
    rec = _record(hlo=bare)
    assert set(scopes.stage_seconds(rec)) == {"unscoped"}
    for name in ("encoder_fwd_ms", "encoder_bwd_ms", "prednet_ms", "joint_loss_ms",
                 "client_other_ms", "server_ms"):
        assert harness.load_reader(name)(rec) is None
        assert harness.load_reader(name)(SimpleNamespace(hlo="", trace=None)) is None
