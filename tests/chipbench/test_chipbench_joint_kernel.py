"""joint_kernel_pct on hand-made compiled text and trace events: the
share of the joint and RNN-T loss stage's device self-time spent in
Pallas kernel calls."""
import pytest

from chipbench import harness

STEP = "jit(round_step)/client/vmap()/while/body/closed_call"
KERNEL = ('  %_joint.3 = (f32[4,4,1000,64]{3,2,1,0}, f32[4,4,1000,64]{3,2,1,0}) custom-call('
          'bf16[4,4,1000,640]{3,2,1,0} %e.1), custom_call_target="tpu_custom_call",'
          ' metadata={op_name="' + STEP + '/jvp(joint)/_joint/pallas_call"}')


def _fusion(name, op_name):
    return (f'  %{name} = f32[4]{{0}} fusion(f32[4]{{0}} %p.0), kind=kLoop,'
            f' metadata={{op_name="{op_name}"}}')


FUSIONS = [
    _fusion("add_reduce_fusion.1", STEP + "/transpose(jvp(rnnt_loss))/while/body/add"),
    _fusion("fusion.2", STEP + "/jvp(encoder)/while/body/closed_call/dot_general"),
    _fusion("fusion.3", STEP + "/jvp(prednet)/tanh"),
]
# the kernel 100 ns, with a 10 ns copy (no scope) running inside it; the
# loss's dynamic program 30 ns; the encoder and the prediction network
# outside the stage
EVENTS = [["_joint.3", 100, 100], ["copy.4", 150, 10], ["add_reduce_fusion.1", 200, 30],
          ["fusion.2", 230, 20], ["fusion.3", 250, 20], ["_joint.3", 400, 50]]


def _record(hlo, events=EVENTS):
    return harness.Record(cell=None, peak={}, chips=1, hlo=hlo,
                          trace={"window": (100, 300), "rounds": 1,
                                 "devices": {"/device:TPU:0": events}})


def test_the_share_of_the_stage_in_kernel_calls():
    read = harness.load_reader("joint_kernel_pct")
    # the kernel's self time is 90 of its 100 ns; the stage's 90 + 30
    assert read(_record("\n".join([KERNEL, *FUSIONS]))) == pytest.approx(100 * 90 / 120)


def test_a_stage_without_kernel_calls_reads_zero():
    read = harness.load_reader("joint_kernel_pct")
    events = [e for e in EVENTS if e[0] != "_joint.3"]
    assert read(_record("\n".join(FUSIONS), events)) == 0.0


@pytest.mark.parametrize("rec", [
    _record("", EVENTS),                                       # no compiled text
    harness.Record(cell=None, peak={}, chips=1, hlo="x"),       # no trace
    _record("\n".join(FUSIONS[1:]), EVENTS[3:5]),               # no event in the stage
])
def test_nothing_to_read(rec):
    assert harness.load_reader("joint_kernel_pct")(rec) is None
