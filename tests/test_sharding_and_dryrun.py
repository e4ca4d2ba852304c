"""Sharding-rule engine + a miniature dry-run (8 fake devices in a
subprocess, since XLA device count locks at first jax init)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch.hlo_cost import analyze, shape_bytes
from repro.launch.sharding import fsdpify, make_param_specs, sanitize_specs


class FakeMesh:
    axis_names = ("data", "model")

    class _D:
        shape = (4, 2)

    devices = _D()


def test_make_param_specs_first_match_wins():
    params = {"layers": {"attn": {"wq": np.zeros((2, 4, 8))}},
              "embed": np.zeros((16, 8))}
    rules = [(r"attn/wq$", P(None, None, "model")), (r"embed$", P("model", None))]
    specs = make_param_specs(params, rules)
    assert specs["layers"]["attn"]["wq"] == P(None, None, "model")
    assert specs["embed"] == P("model", None)


def test_sanitize_drops_nondivisible():
    params = {"w": np.zeros((6, 7))}
    specs = {"w": P("data", "model")}            # 6%4 != 0, 7%2 != 0
    out = sanitize_specs(params, specs, FakeMesh())
    assert out["w"] == P(None, None)
    params2 = {"w": np.zeros((8, 6))}
    out2 = sanitize_specs(params2, {"w": P("data", "model")}, FakeMesh())
    assert out2["w"] == P("data", "model")


def test_sanitize_strips_unknown_axes():
    params = {"w": np.zeros((8, 6))}
    out = sanitize_specs(params, {"w": P(("pod", "data"), None)}, FakeMesh())
    assert out["w"] == P("data", None)


def test_fsdpify_last_free_divisible_dim():
    params = {"big": np.zeros((36, 1024, 512)), "small": np.zeros((4,))}
    specs = {"big": P(None, None, "model"), "small": P()}
    out = fsdpify(params, specs, FakeMesh(), fsdp_axes=("data",), min_size=1024)
    assert out["big"] == P(None, "data", "model")
    assert tuple(out["small"]) in ((), (None,))


def test_hlo_cost_scan_scaling():
    import jax.numpy as jnp

    def f_scan(w, x):
        def body(c, _):
            return jnp.tanh(c @ w), None
        return jax.lax.scan(body, x, None, length=10)[0]

    s = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    r = analyze(jax.jit(f_scan).lower(s, s).compile().as_text())
    expected = 10 * 2 * 128**3
    assert abs(r["flops"] - expected) / expected < 0.05


def test_shape_bytes():
    assert shape_bytes("f32[4,8]{1,0}") == 128
    assert shape_bytes("bf16[10]") == 20
    assert shape_bytes("(f32[2,2], s32[3])") == 28
    assert shape_bytes("pred[]") == 1


DRYRUN_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding

    from repro.configs import get_arch
    from repro.core import FederatedPlan, init_server_state, make_round_step
    from repro.core.fedavg import server_state_specs
    from repro.launch.sharding import make_param_specs, sanitize_specs, named
    from repro.models import build_model

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    arch = get_arch("qwen3-8b")
    cfg = arch.make_smoke_config()
    bundle = build_model(cfg)
    plan = FederatedPlan(clients_per_round=4, local_batch_size=2, engine=arch.engine)
    params = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    pspecs = sanitize_specs(params, make_param_specs(params, arch.param_rules), mesh)
    state = jax.eval_shape(lambda p: init_server_state(plan, p), params)
    sspecs = server_state_specs(plan, pspecs)
    batch = {
        "tokens": jax.ShapeDtypeStruct((4, 1, 2, 32), jnp.int32),
        "weight": jax.ShapeDtypeStruct((4, 1, 2), jnp.float32),
    }
    bspecs = jax.tree.map(lambda _: P("data"), batch)
    step = make_round_step(bundle.loss_fn, plan, jax.random.PRNGKey(1))
    fn = jax.jit(step, in_shardings=(named(mesh, sspecs), named(mesh, bspecs)),
                 out_shardings=(named(mesh, sspecs), None))
    compiled = fn.lower(state, batch).compile()
    ma = compiled.memory_analysis()
    print(json.dumps({"ok": True, "temp": ma.temp_size_in_bytes}))
""")


def test_mini_dryrun_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", DRYRUN_SNIPPET], env=env,
                         capture_output=True, text=True, timeout=420,
                         cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"]


def test_hlo_cost_in_place_update_charged_at_slice_size():
    """Scan carries update one slice per step; the byte model must
    charge the slice, not the whole stacked buffer (cost model v2)."""
    import jax.numpy as jnp

    def f(x):
        def body(c, _):
            return jnp.tanh(c), c
        _, ys = jax.lax.scan(body, x, None, length=64)
        return ys

    s = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    r = analyze(jax.jit(f).lower(s).compile().as_text())
    # v1 charged ~64 x full (64,128,128) buffer ~ 268 MB; v2 charges
    # ~64 x (slice io + tanh io) ~ 64 x ~0.26 MB
    assert r["bytes"] < 5e7, r["bytes"]
