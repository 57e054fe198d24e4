"""HLO collective parser + roofline model."""

from repro.roofline import Roofline, collective_bytes
from repro.roofline.hlo import _shape_bytes


SNIPPET = """
HloModule m
ENTRY %main {
  %p0 = f32[128,256]{1,0} parameter(0)
  %p1 = bf16[64]{0} parameter(1)
  %ag = f32[1024,256]{1,0} all-gather(%p0), dimensions={0}
  %ar = bf16[64]{0} all-reduce(%p1), to_apply=%add
  %rs = f32[16,256]{1,0} reduce-scatter(%ag), dimensions={0}
  %cp = bf16[64]{0} collective-permute(%ar), source_target_pairs={{0,1}}
}
"""


def test_shape_bytes():
    assert _shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert _shape_bytes("bf16[64]") == 128
    assert _shape_bytes("(f32[2,2], s32[3])") == 16 + 12
    assert _shape_bytes("pred[]") == 1


def test_collective_parse_snippet():
    out = collective_bytes(SNIPPET)
    assert out["counts"] == {
        "all-gather": 1, "all-reduce": 1, "reduce-scatter": 1,
        "collective-permute": 1,
    }
    assert out["bytes"]["all-gather"] == 128 * 256 * 4   # operand %p0
    assert out["bytes"]["all-reduce"] == 128             # %p1 bf16[64]
    assert out["bytes"]["reduce-scatter"] == 1024 * 256 * 4
    assert out["bytes"]["collective-permute"] == 128


def test_collective_parse_real_module():
    """Cross-check against a real compiled psum: one all-reduce of a
    known payload size."""
    import subprocess
    import sys
    import os

    child = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
mesh = jax.make_mesh((4,), ("d",))
def f(x):
    return jax.shard_map(lambda y: jax.lax.psum(y, "d"), mesh=mesh,
                     in_specs=P("d"), out_specs=P())(x)
xs = jax.ShapeDtypeStruct((4096,), jnp.float32)
with mesh:
    comp = jax.jit(f, in_shardings=NamedSharding(mesh, P("d"))).lower(xs).compile()
from repro.roofline import collective_bytes
out = collective_bytes(comp.as_text())
assert out["counts"].get("all-reduce", 0) >= 1, out
assert out["bytes"]["all-reduce"] == 1024 * 4, out   # per-device shard
print("PARSE-OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", child], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "PARSE-OK" in r.stdout


def test_roofline_terms():
    r = Roofline(
        arch="a", cell="c", mesh="m", chips=256,
        hlo_flops=197e12,       # exactly 1s of compute
        hlo_bytes=819e9 * 2,    # 2s of HBM
        coll_bytes=50e9 * 0.5,  # 0.5s of ICI
        model_flops=197e12 * 256 * 0.5,
    )
    assert abs(r.t_compute - 1.0) < 1e-9
    assert abs(r.t_memory - 2.0) < 1e-9
    assert abs(r.t_collective - 0.5) < 1e-9
    assert r.dominant == "memory"
    assert abs(r.useful_ratio - 0.5) < 1e-9
    # fraction = useful / (chips * peak * t_bound) = 0.25
    assert abs(r.roofline_fraction - 0.25) < 1e-9


def test_probe_correction():
    from repro.roofline import from_record

    rec = {
        "arch": "a", "cell": "c", "mesh": "m", "chips": 2,
        "cost": {"flops": 999.0, "bytes accessed": 999.0},
        "collectives": {"total_bytes": 999},
        "model_flops": 100.0,
        "probes": {
            "n_layers": 10,
            "L1": {"flops": 30.0, "bytes": 20.0, "collective_bytes": 4},
            "L2": {"flops": 40.0, "bytes": 25.0, "collective_bytes": 6},
        },
    }
    r = from_record(rec)
    assert r.hlo_flops == 30 + 9 * 10     # f1 + (L-1) * (f2-f1)
    assert r.hlo_bytes == 20 + 9 * 5
    assert r.coll_bytes == 4 + 9 * 2
