"""Multi-device dry-run machinery test (8 fake host devices, reduced
configs — the production 512-device sweep runs via launch/dryrun.py).

Runs in a SUBPROCESS because the XLA device count locks at first jax
init and the rest of the suite needs 1 device."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.configs import registry
    from repro.launch import steps as steps_lib
    from repro.roofline.hlo_cost import analyze_hlo

    registry.SHAPES.update({
        "train_4k": {"seq": 64, "batch": 8, "step": "train"},
        "decode_32k": {"seq": 128, "batch": 8, "step": "decode"},
    })
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                ("pod", "data", "model"))
    checks = [("minitron-4b", "train_4k"),
              ("deepseek-v2-236b", "train_4k"),
              ("gemma3-4b", "decode_32k"),
              ("mamba2-370m", "train_4k")]
    for arch, shape in checks:
        e = registry.get(arch)
        plan = steps_lib.CellPlan(microbatch=2 if shape == "train_4k"
                                  else 1)
        built = steps_lib.build_cell(e, shape, mesh, plan=plan,
                                     cfg_override=e.smoke())
        with mesh:
            c = jax.jit(built["fn"], in_shardings=built["in_shardings"],
                        out_shardings=built["out_shardings"],
                        donate_argnums=built["donate"] or ()
                        ).lower(*built["args"]).compile()
        la = analyze_hlo(c.as_text())
        assert la["flops"] > 0, arch
        assert c.memory_analysis().temp_size_in_bytes >= 0
        print(f"OK {arch} {shape} flops={la['flops']:.2e} "
              f"coll={la['collective_total']:.2e}")
    print("ALL_OK")
""")

FL_ROUND_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.configs import registry
    import repro.configs.minitron_4b as m
    from repro.launch.fl_round import build_fl_round
    from repro.roofline.hlo_cost import analyze_hlo
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                ("pod", "data", "model"))
    entry = registry.ArchEntry("minitron-4b", "lm", m.smoke, m.smoke,
                               False)
    totals = {}
    for bits in (None, 8, 2):
        built = build_fl_round(entry, mesh, clients_per_pod=2, bits=bits)
        with mesh:
            c = jax.jit(built["fn"], in_shardings=built["in_shardings"]
                        ).lower(*built["args"]).compile()
        totals[bits] = analyze_hlo(c.as_text())["collective_total"]
    # quantized cross-pod exchange must beat fp32, and int2 beat int8
    assert totals[8] < totals[None], totals
    assert totals[2] < totals[8], totals
    print("ALL_OK", totals)
""")


@pytest.mark.slow
def test_dryrun_cells_small_mesh():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert "ALL_OK" in out.stdout, out.stdout[-2000:] + out.stderr[-2000:]


@pytest.mark.slow
def test_fl_round_multi_pod_compression():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", FL_ROUND_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert "ALL_OK" in out.stdout, out.stdout[-2000:] + out.stderr[-2000:]
