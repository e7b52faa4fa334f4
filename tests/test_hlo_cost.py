"""launch/hlo_cost analyzer validation: loop-aware FLOPs/bytes/collectives
against programs with known analytic costs."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp

from repro.launch.hlo_cost import analyze_hlo, parse_shape_bytes


def test_parse_shape_bytes():
    assert parse_shape_bytes("f32[4,8]") == 128
    assert parse_shape_bytes("bf16[2,3,4]{2,1,0}") == 48
    assert parse_shape_bytes("(f32[10], s32[5])") == 60
    assert parse_shape_bytes("pred[]") == 1  # scalar = one element


def test_matmul_flops_exact():
    m, k, n = 64, 128, 32

    def f(a, b):
        return a @ b

    hlo = (
        jax.jit(f)
        .lower(jnp.zeros((m, k)), jnp.zeros((k, n)))
        .compile()
        .as_text()
    )
    res = analyze_hlo(hlo)
    assert res["flops"] == 2 * m * k * n


def test_scan_multiplies_trip_count():
    L, m = 8, 32

    def f(x, ws):
        def body(x, w):
            return jnp.tanh(x @ w), None

        x, _ = jax.lax.scan(body, x, ws)
        return x

    hlo = (
        jax.jit(f)
        .lower(jnp.zeros((m, m)), jnp.zeros((L, m, m)))
        .compile()
        .as_text()
    )
    res = analyze_hlo(hlo)
    expect = L * 2 * m * m * m
    assert expect * 0.99 <= res["flops"] <= expect * 1.01, res["flops"]
    assert L in res["while_trip_counts"].values()


def test_collectives_counted_with_loop_multiplier():
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.hlo_cost import analyze_hlo

        mesh = jax.make_mesh((4,), ("d",), axis_types=(jax.sharding.AxisType.Auto,))
        L, m = 5, 16

        def f(x, ws):
            def body(x, w):
                y = x @ w
                return jax.lax.with_sharding_constraint(
                    y, NamedSharding(mesh, P(None, None))), None
            x, _ = jax.lax.scan(body, x, ws)
            return x.sum()

        xs = NamedSharding(mesh, P("d", None))
        ws = NamedSharding(mesh, P(None, None, "d"))
        hlo = jax.jit(f, in_shardings=(xs, ws)).lower(
            jax.ShapeDtypeStruct((m, m), jnp.float32),
            jax.ShapeDtypeStruct((L, m, m), jnp.float32),
        ).compile().as_text()
        res = analyze_hlo(hlo)
        # the per-layer resharding forces a collective inside the loop body:
        # counted L times, not once
        assert res["collective_count"] >= L, res
        print("HLO_COST_OK", res["collective_count"])
        """
    )
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
             **({"JAX_PLATFORMS": os.environ["JAX_PLATFORMS"]} if "JAX_PLATFORMS" in os.environ else {})},
    )
    assert "HLO_COST_OK" in proc.stdout, proc.stdout + proc.stderr[-2500:]
