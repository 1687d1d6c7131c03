"""HLO-text analysis: trip-count recovery, FLOP counting, collectives."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import analyze_hlo, split_computations


class TestTripScaledFlops:
    def test_scanned_matmul_flops_exact(self):
        """A matmul scanned N times must count N× the dot FLOPs — the
        exact undercount cost_analysis() exhibits."""
        n_steps, m = 24, 64

        def f(x, w):
            def body(h, _):
                return jnp.tanh(h @ w), None
            h, _ = jax.lax.scan(body, x, None, length=n_steps)
            return h

        x = jnp.ones((m, m))
        w = jnp.ones((m, m))
        compiled = jax.jit(f).lower(x, w).compile()
        stats = analyze_hlo(compiled.as_text())
        analytic = 2.0 * m * m * m * n_steps
        assert stats.dot_flops == pytest.approx(analytic, rel=0.01)

    def test_unscanned_matmul(self):
        m = 32
        f = lambda a, b: a @ b
        compiled = jax.jit(f).lower(jnp.ones((m, m)), jnp.ones((m, m))).compile()
        stats = analyze_hlo(compiled.as_text())
        assert stats.dot_flops == pytest.approx(2.0 * m ** 3, rel=0.01)

    def test_nested_scans_multiply(self):
        inner, outer, m = 4, 6, 16

        def f(x, w):
            def outer_body(h, _):
                def inner_body(hh, _):
                    return hh @ w, None
                h2, _ = jax.lax.scan(inner_body, h, None, length=inner)
                return h2, None
            h, _ = jax.lax.scan(outer_body, x, None, length=outer)
            return h

        compiled = jax.jit(f).lower(jnp.ones((m, m)), jnp.ones((m, m))).compile()
        stats = analyze_hlo(compiled.as_text())
        analytic = 2.0 * m ** 3 * inner * outer
        assert stats.dot_flops == pytest.approx(analytic, rel=0.05)


class TestCollectives:
    def test_psum_counted(self, subproc):
        code = """
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_host_mesh
from repro.launch.hlo_analysis import analyze_hlo

mesh = make_host_mesh((4,), ("data",))
f = shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
              in_specs=P("data"), out_specs=P())
x = jnp.ones((16, 256), jnp.float32)
compiled = jax.jit(f).lower(x).compile()
stats = analyze_hlo(compiled.as_text())
# per-device operand: (4, 256) f32 = 4096 B; ring all-reduce ≈ 2× size
assert stats.collective_counts.get("all-reduce", 0) >= 1, stats.summary()
assert abs(stats.collective_bytes["all-reduce"] - 2 * 4 * 256 * 4) < 1e-6, \\
    stats.summary()
print("OK")
"""
        r = subproc(code, devices=4)
        assert r.returncode == 0, r.stderr[-2000:]
        assert "OK" in r.stdout

    def test_all_gather_counted(self, subproc):
        code = """
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_host_mesh
from repro.launch.hlo_analysis import analyze_hlo

mesh = make_host_mesh((4,), ("data",))
x = jnp.ones((16, 64), jnp.float32)
xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
f = jax.jit(lambda v: v * 2.0, in_shardings=(NamedSharding(mesh, P("data", None)),),
            out_shardings=NamedSharding(mesh, P()))
compiled = f.lower(xs).compile()
stats = analyze_hlo(compiled.as_text())
assert stats.collective_counts.get("all-gather", 0) >= 1, stats.summary()
print("OK")
"""
        r = subproc(code, devices=4)
        assert r.returncode == 0, r.stderr[-2000:]
        assert "OK" in r.stdout


class TestMemoryProxy:
    def test_dot_traffic(self):
        m = 128
        compiled = jax.jit(lambda a, b: a @ b).lower(
            jnp.ones((m, m), jnp.float32), jnp.ones((m, m), jnp.float32)
        ).compile()
        stats = analyze_hlo(compiled.as_text())
        # ≥ operands + result of the dot; ≤ a few× (copies/layout)
        lo = 3 * m * m * 4
        assert lo <= stats.memory_bytes <= 4 * lo

    def test_in_place_cache_update_not_overcharged(self):
        """dynamic-update-slice into a big buffer must charge ~the update
        size, not the buffer size."""
        big = jnp.zeros((4096, 128), jnp.float32)     # 2 MiB
        upd = jnp.ones((1, 128), jnp.float32)         # 512 B

        def f(b, u):
            return jax.lax.dynamic_update_slice(b, u, (17, 0))

        compiled = jax.jit(f, donate_argnums=(0,)).lower(big, upd).compile()
        stats = analyze_hlo(compiled.as_text())
        assert stats.memory_bytes < 64 * 1024, stats.memory_bytes


class TestTrafficAttribution:
    def test_by_shape_sums_to_total(self):
        m = 64

        def f(a, b, c):
            return (a @ b) @ c

        compiled = jax.jit(f).lower(
            jnp.ones((m, m)), jnp.ones((m, m)), jnp.ones((m, m))
        ).compile()
        stats = analyze_hlo(compiled.as_text())
        assert stats.memory_bytes > 0
        assert sum(stats.traffic_by_shape.values()) == pytest.approx(
            stats.memory_bytes
        )

    def test_collective_by_shape_sums(self, subproc):
        code = """
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_host_mesh
from repro.launch.hlo_analysis import analyze_hlo
mesh = make_host_mesh((4,), ("data",))
f = shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
              in_specs=P("data"), out_specs=P())
compiled = jax.jit(f).lower(jnp.ones((16, 64))).compile()
s = analyze_hlo(compiled.as_text())
assert abs(sum(s.collective_by_shape.values())
           - sum(s.collective_bytes.values())) < 1e-6
print("OK")
"""
        r = subproc(code, devices=4)
        assert r.returncode == 0, r.stderr[-1500:]
        assert "OK" in r.stdout


class TestKernelSubstitution:
    def test_attention_internals_identified(self):
        """The roofline substitution must remove score/carry shapes but
        keep activation-shaped traffic."""
        from benchmarks.roofline import kernel_substituted_memory

        rec = {
            "ok": True, "skipped": False,
            "arch": "llama3.2-1b", "shape": "train_4k",
            "chips": 256, "mesh_shape": [16, 16],
            "memory_s": 10.0,
            "traffic_by_shape": {
                "f32[512,512]": 819e9 * 4.0,     # score tiles → removed
                "f32[512,64]": 819e9 * 2.0,      # carries → removed
                "f32[4096,2048]": 819e9 * 3.0,   # (S, D) activations → kept
            },
        }
        adj = kernel_substituted_memory(rec)
        assert adj is not None
        assert adj["removed_s"] == pytest.approx(6.0)
        # memory falls by removed minus the (small) analytic kernel bytes
        assert 3.0 <= adj["memory_s_pallas"] <= 4.6

    def test_no_attention_no_substitution(self):
        from benchmarks.roofline import kernel_substituted_memory

        rec = {
            "ok": True, "skipped": False,
            "arch": "mamba2-1.3b", "shape": "train_4k",
            "chips": 256, "mesh_shape": [16, 16],
            "memory_s": 5.0,
            "traffic_by_shape": {"f32[4096,2048]": 819e9},  # nothing internal
        }
        assert kernel_substituted_memory(rec) is None


class TestParserRobustness:
    def test_split_finds_entry(self):
        compiled = jax.jit(lambda x: x + 1).lower(jnp.ones(8)).compile()
        comps = split_computations(compiled.as_text())
        assert any(n.startswith("main") for n in comps)

    def test_scan_trip_recovered(self):
        def f(x):
            return jax.lax.scan(lambda c, _: (c * 2, None), x, None,
                                length=13)[0]
        compiled = jax.jit(f).lower(jnp.ones(4)).compile()
        stats = analyze_hlo(compiled.as_text())
        assert 13 in stats.loop_trips.values()
