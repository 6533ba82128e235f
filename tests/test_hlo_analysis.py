"""HLO cost-model tests: known-FLOPs programs, scan trip counting,
collective detection (subprocess with forced multi-device host)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_analysis import analyze_hlo


def _compile_text(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


def test_matmul_flops_exact():
    s = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    s2 = jax.ShapeDtypeStruct((512, 128), jnp.float32)
    hlo = _compile_text(lambda a, b: a @ b, s, s2)
    c = analyze_hlo(hlo)
    assert c.flops == pytest.approx(2 * 256 * 512 * 128, rel=1e-6)


def test_scan_multiplies_flops_by_trip_count():
    s = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def f(a, b):
        def body(x, _):
            return jnp.tanh(x @ b), ()
        out, _ = jax.lax.scan(body, a, None, length=12)
        return out

    hlo = _compile_text(f, s, s)
    c = analyze_hlo(hlo)
    assert c.flops == pytest.approx(12 * 2 * 128**3, rel=0.05)
    assert 12 in c.while_trips.values()


def test_while_bounded_by_a_traced_count_and_a_constant_counts_the_constant():
    """A loop ``i < n & i < 12`` with a traced ``n`` compiles its condition
    to one fusion: its static bound, 12, is read from the fused callee."""
    s = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    n = jax.ShapeDtypeStruct((), jnp.int32)

    def f(a, b, n):
        def body(c):
            i, x = c
            return i + 1, jnp.tanh(x @ b)
        _, out = jax.lax.while_loop(lambda c: (c[0] < n) & (c[0] < 12),
                                    body, (jnp.int32(0), a))
        return out

    c = analyze_hlo(_compile_text(f, s, s, n))
    assert c.flops == pytest.approx(12 * 2 * 128**3, rel=0.05)
    assert 12 in c.while_trips.values()


def test_hbm_counts_matmul_traffic():
    s = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    hlo = _compile_text(lambda a, b: a @ b, s, s)
    c = analyze_hlo(hlo)
    # read a + read b + write out = 3 * 4MB (within 2x for copies)
    assert 0.5 * 12e6 <= c.hbm_bytes <= 2.5 * 12e6


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.hlo_analysis import analyze_hlo

    from repro.models.sharding import make_mesh
    mesh = make_mesh((8,), ("d",))
    sh = NamedSharding(mesh, P("d", None))
    rep = NamedSharding(mesh, P())
    s = jax.ShapeDtypeStruct((1024, 256), jnp.float32)

    # data-parallel grad-like reduction -> all-reduce
    def f(x):
        return jnp.sum(x * x)
    hlo = jax.jit(f, in_shardings=(sh,)).lower(s).compile().as_text()
    c = analyze_hlo(hlo)
    out = {"allreduce_ops": c.collective_counts.get("all-reduce", 0),
           "coll_bytes": c.collective_bytes}
    print(json.dumps(out))
""")


def test_collectives_detected_under_mesh(tmp_path):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SUBPROC], cwd=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), env=env,
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["allreduce_ops"] >= 1
    assert out["coll_bytes"] > 0
