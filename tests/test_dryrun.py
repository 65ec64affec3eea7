"""Dry-run machinery: HLO loop-aware analysis + one real (reduced-size)
multi-device lowering through the exact dryrun code path, in a subprocess."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_hlo_loop_aware_flops_exact():
    import jax, jax.numpy as jnp
    from repro.launch.hlo_analysis import analyse_hlo

    def f(x):
        y, _ = jax.lax.scan(lambda c, _: (c @ c, None), x, None, length=9)
        return y

    txt = jax.jit(f).lower(jnp.ones((64, 64))).compile().as_text()
    r = analyse_hlo(txt)
    assert r["dot_flops"] == 9 * 2 * 64 ** 3


def test_hlo_nested_loops():
    import jax
    import jax.numpy as jnp
    from repro.launch.hlo_analysis import analyse_hlo

    def inner(c):
        y, _ = jax.lax.scan(lambda a, _: (a @ a, None), c, None, length=3)
        return y

    def f(x):
        y, _ = jax.lax.scan(lambda c, _: (inner(c), None), x, None, length=5)
        return y

    txt = jax.jit(f).lower(jnp.ones((32, 32))).compile().as_text()
    r = analyse_hlo(txt)
    assert r["dot_flops"] == 5 * 3 * 2 * 32 ** 3


def test_collective_bytes_parsing():
    from repro.launch.dryrun import collective_bytes
    hlo = """
  %ar = bf16[16,128]{1,0} all-reduce(%x), replica_groups={}
  %ag.1 = f32[4,256]{1,0} all-gather(%y), dimensions={0}
  %done = bf16[16,128]{1,0} all-reduce-done(%ar)
"""
    got = collective_bytes(hlo)
    assert got["all-reduce"] == 16 * 128 * 2
    assert got["all-gather"] == 4 * 256 * 4
    assert got["counts"]["all-reduce"] == 1  # -done not double-counted


def test_dryrun_cell_reduced_subprocess():
    """Exercise lower_cell end-to-end with 16 placeholder devices and a
    shrunken mesh (monkeypatched) — proves the plumbing without the cost of
    a 512-way compile inside the test suite."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    code = """
        import jax
        import repro.launch.mesh as mesh_mod
        def small_mesh(*, multi_pod=False):
            shape = (2, 2, 4) if multi_pod else (4, 4)
            axes = ("pod", "data", "model") if multi_pod else ("data", "model")
            return mesh_mod.make_mesh(shape, axes)
        mesh_mod.make_production_mesh = small_mesh
        import repro.launch.dryrun as dr
        dr.make_production_mesh = small_mesh
        import repro.configs as C
        cfg = C.get_arch("granite-3-2b").reduced().with_(n_layers=4)
        C.ARCHS["tiny-test"] = cfg
        for mp in (False, True):
            res = dr.lower_cell("tiny-test", "train_4k", multi_pod=mp)
            assert res["flops"] > 0, res
            assert res["loop_aware"]["dot_flops"] > res["flops"] * 0.5
        res = dr.lower_cell("tiny-test", "decode_32k")
        assert "error" not in res
        print("DRYRUN_OK")
    """
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "DRYRUN_OK" in out.stdout
