"""``chip_smoke.py`` on CPU: every phase at a tiny size with the Pallas
kernels in interpret mode (so the script cannot rot between chip runs), the
four-chip phases on forced host devices, and the refusal to report success
without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

TINY = dict(n_db=3000, n_queries=16, n_inserts=4, request_rows=2,
            hnsw_n=800, hnsw_m=8, hnsw_ef_construction=40,
            hnsw_ef_search=32)


def test_phases_pass_on_cpu_in_interpret_mode(capsys):
    chip_smoke.run_one_chip(chip_smoke.Report(), chip_smoke.Sizes(**TINY),
                            platform="cpu")
    out = capsys.readouterr().out
    for phase in ("library", "serve", "check vs oracle", "jnp parity",
                  "hnsw", "device"):
        assert f"[smoke] {phase}: pass" in out
    assert "tpu_custom_call" in out


def test_four_chip_phases_on_forced_host_devices():
    code = textwrap.dedent(f"""
        import chip_smoke
        chip_smoke.run_four_chips(chip_smoke.Report(),
                                  chip_smoke.Sizes(**{TINY!r}))
        print("FOUR_OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0 and "FOUR_OK" in r.stdout, r.stderr[-3000:]
    assert "[smoke] mesh search: pass" in r.stdout


def test_main_without_tpu_fails(capsys):
    assert chip_smoke.main([]) != 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last)["ok"] is False


def test_script_alone_fails(tmp_path):
    """Without the repository beside it the script cannot run, and says
    nothing that looks like a result."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=dict(env, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout

