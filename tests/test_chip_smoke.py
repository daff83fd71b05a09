"""chip_smoke.py's phases and checks, run on the CPU at a tiny size.

The script itself refuses to run without a TPU; these tests drive its phase
functions directly, with the Pallas kernels in interpret mode, so a broken
check or phase shows up before any chip time is spent.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

TINY = chip_smoke.SmokeConfig(
    sizes=(6, 5, 4, 3, 2, 2), kmax=3, tenants=2, releases=2, n_records=5000,
    secure_kmax=2, rplus_n=4, rplus_d=5, sharded_records=4000,
    use_kernel=True)


def test_single_chip_phases_pass_at_tiny_size(tmp_path):
    rec = chip_smoke.run_single(TINY, tmp_path)
    assert rec["chain_stats"]["pallas_calls"] > 0
    assert rec["compiled_chains"]["fused"] > 0
    assert (tmp_path / "chip_smoke_ledger.jsonl").exists()


def test_four_device_phase_passes_on_virtual_devices():
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke as cs; "
            "cs.run_four(cs.SmokeConfig(sizes=(6, 5, 4, 3, 2, 2), "
            "sharded_records=4000, use_kernel=True)); print('PASS')"
            % str(ROOT))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "PASS" in out.stdout


@pytest.mark.parametrize("where", ["cpu", "alone"])
def test_script_fails_without_tpu_or_repo(tmp_path, where):
    """No CPU fallback, and no result line, on the CPU or with the script
    copied away from its repository."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        (tmp_path / "chip_smoke.py").write_text(script.read_text())
        script = tmp_path / "chip_smoke.py"
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
