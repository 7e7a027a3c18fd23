"""chip_smoke.py on CPU: its checks pass at --smoke size, and it never
claims success without a TPU.  Every run is a child process with its own
compile cache directory, so the test process's JAX state is untouched."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _run(tmp_path, *args, devices=1, script=SCRIPT, timeout=600):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONPATH")}
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run([sys.executable, str(script), *args],
                          cwd=tmp_path, env=env, capture_output=True,
                          text=True, timeout=timeout)


def _assert_no_ok_line(proc):
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_train_serve_checks_pass_on_cpu(tmp_path):
    proc = _run(tmp_path, "--smoke")
    out = proc.stdout
    for line in ("[check] 5 finite losses: ok",
                 "[check] trainer plane fetch equals graph.features "
                 "exactly: ok",
                 "[check] half-cache plane fetch equals graph.features "
                 "exactly: ok",
                 "[check] 16 queries served: ok",
                 "[check] device-plane predictions equal host-plane "
                 "predictions: ok"):
        assert line in out, out + proc.stderr
    assert "tpu_custom_call=False" in out
    assert "[FAIL] platform is tpu (cpu)" in proc.stderr
    _assert_no_ok_line(proc)


def test_multipartition_checks_pass_on_four_cpu_devices(tmp_path):
    proc = _run(tmp_path, "--smoke", "--chips", "4", devices=4)
    out = proc.stdout
    for line in ("[check] real 4-device mesh: ok",
                 "[check] gradients live on 4 distinct devices: ok",
                 "[check] grad_allreduce mean equals the numpy mean: ok",
                 "[check] no batch re-issued: ok"):
        assert line in out, out + proc.stderr
    assert "halo_all_to_all rows equal graph.features[halo_set]" in out
    _assert_no_ok_line(proc)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_refuses_without_tpu_or_repo(tmp_path, where):
    """Full size on CPU stops at the platform check; a copy of the script
    with no repository beside it stops at the import."""
    script = SCRIPT
    if where == "alone":
        script = tmp_path / "chip_smoke.py"
        shutil.copy(SCRIPT, script)
    proc = _run(tmp_path, script=script, timeout=120)
    _assert_no_ok_line(proc)
    assert "[FAIL]" in proc.stderr
