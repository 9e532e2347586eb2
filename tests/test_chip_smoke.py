"""chip_smoke.py off the chip: the dry run passes, the real run refuses, and
the compile cache goes where it is told."""

import json
import os
import subprocess
import sys

from mmlspark_tpu.core import device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # the script picks its own device count
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    return subprocess.run(
        [sys.executable, SMOKE, *args], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=600,
    )


def test_dry_run_cpu_passes_and_says_so(tmp_path):
    proc = _run(["--dry-run-cpu"], tmp_path)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is True
    assert last["dry_run"] is True and last["platform"] == "cpu"
    assert last["device"]["platform"] == "cpu"
    phases = [json.loads(l)["phase"] for l in lines if l.startswith('{"phase"')]
    assert phases == [
        "1_fit", "2_predict", "3_chunked_u", "4_serve", "5_deep",
        "6_kernels", "7_four_chips",
    ]
    # the cache was placed from outside: the script reports that directory
    placed = json.loads(lines[1])["compile_cache_dir"]
    assert placed == str(tmp_path / "jax_cache")


def test_without_a_chip_it_refuses_and_names_the_platform(tmp_path):
    proc = _run([], tmp_path)
    assert proc.returncode != 0
    assert "platform='cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch, tmp_path):
    updates = []
    monkeypatch.setattr(
        device.jax.config, "update", lambda *a: updates.append(a)
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert device.configure_compile_cache() == str(tmp_path)
    assert updates == []


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    updates = []
    monkeypatch.setattr(
        device.jax.config, "update", lambda *a: updates.append(a)
    )
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert device.configure_compile_cache() == want
    assert device.configure_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)] * 2
