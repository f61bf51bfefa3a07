"""chip_smoke.py off the chip: it must refuse to report, and its explicit
dry run must walk every phase at a tiny size with kernels interpreted."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        timeout=600, env=env, cwd=REPO)


def test_no_tpu_is_an_error_with_one_reason_line():
    proc = _run()
    lines = proc.stdout.strip().splitlines()
    assert proc.returncode == 2, proc.stdout[-2000:]
    assert lines[-1].startswith("chip_smoke: no TPU:")
    assert len(lines) == 2          # the environment line and the reason
    assert not any(line.startswith("{") for line in lines)


@pytest.mark.slow
def test_dry_run_walks_every_phase_and_prints_no_result():
    proc = _run("--dry-run", devices=8)
    lines = proc.stdout.strip().splitlines()
    assert proc.returncode == 0, proc.stdout[-3000:]
    assert all(line.startswith("DRY RUN (cpu") for line in lines)
    assert not any('"ok"' in line for line in lines)
    for phase in ("[check flash vs jnp]", "[train]", "[serve bf16]",
                  "[serve int8-kv]", "[serve tp=4]",
                  "[train (dp,fsdp,tp)=(1,2,2)]"):
        assert any(phase in line for line in lines), phase
    assert "smoke_tokens_per_s=not printed" in proc.stdout
