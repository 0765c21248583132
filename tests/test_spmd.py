"""Real-SPMD integration tests: run checks in a subprocess with 8 fake CPU
devices, or as many as a check asks for (keeps the main pytest process
single-device)."""
import pathlib
import subprocess
import sys

import pytest

SCRIPT = pathlib.Path(__file__).parent / "spmd_scripts" / "run_spmd_checks.py"
SRC = str(pathlib.Path(__file__).parent.parent / "src")


def run_check(name: str, timeout: int = 900, devices: int = 8) -> str:
    env = {"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin:/usr/local/bin",
           "JAX_PLATFORMS": "cpu", "HOME": "/tmp",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    proc = subprocess.run([sys.executable, str(SCRIPT), name],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    assert f"PASS {name}" in proc.stdout, proc.stdout[-3000:]
    return proc.stdout


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    run_check("sharded_train_step_matches_single_device")


@pytest.mark.slow
def test_elastic_reshard_resume():
    run_check("elastic_reshard_resume")


@pytest.mark.slow
def test_compressed_psum_error_bound():
    run_check("compressed_psum")


@pytest.mark.slow
def test_decode_cache_stays_sharded():
    run_check("decode_cache_stays_sharded")


@pytest.mark.slow
def test_gpipe_matches_sequential():
    run_check("gpipe_matches_sequential")


@pytest.mark.slow
def test_shard_group_paged_decode_shard_map():
    run_check("shard_group_paged_decode")


@pytest.mark.slow
def test_chunked_prefill_composes_with_tp2():
    run_check("chunked_prefill_tp2")


@pytest.mark.slow
def test_fleet_replica_placement():
    run_check("fleet_replica_placement", devices=4)
