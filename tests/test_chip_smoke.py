"""``chip_smoke.py`` at a tiny size on CPU (kernels through the Pallas
interpreter): every phase reaches exact equality with its oracle, and the
script refuses to report a result off the chip or away from the repo."""
from __future__ import annotations

import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import chip_smoke

TINY_HOST = chip_smoke.HostSize(
    reqs_per_vm=240, resize_interval=1200, promo_interval=50, scale=0.05,
    num_sets=8, max_ways=8, total_blocks=192, pop_capacity=256)
TINY_SERVING = chip_smoke.ServingSize(events=300, live=16, tenants=2,
                                      hbm_pages=24, pop_capacity=64)


@pytest.fixture(scope="module")
def tiny_mix():
    return chip_smoke.paper_mix(TINY_HOST, seed=0)


@pytest.mark.parametrize("phase", ["paper_etica", "paper_cleaner",
                                   "paper_eci"])
def test_paper_host_phase_matches_oracle(phase, tiny_mix):
    out = getattr(chip_smoke, phase)(TINY_HOST, tiny_mix)
    assert out["equal"] and out["vms"] == 12 and out["pop_drops"] == 0
    assert out["requests"] == len(tiny_mix)


def test_serving_phase_matches_oracle_and_decode_reference():
    out = chip_smoke.serving(TINY_SERVING, seed=0)
    assert out["equal"] and out["decode_checks"] > 0


def test_consolidation_phase_matches_single_device():
    out = chip_smoke.consolidation(num_vms=16, reqs=40, chips=1, seed=0)
    assert out["equal"] and out["vms"] == 16 and out["chips"] == 1


def test_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""


def test_refuses_outside_the_repo(tmp_path):
    shutil.copy(Path(chip_smoke.__file__), tmp_path / "chip_smoke.py")
    run = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, timeout=120)
    assert run.returncode != 0
    assert run.stdout == ""


def test_compile_cache_goes_to_env_dir_or_repo_dir(monkeypatch):
    import jax

    from repro.launch import compile_cache
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.__setitem__(name, value))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo_dir = str(Path(chip_smoke.__file__).parent / ".jax_cache")
    assert compile_cache.enable_compile_cache() == repo_dir
    assert updates == {"jax_compilation_cache_dir": repo_dir}
    updates.clear()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert updates == {}
