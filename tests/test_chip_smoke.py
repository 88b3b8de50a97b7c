"""chip_smoke.py off the chip: its phases at a tiny size, its refusal of the
CPU, and the compile-cache helper it shares with the benchmarks.

The phases are the chip run's own code, called in-process with Pallas in
interpret mode; only the script's device check (which these tests bypass
by calling the phases directly) keeps it from running on the CPU.
"""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

from conftest import REPO, SRC


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


TINY = dict(pamap=(4, 64), msd=(4, 64), fleet=(2, 48), items=(4, 256),
            leverage=(3, 64), window=(29, 16), queries=32)


def test_one_chip_phases_hold_their_eps_bounds_on_cpu(capsys):
    cs = _chip_smoke()
    reports = cs.run_phases(jax.devices(), sizes=cs.Sizes(**TINY))
    assert [r["phase"] for r in reports] == ["paper_streams", "fleet"]
    fleet = reports[1]["err_over_bound"]
    # every kind the fleet serves was checked against its exact reference
    for check in ("mat-00", "mat-31", "window", "hh", "quantile_rank",
                  "quantile_phi", "leverage_subspace"):
        assert 0.0 <= fleet[check] <= 1.0
    for r in reports:
        assert r["rows"] > 0 and r["wall_s_setup"] > 0
        assert set(r["tpu_custom_call"]) >= {"query", "publish"}
        for gaps in r["pallas_vs_xla_rel_gap"].values():
            assert gaps["norm"] <= 1e-5  # interpret mode: f32 on both sides
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[0])["cut"]["queries"] == [[1024], [32]]
    assert [json.loads(line)["phase"] for line in lines[1:]] == ["paper_streams", "fleet"]


def test_script_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache

    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])
    compilation_cache.reset_cache()


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir_is_fixed(env_dir, monkeypatch, tmp_path, restore_cache_config):
    from repro.compile_cache import enable_compile_cache

    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    assert str(enable_compile_cache()) == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
