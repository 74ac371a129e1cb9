"""CPU rehearsal of ``chip_smoke.py``: the same serve-and-check path at
reduced width, and the script's refusal to run anywhere but on a TPU."""
import dataclasses
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

import chip_smoke
from repro.configs.registry import get_config, reduced_config
from repro.launch.mesh import use_compile_cache
from repro.models import build_model

ROOT = Path(chip_smoke.__file__).resolve().parent
CFG = reduced_config(get_config(chip_smoke.MODEL))
# the chip's request mix with shorter prompts; the shared prefix is kept
TRAFFIC = chip_smoke.Traffic(prefix_len=256, suffix_len=16,
                             lengths=(64, 128, 256), new_tokens=8)


@pytest.fixture(scope="module")
def result():
    return chip_smoke.run(CFG, TRAFFIC, slots=8, max_len=512)


def test_rehearsal_serves_and_passes_check(result):
    assert chip_smoke.problems(result) == []
    assert result["completed"] == TRAFFIC.n_shared + TRAFFIC.n_distinct
    assert result["prefix_hits"] == TRAFFIC.n_shared - 1
    assert result["cached_tokens"] >= (TRAFFIC.n_shared - 1) * TRAFFIC.prefix_len
    check = result["check"]
    assert check["bad"] == 0
    assert check["tokens"] == result["completed"] * TRAFFIC.new_tokens


def test_check_catches_a_wrong_token(result):
    bundle = build_model(CFG)
    params = bundle.init(jax.random.key(0))
    reqs = [dataclasses.replace(r, generated=list(r.generated))
            for r in result["requests"]]
    wrong = reqs[1]  # a prefix-cache hit
    wrong.generated[3] = (wrong.generated[3] + CFG.vocab_size // 2) \
        % CFG.vocab_size
    check = chip_smoke.check_tokens(bundle, params, reqs)
    assert check["bad"] >= 1
    assert chip_smoke.problems({**result, "check": check})


def test_compile_cache_goes_where_the_env_says(tmp_path):
    cache = tmp_path / "cache"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(cache),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "PYTHONPATH": str(ROOT / "src")}
    code = ("import jax; from repro.launch.mesh import use_compile_cache; "
            "use_compile_cache(); print(jax.config.jax_compilation_cache_dir); "
            "jax.jit(lambda x: x * 2)(1.0).block_until_ready()")
    proc = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [str(cache)]
    assert any(cache.iterdir())


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_refuses_without_a_tpu(where, tmp_path):
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
