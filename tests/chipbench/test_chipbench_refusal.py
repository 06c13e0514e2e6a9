"""The harness measures only on a TPU whose peaks it knows, and only from a
checkout of the program."""
import json
import os
import shutil
import subprocess
import types

import pytest

from chipbench import manifest, run

ROOT = manifest.PKG_ROOT


def fake_jax(platform, kind, n=1):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    return types.SimpleNamespace(devices=lambda: [dev] * n)


def test_unknown_device_kind_is_refused():
    with pytest.raises(run.Refused) as e:
        run.check_devices(fake_jax("tpu", "TPU v99"), 1)
    assert e.value.code != 0


def test_known_tpu_gives_its_peaks():
    peaks = run.check_devices(fake_jax("tpu", "TPU v5 lite"), 1)
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert "source" in peaks


@pytest.mark.parametrize("platform,n,chips", [("cpu", 1, 1), ("gpu", 1, 1),
                                              ("tpu", 1, 4)])
def test_no_tpu_or_too_few_chips_is_refused(platform, n, chips):
    with pytest.raises(run.Refused) as e:
        run.check_devices(fake_jax(platform, "TPU v5 lite", n), chips)
    assert e.value.code != 0


def test_main_on_the_cpu_exits_nonzero_and_prints_no_result(capsys):
    # the suite runs with JAX on the CPU: the harness must refuse
    code = run.main(["--workload", "drrl-paper.chat", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert code != 0
    assert out.out == ""
    assert "no TPU" in out.err


def test_benchmark_files_alone_are_refused(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths has
    no program to measure."""
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in doc["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(doc["command"] + ["--workload", "drrl-paper.chat",
                                         "--seed", "1", "--seconds", "1",
                                         "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "no program" in r.stderr
