"""What keeps a run from lying about the device: chip_smoke.py refuses the
CPU, the compile cache is placed from outside, an unknown chip is an
error and a smaller mesh than asked for is said out loud."""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_the_cpu(tmp_path):
    # the script rebuilds the native library from its sources before it
    # looks for the chip (`make -C native clean all`, half a minute, and
    # under the feet of the workers that have it loaded): the refusal is
    # what is tested, so `make` is a stub on this run's PATH
    stub = tmp_path / "make"
    stub.write_text("#!/bin/sh\nexit 0\n")
    stub.chmod(0o755)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PATH=f"{tmp_path}{os.pathsep}{os.environ['PATH']}")
    env.pop("FLEXFLOW_TPU_PALLAS", None)
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    for line in r.stdout.splitlines():
        assert not json.loads(line).get("ok")


class TestCompileCachePlacement:
    @pytest.fixture(autouse=True)
    def _restore(self):
        names = ("jax_compilation_cache_dir",
                 "jax_include_full_tracebacks_in_locations")
        prev = {n: getattr(jax.config, n) for n in names}
        yield
        for n, v in prev.items():
            jax.config.update(n, v)

    def test_outside_placement_is_left_alone(self, monkeypatch):
        from flexflow_tpu.utils.compile_cache import configure_compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        before = jax.config.jax_compilation_cache_dir
        assert configure_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before

    def test_fixed_path_otherwise(self, monkeypatch):
        from flexflow_tpu.utils.compile_cache import configure_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert configure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform = platform
        self.device_kind = device_kind


@pytest.mark.parametrize("kind,chip", [
    ("TPU v5 lite", "tpu-v5e"), ("TPU v4", "tpu-v4"),
    ("TPU v9 mega", None),
    ("TPU v5 ultra", None),  # no substring catch-all: "v5" is not v5p
])
def test_detect_machine_spec_knows_the_chip_or_raises(monkeypatch, kind, chip):
    from flexflow_tpu.machine import detect_machine_spec
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice("tpu", kind)])
    if chip is None:
        with pytest.raises(ValueError, match=kind):
            detect_machine_spec()
    else:
        assert detect_machine_spec().chip == chip


def test_cpu_platform_is_the_only_way_to_cpu_sim():
    from flexflow_tpu.machine import detect_machine_spec
    assert jax.devices()[0].platform == "cpu"
    assert detect_machine_spec().chip == "cpu-sim"


def test_compile_warns_when_it_uses_fewer_devices_than_asked():
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    ff = FFModel(FFConfig(batch_size=6, workers_per_node=4))
    t = ff.create_tensor((6, 16))
    ff.dense(t, 4)
    with pytest.warns(RuntimeWarning, match="running on 2 devices"):
        ff.compile(SGDOptimizer(lr=0.1),
                   LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [])
    assert ff.mesh.devices.size == 2
