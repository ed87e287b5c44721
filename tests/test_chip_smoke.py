"""chip_smoke.py off the chip: its phases at DLRM_SMOKE on the CPU with
interpret-mode kernels, its refusal to run anywhere but on a TPU, and the
compile-cache placement every entry point shares."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.configs.dlrm import DLRM_SMOKE  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import compile_cache  # noqa: E402


@pytest.fixture
def interpret():
    ops.set_impl("interpret")
    yield
    ops.set_impl("auto")


def _cpu_env(**extra):
    return {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
            "PYTHONPATH": str(ROOT / "src"),
            "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
            "HOME": os.environ.get("HOME", str(ROOT)), **extra}


def test_one_chip_phases_pass_their_checks(interpret, capsys):
    """Serving on ragged and cached agrees with the float32 reference,
    the dense-gradient step (sls_grad_table) agrees with the sparse step,
    and the sparse steps' losses are finite — each check raises if not."""
    chip_smoke.one_chip(DLRM_SMOKE, 64, 0)
    out = capsys.readouterr().out
    errs = [float(t.split("=")[1]) for t in out.split()
            if t.startswith("max_abs_err=")]
    assert len(errs) == 2 and max(errs) <= chip_smoke.CTR_TOL, out
    assert "loss_last=" in out


def test_reference_is_not_the_served_path():
    """The reference answers differently when the params differ — it reads
    the arena it is given, not a cached copy of the served one."""
    import jax.numpy as jnp
    from repro.core import dlrm
    params = dlrm.init(jax.random.PRNGKey(0), DLRM_SMOKE)
    reqs, _, _ = chip_smoke.make_traffic(DLRM_SMOKE, 8, 0)
    a = chip_smoke.reference_ctr(params, DLRM_SMOKE, reqs)
    b = chip_smoke.reference_ctr(dict(params, arena=params["arena"] * 2.0),
                                 DLRM_SMOKE, reqs)
    assert a.shape == (8,) and jnp.all(jnp.isfinite(a))
    assert chip_smoke.max_err(a, b) > 0


def test_main_refuses_a_cpu_backend(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = _cpu_env()
    env.pop("PYTHONPATH")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0 and '"ok"' not in r.stdout


def test_four_chip_phase_on_virtual_devices():
    """The --chips 4 path end to end on four virtual CPU devices: the
    arena split four ways, sharded serving equal to replicated, the
    sharded train step equal to the unsharded one."""
    code = ("import sys; sys.path.insert(0, %r)\n"
            "import chip_smoke\n"
            "from repro.configs.dlrm import DLRM_SMOKE\n"
            "from repro.kernels import ops\n"
            "ops.set_impl('interpret')\n"
            "chip_smoke.four_chips(DLRM_SMOKE, 32, 0)\n" % str(ROOT))
    env = _cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "shard_bytes={0: " in r.stdout and "train sharded" in r.stdout


def test_compile_cache_follows_the_environment(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.use_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)

