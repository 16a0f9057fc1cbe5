"""chip_smoke.py rehearsed on the CPU at a tiny size, so the script that
proves the served path on a TPU cannot rot between chip runs; plus the
placement contract of the persistent compile cache its entry point sets."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


sys.path.insert(0, str(ROOT))
import chip_smoke as smoke  # noqa: E402

TINY = smoke.SmokeConfig(n=1024, dim=16, n_requests=48, bucket=64,
                         recall_min={"f32": 0.98, "pq": 0.9,
                                     "pq_brute": 0.95})


def _env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.update(extra)
    return env


def test_smoke_phases_on_cpu(capsys):
    """All four one-chip phases (f32/PQ x jnp/Pallas-interpret) and their
    checks pass; the ok line is the script's, not main()'s."""
    dev = smoke.main("cpu", cfg=TINY)
    assert dev == {"platform": "cpu", "kind": "cpu", "count": 1}
    out = capsys.readouterr().out
    for tag in ("local/f32/pallas", "local/pq/pallas"):
        assert f"[{tag}] graph recall@10" in out
    assert "pallas-vs-jnp id agreement" in out
    assert '"ok"' not in out


def test_smoke_refuses_unexpected_platform():
    with pytest.raises(smoke.SmokeError, match="expected platform 'tpu'"):
        smoke.main("tpu", cfg=TINY)


def test_smoke_four_chip_path_on_virtual_devices():
    """--chips 4's sharded path on four CPU devices: the DB spreads over
    all four and every check holds against LocalBackend + the reference."""
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke as s; "
            "s.main('cpu', chips=4, cfg=s.SmokeConfig(n=1024, dim=16, "
            "n_requests=48, bucket=64, recall_min=%r))"
            % (str(ROOT), TINY.recall_min))
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=900,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "sharded DB arrays sit on 4 devices" in r.stdout
    assert "[sharded/pq] pallas-vs-jnp id agreement" in r.stdout


def test_smoke_script_fails_off_chip():
    """Run as a script on the CPU it exits non-zero and prints no ok line."""
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                       capture_output=True, text=True, timeout=300,
                       env=_env(JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


CACHE_PROBE = """
import sys
from repro.compile_cache import enable_compile_cache
print(enable_compile_cache(sys.argv[1]))
import jax, jax.numpy as jnp
jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_lands_in_one_place(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise the fixed
    <checkout>/.jax_cache.  Entries land in that directory only."""
    checkout, env_dir = tmp_path / "checkout", tmp_path / "env_cache"
    checkout.mkdir()
    extra = {"JAX_PLATFORMS": "cpu",
             "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
             "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"}
    env = _env(**extra)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", CACHE_PROBE, str(checkout)],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=tmp_path)
    assert r.returncode == 0, r.stderr
    want = env_dir if from_env else checkout / ".jax_cache"
    assert Path(r.stdout.strip()).resolve() == want.resolve()
    written = {p.parent for p in tmp_path.rglob("*-cache")}
    assert written == {want}
