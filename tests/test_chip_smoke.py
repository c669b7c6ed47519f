"""``chip_smoke.py`` rehearsed on the CPU at a reduced config.

The script itself refuses to run without a TPU; these tests drive the same
phases (train, injection save, follower refresh, both fingerprint
backends, serve; and the 2x2 -> 4x1 reshard restore) at smoke sizes so a
change that breaks the chip path fails here first. Each runs in its own
process: ``repro.launch.train`` turns on the persistent compile cache,
which is pointed at a temporary directory instead of the checkout.
"""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rehearse(code: str, tmp_path, n_devices: int = 1) -> str:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{n_devices}")
    code = "import sys\nsys.path.insert(0, %r)\n" % REPO + \
        textwrap.dedent(code)
    r = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_one_chip_path_rehearsed_on_cpu(tmp_path):
    out = _rehearse(f"""
        import chip_smoke as cs
        cs.one_chip({str(tmp_path / "work")!r}, cs.Clock(), smoke=True,
                    batch=4, seq=64, prompt_len=16, new_tokens=8,
                    interpret=True)
    """, tmp_path)
    assert "layers injected, 1 re-key walk, 1 manifest commit" in out
    assert "fingerprint pallas:" in out
    assert "greedy tokens match the full reload" in out


def test_four_chip_path_rehearsed_on_cpu(tmp_path):
    out = _rehearse(f"""
        import chip_smoke as cs
        cs.four_chips({str(tmp_path / "work")!r}, cs.Clock(), smoke=True,
                      seq=64)
    """, tmp_path, n_devices=4)
    assert "step 3 on the 4x1 mesh" in out


def test_refuses_to_run_without_a_tpu(tmp_path):
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
