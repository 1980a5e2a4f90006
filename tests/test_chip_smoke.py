"""chip_smoke.py's contract, as far as a CPU can pin it: the rehearsal
passes, the default mode refuses to run without a TPU and names what it
found, and a leg that raises is a non-zero exit with no result line."""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")

# one virtual device: the parent pytest process pins eight, which would
# switch on the four-device legs
_ENV = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
_ENV["JAX_PLATFORMS"] = "cpu"


def _run(*argv, code=None, env=_ENV):
    cmd = [sys.executable, "-c", code] if code else \
        [sys.executable, SMOKE, *argv]
    return subprocess.run(cmd, capture_output=True, text=True,
                          timeout=300, env=env, cwd=ROOT)


def test_rehearsal_passes_and_says_what_it_is(tmp_path):
    # the cache placed from outside: the smoke must leave it to jax
    cache = str(tmp_path / "cache")
    p = _run("--rehearse-on-cpu",
             env={**_ENV, "JAX_COMPILATION_CACHE_DIR": cache})
    assert p.returncode == 0, p.stderr[-2000:]
    assert f"compile_cache_dir={cache}" in p.stdout
    lines = p.stdout.strip().splitlines()
    assert lines and all("REHEARSAL" in ln and "platform=cpu" in ln
                         for ln in lines), p.stdout
    assert "train_executables=1 recompiles_after_step_1=0" in p.stdout
    assert "executables=4 expected_executables=4 recompile_events=0" \
        in p.stdout
    # a rehearsal can never be read as a pass on the chip
    assert '"ok"' not in p.stdout


def test_default_mode_without_a_tpu_fails_naming_the_platform():
    p = _run()
    assert p.returncode not in (0, None)
    assert "platform=cpu" in p.stderr
    assert p.stdout.strip() == ""


def test_a_leg_that_raises_is_a_nonzero_exit():
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import chip_smoke\n"
        "def boom(*a): raise RuntimeError('leg made to raise')\n"
        "chip_smoke.trainer_leg = boom\n"
        "sys.exit(chip_smoke.main(['--rehearse-on-cpu']))\n" % ROOT)
    p = _run(code=code)
    assert p.returncode not in (0, None)
    assert "leg made to raise" in p.stderr
    assert "all legs passed" not in p.stdout


def test_a_place_never_stands_in_for_another_kind():
    import pytest
    import paddle_tpu as paddle
    assert paddle.CPUPlace(0).get_device().platform == "cpu"
    with pytest.raises(RuntimeError, match="tpu"):
        paddle.TPUPlace(0).get_device()   # conftest pins the CPU
